"""Production mesh (dry-run spec): 16×16 = 256 chips/pod; 2 pods = 512 chips.

Defined as functions so importing this module never touches jax device state.
"""
from __future__ import annotations

import jax


def make_mesh(shape, axis_names):
    """``jax.make_mesh`` with Auto axes: shardings propagate through the
    program, as the serve and train steps are written for."""
    return jax.make_mesh(shape, axis_names,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_test_mesh(data: int = 1, model: int = 1, pod: int = 0):
    """Small mesh for CPU tests (same axis names as production)."""
    if pod:
        return make_mesh((pod, data, model), ("pod", "data", "model"))
    return make_mesh((data, model), ("data", "model"))


# TPU v5e roofline constants (per chip) — EXPERIMENTS.md §Roofline
PEAK_FLOPS_BF16 = 197e12      # FLOP/s
HBM_BW = 819e9                # B/s
ICI_BW = 50e9                 # B/s per link (conservative single-link figure)
HBM_PER_CHIP = 16 * 2**30     # 16 GiB
