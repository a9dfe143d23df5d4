"""Measured-sweep tile autotuner for the scalar-prefetch scan kernels.

The qbuf entry points (`ops.l2_topk_qbuf`, `ops.pq_adc_topk_qbuf`) stream
candidate blocks through a double-buffered VMEM ring; the block size (`tc` /
`tn`) trades DMA granularity against compute-tile shape and is the one knob
whose best value depends on the store, not the batch. This module runs a
small measured sweep over candidate tiles on synthetic operands shaped like
the store, caches the winner per *store shape* (kernel, cap, operand dims, k
— deliberately NOT b_loc/q_cap, which vary per pow2 batch bucket), and keeps
an auditable record of every sweep for the bench JSON.

``impl=None`` sweeps the backend's own kernels (``ops.default_impl``):
Mosaic on a TPU. Pass ``impl="interpret"`` to exercise the sweep on a CPU;
its timings are interpreter seconds and say nothing about the chip.

Timing happens eagerly (outside jit) — benches and engines call
``autotune_*`` up front; the ops wrappers then do a Python-level cache lookup
at trace time, so compiled steps bake the tile in. A step compiled before a
sweep keeps its old tile until re-trace (documented, acceptable: tiles only
change when the store shape does).
"""
from __future__ import annotations

import time

import jax
import numpy as np

_CACHE: dict[tuple, int] = {}
_RECORDS: list[dict] = []

_DEFAULT_TN = 128   # pq_adc_topk_qbuf code-block tile when no sweep has run
_DEFAULT_TC = 256   # l2_topk_qbuf vector-block tile when no sweep has run


def clear() -> None:
    """Drop all cached tiles and sweep records (tests use this)."""
    _CACHE.clear()
    _RECORDS.clear()


def records() -> list[dict]:
    """Auditable sweep log: one dict per autotune call (persisted by benches)."""
    return list(_RECORDS)


def pq_adc_key(cap: int, m: int, ks: int, k: int) -> tuple:
    return ("pq_adc_topk_qbuf", int(cap), int(m), int(ks), int(k))


def l2_key(cap: int, d: int, k: int) -> tuple:
    return ("l2_topk_qbuf", int(cap), int(d), int(k))


def lookup(key: tuple, default: int | None = None) -> int:
    """Trace-time tile lookup; falls back to the kernel's static default."""
    if key in _CACHE:
        return _CACHE[key]
    if default is not None:
        return default
    return _DEFAULT_TN if key and key[0] == "pq_adc_topk_qbuf" else _DEFAULT_TC


def _time_call(fn, *args, repeats: int = 3, **kwargs) -> float:
    """Median wall time of ``fn`` (jit'd; first call compiles, excluded)."""
    out = fn(*args, **kwargs)
    jax.block_until_ready(out)
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = fn(*args, **kwargs)
        jax.block_until_ready(out)
        times.append(time.perf_counter() - t0)
    return float(np.median(times))


def _sweep(key: tuple, run_one, candidates: tuple[int, ...]) -> int:
    if key in _CACHE:
        _RECORDS.append({"key": list(key), "cached": True,
                         "tile": _CACHE[key], "timings_s": None})
        return _CACHE[key]
    timings = {int(t): _time_call(run_one, t) for t in candidates}
    best = min(timings, key=timings.get)
    _CACHE[key] = best
    _RECORDS.append({"key": list(key), "cached": False, "tile": best,
                     "timings_s": {str(t): v for t, v in timings.items()}})
    return best


def autotune_pq_adc_qbuf(cap: int, m: int, ks: int, k: int, *,
                         impl: str | None = None,
                         candidates: tuple[int, ...] = (64, 128, 256),
                         b_loc: int = 4, q_cap: int = 8,
                         q_row: int = 16, seed: int = 0) -> int:
    """Sweep ``tn`` for the ADC qbuf kernel on synthetic operands shaped like
    the store (cap/m/ks/k); returns the winning tile and caches it."""
    from repro.kernels import ops  # local import: ops imports this module

    key = pq_adc_key(cap, m, ks, k)
    if key in _CACHE:
        return _sweep(key, None, candidates)
    rng = np.random.default_rng(seed)
    lut_pad = jax.numpy.asarray(
        rng.standard_normal((q_row + 1, m, ks)).astype(np.float32))
    qbuf = jax.numpy.asarray(
        rng.integers(0, q_row + 1, (b_loc, q_cap)).astype(np.int32))
    codes = jax.numpy.asarray(
        rng.integers(0, ks, (b_loc, cap, m)).astype(np.int32))
    cand_ids = jax.numpy.asarray(
        rng.integers(0, 10 * cap, (b_loc, cap)).astype(np.int32))

    def run_one(tn):
        return ops.pq_adc_topk_qbuf(lut_pad, qbuf, codes, cand_ids, k,
                                    impl=impl, tn=int(tn))

    return _sweep(key, run_one, tuple(int(t) for t in candidates))


def autotune_l2_qbuf(cap: int, d: int, k: int, *,
                     impl: str | None = None,
                     candidates: tuple[int, ...] = (128, 256, 512),
                     b_loc: int = 4, q_cap: int = 8,
                     q_row: int = 16, seed: int = 0) -> int:
    """Sweep ``tc`` for the f32 qbuf kernel on synthetic operands shaped like
    the store (cap/d/k); returns the winning tile and caches it."""
    from repro.kernels import ops

    key = l2_key(cap, d, k)
    if key in _CACHE:
        return _sweep(key, None, candidates)
    rng = np.random.default_rng(seed)
    q_pad = jax.numpy.asarray(
        rng.standard_normal((q_row + 1, d)).astype(np.float32))
    qbuf = jax.numpy.asarray(
        rng.integers(0, q_row + 1, (b_loc, q_cap)).astype(np.int32))
    cands = jax.numpy.asarray(
        rng.standard_normal((b_loc, cap, d)).astype(np.float32))
    cand_ids = jax.numpy.asarray(
        rng.integers(0, 10 * cap, (b_loc, cap)).astype(np.int32))

    def run_one(tc):
        return ops.l2_topk_qbuf(q_pad, qbuf, cands, cand_ids, k,
                                impl=impl, tc=int(tc))

    return _sweep(key, run_one, tuple(int(t) for t in candidates))
