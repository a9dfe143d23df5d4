"""The corpus and query pool of a configuration, generated on the device.

The recipe is the repository's SIFT-like mixture (``make_vector_dataset``:
power-law-weighted anisotropic Gaussian modes, points on segments between
near modes, a uniform floor), copied here so the yardstick does not move with
the program, and drawn with ``jax.random`` in one jitted call: a million
128-d rows take well under a second on the chip where the NumPy recipe takes
ten on the host.

The corpus is fixed by the configuration's ``data_seed``: every run of a
configuration serves the same index, as every user of SIFT1M searches the
same million vectors with the same 10,000 test queries. A run's ``--seed``
chooses the traffic over that pool (see ``traffic.py``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def _sizes(ds: dict) -> tuple[int, int, int]:
    total = int(ds["n_base"]) + int(ds["n_queries"])
    n_bound = int(total * float(ds["boundary_frac"]))
    n_noise = int(total * float(ds["noise_frac"]))
    return total, n_bound, n_noise


@functools.partial(jax.jit,
                   static_argnames=("total", "n_bound", "n_noise", "dim", "n_modes",
                                    "center_scale", "spread"))
def _mixture(key, *, total, n_bound, n_noise, dim, n_modes, center_scale, spread):
    k = jax.random.split(key, 12)
    centers = jax.random.normal(k[0], (n_modes, dim), jnp.float32) * center_scale
    scales = (0.3 + jax.random.gamma(k[1], 2.0, (n_modes, dim)) * 0.25) * spread
    # NumPy's pareto(a) is the Lomax law: classical Pareto minus one
    weights = jax.random.pareto(k[2], 1.5, (n_modes,)) - 1.0 + 0.05
    logits = jnp.log(weights / weights.sum())
    n_core = total - n_bound - n_noise

    modes = jax.random.categorical(k[3], logits, shape=(n_core,))
    core = centers[modes] + jax.random.normal(k[4], (n_core, dim)) * scales[modes]

    a = jax.random.categorical(k[5], logits, shape=(n_bound,))
    c2 = ((centers[:, None] - centers[None]) ** 2).sum(-1)
    c2 = jnp.where(jnp.eye(n_modes, dtype=bool), jnp.inf, c2)
    near5 = jnp.argsort(c2, axis=1)[:, :5]
    b = near5[a, jax.random.randint(k[6], (n_bound,), 0, 5)]
    t = jax.random.beta(k[7], 2.0, 2.0, (n_bound,))[:, None]
    bound = centers[a] * (1 - t) + centers[b] * t
    bound = bound + (jax.random.normal(k[8], (n_bound, dim))
                     * 0.5 * (scales[a] + scales[b]) / 2)

    lo, hi = centers.min(), centers.max()
    noise = jax.random.uniform(k[9], (n_noise, dim), jnp.float32, lo, hi)
    x = jnp.concatenate([core, bound, noise]).astype(jnp.float32)
    return x[jax.random.permutation(k[10], total)]


def make_corpus(ds: dict):
    """(base [n_base, dim], queries [n_queries, dim]) as float32 device arrays,
    from the configuration's ``dataset`` section."""
    total, n_bound, n_noise = _sizes(ds)
    x = _mixture(jax.random.PRNGKey(int(ds["data_seed"])), total=total, n_bound=n_bound,
                 n_noise=n_noise, dim=int(ds["dim"]), n_modes=int(ds["n_modes"]),
                 center_scale=float(ds["center_scale"]), spread=float(ds["spread"]))
    n = int(ds["n_base"])
    return x[:n], x[n:]


def fingerprint(x: np.ndarray) -> str:
    """A short digest of every 997th row, to tell a cached index from a
    corpus it was not built from."""
    import hashlib

    return hashlib.sha256(np.ascontiguousarray(x[::997]).tobytes()).hexdigest()[:16]
