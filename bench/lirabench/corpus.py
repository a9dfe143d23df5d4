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

Without a ``dataset.queries`` section the query pool is the tail of the
base's own draw: queries from the distribution of the corpus, as SIFT1M's
are. With ``{"dist": "shifted", "offset": L, "n_learn": n}`` the queries come
from another distribution, as text queries against image embeddings do in
big-ann-benchmarks' Yandex Text-to-Image: the same topics as the base (its
mode centres and per-mode spreads), with

* mode popularity drawn again from an independent key, so the topics
  queried most are not those the base holds most of;
* the whole query cloud moved by one fixed vector of length ``offset`` in a
  direction drawn from the seed, the gap between two modalities' embeddings
  in a joint space;
* no points between modes and no uniform floor.

From that one distribution come the test pool (``n_queries`` rows) and a
separate learn pool (``n_learn`` rows, none if absent), disjoint draws, the
learn pool for training the probing model on queries such as users send. The
base is the same draw as without the section. The recipe is the benchmark's
stand-in for data nothing here can download; a configuration that uses it
lists it under ``assumed``. It is not calibrated: no published statistic of
Text-to-Image backs the shape of the shift or any ``offset``. A configuration
that adopts it first sets ``offset`` from a figure big-ann-benchmarks or
arXiv:2205.03763 publishes for the dataset (such as a query's distance to its
nearest base vector against a base vector's to its nearest other one), and
cites that figure under ``assumed``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

QUERY_DISTS = ("shifted",)
_QUERY_KEYS = ("dist", "offset", "n_learn")


def check(ds: dict) -> None:
    """Refuse a ``dataset`` section that declares what this module does not
    make: another dtype than float32, an unknown query distribution or key."""
    if ds.get("dtype") != "float32":
        raise ValueError(f"dataset.dtype {ds.get('dtype')!r} is not implemented (float32)")
    qs = ds.get("queries")
    if qs is None:
        return
    if qs.get("dist") not in QUERY_DISTS:
        raise ValueError(f"dataset.queries.dist {qs.get('dist')!r} is not implemented "
                         f"(one of {QUERY_DISTS})")
    for key in qs:
        if key not in _QUERY_KEYS:
            raise ValueError(f"dataset.queries.{key} is not implemented (keys {_QUERY_KEYS})")
    if not float(qs.get("offset", -1.0)) >= 0.0:
        raise ValueError(f"dataset.queries.offset {qs.get('offset')!r} must be a length >= 0")
    if int(qs.get("n_learn", 0)) < 0:
        raise ValueError(f"dataset.queries.n_learn {qs['n_learn']!r} must be >= 0")


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


@functools.partial(jax.jit,
                   static_argnames=("n", "dim", "n_modes", "center_scale", "spread", "offset"))
def _shifted(key, *, n, dim, n_modes, center_scale, spread, offset):
    # the base's mode centres and spreads: _mixture's first two draws
    k = jax.random.split(key, 12)
    centers = jax.random.normal(k[0], (n_modes, dim), jnp.float32) * center_scale
    scales = (0.3 + jax.random.gamma(k[1], 2.0, (n_modes, dim)) * 0.25) * spread
    kq = jax.random.split(jax.random.fold_in(key, 1), 4)
    weights = jax.random.pareto(kq[0], 1.5, (n_modes,)) - 1.0 + 0.05
    modes = jax.random.categorical(kq[1], jnp.log(weights / weights.sum()), shape=(n,))
    direction = jax.random.normal(kq[2], (dim,), jnp.float32)
    shift = direction / jnp.linalg.norm(direction) * offset
    y = centers[modes] + jax.random.normal(kq[3], (n, dim)) * scales[modes] + shift
    return y.astype(jnp.float32)


def make_corpus(ds: dict):
    """(base [n_base, dim], queries [n_queries, dim], learn [n_learn, dim] or
    None) as float32 device arrays, from the configuration's ``dataset``
    section."""
    check(ds)
    total, n_bound, n_noise = _sizes(ds)
    key = jax.random.PRNGKey(int(ds["data_seed"]))
    shape = dict(dim=int(ds["dim"]), n_modes=int(ds["n_modes"]),
                 center_scale=float(ds["center_scale"]), spread=float(ds["spread"]))
    x = _mixture(key, total=total, n_bound=n_bound, n_noise=n_noise, **shape)
    n = int(ds["n_base"])
    qs = ds.get("queries")
    if qs is None:
        return x[:n], x[n:], None
    n_learn = int(qs.get("n_learn", 0))
    y = _shifted(key, n=n_learn + int(ds["n_queries"]), offset=float(qs["offset"]), **shape)
    return x[:n], y[n_learn:], (y[:n_learn] if n_learn else None)


def fingerprint(x: np.ndarray) -> str:
    """A short digest of every 997th row, to tell a cached index from a
    corpus it was not built from."""
    import hashlib

    return hashlib.sha256(np.ascontiguousarray(x[::997]).tobytes()).hexdigest()[:16]
