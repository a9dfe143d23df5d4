"""The plain reference: exact distances and exact k-NN, under the metric a
configuration declares (``dataset.metric``: ``"l2"`` or ``"ip"``).

It imports nothing of the program and reads nothing the program made. It
sees only the corpus and the queries, both made from the seed, and the
answers the timed path returned. The semantics it holds the program to are
those of exact-distance search:

* every returned id lies in the corpus, appears once in its row, and the
  row is sorted ascending with unfilled slots (id -1, distance inf) last;
  a row with no answer at all is missing;
* every returned distance is the distance the metric defines from that
  query to that id's vector, computed in float64 here:

  - ``"l2"``: the squared L2 distance. The f32 tier computes it in its
    scan, residual PQ in its exact rerank. It is compared relative to
    ``|q|^2 + |x|^2`` (the terms the expansion ``|q|^2 - 2 q.x + |x|^2``
    cancels, so float32 rounding scales with them);
  - ``"ip"``: the negated inner product ``-<q, x>``, so that the nearest
    still comes first and the row still ascends: ``bad_answers`` and
    ``recall`` hold unchanged. It is compared relative to ``|q| |x|``: the
    dot sums the products ``q_i x_i``, whose float32 rounding scales with
    their magnitudes, and by Cauchy-Schwarz ``sum |q_i x_i| <= |q| |x|``.
    The value itself may be near zero where those terms are not, so it
    cannot be the scale;
* recall@k against the exact k nearest neighbours under the same metric,
  computed here at HIGHEST precision. It is an end-to-end metric, and
  ``correct`` holds its shortfall (``recall_miss``, 1 - recall@k) under a
  limit: the distances show whether an answer is what it says, the
  shortfall whether the nearest were chosen. With the index and the
  queries fixed, a sound program misses the same neighbours on every seed
  (those in partitions the probing model did not pick), so the limit can
  sit just above that.

The control (``knn(..., passes=3)``) is this reference put in the program's
place one precision lower: the query-vector dot in three bfloat16 passes,
as ``Precision.HIGH`` computes it on a TPU, spelled out so that it computes
the same on any backend. Both metrics take it through the same dot.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

_HIGHEST = jax.lax.Precision.HIGHEST
METRICS = ("l2", "ip")


def _bf16(a):
    # rounds to bfloat16's 8 exponent and 7 mantissa bits and stays float32.
    # Not a float32 -> bfloat16 -> float32 round trip: XLA may drop that as
    # excess precision, and on a TPU v5e the three passes so written read
    # like one (a distance gap of 1e-3 where the CPU reads 3e-6)
    return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)


def _bf16_split(a):
    hi = _bf16(a)
    return hi, _bf16(a - hi)


def _dot(q, x, passes: int):
    """q @ x.T: exact float32 products (passes=6, HIGHEST) or the three-pass
    bfloat16 product (passes=3): hi*hi + hi*lo + lo*hi, lo*lo dropped."""
    if passes == 6:
        return jnp.dot(q, x.T, precision=_HIGHEST)
    if passes != 3:
        raise ValueError(f"passes must be 6 or 3, not {passes}")
    qh, ql = _bf16_split(q)
    xh, xl = _bf16_split(x)
    return (jnp.dot(qh, xh.T, precision=_HIGHEST) + jnp.dot(qh, xl.T, precision=_HIGHEST)
            + jnp.dot(ql, xh.T, precision=_HIGHEST))


def check_metric(metric: str) -> None:
    if metric not in METRICS:
        raise ValueError(f"dataset.metric {metric!r} is not implemented (one of {METRICS})")


@functools.partial(jax.jit, static_argnames=("k", "passes"))
def _knn_block(q, base, base_sq, k: int, passes: int):
    d2 = jnp.sum(q * q, -1, keepdims=True) - 2.0 * _dot(q, base, passes) + base_sq[None, :]
    neg, idx = jax.lax.top_k(-d2, k)
    return -neg, idx.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("k", "passes"))
def _knn_block_ip(q, base, k: int, passes: int):
    ip, idx = jax.lax.top_k(_dot(q, base, passes), k)
    return -ip, idx.astype(jnp.int32)


def knn(queries: np.ndarray, base, k: int, *, metric: str, passes: int = 6,
        block: int = 256):
    """Exact k-NN under ``metric`` by brute force over ``base`` (a device
    array), in blocks of queries. Returns (dists, ids) as host arrays [n, k],
    each row ascending."""
    check_metric(metric)
    base_sq = jnp.sum(base * base, -1) if metric == "l2" else None
    out_d, out_i = [], []
    for s in range(0, len(queries), block):
        q = np.zeros((block, queries.shape[1]), np.float32)
        part = queries[s:s + block]
        q[:len(part)] = part
        if metric == "l2":
            d, i = _knn_block(jnp.asarray(q), base, base_sq, k, passes)
        else:
            d, i = _knn_block_ip(jnp.asarray(q), base, k, passes)
        out_d.append(np.asarray(d)[:len(part)])
        out_i.append(np.asarray(i)[:len(part)])
    return np.concatenate(out_d), np.concatenate(out_i)


def bad_answers(ids: np.ndarray, dists: np.ndarray, n_base: int) -> int:
    """Answer slots that break exact-search structure, plus rows with no
    answer at all (see the module docstring)."""
    valid = ids >= 0
    fin = np.isfinite(dists)
    bad = int((ids >= n_base).sum())
    bad += int((valid != fin).sum())                       # id and distance disagree
    bad += int((valid[:, 1:] & ~valid[:, :-1]).sum())      # an answer after a hole
    d = np.where(valid, dists, np.inf)
    with np.errstate(invalid="ignore"):
        bad += int((np.diff(d, axis=1) < 0).sum())         # not ascending
    uniq = np.where(valid, ids.astype(np.int64), -1 - np.arange(ids.shape[1]))
    s = np.sort(uniq, axis=1)
    bad += int((s[:, 1:] == s[:, :-1]).sum())              # an id twice in a row
    bad += int((~valid.any(1)).sum())                      # no answer
    return bad


def dist_gap(queries: np.ndarray, ids: np.ndarray, dists: np.ndarray,
             base_np: np.ndarray, *, metric: str, block: int = 512) -> float:
    """Widest gap, over every returned (query, id), between the returned
    distance and the float64 distance under ``metric``, relative to
    ``|q|^2 + |x|^2`` for ``"l2"`` and to ``|q| |x|`` for ``"ip"`` (the
    bound on the terms whose float32 rounding each sums; module docstring)."""
    check_metric(metric)
    worst = 0.0
    for s in range(0, len(queries), block):
        i = ids[s:s + block]
        ok = (i >= 0) & (i < len(base_np))
        if not ok.any():
            continue
        q = queries[s:s + block].astype(np.float64)[:, None, :]
        x = base_np[np.where(ok, i, 0)].astype(np.float64)
        if metric == "l2":
            exact = ((q - x) ** 2).sum(-1)
            scale = (q * q).sum(-1) + (x * x).sum(-1)
        else:
            exact = -(q * x).sum(-1)
            scale = np.sqrt((q * q).sum(-1) * (x * x).sum(-1))
        gap = np.abs(dists[s:s + block].astype(np.float64) - exact) / scale
        gap = np.where(ok & np.isfinite(dists[s:s + block]), gap, 0.0)
        worst = max(worst, float(gap.max()))
    return worst


def recall(ids: np.ndarray, gt_ids: np.ndarray) -> float:
    """Mean share of each row's true k nearest neighbours that it returned."""
    k = gt_ids.shape[1]
    hits = 0
    for r, g in zip(ids, gt_ids):
        hits += len(np.intersect1d(r[r >= 0], g, assume_unique=False))
    return hits / (len(gt_ids) * k)
