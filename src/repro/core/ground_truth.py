"""Exact kNN ground truth + kNN partition distributions (paper §2.1).

Batched brute force — used for (a) evaluation GT, (b) probing-model labels on a
training subset (paper appendix A.3 keeps this O(|subset|²·d), not O(N²·d)).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


@functools.partial(jax.jit, static_argnames=("k",))
def _knn_block(q: jax.Array, base: jax.Array, k: int):
    d2 = (
        jnp.sum(q * q, axis=-1, keepdims=True)
        - 2.0 * jnp.dot(q, base.T, precision=jax.lax.Precision.HIGHEST)
        + jnp.sum(base * base, axis=-1)[None, :]
    )
    neg, idx = jax.lax.top_k(-d2, k)
    return -neg, idx.astype(jnp.int32)


def exact_knn(queries: np.ndarray, base: np.ndarray, k: int, *, batch: int = 1024, exclude_self: bool = False):
    """Exact kNN of `queries` in `base`. Returns (dists [Q,k], ids [Q,k]).

    If exclude_self, asks for k+1 and drops exact self-matches (training labels
    where queries ⊆ base).
    """
    kk = k + 1 if exclude_self else k
    q = np.asarray(queries, np.float32)
    b = jnp.asarray(base, np.float32)
    out_d, out_i = [], []
    for s in range(0, len(q), batch):
        d, i = _knn_block(jnp.asarray(q[s : s + batch]), b, kk)
        out_d.append(np.asarray(d))
        out_i.append(np.asarray(i))
    dists, ids = np.concatenate(out_d), np.concatenate(out_i)
    if exclude_self:
        # keep the first k columns that are not self matches (distance ~ 0);
        # rows with fewer (degenerate duplicates) keep columns 1..k
        far = dists > 1e-9
        cols = np.argsort(~far, axis=1, kind="stable")[:, :k]
        cols = np.where((far.sum(1) >= k)[:, None], cols, np.arange(1, k + 1))
        return (np.take_along_axis(dists, cols, 1).astype(np.float32),
                np.take_along_axis(ids, cols, 1).astype(np.int32))
    return dists, ids


def knn_count_distribution(gt_ids: np.ndarray, assign: np.ndarray, n_partitions: int) -> np.ndarray:
    """n^q (paper def. 1): per-query count of GT kNN in each partition. [Q, B]."""
    part = assign[gt_ids]  # [Q, k]
    out = np.zeros((gt_ids.shape[0], n_partitions), np.int32)
    rows = np.repeat(np.arange(gt_ids.shape[0]), gt_ids.shape[1])
    np.add.at(out, (rows, part.reshape(-1)), 1)
    return out


def knn_partition_labels(gt_ids: np.ndarray, assign: np.ndarray, n_partitions: int) -> np.ndarray:
    """p^q: binary mask over partitions that contain ≥1 true kNN. [Q, B] f32."""
    return (knn_count_distribution(gt_ids, assign, n_partitions) > 0).astype(np.float32)


def optimal_nprobe(labels: np.ndarray) -> np.ndarray:
    """(nprobe^q)* = number of kNN partitions."""
    return labels.sum(-1).astype(np.int32)


def nprobe_dist(gt_ids: np.ndarray, assign: np.ndarray, q: np.ndarray, centroids: np.ndarray) -> np.ndarray:
    """nprobe*_dist (paper §2.2): max centroid-distance-rank over kNN partitions —
    how many nearest-centroid probes IVF needs to cover all kNN."""
    d2 = (
        np.sum(q * q, -1, keepdims=True)
        - 2.0 * q @ centroids.T
        + np.sum(centroids * centroids, -1)[None, :]
    )
    rank = np.argsort(np.argsort(d2, -1), -1)  # rank of each partition per query
    part = assign[gt_ids]  # [Q, k]
    out = np.empty(len(q), np.int32)
    for r in range(len(q)):
        out[r] = rank[r, part[r]].max() + 1
    return out
