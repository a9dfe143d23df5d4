"""Padded partition storage (inverted lists with static shapes).

XLA requires static shapes, so inverted lists are materialized as a dense
``[B, capacity, d]`` tensor plus per-partition counts. Rows beyond ``count`` are
padding (id = -1, vector = +inf-ish sentinel so they never win a top-k).

The same structure backs:
  * flat (meta-index-only) search — exhaustive Pallas scan of probed partitions,
  * the two-level index — each partition additionally carries a mini-IVF
    (sub-centroids + sub-assignments) as the TPU-native internal index
    (HNSW replacement; see DESIGN.md §3).
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

PAD_ID = -1
# Padding vectors are pushed far away so they can never enter a top-k.
PAD_DIST_BUMP = 1e9


class PartitionStore(NamedTuple):
    """Dense padded inverted lists. All arrays are device arrays."""

    centroids: jax.Array   # [B, d] f32
    vectors: jax.Array     # [B, capacity, d] f32 (padded)
    ids: jax.Array         # [B, capacity] i32, PAD_ID marks padding
    counts: jax.Array      # [B] i32
    # Optional internal mini-IVF (two-level index):
    sub_centroids: Optional[jax.Array] = None  # [B, S, d]
    sub_assign: Optional[jax.Array] = None     # [B, capacity] i32 in [0, S)

    @property
    def n_partitions(self) -> int:
        return self.vectors.shape[0]

    @property
    def capacity(self) -> int:
        return self.vectors.shape[1]

    @property
    def dim(self) -> int:
        return self.vectors.shape[2]


def build_store(
    x: np.ndarray,
    ids: np.ndarray,
    assign: np.ndarray,
    centroids: np.ndarray,
    *,
    capacity: Optional[int] = None,
    extra: Optional[tuple[np.ndarray, np.ndarray, np.ndarray]] = None,
    align: int = 1,
) -> PartitionStore:
    """Build padded lists host-side (numpy; runs once at index build).

    ``extra`` = (vectors, ids, assign) replica rows appended by the redundancy
    strategy (paper §3.3); replicas share the id of the original point so the
    merge step dedups naturally. Without an explicit ``capacity`` it is the
    largest partition rounded up to a multiple of ``align``.
    """
    b = centroids.shape[0]
    xs, xid, xa = [x], [ids], [assign]
    if extra is not None:
        ev, ei, ea = extra
        if len(ev):
            xs.append(ev)
            xid.append(ei)
            xa.append(ea)
    x_all = np.concatenate(xs, 0)
    id_all = np.concatenate(xid, 0)
    a_all = np.concatenate(xa, 0)

    counts = np.bincount(a_all, minlength=b)
    cap = int(capacity if capacity is not None
              else -(-max(1, counts.max()) // align) * align)
    d = x.shape[1]
    vec = np.full((b, cap, d), 1e6, np.float32)  # far-away padding
    pid = np.full((b, cap), PAD_ID, np.int32)
    fill = np.zeros(b, np.int64)
    order = np.argsort(a_all, kind="stable")
    for j in order:
        p = a_all[j]
        if fill[p] < cap:
            vec[p, fill[p]] = x_all[j]
            pid[p, fill[p]] = id_all[j]
            fill[p] += 1
    return PartitionStore(
        centroids=jnp.asarray(centroids, jnp.float32),
        vectors=jnp.asarray(vec),
        ids=jnp.asarray(pid),
        counts=jnp.asarray(fill.astype(np.int32)),
    )


def attach_internal_index(store: PartitionStore, rng: jax.Array, n_sub: int, n_iters: int = 8) -> PartitionStore:
    """Two-level index: fit a mini-IVF of ``n_sub`` sub-clusters inside every
    partition (vmapped k-means over partitions). TPU-native HNSW replacement."""
    from repro.core.kmeans import kmeans_fit

    def fit_one(rng_i, vecs):
        st = kmeans_fit(rng_i, vecs, n_clusters=n_sub, n_iters=n_iters)
        return st.centroids, st.assign

    rngs = jax.random.split(rng, store.n_partitions)
    sub_c, sub_a = jax.vmap(fit_one)(rngs, store.vectors)
    return store._replace(sub_centroids=sub_c, sub_assign=sub_a.astype(jnp.int32))


def store_stats(store: PartitionStore) -> dict:
    counts = np.asarray(store.counts)
    return {
        "B": store.n_partitions,
        "capacity": store.capacity,
        "total": int(counts.sum()),
        "max_fill": int(counts.max()),
        "min_fill": int(counts.min()),
        "imbalance": float(counts.max() / max(1.0, counts.mean())),
    }
