"""Replica-aware dedup'd merge: exact global top-k over candidate pools.

LIRA's learned redundancy (paper §3.3) stores replicas of boundary points in
several partitions under the SAME id, so every merge of per-partition top-k
pools must collapse duplicate ids down to their best distance before taking
the global top-k. The host evaluation engine used to do this with per-query
Python set-loops; this kernel is the vectorized primitive that replaces them
(and plugs the serving engine's missing dedup).

Algorithm (k rounds of extraction, no sort and no hash table — plain
reductions and selects, which the TPU lowers):
  1. remap invalid entries (id < 0 padding, non-finite distance = masked-out
     partition) to an id sentinel at distance BIG;
  2. each round takes the smallest remaining distance, breaking exact ties by
     the smallest id, and retires every entry carrying that id — so an id is
     emitted once, with its best distance, in (dist, id) order: exactly the
     sort-by-(id, dist), keep-first, top-k-by-dist result of the jnp reference
     (ref.dedup_topk_ref).

Pools wider than one chunk (the serve step's b_loc·k pool is ~10⁵ wide) are
merged in passes: dedup top-k per chunk, then the same kernel over the chunk
winners. That is exact — an id in the global top-k is beaten by fewer than k
distinct ids anywhere, so also within the chunk that holds its best copy.
Grid: (Q_tiles, chunks); VMEM per step ≈ 2·2·TQ·chunk·4 B (TQ=8, chunk=2048 →
256 KiB).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.kernels._util import DEAD, lane_width, pad_dim, round_up

PAD_ID = -1            # matches repro.core.partitions.PAD_ID
BIG = 1e30             # finite distance sentinel (inf arithmetic is unsafe on VPU)
ID_SENTINEL = 2**30    # id sentinel: sorts after every real id
CHUNK = 2048           # pool lanes per grid step: an [8, 2048] f32 block is 16 vregs


def dedup_topk_np(dists: np.ndarray, ids: np.ndarray, k: int):
    """Numpy twin of ref.dedup_topk_ref for host-side callers (the evaluation
    engine), where numpy sorts are ~20× faster than XLA:CPU's.

    One sort instead of two: pack (id, dist) into a single uint64 key — the
    high 32 bits are the id, the low 32 the IEEE-754 total-order image of the
    float32 distance (sign bit set for non-negative floats, bitwise-NOT for
    negative ones — a monotone uint32 map incl. ±0/inf/nan). Sorting the key
    groups ids with the best distance first, like the jnp reference.
    """
    q, p = dists.shape
    d = np.ascontiguousarray(dists, dtype=np.float32)
    ids = np.asarray(ids, np.int32)
    valid = (ids >= 0) & np.isfinite(d)
    d_s = np.where(valid, d, np.inf)
    ids_s = np.where(valid, ids, ID_SENTINEL)
    u = np.ascontiguousarray(d_s).view(np.uint32)
    du = np.where(u & 0x80000000, ~u, u | 0x80000000).astype(np.uint64)
    key = (ids_s.astype(np.uint64) << np.uint64(32)) | du
    order = np.argsort(key, axis=1)
    k2 = np.take_along_axis(key, order, 1)
    i2 = np.take_along_axis(ids_s, order, 1)
    d2 = np.take_along_axis(d_s, order, 1)
    first = np.concatenate([np.ones((q, 1), bool), i2[:, 1:] != i2[:, :-1]], axis=1)
    keep = first & (i2 != ID_SENTINEL)
    d3 = np.where(keep, d2, np.inf)
    # final selection orders by (dist, id) — swap the key halves so distance
    # leads and ids break exact-distance ties deterministically (matches the
    # jnp ref / Pallas kernel)
    fkey = np.where(keep, (k2 << np.uint64(32)) | (k2 >> np.uint64(32)),
                    np.uint64(0xFFFFFFFFFFFFFFFF))
    kk = min(k, p)
    if kk < p:
        part = np.argpartition(fkey, kk - 1, axis=1)[:, :kk]
        fkey = np.take_along_axis(fkey, part, 1)
        d3 = np.take_along_axis(d3, part, 1)
        i2 = np.take_along_axis(i2, part, 1)
    o3 = np.argsort(fkey, axis=1)
    out_d = np.full((q, k), np.inf, np.float32)
    out_i = np.full((q, k), PAD_ID, np.int32)
    out_d[:, :kk] = np.take_along_axis(d3, o3, 1)
    oi = np.take_along_axis(i2, o3, 1)
    out_i[:, :kk] = np.where(np.isfinite(out_d[:, :kk]), oi, PAD_ID)
    return out_d, out_i


def _dedup_topk_kernel(d_ref, i_ref, od_ref, oi_ref, *, k: int):
    d = d_ref[...].astype(jnp.float32)
    ids = i_ref[...]
    invalid = (ids < 0) | ~(d < BIG)          # padding, masked-out (inf), or nan
    ids = jnp.where(invalid, ID_SENTINEL, ids)
    key = jnp.where(invalid, -BIG, -d)        # max-extraction on -dist
    rows, width = od_ref.shape
    col = jax.lax.broadcasted_iota(jnp.int32, (rows, width), 1)
    no_id = jnp.iinfo(jnp.int32).max

    def take(i, carry):
        key, out_d, out_i = carry
        best = jnp.max(key, axis=1, keepdims=True)
        best_id = jnp.min(jnp.where(key == best, ids, no_id), axis=1, keepdims=True)
        at = col == i
        return (jnp.where(ids == best_id, DEAD, key),
                jnp.where(at, -best, out_d), jnp.where(at, best_id, out_i))

    init = (key, jnp.full((rows, width), BIG, jnp.float32),
            jnp.full((rows, width), PAD_ID, jnp.int32))
    _, out_d, out_i = jax.lax.fori_loop(0, k, take, init)
    good = out_d < BIG
    od_ref[...] = jnp.where(good, out_d, jnp.inf)
    oi_ref[...] = jnp.where(good, out_i, PAD_ID)


def _dedup_pass(dists, ids, k: int, tq: int, width: int, interpret: bool):
    """Dedup top-k of every ``width``-wide chunk: [Q, n·width] → [Q, n·kp]."""
    qn = dists.shape[0]
    n_chunks = dists.shape[1] // width
    kp = lane_width(k)
    return pl.pallas_call(
        functools.partial(_dedup_topk_kernel, k=k),
        grid=(qn // tq, n_chunks),
        in_specs=[
            pl.BlockSpec((tq, width), lambda i, j: (i, j)),
            pl.BlockSpec((tq, width), lambda i, j: (i, j)),
        ],
        out_specs=[
            pl.BlockSpec((tq, kp), lambda i, j: (i, j)),
            pl.BlockSpec((tq, kp), lambda i, j: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((qn, n_chunks * kp), jnp.float32),
            jax.ShapeDtypeStruct((qn, n_chunks * kp), jnp.int32),
        ],
        interpret=interpret,
    )(dists, ids)


@functools.partial(jax.jit, static_argnames=("k", "tq", "interpret"))
def dedup_topk(
    dists: jax.Array,   # [Q, P] f32 — Q multiple of tq
    ids: jax.Array,     # [Q, P] i32, <0 = padding
    k: int,
    *,
    tq: int = 8,
    interpret: bool = False,
):
    qn, p = dists.shape
    assert qn % tq == 0 and k > 0, (qn, tq, k)
    # each pass must shrink the pool: a chunk holds at least two outputs' width
    chunk = max(CHUNK, 2 * lane_width(k))
    while True:
        width = min(chunk, round_up(p, 128))
        dists = pad_dim(dists, 1, width, jnp.inf)
        ids = pad_dim(ids, 1, width, PAD_ID)
        last = dists.shape[1] == width
        dists, ids = _dedup_pass(dists, ids, k, tq, width, interpret)
        if last:
            return dists[:, :k], ids[:, :k]
        p = dists.shape[1]
