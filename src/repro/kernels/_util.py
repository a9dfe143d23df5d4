"""Shared kernel-side helpers. ops.py imports every kernel module, so these
live below both layers to avoid import cycles."""
from __future__ import annotations

import jax
import jax.numpy as jnp

# Sentinel for negated-distance running top-k scratch: far below any real
# -dist² so masked/uninitialized slots can never be selected.
NEG_BIG = -1e30
# Marks an entry already taken by a top-k round: below NEG_BIG, so padding is
# still preferred over it and no entry is taken twice. Finite on purpose —
# the VPU compares finite sentinels safely.
DEAD = -3e38

LANES = 128


def round_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def lane_width(k: int) -> int:
    """Width of a running top-k buffer: k rounded up to whole 128-lane
    vregs, so merges concatenate along lane-aligned boundaries."""
    return round_up(k, LANES)


def pad_dim(a: jax.Array, axis: int, mult: int, fill) -> jax.Array:
    """Pad ``axis`` up to a multiple of ``mult`` with ``fill`` (batched kernels
    pad the per-bucket axes; axis 0 stays the bucket count)."""
    pad = (-a.shape[axis]) % mult
    if pad == 0:
        return a
    shape = list(a.shape)
    shape[axis] = pad
    return jnp.concatenate([a, jnp.full(shape, fill, a.dtype)], axis=axis)


def pad_rows(a: jax.Array, mult: int, fill) -> jax.Array:
    """Pad axis 0 up to a multiple of ``mult`` with ``fill``."""
    return pad_dim(a, 0, mult, fill)


def running_init(rows: int, width: int):
    """Empty running top-k: (NEG_BIG scores, -1 ids), ``[rows, width]``."""
    return (jnp.full((rows, width), NEG_BIG, jnp.float32),
            jnp.full((rows, width), -1, jnp.int32))


def top_k_rounds(vals: jax.Array, ids: jax.Array, k: int, width: int):
    """Row-wise top-k of ``vals [R, W]`` (largest first) with their ``ids``,
    in k rounds of max extraction — plain reductions and selects, which the
    TPU lowers (``lax.top_k`` and gathers have no in-kernel lowering).

    Ties go to the lowest column, the order ``lax.top_k`` returns on the
    same row, so a kernel that merges ``[running | block]`` selects exactly
    what ``lax.top_k`` over that concatenation selects. Returns
    ``([R, width], [R, width])``; columns ≥ k hold (NEG_BIG, -1)."""
    r, w = vals.shape
    lane = jax.lax.broadcasted_iota(jnp.int32, (r, w), 1)
    col = jax.lax.broadcasted_iota(jnp.int32, (r, width), 1)

    def take(i, carry):
        cur, out_v, out_i = carry
        best = jnp.max(cur, axis=1, keepdims=True)
        pos = jnp.min(jnp.where(cur == best, lane, w), axis=1, keepdims=True)
        hit = lane == pos
        best_id = jnp.max(jnp.where(hit, ids, -1), axis=1, keepdims=True)
        at = col == i
        return (jnp.where(hit, DEAD, cur), jnp.where(at, best, out_v),
                jnp.where(at, best_id, out_i))

    _, out_v, out_i = jax.lax.fori_loop(0, k, take, (vals, *running_init(r, width)))
    return out_v, out_i


def merge_running(run_v, run_i, blk_v, blk_ids, k: int):
    """Fold one candidate block ``[R, T]`` (ids ``[1, T]``) into a running
    top-k ``[R, width]``: top-k of the concatenation, ties to the running
    entries first."""
    vals = jnp.concatenate([run_v, blk_v], axis=1)
    ids = jnp.concatenate([run_i, jnp.broadcast_to(blk_ids, blk_v.shape)], axis=1)
    return top_k_rounds(vals, ids, k, run_v.shape[1])


def live_blocks(qbuf, cand_ids, tile: int, empty_row: int):
    """Candidate blocks a qbuf scan streams per bucket, ``[B]`` int32: none
    for a bucket whose slots all hold ``empty_row`` (dispatch packs a
    bucket's queries from slot 0), else the ``tile``-slot blocks up to the
    last live slot (id ≥ 0) of ``cand_ids [B, C]``. A block past that slot
    holds only padding, which scores (NEG_BIG, -1) exactly as a running
    top-k starts, so leaving it out changes no result."""
    slot = jnp.arange(1, cand_ids.shape[1] + 1, dtype=jnp.int32)
    extent = jnp.max(jnp.where(cand_ids >= 0, slot, 0), axis=1)
    occupied = jnp.any(qbuf != empty_row, axis=1)
    return jnp.where(occupied, -(-extent // tile), 0).astype(jnp.int32)


def flush_running(run_v, run_i):
    """Running top-k of -dist² → (ascending dist², ids); slots never filled
    by a valid candidate become (inf, -1), like the jnp oracles."""
    invalid = run_v <= NEG_BIG / 2
    return jnp.where(invalid, jnp.inf, -run_v), jnp.where(invalid, -1, run_i)
