"""Fused L2-distance + running-top-k scan — THE partitioned-ANN hot path.

Given a query tile and a stream of candidate blocks (gathered partition rows),
computes squared-L2 distances on the MXU (||q||² - 2 q·cᵀ + ||c||²) and folds
each block into a running top-k held in VMEM scratch — candidates never round-
trip to HBM as a full [Q, C] distance matrix. This is the TPU-native
replacement for Faiss's scan_codes + heap (DESIGN.md §3).

Tiling:
  grid = (Q_tiles, C_blocks); C is the inner ("arbitrary") dimension so the
  running top-k scratch for a query tile stays resident across the scan.
  Block shapes: q [TQ, d], c [TC, d], distance tile [TQ, TC] — TQ, TC multiples
  of 128 keep the MXU fully fed; d should be padded to a lane multiple by the
  caller (ops.py does this).

VMEM working set per step ≈ TQ·d + TC·d + TQ·TC + 2·TQ·(k+TC) f32
(e.g. TQ=TC=256, d=128, k=128 → ~1.1 MB, well under the ~16 MB/core budget).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels._util import (NEG_BIG, flush_running, lane_width,
                                 live_blocks, merge_running, running_init)


def neg_sq_l2(q, c, cid):
    """-‖q − c‖² tile ``[TQ, TC]`` on the MXU, padded candidates (cid < 0)
    at NEG_BIG. HIGHEST precision: an f32 dot on the TPU otherwise rounds its
    operands to bf16, and this scan claims exact f32 distances."""
    d2 = (
        2.0 * jax.lax.dot_general(q, c, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32,
                                  precision=jax.lax.Precision.HIGHEST)
        - jnp.sum(q * q, axis=-1, keepdims=True)
        - jnp.sum(c * c, axis=-1)[None, :]
    )
    return jnp.where(cid < 0, NEG_BIG, d2)


def _l2_topk_kernel(q_ref, c_ref, cid_ref, od_ref, oi_ref, run_d, run_i, *, k: int, n_cblocks: int):
    """One (q_tile, c_block) grid step."""
    cb = pl.program_id(1)

    @pl.when(cb == 0)
    def _init():
        run_d[...], run_i[...] = running_init(*run_d.shape)

    d2 = neg_sq_l2(q_ref[...].astype(jnp.float32), c_ref[...].astype(jnp.float32),
                   cid_ref[...])                                       # [TQ, TC]
    run_d[...], run_i[...] = merge_running(run_d[...], run_i[...], d2, cid_ref[...], k)

    @pl.when(cb == n_cblocks - 1)
    def _flush():
        od_ref[...], oi_ref[...] = flush_running(run_d[...], run_i[...])


@functools.partial(jax.jit, static_argnames=("k", "tq", "tc", "interpret"))
def l2_topk(
    q: jax.Array,         # [Q, d] — Q multiple of tq
    cands: jax.Array,     # [C, d] — C multiple of tc
    cand_ids: jax.Array,  # [C] int32, -1 = padding
    k: int,
    *,
    tq: int = 256,
    tc: int = 256,
    interpret: bool = False,
):
    qn, d = q.shape
    cn = cands.shape[0]
    assert qn % tq == 0 and cn % tc == 0, (qn, tq, cn, tc)
    n_cblocks = cn // tc
    kp = lane_width(k)
    kernel = functools.partial(_l2_topk_kernel, k=k, n_cblocks=n_cblocks)
    od, oi = pl.pallas_call(
        kernel,
        grid=(qn // tq, n_cblocks),
        in_specs=[
            pl.BlockSpec((tq, d), lambda i, j: (i, 0)),
            pl.BlockSpec((tc, d), lambda i, j: (j, 0)),
            pl.BlockSpec((1, tc), lambda i, j: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((tq, kp), lambda i, j: (i, 0)),
            pl.BlockSpec((tq, kp), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((qn, kp), jnp.float32),
            jax.ShapeDtypeStruct((qn, kp), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((tq, kp), jnp.float32),
            pltpu.VMEM((tq, kp), jnp.int32),
        ],
        interpret=interpret,
    )(q, cands, cand_ids.reshape(1, cn))
    return od[:, :k], oi[:, :k]


def _l2_topk_batched_kernel(q_ref, c_ref, cid_ref, od_ref, oi_ref, run_d, run_i,
                            *, k: int, n_cblocks: int):
    """One (bucket, q_tile, c_block) grid step — same running-top-k scheme as
    the flat kernel; the scratch re-initializes per (bucket, q_tile) because the
    c_block axis is innermost."""
    cb = pl.program_id(2)

    @pl.when(cb == 0)
    def _init():
        run_d[...], run_i[...] = running_init(*run_d.shape)

    cid = cid_ref[0]                                                   # [1, TC]
    d2 = neg_sq_l2(q_ref[0].astype(jnp.float32), c_ref[0].astype(jnp.float32), cid)
    run_d[...], run_i[...] = merge_running(run_d[...], run_i[...], d2, cid, k)

    @pl.when(cb == n_cblocks - 1)
    def _flush():
        od_ref[0], oi_ref[0] = flush_running(run_d[...], run_i[...])


@functools.partial(jax.jit, static_argnames=("k", "tq", "tc", "interpret"))
def l2_topk_batched(
    q: jax.Array,         # [B, Q, d] — Q multiple of tq
    cands: jax.Array,     # [B, C, d] — C multiple of tc
    cand_ids: jax.Array,  # [B, C] int32, -1 = padding
    k: int,
    *,
    tq: int = 256,
    tc: int = 256,
    interpret: bool = False,
):
    """Grid-batched l2_topk: scans all B (query-bucket, candidate-set) pairs in
    ONE pallas launch — the serve step's per-partition scan shape."""
    bn, qn, d = q.shape
    cn = cands.shape[1]
    assert qn % tq == 0 and cn % tc == 0, (qn, tq, cn, tc)
    n_cblocks = cn // tc
    kp = lane_width(k)
    kernel = functools.partial(_l2_topk_batched_kernel, k=k, n_cblocks=n_cblocks)
    od, oi = pl.pallas_call(
        kernel,
        grid=(bn, qn // tq, n_cblocks),
        in_specs=[
            pl.BlockSpec((1, tq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, tc, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, 1, tc), lambda b, i, j: (b, 0, j)),
        ],
        out_specs=[
            pl.BlockSpec((1, tq, kp), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, tq, kp), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bn, qn, kp), jnp.float32),
            jax.ShapeDtypeStruct((bn, qn, kp), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((tq, kp), jnp.float32),
            pltpu.VMEM((tq, kp), jnp.int32),
        ],
        interpret=interpret,
    )(q, cands, cand_ids.reshape(bn, 1, cn))
    return od[..., :k], oi[..., :k]


def _l2_topk_qbuf_kernel(qb_ref, nb_ref, q_hbm, vec_hbm, cid_ref, od_ref, oi_ref,
                         q_s, vbuf, sem_q, sem_vec,
                         *, k: int, tc: int, n_slots: int):
    """One bucket per grid step: scalar-prefetched query-row gather (the
    dispatch-buffer rows land in SMEM ahead of the body, so `.at[qb_ref[b,s]]`
    is a plain dynamic DMA index) followed by double-buffered streaming of
    the bucket's first ``nb_ref[b]`` candidate blocks into the running
    top-k — same merge scheme as the grid-batched kernel, same arithmetic
    order, so distances stay bit-identical. The blocks left out hold only
    padding (``_util.live_blocks``); a bucket with none to stream has no
    query and gathers nothing."""
    b = pl.program_id(0)
    n_blk = nb_ref[b]
    width = od_ref.shape[-1]

    @pl.when(n_blk == 0)
    def _no_query():
        od_ref[0], oi_ref[0] = flush_running(*running_init(n_slots, width))

    @pl.when(n_blk > 0)
    def _scan():
        # phase 1: gather this bucket's S query rows from the compact plane
        # (rows on an untiled leading axis: a one-row slice of a tiled axis
        # is not a legal DMA window on the TPU)
        def gather(s, carry):
            cp = pltpu.make_async_copy(q_hbm.at[qb_ref[b, s]], q_s.at[s], sem_q)
            cp.start()
            cp.wait()
            return carry

        jax.lax.fori_loop(0, n_slots, gather, 0)
        q = q_s[...].reshape(n_slots, -1).astype(jnp.float32)   # [S, d]

        # phase 2: stream candidate blocks through a 2-deep VMEM ring; every
        # copy started is waited: block j+1's only when j+1 < n_blk
        def copy_block(j, slot):
            return pltpu.make_async_copy(vec_hbm.at[b, pl.ds(j * tc, tc)],
                                         vbuf.at[slot], sem_vec.at[slot])

        copy_block(0, 0).start()

        def body(j, carry):
            slot = jax.lax.rem(j, 2)

            @pl.when(j + 1 < n_blk)
            def _prefetch_next():
                copy_block(j + 1, jax.lax.rem(j + 1, 2)).start()

            copy_block(j, slot).wait()
            c = vbuf[slot].astype(jnp.float32)      # [TC, d]
            cid = cid_ref[0, :, pl.ds(pl.multiple_of(j * tc, tc), tc)]   # [1, TC]
            return merge_running(*carry, neg_sq_l2(q, c, cid), cid, k)

        init = running_init(n_slots, width)
        od_ref[0], oi_ref[0] = flush_running(*jax.lax.fori_loop(0, n_blk, body, init))


@functools.partial(jax.jit, static_argnames=("k", "tc", "interpret"))
def l2_topk_qbuf(
    q_pad: jax.Array,     # [q_row+1, d] compact queries + sentinel row
    qbuf: jax.Array,      # [B, S] int32 query row per dispatch slot
    cands: jax.Array,     # [B, C, d] — C multiple of tc
    cand_ids: jax.Array,  # [B, C] int32, -1 = padding
    k: int,
    *,
    tc: int = 256,
    interpret: bool = False,
):
    """Dispatch-buffer form of ``l2_topk_batched``: takes the compact
    ``q_pad`` plane plus ``qbuf`` indices instead of a host-expanded
    ``[B, S, d]`` query stack, so the staged operand footprint is
    O(q_row·d) + O(B·S) indices rather than O(B·S·d).

    Each bucket streams only its blocks of ``tc`` slots up to its last live
    slot, and none without a query (``_util.live_blocks``, the second
    scalar-prefetch operand). Rows of a bucket with no query come back
    (inf, -1); empty slots of an occupied bucket compute against the
    sentinel query. Callers drop both downstream, exactly as with the
    expanded form."""
    bn, n_slots = qbuf.shape
    cn, d = cands.shape[1], cands.shape[2]
    assert cn % tc == 0, (cn, tc)
    n_blk = live_blocks(qbuf, cand_ids, tc, q_pad.shape[0] - 1)
    kp = lane_width(k)
    kernel = functools.partial(_l2_topk_qbuf_kernel, k=k, tc=tc, n_slots=n_slots)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(bn,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),              # q_pad stays in HBM
            pl.BlockSpec(memory_space=pl.ANY),              # cands stay in HBM
            pl.BlockSpec((1, 1, cn), lambda b, qb, nb: (b, 0, 0)),  # cand_ids
        ],
        out_specs=[
            pl.BlockSpec((1, n_slots, kp), lambda b, qb, nb: (b, 0, 0)),
            pl.BlockSpec((1, n_slots, kp), lambda b, qb, nb: (b, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((n_slots, 1, d), q_pad.dtype),
            pltpu.VMEM((2, tc, d), cands.dtype),
            pltpu.SemaphoreType.DMA(()),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    od, oi = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((bn, n_slots, kp), jnp.float32),
            jax.ShapeDtypeStruct((bn, n_slots, kp), jnp.int32),
        ],
        interpret=interpret,
    )(qbuf, n_blk, q_pad.reshape(q_pad.shape[0], 1, d), cands,
      cand_ids.reshape(bn, 1, cn))
    return od[..., :k], oi[..., :k]
