"""The qbuf scan kernels as they were before the per-bucket trip count: one
grid step per bucket that gathers every slot's query row and streams every
candidate block of the whole capacity. Kept as the oracle the live-block
kernels must match bit for bit on every occupied slot."""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels._util import flush_running, lane_width, merge_running, running_init
from repro.kernels.l2_topk import neg_sq_l2
from repro.kernels.pq_adc import _neg_adc


def _l2_dense_kernel(qb_ref, q_hbm, vec_hbm, cid_ref, od_ref, oi_ref,
                     q_s, vbuf, sem_q, sem_vec, *, k, tc, n_cblocks, n_slots):
    b = pl.program_id(0)

    def gather(s, carry):
        cp = pltpu.make_async_copy(q_hbm.at[qb_ref[b, s]], q_s.at[s], sem_q)
        cp.start()
        cp.wait()
        return carry

    jax.lax.fori_loop(0, n_slots, gather, 0)
    q = q_s[...].reshape(n_slots, -1).astype(jnp.float32)

    def copy_block(j, slot):
        return pltpu.make_async_copy(vec_hbm.at[b, pl.ds(j * tc, tc)],
                                     vbuf.at[slot], sem_vec.at[slot])

    copy_block(0, 0).start()

    def body(j, carry):
        slot = jax.lax.rem(j, 2)

        @pl.when(j + 1 < n_cblocks)
        def _prefetch_next():
            copy_block(j + 1, jax.lax.rem(j + 1, 2)).start()

        copy_block(j, slot).wait()
        c = vbuf[slot].astype(jnp.float32)
        cid = cid_ref[0, :, pl.ds(pl.multiple_of(j * tc, tc), tc)]
        return merge_running(*carry, neg_sq_l2(q, c, cid), cid, k)

    init = running_init(n_slots, od_ref.shape[-1])
    od_ref[0], oi_ref[0] = flush_running(*jax.lax.fori_loop(0, n_cblocks, body, init))


@functools.partial(jax.jit, static_argnames=("k", "tc"))
def l2_topk_qbuf_dense(q_pad, qbuf, cands, cand_ids, k, *, tc):
    bn, n_slots = qbuf.shape
    cn, d = cands.shape[1], cands.shape[2]
    n_cblocks = cn // tc
    kp = lane_width(k)
    kernel = functools.partial(_l2_dense_kernel, k=k, tc=tc,
                               n_cblocks=n_cblocks, n_slots=n_slots)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(bn,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((1, 1, cn), lambda b, qb: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, n_slots, kp), lambda b, qb: (b, 0, 0)),
            pl.BlockSpec((1, n_slots, kp), lambda b, qb: (b, 0, 0)),
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
        interpret=True,
    )(qbuf, q_pad.reshape(q_pad.shape[0], 1, d), cands, cand_ids.reshape(bn, 1, cn))
    return od[..., :k], oi[..., :k]


def _adc_dense_kernel(qb_ref, lut_hbm, codes_hbm, cid_ref, coff_ref, qoff_ref,
                      od_ref, oi_ref, lut_s, cbuf, sem_lut, sem_codes,
                      *, k, ks, tn, n_nblocks, n_slots):
    b = pl.program_id(0)

    def gather(s, carry):
        cp = pltpu.make_async_copy(lut_hbm.at[qb_ref[b, s]], lut_s.at[s], sem_lut)
        cp.start()
        cp.wait()
        return carry

    jax.lax.fori_loop(0, n_slots, gather, 0)
    lut = lut_s[...].reshape(n_slots, -1)
    qoff = qoff_ref[0]

    def copy_block(j, slot):
        return pltpu.make_async_copy(codes_hbm.at[b, :, pl.ds(j * tn, tn)],
                                     cbuf.at[slot], sem_codes.at[slot])

    copy_block(0, 0).start()

    def body(j, carry):
        slot = jax.lax.rem(j, 2)

        @pl.when(j + 1 < n_nblocks)
        def _prefetch_next():
            copy_block(j + 1, jax.lax.rem(j + 1, 2)).start()

        copy_block(j, slot).wait()
        blk = pl.ds(pl.multiple_of(j * tn, tn), tn)
        cid = cid_ref[0, :, blk]
        negd = _neg_adc(lut, cbuf[slot], cid, coff_ref[0, :, blk], qoff, ks)
        return merge_running(*carry, negd, cid, k)

    init = running_init(n_slots, od_ref.shape[-1])
    od_ref[0], oi_ref[0] = flush_running(*jax.lax.fori_loop(0, n_nblocks, body, init))


@functools.partial(jax.jit, static_argnames=("k", "tn"))
def pq_adc_topk_qbuf_dense(lut_pad, qbuf, codes, cand_ids, k, *, cand_off, q_off, tn):
    bn, n_slots = qbuf.shape
    q_rows, m, ks = lut_pad.shape
    n = codes.shape[1]
    n_nblocks = n // tn
    kp = lane_width(k)
    kernel = functools.partial(_adc_dense_kernel, k=k, ks=ks, tn=tn,
                               n_nblocks=n_nblocks, n_slots=n_slots)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(bn,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((1, 1, n), lambda b, qb: (b, 0, 0)),
            pl.BlockSpec((1, 1, n), lambda b, qb: (b, 0, 0)),
            pl.BlockSpec((1, n_slots, 1), lambda b, qb: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, n_slots, kp), lambda b, qb: (b, 0, 0)),
            pl.BlockSpec((1, n_slots, kp), lambda b, qb: (b, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((n_slots, 1, m * ks), jnp.float32),
            pltpu.VMEM((2, m, tn), jnp.int32),
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
        interpret=True,
    )(qbuf, lut_pad.reshape(q_rows, 1, m * ks), codes.transpose(0, 2, 1),
      cand_ids.reshape(bn, 1, n), cand_off.reshape(bn, 1, n),
      q_off.reshape(bn, n_slots, 1))
    return od[..., :k], oi[..., :k]
