"""PQ asymmetric-distance (ADC) Pallas kernels.

dist[q, n] = Σ_m LUT[q, m, codes[n, m]] — a gather-accumulate over the per-
query lookup table. On TPU the gather over the ks lane axis is realized as a
one-hot contraction on the MXU (ks ≤ 256 keeps the one-hot tile cheap and
turns random access into a dense dot — the standard TPU adaptation of the
Faiss LUT scan; see DESIGN.md §3).

Three entry points:
  * ``pq_adc``       — full [Q, N] ADC distance matrix;
  * ``pq_adc_topk``  — fused LUT-scan + running top-k shortlist (the quantized
    serving tier's stage 1): the [Q, N] distance tile never round-trips to
    HBM, only the [Q, k] shortlist survives — same scratch scheme as l2_topk;
  * ``pq_adc_topk_qbuf`` — the batched serve-step form that takes the COMPACT
    ``lut_pad [q_row+1, m, ks]`` plane plus the ``qbuf [b_loc, q_cap]``
    dispatch buffer instead of a pre-expanded ``[b_loc, q_cap, m, ks]`` LUT
    stack. ``qbuf`` rides as a scalar-prefetch operand
    (``pltpu.PrefetchScalarGridSpec``), so each bucket's grid step DMAs only
    its own slots' LUT rows from HBM into VMEM — the host never materializes
    the ≈nprobe·q_cap_factor× amplified operand the old path staged — and the
    codes stream through a double-buffered in-kernel pipeline. A second
    scalar-prefetch operand gives each bucket its number of code blocks:
    up to its last live slot, none for a bucket with no query.

Tiling: grid = (Q_tiles, N_blocks); LUT tile [TQ, m·ks] stays in VMEM across
the candidate scan, codes stream in as [m, TN] int blocks.
VMEM per step ≈ TQ·m·ks + TN·m·ks (one-hot) + TQ·TN f32
(TQ=128, TN=128, m=16, ks=256 → ~4.5 MB).

Layouts the TPU lowers: LUTs enter as ``[Q, m·ks]`` rows and codes as
``[m, N]`` (one subspace per sublane, slots along lanes), so the one-hot tile
of subspace j is a sublane compare ``[ks, TN]`` and the m tiles stack into one
``[m·ks, TN]`` MXU operand — no 3-D reshape inside the kernel. Per-candidate
operands ride as ``[1, N]`` rows and per-query offsets as ``[Q, 1]`` columns.

The wrappers pad Q/N to tile multiples internally (and strip the padding from
outputs). ``interpret=False`` compiles with Mosaic, which needs a TPU;
``interpret=True`` is the explicit CPU choice.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels._util import (NEG_BIG, flush_running, lane_width, live_blocks,
                                 merge_running, pad_dim, pad_rows as _pad_rows,
                                 running_init)


def adc_scores(lut, codes_t, ks: int):
    """ADC distances ``[S, T]`` of a LUT tile ``lut [S, m·ks]`` against a code
    block ``codes_t [m, T]``: Σ_j lut[s, j·ks + codes_t[j, t]], as one MXU
    contraction with the stacked one-hot ``[m·ks, T]``. HIGHEST precision
    keeps the f32 LUT entries whole (the one-hot is exact in any dtype)."""
    m, t = codes_t.shape
    rows = jax.lax.broadcasted_iota(jnp.int32, (ks, t), 0)
    onehot = jnp.concatenate(
        [(codes_t[j:j + 1, :] == rows).astype(jnp.float32) for j in range(m)],
        axis=0)                                                    # [m·ks, T]
    return jax.lax.dot_general(lut, onehot, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32,
                               precision=jax.lax.Precision.HIGHEST)


def _neg_adc(lut, codes_t, cid, coff, qoff, ks):
    """-(ADC + q_off + cand_off), padded candidates (cid < 0) at NEG_BIG."""
    d = adc_scores(lut, codes_t, ks) + qoff + coff
    return jnp.where(cid < 0, NEG_BIG, -d)


def _pq_adc_kernel(lut_ref, codes_ref, out_ref, *, ks: int):
    out_ref[...] = adc_scores(lut_ref[...], codes_ref[...], ks)


@functools.partial(jax.jit, static_argnames=("tq", "tn", "interpret"))
def pq_adc(
    lut: jax.Array,    # [Q, m, ks] f32 per-query subspace distance tables
    codes: jax.Array,  # [N, m] integer PQ codes (uint8/uint16/int32)
    *,
    tq: int = 128,
    tn: int = 128,
    interpret: bool = False,
) -> jax.Array:
    qn, m, ks = lut.shape
    n = codes.shape[0]
    tq = min(tq, max(8, qn))
    tn = min(tn, max(8, n))
    lp = _pad_rows(lut.reshape(qn, m * ks), tq, 0.0)
    cp = pad_dim(codes.astype(jnp.int32).T, 1, tn, 0)
    kernel = functools.partial(_pq_adc_kernel, ks=ks)
    out = pl.pallas_call(
        kernel,
        grid=(lp.shape[0] // tq, cp.shape[1] // tn),
        in_specs=[
            pl.BlockSpec((tq, m * ks), lambda i, j: (i, 0)),
            pl.BlockSpec((m, tn), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((tq, tn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((lp.shape[0], cp.shape[1]), jnp.float32),
        interpret=interpret,
    )(lp, cp)
    return out[:qn, :n]


def _pq_adc_topk_kernel(lut_ref, codes_ref, cid_ref, coff_ref, qoff_ref,
                        od_ref, oi_ref, run_d, run_i,
                        *, k: int, ks: int, n_nblocks: int):
    nb = pl.program_id(1)

    @pl.when(nb == 0)
    def _init():
        run_d[...], run_i[...] = running_init(*run_d.shape)

    cid = cid_ref[...]                                             # [1, TN]
    negd = _neg_adc(lut_ref[...], codes_ref[...], cid, coff_ref[...],
                    qoff_ref[...], ks)                             # [TQ, TN]
    run_d[...], run_i[...] = merge_running(run_d[...], run_i[...], negd, cid, k)

    @pl.when(nb == n_nblocks - 1)
    def _flush():
        od_ref[...], oi_ref[...] = flush_running(run_d[...], run_i[...])


@functools.partial(jax.jit, static_argnames=("k", "tq", "tn", "interpret"))
def pq_adc_topk(
    lut: jax.Array,       # [Q, m, ks] f32 per-query subspace distance tables
    codes: jax.Array,     # [N, m] integer PQ codes
    cand_ids: jax.Array,  # [N] int32, -1 = padding
    k: int,
    *,
    cand_off: jax.Array | None = None,  # [N] f32 added per candidate
    q_off: jax.Array | None = None,     # [Q] f32 added per query
    tq: int = 128,
    tn: int = 128,
    interpret: bool = False,
):
    """Fused ADC scan + running top-k: ([Q, k] dists asc, [Q, k] ids).

    The optional offsets implement residual PQ (core.pq residual identity):
    ``cand_off`` carries the per-slot cross term 2⟨c, r̂⟩ — it re-ranks the
    shortlist — while ``q_off`` carries the per-query ‖c‖²−2⟨q, c⟩ scalar so
    the returned distances equal exact L2 to the reconstruction."""
    qn, m, ks = lut.shape
    n = codes.shape[0]
    tq = min(tq, max(8, qn))
    tn = min(tn, max(8, n))
    kp = lane_width(k)
    if cand_off is None:
        cand_off = jnp.zeros((n,), jnp.float32)
    if q_off is None:
        q_off = jnp.zeros((qn,), jnp.float32)
    lp = _pad_rows(lut.reshape(qn, m * ks), tq, 0.0)
    cp = pad_dim(codes.astype(jnp.int32).T, 1, tn, 0)
    ip = pad_dim(cand_ids.astype(jnp.int32)[None], 1, tn, -1)
    cop = pad_dim(cand_off.astype(jnp.float32)[None], 1, tn, 0.0)
    qop = _pad_rows(q_off.astype(jnp.float32)[:, None], tq, 0.0)
    n_nblocks = cp.shape[1] // tn
    kernel = functools.partial(_pq_adc_topk_kernel, k=k, ks=ks, n_nblocks=n_nblocks)
    od, oi = pl.pallas_call(
        kernel,
        grid=(lp.shape[0] // tq, n_nblocks),
        in_specs=[
            pl.BlockSpec((tq, m * ks), lambda i, j: (i, 0)),
            pl.BlockSpec((m, tn), lambda i, j: (0, j)),
            pl.BlockSpec((1, tn), lambda i, j: (0, j)),
            pl.BlockSpec((1, tn), lambda i, j: (0, j)),
            pl.BlockSpec((tq, 1), lambda i, j: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((tq, kp), lambda i, j: (i, 0)),
            pl.BlockSpec((tq, kp), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((lp.shape[0], kp), jnp.float32),
            jax.ShapeDtypeStruct((lp.shape[0], kp), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((tq, kp), jnp.float32),
            pltpu.VMEM((tq, kp), jnp.int32),
        ],
        interpret=interpret,
    )(lp, cp, ip, cop, qop)
    return od[:qn, :k], oi[:qn, :k]


def _pq_adc_topk_batched_kernel(lut_ref, codes_ref, cid_ref, coff_ref, qoff_ref,
                                od_ref, oi_ref, run_d, run_i,
                                *, k: int, ks: int, n_nblocks: int):
    """One (bucket, q_tile, n_block) grid step; scratch re-initializes per
    (bucket, q_tile) because the candidate-block axis is innermost."""
    nb = pl.program_id(2)

    @pl.when(nb == 0)
    def _init():
        run_d[...], run_i[...] = running_init(*run_d.shape)

    cid = cid_ref[0]                                               # [1, TN]
    negd = _neg_adc(lut_ref[0], codes_ref[0], cid, coff_ref[0], qoff_ref[0], ks)
    run_d[...], run_i[...] = merge_running(run_d[...], run_i[...], negd, cid, k)

    @pl.when(nb == n_nblocks - 1)
    def _flush():
        od_ref[0], oi_ref[0] = flush_running(run_d[...], run_i[...])


@functools.partial(jax.jit, static_argnames=("k", "tq", "tn", "interpret"))
def pq_adc_topk_batched(
    lut: jax.Array,       # [B, Q, m, ks] per-bucket per-query LUTs
    codes: jax.Array,     # [B, N, m] integer PQ codes
    cand_ids: jax.Array,  # [B, N] int32, -1 = padding
    k: int,
    *,
    cand_off: jax.Array | None = None,  # [B, N] f32 added per candidate
    q_off: jax.Array | None = None,     # [B, Q] f32 added per query
    tq: int = 128,
    tn: int = 128,
    interpret: bool = False,
):
    """Grid-batched pq_adc_topk: all B (query-bucket, code-block) pairs in ONE
    pallas launch — the quantized serve step's per-partition shortlist shape.
    Offsets carry the residual-PQ corrections exactly like the flat kernel."""
    bn, qn, m, ks = lut.shape
    n = codes.shape[1]
    tq = min(tq, max(8, qn))
    tn = min(tn, max(8, n))
    kp = lane_width(k)
    if cand_off is None:
        cand_off = jnp.zeros((bn, n), jnp.float32)
    if q_off is None:
        q_off = jnp.zeros((bn, qn), jnp.float32)
    lp = pad_dim(lut.reshape(bn, qn, m * ks), 1, tq, 0.0)
    cp = pad_dim(codes.astype(jnp.int32).transpose(0, 2, 1), 2, tn, 0)
    ip = pad_dim(cand_ids.astype(jnp.int32)[:, None], 2, tn, -1)
    cop = pad_dim(cand_off.astype(jnp.float32)[:, None], 2, tn, 0.0)
    qop = pad_dim(q_off.astype(jnp.float32)[..., None], 1, tq, 0.0)
    n_nblocks = cp.shape[2] // tn
    kernel = functools.partial(_pq_adc_topk_batched_kernel, k=k, ks=ks,
                               n_nblocks=n_nblocks)
    od, oi = pl.pallas_call(
        kernel,
        grid=(bn, lp.shape[1] // tq, n_nblocks),
        in_specs=[
            pl.BlockSpec((1, tq, m * ks), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, m, tn), lambda b, i, j: (b, 0, j)),
            pl.BlockSpec((1, 1, tn), lambda b, i, j: (b, 0, j)),
            pl.BlockSpec((1, 1, tn), lambda b, i, j: (b, 0, j)),
            pl.BlockSpec((1, tq, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, tq, kp), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, tq, kp), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bn, lp.shape[1], kp), jnp.float32),
            jax.ShapeDtypeStruct((bn, lp.shape[1], kp), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((tq, kp), jnp.float32),
            pltpu.VMEM((tq, kp), jnp.int32),
        ],
        interpret=interpret,
    )(lp, cp, ip, cop, qop)
    return od[:, :qn, :k], oi[:, :qn, :k]


def _pq_adc_topk_qbuf_kernel(qb_ref, nb_ref, lut_hbm, codes_hbm, cid_ref, coff_ref,
                             qoff_ref, od_ref, oi_ref, lut_s, cbuf,
                             sem_lut, sem_codes,
                             *, k: int, ks: int, tn: int, n_slots: int):
    """One bucket per grid step, streaming its first ``nb_ref[b]`` code
    blocks (``_util.live_blocks``: up to its last live slot; none for a
    bucket with no query, whose rows come back (inf, -1) with no gather).
    Two-phase body:

    1. scalar-prefetched LUT gather — ``qb_ref`` (SMEM) names each dispatch
       slot's query row; the rows are DMA'd one by one from the compact
       ``lut_pad`` plane in HBM into the ``lut_s`` VMEM scratch. Empty slots
       (``q_row``) fetch the zero sentinel row. Rows sit on an untiled
       leading axis (``[rows, 1, m·ks]``): a one-row slice of a tiled axis
       is not a legal DMA window on the TPU.
    2. double-buffered candidate streaming — code blocks of ``tn`` slots are
       DMA'd into the 2-deep ``cbuf`` ring; block j+1's copy is in flight
       while block j feeds the one-hot MXU contraction and the running
       top-k merge (carried through the fori_loop, no cross-step scratch).
       Every copy started is waited: block j+1's starts only when
       j+1 < ``nb_ref[b]``.
    """
    b = pl.program_id(0)
    n_blk = nb_ref[b]
    width = od_ref.shape[-1]

    @pl.when(n_blk == 0)
    def _no_query():
        od_ref[0], oi_ref[0] = flush_running(*running_init(n_slots, width))

    @pl.when(n_blk > 0)
    def _scan():
        def gather(s, carry):
            cp = pltpu.make_async_copy(lut_hbm.at[qb_ref[b, s]], lut_s.at[s],
                                       sem_lut)
            cp.start()
            cp.wait()
            return carry

        jax.lax.fori_loop(0, n_slots, gather, 0)
        lut = lut_s[...].reshape(n_slots, -1)       # [S, m·ks] f32
        qoff = qoff_ref[0]                          # [S, 1] f32

        def copy_block(j, slot):
            return pltpu.make_async_copy(codes_hbm.at[b, :, pl.ds(j * tn, tn)],
                                         cbuf.at[slot], sem_codes.at[slot])

        copy_block(0, 0).start()

        def body(j, carry):
            slot = jax.lax.rem(j, 2)

            @pl.when(j + 1 < n_blk)
            def _prefetch_next():
                copy_block(j + 1, jax.lax.rem(j + 1, 2)).start()

            copy_block(j, slot).wait()
            blk = pl.ds(pl.multiple_of(j * tn, tn), tn)
            cid = cid_ref[0, :, blk]                # [1, tn] int32, -1 = padding
            negd = _neg_adc(lut, cbuf[slot], cid, coff_ref[0, :, blk], qoff, ks)
            return merge_running(*carry, negd, cid, k)

        init = running_init(n_slots, width)
        od_ref[0], oi_ref[0] = flush_running(*jax.lax.fori_loop(0, n_blk, body, init))


@functools.partial(jax.jit, static_argnames=("k", "tn", "interpret"))
def pq_adc_topk_qbuf(
    lut_pad: jax.Array,   # [q_row+1, m, ks] compact LUTs + zero sentinel row
    qbuf: jax.Array,      # [B, S] int32 query row per dispatch slot
    codes: jax.Array,     # [B, N, m] int32 PQ codes (N multiple of tn)
    cand_ids: jax.Array,  # [B, N] int32, -1 = padding
    k: int,
    *,
    cand_off: jax.Array,  # [B, N] f32 residual cterm plane (zeros when unused)
    q_off: jax.Array,     # [B, S] f32 per-slot residual offset (zeros when unused)
    tn: int = 128,
    interpret: bool = False,
):
    """Scalar-prefetch-gathered, streaming form of ``pq_adc_topk_batched``.

    Staged operand footprint is O(q_row·m·ks) + O(B·S) indices — independent
    of dispatch fan-out — instead of the O(B·S·m·ks) HBM stack the dense
    batched kernel needs its caller to gather. Each bucket streams only its
    blocks of ``tn`` slots up to its last live slot, and none without a
    query (``_util.live_blocks``, the second scalar-prefetch operand). Rows
    of a bucket with no query come back (inf, -1); empty slots of an
    occupied bucket (``qbuf == q_row``) hold garbage. Callers drop both,
    exactly like the serve step's scatter. VMEM holds one bucket's gathered
    LUT rows (S·m·ks·4 bytes) — S is the dispatch q_cap, small by
    construction.
    """
    bn, n_slots = qbuf.shape
    q_rows, m, ks = lut_pad.shape
    n = codes.shape[1]
    assert n % tn == 0, (n, tn)
    n_blk = live_blocks(qbuf, cand_ids, tn, q_rows - 1)
    kp = lane_width(k)
    kernel = functools.partial(_pq_adc_topk_qbuf_kernel, k=k, ks=ks, tn=tn,
                               n_slots=n_slots)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(bn,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),                     # lut_pad (HBM)
            pl.BlockSpec(memory_space=pl.ANY),                     # codes (HBM)
            pl.BlockSpec((1, 1, n), lambda b, qb, nb: (b, 0, 0)),        # cand_ids
            pl.BlockSpec((1, 1, n), lambda b, qb, nb: (b, 0, 0)),        # cand_off
            pl.BlockSpec((1, n_slots, 1), lambda b, qb, nb: (b, 0, 0)),  # q_off
        ],
        out_specs=[
            pl.BlockSpec((1, n_slots, kp), lambda b, qb, nb: (b, 0, 0)),
            pl.BlockSpec((1, n_slots, kp), lambda b, qb, nb: (b, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((n_slots, 1, m * ks), jnp.float32),  # gathered LUT rows
            pltpu.VMEM((2, m, tn), jnp.int32),           # code stream ring
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
    )(qbuf, n_blk, lut_pad.reshape(q_rows, 1, m * ks), codes.transpose(0, 2, 1),
      cand_ids.reshape(bn, 1, n), cand_off.reshape(bn, 1, n),
      q_off.reshape(bn, n_slots, 1))
    return od[..., :k], oi[..., :k]
