"""Backend-dispatched per-partition scan — the serve step's hot stage.

The serve step turns query→partition routing into static-shape dispatch
buckets: ``qbuf [b_loc, q_cap]`` holds the queries assigned to each local
partition (``q_row`` = empty slot). This module owns everything after that:
scanning each partition's candidates for every query in its bucket and
returning per-(partition, slot) top-k, behind ONE signature with three
interchangeable implementations:

  * ``ref``       — portable jnp paths under ``lax.map`` (every backend; the
                    parity oracle for the kernels);
  * ``pallas``    — the fused Pallas kernels, grid-batched over the whole
                    ``[b_loc, q_cap]`` dispatch buffer in one launch
                    (``kernels.l2_topk_qbuf`` for the f32 tier,
                    ``kernels.pq_adc_topk_qbuf`` for the quantized tiers,
                    threading the residual ``cand_off``/``q_off`` operands).
                    The compact ``q_pad`` / ``lut_pad`` planes and the
                    ``qbuf`` index buffer go straight into the kernels as
                    scalar-prefetch operands — the host never expands them to
                    one copy per occupied dispatch slot, so stage-1 staging is
                    O(q_row·row) + O(b_loc·q_cap) indices instead of
                    O(b_loc·q_cap·row) (see ``staged_operand_bytes``).
                    Compiles with Mosaic and so needs a TPU;
  * ``interpret`` — the same kernels through the Pallas interpreter, on any
                    backend (what CI's parity suite and bench smoke run).

Tier semantics (identical across impls — on the CPU the parity suite asserts
bit-equal distances and set-equal ids; on a TPU the kernel's MXU and XLA's
dot may round the last bit differently). Every f32 distance is taken at
HIGHEST precision: a default f32 dot on the TPU rounds its operands to bf16.

  f32:        fused L2 + running top-k over the partition's vectors;
  quantized:  stage 1 ADC shortlist of ``rk`` slots from the shared per-query
              LUT (+ residual per-slot ``cterm`` and per-(query, partition)
              offset when given), stage 2 exact f32 rerank of the shortlist.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import ops as kops

IMPLS = ("ref", "pallas", "interpret")
_EXACT = jax.lax.Precision.HIGHEST


def resolve_impl(impl: str | None) -> str:
    """Map the config knob to a concrete impl: auto defers to the kernels'
    shared backend policy (kops.default_impl). Fails fast on typos."""
    if impl in (None, "auto"):
        return kops.default_impl()
    if impl not in IMPLS:
        raise ValueError(f"unknown scan impl {impl!r}; expected one of "
                         f"('auto', {', '.join(repr(s) for s in IMPLS)})")
    return impl


def run(impl: str | None, qbuf, q_pad, vecs_loc, ids_loc, k: int, *,
        lut_pad=None, codes_loc=None, rk: int | None = None,
        cterm_loc=None, off_loc=None):
    """Scan every local partition's candidates for its dispatched queries.

    qbuf      [b_loc, q_cap] int32 — query row per slot, ``q_row`` = empty
    q_pad     [q_row + 1, d]       — queries + sentinel row for empty slots
    vecs_loc  [b_loc, cap, d]      — partition vectors (rerank operand)
    ids_loc   [b_loc, cap] int32   — point ids, -1 = padding
    lut_pad   [q_row + 1, m, ks]   — quantized only: shared ADC LUTs + zero row
    codes_loc [b_loc, cap, m]      — quantized only: PQ codes
    rk        int                  — quantized only: shortlist depth
    cterm_loc [b_loc, cap]         — residual only: per-slot cross terms
    off_loc   [b_loc, q_row + 1]   — residual only: per-(partition, query)
                                     offsets, zero row for empty slots

    Returns ([b_loc, q_cap, k] dists, [b_loc, q_cap, k] ids, blocks); rows
    for empty slots hold garbage — the serve step's scatter drops them.
    ``blocks`` [2] int32 is (candidate blocks streamed, blocks of the whole
    capacity) in the kernel's tile: the kernels stream each occupied
    bucket's blocks up to its last live slot (``kops.live_blocks``), the
    ``ref`` path scores every slot, so it reports the whole capacity.
    """
    impl = resolve_impl(impl)
    cap = ids_loc.shape[1]
    if lut_pad is not None:
        tile = kops.pq_qbuf_tile(codes_loc.shape, lut_pad.shape[2], rk)
    else:
        tile = kops.l2_qbuf_tile(vecs_loc.shape, k)
    dense = ids_loc.shape[0] * -(-cap // tile)
    if impl == "ref":
        if lut_pad is not None:
            d, i = _quantized_ref(qbuf, q_pad, vecs_loc, ids_loc, k,
                                  lut_pad, codes_loc, rk, cterm_loc, off_loc)
        else:
            d, i = _f32_ref(qbuf, q_pad, vecs_loc, ids_loc, k)
        return d, i, jnp.array([dense, dense], jnp.int32)
    if lut_pad is not None:
        d, i = _quantized_kernel(qbuf, q_pad, vecs_loc, ids_loc, k, lut_pad,
                                 codes_loc, rk, cterm_loc, off_loc, impl, tile)
    else:
        d, i = _f32_kernel(qbuf, q_pad, vecs_loc, ids_loc, k, impl, tile)
    streamed = kops.live_blocks(qbuf, ids_loc, tile, q_pad.shape[0] - 1).sum()
    return d, i, jnp.stack([streamed, jnp.int32(dense)])


# ------------------------------------------------------------------ f32 tier

def _f32_ref(qbuf, q_pad, vecs_loc, ids_loc, k):
    def scan_partition(args):
        qi, vec_b, id_b = args                               # [q_cap], [cap, d], [cap]
        # the query is quantized to the store dtype (store_dtype=bfloat16
        # halves the dominant vector-read traffic), then both operands are
        # upcast to f32 before the dot — the same point as the kernel
        qs = q_pad[qi].astype(vec_b.dtype).astype(jnp.float32)  # [q_cap, d]
        vec = vec_b.astype(jnp.float32)
        d2 = (
            jnp.sum(qs ** 2, -1, keepdims=True)
            - 2.0 * jax.lax.dot_general(qs, vec, (((1,), (1,)), ((), ())),
                                        preferred_element_type=jnp.float32,
                                        precision=_EXACT)
            + jnp.sum(vec ** 2, -1)[None, :]
        )
        d2 = jnp.where(id_b[None, :] < 0, jnp.inf, d2)
        neg, posk = jax.lax.top_k(-d2, k)
        return -neg, id_b[posk]                              # [q_cap, k] ×2

    return jax.lax.map(scan_partition, (qbuf, vecs_loc, ids_loc))


def _f32_kernel(qbuf, q_pad, vecs_loc, ids_loc, k, impl, tile):
    # cast the COMPACT plane to the store dtype (same quantization point as
    # the ref path's per-slot cast); the kernel gathers each bucket's rows
    # itself via the scalar-prefetched qbuf — no [b_loc, q_cap, d] expansion
    qp = q_pad.astype(vecs_loc.dtype)                        # [q_row + 1, d]
    return kops.l2_topk_qbuf(qp, qbuf, vecs_loc, ids_loc, k, impl=impl,
                             tc=tile)


# ------------------------------------------------------------ quantized tiers

def _quantized_ref(qbuf, q_pad, vecs_loc, ids_loc, k, lut_pad, codes_loc, rk,
                   cterm_loc, off_loc):
    m = codes_loc.shape[-1]
    m_idx = jnp.arange(m)[:, None]
    residual = cterm_loc is not None

    def scan_partition(args):
        if residual:
            qi, codes_b, vec_b, id_b, ct_b, off_b = args
        else:
            qi, codes_b, vec_b, id_b = args    # [q_cap], [cap, m], [cap, d], [cap]
        # stage 1: ADC shortlist over the partition's codes from the shared LUT
        lq = lut_pad[qi]                                     # [q_cap, m, ks]
        ad = lq[:, m_idx, codes_b.astype(jnp.int32).T].sum(1)  # [q_cap, cap]
        if residual:
            # offset add order mirrors the kernel (q_off then cand_off) so the
            # shortlist selection agrees bitwise across impls
            ad = ad + off_b[qi][:, None] + ct_b[None, :]
        ad = jnp.where(id_b[None, :] < 0, jnp.inf, ad)
        _, sl = jax.lax.top_k(-ad, rk)                       # shortlist slots
        # stage 2: exact f32 rerank on the shortlist only
        qs = q_pad[qi].astype(jnp.float32)
        cand = vec_b[sl].astype(jnp.float32)                 # [q_cap, rk, d]
        cid = id_b[sl]
        d2 = (
            jnp.sum(qs * qs, -1)[:, None]
            - 2.0 * jnp.einsum("qd,qrd->qr", qs, cand, precision=_EXACT)
            + jnp.sum(cand * cand, -1)
        )
        d2 = jnp.where(cid < 0, jnp.inf, d2)
        neg, posk = jax.lax.top_k(-d2, k)
        return -neg, jnp.take_along_axis(cid, posk, axis=1)  # [q_cap, k] ×2

    scan_args = (qbuf, codes_loc, vecs_loc, ids_loc)
    if residual:
        scan_args = scan_args + (cterm_loc, off_loc)
    return jax.lax.map(scan_partition, scan_args)


def _quantized_kernel(qbuf, q_pad, vecs_loc, ids_loc, k, lut_pad, codes_loc, rk,
                      cterm_loc, off_loc, impl, tile):
    b_loc, _ = qbuf.shape
    cap = vecs_loc.shape[1]
    # stage 1: one fused launch over all buckets. The kernel ranks by ADC and
    # returns the ids it was given — feed it SLOT indices so the shortlist can
    # gather the f32 rerank operands (invalid slots come back as -1). The
    # compact lut_pad plane + qbuf go in directly; the kernel's scalar-
    # prefetch gather replaces the old host-side lut_pad[qbuf] expansion
    # (one LUT copy per occupied slot, ≈nprobe·q_cap_factor× amplification).
    slots = jnp.broadcast_to(jnp.arange(cap, dtype=jnp.int32)[None, :], (b_loc, cap))
    slots = jnp.where(ids_loc < 0, -1, slots)
    coff = qoff = None
    if cterm_loc is not None:
        coff = cterm_loc                                     # [b_loc, cap]
        qoff = jnp.take_along_axis(off_loc, qbuf, axis=1)    # [b_loc, q_cap]
    _, sl = kops.pq_adc_topk_qbuf(lut_pad, qbuf, codes_loc, slots, rk,
                                  cand_off=coff, q_off=qoff, impl=impl,
                                  tn=tile)
    # stage 2: exact f32 rerank of the shortlist (same math as the ref path)
    safe = jnp.maximum(sl, 0)                                # [b_loc, q_cap, rk]
    cid = jnp.where(sl >= 0,
                    jnp.take_along_axis(ids_loc[:, None, :], safe, axis=2), -1)
    cand = jnp.take_along_axis(vecs_loc[:, None], safe[..., None],
                               axis=2).astype(jnp.float32)   # [b_loc, q_cap, rk, d]
    qs = q_pad[qbuf].astype(jnp.float32)                     # [b_loc, q_cap, d]
    d2 = (
        jnp.sum(qs * qs, -1)[..., None]
        - 2.0 * jnp.einsum("bqd,bqrd->bqr", qs, cand, precision=_EXACT)
        + jnp.sum(cand * cand, -1)
    )
    d2 = jnp.where(cid < 0, jnp.inf, d2)
    neg, posk = jax.lax.top_k(-d2, k)
    return -neg, jnp.take_along_axis(cid, posk, axis=-1)


# ----------------------------------------------------------- bytes accounting

def staged_operand_bytes(qbuf, plane) -> dict:
    """Stage-1 per-query operand staging footprint for a dispatch shape.

    ``plane`` is the compact per-query operand the kernel path stages —
    ``q_pad [q_row+1, d]`` for the f32 tier, ``lut_pad [q_row+1, m, ks]`` for
    the quantized tiers. Returns:

      compact_bytes  — what the qbuf entry points stage: the plane itself
                       plus the int32 ``qbuf`` index buffer
                       (O(q_row·row) + O(b_loc·q_cap));
      expanded_bytes — what the retired host-side ``plane[qbuf]`` gather
                       materialized: one plane row per dispatch slot
                       (O(b_loc·q_cap·row)).

    The ratio is the input amplification the scalar-prefetch rewrite removed;
    benches persist both so the improvement is auditable. Accepts arrays or
    ``jax.ShapeDtypeStruct``s (only ``.shape``/``.dtype`` are read).
    """
    b_loc, q_cap = qbuf.shape
    row_elems = 1
    for s in plane.shape[1:]:
        row_elems *= int(s)
    row_bytes = row_elems * jnp.dtype(plane.dtype).itemsize
    return {
        "compact_bytes": int(plane.shape[0]) * row_bytes + b_loc * q_cap * 4,
        "expanded_bytes": b_loc * q_cap * row_bytes,
    }
