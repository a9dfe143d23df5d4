"""Jit'd public wrappers around the Pallas kernels.

``impl`` picks the backend of every wrapper:
  * ``"pallas"``    — the kernels compiled with Mosaic. TPU only: off a TPU the
                      kernel's lowering raises, it never falls back;
  * ``"interpret"`` — the same kernels through the Pallas interpreter, the
                      explicit choice for checking them on a CPU;
  * ``"ref"``       — the jnp oracles (kernels/ref.py);
  * ``None``        — ``default_impl()``: pallas on a TPU, ref elsewhere.

All wrappers pad inputs to tile multiples and strip padding from outputs, so
callers never worry about alignment.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.kernels import autotune as _autotune
from repro.kernels import dedup_topk as _dd
from repro.kernels import kmeans_assign as _km
from repro.kernels import l2_topk as _l2
from repro.kernels import pq_adc as _adc
from repro.kernels import ref as _ref
from repro.kernels._util import live_blocks  # noqa: F401
from repro.kernels._util import pad_dim as _pad_dim, pad_rows as _pad_rows

# Partition capacities are kept whole 128-lane tiles of slots: the qbuf scans
# then pick a candidate-block tile that divides the capacity and stream the
# store in place. Any other capacity is padded to a tile multiple, a copy of
# the whole store on every call.
SLOT_ALIGN = 128


def _slot_tile(tile: int, n: int) -> int:
    """Candidate-block tile for an ``n``-slot axis: the widest whole-lane
    tile dividing both ``tile`` and ``n`` when ``n`` is lane-aligned (no
    padded copy), else ``tile`` capped at ``n``."""
    if n % SLOT_ALIGN == 0 and tile % SLOT_ALIGN == 0:
        return math.gcd(tile, n)
    return min(tile, max(8, n))


def default_impl() -> str:
    """One backend-selection policy for every dispatch layer (incl.
    serving/scan.py): fused kernels on TPU, jnp reference elsewhere."""
    return "pallas" if jax.default_backend() == "tpu" else "ref"


def _interpret(impl: str) -> bool:
    """Kernel-level ``interpret`` flag for a non-ref impl."""
    if impl not in ("pallas", "interpret"):
        raise ValueError(f"unknown kernel impl {impl!r}; expected 'ref', "
                         "'pallas' or 'interpret'")
    return impl == "interpret"


def l2_topk(q, cands, cand_ids, k: int, *, impl: str | None = None, tq: int = 256, tc: int = 256):
    """Top-k nearest candidates per query. Handles arbitrary Q/C via padding."""
    impl = impl or default_impl()
    if impl == "ref":
        return _ref.l2_topk_ref(q, cands, cand_ids, k)
    interpret = _interpret(impl)
    qn = q.shape[0]
    tq_eff = min(tq, max(8, qn))
    qp = _pad_rows(q, tq_eff, 0.0)
    cp = _pad_rows(cands, tc, 0.0)
    ip = _pad_rows(cand_ids.astype(jnp.int32), tc, -1)
    k_eff = min(k, cp.shape[0])
    d, i = _l2.l2_topk(qp, cp, ip, k_eff, tq=tq_eff, tc=min(tc, cp.shape[0]), interpret=interpret)
    d, i = d[:qn], i[:qn]
    if k_eff < k:  # degenerate pools: inf/-1 fill matches the ref oracle
        d = jnp.concatenate([d, jnp.full((qn, k - k_eff), jnp.inf, d.dtype)], axis=1)
        i = jnp.concatenate([i, jnp.full((qn, k - k_eff), -1, i.dtype)], axis=1)
    return d, i


def l2_topk_batched(q, cands, cand_ids, k: int, *, impl: str | None = None,
                    tq: int = 256, tc: int = 256):
    """Grid-batched top-k scan: [B, Q, d] query buckets vs [B, C, d] candidate
    sets → ([B, Q, k], [B, Q, k]) in one kernel launch (the serve step's
    per-partition scan shape). Pads Q/C to tile multiples internally."""
    impl = impl or default_impl()
    if impl == "ref":
        return _ref.l2_topk_batched_ref(q, cands, cand_ids, k)
    interpret = _interpret(impl)
    _, qn, _ = q.shape
    cn = cands.shape[1]
    tq_eff = min(tq, max(8, qn))
    tc_eff = min(tc, max(8, cn))
    qp = _pad_dim(q, 1, tq_eff, 0.0)
    cp = _pad_dim(cands, 1, tc_eff, 0.0)
    ip = _pad_dim(cand_ids.astype(jnp.int32), 1, tc_eff, -1)
    d, i = _l2.l2_topk_batched(qp, cp, ip, k, tq=tq_eff, tc=tc_eff,
                               interpret=interpret)
    return d[:, :qn], i[:, :qn]


def l2_qbuf_tile(cands_shape, k: int, tc: int | None = None) -> int:
    """Vector-block tile ``l2_topk_qbuf`` streams a ``[B, C, d]`` store in:
    ``tc``, or the autotune cache's (keyed on C/d/k), fitted to C."""
    cn, d = cands_shape[1], cands_shape[2]
    if tc is None:
        tc = _autotune.lookup(_autotune.l2_key(cn, d, k))
    return _slot_tile(tc, cn)


def l2_topk_qbuf(q_pad, qbuf, cands, cand_ids, k: int, *,
                 impl: str | None = None, tc: int | None = None):
    """Dispatch-buffer top-k scan: compact ``q_pad`` [q_row+1, d] + ``qbuf``
    [B, S] indices vs [B, C, d] candidate sets → ([B, S, k], [B, S, k]).
    Replaces the host-side ``q_pad[qbuf]`` expansion — the kernel gathers each
    bucket's rows itself via scalar prefetch. ``tc=None`` consults the
    measured-sweep autotune cache (keyed on the store shape C/d/k). Each
    bucket streams its ``live_blocks`` of that tile."""
    impl = impl or default_impl()
    qbuf = qbuf.astype(jnp.int32)
    if impl == "ref":
        return _ref.l2_topk_qbuf_ref(q_pad, qbuf, cands, cand_ids, k)
    interpret = _interpret(impl)
    tc_eff = l2_qbuf_tile(cands.shape, k, tc)
    cp = _pad_dim(cands, 1, tc_eff, 0.0)
    ip = _pad_dim(cand_ids.astype(jnp.int32), 1, tc_eff, -1)
    return _l2.l2_topk_qbuf(q_pad, qbuf, cp, ip, k, tc=tc_eff,
                            interpret=interpret)


def pq_qbuf_tile(codes_shape, ks: int, k: int, tn: int | None = None) -> int:
    """Code-block tile ``pq_adc_topk_qbuf`` streams a ``[B, N, m]`` code
    plane in: ``tn``, or the autotune cache's (keyed on N/m/ks/k), fitted
    to N."""
    nn, m = codes_shape[1], codes_shape[2]
    if tn is None:
        tn = _autotune.lookup(_autotune.pq_adc_key(nn, m, ks, k))
    return _slot_tile(tn, nn)


def pq_adc_topk_qbuf(lut_pad, qbuf, codes, cand_ids, k: int, *, cand_off=None,
                     q_off=None, impl: str | None = None, tn: int | None = None):
    """Dispatch-buffer fused ADC shortlist: compact ``lut_pad`` [q_row+1, m, ks]
    + ``qbuf`` [B, S] indices vs [B, N, m] code sets → ([B, S, k], [B, S, k]),
    threading the residual ``cand_off`` [B, N] / ``q_off`` [B, S] offsets.
    Replaces the host-side ``lut_pad[qbuf]`` expansion (the O(B·S·m·ks)
    amplification); the kernel gathers each bucket's LUT rows via scalar
    prefetch. ``tn=None`` consults the autotune cache (store shape N/m/ks/k).
    Each bucket streams its ``live_blocks`` of that tile."""
    impl = impl or default_impl()
    qbuf = qbuf.astype(jnp.int32)
    if impl == "ref":
        return _ref.pq_adc_topk_qbuf_ref(lut_pad, qbuf, codes, cand_ids, k,
                                         cand_off=cand_off, q_off=q_off)
    interpret = _interpret(impl)
    bn, n_slots = qbuf.shape
    nn = codes.shape[1]
    tn_eff = pq_qbuf_tile(codes.shape, lut_pad.shape[2], k, tn)
    cp = _pad_dim(codes.astype(jnp.int32), 1, tn_eff, 0)
    ip = _pad_dim(cand_ids.astype(jnp.int32), 1, tn_eff, -1)
    if cand_off is None:
        cand_off = jnp.zeros((bn, nn), jnp.float32)
    if q_off is None:
        q_off = jnp.zeros((bn, n_slots), jnp.float32)
    cop = _pad_dim(cand_off.astype(jnp.float32), 1, tn_eff, 0.0)
    return _adc.pq_adc_topk_qbuf(lut_pad, qbuf, cp, ip, k, cand_off=cop,
                                 q_off=q_off.astype(jnp.float32), tn=tn_eff,
                                 interpret=interpret)


def pq_adc_topk_batched(lut, codes, cand_ids, k: int, *, cand_off=None,
                        q_off=None, impl: str | None = None,
                        tq: int = 128, tn: int = 128):
    """Grid-batched fused ADC shortlist: [B, Q, m, ks] LUT buckets vs [B, N, m]
    code sets → ([B, Q, k], [B, Q, k]) in one launch, threading the residual
    ``cand_off`` [B, N] / ``q_off`` [B, Q] offset operands."""
    impl = impl or default_impl()
    if impl == "ref":
        return _ref.pq_adc_topk_batched_ref(lut, codes, cand_ids, k,
                                            cand_off=cand_off, q_off=q_off)
    return _adc.pq_adc_topk_batched(lut, codes, cand_ids, k, cand_off=cand_off,
                                    q_off=q_off, tq=tq, tn=tn,
                                    interpret=_interpret(impl))


def dedup_topk(dists, ids, k: int, *, impl: str | None = None, tq: int = 8):
    """Replica-aware merge: collapse duplicate ids to their best distance, then
    exact global top-k. Handles arbitrary Q/P (rows padded to ``tq``, the pool
    to whole chunks inside the kernel wrapper)."""
    impl = impl or default_impl()
    if impl == "ref":
        return _ref.dedup_topk_ref(dists, ids, k)
    interpret = _interpret(impl)
    qn = dists.shape[0]
    tq_eff = min(tq, max(8, qn))
    dp = _pad_rows(dists.astype(jnp.float32), tq_eff, jnp.inf)
    ip = _pad_rows(ids.astype(jnp.int32), tq_eff, -1)
    d, i = _dd.dedup_topk(dp, ip, k, tq=tq_eff, interpret=interpret)
    return d[:qn], i[:qn]


def pq_adc(lut, codes, *, impl: str | None = None, tq: int = 128, tn: int = 128):
    """ADC distances [Q, N] from per-query LUTs and PQ codes."""
    impl = impl or default_impl()
    if impl == "ref":
        return _ref.pq_adc_ref(lut, codes)
    return _adc.pq_adc(lut, codes, tq=tq, tn=tn, interpret=_interpret(impl))


def pq_adc_topk(lut, codes, cand_ids, k: int, *, cand_off=None, q_off=None,
                impl: str | None = None, tq: int = 128, tn: int = 128):
    """Fused ADC scan + top-k shortlist: the quantized tier's stage 1.
    Returns ([Q, k] ascending dists inf-padded, [Q, k] ids -1-padded); the
    kernel's NEG_BIG-initialized scratch handles k > N pools natively.
    ``cand_off`` [N] / ``q_off`` [Q] are the residual-PQ offset terms
    (core.pq residual identity): cand_off re-ranks, q_off shifts distances."""
    impl = impl or default_impl()
    if impl == "ref":
        return _ref.pq_adc_topk_ref(lut, codes, cand_ids, k,
                                    cand_off=cand_off, q_off=q_off)
    return _adc.pq_adc_topk(lut, codes, cand_ids, k, cand_off=cand_off,
                            q_off=q_off, tq=tq, tn=tn, interpret=_interpret(impl))


def kmeans_assign(x, centroids, *, impl: str | None = None, tn: int = 512, tb: int = 128):
    """(argmin centroid, min sq-dist) per point."""
    impl = impl or default_impl()
    if impl == "ref":
        return _ref.kmeans_assign_ref(x, centroids)
    interpret = _interpret(impl)
    n, b = x.shape[0], centroids.shape[0]
    tn_eff = min(tn, max(8, n))
    tb_eff = min(tb, b)
    xp = _pad_rows(x, tn_eff, 0.0)
    # pad centroids with far-away rows so they never win the argmin
    cp = _pad_rows(centroids, tb_eff, 1e6)
    a, d = _km.kmeans_assign(xp, cp, tn=tn_eff, tb=tb_eff, interpret=interpret)
    return a[:n], d[:n]
