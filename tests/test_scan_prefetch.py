"""Scalar-prefetch scan entry points (ISSUE 8) — the qbuf kernels that
replaced the host-side ``q_pad[qbuf]`` / ``lut_pad[qbuf]`` expansion.

Covers: parity of ``ops.l2_topk_qbuf`` / ``ops.pq_adc_topk_qbuf`` against
their dense-gather ref oracles across {f32, pq, residual_pq} × {ref,
interpret} — including ragged caps that are not multiples of the stream tile,
empty buckets (every slot ``q_row``), and degenerate k > cap pools; the
autotuner's cache-key path; and the bytes-accounting gates: the staged
operand footprint no longer scales with occupied dispatch slots, and the
traced quantized scan contains no ``[b_loc, q_cap, m, ks]`` intermediate.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import autotune, ops, ref
from repro.serving import scan

B, S, QR, CAP, D, M, KS, K = 5, 7, 11, 37, 16, 8, 16, 9


@pytest.fixture(scope="module")
def qbuf_inputs():
    """Deliberately hostile dispatch shapes: CAP=37 is no multiple of any
    stream tile, bucket 0 is fully empty, bucket 1 half-empty, bucket 2
    ragged (tail slots padded with id -1)."""
    rng = np.random.default_rng(0)
    q_pad = rng.standard_normal((QR + 1, D)).astype(np.float32)
    q_pad[QR] = 1e9                       # sentinel row for empty slots
    qbuf = rng.integers(0, QR, (B, S)).astype(np.int32)
    qbuf[0, :] = QR                       # empty bucket
    qbuf[1, 3:] = QR                      # partially empty bucket
    cands = rng.standard_normal((B, CAP, D)).astype(np.float32)
    cid = rng.integers(0, 500, (B, CAP)).astype(np.int32)
    cid[2, 20:] = -1                      # ragged bucket
    lut_pad = rng.standard_normal((QR + 1, M, KS)).astype(np.float32)
    lut_pad[QR] = 0.0
    codes = rng.integers(0, KS, (B, CAP, M)).astype(np.int32)
    coff = rng.standard_normal((B, CAP)).astype(np.float32)
    qoff = rng.standard_normal((B, S)).astype(np.float32)
    occ = qbuf < QR
    as_j = jnp.asarray
    return dict(q_pad=as_j(q_pad), qbuf=as_j(qbuf), cands=as_j(cands),
                cid=as_j(cid), lut_pad=as_j(lut_pad), codes=as_j(codes),
                coff=as_j(coff), qoff=as_j(qoff), occ=occ)


def _assert_occupied_match(occ, d_a, i_a, d_b, i_b, *, bitwise_dists):
    """Empty slots hold garbage by contract — compare occupied rows only.
    Dists compare bitwise (or as sorted sets when only selection matters);
    ids compare as sets per row (tie order is impl-defined)."""
    d_a, i_a = np.asarray(d_a), np.asarray(i_a)
    d_b, i_b = np.asarray(d_b), np.asarray(i_b)
    if bitwise_dists:
        np.testing.assert_array_equal(d_a[occ], d_b[occ])
    for b in range(occ.shape[0]):
        for s in range(occ.shape[1]):
            if occ[b, s]:
                assert set(i_a[b, s].tolist()) == set(i_b[b, s].tolist()), (b, s)


@pytest.mark.parametrize("tc", [16, 64])
def test_l2_qbuf_matches_dense_gather_oracle(qbuf_inputs, tc):
    x = qbuf_inputs
    d_ref, i_ref = ops.l2_topk_qbuf(x["q_pad"], x["qbuf"], x["cands"],
                                    x["cid"], K, impl="ref")
    d_int, i_int = ops.l2_topk_qbuf(x["q_pad"], x["qbuf"], x["cands"],
                                    x["cid"], K, impl="interpret", tc=tc)
    # kernel-vs-jnp matmul rounding is the pre-existing tolerance of the
    # batched kernels; selection (ids) must agree exactly
    _assert_occupied_match(x["occ"], d_ref, i_ref, d_int, i_int,
                           bitwise_dists=False)
    occ = x["occ"]
    np.testing.assert_allclose(np.asarray(d_ref)[occ], np.asarray(d_int)[occ],
                               rtol=1e-5, atol=1e-5)


def test_l2_qbuf_bitwise_equals_retired_expansion_path(qbuf_inputs):
    """The acceptance anchor: the qbuf kernel is bit-identical to the batched
    kernel fed the host-expanded ``q_pad[qbuf]`` stack it replaced — the
    rewrite changed operand staging, not a single arithmetic bit."""
    x = qbuf_inputs
    qg = x["q_pad"][x["qbuf"]]
    d_old, i_old = ops.l2_topk_batched(qg, x["cands"], x["cid"], K,
                                       impl="interpret", tq=8, tc=16)
    d_new, i_new = ops.l2_topk_qbuf(x["q_pad"], x["qbuf"], x["cands"],
                                    x["cid"], K, impl="interpret", tc=16)
    occ = x["occ"]
    np.testing.assert_array_equal(np.asarray(d_old)[occ], np.asarray(d_new)[occ])
    np.testing.assert_array_equal(np.asarray(i_old)[occ], np.asarray(i_new)[occ])


@pytest.mark.parametrize("residual", [False, True])
@pytest.mark.parametrize("tn", [16, 64])
def test_adc_qbuf_matches_dense_gather_oracle(qbuf_inputs, residual, tn):
    x = qbuf_inputs
    kw = dict(cand_off=x["coff"], q_off=x["qoff"]) if residual else {}
    d_ref, i_ref = ops.pq_adc_topk_qbuf(x["lut_pad"], x["qbuf"], x["codes"],
                                        x["cid"], K, impl="ref", **kw)
    d_int, i_int = ops.pq_adc_topk_qbuf(x["lut_pad"], x["qbuf"], x["codes"],
                                        x["cid"], K, impl="interpret", tn=tn,
                                        **kw)
    _assert_occupied_match(x["occ"], d_ref, i_ref, d_int, i_int,
                           bitwise_dists=False)
    occ = x["occ"]
    np.testing.assert_allclose(np.asarray(d_ref)[occ], np.asarray(d_int)[occ],
                               rtol=1e-5, atol=1e-5)


def test_adc_qbuf_bitwise_equals_retired_expansion_path(qbuf_inputs):
    x = qbuf_inputs
    lq = x["lut_pad"][x["qbuf"]]
    d_old, i_old = ops.pq_adc_topk_batched(
        lq, x["codes"], x["cid"], K, cand_off=x["coff"],
        q_off=x["qoff"], impl="interpret", tq=8, tn=16)
    d_new, i_new = ops.pq_adc_topk_qbuf(
        x["lut_pad"], x["qbuf"], x["codes"], x["cid"], K,
        cand_off=x["coff"], q_off=x["qoff"], impl="interpret", tn=16)
    occ = x["occ"]
    np.testing.assert_array_equal(np.asarray(d_old)[occ], np.asarray(d_new)[occ])
    np.testing.assert_array_equal(np.asarray(i_old)[occ], np.asarray(i_new)[occ])


def test_adc_qbuf_degenerate_k_exceeds_cap(qbuf_inputs):
    x = qbuf_inputs
    k_big = CAP + 13
    d_ref, i_ref = ops.pq_adc_topk_qbuf(x["lut_pad"], x["qbuf"], x["codes"],
                                        x["cid"], k_big, impl="ref")
    d_int, i_int = ops.pq_adc_topk_qbuf(x["lut_pad"], x["qbuf"], x["codes"],
                                        x["cid"], k_big, impl="interpret",
                                        tn=16)
    occ = x["occ"]
    # the slots beyond the pool flush as inf/-1 in both impls
    np.testing.assert_array_equal(np.asarray(i_ref)[occ] < 0,
                                  np.asarray(i_int)[occ] < 0)
    _assert_occupied_match(occ, d_ref, i_ref, d_int, i_int,
                           bitwise_dists=False)


def test_empty_bucket_rows_are_garbage_but_finite_shape(qbuf_inputs):
    """Empty buckets (all slots q_row) must not crash the gather loop; their
    output rows are garbage by contract but the occupied buckets around them
    stay exact."""
    x = qbuf_inputs
    qbuf_all_empty = jnp.full_like(x["qbuf"], QR)
    d, i = ops.pq_adc_topk_qbuf(x["lut_pad"], qbuf_all_empty, x["codes"],
                                x["cid"], K, impl="interpret", tn=16)
    assert d.shape == (B, S, K) and i.shape == (B, S, K)


# ------------------------------------------------------------------ autotune

def test_autotune_cache_key_path():
    autotune.clear()
    try:
        t1 = autotune.autotune_pq_adc_qbuf(32, 2, 16, 4, candidates=(8, 16),
                                           b_loc=2, q_cap=4, q_row=6,
                                           impl="interpret")
        assert t1 in (8, 16)
        recs = autotune.records()
        assert len(recs) == 1 and recs[0]["cached"] is False
        assert set(recs[0]["timings_s"]) == {"8", "16"}
        # same store shape → cache hit, no re-sweep, recorded as cached
        t2 = autotune.autotune_pq_adc_qbuf(32, 2, 16, 4, candidates=(8, 16),
                                           b_loc=2, q_cap=4, q_row=6,
                                           impl="interpret")
        assert t2 == t1
        recs = autotune.records()
        assert len(recs) == 2 and recs[1]["cached"] is True
        # the ops wrapper resolves tn=None through the same cache
        assert autotune.lookup(autotune.pq_adc_key(32, 2, 16, 4)) == t1
        # an unseen shape falls back to the kernel default
        assert autotune.lookup(autotune.pq_adc_key(999, 2, 16, 4)) == 128
        assert autotune.lookup(autotune.l2_key(999, 16, 4)) == 256
    finally:
        autotune.clear()


def test_autotune_l2_sweep_records():
    autotune.clear()
    try:
        t = autotune.autotune_l2_qbuf(32, 8, 4, candidates=(8, 16),
                                      b_loc=2, q_cap=4, q_row=6,
                                      impl="interpret")
        assert t in (8, 16)
        assert autotune.lookup(autotune.l2_key(32, 8, 4)) == t
    finally:
        autotune.clear()


# ----------------------------------------------------------- bytes accounting

def test_staged_operand_bytes_independent_of_slots():
    """The point of the rewrite: compact staging is flat in dispatch fan-out
    while the retired expansion grew linearly with occupied slots."""
    lut_pad = jax.ShapeDtypeStruct((QR + 1, M, KS), jnp.float32)
    small = scan.staged_operand_bytes(jax.ShapeDtypeStruct((B, 4), jnp.int32),
                                      lut_pad)
    big = scan.staged_operand_bytes(jax.ShapeDtypeStruct((B, 64), jnp.int32),
                                    lut_pad)
    row = M * KS * 4
    # expanded: one plane row per slot; compact: the plane + int32 indices
    assert small["expanded_bytes"] == B * 4 * row
    assert big["expanded_bytes"] == B * 64 * row
    assert small["compact_bytes"] == (QR + 1) * row + B * 4 * 4
    # compact grows only by the 4-byte indices (16× fan-out → +B·60·4 bytes,
    # not +B·60·row)
    assert big["compact_bytes"] - small["compact_bytes"] == B * 60 * 4
    assert big["compact_bytes"] < big["expanded_bytes"]


def test_quantized_scan_traces_without_expanded_lut(qbuf_inputs):
    """Structural gate: the traced quantized scan must not contain ANY
    ``[b_loc, q_cap, m, ks]`` f32 intermediate — the amplified operand the
    old host-side ``lut_pad[qbuf]`` gather materialized."""
    x = qbuf_inputs
    jaxpr = jax.make_jaxpr(
        lambda qb, qp, v, i, lp, c: scan.run(
            "interpret", qb, qp, v, i, K, lut_pad=lp, codes_loc=c, rk=K)
    )(x["qbuf"], x["q_pad"], x["cands"], x["cid"], x["lut_pad"], x["codes"])
    expanded = re.escape(f"f32[{B},{S},{M},{KS}]")
    assert not re.search(expanded, str(jaxpr)), (
        "quantized scan re-materializes the per-slot LUT expansion")
    # while the compact plane is still there
    assert f"f32[{QR + 1},{M},{KS}]" in str(jaxpr)
