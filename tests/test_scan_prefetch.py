"""Scalar-prefetch scan entry points (ISSUE 8) — the qbuf kernels that
replaced the host-side ``q_pad[qbuf]`` / ``lut_pad[qbuf]`` expansion.

Covers: parity of ``ops.l2_topk_qbuf`` / ``ops.pq_adc_topk_qbuf`` against
their dense-gather ref oracles across {f32, pq, residual_pq} × {ref,
interpret} — including ragged caps that are not multiples of the stream tile,
empty buckets (every slot ``q_row``), and degenerate k > cap pools; the
autotuner's cache-key path; and the bytes-accounting gates: the staged
operand footprint no longer scales with occupied dispatch slots, and the
traced quantized scan contains no ``[b_loc, q_cap, m, ks]`` intermediate.
Then the per-bucket trip count: each bucket streams only its blocks up to
its last live slot, and none without a query, bit-equal to the
full-capacity scan (``_qbuf_dense_oracle``), and the engine's block counters.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _qbuf_dense_oracle import l2_topk_qbuf_dense, pq_adc_topk_qbuf_dense
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


# --------------------------------------------- live blocks: per-bucket trips

LB, LS, LQR, LCAP, LTILE, LK = 4, 5, 9, 64, 16, 6
LIVE_CASES = ["unoccupied", "no_live_slots", "full", "mid_tile", "tombstones",
              "grown_by_insert"]


def _live_ids(extents, rng, cap=LCAP):
    """[len(extents), cap] ids, live from slot 0 up to each extent."""
    ids = np.full((len(extents), cap), -1, np.int32)
    for b, e in enumerate(extents):
        ids[b, :e] = rng.permutation(10_000)[:e]
    return ids


def _live_case(case, rng):
    """Dispatch shapes for one case: (qbuf, cand ids) and the blocks each
    bucket must stream; queries are packed from slot 0 as dispatch packs
    them, bucket 1 half full."""
    qbuf = rng.integers(0, LQR, (LB, LS)).astype(np.int32)
    qbuf[1, 2:] = LQR
    ids = _live_ids([40, 23, 64, 9], rng)
    if case == "unoccupied":
        qbuf[0] = qbuf[2] = LQR
        n_blk = [0, 2, 0, 1]
    elif case == "no_live_slots":
        ids[1] = -1
        n_blk = [3, 0, 4, 1]
    elif case == "full":
        ids = _live_ids([LCAP] * LB, rng)
        n_blk = [4] * LB
    elif case == "mid_tile":
        ids = _live_ids([37, 5, 50, 17], rng)
        n_blk = [3, 1, 4, 2]
    else:  # tombstones: holes below the extent, one a whole block
        ids = _live_ids([60, 60, 33, 64], rng)
        ids[0, 16:32] = -1
        ids[1, rng.choice(59, 25, replace=False)] = -1
        ids[2, 1:32] = -1
        ids[3, ::3] = -1
        n_blk = [4, 4, 3, 4]
    return qbuf, ids, np.array(n_blk, np.int32)


@pytest.fixture(scope="module")
def grown_store():
    """Id plane and vectors of a store grown by ``insert`` after its build:
    the grown partitions' live slots run past the build's capacity."""
    from repro.data import make_vector_dataset
    from repro.launch.mesh import make_test_mesh
    from repro.serving import BuildConfig, LiraEngine

    ds = make_vector_dataset(n=400, n_queries=4, dim=16, n_modes=8, seed=5)
    eng = LiraEngine.build(make_test_mesh(), ds.base,
                           BuildConfig(n_partitions=LB, k=LK, train_frac=0.5,
                                       epochs=1, nprobe_max=LB))
    cap0 = eng.cfg.capacity
    c0 = np.asarray(eng.store["centroids"])[0]
    n_new = cap0 * LB + 1                     # more rows than the store holds
    x_new = c0 + np.random.default_rng(1).normal(0, 0.01, (n_new, 16)).astype(np.float32)
    eng.insert(x_new, np.arange(n_new) + 10_000)
    assert eng.cfg.capacity > cap0
    occ = np.asarray(eng.store["occupancy"])
    ids = np.where(occ, np.asarray(eng.store["ids"]), -1)
    assert (occ[:, cap0:].any(1)).any()
    return ids, np.asarray(eng.store["vectors"])


def _case_arrays(case, grown_store):
    rng = np.random.default_rng(LIVE_CASES.index(case))
    if case == "grown_by_insert":
        ids, vecs = grown_store
        qbuf = rng.integers(0, LQR, (ids.shape[0], LS)).astype(np.int32)
        qbuf[1, 1:] = LQR
        extent = np.array([np.flatnonzero(r >= 0).max() + 1 if (r >= 0).any() else 0
                           for r in ids])
        n_blk = -(-extent // LTILE)
    else:
        qbuf, ids, n_blk = _live_case(case, rng)
        vecs = rng.standard_normal((LB, LCAP, 16)).astype(np.float32)
    b, cap = ids.shape
    q_pad = rng.standard_normal((LQR + 1, 16)).astype(np.float32)
    q_pad[LQR] = 1e9
    lut_pad = rng.standard_normal((LQR + 1, 4, 16)).astype(np.float32)
    lut_pad[LQR] = 0.0
    codes = rng.integers(0, 16, (b, cap, 4)).astype(np.int32)
    coff = rng.standard_normal((b, cap)).astype(np.float32)
    qoff = rng.standard_normal((b, LS)).astype(np.float32)
    return dict(qbuf=qbuf, ids=ids, n_blk=n_blk, vecs=vecs, q_pad=q_pad,
                lut_pad=lut_pad, codes=codes, coff=coff, qoff=qoff)


@pytest.mark.parametrize("case", LIVE_CASES)
@pytest.mark.parametrize("kernel", ["l2", "adc"])
def test_live_block_scan_bit_equal_to_full_capacity_scan(kernel, case, request):
    """Streaming each bucket only up to its last live slot, and nothing for
    a bucket without a query, returns on every occupied slot exactly what
    the full-capacity scan returned (the oracle keeps that kernel as it
    was), dists and ids bit for bit; a bucket without a query comes back
    (inf, -1)."""
    grown = request.getfixturevalue("grown_store") if case == "grown_by_insert" else None
    x = {n: jnp.asarray(a) for n, a in _case_arrays(case, grown).items()}
    if kernel == "l2":
        new = ops.l2_topk_qbuf(x["q_pad"], x["qbuf"], x["vecs"], x["ids"], LK,
                               impl="interpret", tc=LTILE)
        old = l2_topk_qbuf_dense(x["q_pad"], x["qbuf"], x["vecs"], x["ids"], LK,
                                 tc=LTILE)
    else:
        kw = dict(cand_off=x["coff"], q_off=x["qoff"])
        new = ops.pq_adc_topk_qbuf(x["lut_pad"], x["qbuf"], x["codes"], x["ids"],
                                   LK, impl="interpret", tn=LTILE, **kw)
        old = pq_adc_topk_qbuf_dense(x["lut_pad"], x["qbuf"], x["codes"],
                                     x["ids"], LK, tn=LTILE, **kw)
    occ = np.asarray(x["qbuf"]) < LQR
    for a, b in zip(new, old):
        np.testing.assert_array_equal(np.asarray(a)[occ], np.asarray(b)[occ])
    no_query = ~occ.any(1)
    assert np.isinf(np.asarray(new[0])[no_query]).all()
    assert (np.asarray(new[1])[no_query] == -1).all()
    np.testing.assert_array_equal(
        np.asarray(ops.live_blocks(x["qbuf"], x["ids"], LTILE, LQR)),
        np.where(occ.any(1), x["n_blk"], 0))


def test_live_blocks_counts_tiles_up_to_the_last_live_slot():
    qbuf = jnp.array([[0, 3, 3], [3, 3, 3], [2, 3, 3], [1, 0, 2], [0, 3, 3]])
    ids = -jnp.ones((5, 40), jnp.int32)
    ids = ids.at[0, 0].set(7)          # one live slot: one block
    ids = ids.at[1, :].set(1)          # live, but no query: none
    ids = ids.at[2, 15].set(4)         # last slot of block 1
    ids = ids.at[2, 16].set(-1)
    ids = ids.at[3, 16].set(5)         # first slot of block 2
    ids = ids.at[3, 39].set(9)         # last slot of a ragged capacity
    # bucket 4: a query and no live slot: none
    got = ops.live_blocks(qbuf, ids, 8, 3)
    assert got.dtype == jnp.int32
    assert got.tolist() == [1, 0, 2, 5, 0]
    assert ops.live_blocks(qbuf, ids, 16, 3).tolist() == [1, 0, 1, 3, 0]


def _step_store(rng, extents, cap=384, dim=16):
    b = len(extents)
    ids = _live_ids(extents, rng, cap)
    vecs = rng.standard_normal((b, cap, dim)).astype(np.float32)
    return {"centroids": jnp.asarray(vecs.mean(1)), "vectors": jnp.asarray(vecs),
            "ids": jnp.asarray(ids), "occupancy": jnp.asarray(ids >= 0)}


def _step_cfg(b, cap=384, dim=16):
    from repro.configs.base import LiraSystemConfig

    return LiraSystemConfig(arch="t", dim=dim, n_partitions=b, capacity=cap, k=5,
                            nprobe_max=b)


def test_serve_step_block_counter_sums_occupied_buckets():
    """Each query probes only its best partition (σ above any probability):
    the engine's block counter is the live blocks of exactly the partitions
    some query probed, the dense counter every partition's capacity."""
    from repro.core import probing
    from repro.kernels import ops as kops
    from repro.launch.mesh import make_test_mesh
    from repro.obs.metrics import MetricsRegistry
    from repro.serving import LiraEngine, SearchRequest

    rng = np.random.default_rng(3)
    extents = [384, 130, 0, 7, 200, 300]
    store = _step_store(rng, extents)
    cfg = _step_cfg(len(extents))
    params = probing.init(jax.random.PRNGKey(2),
                          probing.ProbingConfig(dim=16, n_partitions=len(extents)))
    reg = MetricsRegistry()
    eng = LiraEngine(cfg=cfg, params=params, store=store, mesh=make_test_mesh(),
                     metrics=reg)
    q = rng.standard_normal((5, 16)).astype(np.float32)
    res = eng.search(SearchRequest(queries=q, sigma=2.0, impl="interpret"))
    assert (res.nprobe_eff == 1).all()
    cents = store["centroids"]
    cd = (jnp.sum(q * q, -1, keepdims=True)
          - 2.0 * jnp.dot(q, cents.T, precision=jax.lax.Precision.HIGHEST)
          + jnp.sum(cents * cents, -1)[None, :])
    probed = set(np.argmax(np.asarray(probing.apply(params, jnp.asarray(q), cd)), 1).tolist())
    tile = kops.l2_qbuf_tile(store["vectors"].shape, cfg.k)
    expect = sum(-(-extents[b] // tile) for b in probed)
    blocks = reg.counter("lira_engine_scan_blocks_total")
    dense = reg.counter("lira_engine_scan_blocks_dense_total")
    assert blocks.total() == expect
    assert blocks.value(tier="f32", impl="interpret") == expect
    assert dense.total() == len(extents) * -(-384 // tile)
    assert len(probed) < len(extents)          # some partitions had no query


@pytest.mark.parametrize("impl", ["ref", "interpret"])
def test_full_store_streams_every_block(impl):
    """A store filled to capacity, every partition probed, streams exactly
    the blocks of the whole capacity (what the scan streamed before it
    skipped any); a batch with no valid row streams none on the kernel path.
    The ``ref`` path scores every slot and always reports the whole
    capacity."""
    from repro.core import probing
    from repro.kernels import ops as kops
    from repro.launch.mesh import make_test_mesh
    from repro.serving.engine import make_serve_step

    rng = np.random.default_rng(4)
    b = 4
    store = _step_store(rng, [384] * b)
    cfg = _step_cfg(b)
    params = probing.init(jax.random.PRNGKey(0),
                          probing.ProbingConfig(dim=16, n_partitions=b))
    step = jax.jit(make_serve_step(cfg, make_test_mesh(), 8, sigma=-1.0, impl=impl,
                                   count_dedup=True))
    q = jnp.asarray(rng.standard_normal((8, 16)).astype(np.float32))
    dense = b * -(-384 // kops.l2_qbuf_tile(store["vectors"].shape, cfg.k))
    *_, blocks = step(params, store, q, jnp.ones((8,), bool))
    assert np.asarray(blocks).tolist() == [[dense, dense]]
    *_, blocks = step(params, store, q, jnp.zeros((8,), bool))
    assert np.asarray(blocks).tolist() == [[0 if impl == "interpret" else dense, dense]]
