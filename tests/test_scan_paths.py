"""Parity suite for the backend-dispatched partition-scan layer (ISSUE 4).

Three implementations must agree on every tier:
  * serving/scan.py impl="ref"        — portable jnp paths (the oracle),
  * serving/scan.py impl="interpret"  — the grid-batched Pallas kernels
                                        through the interpreter,
  * tests/_scan_oracle.scan_np        — pure-numpy twin.

Unit level: scan.run on synthetic dispatch buffers (random + empty slots +
-1 id padding). End-to-end: LiraEngine.search over random + clustered stores,
f32/quantized/residual × η ∈ {0, 0.03}, asserting bit-identical distances and
set-identical ids per query — plus regression tests for the two dispatch
bugfixes (padded queries masked out of dispatch, q_cap overflow reported).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _scan_oracle import scan_np

from repro.configs.base import LiraSystemConfig
from repro.core import probing
from repro.data import make_vector_dataset
from repro.launch.mesh import make_test_mesh
from repro.serving import scan
from repro.serving.engine import LiraEngine, make_serve_step
from repro.serving.quantized import build_quantized_store


def _assert_scan_matches_np(d_jax, i_jax, d_np, i_np, qbuf, q_row):
    """Occupied slots: same id set and same sorted distances (numpy runs in
    float64, so allclose; the jnp-vs-kernel comparison is exact elsewhere)."""
    occupied = np.asarray(qbuf) < q_row
    d_jax, i_jax = np.asarray(d_jax), np.asarray(i_jax)
    for b, s in zip(*np.nonzero(occupied)):
        fin = np.isfinite(d_np[b, s])
        assert set(i_jax[b, s][np.isfinite(d_jax[b, s])].tolist()) == \
            set(i_np[b, s][fin].tolist()), (b, s)
        np.testing.assert_allclose(d_jax[b, s][np.isfinite(d_jax[b, s])],
                                   d_np[b, s][fin], rtol=1e-4, atol=1e-4)


@pytest.fixture(scope="module")
def scan_inputs():
    """Synthetic dispatch state: random store with -1 id padding, random qbuf
    with empty (q_row) slots — the exact shapes the serve step hands scan.run."""
    host = np.random.default_rng(11)
    b_loc, cap, q_row, q_cap, dim = 6, 40, 12, 8, 16
    vecs = host.normal(0, 1, (b_loc, cap, dim)).astype(np.float32)
    ids = np.arange(b_loc * cap, dtype=np.int32).reshape(b_loc, cap)
    ids[:, -5:] = -1                      # store padding
    ids[2, :] = -1                        # one fully-empty partition
    qbuf = host.integers(0, q_row + 1, (b_loc, q_cap)).astype(np.int32)
    qbuf[:, -1] = q_row                   # guaranteed empty slots
    q = host.normal(0, 1, (q_row, dim)).astype(np.float32)
    q_pad = np.concatenate([q, np.full((1, dim), 1e9, np.float32)], 0)
    return qbuf, q_pad, vecs, ids


@pytest.mark.parametrize("impl", ["ref", "interpret", "pallas"])
def test_scan_f32_matches_numpy_twin(scan_inputs, impl):
    qbuf, q_pad, vecs, ids = scan_inputs
    k = 7

    def run():
        return scan.run(impl, jnp.asarray(qbuf), jnp.asarray(q_pad),
                        jnp.asarray(vecs), jnp.asarray(ids), k)

    if impl == "pallas" and jax.default_backend() != "tpu":
        # "pallas" means Mosaic, which needs a TPU: it raises here instead
        # of quietly interpreting
        with pytest.raises(ValueError, match="interpret mode"):
            run()
        return
    d, i, _ = run()
    d_np, i_np = scan_np(qbuf, q_pad, vecs, ids, k)
    _assert_scan_matches_np(d, i, d_np, i_np, qbuf, q_pad.shape[0] - 1)


def test_scan_f32_kernel_bit_identical_to_ref(scan_inputs):
    qbuf, q_pad, vecs, ids = scan_inputs
    args = (jnp.asarray(qbuf), jnp.asarray(q_pad), jnp.asarray(vecs),
            jnp.asarray(ids), 7)
    d_ref, i_ref, _ = scan.run("ref", *args)
    d_ker, i_ker, _ = scan.run("interpret", *args)
    occupied = qbuf < q_pad.shape[0] - 1
    np.testing.assert_array_equal(np.asarray(d_ref)[occupied], np.asarray(d_ker)[occupied])
    np.testing.assert_array_equal(np.asarray(i_ref)[occupied], np.asarray(i_ker)[occupied])


@pytest.mark.parametrize("residual", [False, True])
@pytest.mark.parametrize("impl", ["ref", "interpret"])
def test_scan_quantized_matches_numpy_twin(scan_inputs, impl, residual):
    qbuf, q_pad, vecs, ids = scan_inputs
    host = np.random.default_rng(12)
    b_loc, cap, _ = vecs.shape
    q_row = q_pad.shape[0] - 1
    m, ks, k, rk = 4, 16, 5, 12
    codes = host.integers(0, ks, (b_loc, cap, m)).astype(np.uint8)
    lut_pad = np.concatenate([host.normal(0, 1, (q_row, m, ks)) ** 2,
                              np.zeros((1, m, ks))], 0).astype(np.float32)
    cterm = off = None
    kwargs = {}
    if residual:
        cterm = host.normal(0, 1, (b_loc, cap)).astype(np.float32)
        off = np.concatenate([host.normal(0, 1, (b_loc, q_row)),
                              np.zeros((b_loc, 1))], 1).astype(np.float32)
        kwargs = {"cterm_loc": jnp.asarray(cterm), "off_loc": jnp.asarray(off)}
    d, i, _ = scan.run(impl, jnp.asarray(qbuf), jnp.asarray(q_pad),
                       jnp.asarray(vecs), jnp.asarray(ids), k,
                       lut_pad=jnp.asarray(lut_pad), codes_loc=jnp.asarray(codes),
                       rk=rk, **kwargs)
    d_np, i_np = scan_np(qbuf, q_pad, vecs, ids, k, lut_pad=lut_pad,
                         codes=codes, rk=rk, cterm=cterm, off=off)
    _assert_scan_matches_np(d, i, d_np, i_np, qbuf, q_row)


def test_l2_topk_k_larger_than_pool_consistent_across_impls():
    """cap < k degenerate pools: every impl (flat + batched) returns the same
    inf/-1-filled shape instead of ref crashing in top_k."""
    from repro.kernels import ops as kops

    host = np.random.default_rng(13)
    q = jnp.asarray(host.normal(0, 1, (3, 4, 8)).astype(np.float32))
    c = jnp.asarray(host.normal(0, 1, (3, 5, 8)).astype(np.float32))
    ids = jnp.asarray(np.tile(np.arange(5, dtype=np.int32), (3, 1)))
    k = 7
    outs = {impl: kops.l2_topk_batched(q, c, ids, k, impl=impl)
            for impl in ("ref", "interpret")}
    for impl, (d, i) in outs.items():
        assert d.shape == (3, 4, k) and i.shape == (3, 4, k), impl
        assert not np.isfinite(np.asarray(d)[..., 5:]).any(), impl
        assert (np.asarray(i)[..., 5:] == -1).all(), impl
    np.testing.assert_array_equal(np.asarray(outs["ref"][1]),
                                  np.asarray(outs["interpret"][1]))
    df, ifl = kops.l2_topk(q[0], c[0], ids[0], k, impl="ref")
    d2, i2 = kops.l2_topk(q[0], c[0], ids[0], k, impl="interpret")
    assert df.shape == d2.shape == (4, k)
    np.testing.assert_array_equal(np.asarray(ifl), np.asarray(i2))


def test_serve_cache_normalizes_impl_aliases(tiny_serving):
    """None, "auto" and the resolved backend name must share one compiled
    serve step (no redundant jit compiles during σ sweeps)."""
    store, params, q, vecs = tiny_serving
    b, cap, dim = vecs.shape
    cfg = LiraSystemConfig(arch="t", dim=dim, n_partitions=b, capacity=cap,
                           k=5, nprobe_max=b)
    eng = LiraEngine(cfg=cfg, params=params, store=store,
                     mesh=make_test_mesh(), sigma=-1.0)
    eng.search(q[:8])
    eng.search(q[:8], impl="auto")
    eng.search(q[:8], impl=scan.resolve_impl("auto"))
    assert len(eng._serve_cache) == 1
    eng.search(q[:8], impl="interpret")
    assert len(eng._serve_cache) == 2


def test_scan_rejects_unknown_impl(scan_inputs):
    qbuf, q_pad, vecs, ids = scan_inputs
    with pytest.raises(ValueError, match="unknown scan impl"):
        scan.run("cuda", jnp.asarray(qbuf), jnp.asarray(q_pad),
                 jnp.asarray(vecs), jnp.asarray(ids), 5)
    assert scan.resolve_impl("auto") in scan.IMPLS
    assert scan.resolve_impl(None) in scan.IMPLS


# --------------------------------------------------------------- end to end

N, NQ, DIM, B, ETA_ON = 1200, 16, 16, 8, 0.03


@pytest.fixture(scope="module", params=["random", "clustered"])
def tier_engines(request):
    """Per dataset: {η: (engine_nonres, engine_res)} — one build per η, the
    residual engine reuses the partitions/probing model with residual codes."""
    if request.param == "clustered":
        ds = make_vector_dataset("clustered", n=N, n_queries=NQ, dim=DIM,
                                 n_modes=B, center_scale=8.0, spread=0.5,
                                 boundary_frac=0.05, noise_frac=0.0, seed=21)
    else:
        host = np.random.default_rng(22)
        from repro.data.synthetic import VectorDataset

        ds = VectorDataset(
            base=host.normal(0, 1, (N, DIM)).astype(np.float32),
            queries=host.normal(0, 1, (NQ, DIM)).astype(np.float32), name="random")
    mesh = make_test_mesh()
    engines = {}
    for eta in (0.0, ETA_ON):
        eng = LiraEngine.build(mesh, ds.base, n_partitions=B, k=10, eta=eta,
                               train_frac=0.5, epochs=2, nprobe_max=B,
                               tier="pq", pq_m=4, pq_ks=32, rerank=4)
        qs = build_quantized_store(jax.random.PRNGKey(9), eng.store["vectors"],
                                   eng.store["ids"], m=4, ks=eng.cfg.pq_ks,
                                   residual=True, centroids=eng.store["centroids"])
        store_r = {**eng.store, "codes": qs.codes, "codebooks": qs.codebooks,
                   "cterm": qs.cterm}
        eng_r = LiraEngine(cfg=dataclasses.replace(eng.cfg, tier="residual_pq"),
                           params=eng.params, store=store_r, mesh=mesh)
        engines[eta] = (eng, eng_r)
    return engines, ds


@pytest.mark.parametrize("eta", [0.0, ETA_ON])
@pytest.mark.parametrize("tier", ["f32", "quantized", "residual"])
def test_engine_kernel_path_matches_ref(tier_engines, tier, eta):
    """The acceptance gate: impl="ref" and the interpret-mode kernel path must
    return bit-identical distances and set-identical ids on every tier."""
    engines, ds = tier_engines
    eng = engines[eta][1 if tier == "residual" else 0]
    tier_name = {"f32": "f32", "quantized": "pq", "residual": "residual_pq"}[tier]
    r_ref = eng.search(ds.queries, sigma=0.3, tier=tier_name, impl="ref")
    r_ker = eng.search(ds.queries, sigma=0.3, tier=tier_name, impl="interpret")
    d_ref, i_ref, np_ref, ov_ref = (r_ref.dists, r_ref.ids, r_ref.nprobe_eff,
                                    r_ref.overflow)
    d_ker, i_ker, np_ker, ov_ker = (r_ker.dists, r_ker.ids, r_ker.nprobe_eff,
                                    r_ker.overflow)
    np.testing.assert_array_equal(d_ref, d_ker)
    np.testing.assert_array_equal(np_ref, np_ker)
    assert ov_ref == ov_ker
    for r in range(NQ):
        fin = np.isfinite(d_ref[r])
        assert set(i_ref[r][fin].tolist()) == set(i_ker[r][fin].tolist()), r


# ------------------------------------------------- dispatch bugfix regressions

@pytest.fixture(scope="module")
def tiny_serving():
    host = np.random.default_rng(5)
    b, cap, dim = 4, 48, 16
    vecs = host.normal(0, 1, (b, cap, dim)).astype(np.float32)
    ids = np.arange(b * cap, dtype=np.int32).reshape(b, cap)
    store = {"centroids": jnp.asarray(vecs.mean(1)), "vectors": jnp.asarray(vecs),
             "ids": jnp.asarray(ids)}
    params = probing.init(jax.random.PRNGKey(0),
                          probing.ProbingConfig(dim=dim, n_partitions=b))
    q = host.normal(0, 1, (32, dim)).astype(np.float32)
    return store, params, q, vecs


def test_padded_batch_identical_to_unpadded(tiny_serving):
    """Bugfix regression: batch-padding rows are masked out of dispatch, so an
    nq=5 search (padded to the 8-bucket) returns exactly what an unpadded
    nq=5 serve step returns — pad rows neither probe partitions, steal q_cap
    slots, nor inflate the overflow count."""
    store, params, q, vecs = tiny_serving
    mesh = make_test_mesh()
    b, cap, dim = vecs.shape
    # tight q_cap: unmasked pad rows would occupy slots and report phantom
    # overflow (σ=-1 makes every row probe all partitions)
    cfg = LiraSystemConfig(arch="t", dim=dim, n_partitions=b, capacity=cap,
                           k=5, nprobe_max=b, q_cap_factor=1.0)
    eng = LiraEngine(cfg=cfg, params=params, store=store, mesh=mesh, sigma=-1.0)
    r_pad = eng.search(q[:5])
    d_pad, i_pad, np_pad, ovf_pad = (r_pad.dists, r_pad.ids, r_pad.nprobe_eff,
                                     r_pad.overflow)
    fn = make_serve_step(cfg, mesh, 5, sigma=-1.0)
    with mesh:
        d_un, i_un, np_un, ovf_un = jax.jit(fn)(params, store, jnp.asarray(q[:5]))
    np.testing.assert_array_equal(d_pad, np.asarray(d_un))
    np.testing.assert_array_equal(i_pad, np.asarray(i_un))
    np.testing.assert_array_equal(np_pad, np.asarray(np_un))
    assert ovf_pad == int(np.asarray(ovf_un).sum()) == 0
    # and the padded result matches the exact brute force (5 real rows only)
    exact = ((q[:5, None] - vecs.reshape(-1, dim)[None]) ** 2).sum(-1)
    want = np.argsort(exact, 1)[:, :5]
    for r in range(5):
        assert set(i_pad[r].tolist()) == set(want[r].tolist()), r


def test_qcap_overflow_is_reported_not_swallowed(tiny_serving):
    """Bugfix regression: a skewed workload (every query probes every
    partition, q_cap sized for the mean) must REPORT its dropped probes."""
    store, params, q, vecs = tiny_serving
    mesh = make_test_mesh()
    b, cap, dim = vecs.shape
    nq = len(q)
    cfg = LiraSystemConfig(arch="t", dim=dim, n_partitions=b, capacity=cap,
                           k=5, nprobe_max=b, q_cap_factor=0.25)
    eng = LiraEngine(cfg=cfg, params=params, store=store, mesh=mesh, sigma=-1.0)
    res = eng.search(q)
    d, i, npb, overflow = res.dists, res.ids, res.nprobe_eff, res.overflow
    # σ=-1: nq·b probes requested, q_cap = nq·b/b · 0.25 per partition kept
    q_cap = max(8, int(nq * b / b * 0.25))
    assert overflow == (nq - q_cap) * b > 0
    assert (npb == b).all()  # nprobe_eff still reports requested probes
    # the same workload with enough slack reports zero
    cfg_ok = dataclasses.replace(cfg, q_cap_factor=float(nq))
    eng_ok = LiraEngine(cfg=cfg_ok, params=params, store=store, mesh=mesh,
                        sigma=-1.0)
    overflow_ok = eng_ok.search(q).overflow
    assert overflow_ok == 0
