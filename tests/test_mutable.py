"""Mutable-index tests (ISSUE 9) — streaming inserts/deletes, staleness-driven
re-partitioning, epoch-safe serving.

Covers the acceptance criteria end to end:
  * sustained churn: ≥20% of rows deleted + fresh rows inserted with periodic
    ``maybe_repartition``, recall@10 within ε=0.02 of a FRESH rebuild over the
    surviving logical set, at equal fixed fanout (σ=-1), across
    {f32, pq, residual_pq};
  * tombstone holes compose with batch-padding ``valid`` masking: deleted ids
    never surface (odd, non-bucket nq so padding rows are in play), and after
    ``compact()`` — the dense rebuild of the survivors — dists and ids are
    bit-identical, across tiers × {ref, interpret};
  * same-shape mutations are ZERO-recompile: the jit-cache hit counter keeps
    hitting after insert/delete, while epoch bumps stay observable
    (``lira_engine_epoch_bumps_total`` counter + ``lira_engine_epoch`` gauge,
    ``SearchStats.epoch``);
  * shape-changing mutations (insert-driven grow, shrinking compact) DO
    invalidate compiled serve steps, counted separately;
  * save/load round-trips a mutated store bit-identically (occupancy +
    staleness counters + epoch);
  * front-end epoch atomicity: mutations drain in-flight coalesced batches,
    so every batch is served wholly within one epoch;
  * host-side planning unit tests (serving/mutable.py).
"""
import jax.numpy as jnp
import numpy as np
import pytest

import jax

from repro.configs.base import FrontendConfig, LiraSystemConfig
from repro.core import ground_truth as gt
from repro.core.metrics import recall_at_k
from repro.core import probing
from repro.data import make_vector_dataset
from repro.launch.mesh import make_test_mesh
from repro.obs.metrics import MetricsRegistry
from repro.serving import BuildConfig, FakeClock, LiraEngine, SearchRequest, tiers
from repro.serving import mutable


# ------------------------------------------------------- host-side planning

def test_plan_insert_prefers_nearest_free_slot():
    occ = np.array([[True, True], [True, False], [False, False]])
    # row 0 is nearest partition 0 (full) -> spills to its 2nd choice (1);
    # row 1 is nearest partition 1 and fits its remaining slot... unless row 0
    # claimed it first — rows are placed in input order.
    dist = np.array([[0.0, 1.0, 2.0],
                     [5.0, 0.0, 1.0]])
    plan = mutable.plan_insert(occ, dist)
    assert plan.parts.tolist() == [1, 1] or plan.parts.tolist() == [1, 2]
    assert plan.ok.all()
    # row 0 landed off its argmin partition -> misassigned; wherever row 1
    # landed, partition 1's single free slot went to exactly one of them
    assert bool(plan.misassigned[0])
    p, s = plan.parts, plan.slots
    assert len({(int(a), int(b)) for a, b in zip(p, s)}) == 2  # distinct slots
    assert not occ[1, 1]  # input occupancy not modified


def test_plan_insert_window_limits_spill_and_reports_failures():
    occ = np.array([[True], [True], [False]])
    dist = np.array([[0.0, 1.0, 2.0]])
    # window=2: only partitions {0, 1} are tried, both full -> no slot
    plan = mutable.plan_insert(occ, dist, window=2)
    assert not plan.ok.any()
    assert plan.parts.tolist() == [-1]
    # default window reaches partition 2
    plan = mutable.plan_insert(occ, dist)
    assert plan.ok.all() and plan.parts.tolist() == [2]
    assert bool(plan.misassigned[0])


def test_grow_store_pads_sentinels_and_refuses_shrink():
    planes = {
        "vectors": np.zeros((2, 3, 4), np.float32),
        "ids": np.arange(6, dtype=np.int32).reshape(2, 3),
        "occupancy": np.ones((2, 3), bool),
        "codes": np.ones((2, 3, 2), np.uint8),
    }
    out = mutable.grow_store(planes, 5)
    assert out["vectors"].shape == (2, 5, 4)
    assert (out["vectors"][:, 3:] == 1e6).all()          # top-k-safe sentinel
    assert (out["ids"][:, 3:] == -1).all()               # scan invalid marker
    assert not out["occupancy"][:, 3:].any()
    assert (out["codes"][:, 3:] == 0).all()              # unnamed planes zero
    assert (out["ids"][:, :3] == planes["ids"]).all()
    with pytest.raises(ValueError, match="cannot shrink"):
        mutable.grow_store(planes, 2)


def test_compact_store_packs_live_rows_and_resets_dead_tail():
    occ = np.array([[False, True, False, True],
                    [True, False, False, False]])
    ids = np.array([[7, 1, 9, 2],
                    [3, -1, -1, -1]], np.int32)
    vecs = np.arange(8, dtype=np.float32).reshape(2, 4, 1)
    planes, new_cap = mutable.compact_store(
        {"ids": ids, "vectors": vecs, "occupancy": occ}, occ)
    assert new_cap == 2                                   # max live count
    assert planes["ids"].tolist() == [[1, 2], [3, -1]]    # stable order, healed
    assert planes["occupancy"].tolist() == [[True, True], [True, False]]
    assert planes["vectors"][0, :, 0].tolist() == [1.0, 3.0]
    assert planes["vectors"][1, 1, 0] == 1e6              # dead tail sentinel
    # min_capacity floors the shrink (the scan's top-k needs k candidates)
    _, cap_floored = mutable.compact_store({"occupancy": occ}, occ,
                                           min_capacity=7)
    assert cap_floored == 7


def test_layout_rows_is_contiguous_and_stable():
    assign = np.array([2, 0, 2, 2, 0])
    slots, counts = mutable.layout_rows(assign, 4)
    assert counts.tolist() == [2, 0, 3, 0]
    assert slots.tolist() == [0, 0, 1, 2, 1]              # input order kept


# ----------------------------------------------------------- tiny raw engine

def _raw_engine(b=4, cap=24, dim=16, live_per_part=18, seed=3, metrics=None):
    """Direct-store f32 engine (no build pass) with genuinely free tail
    slots, so same-shape inserts have somewhere to land."""
    host = np.random.default_rng(seed)
    vecs = np.full((b, cap, dim), 1e6, np.float32)
    ids = np.full((b, cap), -1, np.int32)
    # spread centroids out so row->partition argmin is unambiguous
    cents = host.normal(0, 1, (b, dim)).astype(np.float32) * 8.0
    for p in range(b):
        vecs[p, :live_per_part] = cents[p] + host.normal(
            0, 0.2, (live_per_part, dim)).astype(np.float32)
        ids[p, :live_per_part] = np.arange(live_per_part) + p * live_per_part
    store = {"centroids": jnp.asarray(cents), "vectors": jnp.asarray(vecs),
             "ids": jnp.asarray(ids), "occupancy": jnp.asarray(ids >= 0)}
    params = probing.init(jax.random.PRNGKey(0),
                          probing.ProbingConfig(dim=dim, n_partitions=b))
    cfg = LiraSystemConfig(arch="t", dim=dim, n_partitions=b, capacity=cap,
                           k=5, nprobe_max=b)
    eng = LiraEngine(cfg=cfg, params=params, store=store,
                     mesh=make_test_mesh(), sigma=-1.0, metrics=metrics)
    return eng, cents, host


# ------------------------------------------------- epochs & the jit cache

def test_same_shape_mutations_zero_recompiles():
    """The acceptance gate: insert/delete that keep the store shape MUST keep
    hitting the compiled serve step — epoch bumps are bookkeeping, not
    recompiles — and every bump is observable in the metrics registry."""
    reg = MetricsRegistry()
    eng, cents, host = _raw_engine(metrics=reg)
    q = cents[:2] + 0.01
    r0 = eng.search(q)
    assert r0.stats.epoch == 0 and not r0.stats.cache_hit
    assert reg.counter("lira_engine_jit_cache_misses_total").total() == 1

    assert eng.delete([0, 1, 19]) == 3                    # same-shape
    x_new = cents[1] + host.normal(0, 0.2, (4, 16)).astype(np.float32)
    assert eng.insert(x_new, np.arange(4) + 500) == 4     # fits free slots
    assert reg.counter("lira_engine_capacity_grows_total").total() == 0

    r1 = eng.search(q)
    assert r1.stats.cache_hit and r1.stats.epoch == 2
    assert reg.counter("lira_engine_jit_cache_hits_total").total() == 1
    assert reg.counter("lira_engine_jit_cache_misses_total").total() == 1
    assert reg.counter("lira_engine_epoch_bumps_total").total() == 2
    assert reg.counter("lira_engine_shape_epoch_bumps_total").total() == 0
    assert reg.gauge("lira_engine_epoch").value() == float(eng.epoch) == 2.0
    # store gauges reflect the tombstones delete left behind
    assert reg.gauge("lira_engine_tombstone_slots").value() > 0
    assert reg.gauge("lira_engine_live_slots").value() == 4 * 18 - 3 + 4
    # deleted ids are gone, inserted ids findable
    assert not np.isin([0, 1, 19], r1.ids).any()
    hit = eng.search(x_new[:2])
    assert 500 in hit.ids[0]


def test_insert_grow_is_a_shape_epoch_and_invalidates_compiled_steps():
    reg = MetricsRegistry()
    eng, cents, host = _raw_engine(live_per_part=24, metrics=reg)  # full
    q = cents[:2] + 0.01
    eng.search(q)
    old_cap = eng.cfg.capacity
    x_new = cents[0] + host.normal(0, 0.2, (3, 16)).astype(np.float32)
    eng.insert(x_new, [900, 901, 902])
    assert eng.cfg.capacity > old_cap
    assert reg.counter("lira_engine_capacity_grows_total").total() == 1
    assert reg.counter("lira_engine_shape_epoch_bumps_total").total() == 1
    r = eng.search(q)
    assert not r.stats.cache_hit                          # step invalidated
    assert 900 in eng.search(x_new[:2]).ids[0]


def test_delete_unknown_ids_is_a_noop_without_epoch_bump():
    eng, _, _ = _raw_engine(metrics=MetricsRegistry())
    assert eng.delete([99999, 88888]) == 0
    assert eng.epoch == 0


def test_compact_reclaims_tombstones_and_floors_at_k():
    reg = MetricsRegistry()
    eng, cents, _ = _raw_engine(metrics=reg)
    eng.delete(np.arange(10))                             # partition 0 thins
    old_cap = eng.cfg.capacity
    reclaimed = eng.compact()
    assert reclaimed == (old_cap - eng.cfg.capacity) * eng.cfg.n_partitions
    assert eng.cfg.capacity == 18                          # max live count
    assert reg.counter("lira_engine_compactions_total").total() == 1
    occ = np.asarray(eng.store["occupancy"])
    ids = np.asarray(eng.store["ids"])
    assert not (~occ & (ids >= 0)).any()                  # tombstones healed
    # shrink floors at cfg.k: deleting everything cannot starve the top-k
    eng.delete(np.asarray(ids[occ]))
    eng.compact()
    assert eng.cfg.capacity == eng.cfg.k


def test_staleness_gates_repartition_and_resets():
    reg = MetricsRegistry()
    eng, cents, host = _raw_engine(metrics=reg)
    assert eng.staleness() == 0.0
    assert not eng.maybe_repartition()                    # below threshold
    # plant drift: rows that belong to partition 0 but sit in partition 1
    # (their argmin slot space is full), plus tombstones
    eng.delete(np.arange(30))
    assert eng.staleness() >= eng.cfg.repartition_threshold
    assert eng.maybe_repartition()
    assert eng.staleness() == 0.0                         # drift repaired
    assert reg.counter("lira_engine_repartitions_total").total() == 1
    h = reg.histogram("lira_engine_partition_staleness")
    assert h.count() >= eng.cfg.n_partitions              # observed per check
    # after the pass every live row sits in its argmin partition
    occ = np.asarray(eng.store["occupancy"])
    vecs = np.asarray(eng.store["vectors"], np.float32)
    pb, ps = np.nonzero(occ)
    x = vecs[pb, ps]
    d2 = ((x * x).sum(1)[:, None] - 2.0 * x @ cents.T
          + (cents * cents).sum(1)[None, :])
    assert (d2.argmin(1) == pb).all()


def test_misassigned_inserts_count_toward_staleness():
    eng, cents, host = _raw_engine(live_per_part=24)      # every slot full...
    eng.delete(np.asarray([24 * 1 + 0]))                  # ...except one in p1
    x = cents[0] + host.normal(0, 0.1, (1, 16)).astype(np.float32)
    eng.insert(x, [777])                                  # argmin p0 is full
    assert int(eng._staleness_counters().sum()) == 1
    # the row is live and findable even though it spilled off its partition
    assert 777 in eng.search(np.concatenate([x, x])).ids[0]


# ------------------------------------------------------------ churn gate

CHURN_TIERS = ["f32", "pq", "residual_pq"]


def _build(x, tier, **kw):
    cfg = dict(n_partitions=8, k=10, eta=0.03, train_frac=0.4, epochs=2,
               nprobe_max=8, pq_m=4, pq_ks=32, tier=tier)
    cfg.update(kw)
    return LiraEngine.build(make_test_mesh(), x, BuildConfig(**cfg))


def test_built_capacity_stays_lane_aligned_through_mutations():
    """The build keeps capacity whole 128-lane tiles, so the scan kernels
    stream the store in place; compaction, growth and repartition keep it
    there (an unaligned capacity pads a copy of the store every call)."""
    from repro.kernels import ops as kops

    ds = make_vector_dataset(n=800, n_queries=4, dim=16, n_modes=8, seed=43)
    eng = _build(ds.base, "f32", epochs=1, train_frac=0.5)
    align = kops.SLOT_ALIGN
    live = np.asarray(eng.store["occupancy"]).sum(1)
    assert eng.cfg.capacity % align == 0 and eng.cfg.capacity - align < live.max()
    eng.delete(np.arange(0, len(ds.base), 2))
    eng.compact()
    assert eng.cfg.capacity % align == 0
    cap = eng.cfg.capacity
    n_new = cap * eng.cfg.n_partitions + 1         # more rows than the store holds
    c0 = np.asarray(eng.store["centroids"])[0]
    x_new = c0 + np.random.default_rng(0).normal(0, 0.01, (n_new, 16)).astype(np.float32)
    eng.insert(x_new, np.arange(n_new) + 10_000)
    assert eng.cfg.capacity > cap and eng.cfg.capacity % align == 0
    eng.maybe_repartition(force=True)
    assert eng.cfg.capacity % align == 0


def test_compact_store_rounds_capacity_up_to_align():
    occ = np.array([[True, False, True], [True, False, False]])
    _, cap = mutable.compact_store({"occupancy": occ}, occ, align=8)
    assert cap == 8
    assert mutable.align_up(17, 8) == 24 and mutable.align_up(16, 8) == 16


@pytest.mark.parametrize("tier", CHURN_TIERS)
def test_sustained_churn_recall_matches_fresh_rebuild(tier):
    """≥20% of the base churned (deletes + inserts) with periodic
    ``maybe_repartition``: recall@10 must stay within ε=0.02 of an index
    freshly rebuilt over the surviving logical set, at equal fixed fanout
    (σ=-1 probes all partitions on both sides)."""
    ds = make_vector_dataset(n=2000, n_queries=32, dim=16, n_modes=8, seed=17)
    host = np.random.default_rng(23)
    eng = _build(ds.base, tier)

    n = len(ds.base)
    doomed = host.choice(n, 300, replace=False)
    new_x = ds.base[host.choice(n, 250, replace=False)] + host.normal(
        0, 0.05, (250, ds.base.shape[1])).astype(np.float32)
    new_ids = np.arange(250, dtype=np.int32) + 10_000
    assert (len(doomed) + len(new_x)) / n >= 0.20         # the churn floor

    # interleave deletes / inserts / repartition checks like a live stream
    for i in range(5):
        eng.delete(doomed[i * 60:(i + 1) * 60])
        eng.insert(new_x[i * 50:(i + 1) * 50], new_ids[i * 50:(i + 1) * 50])
        eng.maybe_repartition()
    eng.maybe_repartition(force=True)                     # final settle

    keep = np.setdiff1d(np.arange(n), doomed)
    all_x = np.concatenate([ds.base[keep], new_x], 0)
    all_ids = np.concatenate([keep.astype(np.int32), new_ids], 0)
    fresh = _build(all_x, tier)

    _, gti = gt.exact_knn(ds.queries, all_x, 10)
    gt_ids = all_ids[gti]
    r_churn = eng.search(ds.queries, sigma=-1.0)
    r_fresh = fresh.search(ds.queries, sigma=-1.0)
    rec_churn = recall_at_k(np.asarray(r_churn.ids), gt_ids, 10)
    rec_fresh = recall_at_k(all_ids[np.asarray(r_fresh.ids)], gt_ids, 10)
    assert not np.isin(doomed, r_churn.ids).any()         # the dead stay dead
    assert rec_churn >= rec_fresh - 0.02, (rec_churn, rec_fresh)


# -------------------------------------- tombstones × padding valid masking

@pytest.mark.parametrize("impl", ["ref", "interpret"])
@pytest.mark.parametrize("tier", CHURN_TIERS)
def test_tombstone_holes_compose_with_padding_masking(tier, impl):
    """Property: after deletes, holes must never surface ids NOR perturb the
    survivors' distances — searching the tombstoned store is bit-identical to
    searching its dense ``compact()``-ed rebuild. nq=13 pads to bucket 16, so
    batch-padding rows are in play at the same time as the holes."""
    ds = make_vector_dataset(n=800, n_queries=13, dim=16, n_modes=8, seed=29)
    eng = _build(ds.base, tier, epochs=1, train_frac=0.5)
    host = np.random.default_rng(31)
    dead = host.choice(len(ds.base), 160, replace=False)
    eng.delete(dead)

    holey = eng.search(SearchRequest(queries=ds.queries, sigma=-1.0,
                                     impl=impl))
    assert not np.isin(dead, holey.ids).any()
    assert holey.ids.shape == (13, eng.cfg.k)
    live = np.setdiff1d(np.arange(len(ds.base)), dead)
    assert np.isin(holey.ids[holey.ids >= 0], live).all()

    eng.compact()                                          # dense survivors
    dense = eng.search(SearchRequest(queries=ds.queries, sigma=-1.0,
                                     impl=impl))
    np.testing.assert_array_equal(holey.ids, dense.ids)
    np.testing.assert_array_equal(np.asarray(holey.dists),
                                  np.asarray(dense.dists))


def test_residual_encode_rows_reproduces_build_encoding():
    """Re-encoding a stored vector at its own partition must reproduce the
    build-time codes and cterm bit-identically — otherwise repartition would
    silently re-rank unmoved rows."""
    ds = make_vector_dataset(n=600, n_queries=4, dim=16, n_modes=8, seed=41)
    eng = _build(ds.base, "residual_pq", epochs=1, train_frac=0.5, eta=0.0)
    tier = tiers.resolve("residual_pq")
    occ = np.asarray(eng.store["occupancy"])
    pb, ps = np.nonzero(occ)
    pick = np.random.default_rng(0).choice(len(pb), 50, replace=False)
    pb, ps = pb[pick], ps[pick]
    x = np.asarray(eng.store["vectors"])[pb, ps].astype(np.float32)
    rows = tier.encode_rows(eng.cfg, eng.store, x, pb)
    np.testing.assert_array_equal(
        np.asarray(rows["codes"]), np.asarray(eng.store["codes"])[pb, ps])
    np.testing.assert_array_equal(
        np.asarray(rows["cterm"]), np.asarray(eng.store["cterm"])[pb, ps])


# ------------------------------------------------------------- persistence

def test_save_load_roundtrips_mutated_store(tmp_path):
    eng, cents, host = _raw_engine()
    eng.delete([0, 5, 40])
    x_new = cents[2] + host.normal(0, 0.2, (3, 16)).astype(np.float32)
    eng.insert(x_new, [600, 601, 602])
    eng._staleness_counters()[1] = 4                      # nonzero drift state
    eng.save(tmp_path, step=3)

    back = LiraEngine.load(tmp_path, make_test_mesh())
    assert back.epoch == eng.epoch == 2
    np.testing.assert_array_equal(back._staleness_counters(),
                                  eng._staleness_counters())
    for name in eng.store:
        np.testing.assert_array_equal(
            np.asarray(back.store[name]), np.asarray(eng.store[name]),
            err_msg=name)
    q = cents + 0.01
    a, b = eng.search(q), back.search(q)
    np.testing.assert_array_equal(a.ids, b.ids)
    np.testing.assert_array_equal(np.asarray(a.dists), np.asarray(b.dists))
    assert b.stats.epoch == 2


# ------------------------------------------------------ front-end atomicity

def test_mutations_drain_frontend_and_swap_epochs_atomically():
    eng, cents, host = _raw_engine()
    clock = FakeClock()
    fe = eng.attach_frontend(FrontendConfig(max_batch=64, max_wait_ms=50.0),
                             clock=clock)
    q = (cents[:3] + 0.01).astype(np.float32)
    pending = [fe.submit(SearchRequest(queries=q[i:i + 1])) for i in range(3)]
    assert not any(p.done() for p in pending)             # still coalescing

    eng.delete([2, 3])                                    # quiesces first
    for p in pending:                                     # served pre-swap...
        res = p.result()
        assert res.stats.epoch == 0                       # ...wholly epoch 0
        assert res.stats.batch_size == 3                  # one coalesced batch
    after = fe.submit(SearchRequest(queries=q[:1])).result()
    assert after.stats.epoch == 1                         # bumped atomically
    assert eng.epoch == 1
