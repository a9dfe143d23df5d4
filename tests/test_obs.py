"""Observability layer tests (ISSUE 7) — registry semantics, histogram
bucket math, span nesting on FakeClock, and the serving integration gates:

  * engine/front-end counters and distributions land in the registry with
    the right labels (and several front-ends sharing one registry stay
    isolated via their auto-generated ``frontend=`` label);
  * per-request stage breakdowns sum exactly to end-to-end latency under a
    shared virtual clock;
  * the regression that keeps tracing safe to leave on: tracing-on results
    are bit-identical to tracing-off across {f32, pq, residual_pq} ×
    {ref, interpret};
  * an enabled tracer's spans reach the profiler (one annotation per span,
    none from NOOP), ``engine.device`` splits into dispatch and wait, the
    front-end's head-of-line wait is exact on a virtual clock, and the
    dedup counter compiles under its own ``lira.telemetry`` scope.

All wall-clock-free: tracers run on FakeClock (or are compared only for
structure), so nothing here can flake on a loaded CI box.
"""
import dataclasses
import itertools
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import FrontendConfig, LiraSystemConfig
from repro.core import probing
from repro.launch.mesh import make_test_mesh
from repro.obs import (NOOP, MetricsRegistry, Tracer, default_registry,
                       parse_exposition)
from repro.obs.metrics import LATENCY_BUCKETS_MS, Histogram
from repro.serving import (FakeClock, LiraEngine, SearchRequest,
                           ServingFrontend)
from repro.serving.quantized import build_quantized_store

# ------------------------------------------------------------------ registry


def test_counter_inc_value_labels():
    reg = MetricsRegistry()
    c = reg.counter("hits", "help text")
    c.inc(tier="f32")
    c.inc(2, tier="pq")
    c.inc(tier="pq")
    assert c.value(tier="f32") == 1
    assert c.value(tier="pq") == 3
    assert c.value(tier="nope") == 0
    assert c.total() == 4
    assert c.total(tier="pq") == 3


def test_counter_rejects_decrease():
    c = MetricsRegistry().counter("c")
    with pytest.raises(ValueError, match="cannot decrease"):
        c.inc(-1)


def test_registry_get_or_create_and_kind_conflict():
    reg = MetricsRegistry()
    assert reg.counter("x") is reg.counter("x")
    with pytest.raises(TypeError, match="already registered as counter"):
        reg.gauge("x")
    reg.histogram("h")
    with pytest.raises(ValueError, match="different buckets"):
        reg.histogram("h", buckets=(1.0, 2.0))
    assert reg.get("x") is reg.counter("x")
    assert reg.get("absent") is None
    assert "x" in reg.names() and "h" in reg.names()


def test_gauge_last_write_wins():
    g = MetricsRegistry().gauge("q_cap")
    g.set(2.0)
    g.set(4.0)
    assert g.value() == 4.0


def test_default_registry_is_shared():
    assert default_registry() is default_registry()


# ----------------------------------------------------------------- histogram


def test_latency_buckets_log_spaced():
    """Fixed log-spaced edges: 4 per decade, constant ratio 10^0.25, spanning
    tens of microseconds to tens of seconds of milliseconds-denominated
    latency."""
    edges = np.asarray(LATENCY_BUCKETS_MS)
    ratios = edges[1:] / edges[:-1]
    np.testing.assert_allclose(ratios, 10 ** 0.25, rtol=1e-12)
    assert edges[0] == pytest.approx(10 ** -1.5)
    assert edges[-1] == pytest.approx(10 ** 4)


def test_histogram_bucket_assignment_le_semantics():
    h = Histogram("h", buckets=(1.0, 10.0, 100.0))
    for v in (0.5, 1.0, 5.0, 10.0, 99.0, 1000.0):
        h.observe(v)
    # le-semantics: a value equal to an edge lands in that edge's bucket
    np.testing.assert_array_equal(h.counts(), [2, 2, 1, 1])
    assert h.count() == 6
    assert h.sum() == pytest.approx(0.5 + 1.0 + 5.0 + 10.0 + 99.0 + 1000.0)


def test_histogram_rejects_unsorted_buckets():
    with pytest.raises(ValueError, match="strictly increasing"):
        Histogram("h", buckets=(2.0, 1.0))


def test_histogram_quantile_degenerate_is_exact():
    """All observations equal → min == max clamps the interpolation to the
    exact value, for any q (the FrontendStats p50==p99 contract)."""
    h = Histogram("h")
    for _ in range(10):
        h.observe(1.1)
    assert h.quantile(0.5) == 1.1
    assert h.quantile(0.99) == 1.1


def test_histogram_quantile_bounded_by_observations():
    h = Histogram("h")
    vals = np.linspace(0.2, 7.7, 40)
    h.observe_many(vals)
    for q in (0.0, 0.25, 0.5, 0.9, 1.0):
        est = h.quantile(q)
        assert vals.min() <= est <= vals.max()
    # interpolation is monotone and roughly tracks the true quantile
    assert h.quantile(0.5) == pytest.approx(np.quantile(vals, 0.5), rel=0.5)
    assert h.quantile(0.25) <= h.quantile(0.75)


def test_histogram_empty_quantile_and_bad_q():
    h = Histogram("h")
    assert h.quantile(0.5) == 0.0
    h.observe(1.0)
    with pytest.raises(ValueError, match="outside"):
        h.quantile(1.5)


def test_histogram_observe_many_matches_loop():
    h1, h2 = Histogram("a"), Histogram("b")
    vals = np.random.default_rng(0).lognormal(0, 2, 200)
    h1.observe_many(vals, tier="x")
    for v in vals:
        h2.observe(v, tier="x")
    np.testing.assert_array_equal(h1.counts(tier="x"), h2.counts(tier="x"))
    assert h1.sum(tier="x") == pytest.approx(h2.sum(tier="x"))


def test_render_parse_round_trip():
    reg = MetricsRegistry()
    reg.counter("srv_total", "served").inc(3, tier="f32", impl="ref")
    reg.gauge("depth").set(7)
    h = reg.histogram("lat_ms", buckets=(1.0, 10.0))
    h.observe_many([0.5, 5.0, 50.0], frontend="fe0")
    text = reg.render()
    parsed = parse_exposition(text)
    assert parsed['srv_total{impl="ref",tier="f32"}'] == 3
    assert parsed["depth"] == 7
    assert parsed['lat_ms_bucket{frontend="fe0",le="1"}'] == 1
    assert parsed['lat_ms_bucket{frontend="fe0",le="10"}'] == 2
    assert parsed['lat_ms_bucket{frontend="fe0",le="+Inf"}'] == 3
    assert parsed['lat_ms_count{frontend="fe0"}'] == 3
    assert parsed['lat_ms_sum{frontend="fe0"}'] == pytest.approx(55.5)


def test_parse_exposition_rejects_garbage():
    with pytest.raises(ValueError, match="unparseable"):
        parse_exposition("this is { not a metric")
    with pytest.raises(ValueError, match="non-numeric"):
        parse_exposition("name notafloat")


# -------------------------------------------------------------------- tracer


def test_span_nesting_and_durations_on_fake_clock():
    clock = FakeClock()
    tr = Tracer(clock=clock)
    with tr.span("outer", tier="f32") as outer:
        clock.advance(1e-3)
        with tr.span("inner") as inner:
            clock.advance(2e-3)
        clock.advance(0.5e-3)
    assert inner.parent_id == outer.span_id
    assert outer.parent_id is None
    assert inner.duration_ms == pytest.approx(2.0)
    assert outer.duration_ms == pytest.approx(3.5)
    assert outer.attrs == {"tier": "f32"}
    # children recorded before parents (finish order), both retained
    assert [s.name for s in tr.finished()] == ["inner", "outer"]
    assert tr.children(outer) == [inner]
    assert tr.finished("inner") == [inner]


def test_span_attrs_set_inside_block():
    tr = Tracer(clock=FakeClock())
    with tr.span("s") as sp:
        sp.set(rows=32)
    assert tr.finished("s")[0].attrs == {"rows": 32}


def test_span_open_duration_is_zero():
    tr = Tracer(clock=FakeClock())
    with tr.span("s") as sp:
        assert sp.duration_ms == 0.0


def test_tracer_ring_is_bounded():
    tr = Tracer(clock=FakeClock(), max_spans=5)
    for i in range(12):
        with tr.span(f"s{i}"):
            pass
    assert [s.name for s in tr.finished()] == [f"s{i}" for i in range(7, 12)]


def test_jsonl_export_and_sink(tmp_path):
    clock = FakeClock()
    sunk = []
    tr = Tracer(clock=clock, sink=sunk.append)
    with tr.span("a"):
        clock.advance(1e-3)
    assert sunk and sunk[0]["name"] == "a"
    path = tmp_path / "spans.jsonl"
    assert tr.export_jsonl(str(path)) == 1
    rec = json.loads(path.read_text().splitlines()[0])
    assert rec["name"] == "a"
    assert rec["duration_ms"] == pytest.approx(1.0)
    assert rec["parent_id"] is None


def test_jsonl_file_sink(tmp_path):
    path = tmp_path / "stream.jsonl"
    tr = Tracer(clock=FakeClock(), sink=str(path))
    with tr.span("x"):
        pass
    with tr.span("y"):
        pass
    tr.close()
    names = [json.loads(line)["name"] for line in path.read_text().splitlines()]
    assert names == ["x", "y"]


def test_noop_tracer_is_inert():
    assert NOOP.enabled is False
    with NOOP.span("anything", tier="f32") as sp:
        sp.set(ignored=1)
        assert sp.duration_ms == 0.0
    assert NOOP.finished() == []


class _AnnotationRecorder:
    """Stands in for ``jax.profiler.TraceAnnotation``: logs each open/close."""

    log: list = []

    def __init__(self, name, **kwargs):
        self.name = name
        self.kwargs = kwargs

    def __enter__(self):
        self.log.append(("open", self.name))
        return self

    def __exit__(self, *exc):
        self.log.append(("close", self.name))
        return False


@pytest.fixture
def annotations(monkeypatch):
    log = []
    monkeypatch.setattr(_AnnotationRecorder, "log", log)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _AnnotationRecorder)
    return log


def test_enabled_tracer_opens_one_profiler_annotation_per_span(annotations):
    clock = FakeClock()
    tr = Tracer(clock=clock)
    with tr.span("outer", tier="f32"):
        clock.advance(1e-3)
        with tr.span("inner"):
            clock.advance(2e-3)
    assert annotations == [("open", "outer"), ("open", "inner"),
                           ("close", "inner"), ("close", "outer")]
    # the FakeClock timing of the spans is unchanged
    assert [round(s.duration_ms, 9) for s in tr.finished()] == [2.0, 3.0]


def test_noop_tracer_opens_no_profiler_annotation(annotations):
    with NOOP.span("anything", tier="f32"):
        with NOOP.span("nested"):
            pass
    assert annotations == []


# --------------------------------------------------- serving integration


@pytest.fixture(scope="module")
def obs_engines():
    """Direct-store engines for all three tiers over one partition layout —
    the cheap fixture pattern from test_frontend.py, extended with PQ and
    residual-PQ code planes so the bit-identical gate covers every tier."""
    host = np.random.default_rng(11)
    b, cap, dim, k = 4, 48, 16, 5
    vecs = host.normal(0, 1, (b, cap, dim)).astype(np.float32)
    ids = np.arange(b * cap, dtype=np.int32).reshape(b, cap)
    cents = vecs.mean(1)
    params = probing.init(jax.random.PRNGKey(0),
                          probing.ProbingConfig(dim=dim, n_partitions=b))
    cfg = LiraSystemConfig(arch="t", dim=dim, n_partitions=b, capacity=cap,
                           k=k, nprobe_max=b, pq_m=4, pq_ks=16, rerank=2)
    base = {"centroids": jnp.asarray(cents), "vectors": jnp.asarray(vecs),
            "ids": jnp.asarray(ids)}
    qs = build_quantized_store(jax.random.PRNGKey(1), base["vectors"],
                               base["ids"], m=4, ks=16)
    qr = build_quantized_store(jax.random.PRNGKey(1), base["vectors"],
                               base["ids"], m=4, ks=16, residual=True,
                               centroids=base["centroids"])
    mesh = make_test_mesh()

    def eng(tier, store):
        return LiraEngine(cfg=dataclasses.replace(cfg, tier=tier),
                          params=params, store=store, mesh=mesh, sigma=-1.0)

    engines = {
        "f32": eng("f32", base),
        "pq": eng("pq", {**base, "codes": qs.codes, "codebooks": qs.codebooks}),
        "residual_pq": eng("residual_pq",
                           {**base, "codes": qr.codes,
                            "codebooks": qr.codebooks, "cterm": qr.cterm}),
    }
    q = host.normal(0, 1, (12, dim)).astype(np.float32)
    return engines, q


@pytest.mark.parametrize("tier", ["f32", "pq", "residual_pq"])
@pytest.mark.parametrize("impl", ["ref", "interpret"])
def test_tracing_is_bit_identical(obs_engines, tier, impl):
    """The regression that keeps tracing safe to leave on in production:
    attaching a tracer (and a registry) must not change a single bit of the
    answer on any tier × scan backend."""
    engines, q = obs_engines
    eng = engines[tier]
    req = SearchRequest(queries=q, impl=impl)
    eng.tracer, eng.metrics = None, None
    off = eng.search(req)
    eng.tracer, eng.metrics = Tracer(), MetricsRegistry()
    try:
        on = eng.search(req)
    finally:
        eng.tracer, eng.metrics = None, None
    np.testing.assert_array_equal(off.dists, on.dists)
    np.testing.assert_array_equal(off.ids, on.ids)
    np.testing.assert_array_equal(off.nprobe_eff, on.nprobe_eff)
    assert off.overflow == on.overflow
    assert off.stats.dedup_hits == on.stats.dedup_hits
    # and the traced call actually carried its breakdown
    assert off.stats.stages is None
    assert set(on.stats.stages) == {"prepare", "device", "post"}


def test_engine_metrics_and_stage_sum(obs_engines):
    engines, q = obs_engines
    eng = engines["f32"]
    reg = MetricsRegistry()
    eng.tracer, eng.metrics = Tracer(), reg
    try:
        res = eng.search(SearchRequest(queries=q))
        res2 = eng.search(SearchRequest(queries=q))
    finally:
        eng.tracer, eng.metrics = None, None
    lbl = {"tier": "f32", "impl": "ref"}
    assert reg.counter("lira_engine_searches_total").value(**lbl) == 2
    assert reg.counter("lira_engine_rows_total").value(**lbl) == 24
    # the serve step was warmed by other tests on the engine's own cache key,
    # but THIS registry only saw these two calls: hits + misses == 2
    hits = reg.counter("lira_engine_jit_cache_hits_total").value(**lbl)
    misses = reg.counter("lira_engine_jit_cache_misses_total").value(**lbl)
    assert hits + misses == 2
    assert reg.histogram("lira_engine_nprobe_eff").count(**lbl) == 24
    # σ=-1 probes everything: nprobe_eff == n_partitions for every query
    assert reg.histogram("lira_engine_nprobe_eff").sum(**lbl) == 24 * 4
    assert reg.counter("lira_engine_probes_total").value(**lbl) == 24 * 4
    assert eng.overflow_rate() == 0.0
    # stage breakdown sums to the traced end-to-end latency (host timers
    # around contiguous stages; the gap is span bookkeeping itself)
    for r in (res, res2):
        assert r.stats.latency_ms > 0
        assert sum(r.stats.stages.values()) <= r.stats.latency_ms
        assert sum(r.stats.stages.values()) >= 0.5 * r.stats.latency_ms


def test_engine_device_splits_into_dispatch_and_wait(obs_engines):
    engines, q = obs_engines
    eng = engines["f32"]
    tr = Tracer()
    eng.tracer = tr
    try:
        res = eng.search(SearchRequest(queries=q))
    finally:
        eng.tracer = None
    (root,) = tr.finished("engine.search")
    assert [s.name for s in tr.children(root)] == [
        "engine.prepare", "engine.device", "engine.post"]
    (dev,) = tr.finished("engine.device")
    assert [s.name for s in tr.children(dev)] == ["engine.dispatch",
                                                  "engine.wait"]
    assert set(res.stats.stages) == {"prepare", "device", "post"}


def _scoped_op_names(count_dedup: bool) -> list:
    """``op_name`` of every instruction of a tiny compiled serve step."""
    import re

    from repro.serving.engine import make_serve_step

    b, cap, dim = 4, 48, 16
    cfg = LiraSystemConfig(arch="t", dim=dim, n_partitions=b, capacity=cap,
                           k=5, nprobe_max=b)
    params = probing.init(jax.random.PRNGKey(0),
                          probing.ProbingConfig(dim=dim, n_partitions=b))
    store = {"centroids": jnp.zeros((b, dim)),
             "vectors": jnp.zeros((b, cap, dim)),
             "ids": jnp.zeros((b, cap), jnp.int32)}
    step = make_serve_step(cfg, make_test_mesh(), 8, count_dedup=count_dedup)
    text = jax.jit(step).lower(params, store, jnp.zeros((8, dim)),
                               jnp.ones((8,), bool)).compile().as_text()
    return re.findall(r'op_name="([^"]*)"', text)


def test_dedup_counter_runs_under_its_own_scope_beside_the_merge():
    """``_dup_count`` is telemetry, not merge work: its ops carry
    ``lira.telemetry`` and never ``lira.merge``, so a trace books the
    counter's device time apart from ``step.merge_ms``."""
    names = _scoped_op_names(count_dedup=True)
    tele = [n for n in names if "lira.telemetry" in n]
    assert any(n.endswith("/sort") for n in tele), tele
    assert not [n for n in tele if "lira.merge" in n]
    assert any("lira.merge" in n for n in names)
    # without the counter the scope is gone: it holds nothing else
    assert not [n for n in _scoped_op_names(count_dedup=False)
                if "lira.telemetry" in n]


def test_overflow_rate_counts_dropped_probes_once(obs_engines):
    """Bugfix regression: ``lira_engine_probes_total`` counts ATTEMPTED
    probes (nprobe_eff sums probe_ok before q_cap drops), so the rate is
    dropped/attempted — the old ``dropped + dispatched`` denominator counted
    every dropped probe twice and under-reported the rate."""
    engines, q = obs_engines
    src = engines["f32"]
    reg = MetricsRegistry()
    # q_cap sized far below the σ=-1 fan-out → forced overflow
    eng = LiraEngine(cfg=dataclasses.replace(src.cfg, q_cap_factor=0.25),
                     params=src.params, store=src.store, mesh=src.mesh,
                     sigma=-1.0, metrics=reg)
    res = eng.search(SearchRequest(queries=q))
    dropped = reg.counter("lira_engine_overflow_probes_total").total()
    attempted = reg.counter("lira_engine_probes_total").total()
    assert dropped == res.overflow > 0
    # σ=-1 probes every partition for every row — all attempts are counted,
    # including the ones q_cap later dropped
    assert attempted == len(q) * src.cfg.n_partitions
    assert eng.overflow_rate() == pytest.approx(dropped / attempted)
    # the buggy denominator under-reported exactly like this:
    assert eng.overflow_rate() > dropped / (dropped + attempted)


def test_q_cap_bump_is_observable(obs_engines):
    engines, _ = obs_engines
    src = engines["f32"]
    reg = MetricsRegistry()
    eng = LiraEngine(cfg=dataclasses.replace(src.cfg, auto_q_cap=True),
                     params=src.params, store=src.store, mesh=src.mesh,
                     sigma=-1.0, metrics=reg)
    factor0 = eng.cfg.q_cap_factor
    eng._maybe_bump_q_cap(5)
    assert reg.counter("lira_engine_q_cap_bumps_total").total() == 0
    eng._maybe_bump_q_cap(5)    # second consecutive overflow → bump
    assert reg.counter("lira_engine_q_cap_bumps_total").total() == 1
    assert reg.gauge("lira_engine_q_cap_factor").value() == 2 * factor0
    assert eng.cfg.q_cap_factor == 2 * factor0


# ------------------------------------------------------------ front-end obs


def _traced_frontend(eng, **cfg_kw):
    clock = FakeClock()
    reg = MetricsRegistry()
    tr = Tracer(clock=clock)   # spans on the VIRTUAL clock: exact durations
    defaults = dict(max_batch=8, max_wait_ms=2.0, max_queue=16)
    defaults.update(cfg_kw)
    fe = ServingFrontend(eng, FrontendConfig(**defaults), clock=clock,
                         tracer=tr, metrics=reg)
    return fe, clock, reg, tr


def test_frontend_stage_breakdown_sums_to_latency(obs_engines):
    """Under one shared virtual clock every real-time stage is 0ms wide and
    queue wait is the whole latency — the stage sum is EXACTLY e2e."""
    engines, q = obs_engines
    eng = engines["f32"]
    fe, clock, reg, tr = _traced_frontend(eng)
    eng.tracer = tr            # engine spans nest under frontend.batch
    try:
        pends = [fe.submit(SearchRequest(queries=q[i])) for i in range(2)]
        clock.advance(2.1e-3)
        fe.poll()
    finally:
        eng.tracer = None
    for p in pends:
        st = p.result().stats
        assert st.latency_ms == pytest.approx(2.1)
        assert st.stages["queue"] == pytest.approx(2.1)
        assert sum(st.stages.values()) == pytest.approx(st.latency_ms)
        assert set(st.stages) == {"queue", "assemble", "serve.prepare",
                                  "serve.device", "serve.post"}
    # span hierarchy: engine.search is a child of frontend.batch
    batch = tr.finished("frontend.batch")[0]
    search = tr.finished("engine.search")[0]
    assert search.parent_id == batch.span_id
    # aggregated per-stage histograms landed under this frontend's label
    hs = reg.histogram("lira_frontend_stage_ms")
    assert hs.count(frontend=fe.name, stage="serve.device") == 1
    assert hs.count(frontend=fe.name, stage="assemble") == 1
    assert hs.count(frontend=fe.name, stage="scatter") == 1


def test_frontend_counters_and_isolation(obs_engines):
    """Two front-ends on ONE registry stay separate via the frontend label."""
    engines, q = obs_engines
    eng = engines["f32"]
    reg = MetricsRegistry()
    clock = FakeClock()
    fe_a = ServingFrontend(eng, FrontendConfig(max_batch=4), clock=clock,
                           metrics=reg)
    fe_b = ServingFrontend(eng, FrontendConfig(max_batch=4), clock=clock,
                           metrics=reg)
    assert fe_a.name != fe_b.name
    for i in range(4):
        fe_a.submit(SearchRequest(queries=q[i]))
    fe_a.drain()
    fe_b.submit(SearchRequest(queries=q[0]))
    fe_b.drain()
    assert fe_a.stats().served == 4
    assert fe_b.stats().served == 1
    assert fe_a.stats().batches == 1
    c = reg.counter("lira_frontend_served_total")
    assert c.value(frontend=fe_a.name) == 4
    assert c.value(frontend=fe_b.name) == 1


def test_frontend_qps_needs_two_completions(obs_engines):
    """One completion has no span to divide rows by — qps must read 0.0, not
    rows / epsilon."""
    engines, q = obs_engines
    eng = engines["f32"]
    fe, clock, reg, _ = _traced_frontend(eng)
    fe.submit(SearchRequest(queries=q[0]))
    clock.advance(5e-3)
    fe.poll()
    st = fe.stats()
    assert st.served == 1
    assert st.qps == 0.0
    assert st.p50_ms == pytest.approx(5.0)  # degenerate histogram is exact
    # a second completion establishes a span: qps becomes finite
    fe.submit(SearchRequest(queries=q[1]))
    clock.advance(5e-3)
    fe.poll()
    st = fe.stats()
    assert st.served == 2
    assert st.qps == pytest.approx(2 / 10e-3)


def test_shed_reasons_are_labeled(obs_engines):
    engines, q = obs_engines
    eng = engines["f32"]
    fe, clock, reg, _ = _traced_frontend(eng, max_queue=2, max_wait_ms=50.0)
    clock.advance(1.0)
    # dead on arrival: deadline expired before the (backdated) submit
    doa = fe.submit(SearchRequest(queries=q[0], deadline_ms=1.0),
                    t_arrival=0.0)
    assert doa.result().stats.shed
    # fill the queue, then displace with priority and reject without
    fe.submit(SearchRequest(queries=q[1]))
    fe.submit(SearchRequest(queries=q[2]))
    fe.submit(SearchRequest(queries=q[3], priority=1))    # displaces a waiter
    fe.submit(SearchRequest(queries=q[4]))                # rejected newcomer
    c = reg.counter("lira_frontend_shed_total")
    assert c.value(frontend=fe.name, reason="doa") == 1
    assert c.value(frontend=fe.name, reason="displaced") == 1
    assert c.value(frontend=fe.name, reason="rejected") == 1
    assert fe.stats().shed == 3
    fe.drain()


def _record_observations(reg, name):
    """Every value observed into histogram ``name``, in order."""
    h = reg.histogram(name)
    seen = []
    orig = h.observe

    def observe(value, **labels):
        seen.append(value)
        orig(value, **labels)

    h.observe = observe
    return seen


def _slow_frontend(eng, service_s, **cfg_kw):
    """A front-end on a virtual clock whose every engine call takes
    ``service_s`` seconds of it."""
    clock = FakeClock()
    ticks = itertools.count(0.0, service_s)
    reg = MetricsRegistry()
    cfg = dict(max_batch=8, max_wait_ms=2.0, max_queue=64)
    cfg.update(cfg_kw)
    fe = ServingFrontend(eng, FrontendConfig(**cfg), clock=clock,
                         charge_service=True,
                         service_timer=lambda: next(ticks),
                         metrics=reg)
    return fe, clock, reg


def test_frontend_hol_is_the_wait_behind_a_batch_in_flight(obs_engines):
    """A request enqueued while a batch is in flight waits, head-of-line,
    for that batch's remaining time; one enqueued on an idle front-end
    waits for nothing but coalescing."""
    engines, q = obs_engines
    fe, clock, reg = _slow_frontend(engines["f32"], service_s=5.0)
    hol = _record_observations(reg, "lira_frontend_hol_ms")
    queue = _record_observations(reg, "lira_frontend_queue_ms")
    fe.submit(SearchRequest(queries=q[0]))          # t=0, idle front-end
    clock.advance(2.5e-3)
    fe.poll()                                       # batch A: [2.5 ms, 5.0025 s]
    t_done_a = clock()
    assert t_done_a == pytest.approx(5.0025)
    # B was due 2 s into A's batch: submitted late, stamped with its arrival
    fe.submit(SearchRequest(queries=q[1]), t_arrival=2.0)
    clock.advance(1e-3)
    fe.poll()
    assert hol == [pytest.approx(0.0), pytest.approx((t_done_a - 2.0) * 1e3)]
    assert queue[1] == pytest.approx((t_done_a + 1e-3 - 2.0) * 1e3)
    assert queue[0] == pytest.approx(2.5)
    assert all(h <= w for h, w in zip(hol, queue))
    h = reg.histogram("lira_frontend_hol_ms")
    assert h.count(frontend=fe.name) == 2


def test_frontend_hol_counts_earlier_batches_of_its_own_flush(obs_engines):
    """A group beyond ``max_batch`` rows flushes as several engine calls in a
    row: a later batch's requests wait behind the earlier ones, never longer
    than their whole queue wait."""
    engines, q = obs_engines
    fe, clock, reg = _slow_frontend(engines["f32"], service_s=3.0,
                                    max_batch=8, max_wait_ms=50.0)
    hol = _record_observations(reg, "lira_frontend_hol_ms")
    queue = _record_observations(reg, "lira_frontend_queue_ms")
    fe.submit(SearchRequest(queries=q[0:3]))         # t=0
    clock.advance(1e-3)
    fe.submit(SearchRequest(queries=q[3:6]))
    fe.submit(SearchRequest(queries=q[6:9]))         # 9 rows > 8: flush
    assert fe.stats().batches == 2                   # [3+3 rows], then [3]
    assert hol == [pytest.approx(0.0), pytest.approx(0.0),
                   pytest.approx(3000.0)]
    assert queue == [pytest.approx(1.0), pytest.approx(0.0),
                     pytest.approx(3000.0)]
    assert all(h <= w for h, w in zip(hol, queue))
