"""Device-idle time split by program span (``lirabench/span_gaps.py``), on
hand-made events and on the recorded v5e excerpt, and the readers of the
metrics built on it returning None where there is nothing to read."""
import gzip
import json
import pathlib

import pytest

from lirabench import harness, span_gaps

ROOT = pathlib.Path(__file__).resolve().parents[2]
DATA = pathlib.Path(__file__).parent / "data"
WINDOW = ["bench.window", 0, 1000, "python3"]
NEW_METRICS = ("frontend.hol_ms", "engine.idle_ms.batch", "engine.idle_ms.online",
               "frontend.idle_ms.online", "step.telemetry_ms.batch",
               "step.telemetry_ms.online")


def _ex(busy, host):
    """Device ops at ``busy`` ([start, end) pairs) and ``host`` events."""
    ops = [[f"fusion.{i}", s, e - s, "", 0] for i, (s, e) in enumerate(busy)]
    return {"devices": {"/device:TPU:0": ops}, "host": [WINDOW] + host}


def _ns(split):
    return {k: round(v * 1e9) for k, v in split.items()}


def test_gap_under_engine_wait_goes_to_engine():
    ex = _ex([(0, 100), (300, 1000)], [["engine.wait", 50, 300, "python3"]])
    assert _ns(span_gaps.split(ex)) == {"engine": 200, "frontend": 0, "caller": 0}


def test_gap_under_frontend_scatter_goes_to_frontend():
    ex = _ex([(0, 100), (300, 1000)], [["frontend.scatter", 100, 200, "python3"]])
    assert _ns(span_gaps.split(ex)) == {"engine": 0, "frontend": 200, "caller": 0}


def test_gap_under_the_callers_annotation_only_goes_to_caller():
    ex = _ex([(0, 100), (300, 1000)], [["bench.idle", 0, 1000, "python3"],
                                       ["$frontend.py:368 _serve_batch", 0, 1000, "python3"]])
    assert _ns(span_gaps.split(ex)) == {"engine": 0, "frontend": 0, "caller": 200}


def test_gap_straddling_two_spans_is_split_by_overlap():
    # idle [100, 400): frontend.batch over all of it, engine.post over
    # [100, 160), engine.prepare over [330, 360); the window's tail
    # [900, 1000) is under no span
    ex = _ex([(0, 100), (400, 900)],
             [["frontend.batch", 0, 900, "python3"],
              ["engine.post", 50, 110, "python3"],
              ["engine.prepare", 330, 30, "python3"]])
    assert _ns(span_gaps.split(ex)) == {"engine": 90, "frontend": 210, "caller": 100}


def test_spans_clip_to_the_window_and_nested_spans_count_once():
    ex = _ex([(500, 1000)], [["engine.search", -200, 400, "python3"],
                             ["engine.device", -100, 250, "python3"],
                             ["engine.wait", -50, 150, "python3"]])
    # idle [0, 500): engine.search covers [0, 200)
    assert _ns(span_gaps.split(ex)) == {"engine": 200, "frontend": 0, "caller": 300}


def test_count_only_counts_inside_the_window():
    ex = _ex([(0, 10)], [["engine.search", 5, 1, "t"], ["engine.search", 2000, 1, "t"]])
    assert span_gaps.count(ex, "engine.search") == 1
    assert span_gaps.count(ex, "frontend.batch") == 0


def test_no_window_or_no_device_op_is_an_error():
    with pytest.raises(ValueError):
        span_gaps.split({"devices": {}, "host": []})
    with pytest.raises(ValueError):
        span_gaps.split({"devices": {"/device:TPU:0": [["f", 5000, 10, "", 0]]},
                         "host": [WINDOW]})


def test_v5e_excerpt_has_no_program_span_so_the_caller_gets_every_gap():
    from lirabench import trace_reduce

    ex = json.loads((DATA / "v5e_excerpt.json").read_text())
    r = trace_reduce.reduce(ex)
    got = span_gaps.split(ex)
    assert got["engine"] == 0 and got["frontend"] == 0
    assert got["caller"] == pytest.approx(r["window_s"] - r["busy_s"], rel=1e-9)
    assert got["caller"] > 0


def _run(tmp_path, monkeypatch, ex=None, **kw):
    """A RunContext as the harness hands it to readers; ``ex``, when given,
    is written where the harness keeps the extracted trace lists."""
    monkeypatch.setattr(span_gaps, "TRACE_DIR", tmp_path)
    run = harness.RunContext(cell="sift1m-f32.batch", config={}, mix={"loop": "closed"},
                             seconds=1.0, **kw)
    if ex is not None:
        d = tmp_path / run.cell
        d.mkdir(parents=True, exist_ok=True)
        with gzip.open(d / "extracted.json.gz", "wt") as fh:
            json.dump(ex, fh)
    return run


@pytest.mark.parametrize("name", NEW_METRICS)
def test_reader_is_none_without_tracer_or_trace(tmp_path, monkeypatch, name):
    run = _run(tmp_path, monkeypatch)
    assert harness.reader(ROOT, name)(run) is None


@pytest.mark.parametrize("name", NEW_METRICS)
def test_reader_is_none_on_a_program_without_the_spans(tmp_path, monkeypatch, name):
    """What the readers see on a program that opens no profiler annotation
    and has no telemetry scope or head-of-line histogram: a traced run
    whose lists hold only the benchmark's own events."""
    from repro.obs.metrics import MetricsRegistry

    reg = MetricsRegistry()
    reg.histogram("lira_frontend_queue_ms").observe(5.0, frontend="fe0")
    ex = json.loads((DATA / "v5e_excerpt.json").read_text())
    run = _run(tmp_path, monkeypatch, ex, registry=reg,
               trace={"by_scope": {"lira.merge": 0.3}, "window_s": 2.9, "busy_s": 2.8},
               steps=[object()])
    assert harness.reader(ROOT, name)(run) is None


def test_idle_readers_divide_by_steps(tmp_path, monkeypatch):
    ex = _ex([(0, 100), (400, 900)],
             [["frontend.batch", 0, 900, "python3"],
              ["engine.search", 50, 110, "python3"],
              ["engine.search", 330, 30, "python3"]])
    run = _run(tmp_path, monkeypatch, ex, trace={"by_scope": {}}, steps=[object(), object()])
    assert harness.reader(ROOT, "engine.idle_ms.batch")(run) == pytest.approx(90e-6 / 2)
    assert harness.reader(ROOT, "frontend.idle_ms.online")(run) == pytest.approx(210e-6 / 2)


def test_hol_and_telemetry_readers_read_their_series(tmp_path, monkeypatch):
    from repro.obs.metrics import MetricsRegistry

    reg = MetricsRegistry()
    for v in (0.0, 3000.0):
        reg.histogram("lira_frontend_hol_ms").observe(v, frontend="fe0")
    run = _run(tmp_path, monkeypatch, registry=reg,
               trace={"by_scope": {"lira.telemetry": 0.312}}, steps=[object()] * 2)
    assert harness.reader(ROOT, "frontend.hol_ms")(run) == pytest.approx(1500.0)
    assert harness.reader(ROOT, "step.telemetry_ms.batch")(run) == pytest.approx(156.0)
