"""The trace reduction, on hand-made events and on an excerpt of a real
TPU v5e trace (``data/v5e_excerpt.json``: the ``extract`` lists of one
traced run of ``sift1m-f32.batch``, cut to its first steps)."""
import json
import pathlib

import pytest

from lirabench import trace_reduce as tr

DATA = pathlib.Path(__file__).parent / "data"


def _ex(ops, host):
    return {"devices": {"/device:TPU:0": ops}, "host": host}


def test_nested_ops_count_once_and_gaps_go_to_the_host_event():
    window = ["bench.window", 0, 1000, "python"]
    ops = [  # name, start, duration, op_name, line
        ["while.1", 100, 300, "jit(f)/lira.dispatch/while", 0],
        ["fusion.2", 150, 100, "jit(f)/lira.dispatch/add", 0],
        ["l2_topk_qbuf.1", 500, 200, "jit(f)/lira.scan/jit(l2_topk_qbuf)/pallas_call", 0],
        ["fusion.3", 900, 200, "jit(f)/lira.merge/sort", 0],     # runs past the window
    ]
    host = [window, ["bench.search", 50, 900, "python"], ["PjitFunction(f)", 420, 60, "python"]]
    r = tr.reduce(_ex(ops, host))
    assert r["window_s"] == pytest.approx(1000e-9)
    assert r["busy_s"] == pytest.approx((300 + 200 + 100) * 1e-9)
    assert r["by_scope"]["lira.dispatch"] == pytest.approx(300e-9)   # 200 self + 100 child
    assert r["by_scope"]["lira.scan"] == pytest.approx(200e-9)
    assert r["by_scope"]["lira.merge"] == pytest.approx(100e-9)      # clipped to the window
    assert r["by_kernel"] == {"l2_topk_qbuf": pytest.approx(200e-9)}
    # idle: [0, 100) under bench.search alone; [400, 500) mostly under the
    # shorter PjitFunction; [700, 900) under bench.search
    assert sorted((round(s * 1e9), name) for name, s in r["idle_gaps"]) == [
        (100, "PjitFunction(f)"), (100, "bench.search"), (200, "bench.search")]
    assert r["top_ops"][0] == ["lira.dispatch/while.1", pytest.approx(200e-9)]


def test_no_window_or_no_device_op_is_an_error():
    with pytest.raises(ValueError):
        tr.reduce(_ex([], []))
    with pytest.raises(ValueError):
        tr.reduce(_ex([["fusion.1", 5000, 10, "", 0]], [["bench.window", 0, 100, "python"]]))


def test_hlo_op_names_reads_instruction_metadata():
    text = ('  %l2_topk_qbuf.1 = (f32[8,128]{1,0}) custom-call(%a), custom_call_target='
            '"tpu_custom_call", metadata={op_name="jit(serve_step)/lira.scan/jit(l2_topk_qbuf)'
            '/pallas_call" stack_frame_id=19}, backend_config={}\n'
            '  ROOT %tuple.9 = (f32[8]) tuple(%x)\n')
    assert tr.hlo_op_names(text) == {
        "l2_topk_qbuf.1": "jit(serve_step)/lira.scan/jit(l2_topk_qbuf)/pallas_call"}
    assert tr.scope_of("jit(serve_step)/lira.scan/jit(l2_topk_qbuf)/pallas_call") == "lira.scan"
    assert tr.kernel_of("pq_adc_topk_qbuf.12") == "pq_adc_topk_qbuf"
    assert tr.kernel_of("fusion.12") is None


def _busy_by_sweep(ops, w0, w1):
    """Busy time counted another way: a sweep over start/end events."""
    events = sorted([(max(s, w0), 1) for _, s, d, _, _ in ops if s < w1 and s + d > w0]
                    + [(min(s + d, w1), -1) for _, s, d, _, _ in ops if s < w1 and s + d > w0])
    busy, depth, last = 0, 0, None
    for t, step in events:
        if depth > 0:
            busy += t - last
        depth += step
        last = t
    return busy


@pytest.mark.skipif(not (DATA / "v5e_excerpt.json").exists(), reason="no excerpt recorded")
def test_excerpt_of_a_v5e_trace():
    ex = json.loads((DATA / "v5e_excerpt.json").read_text())
    r = tr.reduce(ex)
    w = next(h for h in ex["host"] if h[0] == tr.WINDOW)
    (ops,) = ex["devices"].values()
    assert r["busy_s"] == pytest.approx(_busy_by_sweep(ops, w[1], w[1] + w[2]) / 1e9)
    assert 0 < r["busy_s"] <= r["window_s"]
    # every op of the serve step carries a lira.* scope, from the trace's
    # own metadata or the compiled program's
    assert {"lira.probing", "lira.dispatch", "lira.scan", "lira.merge"} <= set(r["by_scope"])
    assert set(r["by_kernel"]) >= {"l2_topk_qbuf", "dedup_topk"}
    # self times add up to no more than the busy time per line
    assert sum(r["by_scope"].values()) <= r["busy_s"] * (1 + 1e-9) * max(
        1, len({op[4] for op in ops}))
    assert r["by_kernel"]["l2_topk_qbuf"] <= r["by_scope"]["lira.scan"] * (1 + 1e-9)
    # the kernel contains no other op: its time is the sum of its events
    kernel = sum(op[2] for op in ops if op[0].startswith("l2_topk_qbuf")) / 1e9
    assert r["by_kernel"]["l2_topk_qbuf"] == pytest.approx(kernel)
