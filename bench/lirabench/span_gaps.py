"""Device-idle time inside the window, split by the program span the host
was in.

The program's ``Tracer`` opens a ``jax.profiler.TraceAnnotation`` around
each span, so ``engine.*`` and ``frontend.*`` spans sit on the trace's host
plane, on the device's clock. ``split`` works on the lists
``trace_reduce.extract`` returns: for each interval inside ``bench.window``
in which no operation runs on a device, the part under any ``engine.*`` span
goes to ``engine``, the part under a ``frontend.*`` span but under no
``engine.*`` span to ``frontend``, and the rest to ``caller`` (the
benchmark's loop, or no span at all). Seconds, averaged over the devices
that ran anything in the window, as ``trace_reduce.reduce`` does.

The harness writes those lists next to the trace
(``bench/.cache/trace/<cell>/extracted.json.gz``) before any metric is
read; ``extracted(run)`` reads them back from there.
"""
from __future__ import annotations

import functools
import gzip
import json
import pathlib

from lirabench import trace_reduce

PARTS = {"engine": "engine.", "frontend": "frontend."}
TRACE_DIR = pathlib.Path(__file__).resolve().parents[1] / ".cache" / "trace"


def _union(intervals) -> list:
    return trace_reduce._union((s, e) for s, e in intervals if e > s)


def _intersect(a: list, b: list) -> list:
    """Intersection of two sorted lists of disjoint intervals."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            out.append([s, e])
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def _length(intervals) -> int:
    return sum(e - s for s, e in intervals)


def split(ex: dict) -> dict:
    """Seconds of device idle time in the window under ``engine.*`` spans,
    under ``frontend.*`` spans alone, and under neither (``caller``)."""
    win = [h for h in ex["host"] if h[0] == trace_reduce.WINDOW]
    if not win:
        raise ValueError(f"trace holds no {trace_reduce.WINDOW!r} annotation")
    w0, w1 = win[0][1], win[0][1] + win[0][2]

    def spans(prefix):
        return _union([max(s, w0), min(s + d, w1)] for name, s, d, _ in ex["host"]
                      if name.startswith(prefix))

    eng, fe = spans(PARTS["engine"]), spans(PARTS["frontend"])
    per_dev = []
    for ops in ex["devices"].values():
        busy = _union([max(op[1], w0), min(op[1] + op[2], w1)] for op in ops
                      if op[1] < w1 and op[1] + op[2] > w0)
        if not busy:
            continue
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        idle = [[s, e] for s, e in zip(edges[0::2], edges[1::2]) if e > s]
        in_eng = _length(_intersect(idle, eng))
        in_fe = _intersect(idle, fe)
        fe_only = _length(in_fe) - _length(_intersect(in_fe, eng))
        per_dev.append((in_eng, fe_only, _length(idle) - in_eng - fe_only))
    if not per_dev:
        raise ValueError("no device operation inside the window")
    n = len(per_dev)
    return {part: sum(d[i] for d in per_dev) / n / 1e9
            for i, part in enumerate(("engine", "frontend", "caller"))}


def count(ex: dict, name: str) -> int:
    """Host events named ``name`` that start inside the window."""
    win = [h for h in ex["host"] if h[0] == trace_reduce.WINDOW]
    if not win:
        return 0
    w0, w1 = win[0][1], win[0][1] + win[0][2]
    return sum(1 for h in ex["host"] if h[0] == name and w0 <= h[1] < w1)


@functools.lru_cache(maxsize=2)
def _load(path: str, mtime_ns: int) -> dict:
    with gzip.open(path, "rt") as fh:
        return json.load(fh)


def extracted(run):
    """The run's extracted trace lists, or None where the run was not
    traced."""
    if run.trace is None:
        return None
    path = TRACE_DIR / run.cell / "extracted.json.gz"
    if not path.exists():
        return None
    return _load(str(path), path.stat().st_mtime_ns)


def idle_ms_per_step(run, part: str, span: str):
    """Device-idle milliseconds per serve step under ``part`` (a key of
    ``split``); None where the trace holds no ``span`` annotation, as a
    program without the spans leaves it."""
    ex = extracted(run)
    if ex is None or not run.steps or count(ex, span) == 0:
        return None
    return split(ex)[part] * 1e3 / len(run.steps)
