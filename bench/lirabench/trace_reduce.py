"""From a profiler trace to device time per scope, per kernel, and idle gaps.

Two steps, kept apart so the second can be checked on a recorded excerpt:

* ``extract(path, step_scopes)`` reads the ``.xplane.pb`` that
  ``jax.profiler`` writes and keeps plain lists: every device operation of
  each TPU (HLO instruction name, start, duration, the ``op_name`` path of
  its HLO metadata, line) and every host event with a duration (name,
  start, duration, thread). A TPU trace names each operation by its HLO
  text and carries no ``op_name``; the compiled programs' text supplies it
  (``hlo_op_names``: instruction name → ``op_name``), per serve step.
* ``reduce(extracted)`` works on those lists alone. The window is the host
  annotation ``bench.window``. A device is busy where at least one operation
  runs (the union of their intervals); an operation's self time is its
  duration less the operations nested inside it on the same line, so a
  ``while`` that contains its body is not counted twice. Scope is the first
  ``lira.*`` component of ``op_name`` (the serve step's ``jax.named_scope``
  blocks); a kernel is an operation whose HLO instruction is named after a
  Pallas kernel (``l2_topk_qbuf.1``). Each idle gap is put down to the most
  specific host event that covers most of it: what the host was doing while
  the chip waited.
"""
from __future__ import annotations

import re

WINDOW = "bench.window"
KERNELS = ("l2_topk_qbuf", "pq_adc_topk_qbuf", "dedup_topk")
_KERNEL_RE = re.compile(r"^(%s)(\.\d+)?$" % "|".join(KERNELS))
_SCOPE_RE = re.compile(r"(?:^|/)(lira\.[A-Za-z_]+)")
_HLO_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*?metadata=\{[^}]*op_name=\"([^\"]*)\"")
_INSTR_RE = re.compile(r"^%?([\w.\-]+) = ")


def hlo_op_names(hlo_text: str) -> dict:
    """HLO instruction name → ``op_name`` metadata, from a compiled
    program's ``as_text()``."""
    out = {}
    for line in hlo_text.splitlines():
        m = _HLO_RE.match(line)
        if m:
            out[m.group(1)] = m.group(2)
    return out


def _module_of(ops: list, modules: list) -> list:
    """For each op (sorted by start), the index of the module execution
    whose interval holds it, or -1."""
    out, j = [], 0
    for op in ops:
        while j < len(modules) and modules[j][1] + modules[j][2] <= op[1]:
            j += 1
        inside = j < len(modules) and modules[j][1] <= op[1]
        out.append(j if inside else -1)
    return out


def extract(path: str, step_scopes: list | None = None) -> dict:
    """Plain lists from one ``.xplane.pb`` (see the module docstring).

    ``step_scopes`` holds, for each serve step of the window in order, the
    map HLO instruction name → ``op_name`` of the program that ran it. The
    i-th execution of ``jit_serve_step`` on the device takes the i-th map;
    where the counts differ, every op takes the union of the maps."""
    from jax.profiler import ProfileData

    step_scopes = step_scopes or []
    union: dict = {}
    for m in step_scopes:
        union.update(m)
    pd = ProfileData.from_file(path)
    devices: dict[str, list] = {}
    host: list = []
    for plane in pd.planes:
        if re.match(r"^/device:TPU:\d+$", plane.name):
            modules = sorted([ev.name, int(ev.start_ns), int(ev.duration_ns)]
                             for line in plane.lines if line.name == "XLA Modules"
                             for ev in line.events if ev.name.startswith("jit_serve_step"))
            ops = []
            for li, line in enumerate(ln for ln in plane.lines if ln.name == "XLA Ops"):
                for ev in line.events:
                    m = _INSTR_RE.match(ev.name)     # "%fusion.7 = f32[...] fusion(...)"
                    ops.append([m.group(1) if m else ev.name, int(ev.start_ns),
                                int(ev.duration_ns), "", li])
            ops.sort(key=lambda op: op[1])
            per_step = len(modules) == len(step_scopes)
            for op, mi in zip(ops, _module_of(ops, modules)):
                scopes = step_scopes[mi] if per_step and mi >= 0 else union
                op[3] = scopes.get(op[0], "")
            devices[plane.name] = ops
        elif plane.name.startswith("/host:") and plane.name != "/host:metadata":
            for line in plane.lines:
                for ev in line.events:
                    if ev.duration_ns > 0:
                        host.append([ev.name, int(ev.start_ns), int(ev.duration_ns), line.name])
    return {"devices": devices, "host": host}


def _union(intervals) -> list:
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(s, e, w0, w1):
    return max(s, w0), min(e, w1)


def _self_times(ops) -> list:
    """Each op's duration less its direct children's, per line: ops nested
    inside another op of the same line are its children."""
    by_line: dict = {}
    for i, op in enumerate(ops):
        by_line.setdefault(op[4], []).append(i)
    self_ns = [float(op[2]) for op in ops]
    for idx in by_line.values():
        idx.sort(key=lambda i: (ops[i][1], -ops[i][2]))
        stack: list = []
        for i in idx:
            s, e = ops[i][1], ops[i][1] + ops[i][2]
            while stack and ops[stack[-1]][1] + ops[stack[-1]][2] <= s:
                stack.pop()
            if stack and e <= ops[stack[-1]][1] + ops[stack[-1]][2]:
                self_ns[stack[-1]] -= ops[i][2]
            stack.append(i)
    return self_ns


def scope_of(op_name: str) -> str:
    m = _SCOPE_RE.search(op_name)
    return m.group(1) if m else "other"


def kernel_of(name: str) -> str | None:
    m = _KERNEL_RE.match(name)
    return m.group(1) if m else None


def reduce(ex: dict, *, min_gap_ns: int = 0) -> dict:
    """Window, busy and idle time, device time per scope and per kernel, the
    top operations and the longest idle gaps, averaged over the devices that
    ran anything. Times in seconds."""
    win = [h for h in ex["host"] if h[0] == WINDOW]
    if not win:
        raise ValueError(f"trace holds no {WINDOW!r} annotation")
    w0, w1 = win[0][1], win[0][1] + win[0][2]
    others = [h for h in ex["host"] if h[0] != WINDOW]
    per_dev = []
    for dev, ops in sorted(ex["devices"].items()):
        ops = [op for op in ops if op[1] < w1 and op[1] + op[2] > w0]
        if not ops:
            continue
        self_ns = _self_times(ops)
        busy = _union(_clip(op[1], op[1] + op[2], w0, w1) for op in ops)
        by_scope: dict = {}
        by_kernel: dict = {}
        by_op: dict = {}
        for op, sn in zip(ops, self_ns):
            frac = (min(op[1] + op[2], w1) - max(op[1], w0)) / op[2] if op[2] else 0.0
            t = sn * frac
            sc = scope_of(op[3])
            by_scope[sc] = by_scope.get(sc, 0.0) + t
            kn = kernel_of(op[0])
            if kn:
                by_kernel[kn] = by_kernel.get(kn, 0.0) + t
            key = f"{sc}/{op[0]}"
            by_op[key] = by_op.get(key, 0.0) + t
        gaps = []
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e - s > min_gap_ns:
                gaps.append((s, e))
        per_dev.append({"busy": sum(e - s for s, e in busy), "by_scope": by_scope,
                        "by_kernel": by_kernel, "by_op": by_op, "gaps": gaps})
    if not per_dev:
        raise ValueError("no device operation inside the window")
    n = len(per_dev)

    def mean(key):
        out: dict = {}
        for d in per_dev:
            for k, v in d[key].items():
                out[k] = out.get(k, 0.0) + v / n
        return {k: v / 1e9 for k, v in out.items()}

    by_op = mean("by_op")
    gaps = sorted(((e - s, s, e) for d in per_dev for s, e in d["gaps"]), reverse=True)[:10]
    return {
        "window_s": (w1 - w0) / 1e9,
        "busy_s": sum(d["busy"] for d in per_dev) / n / 1e9,
        "devices": n,
        "by_scope": mean("by_scope"),
        "by_kernel": mean("by_kernel"),
        "top_ops": [[k, v] for k, v in sorted(by_op.items(), key=lambda kv: -kv[1])[:10]],
        "idle_gaps": [[_host_activity(others, s, e), dur / 1e9] for dur, s, e in gaps],
    }


def _host_activity(host, s, e) -> str:
    """The most specific host event that covers most of [s, e): of the events
    overlapping at least half of the gap (or, where none does, the most),
    the shortest."""
    ovl = [(min(e, hs + hd) - max(s, hs), hd, name) for name, hs, hd, _ in host]
    ovl = [o for o in ovl if o[0] > 0]
    if not ovl:
        return "no host event"
    need = min((e - s) / 2, max(o[0] for o in ovl))
    return min((o for o in ovl if o[0] >= need), key=lambda o: o[1])[2]
