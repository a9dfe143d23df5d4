"""Helpers the metric readers share: series of the program's metrics
registry, read through its text exposition, and device time from the trace
reduction."""
from __future__ import annotations


def hist_mean(registry, name: str):
    """Sum over count of a histogram, over all its label sets; None when it
    recorded nothing."""
    from repro.obs.metrics import parse_exposition

    if registry is None or registry.get(name) is None:
        return None
    series = parse_exposition(registry.render())
    total = sum(v for k, v in series.items() if k.split("{")[0] == f"{name}_sum")
    count = sum(v for k, v in series.items() if k.split("{")[0] == f"{name}_count")
    return total / count if count else None


def scope_ms_per_step(run, scope: str):
    """Device milliseconds per serve step under one ``jax.named_scope``."""
    if run.trace is None or not run.steps or scope not in run.trace["by_scope"]:
        return None
    return run.trace["by_scope"][scope] * 1e3 / len(run.steps)


def kernel_roofline(run, kernel: str):
    """Least time of the work the window's steps needed from ``kernel`` over
    its device time, in percent."""
    from lirabench import work

    if run.trace is None or run.peaks is None:
        return None
    t = run.trace["by_kernel"].get(kernel)
    need = [w[kernel] for w in run.work if kernel in w]
    if not t or not need:
        return None
    return 100.0 * sum(work.least_time(w, run.peaks) for w in need) / t


def idle_percent(run):
    if run.trace is None or run.trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - run.trace["busy_s"] / run.trace["window_s"])


def mean_fanout(run):
    """Mean probes per query over the window (the program's ``nprobe_eff``)."""
    import numpy as np

    if not run.steps:
        return None
    return float(np.concatenate([s.nprobe for s in run.steps]).mean())
