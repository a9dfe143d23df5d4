"""step.telemetry_ms.batch (ms/step): device time under ``lira.telemetry``
(the serve step's ``dedup_hits`` counter) per serve step, from the trace."""
from lirabench.series import scope_ms_per_step


def read(run):
    return scope_ms_per_step(run, "lira.telemetry")
