"""p95_ms (ms): 95th percentile of latency from due time to answer, over
every request due in the open loop's window; a request shed or never answered
counts as late as the run."""
import numpy as np


def read(run):
    if run.latencies_ms is None or not len(run.latencies_ms):
        return None
    return float(np.percentile(run.latencies_ms, 95))
