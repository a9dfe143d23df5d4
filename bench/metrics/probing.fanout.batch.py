"""probing.fanout.batch (probes/query): mean partitions probed per query
(``SearchResult.nprobe_eff``) over the window."""
from lirabench.series import mean_fanout


def read(run):
    return mean_fanout(run)
