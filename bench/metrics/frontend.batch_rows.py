"""frontend.batch_rows (rows/batch): rows coalesced per serve call, from the
front-end's ``lira_frontend_batch_rows`` histogram."""
from lirabench.series import hist_mean


def read(run):
    return hist_mean(run.registry, "lira_frontend_batch_rows")
