"""frontend.queue_ms (ms): mean wait from due time to batch launch, from the
front-end's ``lira_frontend_queue_ms`` histogram."""
from lirabench.series import hist_mean


def read(run):
    return hist_mean(run.registry, "lira_frontend_queue_ms")
