"""frontend.hol_ms (ms): mean head-of-line wait per request, the part of
its wait from due time to batch launch spent behind a batch the front-end
was already serving, from the front-end's ``lira_frontend_hol_ms``
histogram."""
from lirabench.series import hist_mean


def read(run):
    return hist_mean(run.registry, "lira_frontend_hol_ms")
