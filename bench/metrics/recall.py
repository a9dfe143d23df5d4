"""recall (fraction): recall@k of every query answered in the window,
against the exact k nearest neighbours at HIGHEST precision."""


def read(run):
    return run.recall if run.attempted else None
