"""setup_s (s): data, index load (or build), compiles and warm-up."""


def read(run):
    return run.setup_s
