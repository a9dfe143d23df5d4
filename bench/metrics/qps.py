"""qps (queries/s): queries answered over the closed loop's window, from
its start to the end of its last batch."""


def read(run):
    if run.mix["loop"] != "closed" or run.window_s <= 0:
        return None
    return run.attempted / run.window_s
