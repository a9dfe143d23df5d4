"""device.idle.batch (%): share of the traced window in which no operation
ran on the device."""
from lirabench.series import idle_percent


def read(run):
    return idle_percent(run)
