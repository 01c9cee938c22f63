"""Share of the traced window in which no operation ran on the device:
1 - busy / window, busy the union of operation intervals, in percent."""
from bench.serving import device_idle


def read(run):
    return device_idle(run)
