"""Share of the traced window in which no operation ran on a device:
1 - busy / window, busy the union of operation intervals, the mean over
the four devices, in percent."""
from bench.serving import device_idle


def read(run):
    return device_idle(run)
