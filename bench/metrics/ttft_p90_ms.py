"""90th percentile of time to first token over every request due in the
window: from its scheduled arrival to its first output token on the host.
A request with no token by the window's close counts at its wait so far, so
a stall cannot hide (host clock)."""
from bench.harness import percentile


def read(run):
    waits = []
    for r in run.requests:
        first = r.times[0] if r.times and r.times[0] <= run.seconds else run.seconds
        waits.append(first - r.arrival)
    return 1e3 * percentile(waits, 90) if waits else None
