"""99th percentile of the gap between consecutive output tokens of a
request, over every gap of every request inside the window (host clock)."""
from bench.harness import percentile
from bench.serving import token_times


def read(run):
    gaps = [b - a for ts in token_times(run) for a, b in zip(ts, ts[1:])]
    return 1e3 * percentile(gaps, 99) if gaps else None
