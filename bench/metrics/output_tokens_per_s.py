"""Output tokens produced in the window, over the window (host clock).

The engine step in progress at the close counts with the share of its
tokens that its time inside the window gives. A backlog of long prompts
runs in cycles of one admission prefill (most of a second, with the
decode of the resident rows behind it) and a few decode steps; counting
whole steps alone, the reading would move by a step's tokens wherever the
close falls, and a small change of speed would move the close across one.
"""
from bench.serving import token_times


def read(run):
    W = run.seconds
    n = float(sum(len(ts) for ts in token_times(run)))
    for start, end in run.steps:
        if start < W < end:
            made = sum(1 for r in run.requests for t in r.times if t == end)
            n += made * (W - start) / (end - start)
    return n / W if n else None
