"""90th percentile of the time a request waits in the engine's queue, from
``submit`` to admission into a slot (span ``engine.queued``), in ms. Only
requests admitted while the trace records count."""
from bench.engine_spans import queue_wait_p90_ms


def read(run):
    return queue_wait_p90_ms(run)
