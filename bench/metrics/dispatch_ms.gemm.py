"""Mean time from the call of the SUMMA program to its return on the host
(span ``bench.summa.dispatch``), in ms: the jitted call's checks of its
arguments and its launch on every chip, before ``block_until_ready``."""
from bench.engine_spans import mean_ms
from bench.summa_trace import DISPATCH


def read(run):
    return mean_ms(run, DISPATCH)
