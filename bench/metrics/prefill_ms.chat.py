"""Mean device time of one prefill program (``jit__lambda``), in ms. Reads the trace."""
from bench.serving import mean_exec_ms


def read(run):
    return mean_exec_ms(run, "prefill")
