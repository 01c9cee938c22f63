"""Mean device time of one decode program (``jit_gspmd_step``), in ms. Reads the trace."""
from bench.serving import mean_exec_ms


def read(run):
    return mean_exec_ms(run, "decode")
