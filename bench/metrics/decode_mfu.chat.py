"""Required operations of each decode step (active rows only, attention
over each row's real length, logits over the vocabulary) over the decode
program's device time at the chip's bf16 peak, in percent. Reads the trace
(``jit_gspmd_step``) and the benchmark's record of each call's rows."""
from bench.serving import step_mfu


def read(run):
    return step_mfu(run, "decode")
