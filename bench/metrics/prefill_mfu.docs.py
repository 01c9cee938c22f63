"""Required operations of the real prompt tokens of each prefill (no padding
rows or positions; causal attention; logits of each row's last token)
over the prefill program's device time at the chip's bf16 peak, in percent.
Reads the trace (``jit__lambda``) and the benchmark's record of each call."""
from bench.serving import step_mfu


def read(run):
    return step_mfu(run, "prefill")
