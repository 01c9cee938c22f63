"""The decode attention kernel's share of its roofline: the least time
its required bytes (K and V at each active row's real length, q and the
output) and operations take at the chip's peaks, over the kernel's time
inside the decode programs, in percent. Reads the trace: Pallas kernel
``flash_decode_pallas`` in ``jit_gspmd_step``."""
from bench.serving import attention_roofline


def read(run):
    return attention_roofline(run, "decode")
