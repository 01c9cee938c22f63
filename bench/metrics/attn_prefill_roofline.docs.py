"""The prefill attention kernel's share of its roofline: the larger of
its required operations (causal, real tokens only) over the peak and its
required bytes (q, k, v, output) over the bandwidth, over the kernel's time
inside the prefill programs, in percent. Reads the trace: Pallas kernel
``flash_decode_pallas`` in ``jit__lambda``."""
from bench.serving import attention_roofline


def read(run):
    return attention_roofline(run, "prefill")
