"""Mean device-idle gap between consecutive step programs (decode
``jit_gspmd_step``, prefill ``jit__lambda``) while a request is resident,
in ms. Reads the trace; gaps in which the benchmark waited for arrivals
(span ``bench.idle``) are left out."""
from bench.serving import host_gap_ms


def read(run):
    return host_gap_ms(run)
