"""The SUMMA inner step's Pallas kernel (``gemm_panel_pallas``) against its
roofline: for each call, the larger of its operations at the bf16 peak and
its required bytes at the memory bandwidth (``bench/gemm_work.py``), over
the kernel's time in the traced window, on all devices, in percent. The
peak is bf16 because ``bench/peaks.py`` publishes no float32 figure for
the chip; a float32 product takes several bf16 passes of the MXU, so this
share reads lower than the kernel's use of a float32 peak would. Reads the
trace."""
from bench import flops, gemm_work, trace_reduce
from bench.summa_trace import is_kernel


def read(run):
    if run.trace is None:
        return None
    tr = run.trace
    lo, hi = tr.window
    calls = sum(1 for d in tr.devices for o in tr.ops[d] if is_kernel(o) and lo <= o.start and o.end <= hi)
    t = sum(trace_reduce.op_time(tr, d, is_kernel) for d in tr.devices)
    if not calls or t <= 0:
        return None
    need = calls * flops.roofline_time(*gemm_work.panel_gemm_cost(run.config), run.peaks)
    return 100.0 * need / t
