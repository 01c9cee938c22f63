"""The whole multiply's share of the grid's peak: 2 * ni * nj * nk
operations per multiply over (chips x bf16 peak x wall time per multiply),
the wall time per multiply being the traced window over the multiplies
dispatched in it, in percent. Reads the trace's ``bench.summa.dispatch``
spans."""
from bench import gemm_work
from bench.summa_trace import multiplies


def read(run):
    n = multiplies(run)
    if not n or run.trace.window_s <= 0:
        return None
    R, Cc = run.config["grid"]
    work = gemm_work.summa_flops(run.config) * n
    return 100.0 * work / (R * Cc * run.peaks["bf16_flops"] * run.trace.window_s)
