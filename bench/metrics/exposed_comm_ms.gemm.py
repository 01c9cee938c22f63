"""Collective time per multiply that no compute overlaps, in ms: on each
device, the time in which a collective operation (``collective-permute*``,
``reduce-scatter*``, ``all-gather*``, ``all-reduce*``) runs and no other
operation does (``trace_reduce.uncovered_s``), over the multiplies
dispatched in the traced window; the mean over the devices. Reads the trace."""
from bench import trace_reduce
from bench.summa_trace import is_collective, multiplies


def read(run):
    n = multiplies(run)
    if not n:
        return None
    tr = run.trace
    compute = lambda o: not o.container and not is_collective(o)
    exposed = [trace_reduce.uncovered_s(tr, d, is_collective, compute) for d in tr.devices]
    return 1e3 * sum(exposed) / len(exposed) / n
