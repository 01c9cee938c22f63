"""Record the small CPU engine trace that test_engine_span_metrics.py reads:

    JAX_PLATFORMS=cpu python bench/tests/record_engine_trace.py

The small configuration of ``tiny.py`` served by ``Engine`` on 2 slots,
its step programs warmed up first. Inside a ``bench.window`` span, three
requests are submitted and the engine is driven one ``bench.step`` at a
time: the first two are admitted together, the third waits in the queue
until the first has finished after 3 decode steps; 5 decode steps in all.
Written to ``bench/tests/data/engine_trace.xplane.pb``.
"""
import glob
import os
import shutil
import sys
import tempfile

import jax

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench.systems.lm_engine import arch_config, make_weights  # noqa: E402
from bench.tests import tiny  # noqa: E402
from repro.serve.engine import Engine, ServeConfig  # noqa: E402

# the trace names source files without their directories
jax.config.update("jax_hlo_source_file_canonicalization_regex", ".*/")

OUT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", "engine_trace.xplane.pb")
# (request id, prompt length, new tokens)
REQUESTS = [(10, 5, 3), (11, 9, 5), (12, 4, 2)]


def serve(eng, rid0=0):
    for rid, plen, new in REQUESTS:
        eng.submit(rid0 + rid, list(range(2, 2 + plen)), max_new_tokens=new)
    while eng.queue or eng.in_flight:
        with jax.profiler.TraceAnnotation("bench.step"):
            eng.run(max_steps=1)


def main():
    cfg = arch_config(tiny.config())
    eng = Engine(cfg, make_weights(cfg, 0), ServeConfig(max_len=64, batch_slots=2, eos_token=-1))
    serve(eng, rid0=100)  # compiles every program the traced pass runs
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp)
    with jax.profiler.TraceAnnotation("bench.window"):
        serve(eng)
    jax.profiler.stop_trace()
    shutil.copy(glob.glob(os.path.join(tmp, "plugins", "profile", "*", "*.xplane.pb"))[0], OUT)
    shutil.rmtree(tmp)


if __name__ == "__main__":
    main()
