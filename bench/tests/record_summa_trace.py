"""Record the small four-device CPU trace that test_summa_metrics.py reads:

    JAX_PLATFORMS=cpu python bench/tests/record_summa_trace.py

The SUMMA system (``bench/systems/summa.py``) at (ni, nj, nk) =
(256, 320, 176) on a 2x2 grid of four CPU devices, set up and warmed up as
a run does it; then, inside a ``bench.window`` span, 4 multiplies, each
marked ``bench.summa.dispatch`` and ``bench.summa.wait``. Written to
``bench/tests/data/summa_trace.xplane.pb``.
"""
import glob
import json
import os
import shutil
import sys
import tempfile

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

import jax  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import harness, traffic  # noqa: E402

# the trace names source files without their directories
jax.config.update("jax_hlo_source_file_canonicalization_regex", ".*/")

OUT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", "summa_trace.xplane.pb")
SMALL = {"ni": 256, "nj": 320, "nk": 176}
MULTIPLIES = 4


def main():
    config = json.loads((harness.BENCH / "configs" / "summa-xl-f32.json").read_text())
    config.update(SMALL)
    mix = traffic.load("summa-loop")
    run = harness.Run("summa-xl-2x2", config, mix, 0, 1.0, {})
    system = harness.load_module(harness.BENCH / "systems" / "summa.py").System(
        config, mix, 0, jax.devices()[:4], run)
    system.setup()
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(MULTIPLIES):
            system.multiply()
    jax.profiler.stop_trace()
    shutil.copy(glob.glob(os.path.join(tmp, "plugins", "profile", "*", "*.xplane.pb"))[0], OUT)
    shutil.rmtree(tmp)


if __name__ == "__main__":
    main()
