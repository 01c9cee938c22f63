"""Shared test helpers.

NOTE: XLA_FLAGS / device-count overrides are deliberately NOT set here —
single-process tests must see 1 CPU device.  Multi-device tests spawn
subprocesses with their own XLA_FLAGS (see ``run_distributed``).

Speed: the ``distributed`` fixture is session-scoped and routes every
subprocess through one shared persistent XLA compilation cache, so repeated
8-device programs (scatter/gather graphs, train steps) compile once per
session instead of once per test.  ``session_mesh`` memoizes in-process Mesh
construction the same way.
"""
import functools
import os
import subprocess
import sys

import pytest

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, SRC)


def run_distributed(code: str, *, devices: int = 8, timeout: int = 480, cache_dir: str | None = None) -> str:
    """Run ``code`` in a fresh python with N fake CPU devices; returns stdout.

    The subprocess prefix sets XLA_FLAGS before importing jax, mirroring
    launch/dryrun.py."""
    prefix = (
        "import os\n"
        f"os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count={devices}'\n"
        "import sys\n"
        f"sys.path.insert(0, {SRC!r})\n"
    )
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    if cache_dir is not None:
        env.setdefault("JAX_COMPILATION_CACHE_DIR", cache_dir)
        env.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "1")
    proc = subprocess.run(
        [sys.executable, "-c", prefix + code],
        capture_output=True,
        text=True,
        timeout=timeout,
        env=env,
    )
    if proc.returncode != 0:
        raise AssertionError(
            f"distributed subprocess failed (rc={proc.returncode}):\n"
            f"--- stdout ---\n{proc.stdout}\n--- stderr ---\n{proc.stderr[-4000:]}"
        )
    return proc.stdout


@pytest.fixture(scope="session")
def compile_cache_dir(tmp_path_factory):
    """One persistent XLA compile cache shared by all subprocess tests."""
    return str(tmp_path_factory.mktemp("jax-compile-cache"))


@pytest.fixture(scope="session")
def distributed(compile_cache_dir):
    return functools.partial(run_distributed, cache_dir=compile_cache_dir)


@functools.lru_cache(maxsize=None)
def _mesh_cached(axis_shapes: tuple, axis_names: tuple):
    from repro.core import make_mesh

    return make_mesh(axis_shapes, axis_names)


@pytest.fixture(scope="session")
def session_mesh():
    """Memoized in-process mesh factory: ``session_mesh((1,), ('r',))``."""
    return _mesh_cached
