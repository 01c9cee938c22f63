"""Checkpoint manager: atomicity, rotation, integrity, async, elastic restore."""
import json
import os
import shutil

import numpy as np
import pytest

import sys
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp

from repro.ckpt.manager import CheckpointManager


def tree(seed=0):
    k = jax.random.PRNGKey(seed)
    return {
        "a": jax.random.normal(k, (4, 8)),
        "nested": {"b": jnp.arange(12, dtype=jnp.int32), "c": jnp.float32(3.5)},
    }


def test_save_restore_roundtrip(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=3)
    t = tree()
    mgr.save(7, t, extra={"loss": 1.25})
    restored, extra = mgr.restore(t)
    assert extra["loss"] == 1.25
    for a, b in zip(jax.tree.leaves(t), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_latest_and_rotation(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    for s in (1, 2, 3, 4):
        mgr.save(s, tree(s))
    assert mgr.latest_step() == 4
    assert mgr.all_steps() == [3, 4]  # rotated


def test_async_save(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    mgr.save_async(5, tree())
    mgr.wait()
    assert mgr.latest_step() == 5
    restored, _ = mgr.restore(tree())
    assert restored["nested"]["b"].shape == (12,)


def test_corruption_detected(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, tree())
    # flip bytes in the array file
    path = os.path.join(str(tmp_path), "step_00000001", "arrays.npz")
    data = bytearray(open(path, "rb").read())
    data[len(data) // 2] ^= 0xFF
    open(path, "wb").write(bytes(data))
    with pytest.raises(Exception):
        mgr.restore(tree())


def test_crash_mid_write_preserves_previous(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, tree(1))
    # simulate a crashed partial write (tmp dir left behind)
    os.makedirs(os.path.join(str(tmp_path), "step_00000002.tmp-999"), exist_ok=True)
    assert mgr.latest_step() == 1
    restored, _ = mgr.restore(tree())
    assert restored is not None


@pytest.mark.slow  # 8-device reshard subprocess
def test_elastic_restore_resharded(distributed):
    """Save under one mesh, restore under a different mesh (scale-down):
    the layout algebra re-derives shardings — contents must be identical."""
    out = distributed(
        """
import numpy as np, jax, jax.numpy as jnp, tempfile, os
from repro.ckpt.manager import CheckpointManager
from repro.models import lm
from repro.models.sharding import make_recipe
from repro import configs

cfg = configs.get('phi4-mini-3.8b', smoke=True)
params = lm.init_model(cfg, jax.random.PRNGKey(0))
specs = lm.build_specs(cfg)

from repro.core import make_mesh
mesh_a = make_mesh((4, 2), ('data', 'model'))
recipe_a = make_recipe(cfg, mesh_a)
params_a = jax.tree.map(lambda x, s: jax.device_put(x, s), params, recipe_a.param_shardings(specs))

d = tempfile.mkdtemp()
mgr = CheckpointManager(d)
mgr.save(3, params_a)

# "scale down": different mesh shape, different shardings
mesh_b = make_mesh((2, 2), ('data', 'model'))
recipe_b = make_recipe(cfg, mesh_b)
restored, _ = mgr.restore(params, shardings=recipe_b.param_shardings(specs))
for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(restored)):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
print('OK')
"""
    )
    assert "OK" in out
