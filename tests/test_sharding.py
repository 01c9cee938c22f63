"""Recipe derivation: the binding mechanism that replaces hand-written
PartitionSpecs (single-process spec math + an 8-device integration run)."""
import numpy as np
import pytest

import sys, os
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from jax.sharding import PartitionSpec as P

from repro.core.dist import partition_spec
from repro.core.layout import scalar, vector
from repro.core import LayoutError
from repro.models.module import pspec


def test_partition_spec_basic():
    w = pspec(("m", 64), ("f", 128)).layout
    assert partition_spec(w, {"m": "data", "f": "model"}) == P("data", "model")
    assert partition_spec(w, {"f": "model"}) == P(None, "model")
    assert partition_spec(w, {}) == P()


def test_partition_spec_priority_conflict():
    """MoE expert weight (e, m, f): e and f both want 'model' — priority wins."""
    w = pspec(("e", 16), ("m", 64), ("f", 128)).layout
    spec = partition_spec(w, {"e": "model", "f": "model", "m": "data"}, priority=["e", "f", "m"])
    assert spec == P("model", "data")
    spec2 = partition_spec(w, {"e": "model", "f": "model", "m": "data"}, priority=["f", "e", "m"])
    assert spec2 == P(None, "data", "model")


def test_partition_spec_tuple_axes():
    w = pspec(("v", 256), ("m", 64)).layout
    spec = partition_spec(w, {"v": ("pod", "model")})
    assert spec == P(("pod", "model"))


def test_named_sharding_on_session_mesh(session_mesh):
    """named_sharding end to end on a real (1-device) mesh; the session-scoped
    factory memoizes Mesh construction across tests."""
    from repro.core.dist import named_sharding

    mesh = session_mesh((1,), ("model",))
    w = pspec(("m", 64), ("f", 128)).layout
    ns = named_sharding(mesh, w, {"f": "model"})
    assert ns.spec == P(None, "model")
    assert session_mesh((1,), ("model",)) is mesh  # memoized, not rebuilt


def test_partition_spec_blocked_dim_rejected():
    from repro.core.layout import blocked, merge_blocks as mb

    # blocked('f','F'): the inner axis keeps the name 'f', so binding 'f'
    # resolves to that axis — unambiguous, allowed:
    l = (scalar(np.float32) ^ vector("f", 128) ^ vector("m", 64)) ^ blocked("f", "F", 32)
    assert partition_spec(l, {"F": "model"}) == P(None, "model")

    # a merged dim whose name matches NO physical axis spans two axes:
    # binding it is ambiguous and must fail before lowering
    l2 = (scalar(np.float32) ^ vector("a", 8) ^ vector("b", 4) ^ vector("m", 64)) ^ mb("b", "a", "f")
    with pytest.raises(LayoutError):
        partition_spec(l2, {"f": "model"})


def test_recipe_bindings_respect_divisibility(distributed):
    out = distributed(
        """
import jax
from repro import configs
from repro.models.sharding import make_recipe

from repro.core import make_mesh
mesh = make_mesh((2, 4), ('data', 'model'))

# qwen: 40 heads % 4 == 0 -> tp mode on this mesh
cfg = configs.get('qwen2.5-32b')
r = make_recipe(cfg, mesh)
assert r.attn_mode == 'tp', r.attn_mode
assert r.bindings.get('f') == 'model'
assert r.bindings.get('m') == 'data'

# phi4 on model=16 would be sp; on model=4, 24 % 4 == 0 -> tp
cfg2 = configs.get('phi4-mini-3.8b')
r2 = make_recipe(cfg2, mesh)
assert r2.attn_mode == 'tp'

# forcing sp works for any arch
r3 = make_recipe(cfg2, mesh, attn_mode='sp')
assert r3.attn_mode == 'sp' and 'h' not in r3.bindings
print('OK')
"""
    )
    assert "OK" in out


def test_moe_replicated_fallback_warns(distributed):
    """When ``n_experts`` does not divide the model axis, the
    ``moe_buf``/``moe_buf_g`` recipe kinds silently replicate the expert
    scatter buffers — ``make_recipe`` must say so out loud (naming the
    recipe kinds and the expert-parallel escape hatch), and stay silent
    when the experts divide cleanly."""
    out = distributed(
        """
import dataclasses, warnings
from repro import configs
from repro.core import make_mesh
from repro.models.sharding import make_recipe

mesh = make_mesh((2, 4), ('data', 'model'))
cfg = configs.get('phi3.5-moe-42b-a6.6b', smoke=True)

# 6 experts % model=4 != 0 -> replicated fallback, must warn
bad = dataclasses.replace(cfg, n_experts=6)
with warnings.catch_warnings(record=True) as w:
    warnings.simplefilter('always')
    make_recipe(bad, mesh)
msgs = [str(x.message) for x in w]
hits = [m for m in msgs if 'moe_buf' in m and 'REPLICATED' in m]
assert hits, msgs
assert "moe_dispatch='ep'" in hits[0], hits[0]

# 8 % 4 == 0 -> sharded buffers, no warning
ok = dataclasses.replace(cfg, n_experts=8)
with warnings.catch_warnings(record=True) as w2:
    warnings.simplefilter('always')
    make_recipe(ok, mesh)
assert not [m for m in (str(x.message) for x in w2) if 'moe_buf' in m]
print('OK')
"""
    )
    assert "OK" in out


@pytest.mark.slow  # 8-device train subprocess
def test_sharded_train_step_matches_single_device(distributed):
    """The whole point of SPMD: distributed step == single-device step."""
    out = distributed(
        """
import numpy as np, jax, jax.numpy as jnp, dataclasses
from repro import configs
from repro.configs.base import ShapeCell
from repro.data.pipeline import DataConfig, make_batch
from repro.models import lm
from repro.models.sharding import make_recipe, batch_shardings
from repro.train.optimizer import OptConfig, init_opt_state
from repro.train.trainer import make_train_step

cfg = configs.get('phi4-mini-3.8b', smoke=True)
cfg = dataclasses.replace(cfg, act_dtype=jnp.float32)
cell = ShapeCell('t', seq_len=64, global_batch=8, kind='train')
params = lm.init_model(cfg, jax.random.PRNGKey(0))
ocfg = OptConfig(lr=1e-3, warmup_steps=0)
opt = init_opt_state(params, ocfg)
batch = jax.tree.map(jnp.asarray, make_batch(cfg, cell, 0, DataConfig(seed=4)))

# single device reference
p_ref, o_ref, m_ref = jax.jit(make_train_step(cfg, None, ocfg))(params, opt, batch)

# 4x2 mesh
from repro.core import make_mesh
mesh = make_mesh((4, 2), ('data', 'model'))
recipe = make_recipe(cfg, mesh)
specs = lm.build_specs(cfg)
shard = recipe.param_shardings(specs)
params_d = jax.tree.map(lambda x, s: jax.device_put(x, s), params, shard)
opt_d = init_opt_state(params_d, ocfg)
batch_d = jax.tree.map(lambda x, s: jax.device_put(x, s), batch, batch_shardings(recipe, batch))
with mesh:
    p_d, o_d, m_d = jax.jit(make_train_step(cfg, recipe, ocfg))(params_d, opt_d, batch_d)

assert abs(float(m_ref['loss']) - float(m_d['loss'])) < 1e-4, (m_ref['loss'], m_d['loss'])
for a, b in zip(jax.tree.leaves(p_ref), jax.tree.leaves(p_d)):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4)
print('OK')
""",
        timeout=560,
    )
    assert "OK" in out
