"""Distributed decode correctness: serving with the KV cache sharded over
the mesh (seq over `model` = the GSPMD flash-decoding merge; batch over
`data`) must produce the same logits as single-device decode."""
import pytest

pytestmark = pytest.mark.slow  # 8-device decode subprocess


def test_decode_sharded_cache_matches_single_device(distributed):
    out = distributed(
        """
import dataclasses
import numpy as np, jax, jax.numpy as jnp
from repro import configs
from repro.models import lm
from repro.models.sharding import make_recipe, decode_state_shardings, batch_shardings, use_recipe

cfg = configs.get('qwen2.5-32b', smoke=True)   # sp mode: cache seq-sharded
cfg = dataclasses.replace(cfg, act_dtype=jnp.float32)
B, CACHE = 4, 64
params = lm.init_model(cfg, jax.random.PRNGKey(0))

def fresh_state():
    return lm.DecodeState(caches=lm.init_cache(cfg, B, CACHE),
                          positions=jnp.zeros((B,), jnp.int32))

toks = [jax.random.randint(jax.random.PRNGKey(i), (B, 1), 0, cfg.vocab) for i in range(6)]

# --- single device reference ---
state = fresh_state()
ref_logits = []
step = jax.jit(lambda p, s, b: lm.decode_step(p, s, b, cfg))
for t in toks:
    lg, state = step(params, state, {'tokens': t})
    ref_logits.append(np.asarray(lg, np.float32))

# --- 4x2 mesh, cache sharded per the recipe ---
from repro.core import make_mesh
mesh = make_mesh((4, 2), ('data', 'model'))
recipe = make_recipe(cfg, mesh)
assert recipe.attn_mode in ('tp', 'sp')
specs = lm.build_specs(cfg)
params_d = jax.tree.map(lambda x, s: jax.device_put(x, s), params, recipe.param_shardings(specs))
state_d = fresh_state()
state_sh = decode_state_shardings(recipe, state_d)
state_d = jax.tree.map(lambda x, s: jax.device_put(x, s), state_d, state_sh)

def dstep(p, s, b):
    with use_recipe(recipe):
        return lm.decode_step(p, s, b, cfg)

dstep = jax.jit(dstep)
with mesh:
    for i, t in enumerate(toks):
        lg, state_d = dstep(params_d, state_d, {'tokens': t})
        np.testing.assert_allclose(np.asarray(lg, np.float32), ref_logits[i],
                                   rtol=2e-4, atol=2e-4)
print('OK distributed decode matches, attn_mode=%s' % recipe.attn_mode)
""",
        timeout=560,
    )
    assert "OK" in out
