"""Per-architecture smoke tests (deliverable f): every assigned arch, reduced
config, one forward + one train step on CPU, asserting shapes and no NaNs."""
import numpy as np
import pytest

import sys, os
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp

from repro import configs
from repro.models import lm
from repro.train.optimizer import OptConfig, init_opt_state
from repro.train.trainer import make_train_step

B, S = 2, 64


def _batch(cfg, key):
    batch = {}
    if cfg.input_kind == "embeds":
        batch["embeds"] = jax.random.normal(key, (B, S, cfg.d_model), jnp.float32)
    else:
        batch["tokens"] = jax.random.randint(key, (B, S), 0, cfg.vocab)
    if cfg.input_kind == "tokens+image":
        batch["image_embeds"] = jax.random.normal(key, (B, cfg.enc_len, cfg.enc_dim), jnp.float32)
    batch["labels"] = jax.random.randint(key, (B, S), 0, cfg.vocab)
    return batch


@pytest.mark.parametrize("arch", configs.ARCH_IDS)
def test_forward_shapes_and_finiteness(arch):
    cfg = configs.get(arch, smoke=True)
    key = jax.random.PRNGKey(0)
    params = lm.init_model(cfg, key)
    logits, aux = lm.forward(params, _batch(cfg, key), cfg)
    assert logits.shape == (B, S, cfg.vocab_padded)
    assert np.isfinite(np.asarray(logits)).all(), arch
    assert np.isfinite(float(aux))


@pytest.mark.parametrize("arch", configs.ARCH_IDS)
@pytest.mark.slow  # full backward per arch
def test_one_train_step(arch):
    cfg = configs.get(arch, smoke=True)
    key = jax.random.PRNGKey(1)
    params = lm.init_model(cfg, key)
    ocfg = OptConfig(lr=1e-3, warmup_steps=1, total_steps=10)
    opt = init_opt_state(params, ocfg)
    step = jax.jit(make_train_step(cfg, None, ocfg))
    new_params, new_opt, metrics = step(params, opt, _batch(cfg, key))
    assert np.isfinite(float(metrics["loss"])), arch
    assert np.isfinite(float(metrics["grad_norm"])), arch
    assert int(new_opt.step) == 1
    # parameters actually moved
    moved = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a - b))), params, new_params)
    assert max(jax.tree.leaves(moved)) > 0.0, arch


def test_full_configs_match_assignment():
    """The exact assigned hyperparameters (guard against drift)."""
    expect = {
        "phi4-mini-3.8b": (32, 3072, 24, 8, 8192, 200064),
        "minicpm3-4b": (62, 2560, 40, 40, 6400, 73448),
        "internlm2-20b": (48, 6144, 48, 8, 16384, 92544),
        "qwen2.5-32b": (64, 5120, 40, 8, 27648, 152064),
        "llama-3.2-vision-11b": (40, 4096, 32, 8, 14336, 128256),
        "phi3.5-moe-42b-a6.6b": (32, 4096, 32, 8, 6400, 32064),
        "arctic-480b": (35, 7168, 56, 8, 4864, 32000),
        "rwkv6-3b": (32, 2560, 40, 40, 8960, 65536),
        "zamba2-7b": (81, 3584, 32, 32, 14336, 32000),
        "musicgen-large": (48, 2048, 32, 32, 8192, 2048),
    }
    for arch, (L, d, h, g, f, v) in expect.items():
        cfg = configs.get(arch)
        got = (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv, cfg.d_ff, cfg.vocab)
        assert got == (L, d, h, g, f, v), (arch, got)


def test_moe_features():
    assert configs.get("phi3.5-moe-42b-a6.6b").n_experts == 16
    arctic = configs.get("arctic-480b")
    assert arctic.n_experts == 128 and arctic.moe_dense_residual
    assert configs.get("qwen2.5-32b").qkv_bias


def test_long_context_support_flags():
    for arch in configs.ARCH_IDS:
        cfg = configs.get(arch)
        expect_long = cfg.family in ("ssm", "hybrid")
        assert ("long_500k" in cfg.supported_shapes()) == expect_long, arch


@pytest.mark.slow  # two full MoE forwards
def test_moe_grouped_dispatch_equivalence():
    """Grouped dispatch (the §Perf lever, now the MoE default at scale) must
    agree with the global dispatch when capacity is non-binding."""
    import jax
    import jax.numpy as jnp
    from repro.models import ffn
    from repro.models.module import init_params

    specs = ffn.moe_specs(32, 64, 4)
    params = init_params(specs, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 16, 32))
    y1, _ = ffn.moe_ffn(params, x, n_experts=4, top_k=2, capacity_factor=8.0)
    y2, _ = ffn.moe_ffn(params, x, n_experts=4, top_k=2, capacity_factor=8.0, groups=4)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), rtol=2e-5, atol=2e-5)
    # groups that don't divide the batch fall back to global dispatch
    y3, _ = ffn.moe_ffn(params, x, n_experts=4, top_k=2, capacity_factor=8.0, groups=3)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y3), rtol=2e-5, atol=2e-5)


def test_moe_expert_parallel_matches_dense_oracle(distributed):
    """ISSUE 9 acceptance: the expert-parallel ragged-a2a dispatch matches
    the dense capacity oracle numerically under dropless counts, its
    blocking interpretation is BITWISE the double-buffered schedule, skewed
    counts tables (zero-token experts, zero split extents) execute, and an
    ineligible context falls back to the dense path with a warning."""
    out = distributed(
        """
import warnings
import numpy as np, jax, jax.numpy as jnp
from repro import configs
from repro.core import make_mesh
from repro.models import ffn
from repro.models.module import init_params
from repro.models.sharding import make_recipe, use_recipe

cfg = configs.get('phi3.5-moe-42b-a6.6b', smoke=True)
mesh = make_mesh((2, 4), ('data', 'model'))
recipe = make_recipe(cfg, mesh)
B, S, m, E, k = 4, 8, cfg.d_model, cfg.n_experts, cfg.moe_top_k
p = init_params(ffn.moe_specs(m, cfg.d_ff, E), jax.random.PRNGKey(0))
x = jax.random.normal(jax.random.PRNGKey(1), (B, S, m), jnp.float32)
Tl = (B // 2) * (S // 4)
counts = (Tl,) * E  # dropless: every expert can hold every local token

# dense oracle at the matching dropless capacity (C = T covers top_k * T / E * (E/k))
yd, auxd = jax.jit(lambda xv: ffn.moe_ffn(p, xv, n_experts=E, top_k=k,
                                          capacity_factor=float(E) / k))(x)

def ep(xv, db=True, cts=counts):
    with use_recipe(recipe):
        return ffn.moe_expert_parallel(p, xv, n_experts=E, top_k=k,
                                       counts=cts, n_groups=2,
                                       double_buffer=db)

ye, auxe = jax.jit(ep)(x)
np.testing.assert_allclose(np.asarray(yd), np.asarray(ye), rtol=2e-5, atol=2e-5)
assert abs(float(auxd) - float(auxe)) < 1e-6

# blocking interpretation is bitwise the double-buffered schedule
yb, _ = jax.jit(lambda xv: ep(xv, db=False))(x)
assert np.array_equal(np.asarray(ye), np.asarray(yb))

# skewed routing: all capacity on rank 0's experts, zero-token elsewhere
skew = (Tl, Tl) + (0,) * (E - 2)
ys, _ = jax.jit(lambda xv: ep(xv, cts=skew))(x)
assert np.isfinite(np.asarray(ys)).all()

# dispatch='ep' without an active recipe falls back, loudly, to the oracle
# (jitted like the oracle: an eager call may round differently from the jit)
with warnings.catch_warnings(record=True) as w:
    warnings.simplefilter('always')
    yf, _ = jax.jit(lambda xv: ffn.moe_ffn(p, xv, n_experts=E, top_k=k,
                                           capacity_factor=float(E) / k,
                                           dispatch='ep'))(x)
assert any('falling back' in str(x.message) for x in w)
assert np.array_equal(np.asarray(yf), np.asarray(yd))
print('OK')
"""
    )
    assert "OK" in out
