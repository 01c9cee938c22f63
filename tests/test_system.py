"""End-to-end behaviour tests for the whole system (the paper's abstraction
driving a real train/serve stack)."""
import numpy as np
import pytest

import sys, os
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp

from repro import configs


def test_all_ten_archs_registered():
    assert len(configs.ARCH_IDS) == 10
    for arch in configs.ARCH_IDS:
        cfg = configs.get(arch)
        smoke = configs.get(arch, smoke=True)
        assert cfg.family == smoke.family, arch
        assert smoke.d_model <= 128, "smoke configs must be reduced"


def test_paper_feature_matrix():
    """Table 1 of the paper, asserted programmatically for our abstraction
    (the benchmark prints the table; this keeps it true)."""
    from benchmarks.feature_matrix import evaluate_features

    feats = evaluate_features()
    assert all(feats.values()), {k: v for k, v in feats.items() if not v}
    assert set(feats) == {
        "auto_transforms", "non_contiguous", "mdspan_like",
        "seamless", "type_safety", "scatter_gather",
    }


@pytest.mark.slow  # multi-step pretrain
def test_end_to_end_tiny_pretrain():
    """Train a tiny model for 40 steps and check it learned the synthetic
    copy structure better than chance (system-level learning signal)."""
    from repro.configs.base import ShapeCell
    from repro.data.pipeline import DataConfig, make_batch
    from repro.models import lm
    from repro.train.optimizer import OptConfig, init_opt_state
    from repro.train.trainer import make_train_step

    cfg = configs.get("phi4-mini-3.8b", smoke=True)
    cell = ShapeCell("t", seq_len=64, global_batch=16, kind="train")
    params = lm.init_model(cfg, jax.random.PRNGKey(0))
    ocfg = OptConfig(lr=3e-3, warmup_steps=5, total_steps=60, weight_decay=0.0)
    opt = init_opt_state(params, ocfg)
    step = jax.jit(make_train_step(cfg, None, ocfg))
    first = last = None
    for s in range(40):
        batch = jax.tree.map(jnp.asarray, make_batch(cfg, cell, s, DataConfig(seed=11)))
        params, opt, m = step(params, opt, batch)
        if s == 0:
            first = float(m["loss"])
        last = float(m["loss"])
    assert last < first - 0.3, (first, last)
