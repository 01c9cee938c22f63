"""Prefill/decode consistency: running the model autoregressively with the
cache must reproduce the full-sequence forward logits — the strongest
correctness property the serving path has."""
import numpy as np
import pytest

import sys, os
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp

from repro import configs
from repro.models import lm

B, S = 2, 16

# The test runs in f32 activations so the comparison is at float tolerance;
# decode uses mathematically identical but differently-associated compute
# (MLA absorbed form, SSM recurrent-vs-chunked), hence small nonzero tols.
TOLS = {
    "dense": 2e-4, "mla": 2e-3, "moe": 2e-3, "vlm": 2e-4, "audio": 2e-4,
    "ssm": 5e-3, "hybrid": 5e-3,
}


def _inputs(cfg, key):
    batch = {}
    if cfg.input_kind == "embeds":
        batch["embeds"] = jax.random.normal(key, (B, S, cfg.d_model), jnp.float32) * 0.3
    else:
        batch["tokens"] = jax.random.randint(key, (B, S), 0, cfg.vocab)
    if cfg.input_kind == "tokens+image":
        batch["image_embeds"] = jax.random.normal(key, (B, cfg.enc_len, cfg.enc_dim), jnp.float32) * 0.3
    return batch


@pytest.mark.parametrize("arch", configs.ARCH_IDS)
@pytest.mark.slow  # full decode loop per arch
def test_decode_matches_forward(arch):
    import dataclasses

    cfg = configs.get(arch, smoke=True)
    # f32 activations: the comparison is then pure-math, not bf16 rounding;
    # align the ssm chunk with the tiny sequence so the train path chunks;
    # high MoE capacity factor => dropless in both paths (capacity dropping
    # is batch-dependent by design and would make the comparison vacuous)
    cfg = dataclasses.replace(
        cfg, act_dtype=jnp.float32, ssm_chunk=min(cfg.ssm_chunk, S), moe_capacity_factor=float(cfg.n_experts or 1)
    )
    key = jax.random.PRNGKey(2)
    params = lm.init_model(cfg, key)
    batch = _inputs(cfg, key)

    # full forward (teacher-forced)
    full_logits, _ = lm.forward(params, batch, cfg)

    # token-by-token decode with the cache
    state = lm.DecodeState(
        caches=lm.init_cache(cfg, B, S),
        positions=jnp.zeros((B,), jnp.int32),
    )
    step = jax.jit(lambda p, s, b: lm.decode_step(p, s, b, cfg))
    outs = []
    for t in range(S):
        sub = {}
        if cfg.input_kind == "embeds":
            sub["embeds"] = batch["embeds"][:, t : t + 1]
        else:
            sub["tokens"] = batch["tokens"][:, t : t + 1]
        if cfg.input_kind == "tokens+image":
            sub["image_embeds"] = batch["image_embeds"]
        logits, state = step(params, state, sub)
        outs.append(logits[:, 0])
    dec_logits = jnp.stack(outs, axis=1)

    tol = TOLS[cfg.family]
    np.testing.assert_allclose(
        np.asarray(dec_logits, np.float32),
        np.asarray(full_logits, np.float32),
        rtol=tol, atol=tol,
        err_msg=f"{arch}: cache decode diverges from full forward",
    )


# ---------------------------------------------------- in-place cache writes ----
# The engine's step programs write each row's new positions into the donated
# stacked cache in place (attention.write_positions).  Length-indexed cache
# leaves, by name; recurrent states (ssm, conv, wkv, shift) are rewritten
# whole every step and are not covered here.
_CACHE_LEAVES = ("k", "v", "c", "kr")


def _engine(arch, params, B, T):
    from repro.serve.engine import Engine, ServeConfig

    cfg = configs.get(arch, smoke=True)
    return Engine(cfg, params, ServeConfig(max_len=T, batch_slots=B, eos_token=-1)), cfg


def _step_args(cfg, B, S, abstract=False):
    if cfg.input_kind == "embeds":
        batch = {"embeds": jnp.zeros((B, S, cfg.d_model), jnp.float32)}
    else:
        batch = {"tokens": jnp.zeros((B, S), jnp.int32)}
    if cfg.input_kind == "tokens+image":
        batch["image_embeds"] = jnp.zeros((B, cfg.enc_len, cfg.enc_dim), jnp.float32)
    if abstract:
        batch = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), batch)
    return batch


def _cache_leaves(caches):
    for path, leaf in jax.tree_util.tree_flatten_with_path(caches)[0]:
        name = getattr(path[-1], "name", "")
        if name in _CACHE_LEAVES:
            yield jax.tree_util.keystr(path), leaf


@pytest.mark.parametrize("program", ["decode", "prefill"])
@pytest.mark.parametrize("arch", ["phi4-mini-3.8b", "minicpm3-4b", "zamba2-7b",
                                  "llama-3.2-vision-11b"])
def test_step_programs_do_not_copy_the_cache(arch, program):
    """The compiled decode and prefill programs hold no copy of a stacked
    cache leaf: a layer scan that passes the cache through its inputs and
    outputs copies the whole stack every step."""
    import re

    B, T = 8, 128
    cfg = configs.get(arch, smoke=True)
    params = jax.eval_shape(lambda: lm.init_model(cfg, jax.random.PRNGKey(0)))
    eng, cfg = _engine(arch, params, B, T)
    state = jax.eval_shape(lambda: eng.state)
    stacked = {tuple(leaf.shape) for _, leaf in _cache_leaves(state.caches)}
    assert stacked
    fn, S = (eng.decode_fn, 1) if program == "decode" else (eng.prefill_fn, 64)
    counts = jax.ShapeDtypeStruct((B,), jnp.int32)
    hlo = fn.lower(params, state, _step_args(cfg, B, S, abstract=True), counts).compile().as_text()
    copies = [line.strip() for line in hlo.splitlines()
              if (m := re.search(r"= \w+\[([\d,]*)\]\{[^}]*\} copy\(", line))
              and tuple(int(d) for d in m.group(1).split(",") if d) in stacked]
    assert not copies, copies


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_step_programs_hold_no_rounding_barrier(program):
    """The served step programs are the model's math as training and the
    float32 reference trace it: the decode step (B rows, S=1) and the
    one-row prefill (into its slot) lower with no optimization barrier, so
    XLA fuses across the activation-dtype casts as it does everywhere else."""
    B, T = 8, 128
    cfg = configs.get("phi4-mini-3.8b", smoke=True)
    params = jax.eval_shape(lambda: lm.init_model(cfg, jax.random.PRNGKey(0)))
    eng, cfg = _engine("phi4-mini-3.8b", params, B, T)
    state = jax.eval_shape(lambda: eng.state)
    if program == "decode":
        fn, rows, S, slot = eng.decode_fn, B, 1, ()
    else:
        fn, rows, S, slot = eng.prefill_fn, 1, 64, (jax.ShapeDtypeStruct((), jnp.int32),)
    counts = jax.ShapeDtypeStruct((rows,), jnp.int32)
    text = fn.lower(params, state, _step_args(cfg, rows, S, abstract=True), counts, *slot).as_text()
    assert "optimization_barrier" not in text


@pytest.mark.parametrize("program", ["decode", "prefill"])
@pytest.mark.parametrize("arch", ["phi4-mini-3.8b", "minicpm3-4b", "zamba2-7b"])
def test_inactive_rows_keep_their_cache(arch, program):
    """Rows with count 0 keep every cache byte through a step, also where a
    prefill chunk's start is clamped (a resident row's length + S runs past
    the cache's end), and active rows change only at the positions they
    write.  Smoke zamba2's shared cache is a 64-slot ring (max_len 96), and
    one of its resident rows has wrapped."""
    B, T = 4, 96
    cfg = configs.get(arch, smoke=True)
    eng, cfg = _engine(arch, lm.init_model(cfg, jax.random.PRNGKey(0)), B, T)
    lengths = np.array([80, 5, 40, 0], np.int32)
    if program == "decode":
        S, counts = 1, np.array([1, 0, 1, 0], np.int32)
        fn = eng.decode_fn
    else:
        S, counts = 32, np.array([0, 0, 20, 32], np.int32)  # rows 0, 1 resident
        fn = eng.prefill_fn
    key = jax.random.PRNGKey(1)

    def fill(path, leaf):
        name = getattr(path[-1], "name", "")
        if name == "length":
            return jnp.broadcast_to(jnp.asarray(lengths), leaf.shape)
        if name in _CACHE_LEAVES:
            return jax.random.normal(jax.random.fold_in(key, leaf.size), leaf.shape).astype(leaf.dtype)
        return leaf

    caches = jax.tree_util.tree_map_with_path(fill, eng.state.caches)
    before = {p: np.asarray(x) for p, x in _cache_leaves(caches)}
    assert before
    state = lm.DecodeState(caches=caches, positions=jnp.asarray(lengths))
    batch = _step_args(cfg, B, S)
    batch = jax.tree.map(lambda x: x + 3 if x.dtype == jnp.int32 else x + 0.5, batch)
    _, new = fn(eng.params, state, batch, jnp.asarray(counts))
    after = {p: np.asarray(x) for p, x in _cache_leaves(new.caches)}
    for path, old in before.items():
        # leaves are (*layers, B, G, T, D) for k/v and (*layers, B, T, R) for
        # c/kr: positions are the second-to-last axis in both
        batch_axis = old.ndim - (4 if path.endswith((".k", ".v")) else 3)
        size = old.shape[-2]
        for b in range(B):
            row_old = np.take(old, b, axis=batch_axis)
            row_new = np.take(after[path], b, axis=batch_axis)
            if not counts[b]:
                assert np.array_equal(row_old, row_new), (path, b)
                continue
            start = min(int(lengths[b]) % size, size - S)
            keep = np.ones(size, bool)
            keep[start:start + S] = False
            assert np.array_equal(np.compress(keep, row_old, axis=-2),
                                  np.compress(keep, row_new, axis=-2)), (path, b)
            assert not np.array_equal(row_old, row_new), (path, b)
    np.testing.assert_array_equal(np.asarray(new.positions), lengths + counts)


@pytest.mark.parametrize("arch", ["phi4-mini-3.8b", "minicpm3-4b", "musicgen-large"])
def test_one_row_prefill_touches_only_its_slot(arch):
    """An admission into slot 3 of a full 8-slot engine runs one one-row
    prefill call that leaves every other slot's cache rows, length and
    position bitwise as they were, and writes slot 3 exactly what a
    1-slot engine writes for the same prompt.  Positions past the prompt's
    bucket keep the slot's old bytes (only ``length`` is reset on
    admission)."""
    B, T, slot = 8, 64, 3
    cfg = configs.get(arch, smoke=True)
    params = lm.init_model(cfg, jax.random.PRNGKey(0))
    eng, cfg = _engine(arch, params, B, T)
    lengths = np.array([30, 5, 12, 0, 40, 7, 22, 9], np.int32)
    key = jax.random.PRNGKey(2)

    def fill(path, leaf):
        name = getattr(path[-1], "name", "")
        if name == "length":
            return jnp.broadcast_to(jnp.asarray(lengths), leaf.shape)
        return jax.random.normal(jax.random.fold_in(key, leaf.size), leaf.shape).astype(leaf.dtype)

    eng.state = lm.DecodeState(caches=jax.tree_util.tree_map_with_path(fill, eng.state.caches),
                               positions=jnp.asarray(lengths))
    before = jax.tree.map(np.asarray, eng.state)
    for j in range(B):
        if j != slot:
            eng.slots[j].request_id = 100 + j
    shapes = []
    row_fn = eng.prefill_fn
    eng.prefill_fn = lambda *a: shapes.append(jax.tree.map(jnp.shape, a[2:])) or row_fn(*a)
    prompt = list(range(3, 24))  # feeds 20 tokens, padded to the 32 bucket
    eng.submit(0, prompt, max_new_tokens=2)
    eng._fill_slots()
    fed, bucket = len(prompt) - 1, 32
    rows = {"tokens": (1, bucket)} if cfg.input_kind != "embeds" else {"embeds": (1, bucket, cfg.d_model)}
    assert shapes == [(rows, (1,), ())]

    one, _ = _engine(arch, params, 1, T)
    one.submit(0, prompt, max_new_tokens=2)
    one._fill_slots()
    after = jax.tree.map(np.asarray, eng.state)
    solo = jax.tree.map(np.asarray, one.state)
    np.testing.assert_array_equal(after.positions, np.where(np.arange(B) == slot, fed, lengths))
    for path, old in _cache_leaves(before.caches):
        new = dict(_cache_leaves(after.caches))[path]
        ref = dict(_cache_leaves(solo.caches))[path]
        axis = old.ndim - (4 if path.endswith((".k", ".v")) else 3)
        for b in range(B):
            if b != slot:
                assert np.array_equal(np.take(old, b, axis), np.take(new, b, axis)), (path, b)
        row, ref_row = np.take(new, slot, axis), np.take(ref, 0, axis)
        assert np.array_equal(row[..., :bucket, :], ref_row[..., :bucket, :]), path
        assert np.array_equal(row[..., bucket:, :], np.take(old, slot, axis)[..., bucket:, :]), path
    for path, leaf in jax.tree_util.tree_flatten_with_path(after.caches)[0]:
        if getattr(path[-1], "name", "") == "length":
            assert (leaf == np.where(np.arange(B) == slot, fed, lengths)).all(), path
