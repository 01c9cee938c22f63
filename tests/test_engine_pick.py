"""The engine picks each next token on the device (``Engine.pick_fn``) from
the logits the decode program returns, and pulls only the ids to the host.
Greedy picks equal the host argmax of those logits over the real
vocabulary, step by step, in every kind of family; sampled picks stay in the
vocabulary, repeat under one seed and fall back to greedy as the
temperature goes to zero."""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import lm
from repro.serve.engine import Engine, ServeConfig

# (request id, prompt, new tokens) on 2 slots: three requests, so one waits
# for a slot and rows finish on different steps
REQUESTS = [(0, [5, 9, 13, 2], 6), (1, [3, 3], 4), (2, [17, 2, 4, 8, 1], 5)]


def _model(arch, **changes):
    cfg = dataclasses.replace(configs.get(arch, smoke=True), **changes)
    return cfg, lm.init_model(cfg, jax.random.PRNGKey(0))


def _serve(cfg, params, *, temperature=0.0, seed=0, alter=None):
    """Serve ``REQUESTS``; returns the finished map and, per decode step,
    the resident slots' request ids and the host argmax of each row of the
    logits ``decode_fn`` returned (after ``alter``, if given)."""
    eng = Engine(cfg, params, ServeConfig(max_len=32, batch_slots=2, eos_token=-1,
                                          temperature=temperature, seed=seed))
    decode, steps = eng.decode_fn, []

    def recorded(*args):
        logits, state = decode(*args)
        if alter is not None:
            logits = alter(logits)
        rows = {i: s.request_id for i, s in enumerate(eng.slots) if s.request_id is not None}
        last = np.asarray(logits[:, -1, : cfg.vocab], np.float32)
        steps.append((rows, np.argmax(last, axis=-1)))
        return logits, state

    eng.decode_fn = recorded
    for rid, prompt, new in REQUESTS:
        eng.submit(rid, prompt, max_new_tokens=new)
    return eng.run(), steps


def _host_argmax_tokens(steps):
    """Each request's tokens as the host argmax of its row at every step."""
    out = {}
    for rows, best in steps:
        for i, rid in rows.items():
            out.setdefault(rid, []).append(int(best[i]))
    return out


def _served(done):
    return {rid: done[rid][len(prompt):] for rid, prompt, _ in REQUESTS}


@pytest.mark.parametrize("arch", ["phi4-mini-3.8b", "rwkv6-3b", "musicgen-large"])
def test_greedy_pick_is_the_host_argmax_of_the_decode_logits(arch):
    """A chunk-prefill family, a per-token family and an embeds-input
    family: every served token is the argmax of its row of the logits
    ``decode_fn`` returned at that step."""
    cfg, params = _model(arch)
    done, steps = _serve(cfg, params)
    assert sorted(done) == [r for r, _, _ in REQUESTS]
    assert _served(done) == _host_argmax_tokens(steps)
    assert {rid: len(t) for rid, t in _served(done).items()} == {r: n for r, _, n in REQUESTS}


@pytest.fixture(scope="module")
def padded():
    """phi4's smoke model with a vocabulary that does not fill its padded
    head (500 of 512 columns), and a decode that forces the padded columns
    to the largest value the logits' dtype holds."""
    cfg, params = _model("phi4-mini-3.8b", vocab=500)
    assert cfg.vocab_padded > cfg.vocab

    def alter(logits):
        return logits.at[..., cfg.vocab:].set(jnp.finfo(logits.dtype).max)

    return cfg, params, alter


def test_greedy_pick_never_takes_a_padded_column(padded):
    cfg, params, alter = padded
    done, steps = _serve(cfg, params, alter=alter)
    served = _served(done)
    assert served == _host_argmax_tokens(steps)
    assert all(0 <= t < cfg.vocab for toks in served.values() for t in toks)
    # the same as without the padded columns forced
    assert served == _served(_serve(cfg, params)[0])


@pytest.mark.parametrize("temperature", [0.0, 1.0])
def test_pick_program_is_neither_step_program(temperature):
    """The benchmark's trace readers tell the step programs by their module
    names (``jit_gspmd_step*`` decode, ``jit__lambda*`` prefill) and pair
    their executions with the calls in order; the picking program must
    match neither, greedy or sampled."""
    cfg, params = _model("phi4-mini-3.8b")
    eng = Engine(cfg, params, ServeConfig(max_len=32, batch_slots=2, temperature=temperature))
    logits = jax.ShapeDtypeStruct((2, 1, cfg.vocab_padded), cfg.act_dtype)
    text = eng.pick_fn.lower(logits, eng._key).as_text()
    module = re.search(r"module @(\S+)", text).group(1)
    assert module == "jit__pick_tokens"
    assert not module.startswith(("jit_gspmd_step", "jit__lambda"))
    ids, key = jax.eval_shape(eng.pick_fn, logits, eng._key)
    assert (ids.shape, ids.dtype) == ((2,), jnp.int32)
    assert key.shape == eng._key.shape


def test_sampled_picks_stay_in_the_vocabulary(padded):
    cfg, params, alter = padded
    done, _ = _serve(cfg, params, temperature=1.0, seed=3, alter=alter)
    served = _served(done)
    assert {rid: len(t) for rid, t in served.items()} == {r: n for r, _, n in REQUESTS}
    assert all(0 <= t < cfg.vocab for toks in served.values() for t in toks)


def test_sampled_picks_repeat_under_one_seed():
    cfg, params = _model("phi4-mini-3.8b")
    a, _ = _serve(cfg, params, temperature=1.0, seed=11)
    b, _ = _serve(cfg, params, temperature=1.0, seed=11)
    assert a == b
    greedy, _ = _serve(cfg, params)
    assert _served(a) != _served(greedy)  # at T = 1 over 512 ids the draw is not the argmax


def test_tiny_temperature_picks_greedy():
    # float32 logits: bf16 ones tie at the top often enough over 512 ids
    # that the draw, which breaks a tie at random, would part from argmax
    cfg, params = _model("phi4-mini-3.8b", act_dtype=jnp.float32)
    cold, _ = _serve(cfg, params, temperature=1e-4, seed=5)
    greedy, _ = _serve(cfg, params)
    assert cold == greedy
