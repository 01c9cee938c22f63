"""The full language model: embeddings -> block stack -> head, for all ten
assigned architectures, plus train/prefill/decode entry points.

Design notes:
  * ``lax.scan`` over stacked layer params everywhere (O(1) HLO in depth);
  * heterogeneous stacks (VLM cross-attn every 5th layer, Zamba2 shared
    block every 6th) scan over *super-blocks*;
  * caches/states are pytrees stacked along the layer dim and carried by the
    same scans;
  * activation sharding comes from the recipe context (see sharding.py);
  * remat: ``cfg.remat='block'`` checkpoints each block's activations.
"""
from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from . import attention as attn_mod
from . import blocks as blk
from . import ssm as ssm_mod
from .module import pspec, stack_specs, init_params, abstract_params, tree_size
from .sharding import shard_act

# ================================================================= specs ====

def build_specs(cfg) -> dict:
    dt = cfg.param_dtype
    specs: dict[str, Any] = {}
    if cfg.input_kind in ("tokens", "tokens+image"):
        specs["embed"] = pspec(("v", cfg.vocab_padded), ("m", cfg.d_model), dtype=dt, init="embed")
    specs["final_norm"] = blk.norm_spec(cfg.d_model, dt)
    if not cfg.tie_embeddings:
        specs["lm_head"] = pspec(("m", cfg.d_model), ("v", cfg.vocab_padded), dtype=dt, fan_in=("m",))

    fam = cfg.family
    if fam in ("dense", "moe", "audio"):
        specs["blocks"] = stack_specs(blk.attn_block_specs(cfg), cfg.n_layers)
    elif fam == "mla":
        specs["blocks"] = stack_specs(blk.mla_block_specs(cfg), cfg.n_layers)
    elif fam == "vlm":
        n_cross = cfg.n_layers // cfg.cross_every
        n_self = cfg.n_layers - n_cross
        group_self = cfg.cross_every - 1
        assert n_self == n_cross * group_self, (n_self, n_cross)
        specs["self_blocks"] = stack_specs(
            stack_specs(blk.attn_block_specs(cfg), group_self, dim="l2"), n_cross
        )
        specs["cross_blocks"] = stack_specs(blk.cross_block_specs(cfg), n_cross)
    elif fam == "ssm":
        specs["blocks"] = stack_specs(blk.rwkv_block_specs(cfg), cfg.n_layers)
    elif fam == "hybrid":
        n_shared = cfg.n_layers // cfg.shared_every
        n_mamba = cfg.n_layers - n_shared
        group_m = cfg.shared_every - 1
        n_tail = n_mamba - n_shared * group_m
        specs["mamba_blocks"] = stack_specs(
            stack_specs(blk.mamba_block_specs(cfg), group_m, dim="l2"), n_shared
        )
        if n_tail:
            specs["tail_blocks"] = stack_specs(blk.mamba_block_specs(cfg), n_tail)
        specs["shared_block"] = blk.shared_attn_block_specs(cfg)
        specs["shared_lora"] = stack_specs(blk.shared_lora_specs(cfg, cfg.shared_lora_rank), n_shared)
    else:
        raise ValueError(f"unknown family {fam!r}")
    return specs


def count_params(cfg, *, active_only: bool = False) -> int:
    """Total (or MoE-active) parameter count."""
    n = tree_size(build_specs(cfg))
    if active_only and cfg.n_experts:
        # subtract inactive experts' weights
        per_expert = 3 * cfg.d_model * cfg.d_ff  # gate/up/down
        inactive = (cfg.n_experts - cfg.moe_top_k) * per_expert * cfg.n_layers
        n -= inactive
    return int(n)


# ============================================================= embeddings ====

def _sinusoidal(positions, d: int):
    """positions (...,) -> (..., d): works for shared (S,) and per-row (B,S)
    position grids (continuous batching offsets every slot independently)."""
    half = d // 2
    freq = jnp.exp(-np.log(10000.0) * jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freq
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def embed_inputs(params, batch, cfg, *, positions=None):
    """batch -> (B, S, m) activations in cfg.act_dtype."""
    if cfg.input_kind == "embeds":
        x = batch["embeds"].astype(cfg.act_dtype)
        S = x.shape[1]
        pos = positions if positions is not None else jnp.arange(S)
        pe = _sinusoidal(pos, cfg.d_model).astype(cfg.act_dtype)
        x = x + (pe if pe.ndim == 3 else pe[None])
        return shard_act(x, "hidden")
    tokens = shard_act(batch["tokens"], "tokens")
    x = params["embed"].astype(cfg.act_dtype)[tokens]
    return shard_act(x, "hidden")


def lm_logits(params, x, cfg):
    x = blk.rmsnorm(params["final_norm"], x)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = jnp.einsum("bsm,mv->bsv", x, head.astype(x.dtype))
    return shard_act(logits, "logits")


# ============================================================ block stacks ====

def _maybe_remat(fn, cfg):
    return jax.checkpoint(fn) if cfg.remat == "block" else fn


def _scan_stack(block_fn, stacked, x, cfg, carry_extra=None):
    """Scan a homogeneous stack. block_fn(p_layer, x) -> (x, aux)."""

    def body(carry, p_layer):
        x, aux = carry
        x, a = block_fn(p_layer, x)
        return (x, aux + a), None

    (x, aux), _ = jax.lax.scan(_maybe_remat(body, cfg), (x, jnp.zeros((), jnp.float32)), stacked)
    return x, aux


def forward(params, batch, cfg, *, positions=None):
    """Full-sequence forward (train / prefill without cache). Returns
    (logits, aux_loss)."""
    x = embed_inputs(params, batch, cfg, positions=positions)
    fam = cfg.family
    aux_total = jnp.zeros((), jnp.float32)

    if fam in ("dense", "moe", "audio"):
        fn = lambda p, x: _drop_cache(blk.attn_block(p, x, cfg, positions=positions))
        x, aux_total = _scan_stack(fn, params["blocks"], x, cfg)
    elif fam == "mla":
        fn = lambda p, x: _drop_cache(blk.mla_block(p, x, cfg, positions=positions))
        x, aux_total = _scan_stack(fn, params["blocks"], x, cfg)
    elif fam == "vlm":
        enc = shard_act(batch["image_embeds"], "enc")

        def group(carry, ps):
            x, aux = carry
            p_self, p_cross = ps
            fn = lambda p, x: _drop_cache(blk.attn_block(p, x, cfg, positions=positions))
            x, a = _scan_stack(fn, p_self, x, cfg)
            x = blk.cross_block(p_cross, x, enc, cfg)
            return (x, aux + a), None

        (x, aux_total), _ = jax.lax.scan(
            _maybe_remat(group, cfg), (x, aux_total), (params["self_blocks"], params["cross_blocks"])
        )
    elif fam == "ssm":
        fn = lambda p, x: _drop_cache(blk.rwkv_block(p, x, cfg))
        x, aux_total = _scan_stack(fn, params["blocks"], x, cfg)
    elif fam == "hybrid":
        def group(carry, ps):
            x, aux = carry
            p_mamba, p_lora = ps
            fn = lambda p, x: _drop_cache(blk.mamba_block(p, x, cfg))
            x, a = _scan_stack(fn, p_mamba, x, cfg)
            x, _, a2 = blk.shared_attn_block(params["shared_block"], p_lora, x, cfg, positions=positions)
            return (x, aux + a + a2), None

        (x, aux_total), _ = jax.lax.scan(
            _maybe_remat(group, cfg), (x, aux_total), (params["mamba_blocks"], params["shared_lora"])
        )
        if "tail_blocks" in params:
            fn = lambda p, x: _drop_cache(blk.mamba_block(p, x, cfg))
            x, a = _scan_stack(fn, params["tail_blocks"], x, cfg)
            aux_total = aux_total + a
    else:
        raise ValueError(fam)
    return lm_logits(params, x, cfg), aux_total


def _drop_cache(out):
    x, _cache, aux = out
    return x, aux


# ================================================================== loss ====

def loss_fn(params, batch, cfg):
    """Next-token cross-entropy (+ MoE aux). Returns (loss, metrics)."""
    logits, aux = forward(params, batch, cfg)
    labels = batch["labels"]  # (B, S) already shifted by the pipeline
    logits = logits.astype(jnp.float32)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    mask = batch.get("loss_mask")
    if mask is None:
        mask = jnp.ones_like(labels, jnp.float32)
    nll = ((logz - gold) * mask).sum() / jnp.maximum(mask.sum(), 1.0)
    loss = nll + aux
    return loss, {"nll": nll, "aux": aux, "ppl_proxy": jnp.exp(jnp.minimum(nll, 20.0))}


# ================================================================ caching ====

class DecodeState(NamedTuple):
    caches: Any  # pytree of per-layer caches, stacked on the layer dim
    positions: jax.Array  # (B,) next position


def init_cache(cfg, batch_size: int, max_len: int):
    """Stacked per-layer cache pytree in act_dtype (layout-recipe sharded)."""
    B, S = batch_size, max_len
    dt = cfg.act_dtype
    zero_len = jnp.zeros((B,), jnp.int32)
    fam = cfg.family

    def kv(n_layers, G=None, D=None):
        G = G or cfg.n_kv
        D = D or cfg.head_dim
        return attn_mod.KVCache(
            k=jnp.zeros((n_layers, B, G, S, D), dt),
            v=jnp.zeros((n_layers, B, G, S, D), dt),
            length=jnp.tile(zero_len, (n_layers, 1)),
        )

    if fam in ("dense", "moe", "audio"):
        return kv(cfg.n_layers)
    if fam == "mla":
        return attn_mod.MLACache(
            c=jnp.zeros((cfg.n_layers, B, S, cfg.mla_kv_rank), dt),
            kr=jnp.zeros((cfg.n_layers, B, S, cfg.mla_d_rope), dt),
            length=jnp.tile(zero_len, (cfg.n_layers, 1)),
        )
    if fam == "vlm":
        n_cross = cfg.n_layers // cfg.cross_every
        group_self = cfg.cross_every - 1
        return {"self": jax.tree.map(lambda x: x.reshape((n_cross, group_self) + x.shape[1:]), kv(n_cross * group_self))}
    if fam == "ssm":
        H = cfg.n_heads
        hd = cfg.d_model // H
        return blk.RWKVBlockState(
            time=ssm_mod.RWKVState(
                wkv=jnp.zeros((cfg.n_layers, B, H, hd, hd), jnp.float32),
                shift=jnp.zeros((cfg.n_layers, B, cfg.d_model), dt),
            ),
            cm_shift=jnp.zeros((cfg.n_layers, B, cfg.d_model), dt),
        )
    if fam == "hybrid":
        n_shared = cfg.n_layers // cfg.shared_every
        group_m = cfg.shared_every - 1
        n_tail = cfg.n_layers - n_shared - n_shared * group_m
        d_inner = cfg.ssm_expand * cfg.d_model
        H = d_inner // cfg.ssm_head_dim
        conv_ch = d_inner + 2 * cfg.ssm_groups * cfg.ssm_state
        Sw = min(S, cfg.shared_window) if S > cfg.shared_window else S

        def mstate(n):
            return ssm_mod.MambaState(
                ssm=jnp.zeros((n, B, H, cfg.ssm_head_dim, cfg.ssm_state), jnp.float32),
                conv=jnp.zeros((n, B, 3, conv_ch), dt),
            )

        out = {
            "mamba": jax.tree.map(
                lambda x: x.reshape((n_shared, group_m) + x.shape[1:]), mstate(n_shared * group_m)
            ),
            "shared": attn_mod.KVCache(
                k=jnp.zeros((n_shared, B, cfg.n_kv, Sw, cfg.head_dim), dt),
                v=jnp.zeros((n_shared, B, cfg.n_kv, Sw, cfg.head_dim), dt),
                length=jnp.tile(zero_len, (n_shared, 1)),
            ),
        }
        if n_tail:
            out["tail"] = mstate(n_tail)
        return out
    raise ValueError(fam)


def _mask_rows(new, old, active):
    """Restore batch rows ``active[b] == False`` of a recurrent state pytree
    to their pre-step values.  Continuous batching runs the full batch
    through every step even when some slots carry no valid tokens — their
    state updates are garbage and must not persist.  Every leaf is (B, ...)
    inside the layer scans, so a broadcast ``where`` on the leading dim is
    the whole merge."""
    if active is None:
        return new

    def leaf(n, o):
        return jnp.where(active.reshape((-1,) + (1,) * (n.ndim - 1)), n, o)

    return jax.tree.map(leaf, new, old)


def _decode_stack(block, stacked, caches, x, active, lead=()):
    """Run one homogeneous run of blocks over its caches:
    ``block(p, x, cache, layer) -> (x, new_cache, aux)``.

    The kind of cache decides how it moves.  Length-indexed caches
    (``KVCache``, ``MLACache``) carry their stacked payloads in the scan and
    each layer writes only its new positions into them, in place
    (:func:`repro.models.attention.write_positions`, which also keeps
    inactive rows' bytes); ``length`` stays per layer in the scanned inputs
    and outputs.  Their payloads may be stacked over more leading dims than
    this run: ``lead`` indexes them (the VLM's self-attention runs inside
    its groups, which are a run of their own).  Recurrent states (RWKV,
    Mamba) are rewritten whole every step: they pass through the scan as
    inputs and outputs, with inactive rows merged back (:func:`_mask_rows`)."""
    if isinstance(caches, (attn_mod.KVCache, attn_mod.MLACache)):
        def body(carry, layer):
            x, stacks = carry
            p, length, i = layer
            x, c, _ = block(p, x, stacks._replace(length=length), lead + (i,))
            return (x, c._replace(length=None)), c.length

        n = caches.length.shape[0]
        (x, stacks), length = jax.lax.scan(
            body, (x, caches._replace(length=None)), (stacked, caches.length, jnp.arange(n)))
        return x, stacks._replace(length=length)

    def body(x, layer):
        p, c = layer
        x, new_c, _ = block(p, x, c, None)
        return x, _mask_rows(new_c, c, active)

    return jax.lax.scan(body, x, (stacked, caches))


# the per-slot leaves of a DecodeState that are not cache payloads: the
# caches' lengths and the positions, with the slots on their last axis
_SLOT_LEAVES = ("length", "positions")


def _is_slot_leaf(path) -> bool:
    return getattr(path[-1], "name", getattr(path[-1], "key", "")) in _SLOT_LEAVES


def _slot_rows(state: DecodeState, slot, rows: int) -> DecodeState:
    """``state`` with lengths and positions cut to slots ``slot`` ..
    ``slot + rows - 1``; the cache payloads stay whole."""
    return jax.tree_util.tree_map_with_path(
        lambda p, x: jax.lax.dynamic_slice_in_dim(x, slot, rows, axis=-1) if _is_slot_leaf(p) else x,
        state)


def _put_slot_rows(full: DecodeState, rows: DecodeState, slot) -> DecodeState:
    """``rows`` (from :func:`_slot_rows`) with its lengths and positions put
    back into those of ``full`` at ``slot``."""
    return jax.tree_util.tree_map_with_path(
        lambda p, f, r: jax.lax.dynamic_update_slice_in_dim(f, r, slot, axis=-1)
        if _is_slot_leaf(p) else r,
        full, rows)


def decode_step(params, state: DecodeState, batch, cfg, *, new_counts=None,
                prefill: bool = False, slot=None):
    """One serve step: embed the new token(s), run all blocks against the
    caches, return (logits, new DecodeState).  ``batch['tokens']`` (B, S)
    (or ``batch['embeds']`` (B, S, m) for the audio family); S == 1 is the
    classic decode step.

    Continuous batching (per-row state):
      * every batch row runs at its *own* absolute position
        (``state.positions[b]``) — RoPE/sinusoidal offsets and causal masks
        are per-row;
      * ``new_counts`` (B,) int32 marks how many of the chunk's S tokens are
        valid per row (0 = the slot is idle this step).  Idle rows keep
        their cache bytes and state (:func:`_decode_stack`) and their
        positions do not advance — the fix for the cross-slot clobbering bug
        where one slot's prefill wrote garbage K/V into every resident
        request's cache;
      * ``prefill=True`` marks a whole-prompt chunk whose active rows start
        at position 0 (admission-time batched prefill); under an ``sp_ring``
        recipe the attention families run the chunk through the
        sequence-parallel ring plan.  A prefill step returns (B, 1, vocab)
        logits: those of each row's last valid token;
      * ``slot`` (a traced int32 scalar) makes the batch's R rows cache slots
        ``slot`` .. ``slot + R - 1`` of a state with more slots: only those
        slots' cache rows, lengths and positions are read and written, and
        the other slots cost nothing (the engine's one-row admission
        prefill).  Length-indexed caches only: a recurrent state has no
        per-slot write.
    Rows may leave garbage *beyond* their valid count inside the cache
    capacity — sound for non-windowed caches because the next write starts
    at ``length + count`` and the attention mask never reads past ``length``.
    The length-indexed caches are written in place: a step moves the
    positions it produces, not the cache.
    """
    full = state
    R, S = (batch["embeds"] if cfg.input_kind == "embeds" else batch["tokens"]).shape[:2]
    if slot is not None:
        if cfg.family in ("ssm", "hybrid"):
            raise ValueError(f"{cfg.family!r} state cannot be stepped one slot at a time")
        state = _slot_rows(state, slot, R)
    positions = state.positions
    pos2d = positions[:, None] + jnp.arange(S, dtype=positions.dtype)[None, :]
    active = None if new_counts is None else new_counts > 0
    adv = S if new_counts is None else new_counts
    x = embed_inputs(params, batch, cfg, positions=pos2d)
    fam = cfg.family
    caches = state.caches
    kw = dict(positions=pos2d, new_counts=new_counts, prefill=prefill, slot=slot)

    def attn_block(p, x, c, layer):
        return blk.attn_block(p, x, cfg, cache=c, layer=layer, **kw)

    def mamba_block(p, x, c, _):
        return blk.mamba_block(p, x, cfg, state=c)

    if fam in ("dense", "moe", "audio"):
        x, new_caches = _decode_stack(attn_block, params["blocks"], caches, x, active)
    elif fam == "mla":
        def mla_block(p, x, c, layer):
            return blk.mla_block(p, x, cfg, cache=c, layer=layer, **kw)

        x, new_caches = _decode_stack(mla_block, params["blocks"], caches, x, active)
    elif fam == "ssm":
        def rwkv_block(p, x, c, _):
            return blk.rwkv_block(p, x, cfg, state=c)

        x, new_caches = _decode_stack(rwkv_block, params["blocks"], caches, x, active)
    elif fam == "vlm":
        enc = shard_act(batch["image_embeds"], "enc")

        def group(p, x, c, layer):
            p_self, p_cross = p
            x, c = _decode_stack(attn_block, p_self, c, x, active, lead=layer)
            return blk.cross_block(p_cross, x, enc, cfg), c, None

        x, c_self = _decode_stack(group, (params["self_blocks"], params["cross_blocks"]),
                                  caches["self"], x, active)
        new_caches = {"self": c_self}
    elif fam == "hybrid":
        shared = caches["shared"]

        def group(carry, layer):
            x, stacks = carry
            (p_mamba, p_lora), c_mamba, length, g = layer
            x, new_c_mamba = _decode_stack(mamba_block, p_mamba, c_mamba, x, active)
            x, c, _ = blk.shared_attn_block(
                params["shared_block"], p_lora, x, cfg, cache=stacks._replace(length=length),
                positions=pos2d, window=cfg.shared_window, new_counts=new_counts, layer=(g,),
            )
            return (x, c._replace(length=None)), (new_c_mamba, c.length)

        (x, stacks), (new_mamba, length) = jax.lax.scan(
            group, (x, shared._replace(length=None)),
            ((params["mamba_blocks"], params["shared_lora"]), caches["mamba"], shared.length,
             jnp.arange(shared.length.shape[0])),
        )
        new_caches = {"mamba": new_mamba, "shared": stacks._replace(length=length)}
        if "tail" in caches:
            x, new_caches["tail"] = _decode_stack(mamba_block, params["tail_blocks"],
                                                  caches["tail"], x, active)
    else:
        raise ValueError(fam)

    if prefill:
        # only each row's last valid token predicts the next one: project that
        # row alone (the full (B, S, vocab) tensor is GBs at serving widths)
        last = jnp.maximum(jnp.broadcast_to(adv, positions.shape) - 1, 0)
        x = x[jnp.arange(x.shape[0]), last][:, None]
    logits = lm_logits(params, x, cfg)
    new = DecodeState(caches=new_caches, positions=positions + adv)
    return logits, new if slot is None else _put_slot_rows(full, new, slot)


# =============================================================== helpers ====

def init_model(cfg, key):
    return init_params(build_specs(cfg), key)


def abstract_model(cfg):
    return abstract_params(build_specs(cfg))
