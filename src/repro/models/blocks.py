"""Decoder blocks and scan-over-layers stacks for all assigned families.

Every stack is built as ``lax.scan`` over homogeneous runs of blocks with
stacked parameters (dim ``l``), which keeps the lowered HLO size O(1) in
depth — essential for compiling 512-device programs of 32..81-layer models.
Heterogeneous patterns (VLM cross-attn every 5th layer, Zamba2's shared
attention block every 6th) become scans over *super-blocks*.
"""
from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from .module import pspec
from . import attention as attn
from . import ffn as ffn_mod
from . import ssm as ssm_mod

# ------------------------------------------------------------------ norms ----

def norm_spec(d: int, dtype=jnp.float32):
    return pspec(("m", d), dtype=dtype, init="ones")


def rmsnorm(w, x, eps: float = 1e-5):
    v = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x.astype(jnp.float32) * jax.lax.rsqrt(v + eps)).astype(x.dtype) * w.astype(x.dtype)


# ------------------------------------------------------------- attn block ----

def attn_block_specs(cfg) -> dict:
    dt = cfg.param_dtype
    s = {
        "ln1": norm_spec(cfg.d_model, dt),
        "ln2": norm_spec(cfg.d_model, dt),
        "attn": attn.gqa_specs(cfg.d_model, cfg.n_heads, cfg.n_kv, cfg.head_dim, qkv_bias=cfg.qkv_bias, dtype=dt),
    }
    if cfg.ffn_kind == "moe":
        s["ffn"] = ffn_mod.moe_specs(cfg.d_model, cfg.d_ff, cfg.n_experts, dense_residual=cfg.moe_dense_residual, dtype=dt)
    elif cfg.ffn_kind == "gelu":
        s["ffn"] = ffn_mod.gelu_mlp_specs(cfg.d_model, cfg.d_ff, dt)
    else:
        s["ffn"] = ffn_mod.swiglu_specs(cfg.d_model, cfg.d_ff, dt)
    return s


def attn_block(p, x, cfg, *, cache=None, positions=None, new_counts=None, prefill=False,
               layer=(), slot=None):
    """Pre-norm attention + FFN. Returns (x, new_cache, aux_loss).

    ``new_counts``/``prefill`` thread the continuous-batching chunk metadata
    to :func:`repro.models.attention.gqa_attention` (per-row valid token
    counts; whole-prompt prefill chunk), ``layer`` the layer's index into a
    stacked cache and ``slot`` the cache slot of the chunk's first row."""
    h, new_cache = attn.gqa_attention(
        p["attn"], rmsnorm(p["ln1"], x),
        n_heads=cfg.n_heads, n_kv=cfg.n_kv, head_dim=cfg.head_dim,
        rope_theta=cfg.rope_theta, positions=positions, cache=cache,
        attn_impl=cfg.attn_impl, block=cfg.attn_block, attn_mixed=cfg.attn_mixed,
        new_counts=new_counts, prefill=prefill, layer=layer, slot=slot,
    )
    x = x + h
    aux = jnp.zeros((), jnp.float32)
    if cfg.ffn_kind == "moe":
        f, aux = ffn_mod.moe_ffn(p["ffn"], rmsnorm(p["ln2"], x), n_experts=cfg.n_experts,
                                 top_k=cfg.moe_top_k, capacity_factor=cfg.moe_capacity_factor,
                                 groups=cfg.moe_groups, dispatch=cfg.moe_dispatch)
    elif cfg.ffn_kind == "gelu":
        f = ffn_mod.gelu_mlp(p["ffn"], rmsnorm(p["ln2"], x))
    else:
        f = ffn_mod.swiglu(p["ffn"], rmsnorm(p["ln2"], x))
    return x + f, new_cache, aux


# -------------------------------------------------------------- MLA block ----

def mla_block_specs(cfg) -> dict:
    dt = cfg.param_dtype
    return {
        "ln1": norm_spec(cfg.d_model, dt),
        "ln2": norm_spec(cfg.d_model, dt),
        "attn": attn.mla_specs(cfg.d_model, cfg.n_heads, q_rank=cfg.mla_q_rank, kv_rank=cfg.mla_kv_rank,
                               d_nope=cfg.mla_d_nope, d_rope=cfg.mla_d_rope, d_v=cfg.mla_d_v, dtype=dt),
        "ffn": ffn_mod.swiglu_specs(cfg.d_model, cfg.d_ff, dt),
    }


def mla_block(p, x, cfg, *, cache=None, positions=None, new_counts=None, prefill=False,
              layer=(), slot=None):
    h, new_cache = attn.mla_attention(
        p["attn"], rmsnorm(p["ln1"], x),
        n_heads=cfg.n_heads, d_nope=cfg.mla_d_nope, d_rope=cfg.mla_d_rope, d_v=cfg.mla_d_v,
        rope_theta=cfg.rope_theta, positions=positions, cache=cache,
        attn_impl=cfg.attn_impl, block=cfg.attn_block, attn_mixed=cfg.attn_mixed,
        new_counts=new_counts, prefill=prefill, layer=layer, slot=slot,
    )
    x = x + h
    f = ffn_mod.swiglu(p["ffn"], rmsnorm(p["ln2"], x))
    return x + f, new_cache, jnp.zeros((), jnp.float32)


# ------------------------------------------------------------ cross block ----

def cross_block_specs(cfg) -> dict:
    dt = cfg.param_dtype
    return {
        "ln1": norm_spec(cfg.d_model, dt),
        "ln2": norm_spec(cfg.d_model, dt),
        "attn": attn.cross_attn_specs(cfg.d_model, cfg.n_heads, cfg.n_kv, cfg.head_dim, cfg.enc_dim, dt),
        "ffn": ffn_mod.swiglu_specs(cfg.d_model, cfg.d_ff, dt),
        "gate_attn": pspec(("z", 1), dtype=dt, init="zeros"),
        "gate_ffn": pspec(("z", 1), dtype=dt, init="zeros"),
    }


def cross_block(p, x, enc, cfg):
    """Gated cross-attention block (Llama-3.2-Vision style)."""
    h = attn.cross_attention(p["attn"], rmsnorm(p["ln1"], x), enc,
                             n_heads=cfg.n_heads, n_kv=cfg.n_kv, head_dim=cfg.head_dim,
                             attn_impl=cfg.attn_impl, block=cfg.attn_block,
                             attn_mixed=cfg.attn_mixed)
    x = x + jnp.tanh(p["gate_attn"].astype(x.dtype)) * h
    f = ffn_mod.swiglu(p["ffn"], rmsnorm(p["ln2"], x))
    return x + jnp.tanh(p["gate_ffn"].astype(x.dtype)) * f


# ------------------------------------------------------------- RWKV block ----

def rwkv_block_specs(cfg) -> dict:
    dt = cfg.param_dtype
    d = cfg.d_model
    return {
        "ln1": norm_spec(d, dt),
        "ln2": norm_spec(d, dt),
        "time_mix": ssm_mod.rwkv6_specs(d, cfg.n_heads, dtype=dt),
        # channel mix (token-shifted squared-relu FFN, Finch style)
        "cm_mix": pspec(("p", 2), ("m", d), dtype=dt, init="zeros"),
        "cm_k": pspec(("m", d), ("f", cfg.d_ff), dtype=dt, fan_in=("m",)),
        "cm_v": pspec(("f", cfg.d_ff), ("m", d), dtype=dt, fan_in=("f",)),
        "cm_r": pspec(("m", d), ("m2", d), dtype=dt, fan_in=("m",)),
    }


class RWKVBlockState(NamedTuple):
    time: ssm_mod.RWKVState
    cm_shift: jax.Array  # (B, m)


def rwkv_block(p, x, cfg, *, state: RWKVBlockState | None = None):
    h, tstate = ssm_mod.rwkv6_mix(p["time_mix"], rmsnorm(p["ln1"], x),
                                  n_heads=cfg.n_heads, chunk=cfg.ssm_chunk,
                                  state=state.time if state is not None else None)
    x = x + h
    xn = rmsnorm(p["ln2"], x)
    prev = state.cm_shift[:, None] if state is not None else jnp.zeros_like(xn[:, :1])
    xp = jnp.concatenate([prev, xn[:, :-1]], axis=1)
    mix = p["cm_mix"].astype(x.dtype)
    xk = xn + (xp - xn) * mix[0]
    xr = xn + (xp - xn) * mix[1]
    k = jnp.square(jax.nn.relu(jnp.einsum("bsm,mf->bsf", xk, p["cm_k"].astype(x.dtype))))
    kv = jnp.einsum("bsf,fm->bsm", k, p["cm_v"].astype(x.dtype))
    r = jax.nn.sigmoid(jnp.einsum("bsm,mn->bsn", xr, p["cm_r"].astype(x.dtype)))
    x = x + r * kv
    new_state = RWKVBlockState(time=tstate, cm_shift=xn[:, -1])
    return x, new_state, jnp.zeros((), jnp.float32)


# ------------------------------------------------------------ Mamba block ----

def mamba_block_specs(cfg) -> dict:
    dt = cfg.param_dtype
    return {
        "ln": norm_spec(cfg.d_model, dt),
        "mix": ssm_mod.mamba2_specs(cfg.d_model, d_state=cfg.ssm_state, head_dim=cfg.ssm_head_dim,
                                    expand=cfg.ssm_expand, n_groups=cfg.ssm_groups, dtype=dt),
    }


def mamba_block(p, x, cfg, *, state=None):
    h, new_state = ssm_mod.mamba2_mix(p["mix"], rmsnorm(p["ln"], x),
                                      d_state=cfg.ssm_state, head_dim=cfg.ssm_head_dim,
                                      expand=cfg.ssm_expand, n_groups=cfg.ssm_groups,
                                      chunk=cfg.ssm_chunk, state=state)
    return x + h, new_state, jnp.zeros((), jnp.float32)


# --------------------------------------------------- Zamba2 shared block ----

def shared_attn_block_specs(cfg) -> dict:
    """One shared transformer block + per-application LoRA on the Q proj."""
    dt = cfg.param_dtype
    return {
        "ln1": norm_spec(cfg.d_model, dt),
        "ln2": norm_spec(cfg.d_model, dt),
        "attn": attn.gqa_specs(cfg.d_model, cfg.n_heads, cfg.n_kv, cfg.head_dim, dtype=dt),
        "ffn": ffn_mod.swiglu_specs(cfg.d_model, cfg.d_ff, dt),
    }


def shared_lora_specs(cfg, rank: int = 8) -> dict:
    dt = cfg.param_dtype
    return {
        "lora_a": pspec(("m", cfg.d_model), ("r", rank), dtype=dt, scale=0.01),
        "lora_b": pspec(("r", rank), ("m", cfg.d_model), dtype=dt, init="zeros"),
    }


def shared_attn_block(p_shared, p_lora, x, cfg, *, cache=None, positions=None, window: int | None = None,
                      new_counts=None, layer=()):
    """Shared-weight attention block with per-application LoRA input adapter.

    ``window`` (if set) restricts attention to a trailing window — the
    long-context adaptation for the hybrid arch (see DESIGN.md)."""
    xa = x + jnp.einsum("bsm,mr,rn->bsn", x, p_lora["lora_a"].astype(x.dtype), p_lora["lora_b"].astype(x.dtype))
    h, new_cache = attn.gqa_attention(
        p_shared["attn"], rmsnorm(p_shared["ln1"], xa),
        n_heads=cfg.n_heads, n_kv=cfg.n_kv, head_dim=cfg.head_dim,
        rope_theta=cfg.rope_theta, positions=positions, cache=cache,
        attn_impl=cfg.attn_impl, block=cfg.attn_block, attn_mixed=cfg.attn_mixed,
        new_counts=new_counts, layer=layer,
    )
    x = x + h
    f = ffn_mod.swiglu(p_shared["ffn"], rmsnorm(p_shared["ln2"], x))
    return x + f, new_cache, jnp.zeros((), jnp.float32)
