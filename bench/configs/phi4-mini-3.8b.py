"""Plain float32 reference of the dense decoder in ``phi4-mini-3.8b.json``.

Written from the published description of Phi-4-mini (arXiv:2503.01743;
Hugging Face ``Phi3ForCausalLM``), with the configuration as it is run:

    x_0      = E[token]                                   (tied embedding)
    h        = RMSNorm(x, w_in, eps)           RMSNorm(x, w) = x / sqrt(mean(x^2) + eps) * w
    q, k, v  = h Wq, h Wk, h Wv                (num_attention_heads query heads,
                                                 num_key_value_heads K/V heads;
                                                 query head i reads K/V head i // (H / G))
    q, k     = RoPE(q), RoPE(k)                (rotate-half form on the first
                                                 partial_rotary_factor * head_dim dims,
                                                 theta = rope_theta)
    a        = softmax(q k^T / sqrt(head_dim) + causal mask) v
    x        = x + a Wo
    h        = RMSNorm(x, w_post, eps)
    x        = x + (silu(h W_gate) * (h W_up)) W_down
    logits   = RMSNorm(x, w_final, eps) E^T

Everything is float32 with ``Precision.HIGHEST`` matmuls; the weights are
the bf16 arrays the benchmark drew, upcast one layer at a time inside the
step so that the reference fits beside them. It imports nothing of the
program; it reads the weights by the names the benchmark gave them.

``low=True`` is the control: the same equations with every matmul operand
rounded to float8 (e4m3, one scale per tensor), the precision step below the
configuration's bfloat16.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32


def _q8(x):
    """Round to float8 e4m3 with one scale for the tensor (448 = its max)."""
    s = jnp.max(jnp.abs(x)) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def _mm(eq, a, b, low):
    if low:
        a, b = _q8(a), _q8(b)
    return jnp.einsum(eq, a, b, precision=HI, preferred_element_type=F32)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, theta, rot):
    """x (B, heads, T, D): rotate-half RoPE on the first ``rot`` dims."""
    T = x.shape[2]
    inv = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=F32) / rot))
    ang = jnp.arange(T, dtype=F32)[:, None] * inv[None, :]
    c, s = jnp.cos(ang), jnp.sin(ang)
    xr, xp = x[..., :rot], x[..., rot:]
    x1, x2 = xr[..., : rot // 2], xr[..., rot // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s, xp], axis=-1)


@functools.partial(jax.jit, static_argnames=("heads", "kv", "theta", "rot", "eps", "low"))
def _layer(blocks, l, x, *, heads, kv, theta, rot, eps, low):
    p = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(a, l, keepdims=False).astype(F32), blocks)
    B, T, _ = x.shape
    D = p["attn"]["wq"].shape[-1]
    h = _rms(x, p["ln1"], eps)
    q = _rope(_mm("btm,mhd->bhtd", h, p["attn"]["wq"], low), theta, rot)
    k = _rope(_mm("btm,mgd->bgtd", h, p["attn"]["wk"], low), theta, rot)
    v = _mm("btm,mgd->bgtd", h, p["attn"]["wv"], low)
    qg = q.reshape(B, kv, heads // kv, T, D)
    s = _mm("bgrtd,bgsd->bgrts", qg, k, low) / np.sqrt(D)
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    s = jnp.where(causal, s, -jnp.inf)
    a = _mm("bgrts,bgsd->bgrtd", jax.nn.softmax(s, axis=-1), v, low).reshape(B, heads, T, D)
    x = x + _mm("bhtd,hdm->btm", a, p["attn"]["wo"], low)
    h = _rms(x, p["ln2"], eps)
    f = jax.nn.silu(_mm("btm,mf->btf", h, p["ffn"]["w_gate"], low)) * _mm("btm,mf->btf", h, p["ffn"]["w_up"], low)
    return x + _mm("btf,fm->btm", f, p["ffn"]["w_down"], low)


@functools.partial(jax.jit, static_argnames=("vocab", "eps", "low", "chunks"))
def _head(embed, w_final, x, targets, *, vocab, eps, low, chunks=8):
    """Best logit, its token and the target's logit at every position, over
    the real vocabulary, the tied embedding taken in chunks of rows."""
    h = _rms(x, w_final.astype(F32), eps)
    n = embed.shape[0] // chunks
    scale = jnp.max(jnp.abs(embed)).astype(F32) / 448.0 if low else None

    def step(carry, c):
        best, top, tgt = carry
        e = jax.lax.dynamic_slice_in_dim(embed, c * n, n).astype(F32)
        if low:
            e = (e / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale
            hh = _q8(h)
        else:
            hh = h
        z = jnp.einsum("btm,vm->btv", hh, e, precision=HI, preferred_element_type=F32)
        ids = c * n + jnp.arange(n)
        z = jnp.where(ids < vocab, z, -jnp.inf)
        cmax, carg = z.max(-1), c * n + z.argmax(-1)
        hit = (targets >= c * n) & (targets < (c + 1) * n)
        zt = jnp.take_along_axis(z, jnp.clip(targets - c * n, 0, n - 1)[..., None], -1)[..., 0]
        return (jnp.maximum(best, cmax), jnp.where(cmax > best, carg, top),
                jnp.where(hit, zt, tgt)), None

    B, T = targets.shape
    init = (jnp.full((B, T), -jnp.inf, F32), jnp.zeros((B, T), jnp.int32), jnp.full((B, T), -jnp.inf, F32))
    (best, top, tgt), _ = jax.lax.scan(step, init, jnp.arange(chunks))
    return best, top, tgt


def logit_stats(weights, config: dict, tokens, targets, *, low: bool = False, rows: int = 2):
    """For token rows (R, T) from position 0: the best logit, the token that
    has it, and the logit of ``targets`` at every position, as numpy arrays
    (R, T). ``weights`` is the tree the benchmark drew: ``embed``,
    ``final_norm`` and per-layer stacks under ``blocks``."""
    c = config
    if weights["embed"].shape[0] % 8:
        raise ValueError("the head takes the embedding rows in 8 equal chunks")
    heads, kv = c["num_attention_heads"], c["num_key_value_heads"]
    D = c.get("head_dim") or c["hidden_size"] // heads
    rot = int(round(D * c.get("partial_rotary_factor", 1.0)))
    eps = float(c["rms_norm_eps"])
    R = tokens.shape[0]
    pad = (-R) % rows
    tokens = np.concatenate([tokens, np.zeros((pad,) + tokens.shape[1:], tokens.dtype)])
    targets = np.concatenate([targets, np.zeros((pad,) + targets.shape[1:], targets.dtype)])
    embed = weights["embed"]
    xs = [jnp.take(embed, jnp.asarray(tokens[i:i + rows]), axis=0).astype(F32)
          for i in range(0, len(tokens), rows)]
    for l in range(c["num_hidden_layers"]):
        xs = [_layer(weights["blocks"], l, x, heads=heads, kv=kv, theta=float(c["rope_theta"]),
                     rot=rot, eps=eps, low=low) for x in xs]
    out = [_head(embed, weights["final_norm"], x, jnp.asarray(targets[i * rows:(i + 1) * rows]),
                 vocab=c["vocab_size"], eps=eps, low=low)
           for i, x in enumerate(xs)]
    best, top, tgt = (np.concatenate([np.asarray(o[j]) for o in out])[:R] for j in range(3))
    return best, top, tgt
