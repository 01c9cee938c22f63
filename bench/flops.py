"""Operations and bytes the work requires, from shapes and real lengths.

Every count here is of the work the algorithm needs, never of what one
implementation happens to compute: real tokens only, no padding rows or
positions, causal attention over the positions a token may see. So the
count stays the same whatever implements the work, and a share of a peak
computed from it cannot pass 100% unless the time leaves out part of the
work.

The dense decoder counts take the configuration's published keys
(``hidden_size``, ``num_attention_heads``, ...), as in the configuration
files under ``bench/configs``.
"""
from __future__ import annotations


def _dims(c: dict):
    d = c["hidden_size"]
    h = c["num_attention_heads"]
    g = c["num_key_value_heads"]
    hd = c.get("head_dim") or d // h
    return d, h, g, hd, c["intermediate_size"], c["num_hidden_layers"], c["vocab_size"]


def dense_layer_params(c: dict) -> int:
    """Matmul weights of one decoder layer: q, k, v, o and a gated FFN."""
    d, h, g, hd, ff, _, _ = _dims(c)
    return d * h * hd + 2 * d * g * hd + h * hd * d + 3 * d * ff


def attention_flops(c: dict, seen: int) -> int:
    """Scores and weighted sum of one query token over ``seen`` positions,
    all layers: 2 * head_dim operations per position for q.k and as many
    for p.v, per head."""
    _, h, _, hd, _, L, _ = _dims(c)
    return 4 * L * h * hd * seen


def decode_flops(c: dict, seen: list[int]) -> int:
    """One decode step: each active row is one token that sees ``seen[i]``
    positions (itself included) and gets logits over the vocabulary."""
    d, *_, L, V = _dims(c)
    per_token = 2 * L * dense_layer_params(c) + 2 * d * V
    return sum(per_token + attention_flops(c, n) for n in seen)


def causal_pairs(n: int) -> int:
    """Query-key pairs of causal attention over ``n`` tokens from position 0."""
    return n * (n + 1) // 2


def prefill_flops(c: dict, fed: list[int]) -> int:
    """One prefill chunk: row ``i`` feeds ``fed[i]`` prompt tokens from
    position 0; only each row's last token is projected to logits."""
    d, _, _, _, _, L, V = _dims(c)
    total = 0
    for n in fed:
        if n <= 0:
            continue
        total += 2 * L * dense_layer_params(c) * n + 2 * d * V
        total += attention_flops(c, 1) * causal_pairs(n)
    return total


def decode_attention_cost(c: dict, seen: list[int], itemsize: int = 2) -> tuple[int, int]:
    """(operations, bytes) of the attention kernel over a decode step, all
    layers: each row reads K and V at its ``seen`` positions, reads its query
    and writes its output."""
    _, h, g, hd, _, L, _ = _dims(c)
    flops = sum(attention_flops(c, n) for n in seen)
    kv = sum(2 * n * g * hd for n in seen)
    qo = len(seen) * 2 * h * hd
    return flops, L * (kv + qo) * itemsize


def prefill_attention_cost(c: dict, fed: list[int], itemsize: int = 2) -> tuple[int, int]:
    """(operations, bytes) of the attention kernel over a prefill chunk, all
    layers: causal attention over each row's ``fed`` tokens; q, k and v read
    once and the output written once."""
    _, h, g, hd, _, L, _ = _dims(c)
    flops = sum(attention_flops(c, 1) * causal_pairs(n) for n in fed if n > 0)
    elems = sum(n * (2 * h + 2 * g) * hd for n in fed if n > 0)
    return flops, L * elems * itemsize


def roofline_time(flops: float, nbytes: float, peaks: dict) -> float:
    """The least time the chip could take: the larger of the operations at
    the bf16 peak and the bytes at the memory bandwidth."""
    return max(flops / peaks["bf16_flops"], nbytes / peaks["hbm_bw"])
