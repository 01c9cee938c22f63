"""The operation and byte counts against hand counts at small shapes."""
from bench import flops

C = {"hidden_size": 8, "num_attention_heads": 2, "num_key_value_heads": 1,
     "intermediate_size": 16, "num_hidden_layers": 3, "vocab_size": 10}
# head_dim 4: q 8x8, k 8x4, v 8x4, o 8x8, FFN 3 x 8x16
LAYER = 64 + 32 + 32 + 64 + 3 * 128


def test_layer_params():
    assert flops.dense_layer_params(C) == LAYER == 576


def test_decode_flops():
    # per token: 2 * 3 layers * 576 + logits 2 * 8 * 10; attention 4 * 3 * 2 * 4 * seen
    per = 2 * 3 * 576 + 160
    assert flops.decode_flops(C, [5]) == per + 96 * 5
    assert flops.decode_flops(C, [5, 1]) == 2 * per + 96 * 6
    assert flops.decode_flops(C, []) == 0


def test_prefill_flops_counts_real_tokens_only():
    # 3 tokens: 3 x matmuls, causal pairs 1 + 2 + 3 = 6, logits of one token
    assert flops.causal_pairs(3) == 6
    one = 2 * 3 * 576 * 3 + 160 + 96 * 6
    assert flops.prefill_flops(C, [3]) == one
    assert flops.prefill_flops(C, [3, 0]) == one  # an idle row adds nothing


def test_attention_costs():
    f, b = flops.decode_attention_cost(C, [5, 2], itemsize=2)
    assert f == 96 * 7
    # K and V: 7 positions x 1 kv head x 4 dims x 2; q and out: 2 rows x 2 heads x 4 x 2
    assert b == 3 * (2 * 7 * 4 + 2 * 2 * 2 * 4) * 2
    f, b = flops.prefill_attention_cost(C, [3], itemsize=2)
    assert f == 96 * 6
    assert b == 3 * 3 * (2 * 2 + 2 * 1) * 4 * 2


def test_roofline():
    peaks = {"bf16_flops": 100.0, "hbm_bw": 10.0}
    assert flops.roofline_time(1000, 50, peaks) == 10.0
    assert flops.roofline_time(100, 50, peaks) == 5.0
