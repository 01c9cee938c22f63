"""Pallas kernel sweeps: every kernel x shapes x dtypes vs the ref.py oracle
(interpret=True executes the kernel body on CPU)."""
import numpy as np
import pytest

import sys, os
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax.numpy as jnp

from repro.kernels import ops, ref

RNG = np.random.default_rng(42)

ALL_MAJORS = ["I/I/K", "I/I/J", "I/K/K", "I/K/J", "J/I/K", "J/I/J", "J/K/K", "J/K/J"]


def _gemm_operands(M, N, K, majors, dtype):
    _, aM, bM = majors.split("/")
    a = jnp.asarray(RNG.standard_normal((K, M) if aM == "K" else (M, K)), dtype)
    b = jnp.asarray(RNG.standard_normal((N, K) if bM == "J" else (K, N)), dtype)
    return a, b


@pytest.mark.parametrize("majors", ALL_MAJORS)
def test_gemm_all_layout_configs(majors):
    a, b = _gemm_operands(64, 48, 32, majors, jnp.float32)
    out = ops.gemm(a, b, majors=majors, impl="interpret", bm=32, bn=16, bk=16)
    np.testing.assert_allclose(out, ref.gemm_ref(a, b, majors=majors), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("shape", [(32, 32, 32), (128, 64, 32), (64, 128, 256)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gemm_shape_dtype_sweep(shape, dtype):
    M, N, K = shape
    a, b = _gemm_operands(M, N, K, "I/I/K", dtype)
    out = ops.gemm(a, b, majors="I/I/K", impl="interpret", bm=32, bn=32, bk=32)
    expect = ref.gemm_ref(a, b, majors="I/I/K")
    # tolerance scales with the contraction length (accumulation order differs)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(expect, np.float32), rtol=tol, atol=tol)


@pytest.mark.parametrize("majors", ["I/I/K", "J/K/J", "I/K/J", "J/I/K"])
def test_gemm_accumulate_input(majors):
    """The SUMMA inner-step path: C = acc + A @ B, with acc in the output
    orientation, across multiple k blocks (acc must load exactly once)."""
    M, N, K = 64, 48, 32
    a, b = _gemm_operands(M, N, K, majors, jnp.float32)
    c_shape = (N, M) if majors.split("/")[0] == "J" else (M, N)
    acc = jnp.asarray(RNG.standard_normal(c_shape), jnp.float32)
    out = ops.gemm(a, b, acc, majors=majors, impl="interpret", bm=32, bn=16, bk=16)
    np.testing.assert_allclose(out, ref.gemm_ref(a, b, acc, majors=majors), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("majors", ["I/I/K", "J/K/J", "I/K/J", "J/I/K"])
def test_gemm_panel_rotation(majors):
    """Buffer-rotation SUMMA step: accumulate A @ B into j-block jb of a
    wider panel, preserving every other block (in-place aliased write),
    with the rotation index a traced scalar."""
    import jax

    M, N, K, NB = 64, 16, 32, 4
    a, b = _gemm_operands(M, N, K, majors, jnp.float32)
    c_major = majors.split("/")[0]
    panel_shape = (N * NB, M) if c_major == "J" else (M, N * NB)
    panel = jnp.asarray(RNG.standard_normal(panel_shape), jnp.float32)
    for jb in [0, 1, 3]:
        want = ref.gemm_panel_ref(a, b, panel, jb, majors=majors)
        got = ops.gemm_panel(a, b, panel, jb, majors=majors, impl="interpret",
                             bm=32, bn=8, bk=16)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
        # untouched blocks are preserved bit for bit
        got = np.asarray(got)
        if c_major == "J":
            mask = np.ones(panel_shape, bool); mask[jb * N:(jb + 1) * N, :] = False
        else:
            mask = np.ones(panel_shape, bool); mask[:, jb * N:(jb + 1) * N] = False
        assert np.array_equal(got[mask], np.asarray(panel)[mask]), (majors, jb)
    # traced rotation index (the per-rank SUMMA case)
    f = jax.jit(lambda jb: ops.gemm_panel(a, b, panel, jb, majors=majors,
                                          impl="interpret", bm=32, bn=8, bk=16))
    np.testing.assert_allclose(
        f(jnp.int32(2)), ref.gemm_panel_ref(a, b, panel, 2, majors=majors),
        rtol=1e-5, atol=1e-5)


def test_gemm_panel_rejects_bad_panel():
    a, b = _gemm_operands(32, 16, 32, "I/I/K", jnp.float32)
    with pytest.raises(ValueError):
        ops.gemm_panel(a, b, jnp.zeros((32, 17), jnp.float32), 0,
                       majors="I/I/K", impl="interpret")


def test_gemm_acc_shape_mismatch_rejected():
    a, b = _gemm_operands(32, 32, 32, "I/I/K", jnp.float32)
    with pytest.raises(ValueError):
        ops.gemm(a, b, jnp.zeros((16, 32), jnp.float32), majors="I/I/K", impl="interpret")


def test_gemm_rejects_bad_blocks():
    a, b = _gemm_operands(30, 30, 30, "I/I/K", jnp.float32)
    with pytest.raises(ValueError):
        ops.gemm(a, b, majors="I/I/K", impl="interpret", bm=16, bn=16, bk=16)


@pytest.mark.parametrize("hq,hkv", [(4, 4), (4, 2), (8, 1)])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_gqa(hq, hkv, causal):
    B, S, D = 2, 128, 32
    q = jnp.asarray(RNG.standard_normal((B, hq, S, D)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((B, hkv, S, D)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((B, hkv, S, D)), jnp.float32)
    out = ops.flash_attention(q, k, v, causal=causal, impl="interpret", bq=32, bk=32)
    expect = ref.attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(out, expect, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-4), (jnp.bfloat16, 3e-2)])
def test_flash_attention_dtypes(dtype, tol):
    B, H, S, D = 1, 2, 64, 16
    q = jnp.asarray(RNG.standard_normal((B, H, S, D)), dtype)
    k = jnp.asarray(RNG.standard_normal((B, H, S, D)), dtype)
    v = jnp.asarray(RNG.standard_normal((B, H, S, D)), dtype)
    out = ops.flash_attention(q, k, v, impl="interpret", bq=16, bk=16)
    expect = ref.attention_ref(q, k, v)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(expect, np.float32), rtol=tol, atol=tol
    )


def test_blockwise_ref_matches_dense():
    """The model-stack attention (pure-jnp blockwise) == dense oracle."""
    B, Hq, Hkv, S, D = 2, 4, 2, 192, 16
    q = jnp.asarray(RNG.standard_normal((B, Hq, S, D)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((B, Hkv, S, D)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((B, Hkv, S, D)), jnp.float32)
    for block in (32, 64, 192):
        out = ref.blockwise_attention_ref(q, k, v, block=block)
        np.testing.assert_allclose(out, ref.attention_ref(q, k, v), rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("shape", [(64, 32), (3, 64, 32), (2, 2, 32, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int32])
def test_transpose_tiled(shape, dtype):
    if dtype == jnp.int32:
        x = jnp.asarray(RNG.integers(0, 100, shape), dtype)
    else:
        x = jnp.asarray(RNG.standard_normal(shape), dtype)
    out = ops.transpose_tiled(x, impl="interpret", bm=16, bn=16)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref.transpose_ref(x)))


def test_flash_attention_long_context_blocks():
    """512-wide blocks over 1k tokens — the prefill configuration, scaled down."""
    B, H, S, D = 1, 2, 1024, 32
    q = jnp.asarray(RNG.standard_normal((B, H, S, D)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((B, H, S, D)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((B, H, S, D)), jnp.float32)
    out = ops.flash_attention(q, k, v, impl="interpret", bq=512, bk=512)
    np.testing.assert_allclose(out, ref.attention_ref(q, k, v), rtol=3e-4, atol=3e-4)


# ------------------------------------------------- ragged seq shapes ---------

@pytest.mark.parametrize("S", [100, 30, 3])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_ragged_seq(S, causal):
    """Seq lengths that do not divide (or are smaller than) the block sizes:
    the kernel pads to block multiples and masks the padded keys, so ragged
    seq shards (ragged_seq_extents) use it directly."""
    B, Hq, Hkv, D = 2, 4, 2, 16
    q = jnp.asarray(RNG.standard_normal((B, Hq, S, D)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((B, Hkv, S, D)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((B, Hkv, S, D)), jnp.float32)
    out = ops.flash_attention(q, k, v, causal=causal, impl="interpret", bq=32, bk=32)
    assert out.shape == q.shape
    expect = ref.attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(out, expect, rtol=2e-4, atol=2e-4)


def test_flash_attention_seq_smaller_than_block():
    """S < bq and S < bk (the S=100, block=512 prefill-tail case)."""
    B, H, S, D = 1, 2, 100, 16
    q = jnp.asarray(RNG.standard_normal((B, H, S, D)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((B, H, S, D)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((B, H, S, D)), jnp.float32)
    out = ops.flash_attention(q, k, v, impl="interpret", bq=512, bk=512)
    np.testing.assert_allclose(out, ref.attention_ref(q, k, v), rtol=2e-4, atol=2e-4)


# ------------------------------------------- carry-state flash kernel --------

def _chain(q, k, v, R, *, causal=True, valid_len=None, bq=32, bk=32):
    """Run the carry kernel over the R KV chunks in block order and
    normalize — the ring-step composition (offsets as traced scalars, the
    shard_map axis_index case)."""
    Sl = k.shape[2] // R
    carry = None
    for t in range(R):
        kb = k[:, :, t * Sl:(t + 1) * Sl]
        vb = v[:, :, t * Sl:(t + 1) * Sl]
        carry = ops.flash_attention_carry(
            q, kb, vb, carry, q_offset=jnp.int32(0), k_offset=jnp.int32(t * Sl),
            valid_len=valid_len, causal=causal, impl="interpret", bq=bq, bk=bk)
    acc, m, l = carry
    l = jnp.where(l == 0.0, 1.0, l)
    return (acc / l[..., None]).astype(q.dtype)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_carry_chain_bitwise_vs_single_shot(causal):
    """The tentpole invariant: R carry-kernel steps over the R KV chunks of a
    sequence compose to EXACTLY the single-shot flash kernel at f32 — same
    arithmetic, same block boundaries, the state just round-trips through
    HBM between pallas_calls instead of living in VMEM scratch."""
    B, Hq, Hkv, S, D, R = 2, 4, 2, 128, 16, 4
    q = jnp.asarray(RNG.standard_normal((B, Hq, S, D)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((B, Hkv, S, D)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((B, Hkv, S, D)), jnp.float32)
    single = ops.flash_attention(q, k, v, causal=causal, impl="interpret",
                                 bq=32, bk=32)
    chained = _chain(q, k, v, R, causal=causal, bq=32, bk=32)
    assert np.array_equal(np.asarray(chained), np.asarray(single)), (
        np.abs(np.asarray(chained) - np.asarray(single)).max())


@pytest.mark.parametrize("hq,hkv", [(4, 2), (8, 1)])
def test_flash_carry_gqa_vs_ref(hq, hkv):
    """Per-step carry state (GQA group mapping) vs the jnp merge oracle."""
    B, S, D, R = 2, 64, 16, 4
    Sl = S // R
    q = jnp.asarray(RNG.standard_normal((B, hq, Sl, D)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((B, hkv, S, D)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((B, hkv, S, D)), jnp.float32)
    carry = cref = None
    me = 2  # resident rank: q chunk sits at global offset me*Sl
    for t in range(R):
        kb = k[:, :, t * Sl:(t + 1) * Sl]
        vb = v[:, :, t * Sl:(t + 1) * Sl]
        carry = ops.flash_attention_carry(
            q, kb, vb, carry, q_offset=me * Sl, k_offset=t * Sl,
            causal=True, impl="interpret", bq=16, bk=16)
        cref = ref.flash_carry_ref(q, kb, vb, cref, q_offset=me * Sl,
                                   k_offset=t * Sl, causal=True)
        for got, want, name in zip(carry, cref, ("acc", "m", "l")):
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=2e-4, atol=2e-4, err_msg=f"step {t} {name}")


def test_flash_carry_ragged_valid_len():
    """Ragged ring shards: global positions >= valid_len are masked; a step
    whose KV block is entirely padding must leave the carry semantics intact
    (self-healing -inf merge)."""
    B, H, S, D, R = 1, 2, 64, 16, 4
    Sl = S // R
    valid = 34  # rank 2's block is half padding, rank 3's all padding
    q = jnp.asarray(RNG.standard_normal((B, H, Sl, D)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((B, H, S, D)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((B, H, S, D)), jnp.float32)
    carry = cref = None
    for t in range(R):
        kb = k[:, :, t * Sl:(t + 1) * Sl]
        vb = v[:, :, t * Sl:(t + 1) * Sl]
        carry = ops.flash_attention_carry(
            q, kb, vb, carry, q_offset=0, k_offset=t * Sl, valid_len=valid,
            causal=False, impl="interpret", bq=16, bk=16)
        cref = ref.flash_carry_ref(q, kb, vb, cref, q_offset=0, k_offset=t * Sl,
                                   valid_len=valid, causal=False)
    acc, m, l = carry
    out = acc / jnp.where(l == 0.0, 1.0, l)[..., None]
    aref, mref, lref = cref
    outref = aref / jnp.where(lref == 0.0, 1.0, lref)[..., None]
    np.testing.assert_allclose(np.asarray(out), np.asarray(outref),
                               rtol=2e-4, atol=2e-4)
    # and the composition over valid keys == dense attention on them
    dense = ref.attention_ref(q, k[:, :, :valid], v[:, :, :valid], causal=False)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(dense, np.float32),
                               rtol=2e-4, atol=2e-4)


def test_flash_carry_ragged_q_chunk():
    """Resident Q chunks that do not divide the block size pad-and-mask, and
    the padded rows' carry stays at the (0, -inf, 0) identity across steps."""
    B, H, Sq, Skv, D = 1, 2, 30, 30, 16
    q = jnp.asarray(RNG.standard_normal((B, H, Sq, D)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((B, H, Skv, D)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((B, H, Skv, D)), jnp.float32)
    carry = ops.flash_attention_carry(q, k, v, None, q_offset=0, k_offset=0,
                                      causal=True, impl="interpret", bq=32, bk=32)
    cref = ref.flash_carry_ref(q, k, v, None, q_offset=0, k_offset=0, causal=True)
    for got, want, name in zip(carry, cref, ("acc", "m", "l")):
        assert got.shape == want.shape, name
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4, err_msg=name)


# ------------------------------------------------------ flash decode -------

@pytest.mark.parametrize("hq,hkv", [(4, 4), (4, 2), (8, 1)])
def test_flash_decode_gqa(hq, hkv):
    """Flash decode vs the dense oracle: per-row cache lengths, GQA group
    stacking, T % bk != 0 (padded tail masked)."""
    B, T, D = 3, 96, 16
    q = jnp.asarray(RNG.standard_normal((B, hq, 1, D)), jnp.float32)
    kc = jnp.asarray(RNG.standard_normal((B, hkv, T, D)), jnp.float32)
    vc = jnp.asarray(RNG.standard_normal((B, hkv, T, D)), jnp.float32)
    clen = jnp.asarray([5, 50, 96], jnp.int32)
    out = ops.flash_decode(q, kc, vc, clen, impl="interpret", bk=40)
    expect = ref.decode_attention_ref(q, kc, vc, clen)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=2e-4, atol=2e-4)


def test_flash_decode_chunk_positions():
    """Multi-token chunks with per-row start positions: cache slot t is
    visible to query j iff t <= q_start[b] + j — continuous batching's
    per-slot causal mask."""
    B, H, G, S, T, D = 2, 4, 2, 4, 64, 16
    q = jnp.asarray(RNG.standard_normal((B, H, S, D)), jnp.float32)
    kc = jnp.asarray(RNG.standard_normal((B, G, T, D)), jnp.float32)
    vc = jnp.asarray(RNG.standard_normal((B, G, T, D)), jnp.float32)
    start = jnp.asarray([10, 0], jnp.int32)
    clen = jnp.asarray([14, 4], jnp.int32)
    out = ops.flash_decode(q, kc, vc, clen, q_start=start, impl="interpret", bk=32)
    expect = ref.decode_attention_ref(q, kc, vc, clen, q_start=start)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=2e-4, atol=2e-4)


def test_flash_decode_chunk_row_blocks():
    """A prefill chunk longer than the query block: rows split into bq-row
    blocks (S % bq != 0 pads the last one), each head looped inside the
    program, per-row causality from the row's start position."""
    B, H, G, S, T, D = 2, 6, 2, 40, 96, 16
    q = jnp.asarray(RNG.standard_normal((B, H, S, D)), jnp.float32)
    kc = jnp.asarray(RNG.standard_normal((B, G, T, D)), jnp.float32)
    vc = jnp.asarray(RNG.standard_normal((B, G, T, D)), jnp.float32)
    start = np.array([0, 37], np.int32)
    clen = jnp.asarray(start + S, jnp.int32)
    out = ops.flash_decode(q, kc, vc, clen, q_start=jnp.asarray(start),
                           impl="interpret", bq=16, bk=32)
    expect = ref.decode_attention_ref(q, kc, vc, clen, q_start=jnp.asarray(start))
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=2e-4, atol=2e-4)


def test_flash_decode_vs_model_decode_tolerance():
    """The kernel path agrees with the model-facing jnp decode within bf16
    rounding tolerance (the jnp path rounds normalized probabilities to the
    cache dtype; the kernel rounds the unnormalized tile)."""
    from repro.models.attention import attention_decode

    B, H, G, T, D = 2, 4, 2, 64, 16
    q = jnp.asarray(RNG.standard_normal((B, H, 1, D)), jnp.bfloat16)
    kc = jnp.asarray(RNG.standard_normal((B, G, T, D)), jnp.bfloat16)
    vc = jnp.asarray(RNG.standard_normal((B, G, T, D)), jnp.bfloat16)
    clen = jnp.asarray([30, 64], jnp.int32)
    jnp_o = attention_decode(q, kc, vc, clen, impl="jnp")
    ker_o = attention_decode(q, kc, vc, clen, impl="interpret")
    np.testing.assert_allclose(np.asarray(jnp_o, np.float32),
                               np.asarray(ker_o, np.float32),
                               rtol=3e-2, atol=3e-2)


def test_flash_decode_bf16_cache():
    B, H, G, T, D = 2, 4, 2, 64, 16
    q = jnp.asarray(RNG.standard_normal((B, H, 1, D)), jnp.bfloat16)
    kc = jnp.asarray(RNG.standard_normal((B, G, T, D)), jnp.bfloat16)
    vc = jnp.asarray(RNG.standard_normal((B, G, T, D)), jnp.bfloat16)
    clen = jnp.asarray([30, 64], jnp.int32)
    out = ops.flash_decode(q, kc, vc, clen, impl="interpret", bk=32)
    expect = ref.decode_attention_ref(q, kc, vc, clen)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32),
                               rtol=3e-2, atol=3e-2)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_carry_custom_vjp_grad_parity(causal):
    """The carry kernel's custom VJP (satellite of the ZeRO train PR): sp_ring
    training takes the Pallas forward, and its gradients — via the jnp-oracle
    recompute backward — must match differentiating the reference merge
    directly, including int offsets as traced operands (float0 cotangents)."""
    import jax

    B, Hq, Hkv, S, D = 1, 4, 2, 64, 16
    q = jnp.asarray(RNG.standard_normal((B, Hq, S, D)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((B, Hkv, S, D)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((B, Hkv, S, D)), jnp.float32)

    def norm(carry):
        acc, m, l = carry
        l = jnp.where(l == 0.0, 1.0, l)
        return acc / l[..., None]

    def loss_kernel(q, k, v):
        c = ops.flash_attention_carry(
            q, k, v, None, q_offset=jnp.int32(0), k_offset=jnp.int32(0),
            causal=causal, impl="interpret", bq=32, bk=32)
        return jnp.sum(jnp.square(norm(c)))

    def loss_ref(q, k, v):
        c = ref.flash_carry_ref(q, k, v, None, q_offset=0, k_offset=0,
                                causal=causal)
        return jnp.sum(jnp.square(norm(c)))

    g_kern = jax.grad(loss_kernel, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_kern, g_ref, "qkv"):
        d = float(jnp.max(jnp.abs(a - b)))
        assert d < 5e-5, (name, d)

    # two chained ring steps: grads flow through the threaded carry state
    Sl = S // 2

    def loss_chain(q, k, v):
        c = None
        for t in range(2):
            c = ops.flash_attention_carry(
                q, k[:, :, t * Sl:(t + 1) * Sl], v[:, :, t * Sl:(t + 1) * Sl],
                c, q_offset=jnp.int32(0), k_offset=jnp.int32(t * Sl),
                causal=causal, impl="interpret", bq=32, bk=32)
        return jnp.sum(jnp.square(norm(c)))

    g_chain = jax.grad(loss_chain, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_chain, g_ref, "qkv"):
        d = float(jnp.max(jnp.abs(a - b)))
        assert d < 5e-5, (name, d)
