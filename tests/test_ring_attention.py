"""Sequence-parallel ring attention (the model stack's double-buffered ring).

Three layers of evidence, mirroring the SUMMA acceptance tests:

  * numerics — the ring (both variants) matches the single-device flash
    reference, and the double-buffered and blocking variants are
    bit-identical at f32 (only the request issue point differs, never the
    math);
  * model integration — ``gqa_attention`` under an ``sp_ring`` recipe
    matches the same op with no recipe at all;
  * static overlap proof — the compiled sp-ring trace contains exactly
    2*(R-1) ring ``collective-permute``s (K and V per step) and 0 serialized
    collectives of ANY kind under the kind-generic classifier, even though
    the rotated payloads are *produced* by the projection GEMMs.
"""


def test_ring_attention_matches_reference_and_variants_bitwise(distributed):
    out = distributed(
        """
import numpy as np, jax, jax.numpy as jnp
from repro.core import make_mesh
from repro.models import attention as attn

mesh = make_mesh((2, 4), ('data', 'model'))
rng = np.random.default_rng(3)
B, H, G, S, D = 2, 4, 2, 32, 8
q = jnp.asarray(rng.standard_normal((B, H, S, D)), jnp.float32)
k = jnp.asarray(rng.standard_normal((B, G, S, D)), jnp.float32)
v = jnp.asarray(rng.standard_normal((B, G, S, D)), jnp.float32)

for causal in (True, False):
    ref = attn.attention_seq(q, k, v, causal=causal, block=8)
    db = attn.ring_attention_seq(q, k, v, mesh=mesh, causal=causal, double_buffer=True)
    bl = attn.ring_attention_seq(q, k, v, mesh=mesh, causal=causal, double_buffer=False)
    # MPI_Isend-before-compute vs compute-then-send: identical math
    assert np.array_equal(np.asarray(db), np.asarray(bl)), causal
    assert np.abs(np.asarray(db) - np.asarray(ref)).max() < 1e-5, causal

# the train step differentiates through the ring: grads must match the
# single-device reference
g_ref = jax.grad(lambda q: attn.attention_seq(q, k, v, block=8).sum())(q)
g_ring = jax.grad(lambda q: attn.ring_attention_seq(q, k, v, mesh=mesh).sum())(q)
assert np.abs(np.asarray(g_ring) - np.asarray(g_ref)).max() < 1e-4

# mismatched q/kv seq lens still fail loudly at trace time
try:
    attn.ring_attention_seq(q[:, :, :30], k, v, mesh=mesh)
    raise SystemExit('expected ValueError')
except ValueError:
    pass
print('OK')
"""
    )
    assert "OK" in out


def test_ring_attention_ragged_seq_shards(distributed):
    """ISSUE 4: sequence lengths that do NOT divide the ring run as ragged
    seq shards — padded capacity KV blocks ride the ring, padded key
    positions are masked, and the numerics match the dense reference for
    both variants (bit-identically to each other), grads included."""
    out = distributed(
        """
import numpy as np, jax, jax.numpy as jnp
from repro.core import make_mesh
from repro.kernels.ref import attention_ref
from repro.models import attention as attn
from repro.models.sharding import ragged_seq_extents

mesh = make_mesh((2, 4), ('data', 'model'))
rng = np.random.default_rng(7)
B, H, G, D = 2, 4, 2, 8
# 30 % 4 = 2 (last rank short); 3 < 4 (two ranks hold pure padding)
for S in (30, 3):
    cap, exts = ragged_seq_extents(S, 4)
    assert sum(exts) == S and max(exts) == cap
    q = jnp.asarray(rng.standard_normal((B, H, S, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, G, S, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, G, S, D)), jnp.float32)
    for causal in (True, False):
        ref = attention_ref(q, k, v, causal=causal)
        db = attn.ring_attention_seq(q, k, v, mesh=mesh, causal=causal,
                                     double_buffer=True)
        bl = attn.ring_attention_seq(q, k, v, mesh=mesh, causal=causal,
                                     double_buffer=False)
        assert db.shape == q.shape, (S, db.shape)
        assert np.array_equal(np.asarray(db), np.asarray(bl)), (S, causal)
        assert np.abs(np.asarray(db) - np.asarray(ref)).max() < 1e-5, (S, causal)
    g_ref = jax.grad(lambda q: attention_ref(q, k, v).sum())(q)
    g_ring = jax.grad(lambda q: attn.ring_attention_seq(q, k, v, mesh=mesh).sum())(q)
    assert np.abs(np.asarray(g_ring) - np.asarray(g_ref)).max() < 1e-4, S
print('OK')
"""
    )
    assert "OK" in out


def test_gqa_attention_sp_ring_recipe_ragged_seq(distributed):
    """The model path on a ragged sequence: gqa_attention under an sp_ring
    recipe with S % model != 0 takes the ring (ragged shards) and matches
    the recipe-free reference."""
    out = distributed(
        """
import numpy as np, jax, jax.numpy as jnp
from types import SimpleNamespace
from repro.core import make_mesh
from repro.models import attention as attn
from repro.models.sharding import make_recipe, use_recipe

cfg = SimpleNamespace(n_heads=4, n_kv=2, head_dim=16, d_model=64, d_ff=128,
                      vocab_padded=256, n_experts=0, family='dense')
mesh = make_mesh((2, 4), ('data', 'model'))
recipe = make_recipe(cfg, mesh, attn_mode='sp_ring')

rng = np.random.default_rng(11)
p = {
    'wq': jnp.asarray(rng.standard_normal((64, 4, 16)) * 0.1, jnp.float32),
    'wk': jnp.asarray(rng.standard_normal((64, 2, 16)) * 0.1, jnp.float32),
    'wv': jnp.asarray(rng.standard_normal((64, 2, 16)) * 0.1, jnp.float32),
    'wo': jnp.asarray(rng.standard_normal((4, 16, 64)) * 0.1, jnp.float32),
}
S = 42  # 42 % 4 = 2: ragged over the model axis
x = jnp.asarray(rng.standard_normal((2, S, 64)), jnp.float32)

ref, _ = attn.gqa_attention(p, x, n_heads=4, n_kv=2, head_dim=16)
with use_recipe(recipe):
    assert attn._ring_applicable(recipe,
                                 jnp.zeros((2, 4, S, 16)), jnp.zeros((2, 2, S, 16)))
    ring, _ = attn.gqa_attention(p, x, n_heads=4, n_kv=2, head_dim=16)
    ring_bl, _ = attn.gqa_attention(p, x, n_heads=4, n_kv=2, head_dim=16,
                                    sp_ring_double_buffer=False)
assert ring.shape == ref.shape
assert np.array_equal(np.asarray(ring), np.asarray(ring_bl))
assert np.abs(np.asarray(ring) - np.asarray(ref)).max() < 1e-4
print('OK')
"""
    )
    assert "OK" in out


def test_gqa_attention_sp_ring_recipe_matches_no_recipe(distributed):
    """The model path: the same params and inputs through ``gqa_attention``
    with and without the sp_ring recipe must agree — the ring is a layout
    decision, not a semantic one (and the double-buffered/blocking variants
    are bit-identical through the full op too)."""
    out = distributed(
        """
import numpy as np, jax, jax.numpy as jnp
from types import SimpleNamespace
from repro.core import make_mesh
from repro.models import attention as attn
from repro.models.sharding import make_recipe, use_recipe

cfg = SimpleNamespace(n_heads=4, n_kv=2, head_dim=16, d_model=64, d_ff=128,
                      vocab_padded=256, n_experts=0, family='dense')
mesh = make_mesh((2, 4), ('data', 'model'))
recipe = make_recipe(cfg, mesh, attn_mode='sp_ring')
assert recipe.attn_mode == 'sp' and recipe.sp_ring

rng = np.random.default_rng(11)
p = {
    'wq': jnp.asarray(rng.standard_normal((64, 4, 16)) * 0.1, jnp.float32),
    'wk': jnp.asarray(rng.standard_normal((64, 2, 16)) * 0.1, jnp.float32),
    'wv': jnp.asarray(rng.standard_normal((64, 2, 16)) * 0.1, jnp.float32),
    'wo': jnp.asarray(rng.standard_normal((4, 16, 64)) * 0.1, jnp.float32),
}
x = jnp.asarray(rng.standard_normal((2, 64, 64)), jnp.float32)

ref, _ = attn.gqa_attention(p, x, n_heads=4, n_kv=2, head_dim=16)
with use_recipe(recipe):
    ring, _ = attn.gqa_attention(p, x, n_heads=4, n_kv=2, head_dim=16)
    ring_bl, _ = attn.gqa_attention(p, x, n_heads=4, n_kv=2, head_dim=16,
                                    sp_ring_double_buffer=False)
assert np.array_equal(np.asarray(ring), np.asarray(ring_bl))
assert np.abs(np.asarray(ring) - np.asarray(ref)).max() < 1e-4
print('OK')
"""
    )
    assert "OK" in out


def test_sp_ring_dryrun_zero_serialized_any_kind(distributed):
    """ISSUE 3 acceptance: the sp ring-attention dry-run trace reports
    exactly 2*(R-1) ring transfers and 0 serialized collectives of any kind,
    for the double-buffered AND blocking variants."""
    out = distributed(
        """
from repro.launch.dryrun import sp_ring_dryrun

rep = sp_ring_dryrun(seq=128, grid=(2, 4), verbose=False)
for variant in ('double_buffered', 'blocking'):
    r = rep[variant]
    assert r['serialized'] == 0, (variant, r)
    assert r['exposed_bytes'] == 0.0, (variant, r)
    kinds = r['overlap_by_kind']
    assert list(kinds) == ['collective-permute'], (variant, kinds)
    assert kinds['collective-permute']['overlapped'] == r['expected_ring_transfers'] == 6
    assert kinds['collective-permute']['overlap_fraction'] == 1.0
print('OK')
"""
    )
    assert "OK" in out


def test_ring_attention_kernel_impl_matches_jnp(distributed):
    """ISSUE 8 tentpole: the ring with the carry-state Pallas flash kernel
    (interpret mode) as its per-step compute matches the jnp-merge ring and
    the single-device reference — dense AND ragged shards, causal and not —
    and the double-buffered/blocking variants of the kernel ring stay
    bit-identical (the plan only moves the issue point, never the math)."""
    out = distributed(
        """
import numpy as np, jax, jax.numpy as jnp
from repro.core import make_mesh
from repro.kernels.ref import attention_ref
from repro.models import attention as attn

mesh = make_mesh((2, 4), ('data', 'model'))
rng = np.random.default_rng(21)
B, H, G, D = 2, 4, 2, 16
for S in (32, 30):  # dividing and ragged over R=4
    q = jnp.asarray(rng.standard_normal((B, H, S, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, G, S, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, G, S, D)), jnp.float32)
    for causal in (True, False):
        ref = attention_ref(q, k, v, causal=causal)
        kdb = attn.ring_attention_seq(q, k, v, mesh=mesh, causal=causal,
                                      double_buffer=True, impl='interpret')
        kbl = attn.ring_attention_seq(q, k, v, mesh=mesh, causal=causal,
                                      double_buffer=False, impl='interpret')
        jn = attn.ring_attention_seq(q, k, v, mesh=mesh, causal=causal,
                                     double_buffer=True, impl='jnp')
        assert kdb.shape == q.shape, (S, kdb.shape)
        assert np.array_equal(np.asarray(kdb), np.asarray(kbl)), (S, causal)
        assert np.abs(np.asarray(kdb) - np.asarray(jn)).max() < 1e-5, (S, causal)
        assert np.abs(np.asarray(kdb) - np.asarray(ref)).max() < 1e-5, (S, causal)
print('OK')
"""
    )
    assert "OK" in out


def test_sp_ring_dryrun_kernel_impl_zero_serialized(distributed):
    """The overlap gate holds with the Pallas kernel in the traced program:
    each ring step's pallas_call consumes the held KV block as a sibling of
    the in-flight rotation, so every permute still classifies overlapped."""
    out = distributed(
        """
from repro.launch.dryrun import sp_ring_dryrun

rep = sp_ring_dryrun(seq=64, grid=(2, 4), attn_impl='interpret', verbose=False)
for variant in ('double_buffered', 'blocking'):
    r = rep[variant]
    assert r['serialized'] == 0, (variant, r)
    assert r['overlap_by_kind']['collective-permute']['overlapped'] == 6
    assert r['plan']['agree'], (variant, r['plan'])
print('OK')
"""
    )
    assert "OK" in out


def test_gqa_attention_prefill_chunk_ring_matches_no_recipe(distributed):
    """The serving prefill path: a whole-prompt chunk through the decode-mode
    op (``cache=`` + ``prefill=True``) under an sp_ring recipe runs the ring
    plan on the fresh Q/K/V while the cache fills — output and cache must
    match the same chunk with no recipe, and the ragged pad slice must ride
    behind the output projection (terminal), not reshard mid-graph."""
    out = distributed(
        """
import numpy as np, jax, jax.numpy as jnp
from types import SimpleNamespace
from repro.core import make_mesh
from repro.models import attention as attn
from repro.models.sharding import make_recipe, use_recipe

cfg = SimpleNamespace(n_heads=4, n_kv=2, head_dim=16, d_model=64, d_ff=128,
                      vocab_padded=256, n_experts=0, family='dense')
mesh = make_mesh((2, 4), ('data', 'model'))
recipe = make_recipe(cfg, mesh, attn_mode='sp_ring')

rng = np.random.default_rng(12)
p = {
    'wq': jnp.asarray(rng.standard_normal((64, 4, 16)) * 0.1, jnp.float32),
    'wk': jnp.asarray(rng.standard_normal((64, 2, 16)) * 0.1, jnp.float32),
    'wv': jnp.asarray(rng.standard_normal((64, 2, 16)) * 0.1, jnp.float32),
    'wo': jnp.asarray(rng.standard_normal((4, 16, 64)) * 0.1, jnp.float32),
}
B, S, T = 2, 64, 128
x = jnp.asarray(rng.standard_normal((B, S, 64)), jnp.float32)
positions = jnp.tile(jnp.arange(S), (B, 1))  # prefill chunks start at 0

def fresh_cache():
    return attn.KVCache(k=jnp.zeros((B, 2, T, 16)), v=jnp.zeros((B, 2, T, 16)),
                        length=jnp.zeros((B,), jnp.int32))

kw = dict(n_heads=4, n_kv=2, head_dim=16, positions=positions, prefill=True)
ref, ref_c = attn.gqa_attention(p, x, cache=fresh_cache(), **kw)
with use_recipe(recipe):
    ring, ring_c = attn.gqa_attention(p, x, cache=fresh_cache(), **kw)
assert np.abs(np.asarray(ring) - np.asarray(ref)).max() < 1e-4
assert np.array_equal(np.asarray(ref_c.length), np.asarray(ring_c.length))
assert np.abs(np.asarray(ref_c.k) - np.asarray(ring_c.k)).max() < 1e-5
print('OK')
"""
    )
    assert "OK" in out


def test_one_row_prefill_ring_matches_no_recipe(distributed):
    """The engine's admission prefill under an sp_ring recipe: a one-row
    chunk written into its slot (``decode_step(slot=...)``) runs the ring
    plan on that row, and its logits, lengths, positions and cache match the
    same call with no recipe.  Positions past the row's count hold padding,
    which the ring attends over and the masked kernel does not, so only the
    valid positions are compared."""
    out = distributed(
        """
import dataclasses
import numpy as np, jax, jax.numpy as jnp
from repro import configs
from repro.core import make_mesh
from repro.models import lm
from repro.models.sharding import make_recipe, use_recipe

cfg = dataclasses.replace(configs.get('phi4-mini-3.8b', smoke=True),
                          act_dtype=jnp.float32, param_dtype=jnp.float32)
params = lm.init_model(cfg, jax.random.PRNGKey(0))
B, T, S, slot = 4, 64, 32, 2
state = lm.DecodeState(caches=lm.init_cache(cfg, B, T), positions=jnp.zeros((B,), jnp.int32))
batch = {'tokens': jax.random.randint(jax.random.PRNGKey(1), (1, S), 0, cfg.vocab)}
counts = jnp.asarray([27], jnp.int32)

def step(recipe):
    def f(st, slot):
        with use_recipe(recipe):
            return lm.decode_step(params, st, batch, cfg, new_counts=counts,
                                  prefill=True, slot=slot)
    return jax.jit(f)

recipe = make_recipe(cfg, make_mesh((2, 4), ('data', 'model')), attn_mode='sp_ring')
ring_step = step(recipe)
assert 'collective_permute' in ring_step.lower(state, jnp.int32(slot)).as_text()
ref_logits, ref = step(None)(state, jnp.int32(slot))
logits, got = ring_step(state, jnp.int32(slot))
assert np.abs(np.asarray(logits) - np.asarray(ref_logits)).max() < 1e-4
assert np.array_equal(np.asarray(got.positions), [0, 0, 27, 0])
assert np.array_equal(np.asarray(got.caches.length), np.asarray(ref.caches.length))
assert (np.asarray(got.caches.length)[:, slot] == 27).all()
for a, b in ((got.caches.k, ref.caches.k), (got.caches.v, ref.caches.v)):
    a, b = np.asarray(a), np.asarray(b)
    assert np.abs(a - b)[..., :27, :].max() < 1e-5
    assert not np.delete(a, slot, axis=1).any()
print('OK')
"""
    )
    assert "OK" in out
