"""The paper's SUMMA GEMM (``examples/distributed_gemm.summa_ring_program``)
against the float64 product, on a 2x2 grid of four CPU devices, for every
C/A/B layout configuration of the paper's Fig. 3; and the same program on
operands rounded to bfloat16, which must read beyond the limit.

The measure is the benchmark's (``bench/configs/summa-xl-f32.py``):
max |C - A @ B| / max |A @ B|. At this size on the CPU sound runs read
about 2e-7 and the bf16-rounded control about 2e-3; the limit lies between.
"""
import json

import pytest

from repro.configs.gemm_case_study import LAYOUT_CONFIGS

NI, NJ, NK = 256, 320, 176  # each divides over the 2x2 grid
LIMIT = 1e-4

PROGRAM = f"""
import json
import jax
import jax.numpy as jnp
import numpy as np
from examples.distributed_gemm import _mat_layout, summa_ring_program
from repro.core import DistBag, bag, scatter

ni, nj, nk, R, Cc = {NI}, {NJ}, {NK}, 2, 2
rng = np.random.default_rng(5)
A = rng.standard_normal((ni, nk)).astype(np.float32)
B = rng.standard_normal((nk, nj)).astype(np.float32)
want = A.astype(np.float64) @ B.astype(np.float64)
bf16 = lambda x: np.asarray(jnp.asarray(x).astype(jnp.bfloat16).astype(jnp.float32))


def summa(majors, a, b):
    fn, meta = summa_ring_program(ni=ni, nj=nj, nk=nk, grid=(R, Cc), majors=majors)
    ga = bag(meta["A_layout"], a if meta["A_layout"].axis_names == ("i", "k") else a.T)
    gb = bag(meta["B_layout"], b if meta["B_layout"].axis_names == ("k", "j") else b.T)
    a_dist = scatter(bag(meta["A_root_l"], ga.data), meta["A_tile"], meta["dtA"])
    b_dist = scatter(bag(meta["B_root_l"], gb.data), meta["B_tile"], meta["dtB"])
    c = DistBag(fn(a_dist.data, b_dist.data), meta["C_tile"], meta["dtA"], ("Ri", "Ck"))
    flat = _mat_layout("i", "j", ni // R, nj // Cc, "i")
    return np.block([[np.asarray(c.tile((r, q)).to_layout(flat).data) for q in range(Cc)]
                     for r in range(R)])


def rel_err(c):
    return float(np.abs(c - want).max() / np.abs(want).max())


out = {{m: rel_err(summa(m, A, B)) for m in {LAYOUT_CONFIGS!r}}}
out["control"] = rel_err(summa("I/I/K", bf16(A), bf16(B)))
print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def readings(distributed):
    out = distributed(PROGRAM, devices=4, timeout=560)
    return json.loads(out.split("RESULT ", 1)[1])


@pytest.mark.parametrize("majors", LAYOUT_CONFIGS)
def test_summa_matches_the_float64_product(readings, majors):
    assert readings[majors] < LIMIT


def test_bf16_rounded_operands_read_beyond_the_limit(readings):
    assert readings["control"] > 10 * LIMIT
    assert max(readings[m] for m in LAYOUT_CONFIGS) < LIMIT / 10
