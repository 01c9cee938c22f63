"""Comm-plan layer (ISSUE 6): declared schedules over Pending — the intent
table, the per-kind executor semantics (issue-before/wait-after placement,
bit-identical blocking interpretation), and plan-vs-HLO agreement including
the hand-built serialized pipeline negative control."""
import numpy as np
import pytest


def test_intent_table_and_constructor_validation():
    from repro.core.plan import CommPlan, halo, intent_of, pipeline, ring

    assert intent_of("ring") == "overlapped"
    assert intent_of("halo") == "overlapped"
    assert intent_of("pipeline") == "serialized"
    with pytest.raises(ValueError):
        intent_of("tree")

    xfer = lambda s, k: None
    comp = lambda c, s, k: c
    assert ring(3, transfer=xfer, compute=comp).intent == "overlapped"
    assert halo(transfer=xfer, compute=comp).intent == "overlapped"
    assert pipeline(2, transfer=xfer, compute=comp).intent == "serialized"
    assert halo(transfer=xfer, compute=comp).steps == 1
    with pytest.raises(ValueError):
        CommPlan("tree", 2, xfer, comp)  # unknown kind
    with pytest.raises(ValueError):
        ring(0, transfer=xfer, compute=comp)  # needs >= 1 step


def test_ring_executor_issue_wait_placement_and_identity():
    """The planner owns the issue/wait points: double-buffered issues step
    k's transfer BEFORE its compute, blocking starts+waits back-to-back at
    the completion point — and both fold the same values (every compute sees
    the pre-transfer state)."""
    import jax.numpy as jnp

    from repro.core import Pending
    from repro.core.plan import ring

    trace: list = []

    def transfer(state, s):
        trace.append(("xfer", s))
        return Pending(state + 1.0)

    def compute(carry, state, s):
        trace.append(("comp", s))
        return carry + state

    plan = ring(4, transfer=transfer, compute=compute,
                epilogue=lambda carry, state: (carry, state))
    carry_db, state_db = plan.run(jnp.float32(0.0), jnp.float32(0.0))
    order_db = list(trace)
    trace.clear()
    carry_bl, state_bl = plan.run(jnp.float32(0.0), jnp.float32(0.0),
                                  double_buffer=False)
    order_bl = list(trace)

    # state visits 0,1,2,3 -> carry = 6; final state = 3 (both modes)
    assert float(carry_db) == 6.0 == float(carry_bl)
    assert float(state_db) == 3.0 == float(state_bl)
    assert order_db == [("xfer", 0), ("comp", 0), ("xfer", 1), ("comp", 1),
                        ("xfer", 2), ("comp", 2), ("comp", 3)]
    assert order_bl == [("comp", 0), ("xfer", 0), ("comp", 1), ("xfer", 1),
                        ("comp", 2), ("xfer", 2), ("comp", 3)]


def test_pipeline_and_halo_executor_semantics():
    import jax.numpy as jnp

    from repro.core import Pending
    from repro.core.plan import halo, pipeline

    # pipeline ships the freshly computed carry: compute -> transfer -> compute
    shipped: list = []

    def transfer(carry, s):
        shipped.append(float(carry))
        return Pending(carry * 2.0)

    plan = pipeline(3, transfer=transfer,
                    compute=lambda c, state, s: c + state)
    out = plan.run(jnp.float32(1.0), jnp.float32(0.0))
    # s0: c=0+1=1, state=2; s1: c=1+2=3, state=6; s2: c=3+6=9
    assert float(out) == 9.0
    assert shipped == [1.0, 3.0]

    # halo: one exchange; epilogue combines interior carry and received state
    h = halo(transfer=lambda s, k: Pending(s * 10.0),
             compute=lambda c, s, k: c + s,
             epilogue=lambda c, s: (c, s))
    c_db, s_db = h.run(jnp.float32(2.0), jnp.float32(1.0))
    c_bl, s_bl = h.run(jnp.float32(2.0), jnp.float32(1.0), double_buffer=False)
    assert float(c_db) == 3.0 and float(s_db) == 20.0
    # blocking waits first, so compute sees the exchanged state
    assert float(c_bl) == 21.0 and float(s_bl) == 20.0


def test_transfer_must_return_pending():
    import jax.numpy as jnp

    from repro.core.plan import ring

    bad = ring(2, transfer=lambda s, k: s,  # forgot the *_start form
               compute=lambda c, s, k: c)
    with pytest.raises(TypeError, match="Pending"):
        bad.run(jnp.float32(0.0), jnp.float32(0.0))


def test_plan_agreement_helper():
    from repro.launch.hlo_walk import CollectiveClass, HloStats, plan_agreement

    st = HloStats()
    st.collectives.append(CollectiveClass(
        computation="%e", var="%p", bytes=4, mult=1.0,
        classification="overlapped", kind="collective-permute"))
    row = plan_agreement(st, "overlapped")
    assert row == {"declared": "overlapped", "proven": "overlapped",
                   "agree": True, "serialized": 0, "overlapped": 1}
    assert not plan_agreement(st, "serialized")["agree"]

    # one serialized collective of another kind flips the all-kind verdict
    st.collectives.append(CollectiveClass(
        computation="%e", var="%ag", bytes=4, mult=1.0,
        classification="serialized", kind="all-gather"))
    row = plan_agreement(st, "overlapped")
    assert row["proven"] == "serialized" and not row["agree"]
    # ... but kind scoping isolates the plan's own transfers
    assert plan_agreement(st, "overlapped", kind="collective-permute")["agree"]
    assert plan_agreement(st, "serialized", kind="all-gather")["agree"]
    with pytest.raises(ValueError):
        plan_agreement(st, "maybe")


def test_plan_vs_hlo_agreement(distributed):
    """End-to-end on the fake mesh: a ring plan compiles to provably
    overlapped transfers, a hand-built serialized pipeline plan (shipping
    each step's freshly computed value — the negative control) stays
    provably serialized, a wrongly-declared intent is caught, and the two
    interpretations of the same ring plan are bit-identical."""
    out = distributed(
        """
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from jax import shard_map
from repro.core import *
from repro.core.p2p import shard_ring_shift_start
from repro.core.plan import intent_of, pipeline, ring
from repro.launch import hlo_walk

R = 8
mesh = make_mesh((R,), ('r',))
xs = jax.ShapeDtypeStruct((R * 16, 16), np.float32)
ws = jax.ShapeDtypeStruct((16, 16), np.float32)

def ring_body(x, w, db=True):
    plan = ring(R,
        transfer=lambda b, s: shard_ring_shift_start(b, 'r', 1),
        compute=lambda acc, b, s: acc + b @ w)
    return plan.run(x, jnp.zeros_like(x), double_buffer=db)

fn = shard_map(ring_body, mesh=mesh, in_specs=(P('r'), P()), out_specs=P('r'))
with mesh:
    hlo = jax.jit(fn).lower(xs, ws).compile().as_text()
st = hlo_walk.analyze(hlo)
row = hlo_walk.plan_agreement(st, intent_of('ring'))
assert row['agree'] and row['proven'] == 'overlapped', row
assert st.collectives_serialized() == 0

# hand-built serialized negative control: the pipeline ships the value each
# step just computed, so dot -> permute -> dot chains with no sibling
def pipe_body(x, w):
    plan = pipeline(R,
        transfer=lambda c, s: shard_ring_shift_start(c, 'r', 1),
        compute=lambda c, b, s: (c + b) @ w)
    return plan.run(x, jnp.zeros_like(x))

fnp = shard_map(pipe_body, mesh=mesh, in_specs=(P('r'), P()), out_specs=P('r'))
with mesh:
    hlo2 = jax.jit(fnp).lower(xs, ws).compile().as_text()
st2 = hlo_walk.analyze(hlo2)
row2 = hlo_walk.plan_agreement(st2, intent_of('pipeline'))
assert row2['agree'] and row2['proven'] == 'serialized', row2
assert st2.collectives_serialized() > 0

# the checker catches wrongly-declared intent in both directions
assert not hlo_walk.plan_agreement(st2, 'overlapped')['agree']
assert not hlo_walk.plan_agreement(st, 'serialized')['agree']

# both interpretations of the SAME ring plan are bit-identical
rng = np.random.default_rng(0)
xv = jnp.asarray(rng.standard_normal((R * 16, 16)), jnp.float32)
wv = jnp.asarray(rng.standard_normal((16, 16)), jnp.float32)
run = lambda db: jax.jit(shard_map(
    lambda x, w: ring_body(x, w, db=db),
    mesh=mesh, in_specs=(P('r'), P()), out_specs=P('r')))(xv, wv)
with mesh:
    a, b = run(True), run(False)
assert np.array_equal(np.asarray(a), np.asarray(b))
print('OK')
"""
    )
    assert "OK" in out


def test_stagger_executor_round_robin_issue_wait_placement():
    """The stagger plan round-robins independent steps: double-buffered
    issues EVERY step's transfer before any wait (the whole wave in flight
    at once); blocking completes each step before the next begins.  Results
    are identical — the steps share no state."""
    from repro.core import Pending
    from repro.core.plan import intent_of, stagger

    assert intent_of("stagger") == "overlapped"

    trace: list = []

    def transfer(v, s):
        trace.append(("xfer", s))

        class Traced(Pending):
            def wait(self2):
                trace.append(("wait", s))
                return Pending.wait(self2)

        return Traced(v * 10)

    def compute(carry, state, s):
        trace.append(("comp", s))
        return s + 1

    plan = stagger(3, transfer=transfer, compute=compute)
    done_db = plan.run(None, None)
    order_db = list(trace)
    trace.clear()
    done_bl = plan.run(None, None, double_buffer=False)
    order_bl = list(trace)

    assert [int(d) for d in done_db] == [10, 20, 30] == [int(d) for d in done_bl]
    assert order_db == [("comp", 0), ("xfer", 0), ("comp", 1), ("xfer", 1),
                        ("comp", 2), ("xfer", 2),
                        ("wait", 0), ("wait", 1), ("wait", 2)]
    assert order_bl == [("comp", 0), ("xfer", 0), ("wait", 0),
                        ("comp", 1), ("xfer", 1), ("wait", 1),
                        ("comp", 2), ("xfer", 2), ("wait", 2)]

    # ``after`` orders step 0's collective behind an earlier one: values and
    # issue/wait order are unchanged; other plan kinds refuse it
    from repro.core.plan import ring

    trace.clear()
    assert [int(d) for d in plan.run(None, None, after=np.float32(7))] == [10, 20, 30]
    assert trace == order_db
    with pytest.raises(ValueError, match="stagger plans only"):
        ring(2, transfer=transfer, compute=compute).run(None, None, after=np.float32(7))


def test_bucket_plan_intent_and_validation():
    from repro.core.plan import CommPlan, bucket, intent_of

    assert intent_of("bucket") == "overlapped"
    xfer = lambda s, k: None
    comp = lambda g, a, k: a
    comb = lambda r, k: None
    red = lambda arrived: None
    assert bucket(3, transfer=xfer, reduce=red, compute=comp,
                  combine=comb).intent == "overlapped"
    # a bucket plan without its all-gather return leg is a declaration bug
    with pytest.raises(ValueError, match="bucket plan needs a combine stage"):
        CommPlan("bucket", 2, xfer, comp, reduce=red)
    # the cross-step reduce barrier only exists in the bucket schedule
    with pytest.raises(ValueError, match="reduce stage is bucket-plan only"):
        CommPlan("stagger", 2, xfer, comp, reduce=red)


def test_bucket_executor_issue_wait_placement_and_identity():
    """The ZeRO bucket schedule: double-buffered puts EVERY bucket's
    reduce-scatter in flight before any wait, runs the single cross-bucket
    reduce barrier, then per-bucket compute, then issues every all-gather
    before waiting; blocking starts+waits each leg back-to-back through the
    same issue path.  The folded values are identical — the waits are pure
    completion points."""
    from repro.core import Pending
    from repro.core.plan import bucket

    trace: list = []

    def traced(value, tag, s):
        class Traced(Pending):
            def wait(self2):
                trace.append((tag, s))
                return Pending.wait(self2)

        return Traced(value)

    def transfer(state, s):
        trace.append(("xfer", s))
        return traced(s + 1, "xwait", s)

    def reduce(arrived):
        trace.append(("reduce",))
        return sum(int(a) for a in arrived)  # sees every bucket's shard

    def compute(gval, arrived_s, s):
        trace.append(("comp", s))
        return 100 * gval + int(arrived_s)

    def combine(result, s):
        trace.append(("cissue", s))
        return traced(result, "cwait", s)

    plan = bucket(3, transfer=transfer, reduce=reduce, compute=compute,
                  combine=combine)
    done_db = plan.run(None, None)
    order_db = list(trace)
    trace.clear()
    done_bl = plan.run(None, None, double_buffer=False)
    order_bl = list(trace)

    # arrived = [1, 2, 3] -> gval = 6 -> results [601, 602, 603], both modes
    assert [int(d) for d in done_db] == [601, 602, 603] == [int(d) for d in done_bl]
    assert order_db == [
        ("xfer", 0), ("xfer", 1), ("xfer", 2),          # whole backward in flight
        ("xwait", 0), ("xwait", 1), ("xwait", 2),
        ("reduce",),                                     # one cross-bucket barrier
        ("comp", 0), ("comp", 1), ("comp", 2),
        ("cissue", 0), ("cissue", 1), ("cissue", 2),     # all prefetches issued
        ("cwait", 0), ("cwait", 1), ("cwait", 2),
    ]
    assert order_bl == [
        ("xfer", 0), ("xwait", 0), ("xfer", 1), ("xwait", 1),
        ("xfer", 2), ("xwait", 2),
        ("reduce",),
        ("comp", 0), ("cissue", 0), ("cwait", 0),
        ("comp", 1), ("cissue", 1), ("cwait", 1),
        ("comp", 2), ("cissue", 2), ("cwait", 2),
    ]
