"""The serving engine's own trace spans (``repro.serve.engine``), read back
from a profiler trace of a small engine run on the CPU: one span per phase
and step, their arguments, one ``engine.queued`` span per request, and the
host spans on the same clock as the step programs' operations."""
import glob
import os
import re
from collections import defaultdict

import jax
import pytest

from repro import configs
from repro.models import lm
from repro.serve.engine import Engine, ServeConfig

PHASES = ("engine.admit", "engine.prefill_launch", "engine.decode_launch",
          "engine.fetch", "engine.sample")
# (request id, prompt length, new tokens) on 2 slots: 10 and 11 are admitted
# together, 12 waits in the queue until 10 finishes after 3 decode steps
REQUESTS = [(10, 5, 3), (11, 9, 5), (12, 4, 2)]
DECODE_STEPS = 5
SLOTS = 2


def _serve(params, cfg, trace_dir=None):
    """Serve ``REQUESTS`` on a fresh engine, under the profiler if
    ``trace_dir`` is given; returns the finished map and the step calls."""
    eng = Engine(cfg, params, ServeConfig(max_len=64, batch_slots=SLOTS, eos_token=-1))
    calls = defaultdict(int)

    def counted(kind, fn):
        def call(*args):
            calls[kind] += 1
            return fn(*args)
        return call

    eng.prefill_fn = counted("prefill", eng.prefill_fn)
    eng.decode_fn = counted("decode", eng.decode_fn)

    def go():
        for rid, plen, new in REQUESTS:
            eng.submit(rid, list(range(2, 2 + plen)), max_new_tokens=new)
        return eng.run()

    if trace_dir is None:
        return go(), calls
    with jax.profiler.trace(trace_dir):
        done = go()
    return done, calls


def _read(trace_dir):
    """The ``engine.*`` host spans as (name, start, end, args), and each
    execution of the decode program as (first op start, last op end), in ns."""
    from jax.profiler import ProfileData

    path = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))[0]
    spans, decode_ops = [], defaultdict(list)
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                end = ev.start_ns + ev.duration_ns
                if ev.name.startswith("engine."):
                    spans.append((ev.name, ev.start_ns, end, dict(ev.stats)))
                    continue
                st = dict(ev.stats)
                if re.sub(r"\(\d+\)$", "", str(st.get("hlo_module", ""))) == "jit_gspmd_step":
                    decode_ops[st.get("run_id")].append((ev.start_ns, end))
    spans.sort(key=lambda s: s[1])
    decode = sorted((min(a for a, _ in ops), max(b for _, b in ops)) for ops in decode_ops.values())
    return spans, decode


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    cfg = configs.get("phi4-mini-3.8b", smoke=True)
    params = lm.init_model(cfg, jax.random.PRNGKey(0))
    off, _ = _serve(params, cfg)
    trace_dir = str(tmp_path_factory.mktemp("engine-trace"))
    on, calls = _serve(params, cfg, trace_dir)
    spans, decode = _read(trace_dir)
    return {"off": off, "on": on, "calls": calls, "spans": spans, "decode": decode}


def named(served, name):
    return [s for s in served["spans"] if s[0] == name]


def test_one_span_per_phase_and_step(served):
    calls = served["calls"]
    assert calls["decode"] == DECODE_STEPS
    for name in ("engine.decode_launch", "engine.fetch", "engine.sample"):
        assert len(named(served, name)) == DECODE_STEPS, name
    # two admitting rounds, each followed by one prefill launch that calls
    # the one-row prefill program once per admitted request
    admits = named(served, "engine.admit")
    assert [s[3]["admitted"] for s in admits] == [2, 1]
    assert [s[3]["queued"] for s in admits] == [1, 0]
    launches = named(served, "engine.prefill_launch")
    assert len(launches) == 2
    assert sum(s[3]["calls"] for s in launches) == calls["prefill"] == 3


def test_queued_span_per_request(served):
    queued = named(served, "engine.queued")
    assert sorted(s[3]["rid"] for s in queued) == [r for r, _, _ in REQUESTS]
    # 12 waits for the first three decode steps; 10 and 11 are admitted at once
    wait = {s[3]["rid"]: s[2] - s[1] for s in queued}
    first_admit = named(served, "engine.admit")[0]
    assert wait[12] > wait[10] and wait[12] > wait[11]
    assert all(s[2] <= first_admit[2] for s in queued if s[3]["rid"] != 12)


def test_span_args(served):
    admits = named(served, "engine.admit")
    # nothing resident at the first round; 11's prompt and steps at the second
    assert admits[0][3]["kv_valid_bytes"] == 0
    assert admits[1][3]["kv_valid_bytes"] > 0
    prefills = named(served, "engine.prefill_launch")
    # rows fed (prompt less its last token) padded to a power of two
    assert [(s[3]["rows"], s[3]["bucket"]) for s in prefills] == [(2, 8), (1, 4)]
    # one call per row; padding: 8 - 4 and 8 - 8 tokens, then 4 - 3
    assert [(s[3]["calls"], s[3]["padded_tokens"]) for s in prefills] == [(2, 4), (1, 1)]
    assert [s[3]["rows"] for s in named(served, "engine.decode_launch")] == [2] * DECODE_STEPS
    assert [s[3]["rows"] for s in named(served, "engine.sample")] == [2] * DECODE_STEPS


def test_fetch_pulls_only_the_picked_ids(served):
    """The host pulls one int32 id per slot a decode step, not the logits."""
    fetches = named(served, "engine.fetch")
    assert [s[3]["bytes"] for s in fetches] == [4 * SLOTS] * DECODE_STEPS


def test_phase_spans_tile_the_loop_in_order(served):
    """The phases never overlap, and each iteration runs
    [admit prefill_launch] decode_launch fetch sample."""
    phases = [s for s in served["spans"] if s[0] in PHASES]
    for a, b in zip(phases, phases[1:]):
        assert a[2] <= b[1], (a, b)
    seq = " ".join(s[0].split(".")[1] for s in phases)
    step = r"(admit prefill_launch )?decode_launch fetch sample"
    assert re.fullmatch(rf"{step}( {step})*", seq), seq
    assert len(re.findall(step, seq)) == DECODE_STEPS


def test_fetch_waits_for_its_decode_program(served):
    """Each fetch ends after the last operation of the decode execution it
    waited on: host spans and device operations share one clock."""
    decode = served["decode"]
    assert len(decode) == DECODE_STEPS
    launches = named(served, "engine.decode_launch")
    for launch, f in zip(launches, named(served, "engine.fetch")):
        waited = [d for d in decode if d[0] < f[2]][-1]
        assert launch[1] <= waited[0] and waited[1] <= f[2]


def test_tokens_identical_with_profiler_on_and_off(served):
    assert served["on"] == served["off"]
    assert {rid: len(t) for rid, t in served["on"].items()} == {
        rid: plen + new for rid, plen, new in REQUESTS}
