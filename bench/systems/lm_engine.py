"""System under test: ``repro.serve.engine.Engine``, the continuous-batching
server, serving a dense decoder LM on one chip.

Set-up makes the weights on the device in one jitted call from the seed,
in the dtype they are served in (bf16), builds the engine the way a user
does (``Engine(cfg, params, ServeConfig(...))``) and warms up every prefill
bucket the cell's traffic can produce, with every slot filled, and the
decode step.

The window drives the engine's public entry points: ``submit`` for each
request when it is due, and ``run(max_steps=1)`` (admission, prefill of the
new slots, one decode step) while it has work. The benchmark notes on the
host's clock when each request's output tokens appear. It wraps the
engine's two step programs (``prefill_fn``, ``decode_fn``) to record the
rows each call carries and to mark them in a trace, and changes nothing
else.

The check, after the window: a sample drawn from the seed of the requests
that finished, the longest among them, goes through the configuration's
plain float32 reference (``bench/configs/<config>.py``) over each prompt
with its served tokens. The number compared is the widest gap by which a
served token's logit lies below the reference's best logit at that
position (greedy decoding serves the top token, so a sound run reads
rounding only).
"""
from __future__ import annotations

import gc
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from bench import harness, traffic
from repro.configs.base import ArchConfig
from repro.models import lm
from repro.serve.engine import Engine, ServeConfig

# standard deviation of each drawn weight: fan-in scaled for the matmul
# weights (the axes of the fan-in after the layer axis), around 1 for the
# norm scales, 0.02 for the tied embedding
FAN_IN_AXES = {"wq": (1,), "wk": (1,), "wv": (1,), "wo": (1, 2),
               "w_gate": (1,), "w_up": (1,), "w_down": (1,)}
NORMS = ("ln1", "ln2", "final_norm")


def arch_config(c: dict) -> ArchConfig:
    """The program's configuration for a dense decoder given by its
    published keys. The program computes full-width RoPE without scaling."""
    if c.get("partial_rotary_factor", 1.0) != 1.0 or c.get("rope_scaling") is not None:
        raise ValueError("the engine runs full-width RoPE without scaling only")
    d, h = c["hidden_size"], c["num_attention_heads"]
    dt = jnp.dtype(c["torch_dtype"])
    return ArchConfig(
        name=c["name"], family="dense", n_layers=c["num_hidden_layers"], d_model=d,
        n_heads=h, n_kv=c["num_key_value_heads"], d_ff=c["intermediate_size"],
        vocab=c["vocab_size"], head_dim=c.get("head_dim") or d // h,
        rope_theta=float(c["rope_theta"]), tie_embeddings=c["tie_word_embeddings"],
        param_dtype=dt, act_dtype=dt,
    )


def weight_seed(seed: int) -> int:
    return int(traffic.rng(seed, 3).integers(0, 2**31 - 1))


def weights_fn(cfg: ArchConfig):
    """The jitted call that draws every weight of the model from a key."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(lm.abstract_model(cfg))

    def draw(path, sds, key):
        name = path[-1].key
        z = jax.random.normal(key, sds.shape, jnp.float32)
        if name in NORMS:
            w = 1.0 + 0.1 * z
        elif name == "embed":
            w = 0.02 * z
        else:
            fan_in = int(np.prod([sds.shape[a] for a in FAN_IN_AXES[name]]))
            w = z * fan_in ** -0.5
        return w.astype(sds.dtype)

    @jax.jit
    def gen(key):
        keys = jax.random.split(key, len(flat))
        return jax.tree_util.tree_unflatten(
            treedef, [draw(p, s, k) for (p, s), k in zip(flat, keys)])

    return gen


def make_weights(cfg: ArchConfig, seed: int):
    """Every weight of the model, drawn on the device in one jitted call."""
    return jax.block_until_ready(weights_fn(cfg)(jax.random.key(weight_seed(seed))))


def buckets(mix: dict, max_len: int) -> list[int]:
    """The prefill lengths the engine pads this mix's admissions to: the
    prompt less its last token, rounded up to a power of two."""
    lo, hi = mix["prompt_len"]["min"] - 1, mix["prompt_len"]["max"] - 1
    return sorted({min(max_len, 1 << (n - 1).bit_length()) for n in range(max(lo, 1), hi + 1)})


class Record:
    __slots__ = ("rid", "arrival", "submitted", "prompt_len", "max_new", "times")

    def __init__(self, rid, arrival, submitted, prompt_len, max_new):
        self.rid, self.arrival, self.submitted = rid, arrival, submitted
        self.prompt_len, self.max_new = prompt_len, max_new
        self.times = []  # host time of each output token, from the window's start


class System:
    def __init__(self, config: dict, mix: dict, seed: int, devices, run):
        self.config, self.mix, self.seed, self.run = config, mix, seed, run
        self.cfg = arch_config(config)
        d = config["deployment"]
        self.scfg = ServeConfig(max_len=d["max_len"], batch_slots=d["batch_slots"],
                                temperature=0.0, eos_token=-1)
        self.window_t0 = None
        self.recording = False

    # ------------------------------------------------------------ set-up ----
    def setup(self):
        t0 = time.perf_counter()
        self.params = make_weights(self.cfg, self.seed)
        t1 = time.perf_counter()
        self.engine = eng = Engine(self.cfg, self.params, self.scfg)
        eng.prefill_fn = self._wrap("engine.prefill", eng.prefill_fn, self._on_prefill)
        eng.decode_fn = self._wrap("engine.decode", eng.decode_fn, self._on_decode)
        self.prefilled = set()
        B = self.scfg.batch_slots
        for b in buckets(self.mix, self.scfg.max_len):
            # a full batch of prompts of b - 2 tokens pads to bucket b
            for i in range(B):
                eng.submit(-1 - i, [1] * (b - 2), max_new_tokens=2)
            eng.run()
            eng.finished.clear()
        self.prefilled.clear()
        self.run.info["setup_split_s"] = {"weights": t1 - t0, "build_and_warm_up": time.perf_counter() - t1}

    def _wrap(self, name, fn, on_call):
        def call(*args):
            if self.recording:
                on_call()
            with TraceAnnotation(name):
                return fn(*args)
        return call

    def _on_prefill(self):
        fed = [len(t) - 1 for rid, t in self.engine.in_flight.items() if rid not in self.prefilled]
        self.prefilled.update(self.engine.in_flight)
        self.run.calls.append(("prefill", time.perf_counter() - self.window_t0, fed))

    def _on_decode(self):
        seen = [len(t) for t in self.engine.in_flight.values()]
        self.run.calls.append(("decode", time.perf_counter() - self.window_t0, seen))

    # ------------------------------------------------------------ window ----
    def drive(self, seconds: float, tick):
        eng, mix, run = self.engine, self.mix, self.run
        kind = mix["kind"]
        if kind == "open_loop":
            due = traffic.open_loop(mix, self.seed, seconds, self.cfg.vocab)
            stream = None
        elif kind == "backlog":
            due, stream = [], traffic.backlog(mix, self.seed, self.cfg.vocab)
        else:
            raise ValueError(f"{kind!r} traffic does not drive a server")
        self.prompts = {}
        recs = {}
        i, n_fin = 0, 0
        clock = time.perf_counter
        self.recording = True
        t0 = self.window_t0 = clock()
        while True:
            now = clock() - t0
            tick(now)
            if now >= seconds:
                break
            if stream is not None:
                while len(eng.queue) < mix["backlog"]:
                    r = next(stream)
                    eng.submit(r.rid, r.prompt, max_new_tokens=r.max_new)
                    recs[r.rid] = Record(r.rid, 0.0, now, len(r.prompt), r.max_new)
                    self.prompts[r.rid] = r.prompt
            while i < len(due) and due[i].arrival <= now:
                r = due[i]
                eng.submit(r.rid, r.prompt, max_new_tokens=r.max_new)
                recs[r.rid] = Record(r.rid, r.arrival, now, len(r.prompt), r.max_new)
                self.prompts[r.rid] = r.prompt
                i += 1
            if not eng.queue and not eng.in_flight:
                nxt = due[i].arrival if i < len(due) else seconds
                with TraceAnnotation("bench.idle"):
                    time.sleep(max(0.0, min(nxt, seconds) - now))
                continue
            start = clock() - t0
            with TraceAnnotation("bench.step"):
                eng.run(max_steps=1)
            t = clock() - t0
            run.steps.append((start, t))
            for rid, toks in eng.in_flight.items():
                rec = recs[rid]
                rec.times.extend([t] * (len(toks) - rec.prompt_len - len(rec.times)))
            if len(eng.finished) > n_fin:
                for rid in list(eng.finished)[n_fin:]:
                    rec = recs[rid]
                    rec.times.extend([t] * (len(eng.finished[rid]) - rec.prompt_len - len(rec.times)))
                n_fin = len(eng.finished)
        self.recording = False
        # the requests due in the window, and what the generator and the
        # server did with them
        run.requests = [r for r in recs.values() if r.arrival < seconds]
        late = [r.submitted - r.arrival for r in run.requests]
        out = sum(sum(1 for t in r.times if t <= seconds) for r in run.requests)
        run.info["requests"] = {
            "due": len(run.requests), "submitted": len(recs),
            "finished": sum(1 for r in run.requests if r.rid in eng.finished),
            "waiting_at_close": len(eng.queue), "resident_at_close": len(eng.in_flight),
            "output_tokens": out}
        if kind == "open_loop":
            # how long after its arrival each request was handed to the engine:
            # the benchmark submits between steps, so this holds the step it waited on
            run.info["generator_late_s"] = {"max": max(late, default=0.0),
                                            "mean": float(np.mean(late)) if late else 0.0}
        run.info["step_calls"] = {k: sum(1 for c in run.calls if c[0] == k) for k in ("prefill", "decode")}
        self.finished = {rid: eng.finished[rid] for rid in eng.finished if rid in recs}

    def free(self):
        """Drop the engine and its KV cache; the weights stay for the check."""
        del self.engine
        gc.collect()

    # ------------------------------------------------------------- check ----
    def sample(self) -> list[int]:
        """Finished requests for the check: the longest, then others drawn
        from the seed, until the mix's ``check_tokens`` served tokens, each
        request counting for at most ``check_tokens_per_request`` of them so
        that the sample spans several requests and slots. Every served token
        of a picked request is compared."""
        served = {rid: len(t) - len(self.prompts[rid]) for rid, t in self.finished.items()}
        if not served:
            return []
        cap = self.mix["check_tokens_per_request"]
        longest = max(served, key=lambda r: (served[r], -r))
        rest = [r for r in sorted(served) if r != longest]
        rest = [rest[j] for j in traffic.rng(self.seed, 4).permutation(len(rest))]
        picked, total = [longest], min(served[longest], cap)
        for r in rest:
            if total >= self.mix["check_tokens"]:
                break
            picked.append(r)
            total += min(served[r], cap)
        return picked

    def rows(self, rids):
        """Each request as one row: prompt and served tokens but the last as
        input; the served token at each position it predicts as target."""
        T = self.scfg.max_len
        tokens = np.zeros((len(rids), T), np.int32)
        targets = np.zeros((len(rids), T), np.int32)
        mask = np.zeros((len(rids), T), bool)
        for j, rid in enumerate(rids):
            seq, P = self.finished[rid], len(self.prompts[rid])
            tokens[j, : len(seq) - 1] = seq[:-1]
            targets[j, : len(seq) - 1] = seq[1:]
            mask[j, P - 1: len(seq) - 1] = True
        return tokens, targets, mask

    def check(self, control: bool = False):
        """The comparison with the reference. With ``control`` the reference
        computed one precision step below the configuration's takes the
        program's place: at each position of the same prompts and served
        tokens, the token it puts first goes through the same comparison as
        a served token."""
        ref = harness.load_module(harness.BENCH / "configs" / f"{self.config['name']}.py")
        rids = self.sample()
        limit = self.config["check"]["logit_gap"]
        if not rids:
            return {"logit_gap": {"value": float("inf"), "limit": limit}}, len(self.run.requests), 0
        tokens, targets, mask = self.rows(rids)
        if control:
            _, targets, _ = ref.logit_stats(self.params, self.config, tokens, targets, low=True)
        best, _, got = ref.logit_stats(self.params, self.config, tokens, targets)
        per_row = np.where(mask, best - got, -np.inf).max(axis=1)
        checks = {"logit_gap": {"value": float(per_row.max()), "limit": limit}}
        self.run.info["control_check" if control else "check"] = {
            "requests": len(rids), "served_tokens": int(mask.sum()),
            "gap_per_request": [float(g) for g in per_row]}
        failed = int((per_row > limit).sum())
        return checks, len(self.run.requests), failed
