"""System under test: the paper's SUMMA GEMM
(``examples/distributed_gemm.summa_ring_program``), one multiply at a time
over a grid of chips.

Set-up builds the program as its users build it,
``summa_ring_program(ni=..., nj=..., nk=..., grid=..., majors=...)``, and
draws A and B from the seed on the chips, as the stacked per-rank tiles the
program takes (``meta["abstract_args"]``), in its own shardings
(``dist_sharding``). It then calls the program until the calls take a
steady time, which compiles it; nothing is scattered or gathered later.
Set-up refuses a program that does not compute at the configuration's
precision: the first rows of the last warm-up call's C against the float64
product of the same rows, by the check's own measure and limit. A program
that rounds its operands to a lower precision then fails the cell in
set-up, before any window is measured.

The window is a closed loop: one caller repeats the multiply back to back,
each call ended by ``block_until_ready``. Spans: ``bench.summa.dispatch``
from the call to its return, ``bench.summa.wait`` around
``block_until_ready``. ``run.steps`` holds each multiply's (start, end) on
the host's clock, and ``run.requests`` one record per multiply for the
serving readers: a multiply is a request that arrives when it is called
and has one output, its C, ready at its end, so ``ttft_p90_ms`` reads the
90th percentile of a multiply's latency. The C of each sampled call, drawn
from the seed, and of the window's last call are kept as the arrays the
call returned.

The check, after the window: each kept C is gathered to the host and
compared with the configuration's reference, the float64 product of the
same operands (``bench/configs/<config>.py``), as max |error| / max |C|.
The control puts the same program, called on A and B rounded to bfloat16,
in the program's place.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from bench import harness, traffic
from examples.distributed_gemm import summa_ring_program
from repro.core import dist_sharding

# rows of C that set-up compares with the float64 product
PROBE_ROWS = 64
# warm-up: batches of this many calls until two in a row take times within
# a tenth of each other, at most WARM_BATCHES of them
WARM_CALLS, WARM_BATCHES = 20, 50


class Multiply:
    """One multiply of the window as a request: called at ``arrival``, its
    one output ready at ``times[0]`` (seconds from the window's start)."""
    __slots__ = ("arrival", "times")

    def __init__(self, start: float, end: float):
        self.arrival, self.times = start, (end,)


def operand_seed(seed: int) -> int:
    return int(traffic.rng(seed, 3).integers(0, 2**31 - 1))


class System:
    def __init__(self, config: dict, mix: dict, seed: int, devices, run):
        if mix["kind"] != "closed_loop" or mix["callers"] != 1:
            raise ValueError(f"{mix['kind']!r} traffic with {mix.get('callers')} callers "
                             "does not drive one caller of the SUMMA program")
        self.config, self.mix, self.seed, self.run = config, mix, seed, run
        self.devices = devices
        self.kept, self._ref = {}, None

    # ------------------------------------------------------------ set-up ----
    def setup(self):
        c = self.config
        t0 = time.perf_counter()
        self.fn, meta = summa_ring_program(ni=c["ni"], nj=c["nj"], nk=c["nk"],
                                           grid=tuple(c["grid"]), majors=c["majors"])
        mesh_devices = set(meta["mesh"].devices.flat)
        if mesh_devices != set(self.devices):
            raise ValueError(f"the program's grid spans {len(mesh_devices)} devices, "
                             f"the cell gives {len(self.devices)}")
        sds_a, sds_b = meta["abstract_args"]
        dtype = jnp.dtype(c["dtype"])
        if sds_a.dtype != dtype:
            raise ValueError(f"the program computes {sds_a.dtype}, the configuration states {dtype}")
        sh_a = dist_sharding(meta["dtA"], meta["A_tile"])
        sh_b = dist_sharding(meta["dtB"], meta["B_tile"])

        @jax.jit(out_shardings=(sh_a, sh_b))
        def draw(key):
            ka, kb = jax.random.split(key)
            return (jax.random.normal(ka, sds_a.shape, dtype),
                    jax.random.normal(kb, sds_b.shape, dtype))

        self.a, self.b = jax.block_until_ready(draw(jax.random.key(operand_seed(self.seed))))
        t1 = time.perf_counter()
        last = None
        for _ in range(WARM_BATCHES):
            s = time.perf_counter()
            for _ in range(WARM_CALLS):
                out = self.fn(self.a, self.b).block_until_ready()
            took = (time.perf_counter() - s) / WARM_CALLS
            if last is not None and abs(took - last) <= 0.1 * last:
                break
            last = took
        self.call_s = took
        self.probe(out)
        self.run.info["setup_split_s"] = {"operands": t1 - t0, "compile_and_warm_up": time.perf_counter() - t1}
        self.run.info["comm_bytes_per_multiply"] = meta["comm_model"]

    def probe(self, out):
        """Raises where the first ``PROBE_ROWS`` rows of ``out`` lie beyond
        the check's limit from the float64 product of the same rows."""
        ref = self.reference_module()
        majors = self.config["majors"]
        a = ref.global_a(np.asarray(self.a), majors)[:PROBE_ROWS]
        b = ref.global_b(np.asarray(self.b), majors)
        err = ref.rel_err(ref.global_c(np.asarray(out), majors)[:PROBE_ROWS], ref.product(a, b))
        self.run.info["setup_probe_rel_err"] = err
        if err > self.config["check"]["rel_err"]:
            raise RuntimeError(f"the program's C reads {err:.3g} from the float64 product, beyond the "
                               f"limit {self.config['check']['rel_err']}: it does not compute at "
                               f"the configuration's precision ({self.config['dtype']})")

    def sampled_calls(self, seconds: float) -> set[int]:
        """Indices of the window's calls whose C is checked: ``check_calls``
        drawn from the seed among the first half of the calls the window
        should hold at the warm-up's pace."""
        n = max(1, int(seconds / self.call_s) // 2)
        k = min(self.mix["check_calls"], n)
        return {int(i) for i in traffic.rng(self.seed, 4).choice(n, size=k, replace=False)}

    # ------------------------------------------------------------ window ----
    def multiply(self):
        """One call of the program, ended by ``block_until_ready``."""
        with TraceAnnotation("bench.summa.dispatch"):
            out = self.fn(self.a, self.b)
        with TraceAnnotation("bench.summa.wait"):
            out.block_until_ready()
        return out

    def drive(self, seconds: float, tick):
        run, fn_call = self.run, self.multiply
        sampled = self.sampled_calls(seconds)
        clock = time.perf_counter
        steps, kept = [], {}
        t0 = clock()
        i, out = 0, None
        while True:
            now = clock() - t0
            tick(now)
            if now >= seconds:
                break
            out = fn_call()
            steps.append((now, clock() - t0))
            if i in sampled:
                kept[i] = out
            i += 1
        if out is not None:
            kept[i - 1] = out
        run.steps = steps
        run.requests = [Multiply(start, end) for start, end in steps]
        self.kept = kept
        run.info["multiplies"] = {
            "in_window": sum(1 for _, e in steps if e <= seconds), "started": len(steps),
            "checked": sorted(kept)}

    def free(self):
        """Nothing to drop: the operands and the kept outputs are the check's."""

    # ------------------------------------------------------------- check ----
    def reference_module(self):
        return harness.load_module(harness.BENCH / "configs" / f"{self.config['name']}.py")

    def reference(self):
        if self._ref is None:
            ref = self.reference_module()
            majors = self.config["majors"]
            a = ref.global_a(np.asarray(self.a), majors)
            b = ref.global_b(np.asarray(self.b), majors)
            self._ref = ref, ref.product(a, b)
        return self._ref

    def check(self, control: bool = False):
        """Each kept C against the float64 product. With ``control`` the
        program, called once on A and B rounded to bfloat16, takes the
        program's place."""
        ref, want = self.reference()
        limit = self.config["check"]["rel_err"]
        if control:
            # through a bfloat16 array: inside one jitted program the chip's
            # compiler drops a float32 -> bfloat16 -> float32 round trip
            rounded = lambda x: x.astype(jnp.bfloat16).astype(x.dtype)
            outs = {"control": self.fn(rounded(self.a), rounded(self.b))}
        else:
            outs = self.kept
        if not outs:
            return {"rel_err": {"value": float("inf"), "limit": limit}}, len(self.run.steps), 0
        errs = {k: ref.rel_err(ref.global_c(np.asarray(c), self.config["majors"]), want)
                for k, c in outs.items()}
        self.run.info["control_check" if control else "check"] = {"rel_err_by_call": list(errs.items())}
        failed = sum(1 for e in errs.values() if e > limit)
        return {"rel_err": {"value": max(errs.values()), "limit": limit}}, len(self.run.steps), failed
