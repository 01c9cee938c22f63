"""The one traffic generator. A mix is a data file, ``bench/traffic/<name>.json``;
this module reads it and makes the requests of a run from ``--seed``.

Mix kinds:

``open_loop``
    Requests arrive on a schedule, whatever the system does: the gaps
    between arrivals are exponential at ``rate_per_s`` (Poisson arrivals).
``backlog``
    Every request is due at the window's start; the benchmark keeps
    ``backlog`` requests queued, so the queue never empties.

Every seed gets the same schedule: each list of lengths or gaps is the
``n`` stratified quantiles ``(i + 0.5) / n`` of its distribution, in one
fixed shuffled order. The seed draws the token ids (and the system's
weights or data), not the amount of work or its order: at four fifths of
the knee, the order alone moved the TTFT tail of six otherwise alike runs
by a factor of three. A ``backlog`` mix draws its requests in blocks of
``block``, each block another fixed shuffle of the same quantiles.

Length distributions, in ``prompt_len`` and ``output_len``:
``{"dist": "lognormal", "median": m, "sigma": s, "min": a, "max": b}``
(clipped to ``[a, b]``) or ``{"dist": "uniform", "min": a, "max": b}``
(whole numbers ``a`` to ``b``).
"""
from __future__ import annotations

import dataclasses
import json
import math
from pathlib import Path
from statistics import NormalDist

import numpy as np

MIX_DIR = Path(__file__).resolve().parent / "traffic"


def load(name: str) -> dict:
    return json.loads((MIX_DIR / f"{name}.json").read_text())


def rng(seed: int, stream: int) -> np.random.Generator:
    """An independent stream per purpose; any whole-number seed."""
    return np.random.default_rng([seed % 2**63, stream])


def quantiles(dist: dict, n: int) -> np.ndarray:
    """The ``n`` stratified quantiles of a length distribution, as ints."""
    u = (np.arange(n) + 0.5) / n
    lo, hi = dist["min"], dist["max"]
    if dist["dist"] == "uniform":
        x = lo + np.floor(u * (hi - lo + 1))
    elif dist["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(p) for p in u])
        x = np.round(dist["median"] * np.exp(dist["sigma"] * z))
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return np.clip(x, lo, hi).astype(np.int64)


def exponential_gaps(rate: float, n: int) -> np.ndarray:
    u = (np.arange(n) + 0.5) / n
    return -np.log1p(-u) / rate


@dataclasses.dataclass
class Request:
    rid: int
    arrival: float  # seconds after the window's start
    prompt: list
    max_new: int


# the seed of the one fixed order of every schedule
ORDER = 0


def _requests(mix: dict, seed: int, n: int, vocab: int, first_rid: int, block: int):
    order = rng(ORDER, 1 + block)
    prompts = order.permutation(quantiles(mix["prompt_len"], n))
    outputs = order.permutation(quantiles(mix["output_len"], n))
    ids = rng(seed, 1_000_000 + block)
    return [Request(first_rid + i, 0.0, ids.integers(0, vocab, size=int(p)).tolist(), int(o))
            for i, (p, o) in enumerate(zip(prompts, outputs))]


def open_loop(mix: dict, seed: int, seconds: float, vocab: int) -> list[Request]:
    """Every request whose arrival falls in ``[0, seconds)``, by arrival."""
    rate = mix["rate_per_s"]
    n = math.ceil(rate * seconds) + 1
    reqs = _requests(mix, seed, n, vocab, 0, 0)
    arrivals = np.cumsum(rng(ORDER, 2).permutation(exponential_gaps(rate, n)))
    for r, t in zip(reqs, arrivals):
        r.arrival = float(t)
    return [r for r in reqs if r.arrival < seconds]


def backlog(mix: dict, seed: int, vocab: int):
    """An endless stream of requests due at 0, in shuffled blocks."""
    block, b = mix["block"], 0
    while True:
        yield from _requests(mix, seed, block, vocab, b * block, b)
        b += 1
