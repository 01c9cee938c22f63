"""The seeded traffic generator."""
import collections
import itertools

import numpy as np
import pytest

from bench import traffic


@pytest.mark.parametrize("name", ["chat", "docs"])
def test_lengths_stay_inside_their_clips(name):
    mix = traffic.load(name)
    for key in ("prompt_len", "output_len"):
        q = traffic.quantiles(mix[key], 500)
        assert q.min() >= mix[key]["min"] and q.max() <= mix[key]["max"]
        assert (np.diff(q) >= 0).all()


def test_lognormal_median():
    q = traffic.quantiles({"dist": "lognormal", "median": 256, "sigma": 1.0, "min": 1, "max": 10**6}, 1001)
    assert q[500] == 256


def test_open_loop_same_seed_same_schedule():
    mix = traffic.load("chat")
    a = traffic.open_loop(mix, 2**31 + 11, 30, 1000)
    b = traffic.open_loop(mix, 2**31 + 11, 30, 1000)
    assert [(r.arrival, r.prompt, r.max_new) for r in a] == [(r.arrival, r.prompt, r.max_new) for r in b]
    assert all(0 <= r.arrival < 30 for r in a)
    assert all(0 <= t < 1000 for r in a for t in r.prompt)
    assert [r.arrival for r in a] == sorted(r.arrival for r in a)


def test_seeds_change_the_token_ids_not_the_schedule():
    mix = traffic.load("chat")
    a, b = (traffic.open_loop(mix, seed, 30, 1000) for seed in (1, 2))
    assert [(r.arrival, len(r.prompt), r.max_new) for r in a] == [(r.arrival, len(r.prompt), r.max_new) for r in b]
    assert [r.prompt for r in a] != [r.prompt for r in b]
    n = len(traffic._requests(mix, 1, 41, 1000, 0, 0))
    reqs = traffic._requests(mix, 1, n, 1000, 0, 0)
    assert sorted(len(r.prompt) for r in reqs) == list(traffic.quantiles(mix["prompt_len"], n))
    assert sorted(r.max_new for r in reqs) == list(traffic.quantiles(mix["output_len"], n))
    assert [len(r.prompt) for r in reqs] != sorted(len(r.prompt) for r in reqs)


def test_backlog_blocks():
    mix = traffic.load("docs")
    reqs = list(itertools.islice(traffic.backlog(mix, 5, 1000), 3 * mix["block"]))
    assert [r.rid for r in reqs] == list(range(3 * mix["block"]))
    assert all(r.arrival == 0.0 for r in reqs)
    per_block = [collections.Counter(len(r.prompt) for r in reqs[i:i + mix["block"]])
                 for i in range(0, len(reqs), mix["block"])]
    assert per_block[0] == per_block[1] == per_block[2]
    again = list(itertools.islice(traffic.backlog(mix, 5, 1000), 3 * mix["block"]))
    assert [r.prompt for r in again] == [r.prompt for r in reqs]
