"""The traffic generator: seeded, in range, and the same work for every
seed."""
import json

import numpy as np
import pytest

from bench import generator, spec


def _spec(name):
    with open(spec.BENCH_DIR / "traffic" / f"{name}.json") as f:
        return json.load(f)


def _take(it, n):
    return [next(it) for _ in range(n)]


@pytest.mark.parametrize("seed", [0, 7, 2 ** 31 + 5, 2 ** 33 + 1])
def test_same_seed_same_requests(seed):
    s = _spec("gen")
    a = generator.Traffic(s, seed, 50304)
    b = generator.Traffic(s, seed, 50304)
    ra, rb = _take(a.backlog(), 40), _take(b.backlog(), 40)
    assert [(r.prompt, r.max_new) for r in ra] == \
        [(r.prompt, r.max_new) for r in rb]


@pytest.mark.parametrize("seeds", [(1, 2), (3, 2 ** 31 + 3)])
def test_seeds_change_the_tokens_not_the_work(seeds):
    s = _spec("gen")
    a, b = (generator.Traffic(s, seed, 100) for seed in seeds)
    a, b = _take(a.backlog(), 48), _take(b.backlog(), 48)
    assert [(len(r.prompt), r.max_new) for r in a] == \
        [(len(r.prompt), r.max_new) for r in b]
    assert [r.prompt for r in a] != [r.prompt for r in b]


def test_backlog_lengths_and_warm_start():
    s = _spec("gen")
    k = s["engine"]["num_slots"]
    reqs = _take(generator.Traffic(s, 3, 50304).backlog(), k + 64)
    for r in reqs:
        assert s["prompt_len"]["min"] <= len(r.prompt) <= \
            s["prompt_len"]["max"]
        assert all(0 <= t < 50304 for t in r.prompt)
    warm, rest = reqs[:k], reqs[k:]
    for r in rest:
        assert s["output_len"]["min"] <= r.max_new <= s["output_len"]["max"]
    # warm-start slots keep a stratified share of their budget: at least
    # one nearly done, none above the longest budget
    assert min(r.max_new for r in warm) < s["output_len"]["min"] / 4
    assert max(r.max_new for r in warm) <= s["output_len"]["max"]
    # log-uniform stratification: the block's median sits at the geometric
    # middle of the range
    block = [len(r.prompt) for r in rest[:s["block"]]]
    mid = np.sqrt(s["prompt_len"]["min"] * s["prompt_len"]["max"])
    assert abs(np.median(block) - mid) / mid < 0.1


@pytest.mark.parametrize("dist", [
    {"dist": "loguniform", "min": 16, "max": 128},
    {"dist": "uniform", "min": 16, "max": 64}])
def test_lengths_hold_their_range_and_quantiles(dist):
    v = generator.lengths(dist, 1000, np.random.default_rng(0))
    assert v.min() >= dist["min"] and v.max() <= dist["max"]
    mid = (np.sqrt(dist["min"] * dist["max"]) if dist["dist"] == "loguniform"
           else (dist["min"] + dist["max"]) / 2)
    assert abs(np.median(v) - mid) / mid < 0.02
