"""The traffic generator: one seed, one schedule; every seed the same sizes
in another order; lengths follow the stated distributions."""
import json
import os
import statistics

import numpy as np
import pytest

import benchpath  # noqa: F401
from chipbench import traffic

MIXES = sorted(f[:-5] for f in os.listdir(
    os.path.join(benchpath.BENCH_DIR, "traffic")) if f.endswith(".json"))
SEEDS = (1319105951, 2 ** 31 + 5, 7)


def mix(name):
    with open(os.path.join(benchpath.BENCH_DIR, "traffic",
                           name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_schedule(name):
    a = traffic.schedule(mix(name), 1319105951, 32000)
    b = traffic.schedule(mix(name), 1319105951, 32000)
    assert [(r.rid, r.max_new) for r in a] == [(r.rid, r.max_new) for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


@pytest.mark.parametrize("name", MIXES)
def test_seeds_permute_one_set_of_sizes(name):
    m = mix(name)
    block = m["arrivals"]["sessions"]
    firsts = []
    for seed in SEEDS:
        reqs = traffic.schedule(m, seed, 32000)
        assert all(0 <= r.prompt.min() and r.prompt.max() < 32000
                   for r in reqs)
        firsts.append((sorted(len(r.prompt) for r in reqs[:block]),
                       sorted(r.max_new for r in reqs[:block]),
                       [len(r.prompt) for r in reqs[:block]]))
    assert all(f[0] == firsts[0][0] and f[1] == firsts[0][1]
               for f in firsts)
    assert len({tuple(f[2]) for f in firsts}) > 1 or block == 1


def test_lognormal_quantiles_follow_the_distribution():
    d = {"dist": "lognormal", "median": 512, "sigma": 0.8, "min": 1,
         "max": 10 ** 9}
    x = traffic.quantiles(d, 4096)
    assert abs(statistics.median(x) - 512) <= 1
    assert abs(np.std(np.log(x)) - 0.8) < 0.01
    clipped = traffic.quantiles(dict(d, min=32, max=2048), 4096)
    assert clipped.min() == 32 and clipped.max() == 2048
    assert np.all(np.diff(x) >= 0)


@pytest.mark.parametrize("name", MIXES)
def test_sessions_fill_the_slots(name):
    m = mix(name)
    reqs = traffic.schedule(m, 3, 1000)
    assert len(reqs) == m["arrivals"]["sessions"] <= m["slots"]
    assert all(m["prompt"]["min"] <= len(r.prompt) <= m["prompt"]["max"]
               for r in reqs)
    with pytest.raises(ValueError):
        traffic.schedule(dict(m, arrivals={"process": "poisson"}), 3, 1000)
