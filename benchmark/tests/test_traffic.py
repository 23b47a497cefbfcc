"""The seeded traffic generator: same seed, same schedule; another seed,
the same work in another order."""

import collections
import json
import os

import numpy as np
import pytest

from benchmark import traffic
from conftest import ROOT

CONFIG = json.load(open(os.path.join(
    ROOT, "benchmark", "configs", "ellipse-400x600.json")))
MIX = json.load(open(os.path.join(
    ROOT, "benchmark", "traffic", "served-poisson.json")))
SEEDS = [0, 7, 2**31 + 5, 2**40 + 3, -12]


@pytest.mark.parametrize("seed", SEEDS)
def test_same_seed_same_schedule(seed):
    assert traffic.schedule(CONFIG, MIX, seed, 40) == \
        traffic.schedule(CONFIG, MIX, seed, 40)
    assert traffic.eps_for_run(CONFIG, seed) == \
        traffic.eps_for_run(CONFIG, seed)
    assert traffic.request_eps(CONFIG, seed, 50) == \
        traffic.request_eps(CONFIG, seed, 50)


def test_different_seeds_differ():
    schedules = [traffic.schedule(CONFIG, MIX, s, 40) for s in SEEDS]
    for i, a in enumerate(schedules):
        for b in schedules[i + 1:]:
            assert a != b
    runs = {traffic.eps_for_run(CONFIG, s) for s in range(40)}
    assert len(runs) == len(CONFIG["eps_choices"])


def test_every_seed_gets_the_same_work():
    """The gaps are one set of quantiles and the ε one multiset, in a
    seed-set order."""
    gaps = [np.sort(traffic.gaps(MIX, s, 40)) for s in SEEDS]
    assert all(np.array_equal(g, gaps[0]) for g in gaps)
    orders = {tuple(traffic.gaps(MIX, s, 40)) for s in SEEDS}
    assert len(orders) == len(SEEDS)
    counts = [collections.Counter(traffic.request_eps(CONFIG, s, 60))
              for s in SEEDS]
    assert all(c == counts[0] for c in counts)
    assert set(counts[0].values()) == {60 // len(CONFIG["eps_choices"])}


@pytest.mark.parametrize("seed", SEEDS)
def test_rate_and_window(seed):
    times = traffic.arrival_times(MIX, seed, 40)
    assert times[0] == 0.0 and np.all(np.diff(times) > 0)
    assert times[-1] < 40
    assert len(times) == round(MIX["rate_per_s"] * 40)
    assert len(traffic.schedule(CONFIG, MIX, seed, 40)) == len(times)

