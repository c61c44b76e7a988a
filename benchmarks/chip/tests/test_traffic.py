"""The frozen generators and the one mix builder: determinism by seed,
and the same work offered under every seed."""
import collections
import json
import os

import numpy as np
import pytest

from traffic.gen import mix
from traffic.gen.workloads import swe_workload, zipf_workload
from traffic.gen.world import SemanticWorld

CHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def spec(name, **kw):
    with open(os.path.join(CHIP, "traffic", name + ".json")) as f:
        s = json.load(f)
    s.update(kw)
    return s


SMALL = {
    "zipf": dict(world={"n_intents": 3000}, prefill_intents=300),
    "swe": dict(world={"n_intents": 300}, warmup_tasks=30),
}


def build(name, seed, seconds=8.0):
    return mix.build(spec(name, **SMALL[name]), dim=32, seed=seed,
                     seconds=seconds)


@pytest.mark.parametrize("name", ["zipf", "swe"])
def test_same_seed_same_traffic(name):
    a, b = build(name, 2**31 + 11), build(name, 2**31 + 11)
    assert a.window == b.window and a.warmup == b.warmup
    assert a.prefill == b.prefill
    q = a.window[0][1]
    assert np.array_equal(a.world.embed(q), b.world.embed(q))


@pytest.mark.parametrize("name", ["zipf", "swe"])
def test_every_seed_offers_the_same_work(name):
    a, b = build(name, 5), build(name, 6)
    assert a.window != b.window                      # another order
    assert [t for t, _ in a.window] == [t for t, _ in b.window] or \
        name == "swe"                                # same schedule
    assert collections.Counter(q for _, q in a.window) == \
        collections.Counter(q for _, q in b.window)
    assert a.warmup == b.warmup and a.prefill == b.prefill
    for m in (a, b):
        due = [t for t, _ in m.window]
        assert due == sorted(due) and 0 <= due[0] and due[-1] < 8.0
    assert np.array_equal(a.world.centers, b.world.centers)   # same world


def test_zipf_count_and_prefill():
    m = build("zipf", 1)
    rate = spec("zipf")["rate_per_s"]
    assert len(m.window) == round(rate * 8.0)
    assert len(m.prefill) == 300 == len(set(m.prefill))
    assert len(m.warmup) == spec("zipf")["warmup_lookups"]


def test_frozen_generators_are_deterministic():
    w1, w2 = SemanticWorld(500, 16, seed=3), SemanticWorld(500, 16, seed=3)
    assert np.array_equal(w1.centers, w2.centers)
    z1 = [r.query for r in zipf_workload(w1, 50, seed=4)]
    z2 = [r.query for r in zipf_workload(w2, 50, seed=4)]
    assert z1 == z2
    s1 = [(r.arrival, r.query) for r in swe_workload(w1, 10, seed=4)]
    s2 = [(r.arrival, r.query) for r in swe_workload(w2, 10, seed=4)]
    assert s1 == s2
