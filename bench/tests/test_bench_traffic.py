"""The traffic generator: every seed gets the same multiset of sizes and
gaps in another order, every arrival lands inside the window, and large
seeds work."""
from __future__ import annotations

import json
import os

import numpy as np
import pytest

from bench.harness import traffic as T

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def mix(name):
    with open(os.path.join(BENCH, "traffic", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", ["longgen-32", "longgen-16", "chat-8"])
def test_seeds_permute_one_multiset(name):
    m = mix(name)
    a = T.plan(m, 3000000001, 48.0, 1000)
    b = T.plan(m, 2 ** 33 + 5, 48.0, 1000)
    assert len(a) == len(b)
    for g in ("in_flight", "backlog", "arrivals"):
        ga = [p for p in a if p.group == g]
        gb = [p for p in b if p.group == g]
        assert sorted(len(p.prompt) for p in ga) == \
            sorted(len(p.prompt) for p in gb)
        assert sorted(p.max_new for p in ga) == sorted(p.max_new for p in gb)
        if g == "arrivals":
            da, db = np.diff([0] + [p.due for p in ga]), \
                np.diff([0] + [p.due for p in gb])
            assert np.allclose(sorted(da), sorted(db))
            assert [p.max_new for p in ga] != [p.max_new for p in gb]
    assert all(0 <= p.due < 48.0 for p in a if p.group == "arrivals")
    assert all(p.prompt and max(p.prompt) < 1000 for p in a)
    assert T.plan(m, 3000000001, 48.0, 1000)[0].prompt == a[0].prompt


def test_lengths_respect_the_mix_caps():
    for name in ("longgen-32", "longgen-16", "chat-8"):
        m = mix(name)
        for p in T.plan(m, 7, 48.0, 1000):
            assert 1 <= len(p.prompt) <= m["prompt_cap"]
            assert len(p.prompt) + p.max_new + 3 - 1 <= m["max_seq"]


def test_quantiles_and_rate():
    v = T.quantiles({"kind": "uniform", "min": 10, "max": 20}, 5)
    assert v.tolist() == [11, 13, 15, 17, 19]
    g = T.gaps("poisson", 2.0, 1000)
    assert abs(g.mean() - 0.5) < 0.01
