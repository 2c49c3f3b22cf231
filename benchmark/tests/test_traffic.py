"""The traffic generator: for a mix and a window, every seed offers the same
count, the same multiset of prompt and answer lengths and the same total of
tokens; only the order, the pairing, the arrival offsets and the ids change."""

import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmark import traffic  # noqa: E402

MIXES = [n[:-5] for n in sorted(os.listdir(os.path.join(HERE, "..", "traffic")))
         if json.load(open(os.path.join(HERE, "..", "traffic", n))).get("driver") == "serve"]


def load(name):
    return json.load(open(os.path.join(HERE, "..", "traffic", f"{name}.json")))


@pytest.mark.parametrize("mix", MIXES)
def test_every_seed_offers_the_same_work(mix):
    spec = load(mix)
    runs = [traffic.generate(spec, 45.0, seed, 32000) for seed in (1, 2, 3_000_000_007)]
    offered = [traffic.offered(r) for r in runs]
    assert offered[0] == offered[1] == offered[2] and offered[0]["requests"] > 0
    multiset = lambda reqs, key: sorted(key(r) for r in reqs if r.measured)
    for key in (lambda r: len(r.prompt), lambda r: r.answer_len, lambda r: (len(r.prompt), r.answer_len)):
        assert multiset(runs[0], key) == multiset(runs[1], key) == multiset(runs[2], key)


@pytest.mark.parametrize("mix", MIXES)
def test_seeds_differ_in_order_and_one_seed_repeats(mix):
    spec = load(mix)
    a, b, again = (traffic.generate(spec, 45.0, s, 32000) for s in (1, 2, 1))
    measured = lambda reqs: [r for r in reqs if r.measured]
    assert [len(r.prompt) for r in measured(a)] != [len(r.prompt) for r in measured(b)]
    assert all(np.array_equal(x.prompt, y.prompt) and x.due_s == y.due_s for x, y in zip(a, again))
    assert not np.array_equal(measured(a)[0].prompt, measured(b)[0].prompt)  # the ids are the seed's too


def test_a_paced_schedule_is_turned_not_redrawn():
    """Every seed offers the same arrivals at the same spacing round the
    window's cycle; the lead-in is the stretch of the cycle before the opening."""
    spec = load("chat")
    a, b = (traffic.generate(spec, 45.0, s, 32000) for s in (5, 6))
    def gaps(reqs):
        m = [r for r in reqs if r.measured]
        cycle = [(y.due_s - x.due_s) % 45.0 for x, y in zip(m, m[1:] + m[:1])]
        k = max(range(len(m)), key=lambda i: (len(m[i].prompt), m[i].answer_len, cycle[i]))
        return [round(g, 9) for g in cycle[k:] + cycle[:k]]
    assert gaps(a) == gaps(b)
    lead = [r for r in a if not r.measured]
    tail = [r for r in a if r.measured and r.due_s >= 45.0 - spec["lead_in_s"]]
    assert [(round(r.due_s + 45.0, 9), len(r.prompt)) for r in lead] == [(round(r.due_s, 9), len(r.prompt)) for r in tail]


@pytest.mark.parametrize("mix", MIXES)
def test_lengths_stay_inside_the_mix_and_the_context(mix):
    spec = load(mix)
    reqs = traffic.generate(spec, 45.0, 5, 32000)
    assert all(spec["prompt_len"]["min"] <= len(r.prompt) <= spec["prompt_len"]["max"] for r in reqs)
    assert all(len(r.prompt) + r.answer_len <= 4096 for r in reqs)
    assert all(0 <= r.due_s <= 45.0 for r in reqs if r.measured) and all(r.due_s < 0 for r in reqs if not r.measured)


def test_quantile_midpoints_follow_the_distribution():
    lens = traffic.quantile_lengths({"dist": "lognormal", "median": 128, "sigma": 0.9, "min": 32, "max": 1024}, 1001)
    assert lens[500] == 128 and lens[0] == 32 and lens[-1] == 1024 and (np.diff(lens) >= 0).all()
    assert list(traffic.quantile_lengths({"dist": "loguniform", "min": 1024, "max": 3968}, 3)) == [1283, 2016, 3166]


def test_slotted_arrivals_put_one_in_each_slot():
    off = traffic.arrival_offsets({"kind": "slotted", "rate_per_s": 2.0}, 10.0, np.random.default_rng(0))
    assert len(off) == 20 and all(i * 0.5 <= t < (i + 1) * 0.5 for i, t in enumerate(off))
