"""The control of "How correct is decided", at a size a test run can hold: the
reference computed in the precision below the configuration's (8-bit integers
for bfloat16) and put in the program's place comes out as not correct, by the
same comparison and the same limits that pass the program's sound runs
(``test_run.py``). The chip's readings at the cells' own sizes are in PERF.md."""

import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

import tiny  # noqa: E402
from benchmark import reference  # noqa: E402

HF = {k: v for k, v in tiny.TINY_CONFIG.items() if isinstance(v, (int, float)) or v is None}
LIMITS = tiny.TINY_CONFIG["limits"]


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_int8_reference_fails_the_served_tokens_limit(seed):
    rng = np.random.default_rng(seed)
    sample = [(rng.integers(0, 256, n, dtype=np.int32), rng.integers(0, 256, 24, dtype=np.int32)) for n in (100, 60, 30, 90)]
    layers = tiny.TINY_CONFIG["serve"]["num_hidden_layers"]
    # the tokens a plain greedy decode would serve: the reference's own argmax, position by position
    logits = reference.served_logits(HF, layers, seed, sample)
    sound = [np.zeros(len(s[1])) for s in sample]  # the reference's best has gap 0 by definition
    control = reference.served_token_gaps(HF, layers, seed, sample, precision="int8")
    assert max(g.max() for g in sound) <= LIMITS["serve"]["logit_gap_max"]
    assert max(g.max() for g in control) > LIMITS["serve"]["logit_gap_max"]
    assert all(lg.shape == (24, 256) for lg in logits)


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_int8_reference_fails_a_training_limit(seed):
    from benchmark.drivers import train

    with open(os.path.join(HERE, "..", "traffic", "train-8k.json")) as f:
        job = json.load(f)
    feed = train.Feed(seed, 256, 2, 256, 0, 0)
    layers = tiny.TINY_CONFIG["train"]["num_hidden_layers"]
    ref = reference.train_steps(HF, layers, seed, feed.fed, job)
    control = train.compare(reference.train_steps(HF, layers, seed, feed.fed, job, precision="int8"), ref)
    assert any(control[name] > limit for name, limit in LIMITS["train"].items()), control
    for fault in ("unchanged_state", "half_batch"):
        planted = train.compare(reference.train_steps(HF, layers, seed, feed.fed, job, fault=fault), ref)
        assert any(planted[name] > limit for name, limit in LIMITS["train"].items()), (fault, planted)
