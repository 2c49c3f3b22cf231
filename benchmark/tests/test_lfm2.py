"""The ``lfm2-24b-a2b`` side of the harness at a size a test can hold: a tiny
cell added by files and entries alone runs and comes out ``correct``; the int8
control and two ways of breaking the timed path come out not correct, and so
do the stand-ins that ``calibrate_lfm2.py`` judges; the
balanced ``expert_bias`` levels the loads and is a function of the seed;
``counts_lfm2.py`` agrees with a count by hand."""

import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

import tiny  # noqa: E402
import tiny_lfm2  # noqa: E402
from benchmark import counts_lfm2, reference_lfm2  # noqa: E402

CONFIG = tiny_lfm2.TINY_LFM2
LIMITS = CONFIG["limits"]["train"]
ON_THE_CHIP_ONLY = {"train_optimizer_share", "train_loss_head_share", "train_attn_kernel_share", "train_unattributed_share",
                    "flash_fwd_ms_per_step", "flash_dq_ms_per_step", "flash_dkv_ms_per_step", "train_conv_op_share",
                    "train_moe_route_share", "train_moe_experts_share", "moe_experts_roofline", "flash_attn_roofline.lfm2"}


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    return tiny_lfm2.add_cell(tiny.make_tree(str(tmp_path_factory.mktemp("bench") / "tree")))


def job():
    with open(os.path.join(HERE, "..", "traffic", "train-lfm2-8k.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("trace", [0, 1])
def test_the_tiny_cell_runs_and_is_correct(tree, trace):
    code, line, err = tiny_lfm2.run_cell(tree, "--workload", "tiny-lfm2", "--seed", "3000000019", "--seconds", "3",
                                         "--trace", str(trace))
    assert code == 0, err[-3000:]
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0, (line, err[-3000:])
    assert line["checks"]["expert_bias_moved"]["value"] == 0.0
    bench = json.load(open(os.path.join(tree, "BENCHMARK.json")))
    mine = [m["name"] for m in bench["per_layer" if trace else "end_to_end"] if "tiny-lfm2" in m.get("workloads", ["tiny-lfm2"])]
    assert set(line["metrics"]) >= set(mine) - ON_THE_CHIP_ONLY, line["metrics"]
    if trace:  # the counters reach the line off the chip too
        assert line["metrics"]["moe_held_load_max_over_mean"]["value"] >= 1.0
        assert 0 < line["metrics"]["train_step_mfu.lfm2"]["value"]


@pytest.mark.parametrize("fault", ["half_batch", "expert_zeroed"])
def test_a_broken_timed_path_comes_out_not_correct(tree, fault):
    code, line, err = tiny_lfm2.run_cell(tree, "--workload", "tiny-lfm2", "--seed", "11", "--seconds", "2", "--trace", "0",
                                         fault=fault)
    assert code == 0 and line is not None, err[-3000:]
    assert line["correct"] is False, (line, err[-2000:])
    assert any(c["value"] > c["limit"] for c in line["checks"].values())


def test_calibrate_judges_each_stand_in_as_a_run_would_be(tree):
    code, line, err = tiny_lfm2.run_cell(tree, "--workload", "tiny-lfm2", "--seeds", "11", "--seconds", "1", "--control", "int8",
                                         "--faults", "half_batch", "--stand-in-seeds", "11", fault="calibrate")
    assert code == 0 and line is not None and line["correct"] is True, err[-3000:]
    for what in ("int8", "half_batch"):
        checks = line[what]["checks"]
        assert line[what]["correct"] is False and not all(c["ok"] for c in checks.values()), line[what]
        assert set(checks) == set(LIMITS) - {"expert_bias_moved", "expert_load_gap"}  # a stand-in has no state and no bias of its own


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_int8_reference_fails_a_training_limit(seed):
    from benchmark.drivers import train

    feed = train.Feed(seed, CONFIG["vocab_size"], 2, 256, 0, 0)
    biases, _ = reference_lfm2.balanced_expert_bias(CONFIG, seed, feed.fed)
    ref = reference_lfm2.train_steps(CONFIG, seed, feed.fed, job(), biases)
    control = train.compare(reference_lfm2.train_steps(CONFIG, seed, feed.fed, job(), biases, precision="int8"), ref)
    assert any(control[name] > limit for name, limit in LIMITS.items() if name in control), control
    planted = train.compare(reference_lfm2.train_steps(CONFIG, seed, feed.fed, job(), biases, fault="half_batch"), ref)
    assert any(planted[name] > limit for name, limit in LIMITS.items() if name in planted), planted


def test_the_balanced_bias_levels_the_loads_and_follows_the_seed():
    from benchmark.drivers import train

    import jax
    import jax.numpy as jnp

    feed = train.Feed(5, CONFIG["vocab_size"], 2, 256, 0, 0)
    biases, gaps = reference_lfm2.balanced_expert_bias(CONFIG, 5, feed.fed)
    again, _ = reference_lfm2.balanced_expert_bias(CONFIG, 5, feed.fed)
    other, _ = reference_lfm2.balanced_expert_bias(CONFIG, 6, feed.fed)
    assert sorted(biases) == [1, 2, 3, 4] and all(np.array_equal(biases[i], again[i]) for i in biases)
    assert any(not np.array_equal(biases[i], other[i]) for i in biases)
    # 1,536 tokens choose 4 of 16: the mean load is 384, and a step of the bias moves a few tokens
    assert max(gaps.values()) <= 0.05, gaps
    # and the band is of the loads the bias gives, not of the search's own book-keeping
    scores = jax.nn.sigmoid(jax.random.normal(jax.random.PRNGKey(0), (4096, 16)) + jnp.linspace(-0.5, 0.5, 16))
    bias, gap = reference_lfm2.level_bias(scores, 4)
    _, chosen = jax.lax.top_k(scores + bias, 4)
    load = np.bincount(np.asarray(chosen).ravel(), minlength=16)
    assert float(gap) == pytest.approx(np.abs(load - 1024).max() / 1024) and float(gap) <= 0.03
    unlevelled = np.bincount(np.asarray(jax.lax.top_k(scores, 4)[1]).ravel(), minlength=16)
    assert np.abs(unlevelled - 1024).max() / 1024 > 0.2


def test_counts_against_a_count_by_hand():
    s = dict(reference_lfm2.spec(CONFIG))
    d, f, fe, v, h, kh, hd = 64, 160, 48, 256, 4, 2, 16
    conv = 3 * d * d + d * d + 3 * d + d
    attn = 2 * d * h * hd + 2 * d * kh * hd + 2 * hd + d
    expert_layer = 4 * 3 * d * fe + d * 16 + d
    assert counts_lfm2.param_count(s) == 4 * conv + attn + (3 * d * f + d) + 4 * expert_layer + v * d + d
    per_token = 4 * (2 * d * 3 * d + 2 * d * d + 2 * 3 * d + 2 * d) + (4 * d * h * hd + 4 * d * kh * hd) + 6 * d * f \
        + 4 * 2 * d * 16 + 2 * d * v
    assert sum(counts_lfm2.forward_flops_per_token(s).values()) == per_token
    seq, pairs = 256, 300.0
    scores = 4 * h * hd * (seq * (seq + 1) // 2)  # QK^T and PV over the causal triangle, one layer
    assert counts_lfm2.train_flops_per_step(s, 2, seq, pairs) == 3 * (2 * seq * per_token + 2 * scores + pairs * 6 * d * fe)
    assert counts_lfm2.grouped_flops_per_step(s, pairs) == 3 * pairs * 6 * d * fe
    assert counts_lfm2.flash_flops_per_step(s, 2, seq) == 3 * 2 * scores


def test_the_published_configuration_counts_the_parameters_the_issue_reckons():
    with open(os.path.join(HERE, "..", "configs", "lfm2-24b-a2b.json")) as f:
        s = dict(reference_lfm2.spec(json.load(f)))
    assert counts_lfm2.param_count(s) == pytest.approx(469e6, rel=0.005)
    # the xla cost of the reference's dense forward agrees with the count, as test_counts.py checks for counts.py
    per_token = counts_lfm2.forward_flops_per_token(s)
    assert per_token["dense_ffn"] == pytest.approx(145e6, rel=0.01) and per_token["conv_op"] == pytest.approx(134e6, rel=0.01)
