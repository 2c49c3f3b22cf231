"""The ``laguna-s-2.1`` side of the harness at a size a test can hold: a tiny
cell added by files and entries alone runs and comes out ``correct``; the int8
control and three ways of breaking the timed path come out not correct, and so
do the stand-ins that ``calibrate_laguna.py`` judges; ``counts_laguna.py``
agrees with a count by hand and with the published configuration's arithmetic."""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

import tiny  # noqa: E402
import tiny_laguna  # noqa: E402
from benchmark import counts_laguna, reference_laguna  # noqa: E402

CONFIG = tiny_laguna.TINY_LAGUNA
LIMITS = CONFIG["limits"]["train"]
ON_THE_CHIP_ONLY = {"train_optimizer_share", "train_loss_head_share", "train_attn_kernel_share", "train_unattributed_share",
                    "flash_fwd_ms_per_step", "flash_dq_ms_per_step", "flash_dkv_ms_per_step", "train_moe_route_share",
                    "train_moe_experts_share", "train_moe_shared_share", "train_attn_gate_share", "moe_experts_roofline.laguna",
                    "flash_attn_roofline.laguna", "flash_window_roofline.laguna"}


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    return tiny_laguna.add_cell(tiny.make_tree(str(tmp_path_factory.mktemp("bench") / "tree")))


def job():
    with open(os.path.join(HERE, "..", "traffic", "train-laguna-8k.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("trace", [0, 1])
def test_the_tiny_cell_runs_and_is_correct(tree, trace):
    code, line, err = tiny_laguna.run_cell(tree, "--workload", "tiny-laguna", "--seed", "3000000019", "--seconds", "3",
                                           "--trace", str(trace))
    assert code == 0, err[-3000:]
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0, (line, err[-3000:])
    assert line["checks"]["check_steps_overflowed"]["value"] == 0.0
    bench = json.load(open(os.path.join(tree, "BENCHMARK.json")))
    mine = [m["name"] for m in bench["per_layer" if trace else "end_to_end"] if "tiny-laguna" in m.get("workloads", ["tiny-laguna"])]
    assert set(line["metrics"]) >= set(mine) - ON_THE_CHIP_ONLY, line["metrics"]
    if trace:  # the counters reach the line off the chip too
        assert line["metrics"]["moe_held_load_max_over_mean"]["value"] >= 1.0
        assert line["metrics"]["moe_overflow_layers_per_step"]["value"] == 0.0
        assert 0 < line["metrics"]["train_step_mfu.laguna"]["value"]


@pytest.mark.parametrize("fault", ["half_batch", "shared_dropped", "window_ignored"])
def test_a_broken_timed_path_comes_out_not_correct(tree, fault):
    code, line, err = tiny_laguna.run_cell(tree, "--workload", "tiny-laguna", "--seed", "11", "--seconds", "2", "--trace", "0",
                                           fault=fault)
    assert code == 0 and line is not None, err[-3000:]
    assert line["correct"] is False, (line, err[-2000:])
    assert any(c["value"] > c["limit"] for c in line["checks"].values())


def test_calibrate_judges_each_stand_in_as_a_run_would_be(tree):
    code, line, err = tiny_laguna.run_cell(tree, "--workload", "tiny-laguna", "--seeds", "11", "--seconds", "1", "--control", "int8",
                                           "--faults", "half_batch", "--stand-in-seeds", "11", fault="calibrate")
    assert code == 0 and line is not None and line["correct"] is True, err[-3000:]
    assert line["steps_overflowed"] == 0 and line["pairs_held_min"] > 0
    for what in ("int8", "half_batch"):
        checks = line[what]["checks"]
        assert line[what]["correct"] is False and not all(c["ok"] for c in checks.values()), line[what]
        assert set(checks) == set(LIMITS) - {"check_steps_overflowed"}  # a stand-in has no path of its own to overflow


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_int8_reference_fails_a_training_limit(seed):
    from benchmark.drivers import train

    feed = train.Feed(seed, CONFIG["vocab_size"], 2, 256, 0, 0)
    ref = reference_laguna.train_steps(CONFIG, seed, feed.fed, job())
    control = train.compare(reference_laguna.train_steps(CONFIG, seed, feed.fed, job(), precision="int8"), ref)
    assert any(control[name] > limit for name, limit in LIMITS.items() if name in control), control
    planted = train.compare(reference_laguna.train_steps(CONFIG, seed, feed.fed, job(), fault="half_batch"), ref)
    assert any(planted[name] > limit for name, limit in LIMITS.items() if name in planted), planted


def test_a_program_that_does_not_read_the_keys_ends_at_once(monkeypatch):
    """What the parent commit does with the cell: ``model_config`` raises before anything is built."""
    from benchmark.drivers import train_laguna
    from dmlcloud_tpu.models import hf

    monkeypatch.setattr(hf, "_laguna_keys", lambda config: {})
    with pytest.raises(SystemExit, match="does not read model_type 'laguna'"):
        train_laguna.model_config(CONFIG, {"seq_len": 256})


def test_counts_against_a_count_by_hand():
    s = dict(reference_laguna.spec(CONFIG))
    d, f, fe, fs, v, kh, hd, e = 64, 160, 48, 48, 256, 1, 16, 16
    attn = lambda h: 2 * d * h * hd + 2 * d * kh * hd + d * h + d
    expert_layer = 4 * 3 * d * fe + d * e + 3 * d * fs + d
    assert counts_laguna.param_count(s) == 2 * attn(2) + 3 * attn(3) + (3 * d * f + d) + 4 * expert_layer + 2 * v * d + d
    proj = lambda h: 4 * d * h * hd + 4 * d * kh * hd + 2 * d * h
    per_token = 2 * proj(2) + 3 * proj(3) + 6 * d * f + 4 * (6 * d * fs + 2 * d * e) + 2 * d * v
    assert sum(counts_laguna.forward_flops_per_token(s).values()) == per_token
    seq, pairs, w = 256, 300.0, 64
    triangle = seq * (seq + 1) // 2
    band = w * (w + 1) // 2 + (seq - w) * w  # the first w queries see all before them, the rest w keys each
    assert counts_laguna.kept_elements(s, "full_attention", seq) == triangle and counts_laguna.kept_elements(s, "sliding_attention", seq) == band
    scores = 2 * 4 * 2 * hd * triangle + 3 * 4 * 3 * hd * band
    assert counts_laguna.attention_flops(s, 2, seq) == 2 * scores
    assert counts_laguna.train_flops_per_step(s, 2, seq, pairs) == 3 * (2 * seq * per_token + 2 * scores + pairs * 6 * d * fe)
    assert counts_laguna.grouped_flops_per_step(s, pairs) == 3 * pairs * 6 * d * fe
    assert counts_laguna.flash_flops_per_step(s, 2, seq, ("sliding_attention",)) == 3 * 2 * 3 * 4 * 3 * hd * band
    q = lambda h: 2 * seq * h * hd * 2
    kv = 2 * seq * kh * hd * 2
    assert counts_laguna.flash_bytes_per_step(s, 2, seq, ("full_attention",)) == 2 * (9 * q(2) + 8 * kv)


def test_the_published_configuration_counts_the_parameters_the_issue_reckons():
    with open(os.path.join(HERE, "..", "configs", "laguna-s-2.1.json")) as f:
        s = dict(reference_laguna.spec(json.load(f)))
    assert counts_laguna.param_count(s) == pytest.approx(567.8e6, rel=0.002)
    per_token = counts_laguna.forward_flops_per_token(s)
    assert per_token["dense_ffn"] == pytest.approx(226e6, rel=0.01) and per_token["head"] == pytest.approx(77e6, rel=0.01)
    assert per_token["shared_expert"] == pytest.approx(75e6, rel=0.01) and per_token["router"] == pytest.approx(6.3e6, rel=0.01)
    assert per_token["attn_proj"] + per_token["attn_gate"] == pytest.approx(69e6, rel=0.03)
    # attention's products a token: the full layers' triangle and the window layers' band, averaged over 8192 positions
    assert counts_laguna.attention_flops(s, 1, 8192) / 8192 == pytest.approx(32e6, rel=0.05)
    assert 2560 * counts_laguna.pair_flops(s) * 4 / 8192 == pytest.approx(24e6, rel=0.03)  # the held experts at the even share
