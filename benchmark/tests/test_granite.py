"""The ``granite-4.0-h-micro`` side of the harness at a size a test can hold: a
tiny cell added by files and entries alone runs and comes out ``correct``; the
int8 control and four ways of breaking the timed path come out not correct,
and so do the stand-ins that ``calibrate_granite.py`` judges;
``counts_granite.py`` agrees with a count by hand, with the program's tree and
with the published configuration's arithmetic."""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

import tiny  # noqa: E402
import tiny_granite  # noqa: E402
from benchmark import counts_granite, reference_granite  # noqa: E402

CONFIG = tiny_granite.TINY_GRANITE
LIMITS = CONFIG["limits"]["train"]
ON_THE_CHIP_ONLY = {"train_optimizer_share", "train_loss_head_share", "train_attn_kernel_share", "train_unattributed_share",
                    "flash_fwd_ms_per_step", "flash_dq_ms_per_step", "flash_dkv_ms_per_step", "train_ssm_scan_share",
                    "train_ssm_conv_share", "train_ssm_gate_norm_share", "train_ssm_proj_share", "ssm_scan_roofline.granite",
                    "flash_attn_roofline.granite"}


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    return tiny_granite.add_cell(tiny.make_tree(str(tmp_path_factory.mktemp("bench") / "tree")))


def published():
    with open(os.path.join(HERE, "..", "configs", "granite-4.0-h-micro.json")) as f:
        return json.load(f)


def job():
    with open(os.path.join(HERE, "..", "traffic", "train-granite-8k.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("trace", [0, 1])
def test_the_tiny_cell_runs_and_is_correct(tree, trace):
    code, line, err = tiny_granite.run_cell(tree, "--workload", "tiny-granite", "--seed", "3000000019", "--seconds", "3",
                                            "--trace", str(trace))
    assert code == 0, err[-3000:]
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0, (line, err[-3000:])
    bench = json.load(open(os.path.join(tree, "BENCHMARK.json")))
    mine = [m["name"] for m in bench["per_layer" if trace else "end_to_end"] if "tiny-granite" in m.get("workloads", ["tiny-granite"])]
    assert set(line["metrics"]) >= set(mine) - ON_THE_CHIP_ONLY, line["metrics"]
    if trace:
        assert 0 < line["metrics"]["train_step_mfu.granite"]["value"]
        assert "ssm/state_absmax over the window's steps" in err


@pytest.mark.parametrize("fault", ["half_batch", "carry_dropped", "residual_unscaled", "rope_applied"])
def test_a_broken_timed_path_comes_out_not_correct(tree, fault):
    code, line, err = tiny_granite.run_cell(tree, "--workload", "tiny-granite", "--seed", "11", "--seconds", "2", "--trace", "0",
                                            fault=fault)
    assert code == 0 and line is not None, err[-3000:]
    assert line["correct"] is False, (line, err[-2000:])
    assert any(c["value"] > c["limit"] for c in line["checks"].values())


def test_calibrate_judges_each_stand_in_as_a_run_would_be(tree):
    code, line, err = tiny_granite.run_cell(tree, "--workload", "tiny-granite", "--seeds", "11", "--seconds", "1", "--control", "int8",
                                            "--faults", "half_batch", "--stand-in-seeds", "11", fault="calibrate")
    assert code == 0 and line is not None and line["correct"] is True, err[-3000:]
    assert line["state_absmax_max"] > 0
    for what in ("int8", "half_batch"):
        checks = line[what]["checks"]
        assert line[what]["correct"] is False and not all(c["ok"] for c in checks.values()), line[what]
        assert set(checks) == set(LIMITS)


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_int8_reference_fails_a_training_limit(seed):
    from benchmark.drivers import train

    feed = train.Feed(seed, CONFIG["vocab_size"], 2, 256, 0, 0)
    ref = reference_granite.train_steps(CONFIG, seed, feed.fed, job())
    control = train.compare(reference_granite.train_steps(CONFIG, seed, feed.fed, job(), precision="int8"), ref)
    assert any(control[name] > limit for name, limit in LIMITS.items() if name in control), control
    planted = train.compare(reference_granite.train_steps(CONFIG, seed, feed.fed, job(), fault="half_batch"), ref)
    assert any(planted[name] > limit for name, limit in LIMITS.items() if name in planted), planted


def test_a_program_that_does_not_read_the_keys_ends_at_once(monkeypatch):
    """What the parent commit does with the cell: ``model_config`` raises before anything is built."""
    from benchmark.drivers import train_granite
    from dmlcloud_tpu.models import hf

    monkeypatch.setattr(hf, "_granite_keys", lambda config: {})
    with pytest.raises(SystemExit, match="does not read model_type 'granitemoehybrid'"):
        train_granite.model_config(CONFIG, {"seq_len": 256})


def test_counts_against_a_count_by_hand():
    s = dict(reference_granite.spec(CONFIG))
    d, f, v, h, kh, hd = 64, 160, 256, 2, 1, 16
    mh, p, n, taps, chunk = 4, 16, 32, 4, 64
    d_inner, conv = mh * p, mh * p + 2 * n
    mixer = d * (d_inner + conv + mh) + taps * conv + conv + 3 * mh + d_inner + d_inner * d
    attn = 2 * d * h * hd + 2 * d * kh * hd
    assert counts_granite.param_count(s) == 3 * mixer + attn + 4 * (3 * d * f + 2 * d) + v * d + d
    per_token = 3 * (2 * d * (d_inner + conv + mh) + 2 * d_inner * d) + 3 * 2 * taps * conv + (4 * d * h * hd + 4 * d * kh * hd) + 4 * 6 * d * f + 2 * d * v
    assert sum(counts_granite.forward_flops_per_token(s).values()) == per_token
    batch, seq = 2, 256
    kept = (seq // chunk) * chunk * (chunk + 1) // 2  # the pairs a chunk's causal mask keeps, over the row's chunks
    scan = batch * (kept * (2 * n + mh * (1 + 2 * p)) + seq * mh * (4 * p * n + 2 * p))
    assert counts_granite.scan_flops(s, batch, seq) == scan
    assert counts_granite.scan_bytes(s, batch, seq) == batch * seq * (2 * (2 * d_inner + 2 * n) + 4 * mh + 2 * (3 * d_inner + 4 * n) + 8 * mh)
    triangle = seq * (seq + 1) // 2
    assert counts_granite.attention_flops(s, batch, seq) == batch * 4 * h * hd * triangle
    assert counts_granite.train_flops_per_step(s, batch, seq) == 3 * (batch * seq * per_token + batch * 4 * h * hd * triangle + 3 * scan)
    assert counts_granite.scan_flops_per_step(s, batch, seq) == 9 * scan
    q, kv = batch * seq * h * hd * 2, batch * seq * kh * hd * 2
    assert counts_granite.flash_bytes_per_step(s, batch, seq) == 9 * q + 8 * kv
    assert counts_granite.flash_flops_per_step(s, batch, seq) == 3 * batch * 4 * h * hd * triangle


@pytest.mark.parametrize("which", ["tiny", "published"])
def test_param_count_is_the_programs_tree(which):
    import jax
    import jax.numpy as jnp

    from benchmark.drivers import train_granite
    from dmlcloud_tpu.models.transformer import DecoderLM

    config = CONFIG if which == "tiny" else published()
    cfg = train_granite.model_config(config, {"seq_len": 256})
    shapes = jax.eval_shape(DecoderLM(cfg).init, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    held = sum(int(jnp.prod(jnp.asarray(x.shape))) for x in jax.tree_util.tree_leaves(shapes))
    assert counts_granite.param_count(dict(reference_granite.spec(config))) == held


def test_the_published_configuration_counts_what_the_issue_reckons():
    config = published()
    s = dict(reference_granite.spec(config))
    assert counts_granite.param_count(s) == pytest.approx(653.0e6, rel=0.001)
    per_token = counts_granite.forward_flops_per_token(s)
    assert per_token["mlp"] / 10 == pytest.approx(100.7e6, rel=0.001) and per_token["ssm_proj"] / 9 == pytest.approx(26.3e6, rel=0.01)
    assert per_token["attn_proj"] == pytest.approx(10.5e6, rel=0.01) and per_token["head"] == pytest.approx(51.4e6, rel=0.01)
    # the scan a token and layer: the issue's 2.2 M counts the whole L x L of a chunk, the mask keeps half of it and the diagonal
    assert counts_granite.scan_flops(s, 1, 8192) / 8192 == pytest.approx(1.6e6, rel=0.03)
    whole = {**config, **{k: config["published"][k] for k in config["reduced"]}, "train": {}}
    assert counts_granite.param_count(dict(reference_granite.spec(whole))) == pytest.approx(3.19e9, rel=0.01)  # "3B" as published


def test_the_file_holds_every_published_number_under_its_key():
    """Against the catalog's row, where the catalog is installed: only the six reduced keys differ."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog beside the model-configs guide here")
    config = published()
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "granite-4.0-h-micro")
    assert sorted(k for k, v in row["config"].items() if config.get(k) != v) == sorted(config["reduced"])
    assert config["source"] == row["source_url"] and all(config["published"][k] == row["config"][k] for k in config["reduced"])
