"""``run.py`` end to end on the CPU at a tiny size, in a copy to which the tiny
configuration, mixes and cells were added as new files and entries alone
(``tiny.py``). ``tiny_run.py`` is what lets a run past the look for a chip: a
step of these tests, not a switch of the harness."""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import tiny  # noqa: E402

KEYS = ["correct", "attempted", "failed", "metrics", "device"]


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    return tiny.make_tree(str(tmp_path_factory.mktemp("bench") / "tree"))


def test_without_a_tpu_there_is_no_run_and_no_result(tree):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run([sys.executable, "benchmark/run.py", "--workload", "tiny-chat", "--seed", "1", "--seconds", "2",
                           "--trace", "0"], cwd=tree, capture_output=True, text=True, env=env, timeout=300)
    assert done.returncode == 3 and not done.stdout.strip()


def test_without_the_program_there_is_no_run(tree, tmp_path):
    bare = tmp_path / "bare"
    bare.mkdir()
    os.symlink(os.path.join(tree, "benchmark"), bare / "benchmark")
    os.symlink(os.path.join(tree, "BENCHMARK.json"), bare / "BENCHMARK.json")
    done = subprocess.run([sys.executable, str(bare / "benchmark" / "run.py"), "--workload", "tiny-chat", "--seed", "1",
                           "--seconds", "2", "--trace", "0"], cwd=bare, capture_output=True, text=True, timeout=300,
                          env=dict(os.environ, JAX_PLATFORMS="cpu"))
    # run.py takes its root from its own (unresolved) path: beside it there is no dmlcloud_tpu/
    assert done.returncode == 2 and not done.stdout.strip()


@pytest.mark.parametrize("cell,trace", [("tiny-chat", 0), ("tiny-chat", 1), ("tiny-long", 0), ("tiny-long", 1),
                                        ("tiny-train", 0), ("tiny-train", 1)])
def test_last_line_holds_the_contracts_keys(tree, cell, trace):
    code, line, err = tiny.run_cell(tree, "--workload", cell, "--seed", "3000000019", "--seconds", "3", "--trace", str(trace))
    assert code == 0, err[-3000:]
    assert list(line)[:3] == KEYS[:3] and all(k in line for k in KEYS) and list(line)[-1] == "checks"
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0, err[-3000:]
    bench = json.load(open(os.path.join(tree, "BENCHMARK.json")))
    kind = "per_layer" if trace else "end_to_end"
    mine = [m["name"] for m in bench[kind] if cell in m.get("workloads", [cell])]
    on_the_chip_only = {"serve_decode_roofline", "serve_prefill_roofline", "flash_attn_roofline"}  # read the device's planes
    assert set(line["metrics"]) >= set(mine) - on_the_chip_only
    assert all(m["unit"] and isinstance(m["value"], float) for m in line["metrics"].values())
    assert "setup_s" in line["metrics"] or trace
    assert ("busy_s" in line["device"]) == bool(trace)
    assert "check " in err  # each number compared, beside its limit, on standard error


def test_a_cell_a_mix_a_configuration_and_a_metric_are_added_by_files_alone(tree):
    """What a later PR does: new files, new entries, no edit to a file that is there."""
    before = {}
    for root, _, names in os.walk(os.path.join(tree, "benchmark")):
        before.update({os.path.join(root, n): os.path.getmtime(os.path.join(root, n)) for n in names})
    here = lambda *p: os.path.join(tree, "benchmark", *p)
    config = json.load(open(here("configs", "tiny.json")))
    config["serve"]["max_slots"] = 2
    json.dump(config, open(here("configs", "dummy.json"), "w"))
    mix = json.load(open(here("traffic", "tinychat.json")))
    mix["arrivals"] = {"kind": "slotted", "rate_per_s": 4.0}
    json.dump(mix, open(here("traffic", "dummymix.json"), "w"))
    with open(here("metrics", "dummy_requests.py"), "w") as f:
        f.write("def read(run):\n    return float(len([r for r in run['requests'] if r['measured']]))\n")
    with open(here("metrics", "dummy_silent.py"), "w") as f:
        f.write("def read(run):\n    return None\n")
    bench = json.load(open(os.path.join(tree, "BENCHMARK.json")))
    bench["configs"].append(dict(name="dummy", source="test", file="benchmark/configs/dummy.json", reduced=[], why="t"))
    bench["workloads"].append(dict(name="dummy-cell", config="dummy", traffic="dummymix", chips=1, why="t"))
    for name in ("dummy_requests", "dummy_silent"):
        bench["per_layer"].append(dict(name=name, unit="count", better="higher", source="program_counter", layer="load generator",
                                       moves="serve_ttft_p50_s", workloads=["dummy-cell"]))
    for m in bench["end_to_end"]:
        if m["name"] in ("serve_ttft_p50_s", "serve_tpot_p90_ms"):
            m["workloads"].append("dummy-cell")
    json.dump(bench, open(os.path.join(tree, "BENCHMARK.json"), "w"))
    code, line, err = tiny.run_cell(tree, "--workload", "dummy-cell", "--seed", "7", "--seconds", "3", "--trace", "1")
    assert code == 0 and line["correct"], err[-3000:]
    assert line["metrics"]["dummy_requests"]["value"] == 12.0  # 4 a second for 3 seconds
    assert "dummy_silent" not in line["metrics"]  # nothing to read: left out, never 0
    code, line, err = tiny.run_cell(tree, "--workload", "dummy-cell", "--seed", "7", "--seconds", "3", "--trace", "0")
    assert code == 0 and set(line["metrics"]) == {"serve_ttft_p50_s", "serve_tpot_p90_ms", "setup_s"}, err[-3000:]
    assert all(os.path.getmtime(p) == t for p, t in before.items())


@pytest.mark.parametrize("cell,fault", [("tiny-chat", "token_altered"), ("tiny-long", "token_altered"),
                                        ("tiny-train", "unchanged_state"), ("tiny-train", "half_batch")])
def test_a_broken_timed_path_comes_out_not_correct(tree, cell, fault):
    code, line, err = tiny.run_cell(tree, "--workload", cell, "--seed", "11", "--seconds", "2", "--trace", "0", fault=fault)
    assert code == 0 and line is not None, err[-3000:]
    assert line["correct"] is False, (line, err[-2000:])
    assert any(c["value"] > c["limit"] for c in line["checks"].values())
