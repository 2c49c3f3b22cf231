"""``readers_setup.py`` and the eight ``setup_*`` readers: on synthetic spans,
where parts that nest or overlap are counted once and a run without set-up
spans reads nothing, and end to end on the tiny train cell (``tiny.py``), where
the program's top-level parts, the epochs before the window and what no
program span covers add up to ``setup_s``."""

import importlib.util
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

import tiny  # noqa: E402

METRICS = ("setup_import_s", "setup_pipeline_s", "setup_model_s", "setup_state_s", "setup_lower_s", "setup_compile_s",
           "setup_first_epochs_s", "setup_outside_program_s")


def reader(name):
    path = os.path.join(os.path.dirname(HERE), "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"metric_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def synthetic_run():
    """Process start at 80, the window opens at 100: set-up is 20 s."""
    setup = lambda label, a, b, parent=None: {"kind": "setup", "label": label, "start": a, "end": b, "parent": parent}
    return {"window": (100.0, 110.0), "setup_s": 20.0, "spans": [
        setup("import", 79.0, 83.0),  # began before the process did (it cannot; clipped all the same)
        setup("pipeline", 84.0, 85.0),
        setup("stage", 85.0, 92.0),
        setup("pre_stage", 85.5, 87.0, "stage"),
        setup("pre_stage", 86.5, 88.0, "stage"),  # overlaps the first: 2.5 s under the two
        setup("state", 88.0, 89.0, "stage"),
        {"kind": "compile", "label": "S.train_step", "start": 89.0, "end": 91.5, "lower_s": 1.0, "cache_hit": True},
        setup("phase_map", 91.5, 91.8, "stage"),
        {"kind": "epoch", "label": "S", "start": 93.0, "end": 95.0},
        {"kind": "epoch", "label": "S", "start": 96.0, "end": 97.0},
        {"kind": "epoch", "label": "S", "start": 99.0, "end": 130.0},  # the window's own
        {"kind": "step_dispatch", "label": None, "start": 101.0, "end": 101.1},
    ]}


@pytest.mark.parametrize("name,seconds", [
    ("setup_import_s", 3.0), ("setup_pipeline_s", 1.0), ("setup_model_s", 2.5), ("setup_state_s", 1.0),
    ("setup_lower_s", 1.0), ("setup_compile_s", 1.5), ("setup_first_epochs_s", 3.0),
    # 20 s less 80-83, 84-92, 93-95 and 96-97: the program's spans, each instant once
    ("setup_outside_program_s", 6.0),
])
def test_each_reader_on_synthetic_spans(name, seconds):
    assert reader(name)(synthetic_run()) == pytest.approx(seconds)


def test_the_top_level_parts_and_what_is_outside_them_add_up_to_setup():
    run = synthetic_run()
    stage = 7.0  # its children are inside it, so no reader adds them again
    parts = reader("setup_import_s")(run) + reader("setup_pipeline_s")(run) + stage
    assert parts + reader("setup_first_epochs_s")(run) + reader("setup_outside_program_s")(run) == pytest.approx(20.0)


@pytest.mark.parametrize("spans", [None, [], [{"kind": "epoch", "label": "S", "start": 93.0, "end": 95.0},
                                              {"kind": "compile", "label": "S.train_step", "start": 89.0, "end": 91.5,
                                               "phases": "x.json"}]])
def test_a_run_without_setup_spans_reads_nothing(spans):
    """No journal, an empty one, or an older program's (epochs, a compile span without ``lower_s``)."""
    run = dict(synthetic_run(), spans=spans)
    assert all(reader(name)(run) is None for name in METRICS)


PROBE = '''
from benchmark import readers_setup, trace


def read(run):
    """The program's top-level set-up spans, each on its own, the epochs before
    the window and what no program span covers, less setup_s: 0 when the parts
    partition set-up, more where two top-level parts overlap."""
    lo, hi = readers_setup.interval(run)
    each = lambda intervals: sum(b - a for a, b in trace.clip(intervals, lo, hi))
    top = [(s["start"], s["end"]) for s in run["spans"] if s["kind"] == "setup" and s["parent"] is None]
    return each(top) + each(readers_setup.pre_window_epochs(run)) + readers_setup.outside_program(run) - run["setup_s"]
'''


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    """``tiny.py``'s tree with a probe, entered for the tiny train cell as a
    later PR would add it, that reads the partition of set-up."""
    tree = tiny.make_tree(str(tmp_path_factory.mktemp("setup") / "tree"))
    bench = json.load(open(os.path.join(tree, "BENCHMARK.json")))
    bench["per_layer"].append(dict(name="probe_setup_partition", unit="s", better="lower", source="program_counter",
                                   layer="set-up", moves="setup_s", workloads=["tiny-train"]))
    json.dump(bench, open(os.path.join(tree, "BENCHMARK.json"), "w"))
    with open(os.path.join(tree, "benchmark", "metrics", "probe_setup_partition.py"), "w") as f:
        f.write(PROBE)
    return tree


def test_the_tiny_train_cell_reads_all_eight_and_they_partition_setup(tree):
    code, line, err = tiny.run_cell(tree, "--workload", "tiny-train", "--seed", "3000000019", "--seconds", "2",
                                    "--trace", "1")
    assert code == 0 and line["correct"], err[-3000:]
    metrics = line["metrics"]
    assert set(METRICS) <= set(metrics), sorted(metrics)
    assert all(metrics[name]["unit"] == "s" and metrics[name]["value"] >= 0.0 for name in METRICS)
    assert metrics["probe_setup_partition"]["value"] == pytest.approx(0.0, abs=0.01)
