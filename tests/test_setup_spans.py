"""Set-up spans (``telemetry/journal.py``: kind ``setup``, labels in
``SETUP_LABELS``): each part of the time from process start to the first step,
recorded by the module that owns it, exactly when a journal is armed, with the
label of the part it nests in as ``parent``; the ``compile`` span's
``lower_s`` and ``cache_hit``; ``trace --setup``, which tables them."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import dmlcloud_tpu as dml
from dmlcloud_tpu.__main__ import main as cli_main
from dmlcloud_tpu.parallel import mesh as mesh_lib
from dmlcloud_tpu.telemetry import journal as journal_mod
from dmlcloud_tpu.telemetry.journal import SETUP_LABELS, SpanJournal
from dmlcloud_tpu.utils import profiling

#: ts is kept to the microsecond, dur to the nanosecond
SLACK = 2e-6


class _Stage(dml.TrainValStage):
    def pre_stage(self):
        rng = np.random.RandomState(0)
        batches = [{"x": rng.randn(8, 4).astype(np.float32), "y": rng.randn(8, 1).astype(np.float32)}] * 2
        self.pipeline.register_model("linear", apply_fn=lambda p, x: x @ p["w"], params={"w": jnp.zeros((4, 1))},
                                     verbose=False)
        self.pipeline.register_optimizer("sgd", optax.sgd(0.05))
        self.pipeline.register_dataset("train", batches, verbose=False)

    def step(self, state, batch):
        return jnp.mean((state.apply_fn(state.params, batch["x"]) - batch["y"]) ** 2)

    def val_epoch(self):
        pass


def _run(journal=None, max_epochs=1, checkpoint_root=None, resume_from=None):
    pipe = dml.TrainingPipeline(name="setup-spans", precompile=True)
    pipe.set_mesh(mesh_lib.create_mesh({"data": 1}, devices=jax.devices()[:1]))
    pipe.append_stage(_Stage(), max_epochs=max_epochs, name="TrainValStage")
    if resume_from is not None:
        pipe.enable_checkpointing(resume_from, resume=True)
    elif checkpoint_root is not None:
        pipe.enable_checkpointing(checkpoint_root)
    if journal is not None:
        journal_mod.activate(journal)
    try:
        pipe.run()
    finally:
        if journal is not None:
            journal_mod.deactivate()
    if pipe.checkpoint_dir is not None:
        pipe.checkpoint_dir.close()
    return pipe


def _setup(records, label):
    return [r for r in records if r["kind"] == "setup" and r["label"] == label]


def _inside(child, parent) -> bool:
    return parent["ts"] - SLACK <= child["ts"] and child["ts"] + child["dur"] <= parent["ts"] + parent["dur"] + SLACK


def test_each_part_once_inside_its_parent(tmp_path, single_runtime):
    journal = SpanJournal(tmp_path)
    _run(journal)
    records = journal.tail(1024)
    parts = {label: _setup(records, label) for label in ("pipeline", "stage", "pre_stage", "state")}
    assert all(len(found) == 1 for found in parts.values()), parts
    parts = {label: found[0] for label, found in parts.items()}
    for label, span in parts.items():
        assert span["parent"] == SETUP_LABELS[label]
    stage = parts["stage"]
    children = [parts["pre_stage"], parts["state"], *_setup(records, "phase_map"),
                *(r for r in records if r["kind"] == "compile")]
    assert len(children) == 4 and all(_inside(c, stage) for c in children)
    assert parts["pre_stage"]["ts"] + parts["pre_stage"]["dur"] <= parts["state"]["ts"] + SLACK
    # the top-level parts follow one another, and the first epoch comes after them
    assert parts["pipeline"]["ts"] + parts["pipeline"]["dur"] <= stage["ts"] + SLACK
    [epoch] = [r for r in records if r["kind"] == "epoch"]
    assert stage["ts"] + stage["dur"] <= epoch["ts"] + SLACK
    assert not _setup(records, "restore")  # not resuming


def test_compile_span_splits_lowering_from_compiling(tmp_path, single_runtime):
    journal = SpanJournal(tmp_path)
    _run(journal)
    [span] = [r for r in journal.tail(1024) if r["kind"] == "compile"]
    assert 0.0 < span["lower_s"] < span["dur"]
    assert isinstance(span["cache_hit"], bool)
    assert json.load(open(span["phases"]))["phases"]  # the phase map is still named here


@pytest.mark.parametrize("armed", [True, False])
def test_spans_and_the_phase_map_exist_only_with_a_journal_armed(tmp_path, single_runtime, monkeypatch, armed):
    emitted, maps = [], []
    emit, write = SpanJournal.emit, profiling.write_phase_map
    monkeypatch.setattr(SpanJournal, "emit", lambda self, *a, **k: emitted.append(a[0]) or emit(self, *a, **k))
    monkeypatch.setattr(profiling, "write_phase_map", lambda *a, **k: maps.append(a) or write(*a, **k))
    monkeypatch.setattr(journal_mod, "_import_readings", (1.0, 2.0))
    pipe = _run(SpanJournal(tmp_path) if armed else None)
    assert pipe.tracker["misc/compile_ms"][0] > 0.0  # the goodput ledger's compile bill, either way
    if armed:
        assert len(maps) == 1 and "setup" in emitted and "compile" in emitted
        assert journal_mod._import_readings is None
    else:
        assert maps == [] and emitted == []
        assert journal_mod._import_readings == (1.0, 2.0)  # left for the first journal armed


def test_import_is_journalled_once_by_the_first_journal_armed(tmp_path, monkeypatch):
    monkeypatch.setattr(journal_mod, "_import_readings", None)
    journal_mod.record_import(10.0, 12.5)
    first, second = SpanJournal(tmp_path / "a"), SpanJournal(tmp_path / "b")
    try:
        journal_mod.activate(first)
        journal_mod.activate(second)
    finally:
        journal_mod.deactivate()
    [span] = first.tail()
    assert (span["kind"], span["label"], span["parent"]) == ("setup", "import", None)
    assert span["dur"] == pytest.approx(2.5)
    assert second.tail() == []


def test_the_package_records_its_own_import(tmp_path):
    """In a fresh process: ``import dmlcloud_tpu`` is the span, and it ends
    before the journal that records it is made."""
    code = (
        "import time; t = time.perf_counter(); import dmlcloud_tpu; from dmlcloud_tpu.telemetry import journal as j; "
        f"made = time.perf_counter(); journal = j.activate(j.SpanJournal({str(tmp_path)!r})); "
        "[s] = journal.tail(); print(t <= dmlcloud_tpu._T_IMPORT, s['label'], s['ts'] + s['dur'] <= journal._wall(made))"
    )
    root = os.path.dirname(os.path.dirname(os.path.abspath(dml.__file__)))
    done = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True, timeout=120, cwd=root,
                          env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert done.stdout.split() == ["True", "import", "True"], done.stderr[-2000:]


def test_restore_is_a_part_of_the_stage_when_resuming(tmp_path, single_runtime):
    first = _run(max_epochs=1, checkpoint_root=str(tmp_path))
    journal = SpanJournal(tmp_path / "telemetry-resumed")
    resumed = _run(journal, max_epochs=2, resume_from=str(first.checkpoint_dir))
    assert resumed.resumed is True
    records = journal.tail(1024)
    [restore], [state], [stage] = _setup(records, "restore"), _setup(records, "state"), _setup(records, "stage")
    assert restore["parent"] == "stage" and _inside(restore, stage)
    assert state["ts"] + state["dur"] <= restore["ts"] + SLACK


def test_trace_setup_tables_the_parts_nested(tmp_path, single_runtime, capsys):
    journal = SpanJournal(tmp_path)
    _run(journal)
    journal.close()
    capsys.readouterr()  # the run's own table
    assert cli_main(["trace", str(tmp_path), "--setup", "--json"]) == 0
    rows = {r["part"]: r for r in json.loads(capsys.readouterr().out)["setup"]}
    assert {"pipeline", "stage", "pre_stage", "state", "phase_map", "compile TrainValStage.train_step"} <= set(rows)
    assert rows["stage"]["depth"] == 0 and rows["pre_stage"]["depth"] == 1
    inner = sum(rows[p]["dur_s"] for p in ("pre_stage", "state", "phase_map", "compile TrainValStage.train_step"))
    assert rows["stage"]["self_s"] == pytest.approx(rows["stage"]["dur_s"] - inner, abs=1e-5)
    assert "lower_s=" in rows["compile TrainValStage.train_step"]["detail"]
    assert cli_main(["trace", str(tmp_path), "--setup"]) == 0
    assert "  pre_stage" in capsys.readouterr().out
