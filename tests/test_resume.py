"""Checkpoint/resume integration: a pipeline interrupted after N epochs and
resumed must reproduce the uninterrupted run — params, optimizer state, metric
histories, and epoch accounting (the reference can only re-find its directory
and call a user hook, SURVEY.md §3.5; here resume is bit-for-bit)."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import dmlcloud_tpu as dml


class _ToyStage(dml.TrainValStage):
    """Deterministic linear-regression stage on a fixed synthetic dataset."""

    def __init__(self, stop_after: int | None = None):
        super().__init__()
        self._stop_after = stop_after

    def pre_stage(self):
        if "linear" in self.pipeline.models:
            return  # second stage in a multi-stage pipeline reuses the registry
        rng = np.random.RandomState(42)
        w_true = rng.randn(4, 1).astype(np.float32)
        xs = rng.randn(8, 16, 4).astype(np.float32)
        batches = [{"x": jnp.asarray(x), "y": jnp.asarray(x @ w_true)} for x in xs]
        self.pipeline.register_model(
            "linear",
            apply_fn=lambda p, x: x @ p["w"],
            params={"w": jnp.zeros((4, 1))},
            verbose=False,
        )
        self.pipeline.register_optimizer("sgd", optax.sgd(0.05, momentum=0.9))
        self.pipeline.register_dataset("train", batches, verbose=False)

    def step(self, state, batch):
        pred = state.apply_fn(state.params, batch["x"])
        return jnp.mean((pred - batch["y"]) ** 2)

    def val_epoch(self):
        pass

    def post_epoch(self):
        if self._stop_after is not None and self.current_epoch >= self._stop_after:
            self.stop_stage()


def _run(tmp_path, resume_from=None, max_epochs=5, stop_after=None, name="toy"):
    pipeline = dml.TrainingPipeline(name=name)
    stage = _ToyStage(stop_after=stop_after)
    pipeline.append_stage(stage, max_epochs=max_epochs, name="TrainValStage")
    if resume_from is not None:
        pipeline.enable_checkpointing(resume_from, resume=True)
    else:
        pipeline.enable_checkpointing(str(tmp_path))
    pipeline.run()
    return pipeline, stage


def test_resume_matches_uninterrupted(tmp_path, single_runtime):
    # 1) interrupted run: completes only 2 of the eventual 5 epochs
    p1, s1 = _run(tmp_path / "a", max_epochs=2)
    run_dir = str(p1.checkpoint_dir)
    assert p1.resumed is False
    assert s1.current_epoch == 3  # two epochs completed
    p1.checkpoint_dir.close()

    # 2) resume: picks up at epoch 3, finishes 5
    p2, s2 = _run(tmp_path / "a", resume_from=run_dir, max_epochs=5)
    assert p2.resumed is True
    assert str(p2.checkpoint_dir) == run_dir
    assert s2.current_epoch == 6
    # tracker has the full 5-epoch history, not just the resumed tail
    assert len(p2.tracker["train/loss"]) == 5
    p2.checkpoint_dir.close()

    # 3) control: the same 5 epochs uninterrupted
    p3, s3 = _run(tmp_path / "b", max_epochs=5)

    w_resumed = np.asarray(s2.state.params["w"])
    w_control = np.asarray(s3.state.params["w"])
    np.testing.assert_allclose(w_resumed, w_control, rtol=1e-6, atol=1e-7)

    # optimizer momentum buffers match too
    mom_resumed = jax.tree_util.tree_leaves(s2.state.opt_state)
    mom_control = jax.tree_util.tree_leaves(s3.state.opt_state)
    for a, b in zip(mom_resumed, mom_control):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-7)

    # loss history of the resumed tail equals the control's tail
    tail_resumed = [float(v) for v in p2.tracker["train/loss"][2:]]
    tail_control = [float(v) for v in p3.tracker["train/loss"][2:]]
    np.testing.assert_allclose(tail_resumed, tail_control, rtol=1e-6)
    p3.checkpoint_dir.close()


def test_fresh_dir_when_not_resuming(tmp_path, single_runtime):
    p1, _ = _run(tmp_path / "x", max_epochs=1)
    p2, _ = _run(tmp_path / "x", max_epochs=1)
    assert str(p1.checkpoint_dir) != str(p2.checkpoint_dir)
    assert p1.resumed is False and p2.resumed is False
    p1.checkpoint_dir.close()
    p2.checkpoint_dir.close()


def test_stopped_stage_not_retrained_on_resume(tmp_path, single_runtime):
    """A stage that ended early via stop_stage() must stay stopped on resume —
    not silently re-train its remaining epochs with a stale stop condition."""
    p1, s1 = _run(tmp_path / "s", max_epochs=10, stop_after=2)
    run_dir = str(p1.checkpoint_dir)
    assert s1.current_epoch == 3  # stopped after epoch 2
    n_epochs_before = len(p1.tracker["train/loss"])
    p1.checkpoint_dir.close()

    p2, s2 = _run(tmp_path / "s", resume_from=run_dir, max_epochs=10)
    assert s2._stop_requested is True
    assert s2.current_epoch == 3  # no additional epochs ran
    assert len(p2.tracker["train/loss"]) == n_epochs_before
    p2.checkpoint_dir.close()


def test_duplicate_explicit_stage_name_raises(single_runtime):
    pipeline = dml.TrainingPipeline(name="dup")
    pipeline.append_stage(_ToyStage(), max_epochs=1, name="pretrain")
    with pytest.raises(ValueError, match="already exists"):
        pipeline.append_stage(_ToyStage(), max_epochs=1, name="pretrain")


def test_two_unnamed_stages_get_distinct_scopes(tmp_path, single_runtime):
    """Two unnamed stages of the same class must not share a checkpoint scope
    (Orbax step ids would collide and resume would restore the wrong stage)."""
    pipeline = dml.TrainingPipeline(name="two")
    pipeline.append_stage(_ToyStage(), max_epochs=1)
    pipeline.append_stage(_ToyStage(), max_epochs=1)
    assert pipeline.stages[0].name != pipeline.stages[1].name
    pipeline.enable_checkpointing(str(tmp_path))
    pipeline.run()
    state_root = pipeline.checkpoint_dir.state_dir
    assert (state_root / pipeline.stages[0].name).exists()
    assert (state_root / pipeline.stages[1].name).exists()
    pipeline.checkpoint_dir.close()


def test_corrupt_meta_sidecar_still_resumes(tmp_path, single_runtime):
    """A truncated metadata sidecar (crash mid-write) must degrade to
    Orbax-only resume, not kill the resumed run."""
    p1, _ = _run(tmp_path / "c", max_epochs=2)
    run_dir = str(p1.checkpoint_dir)
    p1.checkpoint_dir.close()
    meta_dir = p1.checkpoint_dir.path / "meta" / "TrainValStage"
    corrupted = 0
    for f in meta_dir.glob("*.json"):
        f.write_text(f.read_text()[: len(f.read_text()) // 2])  # truncate
        corrupted += 1
    assert corrupted > 0  # the sidecars must actually exist to be corrupted

    p2, s2 = _run(tmp_path / "c", resume_from=run_dir, max_epochs=4)
    assert p2.resumed is True
    assert s2.current_epoch == 5  # resumed from Orbax step 2, ran 3..4
    p2.checkpoint_dir.close()


def test_missing_meta_sidecar_still_resumes(tmp_path, single_runtime):
    p1, _ = _run(tmp_path / "m", max_epochs=2)
    run_dir = str(p1.checkpoint_dir)
    p1.checkpoint_dir.close()
    meta_dir = p1.checkpoint_dir.path / "meta" / "TrainValStage"
    for f in meta_dir.glob("*.json"):
        f.unlink()

    p2, s2 = _run(tmp_path / "m", resume_from=run_dir, max_epochs=4)
    assert p2.resumed is True
    assert s2.current_epoch == 5
    p2.checkpoint_dir.close()


def test_sidecar_is_json_not_pickle(tmp_path, single_runtime):
    """The resume sidecar must be plain JSON — loading a checkpoint dir must
    never execute code from it (pickle did)."""
    import json

    p1, _ = _run(tmp_path / "j", max_epochs=1)
    p1.checkpoint_dir.close()
    meta_dir = p1.checkpoint_dir.path / "meta" / "TrainValStage"
    files = sorted(meta_dir.glob("*"))
    assert files and all(f.suffix == ".json" for f in files)
    meta = json.loads(files[-1].read_text())
    assert meta["epoch"] == 1
    assert meta["stopped"] is False
    assert "histories" in meta["tracker"]


def test_structurally_invalid_sidecar_degrades(tmp_path, single_runtime):
    """A sidecar that parses as JSON but has an incomplete tracker state must
    degrade to Orbax-only resume, not crash in load_state_dict."""
    import json

    p1, _ = _run(tmp_path / "v", max_epochs=2)
    run_dir = str(p1.checkpoint_dir)
    p1.checkpoint_dir.close()
    meta_dir = p1.checkpoint_dir.path / "meta" / "TrainValStage"
    for f in meta_dir.glob("*.json"):
        f.write_text(json.dumps({"epoch": 2, "stopped": False, "tracker": {"histories": {}}}))

    p2, s2 = _run(tmp_path / "v", resume_from=run_dir, max_epochs=4)
    assert p2.resumed is True
    assert s2.current_epoch == 5
    p2.checkpoint_dir.close()


def test_legacy_pickle_sidecar_ignored(tmp_path, single_runtime):
    """Pre-JSON checkpoints carry .pkl sidecars; resume must NOT unpickle them
    (code execution) — it degrades to Orbax-only with a warning."""
    p1, _ = _run(tmp_path / "p", max_epochs=2)
    run_dir = str(p1.checkpoint_dir)
    p1.checkpoint_dir.close()
    meta_dir = p1.checkpoint_dir.path / "meta" / "TrainValStage"
    for f in meta_dir.glob("*.json"):
        # a malicious pickle would execute on load; here any bytes prove
        # the file is never opened by the unpickler (it would raise)
        f.with_suffix(".pkl").write_bytes(b"\x80\x04never loaded")
        f.unlink()

    p2, s2 = _run(tmp_path / "p", resume_from=run_dir, max_epochs=4)
    assert p2.resumed is True
    assert s2.current_epoch == 5
    p2.checkpoint_dir.close()


@pytest.mark.parametrize("bad", ["../escape", "a/b", "", ".", "..", "name with space"])
def test_invalid_stage_name_rejected(single_runtime, bad):
    """Stage names key checkpoint subdirectories (state/<name>, meta/<name>);
    path separators and dot-dirs must be rejected."""
    pipeline = dml.TrainingPipeline(name="badname")
    with pytest.raises(ValueError, match="invalid"):
        pipeline.append_stage(_ToyStage(), max_epochs=1, name=bad)


def test_resume_with_save_in_flight_uses_last_completed(tmp_path, single_runtime):
    """A run killed WITH an async save still in flight must resume from the
    last COMPLETED checkpoint: Orbax commits via tmp-dir + rename, so an
    uncommitted save is invisible to latest_step(). Emulated deterministically
    by planting the tmp directory a kill mid-commit leaves behind."""
    p1, s1 = _run(tmp_path / "k", max_epochs=2)
    run_dir = str(p1.checkpoint_dir)
    p1.checkpoint_dir.close()

    # the kill artifact: epoch 3's save was dispatched but never committed
    scope_dir = p1.checkpoint_dir.state_dir / "TrainValStage"
    (scope_dir / "3.orbax-checkpoint-tmp-1234567890").mkdir()
    # the root may also have written epoch 3's sidecar before dying — resume
    # must key off Orbax's committed steps, not the sidecar
    meta_dir = p1.checkpoint_dir.path / "meta" / "TrainValStage"
    (meta_dir / "3.json").write_text((meta_dir / "2.json").read_text())

    p2, s2 = _run(tmp_path / "k", resume_from=run_dir, max_epochs=5)
    assert p2.resumed is True
    assert s2.current_epoch == 6  # resumed at 3 (last completed = 2), ran 3..5
    p2.checkpoint_dir.close()

    # bit-exact equivalence with an uninterrupted control run
    p3, s3 = _run(tmp_path / "kc", max_epochs=5)
    np.testing.assert_allclose(
        np.asarray(s2.state.params["w"]), np.asarray(s3.state.params["w"]), rtol=1e-6, atol=1e-7
    )
    p3.checkpoint_dir.close()


def test_resume_with_sync_checkpointing_matches(tmp_path, single_runtime):
    """async_checkpoint() False (the bisection baseline) must resume to the
    exact same weights as the async default."""

    class SyncCkpt(_ToyStage):
        def async_checkpoint(self):
            return False

    def run_sync(root, resume_from=None, max_epochs=5):
        pipeline = dml.TrainingPipeline(name="toy")
        stage = SyncCkpt()
        pipeline.append_stage(stage, max_epochs=max_epochs, name="TrainValStage")
        if resume_from is not None:
            pipeline.enable_checkpointing(resume_from, resume=True)
        else:
            pipeline.enable_checkpointing(str(root))
        pipeline.run()
        return pipeline, stage

    p1, _ = run_sync(tmp_path / "sync", max_epochs=2)
    run_dir = str(p1.checkpoint_dir)
    p1.checkpoint_dir.close()
    p2, s2 = run_sync(tmp_path / "sync", resume_from=run_dir, max_epochs=5)
    p2.checkpoint_dir.close()

    p3, s3 = _run(tmp_path / "async", max_epochs=5)  # async default, uninterrupted
    np.testing.assert_allclose(
        np.asarray(s2.state.params["w"]), np.asarray(s3.state.params["w"]), rtol=1e-6, atol=1e-7
    )
    p3.checkpoint_dir.close()


def test_checkpoint_every_zero_disables_state_saves(tmp_path, single_runtime):
    class NoCkptStage(_ToyStage):
        def checkpoint_every(self):
            return 0

    pipeline = dml.TrainingPipeline(name="nockpt")
    pipeline.append_stage(NoCkptStage(), max_epochs=1, name="TrainValStage")
    pipeline.enable_checkpointing(str(tmp_path))
    pipeline.run()
    state_dir = pipeline.checkpoint_dir.state_dir / "TrainValStage"
    assert not state_dir.exists() or not any(state_dir.iterdir())
    pipeline.checkpoint_dir.close()


class _BestStage(_ToyStage):
    """Tracks a controlled non-monotonic 'score' so keep-best retention is
    distinguishable from keep-most-recent."""

    PATTERN = [1.0, 5.0, 2.0, 4.0, 3.0]

    def pre_epoch(self):
        self.track_reduce("score", self.PATTERN[self.current_epoch - 1], prefixed=False)

    def checkpoint_best_metric(self):
        return "score"

    def checkpoint_best_mode(self):
        return "max"

    def checkpoint_keep(self):
        return 2


def test_keep_best_retention(tmp_path, single_runtime):
    pipeline = dml.TrainingPipeline(name="best")
    stage = _BestStage()
    pipeline.append_stage(stage, max_epochs=5, name="TrainValStage")
    pipeline.enable_checkpointing(str(tmp_path))
    pipeline.run()
    run_dir = str(pipeline.checkpoint_dir)
    pipeline.checkpoint_dir.close()

    # retention kept the two highest-scoring epochs (2: 5.0, 4: 4.0) plus the
    # newest (5 — Orbax always preserves the latest so requeue resume stays
    # fresh), and dropped epochs 1 and 3
    from dmlcloud_tpu.checkpoint import CheckpointDir

    ckpt = CheckpointDir(run_dir)
    assert sorted(ckpt.state_manager("TrainValStage").all_steps()) == [2, 4, 5]
    # resume sidecars stayed in lockstep with the kept steps
    metas = sorted(int(f.stem) for f in (ckpt.path / "meta" / "TrainValStage").glob("*.json"))
    assert metas == [2, 4, 5]
    ckpt.close()


def test_keep_best_invalid_mode_rejected(tmp_path, single_runtime):
    class BadMode(_BestStage):
        def checkpoint_best_mode(self):
            return "most"

    pipeline = dml.TrainingPipeline(name="badmode")
    pipeline.append_stage(BadMode(), max_epochs=1, name="TrainValStage")
    pipeline.enable_checkpointing(str(tmp_path))
    with pytest.raises(ValueError, match="checkpoint_best_mode"):
        pipeline.run()


def test_user_configured_manager_in_pre_stage_wins(tmp_path, single_runtime):
    """The documented pattern — binding scope options via state_manager(...)
    in pre_stage — must not collide with the stage's automatic retention
    config."""

    class UserCfg(_ToyStage):
        def pre_stage(self):
            super().pre_stage()
            self.pipeline.checkpoint_dir.state_manager("TrainValStage", max_to_keep=10)

    pipeline = dml.TrainingPipeline(name="usercfg")
    pipeline.append_stage(UserCfg(), max_epochs=2, name="TrainValStage")
    pipeline.enable_checkpointing(str(tmp_path))
    pipeline.run()  # would raise RuntimeError if the stage re-bound options
    assert pipeline.checkpoint_dir._manager_opts["TrainValStage"][0] == 10


def test_identical_policy_respecification_is_idempotent(tmp_path, single_runtime):
    """Re-specifying a byte-identical keep-best policy (fresh lambdas) must
    not trip the changed-options guard."""
    from dmlcloud_tpu.checkpoint import CheckpointDir
    from orbax.checkpoint import checkpoint_managers as ocm

    ckpt = CheckpointDir(str(tmp_path / "run"))
    ckpt.create()

    def policy():
        return ocm.AnyPreservationPolicy(
            [ocm.LatestN(n=1), ocm.BestN(get_metric_fn=lambda m: m["s"], n=2)]
        )

    m1 = ckpt.state_manager("s", preservation_policy=policy())
    m2 = ckpt.state_manager("s", preservation_policy=policy())  # same config, new lambdas
    assert m1 is m2
    with pytest.raises(RuntimeError, match="already exists"):
        ckpt.state_manager("s", preservation_policy=ocm.AnyPreservationPolicy([ocm.LatestN(n=5)]))
    ckpt.close()


class _PreemptAtEpoch(_ToyStage):
    """Raises a (handled) preemption signal against our own process DURING a
    chosen epoch (before its steps run) — models Cloud TPU/Slurm sending
    SIGTERM/SIGUSR1 mid-training; the run exits after that epoch finishes."""

    def __init__(self, signal_at_epoch: int):
        super().__init__()
        self._signal_at = signal_at_epoch

    def pre_epoch(self):
        if self.current_epoch == self._signal_at:
            import os
            import signal

            os.kill(os.getpid(), signal.SIGUSR1)


def test_preemption_exits_cleanly_and_resumes(tmp_path, single_runtime):
    # run 1: signal arrives during epoch 2 of 5 -> clean exit, NOT stopped
    p1 = dml.TrainingPipeline(name="toy")
    s1 = _PreemptAtEpoch(signal_at_epoch=2)
    p1.append_stage(s1, max_epochs=5, name="TrainValStage")
    p1.enable_checkpointing(str(tmp_path / "p"))
    p1.enable_preemption_handling(signals=("SIGUSR1",))
    p1.run()
    run_dir = str(p1.checkpoint_dir)
    assert p1._preempted is True
    assert s1.current_epoch == 3  # exactly two epochs completed
    assert s1._stop_requested is False  # preemption != user stop
    p1.checkpoint_dir.close()

    # run 2 (the requeue): resumes at epoch 3 and finishes all 5
    p2, s2 = _run(tmp_path / "p", resume_from=run_dir, max_epochs=5)
    assert p2.resumed is True
    assert s2.current_epoch == 6
    assert len(p2.tracker["train/loss"]) == 5
    p2.checkpoint_dir.close()

    # equivalence with an uninterrupted control run
    p3, s3 = _run(tmp_path / "q", max_epochs=5)
    np.testing.assert_allclose(
        np.asarray(s2.state.params["w"]), np.asarray(s3.state.params["w"]), rtol=1e-6, atol=1e-7
    )
    p3.checkpoint_dir.close()


def test_preemption_skips_remaining_stages(tmp_path, single_runtime):
    p = dml.TrainingPipeline(name="toy")
    first = _PreemptAtEpoch(signal_at_epoch=1)
    second = _ToyStage()
    p.append_stage(first, max_epochs=2, name="first")
    p.append_stage(second, max_epochs=2, name="second")
    p.enable_checkpointing(str(tmp_path / "s"))
    p.enable_preemption_handling(signals=("SIGUSR1",))
    p.run()
    assert first.current_epoch == 2  # exited after epoch 1
    assert second.current_epoch == 1  # never ran an epoch
    p.checkpoint_dir.close()


def test_preemption_forces_save_despite_checkpoint_every(tmp_path, single_runtime):
    """checkpoint_every() > 1 must not lose the preempted epoch: the
    preemption exit is 'final' for the save decision."""
    import signal

    class SparseCkpt(_PreemptAtEpoch):
        def checkpoint_every(self):
            return 5

    prev = signal.getsignal(signal.SIGUSR1)
    p1 = dml.TrainingPipeline(name="toy")
    s1 = SparseCkpt(signal_at_epoch=2)
    p1.append_stage(s1, max_epochs=9, name="TrainValStage")
    p1.enable_checkpointing(str(tmp_path / "p"))
    p1.enable_preemption_handling(signals=("SIGUSR1",))
    p1.run()
    assert p1.checkpoint_dir.latest_step(scope="TrainValStage") == 2  # forced save
    run_dir = str(p1.checkpoint_dir)
    p1.checkpoint_dir.close()
    # handler restored after the run (no stale process-wide disposition)
    assert signal.getsignal(signal.SIGUSR1) == prev

    p2 = dml.TrainingPipeline(name="toy")
    s2 = _ToyStage()
    p2.append_stage(s2, max_epochs=3, name="TrainValStage")
    p2.enable_checkpointing(run_dir, resume=True)
    p2.run()
    assert s2.current_epoch == 4  # resumed at 3, finished 3
    p2.checkpoint_dir.close()


def test_preemption_rearming_is_safe(tmp_path, single_runtime):
    """Double enable must keep the ORIGINAL disposition for restore, reset a
    stale flag, and reject bad signal names before installing anything."""
    import signal

    prev = signal.getsignal(signal.SIGUSR1)
    p = dml.TrainingPipeline(name="toy")
    p._preempted = True  # stale flag from a notional earlier run
    p.enable_preemption_handling(signals=("SIGUSR1",))
    p.enable_preemption_handling(signals=("SIGUSR1",))  # re-arm
    assert p._preempted is False
    assert p._prev_signal_handlers[signal.SIGUSR1] == prev  # original, not our closure
    p._teardown(None)
    assert signal.getsignal(signal.SIGUSR1) == prev

    p2 = dml.TrainingPipeline(name="toy")
    with pytest.raises(AttributeError):
        p2.enable_preemption_handling(signals=("SIGUSR1", "SIGNOPE"))
    # nothing half-installed: SIGUSR1's disposition is untouched
    assert signal.getsignal(signal.SIGUSR1) == prev
