"""REAL multi-controller tests: spawn 2-3 OS processes that rendezvous via
``jax.distributed.initialize`` on localhost, then exercise the paths that
world-size-1 tests cannot reach — ``init_from_env``, the three object
collectives, the fused single-collective metric exchange (incl. ragged
tracking diagnostics), barrier timeout with straggler naming, and a full
pipeline train+resume across two processes.

This goes past the reference's world-1 HashStore trick
(/root/reference/test/conftest.py:6-10): every collective here crosses a
process boundary for real (KV store over gRPC, arrays over gloo).
"""

import os
import subprocess
import sys
import textwrap

import pytest

from dmlcloud_tpu.utils.tcp import find_free_port

pytestmark = pytest.mark.multiprocess

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PRELUDE = """
import os, sys
sys.path.insert(0, {repo!r})
import numpy as np
from dmlcloud_tpu.parallel import runtime as rt

backend = rt.init_auto()
assert backend == "env", backend
RANK, WORLD = rt.rank(), rt.world_size()
"""


def _spawn(tmp_path, body: str, n: int = 2, timeout: int = 240):
    """Run ``body`` (after the init prelude) in ``n`` coordinated processes;
    returns per-rank stdout. Asserts every rank exits 0."""
    script = tmp_path / "worker.py"
    script.write_text(_PRELUDE.format(repo=_REPO) + textwrap.dedent(body))
    port = find_free_port()
    procs = []
    for i in range(n):
        env = dict(os.environ)
        env.update(
            {
                "JAX_PLATFORMS": "cpu",
                "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
                "DMLCLOUD_TPU_COORDINATOR": f"localhost:{port}",
                "DMLCLOUD_TPU_NUM_PROCESSES": str(n),
                "DMLCLOUD_TPU_PROCESS_ID": str(i),
            }
        )
        procs.append(
            subprocess.Popen(
                [sys.executable, str(script)],
                env=env,
                stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT,
                text=True,
            )
        )
    outs = []
    for i, p in enumerate(procs):
        try:
            out, _ = p.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            pytest.fail(f"rank {i} timed out after {timeout}s")
        outs.append(out)
    for i, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"rank {i} failed (rc={p.returncode}):\n{out}"
    return outs


def test_init_and_object_collectives(tmp_path):
    """init_from_env + broadcast/all_gather/gather over the coordination-service
    KV store, all crossing a real process boundary."""
    _spawn(
        tmp_path,
        """
        assert WORLD == 2 and RANK in (0, 1)
        got = rt.broadcast_object({"cfg": [1, 2, 3]} if RANK == 0 else None)
        assert got == {"cfg": [1, 2, 3]}, got
        gathered = rt.all_gather_object(("rank", RANK))
        assert gathered == [("rank", i) for i in range(WORLD)], gathered
        g = rt.gather_object(RANK * 10)
        if RANK == 0:
            assert g == [0, 10], g
        else:
            assert g is None, g
        rt.barrier("done", timeout=60)
        print("COLLECTIVES-OK", RANK)
        """,
    )


def test_divergent_collective_call_sites_fail_loudly(tmp_path):
    """A rank-conditional collective pairing two DIFFERENT call sites must
    raise CollectiveMismatchError on the receiver — not silently deliver
    whatever object the other rank happened to publish at that sequence
    number (runtime.py's _seq counters assume identical call sequences).
    An explicit shared tag= opts intentional cross-site pairs back in."""
    _spawn(
        tmp_path,
        """
        # the corruption scenario: rank 0 publishes from one call site while
        # rank 1 receives at the same sequence number from another
        if RANK == 0:
            rt.broadcast_object({"secret": 42})
            print("DIVERGE-OK", RANK)
        else:
            try:
                rt.broadcast_object(None)
            except rt.CollectiveMismatchError as e:
                assert "diverged" in str(e), e
                assert "tag=" in str(e), e
                print("DIVERGE-OK", RANK)
            else:
                raise SystemExit("expected CollectiveMismatchError, got an object")
        rt.barrier("resync", timeout=60)

        # intentional cross-site pairing: an explicit shared tag makes it legal
        if RANK == 0:
            got = rt.broadcast_object({"cfg": 7}, tag="cfg-exchange")
        else:
            got = rt.broadcast_object(tag="cfg-exchange")
        assert got == {"cfg": 7}, got
        print("TAGGED-OK", RANK)
        """,
    )


def test_fused_metric_exchange(tmp_path):
    """The packed single-collective epoch exchange across real processes:
    MEAN/SUM/MIN/MAX combine correctly, local metrics stay local, and every
    rank sees identical reduced histories."""
    _spawn(
        tmp_path,
        """
        from dmlcloud_tpu.metrics import MetricTracker, Reduction
        t = MetricTracker()
        t.register_metric("loss", Reduction.MEAN)
        t.register_metric("cnt", Reduction.SUM)
        t.register_metric("hi", Reduction.MAX)
        t.register_metric("lo", Reduction.MIN)
        t.register_metric("local_cnt", Reduction.SUM, globally=False)
        t.track("loss", 1.0 + RANK)
        t.track("cnt", 7)
        t.track("hi", float(RANK))
        t.track("lo", float(RANK))
        t.track("local_cnt", RANK + 1)
        t.next_epoch()
        assert abs(t["loss"][0] - 1.5) < 1e-6, t["loss"]
        assert int(t["cnt"][0]) == 14
        assert t["hi"][0] == 1.0 and t["lo"][0] == 0.0
        assert int(t["local_cnt"][0]) == RANK + 1  # NOT globally reduced
        print("FUSED-OK", RANK)
        """,
    )


def test_fused_exchange_ragged_tracking_raises(tmp_path):
    """One rank tracks a metric, the other does not — every rank must raise
    the ragged-tracking diagnostic (diverged control flow is a bug)."""
    _spawn(
        tmp_path,
        """
        from dmlcloud_tpu.metrics import MetricTracker, Reduction
        t = MetricTracker()
        t.register_metric("loss", Reduction.MEAN)
        t.register_metric("sometimes", Reduction.MEAN)
        t.track("loss", 1.0)
        if RANK == 0:
            t.track("sometimes", 2.0)
        try:
            t.next_epoch()
            raise SystemExit("expected ragged-tracking ValueError")
        except ValueError as e:
            assert "some workers tracked" in str(e), e
        print("RAGGED-OK", RANK)
        """,
    )


def test_barrier_timeout_names_stragglers(tmp_path):
    """Rank 1 never reaches the barrier; rank 0's timeout error must name
    rank 1 (parity with the reference's monitored_barrier wait_all_ranks)."""
    outs = _spawn(
        tmp_path,
        """
        import time
        if RANK == 0:
            try:
                rt.barrier("straggle", timeout=3)
                raise SystemExit("barrier unexpectedly passed")
            except rt.BarrierTimeout as e:
                assert e.stragglers == [1], e.stragglers
                print("STRAGGLERS", e.stragglers)
            time.sleep(4)  # outlive rank 1 so the coordinator survives its exit
        else:
            time.sleep(3.5)  # never arrive at the barrier
            print("SLEPT", RANK)
        """,
    )
    assert "STRAGGLERS [1]" in outs[0]


def test_fsdp_sharded_checkpoint_across_processes(tmp_path):
    """Params sharded over an fsdp axis spanning BOTH processes' devices:
    Orbax saves each host's shards in parallel and restores them with the
    original sharding — the multi-host checkpointing claim, executed."""
    _spawn(
        tmp_path,
        """
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from dmlcloud_tpu.checkpoint import CheckpointDir
        from dmlcloud_tpu.parallel import mesh as mesh_lib

        mesh = mesh_lib.create_mesh({{"fsdp": 2}})
        sharding = NamedSharding(mesh, P("fsdp"))
        # a global [8, 4] array, rows 0-3 on process 0, rows 4-7 on process 1
        local = np.arange(16, dtype=np.float32).reshape(4, 4) + 100 * RANK
        arr = jax.make_array_from_process_local_data(sharding, local)

        ckpt = CheckpointDir({ckpt!r})
        if rt.is_root() and not ckpt.is_valid:
            ckpt.create()
        rt.barrier("created", timeout=60)
        ckpt.save_state(1, {{"w": arr}}, scope="fsdp_stage")
        ckpt.wait_until_finished()
        rt.barrier("saved", timeout=120)

        template = {{"w": jax.device_put(jnp.zeros((8, 4)), sharding)}}
        restored = ckpt.restore_state(1, template=template, scope="fsdp_stage")["w"]
        assert restored.sharding.spec == P("fsdp"), restored.sharding
        # every process checks ITS addressable shard round-tripped
        for shard in restored.addressable_shards:
            np.testing.assert_array_equal(np.asarray(shard.data), local)
        ckpt.close()
        print("FSDP-CKPT-OK", RANK)
        """.format(ckpt=str(tmp_path / "fsdp_run")),
        timeout=300,
    )


#: Shared worker-body fragment: a deterministic toy TrainValStage (linear
#: regression, per-process data shard). Tests concatenate their specifics
#: after it — one source of truth for the registration API in use.
_TOY_STAGE = """
    import jax, jax.numpy as jnp, optax
    import dmlcloud_tpu as dml

    class Toy(dml.TrainValStage):
        def pre_stage(self):
            rng = np.random.RandomState(0)
            w = rng.randn(4, 1).astype(np.float32)
            xs = rng.randn(4, 8, 4).astype(np.float32)  # per-process shard
            batches = [{"x": jnp.asarray(x), "y": jnp.asarray(x @ w)} for x in xs]
            self.pipeline.register_model(
                "lin", apply_fn=lambda p, x: x @ p["w"], params={"w": jnp.zeros((4, 1))}, verbose=False
            )
            self.pipeline.register_optimizer("sgd", optax.sgd(0.05))
            self.pipeline.register_dataset("train", batches, verbose=False)

        def step(self, state, batch):
            return jnp.mean((state.apply_fn(state.params, batch["x"]) - batch["y"]) ** 2)

        def val_epoch(self):
            pass
"""


def test_pipeline_train_and_resume_two_processes(tmp_path):
    """End-to-end: a 2-process pipeline (mesh spanning both processes' CPU
    devices, global-batch step, Orbax collective checkpointing) trains 2
    epochs; a second 2-process run resumes — with the resume sidecar
    CORRUPTED, so both processes must take the root-broadcast degraded path
    in lockstep (the divergence scenario that used to deadlock) — and
    finishes at the same epoch on every rank."""
    ckpt_root = tmp_path / "runs"
    body = _TOY_STAGE + """
    import json

    CKPT = {ckpt!r}
    RESUME = os.environ["RESUME_PHASE"] == "1"

    pipeline = dml.TrainingPipeline(name="mp")
    stage = Toy()
    pipeline.append_stage(stage, max_epochs=4 if RESUME else 2, name="stage")
    pipeline.enable_checkpointing(CKPT, resume=RESUME)
    pipeline.run()
    if not RESUME:
        assert stage.current_epoch == 3, stage.current_epoch
    else:
        # corrupt sidecar -> Orbax-only resume from epoch 2, both ranks agree
        assert stage.current_epoch == 5, stage.current_epoch
    pipeline.checkpoint_dir.wait_until_finished()
    print("PHASE-OK", RANK, stage.current_epoch)
    """.format(ckpt=str(ckpt_root))

    env_marker = "\n    os.environ.setdefault('RESUME_PHASE', '0')\n"
    os.environ["RESUME_PHASE"] = "0"
    try:
        _spawn(tmp_path, env_marker + body, timeout=300)
        # corrupt every sidecar: both processes must degrade identically
        run_dirs = [d for d in ckpt_root.iterdir() if d.is_dir()]
        assert len(run_dirs) == 1
        meta_dir = run_dirs[0] / "meta" / "stage"
        sidecars = list(meta_dir.glob("*.json"))
        assert sidecars
        for f in sidecars:
            f.write_text("{not json")
        os.environ["RESUME_PHASE"] = "1"
        # point resume at the exact run dir (Slurm rediscovery is not in play)
        body_resume = body.replace("CKPT = ", f"CKPT = {str(run_dirs[0])!r}  # ")
        _spawn(tmp_path, env_marker + body_resume, timeout=300)
    finally:
        os.environ.pop("RESUME_PHASE", None)


def test_packed_flash_step_across_processes(tmp_path):
    """A packed (segment_ids) flash-attention gradient step over a REAL
    2-process data mesh: per-process batch shards assemble into the global
    array, the compiled step runs collectively, and both ranks agree on the
    loss (one data-parallel psum)."""
    outs = _spawn(
        tmp_path,
        """
        import jax, jax.numpy as jnp
        from dmlcloud_tpu.models.transformer import DecoderLM, TransformerConfig, lm_loss
        from dmlcloud_tpu.parallel import mesh as mesh_lib

        mesh = mesh_lib.create_mesh({"data": 2})
        cfg = TransformerConfig(vocab_size=64, num_layers=1, num_heads=2, head_dim=8,
                                hidden_dim=16, mlp_dim=32, max_seq_len=16,
                                dtype=jnp.float32, attn_impl="flash", sliding_window=6)
        model = DecoderLM(cfg)
        local_toks = np.random.RandomState(RANK).randint(1, 64, size=(2, 16)).astype(np.int32)
        local_segs = np.repeat(np.arange(1, 5)[None], 2, 0).repeat(4, axis=1).astype(np.int32)
        params = model.init(jax.random.PRNGKey(0), jnp.asarray(local_toks[:1]))["params"]
        params = mesh_lib.shard_pytree(params, mesh, "replicate")
        toks = mesh_lib.make_global_batch(local_toks, mesh)
        segs = mesh_lib.make_global_batch(local_segs, mesh)

        @jax.jit
        def step(p, toks, segs):
            def loss_fn(p):
                return lm_loss(model.apply({"params": p}, toks, segment_ids=segs),
                               toks, segment_ids=segs)
            return jax.value_and_grad(loss_fn)(p)

        loss, grads = step(params, toks, segs)
        finite = all(bool(jnp.all(jnp.isfinite(g))) for g in jax.tree_util.tree_leaves(grads))
        print("LOSS", float(loss), "GRADS_FINITE", finite, flush=True)
        rt.barrier("done", timeout=120)
        """,
        n=2,
    )
    import math

    losses = []
    for out in outs:
        line = [l for l in out.splitlines() if l.startswith("LOSS ")]
        assert line, out
        parts = line[0].split()
        losses.append(float(parts[1]))
        assert parts[3] == "True", f"non-finite grads: {line[0]}"
    assert math.isfinite(losses[0])
    assert losses[0] == losses[1]  # the psum'd global loss is identical on both ranks


def test_one_sided_preemption_coordinates_both_ranks(tmp_path):
    """A preemption signal delivered to ONE rank only: both ranks must agree
    to exit at the same epoch boundary (the un-signaled rank would otherwise
    hang in the next epoch's collectives), save the checkpoint, and leave
    the stage resumable (not stopped)."""
    body = _TOY_STAGE + """
    class PreemptToy(Toy):
        def pre_epoch(self):
            if RANK == 1 and self.current_epoch == 2:
                import os as _os, signal as _signal
                _os.kill(_os.getpid(), _signal.SIGUSR1)  # rank 1 ONLY

    pipeline = dml.TrainingPipeline(name="mp-preempt")
    stage = PreemptToy()
    pipeline.append_stage(stage, max_epochs=5, name="stage")
    pipeline.enable_checkpointing({ckpt!r})
    pipeline.enable_preemption_handling(signals=("SIGUSR1",))
    pipeline.run()
    # saves committed: pipeline.run()'s _post_run waits on the checkpoint dir
    assert stage.current_epoch == 3, stage.current_epoch  # both exit after epoch 2
    assert stage._stop_requested is False
    assert pipeline.checkpoint_dir.latest_step(scope="stage") == 2
    print("PREEMPT-OK", RANK, stage.current_epoch)
    """.replace("{ckpt!r}", repr(str(tmp_path / "runs")))
    outs = _spawn(tmp_path, body, timeout=300)
    for out in outs:
        assert "PREEMPT-OK" in out


def test_mid_epoch_step_save_and_resume_two_processes(tmp_path):
    """Step-granular checkpointing across a REAL 2-process group: rank 0
    alone sees a 'preemption' mid-epoch, the coordinated poll at the next
    step-save boundary makes BOTH ranks save collectively (sharded Orbax
    write) and exit mid-epoch; a second 2-process run resumes inside the
    epoch and finishes with both ranks in agreement."""
    ckpt_root = tmp_path / "runs"
    body = _TOY_STAGE + """
    CKPT = {ckpt!r}
    RESUME = os.environ["RESUME_PHASE"] == "1"

    class StepToy(Toy):
        def checkpoint_every_steps(self):
            return 2

        def device_prefetch(self):
            return 0  # keep batch consumption aligned with steps

        def pre_stage(self):
            super().pre_stage()
            if not RESUME:
                pipe = self.pipeline
                batches = pipe.datasets["train"]

                class Trigger:  # rank 0 'catches a signal' after batch 3
                    def __iter__(self):
                        for i, b in enumerate(batches):
                            yield b
                            if RANK == 0 and i + 1 == 3:
                                pipe._preempted = True

                    def __len__(self):
                        return len(batches)

                pipe.datasets["train"] = Trigger()

    pipeline = dml.TrainingPipeline(name="mpstep")
    if not RESUME:
        pipeline._preemption_enabled = True
        pipeline._preempted = False
    stage = StepToy()
    pipeline.append_stage(stage, max_epochs=2, name="stage")
    pipeline.enable_checkpointing(CKPT, resume=RESUME)
    pipeline.run()
    if not RESUME:
        assert stage._mid_epoch_exit and stage._preempt_exit
        # the poll at step 4 (save cadence 2) cut epoch 1 short on BOTH ranks
        assert int(stage.state.step) == 4, int(stage.state.step)
    else:
        assert int(stage.state.step) == 8, int(stage.state.step)
        assert stage.current_epoch == 3, stage.current_epoch
    fp = float(np.abs(np.asarray(stage.state.params["w"])).sum())
    pipeline.checkpoint_dir.wait_until_finished()
    print("STEP-PHASE-OK", RANK, round(fp, 6))
    """.format(ckpt=str(ckpt_root))

    env_marker = "\n    os.environ.setdefault('RESUME_PHASE', '0')\n"
    os.environ["RESUME_PHASE"] = "0"
    try:
        outs = _spawn(tmp_path, env_marker + body, timeout=480)
        run_dirs = [d for d in ckpt_root.iterdir() if d.is_dir()]
        assert len(run_dirs) == 1
        assert (run_dirs[0] / "state" / "stage.steps").exists()
        os.environ["RESUME_PHASE"] = "1"
        body_resume = body.replace("CKPT = ", f"CKPT = {str(run_dirs[0])!r}  # ")
        outs = _spawn(tmp_path, env_marker + body_resume, timeout=480)
        # both ranks ended on identical params
        fps = {line.split()[-1] for out in outs for line in out.splitlines() if "STEP-PHASE-OK" in line}
        assert len(fps) == 1, fps
    finally:
        os.environ.pop("RESUME_PHASE", None)


def test_tensorboard_and_wandb_init_are_root_only(tmp_path):
    """Regression guard for the decorator-placement class of bug: in a
    2-process run, only the root creates TensorBoard event files (and a
    stub wandb module records init on the root alone)."""
    pytest.importorskip("tensorboardX")
    tb_dir = tmp_path / "tb"
    body = _TOY_STAGE + """
    import sys, types, glob

    # stub wandb so _start_wandb's root_only gating is observable without
    # the real service: record which rank called init
    calls = []
    stub = types.ModuleType("wandb")
    stub.init = lambda **kw: calls.append(RANK)
    stub.log = lambda *a, **k: None
    stub.finish = lambda **kw: None
    stub.run = None
    sys.modules["wandb"] = stub

    pipeline = dml.TrainingPipeline(name="obs")
    pipeline.enable_tensorboard({tb!r})
    pipeline.enable_wandb(project="x")
    pipeline.append_stage(Toy(), max_epochs=1, name="stage")
    pipeline.run()
    assert calls == ([0] if RANK == 0 else []), calls
    n_events = len(glob.glob({tb!r} + "/events.*"))
    if RANK == 0:
        assert n_events >= 1, "root wrote no event files"
    print("OBS-OK", RANK, n_events)
    """.format(tb=str(tb_dir))
    outs = _spawn(tmp_path, body, timeout=480)
    assert all("OBS-OK" in out for out in outs)
    import glob

    assert len(glob.glob(str(tb_dir) + "/events.*")) == 1  # exactly one writer existed
