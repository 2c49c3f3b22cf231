"""Flight recorder & goodput telemetry (dmlcloud_tpu.telemetry).

Covers: journal schema v1 (LOCKED — a change here is a schema bump, not an
edit), ring/flush mechanics, the multi-rank Chrome-trace merge and its CLI,
an end-to-end CPU pipeline run with ``telemetry=True`` (bucket times must
sum to the epoch wall time), the goodput ledger, and the hang watchdog's
forensics dump — including the barrier-straggler integration: a timed-out
barrier must leave the non-arriving ranks where the dump can name them.
"""

import json
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import dmlcloud_tpu as dml
from dmlcloud_tpu.__main__ import main as cli_main
from dmlcloud_tpu.parallel import runtime
from dmlcloud_tpu.telemetry import (
    SCHEMA_VERSION,
    SPAN_KINDS,
    HangWatchdog,
    SpanJournal,
    journal as journal_mod,
    ledger_from_tracker,
    load_journals,
    to_chrome_trace,
)
from dmlcloud_tpu.telemetry.goodput import flops_from_compiled

# ---------------------------------------------------------------------------
# schema v1 lock
# ---------------------------------------------------------------------------

#: The locked v1 vocabulary. Adding a kind is a PR-visible edit HERE;
#: renaming or removing one requires a schema version bump.
V1_KINDS = {
    "run", "stage", "epoch", "step_dispatch", "data_wait", "h2d",
    "metric_readback", "checkpoint", "barrier", "compile", "host_stall",
    "watchdog", "sanitizer",
    # serving engine (PR 8): queue wait, chunked prefill, decode batches
    "queue_wait", "prefill", "decode_batch",
    # speculative serving (PR 10): draft-model calls, verification passes
    "draft", "verify",
    # overload control (PR 13): isolated step failures, graceful drain
    "fault", "drain",
    # multi-replica router (PR 15): placement, dead-replica resubmission,
    # router-coordinated drain of one replica
    "route", "failover", "replica_drain",
    # Medusa decoding (PR 16): draftless speculative rounds
    "medusa",
    # observability plane (PR 19): admission into a decode slot, prefix
    # cache lookups, copy-on-write forks, SLO burn-rate alerts
    "admission", "prefix_lookup", "cow_fork", "slo_alert",
    # IR-level verifier (PR 20): one traced/audited program per span
    # (named "preflight" because "verify" was already the spec-decode
    # verification pass)
    "preflight",
    # serve call split (PR 25): one engine step, the host work before a device
    # call, the call's uploads, launch and fetch; a profile the program took
    "engine_step", "call_build", "call_upload", "call_launch", "call_fetch",
    "profile",
    # set-up (PR 39): one part of the time from process start to the first
    # step, by label (journal.SETUP_LABELS), with its parent's label
    "setup",
}

#: Core fields every v1 record carries, with their types.
V1_FIELDS = {"v": int, "kind": str, "ts": float, "dur": float, "rank": int, "tid": str}


class TestSchemaV1:
    def test_version_and_kinds_locked(self):
        assert SCHEMA_VERSION == 1
        assert SPAN_KINDS == frozenset(V1_KINDS)

    def test_record_fields_locked(self, tmp_path):
        j = SpanJournal(tmp_path, rank=3)
        t0 = j.now()
        rec = j.emit("step_dispatch", t0, t0 + 0.001, label="x", step=7)
        for field, typ in V1_FIELDS.items():
            assert field in rec, f"v1 record lost core field {field!r}"
            assert isinstance(rec[field], typ), (field, rec[field])
        assert rec["v"] == 1
        assert rec["rank"] == 3
        assert rec["label"] == "x"
        assert rec["step"] == 7  # attrs ride as extra keys
        assert rec["dur"] == pytest.approx(0.001, abs=1e-6)

    def test_round_trips_through_jsonl(self, tmp_path):
        j = SpanJournal(tmp_path, rank=0)
        t0 = j.now()
        j.emit("epoch", t0, t0 + 0.5, label="TrainValStage", epoch=2)
        j.close()
        lines = (tmp_path / "journal-rank0.jsonl").read_text().splitlines()
        assert len(lines) == 1
        rec = json.loads(lines[0])
        assert rec["kind"] == "epoch" and rec["epoch"] == 2 and rec["v"] == 1


class TestJournal:
    def test_ring_keeps_last_n(self, tmp_path):
        j = SpanJournal(tmp_path, ring_size=8)
        t = j.now()
        for i in range(20):
            j.emit("step_dispatch", t, t, step=i)
        tail = j.tail(5)
        assert [r["step"] for r in tail] == [15, 16, 17, 18, 19]
        assert len(j) == 8  # ring bounded even though 20 were emitted

    def test_flush_is_incremental_and_complete(self, tmp_path):
        j = SpanJournal(tmp_path)
        t = j.now()
        j.emit("data_wait", t, t)
        assert j.flush() == 1
        j.emit("data_wait", t, t)
        j.emit("h2d", t, t)
        assert j.flush() == 2
        assert j.flush() == 0
        j.close()
        assert len((tmp_path / "journal-rank0.jsonl").read_text().splitlines()) == 3

    def test_background_flusher_writes_without_close(self, tmp_path):
        j = SpanJournal(tmp_path, flush_interval=0.05).start()
        t = j.now()
        j.emit("barrier", t, t, label="x")
        deadline = time.perf_counter() + 5.0
        path = tmp_path / "journal-rank0.jsonl"
        while time.perf_counter() < deadline:
            if path.read_text().strip():
                break
            time.sleep(0.02)
        j.close()
        assert path.read_text().strip(), "flusher thread never wrote the pending span"

    def test_span_ctx_manager_and_on_emit(self, tmp_path):
        j = SpanJournal(tmp_path)
        pings = []
        j.on_emit = lambda: pings.append(1)
        with j.span("compile", label="train_step"):
            pass
        assert pings == [1]
        assert j.tail(1)[0]["kind"] == "compile"

    def test_module_level_noop_when_inactive(self):
        assert journal_mod.active_journal() is None
        with journal_mod.span("h2d"):  # must not raise, must not record
            pass
        assert journal_mod.emit("h2d", 0.0, 1.0) is None

    def test_emit_thread_name_rides_tid(self, tmp_path):
        j = SpanJournal(tmp_path)
        out = {}

        def worker():
            t = j.now()
            out["rec"] = j.emit("h2d", t, t)

        th = threading.Thread(target=worker, name="prefetcher")
        th.start()
        th.join()
        assert out["rec"]["tid"] == "prefetcher"


class TestChromeTrace:
    def _write_journal(self, d, rank, n=3):
        j = SpanJournal(d, rank=rank)
        t = j.now()
        for i in range(n):
            j.emit("step_dispatch", t + i * 0.01, t + i * 0.01 + 0.005, step=i)
        j.emit("epoch", t, t + n * 0.01, label="stage", epoch=1)
        j.close()

    def test_merges_ranks_into_one_trace(self, tmp_path):
        tdir = tmp_path / "telemetry"
        self._write_journal(tdir, rank=0)
        self._write_journal(tdir, rank=1)
        records = load_journals(tmp_path)  # accepts the run dir
        assert {r["rank"] for r in records} == {0, 1}
        trace = to_chrome_trace(records)
        events = trace["traceEvents"]
        x = [e for e in events if e["ph"] == "X"]
        assert len(x) == 8  # 4 spans per rank
        assert {e["pid"] for e in x} == {0, 1}
        for e in x:
            assert isinstance(e["tid"], int)
            assert e["ts"] >= 0.0 and e["dur"] >= 0.0
        names = {e["args"]["name"] for e in events if e["name"] == "process_name"}
        assert names == {"rank 0", "rank 1"}
        # rebased to the earliest span so the viewer opens at t=0
        assert min(e["ts"] for e in x) == 0.0

    def test_missing_journals_is_a_clear_error(self, tmp_path):
        with pytest.raises(FileNotFoundError, match="telemetry"):
            load_journals(tmp_path / "nope")

    def test_truncated_trailing_line_skipped(self, tmp_path):
        tdir = tmp_path / "telemetry"
        self._write_journal(tdir, rank=0, n=2)
        with open(tdir / "journal-rank0.jsonl", "a") as f:
            f.write('{"v": 1, "kind": "step_dis')  # killed mid-write
        records = load_journals(tmp_path)
        assert len(records) == 3

    def test_timeline_cli(self, tmp_path, capsys):
        self._write_journal(tmp_path / "telemetry", rank=0)
        out_file = tmp_path / "trace.json"
        rc = cli_main(["timeline", str(tmp_path), "-o", str(out_file)])
        assert rc == 0
        trace = json.loads(out_file.read_text())
        assert trace["traceEvents"] and trace["metadata"]["schema"] == 1
        # stdout mode emits the JSON itself
        rc = cli_main(["timeline", str(tmp_path)])
        assert rc == 0
        assert json.loads(capsys.readouterr().out)["traceEvents"]

    def test_timeline_cli_without_journals(self, tmp_path, capsys):
        rc = cli_main(["timeline", str(tmp_path)])
        assert rc == 1
        assert "telemetry" in capsys.readouterr().err


# ---------------------------------------------------------------------------
# end-to-end: CPU pipeline run with telemetry=True
# ---------------------------------------------------------------------------


class _TeleStage(dml.TrainValStage):
    def __init__(self, batches):
        super().__init__()
        self._batches = batches

    def pre_stage(self):
        import flax.linen as nn

        class MLP(nn.Module):
            @nn.compact
            def __call__(self, x):
                return nn.Dense(1)(x)

        model = MLP()
        self.pipeline.register_model(
            "m", model, params=model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8))), verbose=False
        )
        self.pipeline.register_optimizer("sgd", optax.sgd(0.01))
        self.pipeline.register_dataset("train", self._batches, verbose=False)

    def step(self, state, batch):
        pred = state.apply_fn({"params": state.params}, batch["x"])
        return jnp.mean((pred - batch["y"]) ** 2)

    def log_every(self):
        return 5


def _batches(n=12, b=16, d=8):
    rng = np.random.RandomState(0)
    w = rng.randn(d, 1).astype(np.float32)
    xs = rng.randn(n, b, d).astype(np.float32)
    return [{"x": x, "y": x @ w} for x in xs]


@pytest.fixture
def tele_run(tmp_path, single_runtime):
    pipeline = dml.TrainingPipeline(name="tele", telemetry=True)
    pipeline.append_stage(_TeleStage(_batches()), max_epochs=2)
    pipeline.enable_checkpointing(str(tmp_path))
    pipeline.run()
    return pipeline


class TestPipelineTelemetry:
    def test_journal_written_and_timeline_converts(self, tele_run):
        run_dir = str(tele_run.checkpoint_dir.path)
        records = load_journals(run_dir)
        kinds = {r["kind"] for r in records}
        # the instrumentation points the tentpole wires up, all firing
        for expected in ("run", "stage", "epoch", "step_dispatch", "data_wait", "h2d", "checkpoint"):
            assert expected in kinds, f"no {expected!r} spans in the journal"
        assert all(r["v"] == 1 for r in records)
        trace = to_chrome_trace(records)
        json.dumps(trace)  # valid, serializable Chrome-trace JSON
        assert any(e.get("cat") == "epoch" for e in trace["traceEvents"])
        # two epochs ran -> two epoch spans
        assert sum(1 for r in records if r["kind"] == "epoch") == 2

    def test_goodput_buckets_sum_to_epoch_time(self, tele_run):
        tracker = tele_run.tracker
        epochs = tracker["misc/epoch_time"]
        data_wait = tracker["misc/data_wait_ms"]
        ckpt = tracker["misc/ckpt_ms"]
        stall = tracker["misc/host_stall_ms"]
        goodput = tracker["misc/goodput"]
        assert len(goodput) == 2
        for i, epoch_s in enumerate(epochs):
            productive = float(goodput[i]) * float(epoch_s)
            other = (float(data_wait[i]) + float(stall[i])) / 1e3
            # disjoint buckets (ckpt is inside stall) must reassemble the
            # epoch wall time — the acceptance bound is 5%
            assert productive + other == pytest.approx(float(epoch_s), rel=0.05)
            assert float(ckpt[i]) <= float(stall[i]) + 1e-6

    def test_ledger_and_goodput_json(self, tele_run):
        ledger = ledger_from_tracker(tele_run.tracker)
        assert len(ledger.rows) == 2
        totals = ledger.totals()
        assert 0.0 < totals["goodput_frac"] <= 1.0
        table = ledger.format_table()
        assert "goodput" in table and "data_wait" in table
        gp = json.loads((tele_run.checkpoint_dir.path / "telemetry" / "goodput.json").read_text())
        assert gp["v"] == 1
        assert gp["totals"]["epochs"] == 2
        for row in gp["epochs"]:
            bucket_sum = row["data_wait_s"] + row["ckpt_s"] + row["stall_s"] + row["productive_s"]
            assert bucket_sum == pytest.approx(row["epoch_s"], rel=0.05)

    def test_disarmed_after_run(self, tele_run):
        assert not tele_run.telemetry_armed
        assert journal_mod.active_journal() is None

    def test_diag_run_summary(self, tele_run, capsys):
        rc = cli_main(["diag", "--json", "--run", str(tele_run.checkpoint_dir.path)])
        assert rc == 0
        info = json.loads(capsys.readouterr().out)
        assert info["telemetry"]["goodput"]["epochs"] == 2
        assert info["telemetry"]["journal"]["spans"] > 0
        assert "step_dispatch" in info["telemetry"]["journal"]["kinds"]

    def test_telemetry_off_by_default(self, tmp_path, single_runtime):
        pipeline = dml.TrainingPipeline(name="off")
        pipeline.append_stage(_TeleStage(_batches(n=4)), max_epochs=1)
        pipeline.enable_checkpointing(str(tmp_path))
        pipeline.run()
        assert not (pipeline.checkpoint_dir.path / "telemetry").exists()
        assert "misc/goodput" not in pipeline.tracker

    def test_invalid_telemetry_arg_rejected(self):
        with pytest.raises(ValueError, match="telemetry"):
            dml.TrainingPipeline(telemetry=3.14)


# ---------------------------------------------------------------------------
# goodput unit coverage
# ---------------------------------------------------------------------------


class TestGoodputLedger:
    def _tracker(self):
        from dmlcloud_tpu.metrics import MetricTracker, Reduction

        t = MetricTracker()
        for name in ("misc/epoch_time", "misc/data_wait_ms", "misc/ckpt_ms",
                     "misc/host_stall_ms", "misc/goodput"):
            t.register_metric(name)
        for epoch_s, dw, ck, st in ((10.0, 1000.0, 500.0, 1500.0), (8.0, 800.0, 0.0, 200.0)):
            t.track("misc/epoch_time", epoch_s)
            t.track("misc/data_wait_ms", dw)
            t.track("misc/ckpt_ms", ck)
            t.track("misc/host_stall_ms", st)
            t.track("misc/goodput", (epoch_s - (dw + st) / 1e3) / epoch_s)
            t.next_epoch()
        return t

    def test_rows_and_totals(self):
        ledger = ledger_from_tracker(self._tracker())
        assert len(ledger.rows) == 2
        r = ledger.rows[0]
        assert r["epoch_s"] == 10.0
        assert r["data_wait_s"] == 1.0
        assert r["ckpt_s"] == 0.5
        assert r["stall_s"] == 1.0  # host_stall minus the ckpt share
        assert r["productive_s"] == pytest.approx(7.5)
        totals = ledger.totals()
        assert totals["wall_s"] == pytest.approx(18.0)
        assert totals["productive_s"] == pytest.approx(7.5 + 7.0)
        assert totals["goodput_frac"] == pytest.approx(14.5 / 18.0, rel=1e-3)

    def test_empty_tracker(self):
        from dmlcloud_tpu.metrics import MetricTracker

        ledger = ledger_from_tracker(MetricTracker())
        assert ledger.rows == []
        assert ledger.totals()["goodput_frac"] is None

    def test_flops_from_compiled(self):
        class FakeCompiled:
            def cost_analysis(self):
                return {"flops": 2.5e9}

        class Broken:
            def cost_analysis(self):
                raise RuntimeError("no analysis on this backend")

        assert flops_from_compiled(FakeCompiled(), n_devices=4) == 1e10
        assert flops_from_compiled(Broken()) is None
        class Listy:
            def cost_analysis(self):
                return [{"flops": 5.0}]

        assert flops_from_compiled(Listy()) == 5.0


# ---------------------------------------------------------------------------
# hang watchdog + forensics
# ---------------------------------------------------------------------------


class _FakeClient:
    """Same stub as test_runtime's: arrival keys + scripted wait error."""

    def __init__(self, wait_error=None):
        self.kv = {}
        self.wait_error = wait_error

    def key_value_set(self, key, value):
        self.kv[key] = value

    def blocking_key_value_get(self, key, timeout_ms):
        if key in self.kv:
            return self.kv[key]
        raise RuntimeError("DEADLINE_EXCEEDED: key not found")

    def key_value_delete(self, key):
        self.kv.pop(key, None)

    def wait_at_barrier(self, barrier_id, timeout_in_ms):
        if self.wait_error is not None:
            raise self.wait_error


class TestWatchdog:
    def _watchdog(self, tmp_path, journal=None, threshold=10.0):
        clock = {"t": 100.0}
        wd = HangWatchdog(
            tmp_path / "forensics",
            rank=0,
            world_size=4,
            threshold_s=threshold,
            journal=journal,
            clock=lambda: clock["t"],
        )
        return wd, clock

    def test_no_dump_below_threshold(self, tmp_path):
        wd, clock = self._watchdog(tmp_path)
        clock["t"] += 9.0
        assert wd.check() is None
        assert not (tmp_path / "forensics").exists()

    def test_dump_once_per_stall_and_rearm(self, tmp_path):
        wd, clock = self._watchdog(tmp_path)
        clock["t"] += 11.0
        path = wd.check()
        assert path is not None
        assert wd.check() is None  # same stall: no dump storm
        wd.notify()
        clock["t"] += 11.0
        assert wd.check() is not None  # new stall after progress re-arms

    def test_dump_contents(self, tmp_path):
        j = SpanJournal(tmp_path / "telemetry", rank=0, ring_size=16)
        t = j.now()
        for i in range(20):
            j.emit("step_dispatch", t, t, step=i)
        wd, clock = self._watchdog(tmp_path, journal=j, threshold=5.0)
        clock["t"] += 6.0
        path = wd.check()
        dump = json.loads(open(path).read())
        assert dump["v"] == 1
        assert dump["rank"] == 0 and dump["world_size"] == 4
        assert "no span/step progress" in dump["reason"]
        assert dump["last_progress_age_s"] == pytest.approx(6.0)
        # last-N spans from the ring (bounded by ring_size=16)
        assert [r["step"] for r in dump["spans"]] == list(range(4, 20))
        # every live thread's stack, this test's own frame included
        me = [th for th in dump["threads"] if th["name"] == threading.current_thread().name]
        assert me and any("test_telemetry" in line for line in me[0]["stack"])
        j.close()

    def test_barrier_straggler_feeds_forensics(self, tmp_path, single_runtime, monkeypatch):
        """The acceptance path: a barrier that times out records the ranks
        that never arrived, and the watchdog's dump names them."""
        client = _FakeClient(wait_error=RuntimeError("DEADLINE_EXCEEDED while waiting"))
        monkeypatch.setattr(runtime, "_client", lambda: client)
        monkeypatch.setattr(runtime, "world_size", lambda: 4)
        monkeypatch.setattr(runtime, "rank", lambda: 0)
        j = SpanJournal(tmp_path / "telemetry", rank=0)
        journal_mod.activate(j)
        try:
            with pytest.raises(runtime.BarrierTimeout):
                runtime.barrier("epoch_end", timeout=1)
        finally:
            journal_mod.deactivate()
        wd, clock = self._watchdog(tmp_path, journal=j, threshold=5.0)
        clock["t"] += 6.0
        dump = json.loads(open(wd.check()).read())
        # the stuck ranks, by name: this rank arrived, 1..3 never did
        assert dump["barrier"]["status"] == "timeout"
        assert dump["barrier"]["stragglers"] == [1, 2, 3]
        assert dump["barrier"]["tag"] == "epoch_end"
        # the timed-out barrier also journaled a span for the timeline
        barrier_spans = [r for r in j.tail(64) if r["kind"] == "barrier"]
        assert barrier_spans and barrier_spans[-1]["status"] == "timeout"
        assert barrier_spans[-1]["stragglers"] == [1, 2, 3]
        j.close()

    def test_stalled_step_triggers_dump(self, tmp_path, single_runtime):
        """Acceptance: a mocked stalled step (the feed hangs mid-epoch) makes
        the real watchdog thread dump forensics naming this rank."""

        def stalling_batches():
            for i, b in enumerate(_batches(n=6)):
                if i == 3:
                    time.sleep(1.0)  # the "hang": 4x the threshold
                yield b

        class StallingStage(_TeleStage):
            def pre_stage(self):
                super().pre_stage()
                self.pipeline.datasets["train"] = stalling_batches()

        pipeline = dml.TrainingPipeline(
            name="hang",
            telemetry={
                "dir": str(tmp_path / "tele"),
                "hang_threshold_s": 0.25,
                "watchdog_interval_s": 0.05,
            },
        )
        pipeline.append_stage(StallingStage(_batches(n=6)), max_epochs=1)
        pipeline.run()
        dump_file = tmp_path / "forensics" / "rank0.json"
        assert dump_file.exists(), "watchdog never dumped during the stalled step"
        dump = json.loads(dump_file.read_text())
        assert dump["rank"] == 0
        assert "no span/step progress" in dump["reason"]
        assert any(t["stack"] for t in dump["threads"])

    def test_uncaught_exception_dumps_forensics(self, tmp_path, single_runtime):
        class BoomStage(_TeleStage):
            def post_epoch(self):
                raise RuntimeError("boom mid-run")

        pipeline = dml.TrainingPipeline(name="boom", telemetry={"dir": str(tmp_path / "tele")})
        pipeline.append_stage(BoomStage(_batches(n=4)), max_epochs=1)
        with pytest.raises(RuntimeError, match="boom"):
            pipeline.run()
        dump = json.loads((tmp_path / "forensics" / "rank0.json").read_text())
        assert "uncaught exception" in dump["reason"]
        assert "boom mid-run" in dump["reason"]
        assert not pipeline.telemetry_armed  # teardown still disarmed cleanly


# ---------------------------------------------------------------------------
# goodput advisor (ROADMAP-3 slice): doctored ledgers -> concrete knobs
# ---------------------------------------------------------------------------


class TestGoodputAdvisor:
    def _row(self, epoch, epoch_s, data_wait_s, pad_fraction=None, shard_reader=None):
        return {
            "epoch": epoch,
            "epoch_s": epoch_s,
            "data_wait_s": data_wait_s,
            "ckpt_s": 0.0,
            "stall_s": 0.1,
            "productive_s": max(epoch_s - data_wait_s - 0.1, 0.0),
            "goodput": None,
            "mfu": None,
            "pad_fraction": pad_fraction,
            "shard_reader": shard_reader,
        }

    def test_quiet_below_the_threshold(self):
        from dmlcloud_tpu.telemetry.goodput import advise_rows

        assert advise_rows([self._row(1, 10.0, 1.0), self._row(2, 10.0, 2.9)]) == []
        assert advise_rows([]) == []

    def test_data_wait_dominance_suggests_prefetch(self):
        from dmlcloud_tpu.telemetry.goodput import advise_rows

        advice = advise_rows([self._row(1, 10.0, 0.5), self._row(2, 10.0, 4.2)])
        assert len(advice) == 1
        assert "prefetch" in advice[0] and "host_prefetch" in advice[0]
        assert "epoch(s) 2" in advice[0]

    def test_pad_mask_adds_the_pack_stream_suggestion(self):
        from dmlcloud_tpu.telemetry.goodput import advise_rows

        advice = advise_rows([self._row(1, 10.0, 4.0, pad_fraction=0.72)])
        assert len(advice) == 2
        assert "pack_stream" in advice[1] and "72%" in advice[1]
        # a mask with little padding does not trigger the packing advice
        advice = advise_rows([self._row(1, 10.0, 4.0, pad_fraction=0.05)])
        assert len(advice) == 1

    def test_shard_reader_starvation_targets_the_reader_knobs(self):
        """When a disk ShardReader fed the starved epochs, the advice names
        the reader's own knobs — buffers= / read_ahead= — INSTEAD of the
        generic downstream prefetch row (which would only move the same
        starvation one stage later)."""
        from dmlcloud_tpu.telemetry.goodput import advise_rows

        advice = advise_rows([self._row(1, 10.0, 4.5, shard_reader=1.0)])
        assert len(advice) == 1
        assert "ShardReader" in advice[0]
        assert "buffers=" in advice[0] and "read_ahead=" in advice[0]
        assert "host_prefetch" not in advice[0]

    def test_shard_reader_in_healthy_epoch_keeps_generic_advice(self):
        """The reader advice keys off the STARVED epochs: a ShardReader that
        fed only well-overlapped epochs doesn't hijack the generic row."""
        from dmlcloud_tpu.telemetry.goodput import advise_rows

        rows = [
            self._row(1, 10.0, 0.2, shard_reader=1.0),  # healthy, reader-fed
            self._row(2, 10.0, 4.5),  # starved, generic iterable
        ]
        advice = advise_rows(rows)
        assert len(advice) == 1
        assert "host_prefetch" in advice[0]
        assert "ShardReader" not in advice[0]

    def test_shard_reader_advice_composes_with_pad_advice(self):
        from dmlcloud_tpu.telemetry.goodput import advise_rows

        advice = advise_rows([self._row(1, 10.0, 4.0, pad_fraction=0.4, shard_reader=1.0)])
        assert len(advice) == 2
        assert "read_ahead=" in advice[0]
        assert "pack_stream" in advice[1]

    def test_ledger_advise_delegates(self):
        from dmlcloud_tpu.telemetry.goodput import GoodputLedger, advise_rows

        rows = [self._row(1, 10.0, 5.0, pad_fraction=0.5)]
        assert GoodputLedger(rows).advise() == advise_rows(rows)

    def test_diag_run_reports_advice_from_doctored_ledger(self, tmp_path, capsys):
        """diag --run derives the SAME advice from the persisted
        goodput.json rows — no live tracker needed."""
        tele = tmp_path / "telemetry"
        tele.mkdir()
        doctored = {
            "v": 1,
            "epochs": [self._row(1, 10.0, 6.0, pad_fraction=0.7)],
            "totals": {"epochs": 1, "wall_s": 10.0, "compile_s": 0.0, "data_wait_s": 6.0,
                       "ckpt_s": 0.0, "host_stall_s": 0.1, "productive_s": 3.9,
                       "goodput_frac": 0.39, "mfu": None},
        }
        (tele / "goodput.json").write_text(json.dumps(doctored))
        rc = cli_main(["diag", "--json", "--run", str(tmp_path)])
        info = json.loads(capsys.readouterr().out)
        assert rc == 0
        advice = info["telemetry"]["advice"]
        assert len(advice) == 2
        assert "prefetch" in advice[0] and "pack_stream" in advice[1]

        cli_main(["diag", "--run", str(tmp_path)])
        out = capsys.readouterr().out
        assert "advice:" in out and "pack_stream" in out

    def test_healthy_run_gets_no_advice(self, tele_run, capsys):
        """The real telemetry e2e run (tiny batches, no starvation) stays
        quiet — the advisor only speaks on evidence."""
        from dmlcloud_tpu.telemetry.goodput import ledger_from_tracker

        ledger = ledger_from_tracker(tele_run.tracker)
        for line in ledger.advise():
            assert "data_wait" in line  # if it ever fires here, it is honest
