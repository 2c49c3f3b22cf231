"""Profiling helpers: real jax.profiler traces land on disk, profile_steps
returns the computed result, StepTimer percentiles behave."""

import jax.numpy as jnp
import numpy as np
import pytest

from dmlcloud_tpu.utils.profiling import StepTimer, profile_steps, trace


def test_trace_writes_profile(tmp_path):
    logdir = tmp_path / "prof"
    with trace(str(logdir)):
        x = jnp.ones((64, 64)) @ jnp.ones((64, 64))
        float(x.sum())
    files = list(logdir.rglob("*"))
    assert any(f.is_file() for f in files), "no trace artifacts written"


def test_profile_steps_returns_result(tmp_path):
    def step():
        return jnp.arange(4.0) * 2

    out = profile_steps(step, 3, str(tmp_path / "prof"))
    np.testing.assert_array_equal(np.asarray(out), [0.0, 2.0, 4.0, 6.0])


def test_step_timer_percentiles():
    t = StepTimer()
    t.tick()
    for _ in range(10):
        t.tick()
    assert t.count == 10
    summary = t.summary()
    assert summary["p50_ms"] >= 0.0
    assert summary["p95_ms"] >= summary["p50_ms"]
    assert summary["p99_ms"] >= summary["p95_ms"]
    assert summary["max_ms"] >= summary["p99_ms"]
    assert summary["total_ms"] == pytest.approx(sum(t._t))
    assert StepTimer().summary() == {}


def test_step_timer_reset_forgets_last_tick():
    t = StepTimer()
    t.tick()
    t.tick()
    assert t.count == 1
    t.reset()
    assert t.count == 0 and t.summary() == {}
    # the first tick after reset starts a NEW sequence: no phantom interval
    # spanning the reset gap
    t.tick()
    assert t.count == 0
    t.tick()
    assert t.count == 1


def test_phase_table_requires_trace_dir(tmp_path):
    from dmlcloud_tpu.utils.profiling import phase_table

    with pytest.raises(FileNotFoundError, match="xplane"):
        phase_table(str(tmp_path))


def _table(phases, kernels=()):
    return {"device": "/device:TPU:0", "profile_start_ns": None, "steps": 2, "busy_ms_per_step": 1.5,
            "phases": phases, "kernels": list(kernels)}


def test_format_phase_table_hides_small_rows_and_lists_kernels():
    from dmlcloud_tpu.utils.profiling import format_phase_table

    rows = [
        {"phase": "mlp", "direction": "bwd", "time_frac": 0.9, "ms_per_step": 1.0, "n_per_step": 3},
        {"phase": "embed", "direction": "fwd", "time_frac": 0.0001, "ms_per_step": 0.0, "n_per_step": 1},
    ]
    out = format_phase_table(_table(rows))
    assert "mlp" in out and "bwd" in out and "embed" not in out  # sub-0.1% rows hidden
    assert "kernel" not in out  # no kernel block without kernels
    kernel = {"kernel": "flash_fwd", "time_frac": 0.1, "ms_per_step": 0.15, "n_per_step": 2}
    assert "flash_fwd" in format_phase_table(_table(rows, [kernel]))


def _load_analyze_trace():
    import importlib.util
    from pathlib import Path

    path = Path(__file__).resolve().parent.parent / "scripts" / "analyze_trace.py"
    if not path.is_file():
        pytest.skip("scripts/ not present next to the package")
    spec = importlib.util.spec_from_file_location("_analyze_trace_under_test", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_analyze_trace_json_schema(monkeypatch, capsys):
    import json

    mod = _load_analyze_trace()
    table = _table([{"phase": "mlp", "direction": "-", "time_frac": 1.0, "ms_per_step": 1.0, "n_per_step": 1}])
    seen = {}
    monkeypatch.setattr(mod, "phase_table", lambda d, **kw: seen.update(kw) or table)
    assert mod.main(["/tmp/whatever", "--json", "--steps", "7", "--program", "train_step"]) == 0
    out = json.loads(capsys.readouterr().out)
    # v3: the phase table replaced v2's tensorflow-read roofline keys (serve-journal
    # inputs still give a "serve" object — see tests/test_observability.py)
    assert out == {"version": 3, "table": table}
    assert seen == {"phases": None, "steps": 7, "program": "train_step"}


def test_analyze_trace_empty_rows_is_a_clear_message(monkeypatch, capsys):
    mod = _load_analyze_trace()
    monkeypatch.setattr(mod, "phase_table", lambda d, **kw: _table([]))
    assert mod.main(["/tmp/whatever"]) == 1
    err = capsys.readouterr().err
    assert "no XLA op rows" in err and "block_until_ready" in err
    assert mod.main(["/tmp/whatever", "--json"]) == 1  # same guard on the json path


def test_peak_flops_for_kind():
    from dmlcloud_tpu.utils.profiling import chip_peak_flops, peak_flops_for_kind

    assert peak_flops_for_kind("TPU v5 lite") == 197e12
    assert peak_flops_for_kind("TPU v6e") == 918e12
    assert peak_flops_for_kind("cpu") is None
    with pytest.raises(ValueError, match="no bf16 peak known"):
        chip_peak_flops()  # the suite's devices are CPUs: not in the table, no default


class TestStallTimerNesting:
    """StallTimer.measure() nesting-safety: nested spans (block()/fetch()
    called inside an outer measure()) must not double-count — only the
    outermost span accumulates."""

    @staticmethod
    def _with_fake_clock(monkeypatch):
        """Each perf_counter_ns read advances a fake clock by exactly 1 ms,
        making the accounting arithmetic deterministic."""
        from dmlcloud_tpu.utils import profiling

        clock = {"ns": 0}

        def fake_ns():
            clock["ns"] += 1_000_000
            return clock["ns"]

        monkeypatch.setattr(profiling.time, "perf_counter_ns", fake_ns)
        return clock

    def test_nested_measure_counts_outer_span_once(self, monkeypatch):
        from dmlcloud_tpu.utils.profiling import StallTimer

        self._with_fake_clock(monkeypatch)
        t = StallTimer()
        with t.measure():          # clock read #1 (enter, 1ms)
            with t.measure():      # nested: NO clock read
                pass
            with t.measure():      # nested: NO clock read
                pass
        # clock read #2 (exit, 2ms): exactly one 1ms outer span accumulated.
        # The pre-fix accounting read the clock in every measure() and
        # would have reported 3 overlapping spans here.
        assert t.ms == 1.0

    def test_nested_fetch_and_block_accumulate_once(self, monkeypatch):
        from dmlcloud_tpu.utils.profiling import StallTimer

        self._with_fake_clock(monkeypatch)
        t = StallTimer()
        with t.measure():
            t.fetch(np.ones(3))            # rides the outer span
            t.block({"x": np.ones(2)})     # rides the outer span
        assert t.ms == 1.0

    def test_sequential_measures_still_sum(self, monkeypatch):
        from dmlcloud_tpu.utils.profiling import StallTimer

        self._with_fake_clock(monkeypatch)
        t = StallTimer()
        with t.measure():
            pass
        with t.measure():
            pass
        assert t.ms == 2.0
        t.reset()
        assert t.ms == 0.0

    def test_real_clock_sanity(self):
        import time as _time

        from dmlcloud_tpu.utils.profiling import StallTimer

        t = StallTimer()
        with t.measure():
            with t.measure():
                _time.sleep(0.01)
        # one ~10ms span, not ~20ms of double-counted overlap
        assert 5.0 <= t.ms < 1000.0


class TestStallTimerLabels:
    """measure(label=...) attributes spans to named buckets — how the
    goodput ledger splits checkpoint waits from metric readbacks — and, with
    the telemetry journal armed, emits them as typed spans."""

    def test_labels_accumulate_separately(self, monkeypatch):
        from dmlcloud_tpu.utils.profiling import StallTimer

        TestStallTimerNesting._with_fake_clock(monkeypatch)
        t = StallTimer()
        with t.measure(label="checkpoint"):
            pass
        with t.measure(label="checkpoint"):
            pass
        with t.measure(label="metric_readback"):
            pass
        with t.measure():  # unlabeled: total only
            pass
        assert t.label_ms("checkpoint") == 2.0
        assert t.label_ms("metric_readback") == 1.0
        assert t.label_ms("nope") == 0.0
        assert t.ms == 4.0

    def test_nested_label_attributes_outermost_only(self, monkeypatch):
        from dmlcloud_tpu.utils.profiling import StallTimer

        TestStallTimerNesting._with_fake_clock(monkeypatch)
        t = StallTimer()
        with t.measure(label="checkpoint"):
            with t.measure(label="metric_readback"):  # nested: no span of its own
                pass
        assert t.label_ms("checkpoint") == 1.0
        assert t.label_ms("metric_readback") == 0.0

    def test_reset_clears_labels(self, monkeypatch):
        from dmlcloud_tpu.utils.profiling import StallTimer

        TestStallTimerNesting._with_fake_clock(monkeypatch)
        t = StallTimer()
        with t.measure(label="checkpoint"):
            pass
        t.reset()
        assert t.ms == 0.0 and t.label_ms("checkpoint") == 0.0

    def test_labeled_span_reaches_journal(self, tmp_path):
        from dmlcloud_tpu.telemetry import journal as journal_mod
        from dmlcloud_tpu.telemetry.journal import SpanJournal
        from dmlcloud_tpu.utils.profiling import StallTimer

        j = journal_mod.activate(SpanJournal(tmp_path))
        try:
            t = StallTimer()
            with t.measure(label="checkpoint"):
                pass
            with t.measure(label="custom_wait"):  # not a v1 kind
                pass
            with t.measure():  # unlabeled: no journal span
                pass
        finally:
            journal_mod.deactivate()
        recs = j.tail(10)
        assert [r["kind"] for r in recs] == ["checkpoint", "host_stall"]
        assert recs[1]["label"] == "custom_wait"  # label preserved as attr
        j.close()


# ---------------------------------------------------------------------------
# phases: op_name -> (phase, direction), compiled step -> map, trace -> table
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("op_name,expected", [
    ("jit(train_step)/jit(main)/jvp(DecoderLM)/layer_0/attn/q_proj/dot_general", ("attn_proj", "fwd")),
    ("jit(train_step)/transpose(jvp(DecoderLM))/layer_1/attn/attn_kernel/flash_bwd_dq/pallas_call", ("attn_kernel", "bwd")),
    ("jit(train_step)/jvp(DecoderLM)/layer_0/mlp_norm/mul", ("norm", "fwd")),
    ("jit(train_step)/transpose(jvp(DecoderLM))/jvp(DecoderLM)/checkpoint/rematted_computation/layer_0/mlp/mul",
     ("mlp", "recompute")),
    ("jit(train_step)/transpose(jvp(DecoderLM))/jvp(DecoderLM)/checkpoint/layer_0/mlp/gate_proj/add_any", ("mlp", "bwd")),
    ("jit(train_step)/transpose(jvp(mlp))/mul", ("mlp", "bwd")),  # a transform wraps the scope it crosses
    ("jit(train_step)/optimizer/add", ("optimizer", "-")),
    ("jit(_paged_step)/DecoderLM/layer_3/attn/kv_write/scatter", ("kv_write", "-")),
    ("jit(_paged_step)/DecoderLM/head/lm_head/dot_general", ("head", "-")),
    ("jit(train_step)/jvp(DecoderLM)/layer_0/add", (None, "fwd")),  # a residual: no scope names a phase
    ("params['layer_0']['attn']['k_proj']['kernel']", (None, "-")),  # a parameter is no operation
])
def test_phase_of(op_name, expected):
    from dmlcloud_tpu.utils.profiling import phase_of

    assert phase_of(op_name) == expected


HLO = """HloModule jit_step

%fused_computation (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  %m = f32[8]{0} multiply(%p, %p), metadata={op_name="jit(step)/jvp(M)/attn_norm/mul"}
  ROOT %a = f32[8]{0} add(%m, %p), metadata={op_name="jit(step)/jvp(M)/mlp/add"}
}

%fused_computation.1 (p.1: f32[8]) -> (f32[8], f32[8]) {
  %p.1 = f32[8]{0} parameter(0)
  %n.1 = f32[8]{0} negate(%p.1), metadata={op_name="jit(step)/optimizer/neg"}
  %n.2 = f32[8]{0} negate(%n.1), metadata={op_name="jit(step)/optimizer/neg"}
  %n.3 = f32[8]{0} negate(%n.2), metadata={op_name="jit(step)/grad_clip/neg"}
  ROOT %t = (f32[8]{0}, f32[8]{0}) tuple(%n.2, %n.3)
}

ENTRY %main (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0), metadata={op_name="x"}
  %fusion = f32[8]{0} fusion(%x), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(step)/jvp(M)/mlp/add"}
  %fusion.1 = (f32[8]{0}, f32[8]{0}) fusion(%fusion), kind=kLoop, calls=%fused_computation.1
  %flash_fwd.3 = f32[8]{0} custom-call(%fusion), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp(M)/attn/attn_kernel/flash_fwd/pallas_call"}
  ROOT %copy = f32[8]{0} copy(%flash_fwd.3)
}
"""


def test_phase_map_takes_a_fusions_phase_from_its_root():
    from dmlcloud_tpu.utils.profiling import phase_map

    m = phase_map(HLO)
    assert m["fusion"] == ("mlp", "fwd")  # the root's phase, though the body also holds a norm
    assert m["fusion.1"] == ("optimizer", "-")  # a tuple root carries none: what most of the body carries
    assert m["flash_fwd.3"] == ("attn_kernel", "fwd")
    assert m["copy"] == (None, "-") and m["x"] == (None, "-")  # reported, with no phase


def test_phase_table_reads_a_recorded_tpu_trace_without_tensorflow():
    """The small profile recorded on a v5e (benchmark/tests): device time by
    phase through a hand-made map, the clock anchor, kernels none."""
    import os
    import sys

    from dmlcloud_tpu.utils.profiling import format_phase_table, phase_table

    fixture = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark", "tests", "fixture.xplane.pb")
    if not os.path.isfile(fixture):
        pytest.skip("benchmark/ not present next to the package")
    table = phase_table(fixture, phases={"convolution_tanh_fusion": ("mlp", "-")}, steps=4, program="bench_fixture_step")
    assert "tensorflow" not in sys.modules
    rows = {(r["phase"], r["direction"]): r for r in table["phases"]}
    assert rows[("mlp", "-")]["ms_per_step"] == pytest.approx(62.978e-3 / 4, rel=1e-6)  # the four fusions
    assert rows[("mlp", "-")]["n_per_step"] == 1 and rows[("unattributed", "-")]["n_per_step"] == 2  # two copies a run
    assert sum(r["time_frac"] for r in table["phases"]) == pytest.approx(1.0)
    assert table["busy_ms_per_step"] == pytest.approx(72.465e-3 / 4, rel=1e-3)
    assert table["profile_start_ns"] == 1790715310895364939 and table["kernels"] == []
    assert "mlp" in format_phase_table(table)
    # another program's name keeps nothing
    assert phase_table(fixture, program="train_step")["phases"] == []


def test_trace_emits_a_profile_span_on_an_armed_journal(tmp_path):
    from dmlcloud_tpu.telemetry import journal as journal_mod
    from dmlcloud_tpu.telemetry.journal import SpanJournal

    with trace(str(tmp_path / "quiet")):  # no journal: nothing to emit to, nothing raised
        pass
    j = journal_mod.activate(SpanJournal(tmp_path / "journal"))
    try:
        with trace(str(tmp_path / "prof")):
            jnp.ones(4).block_until_ready()
    finally:
        journal_mod.deactivate()
    [rec] = [r for r in j.tail(8) if r["kind"] == "profile"]
    assert rec["label"].endswith("prof") and 0 <= rec["started_after"] <= rec["dur"]
    j.close()
