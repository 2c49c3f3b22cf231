"""Profiling helpers: real jax.profiler traces land on disk, profile_steps
returns the computed result, StepTimer percentiles behave."""

import jax.numpy as jnp
import numpy as np
import pytest

from dmlcloud_tpu.utils.profiling import StepTimer, profile_steps, trace


@pytest.mark.slow
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


def test_roofline_requires_trace_dir(tmp_path):
    import pytest

    from dmlcloud_tpu.utils.profiling import roofline

    with pytest.raises(FileNotFoundError, match="xplane"):
        roofline(str(tmp_path))


def test_format_roofline_renders_without_peaks():
    from dmlcloud_tpu.utils.profiling import format_roofline

    peaks = {"device": "X", "peak_tflops": 0.0, "peak_hbm_gbps": 0.0}
    rows = [
        {"category": "fusion", "time_frac": 0.9, "ms_per_step": 1.0, "tflops": 2.0, "gbps": 10.0, "n_per_step": 3},
        {"category": "tiny", "time_frac": 0.0001, "ms_per_step": 0.0, "tflops": 0.0, "gbps": 0.0, "n_per_step": 1},
    ]
    out = format_roofline(peaks, rows)
    assert "fusion" in out and "tiny" not in out  # sub-0.1% rows hidden
    assert "% of peak" not in out  # no bogus percentage from a zero peak


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
    peaks = {"device": "X", "peak_tflops": 1.0, "peak_hbm_gbps": 2.0}
    rows = [
        {"category": "fusion", "time_frac": 1.0, "ms_per_step": 1.0,
         "tflops": 1.0, "gbps": 1.0, "n_per_step": 1},
    ]
    monkeypatch.setattr(mod, "roofline", lambda d, steps=30: (peaks, rows))
    assert mod.main(["/tmp/whatever", "--json", "--steps", "7"]) == 0
    out = json.loads(capsys.readouterr().out)
    # v2 is ADDITIVE over v1: the roofline keys are locked unchanged
    # (serve-journal inputs add a "serve" object instead — see
    # tests/test_observability.py)
    assert out["version"] == 2
    assert out["steps"] == 7
    assert out["peaks"] == peaks and out["rows"] == rows


def test_analyze_trace_empty_rows_is_a_clear_message(monkeypatch, capsys):
    mod = _load_analyze_trace()
    peaks = {"device": "X", "peak_tflops": 1.0, "peak_hbm_gbps": 2.0}
    monkeypatch.setattr(mod, "roofline", lambda d, steps=30: (peaks, []))
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
