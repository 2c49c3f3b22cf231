"""Serve observability plane (doc/observability.md): the metrics
registry's typed families and bounded cardinality, Prometheus exposition
round-trips through the strict parser, SLO burn-rate alerting off a fake
clock, the stdlib /metrics endpoint, the engine integration
(``metrics=True`` / ``slos=``), request-scoped trace linkage, the
flush-on-exit hardening, the observability CLI (``trace`` / ``top`` /
``timeline --by-request`` / the diag alert census), and analyze_trace's
serve mode of its JSON schema."""

import json
import os
import subprocess
import sys
import urllib.error
import urllib.request

import numpy as np
import pytest

from dmlcloud_tpu.serve import SLO, MetricsServer, ServeEngine, SLOMonitor
from dmlcloud_tpu.telemetry import journal as journal_mod
from dmlcloud_tpu.telemetry.journal import (
    SpanJournal,
    linked_trace_report,
    load_journals,
    to_request_trace,
)
from dmlcloud_tpu.telemetry.metrics_registry import (
    ITL_BUCKETS,
    OVERFLOW_LABEL,
    TTFT_BUCKETS,
    MetricsRegistry,
    parse_prometheus_text,
    to_prometheus_text,
)


def _engine(model, params, **kw):
    kw.setdefault("num_blocks", 64)
    kw.setdefault("block_size", 4)
    kw.setdefault("max_slots", 2)
    kw.setdefault("prefill_chunk", 8)
    return ServeEngine(model, params, **kw)


def _prompt(seed, n=12):
    return np.random.RandomState(seed).randint(0, 61, size=n).astype(np.int32)


def _flat_samples(fams):
    """parse_prometheus_text output flattened to {(name, labels): float}
    (the parser keeps sample values as raw strings)."""
    return {
        (n, tuple(sorted(l.items()))): float(v)
        for fam in fams.values() for n, l, v in fam["samples"]
    }


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------


class TestMetricsRegistry:
    def test_typed_families_and_snapshot_is_plain(self):
        reg = MetricsRegistry()
        c = reg.counter("req_total", "requests")
        c.inc()
        c.inc(2.5)
        with pytest.raises(ValueError):
            c.inc(-1)
        g = reg.gauge("depth", "queue depth")
        g.set(4)
        g.dec()
        h = reg.histogram("ttft_s", "ttft", buckets=TTFT_BUCKETS)
        h.observe(0.03)
        h.observe(100.0)  # lands in +Inf
        snap = reg.snapshot()
        json.dumps(snap)  # plain dicts, JSON-safe by contract
        assert snap["req_total"]["series"][0]["value"] == 3.5
        assert snap["depth"]["series"][0]["value"] == 3.0
        hs = snap["ttft_s"]["series"][0]
        assert hs["count"] == 2 and hs["buckets"][-1] == ["+Inf", 2]

    def test_reregister_same_family_kind_mismatch_raises(self):
        reg = MetricsRegistry()
        fam = reg.counter("x_total")
        assert reg.counter("x_total") is fam  # dedup, not a new family
        with pytest.raises(ValueError):
            reg.gauge("x_total")
        with pytest.raises(ValueError):
            reg.counter("x_total", labels=("status",))  # label-set mismatch

    def test_labels_exact_set_enforced(self):
        reg = MetricsRegistry()
        fam = reg.counter("t_total", labels=("status",))
        fam.labels(status="ok").inc()
        with pytest.raises(ValueError):
            fam.labels(tenant="x")
        with pytest.raises(ValueError):
            fam.labels(status="ok", tenant="x")

    def test_cardinality_overflow_collapses(self):
        reg = MetricsRegistry()
        fam = reg.counter("per_rid_total", labels=("rid",), max_series=2)
        fam.labels(rid="a").inc()
        fam.labels(rid="b").inc()
        for rid in ("c", "d", "e"):  # past the cap: ONE overflow series
            fam.labels(rid=rid).inc()
        assert fam.overflows == 3
        snap = reg.snapshot()["per_rid_total"]
        labels = [s["labels"]["rid"] for s in snap["series"]]
        assert labels.count(OVERFLOW_LABEL) == 1
        overflow = next(
            s for s in snap["series"] if s["labels"]["rid"] == OVERFLOW_LABEL
        )
        assert overflow["value"] == 3.0
        assert snap["overflows"] == 3

    def test_invalid_names_rejected(self):
        reg = MetricsRegistry()
        with pytest.raises(ValueError):
            reg.counter("bad name")
        with pytest.raises(ValueError):
            reg.counter("ok_total", labels=("bad-label",))
        with pytest.raises(ValueError):
            reg.counter("ok_total", labels=("__reserved",))
        with pytest.raises(ValueError):
            reg.histogram("h", buckets=(2.0, 1.0))  # unsorted buckets

    def test_prometheus_round_trip(self):
        reg = MetricsRegistry()
        reg.counter("dml_req_total", "requests", labels=("status",)).labels(
            status="ok"
        ).inc(7)
        reg.gauge("dml_depth", "depth").set(3)
        h = reg.histogram("dml_ttft_seconds", "ttft", buckets=ITL_BUCKETS)
        h.observe(0.002)
        h.observe(0.02)
        text = reg.snapshot()
        page = to_prometheus_text(text)
        fams = parse_prometheus_text(page)
        assert fams["dml_req_total"]["type"] == "counter"
        assert fams["dml_depth"]["type"] == "gauge"
        assert fams["dml_ttft_seconds"]["type"] == "histogram"
        samples = _flat_samples(fams)
        assert samples[("dml_req_total", (("status", "ok"),))] == 7.0
        hist = fams["dml_ttft_seconds"]["samples"]
        counts = {n for n, _, _ in hist}
        assert {"dml_ttft_seconds_bucket", "dml_ttft_seconds_sum",
                "dml_ttft_seconds_count"} <= counts
        inf = next(
            float(v) for n, l, v in hist
            if n == "dml_ttft_seconds_bucket" and l.get("le") == "+Inf"
        )
        total = next(
            float(v) for n, _, v in hist if n == "dml_ttft_seconds_count"
        )
        assert inf == total == 2.0

    def test_multi_snapshot_merge_tags_extra_labels(self):
        a, b = MetricsRegistry(), MetricsRegistry()
        a.counter("dml_req_total", "requests").inc(1)
        b.counter("dml_req_total", "requests").inc(2)
        page = to_prometheus_text(
            (a.snapshot(), {"replica": "r0"}), (b.snapshot(), {"replica": "r1"})
        )
        # one HELP/TYPE header for the merged family, two tagged series
        assert page.count("# TYPE dml_req_total") == 1
        fams = parse_prometheus_text(page)
        by_replica = {
            l["replica"]: float(v) for _, l, v in fams["dml_req_total"]["samples"]
        }
        assert by_replica == {"r0": 1.0, "r1": 2.0}
        # a kind collision across snapshots is a hard error
        g = MetricsRegistry()
        g.gauge("dml_req_total").set(1)
        with pytest.raises(ValueError):
            to_prometheus_text(a.snapshot(), g.snapshot())

    def test_save_never_raises_and_close_is_idempotent(self, tmp_path):
        path = tmp_path / "metrics.json"
        reg = MetricsRegistry(save_path=path)
        reg.counter("x_total").inc(5)
        assert reg.save() == str(path)
        assert json.loads(path.read_text())["x_total"]["series"][0]["value"] == 5.0
        reg.close()
        reg.close()  # idempotent
        # a doomed path is swallowed, not raised (metrics must not kill serving)
        assert MetricsRegistry().save(tmp_path / "no" / "such" / "dir" / "m.json") is None


# ---------------------------------------------------------------------------
# SLO monitor (fake clock — no sleeps anywhere)
# ---------------------------------------------------------------------------


def _slo_latency(**kw):
    kw.setdefault("ttft_p99_s", 0.1)
    kw.setdefault("good_fraction", 0.5)
    kw.setdefault("window_s", 10.0)
    kw.setdefault("fast_window_s", 1.0)
    kw.setdefault("burn_threshold", 1.5)
    return SLO("lat", **kw)


class TestSLOMonitor:
    def test_declaration_validation(self):
        with pytest.raises(ValueError):
            SLO("empty")  # no objective at all
        with pytest.raises(ValueError):
            SLO("bad", ttft_p99_s=-1)
        with pytest.raises(ValueError):
            SLO("bad", availability=1.5)
        with pytest.raises(ValueError):
            SLO("bad", ttft_p99_s=1.0, window_s=1.0, fast_window_s=2.0)
        with pytest.raises(ValueError):
            SLOMonitor([_slo_latency(), _slo_latency()])  # duplicate names

    def test_multi_window_burn_fires_once_then_rearms(self):
        mon = SLOMonitor([_slo_latency()], clock=lambda: 0.0)
        # sustained breach: every request misses the 100ms target across
        # both windows
        for i in range(20):
            mon.record_ttft(None, 1.0, now=i * 0.05)
        fired = mon.evaluate(now=1.0)
        assert [a["slo"] for a in fired] == ["lat"]
        assert fired[0]["part"] == "ttft"
        assert fired[0]["burn_fast"] >= 1.5 and fired[0]["burn_slow"] >= 1.5
        # still burning: the latch holds, no second page for the same breach
        mon.record_ttft(None, 1.0, now=1.2)
        assert mon.evaluate(now=1.3) == []
        # recovery: the fast window fills with good requests and re-arms
        for i in range(20):
            mon.record_ttft(None, 0.01, now=3.0 + i * 0.04)
        assert mon.evaluate(now=3.9) == []
        # a fresh sustained breach fires a SECOND alert
        for i in range(40):
            mon.record_ttft(None, 1.0, now=5.0 + i * 0.1)
        assert len(mon.evaluate(now=9.0)) == 1
        assert len(mon.alerts) == 2

    def test_one_slow_request_does_not_page(self):
        mon = SLOMonitor([_slo_latency()], clock=lambda: 0.0)
        # plenty of good traffic in the slow window, ONE bad request
        for i in range(50):
            mon.record_ttft(None, 0.01, now=i * 0.1)
        mon.record_ttft(None, 5.0, now=4.95)
        assert mon.evaluate(now=5.0) == []  # slow window is not burning

    def test_cancelled_spends_no_budget(self):
        slo = SLO("avail", availability=0.9, window_s=10.0, fast_window_s=1.0,
                  burn_threshold=1.0)
        mon = SLOMonitor([slo], clock=lambda: 0.0)
        for i in range(30):
            mon.record_terminal(None, "cancelled", now=i * 0.1)
        assert mon.evaluate(now=3.0) == []
        assert mon.status(now=3.0)["objectives"]["avail"]["availability"]["n"] == 0
        # errors DO spend it
        for i in range(10):
            mon.record_terminal(None, "error", now=4.0 + i * 0.05)
        assert len(mon.evaluate(now=4.5)) == 1

    def test_tenant_scoping(self):
        slo = SLO("gold", tenant="gold", ttft_p99_s=0.1, good_fraction=0.5,
                  window_s=10.0, fast_window_s=1.0, burn_threshold=1.0)
        mon = SLOMonitor([slo], clock=lambda: 0.0)
        for i in range(20):  # the breach is entirely another tenant's
            mon.record_ttft("bronze", 9.0, now=i * 0.05)
        assert mon.evaluate(now=1.0) == []
        for i in range(20):
            mon.record_ttft("gold", 9.0, now=2.0 + i * 0.05)
        assert len(mon.evaluate(now=3.0)) == 1

    def test_alert_journals_slo_alert_span(self, tmp_path):
        j = SpanJournal(tmp_path, rank=0)
        journal_mod.activate(j)
        try:
            mon = SLOMonitor([_slo_latency()], clock=lambda: 0.0)
            for i in range(20):
                mon.record_ttft(None, 1.0, now=i * 0.05)
            assert mon.evaluate(now=1.0)
        finally:
            journal_mod.deactivate()
        spans = [r for r in j.tail(64) if r["kind"] == "slo_alert"]
        assert len(spans) == 1
        assert spans[0]["slo"] == "lat" and spans[0]["part"] == "ttft"
        assert spans[0]["burn_fast"] >= 1.5

    def test_status_scorecard(self):
        mon = SLOMonitor([_slo_latency()], clock=lambda: 2.0)
        for i in range(10):
            mon.record_ttft(None, 0.02, now=1.0 + i * 0.01)
        st = mon.status()  # falls back to the injected clock
        ttft = st["objectives"]["lat"]["ttft"]
        assert ttft["n"] == 10 and ttft["target_p99_s"] == 0.1
        assert ttft["observed_p99_s"] == pytest.approx(0.02, abs=1e-6)
        assert st["alerts"] == 0


# ---------------------------------------------------------------------------
# /metrics HTTP endpoint
# ---------------------------------------------------------------------------


class TestMetricsServer:
    def test_scrape_404_and_500(self):
        reg = MetricsRegistry()
        reg.counter("dml_up_total").inc()
        with MetricsServer(lambda: to_prometheus_text(reg.snapshot())) as srv:
            with urllib.request.urlopen(srv.url, timeout=5) as resp:
                assert resp.status == 200
                assert resp.headers["Content-Type"].startswith("text/plain")
                body = resp.read().decode()
            assert parse_prometheus_text(body)["dml_up_total"]["type"] == "counter"
            with pytest.raises(urllib.error.HTTPError) as e404:
                urllib.request.urlopen(
                    f"http://127.0.0.1:{srv.port}/nope", timeout=5)
            assert e404.value.code == 404
        # a raising source answers 500 — it never kills the serving process
        def boom():
            raise RuntimeError("registry on fire")

        with MetricsServer(boom) as srv:
            with pytest.raises(urllib.error.HTTPError) as e500:
                urllib.request.urlopen(srv.url, timeout=5)
            assert e500.value.code == 500
            assert "registry on fire" in e500.value.read().decode()

    def test_port_requires_start(self):
        srv = MetricsServer(lambda: "")
        with pytest.raises(RuntimeError):
            srv.port


# ---------------------------------------------------------------------------
# engine integration: metrics=True / slos=, trace linkage
# ---------------------------------------------------------------------------


class TestEngineObservability:
    def test_metrics_slo_and_traces_plumbed(self, tiny_model, tmp_path):
        model, params = tiny_model
        j = SpanJournal(tmp_path / "telemetry", rank=0)
        journal_mod.activate(j)
        try:
            engine = _engine(
                model, params, metrics=True,
                slos=[SLO("loose", ttft_p99_s=1e9, availability=0.5)],
            )
            a = engine.submit(_prompt(0), max_new_tokens=6, tenant="gold")
            b = engine.submit(_prompt(1), max_new_tokens=4)
            engine.run()
        finally:
            journal_mod.deactivate()
        assert engine.status(a) == "ok" and engine.status(b) == "ok"

        # exposition parses as strict Prometheus text and carries the
        # schema-locked serve families with the right values
        fams = parse_prometheus_text(engine.metrics_text())
        flat = _flat_samples(fams)
        assert flat[("dml_serve_requests_total", ())] == 2.0
        assert flat[("dml_serve_terminal_total", (("status", "ok"),))] == 2.0
        assert flat[("dml_serve_tokens_total", ())] == 10.0
        assert flat[("dml_serve_ttft_seconds_count", ())] == 2.0
        assert flat[("dml_serve_itl_seconds_count", ())] > 0
        assert flat[("dml_serve_active_requests", ())] == 0.0
        for fam in ("dml_serve_kv_blocks_free", "dml_serve_queue_depth",
                    "dml_serve_decode_batch_size"):
            assert fam in fams

        # the ledger summary surfaces the SLO scorecard
        slo = engine.ledger.summary()["slo"]["objectives"]["loose"]
        assert slo["ttft"]["n"] == 2
        assert slo["availability"]["observed"] == 1.0

        # every span either carries this request's trace id or lists it:
        # one causal trace per request, zero orphans
        report = linked_trace_report(j.tail(10 ** 6))
        assert report["orphans"] == []
        assert {f"tr-{a}", f"tr-{b}"} <= set(report["traces"])
        kinds_a = {r["kind"] for r in report["traces"][f"tr-{a}"]}
        assert {"queue_wait", "admission", "prefill", "decode_batch"} <= kinds_a
        assert report["statuses"][f"tr-{a}"] is None  # no fault touched it
        adm = next(
            r for r in report["traces"][f"tr-{a}"] if r["kind"] == "admission"
        )
        assert adm["tenant"] == "gold"

    def test_fault_stamps_trace_with_terminal_status(self, tiny_model, tmp_path):
        model, params = tiny_model
        j = SpanJournal(tmp_path / "telemetry", rank=0)
        journal_mod.activate(j)
        try:
            engine = _engine(model, params, metrics=True)
            boom = {"armed": True}

            def injector(point, seqs):
                if point == "decode" and boom["armed"]:
                    boom["armed"] = False
                    raise RuntimeError("injected decode fault")

            engine.fault_injector = injector
            rid = engine.submit(_prompt(2), max_new_tokens=6)
            engine.run()
        finally:
            journal_mod.deactivate()
        assert engine.status(rid) == "error"
        report = linked_trace_report(j.tail(10 ** 6))
        assert report["orphans"] == []
        assert report["statuses"][f"tr-{rid}"] == "error"
        flat = _flat_samples(parse_prometheus_text(engine.metrics_text()))
        assert flat[("dml_serve_terminal_total", (("status", "error"),))] == 1.0

    def test_drain_verdict_counts_slo_alerts(self, tiny_model, tmp_path):
        from dmlcloud_tpu.checkpoint import read_requeue_verdict

        model, params = tiny_model
        engine = _engine(
            model, params, run_dir=str(tmp_path),
            slos=[SLO("loose", ttft_p99_s=1e9)],
        )
        engine.submit(_prompt(3), max_new_tokens=4)
        engine.run()
        engine.drain(reason="test")
        verdict = read_requeue_verdict(str(tmp_path))
        assert verdict["serve"]["slo_alerts"] == 0


# ---------------------------------------------------------------------------
# flush-on-exit hardening (subprocess — the process exits WITHOUT close())
# ---------------------------------------------------------------------------


_EXIT_CHILD = """
import sys
sys.argv = ["flush_child"]
from dmlcloud_tpu.telemetry import journal as journal_mod
from dmlcloud_tpu.telemetry.journal import SpanJournal
from dmlcloud_tpu.telemetry.metrics_registry import MetricsRegistry

run_dir = {run_dir!r}
j = SpanJournal(run_dir, rank=0, flush_interval=3600.0).start()
journal_mod.activate(j)
t = journal_mod.now()
journal_mod.emit("queue_wait", t, t + 0.001, request=0, trace="tr-0")
reg = MetricsRegistry(save_path=run_dir + "/metrics.json")
reg.counter("dml_exit_total").inc(3)
# no close(), no deactivate(): atexit hooks must flush both
"""


class TestFlushOnExit:
    def test_journal_and_registry_survive_unclean_exit(self, tmp_path):
        env = dict(os.environ)
        env.setdefault("JAX_PLATFORMS", "cpu")
        proc = subprocess.run(
            [sys.executable, "-c", _EXIT_CHILD.format(run_dir=str(tmp_path))],
            env=env, capture_output=True, text=True, timeout=120,
        )
        assert proc.returncode == 0, proc.stderr
        records = load_journals(tmp_path)
        # the package's import, journalled by the first journal armed, survives too
        assert [r["kind"] for r in records] == ["setup", "queue_wait"]
        assert records[1]["trace"] == "tr-0"
        snap = json.loads((tmp_path / "metrics.json").read_text())
        assert snap["dml_exit_total"]["series"][0]["value"] == 3.0


# ---------------------------------------------------------------------------
# CLI: trace / top / timeline --by-request / diag alert census
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def obs_run(tiny_model, tmp_path_factory):
    """One observability-armed serve run shared by the CLI tests: journal
    + a saved registry snapshot under <run>/telemetry/, two requests, one
    hand-appended slo_alert record for the diag census."""
    model, params = tiny_model
    run_dir = tmp_path_factory.mktemp("obs_run")
    tdir = run_dir / "telemetry"
    j = SpanJournal(tdir, rank=0)
    journal_mod.activate(j)
    try:
        engine = _engine(model, params, metrics=True)
        engine.submit(_prompt(0), max_new_tokens=6, tenant="gold")
        engine.submit(_prompt(1), max_new_tokens=4)
        engine.run()
        snap = engine.metrics_snapshot()
    finally:
        journal_mod.deactivate()
        j.close()
    (tdir / "metrics.json").write_text(json.dumps(snap))
    alert = {
        "v": 1, "kind": "slo_alert", "label": "lat", "ts": journal_mod.now(),
        "dur": 1.0, "rank": 0, "tid": "main", "slo": "lat", "part": "ttft",
        "tenant": "", "burn_fast": 3.2, "burn_slow": 2.1,
    }
    with open(tdir / "journal-rank0.jsonl", "a", encoding="utf-8") as f:
        f.write(json.dumps(alert) + "\n")
    return str(run_dir)


class TestObservabilityCLI:
    def test_trace_cli_json(self, obs_run, capsys):
        from dmlcloud_tpu.__main__ import main

        assert main(["trace", obs_run, "--rid", "0", "--json"]) == 0
        out = json.loads(capsys.readouterr().out)
        assert out["trace"] == "tr-0"
        assert out["status"] is None  # no fault stamped this trace
        b = out["ttft_breakdown"]
        assert b["ttft_s"] is not None and b["ttft_s"] > 0
        assert b["queue_s"] >= 0 and b["prefill_s"] > 0
        assert {s["kind"] for s in out["spans"]} >= {"admission", "prefill"}

    def test_trace_cli_table_and_unknown_rid(self, obs_run, capsys):
        from dmlcloud_tpu.__main__ import main

        assert main(["trace", obs_run, "--rid", "0"]) == 0
        out = capsys.readouterr().out
        assert "TTFT" in out and "prefill" in out
        assert main(["trace", obs_run, "--rid", "99"]) == 1
        assert "tr-99" in capsys.readouterr().err

    def test_timeline_by_request(self, obs_run, tmp_path, capsys):
        from dmlcloud_tpu.__main__ import main

        out_path = tmp_path / "trace.json"
        assert main(["timeline", obs_run, "--by-request", "-o", str(out_path)]) == 0
        trace = json.loads(out_path.read_text())
        names = {e.get("name") for e in trace["traceEvents"]}
        # one thread-name metadata event per request track
        assert any(n == "thread_name" for n in names)
        records = load_journals(obs_run)
        tracks = to_request_trace(records)
        assert tracks["traceEvents"]  # importable helper agrees with the CLI

    def test_top_once_renders_a_frame(self, obs_run, capsys):
        from dmlcloud_tpu.__main__ import main

        assert main(["top", obs_run, "--once"]) == 0
        out = capsys.readouterr().out
        assert "requests" in out and "kv pool" in out

    def test_top_url_scrapes_prometheus(self, obs_run, capsys):
        from dmlcloud_tpu.__main__ import main

        snap = json.loads(
            open(os.path.join(obs_run, "telemetry", "metrics.json")).read()
        )
        with MetricsServer(lambda: to_prometheus_text(snap)) as srv:
            assert main(["top", "--url", srv.url, "--once"]) == 0
        assert "requests" in capsys.readouterr().out

    def test_diag_run_counts_slo_alerts(self, obs_run, capsys):
        from dmlcloud_tpu.__main__ import main

        assert main(["diag", "--json", "--run", obs_run]) == 0
        out = json.loads(capsys.readouterr().out)
        census = out["telemetry"]["slo_alerts"]
        assert census["count"] == 1
        assert census["by_objective"] == {"lat/ttft": 1}
        assert census["max_burn_fast"] == pytest.approx(3.2)


# ---------------------------------------------------------------------------
# analyze_trace: serve mode of the JSON schema
# ---------------------------------------------------------------------------


def _load_analyze_trace():
    import importlib.util
    from pathlib import Path

    path = Path(__file__).resolve().parent.parent / "scripts" / "analyze_trace.py"
    if not path.is_file():
        pytest.skip("scripts/ not present next to the package")
    spec = importlib.util.spec_from_file_location("_analyze_trace_obs_test", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _synthetic_serve_journal(tmp_path):
    def rec(kind, ts, dur, **attrs):
        return {"v": 1, "kind": kind, "label": None, "ts": ts, "dur": dur,
                "rank": 0, "tid": "main", **attrs}

    records = [
        rec("queue_wait", 0.00, 0.01, request=0, trace="tr-0"),
        rec("admission", 0.01, 0.01, request=0, trace="tr-0", tenant="hot"),
        rec("prefill", 0.02, 0.03, request=0, trace="tr-0"),
        rec("decode_batch", 0.05, 0.01, traces=["tr-0"]),
        rec("decode_batch", 0.07, 0.01, traces=["tr-0", "tr-1"]),
        rec("queue_wait", 0.03, 0.01, request=1, trace="tr-1"),
        rec("admission", 0.04, 0.01, request=1, trace="tr-1", tenant="cold"),
        rec("prefill", 0.05, 0.02, request=1, trace="tr-1"),
        rec("fault", 0.09, 0.0, request=1, trace="tr-1", status="error"),
    ]
    with open(tmp_path / "journal-rank0.jsonl", "w", encoding="utf-8") as f:
        for r in records:
            f.write(json.dumps(r) + "\n")


class TestAnalyzeTraceServe:
    def test_serve_mode_json_schema(self, tmp_path, capsys):
        mod = _load_analyze_trace()
        _synthetic_serve_journal(tmp_path)
        assert mod.main([str(tmp_path), "--json"]) == 0
        out = json.loads(capsys.readouterr().out)
        assert out["version"] == 3  # the profiler-trace object changed shape (PR 25); "serve" is v2's
        s = out["serve"]
        assert s["requests"] == 2 and s["orphan_spans"] == 0
        assert s["statuses"] == {"ok": 1, "error": 1}
        assert s["ttft_ms"]["n"] == 2
        assert s["ttft_ms"]["p50"] == pytest.approx(50.0, abs=5.0)
        assert set(s["tenants"]) == {"hot", "cold"}

    def test_tenant_filter(self, tmp_path, capsys):
        mod = _load_analyze_trace()
        _synthetic_serve_journal(tmp_path)
        assert mod.main([str(tmp_path), "--json", "--tenant", "hot"]) == 0
        out = json.loads(capsys.readouterr().out)
        assert out["serve"]["requests"] == 1
        assert set(out["serve"]["tenants"]) == {"hot"}

    def test_table_output_and_tenant_without_journals(self, tmp_path, capsys):
        mod = _load_analyze_trace()
        _synthetic_serve_journal(tmp_path)
        assert mod.main([str(tmp_path)]) == 0
        out = capsys.readouterr().out
        assert "ttft_ms" in out and "2 requests" in out
        # --tenant is meaningless on a profiler-trace (xplane) directory
        empty = tmp_path / "empty"
        empty.mkdir()
        assert mod.main([str(empty), "--tenant", "hot"]) == 2
