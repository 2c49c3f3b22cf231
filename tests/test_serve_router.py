"""Multi-replica serving front door (dmlcloud_tpu/serve/router.py).

The load-bearing contracts, each tested here:

- routing: N in-process engine replicas behind one submit/step surface;
  placement spreads by least-outstanding load, per-tenant DRR preserves
  FIFO within a tenant, prefix affinity (stable content addresses) sends
  a warm template back to the replica that served it last;
- health: the failure detector runs off ONE injectable ``clock=`` — a
  missed heartbeat fails the replica over with a fake clock, no sleeps;
- failover, at-most-once: live requests on a dead/raising replica are
  re-placed from scratch with bounded retries + exponential backoff and
  end terminal ``error`` when the budget is spent; a retry that lands on
  an engine that secretly admitted the original re-attaches through
  ``DuplicateRequest`` instead of double-admitting; router-wide, every
  request ends in exactly one ``TERMINAL_STATUSES`` state;
- circuit breaker: K consecutive failures trip it open (placements shed
  to siblings), cooldown -> half-open risks ONE probe, only an ``ok``
  probe closes it, a failed probe doubles the cooldown;
- drain: queued requests migrate off (fresh token — the old one stays
  burned), running ones finish in place, the emptied replica is removed
  and a PR-7 ``requeue.json`` verdict records the drain;
- chaos: random replica kills/stalls/drains at every phase under a TIGHT
  pool — per step every replica still audits free+unique-live==capacity,
  no request is ever live on two engines at once, and greedy survivors
  stay token-identical to a fault-free reference engine;
- determinism across interpreters: prefix-cache content addresses and a
  seeded chaos drill's event log are byte-identical under different
  ``PYTHONHASHSEED`` (subprocess test — the hints replicas would exchange
  and the replay log must not depend on per-process hash salt);
- the ledger's per-tenant TTFT percentiles survive record eviction, and
  ``ServeEngine.submit(token=)`` enforces caller idempotency.

The stub-engine tests exercise the router's control plane (pure host
logic) without compiling anything; the integration tests reuse the
tiny-model idiom of tests/test_serve.py.
"""

import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from dmlcloud_tpu.checkpoint import read_requeue_verdict
from dmlcloud_tpu.serve import (
    ChaosMonkey,
    DuplicateRequest,
    Router,
    ServeEngine,
    ServeLedger,
    TERMINAL_STATUSES,
)
from dmlcloud_tpu.serve.prefix_cache import content_key, prefix_keys, root_key
from dmlcloud_tpu.telemetry import journal as journal_mod
from dmlcloud_tpu.telemetry.journal import SpanJournal
from dmlcloud_tpu.telemetry.metrics_registry import parse_prometheus_text


# ---------------------------------------------------------------------------
# a fake clock and a pure-host stub engine (the router only sees the
# engine SURFACE: submit/step/status/cancel/output/idle + pool geometry)
# ---------------------------------------------------------------------------


class _Clock:
    def __init__(self, t=0.0):
        self.t = float(t)

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += float(dt)


class _StubPool:
    def __init__(self, block_size=4, num_blocks=64):
        self.block_size = block_size
        self.num_blocks = num_blocks

    def blocks_for(self, tokens):
        return max(1, -(-int(tokens) // self.block_size))

    def assert_consistent(self):
        pass


class _StubEngine:
    """In-memory stand-in honouring the engine surface the Router uses.
    ``steps_to_finish`` is the service time in steps, ``max_slots`` caps
    concurrently-running requests (the rest report ``queued``), and
    ``fail_next`` makes the next N ``step()`` calls raise."""

    def __init__(self, *, clock=None, steps_to_finish=2, max_slots=4,
                 block_size=4, num_blocks=64, prefill_chunk=8):
        self.pool = _StubPool(block_size, num_blocks)
        self.draft_pool = None
        self.scheduler = types.SimpleNamespace(prefill_chunk=prefill_chunk)
        self.ledger = ServeLedger()
        self.clock = clock if clock is not None else _Clock()
        self.steps_to_finish = steps_to_finish
        self.max_slots = max_slots
        self.fail_next = 0
        self._all = {}
        self._tokens = {}
        self._next = 0
        self.submits = []  # (rid, token, tenant) admission audit trail

    def submit(self, prompt, max_new_tokens=32, *, token=None, tenant=None, **kw):
        if token is not None and token in self._tokens:
            raise DuplicateRequest(token, self._tokens[token])
        rid = self._next
        self._next += 1
        self._all[rid] = {
            "status": None, "left": self.steps_to_finish, "token": token,
            "prompt": np.asarray(prompt, np.int32), "max_new": int(max_new_tokens),
        }
        if token is not None:
            self._tokens[token] = rid
        self.ledger.arrived(rid, self.clock(), tenant=tenant)
        self.submits.append((rid, token, tenant))
        return rid

    def _running(self):
        live = [r for r, s in self._all.items() if s["status"] is None]
        return live[: self.max_slots]

    def step(self):
        if self.fail_next > 0:
            self.fail_next -= 1
            raise RuntimeError("injected step failure")
        running = self._running()
        now = self.clock()
        for rid in running:
            s = self._all[rid]
            if "first" not in s:
                s["first"] = True
                self.ledger.first_token(rid, now)
            s["left"] -= 1
            if s["left"] <= 0:
                s["status"] = "ok"
                self.ledger.finished(rid, now, "ok")
        return bool(running)

    def status(self, rid):
        if rid not in self._all:
            raise KeyError(rid)
        s = self._all[rid]
        if s["status"] is not None:
            return s["status"]
        return "running" if rid in self._running() else "queued"

    def statuses(self):
        return {rid: self.status(rid) for rid in self._all}

    def cancel(self, rid):
        s = self._all.get(rid)
        if s is None or s["status"] is not None:
            return False
        s["status"] = "cancelled"
        self.ledger.finished(rid, self.clock(), "cancelled")
        return True

    def output(self, rid):
        s = self._all[rid]
        if s["status"] != "ok":
            raise KeyError(rid)
        return np.concatenate([s["prompt"], np.arange(s["max_new"], dtype=np.int32)])

    @property
    def idle(self):
        return all(s["status"] is not None for s in self._all.values())

    def leaked_blocks(self):
        return 0


def _stub_router(n=2, clock=None, engine_kw=None, **router_kw):
    clock = clock if clock is not None else _Clock()
    engines = [_StubEngine(clock=clock, **(engine_kw or {})) for _ in range(n)]
    router_kw.setdefault("drr_quantum", 100)  # placement on first visit
    router_kw.setdefault("backoff_base_s", 0.0)
    return Router(engines, clock=clock, **router_kw), clock


# ---------------------------------------------------------------------------
# routing basics (stub engines — control plane only)
# ---------------------------------------------------------------------------


class TestRouterBasics:
    def test_routes_all_terminal_ok(self):
        router, _ = _stub_router(n=3)
        rids = [
            router.submit(list(range(4)), 4, tenant="a" if i % 2 else "b")
            for i in range(6)
        ]
        outs = router.run(max_steps=50)
        assert router.idle
        assert set(router.statuses().values()) == {"ok"}
        assert router.summary()["statuses"] == {"ok": 6}
        assert router.leaked_blocks() == 0
        for rid in rids:
            assert np.array_equal(outs[rid], router.output(rid))

    def test_least_outstanding_spreads_load(self):
        router, _ = _stub_router(n=2, engine_kw={"steps_to_finish": 10})
        # distinct prompts: identical ones would share an affinity key and
        # deliberately co-locate
        a = router.submit(list(range(4)), 4)
        b = router.submit(list(range(10, 14)), 4)
        router.step()
        assert router._records[a].replica == "r0"
        assert router._records[b].replica == "r1"

    def test_status_lifecycle_and_queued_cancel(self):
        # a tiny quantum: the head needs more credit than one visit grants,
        # so the request stays router-queued across the first steps
        router, _ = _stub_router(n=1, drr_quantum=1)
        rid = router.submit(list(range(16)), 16)
        assert router.status(rid) == "queued"
        assert router.cancel(rid)
        assert router.status(rid) == "cancelled"
        assert not router.cancel(rid)  # already terminal: idempotent no
        assert router.idle
        router.step()  # the cancelled record never places
        assert router._records[rid].replica is None

    def test_unknown_rid_raises(self):
        router, _ = _stub_router(n=1)
        with pytest.raises(KeyError):
            router.status(99)

    def test_per_tenant_fifo_survives_interleaving(self):
        # one slow replica, interleaved tenants, a quantum small enough
        # that placement takes several DRR visits — per-tenant first
        # placements must still come out in arrival order
        router, _ = _stub_router(
            n=2, drr_quantum=2, engine_kw={"steps_to_finish": 1, "max_slots": 1}
        )
        placements = []
        orig = router._place

        def spy(rec, rep, now):
            placements.append((rec.tenant, rec.rid, rec.retries))
            return orig(rec, rep, now)

        router._place = spy
        rids = []
        for i in range(8):
            tenant = "hot" if i % 2 == 0 else "cold"
            rids.append(router.submit(list(range(8)), 8, tenant=tenant))
        router.run(max_steps=200)
        assert router.idle and set(router.statuses().values()) == {"ok"}
        for tenant in ("hot", "cold"):
            first = [rid for (t, rid, retries) in placements
                     if t == tenant and retries == 0]
            assert first == sorted(first), f"tenant {tenant} placed out of order"


# ---------------------------------------------------------------------------
# health detection + failover (fake clock — no sleeps)
# ---------------------------------------------------------------------------


class TestFailover:
    def test_missed_heartbeat_fails_over(self):
        router, clock = _stub_router(
            n=2, heartbeat_timeout_s=1.0, engine_kw={"steps_to_finish": 5}
        )
        rid = router.submit(list(range(4)), 4)
        router.step()
        rec = router._records[rid]
        assert rec.replica == "r0"
        # r0 wedges: it misses steps while the clock runs past the deadline
        router.stall_replica("r0", 10)
        clock.advance(2.0)
        assert router.healthy()["r0"] is False
        router.step()  # r1 beats (it stepped), r0 misses its deadline
        assert router.failovers == 1
        assert rec.replica == "r1" and rec.retries == 1
        assert rec.token.endswith(".f1")  # definitively cancelled: fresh token
        router.run(max_steps=50)
        assert router.status(rid) == "ok"

    def test_step_raise_retries_exhausted_to_error(self):
        router, _ = _stub_router(
            n=2, max_retries=1, breaker_threshold=100,
            engine_kw={"steps_to_finish": 5},
        )
        for rep in router.replicas.values():
            rep.engine.fail_next = 100  # every step raises, everywhere
        rid = router.submit(list(range(4)), 4)
        for _ in range(10):
            router.step()
            if router.idle:
                break
        assert router.status(rid) == "error"
        assert router.idle
        assert router._records[rid].retries == router.max_retries + 1
        with pytest.raises(KeyError):
            router.output(rid)

    def test_kill_reaps_engine_and_keeps_token(self):
        router, _ = _stub_router(n=2, engine_kw={"steps_to_finish": 6})
        a = router.submit(list(range(4)), 4)
        b = router.submit(list(range(4)), 4)
        router.step()
        rec = router._records[a]
        assert rec.replica == "r0"
        token_before = rec.token
        router.kill_replica("r0", "drill")
        r0 = router.replicas["r0"]
        assert not r0.alive and router.kills == 1
        # the reap: nothing left live on the dead engine, audit still clean
        assert all(st in TERMINAL_STATUSES for st in r0.engine.statuses().values())
        # fatal failover keeps the token: if the "dead" replica ever saw
        # the retry, dedup would re-attach (at-most-once) — so no rotation
        assert rec.token == token_before and rec.retries == 1
        router.run(max_steps=60)
        assert router.status(a) == "ok" and router.status(b) == "ok"
        assert router._records[a].replica == "r1"
        assert router.leaked_blocks() == 0

    def test_duplicate_request_reattaches(self):
        router, clock = _stub_router(n=1)
        rid = router.submit(list(range(4)), 4)
        router.step()
        rec = router._records[rid]
        rep = router.replicas[rec.replica]
        erid = rec.engine_rid
        admissions = len(rep.engine.submits)
        # the ambiguous-failure window: the router re-places a request the
        # engine ALREADY admitted under the same token — the engine raises
        # DuplicateRequest and the router re-attaches, never double-admits
        router._place(rec, rep, clock())
        assert rec.engine_rid == erid
        assert len(rep.engine.submits) == admissions


# ---------------------------------------------------------------------------
# circuit breaker
# ---------------------------------------------------------------------------


class TestCircuitBreaker:
    def _router(self):
        return _stub_router(
            n=2, breaker_threshold=2, breaker_cooldown_s=1.0,
            heartbeat_timeout_s=1e9, max_retries=10,
            engine_kw={"steps_to_finish": 10},
        )

    def test_trip_half_open_probe_close(self):
        router, clock = self._router()
        r0 = router.replicas["r0"]
        r0.engine.fail_next = 2
        router.step()
        assert r0.consec_failures == 1 and r0.breaker == "closed"
        router.step()
        assert r0.breaker == "open"
        # open: placements shed to the sibling (distinct prompts — same
        # ones would share affinity keys and skew the choice)
        a = router.submit(list(range(4)), 4)
        b = router.submit(list(range(10, 14)), 4)
        router.step()
        assert router._records[a].replica == "r1"
        assert router._records[b].replica == "r1"
        # cooldown over: half-open risks exactly ONE probe
        clock.advance(1.5)
        c = router.submit(list(range(20, 24)), 4)
        d = router.submit(list(range(30, 34)), 4)
        router.step()
        assert r0.breaker == "half_open"
        assert router._records[c].replica == "r0" and r0.probe_rid == c
        assert router._records[d].replica == "r1"
        # the probe terminates ok -> the breaker closes
        router.run(max_steps=60)
        assert router.status(c) == "ok"
        assert r0.breaker == "closed" and r0.consec_failures == 0
        assert r0.probe_rid is None

    def test_failed_probe_reopens_with_doubled_cooldown(self):
        router, clock = self._router()
        r0 = router.replicas["r0"]
        r0.engine.fail_next = 2
        router.step()
        router.step()
        assert r0.breaker == "open"
        cooldown = r0.cooldown
        clock.advance(1.5)
        c = router.submit(list(range(20, 24)), 4)
        router.step()
        assert r0.breaker == "half_open" and r0.probe_rid == c
        r0.engine.fail_next = 1  # the probe's very next step fails
        router.step()
        assert r0.breaker == "open"
        assert r0.cooldown == cooldown * 2.0  # back off harder
        assert r0.probe_rid is None
        # the probe request itself failed over to the sibling
        assert router._records[c].replica == "r1"
        router.run(max_steps=60)
        assert router.status(c) == "ok"


# ---------------------------------------------------------------------------
# drain + affinity
# ---------------------------------------------------------------------------


class TestDrainAndAffinity:
    def test_drain_migrates_queued_finishes_running_writes_verdict(self, tmp_path):
        router, _ = _stub_router(
            n=2, run_dir=tmp_path,
            engine_kw={"steps_to_finish": 4, "max_slots": 1},
        )
        a = router.submit(list(range(4)), 4)
        b = router.submit(list(range(10, 14)), 4)
        c = router.submit(list(range(20, 24)), 4)
        router.step()
        # a->r0, b->r1 (least outstanding), c->r0 (tie break) but r0 has
        # one slot: c sits engine-queued — exactly what a drain migrates
        rec_c = router._records[c]
        assert rec_c.replica == "r0"
        assert router.status(c) == "queued"
        token_c = rec_c.token
        router.drain_replica("r0")
        r0 = router.replicas["r0"]
        assert r0.draining and r0.migrated == 1
        assert rec_c.replica is None
        assert rec_c.token == f"{token_c}.m"  # fresh token, old one burned
        assert rec_c.retries == 0  # a migration is not a failure retry
        router.run(max_steps=100)
        assert set(router.statuses().values()) == {"ok"}
        assert router._records[c].replica == "r1"
        assert r0.removed and not r0.alive
        assert router.failovers == 0
        verdict = read_requeue_verdict(tmp_path)
        assert verdict is not None and verdict["requeue"] is False
        assert verdict["kind"] == "completed"
        assert verdict["serve"]["replica"] == "r0"
        assert verdict["serve"]["migrated"] == 1
        assert verdict["serve"]["drained_clean"] is True

    def test_prefix_affinity_beats_load_tiebreak(self):
        router, _ = _stub_router(n=2, engine_kw={"steps_to_finish": 1})
        warm = list(range(8))  # two full blocks: a real affinity key
        a = router.submit(warm, 4)
        router.run(max_steps=20)
        assert router._records[a].replica == "r0"
        # load up r0 so least-outstanding would now prefer r1...
        for rep in router.replicas.values():
            rep.engine.steps_to_finish = 50
        router.submit(list(range(100, 104)), 4)
        b = router.submit(warm, 4)
        router.step()
        # ...but the warm template still routes to the replica that
        # served it last
        assert router._records[b].replica == "r0"

    def test_affinity_falls_back_when_warm_replica_unplaceable(self):
        router, _ = _stub_router(n=2, engine_kw={"steps_to_finish": 1})
        warm = list(range(8))
        a = router.submit(warm, 4)
        router.run(max_steps=20)
        assert router._records[a].replica == "r0"
        router.kill_replica("r0", "gone")
        b = router.submit(warm, 4)
        router.run(max_steps=20)
        assert router.status(b) == "ok"
        assert router._records[b].replica == "r1"


# ---------------------------------------------------------------------------
# telemetry: the router's span kinds
# ---------------------------------------------------------------------------


class TestRouterTelemetry:
    def test_route_failover_drain_spans(self, tmp_path):
        j = SpanJournal(tmp_path / "telemetry", rank=0, ring_size=64)
        journal_mod.activate(j)
        try:
            router, _ = _stub_router(n=2, engine_kw={"steps_to_finish": 3})
            router.submit(list(range(4)), 4)
            router.submit(list(range(4)), 4)
            router.step()
            router.kill_replica("r0", "drill")
            router.run(max_steps=50)
            router.drain_replica("r1")
            router.step()
            assert router.replicas["r1"].removed
        finally:
            journal_mod.deactivate()
        kinds = {r["kind"] for r in j.tail(64)}
        assert {"route", "failover", "replica_drain"} <= kinds


# ---------------------------------------------------------------------------
# request-scoped tracing across failure (PR 19)
# ---------------------------------------------------------------------------


class TestTracePropagation:
    def test_failover_rotates_token_but_keeps_trace(self, tmp_path):
        """A heartbeat failover definitively cancels and resubmits under a
        FRESH token (at-most-once), but the trace id never rotates — the
        retry's spans land in the SAME causal trace as the original
        placement."""
        from dmlcloud_tpu.telemetry.journal import linked_trace_report

        j = SpanJournal(tmp_path / "telemetry", rank=0, ring_size=256)
        journal_mod.activate(j)
        try:
            router, clock = _stub_router(
                n=2, heartbeat_timeout_s=1.0, engine_kw={"steps_to_finish": 5}
            )
            rid = router.submit(list(range(4)), 4)
            router.step()
            rec = router._records[rid]
            trace_before = rec.trace
            router.stall_replica(rec.replica, 10)
            clock.advance(2.0)
            router.step()
            assert rec.retries == 1 and rec.token.endswith(".f1")
            assert rec.trace == trace_before == f"tr-{rid}"
            router.run(max_steps=50)
            assert router.status(rid) == "ok"
        finally:
            journal_mod.deactivate()
        report = linked_trace_report(j.tail(256))
        assert report["orphans"] == []
        spans = report["traces"][f"tr-{rid}"]
        kinds = [r["kind"] for r in spans]
        # original placement, the failover, and the re-placement all link
        assert kinds.count("route") == 2 and kinds.count("failover") == 1

    def test_kill_one_drain_one_drill_has_zero_orphans(self, tmp_path):
        """The router drill's journal walk: kill a replica mid-flight,
        drain another — every request-scoped span still carries its trace
        id (zero orphans) and every submitted request resolves to exactly
        one trace."""
        from dmlcloud_tpu.telemetry.journal import linked_trace_report

        j = SpanJournal(tmp_path / "telemetry", rank=0, ring_size=512)
        journal_mod.activate(j)
        try:
            router, _ = _stub_router(n=3, engine_kw={"steps_to_finish": 4})
            rids = [router.submit(list(range(i, i + 4)), 4) for i in range(6)]
            router.step()
            router.kill_replica("r0", "drill")
            router.run(max_steps=30)
            router.drain_replica("r1", "drill")
            router.run(max_steps=60)
            assert all(router.status(r) in TERMINAL_STATUSES for r in rids)
        finally:
            journal_mod.deactivate()
        report = linked_trace_report(j.tail(512))
        assert report["orphans"] == []
        assert set(report["traces"]) == {f"tr-{r}" for r in rids}
        for spans in report["traces"].values():
            assert spans  # no empty trace

    def test_exhausted_retries_stamp_the_trace_status(self, tmp_path):
        """A request that burns its whole retry budget ends ``error`` AND
        its trace says so: the terminal fault span carries the trace id,
        so ``linked_trace_report`` surfaces the status per trace."""
        from dmlcloud_tpu.telemetry.journal import linked_trace_report

        j = SpanJournal(tmp_path / "telemetry", rank=0, ring_size=256)
        journal_mod.activate(j)
        try:
            router, _ = _stub_router(
                n=2, max_retries=1, breaker_threshold=100,
                engine_kw={"steps_to_finish": 5},
            )
            for rep in router.replicas.values():
                rep.engine.fail_next = 100
            rid = router.submit(list(range(4)), 4)
            for _ in range(10):
                router.step()
                if router.idle:
                    break
            assert router.status(rid) == "error"
        finally:
            journal_mod.deactivate()
        report = linked_trace_report(j.tail(256))
        assert report["orphans"] == []
        assert report["statuses"][f"tr-{rid}"] == "error"


# ---------------------------------------------------------------------------
# ledger: per-tenant percentiles survive eviction (satellite)
# ---------------------------------------------------------------------------


class TestLedgerTenantPercentiles:
    def test_percentiles_survive_record_eviction(self):
        led = ServeLedger(max_records=4)
        for i in range(20):
            tenant = "hot" if i % 2 == 0 else "cold"
            led.arrived(i, float(i), tenant=tenant)
            led.first_token(i, float(i) + (0.1 if tenant == "hot" else 0.5))
            led.finished(i, float(i) + 1.0, "ok")
        assert len(led.records) <= 4  # eviction really happened
        tt = led.summary()["tenant_ttft"]
        assert set(tt) == {"hot", "cold"}
        assert tt["hot"]["n"] == 10 and tt["cold"]["n"] == 10
        assert tt["hot"]["p50_s"] == pytest.approx(0.1)
        assert tt["cold"]["p50_s"] == pytest.approx(0.5)
        assert tt["cold"]["p99_s"] == pytest.approx(0.5)
        # the per-record accessor honestly reads only what is retained
        assert len(led.ttfts("hot")) <= 4

    def test_unknown_tenant_absent(self):
        led = ServeLedger()
        led.arrived(0, 0.0)  # no tenant
        led.first_token(0, 0.5)
        led.finished(0, 1.0, "ok")
        assert led.summary()["tenant_ttft"] == {}


# ---------------------------------------------------------------------------
# engine submit idempotency (satellite; host-side — no decode needed)
# ---------------------------------------------------------------------------


# tiny_model (the shared 61-vocab serve LM) comes from conftest.py,
# session-scoped: the same instance test_serve uses.


def _prompt(n, seed=0):
    return np.random.RandomState(seed).randint(0, 61, size=(n,)).astype(np.int32)


def _engine(model, params, **kw):
    kw.setdefault("num_blocks", 64)
    kw.setdefault("block_size", 4)
    kw.setdefault("max_slots", 2)
    kw.setdefault("prefill_chunk", 8)
    return ServeEngine(model, params, **kw)


class TestSubmitIdempotency:
    def test_duplicate_token_rejected_with_original_rid(self, tiny_model):
        eng = _engine(*tiny_model)
        rid = eng.submit(_prompt(6), 4, token="job-1")
        with pytest.raises(DuplicateRequest) as exc:
            eng.submit(_prompt(8, seed=1), 4, token="job-1")
        assert exc.value.rid == rid and exc.value.token == "job-1"
        assert eng.submit(_prompt(8, seed=1), 4, token="job-2") != rid

    def test_token_stays_burned_until_record_evicted(self, tiny_model):
        eng = _engine(*tiny_model, max_done=2)
        rids = [eng.submit(_prompt(6, seed=i), 4, token=f"t{i}") for i in range(3)]
        eng.run()
        # t0's record was retention-evicted (max_done=2) — gone from the
        # status surface, and its token is free again; t2's record is
        # retained: still a duplicate
        with pytest.raises(KeyError):
            eng.status(rids[0])
        assert all(eng.status(r) == "ok" for r in rids[1:])
        eng.submit(_prompt(6, seed=0), 4, token="t0")
        with pytest.raises(DuplicateRequest):
            eng.submit(_prompt(6, seed=2), 4, token="t2")


# ---------------------------------------------------------------------------
# the failover property drill: random kills/stalls/drains at every phase
# under a tight pool (real engines — the pool audit is the point)
# ---------------------------------------------------------------------------


class _DrillChaos:
    """Seeded replica-level chaos: at any router step a standing replica
    may be killed, drained, or stalled — guarded so at least one
    non-draining replica always remains."""

    def __init__(self, router, seed):
        self.router = router
        self.rng = np.random.RandomState(seed)
        self.events = []

    def __call__(self, point, seqs):
        r = self.router
        standing = [
            name for name, rep in r.replicas.items()
            if rep.alive and not rep.removed and not rep.draining
        ]
        if len(standing) > 1 and self.rng.random_sample() < 0.02:
            name = standing[int(self.rng.randint(len(standing)))]
            self.events.append(("kill", name))
            r.kill_replica(name, "drill")
            standing.remove(name)
        if len(standing) > 1 and self.rng.random_sample() < 0.02:
            name = standing[int(self.rng.randint(len(standing)))]
            self.events.append(("drain", name))
            r.drain_replica(name)
            standing.remove(name)
        if standing and self.rng.random_sample() < 0.05:
            name = standing[int(self.rng.randint(len(standing)))]
            self.events.append(("stall", name))
            r.stall_replica(name, 2)


class TestFailoverProperty:
    def test_random_replica_chaos_under_tight_pool(self, tiny_model, tmp_path):
        model, params = tiny_model
        n_req = 10
        prompts = [_prompt(6 + (i % 3) * 4, seed=100 + i) for i in range(n_req)]
        max_new = [4 + (i % 2) * 2 for i in range(n_req)]
        # the fault-free reference arm: greedy engine output is
        # batch-composition-independent, so one engine serving everything
        # pins the expected tokens for every request
        ref = _engine(model, params)
        ref_rids = [ref.submit(p, m) for p, m in zip(prompts, max_new)]
        ref_outs = ref.run()
        assert all(ref.status(r) == "ok" for r in ref_rids)

        engines = [
            _engine(model, params, num_blocks=24, max_slots=2) for _ in range(3)
        ]
        router = Router(
            engines, heartbeat_timeout_s=1e9, max_retries=3,
            backoff_base_s=0.0, breaker_threshold=3, breaker_cooldown_s=0.01,
            run_dir=tmp_path,
        )
        chaos = _DrillChaos(router, seed=7)
        router.fault_injector = chaos
        placements = []
        orig = router._place

        def spy(rec, rep, now):
            placements.append((rec.tenant, rec.rid, rec.retries))
            return orig(rec, rep, now)

        router._place = spy
        rids = [
            router.submit(p, m, tenant="hot" if i % 2 == 0 else "cold")
            for i, (p, m) in enumerate(zip(prompts, max_new))
        ]
        steps = 0
        while not router.idle and steps < 2000:
            router.step()
            steps += 1
            # the per-step invariants, on EVERY replica, at every phase:
            # free + unique-live == capacity ...
            for rep in router.replicas.values():
                rep.engine.pool.assert_consistent()
            # ... and no request is ever live on two engines at once
            # (at-most-once across failover/migration token rotations)
            live_on = {}
            for name, rep in router.replicas.items():
                for seq in rep.engine._all.values():
                    if seq.status is None and seq.token:
                        base = seq.token.split(".")[0]
                        live_on.setdefault(base, []).append(name)
            for base, names in live_on.items():
                assert len(names) == 1, f"{base} live on {names} at step {steps}"

        assert router.idle, f"drill did not converge (events: {chaos.events})"
        statuses = router.statuses()
        assert set(statuses.values()) <= set(TERMINAL_STATUSES)
        assert router.leaked_blocks() == 0
        # survivors stay token-identical to the fault-free reference
        ok = [rid for rid in rids if statuses[rid] == "ok"]
        assert len(ok) >= n_req // 2, f"too much collateral: {statuses}"
        for rid in ok:
            assert np.array_equal(router.output(rid), ref_outs[rid]), rid
        # strict per-tenant FIFO for first placements
        for tenant in ("hot", "cold"):
            first = [rid for (t, rid, retries) in placements
                     if t == tenant and retries == 0]
            assert first == sorted(first)
        # any drain that ran to completion left its verdict behind
        if any(rep.removed for rep in router.replicas.values()):
            verdict = read_requeue_verdict(tmp_path)
            assert verdict is not None and verdict["serve"]["drained_clean"]


# ---------------------------------------------------------------------------
# token identity through an operator kill + drain (integration)
# ---------------------------------------------------------------------------


class TestRouterIntegration:
    def test_outputs_identical_through_kill_and_drain(self, tiny_model, tmp_path):
        model, params = tiny_model
        prompts = [_prompt(8, seed=200 + i) for i in range(6)]
        ref = _engine(model, params)
        for p in prompts:
            ref.submit(p, 6)
        ref_outs = ref.run()

        engines = [_engine(model, params, metrics=True) for _ in range(3)]
        router = Router(
            engines, heartbeat_timeout_s=1e9, max_retries=2,
            backoff_base_s=0.0, run_dir=tmp_path,
        )
        rids = [router.submit(p, 6, tenant="t") for p in prompts]
        # let work spread, then kill one replica and drain another
        for _ in range(3):
            router.step()
        router.kill_replica("r2", "drill")
        router.drain_replica("r1")
        router.run(max_steps=500)
        assert router.idle
        assert set(router.statuses().values()) == {"ok"}
        assert router.leaked_blocks() == 0
        for i, rid in enumerate(rids):
            assert np.array_equal(router.output(rid), ref_outs[i])
        assert router.replicas["r1"].removed
        assert read_requeue_verdict(tmp_path)["serve"]["replica"] == "r1"
        # and the pool's metrics are ONE valid Prometheus page: the router's
        # own series plus every replica's registry under a replica label
        fams = parse_prometheus_text(router.metrics_text())
        assert [float(v) for _, _, v in fams["dml_router_kills_total"]["samples"]] == [1.0]
        tagged = {l["replica"] for f in fams.values() for _, l, _ in f["samples"] if "replica" in l}
        assert tagged == {"r0", "r1", "r2"}
        assert "dml_serve_tokens_total" in fams


# ---------------------------------------------------------------------------
# cross-process determinism (satellites): stable prefix addresses and a
# byte-identical chaos replay under different PYTHONHASHSEED
# ---------------------------------------------------------------------------

_DET_SCRIPT = r"""
import json
from dmlcloud_tpu.serve.prefix_cache import content_key, prefix_keys, root_key
from dmlcloud_tpu.serve import ChaosMonkey, Router, ServeLedger

out = {"prefix": {
    "keys": prefix_keys(list(range(40)), 8),
    "adapter3": prefix_keys(list(range(40)), 8, adapter=3),
    "root": root_key(0),
    "chain": content_key(123, (7, 8, 9)),
}}


class _Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


class _Pool:
    block_size = 4
    num_blocks = 64

    def blocks_for(self, n):
        return max(1, -(-int(n) // 4))

    def assert_consistent(self):
        pass


class _Stub:
    def __init__(self, clock):
        import types
        self.pool = _Pool()
        self.draft_pool = None
        self.scheduler = types.SimpleNamespace(prefill_chunk=8)
        self.ledger = ServeLedger()
        self.clock = clock
        self._all = {}
        self._next = 0

    def submit(self, prompt, max_new_tokens=8, *, token=None, tenant=None, **kw):
        rid = self._next
        self._next += 1
        self._all[rid] = {"st": None, "left": 3}
        self.ledger.arrived(rid, self.clock(), tenant=tenant)
        return rid

    def step(self):
        did = False
        for rid, s in self._all.items():
            if s["st"] is None:
                did = True
                s["left"] -= 1
                if s["left"] <= 0:
                    s["st"] = "ok"
                    self.ledger.finished(rid, self.clock(), "ok")
        return did

    def status(self, rid):
        if rid not in self._all:
            raise KeyError(rid)
        st = self._all[rid]["st"]
        return st if st is not None else "running"

    def statuses(self):
        return {r: self.status(r) for r in self._all}

    def cancel(self, rid):
        s = self._all.get(rid)
        if s is None or s["st"] is not None:
            return False
        s["st"] = "cancelled"
        self.ledger.finished(rid, self.clock(), "cancelled")
        return True

    @property
    def idle(self):
        return all(s["st"] is not None for s in self._all.values())

    def leaked_blocks(self):
        return 0


clock = _Clock()
router = Router(
    [_Stub(clock) for _ in range(3)], clock=clock,
    heartbeat_timeout_s=1e9, max_retries=3, backoff_base_s=0.0,
    drr_quantum=100,
)
monkey = ChaosMonkey(
    seed=11, p_replica_kill=0.04, max_replica_kills=1,
    p_replica_stall=0.15, replica_stall_steps=2,
).attach_router(router)
for i in range(8):
    router.submit(list(range(i, i + 8)), 8, tenant="a" if i % 2 else "b")
steps = 0
while not router.idle and steps < 300:
    router.step()
    clock.t += 0.01
    steps += 1
out["chaos"] = {
    "log": monkey.log,
    "statuses": {str(k): v for k, v in sorted(router.statuses().items())},
    "failovers": router.failovers,
    "kills": router.kills,
    "idle": router.idle,
}
print(json.dumps(out, sort_keys=True))
"""


@pytest.fixture(scope="module")
def _det_runs():
    """The same seeded drill in two fresh interpreters with DIFFERENT
    hash seeds; both stdouts, raw."""
    outs = []
    for hash_seed in ("0", "4271"):
        env = dict(os.environ, PYTHONHASHSEED=hash_seed, JAX_PLATFORMS="cpu")
        proc = subprocess.run(
            [sys.executable, "-c", _DET_SCRIPT],
            capture_output=True, timeout=240, env=env,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        )
        assert proc.returncode == 0, proc.stderr.decode()
        outs.append(proc.stdout)
    return outs


class TestCrossProcessDeterminism:
    def test_prefix_keys_independent_of_hash_seed(self, _det_runs):
        a, b = (json.loads(o)["prefix"] for o in _det_runs)
        assert a == b
        # and both agree with THIS process (a third hash seed, in effect)
        assert a["keys"] == prefix_keys(list(range(40)), 8)
        assert a["adapter3"] == prefix_keys(list(range(40)), 8, adapter=3)
        assert a["root"] == root_key(0)
        assert a["chain"] == content_key(123, (7, 8, 9))
        # adapter id is part of the address: no cross-tenant aliasing
        assert a["keys"] != a["adapter3"]

    def test_chaos_event_log_replays_byte_identical(self, _det_runs):
        a, b = _det_runs
        assert a == b  # the WHOLE drill record, byte for byte
        chaos = json.loads(a)["chaos"]
        assert chaos["idle"] is True
        assert set(chaos["statuses"].values()) <= set(TERMINAL_STATUSES)
        # the drill actually injected something worth replaying
        assert any(kind in ("replica_kill", "replica_stall")
                   for (_, kind, _detail) in chaos["log"])
