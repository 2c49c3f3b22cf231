"""The span journal: typed, timestamped spans in a ring + off-thread JSONL.

Design constraints (they shape everything here):

- **Hot-loop cost is one dict build + two deque appends.** ``emit`` never
  touches the filesystem; a daemon writer thread drains the pending queue to
  ``journal-rank<k>.jsonl`` every ``flush_interval`` seconds and at close.
- **Durations are monotonic, timestamps are mergeable.** Every span's
  duration comes from ``time.perf_counter`` (wall clocks jump; lint rule
  DML108 enforces the same rule on user code). For the cross-host merge each
  journal records ONE wall-clock anchor at creation and reports
  ``ts = wall_anchor + (perf_now - perf_anchor)`` — monotonic within a host,
  comparable across hosts to NTP precision.
- **The ring outlives the file.** The last ``ring_size`` spans stay in
  memory for the hang watchdog's forensics dump — when the job is wedged the
  flusher thread may be too, so the dump reads the ring, not the file.

Schema v1 (one JSON object per line; locked by tests/test_telemetry.py)::

    {"v": 1, "kind": <SPAN_KINDS>, "label": str|null, "ts": float (s, epoch),
     "dur": float (s), "rank": int, "tid": str, ...attrs}

Extra keys are rule-following attrs (e.g. ``step``, ``scope``, ``op``);
consumers must ignore unknown keys. A version bump is a new schema, never a
silent field change.
"""

from __future__ import annotations

import atexit
import collections
import io
import json
import os
import threading
import time
from contextlib import contextmanager, nullcontext
from typing import Any, Iterable

__all__ = [
    "SCHEMA_VERSION",
    "SPAN_KINDS",
    "SETUP_LABELS",
    "REQUEST_SPAN_KINDS",
    "BATCH_SPAN_KINDS",
    "SpanJournal",
    "activate",
    "deactivate",
    "active_journal",
    "span",
    "emit",
    "setup",
    "now",
    "load_journals",
    "to_chrome_trace",
    "to_request_trace",
    "linked_trace_report",
]

SCHEMA_VERSION = 1

#: The typed span vocabulary of schema v1. ``emit`` accepts unknown kinds
#: (forward compatibility for user spans) but everything the framework
#: itself emits is in this set, and the timeline converter colors by it.
SPAN_KINDS = frozenset(
    {
        "run",  # whole pipeline run
        "stage",  # one Stage.run()
        "epoch",  # one epoch (train+val)
        "step_dispatch",  # host enqueue of one compiled step
        "data_wait",  # host blocked waiting for the next batch
        "h2d",  # host->device transfer dispatch of one batch
        "metric_readback",  # host blocked fetching device values
        "checkpoint",  # save dispatch / commit wait
        "barrier",  # control-plane barrier
        "compile",  # AOT precompile of one signature
        "preflight",  # IR-level verify of one program (lint/ir.py; "verify" is taken by spec decode)
        "host_stall",  # any other accounted host block (StallTimer)
        "watchdog",  # forensics dump events
        "sanitizer",  # runtime sanitizer violations (lint/sanitize.py)
        "queue_wait",  # serving: request arrival -> admission (serve/)
        "prefill",  # serving: one chunked-prefill device call
        "decode_batch",  # serving: one continuous-batching decode step
        "draft",  # serving: draft-model device call (spec proposals/prefill)
        "verify",  # serving: one k+1-position spec verification pass
        "fault",  # serving: a step failure isolated to its request(s)
        "drain",  # serving: graceful-drain window (request -> verdict)
        "route",  # serving: router placement of one request on a replica
        "failover",  # serving: resubmission of a request off a dead replica
        "replica_drain",  # serving: router-coordinated drain of one replica
        "medusa",  # serving: one fused Medusa propose+verify round
        "admission",  # serving: scheduler admission of one request
        "prefix_lookup",  # serving: radix-tree prefix match at admission
        "cow_fork",  # serving: one copy-on-write block fork
        "slo_alert",  # serving: a multi-window SLO burn-rate alert fired
        "engine_step",  # serving: one ServeEngine.step(), bookkeeping and device calls
        "call_build",  # serving: host work before a device call (COW guards, tables, row params)
        "call_upload",  # serving: a call's host arrays to the device, the key's fold-in
        "call_launch",  # serving: the jitted call returning, the pool swapped
        "call_fetch",  # serving: the call's tokens back on the host (the one sync)
        "profile",  # a jax.profiler trace the program took (utils.profiling.trace)
        "setup",  # one part of the time from process start to the first step (label: SETUP_LABELS)
        "remat",  # a train step traced with remat: the blocks that keep their MLP's products, the room (no duration)
        "mhc",  # a hyper-connection traced: the path its maps take (kernels or plain), the stream's shape, why (no duration)
    }
)

#: Serve span kinds that are REQUEST-SCOPED: once request tracing is on
#: (``Router.submit``/``ServeEngine.submit`` mint trace ids), every
#: record of these kinds carries a ``trace`` attr — a record without one
#: is an ORPHAN (:func:`linked_trace_report` flags it). A ``fault``
#: record is request-scoped exactly when it carries a ``request`` attr
#: (batch-level degrade faults are not tied to one request).
REQUEST_SPAN_KINDS = frozenset(
    {"queue_wait", "admission", "prefix_lookup", "prefill", "cow_fork",
     "route", "failover"}
)

#: Serve span kinds that advance a whole decode BATCH: they carry a
#: ``traces`` list attr linking every request that rode the batch.
BATCH_SPAN_KINDS = frozenset({"decode_batch", "draft", "verify", "medusa"})

#: The ``setup`` span labels and the label of the set-up span each one nests
#: in (its ``parent`` attr; None for a top-level part). A part's self time is
#: its duration less its children's.
SETUP_LABELS = {
    "import": None,  # `import dmlcloud_tpu`: __init__.py's first to last line
    "pipeline": None,  # TrainingPipeline._pre_run through the user's pre_run()
    "stage": None,  # one stage's _pre_stage, whole (TrainValStage's included)
    "pre_stage": "stage",  # the user's pre_stage() hook
    "state": "stage",  # make_state(): the TrainState on the device, the optimizer's init
    "restore": "stage",  # _restore_state(), when resuming
    "phase_map": "stage",  # write_phase_map after a compile (armed runs only)
}

_JOURNAL_GLOB_PREFIX = "journal-rank"


class SpanJournal:
    """Per-host append-only span recorder (see module docstring)."""

    def __init__(
        self,
        directory: str | os.PathLike,
        rank: int = 0,
        ring_size: int = 1024,
        flush_interval: float = 2.0,
    ):
        self.directory = os.fspath(directory)
        self.rank = int(rank)
        self.path = os.path.join(self.directory, f"{_JOURNAL_GLOB_PREFIX}{self.rank}.jsonl")
        self._ring: collections.deque = collections.deque(maxlen=int(ring_size))
        self._pending: list[dict] = []
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None
        self._atexit = None
        self._flush_interval = float(flush_interval)
        self._wall0 = time.time()
        self._perf0 = time.perf_counter()
        #: perf_counter of the most recent emit — the watchdog's progress probe
        self.last_emit = self._perf0
        #: called (with no args) after every emit when set — the pipeline
        #: wires this to ``HangWatchdog.notify`` so any span counts as life
        self.on_emit = None
        os.makedirs(self.directory, exist_ok=True)
        # truncate a leftover journal from a previous run in the same dir
        with open(self.path, "w", encoding="utf-8"):
            pass

    # -- clock ---------------------------------------------------------------
    @staticmethod
    def now() -> float:
        """Monotonic seconds — the only clock span boundaries may come from."""
        return time.perf_counter()

    def _wall(self, perf_t: float) -> float:
        return self._wall0 + (perf_t - self._perf0)

    # -- recording -----------------------------------------------------------
    def emit(
        self, kind: str, start: float, end: float | None = None, label: str | None = None, **attrs: Any
    ) -> dict:
        """Record one span. ``start``/``end`` are ``SpanJournal.now()``
        readings (``end`` defaults to now). Returns the schema-v1 record."""
        if end is None:
            end = time.perf_counter()
        rec = {
            "v": SCHEMA_VERSION,
            "kind": kind,
            "label": label,
            "ts": round(self._wall(start), 6),
            "dur": round(max(end - start, 0.0), 9),
            "rank": self.rank,
            "tid": threading.current_thread().name,
        }
        if attrs:
            rec.update(attrs)
        with self._lock:
            self._pending.append(rec)
            self._ring.append(rec)
        self.last_emit = end
        cb = self.on_emit
        if cb is not None:
            cb()
        return rec

    @contextmanager
    def span(self, kind: str, label: str | None = None, **attrs: Any):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.emit(kind, t0, label=label, **attrs)

    def tail(self, n: int = 64) -> list[dict]:
        """The most recent ``n`` spans from the in-memory ring (newest last)."""
        with self._lock:
            items = list(self._ring)
        return items[-int(n):]

    def __len__(self) -> int:
        with self._lock:
            return len(self._ring)

    # -- flushing ------------------------------------------------------------
    def flush(self) -> int:
        """Drain pending spans to the JSONL file; returns lines written."""
        with self._lock:
            batch, self._pending = self._pending, []
        if not batch:
            return 0
        buf = io.StringIO()
        for rec in batch:
            buf.write(json.dumps(rec, separators=(",", ":")))
            buf.write("\n")
        with open(self.path, "a", encoding="utf-8") as f:
            f.write(buf.getvalue())
        return len(batch)

    def start(self) -> "SpanJournal":
        """Start the off-thread flusher (idempotent), and register an
        ``atexit`` flush — spans emitted after the flusher's last wakeup
        survive a process that exits without calling :meth:`close` (the
        daemon thread dies mid-interval; the hook drains what it left)."""
        if self._thread is None or not self._thread.is_alive():
            self._stop.clear()
            self._thread = threading.Thread(
                target=self._flush_loop, name=f"dml-journal-r{self.rank}", daemon=True
            )
            self._thread.start()
        if self._atexit is None:
            self._atexit = self.flush
            atexit.register(self._atexit)
        return self

    def _flush_loop(self) -> None:
        while not self._stop.wait(self._flush_interval):
            try:
                self.flush()
            except OSError:  # a full/unmounted disk must never kill training
                pass

    def close(self) -> None:
        """Stop the flusher, drop the atexit hook and write everything
        still pending."""
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5.0)
            self._thread = None
        if self._atexit is not None:
            atexit.unregister(self._atexit)
            self._atexit = None
        try:
            self.flush()
        except OSError:
            pass


# --------------------------------------------------------------- active hook
#
# Instrumentation points all over the framework (stage, data/device,
# checkpoint, compile/aot, parallel/runtime, utils/profiling) call the
# module-level ``span``/``emit`` below. With no journal armed they are a
# single attribute read + None check — the default path stays free.

_active: SpanJournal | None = None
#: ``perf_counter`` at the top and bottom of the package's ``__init__``, until
#: the first journal armed in the process records them as the ``import`` span
_import_readings: tuple[float, float] | None = None


def record_import(start: float, end: float) -> None:
    global _import_readings
    _import_readings = (start, end)


def activate(journal: SpanJournal) -> SpanJournal:
    global _active, _import_readings
    _active = journal
    if _import_readings is not None:
        readings, _import_readings = _import_readings, None
        setup("import", *readings)
    return journal


def deactivate() -> None:
    global _active
    _active = None


def active_journal() -> SpanJournal | None:
    return _active


def span(kind: str, label: str | None = None, **attrs: Any):
    """Context manager recording a span on the active journal; no-op when
    telemetry is not armed."""
    j = _active
    if j is None:
        return nullcontext()
    return j.span(kind, label=label, **attrs)


def emit(kind: str, start: float, end: float | None = None, label: str | None = None, **attrs: Any):
    """Record a span on the active journal (no-op when not armed)."""
    j = _active
    if j is None:
        return None
    return j.emit(kind, start, end, label=label, **attrs)


def setup(label: str, start: float, end: float | None = None):
    """Record one part of set-up (``SETUP_LABELS``) on the active journal,
    with its ``parent``; no-op when not armed."""
    j = _active
    if j is None:
        return None
    return j.emit("setup", start, end, label=label, parent=SETUP_LABELS[label])


def now() -> float:
    return time.perf_counter()


# ------------------------------------------------------------ merge / export


def _telemetry_dir(run_dir: str | os.PathLike) -> str:
    """Accept a run dir (containing ``telemetry/``) or a telemetry dir."""
    run_dir = os.fspath(run_dir)
    sub = os.path.join(run_dir, "telemetry")
    if os.path.isdir(sub):
        return sub
    return run_dir


def load_journals(run_dir: str | os.PathLike) -> list[dict]:
    """Read every rank's ``journal-rank*.jsonl`` under ``run_dir`` (or its
    ``telemetry/`` subdir) into one record list sorted by timestamp.
    Truncated trailing lines (a killed writer mid-line) are skipped."""
    tdir = _telemetry_dir(run_dir)
    records: list[dict] = []
    try:
        names = sorted(os.listdir(tdir))
    except OSError:
        raise FileNotFoundError(f"no telemetry journal directory at {tdir}") from None
    found = False
    for name in names:
        if not (name.startswith(_JOURNAL_GLOB_PREFIX) and name.endswith(".jsonl")):
            continue
        found = True
        with open(os.path.join(tdir, name), "r", encoding="utf-8") as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    records.append(json.loads(line))
                except ValueError:
                    continue  # half-written final line of a killed run
    if not found:
        raise FileNotFoundError(
            f"no {_JOURNAL_GLOB_PREFIX}*.jsonl under {tdir} — was the run launched "
            "with TrainingPipeline(telemetry=True)?"
        )
    records.sort(key=lambda r: r.get("ts", 0.0))
    return records


def to_chrome_trace(records: Iterable[dict]) -> dict:
    """Merge schema-v1 records into Chrome-trace JSON (the ``traceEvents``
    format Perfetto and ``chrome://tracing`` both load): one trace process
    per rank, one track per originating thread, complete ('X') events with
    microsecond timestamps rebased to the earliest span."""
    records = [r for r in records if "ts" in r and "dur" in r]
    t0 = min((r["ts"] for r in records), default=0.0)
    events: list[dict] = []
    # pid/tid must be integers for chrome://tracing; thread names ride the
    # 'M' metadata events instead
    tids: dict[int, dict[str, int]] = {}
    for r in records:
        rank = int(r.get("rank", 0))
        tname = str(r.get("tid", "main"))
        if rank not in tids:
            tids[rank] = {}
            events.append(
                {"name": "process_name", "ph": "M", "pid": rank, "args": {"name": f"rank {rank}"}}
            )
        if tname not in tids[rank]:
            tid = tids[rank][tname] = len(tids[rank])
            events.append(
                {"name": "thread_name", "ph": "M", "pid": rank, "tid": tid, "args": {"name": tname}}
            )
        kind = str(r.get("kind", "?"))
        label = r.get("label")
        args = {
            k: v
            for k, v in r.items()
            if k not in ("v", "kind", "label", "ts", "dur", "rank", "tid")
        }
        events.append(
            {
                "name": f"{kind}:{label}" if label else kind,
                "cat": kind,
                "ph": "X",
                "ts": round((r["ts"] - t0) * 1e6, 3),
                "dur": round(r["dur"] * 1e6, 3),
                "pid": rank,
                "tid": tids[rank][tname],
                "args": args,
            }
        )
    return {
        "traceEvents": events,
        "displayTimeUnit": "ms",
        "metadata": {"source": "dmlcloud_tpu telemetry journal", "schema": SCHEMA_VERSION},
    }


def _record_traces(rec: dict) -> list:
    """The trace id(s) a record links into: its ``trace`` attr, or the
    ``traces`` list a batch span carries (one span, many requests)."""
    t = rec.get("trace")
    if t is not None:
        return [t]
    ts = rec.get("traces")
    return list(ts) if ts else []


def to_request_trace(records: Iterable[dict]) -> dict:
    """The REQUEST-TRACK view of a merged journal: Chrome-trace JSON with
    one track (thread) per trace id under a single "requests" process,
    so Perfetto shows each request's causal chain — route, queue wait,
    admission, prefill chunks, every decode batch it rode, failover hops
    — as one horizontal lane even when the spans came from different
    replicas/ranks. Batch spans are duplicated into every linked
    request's track (the batch IS part of each rider's critical path).
    Records without trace linkage are skipped — this view is additive to
    :func:`to_chrome_trace`, never a replacement."""
    records = [r for r in records if "ts" in r and "dur" in r]
    t0 = min((r["ts"] for r in records), default=0.0)
    # track order: first appearance of each trace id
    tids: dict[str, int] = {}
    events: list[dict] = [
        {"name": "process_name", "ph": "M", "pid": 0, "args": {"name": "requests"}}
    ]
    for r in records:
        for trace in _record_traces(r):
            trace = str(trace)
            if trace not in tids:
                tids[trace] = len(tids)
                events.append(
                    {"name": "thread_name", "ph": "M", "pid": 0,
                     "tid": tids[trace], "args": {"name": trace}}
                )
            kind = str(r.get("kind", "?"))
            label = r.get("label")
            args = {
                k: v for k, v in r.items()
                if k not in ("v", "kind", "label", "ts", "dur", "tid", "traces")
            }
            events.append(
                {
                    "name": f"{kind}:{label}" if label else kind,
                    "cat": kind,
                    "ph": "X",
                    "ts": round((r["ts"] - t0) * 1e6, 3),
                    "dur": round(r["dur"] * 1e6, 3),
                    "pid": 0,
                    "tid": tids[trace],
                    "args": args,
                }
            )
    return {
        "traceEvents": events,
        "displayTimeUnit": "ms",
        "metadata": {
            "source": "dmlcloud_tpu telemetry journal (request tracks)",
            "schema": SCHEMA_VERSION,
            "traces": len(tids),
        },
    }


def linked_trace_report(records: Iterable[dict]) -> dict:
    """Walk a merged journal and group serve spans by trace id — the
    linkage auditor the router chaos drill gates on (zero orphans).
    Returns plain dicts::

        {"traces": {trace_id: [records, ts-sorted]},
         "orphans": [request-scoped serve records with NO trace linkage],
         "statuses": {trace_id: terminal status stamped by a fault span
                      or None}}

    A record is an orphan when its kind is in :data:`REQUEST_SPAN_KINDS`
    (or it is a ``fault`` carrying a ``request`` attr — a per-request
    fault, not a batch degrade) but it carries neither ``trace`` nor
    ``traces`` — exactly the span that would dangle unexplained in the
    request-track view."""
    traces: dict[str, list[dict]] = {}
    orphans: list[dict] = []
    statuses: dict[str, Any] = {}
    for r in records:
        linked = _record_traces(r)
        kind = r.get("kind")
        if not linked:
            if kind in REQUEST_SPAN_KINDS or (kind == "fault" and "request" in r):
                orphans.append(r)
            continue
        for t in linked:
            t = str(t)
            traces.setdefault(t, []).append(r)
            if kind == "fault":
                statuses[t] = r.get("status", "error")
    for spans in traces.values():
        spans.sort(key=lambda r: r.get("ts", 0.0))
    for t in traces:
        statuses.setdefault(t, None)
    return {"traces": traces, "orphans": orphans, "statuses": statuses}
