"""Profiling helpers — the idiomatic upgrade over the reference's wall-clock
timers (reference stage.py:299,303,314 tracks only ``misc/step_time_ms``;
SURVEY.md §5.1): capture real XLA traces viewable in TensorBoard/Perfetto.

- ``trace(logdir)``: context manager around ``jax.profiler`` — wrap any block
  (a few train steps) to record device timelines, HLO op breakdown, and memory.
- ``profile_steps(fn, n, logdir)``: run a callable ``n`` times under a trace.
- ``roofline(trace_dir)``: parse the trace's own per-op hardware counters
  (hlo_category / flops / bytes_accessed) into a per-category roofline
  table next to the chip's peaks — the analysis that settled whether the
  ResNet bench was MXU- or HBM-bound (doc/performance.md §6).
- ``StepTimer``: dispatch-to-dispatch wall timer with p50/p95 summaries, the
  host-side complement used by bench.py.
- ``StallTimer``: accumulates the wall-clock the host spends *blocked* on
  device results or pending checkpoint commits — the overlap engine's
  ``misc/host_stall_ms`` metric (stage.py) and the host-stall fraction
  ``bench.py --overlap-child`` reports.
"""

from __future__ import annotations

import collections
import glob
import os
import time
from contextlib import contextmanager

import numpy as np

__all__ = ["trace", "profile_steps", "roofline", "format_roofline", "StepTimer", "StallTimer"]


class StallTimer:
    """Accumulates host-stall time: every block the training loop spends
    waiting on the device (value fetches, ``block_until_ready``, waiting for
    a previous async checkpoint to commit) runs under ``measure()`` and adds
    to one counter. The epoch loop resets it per epoch and publishes the
    total as ``misc/host_stall_ms`` — the number the overlap engine exists
    to drive toward zero."""

    def __init__(self):
        self._ns = 0
        self._depth = 0
        self._outer_t0 = 0
        self._outer_label: str | None = None
        #: label -> accumulated ns for spans measured with ``measure(label=)``
        #: — how the goodput ledger splits checkpoint waits from metric
        #: readbacks inside one total (telemetry/goodput.py)
        self._label_ns: dict[str, int] = {}

    @contextmanager
    def measure(self, label: str | None = None):
        """Time a host-blocked span. Nesting-safe: a ``measure()`` (or
        ``block()``/``fetch()``) inside an outer ``measure()`` contributes
        nothing of its own — only the outermost span accumulates, so nested
        blocks are never double-counted. ``label`` attributes the outermost
        span to a named bucket (``label_ms``) and, when the telemetry
        journal is armed, emits it as a typed span.

        Measured spans are also *sanctioned* for the runtime sanitizer
        (lint/sanitize.py) — the same exemption the static DML101 rule
        grants ``with <x>.measure():`` blocks: an accounted sync is the
        framework's own pattern, never a violation."""
        from ..lint.sanitize import sanctioned

        self._depth += 1
        if self._depth == 1:
            self._outer_t0 = time.perf_counter_ns()
            self._outer_label = label
        try:
            with sanctioned():
                yield
        finally:
            self._depth -= 1
            if self._depth == 0:
                t1 = time.perf_counter_ns()
                dt = t1 - self._outer_t0
                self._ns += dt
                label = self._outer_label
                if label is not None:
                    self._label_ns[label] = self._label_ns.get(label, 0) + dt
                    from ..telemetry import journal as _journal

                    if _journal.active_journal() is not None:
                        kind = label if label in _journal.SPAN_KINDS else "host_stall"
                        _journal.emit(
                            kind,
                            self._outer_t0 / 1e9,
                            t1 / 1e9,
                            label=None if kind == label else label,
                        )

    def block(self, tree, label: str | None = "metric_readback"):
        """``jax.block_until_ready`` under the timer (the epoch-end sync)."""
        import jax

        with self.measure(label=label):
            return jax.block_until_ready(tree)

    def fetch(self, value, label: str | None = "metric_readback"):
        """Fetch ``value`` to host under the timer, returning a numpy array."""
        with self.measure(label=label):
            return np.asarray(value)

    @property
    def ms(self) -> float:
        return self._ns / 1e6

    def label_ms(self, label: str) -> float:
        """Accumulated ms of outermost spans measured under ``label``."""
        return self._label_ns.get(label, 0) / 1e6

    def reset(self) -> None:
        self._ns = 0
        self._label_ns.clear()


@contextmanager
def trace(logdir: str, create_perfetto_link: bool = False):
    """Record a JAX profiler trace into ``logdir`` (TensorBoard-compatible).

    Traces include the TPU device timeline, HLO-level op costs, and host
    activity — strictly more than the reference's per-step wall timers.
    """
    import jax

    jax.profiler.start_trace(logdir, create_perfetto_link=create_perfetto_link)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def profile_steps(fn, n: int, logdir: str, *args, **kwargs):
    """Run ``fn(*args, **kwargs)`` ``n`` times under a trace; returns the last
    result (blocked until ready so the trace covers real device work)."""
    import jax

    result = None
    with trace(logdir):
        for _ in range(n):
            result = fn(*args, **kwargs)
        jax.block_until_ready(result)
    return result


#: bf16 peak FLOP/s by TPU device_kind substring (Google Cloud's published
#: per-chip peaks). The same table bench.py uses for its MFU lines; a kind
#: that is not here has no peak — ``chip_peak_flops`` raises.
PEAK_BF16_FLOPS = {
    "v4": 275e12,
    "v5 lite": 197e12,
    "v5e": 197e12,
    "v5p": 459e12,
    "v6 lite": 918e12,
    "v6e": 918e12,
}


def peak_flops_for_kind(kind: str) -> float | None:
    """Peak bf16 FLOP/s for a ``device_kind`` string, or None if unknown
    (callers skip the metric or raise — a made-up peak turns MFU numbers on
    other devices into nonsense)."""
    kind = kind.lower()
    for key, peak in PEAK_BF16_FLOPS.items():
        if key in kind:
            return peak
    return None


def chip_peak_flops(device=None) -> float:
    """Peak bf16 FLOP/s of ``device`` (default: the first local device).
    A device kind that ``PEAK_BF16_FLOPS`` does not list is an error, not a
    default: utilisation against another chip's peak is not a number."""
    import jax

    kind = (device or jax.local_devices()[0]).device_kind
    peak = peak_flops_for_kind(kind)
    if peak is None:
        raise ValueError(
            f"no bf16 peak known for device kind {kind!r}; add it to "
            "utils.profiling.PEAK_BF16_FLOPS with its source"
        )
    return peak


def _xplane_pb2():
    # generated protos predate protobuf 5's C++ descriptor pool checks
    os.environ.setdefault("PROTOCOL_BUFFERS_PYTHON_IMPLEMENTATION", "python")
    try:
        from tensorflow.tsl.profiler.protobuf import xplane_pb2
    except ImportError as e:  # tensorflow ships the xplane schema
        raise ImportError(
            "roofline() reads the trace's xplane.pb through tensorflow's proto schema, and "
            "the tensorflow package is absent on this installation (pyproject.toml does not "
            "ask for it); ROADMAP S2 moves the reader to jax.profiler.ProfileData, which "
            "needs only jax"
        ) from e
    return xplane_pb2


def _stat_value(plane, st):
    """Decode an XStat across its value oneof (incl. uint64 and interned refs)."""
    kind = st.WhichOneof("value")
    if kind is None:
        return None
    if kind == "ref_value":  # string interned in stat_metadata
        return plane.stat_metadata[st.ref_value].name
    return getattr(st, kind)


def roofline(trace_dir: str, steps: int = 1) -> tuple[dict, list[dict]]:
    """Aggregate a ``jax.profiler`` trace by HLO category from the chip's own
    op counters. Returns ``(peaks, rows)``: ``peaks`` has the device type and
    hardware peaks (TFLOP/s, HBM GB/s); each row has ``category``,
    ``time_frac``, ``ms_per_step``, ``tflops`` (achieved), ``gbps``
    (achieved), ``n_per_step``. ``steps`` = timed steps inside the trace.

    Counter conventions: ``flops`` counts multiply-add as TWO ops (the MFU
    convention — compare against peak directly); ``bytes_accessed`` includes
    VMEM-resident reads, so aggregates may exceed the HBM peak while per-op
    numbers near it still identify bandwidth-bound ops."""
    xplane_pb2 = _xplane_pb2()
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins/profile/*/*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no xplane.pb under {trace_dir} (not a jax.profiler trace dir?)")
    xs = xplane_pb2.XSpace()
    with open(paths[-1], "rb") as f:
        xs.ParseFromString(f.read())
    plane = next(
        (
            p
            for p in xs.planes
            if p.name.startswith("/device:TPU") and any(l.name == "XLA Ops" for l in p.lines)
        ),
        None,
    )
    if plane is None:
        raise ValueError("no TPU device plane with an 'XLA Ops' line in this trace")

    def stats_of(stats):
        return {plane.stat_metadata[st.metadata_id].name: _stat_value(plane, st) for st in stats}

    pstats = stats_of(plane.stats)
    peaks = {
        "device": pstats.get("device_type_string", "?"),
        "peak_tflops": float(pstats.get("peak_teraflops_per_second", 0) or 0),
        "peak_hbm_gbps": float(pstats.get("peak_hbm_bw_gigabytes_per_second", 0) or 0),
    }
    (ops_line,) = [l for l in plane.lines if l.name == "XLA Ops"]
    agg = collections.defaultdict(lambda: [0.0, 0.0, 0.0, 0])  # ps, flops, bytes, n
    for ev in ops_line.events:
        s = stats_of(plane.event_metadata[ev.metadata_id].stats)
        row = agg[s.get("hlo_category", "?")]
        row[0] += ev.duration_ps
        row[1] += float(s.get("flops", 0) or 0)
        row[2] += float(s.get("bytes_accessed", 0) or 0)
        row[3] += 1
    total_ps = sum(v[0] for v in agg.values()) or 1.0
    rows = [
        {
            "category": cat,
            "time_frac": ps / total_ps,
            "ms_per_step": ps / 1e9 / steps,
            "tflops": fl / ps if ps else 0.0,  # flops/ps == TFLOP/s
            "gbps": by / (ps / 1e12) / 1e9 if ps else 0.0,
            "n_per_step": n // steps,
        }
        for cat, (ps, fl, by, n) in sorted(agg.items(), key=lambda kv: -kv[1][0])
    ]
    return peaks, rows


def format_roofline(peaks: dict, rows: list[dict], min_frac: float = 0.001) -> str:
    """Human-readable roofline table (what scripts/analyze_trace.py prints)."""
    out = [
        f"device: {peaks['device']}  peak {peaks['peak_tflops']:.0f} TF/s, "
        f"HBM {peaks['peak_hbm_gbps']:.0f} GB/s",
        f"{'category':<28}{'time%':>7}{'ms/step':>9}{'TFLOP/s':>9}{'GB/s':>8}{'n/step':>8}",
    ]
    for r in rows:
        if r["time_frac"] < min_frac:
            continue
        out.append(
            f"{r['category']:<28}{r['time_frac'] * 100:>6.1f}%{r['ms_per_step']:>8.2f}"
            f"{r['tflops']:>9.1f}{r['gbps']:>8.0f}{r['n_per_step']:>8}"
        )
    total_ms = sum(r["ms_per_step"] for r in rows)
    tf = sum(r["tflops"] * r["ms_per_step"] for r in rows) / total_ms if total_ms else 0.0
    pct = f" ({tf / peaks['peak_tflops'] * 100:.0f}% of peak)" if peaks["peak_tflops"] else ""
    out.append(f"total: {total_ms:.2f} ms/step on device; aggregate {tf:.1f} TFLOP/s{pct}")
    return "\n".join(out)


class StepTimer:
    """Dispatch-to-dispatch step timer with percentile summaries."""

    def __init__(self):
        self._t: list[float] = []
        self._last: float | None = None

    def tick(self) -> None:
        now = time.perf_counter_ns()
        if self._last is not None:
            self._t.append((now - self._last) / 1e6)
        self._last = now

    @property
    def count(self) -> int:
        return len(self._t)

    def summary(self) -> dict[str, float]:
        if not self._t:
            return {}
        arr = np.asarray(self._t)
        return {
            "mean_ms": float(arr.mean()),
            "p50_ms": float(np.percentile(arr, 50)),
            "p95_ms": float(np.percentile(arr, 95)),
            "p99_ms": float(np.percentile(arr, 99)),
            "max_ms": float(arr.max()),
            "total_ms": float(arr.sum()),
        }

    def reset(self) -> None:
        """Forget all recorded intervals AND the last tick, so the next
        ``tick()`` starts a fresh dispatch-to-dispatch sequence (no phantom
        interval spanning the reset)."""
        self._t.clear()
        self._last = None
