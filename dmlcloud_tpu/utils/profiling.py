"""Profiling helpers — the idiomatic upgrade over the reference's wall-clock
timers (reference stage.py:299,303,314 tracks only ``misc/step_time_ms``;
SURVEY.md §5.1): capture real XLA traces viewable in TensorBoard/Perfetto.

- ``trace(logdir)``: context manager around ``jax.profiler`` — wrap any block
  (a few train steps) to record device timelines, HLO op breakdown, and memory.
- ``profile_steps(fn, n, logdir)``: run a callable ``n`` times under a trace.
- ``phase_map(compiled)``: the phase (``PHASES``) and direction of every
  instruction of a compiled step, from the ``op_name`` its own HLO text keeps.
- ``phase_table(trace_dir, phases)``: device time by phase and by kernel from
  a trace, read with ``jax.profiler.ProfileData`` alone.
- ``StepTimer``: dispatch-to-dispatch wall timer with p50/p95 summaries, the
  host-side complement of a device trace.
- ``StallTimer``: accumulates the wall-clock the host spends *blocked* on
  device results or pending checkpoint commits — the overlap engine's
  ``misc/host_stall_ms`` metric (stage.py).
"""

from __future__ import annotations

import bisect
import collections
import glob
import json
import os
import re
import time
from contextlib import contextmanager

import numpy as np

__all__ = [
    "trace", "profile_steps", "PHASES", "phase_of", "phase_map", "write_phase_map", "phase_table",
    "format_phase_table", "StepTimer", "StallTimer",
]


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

    from ..telemetry import journal as _journal

    t0 = time.perf_counter()
    jax.profiler.start_trace(logdir, create_perfetto_link=create_perfetto_link)
    t_started = time.perf_counter()
    try:
        yield
    finally:
        t_stop = time.perf_counter()
        jax.profiler.stop_trace()
        # the clock anchor: the profile's nanoseconds count from the profiler's
        # start, which lies between ``ts`` and ``ts + started_after``
        _journal.emit("profile", t0, t_stop, label=logdir, started_after=t_started - t0,
                      stopped_after=time.perf_counter() - t_stop)


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
#: per-chip peaks), what ``misc/mfu`` divides by; a kind that is not here
#: has no peak — ``chip_peak_flops`` raises.
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


#: The phase vocabulary (doc/observability.md). A phase is a path component
#: of an instruction's ``op_name``: a ``jax.named_scope`` the program opened
#: (``attn_kernel``, ``loss_head``, ``grad_clip``, ``optimizer``, ``kv_write``,
#: ``kv_gather``, ``attention``, ``head``, ``sampling``) or a Flax module's
#: name (``embed``, ``mlp``; ``attn`` and ``*_norm`` through the aliases below).
#: The expert layer and the short convolution open scopes of their own
#: (``moe_route``: scores, top-k, sort and the two moves of rows; ``moe_experts``:
#: the grouped products; ``moe_shared``: the shared expert; ``conv_op``: the whole
#: operator). ``attn_window_kernel`` is ``attn_kernel`` for the kernels of a
#: ``sliding_attention`` layer, ``attn_gate`` the per-head gate on attention's output.
#: The Mamba-2 mixer opens four: ``ssm_proj`` (its two projections), ``ssm_conv``
#: (depthwise conv, bias, silu, the split), ``ssm_scan`` (softplus, the running sums
#: and exponentials, the chunk products, the carry, ``D x``), ``ssm_gate_norm``.
PHASES = (
    "embed", "norm", "attn_proj", "attn_kernel", "attn_window_kernel", "attn_gate", "kv_write", "kv_gather", "attention",
    "mlp", "conv_op", "ssm_proj", "ssm_conv", "ssm_scan", "ssm_gate_norm", "moe_route", "moe_experts", "moe_shared",
    "loss_head", "head", "sampling", "grad_clip", "optimizer",
)
#: Flax module names that are not themselves phase names.
_PHASE_ALIASES = {"attn": "attn_proj"}
#: what jax wraps round a path component when a transformation passes over it
_TRANSFORMS = frozenset(
    {"jit", "pjit", "jvp", "transpose", "vmap", "pmap", "shard_map", "checkpoint", "remat",
     "custom_jvp", "custom_vjp"}
)
#: Kernels XLA itself puts in a program carry no scope: their phase goes by the
#: instruction's name. ``jax.lax.ragged_dot`` is a grouped-matmul custom call
#: on the TPU (``%ragged-dot-none.7``) whose ``op_name`` is that name alone or,
#: in a branch of the expert layer's ``cond``, the names round the ``cond``.
_KERNEL_PHASES = (("ragged-dot", "moe_experts"),)
_HLO_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*->.*\{\s*$")
_HLO_INSTRUCTION = re.compile(r"^\s+(ROOT\s+)?%?([\w.\-]+)\s*=")
_HLO_OP_NAME = re.compile(r'op_name="([^"]*)"')
_HLO_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
_IDENT = re.compile(r"[\w.\-]+")


def phase_of(op_name: str) -> tuple[str | None, str]:
    """``(phase, direction)`` of one ``op_name``. The phase is the innermost
    path component that names one (``PHASES``, or a Flax module name that
    stands for one); ``None`` where no component does. The direction comes
    from jax's own markers: ``recompute`` under ``rematted_computation``,
    ``bwd`` under ``transpose(``, ``fwd`` under ``jvp(``, ``-`` where the
    instruction was never differentiated (the optimizer, a serve step)."""
    if "rematted_computation" in op_name:
        direction = "recompute"
    elif "transpose(" in op_name:
        direction = "bwd"
    elif "jvp(" in op_name:
        direction = "fwd"
    else:
        direction = "-"
    for component in reversed(op_name.split("/")):
        for word in reversed(_IDENT.findall(component)):
            if word in _TRANSFORMS:
                continue
            word = _PHASE_ALIASES.get(word, word)
            if word in PHASES:
                return word, direction
            if word.endswith("_norm"):
                return "norm", direction
            break  # the component's own name is no phase: look further out
    return None, direction


def _hlo_computations(text: str) -> dict:
    """``{computation: [(instruction, op_name | None, is_root, called computation | None)]}``
    of an HLO module's text."""
    computations, current = {}, None
    for line in text.splitlines():
        if current is None:
            m = _HLO_COMPUTATION.match(line)
            if m:
                current = computations.setdefault(m.group(1), [])
            continue
        if line.startswith("}"):
            current = None
            continue
        m = _HLO_INSTRUCTION.match(line)
        if m:
            op_name, calls = _HLO_OP_NAME.search(line), _HLO_CALLS.search(line)
            current.append((m.group(2), op_name.group(1) if op_name else None, bool(m.group(1)),
                            calls.group(1) if calls else None))
    return computations


def phase_map(compiled) -> dict[str, tuple[str | None, str]]:
    """``{instruction name: (phase, direction)}`` for every instruction of a
    compiled step (``jitted.lower(...).compile()``, or its ``as_text()``).

    The profile a chip writes names each device operation by its HLO
    instruction (``%fusion.129 = ...``) and carries no ``op_name``; the
    compiled executable's own text does. This joins the two by instruction
    name, so it has to be the text of the executable that ran: XLA numbers
    instructions per compile.

    A fusion runs, and is timed, as one operation. XLA gives the fusion
    instruction the metadata of its root, so a fusion's phase is its root's;
    where the root carries none (a tuple of several outputs), the phase most
    of the fused instructions carry. A fused body that spans two phases is
    not split: its whole time goes to that one phase. A kernel XLA places
    itself goes by its instruction's name (``_KERNEL_PHASES``). Instructions whose
    ``op_name`` holds no phase (parameters, copies XLA added, the key's
    fold-in) map to ``(None, direction)`` and are the table's
    ``unattributed`` row."""
    text = compiled if isinstance(compiled, str) else compiled.as_text()
    computations = _hlo_computations(text)
    out = {}
    for instructions in computations.values():
        for name, op_name, _, calls in instructions:
            phase, direction = phase_of(op_name) if op_name else (None, "-")
            if phase is None and calls in computations:
                body = [(phase_of(o), is_root) for _, o, is_root, _ in computations[calls] if o]
                named = [pd for pd, _ in body if pd[0] is not None]
                root = next((pd for pd, is_root in body if is_root), (None, "-"))
                if root[0] is not None:
                    phase, direction = root
                elif named:
                    phase, direction = collections.Counter(named).most_common(1)[0][0]
            if phase is None:
                phase = next((p for prefix, p in _KERNEL_PHASES if name.startswith(prefix)), None)
            out[name] = (phase, direction)
    return out


def write_phase_map(compiled, path: str, program: str) -> str:
    """``phase_map(compiled)`` as JSON at ``path``:
    ``{"program": ..., "phases": {instruction: [phase, direction]}}``."""
    with open(path, "w", encoding="utf-8") as f:
        json.dump({"program": program, "phases": phase_map(compiled)}, f)
    return path


def _newest_xplane(trace_dir: str) -> str:
    if os.path.isfile(trace_dir):
        return trace_dir
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins/profile/*/*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no xplane.pb under {trace_dir} (not a jax.profiler trace dir?)")
    return paths[-1]


def _self_times(events: list) -> list:
    """``(name, ns)`` per event of one device line, each instant given to the
    innermost event open over it (a ``while`` spans its body's operations)."""
    out, stack = [], []  # stack of (end, index into out)
    for start, end, name in sorted(events, key=lambda e: (e[0], -e[1])):
        while stack and stack[-1][0] <= start:
            stack.pop()
        if stack:
            out[stack[-1][1]][1] -= min(end, stack[-1][0]) - start
        out.append([name, end - start])
        stack.append((end, len(out) - 1))
    return [(n, max(ns, 0)) for n, ns in out]


def phase_table(trace_dir: str, phases: dict | str | None = None, steps: int = 1, program: str | None = None) -> dict:
    """Device time by phase and by kernel from a ``jax.profiler`` trace, read
    with ``jax.profiler.ProfileData`` alone. ``phases`` is a ``phase_map`` (or
    the path of the JSON ``write_phase_map`` wrote) of the program that ran;
    without one every operation is ``unattributed`` and only the kernels are
    told apart. ``program`` keeps the operations that ran inside programs
    whose name holds it (``train_step``, ``paged_step``); ``steps`` is how
    many steps the trace holds. Returns::

        {"device": plane name, "profile_start_ns": ..., "steps": n, "busy_ms_per_step": ...,
         "phases": [{"phase", "direction", "time_frac", "ms_per_step", "n_per_step"}],
         "kernels": [{"kernel", "time_frac", "ms_per_step", "n_per_step"}]}

    ``phases`` rows sum to the device's busy time inside those programs; a
    kernel is a custom call, named as the program named it
    (``pallas_call(name=...)``)."""
    import jax

    if isinstance(phases, str):
        with open(phases, encoding="utf-8") as f:
            phases = json.load(f)["phases"]
    phases = phases or {}
    data = jax.profiler.ProfileData.from_file(_newest_xplane(trace_dir))
    plane = next(
        (p for p in data.planes if p.name.startswith("/device:") and any(l.name == "XLA Ops" for l in p.lines)),
        None,
    )
    if plane is None:
        raise ValueError("no device plane with an 'XLA Ops' line in this trace")
    lines = {l.name: l for l in plane.lines}
    ops = [(e.start_ns, e.start_ns + e.duration_ns, e.name) for e in lines["XLA Ops"].events]
    if program is not None and "XLA Modules" in lines:
        runs = sorted((e.start_ns, e.start_ns + e.duration_ns) for e in lines["XLA Modules"].events if program in e.name)
        starts = [a for a, _ in runs]
        # an operation belongs to the run it started in (ends are rounded and may pass the run's by a nanosecond)
        in_run = lambda t: (i := bisect.bisect_right(starts, t) - 1) >= 0 and t < runs[i][1]
        ops = [o for o in ops if in_run(o[0])]
    by_phase = collections.defaultdict(lambda: [0, 0])
    by_kernel = collections.defaultdict(lambda: [0, 0])
    for name, ns in _self_times(ops):
        m = _IDENT.search(name)
        instruction = m.group(0) if m else name
        phase, direction = phases.get(instruction) or (None, "-")
        row = by_phase[(phase or "unattributed", direction)]
        row[0] += ns
        row[1] += 1
        if " custom-call(" in name:
            row = by_kernel[re.sub(r"\.\d+$", "", instruction)]  # flash_fwd.3 -> flash_fwd
            row[0] += ns
            row[1] += 1
    total = sum(ns for ns, _ in by_phase.values()) or 1
    rows = lambda agg, key: [
        {**key(k), "time_frac": ns / total, "ms_per_step": ns / 1e6 / steps, "n_per_step": n // steps}
        for k, (ns, n) in sorted(agg.items(), key=lambda kv: -kv[1][0])
    ]
    started = [dict(p.stats).get("profile_start_time") for p in data.planes if p.name == "Task Environment"]
    return {
        "device": plane.name,
        # Unix nanoseconds of the profile's time zero, where the runtime wrote it: an
        # event at ``t`` ns lies at ``profile_start_ns + t``, the journal's ``ts`` x 1e9
        "profile_start_ns": started[0] if started else None,
        "steps": steps,
        "busy_ms_per_step": total / 1e6 / steps if by_phase else 0.0,
        "phases": rows(by_phase, lambda k: {"phase": k[0], "direction": k[1]}),
        "kernels": rows(by_kernel, lambda k: {"kernel": k}),
    }


def format_phase_table(table: dict, min_frac: float = 0.001) -> str:
    """Human-readable phase table (what scripts/analyze_trace.py prints)."""
    out = [
        f"device: {table['device']}  {table['busy_ms_per_step']:.2f} ms/step busy over {table['steps']} step(s)",
        f"{'phase':<16}{'direction':<11}{'time%':>7}{'ms/step':>10}{'n/step':>8}",
    ]
    for r in table["phases"]:
        if r["time_frac"] >= min_frac:
            out.append(
                f"{r['phase']:<16}{r['direction']:<11}{r['time_frac'] * 100:>6.1f}%{r['ms_per_step']:>10.3f}{r['n_per_step']:>8}"
            )
    if table["kernels"]:
        out.append(f"{'kernel':<27}{'time%':>7}{'ms/step':>10}{'n/step':>8}")
        for r in table["kernels"]:
            out.append(
                f"{r['kernel']:<27}{r['time_frac'] * 100:>6.1f}%{r['ms_per_step']:>10.3f}{r['n_per_step']:>8}"
            )
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
