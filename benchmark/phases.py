"""Device time by the program's own phases, and idle time by its own spans.

The profile names a device operation by its HLO instruction (``%fusion.129 =
...``), numbered anew by every compile. The program says which phase each
instruction belongs to: with a journal armed, ``PrecompiledStep.precompile``
writes ``{"program", "phases": {instruction: [phase, direction]}}`` beside the
journal and names the file in its ``compile`` span (``phases``). Kernels need no
map: a Pallas kernel's instruction carries the ``name=`` its ``pallas_call`` was
given (``flash_fwd.1``).

A program that writes no map (an older one) has nothing to read here: every
function returns None and the reader leaves its metric out.
"""

from __future__ import annotations

import bisect
import json
import re

from benchmark import readers, trace

_INSTRUCTION = re.compile(r"[\w.\-]+")
#: journal spans that say what state a request was in, not what the host thread did
_NOT_HOST_WORK = ("queue_wait",)


def load_map(run, program: str):
    """The phase map of the compiled step whose name holds ``program``, found
    through the ``compile`` span the program wrote; None without one."""
    for s in run.get("spans") or ():
        if s["kind"] == "compile" and s.get("phases") and program in (s.get("label") or ""):
            try:
                with open(s["phases"], encoding="utf-8") as f:
                    return json.load(f)["phases"]
            except (OSError, ValueError, KeyError):
                continue
    return None


def self_times(events) -> list:
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


def program_ops(run, program: str):
    """``(runs, [(instruction, ns, is a custom call)])``: how often programs
    whose name holds ``program`` ran on the first chip inside the traced
    window, and the operations that started inside them with the time each
    took itself. Kept on ``run``: several readers ask for the same program."""
    traced = run.get("trace")
    if not traced or not traced.get("ops") or not traced["ops"][0]:
        return None
    kept = run.setdefault("_program_ops", {})
    if program in kept:
        return kept[program]
    lo, hi = traced["window_ns"]
    runs = sorted((a, b) for a, b, n in traced["modules"][0] if program in n.split("(")[0] and a >= lo and b <= hi)
    starts = [a for a, _ in runs]

    def inside(t):  # an operation's end is rounded and may pass its program's by a nanosecond: go by its start
        i = bisect.bisect_right(starts, t) - 1
        return i >= 0 and t < runs[i][1]

    ops = self_times([e for e in traced["ops"][0] if inside(e[0])])
    kept[program] = (len(runs), [(_INSTRUCTION.search(n).group(0), ns, " custom-call(" in n)
                                 for n, ns in ops if _INSTRUCTION.search(n)]) if runs else None
    return kept[program]


def by_phase(run, program: str):
    """``{"steps", "busy_ns", "phases": {(phase, direction): ns}}`` over the
    traced runs of ``program``; operations the map gives no phase are under
    ``("unattributed", "-")``. None without a map or a device profile."""
    phases, found = load_map(run, program), program_ops(run, program)
    if phases is None or found is None:
        return None
    steps, ops = found
    out = {}
    for instruction, ns, _ in ops:
        phase, direction = phases.get(instruction) or (None, "-")
        key = (phase or "unattributed", direction)
        out[key] = out.get(key, 0) + ns
    return {"steps": steps, "busy_ns": sum(out.values()), "phases": out}


def phase_share(run, program: str, wanted) -> float | None:
    """Per cent of the traced steps' busy time spent in the phases ``wanted``."""
    table = by_phase(run, program)
    if table is None or not table["busy_ns"]:
        return None
    return 100.0 * sum(ns for (phase, _), ns in table["phases"].items() if phase in wanted) / table["busy_ns"]


def kernels(run, program: str):
    """``{"steps", "busy_ns", "kernels": {name: ns}}``: the custom calls of the
    traced runs of ``program`` by the name the program gave them
    (``flash_fwd.1`` -> ``flash_fwd``)."""
    found = program_ops(run, program)
    if found is None:
        return None
    steps, ops = found
    out = {}
    for instruction, ns, is_call in ops:
        if is_call:
            name = re.sub(r"\.\d+$", "", instruction)
            out[name] = out.get(name, 0) + ns
    return {"steps": steps, "busy_ns": sum(ns for _, ns, _ in ops), "kernels": out}


def kernel_ms_per_step(run, program: str, kernel: str) -> float | None:
    table = kernels(run, program)
    if table is None or kernel not in table["kernels"]:
        return None
    return table["kernels"][kernel] * 1e-6 / table["steps"]


def idle_by_span(run):
    """Seconds of the first chip's idle time in the traced window under each
    kind of the program's journal spans, the innermost (shortest) where several
    are open, ``other`` where none is. The spans are on ``perf_counter``; the
    profile's window opened at ``run["trace_span"][0]``."""
    traced, at = run.get("trace"), (run.get("trace_span") or [None])[0]
    if not traced or not traced.get("ops") or not traced["ops"][0] or at is None or not run.get("spans"):
        return None
    lo, hi = traced["window_ns"]
    busy = trace.union(trace.clip([(a, b) for a, b, _ in traced["ops"][0]], lo, hi))
    gaps, t = [], lo
    for a, b in busy + [[hi, hi]]:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    spans = [(lo + (s["start"] - at) / trace.NS, lo + (s["end"] - at) / trace.NS, "bench:" + s["kind"])
             for s in run["spans"] if s["kind"] not in _NOT_HOST_WORK and s["end"] > s["start"]]
    spans = [x for x in spans if x[1] > lo and x[0] < hi]  # the journal holds the whole run, the profile seconds of it
    return {k[len("host:"):]: v * trace.NS for k, v in trace.attribute(gaps, spans).items()}


# ------------------------------------------------------------ serve call spans


def span_ms_p50(run, kind: str) -> float | None:
    """Median length, in ms, of the window's journal spans of ``kind``."""
    if not run.get("spans"):
        return None
    mine = [s for s in readers.in_window(run, run["spans"]) if s["kind"] == kind]
    return readers.percentile([(s["end"] - s["start"]) * 1e3 for s in mine], 50)


#: the spans inside an ``engine_step`` that are not its bookkeeping
_CALL_KINDS = ("call_build", "prefill", "decode_batch", "draft", "verify", "medusa")


def step_bookkeeping_ms_p50(run) -> float | None:
    """Median, over the window's ``engine_step`` spans, of the step minus the
    ``call_build`` and device-call spans inside it: admission, expiry, the
    scheduler, the ledger, token bookkeeping."""
    if not run.get("spans"):
        return None
    spans = sorted(readers.in_window(run, run["spans"]), key=lambda s: s["start"])
    steps = [s for s in spans if s["kind"] == "engine_step"]
    if not steps:
        return None
    inner = [s for s in spans if s["kind"] in _CALL_KINDS]
    starts = [s["start"] for s in inner]
    out = []
    for step in steps:
        i = bisect.bisect_left(starts, step["start"])
        inside = 0.0
        while i < len(inner) and inner[i]["start"] < step["end"]:
            inside += inner[i]["end"] - inner[i]["start"]
            i += 1
        out.append((step["end"] - step["start"] - inside) * 1e3)
    return readers.percentile(out, 50)
