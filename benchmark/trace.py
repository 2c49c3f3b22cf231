"""From an ``.xplane.pb`` to numbers, with nothing but ``jax.profiler.ProfileData``.

What a TPU trace holds (looked at by hand, PR 24): one plane ``/device:TPU:<n>``
per chip with the lines ``XLA Modules`` (one event per executed program,
``jit_<name>(<hash>)``), ``XLA Ops`` (one event per HLO operation, named by its
HLO text ``%fusion.12 = bf16[16,4096]{...} fusion(...)``) and ``Async XLA Ops``
(copies and collectives in flight, overlapping the others); and the plane
``/host:CPU`` whose line ``python`` holds the ``jax.profiler.TraceAnnotation``s
the drivers write (``bench:<what>``). Times are nanoseconds on one axis; the
device's clock sits about a millisecond off the host's, so ``align`` shifts the
device's events by the smallest amount that lets no program start before the
host call that launched it. The shift matters only to which annotation a gap
falls under; busy time and the window's length do not depend on it.

- busy: the union of the intervals of ``XLA Ops`` events, per chip;
- window: first to last instant of the profile that any kept event covers, or
  the ``bench:traced`` annotation where the driver wrote one;
- idle gaps: the window minus busy, each gap given to the innermost ``bench:``
  annotation over its midpoint (``host:<what>``), ``host:other`` without one.
"""

from __future__ import annotations

import re

NS = 1e-9
_OP = re.compile(r"^%?([\w.\-]+)(?:\s*=\s*(?:\()?(\w+)\[([\d,]*)\])?")


def op_label(name: str) -> str:
    """``%fusion.12 = bf16[16,4096]{1,0:T(8,128)} fusion(...)`` -> ``fusion.12_bf16_16_4096_``."""
    m = _OP.match(name)
    if not m:
        return name[:64]
    label = m.group(1)
    if m.group(2):
        label += f"_{m.group(2)}_{m.group(3).replace(',', '_')}_"
    return label


def load(path: str) -> dict:
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    chips, annotations, launches = [], [], []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            chip = {"ops": [], "modules": [], "async": []}
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules", "Async XLA Ops": "async"}.get(line.name)
                if key:
                    chip[key] = sorted((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name) for ev in line.events)
            chips.append(chip)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench:"):
                        annotations.append((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name))
                    elif ev.name == "PJRT_LoadedExecutable_Execute":
                        launches.append(ev.start_ns)
    return {"chips": chips, "annotations": sorted(annotations), "launches": sorted(launches)}


def union(intervals) -> list:
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def clip(intervals, lo, hi) -> list:
    return [(max(a, lo), min(b, hi)) for a, b in intervals if min(b, hi) > max(a, lo)]


def align(loaded: dict) -> float:
    """Nanoseconds to add to device times. The host's ``PJRT_LoadedExecutable_Execute``
    events and the first chip's programs come in the same order, and no program
    starts before the call that launched it: the largest lead of a program over
    its call is the least the device's clock is behind (0 where the two lists
    do not pair up)."""
    if not loaded["chips"]:
        return 0.0
    modules, launches = loaded["chips"][0]["modules"], loaded["launches"]
    if not modules or len(modules) != len(launches):
        return 0.0
    return max(launch - start for launch, (start, _, _) in zip(launches, modules))


def attribute(gaps, annotations) -> dict:
    """Seconds of ``gaps`` under each annotation, the innermost (shortest)
    one where several are open; ``host:other`` where none is."""
    cuts = sorted({t for a, b in gaps for t in (a, b)} | {t for a, b, _ in annotations for t in (a, b)})
    by_host = {}
    gi = 0
    gaps = sorted(gaps)
    for lo, hi in zip(cuts, cuts[1:]):
        while gi < len(gaps) and gaps[gi][1] <= lo:
            gi += 1
        if gi == len(gaps) or not (gaps[gi][0] <= lo and hi <= gaps[gi][1]):
            continue
        over = [x for x in annotations if x[0] <= lo and hi <= x[1]]
        label = "host:" + (min(over, key=lambda x: x[1] - x[0])[2][len("bench:"):] if over else "other")
        by_host[label] = by_host.get(label, 0.0) + (hi - lo)
    return by_host


def reduce(path, chips: int = 1, spans=(), traced_at=None) -> dict:
    """The contract's ``busy_s``/``window_s``, the operations that took most
    device time, and the idle gaps by what the host was doing. ``spans`` are
    further host intervals ``(start_s, end_s, "bench:<what>")`` on the clock on
    which the ``bench:traced`` annotation opened at ``traced_at`` (the program's
    journal spans, which are not in the profile)."""
    empty = {"busy_s": 0.0, "window_s": 0.0, "device_ops": [], "idle_gaps": [], "modules": [], "ops": [],
             "async": [], "shift_ns": 0.0}
    if path is None:
        return empty
    loaded = load(path)
    if not loaded["chips"]:
        return empty
    shift = align(loaded)
    used = loaded["chips"][:chips]
    traced = [a for a in loaded["annotations"] if a[2] == "bench:traced"]
    if traced:
        lo, hi = traced[0][0], traced[-1][1]
    else:
        every = [(a + shift, b + shift) for chip in used for a, b, _ in chip["ops"] + chip["modules"]]
        every += [(a, b) for a, b, _ in loaded["annotations"]]
        lo, hi = min(a for a, _ in every), max(b for _, b in every)
    inner = [a for a in loaded["annotations"] if a[2] != "bench:traced"]
    if traced and traced_at is not None:
        inner += [(lo + (a - traced_at) / NS, lo + (b - traced_at) / NS, n) for a, b, n in spans]
    busy_per_chip, totals = [], {}
    for chip in used:
        ops = [(a + shift, b + shift, n) for a, b, n in chip["ops"]]
        busy = union(clip([(a, b) for a, b, _ in ops], lo, hi))
        busy_per_chip.append(sum(b - a for a, b in busy))
        for a, b, n in ops:
            d = min(b, hi) - max(a, lo)
            if d > 0:
                totals[op_label(n)] = totals.get(op_label(n), 0.0) + d / len(used)
    # gaps of the first chip, by what the host was doing in them
    first = union(clip([(a + shift, b + shift) for a, b, _ in used[0]["ops"]], lo, hi))
    gaps, at = [], lo
    for a, b in first + [[hi, hi]]:
        if a > at:
            gaps.append((at, a))
        at = max(at, b)
    top = lambda d: [[k, v * NS] for k, v in sorted(d.items(), key=lambda kv: -kv[1])]
    moved = lambda key: [[(a + shift, b + shift, n) for a, b, n in chip[key]] for chip in used]
    return {
        "busy_s": sum(busy_per_chip) / len(busy_per_chip) * NS,
        "window_s": (hi - lo) * NS,
        "device_ops": top(totals),
        "idle_gaps": top(attribute(gaps, inner)),
        "modules": moved("modules"), "ops": moved("ops"), "async": moved("async"),
        "window_ns": (lo, hi),
        "shift_ns": shift,
    }
