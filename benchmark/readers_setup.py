"""What the eight ``setup_*`` metrics read: the program's ``setup``,
``compile`` and ``epoch`` spans (its journal, ``--trace 1`` only) inside the
set-up interval ``[window[0] - setup_s, window[0]]``, on the one
``perf_counter`` clock of the journal, ``run.py``'s process start and the
window. Spans that nest or overlap are counted once: a metric is the length
of the union of its intervals, cut to set-up.

The top-level parts (``import``, ``pipeline``, ``stage``, the epochs that end
before the window opens) and what no program span covers
(``outside_program_s``) add up to ``setup_s``. A run whose journal holds no
``setup`` span (an older program's, or another driver's) reads nothing.
"""

from __future__ import annotations

from benchmark import trace

#: the spans of the program's own set-up; a ``compile`` span lies inside ``stage``
_PROGRAM_KINDS = ("setup", "compile")


def interval(run):
    """``(process start, window opening)``, or None without set-up spans."""
    spans, window = run.get("spans"), run.get("window")
    if not spans or not window or run.get("setup_s") is None or not any(s["kind"] == "setup" for s in spans):
        return None
    return window[0] - run["setup_s"], window[0]


def seconds(run, intervals) -> float:
    lo, hi = interval(run)
    return sum(b - a for a, b in trace.union(trace.clip(intervals, lo, hi)))


def part(run, label: str):
    """Seconds under the ``setup`` spans labelled ``label``."""
    if interval(run) is None:
        return None
    found = [(s["start"], s["end"]) for s in run["spans"] if s["kind"] == "setup" and s["label"] == label]
    return seconds(run, found) if found else None


def compile_parts(run, lowering: bool):
    """Seconds of the ``compile`` spans' ``.lower()`` (their first ``lower_s``
    seconds), or of the rest: XLA's compile, or the cache's load when warm."""
    if interval(run) is None:
        return None
    found = [s for s in run["spans"] if s["kind"] == "compile" and "lower_s" in s]
    if not found:
        return None
    cut = lambda s: s["start"] + s["lower_s"]
    return seconds(run, [(s["start"], cut(s)) if lowering else (cut(s), s["end"]) for s in found])


def pre_window_epochs(run) -> list:
    """The ``epoch`` spans that end before the window opens: the checked steps
    and the lead-in, with what the driver reads between them."""
    opened = run["window"][0]
    return [(s["start"], s["end"]) for s in run["spans"] if s["kind"] == "epoch" and s["end"] <= opened]


def first_epochs(run):
    if interval(run) is None:
        return None
    found = pre_window_epochs(run)
    return seconds(run, found) if found else None


def outside_program(run):
    """Set-up less the union of the program's set-up and compile spans and the
    pre-window epochs: import of ``jax``, the backend's start, the harness's own
    imports and work."""
    span = interval(run)
    if span is None:
        return None
    mine = [(s["start"], s["end"]) for s in run["spans"] if s["kind"] in _PROGRAM_KINDS] + pre_window_epochs(run)
    return span[1] - span[0] - seconds(run, mine)
