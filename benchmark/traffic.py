"""The one traffic generator: a mix's data file in, a list of requests out.

A serve mix (``benchmark/traffic/<mix>.json``, ``"driver": "serve"``) states

- ``prompt_len`` / ``answer_len``: ``{"dist": "lognormal", "median", "sigma",
  "min", "max"}``, ``{"dist": "loguniform", "min", "max"}`` or ``{"dist":
  "fixed", "value"}``;
- ``arrivals``: ``{"kind": "poisson_fixed_count", "rate_per_s"}`` (N = round(rate
  x seconds) offsets uniform over the window, sorted: a Poisson process given its
  count), ``{"kind": "slotted", "rate_per_s"}`` (one arrival uniform inside each
  of N equal slots) or ``{"kind": "backlog", "requests_per_s", "cycle_requests"}``
  (everything due at the window's opening: a cycle of ``cycle_requests``
  requests repeated to about requests_per_s x seconds in all);
- ``lead_in_s`` (paced mixes) or ``lead_in_requests`` (backlog): unmeasured
  traffic of the same mix just before the window.

Lengths are not drawn: the N requests get the N quantile midpoints of the
distribution, paired with the arrival offsets by permutations drawn, like the
offsets, from the mix's ``schedule_seed``. Every run seed therefore offers the
same requests at the same spacing: the same multiset of prompt and answer
lengths, the same total of tokens, the same bunches. What the run's seed does
is turn the schedule (``generate``) and draw the token ids. Nothing here knows
a mix by name.
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np


@dataclasses.dataclass
class Request:
    index: int
    due_s: float  # offset from the window's opening; negative in the lead-in
    prompt: np.ndarray
    answer_len: int
    measured: bool


def quantile_lengths(spec: dict, n: int) -> np.ndarray:
    """The n quantile midpoints ((i + 0.5) / n) of ``spec``'s distribution, as
    whole numbers inside its bounds, in ascending order."""
    if n <= 0:
        return np.zeros(0, np.int64)
    q = (np.arange(n) + 0.5) / n
    dist = spec["dist"]
    if dist == "fixed":
        return np.full(n, int(spec["value"]), np.int64)
    if dist == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(p)) for p in q])
        x = float(spec["median"]) * np.exp(float(spec["sigma"]) * z)
    elif dist == "loguniform":
        lo, hi = math.log(spec["min"]), math.log(spec["max"])
        x = np.exp(lo + (hi - lo) * q)
    else:
        raise ValueError(f"unknown length distribution {dist!r}")
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def arrival_offsets(spec: dict, seconds: float, rng: np.random.Generator) -> np.ndarray:
    kind = spec["kind"]
    if kind == "backlog":
        return np.zeros(int(round(spec["requests_per_s"] * seconds)))
    n = int(round(spec["rate_per_s"] * seconds))
    if kind == "poisson_fixed_count":
        return np.sort(rng.uniform(0.0, seconds, n))
    if kind == "slotted":
        return (np.arange(n) + rng.uniform(0.0, 1.0, n)) * (seconds / max(n, 1))
    raise ValueError(f"unknown arrival kind {kind!r}")


def generate(mix: dict, seconds: float, seed: int, vocab: int, lead_out_s: float = 0.0) -> list:
    """Lead-in requests (due before 0, unmeasured), the window's, and for
    ``lead_out_s`` past the close the cycle going on (unmeasured: a traced run
    records its profile there).

    The schedule (offsets, and which offset gets which prompt and answer
    length) is drawn from the mix's own ``schedule_seed``, so it is one and
    the same for every run of the mix at this length. The run's seed turns it:
    a paced schedule is rotated round the window by a seeded offset (an
    arrival that passes the close comes round to the opening, and the lead-in
    is the stretch of the same cycle that precedes the opening), a backlog is
    put in a seeded order. The seed also draws every token id."""
    arrivals = mix["arrivals"]
    plan = np.random.default_rng([int(mix.get("schedule_seed", 0)), 0x5CED])
    offsets = arrival_offsets(arrivals, seconds, plan)
    n = len(offsets)
    rng = np.random.default_rng([int(seed), 0x7AFF1C])
    lead_s = float(mix.get("lead_in_s", 0.0))
    if arrivals["kind"] == "backlog":
        # a backlog is a short cycle of requests over and over, so that whatever stretch of it a
        # window gets through holds the same mix; the seed chooses where in the cycle it starts
        cycle = int(arrivals["cycle_requests"])
        n = cycle * max(1, round(n / cycle))
        base_p = plan.permutation(quantile_lengths(mix["prompt_len"], cycle))
        base_a = plan.permutation(quantile_lengths(mix["answer_len"], cycle))
        start = int(rng.integers(cycle))
        at = lambda j: (int(base_p[(start + j) % cycle]), int(base_a[(start + j) % cycle]))
        lead = [(-lead_s, *at(j)) for j in range(-int(mix.get("lead_in_requests", 0)), 0)]
        window = [(0.0, *at(j)) for j in range(n)]
        tail = []  # a backlog outlasts the window by design
    else:
        prompts = plan.permutation(quantile_lengths(mix["prompt_len"], n))
        answers = plan.permutation(quantile_lengths(mix["answer_len"], n))
        turned = (offsets + rng.uniform(0.0, seconds)) % seconds
        order = np.argsort(turned, kind="stable")
        window = [(float(turned[i]), int(prompts[i]), int(answers[i])) for i in order]
        lead = [(due - seconds, p, a) for due, p, a in window if due >= seconds - lead_s]
        tail = [(due + seconds, p, a) for due, p, a in window if due < lead_out_s]
    out = []
    for measured, rows in ((False, lead), (True, window), (False, tail)):
        for due, p, a in rows:
            out.append(Request(len(out), due, rng.integers(0, vocab, p, dtype=np.int32), a, measured))
    return out


def offered(requests) -> dict:
    """What a window offers, for the tests and the result line."""
    measured = [r for r in requests if r.measured]
    return {
        "requests": len(measured),
        "prompt_tokens": int(sum(len(r.prompt) for r in measured)),
        "answer_tokens": int(sum(r.answer_len for r in measured)),
    }
