"""Arithmetic the metric readers share: percentiles over all requests, the
serve calls of a window with their counts, the pairing of calls and traced
programs. A reader that finds nothing to read returns None.

``run`` is what a driver returned plus what ``run.py`` added: ``window``
(``perf_counter`` at the opening and the close), ``requests`` (one row each),
``steps``, ``spans`` (the program's journal, ``--trace 1`` only), ``trace`` (the
reduced profile), ``hf``, ``config``, ``mix``, ``peaks``, ``chips``.
"""

from __future__ import annotations

import math

from benchmark import counts


def percentile(values, q: float):
    """Linear interpolation between closest ranks, over every value; +inf
    (a request that failed) stays in, and a percentile that lands on it is
    not a number to report."""
    values = sorted(values)
    if not values:
        return None
    pos = (len(values) - 1) * q / 100.0
    lo, hi = math.floor(pos), math.ceil(pos)
    if math.isinf(values[hi]):
        return None
    return values[lo] + (values[hi] - values[lo]) * (pos - lo)


def measured(run) -> list:
    return [r for r in run["requests"] if r["measured"]]


def ttfts(run) -> list:
    return [(r["first_token"] - r["due"]) if r["first_token"] is not None and r["status"] == "ok" else math.inf
            for r in measured(run)]


def tpots_ms(run) -> list:
    out = []
    for r in measured(run):
        if r["answer_len"] < 2:
            continue
        ok = r["status"] == "ok" and r["tokens"] >= 2
        out.append((r["finished"] - r["first_token"]) / (r["tokens"] - 1) * 1e3 if ok else math.inf)
    return out


def in_window(run, spans):
    lo, hi = run["window"]
    return [s for s in spans if s["start"] >= lo and s["end"] <= hi]


def serve_calls(run):
    """Every device call of the engine since the journal was armed, in order,
    with the operations and bytes ``counts.py`` gives its shapes. A decode
    row's position is its prompt plus the decode steps it has had."""
    if not run.get("spans"):
        return None
    if "_serve_calls" in run:
        return run["_serve_calls"]
    hf, layers = run["hf"], run["engine_shapes"]["num_layers"]
    prompt_len = {r["rid"]: r["prompt_len"] for r in run["requests"] if r["rid"] is not None}
    decoded = {}
    calls = []
    for s in sorted((s for s in run["spans"] if s["kind"] in ("prefill", "decode_batch")), key=lambda s: s["start"]):
        if s["kind"] == "prefill":
            rid, chunk = s["request"], s["chunk"]
            if rid not in prompt_len:
                continue
            final = s["fill"] >= prompt_len[rid]
            flops, nbytes = counts.prefill_call(hf, layers, s["fill"] - chunk, chunk, final)
            calls.append({"kind": "prefill", "start": s["start"], "end": s["end"], "flops": flops, "bytes": nbytes,
                          "tokens": chunk})
        else:
            rids = [int(t.split("-", 1)[1]) for t in s["traces"]]
            if any(r not in prompt_len for r in rids):
                continue
            fills = [prompt_len[r] + decoded.get(r, 0) for r in rids]
            for r in rids:
                decoded[r] = decoded.get(r, 0) + 1
            flops, nbytes = counts.decode_call(hf, layers, fills)
            calls.append({"kind": "decode", "start": s["start"], "end": s["end"], "flops": flops, "bytes": nbytes,
                          "tokens": len(rids)})
    run["_serve_calls"] = calls
    return calls


def serve_host_ms_per_step(run):
    """Median, over the window's engine steps, of the step's span minus the
    device-call spans inside it."""
    calls = serve_calls(run)
    if calls is None:
        return None
    lo, hi = run["window"]
    steps = [(a, b) for a, b in run["steps"] if a >= lo and b <= hi]
    if not steps:
        return None
    inside = [0.0] * len(steps)
    i = 0
    for c in calls:
        while i < len(steps) and steps[i][1] < c["start"]:
            i += 1
        if i < len(steps) and steps[i][0] <= c["start"] and c["end"] <= steps[i][1]:
            inside[i] += c["end"] - c["start"]
    return percentile([(b - a - x) * 1e3 for (a, b), x in zip(steps, inside)], 50)


def serve_call_ms_p50(run, kind):
    calls = serve_calls(run)
    if calls is None:
        return None
    return percentile([(c["end"] - c["start"]) * 1e3 for c in in_window(run, calls) if c["kind"] == kind], 50)


def serve_step_mfu(run):
    """Model operations of every token processed in the window, over the
    window times the chips' bf16 peak, in per cent."""
    calls = serve_calls(run)
    if calls is None:
        return None
    lo, hi = run["window"]
    flops = sum(c["flops"] for c in in_window(run, calls))
    return 100.0 * flops / ((hi - lo) * run["peaks"]["bf16_flops"] * run["chips"]) if flops else None


def traced_pairs(run, name_part: str, calls):
    """(call, program's device seconds) for the calls made while the profiler
    ran, paired in order with the traced programs whose name holds
    ``name_part``; None where the two lists do not pair up."""
    trace, span = run.get("trace"), run.get("trace_span")
    if not trace or not trace["modules"] or not span or span[1] is None or calls is None:
        return None
    mine = [c for c in calls if c["start"] >= span[0] and c["end"] <= span[1]]
    programs = [(a, b) for a, b, n in trace["modules"][0] if name_part in n]
    if not programs:
        # a jitted functools.partial has no name of its own ("jit__unknown"): of the programs that
        # ran exactly as often as the calls were made (the key's fold-in does too), the one that took longest
        by_name = {}
        for a, b, n in trace["modules"][0]:
            by_name.setdefault(n.split("(")[0], []).append((a, b))
        same = [v for v in by_name.values() if len(v) == len(mine)]
        programs = max(same, key=lambda v: sum(b - a for a, b in v)) if same else []
    if not mine or len(mine) != len(programs):
        return None
    return [(c, (b - a) * 1e-9) for c, (a, b) in zip(mine, programs)]


def serve_roofline(run, kind):
    """Least time the chip could take for the traced calls of ``kind`` over the
    device time their programs took, in per cent."""
    pairs = traced_pairs(run, "paged_step", serve_calls(run))
    if pairs is None:
        return None
    pairs = [(c, t) for c, t in pairs if c["kind"] == kind]
    took = sum(t for _, t in pairs)
    if not took:
        return None
    least = sum(counts.roofline_seconds(c["flops"], c["bytes"], run["peaks"]) for c, _ in pairs)
    return 100.0 * least / took


def compiles_in_window(run):
    before, after = run["cache_events_at_open"], run["cache_events_at_close"]
    n = sum(after.values()) - sum(before.values())
    if run.get("signatures") and None not in run["signatures"]:
        n += run["signatures"][1] - run["signatures"][0]
    return n


# ------------------------------------------------------------------ training


def train_tokens_per_s(run):
    lo, hi = run["window"]
    return run["steps_in_window"] * run["tokens_per_step"] / (hi - lo) if run["steps_in_window"] else None


def train_step_mfu(run):
    """Forward and backward operations a step needs, times the steps of the
    window, over the window times the chips' bf16 peak, in per cent."""
    shapes = run["train_shapes"]
    flops = counts.train_flops_per_step(run["hf"], shapes["num_layers"], shapes["batch"], shapes["seq_len"])
    lo, hi = run["window"]
    if not run["steps_in_window"]:
        return None
    return 100.0 * flops * run["steps_in_window"] / ((hi - lo) * run["peaks"]["bf16_flops"] * run["chips"])


def is_kernel(op_name: str) -> bool:
    """A Pallas kernel in the trace: an HLO custom call."""
    return "custom-call" in op_name.split("(")[0] or " custom-call(" in op_name


def flash_attn_roofline(run):
    """Least time a chip could take for the attention kernels of the traced
    steps over the device time the kernels took there, in per cent."""
    trace = run.get("trace")
    if not trace or not trace["ops"]:
        return None
    lo, hi = trace["window_ns"]
    inside = [(a, b, n.split("(")[0]) for a, b, n in trace["modules"][0] if a >= lo and b <= hi]
    names = {n for _, _, n in inside if "train_step" in n}
    if not names and inside:  # no name to go by: the step is the program that took most of the time
        totals = {}
        for a, b, n in inside:
            totals[n] = totals.get(n, 0) + (b - a)
        names = {max(totals, key=totals.get)}
    steps = sum(1 for _, _, n in inside if n in names)
    took = [sum(b - a for a, b, n in ops if is_kernel(n) and a >= lo and b <= hi) * 1e-9 for ops in trace["ops"]]
    took = sum(took) / len(took)
    if not steps or not took:
        return None
    shapes, chips = run["train_shapes"], run["chips"]
    flops = counts.flash_flops_per_step(run["hf"], shapes["num_layers"], shapes["batch"], shapes["seq_len"]) / chips
    nbytes = counts.flash_bytes_per_step(run["hf"], shapes["num_layers"], shapes["batch"], shapes["seq_len"]) / chips
    return 100.0 * steps * counts.roofline_seconds(flops, nbytes, run["peaks"]) / took
