"""Device time by phase and by kernel of a ``jax.profiler`` trace — or, pointed
at a SERVE run's span journals, the per-request latency table.

Thin CLI over ``dmlcloud_tpu.utils.profiling.phase_table``, which reads the
trace's ``.xplane.pb`` with ``jax.profiler.ProfileData`` alone:

    python scripts/analyze_trace.py /tmp/tr --steps 30 --program train_step \
        --phases /tmp/run/telemetry/phases-MyStage.train_step-1.json

The profile names an operation by its HLO instruction and carries no scope; the
phase of each instruction comes from the phase map the program writes beside
its journal when telemetry is armed (``PrecompiledStep.precompile``), or that
``utils.profiling.write_phase_map`` / ``ServeEngine.phase_map`` give for any
compiled step. Without ``--phases`` everything is ``unattributed`` and only the
kernels, which carry their ``pallas_call(name=...)``, are told apart
(doc/observability.md, "Phases").

When the directory holds telemetry span journals instead (a serve run:
``journal-rank*.jsonl`` under it or its ``telemetry/``), the analysis
switches to the request plane — per-request TTFT/ITL percentiles derived
from the linked traces (doc/observability.md), with ``--tenant`` focusing
one tenant's requests. ITL is estimated from the gaps between successive
decode batches a request rode (the journal records batches, not tokens).
"""

import argparse
import json
import sys

sys.path.insert(0, __file__.rsplit("/", 2)[0])

from dmlcloud_tpu.utils.profiling import format_phase_table, phase_table  # noqa: E402

#: bump when the --json object's shape changes (consumers pin on this).
#: v3: a profiler trace gives the phase table ("table") where v2 gave the
#: tensorflow-read roofline ("peaks"/"rows"); the "serve" object is v2's.
JSON_SCHEMA_VERSION = 3

_BATCH_KINDS = ("decode_batch", "draft", "verify", "medusa")


def _pcts(vals):
    import numpy as np

    if not vals:
        return {"n": 0, "p50": None, "p90": None, "p99": None}
    return {
        "n": len(vals),
        "p50": round(float(np.percentile(vals, 50)), 3),
        "p90": round(float(np.percentile(vals, 90)), 3),
        "p99": round(float(np.percentile(vals, 99)), 3),
    }


def serve_summary(records, tenant=None):
    """Per-request latency scorecard from journal records: TTFT per trace
    (arrival -> end of its last prefill chunk, the step that samples the
    first token), ITL per trace (gaps between the ENDS of successive
    batch spans it rode), grouped overall and per tenant. ``tenant``
    narrows to one tenant's traces (requests with no tenant attr carry
    ``""``)."""
    from dmlcloud_tpu.telemetry.journal import linked_trace_report

    report = linked_trace_report(records)
    ttfts, itls = [], []
    tenants = {}
    kept = 0
    for tid, spans in report["traces"].items():
        ten = next(
            (str(s["tenant"]) for s in spans if s.get("tenant") not in (None,)),
            "",
        )
        if tenant is not None and ten != tenant:
            continue
        kept += 1
        t0 = min(s["ts"] for s in spans)
        prefills = [s for s in spans if s["kind"] == "prefill"]
        entry = tenants.setdefault(ten, {"ttft": [], "itl": []})
        if prefills:
            ttft_ms = (max(s["ts"] + s["dur"] for s in prefills) - t0) * 1e3
            ttfts.append(ttft_ms)
            entry["ttft"].append(ttft_ms)
        ends = sorted(
            s["ts"] + s["dur"] for s in spans if s["kind"] in _BATCH_KINDS
        )
        gaps = [(b - a) * 1e3 for a, b in zip(ends, ends[1:])]
        itls.extend(gaps)
        entry["itl"].extend(gaps)
    statuses = {}
    for tid, st in report["statuses"].items():
        key = st if st is not None else "ok"
        statuses[key] = statuses.get(key, 0) + 1
    return {
        "requests": kept,
        "spans": len(records),
        "orphan_spans": len(report["orphans"]),
        "statuses": statuses,
        "ttft_ms": _pcts(ttfts),
        "itl_ms": _pcts(itls),
        "tenants": {
            t: {"ttft_ms": _pcts(v["ttft"]), "itl_ms": _pcts(v["itl"])}
            for t, v in sorted(tenants.items())
        },
    }


def _format_serve(s):
    def row(name, p):
        f = lambda v: "      -" if v is None else f"{v:7.1f}"  # noqa: E731
        return f"  {name:<10} {p['n']:>5} {f(p['p50'])} {f(p['p90'])} {f(p['p99'])}"

    lines = [
        f"serve journal: {s['requests']} requests, {s['spans']} spans "
        f"({s['orphan_spans']} orphans), statuses {s['statuses']}",
        f"  {'':<10} {'n':>5} {'p50':>7} {'p90':>7} {'p99':>7}",
        row("ttft_ms", s["ttft_ms"]),
        row("itl_ms", s["itl_ms"]),
    ]
    for t, v in s["tenants"].items():
        lines.append(f"  tenant {t or '(default)'!r}:")
        lines.append(row("  ttft_ms", v["ttft_ms"]))
        lines.append(row("  itl_ms", v["itl_ms"]))
    return "\n".join(lines)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "trace_dir",
        help="directory passed to jax.profiler.trace, or a serve run dir "
        "with telemetry journals",
    )
    ap.add_argument("--steps", type=int, default=30, help="timed steps inside the trace")
    ap.add_argument("--phases", default=None, help="phase map (JSON) of the program that ran")
    ap.add_argument("--program", default=None, help="keep programs whose name holds this (train_step, paged_step)")
    ap.add_argument(
        "--tenant", default=None,
        help="serve journals: only this tenant's requests",
    )
    ap.add_argument(
        "--json", action="store_true",
        help='machine-readable output: {"version", "table"} for a profiler trace, '
        '{"version", "serve"} for serve journals',
    )
    args = ap.parse_args(argv)

    # serve-journal mode: span journals under the dir win over xplane
    from dmlcloud_tpu.telemetry.journal import load_journals

    try:
        records = load_journals(args.trace_dir)
    except FileNotFoundError:
        records = []
    if records:
        summary = serve_summary(records, tenant=args.tenant)
        if args.json:
            print(json.dumps({"version": JSON_SCHEMA_VERSION, "serve": summary},
                             sort_keys=True))
        else:
            print(_format_serve(summary))
        return 0
    if args.tenant is not None:
        print("analyze_trace: --tenant only applies to serve journals",
              file=sys.stderr)
        return 2

    table = phase_table(args.trace_dir, phases=args.phases, steps=args.steps, program=args.program)
    if not table["phases"]:
        # a device plane with zero op events: the traced region dispatched no
        # device work (trace() wrapped host-only code, or the steps never ran)
        print(
            f"analyze_trace: trace under {args.trace_dir} contains no XLA op rows — "
            "the traced region executed no device work. Wrap actual train steps "
            "in profiling.trace() and block_until_ready before closing it.",
            file=sys.stderr,
        )
        return 1
    if args.json:
        print(json.dumps({"version": JSON_SCHEMA_VERSION, "table": table}, sort_keys=True))
    else:
        print(format_phase_table(table))
    return 0


if __name__ == "__main__":
    sys.exit(main())
