#!/usr/bin/env python3
"""What a ``benchmark`` PR runs on the chip when it defines or re-defines a
serve cell; the benchmark's own runs never call it.

    python benchmark/calibrate.py sweep --workload m7b-serve-chat --rates 2,3,4,5,6 --seconds 30
    python benchmark/calibrate.py gaps  --workload m7b-serve-chat --seeds 11,12,13 --seconds 10 --control int8
    python benchmark/calibrate.py trainread --workload m7b-train-8k --seeds 11,12,13 --seconds 2 --control int8 \
        --faults half_batch,unchanged_state

``sweep`` finds the knee of a paced mix: one warm engine, one window per rate
(same seed, same multiset per second), and for each rate whether the backlog
grew: requests unfinished at the close, the seconds the drain took, TTFT and
TPOT. The knee is the highest rate at which the backlog does not grow over a
window; the cell's rate is a stated share of it and goes into the mix's file.

``gaps`` reads the number ``correct`` compares, seed by seed, on one engine
whose weights are replaced for each seed: the program's reading (what a run
would compare) and, with ``--control``, the reading of the reference computed
in that lower precision and put in the program's place.
"""

from __future__ import annotations

import argparse
import copy
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import readers, reference, run as bench_run, weights  # noqa: E402


def context(args, seed):
    bench = bench_run.load_json(bench_run.ROOT, "BENCHMARK.json")
    cell = next(w for w in bench["workloads"] if w["name"] == args.workload)
    ns = argparse.Namespace(workload=args.workload, seed=seed, seconds=args.seconds, trace=0)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(bench_run.ROOT, ".jax_cache"))
    ctx = bench_run.Context(ns, bench, cell)
    ctx.devices = bench_run.devices_for(int(cell["chips"]))
    return ctx


def sweep(args):
    ctx = context(args, args.seed)
    serve = bench_run.load_module("drivers", "serve")
    model, engine = serve.setup(ctx)
    for rate in [float(r) for r in args.rates.split(",")]:
        mix = copy.deepcopy(ctx.mix)
        mix["arrivals"]["rate_per_s"] = rate
        result, _ = serve.window(ctx, model, engine, mix)
        close = result["window"][1]
        rows = readers.measured(result)
        late = [r for r in rows if r["finished"] is None or r["finished"] > close]
        drain = max((r["finished"] or float("inf")) for r in rows) - close
        busy = sum(b - a for a, b in result["steps"] if a >= result["window"][0] and b <= close)
        line = {
            "rate_per_s": rate, "requests": len(rows), "failed": result["failed"],
            "unfinished_at_close": len(late), "drain_s": drain,
            "ttft_p50_s": readers.percentile(readers.ttfts(result), 50),
            "ttft_p95_s": readers.percentile(readers.ttfts(result), 95),
            "tpot_p90_ms": readers.percentile(readers.tpots_ms(result), 90),
            "queue_wait_p90_s": readers.percentile([r["admitted"] - r["arrival"] for r in rows if r["admitted"]], 90),
            "engine_steps_in_window": sum(a >= result["window"][0] and b <= close for a, b in result["steps"]),
            "share_of_window_inside_step": busy / (close - result["window"][0]),
        }
        print(json.dumps(line), flush=True)


def windows(args):
    """Several windows on one warm engine, each a (label, overrides of the
    mix, seed) of ``--plan`` (JSON), every request's row written to
    ``chiprun_out/windows.jsonl``: for looking at what makes a metric repeat."""
    serve = bench_run.load_module("drivers", "serve")
    plan = json.loads(args.plan)
    ctx = context(args, plan[0]["seed"])
    model, engine = serve.setup(ctx)
    os.makedirs(os.path.join(bench_run.ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(bench_run.ROOT, "chiprun_out", "windows.jsonl"), "a") as out:
        for item in plan:
            mix = copy.deepcopy(ctx.mix)
            for key, value in item.get("mix", {}).items():
                if isinstance(value, dict):
                    mix[key] = {**mix.get(key, {}), **value}
                else:
                    mix[key] = value
            ctx.seed = item["seed"]
            result, _ = serve.window(ctx, model, engine, mix)
            rows = [{k: r[k] for k in ("index", "measured", "due", "submitted", "prompt_len", "answer_len", "status",
                                       "admitted", "first_token", "finished", "tokens")} for r in result["requests"]]
            line = {"label": item["label"], "seed": item["seed"], "window": result["window"], "rows": rows,
                    "ttft_p50_s": readers.percentile(readers.ttfts(result), 50),
                    "tpot_p90_ms": readers.percentile(readers.tpots_ms(result), 90)}
            out.write(json.dumps(line) + "\n")
            out.flush()
            print(json.dumps({k: v for k, v in line.items() if k != "rows"}), flush=True)


def gaps(args):
    import jax
    import jax.numpy as jnp

    serve = bench_run.load_module("drivers", "serve")
    seeds = [int(s) for s in args.seeds.split(",")]
    ctx = context(args, seeds[0])
    model, engine = serve.setup(ctx)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    role = ctx.config["serve"]
    for seed in seeds:
        ctx.seed = seed
        engine.params = None
        gc.collect()
        engine.params = weights.tree_like(seed, shapes, jnp.bfloat16)
        t0 = time.perf_counter()
        result, sample = serve.window(ctx, model, engine, ctx.mix)
        held, engine.params = engine.params, None
        del held
        gc.collect()
        line = {"seed": seed, "attempted": result["attempted"], "failed": result["failed"],
                "sampled_tokens": int(sum(len(s[1]) for s in sample)), "window_and_drain_s": time.perf_counter() - t0}
        if not sample:
            print(json.dumps({**line, "note": "no request finished inside the window: nothing to compare"}), flush=True)
            continue
        t0 = time.perf_counter()
        program = reference.served_token_gaps(ctx.hf, role["num_hidden_layers"], seed, sample)
        line["reference_s"] = time.perf_counter() - t0
        line["program_gap_max"] = float(max(g.max() for g in program))
        line["program_gap_over_0.01"] = int(sum((g > 0.01).sum() for g in program))
        line["program_gap_mean"] = float(sum(g.sum() for g in program) / sum(len(g) for g in program))
        if args.control:
            control = reference.served_token_gaps(ctx.hf, role["num_hidden_layers"], seed, sample, precision=args.control)
            line[f"{args.control}_gap_max"] = float(max(g.max() for g in control))
            line[f"{args.control}_gap_over_0.01"] = int(sum((g > 0.01).sum() for g in control))
            line[f"{args.control}_gap_mean"] = float(sum(g.sum() for g in control) / sum(len(g) for g in control))
        print(json.dumps(line), flush=True)


def trainread(args):
    """Per seed: the program's first steps against the reference, then the
    reference in ``--control`` precision and with each of ``--faults`` planted,
    put in the program's place."""
    train = bench_run.load_module("drivers", "train")
    for seed in [int(s) for s in args.seeds.split(",")]:
        ctx = context(args, seed)
        ctx.seconds = args.seconds
        result = train.run(ctx)
        role = ctx.config["train"]
        batches = train.Feed(seed, ctx.hf["vocab_size"], ctx.mix["batch"], ctx.mix["seq_len"], 0, 0).fed
        t0 = time.perf_counter()
        ref = reference.train_steps(ctx.hf, role["num_hidden_layers"], seed, batches, ctx.mix)
        took = time.perf_counter() - t0
        line = {"seed": seed, "reference_s": took, "program": train.compare(result["program_readings"], ref),
                "tokens_per_s": readers.train_tokens_per_s(result), "losses": result["program_readings"]["loss"]}
        stand_ins = ([args.control] if args.control else []) + [f for f in args.faults.split(",") if f]
        for what in stand_ins if seed in [int(s) for s in (args.stand_in_seeds or args.seeds).split(",")] else []:
            kw = {"precision": what} if what == args.control else {"fault": what}
            stand_in = reference.train_steps(ctx.hf, role["num_hidden_layers"], seed, batches, ctx.mix, **kw)
            line[what] = train.compare(stand_in, ref)
        print(json.dumps(line), flush=True)


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("what", choices=("sweep", "gaps", "trainread", "windows"))
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seconds", type=float, default=30.0)
    parser.add_argument("--seed", type=int, default=1)
    parser.add_argument("--rates", default="2,3,4,5,6")
    parser.add_argument("--seeds", default="1,2,3")
    parser.add_argument("--control", default=None, choices=(None, "int8"))
    parser.add_argument("--faults", default="")
    parser.add_argument("--plan", default="[]")
    parser.add_argument("--stand-in-seeds", default=None, help="trainread: the seeds on which control and faults are read too")
    args = parser.parse_args()
    {"sweep": sweep, "gaps": gaps, "trainread": trainread, "windows": windows}[args.what](args)


if __name__ == "__main__":
    main()
