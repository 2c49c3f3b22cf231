#!/usr/bin/env python3
"""``calibrate.py trainread`` for ``lfm2-train-8k``: what the PR that set the
cell's limits ran on the chip; the benchmark's own runs never call it.

    python benchmark/calibrate_lfm2.py --seeds 11,12,13 --seconds 2 --control int8 --faults half_batch --stand-in-seeds 11,12,13
    python benchmark/calibrate_lfm2.py --seeds 11,12,13 --seconds 10 --bias zero

Per seed, one line: the program's first steps against the reference (every
number ``correct`` compares), tokens per second, and the window's counters
(``moe/pairs_held`` mean, first and last quarter, ``moe/load_max_over_mean``
median); on ``--stand-in-seeds`` also the reference in ``--control`` precision
and with each of ``--faults`` planted, put in the program's place and judged
as a run is (``drivers/train_lfm2.judge``): its readings beside the cell's
limits, and the ``correct`` a run with those readings would print.
``--bias zero`` leaves ``expert_bias`` at nought, to read what a seed does to
the held load when nothing levels it.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

from benchmark import calibrate, readers, reference_lfm2, run as bench_run  # noqa: E402
from benchmark.drivers import train as base  # noqa: E402


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", default="lfm2-train-8k")
    parser.add_argument("--seeds", default="1,2,3")
    parser.add_argument("--seconds", type=float, default=2.0)
    parser.add_argument("--control", default=None, choices=(None, "int8"))
    parser.add_argument("--faults", default="")
    parser.add_argument("--stand-in-seeds", default="")
    parser.add_argument("--bias", default="balanced", choices=("balanced", "zero"))
    args = parser.parse_args()
    if args.bias == "zero":
        def no_bias(config, seed, batches):
            s = dict(reference_lfm2.spec(config))
            return {i: np.zeros(s["experts"], np.float32) for i in range(s["dense"], len(s["layers"]))}, {}

        reference_lfm2.balanced_expert_bias = no_bias
    train = bench_run.load_module("drivers", "train_lfm2")
    stand_in_seeds = [int(s) for s in args.stand_in_seeds.split(",") if s]
    for seed in [int(s) for s in args.seeds.split(",")]:
        ctx = calibrate.context(args, seed)
        ctx.seconds = args.seconds
        result = train.run(ctx)
        pairs, quarter = result["pairs_held"], max(len(result["pairs_held"]) // 4, 1)
        line = {"seed": seed, "bias": args.bias, "correct": all(c["ok"] for c in result["checks"].values()), "program": base.compare(result["program_readings"], ctx.reference),
                "setup_s": ctx.setup_s, "tokens_per_s": readers.train_tokens_per_s(result), "steps": result["steps_in_window"],
                "pairs_held_mean": statistics.fmean(pairs), "pairs_held_first_quarter": statistics.fmean(pairs[:quarter]),
                "pairs_held_last_quarter": statistics.fmean(pairs[-quarter:]),
                "load_max_over_mean_median": statistics.median(result["load_max_over_mean"]),
                "bias_gaps": result["bias_gaps"], "losses": result["program_readings"]["loss"],
                "memory_peak_bytes": result["memory_peak_bytes"]}
        if seed in stand_in_seeds:
            batches = base.Feed(seed, ctx.config["vocab_size"], ctx.mix["batch"], ctx.mix["seq_len"], 0, 0).fed
            for what in ([args.control] if args.control else []) + [f for f in args.faults.split(",") if f]:
                kw = {"precision": what} if what == args.control else {"fault": what}
                t0 = time.perf_counter()
                stand_in = reference_lfm2.train_steps(ctx.config, seed, batches, ctx.mix, ctx.biases, **kw)
                checks = train.judge(ctx, stand_in)
                line[what] = {"correct": all(c["ok"] for c in checks.values()), "checks": checks,
                              "readings": base.compare(stand_in, ctx.reference)}
                ctx.note(f"stand-in {what} on seed {seed}: correct {line[what]['correct']}, {time.perf_counter() - t0:.1f}s")
        print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
