#!/usr/bin/env python3
"""``calibrate.py trainread`` for ``granite-train-8k``: what the PR that set the
cell's limits ran on the chip; the benchmark's own runs never call it.

    python benchmark/calibrate_granite.py --seeds 11,12,13 --seconds 2 --control int8 --faults half_batch --stand-in-seeds 11,12

Per seed, one line: the program's first steps against the reference (every
number ``correct`` compares), tokens per second, the window's largest
``ssm/state_absmax`` and the device's peak memory; on ``--stand-in-seeds`` also
the reference in ``--control`` precision and with each of ``--faults`` planted,
put in the program's place and judged as a run is
(``drivers/train_granite.judge``): its readings beside the cell's limits, and
the ``correct`` a run with those readings would print.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import calibrate, readers, reference_granite, run as bench_run  # noqa: E402
from benchmark.drivers import train as base  # noqa: E402


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", default="granite-train-8k")
    parser.add_argument("--seeds", default="1,2,3")
    parser.add_argument("--seconds", type=float, default=2.0)
    parser.add_argument("--control", default=None, choices=(None, "int8"))
    parser.add_argument("--faults", default="")
    parser.add_argument("--stand-in-seeds", default="")
    args = parser.parse_args()
    train = bench_run.load_module("drivers", "train_granite")
    stand_in_seeds = [int(s) for s in args.stand_in_seeds.split(",") if s]
    for seed in [int(s) for s in args.seeds.split(",")]:
        ctx = calibrate.context(args, seed)
        ctx.seconds = args.seconds
        result = train.run(ctx)
        line = {"seed": seed, "correct": all(c["ok"] for c in result["checks"].values()),
                "program": base.compare(result["program_readings"], ctx.reference),
                "setup_s": ctx.setup_s, "tokens_per_s": readers.train_tokens_per_s(result), "steps": result["steps_in_window"],
                "state_absmax_max": max(result["state_absmax"], default=None),
                "losses": result["program_readings"]["loss"], "memory_peak_bytes": result["memory_peak_bytes"]}
        if seed in stand_in_seeds:
            batches = base.Feed(seed, ctx.config["vocab_size"], ctx.mix["batch"], ctx.mix["seq_len"], 0, 0).fed
            for what in ([args.control] if args.control else []) + [f for f in args.faults.split(",") if f]:
                kw = {"precision": what} if what == args.control else {"fault": what}
                t0 = time.perf_counter()
                stand_in = reference_granite.train_steps(ctx.config, seed, batches, ctx.mix, **kw)
                checks = train.judge(ctx, stand_in)
                line[what] = {"correct": all(c["ok"] for c in checks.values()), "checks": checks,
                              "readings": base.compare(stand_in, ctx.reference)}
                ctx.note(f"stand-in {what} on seed {seed}: correct {line[what]['correct']}, {time.perf_counter() - t0:.1f}s")
        print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
