#!/usr/bin/env python3
"""The benchmark's one command.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A new process: finds the cell in ``BENCHMARK.json``, reads its configuration
(``benchmark/configs/<config>.json``) and its traffic mix or training job
(``benchmark/traffic/<traffic>.json``), hands both to the driver the mix names
(``benchmark/drivers/<driver>.py``), and prints as the last line of standard
output the contract's JSON object. With ``--trace 0`` the metrics are the
cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics; either
way each metric is read by ``benchmark/metrics/<name>.py`` from the run's
records, and one that finds nothing to read is left out.

No chip, no run: any platform but ``tpu``, or fewer chips than the cell asks
for, ends with exit code 3 and no result line. A directory without the program
ends with exit code 2.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import glob  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
# started as a script, python puts benchmark/ itself on the path, where trace.py would hide the standard module
sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != HERE]
#: seconds of the window that a ``--trace 1`` run records with the profiler
TRACE_SECONDS = 3.0


def note(message: str) -> None:
    print(f"[benchmark] {message}", file=sys.stderr, flush=True)


def load_json(*parts) -> dict:
    with open(os.path.join(*parts), encoding="utf-8") as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``benchmark/<kind>/<name>.py``, found by the name ``BENCHMARK.json`` gives."""
    path = os.path.join(HERE, kind, f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"benchmark.{kind}.{name.replace('.', '_').replace('-', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def devices_for(chips: int):
    """The TPU chips the cell runs on, or exit 3."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        note(f"needs {chips} TPU chip(s); jax sees {len(devices)} x {devices[0].platform}: no run")
        sys.exit(3)
    return devices[:chips]


class Context:
    """What a driver is given, and the harness's own clock and profiler."""

    def __init__(self, args, bench, cell):
        self.args, self.bench, self.cell = args, bench, cell
        self.seed, self.seconds, self.trace = int(args.seed), float(args.seconds), bool(args.trace)
        config_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
        self.config = load_json(ROOT, config_entry["file"])
        self.mix = load_json(HERE, "traffic", f"{cell['traffic']}.json")
        #: the published keys as the file holds them (numbers, booleans, null)
        self.hf = {k: v for k, v in self.config.items() if not isinstance(v, (dict, list, str))}
        self.t_process = T_PROCESS
        self.trace_seconds = TRACE_SECONDS
        self.note = note
        self.setup_s = None
        self.trace_dir = None
        self.cache_events = {"hits": 0, "misses": 0}
        self.cache_events_at_open = self.cache_events_at_close = None

    def tmp_dir(self, name: str) -> str:
        path = os.path.join(ROOT, ".bench_tmp", f"{self.cell['name']}", name)
        os.makedirs(path, exist_ok=True)
        return path

    def listen_to_cache(self):
        import jax

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_events["hits"] += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.cache_events["misses"] += 1

        jax.monitoring.register_event_listener(on_event)

    def window_opened(self, now: float) -> None:
        """Set-up ends here: process start to the window's opening."""
        self.setup_s = now - self.t_process
        self.cache_events_at_open = dict(self.cache_events)

    def window_closed(self) -> None:
        self.cache_events_at_close = dict(self.cache_events)

    def start_trace(self) -> None:
        import jax

        self.trace_dir = self.tmp_dir("trace")
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # the host's TraceAnnotations are kept; a line per Python call is not
        jax.profiler.start_trace(self.trace_dir, profiler_options=options)

    def stop_trace(self) -> None:
        import jax

        jax.profiler.stop_trace()

    def maybe_trace(self, records) -> None:
        """For drivers whose window is one call: with ``--trace 1`` the profiler
        records its first ``trace_seconds``."""
        if not self.trace:
            return
        import jax

        self.start_trace()
        self._traced = jax.profiler.TraceAnnotation("bench:traced")
        records["trace_span"] = [time.perf_counter(), None]
        self._traced.__enter__()

    def end_trace(self, records) -> None:
        if not self.trace:
            return
        records["trace_span"][1] = time.perf_counter()
        self._traced.__exit__(None, None, None)
        self.stop_trace()

    def trace_file(self):
        if self.trace_dir is None:
            return None
        found = glob.glob(os.path.join(self.trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
        return found[0] if found else None

    def memory_peak_bytes(self) -> int:
        import jax

        return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in self.devices)


def read_metrics(ctx, run) -> dict:
    """The cell's metrics of this run's kind, each from its own reader."""
    kind = "per_layer" if ctx.trace else "end_to_end"
    out = {}
    for entry in ctx.bench[kind]:
        if "workloads" in entry and ctx.cell["name"] not in entry["workloads"]:
            continue
        value = load_module("metrics", entry["name"]).read(run)
        if value is not None:
            out[entry["name"]] = {"value": float(value), "unit": entry["unit"]}
    return out


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)

    bench = load_json(ROOT, "BENCHMARK.json")
    cell = next((w for w in bench["workloads"] if w["name"] == args.workload), None)
    if cell is None:
        note(f"no cell {args.workload!r} in BENCHMARK.json")
        return 2
    if not os.path.isdir(os.path.join(ROOT, "dmlcloud_tpu")):
        note("the program (dmlcloud_tpu/) is not in this directory: nothing to measure")
        return 2
    # one place for compiled programs, inside the checkout unless the machine names another
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(ROOT, ".jax_cache"))
    sys.path.insert(0, ROOT)
    ctx = Context(args, bench, cell)
    ctx.devices = devices_for(int(cell["chips"]))
    note(f"devices at {time.perf_counter() - T_PROCESS:.1f}s")
    ctx.listen_to_cache()

    from benchmark import peaks, trace

    ctx.peaks = peaks.for_kind(ctx.devices[0].device_kind)
    run = load_module("drivers", ctx.mix["driver"]).run(ctx)
    run.update(ctx=ctx, config=ctx.config, mix=ctx.mix, hf=ctx.hf, peaks=ctx.peaks, chips=len(ctx.devices),
               setup_s=ctx.setup_s, cache_events_at_close=ctx.cache_events_at_close, cache_events_at_open=ctx.cache_events_at_open)
    device = {"platform": ctx.devices[0].platform, "kind": ctx.devices[0].device_kind, "count": len(ctx.devices),
              "memory_peak_bytes": run["memory_peak_bytes"]}
    result = {"correct": None, "attempted": run["attempted"], "failed": run["failed"]}
    if ctx.trace:
        run["trace"] = reduced = trace.reduce(
            ctx.trace_file(), chips=len(ctx.devices), spans=run.get("host_spans", ()),
            traced_at=(run.get("trace_span") or [None])[0])
        device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
        result["breakdown"] = {"device_ops": reduced["device_ops"][:10], "idle_gaps": reduced["idle_gaps"][:10]}
        note(f"traced {reduced['window_s']:.3f}s, busy {reduced['busy_s']:.3f}s, clocks {reduced['shift_ns'] / 1e6:.2f} ms apart; "
             f"programs: {sorted({n.split('(')[0] for chip in reduced['modules'] for _, _, n in chip})[:8]}")
    result["metrics"] = read_metrics(ctx, run)
    result["device"] = device
    checks = run["checks"]
    result["correct"] = bool(checks) and all(c["ok"] for c in checks.values())
    result["checks"] = {name: {"value": c["value"], "limit": c["limit"]} for name, c in checks.items()}
    shutil.rmtree(os.path.join(ROOT, ".bench_tmp", cell["name"]), ignore_errors=True)
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r} {'ok' if c['ok'] else 'NOT OK'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
