"""Records the small device trace that ``test_trace.py`` reduces.

Run on the chip (``python benchmark/tests/record_trace_fixture.py <out_dir>``):
a few steps of one small jitted program under ``jax.profiler``, each inside a
``TraceAnnotation`` as the drivers write them, with host sleeps between so that
there is idle time to attribute. Prints what the trace holds, plane by plane,
and copies the ``.xplane.pb`` to ``<out_dir>/fixture.xplane.pb``.
"""

import glob
import os
import shutil
import sys
import time

import jax
import jax.numpy as jnp


def main(out_dir: str) -> None:
    os.makedirs(out_dir, exist_ok=True)
    trace_dir = os.path.join(out_dir, "trace")

    @jax.jit
    def bench_fixture_step(x, w):
        with jax.named_scope("fixture_matmul"):
            y = x @ w
        return jnp.tanh(y).astype(x.dtype)

    x = jnp.ones((1024, 1024), jnp.bfloat16)
    w = jnp.ones((1024, 1024), jnp.bfloat16)
    jax.block_until_ready(bench_fixture_step(x, w))
    jax.profiler.start_trace(trace_dir)
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench:window"):
        for i in range(4):
            with jax.profiler.TraceAnnotation("bench:fixture_call", step=i):
                x = bench_fixture_step(x, w)
                jax.block_until_ready(x)
            with jax.profiler.TraceAnnotation("bench:fixture_sleep"):
                time.sleep(0.002)
    window = time.perf_counter() - t0
    jax.profiler.stop_trace()
    [path] = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    shutil.copy(path, os.path.join(out_dir, "fixture.xplane.pb"))
    print("window_s", window, "bytes", os.path.getsize(path))
    data = jax.profiler.ProfileData.from_file(path)
    for plane in data.planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            events = list(line.events)
            print("  LINE", line.name, len(events))
            for ev in events[:6]:
                stats = {k: v for k, v in list(ev.stats)[:8]}
                print("     ", ev.name[:80], ev.start_ns, ev.duration_ns, stats)


if __name__ == "__main__":
    main(sys.argv[1] if len(sys.argv) > 1 else "chiprun_out/trace_fixture")
