"""ResNet-50 raw-step tuning harness.

Runs the bench's raw train step under a matrix of variants on the chip (this
one process touches the device itself; bench.py's one-process rule) and prints images/s + MFU per variant, optionally capturing a
``jax.profiler`` trace of the best one for doc/performance.md analysis.

    python scripts/tune_resnet.py                 # sweep variants
    python scripts/tune_resnet.py --trace /tmp/tr # also trace the winner

Variants (each a delta on the bench's baseline step, bench.py:77-112):
- batch: 128 / 256 / 512 / 1024 (HBM permitting)
- input dtype: f32 (baseline) vs bf16 images (halves input HBM traffic)
- BN axis_name sync off (single chip) is already the baseline; 'fused_bn'
  folds scale/bias into conv output via XLA (it fuses these anyway — the
  variant exists to CONFIRM that with numbers, not to assume it)
"""

import argparse
import functools
import sys
import time

sys.path.insert(0, __file__.rsplit("/", 2)[0])

import jax
import jax.numpy as jnp
import numpy as np
import optax

from bench import IMG, TRAIN_FLOPS_PER_IMAGE, chip_peak_flops, make_model_and_state


def raw_step_fn(model, tx):
    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def train_step(params, batch_stats, opt_state, batch):
        def loss_fn(p):
            logits, new_state = model.apply(
                {"params": p, "batch_stats": batch_stats},
                batch["image"], train=True, mutable=["batch_stats"],
            )
            loss = optax.softmax_cross_entropy_with_integer_labels(logits, batch["label"]).mean()
            return loss, new_state["batch_stats"]

        (loss, new_bs), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        updates, new_opt = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), new_bs, new_opt, loss

    return train_step


def run_variant(batch_size: int, image_dtype, warmup=5, steps=30, trace_dir=None):
    model, variables, tx = make_model_and_state()
    params, batch_stats = variables["params"], variables["batch_stats"]
    opt_state = tx.init(params)
    rng = np.random.RandomState(0)
    batch = {
        "image": jnp.asarray(rng.rand(batch_size, IMG, IMG, 3), image_dtype),
        "label": jnp.asarray(rng.randint(0, 1000, size=batch_size), jnp.int32),
    }
    step = raw_step_fn(model, tx)
    batch = jax.device_put(batch)
    for _ in range(warmup):
        params, batch_stats, opt_state, loss = step(params, batch_stats, opt_state, batch)
    float(loss)  # completion sync before the clock starts
    ctx = jax.profiler.trace(trace_dir) if trace_dir else None
    if ctx:
        ctx.__enter__()
    t0 = time.perf_counter()
    for _ in range(steps):
        params, batch_stats, opt_state, loss = step(params, batch_stats, opt_state, batch)
    float(loss)
    dt = time.perf_counter() - t0
    if ctx:
        ctx.__exit__(None, None, None)
    ips = steps * batch_size / dt
    return ips, ips * TRAIN_FLOPS_PER_IMAGE / chip_peak_flops()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--trace", default=None, help="profile-trace dir for the best variant")
    ap.add_argument("--batches", default="128,256,512,1024")
    ap.add_argument("--steps", type=int, default=30)
    args = ap.parse_args()

    print(f"device: {jax.devices()[0].device_kind}, peak {chip_peak_flops()/1e12:.0f} TF/s bf16")
    results = {}
    for b in [int(x) for x in args.batches.split(",")]:
        for dt_name, dt in (("f32", jnp.float32), ("bf16", jnp.bfloat16)):
            name = f"b{b}/{dt_name}"
            try:
                ips, mfu = run_variant(b, dt, steps=args.steps)
            except Exception as e:  # HBM exhaustion at large batches
                print(f"{name:>12}: FAILED {type(e).__name__}: {str(e)[:120]}")
                continue
            results[name] = (ips, mfu)
            print(f"{name:>12}: {ips:8.1f} img/s  MFU {mfu:.3f}", flush=True)
    if not results:
        sys.exit(1)
    best = max(results, key=lambda k: results[k][0])
    print(f"best: {best} -> {results[best][0]:.1f} img/s, MFU {results[best][1]:.3f}")
    if args.trace:
        b = int(best.split("/")[0][1:])
        dt = jnp.bfloat16 if best.endswith("bf16") else jnp.float32
        ips, mfu = run_variant(b, dt, steps=args.steps, trace_dir=args.trace)
        print(f"traced {best} -> {ips:.1f} img/s; trace in {args.trace}")


if __name__ == "__main__":
    main()
