"""A tiny ``granitemoehybrid`` configuration and cell on top of
``tiny.make_tree``, added by files and entries alone, and the runner that lets a
run past the look for a chip with three more ways to break the timed path:

    python tiny_granite.py <tree> <fault> <run.py arguments>

``carry_dropped``: the scan's chunks are handed no state (each starts from 0).
``residual_unscaled``: ``residual_multiplier`` is left out of every branch.
``rope_applied``: the position-less attention layer rotates q and k.
``calibrate``: no fault; the arguments go to ``calibrate_granite.py``'s ``main``
in the tree. Every other fault is ``tiny_run.py``'s.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

LAYERS = ["mamba", "mamba", "attention", "mamba"]
TINY_GRANITE = dict(
    source="test", model_type="granitemoehybrid", vocab_size=256, hidden_size=64, intermediate_size=160, shared_intermediate_size=160,
    num_hidden_layers=4, layer_types=LAYERS, num_attention_heads=2, num_key_value_heads=1, max_position_embeddings=512,
    attention_bias=False, rms_norm_eps=1e-5, hidden_act="silu", normalization_function="rmsnorm", num_local_experts=0,
    num_experts_per_tok=0, position_embedding_type="nope", rope_theta=10000, rope_scaling=None, tie_word_embeddings=True,
    attention_multiplier=0.0625, embedding_multiplier=12, residual_multiplier=0.22, logits_scaling=8,
    mamba_n_heads=4, mamba_d_head=16, mamba_d_state=32, mamba_n_groups=1, mamba_d_conv=4, mamba_chunk_size=64, mamba_expand=2,
    mamba_conv_bias=True, mamba_proj_bias=False,
    published=dict(num_attention_heads=4, num_key_value_heads=2, mamba_n_heads=8),
    train=dict(kv_heads_held=[1, 2], mamba_heads_held=[4, 8], param_dtype="float32", compute_dtype="bfloat16", attn_impl="flash",
               remat=True),
    # limits of the tiny size alone, between the sound runs' readings and the int8 control's on the CPU
    limits=dict(train=dict(loss_step1_rel=3e-4, grad_norm_worst_leaf=4e-2, delta_norm_worst_leaf=1.8e-2)),
)


def add_cell(tree: str) -> str:
    """``tiny-granite`` beside the cells ``tiny.make_tree`` made in ``tree``."""
    here = lambda *p: os.path.join(tree, "benchmark", *p)
    json.dump(TINY_GRANITE, open(here("configs", "tiny-granite.json"), "w"))
    job = json.load(open(here("traffic", "train-granite-8k.json")))
    job.update(batch=2, seq_len=256)
    json.dump(job, open(here("traffic", "tinygranite.json"), "w"))
    bench = json.load(open(os.path.join(tree, "BENCHMARK.json")))
    bench["configs"].append(dict(name="tiny-granite", source="test", file="benchmark/configs/tiny-granite.json", reduced=[], why="t"))
    bench["workloads"].append(dict(name="tiny-granite", config="tiny-granite", traffic="tinygranite", chips=1, why="t"))
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if "granite-train-8k" in metric.get("workloads", ()):
            metric["workloads"].append("tiny-granite")
    json.dump(bench, open(os.path.join(tree, "BENCHMARK.json"), "w"))
    return tree


def run_cell(tree: str, *argv, fault: str | None = None, timeout=900):
    cmd = [sys.executable, os.path.abspath(__file__), tree, fault or "none", *argv]
    env = dict(os.environ, JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=os.path.join(tree, ".jax_cache"))
    done = subprocess.run(cmd, capture_output=True, text=True, timeout=timeout, env=env)
    lines = [ln for ln in done.stdout.splitlines() if ln.startswith("{")]
    return done.returncode, (json.loads(lines[-1]) if lines else None), done.stderr


if __name__ == "__main__":
    tree, fault = sys.argv[1], sys.argv[2]
    if fault == "carry_dropped":
        sys.path.insert(0, tree)
        import jax.numpy as jnp

        from dmlcloud_tpu.ops import ssd

        whole = ssd.ssd_chunked

        def every_chunk_from_nothing(x, dt, a, b, c, d, chunk, return_carry=False):
            rows = lambda v: v.reshape(-1, chunk, *v.shape[2:])  # each chunk a sequence of its own
            y = whole(rows(x), rows(dt), a, rows(b), rows(c), d, chunk).reshape(x.shape)
            return (y, jnp.zeros((x.shape[0], x.shape[1] // chunk, *x.shape[2:], b.shape[-1]))) if return_carry else y

        ssd.ssd_chunked = every_chunk_from_nothing
        sys.argv[2] = "none"
    if fault == "residual_unscaled":
        sys.path.insert(0, tree)
        from dmlcloud_tpu.models import transformer

        transformer._branch = lambda cfg, out: out
        sys.argv[2] = "none"
    if fault == "rope_applied":
        sys.path.insert(0, tree)
        from dmlcloud_tpu.models import hf

        keys = hf._granite_keys
        hf._granite_keys = lambda config: {**keys(config), "position_embedding": "rope"}
        sys.argv[2] = "none"
    if fault == "calibrate":  # the look for a chip skipped as tiny_run.py skips it
        sys.path.insert(0, tree)
        import jax

        from benchmark import calibrate_granite, peaks, run as bench_run

        bench_run.devices_for = lambda chips: jax.devices()[:chips]
        peaks.PEAKS[jax.devices()[0].device_kind] = peaks.PEAKS["TPU v5 lite"]
        sys.argv = ["calibrate_granite.py", *sys.argv[3:]]
        sys.exit(calibrate_granite.main())
    import runpy

    sys.argv = [os.path.join(HERE, "tiny_run.py"), *sys.argv[1:]]
    runpy.run_path(sys.argv[0], run_name="__main__")
