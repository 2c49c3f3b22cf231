"""A tiny ``lfm2`` configuration and cell on top of ``tiny.make_tree``, added by
files and entries alone, and the runner that lets a run past the look for a
chip with one more way to break the timed path:

    python tiny_lfm2.py <tree> <fault> <run.py arguments>

``expert_zeroed``: the first held expert's output never reaches its tokens.
``calibrate``: no fault; the arguments go to ``calibrate_lfm2.py``'s ``main``
in the tree. Every other fault is ``tiny_run.py``'s.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

LAYERS = ["conv", "full_attention", "conv", "conv", "conv"]
TINY_LFM2 = dict(
    source="test", model_type="lfm2_moe", conv_L_cache=3, conv_bias=False, hidden_size=64, intermediate_size=160,
    layer_types=LAYERS, max_position_embeddings=512, moe_intermediate_size=48, norm_eps=1e-5, norm_topk_prob=True,
    num_attention_heads=4, num_dense_layers=1, num_experts=4, num_experts_per_tok=4, num_hidden_layers=5,
    num_key_value_heads=2, rope_parameters=dict(rope_theta=1000000, rope_type="default"), routed_scaling_factor=1,
    use_expert_bias=True, vocab_size=256, published=dict(num_experts=16),
    train=dict(experts_held=[4, 8], param_dtype="float32", compute_dtype="bfloat16", attn_impl="flash"),
    # limits of the tiny size alone, between the sound runs' readings and the int8 control's on the CPU
    limits=dict(train=dict(grad_norm_worst_leaf=4e-2, delta_norm_worst_leaf=1.8e-2, expert_bias_moved=0.0, expert_load_gap=0.05)),
)


def add_cell(tree: str) -> str:
    """``tiny-lfm2`` beside the cells ``tiny.make_tree`` made in ``tree``."""
    here = lambda *p: os.path.join(tree, "benchmark", *p)
    json.dump(TINY_LFM2, open(here("configs", "tiny-lfm2.json"), "w"))
    job = json.load(open(here("traffic", "train-lfm2-8k.json")))
    job.update(batch=2, seq_len=256)
    json.dump(job, open(here("traffic", "tinylfm2.json"), "w"))
    bench = json.load(open(os.path.join(tree, "BENCHMARK.json")))
    bench["configs"].append(dict(name="tiny-lfm2", source="test", file="benchmark/configs/tiny-lfm2.json", reduced=[], why="t"))
    bench["workloads"].append(dict(name="tiny-lfm2", config="tiny-lfm2", traffic="tinylfm2", chips=1, why="t"))
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if "lfm2-train-8k" in metric.get("workloads", ()):
            metric["workloads"].append("tiny-lfm2")
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
    if fault == "expert_zeroed":
        sys.path.insert(0, tree)
        import jax.numpy as jnp

        from dmlcloud_tpu.models import moe

        whole = moe.sort_pairs

        def broken(chosen, gates, held):
            order, inverse, weight, sizes = whole(chosen, gates, held)
            return order, inverse, jnp.where(jnp.arange(weight.shape[0]) < sizes[0], 0.0, weight), sizes

        moe.sort_pairs = broken
        sys.argv[2] = "none"
    if fault == "calibrate":  # the look for a chip skipped as tiny_run.py skips it
        sys.path.insert(0, tree)
        import jax

        from benchmark import calibrate_lfm2, peaks, run as bench_run

        bench_run.devices_for = lambda chips: jax.devices()[:chips]
        peaks.PEAKS[jax.devices()[0].device_kind] = peaks.PEAKS["TPU v5 lite"]
        sys.argv = ["calibrate_lfm2.py", *sys.argv[3:]]
        sys.exit(calibrate_lfm2.main())
    import runpy

    sys.argv = [os.path.join(HERE, "tiny_run.py"), *sys.argv[1:]]
    runpy.run_path(sys.argv[0], run_name="__main__")
