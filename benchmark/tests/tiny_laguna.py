"""A tiny ``laguna`` configuration and cell on top of ``tiny.make_tree``, added
by files and entries alone, and the runner that lets a run past the look for a
chip with two more ways to break the timed path:

    python tiny_laguna.py <tree> <fault> <run.py arguments>

``shared_dropped``: the shared expert's output never reaches its tokens.
``window_ignored``: the window layers attend to every key before them.
``calibrate``: no fault; the arguments go to ``calibrate_laguna.py``'s ``main``
in the tree. Every other fault is ``tiny_run.py``'s.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

LAYERS = ["full_attention", "sliding_attention", "sliding_attention", "sliding_attention", "full_attention"]
TINY_LAGUNA = dict(
    source="test", model_type="laguna", vocab_size=256, hidden_size=64, intermediate_size=160, num_hidden_layers=5,
    num_attention_heads=2, num_key_value_heads=1, head_dim=16, max_position_embeddings=512, attention_bias=False,
    rms_norm_eps=1e-6, num_experts=4, num_experts_per_tok=4, moe_intermediate_size=48, shared_expert_intermediate_size=48,
    norm_topk_prob=True, decoder_sparse_step=1, mlp_only_layers=[0], tie_word_embeddings=False, gating="per-head",
    sliding_window=64,
    rope_parameters=dict(
        full_attention=dict(rope_theta=500000, rope_type="yarn", factor=8, original_max_position_embeddings=64, beta_slow=1,
                            beta_fast=8, attention_factor=1.2079441541679836, partial_rotary_factor=0.5),
        sliding_attention=dict(rope_type="default", rope_theta=10000, partial_rotary_factor=1)),
    layer_types=LAYERS, moe_apply_router_weight_on_input=False, mlp_layer_types=["dense"] + ["sparse"] * 4,
    gating_types=["per_head"] * 5, moe_routed_scaling_factor=2.5, num_attention_heads_per_layer=[2, 3, 3, 3, 2],
    moe_router_logit_softcapping=0, published=dict(num_experts=16, num_key_value_heads=4),
    train=dict(experts_held=[4, 8], kv_heads_held=[1, 2], param_dtype="float32", compute_dtype="bfloat16", attn_impl="flash"),
    # limits of the tiny size alone, between the sound runs' readings and the int8 control's on the CPU
    limits=dict(train=dict(grad_norm_worst_leaf=4e-2, delta_norm_worst_leaf=1.8e-2, check_steps_overflowed=0.0)),
)


def add_cell(tree: str) -> str:
    """``tiny-laguna`` beside the cells ``tiny.make_tree`` made in ``tree``."""
    here = lambda *p: os.path.join(tree, "benchmark", *p)
    json.dump(TINY_LAGUNA, open(here("configs", "tiny-laguna.json"), "w"))
    job = json.load(open(here("traffic", "train-laguna-8k.json")))
    job.update(batch=2, seq_len=256)
    json.dump(job, open(here("traffic", "tinylaguna.json"), "w"))
    bench = json.load(open(os.path.join(tree, "BENCHMARK.json")))
    bench["configs"].append(dict(name="tiny-laguna", source="test", file="benchmark/configs/tiny-laguna.json", reduced=[], why="t"))
    bench["workloads"].append(dict(name="tiny-laguna", config="tiny-laguna", traffic="tinylaguna", chips=1, why="t"))
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if "laguna-train-8k" in metric.get("workloads", ()):
            metric["workloads"].append("tiny-laguna")
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
    if fault == "shared_dropped":
        sys.path.insert(0, tree)
        from dmlcloud_tpu.models import moe

        whole = moe.SwiGLU.__call__
        moe.SwiGLU.__call__ = lambda self, x: 0.0 * whole(self, x)
        sys.argv[2] = "none"
    if fault == "window_ignored":
        sys.path.insert(0, tree)
        from dmlcloud_tpu.models import transformer

        told = transformer.TransformerConfig.attention_layer
        transformer.TransformerConfig.attention_layer = lambda self, i=None: told(self, i)._replace(window=None)
        sys.argv[2] = "none"
    if fault == "calibrate":  # the look for a chip skipped as tiny_run.py skips it
        sys.path.insert(0, tree)
        import jax

        from benchmark import calibrate_laguna, peaks, run as bench_run

        bench_run.devices_for = lambda chips: jax.devices()[:chips]
        peaks.PEAKS[jax.devices()[0].device_kind] = peaks.PEAKS["TPU v5 lite"]
        sys.argv = ["calibrate_laguna.py", *sys.argv[3:]]
        sys.exit(calibrate_laguna.main())
    import runpy

    sys.argv = [os.path.join(HERE, "tiny_run.py"), *sys.argv[1:]]
    runpy.run_path(sys.argv[0], run_name="__main__")
