"""Scaffolding of the harness's own tests: a copy of the benchmark beside a
link to the program, with a tiny configuration, tiny mixes and cells for them
added as new files and new entries only, the way a later PR adds its own."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

TINY_CONFIG = dict(
    source="test", hidden_act="silu", hidden_size=64, intermediate_size=160, max_position_embeddings=512,
    num_attention_heads=4, num_key_value_heads=2, rms_norm_eps=1e-5, rope_theta=10000.0, sliding_window=128,
    tie_word_embeddings=False, vocab_size=256, reduced=["num_hidden_layers"],
    train=dict(num_hidden_layers=1, param_dtype="float32", compute_dtype="bfloat16", attn_impl="flash"),
    serve=dict(num_hidden_layers=2, max_seq_len=256, num_blocks=64, block_size=16, max_slots=4),
    # limits of the tiny size alone, between the readings of sound runs and of the int8 control on the CPU
    limits=dict(serve={"logit_gap_max": 0.012, "gap_over_0.01_share": 0.02},
                train=dict(loss_step1_rel=2e-4, grad_norm_worst_leaf=4e-3, delta_norm_worst_leaf=1e-2)),
)


def make_tree(dst: str) -> str:
    """``dst``/BENCHMARK.json + benchmark/ + a link to dmlcloud_tpu/, with the
    tiny cells ``tiny-chat``, ``tiny-long`` and ``tiny-train`` added."""
    os.makedirs(dst)
    shutil.copytree(os.path.join(REPO, "benchmark"), os.path.join(dst, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(REPO, "dmlcloud_tpu"), os.path.join(dst, "dmlcloud_tpu"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    put = lambda rel, obj: json.dump(obj, open(os.path.join(dst, "benchmark", rel), "w"))
    get = lambda rel: json.load(open(os.path.join(REPO, "benchmark", rel)))
    put("configs/tiny.json", TINY_CONFIG)
    chat = get("traffic/chat.json")
    chat["prompt_len"].update(median=24, min=8, max=100)
    chat["answer_len"].update(median=8, min=4, max=24)
    chat["arrivals"]["rate_per_s"], chat["lead_in_s"] = 6.0, 1.0
    put("traffic/tinychat.json", chat)
    long = get("traffic/longprompt.json")
    long["prompt_len"].update(min=64, max=200)
    long["answer_len"].update(value=8, min=8, max=8)
    long["arrivals"].update(requests_per_s=20, cycle_requests=4)
    long["lead_in_requests"], long["lead_in_s"] = 4, 1.0
    put("traffic/tinylong.json", long)
    job = get("traffic/train-8k.json")
    job.update(batch=2, seq_len=256)
    put("traffic/tinytrain.json", job)
    bench["configs"].append(dict(name="tiny", source="test", file="benchmark/configs/tiny.json",
                                 reduced=["num_hidden_layers"], why="test"))
    like = {"tiny-long": ("m7b-serve-longprompt", "tinylong"), "tiny-train": ("m7b-train-8k", "tinytrain")}
    for name, (model, mix) in like.items():
        bench["workloads"].append(dict(name=name, config="tiny", traffic=mix, chips=1, why="test"))
        for metric in bench["end_to_end"] + bench["per_layer"]:
            if model in metric.get("workloads", ()):
                metric["workloads"].append(name)
    if not any(w["name"] == "m7b-serve-longprompt" for w in bench["workloads"]):
        # likewise a backlog cell, where the benchmark holds none yet
        bench["end_to_end"].append(dict(name="serve_tokens_per_s", unit="tokens/s", better="higher", bound=0.1,
                                        source="host_clock", workloads=["tiny-long"]))
        long_layers = [("serve_host_ms_per_step.long", "ms", "serve loop, host"),
                       ("serve_prefill_call_ms_p50", "ms", "compiled serve step"),
                       ("serve_step_mfu.long", "%", "compiled serve step"),
                       ("serve_prefill_roofline", "%", "kernels, serve")]
        bench["per_layer"] += [dict(name=n, unit=u, better="lower", source="program_span", layer=layer,
                                    moves="serve_tokens_per_s", workloads=["tiny-long"]) for n, u, layer in long_layers]
    # a paced chat cell brings its own metrics, as the PR that adds m7b-serve-chat will: entries alone,
    # the readers are already under metrics/
    bench["workloads"].append(dict(name="tiny-chat", config="tiny", traffic="tinychat", chips=1, why="test"))
    bench["end_to_end"] += [dict(name=n, unit=u, better="lower", bound=0.1, source="host_clock", workloads=["tiny-chat"])
                            for n, u in (("serve_ttft_p50_s", "s"), ("serve_tpot_p90_ms", "ms"))]
    chat_layers = [("loadgen_late_p99_ms", "ms", "load generator", "serve_ttft_p50_s"),
                   ("serve_ttft_p95_s", "s", "serve loop, host", "serve_ttft_p50_s"),
                   ("serve_queue_wait_p90_s", "s", "serve loop, host", "serve_ttft_p50_s"),
                   ("serve_host_ms_per_step.chat", "ms", "serve loop, host", "serve_tpot_p90_ms"),
                   ("serve_decode_call_ms_p50", "ms", "compiled serve step", "serve_tpot_p90_ms"),
                   ("serve_step_mfu.chat", "%", "compiled serve step", "serve_tpot_p90_ms"),
                   ("serve_decode_roofline", "%", "kernels, serve", "serve_tpot_p90_ms")]
    bench["per_layer"] += [dict(name=n, unit=u, better="lower", source="program_span", layer=layer, moves=moves,
                                workloads=["tiny-chat"]) for n, u, layer, moves in chat_layers]
    json.dump(bench, open(os.path.join(dst, "BENCHMARK.json"), "w"))
    return dst


def run_cell(tree: str, *argv, fault: str | None = None, timeout=900):
    """One run of ``tree``'s run.py in a process of its own, let through the
    look for a chip by ``tiny_run.py``; returns (exit code, last line's object
    or None, standard error)."""
    cmd = [sys.executable, os.path.join(tree, "benchmark", "tests", "tiny_run.py"), tree, fault or "none", *argv]
    env = dict(os.environ, JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=os.path.join(tree, ".jax_cache"))
    done = subprocess.run(cmd, capture_output=True, text=True, timeout=timeout, env=env)
    lines = [ln for ln in done.stdout.splitlines() if ln.startswith("{")]
    return done.returncode, (json.loads(lines[-1]) if lines else None), done.stderr
