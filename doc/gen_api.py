"""Generate doc/api.md — the API reference — by introspecting the package.

The reference ships a sphinx autosummary skeleton
(/root/reference/doc/reference.rst:1-8, doc/conf.py); this image has no
sphinx, so the reference page is generated ahead of time and committed:

    python doc/gen_api.py        # rewrites doc/api.md

doc/conf.py remains wired for autosummary, so a sphinx build elsewhere
produces the same surface as HTML.
"""

from __future__ import annotations

import importlib
import inspect
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

#: (module, one-line section blurb). Order == page order.
MODULES = [
    ("dmlcloud_tpu", "Package root: the public exports."),
    ("dmlcloud_tpu.pipeline", "TrainingPipeline — the experiment orchestrator."),
    ("dmlcloud_tpu.stage", "Stage / TrainValStage — the training loop API."),
    ("dmlcloud_tpu.train_state", "TrainState — the pytree that flows through the compiled step."),
    ("dmlcloud_tpu.metrics", "Metric tracking with a fused epoch-end exchange."),
    ("dmlcloud_tpu.checkpoint", "Checkpoint directory contract + Orbax tensor state."),
    ("dmlcloud_tpu.parallel.runtime", "Distributed runtime: init ladder, collectives, barriers."),
    ("dmlcloud_tpu.parallel.mesh", "Device meshes and sharding policies."),
    ("dmlcloud_tpu.parallel.pipeline_parallel", "GPipe pipeline parallelism as one XLA program."),
    ("dmlcloud_tpu.ops.flash_attention", "Fused Pallas flash-attention kernels (fwd + bwd)."),
    ("dmlcloud_tpu.ops.ring_attention", "Ring attention: sequence parallelism over the mesh."),
    ("dmlcloud_tpu.ops.grouped_matmul", "Grouped products over ragged groups, and the moves of rows to experts and back."),
    ("dmlcloud_tpu.ops.ssd", "The chunked state-space scan (Mamba-2's SSD form): two Pallas kernels with their own backward, and the plain form."),
    ("dmlcloud_tpu.ops.hyper_connection", "A sublayer's hyper-connection maps over a lane-flat stream: four Pallas kernels with their own backward on one TPU device, and the plain form."),
    ("dmlcloud_tpu.models.transformer", "Llama-style decoder LM building blocks."),
    ("dmlcloud_tpu.models.generate", "Autoregressive generation: sampling + beam search."),
    ("dmlcloud_tpu.models.moe", "Dropless mixture-of-experts layer: a chip's share of the experts, expert parallelism."),
    ("dmlcloud_tpu.models.resnet", "ResNet family (NHWC, bf16-friendly)."),
    ("dmlcloud_tpu.models.cnn", "Small CNNs for the example flows."),
    ("dmlcloud_tpu.models.encoder", "Transformer encoder blocks."),
    ("dmlcloud_tpu.models.bert", "BERT-style masked-LM encoder."),
    ("dmlcloud_tpu.models.vit", "Vision Transformer."),
    ("dmlcloud_tpu.models.clip", "CLIP-style dual-encoder contrastive model."),
    ("dmlcloud_tpu.models.hf", "HuggingFace checkpoint import."),
    ("dmlcloud_tpu.models.lora", "LoRA adapter finetuning (init/merge/export)."),
    ("dmlcloud_tpu.models.quant", "Weight-only int8 quantization for decode."),
    ("dmlcloud_tpu.models.speculative", "Speculative decoding: exact greedy or exact sampled, draft-verified."),
    ("dmlcloud_tpu.ops.paged_attention", "Paged KV gather/scatter indexing for the serving engine."),
    ("dmlcloud_tpu.serve.kv_pool", "Paged KV-cache block pool: device pages, host free list."),
    ("dmlcloud_tpu.serve.prefix_cache", "Radix-tree prefix sharing: content-addressed, refcounted blocks."),
    ("dmlcloud_tpu.serve.scheduler", "Continuous-batching FIFO scheduler with chunked prefill."),
    ("dmlcloud_tpu.serve.engine", "ServeEngine: the continuous-batching serving loop."),
    ("dmlcloud_tpu.serve.adapters", "AdapterSet: multi-tenant LoRA serving, merge-free."),
    ("dmlcloud_tpu.serve.ledger", "Per-request latency ledger (TTFT, queue depth)."),
    ("dmlcloud_tpu.serve.chaos", "Seeded, replayable fault injection for serving drills."),
    ("dmlcloud_tpu.serve.router", "Multi-replica front door: health-checked routing, failover, drain."),
    ("dmlcloud_tpu.serve.slo", "Declarative SLOs with multi-window burn-rate alerting."),
    ("dmlcloud_tpu.serve.metrics_http", "Stdlib HTTP endpoint for Prometheus scrapes."),
    ("dmlcloud_tpu.telemetry.metrics_registry", "Typed metrics: counters, gauges, histograms, Prometheus text."),
    ("dmlcloud_tpu.lint.ir", "IR-level program verifier: trace, AOT-compile, audit (DML6xx)."),
    ("dmlcloud_tpu.lint.rules_ir", "The DML6xx rules over traced/compiled step programs."),
    ("dmlcloud_tpu.data.datasets", "Composable data pipelines + reference-parity shims."),
    ("dmlcloud_tpu.data.store", "Disk-native data plane: mmap'd .dmlshard corpora + async ShardReader."),
    ("dmlcloud_tpu.data.sharding", "Per-process dataset index sharding."),
    ("dmlcloud_tpu.data.device", "Host-to-device batch transfer."),
    ("dmlcloud_tpu.utils.config", "Config container with interpolation."),
    ("dmlcloud_tpu.utils.logging", "Experiment logging, diagnostics, IO redirection."),
    ("dmlcloud_tpu.utils.seed", "Seeding and determinism flags."),
    ("dmlcloud_tpu.utils.profiling", "jax.profiler traces, phase maps and phase tables, step timers."),
    ("dmlcloud_tpu.utils.tensorboard", "TensorBoard metrics sink."),
    ("dmlcloud_tpu.utils.table", "Live progress table."),
    ("dmlcloud_tpu.utils.slurm", "Slurm environment parsing."),
    ("dmlcloud_tpu.utils.wandb", "Weights & Biases glue."),
    ("dmlcloud_tpu.utils.serialization", "JSON-safe state serialization."),
    ("dmlcloud_tpu.utils.tcp", "TCP helpers (free ports, reachability)."),
    ("dmlcloud_tpu.utils.git", "Git state capture."),
    ("dmlcloud_tpu.utils.project", "Project introspection."),
    ("dmlcloud_tpu.utils.thirdparty", "Third-party library probing."),
    ("dmlcloud_tpu.utils.argparse_ext", "argparse extensions (enum actions)."),
]


def _scrub(text: str) -> str:
    """Object reprs embed per-process addresses (e.g. flax's _Sentinel
    default: "<... object at 0x7f...>") — in signatures AND in dataclass
    auto-docstrings. Scrub them or the page churns every interpreter run
    and the CI staleness gate can never pass."""
    return re.sub(r" at 0x[0-9a-fA-F]+", "", text)


def _first_paragraph(obj) -> str:
    doc = inspect.getdoc(obj) or ""
    para = doc.split("\n\n", 1)[0].replace("\n", " ").strip()
    return _scrub(para)


def _signature(obj) -> str:
    try:
        sig = _scrub(str(inspect.signature(obj)))
    except (TypeError, ValueError):
        return ""
    return sig if len(sig) <= 110 else sig[:107] + "..."


def _public_members(mod):
    """(classes, functions) defined in (or exported by) this module."""
    names = getattr(mod, "__all__", None)
    if names is None:
        names = [n for n in vars(mod) if not n.startswith("_")]
    classes, functions = [], []
    for n in sorted(names):
        obj = getattr(mod, n, None)
        if obj is None:
            continue
        home = getattr(obj, "__module__", None)
        if mod.__name__ != "dmlcloud_tpu" and home is not None and not str(home).startswith("dmlcloud_tpu"):
            continue  # re-exported third-party symbol
        if inspect.isclass(obj):
            classes.append((n, obj))
        elif inspect.isfunction(obj):
            functions.append((n, obj))
    return classes, functions


def _class_methods(cls):
    out = []
    for n, m in sorted(vars(cls).items()):
        if n.startswith("_") or not (inspect.isfunction(m) or isinstance(m, (classmethod, staticmethod))):
            continue
        fn = m.__func__ if isinstance(m, (classmethod, staticmethod)) else m
        out.append((n, fn))
    return out


def generate() -> str:
    lines = [
        "# API reference",
        "",
        "Generated from the package docstrings by `doc/gen_api.py` — rerun it "
        "after changing the public surface. Coverage mirrors the reference's "
        "autosummary skeleton (`doc/reference.rst`) at module granularity.",
        "",
    ]
    for mod_name, blurb in MODULES:
        mod = importlib.import_module(mod_name)
        lines += [f"## `{mod_name}`", "", blurb, ""]
        mod_doc = _first_paragraph(mod)
        if mod_doc and mod_doc != blurb:
            lines += [mod_doc, ""]
        classes, functions = _public_members(mod)
        for n, cls in classes:
            lines += [f"### class `{mod_name}.{n}`", ""]
            doc = _first_paragraph(cls)
            if doc:
                lines += [doc, ""]
            methods = _class_methods(cls)
            if methods:
                for mn, m in methods:
                    mdoc = _first_paragraph(m)
                    lines.append(f"- **`{mn}{_signature(m)}`** — {mdoc}" if mdoc else f"- **`{mn}{_signature(m)}`**")
                lines.append("")
        for n, fn in functions:
            doc = _first_paragraph(fn)
            lines += [f"### `{mod_name}.{n}{_signature(fn)}`", ""]
            if doc:
                lines += [doc, ""]
    return "\n".join(lines) + "\n"


if __name__ == "__main__":
    out = os.path.join(os.path.dirname(os.path.abspath(__file__)), "api.md")
    text = generate()
    with open(out, "w") as f:
        f.write(text)
    n_sections = text.count("\n### ")
    print(f"wrote {out}: {len(text.splitlines())} lines, {n_sections} entries")
