"""``drivers/train.py`` for ``lfm2-24b-a2b``: the same four epochs, checks and
records on one ``TrainValStage`` of one ``TrainingPipeline``, with what this
configuration adds:

- the model comes from ``transformer_config_from_hf`` on the configuration's
  published keys, with the router at its published width and ``experts_held``
  from the file (one chip's share of the expert-parallel deployment it states);
- weights from ``weights_lfm2`` (experts under their published indices, tied
  embedding), and ``expert_bias`` SET, not drawn: before the state is built,
  ``reference_lfm2.balanced_expert_bias`` levels the experts' loads on the
  run's first ``BIAS_BATCHES`` batches, so that every seed sends the held
  experts the same amount of work; program and reference are handed the same
  vectors, and the widest gap it left is compared with ``expert_load_gap``;
- the step returns the expert layers' counters beside its loss, and the window
  keeps every step's ``moe/pairs_held`` and ``moe/load_max_over_mean``.
"""

from __future__ import annotations

import dataclasses
import gc
import time
import types

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import reference_lfm2, weights_lfm2
from benchmark.drivers import train as base


def model_config(config: dict, job: dict):
    """The program's configuration of the model the file describes."""
    from dmlcloud_tpu.models.hf import transformer_config_from_hf

    role = config["train"]
    published = {**config, "num_experts": config["published"]["num_experts"]}
    return transformer_config_from_hf(
        types.SimpleNamespace(**published), max_seq_len=job["seq_len"], attn_impl=role["attn_impl"],
        dtype=jnp.bfloat16, experts_held=tuple(role["experts_held"]),
    )


def build_stage(ctx, cfg, feed, job, records, biases):
    import optax

    import dmlcloud_tpu as dml
    from dmlcloud_tpu.models.moe import moe_counters
    from dmlcloud_tpu.models.transformer import DecoderLM, llama_partition_rules, lm_loss

    o = job["optimizer"]
    annotate = jax.profiler.TraceAnnotation
    counters = ("moe/pairs_held", "moe/load_max_over_mean")

    class BenchStage(dml.TrainValStage):
        def pre_stage(self):
            model = DecoderLM(dataclasses.replace(cfg, mesh=self.mesh))
            shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
            params = weights_lfm2.tree_like(ctx.seed, shapes, jnp.float32, cfg.experts_held[0])
            self.pipeline.register_dataset("train", feed)
            self.pipeline.register_model("lm", model, params={"params": params, "buffers": reference_lfm2.bias_tree(biases)},
                                         sharding=llama_partition_rules())
            schedule = optax.warmup_cosine_decay_schedule(o["init_lr"], o["peak_lr"], o["warmup_steps"], o["decay_steps"])
            tx = optax.adamw(schedule, b1=o["b1"], b2=o["b2"], eps=o["eps"], weight_decay=o["weight_decay"])
            self.pipeline.register_optimizer("adamw", tx, scheduler=schedule)

        def gradient_clip(self):
            return float(job["gradient_clip"])

        def step(self, state, batch):
            logits, stats = state.apply_fn({"params": state.params, **state.extras}, batch, mutable=["moe_stats"])
            return lm_loss(logits, batch), moe_counters(stats)

        def train_epoch(self):
            phase = feed.phase
            jax.block_until_ready(self.state)
            if phase == "window":
                ctx.maybe_trace(records)
                t0 = time.perf_counter()
                feed.deadline = t0 + feed.seconds
                ctx.window_opened(t0)
            else:
                t0 = time.perf_counter()
            with annotate("bench:train_loop"):
                super().train_epoch()  # ends in block_until_ready on the last step's metrics
            t1 = time.perf_counter()
            records["epochs"][phase] = (t0, t1)
            if phase == "window":
                ctx.window_closed()
                ctx.end_trace(records)

        def run_epoch(self):
            super().run_epoch()
            phase = feed.phase
            # the tracker still holds every step's value here, before the epoch's reduce
            values = lambda name: [float(self._stall.fetch(v)) for v in self.tracker.reducers[name].values]
            prefix = self.train_metric_prefix()
            loss_name = f"{prefix}/{self.loss_metric_name()}"
            if phase in ("check_1", "check_2"):
                records["losses"] += values(loss_name)
            if phase == "check_1":
                records["grad_norm"] = base.first_gradient_norms(self.state, o["b1"])
                feed.phase = "check_2"
            elif phase == "check_2":
                records["delta_norm"] = base.change_norms(self.state.params, self.pipeline.models["lm"].params)
                records["bias_after"] = jax.device_get(self.state.extras["buffers"])
                feed.phase = "lead_in"
            elif phase == "lead_in":
                feed.phase = "window"
            else:
                records["dispatch_ms"] = values("misc/step_dispatch_ms")
                records["window_loss"] = values(loss_name)[-1:]
                for name in counters:
                    records[name] = values(f"{prefix}/{name}")

    return BenchStage()


def run(ctx) -> dict:
    import dmlcloud_tpu as dml
    from dmlcloud_tpu.parallel import mesh as mesh_lib

    job, config = ctx.mix, ctx.config
    cfg = model_config(config, job)  # first: a program that lacks these layers ends here, at once
    seconds = min(ctx.seconds, ctx.trace_seconds) if ctx.trace else ctx.seconds
    feed = base.Feed(ctx.seed, config["vocab_size"], job["batch"], job["seq_len"], seconds, job.get("lead_in_steps", 3))
    records = {"epochs": {}, "losses": [], "trace_span": None}
    t0 = time.perf_counter()
    biases, gaps = reference_lfm2.balanced_expert_bias(
        config, ctx.seed, [feed.batch(k) for k in range(reference_lfm2.BIAS_BATCHES)])
    biases = {i: np.asarray(b) for i, b in biases.items()}
    ctx.note(f"expert_bias set in {time.perf_counter() - t0:.2f}s; widest load gap by layer {gaps}")
    gc.collect()
    journal = None
    if ctx.trace:
        from dmlcloud_tpu.telemetry import journal as journal_mod

        from benchmark.drivers.serve import memory_journal

        journal = journal_mod.activate(memory_journal(ctx.tmp_dir("journal")))
    stage = build_stage(ctx, cfg, feed, job, records, biases)
    pipe = dml.TrainingPipeline({"seed": int(ctx.seed) % (2**31 - 1)}, name=f"bench-{ctx.cell['name']}", precompile=True)
    pipe.set_mesh(mesh_lib.create_mesh({"data": 1}, devices=ctx.devices[:1]))
    pipe.append_stage(stage, max_epochs=4)
    pipe.run()
    if journal is not None:
        journal_mod.deactivate()

    result = {
        "kind": "train",
        "window": records["epochs"]["window"],
        "steps_in_window": feed.window_steps,
        "tokens_per_step": job["batch"] * job["seq_len"],
        "train_shapes": {"num_layers": cfg.num_layers, "batch": job["batch"], "seq_len": job["seq_len"]},
        "dispatch_ms": records.get("dispatch_ms", []),
        "pairs_held": records.get("moe/pairs_held", []),
        "load_max_over_mean": records.get("moe/load_max_over_mean", []),
        "bias_gaps": gaps,
        "recompiles": pipe.tracker["misc/recompiles"][-1] if "misc/recompiles" in pipe.tracker else None,
        "signatures": (1, stage._train_compiled._cache_size()) if getattr(stage, "_train_compiled", None) else None,
        "spans": journal.spans if journal is not None else None,
        "host_spans": [(s["start"], s["end"], "bench:dispatch") for s in journal.spans if s["kind"] == "step_dispatch"]
        if journal is not None else [],
        "trace_span": records["trace_span"],
        "attempted": feed.window_steps,
        "failed": 0 if np.isfinite(records.get("window_loss", [np.nan])).all() else feed.window_steps,
        "memory_peak_bytes": ctx.memory_peak_bytes(),
    }
    pairs, quarter = result["pairs_held"], max(len(result["pairs_held"]) // 4, 1)
    if pairs:
        ctx.note(f"moe/pairs_held a step: mean {np.mean(pairs):.1f}, first quarter of the window {np.mean(pairs[:quarter]):.1f}, "
                 f"last {np.mean(pairs[-quarter:]):.1f}")
    mem = stage._train_compiled.any_compiled().memory_analysis()
    ctx.note(f"memory_analysis of the step: arguments {mem.argument_size_in_bytes}, temporaries {mem.temp_size_in_bytes}, "
             f"outputs {mem.output_size_in_bytes}, aliased {mem.alias_size_in_bytes} bytes")
    program = {"loss": records["losses"], "grad_norm": records["grad_norm"], "delta_norm": records["delta_norm"]}
    bias_moved = max(float(np.abs(np.asarray(after["moe"]["expert_bias"]) - biases[int(name.split("_")[1])]).max())
                     for name, after in records["bias_after"].items())
    batches = feed.fed
    # the program's state leaves the device before the reference comes onto it
    stage.state = None
    pipe.models.clear()
    del stage, pipe
    gc.collect()
    jax.clear_caches()
    ctx.reference, ctx.biases = reference_lfm2.train_steps(config, ctx.seed, batches, job, biases), biases
    # what three optimizer steps did to the bias (nothing, or the guarantee is broken), and how level it left the loads
    result["checks"] = judge(ctx, program, {"expert_bias_moved": bias_moved, "expert_load_gap": max(gaps.values(), default=0.0)})
    result["program_readings"] = program
    return result


def judge(ctx, readings, own=()) -> dict:
    """Every number ``limits.train`` names, beside its limit: ``readings`` (the
    program's, or a stand-in's that ``calibrate_lfm2.py`` puts in its place)
    against ``ctx.reference``, and the driver's ``own`` numbers."""
    limits = ctx.config["limits"]["train"]
    numbers = {**base.compare(readings, ctx.reference), **dict(own)}
    checks = {}
    for name, value in numbers.items():
        if name.endswith("_name"):
            continue
        if name in limits:
            checks[name] = {"value": value, "limit": limits[name], "ok": bool(value <= limits[name])}
        else:
            ctx.note(f"read but not compared: {name} = {value!r}")
    for name in ("grad_norm_worst_leaf", "delta_norm_worst_leaf"):
        ctx.note(f"{name} at {numbers[name + '_name']}")
    return checks
