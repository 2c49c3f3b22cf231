"""``drivers/train.py`` for ``granite-4.0-h-micro``: the same four epochs, checks
and records on one ``TrainValStage`` of one ``TrainingPipeline``, with what
this configuration adds:

- the model comes from ``transformer_config_from_hf`` on the configuration's
  published keys as the file runs them: the head share needs nothing of the
  program but the head size, which the file's published head count gives
  (``hidden_size / published num_attention_heads``; the key that is reduced
  would give twice that). ``remat`` is the file's. A program that does not read
  these keys ends in ``model_config``, at once;
- weights from ``weights_granite`` (heads under their published indices,
  ``o_proj`` / ``out_proj`` scaled as in the whole layer, the tied embedding);
- the step returns the mixers' counter beside its loss, and the window keeps
  every step's ``ssm/state_absmax``.
"""

from __future__ import annotations

import dataclasses
import gc
import time
import types

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import reference_granite
from benchmark.drivers import train as base
from benchmark.drivers.train_lfm2 import judge  # every number ``limits.train`` names beside its limit: the same for this cell


def model_config(config: dict, job: dict):
    """The program's configuration of the model the file describes."""
    from dmlcloud_tpu.models.hf import transformer_config_from_hf

    role = config["train"]
    cfg = transformer_config_from_hf(
        types.SimpleNamespace(**config), max_seq_len=job["seq_len"], attn_impl=role["attn_impl"], dtype=jnp.bfloat16,
        head_dim=config["hidden_size"] // config["published"]["num_attention_heads"], remat=bool(role["remat"]),
    )
    read = (tuple(cfg.layer_types or ()), getattr(cfg, "mamba_n_heads", None), getattr(cfg, "residual_multiplier", None))
    want = (tuple("full_attention" if k == "attention" else k for k in config["layer_types"]), config["mamba_n_heads"],
            config["residual_multiplier"])
    if read != want:
        raise SystemExit(f"this program does not read model_type {config['model_type']!r}: layers, mixer heads and residual "
                         f"multiplier {read!r}, the file has {want!r}")
    return cfg


def build_stage(ctx, cfg, feed, job, records):
    import optax

    import dmlcloud_tpu as dml
    from dmlcloud_tpu.models.transformer import DecoderLM, llama_partition_rules, lm_loss, ssm_counters

    from benchmark import weights_granite

    o = job["optimizer"]
    annotate = jax.profiler.TraceAnnotation
    share = reference_granite.share(dict(reference_granite.spec(ctx.config)))

    class BenchStage(dml.TrainValStage):
        def pre_stage(self):
            model = DecoderLM(dataclasses.replace(cfg, mesh=self.mesh))
            shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
            params = weights_granite.tree_like(ctx.seed, shapes, jnp.float32, share)
            self.pipeline.register_dataset("train", feed)
            self.pipeline.register_model("lm", model, params=params, sharding=llama_partition_rules())
            schedule = optax.warmup_cosine_decay_schedule(o["init_lr"], o["peak_lr"], o["warmup_steps"], o["decay_steps"])
            tx = optax.adamw(schedule, b1=o["b1"], b2=o["b2"], eps=o["eps"], weight_decay=o["weight_decay"])
            self.pipeline.register_optimizer("adamw", tx, scheduler=schedule)

        def gradient_clip(self):
            return float(job["gradient_clip"])

        def step(self, state, batch):
            logits, stats = state.apply_fn({"params": state.params}, batch, mutable=["ssm_stats"])
            return lm_loss(logits, batch), ssm_counters(stats)

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
                feed.phase = "lead_in"
            elif phase == "lead_in":
                feed.phase = "window"
            else:
                records["dispatch_ms"] = values("misc/step_dispatch_ms")
                records["window_loss"] = values(loss_name)[-1:]
                records["state_absmax"] = values(f"{prefix}/ssm/state_absmax")

    return BenchStage()


def run(ctx) -> dict:
    import dmlcloud_tpu as dml
    from dmlcloud_tpu.parallel import mesh as mesh_lib

    job, config = ctx.mix, ctx.config
    cfg = model_config(config, job)  # first: a program that lacks these layers ends here, at once
    seconds = min(ctx.seconds, ctx.trace_seconds) if ctx.trace else ctx.seconds
    feed = base.Feed(ctx.seed, config["vocab_size"], job["batch"], job["seq_len"], seconds, job.get("lead_in_steps", 3))
    records = {"epochs": {}, "losses": [], "trace_span": None}
    journal = None
    if ctx.trace:
        from dmlcloud_tpu.telemetry import journal as journal_mod

        from benchmark.drivers.serve import memory_journal

        journal = journal_mod.activate(memory_journal(ctx.tmp_dir("journal")))
    stage = build_stage(ctx, cfg, feed, job, records)
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
        "state_absmax": records.get("state_absmax", []),
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
    if result["state_absmax"]:
        ctx.note(f"ssm/state_absmax over the window's steps: largest {max(result['state_absmax']):.4g}, median "
                 f"{float(np.median(result['state_absmax'])):.4g}")
    mem = stage._train_compiled.any_compiled().memory_analysis()
    ctx.note(f"memory_analysis of the step: arguments {mem.argument_size_in_bytes}, temporaries {mem.temp_size_in_bytes}, "
             f"outputs {mem.output_size_in_bytes}, aliased {mem.alias_size_in_bytes} bytes")
    program = {"loss": records["losses"], "grad_norm": records["grad_norm"], "delta_norm": records["delta_norm"]}
    batches = feed.fed
    # the program's state leaves the device before the reference comes onto it
    stage.state = None
    pipe.models.clear()
    del stage, pipe
    gc.collect()
    jax.clear_caches()
    ctx.reference = reference_granite.train_steps(config, ctx.seed, batches, job)
    result["checks"] = judge(ctx, program)
    result["program_readings"] = program
    return result
