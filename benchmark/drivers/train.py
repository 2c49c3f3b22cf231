"""How a training window is driven: one ``TrainValStage`` on one
``TrainingPipeline``, through the stage's normal loop.

Set-up builds the one object (the compiled, donated step with its state) and
drives it from the seed through its first steps: epoch 1 is step 1, epoch 2
steps 2 and 3, each on its own seeded batch through the window's own feed and
call; between them the driver reads what ``correct`` compares (each step's loss,
the first gradient's norms out of the optimizer's first moment, the norms of
the parameters' change after step 3). Epoch 3 is a short lead-in, epoch 4 the
window: seeded batches until the clock passes ``--seconds``, the epoch's last
step ended by ``block_until_ready``. The same stage, state and compiled step
run all four.

The stage is given what sizing needs (model, parameters from the seed, batch
shape, optimiser, mesh); remat, prefetch, logging and every other policy stay
at the program's defaults.
"""

from __future__ import annotations

import dataclasses
import gc
import time
import types

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import reference, weights

CHECK_STEPS = 3


class Feed:
    """The job's batches: token ids uniform over the vocabulary, step k's a
    function of (seed, k). What an epoch yields depends on the phase the driver
    set; the program may look at an epoch's first batch more than once (its
    precompile does), so the check steps are numbered, not drawn in passing."""

    def __init__(self, seed, vocab, batch, seq, seconds, lead_in_steps):
        self.seed, self.vocab, self.shape = int(seed), vocab, (batch, seq)
        self.seconds, self.lead_in_steps = seconds, lead_in_steps
        self.phase = "check_1"
        self.deadline = None
        self.window_steps = 0

    def batch(self, k: int) -> np.ndarray:
        return np.random.default_rng([self.seed, 0xBA7C4, k]).integers(0, self.vocab, self.shape, dtype=np.int32)

    @property
    def fed(self) -> list:
        """The check steps' batches, for the reference."""
        return [self.batch(k) for k in range(CHECK_STEPS)]

    def __iter__(self):
        if self.phase == "check_1":
            yield self.batch(0)
        elif self.phase == "check_2":
            for k in range(1, CHECK_STEPS):
                yield self.batch(k)
        elif self.phase == "lead_in":
            for k in range(self.lead_in_steps):
                yield self.batch(CHECK_STEPS + k)
        else:
            while time.perf_counter() < self.deadline:
                self.window_steps += 1
                yield self.batch(CHECK_STEPS + self.lead_in_steps + self.window_steps)


def build_stage(ctx, feed, job, records):
    import optax

    import dmlcloud_tpu as dml
    from dmlcloud_tpu.models.hf import transformer_config_from_hf
    from dmlcloud_tpu.models.transformer import DecoderLM, llama_partition_rules, lm_loss

    role = ctx.config["train"]
    cfg = transformer_config_from_hf(
        types.SimpleNamespace(**ctx.hf, num_hidden_layers=role["num_hidden_layers"]),
        num_layers=role["num_hidden_layers"], max_seq_len=job["seq_len"], attn_impl=role["attn_impl"],
        dtype=jnp.bfloat16,
    )
    o = job["optimizer"]
    annotate = jax.profiler.TraceAnnotation

    class BenchStage(dml.TrainValStage):
        def pre_stage(self):
            model = DecoderLM(dataclasses.replace(cfg, mesh=self.mesh))
            shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
            params = weights.tree_like(ctx.seed, shapes, jnp.float32)
            self.pipeline.register_dataset("train", feed)
            self.pipeline.register_model("lm", model, params=params, sharding=llama_partition_rules())
            schedule = optax.warmup_cosine_decay_schedule(o["init_lr"], o["peak_lr"], o["warmup_steps"], o["decay_steps"])
            tx = optax.adamw(schedule, b1=o["b1"], b2=o["b2"], eps=o["eps"], weight_decay=o["weight_decay"])
            self.pipeline.register_optimizer("adamw", tx, scheduler=schedule)

        def gradient_clip(self):
            return float(job["gradient_clip"])

        def step(self, state, batch):
            return lm_loss(state.apply_fn({"params": state.params}, batch), batch)

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
            loss_name = f"{self.train_metric_prefix()}/{self.loss_metric_name()}"
            if phase in ("check_1", "check_2"):
                records["losses"] += values(loss_name)
            if phase == "check_1":
                records["grad_norm"] = first_gradient_norms(self.state, o["b1"])
                feed.phase = "check_2"
            elif phase == "check_2":
                records["delta_norm"] = change_norms(self.state.params, self.pipeline.models["lm"].params)
                feed.phase = "lead_in"
            elif phase == "lead_in":
                feed.phase = "window"
            else:
                records["dispatch_ms"] = values("misc/step_dispatch_ms")
                records["window_loss"] = values(loss_name)[-1:]

    return BenchStage(), cfg


def leaf_names(tree) -> dict:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {weights.path_name(p): x for p, x in flat}


def first_gradient_norms(state, b1) -> dict:
    """After step 1 Adam's first moment is (1 - b1) x the gradient it was given."""
    mu = next(s.mu for s in jax.tree_util.tree_leaves(state.opt_state, is_leaf=lambda s: hasattr(s, "mu"))
              if hasattr(s, "mu"))
    norms = jax.jit(lambda t: {n: jnp.sqrt(jnp.sum(x.astype(jnp.float32) ** 2)) / (1.0 - b1)
                               for n, x in leaf_names(t).items()})(mu)
    return {n: float(v) for n, v in norms.items()}


def change_norms(params, start) -> dict:
    a, b = leaf_names(params), leaf_names(start)
    norms = jax.jit(lambda a, b: {n: jnp.sqrt(jnp.sum((a[n] - b[n]) ** 2)) for n in a})(a, b)
    return {n: float(v) for n, v in norms.items()}


def run(ctx) -> dict:
    import dmlcloud_tpu as dml
    from dmlcloud_tpu.parallel import mesh as mesh_lib

    job = ctx.mix
    role = ctx.config["train"]
    # a traced run is a short window, traced from end to end
    seconds = min(ctx.seconds, ctx.trace_seconds) if ctx.trace else ctx.seconds
    feed = Feed(ctx.seed, ctx.hf["vocab_size"], job["batch"], job["seq_len"], seconds, job.get("lead_in_steps", 3))
    records = {"epochs": {}, "losses": [], "trace_span": None}
    journal = None
    if ctx.trace:
        from dmlcloud_tpu.telemetry import journal as journal_mod

        from benchmark.drivers.serve import memory_journal

        journal = journal_mod.activate(memory_journal(ctx.tmp_dir("journal")))
    stage, cfg = build_stage(ctx, feed, job, records)
    pipe = dml.TrainingPipeline({"seed": int(ctx.seed) % (2**31 - 1)}, name=f"bench-{ctx.cell['name']}", precompile=True)
    mesh_axes = job.get("mesh")
    pipe.set_mesh(mesh_axes if mesh_axes else mesh_lib.create_mesh({"data": 1}, devices=ctx.devices[:1]))
    pipe.append_stage(stage, max_epochs=4)
    pipe.run()
    if journal is not None:
        journal_mod.deactivate()

    t0, t1 = records["epochs"]["window"]
    tokens_per_step = job["batch"] * job["seq_len"]
    result = {
        "kind": "train",
        "window": (t0, t1),
        "steps_in_window": feed.window_steps,
        "tokens_per_step": tokens_per_step,
        "train_shapes": {"num_layers": role["num_hidden_layers"], "batch": job["batch"], "seq_len": job["seq_len"]},
        "dispatch_ms": records.get("dispatch_ms", []),
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
    program = {"loss": records["losses"], "grad_norm": records["grad_norm"], "delta_norm": records["delta_norm"]}
    batches = feed.fed
    # the program's state leaves the device before the reference comes onto it
    stage.state = None
    pipe.models.clear()
    del stage, pipe
    gc.collect()
    jax.clear_caches()
    result["checks"] = check(ctx, program, batches)
    result["program_readings"] = program
    return result


def worst_leaf_gap(program: dict, ref: dict, skip=()) -> tuple:
    """The widest gap between the program's norm of a leaf and the reference's,
    against the reference's norm of that leaf or of the median leaf, whichever
    is larger."""
    names = [n for n in ref if n not in skip]
    median = float(np.median([ref[n] for n in names]))
    gaps = {n: abs(program[n] - ref[n]) / max(ref[n], median) for n in names}
    worst = max(gaps, key=gaps.get)
    return gaps[worst], worst


def compare(program: dict, ref: dict) -> dict:
    """The numbers ``correct`` compares for a training cell."""
    out = {}
    for i, (a, b) in enumerate(zip(program["loss"], ref["loss"])):
        out[f"loss_step{i + 1}_rel"] = abs(a - b) / abs(b)
    out["grad_norm_worst_leaf"], out["grad_norm_worst_leaf_name"] = worst_leaf_gap(program["grad_norm"], ref["grad_norm"])
    # a leaf whose gradient is nought to rounding in the reference moves under Adam by round-off alone
    median = float(np.median(list(ref["grad_norm"].values())))
    still = [n for n, g in ref["grad_norm"].items() if g < 1e-3 * median]
    out["delta_norm_worst_leaf"], out["delta_norm_worst_leaf_name"] = worst_leaf_gap(
        program["delta_norm"], ref["delta_norm"], skip=still)
    return out


def check(ctx, program, batches, precision="reference", fault=None) -> dict:
    role = ctx.config["train"]
    limits = ctx.config["limits"]["train"]
    ref = reference.train_steps(ctx.hf, role["num_hidden_layers"], ctx.seed, batches, ctx.mix, precision, fault)
    numbers = compare(program, ref)
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
