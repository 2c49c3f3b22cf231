#!/usr/bin/env python3
"""Does the system still start on the chip? The trainer and the serving
engine, through the entry points a user calls, at Mistral-7B-v0.1's widths.

    python chip_smoke.py              one TPU chip: train phase, then serve phase
    python chip_smoke.py --chips 4    four chips: the sharded train step against
                                      one device, and nothing else
    python chip_smoke.py --rehearse   any device, tiny widths: the same phase
                                      functions, to check the control flow
                                      (add --chips 4 under
                                      XLA_FLAGS=--xla_force_host_platform_device_count=4)

Every line of standard output that starts with ``{`` is one JSON object (the
pipeline's own banner and table are printed between them). The LAST line is

    {"ok": true, "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}}

with the device as jax reports it. ``ok`` means "every phase ran at full
width on a TPU and passed its checks" and nothing else: without ``--rehearse``
the script refuses to run a phase on any other platform, and a rehearsal ends
``"ok": false`` with a non-zero exit code however well it went. Depth is the
only thing cut from the published model (``reduced`` in the phase lines);
weights and tokens are random from ``--seed``. Timings in the phase lines are
smoke timings of one run — enough to see that nothing is absurd, not a
benchmark.

One process uses the chip: both phases run here, and nothing that needs a
device is started as a child.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import time
import traceback
import types

import jax
import jax.numpy as jnp
import numpy as np
import optax

import dmlcloud_tpu as dml
from dmlcloud_tpu.compile import cache as compile_cache
from dmlcloud_tpu.models.generate import generate
from dmlcloud_tpu.models.hf import transformer_config_from_hf
from dmlcloud_tpu.models.transformer import DecoderLM, llama_partition_rules, lm_loss
from dmlcloud_tpu.parallel import mesh as mesh_lib
from dmlcloud_tpu.parallel import runtime
from dmlcloud_tpu.serve import ServeEngine

#: config.json of mistralai/Mistral-7B-v0.1 (huggingface.co), the keys
#: models/hf.py maps onto TransformerConfig
MISTRAL_7B_V01 = dict(
    vocab_size=32000, hidden_size=4096, intermediate_size=14336, num_hidden_layers=32,
    num_attention_heads=32, num_key_value_heads=8, max_position_embeddings=32768,
    rope_theta=10000.0, sliding_window=4096, tie_word_embeddings=False,
)


@dataclasses.dataclass(frozen=True)
class Preset:
    """One sizing of the two phases. ``hf`` is the published model; the rest
    is what a 16 GB chip forces (``memory_analysis()`` of the two compiled
    programs, CHANGES.md PR 21) or, for the rehearsal, what a CPU finishes
    in seconds."""

    hf: dict
    # fp32 params + grads + AdamW moments cost ~3.5 GB a layer on top of ~4 GB
    # for embedding and head: two layers fit one chip, three do not
    train_layers: int
    train_seq: int  # twice the window, so the kernel really skips blocks
    train_steps: int
    # bf16 weights of 16 layers take ~7 GiB; the pool takes most of the rest
    # (a 16-token page over 16 layers is 1 MiB)
    serve_layers: int
    serve_max_len: int
    num_blocks: int
    prompt_lens: tuple
    new_tokens: int


FULL = Preset(
    hf=MISTRAL_7B_V01, train_layers=2, train_seq=8192, train_steps=6,
    serve_layers=16, serve_max_len=2048, num_blocks=5000,
    prompt_lens=(1024, 1024, 512, 512, 256, 256, 128, 128), new_tokens=64,
)
TINY = Preset(
    hf=dict(
        vocab_size=256, hidden_size=64, intermediate_size=160, num_hidden_layers=4,
        num_attention_heads=4, num_key_value_heads=2, max_position_embeddings=512,
        rope_theta=10000.0, sliding_window=128, tie_word_embeddings=False,
    ),
    train_layers=1, train_seq=256, train_steps=4,
    serve_layers=2, serve_max_len=128, num_blocks=48,
    prompt_lens=(40, 16, 16), new_tokens=4,
)

#: |flash loss - dot loss| / loss at the first step. Both paths multiply in
#: bf16 and accumulate in fp32, but the dot path rounds the scores to bf16
#: before its softmax and the kernel does not, so single outputs differ by
#: about one bf16 ulp (2**-8); the loss is a mean over thousands of tokens, in
#: which that noise averages out. Four ulps over 8 leaves room for the
#: reduction orders and still catches a wrong mask or window, which moves the
#: loss by per cent.
LOSS_RTOL = 2.0**-9
#: sharded against one device, loss and global grad-norm: the same bf16
#: program with other reduction orders (a wrong-axis psum is a factor, not a
#: rounding). The norm sums 7e8 squares whose terms each carry bf16 noise.
SHARDED_RTOL = 2.0**-7
#: engine and generate() against a plain forward: every token the engine
#: emits, and generate()'s token where the two part, must lie this close to
#: the plain forward's best logit at that position. The
#: random model's logits are ~N(0, 1) over 32000 words (the winner sits near
#: 4, a wrong page or position lands several units below it). Their noise is
#: bf16's: the dot path rounds attention scores of magnitude up to ~8 to one
#: ulp (2**-8 relative, so ~3% in a softmax weight), the three paths reduce in
#: different orders (32-token paged chunks, whole-prompt prefill, one padded
#: forward), and 16 layers add up. On the chip the gaps at the divergences
#: were up to 0.038 (CHANGES.md PR 21); 2**-4 is sixteen ulps at scale 1.
LOGIT_TIE_TOL = 2.0**-4


class SmokeFailure(AssertionError):
    """A phase ran and one of its checks did not hold."""


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


def note(message: str) -> None:
    """Progress on standard error: what a failed phase got through."""
    print(f"[chip_smoke] {message}", file=sys.stderr, flush=True)


def check(condition, message: str) -> None:
    if not condition:
        raise SmokeFailure(message)


def model_config(preset: Preset, *, num_layers: int, max_seq_len: int, attn_impl: str):
    """The published geometry with only depth (and the context the phase
    needs) changed — every width comes from ``preset.hf``."""
    return transformer_config_from_hf(
        types.SimpleNamespace(**preset.hf),
        num_layers=num_layers, max_seq_len=max_seq_len, attn_impl=attn_impl, dtype=jnp.bfloat16,
    )


def seeded_tokens(seed: int, vocab: int, shape) -> np.ndarray:
    return np.random.RandomState(seed).randint(0, vocab, size=shape).astype(np.int32)


def device_bytes(stat: str) -> list:
    """One ``memory_stats()`` entry of every device (None where the backend
    keeps no such statistic, as the CPU does). ``peak_bytes_in_use`` is a
    process-lifetime high-water mark."""
    return [(d.memory_stats() or {}).get(stat) for d in jax.devices()]


class CacheCounter:
    """Counts jax's own persistent-cache events (a hit is an executable
    loaded instead of compiled)."""

    def __init__(self):
        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self) -> dict:
        return {
            "dir": compile_cache.configured_cache_dir(),
            "entries": compile_cache.entry_count(),
            "hits": self.hits,
            "misses": self.misses,
        }


# ------------------------------------------------------------------ training


def make_optimizer():
    schedule = optax.warmup_cosine_decay_schedule(0.0, 1e-4, 20, 2000)
    return optax.adamw(schedule), schedule


class SmokeLMStage(dml.TrainValStage):
    """examples/train_lm.py's ``LMStage`` on one seeded batch that repeats:
    the same registrations, clipping, loss and step, so the compiled program
    is the one a user of the example gets. ``reference(stage)`` is called
    once when the parameters are freshly initialised, before the state takes
    them over."""

    def __init__(self, model_cfg, batch: np.ndarray, steps: int, reference):
        super().__init__()
        self.model_cfg = model_cfg
        self.batch = batch
        self.steps = steps
        self.reference = reference
        self.reference_result = None
        self.step_losses: list[float] = []

    def pre_stage(self):
        # the flash kernel shard_maps itself over the mesh (XLA cannot partition it)
        model = DecoderLM(dataclasses.replace(self.model_cfg, mesh=self.mesh))
        self.pipeline.register_dataset("train", [self.batch] * self.steps)
        self.pipeline.register_model(
            "lm", model, init_args=(np.zeros((1, 8), np.int32),), sharding=llama_partition_rules()
        )
        tx, schedule = make_optimizer()
        self.pipeline.register_optimizer("adamw", tx, scheduler=schedule)
        self.reference_result = self.reference(self)

    def gradient_clip(self):
        return 1.0

    def step(self, state, batch):
        logits = state.apply_fn({"params": state.params}, batch)
        return lm_loss(logits, batch)

    def run_epoch(self):
        super().run_epoch()
        # the epoch-end reduce folds the per-step losses into one mean; the
        # tracker still holds them here, already computed (train_epoch ends
        # in block_until_ready)
        name = f"{self.train_metric_prefix()}/{self.loss_metric_name()}"
        self.step_losses += [float(self._stall.fetch(v)) for v in self.tracker.reducers[name].values]


def run_train_stage(model_cfg, batch, steps, reference, seed, mesh):
    """A ``TrainValStage`` on a ``TrainingPipeline`` as the example builds
    them, precompiled so that compile time and step time come apart and the
    stage's signature registry counts recompiles. Returns the finished
    stage, its compiled step's text and the common part of the phase's output line."""
    pipe = dml.TrainingPipeline({"seed": seed}, name="chip-smoke-train", precompile=True)
    pipe.set_mesh(mesh)
    stage = SmokeLMStage(model_cfg, batch, steps, reference)
    pipe.append_stage(stage, max_epochs=1)
    t0 = time.perf_counter()
    pipe.run()
    wall_s = time.perf_counter() - t0

    losses = stage.step_losses
    check(len(losses) == steps, f"expected {steps} step losses, the tracker held {len(losses)}")
    check(all(np.isfinite(losses)), f"non-finite loss among the steps: {losses}")
    check(losses[-1] < losses[0], f"loss did not fall on a repeating batch: {losses}")
    recompiles = pipe.tracker["misc/recompiles"][-1]
    signatures = stage._train_compiled._cache_size()
    check(
        recompiles == 0 and signatures == 1,
        f"the train step recompiled after step 1: {recompiles} recompile(s), {signatures} signature(s)",
    )
    compiled = stage._train_compiled.any_compiled()
    hlo = compiled.as_text()
    has_kernel = "tpu_custom_call" in hlo
    if jax.devices()[0].platform == "tpu":
        # off the TPU flash_attention takes its blockwise-XLA twin by design;
        # on it, a step without the Pallas kernel means the kernel gave way
        check(has_kernel, "the compiled train step holds no tpu_custom_call: the flash kernel gave way")
    mem = compiled.memory_analysis()
    n_params = sum(int(x.size) for x in jax.tree_util.tree_leaves(stage.state.params))
    line = {
        "params_m": round(n_params / 1e6, 1),
        "tokens_per_step": int(batch.size),
        "losses": [round(v, 4) for v in losses],
        "pallas_kernel_in_hlo": has_kernel,
        "recompiles_after_step_1": int(recompiles),
        "compile_s": round(pipe.tracker["misc/compile_ms"][-1] / 1e3, 2),
        "smoke_step_ms": round(pipe.tracker["misc/train_step_avg_ms"][-1], 1),
        "wall_s": round(wall_s, 1),
        "compiled_step_bytes": {
            "arguments": mem.argument_size_in_bytes, "aliased": mem.alias_size_in_bytes,
            "temporaries": mem.temp_size_in_bytes, "outputs": mem.output_size_in_bytes,
        },
    }
    return stage, hlo, line


def train_phase(preset: Preset, seed: int) -> dict:
    cfg = model_config(preset, num_layers=preset.train_layers, max_seq_len=preset.train_seq, attn_impl="flash")
    batch = seeded_tokens(seed, cfg.vocab_size, (1, preset.train_seq))

    def dot_loss(stage):
        ref = DecoderLM(dataclasses.replace(cfg, attn_impl="dot"))
        loss = jax.jit(lambda p, t: lm_loss(ref.apply({"params": p}, t), t))
        return float(loss(stage.pipeline.models["lm"].params, batch))

    one_chip = mesh_lib.create_mesh({"data": 1}, devices=jax.devices()[:1])
    stage, _, line = run_train_stage(cfg, batch, preset.train_steps, dot_loss, seed, one_chip)
    ref = stage.reference_result
    rel = abs(stage.step_losses[0] - ref) / abs(ref)
    check(rel <= LOSS_RTOL, f"first-step loss {stage.step_losses[0]} (flash) vs {ref} (dot): rel {rel:.2e} > {LOSS_RTOL:.2e}")
    return {
        "phase": "train",
        "reduced": {"num_layers": [preset.hf["num_hidden_layers"], preset.train_layers]},
        **line,
        "first_loss_dot_path": round(ref, 4),
        "first_loss_rel_diff": float(f"{rel:.3e}"),
        "peak_bytes_in_use": device_bytes("peak_bytes_in_use")[0],
    }


def four_chip_phase(preset: Preset, seed: int) -> dict:
    """The same train step on an fsdp=2 x model=2 mesh against one device:
    loss and global grad-norm of the sharded program (the fingerprint
    ``__graft_entry__.dryrun_multichip`` uses) and the first step of the
    pipeline's own sharded stage, all from the same parameters."""
    mesh_axes = {"fsdp": 2, "model": 2}
    cfg = model_config(preset, num_layers=preset.train_layers, max_seq_len=preset.train_seq, attn_impl="flash")
    batch = seeded_tokens(seed, cfg.vocab_size, (2, preset.train_seq))

    def fingerprint(model, params, tokens):
        loss, grads = jax.jit(jax.value_and_grad(
            lambda p: lm_loss(model.apply({"params": p}, tokens), tokens)
        ))(params)
        return [float(loss), float(optax.global_norm(grads))]

    def both_fingerprints(stage):
        entry = stage.pipeline.models["lm"]
        sharded = fingerprint(entry.module, entry.params, mesh_lib.make_global_batch(batch, stage.mesh))
        dev0 = jax.devices()[0]
        single = fingerprint(DecoderLM(cfg), jax.device_put(entry.params, dev0), jax.device_put(batch, dev0))
        return sharded, single

    stage, hlo, line = run_train_stage(cfg, batch, preset.train_steps, both_fingerprints, seed, mesh_axes)
    sharded, single = stage.reference_result
    rels = {}
    for kind, a, b in zip(("loss", "grad_norm"), sharded, single):
        rels[kind] = abs(a - b) / abs(b)
        check(rels[kind] <= SHARDED_RTOL, f"sharded {kind} {a} vs one device {b}: rel {rels[kind]:.2e} > {SHARDED_RTOL:.2e}")
    rel_stage = abs(stage.step_losses[0] - single[0]) / abs(single[0])
    check(rel_stage <= SHARDED_RTOL, f"the sharded stage's first loss {stage.step_losses[0]} vs one device {single[0]}")

    in_use = device_bytes("bytes_in_use")
    if all(b is not None for b in in_use):
        # the state is 12 bytes a parameter; spread over four devices no one
        # of them may hold more than half of what there is
        check(max(in_use) <= 0.5 * sum(in_use), f"the state is not spread over the devices: {in_use}")
    collectives = {op: hlo.count(f" {op}(") + hlo.count(f" {op}-start(") for op in
                   ("all-gather", "reduce-scatter", "all-reduce", "all-to-all", "collective-permute")}
    check(collectives["all-gather"] > 0, "no all-gather in the sharded step: the parameters are not sharded")
    check(
        collectives["reduce-scatter"] + collectives["all-reduce"] > 0,
        "no gradient reduction in the sharded step",
    )
    return {
        "phase": "train_4chip",
        "mesh": dict(stage.mesh.shape),
        "reduced": {"num_layers": [preset.hf["num_hidden_layers"], preset.train_layers]},
        **line,
        "sharded_loss_gradnorm": sharded,
        "one_device_loss_gradnorm": single,
        "rel_diff": {k: float(f"{v:.3e}") for k, v in rels.items()},
        "stage_first_loss_rel_diff": float(f"{rel_stage:.3e}"),
        "bytes_in_use_per_device": in_use,
        "peak_bytes_in_use_per_device": device_bytes("peak_bytes_in_use"),
        "collectives_in_compiled_step": collectives,
    }


# ------------------------------------------------------------------- serving


def run_requests(engine: ServeEngine, prompts, new_tokens: int) -> tuple[list, float, int]:
    """Submit every prompt, step the engine until it is idle, and return
    the outputs in submission order, the wall seconds and the step count."""
    t0 = time.perf_counter()
    rids = [engine.submit(p, max_new_tokens=new_tokens) for p in prompts]
    steps = 0
    while not engine.idle:
        engine.step()
        steps += 1
    jax.block_until_ready(engine.pool.pools)
    wall_s = time.perf_counter() - t0
    statuses = [engine.status(r) for r in rids]
    check(all(s == "ok" for s in statuses), f"not every request ended ok: {statuses}")
    outs = [engine.output(r) for r in rids]
    check(all(len(o) == new_tokens for o in outs), f"short outputs: {[len(o) for o in outs]}")
    pool = engine.pool
    check(
        pool.num_free + pool.num_live == pool.num_blocks and pool.num_live == 0 and engine.leaked_blocks() == 0,
        f"blocks leaked: free {pool.num_free} live {pool.num_live} capacity {pool.num_blocks}",
    )
    return outs, wall_s, steps


def serve_phase(preset: Preset, seed: int) -> dict:
    cfg = model_config(preset, num_layers=preset.serve_layers, max_seq_len=preset.serve_max_len, attn_impl="dot")
    model = DecoderLM(cfg)
    t0 = time.perf_counter()
    # bf16 from the start: the fp32 tree of 16 layers alone would fill the chip
    params = jax.jit(
        lambda key: jax.tree_util.tree_map(
            lambda x: x.astype(cfg.dtype), model.init(key, jnp.zeros((1, 8), jnp.int32))["params"]
        )
    )(jax.random.PRNGKey(seed))
    jax.block_until_ready(params)
    init_s = time.perf_counter() - t0
    note(f"serve: bf16 parameters of {cfg.num_layers} layers initialised in {init_s:.1f}s")
    prompts = [seeded_tokens(seed + 1 + i, cfg.vocab_size, n) for i, n in enumerate(preset.prompt_lens)]

    engine = ServeEngine(model, params, num_blocks=preset.num_blocks, max_slots=len(prompts))
    # the first pass compiles every (batch x table) bucket this traffic
    # touches; the second is the same traffic on a warm engine
    cold_outs, cold_s, _ = run_requests(engine, prompts, preset.new_tokens)
    signatures = engine.compiled_signatures()
    note(f"serve: first pass {cold_s:.1f}s, {signatures} signatures compiled")
    outs, warm_s, steps = run_requests(engine, prompts, preset.new_tokens)
    note(f"serve: warm pass {warm_s:.2f}s over {steps} engine steps")
    check(engine.compiled_signatures() == signatures, "the warm pass compiled a new signature")
    check(signatures <= engine.max_signatures, f"{signatures} signatures exceed the budget {engine.max_signatures}")
    check(
        all(np.array_equal(a, b) for a, b in zip(cold_outs, outs)),
        "the same greedy requests gave other tokens on the second pass",
    )

    # serial generate(): one request at a time through the dense cache
    t0 = time.perf_counter()
    refs = [np.asarray(generate(model, params, p[None], preset.new_tokens))[0] for p in prompts]
    generate_s = time.perf_counter() - t0
    note(f"serve: serial generate() {generate_s:.1f}s")

    # the plain forward is the judge: one pass over prompt + engine output gives
    # the logits every emitted token was chosen from (causal, so the right
    # padding to one common length changes nothing before it)
    longest = max(preset.prompt_lens) + preset.new_tokens
    plain = jax.jit(  # only the rows the generated tokens were chosen from leave the device
        lambda p, toks, first: jax.lax.dynamic_slice_in_dim(model.apply({"params": p}, toks)[0], first, preset.new_tokens)
    )
    worst_gap, divergences = 0.0, []
    for i, (prompt, out, ref) in enumerate(zip(prompts, outs, refs)):
        padded = np.zeros((1, longest), np.int32)
        padded[0, : len(prompt) + len(out)] = np.concatenate([prompt, out])
        logits = np.asarray(plain(params, padded, len(prompt) - 1), np.float32)  # row j: generated token j
        gap = lambda j, tok: float(logits[j].max() - logits[j, int(tok)])
        worst_gap = max(worst_gap, max(gap(j, tok) for j, tok in enumerate(out)))
        if not np.array_equal(out, ref):
            pos = int(np.argmax(out != ref))  # the prefixes agree up to here
            divergences.append({"request": i, "position": pos, "logit_gap_engine": round(gap(pos, out[pos]), 5),
                                "logit_gap_generate": round(gap(pos, ref[pos]), 5)})
    check(
        worst_gap <= LOGIT_TIE_TOL,
        f"the engine emitted a token {worst_gap:.4f} below the plain forward's best (tolerance {LOGIT_TIE_TOL})",
    )
    check(
        all(d["logit_gap_generate"] <= LOGIT_TIE_TOL for d in divergences),
        f"generate() leaves the engine where the plain forward sees no near tie "
        f"(tolerance {LOGIT_TIE_TOL}): {divergences}",
    )
    tokens = len(prompts) * preset.new_tokens
    return {
        "phase": "serve",
        "reduced": {"num_layers": [preset.hf["num_hidden_layers"], preset.serve_layers]},
        "params_m": round(sum(int(x.size) for x in jax.tree_util.tree_leaves(params)) / 1e6, 1),
        "pool": {"blocks": engine.pool.num_blocks, "block_size": engine.pool.block_size,
                 "tokens": engine.pool.num_blocks * engine.pool.block_size},
        "requests": len(prompts), "prompt_lens": list(preset.prompt_lens), "new_tokens": preset.new_tokens,
        "all_ok": True, "leaked_blocks": 0,
        "signatures": signatures, "signature_budget": engine.max_signatures,
        "worst_logit_gap_of_an_engine_token": round(worst_gap, 5),
        "token_identical_to_generate": f"{len(prompts) - len(divergences)}/{len(prompts)}",
        "divergences": divergences,
        "init_s": round(init_s, 1),
        "compile_s": round(cold_s - warm_s, 1),
        "smoke_warm_pass_s": round(warm_s, 2),
        "smoke_ms_per_engine_step": round(warm_s / steps * 1e3, 1),
        "smoke_ms_per_token": round(warm_s / tokens * 1e3, 1),
        "generate_serial_s": round(generate_s, 1),
        "peak_bytes_in_use": device_bytes("peak_bytes_in_use")[0],
    }


# ---------------------------------------------------------------------- main


def versions() -> dict:
    import flax
    import jaxlib
    import orbax.checkpoint as ocp

    from dmlcloud_tpu.utils.thirdparty import try_get_version

    return {
        "python": sys.version.split()[0], "jax": jax.__version__, "jaxlib": jaxlib.__version__,
        "libtpu": try_get_version("libtpu"), "flax": flax.__version__, "optax": optax.__version__,
        "orbax": ocp.__version__,
    }


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1)
    parser.add_argument("--rehearse", action="store_true")
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args(argv)

    rung = runtime.init_auto()
    devices = jax.devices()
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind, "count": len(devices)}
    emit({"device": device, "versions": versions(), "init_rung": rung, "options": vars(args)})
    verdict = {"ok": False, "device": device}
    if device["count"] < args.chips:
        emit({"refused": f"--chips {args.chips} needs {args.chips} devices, jax sees {device['count']}"})
        print(json.dumps(verdict))
        return 2
    if device["platform"] != "tpu" and not args.rehearse:
        emit({"refused": f"platform is {device['platform']!r}, not 'tpu'; no phase was run (see --rehearse)"})
        print(json.dumps(verdict))
        return 2

    counter = CacheCounter()
    compile_cache.configure_cache()  # before the first compile; the pipeline and the engine would, later
    emit({"compile_cache": counter.snapshot()})
    preset = TINY if args.rehearse else FULL
    phases = [four_chip_phase] if args.chips == 4 else [train_phase, serve_phase]
    passed = []
    for phase in phases:
        try:
            line = phase(preset, args.seed)
        except Exception:  # noqa: BLE001 — reported, and the run ends non-zero
            traceback.print_exc()
            emit({"phase_failed": phase.__name__})
            break
        emit({**line, "compile_cache": counter.snapshot()})
        passed.append(phase.__name__)
        gc.collect()  # the phase's state and executables leave the device before the next

    from dmlcloud_tpu.native import _lib as native_lib

    emit({"native": {
        "data": "token batches from --seed; no packer or interleaver runs on this path",
        "libdmltpu_requested": native_lib._TRIED, "libdmltpu_loaded": native_lib._LIB is not None,
    }})
    all_passed = len(passed) == len(phases) and native_lib._TRIED == (native_lib._LIB is not None)
    if args.rehearse:
        emit({"rehearsal": True, "phases_passed": passed})
    verdict["ok"] = all_passed and not args.rehearse and device["platform"] == "tpu"
    print(json.dumps(verdict))
    return 0 if verdict["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
