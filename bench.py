"""Benchmark: ResNet-50 synthetic-ImageNet training throughput per chip.

The BASELINE.md headline metric ("ResNet-50 images/sec/chip"; the reference
publishes no numbers, BASELINE.json "published": {}). Two measurements:

1. raw: a hand-written jitted train step (bf16 NHWC ResNet-50 v1.5,
   SGD+momentum, BN batch_stats threaded as aux) — the ceiling a user could
   reach with plain JAX on this chip.
2. framework: the same model driven through TrainingPipeline/TrainValStage —
   what users of this framework actually get, including metric tracking.

Prints ONE JSON line; ``value`` is the framework-path throughput and
``vs_baseline`` is framework/raw (1.0 == zero framework overhead; the
reference's equivalent overhead is its Python hot loop, stage.py:298-314).

ONE PROCESS PER CHIP. A chip belongs to one process at a time: a parent that
has touched a jax backend holds it, and a child that needs it then fails or
hangs. So this parent never touches one (importing jax and dmlcloud_tpu does
not create a client; ``jax.devices()`` or the first computation would), every
device measurement runs in ONE child (``python bench.py --tpu-child``) that
``main()`` starts exactly once, and every other child is pinned to the CPU
(``JAX_PLATFORMS=cpu``). The device path has no fallback: a child that finds
no TPU, or loses a phase, exits non-zero, and then so does the parent,
without printing a value.
"""

import functools
import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax

import dmlcloud_tpu as dml
from dmlcloud_tpu.models.resnet import ResNet50
from dmlcloud_tpu.parallel import init_auto

#: Candidate per-chip batch sizes: the raw step is timed at each and the
#: headline (raw ceiling + framework path) uses the fastest — batch is a
#: free throughput parameter on one chip, so the bench should not pin an
#: arbitrary one. Candidates that exhaust HBM are skipped (caught per-batch).
BATCH_CANDIDATES = (128, 256, 512)
IMG = 224
WARMUP_STEPS = 5
TIMED_STEPS = 30

#: ResNet-50 v1.5 @ 224^2: 4.1 GMACs forward = 8.2 GFLOPs in the MFU
#: convention (multiply-add = 2 ops — what the chip's own counters and every
#: peak-TFLOP/s figure use); training ~= 3x forward (backward ~2x). The
#: widely quoted "4.1 GFLOPs" is the MAC count — using it halves MFU against
#: a peak quoted in real FLOPs. Hardware cross-check: the step trace counts
#: 23.9 GFLOPs/image trained (scripts/analyze_trace.py on the
#: tune_resnet.py trace), within 3% of 3 x 8.2e9.
TRAIN_FLOPS_PER_IMAGE = 3 * 8.2e9

from dmlcloud_tpu.utils.profiling import chip_peak_flops  # noqa: E402 — shared peak table


def synthetic_batch(rng: np.random.RandomState, batch: int):
    return {
        "image": rng.rand(batch, IMG, IMG, 3).astype(np.float32),
        "label": rng.randint(0, 1000, size=batch),
    }


def make_model_and_state():
    model = ResNet50(num_classes=1000, dtype=jnp.bfloat16)
    variables = model.init(jax.random.PRNGKey(0), jnp.zeros((1, IMG, IMG, 3)), train=True)
    tx = optax.sgd(0.1, momentum=0.9)
    return model, variables, tx


def bench_raw(batch) -> float:
    batch_size = int(batch["label"].shape[0])
    model, variables, tx = make_model_and_state()
    params, batch_stats = variables["params"], variables["batch_stats"]
    opt_state = tx.init(params)

    # donate the state buffers like the framework path does (stage.py jit
    # donate_argnums) — otherwise the raw "ceiling" pays an extra whole-model
    # copy per step that no real training loop would
    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def train_step(params, batch_stats, opt_state, batch):
        def loss_fn(p):
            logits, new_state = model.apply(
                {"params": p, "batch_stats": batch_stats},
                batch["image"],
                train=True,
                mutable=["batch_stats"],
            )
            loss = optax.softmax_cross_entropy_with_integer_labels(logits, batch["label"]).mean()
            return loss, new_state["batch_stats"]

        (loss, new_bs), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        updates, new_opt = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), new_bs, new_opt, loss

    device_batch = jax.device_put(batch)
    for _ in range(WARMUP_STEPS):
        params, batch_stats, opt_state, loss = train_step(params, batch_stats, opt_state, device_batch)
    float(loss)  # completion sync: the fetch waits for the whole dependency chain

    t0 = time.perf_counter()
    for _ in range(TIMED_STEPS):
        params, batch_stats, opt_state, loss = train_step(params, batch_stats, opt_state, device_batch)
    float(loss)  # forces the whole dependency chain
    dt = time.perf_counter() - t0
    return TIMED_STEPS * batch_size / dt


class ResNetBenchStage(dml.TrainValStage):
    def __init__(self, batch):
        super().__init__()
        self._batch = batch

    def pre_stage(self):
        model, variables, tx = make_model_and_state()
        self.pipeline.register_model("resnet50", model, params=variables, verbose=False)
        self.pipeline.register_optimizer("sgd", tx)
        steps = WARMUP_STEPS + TIMED_STEPS
        # pre-stage the batch on device once — host->HBM transfer is not part
        # of the step-throughput metric (the raw path does the same)
        device_batch = jax.device_put(self._batch)
        self.pipeline.register_dataset("train", [device_batch] * steps, verbose=False)

    def step(self, state, batch):
        logits, new_state = state.apply_fn(
            {"params": state.params, **state.extras},
            batch["image"],
            train=True,
            mutable=["batch_stats"],
        )
        loss = optax.softmax_cross_entropy_with_integer_labels(logits, batch["label"]).mean()
        return loss, {}, {"batch_stats": new_state["batch_stats"]}

    def val_epoch(self):  # throughput bench: train only
        pass


def _instrument_stage(stage):
    """Timer hook: marks completion of [first step, warmup tail, timed tail]
    on device (the first two coincide when WARMUP_STEPS == 1). The last two
    bracket the throughput window; the first, against the time ``run()`` was
    entered, is the time-to-first-step — the startup tax every receipt now
    records."""
    marks: list = []
    count = [0]
    mark_at = {1, WARMUP_STEPS, WARMUP_STEPS + TIMED_STEPS}
    orig_build = stage._build_train_step

    def instrumented_build():
        fn = orig_build()
        loss_name = stage.loss_metric_name()

        def wrapped(state, b):
            out = fn(state, b)
            count[0] += 1
            if count[0] in mark_at:
                float(out[1][loss_name])  # value fetch forces the whole chain
                marks.append(time.perf_counter())
            return out

        return wrapped

    stage._build_train_step = instrumented_build
    return marks


def bench_framework(batch) -> dict:
    pipeline = dml.TrainingPipeline(name="bench-resnet50")
    stage = ResNetBenchStage(batch)
    pipeline.append_stage(stage, max_epochs=1)
    marks = _instrument_stage(stage)
    t0 = time.perf_counter()
    pipeline.run()
    batch_size = int(batch["label"].shape[0])
    return {
        "ips": TIMED_STEPS * batch_size / (marks[-1] - marks[-2]),
        "time_to_first_step_s": marks[0] - t0,
    }


def _lm_model(s=1024, layers=12, vocab=32000, hidden=768, heads=12, kv=4, head_dim=64,
              mlp=2048, remat=False):
    from dmlcloud_tpu.models.transformer import DecoderLM, TransformerConfig

    cfg = TransformerConfig(
        vocab_size=vocab, num_layers=layers, num_heads=heads, num_kv_heads=kv,
        head_dim=head_dim, hidden_dim=hidden, mlp_dim=mlp, max_seq_len=s,
        dtype=jnp.bfloat16, attn_impl="flash", remat=remat,
    )
    return DecoderLM(cfg), cfg


def bench_lm(iters=15, b=8, s=1024, layers=12, vocab=32000, vocab_chunk=0, **model_kw):
    """Decoder-LM training throughput (tokens/s/chip): Llama-style bf16
    model, flash attention, donated jitted step. MFU uses the standard
    6·params FLOPs/token training estimate. ``vocab_chunk > 0`` computes the
    loss via chunked_lm_loss (no [B,S,V] logits materialized) instead of the
    full-logits path — same model, same tokens, so the ratio of the two is
    the chunked-loss overhead (or win) at this vocab."""
    import jax.tree_util as jtu

    from dmlcloud_tpu.models.transformer import chunked_lm_loss, lm_loss

    model, cfg = _lm_model(s, layers, vocab, **model_kw)
    tokens = jnp.asarray(np.random.RandomState(0).randint(0, cfg.vocab_size, (b, s)), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens[:1, :8])["params"]
    # MFU counts matmul params only (PaLM convention): the embedding table
    # is a lookup, no FLOPs — the (untied) lm_head matmul still counts
    n_params = sum(int(x.size) for x in jtu.tree_leaves(params)) - int(
        params["embed"]["embedding"].size
    )
    tx = optax.adamw(1e-4)
    opt = tx.init(params)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(params, opt, tokens):
        def loss_fn(p):
            if vocab_chunk > 0:
                hidden_out = model.apply({"params": p}, tokens, return_hidden=True)
                return chunked_lm_loss(
                    hidden_out, p["lm_head"]["kernel"], tokens, vocab_chunk=vocab_chunk
                )
            return lm_loss(model.apply({"params": p}, tokens), tokens)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        up, new_opt = tx.update(grads, opt, params)
        return optax.apply_updates(params, up), new_opt, loss

    for _ in range(3):
        params, opt, loss = step(params, opt, tokens)
    float(loss)  # completion sync
    t0 = time.perf_counter()
    for _ in range(iters):
        params, opt, loss = step(params, opt, tokens)
    float(loss)
    dt = (time.perf_counter() - t0) / iters
    tps = b * s / dt
    mfu = tps * 6 * n_params / chip_peak_flops()
    return tps, mfu


class LMBenchStage(dml.TrainValStage):
    """The transformer family's framework path: DecoderLM + flash attention
    driven through TrainingPipeline/TrainValStage, so the flagship features
    get the same overhead measurement bench_framework gives ResNet."""

    def __init__(self, tokens, s, layers, vocab):
        super().__init__()
        self._tokens = tokens
        self._shape = (s, layers, vocab)

    def pre_stage(self):
        model, cfg = _lm_model(*self._shape)
        params = model.init(jax.random.PRNGKey(0), self._tokens[:1, :8])
        self.pipeline.register_model("lm", model, params=params, verbose=False)
        self.pipeline.register_optimizer("adamw", optax.adamw(1e-4))
        device_tokens = jax.device_put(self._tokens)
        self.pipeline.register_dataset(
            "train", [device_tokens] * (WARMUP_STEPS + TIMED_STEPS), verbose=False
        )

    def step(self, state, batch):
        from dmlcloud_tpu.models.transformer import lm_loss

        return lm_loss(state.apply_fn({"params": state.params}, batch), batch)

    def val_epoch(self):  # throughput bench: train only
        pass


def bench_lm_framework(b=8, s=1024, layers=12, vocab=32000) -> dict:
    """Tokens/s of the same LM config as bench_lm, through the full
    framework path. vs bench_lm's raw loop == the framework overhead for
    transformer users."""
    tokens = jnp.asarray(np.random.RandomState(0).randint(0, vocab, (b, s)), jnp.int32)
    pipeline = dml.TrainingPipeline(name="bench-lm")
    stage = LMBenchStage(tokens, s, layers, vocab)
    pipeline.append_stage(stage, max_epochs=1)
    marks = _instrument_stage(stage)
    t0 = time.perf_counter()
    pipeline.run()
    return {
        "tps": TIMED_STEPS * b * s / (marks[-1] - marks[-2]),
        "time_to_first_step_s": marks[0] - t0,
    }


def bench_decode(b=8, prompt_len=128, new_tokens=512, layers=12, vocab=32000, reps=3):
    """Greedy decode throughput (generated tokens/s): chunked-attend cache
    (attention cost scales with fill, models/generate.py). One compile, then
    best-of-reps timed runs. Returns (bf16_tps, int8_weight_tps) — decode is
    weight-bandwidth-bound, so int8 weight-only quantization (models/quant.py)
    is measured on exactly the same generate call."""
    from dmlcloud_tpu.models.generate import generate
    from dmlcloud_tpu.models.quant import quantize_tree

    model, cfg = _lm_model(s=prompt_len + new_tokens, layers=layers, vocab=vocab)
    prompt = jnp.asarray(
        np.random.RandomState(0).randint(0, vocab, (b, prompt_len)), jnp.int32
    )
    params = model.init(jax.random.PRNGKey(0), prompt[:1, :8])["params"]

    def timed(p):
        np.asarray(generate(model, p, prompt, new_tokens))  # compile + sync
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            np.asarray(generate(model, p, prompt, new_tokens))  # value fetch = sync
            best = min(best, time.perf_counter() - t0)
        return b * new_tokens / best

    tps = timed(params)
    int8_tps = None
    try:
        int8_tps = timed(quantize_tree(params))
    except Exception as e:  # quantized path must not cost the bf16 number
        print(f"child: int8 decode bench failed: {type(e).__name__}: {e}", file=sys.stderr)
    return tps, int8_tps


def bench_speculative(b=8, prompt_len=64, new_tokens=256, k=4, vocab=512,
                      train_steps=400, train_b=32, train_s=128, reps=3,
                      target_layers=12, draft_layers=2, lr=1e-3, **model_kw):
    """Speculative-decoding speedup over plain greedy decode of the SAME
    target, plus the measured draft accept rate (models/speculative.py).

    Target (12L/768d) and draft (2L/768d) are first trained for a few
    seconds on a learnable synthetic corpus so the draft actually agrees
    with the target — speculation's win depends on the accept rate, so a
    bench against an unlearnable distribution would measure nothing real.
    Returns (plain_tps, spec_tps, accept_rate, k, target_loss, draft_loss);
    the two final train losses are the published learnedness gate — an
    accept rate only means something when both sit near the corpus's
    ~0.9-nat entropy floor (not far above = unlearned, not ~0 = memorized)."""
    from dmlcloud_tpu.data import markov_tokens
    from dmlcloud_tpu.models.generate import generate
    from dmlcloud_tpu.models.speculative import speculative_generate
    from dmlcloud_tpu.models.transformer import lm_loss

    max_len = prompt_len + new_tokens + k + 1
    target, _ = _lm_model(s=max_len, layers=target_layers, vocab=vocab, **model_kw)
    draft, _ = _lm_model(s=max_len, layers=draft_layers, vocab=vocab, **model_kw)
    # MANY distinct batches, cycled: training on one fixed batch memorizes
    # the noisy sequences (loss -> 0) instead of learning the successor
    # table, and a memorizer agrees with nothing on fresh prompts
    n_batches = min(train_steps, 16)
    corpus = markov_tokens(vocab, train_b * n_batches, train_s)
    batches = [
        jnp.asarray(corpus[i * train_b:(i + 1) * train_b], jnp.int32) for i in range(n_batches)
    ]

    def train(model, seed):
        params = model.init(jax.random.PRNGKey(seed), batches[0][:1, :8])["params"]
        tx = optax.adamw(lr)
        opt = tx.init(params)

        @functools.partial(jax.jit, donate_argnums=(0, 1))
        def step(params, opt, tokens):
            loss, grads = jax.value_and_grad(
                lambda p: lm_loss(model.apply({"params": p}, tokens), tokens)
            )(params)
            up, new_opt = tx.update(grads, opt, params)
            return optax.apply_updates(params, up), new_opt, loss

        for i in range(train_steps):
            params, opt, loss = step(params, opt, batches[i % n_batches])
        return params, float(loss)

    tparams, target_loss = train(target, 0)
    dparams, draft_loss = train(draft, 1)
    # fresh prompts from the SAME successor table the models trained on
    prompt = jnp.asarray(markov_tokens(vocab, b, prompt_len, seed=7, table_seed=0), jnp.int32)

    def timed(fn):
        np.asarray(fn())  # compile + sync
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            np.asarray(fn())
            best = min(best, time.perf_counter() - t0)
        return b * new_tokens / best

    plain_tps = timed(lambda: generate(target, tparams, prompt, new_tokens))

    # ONE compiled spec program: the stats ride the timed variant (greedy is
    # deterministic, so every rep returns identical rounds/advance)
    stats = {}

    def spec_fn():
        toks, stats["rg"] = speculative_generate(
            target, tparams, draft, dparams, prompt, new_tokens, k=k, return_stats=True
        )
        return toks

    spec_tps = timed(spec_fn)
    rounds, _, accepted = (np.asarray(x, np.float64) for x in stats["rg"])
    # the EXACT per-row acceptance counter (models/speculative.py): robust
    # to eos truncation, unlike the old advance-derived algebra
    accept_rate = float(np.mean(accepted / np.maximum(rounds * k, 1)))
    return plain_tps, spec_tps, accept_rate, k, target_loss, draft_loss


def bench_lm_scale(b=4, s=1024, iters=8, **model_kw):
    """Scale-up MFU datapoint: a 24L/1024d model (≈370M matmul params),
    remat OFF vs ON at the same batch — shows whether the framework's step
    holds MFU as the model grows and what recomputation costs.
    Returns {"tps": .., "mfu": .., "tps_remat": .., "mfu_remat": ..}."""
    big = dict(layers=24, vocab=32000, hidden=1024, heads=16, kv=8, head_dim=64, mlp=2816)
    big.update(model_kw)
    out = {}
    try:
        tps, mfu = bench_lm(iters=iters, b=b, s=s, **big)
        out["tps"], out["mfu"] = tps, mfu
    except Exception as e:  # noqa: BLE001 — e.g. HBM exhaustion without remat
        print(f"child: 24L no-remat bench failed: {type(e).__name__}: {e}", file=sys.stderr)
    tps_r, mfu_r = bench_lm(iters=iters, b=b, s=s, remat=True, **big)
    out["tps_remat"], out["mfu_remat"] = tps_r, mfu_r
    return out


def bench_flash(seq=8192, b=2, h=8, d=64, iters=20):
    """On-chip flash-kernel microbench: fused Pallas kernel vs the unfused
    einsum path, causal. Returns (fwd tokens/s, fwd speedup_vs_dot,
    window speedup, fwd+bwd speedup_vs_dot — the number training pays)."""
    from dmlcloud_tpu.ops.flash_attention import _reference_attention, flash_attention

    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(b, seq, h, d), jnp.bfloat16) * 0.5
    k = jnp.asarray(rng.randn(b, seq, h, d), jnp.bfloat16) * 0.5
    v = jnp.asarray(rng.randn(b, seq, h, d), jnp.bfloat16)

    def timed(fn, reps=3):
        out = fn(q, k, v)
        np.asarray(out[..., :1, :1].astype(jnp.float32))  # value fetch = completion sync
        best = float("inf")
        for _ in range(reps):  # best-of-reps: one run is noisy
            t0 = time.perf_counter()
            for _ in range(iters):
                out = fn(q, k, v)
            np.asarray(out[..., :1, :1].astype(jnp.float32))
            best = min(best, (time.perf_counter() - t0) / iters)
        return best

    def grad_of(attn):
        def loss(q, k, v):
            return jnp.sum(attn(q, k, v).astype(jnp.float32) ** 2)

        return jax.grad(loss, argnums=0)

    flash_fn = lambda q, k, v: flash_attention(q, k, v, causal=True)
    dot_fn = lambda q, k, v: _reference_attention(q, k, v, True, 1.0 / np.sqrt(d))
    t_flash = timed(jax.jit(flash_fn))
    t_dot = timed(jax.jit(dot_fn))
    # sliding window at W=1024: stale K/V blocks are skipped + DMAs elided,
    # so this should approach full-flash-time x (W / S) as S grows
    t_win = timed(jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True, window=1024)))
    # fwd+bwd: what a training step actually pays. Guarded separately — the
    # UNFUSED backward materializes fp32 scores (~4 GB at S=8k) and can OOM
    # where everything above fits; the banked fwd numbers must survive that.
    fwdbwd_speedup = None
    try:
        t_flash_bwd = timed(jax.jit(grad_of(flash_fn)), reps=2)
        t_dot_bwd = timed(jax.jit(grad_of(dot_fn)), reps=2)
        fwdbwd_speedup = t_dot_bwd / t_flash_bwd
    except Exception as e:  # noqa: BLE001
        print(f"child: flash fwd+bwd timing failed: {type(e).__name__}: {e}", file=sys.stderr)
    return b * seq / t_flash, t_dot / t_flash, t_flash / t_win, fwdbwd_speedup


#: Marker line of the --kernels-child results (CPU-pinned).
_KERNELS_MARKER = "KERNEL_BENCH_RESULTS "

#: the CPU-smoke kernel A/B configs — pinned so receipts stay comparable
#: across rounds (same box, same shapes as the prior BENCH_r* smokes)
_KERNEL_FLASH_CFG = dict(seq=512, b=1, h=2, d=64)
_KERNEL_INT8_CFG = dict(b=2, prompt_len=16, new_tokens=32, layers=2, vocab=512)
_KERNEL_SPEC_CFG = dict(
    vocab=64, train_steps=100, train_b=8, train_s=32, b=4, prompt_len=16, new_tokens=48, k=3,
    target=dict(layers=6, hidden=256, heads=4, kv=2, head_dim=32, mlp=768),
    draft=dict(layers=1, hidden=128, heads=2, kv=1, head_dim=32, mlp=384),
)


def _best_of(fn, sync, iters=1, reps=3):
    """best-of-reps wall time of ``iters`` calls of ``fn`` (sync via value
    fetch of ``sync(out)``)."""
    out = fn()
    np.asarray(sync(out))
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn()
        np.asarray(sync(out))
        best = min(best, (time.perf_counter() - t0) / iters)
    return best


def kernel_flash_ab(seq=512, b=1, h=2, d=64, iters=10, reps=3):
    """Flash attention (blockwise-XLA off-TPU path) vs the unfused einsum
    reference, fwd AND fwd+bwd (the number training pays), on the pinned
    CPU-smoke config. The backward is the custom_vjp recompute-from-LSE
    path on the flash side and plain autodiff on the reference side —
    exactly what each implementation makes a training step pay."""
    from dmlcloud_tpu.ops.flash_attention import _reference_attention, flash_attention

    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(b, seq, h, d), jnp.bfloat16) * 0.5
    k = jnp.asarray(rng.randn(b, seq, h, d), jnp.bfloat16) * 0.5
    v = jnp.asarray(rng.randn(b, seq, h, d), jnp.bfloat16)
    sync1 = lambda out: out[..., :1, :1].astype(jnp.float32)

    flash = jax.jit(lambda: flash_attention(q, k, v, causal=True))
    dot = jax.jit(lambda: _reference_attention(q, k, v, True, 1.0 / np.sqrt(d)))
    win = jax.jit(lambda: flash_attention(q, k, v, causal=True, window=128))
    t_flash = _best_of(flash, sync1, iters, reps)
    t_dot = _best_of(dot, sync1, iters, reps)
    t_win = _best_of(win, sync1, iters, reps)

    def grad_of(attn):
        loss = lambda q, k, v: jnp.sum(attn(q, k, v).astype(jnp.float32) ** 2)
        g = jax.grad(loss, argnums=(0, 1, 2))
        return jax.jit(lambda: g(q, k, v))

    sync_g = lambda gs: gs[0][..., :1, :1].astype(jnp.float32)
    t_flash_bwd = _best_of(grad_of(lambda q, k, v: flash_attention(q, k, v, causal=True)), sync_g, iters, reps)
    t_dot_bwd = _best_of(
        grad_of(lambda q, k, v: _reference_attention(q, k, v, True, 1.0 / np.sqrt(d))), sync_g, iters, reps
    )
    return {
        "config": dict(seq=seq, b=b, h=h, d=d, dtype="bfloat16", causal=True),
        "fwd_tokens_per_sec": round(b * seq / t_flash, 1),
        "fwd_speedup_vs_unfused": round(t_dot / t_flash, 3),
        "fwdbwd_speedup_vs_unfused": round(t_dot_bwd / t_flash_bwd, 3),
        "window128_speedup_vs_full": round(t_flash / t_win, 3),
    }


def _spec_lm(vocab, s, layers, hidden, heads, kv, head_dim, mlp):
    from dmlcloud_tpu.models.transformer import DecoderLM, TransformerConfig

    cfg = TransformerConfig(
        vocab_size=vocab, num_layers=layers, num_heads=heads, num_kv_heads=kv,
        head_dim=head_dim, hidden_dim=hidden, mlp_dim=mlp, max_seq_len=s,
        dtype=jnp.float32, attn_impl="flash",
    )
    return DecoderLM(cfg)


def kernel_spec_ab(reps=3):
    """Speculative vs plain greedy decode on a target/draft pair trained on
    the same learnable Markov corpus (fp32 — exact arithmetic, so the
    token-identity contract is bitwise). Also runs the SHARED-MODEL smoke:
    draft == target must accept every proposal (rate exactly 1.0) — the
    provably->0 contract the r01-r05 receipts' 0.0 showed was never being
    measured (their smoke trained the pair 5 steps; see bench.py
    spec_kw)."""
    from dmlcloud_tpu.data import markov_tokens
    from dmlcloud_tpu.models.generate import generate
    from dmlcloud_tpu.models.speculative import speculative_generate
    from dmlcloud_tpu.models.transformer import lm_loss

    cfg = _KERNEL_SPEC_CFG
    vocab, k = cfg["vocab"], cfg["k"]
    max_len = cfg["prompt_len"] + cfg["new_tokens"] + k + 1
    target = _spec_lm(vocab, max_len, **cfg["target"])
    draft = _spec_lm(vocab, max_len, **cfg["draft"])
    n_batches = 8
    corpus = markov_tokens(vocab, cfg["train_b"] * n_batches, cfg["train_s"])
    batches = [
        jnp.asarray(corpus[i * cfg["train_b"]:(i + 1) * cfg["train_b"]], jnp.int32)
        for i in range(n_batches)
    ]

    def train(model, seed):
        params = model.init(jax.random.PRNGKey(seed), batches[0][:1, :8])["params"]
        tx = optax.adamw(2e-3)
        opt = tx.init(params)

        @functools.partial(jax.jit, donate_argnums=(0, 1))
        def step(params, opt, tokens):
            loss, grads = jax.value_and_grad(
                lambda p: lm_loss(model.apply({"params": p}, tokens), tokens)
            )(params)
            up, new_opt = tx.update(grads, opt, params)
            return optax.apply_updates(params, up), new_opt, loss

        for i in range(cfg["train_steps"]):
            params, opt, loss = step(params, opt, batches[i % n_batches])
        return params, float(loss)

    tparams, target_loss = train(target, 0)
    dparams, draft_loss = train(draft, 1)
    prompt = jnp.asarray(
        markov_tokens(vocab, cfg["b"], cfg["prompt_len"], seed=7, table_seed=0), jnp.int32
    )
    new = cfg["new_tokens"]

    plain = lambda: generate(target, tparams, prompt, new)
    t_plain = _best_of(plain, lambda o: o, reps=reps)
    stats = {}

    def spec():
        toks, stats["rga"] = speculative_generate(
            target, tparams, draft, dparams, prompt, new, k=k, return_stats=True
        )
        return toks

    t_spec = _best_of(spec, lambda o: o, reps=reps)
    rounds, _, accepted = (np.asarray(x, np.float64) for x in stats["rga"])
    accept = float(np.mean(accepted / np.maximum(rounds * k, 1)))
    identical = bool(np.array_equal(np.asarray(plain()), np.asarray(spec())))

    # shared-model smoke: draft IS the target — acceptance must be exactly 1
    toks_s, (r_s, _, a_s) = speculative_generate(
        target, tparams, target, tparams, prompt, 16, k=k, return_stats=True
    )
    shared_accept = float(np.mean(np.asarray(a_s, np.float64) / np.maximum(np.asarray(r_s, np.float64) * k, 1)))
    shared_identical = bool(
        np.array_equal(np.asarray(generate(target, tparams, prompt, 16)), np.asarray(toks_s))
    )
    return {
        "config": {kk: vv for kk, vv in cfg.items()},
        "plain_tokens_per_sec": round(cfg["b"] * new / t_plain, 1),
        "spec_tokens_per_sec": round(cfg["b"] * new / t_spec, 1),
        "speedup_vs_plain": round(t_plain / t_spec, 3),
        "accept_rate": round(accept, 4),
        "token_identical_to_plain_greedy": identical,
        "target_loss": round(target_loss, 3),
        "draft_loss": round(draft_loss, 3),
        "shared_model_accept_rate": round(shared_accept, 4),
        "shared_model_token_identical": shared_identical,
    }


def _interleaved_best(fns, reps=3):
    """Best-of wall times of several closures, measured INTERLEAVED (arm 0,
    arm 1, ..., repeat) so machine drift during the run penalises every arm
    equally instead of whichever happened to go last."""
    for fn in fns:
        np.asarray(fn())  # warm + compile outside the timed region
    best = [float("inf")] * len(fns)
    for _ in range(reps):
        for j, fn in enumerate(fns):
            t0 = time.perf_counter()
            np.asarray(fn())
            best[j] = min(best[j], time.perf_counter() - t0)
    return best


def kernel_int8_ab(reps=5):
    """int8 weight-only decode (fused QuantDense path) vs the bf16 baseline
    on the pinned CPU-smoke decode config — exactly bench_decode's A/B, at
    the smoke shape the prior receipts used.

    The primary number decodes from a tree prepared ONCE with
    ``prepare_decode_params`` (model-load-time work in a serving loop: the
    off-TPU int8 -> fp32 operand widen is pre-paid, so the measured calls
    contain only the decode itself). ``speedup_unprepared`` keeps the raw
    pass-the-quantized-tree-every-call ratio visible — it re-pays the widen
    once per call."""
    from dmlcloud_tpu.models.generate import generate
    from dmlcloud_tpu.models.quant import prepare_decode_params, quantize_tree

    cfg = _KERNEL_INT8_CFG
    model, _ = _lm_model(
        s=cfg["prompt_len"] + cfg["new_tokens"], layers=cfg["layers"], vocab=cfg["vocab"]
    )
    prompt = jnp.asarray(
        np.random.RandomState(0).randint(0, cfg["vocab"], (cfg["b"], cfg["prompt_len"])), jnp.int32
    )
    params = model.init(jax.random.PRNGKey(0), prompt[:1, :8])["params"]
    new = cfg["new_tokens"]

    qparams = quantize_tree(params)
    prepared = prepare_decode_params(qparams, jnp.bfloat16)
    t_bf16, t_int8, t_raw = _interleaved_best(
        [
            lambda: generate(model, params, prompt, new),
            lambda: generate(model, prepared, prompt, new),
            lambda: generate(model, qparams, prompt, new),
        ],
        reps=reps,
    )
    agreement = float(
        (np.asarray(generate(model, params, prompt, new)) == np.asarray(generate(model, prepared, prompt, new))).mean()
    )
    # identical arithmetic (int8 -> fp32 widen is exact), so prepared and
    # raw quantized trees must decode to the same tokens
    prep_identical = bool(
        np.array_equal(
            np.asarray(generate(model, qparams, prompt, new)),
            np.asarray(generate(model, prepared, prompt, new)),
        )
    )
    return {
        "config": dict(cfg, hidden=768, dtype="bfloat16"),
        "bf16_tokens_per_sec": round(cfg["b"] * new / t_bf16, 1),
        "int8_tokens_per_sec": round(cfg["b"] * new / t_int8, 1),
        "speedup": round(t_bf16 / t_int8, 3),
        "speedup_unprepared": round(t_bf16 / t_raw, 3),
        "prepared_token_identical_to_raw_int8": prep_identical,
        "greedy_agreement": round(agreement, 4),
    }


def kernels_child_main():
    """Runs the three kernel A/Bs in a fresh CPU-pinned process and prints
    one marker line of JSON — the source of the ``BENCH_kernels_*.json``
    receipts and of ``bench.py --gate``'s "current" kernel ratios."""
    jax.config.update("jax_platforms", "cpu")
    results: dict = {"errors": [], "host": _host_fingerprint()}
    for name, fn in (("flash_attn", kernel_flash_ab), ("int8_decode", kernel_int8_ab),
                     ("spec_decode", kernel_spec_ab)):
        try:
            results[name] = fn()
        except Exception as e:  # noqa: BLE001 — one A/B must not kill the rest
            results[name] = None
            results["errors"].append(f"{name}: {type(e).__name__}: {e}")
            print(f"kernels-child: {name} failed: {type(e).__name__}: {e}", file=sys.stderr, flush=True)
    flash = results.get("flash_attn") or {}
    spec = results.get("spec_decode") or {}
    int8 = results.get("int8_decode") or {}
    # the flat, schema-stable section the perf gate compares across receipts
    results["gate"] = {
        "flash_fwd_speedup_vs_unfused": flash.get("fwd_speedup_vs_unfused"),
        "flash_fwdbwd_speedup_vs_unfused": flash.get("fwdbwd_speedup_vs_unfused"),
        "spec_decode_speedup_vs_plain": spec.get("speedup_vs_plain"),
        "spec_decode_accept_rate": spec.get("accept_rate"),
        "int8_decode_speedup": int8.get("speedup"),
    }
    print(_KERNELS_MARKER + json.dumps(results), flush=True)


def bench_kernels(timeout_s: int = 1800) -> dict | None:
    """Launch the kernel A/Bs in a CPU-pinned child; returns its results
    dict, or None on failure."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--kernels-child"],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
    )
    try:
        out, _ = proc.communicate(timeout=timeout_s)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.communicate()
        return None
    for line in (out or "").splitlines():
        if line.startswith(_KERNELS_MARKER):
            try:
                return json.loads(line[len(_KERNELS_MARKER):])
            except ValueError:
                return None
    return None


# ------------------------------------------------------- elastic drill bench

_ELASTIC_MARKER = "ELASTIC_BENCH_RESULTS "

#: drill geometry: 2 epochs of 16 batches, step-save every 2, SIGTERM after
#: batch 7 -> drain at the step-8 boundary, resume on HALF the devices
_ELASTIC_N_BATCHES = 16
_ELASTIC_SAVE_EVERY = 2
_ELASTIC_KILL_AFTER = 7
_ELASTIC_EPOCHS = 2


def elastic_child_main():
    """The preemption drill as a benchmark (doc/elasticity.md): train on a
    4-device mesh, deliver a REAL SIGTERM mid-epoch, drain at the next
    step-save boundary, then resume the SAME run dir on a 2-device mesh and
    finish. Emits one marker line of JSON — the source of the
    ``BENCH_elastic_*.json`` receipts:

    - ``save_on_preempt_latency_s``  the drain's final committed save
    - ``time_to_resume_s``           resumed run start -> first resumed
                                     optimizer step dispatched (restore +
                                     resharding + data fast-forward)
    - ``steps_replayed``             final step count vs the exact-resume
                                     expectation (positive = replayed
                                     batches, negative = skipped)

    Needs ``XLA_FLAGS=--xla_force_host_platform_device_count=4`` in the
    environment (``bench_elastic`` sets it) — the flag must precede backend
    init, which is why this runs as a child."""
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_cpu_enable_async_dispatch", False)

    import shutil
    import signal as _signal
    import tempfile

    import optax

    import dmlcloud_tpu as dml
    from dmlcloud_tpu.checkpoint import read_requeue_verdict
    from dmlcloud_tpu.data import DataPipeline
    from dmlcloud_tpu.parallel import mesh as mesh_lib

    rng = np.random.RandomState(0)
    w_true = np.array([[1.0], [-2.0], [0.5], [3.0]], np.float32)
    xs = rng.randn(_ELASTIC_N_BATCHES, 16, 4).astype(np.float32)
    batches = [{"x": x, "y": x @ w_true} for x in xs]

    class SigtermSource:
        """Yields the drill batches; delivers SIGTERM to this process after
        batch ``kill_after`` (the production preemption path, handler and
        all). Records the wall time of every yield so the resumed run's
        first post-fast-forward batch timestamps time-to-resume."""

        def __init__(self, kill_after=None):
            self.kill_after = kill_after
            self.fired = False
            self.yield_times: list = []

        def __iter__(self):
            for i, b in enumerate(batches):
                self.yield_times.append(time.perf_counter())
                yield b
                if self.kill_after is not None and not self.fired and i + 1 == self.kill_after:
                    self.fired = True
                    os.kill(os.getpid(), _signal.SIGTERM)

        def __len__(self):
            return len(batches)

    class DrillStage(dml.TrainValStage):
        def __init__(self, source):
            super().__init__()
            self._source = source

        def checkpoint_every_steps(self):
            return _ELASTIC_SAVE_EVERY

        def device_prefetch(self):
            return 0  # keep batch consumption aligned with optimizer steps

        def pre_stage(self):
            self.pipeline.register_model(
                "lin",
                apply_fn=lambda p, x: x @ p["w"],
                params={"w": jnp.zeros((4, 1))},
                verbose=False,
            )
            self.pipeline.register_optimizer("sgd", optax.sgd(0.05))
            self.pipeline.register_dataset(
                "train", DataPipeline.from_source(self._source), verbose=False
            )

        def step(self, state, batch):
            return jnp.mean((state.apply_fn(state.params, batch["x"]) - batch["y"]) ** 2)

        def val_epoch(self):
            pass

    def run(ckpt_dir, source, n_devices, preemptible=False):
        pipe = dml.TrainingPipeline(name="elastic-drill")
        pipe.set_mesh(
            mesh_lib.create_mesh({"data": n_devices}, devices=jax.devices()[:n_devices])
        )
        pipe.enable_checkpointing(str(ckpt_dir), resume=True)
        if preemptible:
            pipe.enable_preemption_handling(signals=("SIGTERM",))
        stage = DrillStage(source)
        pipe.append_stage(stage, max_epochs=_ELASTIC_EPOCHS, name="drill")
        pipe.run()
        pipe.checkpoint_dir.close()
        return pipe, stage

    workdir = tempfile.mkdtemp(prefix="dml-elastic-bench-")
    try:
        # phase A: preempted mid-epoch on data=4
        t_a = time.perf_counter()
        pipe1, stage1 = run(os.path.join(workdir, "run"), SigtermSource(_ELASTIC_KILL_AFTER), 4, preemptible=True)
        phase_a_s = time.perf_counter() - t_a
        verdict = read_requeue_verdict(pipe1.checkpoint_dir.path) or {}
        drained_step = int(jax.device_get(stage1.state.step))

        # phase B: the requeue — SAME run dir, HALF the devices
        source_b = SigtermSource()
        t_resume = time.perf_counter()
        pipe2, stage2 = run(pipe1.checkpoint_dir.path, source_b, 2)
        phase_b_s = time.perf_counter() - t_resume
        final_step = int(jax.device_get(stage2.state.step))

        # the resumed run's data fast-forward consumes the already-seen
        # prefix from the source; its (drained_step+1)-th yield is the first
        # batch the FIRST RESUMED optimizer step consumes
        first_new = (
            source_b.yield_times[drained_step]
            if len(source_b.yield_times) > drained_step
            else t_resume + phase_b_s
        )
        steps_replayed = final_step - _ELASTIC_EPOCHS * _ELASTIC_N_BATCHES
        results = {
            "host": _host_fingerprint(),
            "workload": {
                "n_batches": _ELASTIC_N_BATCHES,
                "epochs": _ELASTIC_EPOCHS,
                "save_every_steps": _ELASTIC_SAVE_EVERY,
                "kill_after_batch": _ELASTIC_KILL_AFTER,
                "devices_before": 4,
                "devices_after": 2,
            },
            "drained_step": drained_step,
            "final_step": final_step,
            "requeue_verdict": {k: verdict.get(k) for k in ("requeue", "kind", "mid_epoch")},
            "steps_replayed": steps_replayed,
            "save_on_preempt_latency_s": verdict.get("save_on_preempt_latency_s"),
            "time_to_resume_s": round(first_new - t_resume, 4),
            "phase_a_wall_s": round(phase_a_s, 3),
            "phase_b_wall_s": round(phase_b_s, 3),
        }
        lat = results["save_on_preempt_latency_s"]
        results["gate"] = {
            # exact data-order resumption is pass/fail: 1.0 only when not a
            # single optimizer step was replayed or skipped AND the drain
            # left a resumable preemption verdict
            "elastic_exact_resume": float(
                steps_replayed == 0 and verdict.get("requeue") is True
            ),
            "elastic_save_on_preempt_latency_s": lat,
            "elastic_time_to_resume_s": results["time_to_resume_s"],
        }
        print(_ELASTIC_MARKER + json.dumps(results), flush=True)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)


def bench_elastic(timeout_s: int = 900) -> dict | None:
    """Run the preemption drill in a child pinned to 4 fake CPU devices;
    returns its results dict, or None on failure."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = env.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=4"
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--elastic-child"],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
    )
    try:
        out, _ = proc.communicate(timeout=timeout_s)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.communicate()
        return None
    for line in (out or "").splitlines():
        if line.startswith(_ELASTIC_MARKER):
            try:
                return json.loads(line[len(_ELASTIC_MARKER):])
            except ValueError:
                return None
    return None


# ------------------------------------------------------- serving engine bench

_SERVE_MARKER = "SERVE_BENCH_RESULTS "

#: the CPU-smoke serving A/B config — pinned so receipts stay comparable.
#: fp32 (XLA:CPU's native GEMM dtype): the token-identity check is exact
#: and neither arm pays the bf16 emulation tax. The model is sized so that
#: decode is weight-bandwidth-bound (~24M params streamed per token — the
#: regime serving actually lives in; a toy model would measure Python
#: dispatch, which batching cannot amortise). The Poisson arrivals
#: saturate both arms (mean interarrival far below the serial per-request
#: service time), so tokens/s measures each arm's max sustainable
#: throughput and TTFT measures behavior under queueing load.
_SERVE_CFG = dict(
    vocab=2048, layers=6, heads=8, kv=4, head_dim=64, hidden=512, mlp=1408,
    max_seq_len=160, n_requests=24, prompt_lens=(16, 32, 48),
    new_tokens=(24, 32, 48), mean_interarrival_s=0.02, seed=0,
    block_size=16, num_blocks=96, max_slots=8, prefill_chunk=32,
)


def _serve_model():
    from dmlcloud_tpu.models.transformer import DecoderLM, TransformerConfig

    c = _SERVE_CFG
    cfg = TransformerConfig(
        vocab_size=c["vocab"], num_layers=c["layers"], num_heads=c["heads"],
        num_kv_heads=c["kv"], head_dim=c["head_dim"], hidden_dim=c["hidden"],
        mlp_dim=c["mlp"], max_seq_len=c["max_seq_len"], dtype=jnp.float32,
    )
    model = DecoderLM(cfg)
    params = model.init(jax.random.PRNGKey(0), jnp.ones((1, 8), jnp.int32))["params"]
    return model, params


def _serve_trace():
    """The pinned Poisson request trace: (offset_s, prompt, max_new) per
    request, offsets ascending. Prompt/generation lengths cycle through
    the pinned sets so both arms see the same bounded signature mix."""
    c = _SERVE_CFG
    rs = np.random.RandomState(c["seed"])
    offsets = np.cumsum(rs.exponential(c["mean_interarrival_s"], c["n_requests"]))
    trace = []
    for i in range(c["n_requests"]):
        pl = c["prompt_lens"][i % len(c["prompt_lens"])]
        new = c["new_tokens"][i % len(c["new_tokens"])]
        prompt = rs.randint(0, c["vocab"], size=pl).astype(np.int32)
        trace.append((float(offsets[i]), prompt, int(new)))
    return trace


def _serve_serial_arm(model, params, trace):
    """The baseline: serial ``generate()`` calls replayed against the same
    arrival times. Each request is serviced alone, FIFO; its first token
    exists only when its whole compiled generate returns, so TTFT =
    completion - arrival (that is the honest serial number — the one
    compiled program emits nothing incrementally). Signatures are warmed
    before the timed replay, same as the engine arm."""
    from dmlcloud_tpu.models.generate import generate

    sigs = {}
    for _, prompt, new in trace:
        sigs.setdefault((prompt.size, new), prompt)
    for (_, new), prompt in sigs.items():
        np.asarray(generate(model, params, jnp.asarray(prompt)[None], new))

    outs, ttfts = [], []
    t_free = total_tokens = 0.0
    for off, prompt, new in trace:
        start = max(off, t_free)
        t0 = time.perf_counter()
        out = np.asarray(generate(model, params, jnp.asarray(prompt)[None], new))
        done = start + (time.perf_counter() - t0)
        ttfts.append(done - off)
        t_free = done
        total_tokens += new
        outs.append(out[0])
    wall = t_free - trace[0][0]
    return {
        "tokens_per_sec": round(total_tokens / wall, 1),
        "p50_ttft_s": round(float(np.percentile(ttfts, 50)), 4),
        "p99_ttft_s": round(float(np.percentile(ttfts, 99)), 4),
        "wall_s": round(wall, 3),
    }, outs


#: the CPU-smoke SPECULATIVE serving A/B config — pinned so receipts stay
#: comparable. Same Poisson arrival law as _SERVE_CFG, but prompts come
#: from a learnable Markov chain and the target/draft pair is TRAINED on
#: it first (kernel_spec_ab's recipe): speculation's win IS the accept
#: rate, so an untrained pair would measure nothing. The ~60x-smaller
#: draft makes a proposal pass nearly free next to a verify. max_slots=2,
#: k=3 keeps the smoke's verify pass (slots x (k+1) positions) inside the
#: CPU's weight-bandwidth-bound regime — the regime TPU decode lives in
#: at much larger batches; at 8 slots the CPU smoke turns compute-bound
#: and measures the wrong machine (sweep in PR 10's notes: 1.73x at 2
#: slots vs 1.09x at 8).
_SERVE_SPEC_CFG = dict(
    vocab=256, max_seq_len=192, k=3,
    target=dict(layers=5, heads=8, kv=4, head_dim=48, hidden=384, mlp=1024),
    draft=dict(layers=1, heads=2, kv=1, head_dim=32, hidden=96, mlp=256),
    train_steps=120, train_b=8, train_s=48, train_lr=2e-3,
    n_requests=24, prompt_lens=(16, 32, 48), new_tokens=(24, 32, 48),
    mean_interarrival_s=0.02, seed=0,
    block_size=16, num_blocks=64, max_slots=2, prefill_chunk=32,
)


_SPEC_SERVE_MODELS_CACHE: list = []


def _spec_serve_models():
    """The trained target/draft pair of the speculative serving A/B: both
    models fit the same pinned Markov corpus (fp32 — greedy token-identity
    is exact), so the draft genuinely agrees with the target and the
    receipt's accept rate is a property of speculation, not luck.
    Memoized within the child process — the Medusa section reuses the SAME
    trained target (and pinned trace), so the spec-vs-medusa comparison is
    paired, not a retrain."""
    if _SPEC_SERVE_MODELS_CACHE:
        return _SPEC_SERVE_MODELS_CACHE[0]
    from dmlcloud_tpu.data import markov_tokens
    from dmlcloud_tpu.models.transformer import DecoderLM, TransformerConfig, lm_loss

    c = _SERVE_SPEC_CFG

    def build(kind):
        mc = c[kind]
        cfg = TransformerConfig(
            vocab_size=c["vocab"], num_layers=mc["layers"], num_heads=mc["heads"],
            num_kv_heads=mc["kv"], head_dim=mc["head_dim"], hidden_dim=mc["hidden"],
            mlp_dim=mc["mlp"], max_seq_len=c["max_seq_len"], dtype=jnp.float32,
        )
        return DecoderLM(cfg)

    target, draft = build("target"), build("draft")
    n_batches = 8
    corpus = markov_tokens(c["vocab"], c["train_b"] * n_batches, c["train_s"])
    batches = [
        jnp.asarray(corpus[i * c["train_b"]:(i + 1) * c["train_b"]], jnp.int32)
        for i in range(n_batches)
    ]

    def train(model, seed):
        params = model.init(jax.random.PRNGKey(seed), batches[0][:1, :8])["params"]
        tx = optax.adamw(c["train_lr"])
        opt = tx.init(params)

        @functools.partial(jax.jit, donate_argnums=(0, 1))
        def step(params, opt, tokens):
            loss, grads = jax.value_and_grad(
                lambda p: lm_loss(model.apply({"params": p}, tokens), tokens)
            )(params)
            up, new_opt = tx.update(grads, opt, params)
            return optax.apply_updates(params, up), new_opt, loss

        for i in range(c["train_steps"]):
            params, opt, loss = step(params, opt, batches[i % n_batches])
        return params, float(loss)

    tparams, tloss = train(target, 0)
    dparams, dloss = train(draft, 1)
    _SPEC_SERVE_MODELS_CACHE.append((target, tparams, tloss, draft, dparams, dloss))
    return _SPEC_SERVE_MODELS_CACHE[0]


def _spec_serve_trace():
    """The pinned Poisson spec-serving trace: same arrival law as
    ``_serve_trace`` but Markov-chain prompts (same table as the training
    corpus), so generation follows learned structure and the accept rate
    measures draft/target agreement."""
    from dmlcloud_tpu.data import markov_tokens

    c = _SERVE_SPEC_CFG
    rs = np.random.RandomState(c["seed"])
    offsets = np.cumsum(rs.exponential(c["mean_interarrival_s"], c["n_requests"]))
    longest = max(c["prompt_lens"])
    prompts = markov_tokens(c["vocab"], c["n_requests"], longest, seed=77, table_seed=0)
    trace = []
    for i in range(c["n_requests"]):
        pl = c["prompt_lens"][i % len(c["prompt_lens"])]
        new = c["new_tokens"][i % len(c["new_tokens"])]
        trace.append((float(offsets[i]), prompts[i, :pl].astype(np.int32), int(new)))
    return trace


def _spec_serve_section():
    """The speculative-serving A/B: the spec-decode engine (trained draft,
    ``spec_k`` proposals/round) vs the SAME engine without speculation on
    the same pinned trace and the same trained target — the composition
    receipt ISSUE 10 asks for. Returns the results dict whose numbers feed
    the ``serve_spec_*`` gate keys."""
    from dmlcloud_tpu.models.generate import generate
    from dmlcloud_tpu.serve import ServeEngine
    from dmlcloud_tpu.serve.ledger import ServeLedger

    c = _SERVE_SPEC_CFG
    target, tparams, tloss, draft, dparams, dloss = _spec_serve_models()
    trace = _spec_serve_trace()

    # serial greedy reference (identity only, not a timed arm — the PR-8
    # receipt already locks engine-vs-serial)
    serial_outs = [
        np.asarray(generate(target, tparams, jnp.asarray(p)[None], n))[0]
        for _, p, n in trace
    ]

    def engine_kw():
        return dict(
            num_blocks=c["num_blocks"], block_size=c["block_size"],
            max_slots=c["max_slots"], prefill_chunk=c["prefill_chunk"],
        )

    def run_arm(**extra):
        eng = ServeEngine(target, tparams, **engine_kw(), **extra)
        eng.serve_trace([(0.0, p, n) for _, p, n in trace])  # warm: compile all
        warm_outs = [eng.output(i) for i in range(len(trace))]
        warm_sigs = eng.compiled_signatures()
        eng.ledger = ServeLedger()
        summary = eng.serve_trace(trace)
        return eng, summary, warm_outs, warm_sigs

    base_eng, base, _, _ = run_arm()
    spec_eng, spec, spec_outs, spec_warm_sigs = run_arm(
        spec_k=c["k"], draft_model=draft, draft_params=dparams
    )
    recompiles = spec_eng.compiled_signatures() - spec_warm_sigs

    identical = all(
        np.array_equal(w, s) for w, s in zip(spec_outs, serial_outs)
    )
    speedup = (
        round(spec["tokens_per_sec"] / base["tokens_per_sec"], 3)
        if spec["tokens_per_sec"] and base["tokens_per_sec"]
        else None
    )
    rnd = lambda d: {
        k: (round(v, 4) if isinstance(v, float) else v) for k, v in d.items()
    }
    return {
        "config": dict(c),
        "target_loss": round(tloss, 3),
        "draft_loss": round(dloss, 3),
        "engine": rnd(base),
        "spec_engine": {
            **rnd(spec),
            "compiled_signatures": spec_eng.compiled_signatures(),
            "max_signatures": spec_eng.max_signatures,
            "target_pool": spec_eng.pool.stats(),
            "draft_pool": spec_eng.draft_pool.stats(),
        },
        "speedup_tokens_per_sec": speedup,
        "accept_rate": spec["accept_rate"],
        "token_identical_to_serial": bool(identical),
        "mid_run_recompiles": int(recompiles),
    }


def _train_medusa_heads(target, tparams, k, steps=300, lr=2e-3):
    """Distil ``k - 1`` Medusa heads on the FROZEN trained target: head
    ``h`` learns to predict the token ``h + 2`` positions ahead from the
    final hidden state (one target forward per batch, stop-gradient'd —
    only the tiny head stacks train). Returns ``(heads, final_loss)``."""
    from dmlcloud_tpu.data import markov_tokens
    from dmlcloud_tpu.models.speculative import init_medusa_heads, medusa_head_logits

    c = _SERVE_SPEC_CFG
    n_batches = 8
    corpus = markov_tokens(c["vocab"], c["train_b"] * n_batches, c["train_s"])
    batches = [
        jnp.asarray(corpus[i * c["train_b"]:(i + 1) * c["train_b"]], jnp.int32)
        for i in range(n_batches)
    ]
    heads = init_medusa_heads(
        target.cfg, k, jax.random.PRNGKey(2),
        lm_head_kernel=tparams["lm_head"]["kernel"],
    )
    tx = optax.adamw(lr)
    opt = tx.init(heads)
    d = target.cfg.hidden_dim

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(heads, opt, tokens):
        hidden = jax.lax.stop_gradient(
            target.apply({"params": tparams}, tokens, return_hidden=True)
        )  # [B, S, D] — the SAME tensor the serving step hands the heads

        def loss_fn(heads):
            b, s, _ = hidden.shape
            hl = medusa_head_logits(heads, hidden.reshape(-1, d)).reshape(b, s, k - 1, -1)
            total = 0.0
            for h in range(k - 1):
                off = h + 2  # head h proposes the token off positions ahead
                lg = hl[:, : s - off, h].astype(jnp.float32)
                lb = tokens[:, off:]
                total += optax.softmax_cross_entropy_with_integer_labels(lg, lb).mean()
            return total / (k - 1)

        loss, grads = jax.value_and_grad(loss_fn)(heads)
        up, new_opt = tx.update(grads, opt, heads)
        return optax.apply_updates(heads, up), new_opt, loss

    loss = None
    for i in range(steps):
        heads, opt, loss = step(heads, opt, batches[i % n_batches])
    return heads, float(loss)


def _serve_medusa_section():
    """The Medusa-serving A/B (PR 16): the SAME trained target as the spec
    section, its separate draft model replaced by ``k - 1`` distilled
    decode heads — no draft model, no draft prefill mirror, no second page
    pool anywhere — vs the plain engine on the SAME pinned Markov trace.
    Returns the results dict behind the ``serve_medusa_*`` gate keys."""
    from dmlcloud_tpu.models.generate import generate
    from dmlcloud_tpu.serve import ServeEngine
    from dmlcloud_tpu.serve.ledger import ServeLedger

    c = _SERVE_SPEC_CFG
    k = c["k"]
    target, tparams, tloss, _, _, _ = _spec_serve_models()
    heads, head_loss = _train_medusa_heads(target, tparams, k)
    trace = _spec_serve_trace()

    serial_outs = [
        np.asarray(generate(target, tparams, jnp.asarray(p)[None], n))[0]
        for _, p, n in trace
    ]

    def run_arm(**extra):
        eng = ServeEngine(
            target, tparams, num_blocks=c["num_blocks"], block_size=c["block_size"],
            max_slots=c["max_slots"], prefill_chunk=c["prefill_chunk"], **extra,
        )
        eng.serve_trace([(0.0, p, n) for _, p, n in trace])  # warm: compile all
        warm_outs = [eng.output(i) for i in range(len(trace))]
        warm_sigs = eng.compiled_signatures()
        eng.ledger = ServeLedger()
        summary = eng.serve_trace(trace)
        return eng, summary, warm_outs, warm_sigs

    base_eng, base, _, _ = run_arm()
    med_eng, med, med_outs, med_warm_sigs = run_arm(medusa_k=k, medusa_heads=heads)
    recompiles = med_eng.compiled_signatures() - med_warm_sigs
    # budget-only spec-mode twin (self-draft, never stepped): the docs'
    # signature-budget-SHRINKS claim, measured on identical bucket sets
    spec_twin = ServeEngine(
        target, tparams, num_blocks=c["num_blocks"], block_size=c["block_size"],
        max_slots=c["max_slots"], prefill_chunk=c["prefill_chunk"], spec_k=k,
    )

    # the deleted-draft-pool contract, asserted on the live engine: no
    # second pool exists, and the one pool is clean after the run
    assert med_eng.draft_pool is None
    pool_stats = med_eng.pool.stats()
    assert pool_stats["free"] + pool_stats["live"] == pool_stats["capacity"]
    leaked = med_eng.leaked_blocks()

    identical = all(
        np.array_equal(w, s) for w, s in zip(med_outs, serial_outs)
    )
    speedup = (
        round(med["tokens_per_sec"] / base["tokens_per_sec"], 3)
        if med["tokens_per_sec"] and base["tokens_per_sec"]
        else None
    )
    rnd = lambda d: {
        k_: (round(v, 4) if isinstance(v, float) else v) for k_, v in d.items()
    }
    return {
        "config": dict(c),
        "target_loss": round(tloss, 3),
        "head_distill_loss": round(head_loss, 3),
        "engine": rnd(base),
        "medusa_engine": {
            **rnd(med),
            "compiled_signatures": med_eng.compiled_signatures(),
            "max_signatures": med_eng.max_signatures,
            "target_pool": pool_stats,
            "draft_pool_blocks": 0,  # structurally: med_eng.draft_pool is None
            "leaked_blocks": int(leaked),
        },
        "speedup_tokens_per_sec": speedup,
        "accept_rate": med["accept_rate"],
        "token_identical_to_serial": bool(identical),
        "mid_run_recompiles": int(recompiles),
        # the signature-budget delta vs spec mode the docs quote (< 0: no
        # draft prefill bucket set, no second per-round step)
        "max_signatures_vs_spec_mode": med_eng.max_signatures - spec_twin.max_signatures,
        "max_signatures_detail": {
            "medusa": med_eng.max_signatures,
            "spec": spec_twin.max_signatures,
            "plain": base_eng.max_signatures,
        },
    }


#: the CPU-smoke PREFIX-CACHE serving A/B config — pinned so receipts stay
#: comparable. The realistic multi-tenant shape: 80% of requests share one
#: of a handful of templates (a long system prompt / few-shot preamble)
#: with a short unique suffix; 20% are fully unique. Long prompts + short
#: generations make the trace PREFILL-dominated — the regime prefix
#: sharing exists for — and the small prefill chunk makes the uncached
#: cost visible (7+ chunks cold vs 1 warm). Arrivals are paced (not
#: saturating) so TTFT measures prefill latency, not queueing.
_SERVE_PREFIX_CFG = dict(
    n_requests=30, n_templates=4, template_len=112, suffix_lens=(4, 8),
    new_tokens=8, mean_interarrival_s=0.05, seed=0,
    block_size=16, num_blocks=96, max_slots=4, prefill_chunk=16,
)


def _serve_prefix_trace():
    """The pinned 80%-shared-template Poisson trace: request ``i`` is
    template-shaped unless ``i % 5 == 4`` (exactly 80%), cycling through
    the templates; suffixes and the 20% unique prompts are fresh draws."""
    c = _SERVE_PREFIX_CFG
    sc = _SERVE_CFG  # model geometry (vocab, max_seq_len) is the serve model's
    rs = np.random.RandomState(c["seed"])
    templates = [
        rs.randint(0, sc["vocab"], size=c["template_len"]).astype(np.int32)
        for _ in range(c["n_templates"])
    ]
    offsets = np.cumsum(rs.exponential(c["mean_interarrival_s"], c["n_requests"]))
    trace, shared = [], []
    for i in range(c["n_requests"]):
        if i % 5 != 4:
            tmpl = templates[i % c["n_templates"]]
            suffix = rs.randint(
                0, sc["vocab"], size=c["suffix_lens"][i % len(c["suffix_lens"])]
            ).astype(np.int32)
            prompt = np.concatenate([tmpl, suffix])
            shared.append(True)
        else:
            prompt = rs.randint(
                0, sc["vocab"], size=c["template_len"] + c["suffix_lens"][0]
            ).astype(np.int32)
            shared.append(False)
        trace.append((float(offsets[i]), prompt, c["new_tokens"]))
    return trace, shared


def _serve_prefix_section():
    """The prefix-cache A/B: the engine WITH radix-tree sharing
    (``prefix_cache=True``) vs the SAME engine without it, on the pinned
    80%-shared-template trace — the tentpole receipt of ISSUE 11. Returns
    the results dict whose numbers feed the ``serve_prefix_*`` gate keys:
    warm-template p50 TTFT (the headline — near-zero prefill for a warm
    template), hit rate, the fraction of prefill tokens saved, greedy
    token-identity to the uncached engine, and zero mid-run recompiles."""
    from dmlcloud_tpu.serve import ServeEngine
    from dmlcloud_tpu.serve.ledger import ServeLedger

    c = _SERVE_PREFIX_CFG
    model, params = _serve_model()
    trace, shared = _serve_prefix_trace()

    def engine_kw():
        return dict(
            num_blocks=c["num_blocks"], block_size=c["block_size"],
            max_slots=c["max_slots"], prefill_chunk=c["prefill_chunk"],
        )

    def run_arm(**extra):
        eng = ServeEngine(model, params, **engine_kw(), **extra)
        # warm pass: compiles every signature AND (in the cached arm)
        # populates the radix tree — the measured replay is the warm
        # steady state a long-running server lives in
        eng.serve_trace([(0.0, p, n) for _, p, n in trace])
        warm_outs = [eng.output(i) for i in range(len(trace))]
        warm_sigs = eng.compiled_signatures()
        eng.ledger = ServeLedger()
        summary = eng.serve_trace(trace)
        return eng, summary, warm_outs, warm_sigs

    base_eng, base, base_outs, _ = run_arm()
    pref_eng, pref, pref_outs, pref_warm_sigs = run_arm(prefix_cache=True)
    recompiles = pref_eng.compiled_signatures() - pref_warm_sigs

    identical = all(
        np.array_equal(a, b) for a, b in zip(pref_outs, base_outs)
    )

    def warm_p50(eng, offset):
        ttfts = [
            eng.ledger.records[offset + i]["first_token"]
            - eng.ledger.records[offset + i]["arrival"]
            for i in range(len(trace))
            if shared[i]
        ]
        return round(float(np.percentile(ttfts, 50)), 4)

    # the measured replay's requests are ids n..2n-1 (the warm pass took 0..n-1)
    n = len(trace)
    warm_cached = warm_p50(pref_eng, n)
    warm_uncached = warm_p50(base_eng, n)
    s = pref_eng.ledger.summary()
    rnd = lambda d: {
        k: (round(v, 4) if isinstance(v, float) else v) for k, v in d.items()
    }
    return {
        "config": dict(c),
        "engine": rnd(base),
        "prefix_engine": {
            **rnd(pref),
            "compiled_signatures": pref_eng.compiled_signatures(),
            "max_signatures": pref_eng.max_signatures,
            "pool": pref_eng.pool.stats(),
            "cache": pref_eng.prefix.stats(),
        },
        # template-shaped requests' p50 TTFT, measured in each arm on the
        # SAME request subset — the headline near-zero-prefill number
        "warm_template_p50_ttft_s": warm_cached,
        "uncached_template_p50_ttft_s": warm_uncached,
        "warm_ttft_ratio": (
            round(warm_cached / warm_uncached, 4) if warm_uncached else None
        ),
        "hit_rate": s["prefix_hit_rate"],
        "cached_token_frac": s["cached_token_frac"],
        "prefill_tokens_saved_frac": s["prefill_tokens_saved_frac"],
        "token_identical_to_uncached": bool(identical),
        "mid_run_recompiles": int(recompiles),
    }


#: the CPU-smoke overload/chaos drill config — pinned so receipts stay
#: comparable. Engine geometry rides _SERVE_CFG; the trace is adversarial
#: by construction: one HOT tenant bursts 16 requests at t~0 against a
#: 6-deep admission queue (forcing oldest-deadline shedding) while one
#: COLD tenant trickles 4 requests behind it — deficit round-robin
#: fairness is what keeps the cold tenant's TTFT flat under the burst
#: (the gated ``serve_chaos_cold_p99_ttft_s``). A seeded ChaosMonkey
#: injects step faults, pool-exhaustion squats and random cancels during
#: the replay; the receipt proves goodput under fire, zero leaked blocks,
#: and that SURVIVORS (status ``ok``) are greedy-token-identical to a
#: fault-free run. Cold requests carry priority 1 (hot 0) so the shed
#: policy prefers hot victims; hot deadlines give oldest-deadline a key.
_SERVE_CHAOS_CFG = dict(
    hot_requests=16, cold_requests=4,
    hot_burst_s=0.005, cold_start_s=0.05, cold_spacing_s=0.2,
    prompt_lens=(16, 32, 48), new_tokens=(24, 32),
    hot_deadline_s=8.0, max_waiting=6, shed_policy="oldest-deadline",
    fairness="tenant", seed=0,
    chaos_seed=7, p_fault=0.06, max_faults=3,
    p_exhaust=0.12, exhaust_blocks=8, exhaust_steps=2, p_cancel=0.04,
)


def _serve_chaos_trace():
    """The pinned two-tenant adversarial trace: (offset_s, prompt,
    max_new, submit-kwargs) per request, offsets ascending."""
    c, sc = _SERVE_CHAOS_CFG, _SERVE_CFG
    rs = np.random.RandomState(c["seed"])

    def prompt(i):
        return rs.randint(
            0, sc["vocab"], size=c["prompt_lens"][i % len(c["prompt_lens"])]
        ).astype(np.int32)

    trace = []
    for i in range(c["hot_requests"]):
        trace.append((
            i * c["hot_burst_s"], prompt(i),
            c["new_tokens"][i % len(c["new_tokens"])],
            {"tenant": "hot", "deadline_s": c["hot_deadline_s"], "priority": 0},
        ))
    for j in range(c["cold_requests"]):
        trace.append((
            c["cold_start_s"] + j * c["cold_spacing_s"], prompt(j),
            c["new_tokens"][j % len(c["new_tokens"])],
            {"tenant": "cold", "priority": 1},
        ))
    trace.sort(key=lambda e: e[0])
    return trace


def _serve_chaos_section():
    """The overload/chaos drill (ISSUE 13's receipt): the bounded-queue,
    tenant-fair engine replays the adversarial two-tenant trace with a
    seeded ChaosMonkey attached. Returns the results dict whose numbers
    feed the ``serve_chaos_*`` gate keys: goodput under fire, cold-tenant
    p99 TTFT (fairness' observable), zero leaked blocks after the drill,
    every request terminal, and survivors greedy-token-identical to the
    fault-free reference arm."""
    from dmlcloud_tpu.serve import ChaosMonkey, ServeEngine, TERMINAL_STATUSES
    from dmlcloud_tpu.serve.ledger import ServeLedger

    c, sc = _SERVE_CHAOS_CFG, _SERVE_CFG
    model, params = _serve_model()
    trace = _serve_chaos_trace()
    n = len(trace)

    def engine_kw():
        return dict(
            num_blocks=sc["num_blocks"], block_size=sc["block_size"],
            max_slots=sc["max_slots"], prefill_chunk=sc["prefill_chunk"],
        )

    # reference arm: same prompts, no limits, no faults — greedy decode is
    # batch-composition-independent, so these are the outputs every chaos
    # SURVIVOR must reproduce bit-for-bit
    ref = ServeEngine(model, params, **engine_kw())
    ref.serve_trace([(0.0, p, new) for _, p, new, _ in trace])
    ref_outs = [ref.output(i) for i in range(n)]

    eng = ServeEngine(
        model, params, **engine_kw(),
        shed_policy=c["shed_policy"], fairness=c["fairness"],
    )
    # warm pass with the admission bound lifted: compiles every signature
    # without shedding, so the measured replay's latencies are compile-free
    eng.serve_trace([(0.0, p, new) for _, p, new, _ in trace])
    eng.scheduler.max_waiting = c["max_waiting"]
    eng.ledger = ServeLedger()

    monkey = ChaosMonkey(
        c["chaos_seed"], p_fault=c["p_fault"], max_faults=c["max_faults"],
        p_exhaust=c["p_exhaust"], exhaust_blocks=c["exhaust_blocks"],
        exhaust_steps=c["exhaust_steps"], p_cancel=c["p_cancel"],
    )
    monkey.attach(eng)
    summary = eng.serve_trace(trace)
    monkey.detach()
    leaked = eng.leaked_blocks()

    # the measured replay's requests are ids n..2n-1 (the warm pass took 0..n-1)
    statuses = [eng.status(n + i) for i in range(n)]
    all_terminal = all(s in TERMINAL_STATUSES for s in statuses)
    survivors = [i for i, s in enumerate(statuses) if s == "ok"]
    identical = all(
        np.array_equal(eng.output(n + i), ref_outs[i]) for i in survivors
    )
    cold_ttfts = eng.ledger.ttfts(tenant="cold")
    cold_p99 = (
        round(float(np.percentile(cold_ttfts, 99)), 4) if cold_ttfts else None
    )
    rnd = lambda d: {
        k: (round(v, 4) if isinstance(v, float) else v) for k, v in d.items()
    }
    return {
        "config": dict(c),
        "summary": rnd(summary),
        "statuses": eng.ledger.status_counts(),
        "injected_faults": int(monkey.faults),
        "chaos_events": len(monkey.log),
        "survivors_ok": len(survivors),
        "leaked_blocks": int(leaked),
        "survivor_token_identical": bool(identical),
        "all_terminal": bool(all_terminal),
        "goodput_tokens_per_sec": summary["goodput_tokens_per_sec"],
        "cold_p99_ttft_s": cold_p99,
    }


#: the CPU-smoke multi-replica ROUTER drill config — pinned so receipts
#: stay comparable. Engine geometry rides _SERVE_CFG; three in-process
#: replicas sit behind one Router on a Poisson two-tenant trace (a hot
#: tenant bursting, a cold tenant trickling — the DRR-across-replicas
#: observable). Mid-trace, once kill_after_done requests are terminal,
#: replica r2 is KILLED (live requests fail over, its engine reaped);
#: once drain_after_done are terminal, r1 is DRAINED (queued work
#: migrates, running work finishes, a requeue verdict is written). The
#: survivors of all that must be greedy-token-identical to a fault-free
#: pass, everything must end terminal with zero leaked blocks, and the
#: p99 TTFT — measured ROUTER-side, so a failover's re-prefill and
#: backoff are inside the number — is the gated latency.
#: heartbeat_timeout_s is generous because all replicas step from ONE
#: host loop here: a slow sibling step must not read as a missed beat.
_SERVE_ROUTER_CFG = dict(
    n_replicas=3,
    hot_requests=12, cold_requests=6,
    hot_mean_interarrival_s=0.01, cold_start_s=0.05, cold_spacing_s=0.15,
    prompt_lens=(16, 32, 48), new_tokens=(24, 32),
    seed=3,
    kill_after_done=5, kill_replica="r2",
    drain_after_done=10, drain_replica="r1",
    heartbeat_timeout_s=5.0, max_retries=3, backoff_base_s=0.01,
)


def _serve_router_trace():
    """The pinned two-tenant Poisson trace the router drill replays:
    (offset_s, prompt, max_new, submit-kwargs) per request, offsets
    ascending."""
    c, sc = _SERVE_ROUTER_CFG, _SERVE_CFG
    rs = np.random.RandomState(c["seed"])

    def prompt(i):
        return rs.randint(
            0, sc["vocab"], size=c["prompt_lens"][i % len(c["prompt_lens"])]
        ).astype(np.int32)

    trace = []
    offsets = np.cumsum(
        rs.exponential(c["hot_mean_interarrival_s"], c["hot_requests"])
    )
    for i in range(c["hot_requests"]):
        trace.append((
            float(offsets[i]), prompt(i),
            c["new_tokens"][i % len(c["new_tokens"])], {"tenant": "hot"},
        ))
    for j in range(c["cold_requests"]):
        trace.append((
            c["cold_start_s"] + j * c["cold_spacing_s"], prompt(j),
            c["new_tokens"][j % len(c["new_tokens"])], {"tenant": "cold"},
        ))
    trace.sort(key=lambda e: e[0])
    return trace


def _serve_router_section():
    """The multi-replica front-door drill (ISSUE 15's receipt): three
    warmed engine replicas behind one Router replay the pinned Poisson
    two-tenant trace; one replica is killed mid-trace and one drained.
    Returns the results dict whose numbers feed the ``serve_router_*``
    gate keys: every request terminal router-wide, zero leaked blocks
    (killed replica reaped and audited too), survivors token-identical
    to a fault-free pass, and the router-side p99 TTFTs (all requests,
    failover included, plus the cold tenant's under the hot burst)."""
    import tempfile

    from dmlcloud_tpu.checkpoint import read_requeue_verdict
    from dmlcloud_tpu.serve import Router, ServeEngine, TERMINAL_STATUSES
    from dmlcloud_tpu.serve.ledger import ServeLedger

    c, sc = _SERVE_ROUTER_CFG, _SERVE_CFG
    model, params = _serve_model()
    trace = _serve_router_trace()
    n = len(trace)
    warm = [(0.0, p, new) for _, p, new, _ in trace]

    # each engine has its OWN jit cache (per-engine TraceGuard budget), so
    # every replica warms on the full signature set; replica 0's fault-free
    # warm pass doubles as the reference arm every survivor must reproduce
    # bit-for-bit (greedy decode is batch-composition-independent)
    engines = []
    ref_outs = None
    for r in range(c["n_replicas"]):
        eng = ServeEngine(
            model, params,
            num_blocks=sc["num_blocks"], block_size=sc["block_size"],
            max_slots=sc["max_slots"], prefill_chunk=sc["prefill_chunk"],
        )
        eng.serve_trace(warm)
        if ref_outs is None:
            ref_outs = [eng.output(i) for i in range(n)]
        eng.ledger = ServeLedger()
        engines.append(eng)

    run_dir = tempfile.mkdtemp(prefix="bench_router_")
    router = Router(
        engines,
        heartbeat_timeout_s=c["heartbeat_timeout_s"],
        max_retries=c["max_retries"], backoff_base_s=c["backoff_base_s"],
        run_dir=run_dir,
    )

    # the drill's controller: deterministic kill + drain, triggered by
    # terminal-count thresholds (robust to wall-clock jitter — "mid-trace"
    # by progress, not by seconds)
    fired = {"kill": False, "drain": False}

    def controller(point, seqs):
        if point != "router_step":
            return
        done = sum(
            1 for s in router.statuses().values() if s in TERMINAL_STATUSES
        )
        if not fired["kill"] and done >= c["kill_after_done"]:
            fired["kill"] = True
            router.kill_replica(c["kill_replica"], reason="bench drill")
        if not fired["drain"] and done >= c["drain_after_done"]:
            fired["drain"] = True
            router.drain_replica(c["drain_replica"], reason="bench drill")

    router.fault_injector = controller
    summary = router.serve_trace(trace)
    leaked = router.leaked_blocks()

    statuses = [router.status(rid) for rid in range(n)]
    all_terminal = all(s in TERMINAL_STATUSES for s in statuses)
    survivors = [rid for rid, s in enumerate(statuses) if s == "ok"]
    identical = all(
        np.array_equal(router.output(rid), ref_outs[rid]) for rid in survivors
    )
    all_ttfts = router.ttfts()
    cold_ttfts = router.ttfts(tenant="cold")
    p99 = lambda xs: round(float(np.percentile(xs, 99)), 4) if xs else None
    verdict = read_requeue_verdict(run_dir)
    return {
        "config": {k: v for k, v in c.items()},
        "summary": summary,
        "kill_fired": fired["kill"],
        "drain_fired": fired["drain"],
        "failovers": int(router.failovers),
        "survivors_ok": len(survivors),
        "leaked_blocks": int(leaked),
        "survivor_token_identical": bool(identical),
        "all_terminal": bool(all_terminal),
        "failover_p99_ttft_s": p99(all_ttfts),
        "cold_p99_ttft_s": p99(cold_ttfts),
        "drain_verdict": (verdict or {}).get("serve"),
    }


def serve_child_main():
    """A/B the continuous-batching engine against serial ``generate()`` on
    the pinned Poisson trace, then the speculative engine against the
    plain engine on the pinned Markov trace, then the prefix-cache engine
    against the uncached engine on the pinned 80%-shared-template trace,
    then the overload/chaos drill on the adversarial two-tenant trace,
    then the multi-replica router drill (kill one replica mid-trace,
    drain another) (CPU-pinned child); prints one marker line of JSON —
    the source of ``BENCH_serve_*.json`` and of ``bench.py --gate
    --suite serve``'s current numbers."""
    jax.config.update("jax_platforms", "cpu")
    from dmlcloud_tpu.serve import ServeEngine
    from dmlcloud_tpu.serve.ledger import ServeLedger

    c = _SERVE_CFG
    model, params = _serve_model()
    trace = _serve_trace()

    serial, serial_outs = _serve_serial_arm(model, params, trace)

    engine = ServeEngine(
        model, params, num_blocks=c["num_blocks"], block_size=c["block_size"],
        max_slots=c["max_slots"], prefill_chunk=c["prefill_chunk"],
    )
    # warm pass: same trace, zero offsets — compiles every signature the
    # replay will hit (per-engine jit cache), then measure fresh
    engine.serve_trace([(0.0, p, n) for _, p, n in trace])
    warm_outs = [engine.output(i) for i in range(len(trace))]
    engine.ledger = ServeLedger()
    summary = engine.serve_trace(trace)

    identical = all(
        np.array_equal(w, s[: len(w)]) and len(w) == len(s)
        for w, s in zip(warm_outs, serial_outs)
    )
    speedup = (
        round(summary["tokens_per_sec"] / serial["tokens_per_sec"], 3)
        if summary["tokens_per_sec"] and serial["tokens_per_sec"]
        else None
    )
    spec = _spec_serve_section()
    medusa = _serve_medusa_section()
    prefix = _serve_prefix_section()
    chaos = _serve_chaos_section()
    router = _serve_router_section()
    results = {
        "config": dict(c),
        "value_source": "cpu_smoke",
        "host": _host_fingerprint(),
        "serial": serial,
        "engine": {
            **{k: (round(v, 4) if isinstance(v, float) else v) for k, v in summary.items()},
            "compiled_signatures": engine.compiled_signatures(),
            "max_signatures": engine.max_signatures,
        },
        "speedup_tokens_per_sec": speedup,
        "token_identical_to_serial": identical,
        "spec": spec,
        "medusa": medusa,
        "prefix": prefix,
        "chaos": chaos,
        "router": router,
        # the flat, schema-stable section the perf gate compares
        "gate": {
            "serve_tokens_per_sec_speedup": speedup,
            "serve_engine_tokens_per_sec": summary["tokens_per_sec"],
            "serve_p99_ttft_s": summary["p99_ttft_s"],
            # speculative-decode composition (ISSUE 10): speedup over the
            # non-spec engine, accept-rate floor, greedy token-identity and
            # the zero-mid-run-recompile contract as pass/fail ints
            "serve_spec_speedup_vs_engine": spec["speedup_tokens_per_sec"],
            "serve_spec_accept_rate": spec["accept_rate"],
            "serve_spec_tokens_per_sec": spec["spec_engine"]["tokens_per_sec"],
            "serve_spec_p99_ttft_s": spec["spec_engine"]["p99_ttft_s"],
            "serve_spec_token_identical": int(bool(spec["token_identical_to_serial"])),
            "serve_spec_zero_recompiles": int(spec["mid_run_recompiles"] == 0),
            # Medusa decoding (PR 16): the draftless speculative mode —
            # throughput at least the plain engine's, accept rate of the
            # distilled heads, greedy token-identity, zero mid-run
            # recompiles, and the deleted-draft-pool contract (no second
            # pool allocated, pool clean after the run) as pass/fail ints
            "serve_medusa_speedup_vs_engine": medusa["speedup_tokens_per_sec"],
            "serve_medusa_accept_rate": medusa["accept_rate"],
            "serve_medusa_tokens_per_sec": medusa["medusa_engine"]["tokens_per_sec"],
            "serve_medusa_p99_ttft_s": medusa["medusa_engine"]["p99_ttft_s"],
            "serve_medusa_token_identical": int(bool(medusa["token_identical_to_serial"])),
            "serve_medusa_zero_recompiles": int(medusa["mid_run_recompiles"] == 0),
            "serve_medusa_zero_draft_blocks": int(
                medusa["medusa_engine"]["draft_pool_blocks"] == 0
                and medusa["medusa_engine"]["leaked_blocks"] == 0
            ),
            # prefix-cache sharing (ISSUE 11): warm-template TTFT as a
            # lower-is-better latency, hit rate + prefill-skip fraction as
            # ratios, token-identity-to-uncached and the
            # zero-mid-run-recompile contract as pass/fail ints
            "serve_prefix_warm_ttft_s": prefix["warm_template_p50_ttft_s"],
            "serve_prefix_hit_rate": prefix["hit_rate"],
            "serve_prefix_prefill_tokens_saved_frac": prefix["prefill_tokens_saved_frac"],
            "serve_prefix_token_identical": int(bool(prefix["token_identical_to_uncached"])),
            "serve_prefix_zero_recompiles": int(prefix["mid_run_recompiles"] == 0),
            # overload/chaos drill (ISSUE 13): goodput under injected
            # faults, the cold tenant's p99 TTFT under a hot-tenant burst
            # as a lower-is-better latency, and the robustness contracts
            # (zero leaked blocks, every request terminal, survivors
            # greedy-token-identical to a fault-free run) as pass/fail ints
            "serve_chaos_goodput_tokens_per_sec": chaos["goodput_tokens_per_sec"],
            "serve_chaos_cold_p99_ttft_s": chaos["cold_p99_ttft_s"],
            "serve_chaos_zero_leaked_blocks": int(chaos["leaked_blocks"] == 0),
            "serve_chaos_survivor_token_identical": int(bool(chaos["survivor_token_identical"])),
            "serve_chaos_all_terminal": int(bool(chaos["all_terminal"])),
            # multi-replica router drill (ISSUE 15): every request ends in
            # exactly one terminal status router-wide despite a replica
            # kill and a replica drain mid-trace, zero leaked blocks
            # across all replicas (the killed one reaped and audited),
            # survivors greedy-token-identical to a fault-free pass, and
            # the router-side p99 TTFTs (failover re-prefill and backoff
            # inside the number; the cold tenant's under the hot burst)
            # as lower-is-better latencies
            "serve_router_all_terminal": int(bool(router["all_terminal"])),
            "serve_router_zero_leaked_blocks": int(router["leaked_blocks"] == 0),
            "serve_router_survivor_token_identical": int(bool(router["survivor_token_identical"])),
            "serve_router_failover_p99_ttft_s": router["failover_p99_ttft_s"],
            "serve_router_hot_tenant_cold_p99_ttft_s": router["cold_p99_ttft_s"],
        },
    }
    print(_SERVE_MARKER + json.dumps(results), flush=True)


def bench_serve(timeout_s: int = 1200) -> dict | None:
    """Run the serving A/B in a CPU-pinned child; returns its results
    dict, or None on failure."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--serve-child"],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
    )
    try:
        out, _ = proc.communicate(timeout=timeout_s)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.communicate()
        return None
    for line in (out or "").splitlines():
        if line.startswith(_SERVE_MARKER):
            try:
                return json.loads(line[len(_SERVE_MARKER):])
            except ValueError:
                return None
    return None


# ---------------------------------------------------- observability bench

_OBS_MARKER = "OBS_BENCH_RESULTS "

#: the observability-overhead A/B config (ISSUE 19) — pinned so receipts
#: stay comparable. The instrumented arm arms EVERYTHING at once (span
#: journal, metrics registry, SLO monitors) against the bare engine on
#: the SAME pinned Poisson trace as the serve A/B; best-of-N replays per
#: arm because a single CPU replay carries ~5% scheduler noise, which
#: would drown the ≤3% budget the gate enforces.
_OBS_CFG = dict(best_of=3, overhead_budget_frac=0.03)


def _obs_replay_best(engine, trace, best_of):
    """Replay the pinned trace ``best_of`` times on an already-warmed
    engine (ledger reset between replays) and return the best
    tokens_per_sec — the noise-robust throughput estimate of one arm."""
    from dmlcloud_tpu.serve.ledger import ServeLedger

    best = 0.0
    for _ in range(best_of):
        engine.ledger = ServeLedger()
        summary = engine.serve_trace(trace)
        best = max(best, float(summary["tokens_per_sec"]))
    return best


def _obs_overhead_section():
    """The tracing+metrics+SLO overhead A/B: two engines over the pinned
    Poisson serve trace — one bare, one with the full observability plane
    armed (journal spans flushing off-thread, metrics registry hot-path
    counters/histograms, SLO monitors evaluated every step). Returns the
    overhead fraction the ≤3% gate budget applies to."""
    import tempfile

    from dmlcloud_tpu.serve import SLO, ServeEngine
    from dmlcloud_tpu.telemetry import journal as tj
    from dmlcloud_tpu.telemetry.metrics_registry import parse_prometheus_text

    c, oc = _SERVE_CFG, _OBS_CFG
    model, params = _serve_model()
    trace = _serve_trace()
    warm = [(0.0, p, n) for _, p, n in trace]
    kwargs = dict(
        num_blocks=c["num_blocks"], block_size=c["block_size"],
        max_slots=c["max_slots"], prefill_chunk=c["prefill_chunk"],
    )

    bare = ServeEngine(model, params, **kwargs)
    bare.serve_trace(warm)
    bare_tps = _obs_replay_best(bare, trace, oc["best_of"])

    run_dir = tempfile.mkdtemp(prefix="bench_obs_")
    j = tj.SpanJournal(os.path.join(run_dir, "telemetry"))
    j.start()
    tj.activate(j)
    try:
        instr = ServeEngine(
            model, params, metrics=True,
            slos=[SLO("bench-ttft", ttft_p99_s=30.0, availability=0.5)],
            **kwargs,
        )
        instr.serve_trace(warm)
        instr_tps = _obs_replay_best(instr, trace, oc["best_of"])
        metrics_text = instr.metrics_text()
    finally:
        tj.deactivate()
        j.close()

    try:
        families = parse_prometheus_text(metrics_text)
        engine_metrics_valid = bool(families)
    except ValueError:
        engine_metrics_valid = False
    spans = j.tail(10 ** 6)
    overhead = max(0.0, (bare_tps - instr_tps) / bare_tps) if bare_tps else 1.0
    return {
        "config": dict(oc),
        "bare_tokens_per_sec": round(bare_tps, 1),
        "instrumented_tokens_per_sec": round(instr_tps, 1),
        "overhead_frac": round(overhead, 4),
        "spans_journaled": len(spans),
        "engine_metrics_valid": engine_metrics_valid,
        "leaked_blocks": int(instr.leaked_blocks()),
    }


def _obs_router_trace_drill():
    """The linked-trace drill: the SAME kill-one-drain-one router drill as
    ``_serve_router_section`` but with the span journal armed, so every
    span each request touches — across replicas, failover retries, and
    the drained replica's handoff — is journaled. The gate key is binary:
    every logical request resolves to exactly ONE trace id and the
    journal walk finds ZERO orphan request-scoped spans. Also scrapes
    ``Router.metrics_text()`` and validates it as Prometheus text."""
    import tempfile

    from dmlcloud_tpu.serve import Router, ServeEngine, TERMINAL_STATUSES
    from dmlcloud_tpu.serve.ledger import ServeLedger
    from dmlcloud_tpu.telemetry import journal as tj
    from dmlcloud_tpu.telemetry.journal import linked_trace_report
    from dmlcloud_tpu.telemetry.metrics_registry import parse_prometheus_text

    c, sc = _SERVE_ROUTER_CFG, _SERVE_CFG
    model, params = _serve_model()
    trace = _serve_router_trace()
    n = len(trace)
    warm = [(0.0, p, new) for _, p, new, _ in trace]

    engines = []
    for _ in range(c["n_replicas"]):
        eng = ServeEngine(
            model, params, metrics=True,
            num_blocks=sc["num_blocks"], block_size=sc["block_size"],
            max_slots=sc["max_slots"], prefill_chunk=sc["prefill_chunk"],
        )
        eng.serve_trace(warm)
        eng.ledger = ServeLedger()
        engines.append(eng)

    run_dir = tempfile.mkdtemp(prefix="bench_obs_router_")
    j = tj.SpanJournal(os.path.join(run_dir, "telemetry"))
    j.start()
    tj.activate(j)
    try:
        router = Router(
            engines,
            heartbeat_timeout_s=c["heartbeat_timeout_s"],
            max_retries=c["max_retries"], backoff_base_s=c["backoff_base_s"],
            run_dir=run_dir,
        )
        fired = {"kill": False, "drain": False}

        def controller(point, seqs):
            if point != "router_step":
                return
            done = sum(
                1 for s in router.statuses().values() if s in TERMINAL_STATUSES
            )
            if not fired["kill"] and done >= c["kill_after_done"]:
                fired["kill"] = True
                router.kill_replica(c["kill_replica"], reason="obs drill")
            if not fired["drain"] and done >= c["drain_after_done"]:
                fired["drain"] = True
                router.drain_replica(c["drain_replica"], reason="obs drill")

        router.fault_injector = controller
        router.serve_trace(trace)
        metrics_text = router.metrics_text()
    finally:
        tj.deactivate()
        j.close()

    records = tj.load_journals(run_dir)
    report = linked_trace_report(records)
    expected = {f"tr-{rid}" for rid in range(n)}
    linked = (
        not report["orphans"]
        and expected <= set(report["traces"])
        and all(report["traces"][t] for t in expected)
    )
    try:
        families = parse_prometheus_text(metrics_text)
        metrics_valid = bool(families)
    except ValueError:
        families, metrics_valid = {}, False
    statuses = [router.status(rid) for rid in range(n)]
    return {
        "requests": n,
        "kill_fired": fired["kill"],
        "drain_fired": fired["drain"],
        "failovers": int(router.failovers),
        "spans_journaled": len(records),
        "traces": len(report["traces"]),
        "orphan_spans": len(report["orphans"]),
        "trace_linked": bool(linked),
        "all_terminal": all(s in TERMINAL_STATUSES for s in statuses),
        "leaked_blocks": int(router.leaked_blocks()),
        "metrics_families": len(families),
        "metrics_valid": bool(metrics_valid),
    }


def obs_child_main():
    """A/B the observability plane's overhead (journal + metrics + SLO
    armed vs bare engine on the pinned Poisson trace), then the
    journal-armed kill-one-drain-one router drill proving every span
    links into exactly one per-request trace with zero orphans, then
    Prometheus-exposition validity (CPU-pinned child); prints one marker
    line of JSON — the source of ``BENCH_obs_*.json`` and of the
    ``--suite serve`` merged gate's obs keys."""
    jax.config.update("jax_platforms", "cpu")

    overhead = _obs_overhead_section()
    drill = _obs_router_trace_drill()
    results = {
        "config": {**_OBS_CFG, "serve": dict(_SERVE_CFG)},
        "value_source": "cpu_smoke",
        "host": _host_fingerprint(),
        "overhead": overhead,
        "router_drill": drill,
        # the flat, schema-stable section the perf gate compares: the
        # overhead fraction is lower-is-better (≤3% budget locked by the
        # committed-receipt test), linkage + exposition are pass/fail ints
        "gate": {
            "obs_overhead_frac": overhead["overhead_frac"],
            "obs_trace_linked": int(bool(drill["trace_linked"])),
            "obs_metrics_valid": int(
                bool(drill["metrics_valid"]) and bool(overhead["engine_metrics_valid"])
            ),
        },
    }
    print(_OBS_MARKER + json.dumps(results), flush=True)


def bench_obs(timeout_s: int = 1200) -> dict | None:
    """Run the observability overhead A/B + linked-trace drill in a
    CPU-pinned child; returns its results dict, or None on failure."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--obs-child"],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
    )
    try:
        out, _ = proc.communicate(timeout=timeout_s)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.communicate()
        return None
    for line in (out or "").splitlines():
        if line.startswith(_OBS_MARKER):
            try:
                return json.loads(line[len(_OBS_MARKER):])
            except ValueError:
                return None
    return None


# ------------------------------------------------------- data plane bench

_DATA_MARKER = "DATA_BENCH_RESULTS "

#: the CPU-smoke data-plane A/B config — pinned so receipts stay
#: comparable. A ragged corpus with lognormal document lengths (median 64
#: tokens under a 256-slot row: pad-to-max wastes ~3/4 of every batch —
#: the regime packing exists for), drawn as a deterministic weighted mix
#: of two sources so the receipt exercises the WHOLE streaming plane
#: (mix -> pack_stream -> batch -> TrainValStage). fp32 2-layer decoder:
#: big enough that the step dominates Python dispatch, small enough that
#: the A/B finishes in CI time.
_DATA_CFG = dict(
    vocab=512, layers=2, heads=4, kv=2, head_dim=32, hidden=128, mlp=256,
    seq_len=256, batch=8, n_docs=768, len_median=64.0, len_sigma=0.6,
    min_len=4, chunk_docs=192, mix_weights=(3.0, 1.0), seed=0, epochs=2,
    # the disk arm (PR 18): the same mixed stream staged as mmap'd
    # .dmlshard files and re-read through the async ShardReader, packed
    # with the window FFD packer instead of the chunked greedy fill
    pack_window=512, shard_tokens=16384, reader_buffers=2, read_ahead=64,
)


def _data_corpus():
    """The pinned ragged corpus, pre-split into the two mix sources: token
    ids are drawn from [1, vocab) so id 0 stays the pad id."""
    c = _DATA_CFG
    rs = np.random.RandomState(c["seed"])
    lengths = np.clip(
        np.round(rs.lognormal(np.log(c["len_median"]), c["len_sigma"], c["n_docs"])),
        c["min_len"], c["seq_len"],
    ).astype(np.int64)
    docs = [rs.randint(1, c["vocab"], size=int(n)).astype(np.int32) for n in lengths]
    half = len(docs) // 2
    return docs[:half], docs[half:]


def _data_mix_stream():
    """mix(sources, weights, seed): the deterministic weighted document
    stream BOTH arms consume — only the batching differs."""
    from dmlcloud_tpu.data import DataPipeline

    c = _DATA_CFG
    a, b = _data_corpus()
    return DataPipeline.mix(
        [DataPipeline.from_source(a), DataPipeline.from_source(b)],
        weights=c["mix_weights"], seed=c["seed"],
    )


def _data_arm(packed: bool, stats=None, disk_dir=None) -> dict:
    """One arm of the A/B through the real TrainValStage train step: the
    mixed document stream either pad-to-max (one document per row,
    ``segment_ids`` marking the pad slots — the correct-loss baseline) or
    streamed through ``pack_stream``. Both arms train the same fp32
    decoder with the segment-masked loss; telemetry arms the goodput
    ledger, so data_wait and pad_fraction come from the same accounting
    production runs use. Epoch 1 absorbs any warmup; the reported numbers
    come from epoch 2's tracker metrics.

    ``disk_dir`` switches the source to the disk plane: the async
    ``ShardReader`` over the staged ``.dmlshard`` corpus (same document
    order as the in-memory mix), packed by the window-FFD packer
    (``pack_window=``) instead of the chunked greedy fill — epoch 1
    additionally absorbs the cold mmap page faults, so epoch 2 is the
    sustained-from-disk figure."""
    import optax

    import dmlcloud_tpu as dml
    from dmlcloud_tpu.models.transformer import DecoderLM, TransformerConfig, lm_loss

    c = _DATA_CFG
    seq_len, batch = c["seq_len"], c["batch"]

    def pad_row(doc):
        tokens = np.zeros(seq_len, np.int32)
        segs = np.zeros(seq_len, np.int32)
        tokens[: doc.size] = doc
        segs[: doc.size] = 1
        return {"tokens": tokens, "segment_ids": segs}

    def collate(rows):
        return {k: np.stack([r[k] for r in rows]) for k in ("tokens", "segment_ids")}

    if disk_dir is not None:
        from dmlcloud_tpu.data import ShardReader

        stream = ShardReader(
            disk_dir, buffers=c["reader_buffers"], read_ahead=c["read_ahead"]
        ).pack_stream(seq_len, pack_window=c["pack_window"], stats=stats)
    else:
        stream = _data_mix_stream()
        if packed:
            stream = stream.pack_stream(seq_len, chunk_docs=c["chunk_docs"], stats=stats)
        else:
            stream = stream.map(pad_row)
    ds = stream.batch(batch, drop_remainder=True, collate=collate)

    class DataStage(dml.TrainValStage):
        def pre_stage(self):
            cfg = TransformerConfig(
                vocab_size=c["vocab"], num_layers=c["layers"], num_heads=c["heads"],
                num_kv_heads=c["kv"], head_dim=c["head_dim"], hidden_dim=c["hidden"],
                mlp_dim=c["mlp"], max_seq_len=seq_len, dtype=jnp.float32,
            )
            self.pipeline.register_model(
                "lm", DecoderLM(cfg),
                init_args=(np.zeros((1, 8), np.int32),), verbose=False,
            )
            self.pipeline.register_optimizer("sgd", optax.sgd(1e-3))
            self.pipeline.register_dataset("train", ds, verbose=False)

        def step(self, state, batch):
            logits = state.apply_fn(
                {"params": state.params}, batch["tokens"], segment_ids=batch["segment_ids"]
            )
            return lm_loss(logits, batch["tokens"], segment_ids=batch["segment_ids"])

        def val_epoch(self):  # throughput bench: train only
            pass

        def precompile(self):
            # AOT the one fixed-shape signature up front: misc/recompiles
            # then counts every mid-run XLA compile (0 is the contract —
            # both arms emit fixed [batch, seq_len] rows by construction)
            return True

        def log_every(self):
            return 0

    arm_name = "disk" if disk_dir is not None else ("packed" if packed else "pad")
    pipeline = dml.TrainingPipeline(name=f"bench-data-{arm_name}", telemetry=True)
    pipeline.append_stage(DataStage(), max_epochs=c["epochs"], name="stage")
    pipeline.run()
    tracker = pipeline.tracker

    def last(name):
        if name in tracker and tracker[name] and tracker[name][-1] is not None:
            return float(tracker[name][-1])
        return None

    steps = int(last("misc/worker_train_batches") or 0)
    step_ms = last("misc/train_step_avg_ms") or 0.0
    pad_frac = last("misc/pad_fraction") or 0.0
    slots = steps * batch * seq_len
    real_tokens = slots * (1.0 - pad_frac)
    elapsed_s = steps * step_ms / 1e3
    recompiles = sum(int(v or 0) for v in tracker["misc/recompiles"]) if "misc/recompiles" in tracker else None
    return {
        "steps_per_epoch": steps,
        "step_avg_ms": round(step_ms, 3),
        "pad_fraction": round(pad_frac, 4),
        "real_tokens_per_epoch": int(real_tokens),
        "tokens_per_sec": round(real_tokens / elapsed_s, 1) if elapsed_s > 0 else None,
        "data_wait_s": round((last("misc/data_wait_ms") or 0.0) / 1e3, 4),
        "goodput_frac": last("misc/goodput"),
        "recompiles": recompiles,
    }


def _data_disk_replay_drill(corpus_dir: str) -> float:
    """The 4→2 reshard zero-replay drill, pure host: four ws=4 readers
    consume a prefix in lockstep, one saves its cursor, two ws=2 readers
    resume from it and drain. Every record is keyed by content (random
    int32 docs — collisions are astronomically unlikely) and must be seen
    EXACTLY once across the two phases: a replayed record double-counts,
    a skipped record never appears. Returns 1.0 on exact coverage."""
    from dmlcloud_tpu.data import ShardReader, ShardStore

    store = ShardStore(corpus_dir)
    n = store.total_records
    expected = {}
    for g in range(n):
        expected.setdefault(store.record(g).tobytes(), []).append(g)
    seen: dict = {}

    def consume(rec):
        key = rec.tobytes()
        seen[key] = seen.get(key, 0) + 1

    k = max(1, (n // 4) // 3)  # a third of the corpus before the reshard
    readers4 = [ShardReader(store, rank=r, world_size=4) for r in range(4)]
    iters = [iter(r) for r in readers4]
    for _ in range(k):
        for it in iters:
            consume(next(it))
    state = readers4[0].state_dict()
    if state["global_offset"] != 4 * k:
        return 0.0
    for r in range(2):
        reader = ShardReader(store, rank=r, world_size=2)
        reader.load_state_dict(state)
        for rec in reader:
            consume(rec)
    ok = all(seen.get(key, 0) == len(gs) for key, gs in expected.items()) and sum(
        seen.values()
    ) == n
    return float(ok)


def data_child_main():
    """A/B the streaming packed data plane against pad-to-max on the pinned
    ragged corpus, plus the disk arm — the same mixed stream staged as
    mmap'd ``.dmlshard`` files, read back through the async ``ShardReader``
    and packed by the window-FFD packer (CPU-pinned child); prints one
    marker line of JSON — the source of ``BENCH_data_*.json`` and of
    ``bench.py --gate --suite data``'s current numbers."""
    jax.config.update("jax_platforms", "cpu")
    import tempfile

    from dmlcloud_tpu.data import PackStats
    from dmlcloud_tpu.data.store import build_corpus
    from dmlcloud_tpu.native import pack as native_pack

    c = _DATA_CFG
    # pad arm FIRST so in-process warm-up bias favors the baseline — a
    # packed win is then conservative, never an ordering artifact
    pad = _data_arm(packed=False)
    stats = PackStats()
    packed = _data_arm(packed=True, stats=stats)
    packed["pack"] = stats.as_dict()

    # stage the SAME mixed document stream to disk and re-run the packed
    # arm through the shard plane (epoch 1 absorbs the cold mmap faults;
    # epoch 2 is the sustained-from-disk figure)
    with tempfile.TemporaryDirectory(prefix="bench-data-shards-") as corpus_dir:
        manifest = build_corpus(
            corpus_dir, _data_mix_stream(), shard_tokens=c["shard_tokens"]
        )
        disk_stats = PackStats()
        disk = _data_arm(packed=True, stats=disk_stats, disk_dir=corpus_dir)
        disk["pack"] = disk_stats.as_dict()
        disk["corpus"] = {
            "shards": len(manifest["shards"]),
            "records": manifest["total_records"],
            "tokens": manifest["total_tokens"],
        }
        zero_replay = _data_disk_replay_drill(corpus_dir)

    speedup = (
        round(packed["tokens_per_sec"] / pad["tokens_per_sec"], 3)
        if packed["tokens_per_sec"] and pad["tokens_per_sec"]
        else None
    )
    reclaimed = round(pad["pad_fraction"] - packed["pad_fraction"], 4)
    zero_recompiles = float(
        (pad["recompiles"] or 0) == 0
        and (packed["recompiles"] or 0) == 0
        and (disk["recompiles"] or 0) == 0
    )
    results = {
        "workload": {
            **{k: (list(v) if isinstance(v, tuple) else v) for k, v in c.items()},
            "corpus": "lognormal doc lengths, pinned seed, 2-source weighted mix",
            "native_packer": native_pack.available(),
        },
        "value_source": "cpu_smoke",
        "host": _host_fingerprint(),
        "pad_to_max": pad,
        "packed_stream": packed,
        "disk_stream": disk,
        "packed_vs_pad_tokens_per_sec": speedup,
        # wasted-token fraction before vs after: the reclaimed padding
        "padding_waste_reclaimed": reclaimed,
        "disk_zero_replay": zero_replay,
        # the flat, schema-stable section the perf gate compares
        "gate": {
            "data_packed_speedup_vs_pad": speedup,
            "data_packed_tokens_per_sec": packed["tokens_per_sec"],
            "data_padding_waste_reclaimed": reclaimed,
            "data_zero_recompiles": zero_recompiles,
            "data_wait_s": packed["data_wait_s"],
            # the disk plane (PR 18): sustained tokens/s from the mmap'd
            # corpus, the FFD pad fraction (lower-is-better), the reader's
            # data_wait (lower-is-better), and the 4->2 reshard drill bit
            "data_disk_tokens_per_sec": disk["tokens_per_sec"],
            "data_disk_pad_fraction": disk["pad_fraction"],
            "data_disk_wait_s": disk["data_wait_s"],
            "data_disk_zero_replay": zero_replay,
        },
    }
    print(_DATA_MARKER + json.dumps(results), flush=True)


def bench_data(timeout_s: int = 900) -> dict | None:
    """Run the data-plane A/B in a CPU-pinned child; returns its results
    dict, or None on failure."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--data-child"],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
    )
    try:
        out, _ = proc.communicate(timeout=timeout_s)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.communicate()
        return None
    for line in (out or "").splitlines():
        if line.startswith(_DATA_MARKER):
            try:
                return json.loads(line[len(_DATA_MARKER):])
            except ValueError:
                return None
    return None


# ------------------------------------------------ quantized-training bench

_TRAIN_QUANT_MARKER = "TRAIN_QUANT_BENCH_RESULTS "

#: the CPU-smoke quantized-training A/B config — pinned so the
#: ``BENCH_train_quant_*.json`` receipts stay comparable across commits.
#: Shapes are sized so the projection GEMMs dominate the step on one CPU
#: core (the convert-per-GEMM tax of the emulated-bf16 arm, and the int8
#: arm's avoidance of it, is what the A/B measures — doc/performance.md).
_TRAIN_QUANT_CFG = dict(
    vocab=512, layers=3, heads=8, kv=4, head_dim=32, hidden=256, mlp=1024,
    max_seq_len=128, batch=8, seq=96, lr=1e-3, batches_per_epoch=6,
    epochs=4, seed=0,
    # int8 trains fp32 master weights; its trajectory must track the bf16
    # baseline's to within this relative gap on the final epoch's mean loss
    loss_rel_bound=0.05,
)


def _train_quant_arm(precision: str, dtype):
    """One training arm of the quantized-training A/B: the pinned tiny LM
    driven through the REAL ``TrainValStage`` (``precision=`` is the
    production switch being benchmarked, not a bench-local reimplementation)
    on the pinned corpus. Epoch 0 pays compilation; steps/s comes from the
    remaining epochs' wall time. Returns (steps_per_sec, per-epoch mean
    train losses)."""
    from dmlcloud_tpu.models.transformer import DecoderLM, TransformerConfig

    c = _TRAIN_QUANT_CFG
    cfg = TransformerConfig(
        vocab_size=c["vocab"], num_layers=c["layers"], num_heads=c["heads"],
        num_kv_heads=c["kv"], head_dim=c["head_dim"], hidden_dim=c["hidden"],
        mlp_dim=c["mlp"], max_seq_len=c["max_seq_len"], dtype=dtype,
    )
    rng = np.random.RandomState(c["seed"])
    train = [
        {"tokens": rng.randint(0, c["vocab"], size=(c["batch"], c["seq"])).astype(np.int32)}
        for _ in range(c["batches_per_epoch"])
    ]
    val = [dict(train[0])]
    epoch_times: list = []

    class QuantBenchStage(dml.TrainValStage):
        def pre_stage(self):
            model = DecoderLM(cfg)
            params = model.init(jax.random.PRNGKey(0), jnp.ones((1, 8), jnp.int32))
            self.pipeline.register_model("lm", model, params=params, verbose=False)
            self.pipeline.register_optimizer("adamw", optax.adamw(c["lr"]))
            self.pipeline.register_dataset("train", train, verbose=False)
            self.pipeline.register_dataset("val", val, verbose=False)

        def pre_epoch(self):
            self._t0 = time.perf_counter()

        def post_epoch(self):
            epoch_times.append(time.perf_counter() - self._t0)

        def step(self, state, batch):
            toks = batch["tokens"]
            logits = state.apply_fn({"params": state.params}, toks[:, :-1])
            loss = optax.softmax_cross_entropy_with_integer_labels(
                logits.astype(jnp.float32), toks[:, 1:]
            ).mean()
            return loss

    pipe = dml.TrainingPipeline(name=f"quant-bench-{precision}")
    stage = QuantBenchStage(precision=precision)
    pipe.append_stage(stage, max_epochs=c["epochs"])
    pipe.run()
    losses = [float(x) for x in stage.tracker["train/loss"]]
    timed = epoch_times[1:]  # epoch 0 pays jit compilation
    steps_per_sec = c["batches_per_epoch"] * len(timed) / sum(timed)
    return steps_per_sec, losses


def train_quant_child_main():
    """A/B the quantized training path (``TrainValStage(precision="int8")``
    over fp32 master weights, models/quant.py) against the plain bf16 stage
    on the pinned tiny-LM config (CPU-pinned child); prints one marker line
    of JSON — the source of the ``BENCH_train_quant_*.json`` receipts. The
    int8 arm must be FASTER than bf16 (XLA:CPU emulates bf16 GEMMs with a
    widen/round pass the int8 path never takes; on TPU the win is the int8
    MXU path) and its loss trajectory must track bf16's."""
    jax.config.update("jax_platforms", "cpu")
    c = _TRAIN_QUANT_CFG
    bf16_sps, bf16_losses = _train_quant_arm("full", jnp.bfloat16)
    int8_sps, int8_losses = _train_quant_arm("int8", jnp.float32)
    tokens_per_step = c["batch"] * (c["seq"] - 1)
    loss_rel_gap = abs(int8_losses[-1] - bf16_losses[-1]) / max(abs(bf16_losses[-1]), 1e-9)
    trajectory_ok = loss_rel_gap <= c["loss_rel_bound"]
    results = {
        "config": dict(c),
        "value_source": "cpu_smoke",
        "host": _host_fingerprint(),
        "bf16": {
            "steps_per_sec": round(bf16_sps, 4),
            "tokens_per_sec": round(bf16_sps * tokens_per_step, 1),
            "epoch_losses": [round(x, 5) for x in bf16_losses],
        },
        "int8": {
            "steps_per_sec": round(int8_sps, 4),
            "tokens_per_sec": round(int8_sps * tokens_per_step, 1),
            "epoch_losses": [round(x, 5) for x in int8_losses],
        },
        "loss_rel_gap_final_epoch": round(loss_rel_gap, 5),
        # the flat, schema-stable section the perf gate compares
        "gate": {
            "train_int8_speedup_vs_bf16": round(int8_sps / bf16_sps, 3),
            "train_int8_steps_per_sec": round(int8_sps, 3),
            "train_int8_tokens_per_sec": round(int8_sps * tokens_per_step, 1),
            "train_int8_loss_trajectory_ok": int(trajectory_ok),
        },
    }
    print(_TRAIN_QUANT_MARKER + json.dumps(results), flush=True)


def bench_train_quant(timeout_s: int = 1200) -> dict | None:
    """Run the quantized-training A/B in a CPU-pinned child; returns its
    results dict, or None on failure."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--train-quant-child"],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
    )
    try:
        out, _ = proc.communicate(timeout=timeout_s)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.communicate()
        return None
    for line in (out or "").splitlines():
        if line.startswith(_TRAIN_QUANT_MARKER):
            try:
                return json.loads(line[len(_TRAIN_QUANT_MARKER):])
            except ValueError:
                return None
    return None


# --------------------------------------------------------------- perf gate

#: relative drop in a gate metric that fails the gate (15%: comfortably
#: above the observed CPU-smoke run-to-run noise of ~5%, far below the
#: regressions the gate exists to catch — 0.48x, 0.19x, a dead accept rate)
_GATE_TOLERANCE = 0.15

#: goodput-ledger keys compared when both receipts carry them (the full
#: bench.py receipts do; kernel receipts usually don't)
_GATE_GOODPUT_KEYS = ("goodput_frac",)

#: gate metrics where SMALLER is better (the elastic drill's latencies);
#: everything else is a speedup/ratio where bigger is better
_GATE_LOWER_IS_BETTER = frozenset(
    {
        "elastic_save_on_preempt_latency_s",
        "elastic_time_to_resume_s",
        "serve_p99_ttft_s",
        "serve_spec_p99_ttft_s",
        "serve_medusa_p99_ttft_s",
        "serve_prefix_warm_ttft_s",
        "serve_chaos_cold_p99_ttft_s",
        "serve_router_failover_p99_ttft_s",
        "serve_router_hot_tenant_cold_p99_ttft_s",
        "data_wait_s",
        "data_disk_wait_s",
        "data_disk_pad_fraction",
        "obs_overhead_frac",
        "tier1_suite_wall_s",
        "lint_cold_wall_s",
        "lint_warm_wall_s",
        "verify_wall_s",
    }
)

#: relative GROWTH allowed for the lower-is-better latency metrics (100%:
#: wall-clock latencies on a shared CI box are far noisier than kernel
#: ratios; the gate exists to catch the async save turning sync or the
#: resume path re-running whole epochs — order-of-magnitude breakage)
_GATE_LATENCY_TOLERANCE = 1.0


def _host_fingerprint() -> dict:
    """Where a receipt's numbers were measured: CPU count, platform string,
    python version. Stamped into every bench child's receipt so the gate can
    WARN (not fail) when an ABSOLUTE baseline key — a tokens/s or a latency,
    as opposed to a within-run ratio — was committed on a different box and
    its floor may simply not transfer."""
    import platform as _platform

    return {
        "cpu_count": os.cpu_count(),
        "platform": _platform.platform(),
        "python": _platform.python_version(),
    }


#: gate keys whose baseline value is an ABSOLUTE measurement of the box it
#: ran on (throughputs, latencies, wall times) rather than a within-run
#: ratio — the ones the cross-host warning below is about
def _absolute_gate_keys(metrics: dict) -> list:
    return [
        k for k in metrics
        if k.endswith(("_per_sec", "_s")) and k not in ("tokens_per_sec_speedup",)
    ]


def _warn_if_cross_host(receipt: dict, name: str) -> None:
    """Print a stderr warning when ``receipt`` carries a host fingerprint
    that does not match this box and contributes absolute (non-ratio) gate
    keys. Old receipts without a fingerprint stay silent — nothing to
    compare."""
    host = receipt.get("host")
    if not isinstance(host, dict):
        return
    here = _host_fingerprint()
    if host == here:
        return
    abs_keys = _absolute_gate_keys(_gate_metrics(receipt))
    if not abs_keys:
        return
    print(
        f"gate: WARNING — baseline {name} was recorded on a different host "
        f"({host.get('platform')}, {host.get('cpu_count')} cpus; this box: "
        f"{here.get('platform')}, {here.get('cpu_count')} cpus); its absolute "
        f"floors may not transfer: {', '.join(sorted(abs_keys))}",
        file=sys.stderr,
    )


def _gate_metrics(receipt: dict) -> dict:
    """The comparable higher-is-better metrics of a receipt: the flat
    ``gate`` section every kernels receipt carries, plus the goodput
    productive fraction when present (full ``bench.py`` receipts)."""
    out = {}
    for k, v in (receipt.get("gate") or {}).items():
        if isinstance(v, (int, float)):
            out[k] = float(v)
    src = receipt.get("parsed") or receipt  # driver-wrapped or bare receipt
    for k in _GATE_GOODPUT_KEYS:
        v = src.get(k)
        if isinstance(v, (int, float)):
            out[k] = float(v)
    return out


def _latest_receipt(prefix: str) -> str | None:
    import glob

    here = os.path.dirname(os.path.abspath(__file__))
    receipts = sorted(glob.glob(os.path.join(here, f"BENCH_{prefix}_*.json")))
    return receipts[-1] if receipts else None


def _latest_kernels_receipt() -> str | None:
    return _latest_receipt("kernels")


def run_gate(baseline_path: str, current: dict | str | None = None,
             tolerance: float = _GATE_TOLERANCE) -> int:
    """Compare the current kernel ratios + goodput against a committed
    receipt; exit-code semantics: 0 pass, 1 regression, 2 couldn't measure.

    ``current`` may be a results dict, a path to a receipt JSON, or None to
    measure fresh via the CPU-pinned kernels child. Every metric the
    BASELINE carries must be present in the current run (a silently missing
    number is a failure, not a pass — that is exactly how the r05 all-null
    receipt slipped through) and must not drop more than ``tolerance``
    relative. Metrics only the current run carries are informational.

    ``baseline_path`` may also be an already-merged metrics dict (the
    serve suite folds EVERY committed receipt into one baseline, each key
    at its most recently committed value)."""
    if isinstance(baseline_path, dict):
        baseline, baseline_name = baseline_path, "merged receipts"
    else:
        with open(baseline_path) as f:
            baseline = json.load(f)
        baseline_name = os.path.basename(baseline_path)
        _warn_if_cross_host(baseline, baseline_name)
    if isinstance(current, str):
        with open(current) as f:
            current = json.load(f)
    elif current is None:
        print("gate: measuring current kernel ratios (CPU-pinned child)...", file=sys.stderr)
        current = bench_kernels()
        if current is None:
            print("gate: FAIL — kernels child produced no results", file=sys.stderr)
            return 2
    base_m, cur_m = _gate_metrics(baseline), _gate_metrics(current)
    if not base_m:
        print(f"gate: FAIL — no gate metrics in baseline {baseline_name}", file=sys.stderr)
        return 2
    failures = []
    width = max(len(k) for k in base_m)
    print(f"perf gate vs {baseline_name} (tolerance {tolerance:.0%}):")
    for k, bv in sorted(base_m.items()):
        cv = cur_m.get(k)
        if cv is None:
            failures.append(k)
            print(f"  {k:<{width}}  baseline {bv:8.3f}  current     MISSING  FAIL")
            continue
        if k in _GATE_LOWER_IS_BETTER:
            # a latency: regression is GROWTH, judged against the (wide)
            # latency tolerance — wall clock on CI is noisy
            drop = (cv - bv) / bv if bv > 0 else 0.0
            bad = drop > max(tolerance, _GATE_LATENCY_TOLERANCE)
        else:
            drop = (bv - cv) / bv if bv > 0 else 0.0
            bad = drop > tolerance
        print(
            f"  {k:<{width}}  baseline {bv:8.3f}  current {cv:8.3f}  "
            f"{'FAIL' if bad else 'ok':>4}  ({-drop:+.1%})"
        )
        if bad:
            failures.append(k)
    if failures:
        print(f"gate: FAIL — {len(failures)} metric(s) regressed: {', '.join(failures)}")
        return 1
    print("gate: PASS")
    return 0


def gate_main(argv: list) -> int:
    """``bench.py --gate [--suite kernels|elastic|serve|data|tier1|all]
    [--baseline B.json] [--current C.json] [--tolerance 0.15]`` — CI
    regression gate over the committed receipts (scripts/perf_gate.sh
    wires it into the lint-gate flow). The ``kernels`` suite (default)
    measures the kernel A/Bs AND the quantized-training A/B against every
    committed ``BENCH_kernels_*.json`` + ``BENCH_train_*.json`` merged into
    one baseline (the ``train_int8_*`` speedup/trajectory keys stay
    enforced; a vanished metric FAILS); the ``elastic`` suite runs the preemption
    drill and compares its metrics against the last committed
    ``BENCH_elastic_*.json`` (exact resume, save-on-preempt latency,
    time-to-resume); the ``serve`` suite replays the Poisson serving A/B
    against EVERY committed ``BENCH_serve_*.json`` merged into one
    baseline — each key at its most recently committed value (tokens/s
    speedup vs serial generate, p99 TTFT, the ``serve_spec_*`` composition
    keys, the ``serve_prefix_*`` sharing keys, the ``serve_chaos_*``
    robustness keys and the ``serve_router_*`` failover/drain keys —
    latencies judged lower-is-better; every receipt's keys stay enforced,
    so a silently-vanished metric FAILS — and, when a committed
    ``BENCH_obs_*.json`` exists, the observability child runs too and its
    ``obs_overhead_frac`` (lower-is-better, ≤3% budget) /
    ``obs_trace_linked`` / ``obs_metrics_valid`` keys merge into the same
    comparison); the ``data`` suite replays the streaming
    packed-vs-pad-to-max A/B plus the disk arm against EVERY committed
    ``BENCH_data_*.json`` merged into one baseline (packed tokens/s
    speedup, padding waste reclaimed, 0 mid-run recompiles, data_wait as
    a lower-is-better latency, and the PR-18 disk keys: sustained
    tokens/s off the mmap'd shards, the FFD pad fraction and reader wait
    lower-is-better, the 4→2 reshard zero-replay bit); the ``tier1`` suite (opt-in, not part of ``all``) times the
    tier-1 pytest run and gates its wall seconds lower-is-better against
    the last ``BENCH_tier1_*.json``; the ``lint`` suite (also opt-in) runs
    the incremental-cache cold/warm A/B (scripts/bench_lint.py) and gates
    both wall times plus the ``lint_incremental_ok`` warm-budget bit
    against the last ``BENCH_lint_*.json``. A missing metric FAILS in every
    suite; ``all`` chains them and fails on the worst. Baselines recorded
    on a different host WARN about their absolute (non-ratio) keys."""

    def _opt(flag, default=None):
        if flag in argv:
            i = argv.index(flag)
            if i + 1 < len(argv):
                return argv[i + 1]
        return default

    suite = _opt("--suite", "kernels")
    tolerance = float(_opt("--tolerance", _GATE_TOLERANCE))
    if suite not in ("kernels", "elastic", "serve", "data", "tier1", "lint", "all"):
        print(
            f"gate: unknown --suite {suite!r} (kernels|elastic|serve|data|tier1|lint|all)",
            file=sys.stderr,
        )
        return 2

    def _merged_baseline(patterns: list) -> dict | None:
        """EVERY committed receipt matching ``patterns`` folds into ONE
        merged baseline, each key at its most recently committed value
        (receipts sorted by name; later receipts override earlier per key).
        That is what keeps a silently-vanished metric a FAIL — every
        receipt's keys stay enforced — without an older receipt's stale
        absolute numbers resurrecting as floors. Receipts from a different
        host WARN about their absolute keys on the way in."""
        import glob as _glob

        here = os.path.dirname(os.path.abspath(__file__))
        receipts: list = []
        for pat in patterns:
            receipts.extend(_glob.glob(os.path.join(here, pat)))
        if not receipts:
            return None
        merged: dict = {}
        for path in sorted(receipts):
            with open(path) as f:
                receipt = json.load(f)
            _warn_if_cross_host(receipt, os.path.basename(path))
            merged.update(_gate_metrics(receipt))
        return {"gate": merged}

    rcs = []
    if suite in ("kernels", "all"):
        explicit = _opt("--baseline") if suite == "kernels" else None
        if explicit is not None:
            baseline = explicit
        else:
            # kernel receipts AND the quantized-training receipts merge into
            # one baseline (PR 16): the train_int8_* keys are enforced the
            # same way the serve suite enforces serve_prefix_* — a vanished
            # metric FAILS, the latest committed value is the floor
            baseline = _merged_baseline(["BENCH_kernels_*.json", "BENCH_train_*.json"])
        if baseline is None:
            print(
                "gate: FAIL — no --baseline and no committed BENCH_kernels_*.json"
                " / BENCH_train_*.json",
                file=sys.stderr,
            )
            return 2
        current = _opt("--current") if suite == "kernels" else None
        if current is None and (
            not isinstance(baseline, dict) or any(
                k.startswith("train_") for k in baseline["gate"]
            )
        ):
            # the merged baseline carries train_int8_* keys, so the current
            # run must produce them too: both CPU-pinned children run and
            # their gate sections merge (missing either child = FAIL)
            print("gate: measuring current kernel ratios (CPU-pinned child)...", file=sys.stderr)
            cur_k = bench_kernels()
            print("gate: running the quantized-training A/B (train-quant child)...", file=sys.stderr)
            cur_t = bench_train_quant()
            if cur_k is None or cur_t is None:
                which = "kernels" if cur_k is None else "train-quant"
                print(f"gate: FAIL — {which} child produced no results", file=sys.stderr)
                return 2
            current = {"gate": {**_gate_metrics(cur_k), **_gate_metrics(cur_t)}}
        rcs.append(run_gate(baseline, current, tolerance))
    if suite in ("elastic", "all"):
        baseline = _opt("--baseline") if suite == "elastic" else None
        baseline = baseline or _latest_receipt("elastic")
        if baseline is None:
            print("gate: FAIL — no --baseline and no committed BENCH_elastic_*.json", file=sys.stderr)
            return 2
        current = _opt("--current") if suite == "elastic" else None
        if current is None:
            print("gate: running the preemption drill (elastic suite child)...", file=sys.stderr)
            current = bench_elastic()
            if current is None:
                print("gate: FAIL — elastic drill child produced no results", file=sys.stderr)
                return 2
        rcs.append(run_gate(baseline, current, tolerance))
    if suite in ("serve", "all"):
        explicit = _opt("--baseline") if suite == "serve" else None
        if explicit is not None:
            baseline = explicit
        else:
            # EVERY committed serve receipt folds into ONE merged baseline —
            # a silently-vanished serve_prefix_* (or serve_medusa_*) metric
            # FAILS while an older receipt's stale absolute numbers (e.g.
            # pr08's tokens/s from a different box era) do not resurrect as
            # floors (_merged_baseline). PR 19's observability receipts
            # (BENCH_obs_*.json: obs_overhead_frac / obs_trace_linked /
            # obs_metrics_valid) merge into the SAME baseline, so a
            # vanished obs key fails the serve suite too.
            baseline = _merged_baseline(["BENCH_serve_*.json", "BENCH_obs_*.json"])
            if baseline is None:
                print("gate: FAIL — no --baseline and no committed BENCH_serve_*.json", file=sys.stderr)
                return 2
        current = _opt("--current") if suite == "serve" else None
        if current is None and (
            not isinstance(baseline, dict) or any(
                k.startswith("obs_") for k in baseline["gate"]
            )
        ):
            # the merged baseline carries obs_* keys, so the current run
            # must produce them too: both CPU-pinned children run and
            # their gate sections merge (missing either child = FAIL)
            print("gate: running the serving A/B (serve suite child)...", file=sys.stderr)
            cur_s = bench_serve()
            print("gate: running the observability A/B (obs suite child)...", file=sys.stderr)
            cur_o = bench_obs()
            if cur_s is None or cur_o is None:
                which = "serve" if cur_s is None else "obs"
                print(f"gate: FAIL — {which} bench child produced no results", file=sys.stderr)
                return 2
            current = {"gate": {**_gate_metrics(cur_s), **_gate_metrics(cur_o)}}
        elif current is None:
            print("gate: running the serving A/B (serve suite child)...", file=sys.stderr)
            current = bench_serve()
            if current is None:
                print("gate: FAIL — serve bench child produced no results", file=sys.stderr)
                return 2
        rcs.append(run_gate(baseline, current, tolerance))
    if suite in ("data", "all"):
        baseline = _opt("--baseline") if suite == "data" else None
        if baseline is None:
            # EVERY committed data receipt folds into ONE merged baseline
            # (PR 18): pr09's in-memory keys and pr18's disk keys are
            # enforced together — a vanished metric FAILS, the latest
            # committed value is each key's floor
            baseline = _merged_baseline(["BENCH_data_*.json"])
        if baseline is None:
            print("gate: FAIL — no --baseline and no committed BENCH_data_*.json", file=sys.stderr)
            return 2
        current = _opt("--current") if suite == "data" else None
        if current is None:
            print("gate: running the data-plane A/B (data suite child)...", file=sys.stderr)
            current = bench_data()
            if current is None:
                print("gate: FAIL — data bench child produced no results", file=sys.stderr)
                return 2
        rcs.append(run_gate(baseline, current, tolerance))
    if suite == "tier1":
        # NOT part of --suite all: this one runs the whole tier-1 test
        # suite (CI runs it separately anyway) and gates its WALL TIME as a
        # lower-is-better latency against the last committed
        # BENCH_tier1_*.json — the budget receipt of the fixture-sharing /
        # slow-marker work, so a suite that quietly doubles fails here
        # before it times out the real CI job.
        baseline = _opt("--baseline") or _latest_receipt("tier1")
        if baseline is None:
            print("gate: FAIL — no --baseline and no committed BENCH_tier1_*.json", file=sys.stderr)
            return 2
        current = _opt("--current")
        if current is None:
            print("gate: timing the tier-1 suite (pytest child)...", file=sys.stderr)
            current = bench_tier1()
            if current is None:
                print("gate: FAIL — tier-1 suite child produced no results", file=sys.stderr)
                return 2
        rcs.append(run_gate(baseline, current, tolerance))
    if suite == "lint":
        # NOT part of --suite all (CI's lint_gate.sh already runs the
        # linter on every invocation): cold-vs-warm A/B of the incremental
        # lint cache against the last committed BENCH_lint_pr17-style
        # receipt. The child refuses to emit a receipt if the warm run
        # changes the findings, and stamps lint_incremental_ok=0 when warm
        # exceeds its budget fraction of cold — either FAILS here (a
        # vanished metric fails too, like every other suite). PR 20's IR
        # verifier receipts (BENCH_verify_*.json: verify_wall_s + the
        # verify_caught_donation / verify_caught_oom defect-detection
        # bits) merge into the SAME baseline, so a verifier that goes
        # blind — or a vanished verify key — fails the lint suite too.
        explicit = _opt("--baseline")
        if explicit is not None:
            baseline = explicit
        else:
            baseline = _merged_baseline(["BENCH_lint_*.json", "BENCH_verify_*.json"])
        if baseline is None:
            print("gate: FAIL — no --baseline and no committed BENCH_lint_*.json", file=sys.stderr)
            return 2
        current = _opt("--current")
        if current is None and (
            not isinstance(baseline, dict) or any(
                k.startswith("verify_") for k in baseline["gate"]
            )
        ):
            # the merged baseline carries verify_* keys, so the current
            # run must produce them too: both children run and their gate
            # sections merge (missing either child = FAIL)
            print("gate: running the lint cold/warm A/B (bench_lint child)...", file=sys.stderr)
            cur_l = bench_lint()
            print("gate: running the IR verifier A/B (bench_verify child)...", file=sys.stderr)
            cur_v = bench_verify()
            if cur_l is None or cur_v is None:
                which = "lint" if cur_l is None else "verify"
                print(f"gate: FAIL — {which} bench child produced no results", file=sys.stderr)
                return 2
            current = {"gate": {**_gate_metrics(cur_l), **_gate_metrics(cur_v)}}
        elif current is None:
            print("gate: running the lint cold/warm A/B (bench_lint child)...", file=sys.stderr)
            current = bench_lint()
            if current is None:
                print("gate: FAIL — lint bench child produced no results", file=sys.stderr)
                return 2
        rcs.append(run_gate(baseline, current, tolerance))
    return max(rcs)


def bench_lint(timeout_s: int = 300) -> dict | None:
    """Run scripts/bench_lint.py (pure-stdlib child — the linter must stay
    importable without jax) and return its receipt dict: cold/warm wall
    seconds of the self-lint plus the ``lint_incremental_ok`` bit. None if
    the child failed or produced no receipt."""
    import tempfile

    here = os.path.dirname(os.path.abspath(__file__))
    with tempfile.TemporaryDirectory() as tmp:
        out = os.path.join(tmp, "lint_receipt.json")
        cmd = [sys.executable, os.path.join(here, "scripts", "bench_lint.py"), "-o", out]
        try:
            proc = subprocess.run(
                cmd, cwd=here, timeout=timeout_s,
                stdout=subprocess.DEVNULL, stderr=subprocess.PIPE, text=True,
            )
        except subprocess.TimeoutExpired:
            return None
        if proc.returncode != 0:
            sys.stderr.write(proc.stderr or "")
            return None
        try:
            with open(out) as f:
                return json.load(f)
        except (OSError, ValueError):
            return None


def bench_verify(timeout_s: int = 300) -> dict | None:
    """Run scripts/bench_verify.py (CPU-pinned child — the IR verifier
    needs jax, unlike the pure-stdlib linter) and return its receipt dict:
    verify wall seconds over the pinned train+serve configs plus the
    ``verify_caught_donation``/``verify_caught_oom`` defect-detection
    bits. None if the child failed or produced no receipt."""
    import tempfile

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    here = os.path.dirname(os.path.abspath(__file__))
    with tempfile.TemporaryDirectory() as tmp:
        out = os.path.join(tmp, "verify_receipt.json")
        cmd = [sys.executable, os.path.join(here, "scripts", "bench_verify.py"), "-o", out]
        try:
            proc = subprocess.run(
                cmd, cwd=here, timeout=timeout_s, env=env,
                stdout=subprocess.DEVNULL, stderr=subprocess.PIPE, text=True,
            )
        except subprocess.TimeoutExpired:
            return None
        if proc.returncode != 0:
            sys.stderr.write(proc.stderr or "")
            return None
        try:
            with open(out) as f:
                return json.load(f)
        except (OSError, ValueError):
            return None


def bench_tier1(timeout_s: int = 870) -> dict | None:
    """Time the tier-1 suite (the CI verify command, CPU-pinned, ``-m 'not
    slow'``) and return a receipt-shaped dict: wall seconds as a
    lower-is-better gate metric plus the pass/fail bit. ``timeout_s``
    defaults to the CI budget — a suite that exceeds it returns rc 124
    semantics (tier1_exit_ok 0), not None, so the gate shows the number."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    here = os.path.dirname(os.path.abspath(__file__))
    cmd = [
        sys.executable, "-m", "pytest", "tests/", "-q", "-m", "not slow",
        "--continue-on-collection-errors", "-p", "no:cacheprovider",
        "-p", "no:xdist", "-p", "no:randomly",
    ]
    t0 = time.perf_counter()
    proc = subprocess.Popen(
        cmd, cwd=here, env=env, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True,
    )
    try:
        out, _ = proc.communicate(timeout=timeout_s)
        rc = proc.returncode
    except subprocess.TimeoutExpired:
        proc.kill()
        out, _ = proc.communicate()
        rc = 124
    wall = time.perf_counter() - t0
    tail = "\n".join((out or "").splitlines()[-3:])
    return {
        "value_source": "cpu_smoke",
        "host": _host_fingerprint(),
        "pytest_rc": rc,
        "summary_tail": tail,
        "gate": {
            "tier1_suite_wall_s": round(wall, 1),
            "tier1_exit_ok": int(rc == 0),
        },
    }


_METRICS_WORKER = """
import os, sys, time
sys.path.insert(0, {repo!r})
import numpy as np
from dmlcloud_tpu.parallel import runtime as rt
from dmlcloud_tpu.metrics import MetricTracker, Reduction

rt.init_auto()
tracker = MetricTracker()
names = [f"m{{i}}" for i in range(12)]
for name in names:
    tracker.register_metric(name, Reduction.MEAN)
times = []
for epoch in range({epochs}):
    for name in names:
        tracker.track(name, float(epoch))
    rt.barrier("align")  # align ranks: time the exchange, not launch skew
    t0 = time.perf_counter()
    tracker.next_epoch()
    times.append(time.perf_counter() - t0)

# The reference's exchange, on the same control plane: per metric, one
# object gather (emptiness consensus) + one numeric all-reduce — 2
# collectives x 12 metrics per epoch (/root/reference/dmlcloud/metrics.py:121-141)
# vs the tracker's ONE packed collective above.
ref_times = []
for epoch in range({epochs}):
    rt.barrier("align_ref")
    t0 = time.perf_counter()
    for name in names:
        gathered = rt.all_gather_object((name, False))
        vals = rt.all_gather_array(np.asarray([float(epoch)], np.float32))
        _ = float(np.mean(vals))
    ref_times.append(time.perf_counter() - t0)
if rt.rank() == 0:
    print("P50_MS", float(np.percentile(np.asarray(times[5:]) * 1e3, 50)), flush=True)
    print("REF_P50_MS", float(np.percentile(np.asarray(ref_times[5:]) * 1e3, 50)), flush=True)
"""


def bench_metrics_allreduce(n_procs=8, epochs=40):
    """p50 latency of the fused epoch-end metric exchange (12 metrics) across
    ``n_procs`` real coordinated processes on localhost (CPU backend — the
    one-chip environment cannot host a multi-process TPU group). The same
    worker also times the reference's exchange pattern — 2 collectives per
    metric per epoch (/root/reference/dmlcloud/metrics.py:121-141) — on the
    same runtime, so the fused-vs-reference speedup is measured, not
    claimed. Returns (fused_p50_ms, reference_pattern_p50_ms); either may be
    None if the group fails."""
    import tempfile

    from dmlcloud_tpu.utils.tcp import find_free_port

    repo = os.path.dirname(os.path.abspath(__file__))
    with tempfile.TemporaryDirectory() as td:
        script = os.path.join(td, "worker.py")
        with open(script, "w") as f:
            f.write(_METRICS_WORKER.format(repo=repo, epochs=epochs))
        port = find_free_port()
        procs = []
        for i in range(n_procs):
            env = dict(os.environ)
            env.update(
                {
                    "JAX_PLATFORMS": "cpu",
                    "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
                    "DMLCLOUD_TPU_COORDINATOR": f"localhost:{port}",
                    "DMLCLOUD_TPU_NUM_PROCESSES": str(n_procs),
                    "DMLCLOUD_TPU_PROCESS_ID": str(i),
                }
            )
            procs.append(
                subprocess.Popen(
                    [sys.executable, script], env=env,
                    stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
                )
            )
        p50 = ref_p50 = None
        try:
            for i, p in enumerate(procs):
                try:
                    out, _ = p.communicate(timeout=300)
                except subprocess.TimeoutExpired:
                    return None, None
                if p.returncode != 0:
                    return None, None
                if i == 0:
                    for line in out.splitlines():
                        if line.startswith("P50_MS "):
                            p50 = float(line.split()[1])
                        elif line.startswith("REF_P50_MS "):
                            ref_p50 = float(line.split()[1])
        finally:
            for q in procs:  # a failed rank must not orphan the rest in a barrier
                if q.poll() is None:
                    q.kill()
        return p50, ref_p50


#: Marker line of the --overlap-child results (CPU-only).
_OVERLAP_MARKER = "OVERLAP_BENCH_RESULTS "


def _overlap_config(engine_on: bool, steps: int, batch: int, ckpt_root: str) -> dict:
    """Two epochs of a small MLP regression through TrainingPipeline with the
    overlap engine fully on or fully off (async checkpoints + deferred
    metrics + double-buffered prefetch vs sync + eager + unbuffered), with
    mid-epoch step saves exercising the checkpoint path. Epoch 1 absorbs
    compile; the reported steps/sec and host-stall fraction come from epoch
    2's tracker metrics (misc/train_step_avg_ms, misc/host_stall_ms)."""
    rng = np.random.RandomState(0)
    xs = rng.randn(steps, batch, 64).astype(np.float32)
    w_true = rng.randn(64, 1).astype(np.float32)
    batches = [{"x": x, "y": x @ w_true} for x in xs]

    class OverlapStage(dml.TrainValStage):
        def pre_stage(self):
            import flax.linen as nn

            class MLP(nn.Module):
                @nn.compact
                def __call__(self, x):
                    return nn.Dense(1)(jax.nn.relu(nn.Dense(256)(x)))

            model = MLP()
            self.pipeline.register_model(
                "mlp", model, params=model.init(jax.random.PRNGKey(0), jnp.zeros((1, 64))),
                verbose=False,
            )
            self.pipeline.register_optimizer("sgd", optax.sgd(0.01))
            self.pipeline.register_dataset("train", batches, verbose=False)

        def step(self, state, batch):
            pred = state.apply_fn({"params": state.params}, batch["x"])
            return jnp.mean((pred - batch["y"]) ** 2)

        def val_epoch(self):  # train-only measurement
            pass

        # the three overlap-engine flags, flipped together
        def async_checkpoint(self):
            return engine_on

        def deferred_metrics(self):
            return engine_on

        def prefetch_depth(self):
            return 2 if engine_on else 0

        def checkpoint_every(self):
            return 0  # step saves only — epoch saves land outside the timed window

        def checkpoint_every_steps(self):
            return max(steps // 4, 1)

        def log_every(self):
            return 25

    # the engine-on run doubles as the goodput-receipt source: telemetry
    # arms the ledger (misc/goodput + bucket metrics) at negligible cost
    pipeline = dml.TrainingPipeline(
        name=f"bench-overlap-{'on' if engine_on else 'off'}", telemetry=engine_on
    )
    pipeline.append_stage(OverlapStage(), max_epochs=2)
    pipeline.enable_checkpointing(ckpt_root)
    pipeline.run()
    tracker = pipeline.tracker
    step_ms = float(tracker["misc/train_step_avg_ms"][-1])
    stall_ms = float(tracker["misc/host_stall_ms"][-1])
    epoch_ms = float(tracker["misc/epoch_time"][-1]) * 1e3
    pipeline.checkpoint_dir.close()
    out = {
        "steps_per_sec": round(1e3 / step_ms, 2),
        "host_stall_ms_per_epoch": round(stall_ms, 2),
        "host_stall_frac": round(stall_ms / max(epoch_ms, 1e-9), 4),
    }
    if engine_on:
        def _last(name, scale=1.0):
            if name in tracker and tracker[name] and tracker[name][-1] is not None:
                return round(float(tracker[name][-1]) * scale, 6)
            return None

        # first-class goodput breakdown (last epoch, seconds) — the receipt
        # fields BENCH_*.json tracks across rounds
        out["goodput"] = {
            "goodput_frac": _last("misc/goodput"),
            "data_wait_s": _last("misc/data_wait_ms", 1e-3),
            "ckpt_s": _last("misc/ckpt_ms", 1e-3),
            "compile_s": _last("misc/compile_ms", 1e-3) or 0.0,
        }
    return out


def overlap_child_main():
    """Runs in a fresh CPU-pinned process: the overlap engine A/B on the
    same workload, printed behind one marker line."""
    jax.config.update("jax_platforms", "cpu")
    import tempfile

    smoke = bool(os.environ.get("DML_BENCH_SMOKE"))
    steps, batch = (60, 16) if smoke else (240, 64)
    out = {"steps": steps, "batch": batch}
    with tempfile.TemporaryDirectory() as td:
        # engine OFF first so any in-process jit warm-up bias favors OFF,
        # making an ON win conservative rather than an artifact
        out["off"] = _overlap_config(False, steps, batch, os.path.join(td, "off"))
        out["on"] = _overlap_config(True, steps, batch, os.path.join(td, "on"))
    on, off = out["on"], out["off"]
    out["steps_per_sec_ratio_on_vs_off"] = round(on["steps_per_sec"] / off["steps_per_sec"], 4)
    print(_OVERLAP_MARKER + json.dumps(out), flush=True)


def bench_overlap(timeout_s: int = 900) -> dict | None:
    """Launch the overlap A/B in a CPU-pinned child (it must not touch the
    chip) and return its results dict, or None on failure."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--overlap-child"],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
    )
    try:
        out, _ = proc.communicate(timeout=timeout_s)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.communicate()
        return None
    for line in (out or "").splitlines():
        if line.startswith(_OVERLAP_MARKER):
            try:
                return json.loads(line[len(_OVERLAP_MARKER):])
            except ValueError:
                return None
    return None


#: Marker lines of the compile-bench (cold-start) results. The worker runs
#: ONE cold-or-warm measurement; the child orchestrates workers + ragged A/B.
_COMPILE_WORKER_MARKER = "COMPILE_WORKER_RESULTS "
_COMPILE_MARKER = "COMPILE_BENCH_RESULTS "


def compile_worker_main():
    """One time-to-first-step measurement in THIS process (the persistent
    compilation cache only proves itself across processes, so cold and warm
    each get a fresh interpreter): a 3x1024-hidden MLP TrainValStage with
    ``precompile=True`` and the compile cache at ``$DML_COMPILE_CACHE_DIR``.
    Prints one marker line of JSON."""
    jax.config.update("jax_platforms", "cpu")
    cache_dir = os.environ["DML_COMPILE_CACHE_DIR"]
    smoke = bool(os.environ.get("DML_BENCH_SMOKE"))
    steps, batch, hidden = (6, 16, 256) if smoke else (8, 32, 1024)

    rng = np.random.RandomState(0)
    w_true = rng.randn(64, 1).astype(np.float32)
    xs = rng.randn(steps, batch, 64).astype(np.float32)
    batches = [{"x": x, "y": x @ w_true} for x in xs]

    class CompileBenchStage(dml.TrainValStage):
        ttfs_mark = None

        def pre_stage(self):
            import flax.linen as nn

            class MLP(nn.Module):
                @nn.compact
                def __call__(self, x):
                    for _ in range(3):
                        x = jax.nn.relu(nn.Dense(hidden)(x))
                    return nn.Dense(1)(x)

            model = MLP()
            self.pipeline.register_model(
                "mlp", model, params=model.init(jax.random.PRNGKey(0), jnp.zeros((1, 64))),
                verbose=False,
            )
            self.pipeline.register_optimizer("adamw", optax.adamw(1e-3))
            self.pipeline.register_dataset("train", batches, verbose=False)

        def step(self, state, batch):
            pred = state.apply_fn({"params": state.params}, batch["x"])
            return jnp.mean((pred - batch["y"]) ** 2)

        def val_epoch(self):  # startup-tax measurement: train only
            pass

        def train_epoch(self):
            if self.ttfs_mark is None:
                orig, loss_name = self._train_step_fn, self.loss_metric_name()

                def first_step_marked(state, b):
                    out = orig(state, b)
                    if self.ttfs_mark is None:
                        self._stall.fetch(out[1][loss_name])  # completion sync
                        type(self).ttfs_mark = time.perf_counter()
                    return out

                self._train_step_fn = first_step_marked
            super().train_epoch()

    pipeline = dml.TrainingPipeline(
        name="bench-compile", compile_cache=cache_dir, precompile=True
    )
    stage = CompileBenchStage()
    pipeline.append_stage(stage, max_epochs=1)
    t0 = time.perf_counter()
    pipeline.run()
    total = time.perf_counter() - t0

    from dmlcloud_tpu.compile.cache import cache_stats

    stats = cache_stats()
    compile_ms = pipeline.tracker["misc/compile_ms"][0]
    out = {
        "time_to_first_step_s": round(CompileBenchStage.ttfs_mark - t0, 4),
        "precompile_ms": round(float(compile_ms), 1) if compile_ms is not None else None,
        "run_total_s": round(total, 4),
        "cache_entries": stats["entries"],
        "aot_hits": stats["aot_hits"],
        "aot_misses": stats["aot_misses"],
    }
    print(_COMPILE_WORKER_MARKER + json.dumps(out), flush=True)


def _run_compile_worker(cache_dir: str, timeout_s: int = 600) -> dict | None:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    # this A/B measures its OWN fresh directory cold, then warm: the launcher's
    # cache, which would otherwise win (compile/cache.py), is kept out of it
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env["DML_COMPILE_CACHE_DIR"] = cache_dir
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--compile-worker"],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
    )
    try:
        out, _ = proc.communicate(timeout=timeout_s)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.communicate()
        return None
    for line in (out or "").splitlines():
        if line.startswith(_COMPILE_WORKER_MARKER):
            try:
                return json.loads(line[len(_COMPILE_WORKER_MARKER):])
            except ValueError:
                return None
    return None


def _ragged_config(buckets, sizes, epochs=2) -> dict:
    """One ragged-batch run (in-process, CPU): linear regression over batches
    of the given sizes, precompiled, with or without shape buckets. Returns
    the compiled-signature count and the per-epoch mid-run compile count —
    bounded by len(buckets) with bucketing, growing with the distinct sizes
    without."""
    from dmlcloud_tpu.compile import masked_mean

    rng = np.random.RandomState(0)
    w_true = rng.randn(32, 1).astype(np.float32)
    batches = []
    for s in sizes:
        x = rng.randn(s, 32).astype(np.float32)
        batches.append({"x": x, "y": x @ w_true})

    class RaggedStage(dml.TrainValStage):
        def pre_stage(self):
            self.pipeline.register_model(
                "linear",
                apply_fn=lambda p, x: x @ p["w"],
                params={"w": jnp.zeros((32, 1))},
                verbose=False,
            )
            self.pipeline.register_optimizer("sgd", optax.sgd(0.05))
            self.pipeline.register_dataset("train", batches, verbose=False)

        def step(self, state, batch):
            pred = state.apply_fn(state.params, batch["x"])
            per_sample = jnp.sum((pred - batch["y"]) ** 2, axis=-1)
            if "sample_mask" in batch:
                return masked_mean(per_sample, batch["sample_mask"])
            return jnp.mean(per_sample)

        def val_epoch(self):
            pass

    pipeline = dml.TrainingPipeline(
        name=f"bench-ragged-{'buckets' if buckets else 'none'}",
        precompile=True,
        buckets=buckets,
    )
    stage = RaggedStage()
    pipeline.append_stage(stage, max_epochs=epochs)
    pipeline.run()
    return {
        "bucket_set": list(buckets) if buckets else None,
        "compiled_signatures": stage._train_compiled._cache_size(),
        "recompiles_per_epoch": [int(x) for x in pipeline.tracker["misc/recompiles"]],
    }


def compile_child_main():
    """The cold-start A/B, printed behind one marker line: (1) cold vs warm
    persistent-cache time-to-first-step, each in a fresh worker process
    sharing one cache dir; (2) ragged-batch signature growth with vs without
    shape buckets (in-process)."""
    jax.config.update("jax_platforms", "cpu")
    import tempfile

    out: dict = {}
    with tempfile.TemporaryDirectory() as td:
        cache_dir = os.path.join(td, "xla-cache")
        out["cold"] = _run_compile_worker(cache_dir)
        out["warm"] = _run_compile_worker(cache_dir)
    cold, warm = out.get("cold") or {}, out.get("warm") or {}
    if cold.get("time_to_first_step_s") and warm.get("time_to_first_step_s"):
        out["warm_vs_cold_ttfs_ratio"] = round(
            warm["time_to_first_step_s"] / cold["time_to_first_step_s"], 4
        )
    smoke = bool(os.environ.get("DML_BENCH_SMOKE"))
    sizes = (16, 16, 10, 6, 16, 3) if smoke else (64, 64, 40, 24, 64, 64, 12, 64)
    ragged_buckets = (8, 16) if smoke else (16, 32, 64)
    out["ragged"] = {
        "batch_sizes": list(sizes),
        "no_buckets": _ragged_config(None, sizes),
        "buckets": _ragged_config(ragged_buckets, sizes),
    }
    print(_COMPILE_MARKER + json.dumps(out), flush=True)


def bench_compile(timeout_s: int = 1200) -> dict | None:
    """Launch the cold-start A/B in a CPU-pinned child; returns its results
    dict, or None on failure."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--compile-child"],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
    )
    try:
        out, _ = proc.communicate(timeout=timeout_s)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.communicate()
        return None
    for line in (out or "").splitlines():
        if line.startswith(_COMPILE_MARKER):
            try:
                return json.loads(line[len(_COMPILE_MARKER):])
            except ValueError:
                return None
    return None


#: Marker line the --tpu-child prints its results behind. Everything else the
#: child writes (XLA chatter, per-batch skips) goes to stderr.
_CHILD_MARKER = "TPU_BENCH_RESULTS "

#: Hard cap on the device child. Generous: ~10 distinct programs compile
#: first, and the sub-benches together run several minutes (incl. the
#: speculative bench's short training runs and the 24L scale-up pair).
_CHILD_TIMEOUT_S = 2400


def _sweep_batches(candidates, run, name, score=lambda v: v):
    """Measure ``run(b)`` per candidate batch size; a candidate that raises
    (e.g. HBM exhaustion at the largest) is skipped with a stderr note.
    Returns ``(by_batch, best_b)`` with best picked by ``score``; raises
    only when every candidate failed."""
    by_batch = {}
    for b in candidates:
        try:
            by_batch[b] = run(b)
        except Exception as e:  # noqa: BLE001
            print(f"child: {name} bench failed at batch {b}: {type(e).__name__}: {e}", file=sys.stderr)
    if not by_batch:
        raise RuntimeError(f"{name} bench failed at every candidate batch size")
    return by_batch, max(by_batch, key=lambda b: score(by_batch[b]))


def child_main():
    """The device path: every bench that needs the chip, in this one process,
    then one marker line of JSON. No fallback — another platform than a TPU,
    or a sub-bench that raises, ends the child non-zero without results."""
    from dmlcloud_tpu.compile.cache import configure_cache

    configure_cache()  # before the first compile
    init_auto()
    device = jax.devices()[0]
    if device.platform != "tpu":
        raise SystemExit(f"child: the device path needs a TPU, jax found {device.platform!r} ({device.device_kind})")
    results: dict = {}

    def resnet():
        raw_by_batch, best_batch = _sweep_batches(
            BATCH_CANDIDATES,
            lambda b: bench_raw(synthetic_batch(np.random.RandomState(0), b)),
            "resnet raw",
        )
        fw = bench_framework(synthetic_batch(np.random.RandomState(0), best_batch))
        return {
            "raw_by_batch": {str(k): round(v, 2) for k, v in raw_by_batch.items()},
            "best_batch": best_batch,
            "raw_ips": raw_by_batch[best_batch],
            "fw_ips": fw["ips"],
            "time_to_first_step_s": fw["time_to_first_step_s"],
        }

    def lm():
        # batch is a free throughput parameter on one chip (same reasoning
        # as the ResNet sweep): take the fastest candidate as the headline
        by_batch, best_b = _sweep_batches(
            (8, 16, 32), lambda b: bench_lm(iters=15, b=b), "lm raw", score=lambda v: v[0]
        )
        tps, mfu = by_batch[best_b]
        fw = bench_lm_framework(b=best_b)
        return {
            "raw_tps": tps, "mfu": mfu, "fw_tps": fw["tps"], "batch_size": best_b,
            "time_to_first_step_s": fw["time_to_first_step_s"],
            "raw_tps_by_batch": {str(b): round(v[0], 1) for b, v in by_batch.items()},
        }

    def chunked():
        # chunked-loss at the SAME batch the headline LM number used, so the
        # ratio is batch-for-batch
        vocab_chunk = 4096
        return {"tps": bench_lm(b=results["lm"]["batch_size"], vocab_chunk=vocab_chunk)[0],
                "vocab_chunk": vocab_chunk}

    plan = [
        ("resnet", resnet),
        ("flash", lambda: list(bench_flash())),
        ("lm", lm),
        ("decode", lambda: list(bench_decode())),
        ("speculative", lambda: list(bench_speculative())),
        ("chunked_lm", chunked),
        ("lm_scale", bench_lm_scale),
    ]
    for name, fn in plan:
        results[name] = fn()
    results["peak_flops"] = chip_peak_flops()
    results["device_kind"] = device.device_kind
    print(_CHILD_MARKER + json.dumps(results), flush=True)


def _run_tpu_child() -> dict | None:
    """Start the device child ONCE and return its results, or None when it
    found no TPU, lost a sub-bench, or ran into the time cap — there is no
    second attempt and nothing stands in for a missing result."""
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--tpu-child"],
        stdout=subprocess.PIPE, text=True,
    )
    try:
        out, _ = proc.communicate(timeout=_CHILD_TIMEOUT_S)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.communicate()
        print(f"parent: the device child exceeded {_CHILD_TIMEOUT_S}s", file=sys.stderr)
        return None
    if proc.returncode != 0:
        print(f"parent: the device child exited with code {proc.returncode}", file=sys.stderr)
        return None
    for line in (out or "").splitlines():
        if line.startswith(_CHILD_MARKER):
            return json.loads(line[len(_CHILD_MARKER):])
    print("parent: the device child printed no results", file=sys.stderr)
    return None


def _rnd(x, digits):
    return round(x, digits) if x is not None else None


def main() -> int:
    # the device path first: with no chip, or a lost phase, this ends here
    # and no value is printed
    tpu = _run_tpu_child()
    if tpu is None:
        return 1
    # then the CPU-pinned children (they never need the chip)
    try:
        metrics_p50, metrics_ref_p50 = bench_metrics_allreduce()
    except Exception as e:  # noqa: BLE001
        print(f"parent: metrics-allreduce bench failed: {type(e).__name__}: {e}", file=sys.stderr)
        metrics_p50 = metrics_ref_p50 = None
    try:
        overlap = bench_overlap()
    except Exception as e:  # noqa: BLE001
        print(f"parent: overlap bench failed: {type(e).__name__}: {e}", file=sys.stderr)
        overlap = None
    try:
        compile_ab = bench_compile()
    except Exception as e:  # noqa: BLE001
        print(f"parent: compile bench failed: {type(e).__name__}: {e}", file=sys.stderr)
        compile_ab = None

    peak = tpu["peak_flops"]
    resnet, lm, lm_scale = tpu["resnet"], tpu["lm"], tpu["lm_scale"]
    raw_ips, fw_ips = resnet["raw_ips"], resnet["fw_ips"]
    flash, decode, spec, chunked = tpu["flash"], tpu["decode"], tpu["speculative"], tpu["chunked_lm"]
    chunked_tps = chunked["tps"]
    extras = {
                    "value_source": "framework",
                    "raw_images_per_sec": _rnd(raw_ips, 2),
                    "batch_size": resnet.get("best_batch"),
                    "raw_images_per_sec_by_batch": resnet.get("raw_by_batch"),
                    "mfu": _rnd(fw_ips * TRAIN_FLOPS_PER_IMAGE / peak, 4),
                    "raw_mfu": _rnd(raw_ips * TRAIN_FLOPS_PER_IMAGE / peak, 4),
                    "flash_attn_tokens_per_sec_s8k": _rnd(flash[0], 1),
                    "flash_attn_speedup_vs_unfused_s8k": _rnd(flash[1], 3),
                    "flash_attn_window1k_speedup_vs_full_s8k": _rnd(flash[2], 3),
                    "flash_attn_fwdbwd_speedup_vs_unfused_s8k": _rnd(flash[3], 3),
                    "lm_train_tokens_per_sec_12l_768d_s1k": _rnd(lm.get("raw_tps"), 1),
                    "lm_train_batch_size": lm.get("batch_size"),
                    "lm_train_tokens_per_sec_by_batch": lm.get("raw_tps_by_batch"),
                    "lm_train_mfu": _rnd(lm.get("mfu"), 4),
                    "lm_framework_tokens_per_sec": _rnd(lm.get("fw_tps"), 1),
                    "lm_framework_time_to_first_step_s": _rnd(lm.get("time_to_first_step_s"), 3),
                    "lm_vs_baseline": _rnd(
                        lm["fw_tps"] / lm["raw_tps"] if lm.get("fw_tps") and lm.get("raw_tps") else None, 4
                    ),
                    "decode_tokens_per_sec_b8_p128_n512": _rnd(decode[0], 1),
                    "decode_tokens_per_sec_b8_p128_n512_int8_weights": _rnd(decode[1], 1),
                    "decode_int8_speedup": _rnd(
                        decode[1] / decode[0] if decode[0] and decode[1] else None, 3
                    ),
                    "spec_decode_plain_tokens_per_sec_b8_p64_n256": _rnd(spec[0], 1),
                    "spec_decode_tokens_per_sec_b8_p64_n256": _rnd(spec[1], 1),
                    "spec_decode_speedup_vs_plain": _rnd(
                        spec[1] / spec[0] if spec[0] and spec[1] else None, 3
                    ),
                    "spec_decode_accept_rate": _rnd(spec[2], 4),
                    "spec_decode_k": spec[3],
                    # learnedness gate: the accept rate is only meaningful
                    # with both losses near the corpus's ~0.9-nat floor
                    "spec_decode_train_loss_target": _rnd(spec[4], 3),
                    "spec_decode_train_loss_draft": _rnd(spec[5], 3),
                    "lm_train_tokens_per_sec_24l_1024d_s1k": _rnd(lm_scale.get("tps"), 1),
                    "lm_train_mfu_24l_1024d": _rnd(lm_scale.get("mfu"), 4),
                    "lm_train_tokens_per_sec_24l_1024d_s1k_remat": _rnd(lm_scale.get("tps_remat"), 1),
                    "lm_train_mfu_24l_1024d_remat": _rnd(lm_scale.get("mfu_remat"), 4),
                    "metrics_allreduce_p50_ms_8proc_12metrics": _rnd(metrics_p50, 3),
                    "metrics_allreduce_p50_ms_8proc_12metrics_reference_pattern": _rnd(
                        metrics_ref_p50, 3
                    ),
                    "metrics_exchange_speedup_vs_reference_pattern": _rnd(
                        metrics_ref_p50 / metrics_p50 if metrics_p50 and metrics_ref_p50 else None, 2
                    ),
                    # NOT an ICI latency: this environment has one chip, so the
                    # exchange is measured across coordinated host processes
                    "metrics_allreduce_measurement_env": (
                        "8 coordinated CPU processes, one host (loopback gRPC/gloo); "
                        "TPU-pod ICI unavailable in this single-chip environment"
                    ),
                    "platform": "tpu",
                    "device_kind": tpu["device_kind"],
    }
    extras[f"lm_train_tokens_per_sec_chunked_loss_c{chunked['vocab_chunk']}"] = _rnd(chunked_tps, 1)
    extras["chunked_loss_vocab_chunk"] = chunked["vocab_chunk"]
    extras["chunked_loss_ratio_vs_full"] = _rnd(
        chunked_tps / lm["raw_tps"] if chunked_tps and lm.get("raw_tps") else None, 4
    )
    if compile_ab is not None:
        cold, warm = compile_ab.get("cold") or {}, compile_ab.get("warm") or {}
        ragged = compile_ab.get("ragged") or {}
        nb, wb = ragged.get("no_buckets") or {}, ragged.get("buckets") or {}
        extras.update(
            {
                "compile_cold_time_to_first_step_s": cold.get("time_to_first_step_s"),
                "compile_warm_time_to_first_step_s": warm.get("time_to_first_step_s"),
                "compile_warm_vs_cold_ttfs_ratio": compile_ab.get("warm_vs_cold_ttfs_ratio"),
                "ragged_signatures_no_buckets": nb.get("compiled_signatures"),
                "ragged_signatures_with_buckets": wb.get("compiled_signatures"),
                "ragged_recompiles_per_epoch_no_buckets": nb.get("recompiles_per_epoch"),
                "ragged_recompiles_per_epoch_with_buckets": wb.get("recompiles_per_epoch"),
                "compile_bench_env": (
                    "CPU child processes; cold/warm share one fresh persistent-cache "
                    "dir, each measured in its own interpreter; ragged A/B in-process "
                    "with precompile=True"
                ),
            }
        )
    if overlap is not None:
        on, off = overlap.get("on") or {}, overlap.get("off") or {}
        extras.update(
            {
                "overlap_engine_steps_per_sec_on": on.get("steps_per_sec"),
                "overlap_engine_steps_per_sec_off": off.get("steps_per_sec"),
                "overlap_engine_speedup_on_vs_off": overlap.get("steps_per_sec_ratio_on_vs_off"),
                "overlap_engine_host_stall_frac_on": on.get("host_stall_frac"),
                "overlap_engine_host_stall_frac_off": off.get("host_stall_frac"),
                "overlap_engine_host_stall_ms_on": on.get("host_stall_ms_per_epoch"),
                "overlap_engine_host_stall_ms_off": off.get("host_stall_ms_per_epoch"),
                "overlap_engine_env": (
                    f"CPU child process, MLP {overlap.get('steps')} steps x batch "
                    f"{overlap.get('batch')}, mid-epoch step saves; "
                    "async_checkpoint+deferred_metrics+prefetch_depth=2 vs all off"
                ),
            }
        )
    # first-class goodput breakdown (telemetry ledger of the engine-on
    # overlap run — a CPU child's)
    goodput = (overlap or {}).get("on", {}).get("goodput") or {}
    print(
        json.dumps(
            {
                "metric": "resnet50_images_per_sec_per_chip",
                "value": _rnd(fw_ips, 2),
                "unit": "images/s",
                # first-class: the startup tax (framework ResNet path, run()
                # entry -> first step executed), tracked across receipts
                "time_to_first_step_s": _rnd(resnet.get("time_to_first_step_s"), 3),
                "goodput_frac": goodput.get("goodput_frac"),
                "data_wait_s": goodput.get("data_wait_s"),
                "ckpt_s": goodput.get("ckpt_s"),
                "compile_s": goodput.get("compile_s"),
                "vs_baseline": _rnd(fw_ips / raw_ips, 4),
                "extras": extras,
            }
        )
    )
    return 0


if __name__ == "__main__":
    if "--tpu-child" in sys.argv[1:]:
        child_main()
    elif "--overlap-child" in sys.argv[1:]:
        overlap_child_main()
    elif "--compile-child" in sys.argv[1:]:
        compile_child_main()
    elif "--compile-worker" in sys.argv[1:]:
        compile_worker_main()
    elif "--kernels-child" in sys.argv[1:]:
        kernels_child_main()
    elif "--elastic-child" in sys.argv[1:]:
        elastic_child_main()
    elif "--serve-child" in sys.argv[1:]:
        serve_child_main()
    elif "--obs-child" in sys.argv[1:]:
        obs_child_main()
    elif "--data-child" in sys.argv[1:]:
        data_child_main()
    elif "--train-quant-child" in sys.argv[1:]:
        train_quant_child_main()
    elif "--gate" in sys.argv[1:]:
        sys.exit(gate_main(sys.argv[1:]))
    else:
        sys.exit(main())
