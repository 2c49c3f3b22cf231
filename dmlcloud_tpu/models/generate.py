"""Autoregressive text generation for DecoderLM — the inference half the
training stack feeds into (the reference ships no inference path at all;
this is TPU-side scope).

TPU-first shape of the problem:

- The KV cache is a static-shape pytree ([B, max_len, KH, D] per layer,
  bf16); every decode step writes one slot with ``dynamic_update_slice``.
  Attention reads only a STATIC prefix of the buffer (``attend_len``),
  grown chunk-by-chunk as the cache fills, so per-token attention cost
  scales with the filled length instead of max_len — while every shape
  stays static.
- Generation is ONE jitted program: prefill over the (padded) prompt, then
  a short chain of ``lax.scan`` segments (one per attend-length chunk,
  at most ``_DECODE_CHUNKS``). No per-token Python dispatch; the only
  host transfer is the final token matrix.
- Sampling is functional: greedy at ``temperature=0``, otherwise
  temperature softmax with optional top-k and nucleus (top-p) truncation,
  PRNG folded per step.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from .transformer import DecoderLM, TransformerConfig


#: Max number of scan segments in a chunked decode: bounds trace/compile
#: size (each segment is one scan body) while the growing attend_len keeps
#: attention work proportional to fill.
_DECODE_CHUNKS = 8


def init_cache(cfg: TransformerConfig, batch_size: int, max_len: int | None = None, dtype=jnp.bfloat16):
    """Zeroed KV cache pytree: ``{layer_i: {k, v: [B, S, KH, D]}}``."""
    cfg.require_attention_only("a KV cache")
    s = max_len or cfg.max_seq_len
    shape = (batch_size, s, cfg.kv_heads, cfg.head_dim)
    return {
        f"layer_{i}": {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
        for i in range(cfg.num_layers)
    }


def rewind_cache(cache, fill_len):
    """Rewind a KV cache to ``fill_len`` valid positions: slots at
    ``position >= fill_len`` are zeroed in ONE masked select over the tree —
    the single rewind primitive speculative decoding needs to discard a
    rejected draft tail (instead of k per-slot re-dispatches), and the only
    way to make a cache that speculated past ``fill_len`` bit-identical to
    one that never did. ``fill_len`` may be traced ([B] per-row or scalar);
    the masked positions never influence attention (the causal/attend_len
    masks already exclude them), so rewinding is semantically free — it
    matters when caches are compared, checkpointed, or handed to a consumer
    that trusts the whole buffer."""
    fill_len = jnp.asarray(fill_len, jnp.int32)

    def mask_leaf(x):  # x: [B, S, KH, D]
        pos = jnp.arange(x.shape[1], dtype=jnp.int32)
        keep = pos[None, :] < jnp.reshape(fill_len, (-1, 1))  # [B or 1, S]
        return jnp.where(keep[:, :, None, None], x, jnp.zeros((), x.dtype))

    return jax.tree_util.tree_map(mask_leaf, cache)


def _chunked_scan(step, carry, first_step, n_total, attend_len_for_end):
    """Run ``step(carry, i, attend_len=...)`` over steps
    [first_step, first_step + n_total) as at most ``_DECODE_CHUNKS``
    ``lax.scan`` segments; segment covering steps < end gets the static
    ``attend_len_for_end(end)``. The single source of truth for decode
    chunking — greedy/sampling and beam search share it (their index bases
    differ by one, hence the callback). Returns (carry, per-segment ys)."""
    chunk = -(-n_total // _DECODE_CHUNKS) if n_total else 1
    ys = []
    for start in range(first_step, first_step + n_total, chunk):
        end = min(start + chunk, first_step + n_total)
        seg_step = functools.partial(step, attend_len=attend_len_for_end(end))
        carry, y = jax.lax.scan(seg_step, carry, jnp.arange(start, end))
        ys.append(y)
    return carry, ys


def decode_step(
    model: DecoderLM, params, tokens, cache, *, offset=0, pad_len=None, attend_len=None,
    pages=None, adapters=None, return_hidden=False,
):
    """THE cache-step primitive: one model application that writes
    ``tokens``' K/V into ``cache`` and returns ``(logits, new_cache)``.

    Every decode path — :func:`generate`, :func:`beam_search`,
    ``speculative_generate`` and the continuous-batching serving engine
    (``dmlcloud_tpu.serve``) — funnels its cache-carrying model calls
    through this one function, so the cache write/attend convention (write
    the slot BEFORE attention reads it, causal mask over the filled
    prefix) cannot drift between them: a numerics change lands in all four
    at once or not at all.

    ``cache`` is either the dense ``init_cache`` tree stepped at the
    scalar ``offset`` (with optional ``pad_len`` ragged-prompt positions
    and ``attend_len`` bounded reads), or the serving engine's pool pages
    stepped via ``pages=(block_tables, fill)``; ``adapters`` threads
    per-row LoRA deltas for multi-tenant serving (``serve.AdapterSet``).
    ``return_hidden=True`` returns ``((logits, hidden), new_cache)`` — the
    Medusa serving path reads the final hidden states for its extra decode
    heads out of the SAME forward that produced the base logits."""
    return model.apply(
        {"params": params}, tokens, cache=cache, offset=offset, pad_len=pad_len,
        attend_len=attend_len, pages=pages, adapters=adapters, return_hidden=return_hidden,
    )


@jax.named_scope("sampling")
def sample_logits(logits, rng, temperature: float, top_k: int, top_p: float):
    """logits: [B, V] fp32 -> tokens [B] int32."""
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits / temperature
    if top_k > 0:
        kth = jax.lax.top_k(logits, top_k)[0][..., -1:]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if top_p < 1.0:
        # nucleus: keep the smallest prefix of the sorted distribution whose
        # mass reaches top_p (the first token always stays)
        sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
        csum = jnp.cumsum(jax.nn.softmax(sorted_logits, axis=-1), axis=-1)
        # first index reaching p; clamped so a cumsum that never reaches
        # top_p (rounding near 1.0) keeps everything EXPLICITLY instead of
        # via take_along_axis's implicit clip-at-bounds indexing
        cutoff_idx = jnp.minimum(
            jnp.sum(csum < top_p, axis=-1, keepdims=True), logits.shape[-1] - 1
        )
        cutoff = jnp.take_along_axis(sorted_logits, cutoff_idx, axis=-1)
        logits = jnp.where(logits < cutoff, -jnp.inf, logits)
    return jax.random.categorical(rng, logits, axis=-1).astype(jnp.int32)


@jax.named_scope("sampling")
def _truncate_scaled(logits, temperature, top_k, top_p):
    """Per-row temperature/top-k/nucleus truncation with TRACED params.

    ``logits`` is ``[B, V]`` or ``[B, T, V]``; ``temperature``/``top_k``/
    ``top_p`` are ``[B]`` arrays (one value per row — the serving engine's
    mixed-tenant case). Returns logits scaled and masked so their softmax
    IS each row's sampling distribution, applying the SAME ops in the SAME
    order as :func:`sample_logits` (scale, then top-k mask, then nucleus
    mask) so a batch whose rows share one parameter set truncates
    bit-identically to the scalar path. Rows with ``temperature == 0`` are
    left at scale 1 (their caller takes the argmax; the division must
    merely stay finite), ``top_k <= 0`` / ``top_p >= 1`` disable the
    respective mask per row — every knob is data, nothing recompiles."""
    v = logits.shape[-1]
    extra = logits.ndim - 2  # 0 for [B, V], 1 for [B, T, V]
    bshape = (-1,) + (1,) * (extra + 1)
    temperature = jnp.reshape(temperature, bshape)
    top_k = jnp.reshape(top_k, bshape)
    top_p = jnp.reshape(top_p, bshape)
    x = logits / jnp.where(temperature > 0, temperature, 1.0)
    # top-k: the row's k-th largest value is the cut (k clamped into [1, V]
    # so the disabled rows still index validly; their mask is dropped)
    sorted_desc = jnp.flip(jnp.sort(x, axis=-1), axis=-1)
    kth = jnp.take_along_axis(
        sorted_desc, jnp.clip(top_k, 1, v) - 1, axis=-1
    )
    x = jnp.where((top_k > 0) & (x < kth), -jnp.inf, x)
    # nucleus: smallest prefix of the sorted distribution reaching top_p
    # (sample_logits' clamp semantics — the first token always survives)
    sx = jnp.flip(jnp.sort(x, axis=-1), axis=-1)
    csum = jnp.cumsum(jax.nn.softmax(sx, axis=-1), axis=-1)
    cutoff_idx = jnp.minimum(
        jnp.sum(csum < top_p, axis=-1, keepdims=True), v - 1
    )
    cutoff = jnp.take_along_axis(sx, cutoff_idx, axis=-1)
    return jnp.where((top_p < 1.0) & (x < cutoff), -jnp.inf, x)


@jax.named_scope("sampling")
def sample_logits_batched(logits, rng, temperature, top_k, top_p):
    """Per-row traced twin of :func:`sample_logits`: ``logits`` is
    ``[B, V]`` fp32, the sampling params are ``[B]`` arrays so ONE
    compiled program serves mixed greedy/sampled tenants (the serving
    engine's batched-sampling contract). Rows with ``temperature == 0``
    return the exact argmax — bit-identical to the scalar greedy path —
    and a batch whose rows all carry one parameter set samples the same
    tokens as ``sample_logits`` with those scalars (same rng, same masked
    logits, same categorical)."""
    temperature = jnp.asarray(temperature, jnp.float32)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    x = _truncate_scaled(logits, temperature, top_k, top_p)
    sampled = jax.random.categorical(rng, x, axis=-1).astype(jnp.int32)
    return jnp.where(temperature > 0, sampled, greedy)


@functools.partial(
    jax.jit,
    static_argnames=("model", "max_new_tokens", "temperature", "top_k", "top_p", "eos_id", "pad_id"),
)
def _generate_compiled(
    model: DecoderLM,
    params,
    prompt: jnp.ndarray,
    pad_len: jnp.ndarray | None,
    rng: jax.Array,
    max_new_tokens: int,
    temperature: float,
    top_k: int,
    top_p: float,
    eos_id: int,
    pad_id: int,
):
    b, t = prompt.shape
    # int8 weight-only kernels (models/quant.py) stay quantized END TO END:
    # the quant-aware dense layers feed them to the matmul with the
    # per-channel scales applied to the fp32 accumulator — q * scale is
    # never materialised. Only exotically-quantized non-kernel leaves
    # rehydrate here. Off-TPU, the int8 -> fp32 GEMM-operand widen is
    # hoisted out of the decode loop (once per call, not once per step —
    # see widen_quant_tree); on TPU q stays int8 into the MXU.
    from .quant import dequant_tree, widen_quant_tree

    params = dequant_tree(params, model.cfg.dtype, keep=lambda p: p.endswith("kernel"))
    if jax.default_backend() != "tpu":
        params = widen_quant_tree(params)
    # cache in the model's compute dtype so fp32 configs stay exact
    cache = init_cache(model.cfg, b, t + max_new_tokens, dtype=model.cfg.dtype)

    # Prefill: one pass over the whole prompt fills cache slots [0, t).
    # Left padding means every row's LAST slot is real, so sampling reads
    # logits[:, -1] and decode write offsets stay uniform across rows.
    # attend_len=t: the empty generation tail is never read.
    logits, cache = decode_step(
        model, params, prompt, cache, offset=0, pad_len=pad_len, attend_len=t
    )
    last = logits[:, -1]  # [B, V]

    def sample_next(prev_logits, rng, done):
        tok = sample_logits(prev_logits, rng, temperature, top_k, top_p)
        tok = jnp.where(done, pad_id, tok)
        return tok, done | (tok == eos_id)

    def step(carry, i, attend_len):
        cache, prev_logits, rng, done = carry
        rng, sub = jax.random.split(rng)
        tok, done = sample_next(prev_logits, sub, done)
        logits, cache = decode_step(
            model, params, tok[:, None], cache, offset=t + i, pad_len=pad_len,
            attend_len=attend_len,
        )
        return (cache, logits[:, 0], rng, done), tok

    # N-1 decode steps as a chain of scans (the Nth token needs only a
    # sample, not another forward pass): each scan segment attends over a
    # statically-bounded prefix that grows with the fill, so attention work
    # totals O(N * (t + N/2)) instead of O(N * (t + N)).
    # step i writes slot t + i, so the segment ending at `end` needs t + end.
    carry = (cache, last, rng, jnp.zeros((b,), bool))
    carry, chunks = _chunked_scan(step, carry, 0, max_new_tokens - 1, lambda end: t + end)
    cache, last, rng, done = carry
    final_tok, _ = sample_next(last, jax.random.split(rng)[1], done)
    tokens = jnp.concatenate(chunks + [final_tok[None]], axis=0)
    return tokens.T  # [B, max_new_tokens]


def _pad_len_from_mask(prompt_mask, b: int, t: int):
    """[B, T] {0,1} LEFT-pad keep-mask -> per-row pad counts [B] int32
    (None passthrough). Concrete masks are validated eagerly — a
    right-padded mask would silently generate garbage."""
    if prompt_mask is None:
        return None
    import numpy as np

    if jnp.shape(prompt_mask) != (b, t):
        raise ValueError(f"prompt_mask must be [B, T] == {(b, t)}, got {jnp.shape(prompt_mask)}")
    if not isinstance(prompt_mask, jax.core.Tracer):
        host = np.asarray(prompt_mask).astype(np.int32)
        if not (np.diff(host, axis=1) >= 0).all():
            raise ValueError("prompt_mask must be LEFT padding: zeros then ones per row")
    prompt_mask = jnp.asarray(prompt_mask, jnp.int32)
    return (t - jnp.sum(prompt_mask, axis=1)).astype(jnp.int32)


def _check_len(model: DecoderLM, t: int, max_new_tokens: int) -> None:
    if t + max_new_tokens > model.cfg.max_seq_len:
        raise ValueError(
            f"prompt ({t}) + max_new_tokens ({max_new_tokens}) exceeds max_seq_len ({model.cfg.max_seq_len})"
        )


def generate(
    model: DecoderLM,
    params: Any,
    prompt: jnp.ndarray,
    max_new_tokens: int = 32,
    *,
    temperature: float = 0.0,
    top_k: int = 0,
    top_p: float = 1.0,
    rng: jax.Array | None = None,
    eos_id: int = -1,
    pad_id: int = 0,
    prompt_mask: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Generate ``max_new_tokens`` continuations of ``prompt`` [B, T] int32.
    Greedy when ``temperature == 0``; otherwise temperature sampling with
    optional ``top_k`` / nucleus ``top_p`` truncation. Rows that emit
    ``eos_id`` keep emitting ``pad_id``. Returns [B, max_new_tokens] int32.

    Ragged prompts: LEFT-pad them to a common length and pass
    ``prompt_mask`` ([B, T] {0,1}, zeros first) — pad slots are masked out
    of attention and rotary positions count from each row's first real
    token, so every row decodes exactly as it would unpadded.

    The whole generation — prefill + scan over decode steps — is one
    compiled program; recompiles happen only when shapes or the static
    knobs change.
    """
    prompt = jnp.asarray(prompt, jnp.int32)
    b, t = prompt.shape
    _check_len(model, t, max_new_tokens)
    pad_len = _pad_len_from_mask(prompt_mask, b, t)
    if rng is None:
        rng = jax.random.PRNGKey(0)
    return _generate_compiled(
        model, params, prompt, pad_len, rng,
        int(max_new_tokens), float(temperature), int(top_k), float(top_p), int(eos_id), int(pad_id),
    )


@functools.partial(
    jax.jit, static_argnames=("model", "max_new_tokens", "num_beams", "eos_id", "pad_id")
)
def _beam_search_compiled(
    model: DecoderLM,
    params,
    prompt: jnp.ndarray,
    pad_len: jnp.ndarray | None,
    length_penalty: jnp.ndarray,
    max_new_tokens: int,
    num_beams: int,
    eos_id: int,
    pad_id: int,
):
    b, t = prompt.shape
    k = num_beams
    v = model.cfg.vocab_size
    neg = jnp.float32(-1e30)

    # int8 weight-only kernels stay quantized in-program, with the off-TPU
    # operand widen hoisted out of the beam loop (see _generate_compiled)
    from .quant import dequant_tree, widen_quant_tree

    params = dequant_tree(params, model.cfg.dtype, keep=lambda p: p.endswith("kernel"))
    if jax.default_backend() != "tpu":
        params = widen_quant_tree(params)
    # Prefill once per batch row, then tile the cache across beams.
    cache = init_cache(model.cfg, b, t + max_new_tokens, dtype=model.cfg.dtype)
    logits, cache = decode_step(
        model, params, prompt, cache, offset=0, pad_len=pad_len, attend_len=t
    )
    cache = jax.tree_util.tree_map(lambda x: jnp.repeat(x, k, axis=0), cache)  # [B*K, ...]
    pad_len_k = None if pad_len is None else jnp.repeat(pad_len, k, axis=0)  # beam-tiled
    first_lp = jax.nn.log_softmax(logits[:, -1].astype(jnp.float32))  # [B, V]

    # Step 0: the K best first tokens seed the beams.
    scores, tok = jax.lax.top_k(first_lp, k)  # [B, K]
    finished = tok == eos_id
    tokens = jnp.full((b, k, max_new_tokens), pad_id, jnp.int32)
    tokens = tokens.at[:, :, 0].set(tok)
    lengths = jnp.ones((b, k), jnp.int32)  # emitted tokens incl. eos

    def step(carry, i, attend_len):
        cache, tokens, scores, lengths, finished, last_tok = carry
        # last_tok was emitted at position t + i - 1; its K/V lands there
        logits, cache = decode_step(
            model, params, last_tok.reshape(b * k, 1), cache, offset=t + i - 1,
            pad_len=pad_len_k, attend_len=attend_len,
        )
        lp = jax.nn.log_softmax(logits[:, 0].astype(jnp.float32)).reshape(b, k, v)
        # finished beams may only extend with pad at no cost; everything else
        # is impossible, so a finished beam's score freezes
        pad_only = jnp.full((v,), neg).at[pad_id].set(0.0)
        lp = jnp.where(finished[..., None], pad_only[None, None], lp)

        cand = scores[..., None] + lp  # [B, K, V]
        scores, flat_idx = jax.lax.top_k(cand.reshape(b, k * v), k)  # [B, K]
        beam_idx = flat_idx // v  # which parent beam
        tok = (flat_idx % v).astype(jnp.int32)

        # reorder per-beam state to follow the winning parents. Only the
        # FILLED cache prefix needs the gather — unwritten tail slots are
        # zeros on every beam, so reordering them would move identical data
        def reorder_prefix(x):
            pre = jax.lax.slice_in_dim(x, 0, attend_len, axis=1)
            pre = jnp.take_along_axis(
                pre.reshape(b, k, *pre.shape[1:]),
                beam_idx.reshape(b, k, *([1] * (x.ndim - 1))),
                axis=1,
            ).reshape(b * k, *pre.shape[1:])
            return jax.lax.dynamic_update_slice_in_dim(x, pre, 0, axis=1)

        take = lambda x: jnp.take_along_axis(x, beam_idx, axis=1)
        tokens = jnp.take_along_axis(tokens, beam_idx[..., None], axis=1)
        lengths, finished = take(lengths), take(finished)
        cache = jax.tree_util.tree_map(reorder_prefix, cache)

        tokens = tokens.at[:, :, i].set(tok)
        lengths = jnp.where(finished, lengths, lengths + 1)
        finished = finished | (tok == eos_id)
        return (cache, tokens, scores, lengths, finished, tok), None

    # chunked like generate(): each scan segment attends over (and gathers)
    # a statically-bounded prefix that grows with the fill. Beam step i
    # writes slot t + i - 1, so the segment ending at `end` needs t + end - 1.
    carry = (cache, tokens, scores, lengths, finished, tok)
    carry, _ = _chunked_scan(step, carry, 1, max_new_tokens - 1, lambda end: t + end - 1)
    (cache, tokens, scores, lengths, finished, _) = carry

    # pick each row's best beam under GNMT-style length normalisation
    norm = scores / (lengths.astype(jnp.float32) ** length_penalty)
    best = jnp.argmax(norm, axis=1)  # [B]
    best_tokens = jnp.take_along_axis(tokens, best[:, None, None], axis=1)[:, 0]
    best_scores = jnp.take_along_axis(norm, best[:, None], axis=1)[:, 0]
    return best_tokens, best_scores


def beam_search(
    model: DecoderLM,
    params: Any,
    prompt: jnp.ndarray,
    max_new_tokens: int = 32,
    *,
    num_beams: int = 4,
    length_penalty: float = 1.0,
    eos_id: int = -1,
    pad_id: int = 0,
    prompt_mask: jnp.ndarray | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Beam-search decoding: returns ``(tokens [B, max_new_tokens],
    scores [B])`` where scores are length-normalised sequence log-probs
    (``sum logp / len**length_penalty``). Beams that emit ``eos_id`` freeze
    and pad. Like :func:`generate`, the whole search — prefill, scan, beam
    reordering (cache gathered along the beam axis) — is ONE compiled
    program. Ragged prompts work like :func:`generate`: LEFT-pad and pass
    ``prompt_mask``."""
    prompt = jnp.asarray(prompt, jnp.int32)
    b, t = prompt.shape
    _check_len(model, t, max_new_tokens)
    if num_beams < 1:
        raise ValueError(f"num_beams must be >= 1, got {num_beams}")
    if num_beams > model.cfg.vocab_size:
        raise ValueError("num_beams cannot exceed vocab_size")
    if not 0 <= pad_id < model.cfg.vocab_size:
        # pad_id is a scatter index into the finished-beam cost vector; an
        # out-of-range value would silently corrupt eos handling under jit
        raise ValueError(f"pad_id must be in [0, vocab_size), got {pad_id}")
    pad_len = _pad_len_from_mask(prompt_mask, b, t)
    # length_penalty rides as a traced operand: sweeping it must not
    # recompile the whole search
    return _beam_search_compiled(
        model, params, prompt, pad_len, jnp.float32(length_penalty), int(max_new_tokens),
        int(num_beams), int(eos_id), int(pad_id),
    )
