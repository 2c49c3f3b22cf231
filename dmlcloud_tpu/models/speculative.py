"""Speculative decoding: exact greedy OR exact sampled generation, fewer
target passes.

A small draft model proposes ``k`` tokens autoregressively; the target
model verifies all of them in ONE forward pass (k+1 positions). At
temperature 0 it accepts the longest matching prefix plus its own
correction token; at temperature > 0 it runs the rejection-sampling
acceptance rule (accept with ``min(1, p_t/p_d)``, resample rejections
from the residual), which preserves the target's sampling distribution
exactly. Either way the draft changes the cost, never the result: greedy
output matches ``generate(target, ...)`` token for token, sampled output
is statistically indistinguishable from target-only sampling (both
asserted in tests). One caveat: the verify pass batches k+1 positions
where plain decode runs one, so a bf16 near-tie between two logits can
reduce in a different order and flip an argmax; exact-arithmetic (fp32)
configs are bitwise-identical. Decode cost per accepted token drops from one full
weight-stream of the target to ``~1/(n_accept+1)`` of one, plus k+1 cheap
draft passes (the wall-clock gain on the weight-bandwidth-bound decode
path is not measured on the chip). (The reference has no inference
path at all; this composes with the int8 weight-only quantization in
``models/quant.py`` — pass quantized trees for either model.)

TPU-first mechanics (everything static-shape, one compiled program):

- One ``lax.while_loop`` over verification rounds, with the whole
  accept/rollback decision ON DEVICE — no host round-trips anywhere in
  the loop. Each round runs exactly ``k`` draft passes (unrolled — ``k``
  is static) and one (k+1)-token target pass at a DYNAMIC cache offset
  (the transformer's decode path already supports traced offsets).
- The FIRST draft pass of a round processes two tokens
  ``[y[pos-2], y[pos-1]]`` at offset ``pos-2``: when the previous round
  accepted all ``k`` proposals, the draft cache has a one-slot gap at the
  bonus token's position — the 2-token pass fills it, which is what lets
  the round run ``k`` draft passes instead of the k+1 the pre-PR-6 loop
  paid (the old (k+1)-th pass existed only to write that slot every
  round). In every other case the extra slot is an identical rewrite.
- Rejected proposals leave stale K/V in both caches, but every round
  writes the contiguous range starting at its own offset, and the next
  round's offset never exceeds the previous offset + accepted + 1 — so
  stale slots are always overwritten (in-pass, before attention reads
  them) before the causal mask can expose them. ``return_cache=True``
  additionally applies :func:`~dmlcloud_tpu.models.generate.rewind_cache`
  ONCE after the loop — one masked select discarding the whole stale
  tail, instead of per-slot re-dispatches — so the returned caches are
  bit-identical to a non-speculative decode of the same accepted prefix.
- Batching: the B=1 routine is ``vmap``-ed over rows (per-row dynamic
  offsets come for free); under vmap the while_loop keeps running until
  every row finishes. Only the CHEAP carry leaves (pos/y/done/counters)
  are done-masked: a finished row's cache writes keep landing at its
  frozen ``pos`` with frozen inputs — idempotent, never read back into
  ``y`` — so the loop avoids two whole-cache selects per round.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from .transformer import DecoderLM

__all__ = ["speculative_generate", "verify_proposals", "init_medusa_heads", "medusa_head_logits"]


def _greedy(logits):
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


def verify_proposals(tlogits, dlogits, proposals, rng, temperature, top_k, top_p, eos_id):
    """The batched accept rule — one verification round for ``B`` rows
    with PER-ROW sampling params (the serving engine's spec-decode step;
    the single-row loop above is the same math specialised to B=1 and one
    static greedy/sampled switch).

    ``tlogits`` is the target's ``[B, k+1, V]`` verification logits over
    ``[y_last, d_1..d_k]``; ``dlogits`` is ``[B, k, V]`` — row ``i`` is
    the TRUNCATED, SCALED draft distribution ``d_{i+1}`` was sampled from
    (``generate._truncate_scaled`` output; for greedy rows the values are
    never read); ``proposals`` is ``[B, k]``; ``temperature``/``top_k``/
    ``top_p``/``eos_id`` are ``[B]`` traced arrays. Rows with
    ``temperature == 0`` take the greedy rule (longest matching prefix +
    the target's correction token — committed tokens are exactly what
    greedy ``generate`` would emit); rows with ``temperature > 0`` run
    rejection sampling against their OWN truncated distributions, which
    preserves each row's truncated target sampling distribution exactly.

    Returns ``(new_tokens [B, k+1], n_new [B], n_accept [B])`` int32:
    tokens to commit (positions ``>= n_new`` are meaningless), how many
    to commit this round (``>= 1``; truncated at a row's own eos), and
    the exact count of verifier-accepted proposals (the accept-rate
    numerator; drafted is always ``k``)."""
    from .generate import _truncate_scaled

    b, kp1, _ = tlogits.shape
    k = kp1 - 1
    temperature = jnp.asarray(temperature, jnp.float32)
    ar = jnp.arange(k + 1)[None, :]  # [1, k+1]
    no = jnp.zeros((b, 1), bool)

    # --- greedy rule: longest matching prefix + correction ---
    greedy_tok = _greedy(tlogits)  # [B, k+1]
    match = proposals == greedy_tok[:, :k]
    n_acc_g = jnp.argmin(jnp.concatenate([match, no], axis=1), axis=1)
    new_g = jnp.where(ar <= n_acc_g[:, None], greedy_tok, 0)

    # --- rejection sampling (Leviathan et al. 2023), per-row params ---
    tlp = jax.nn.log_softmax(
        _truncate_scaled(tlogits.astype(jnp.float32), temperature, top_k, top_p), axis=-1
    )  # [B, k+1, V]
    # (k+1)-th draft row is an indexing placeholder — selected only when
    # every proposal was accepted, where probs comes from p_t alone
    dlp = jax.nn.log_softmax(
        jnp.concatenate(
            [dlogits.astype(jnp.float32), jnp.zeros_like(dlogits[:, :1])], axis=1
        ),
        axis=-1,
    )
    lp_t = jnp.take_along_axis(tlp[:, :k], proposals[..., None], axis=-1)[..., 0]
    lp_d = jnp.take_along_axis(dlp[:, :k], proposals[..., None], axis=-1)[..., 0]
    u = jax.random.uniform(rng, (b, k))
    accept = jnp.log(u) < jnp.minimum(lp_t - lp_d, 0.0)
    n_acc_s = jnp.argmin(jnp.concatenate([accept, no], axis=1), axis=1)
    p_t = jnp.exp(jnp.take_along_axis(tlp, n_acc_s[:, None, None], axis=1)[:, 0])  # [B, V]
    p_d = jnp.exp(jnp.take_along_axis(dlp, n_acc_s[:, None, None], axis=1)[:, 0])
    residual = jnp.maximum(p_t - p_d, 0.0)
    probs = jnp.where((n_acc_s == k)[:, None], p_t, residual)
    probs = probs / jnp.maximum(probs.sum(-1, keepdims=True), 1e-30)
    final_tok = jax.random.categorical(
        jax.random.fold_in(rng, 1), jnp.log(probs + 1e-30), axis=-1
    ).astype(jnp.int32)
    prop_pad = jnp.concatenate([proposals, jnp.zeros((b, 1), jnp.int32)], axis=1)
    new_s = jnp.where(
        ar < n_acc_s[:, None], prop_pad,
        jnp.where(ar == n_acc_s[:, None], final_tok[:, None], 0),
    )

    sampled = temperature > 0
    n_accept = jnp.where(sampled, n_acc_s, n_acc_g).astype(jnp.int32)
    new_tokens = jnp.where(sampled[:, None], new_s, new_g).astype(jnp.int32)

    # a row's own eos truncates its round: tokens strictly after the first
    # eos never commit, and the advance stops at the eos inclusive
    is_eos = new_tokens == eos_id[:, None]
    seen_eos = jnp.cumsum(is_eos, axis=1) - is_eos.astype(jnp.int32) > 0
    hit_eos = jnp.any(is_eos & ~seen_eos & (ar <= n_accept[:, None]), axis=1)
    n_new = jnp.minimum(
        n_accept + 1,
        jnp.where(hit_eos, jnp.argmax(is_eos & ~seen_eos, axis=1) + 1, k + 1),
    ).astype(jnp.int32)
    return new_tokens, n_new, n_accept


def init_medusa_heads(cfg, k: int, rng: jax.Array, lm_head_kernel=None):
    """Parameters for ``k - 1`` Medusa decode heads (Cai et al., "Medusa:
    Simple LLM Inference Acceleration Framework with Multiple Decoding
    Heads"): head ``h`` predicts the token ``h + 2`` positions ahead of the
    round's anchor from the SAME final hidden state the base ``lm_head``
    reads — the ``k - 1`` heads cover a ``medusa_k = k`` round's lookahead
    (the round's first position is always the last committed token), so a
    Medusa round needs no heads at all when ``k == 1``.

    Each head is one Medusa-1 residual block over the hidden state::

        logits_h = (hidden + silu(hidden @ w1[h] + b1[h])) @ w2[h]

    stacked across heads: ``w1 [k-1, D, D]``, ``b1 [k-1, D]``,
    ``w2 [k-1, D, V]`` (fp32 — the proposal distributions feed the exact
    rejection-sampling verify). ``w1``/``b1`` start at ZERO, so a fresh
    head's block is the identity over the hidden state; with
    ``lm_head_kernel`` ([D, V], the base model's unembedding) every head
    then starts as an exact copy of the base next-token head — the
    standard warm start for head distillation. Without it ``w2`` draws
    small normals. ``k == 1`` returns empty (0-head) stacks, which
    ``medusa_head_logits`` maps to an empty ``[B, 0, V]``."""
    if k < 1:
        raise ValueError(f"k (proposals per Medusa round) must be >= 1, got {k}")
    d, v, h = cfg.hidden_dim, cfg.vocab_size, k - 1
    if lm_head_kernel is not None:
        w2 = jnp.broadcast_to(jnp.asarray(lm_head_kernel, jnp.float32)[None], (h, d, v))
    else:
        w2 = 0.02 * jax.random.normal(rng, (h, d, v), jnp.float32)
    return {
        "w1": jnp.zeros((h, d, d), jnp.float32),
        "b1": jnp.zeros((h, d), jnp.float32),
        "w2": jnp.asarray(w2, jnp.float32),
    }


def medusa_head_logits(heads, hidden):
    """Apply every Medusa head to one batch of final hidden states:
    ``hidden [B, D]`` -> ``[B, k-1, V]`` fp32, row ``h`` the block-``h``
    head's logits (``init_medusa_heads``' residual form). All heads run as
    two stacked einsums — one fused matmul pair per round, not a Python
    loop over heads."""
    hidden = hidden.astype(jnp.float32)
    pre = jnp.einsum("bd,hde->bhe", hidden, heads["w1"]) + heads["b1"][None]
    res = hidden[:, None, :] + jax.nn.silu(pre)
    return jnp.einsum("bhd,hdv->bhv", res, heads["w2"])


def _row_spec_decode(
    target: DecoderLM,
    draft: DecoderLM,
    target_params,
    draft_params,
    prompt,  # [T] int32, one row
    rng,  # per-row PRNG key (unused at temperature 0)
    pad_len,  # [1] int32 — this row's LEFT-pad count (0 when not ragged)
    max_new_tokens: int,
    k: int,
    eos_id: int,
    pad_id: int,
    temperature,  # traced scalar — a new value must not recompile
    sampled: bool,  # static: selects the greedy or rejection-sampling body
    ragged: bool,  # static: False keeps the pad_len=None fast path compiled
    return_stats: bool = False,  # static: also return (rounds, advanced, accepted)
    return_cache: bool = False,  # static: also return the rewound KV caches
):
    from .generate import decode_step, init_cache, rewind_cache
    from .quant import dequant_tree, widen_quant_tree

    # int8 kernels stay quantized for the fused QuantDense path; only
    # exotic non-kernel quantized leaves rehydrate, and off-TPU the operand
    # widen is hoisted out of the verification loop (see generate.py)
    keep_kernel = lambda p: p.endswith("kernel")
    target_params = dequant_tree(target_params, target.cfg.dtype, keep=keep_kernel)
    draft_params = dequant_tree(draft_params, draft.cfg.dtype, keep=keep_kernel)
    if jax.default_backend() != "tpu":
        target_params = widen_quant_tree(target_params)
        draft_params = widen_quant_tree(draft_params)

    t = prompt.shape[0]
    # vmap hands a scalar; apply wants [B]=[1]. Unpadded calls pass None so
    # the transformer keeps its cheaper non-ragged decode program
    pad_len = jnp.reshape(pad_len, (1,)) if ragged else None
    # slack: the last round may propose past the buffer end; clamp-free
    # writes land in the slack and are sliced off at the end
    cache_len = t + max_new_tokens + k + 1
    tcache = init_cache(target.cfg, 1, cache_len, dtype=target.cfg.dtype)
    dcache = init_cache(draft.cfg, 1, cache_len, dtype=draft.cfg.dtype)
    row = prompt[None]  # [1, T]

    # Prefill both models over the prompt. attend_len=None: these are
    # one-time full passes, the fill-proportional chunking that matters in
    # plain decode buys little across a single prefill.
    tlogits, tcache = decode_step(
        target, target_params, row, tcache, offset=0, pad_len=pad_len, attend_len=t
    )
    _, dcache = decode_step(
        draft, draft_params, row, dcache, offset=0, pad_len=pad_len, attend_len=t
    )

    def _pick(logits, key):
        """Next token from target logits: argmax, or a temperature sample."""
        if not sampled:
            return _greedy(logits)
        return jax.random.categorical(key, logits.astype(jnp.float32) / temperature)

    # y holds the full sequence: prompt + generated (+ slack)
    y = jnp.zeros((cache_len,), jnp.int32)
    y = jax.lax.dynamic_update_slice(y, prompt, (0,))
    rng, first_key = jax.random.split(rng)
    # the first new token needs no speculation: it comes straight from the
    # target's prefill logits (exact greedy / exact target sample)
    first_tok = _pick(tlogits[0, -1], first_key).astype(jnp.int32)
    y = y.at[t].set(first_tok)
    # pos = next position to fill; rounds start at pos = t+1
    state = {
        "pos": jnp.asarray(t + 1, jnp.int32),
        "y": y,
        "rng": rng,
        "tcache": tcache,
        "dcache": dcache,
        "done": first_tok == eos_id,
        # verification rounds run (one target pass each) and draft
        # proposals the verifier accepted — together the EXACT accept-rate
        # observable: accept_rate = accepted / (rounds * k)
        "rounds": jnp.asarray(0, jnp.int32),
        "accepted": jnp.asarray(0, jnp.int32),
    }

    def cond(s):
        return (s["pos"] < t + max_new_tokens) & ~s["done"]

    def round_body(s):
        pos = s["pos"]
        y = s["y"]
        round_key = jax.random.fold_in(s["rng"], pos) if sampled else None

        def pick_draft(row, i):
            if sampled:
                return jax.random.categorical(
                    jax.random.fold_in(round_key, i), row.astype(jnp.float32) / temperature
                ).astype(jnp.int32)
            return _greedy(row)

        # --- draft proposes k tokens in k passes (unrolled: k is static).
        # Pass 0 feeds [y[pos-2], y[pos-1]] at offset pos-2 — the extra
        # leading token closes the draft cache's one-slot gap after a
        # fully-accepted round (see module docstring) and is an identical
        # rewrite otherwise; its last-position logits propose d_1.
        first2 = jax.lax.dynamic_slice(y, (pos - 2,), (2,))[None]  # [1, 2]
        logits, dcache = decode_step(
            draft, draft_params, first2, s["dcache"],
            offset=pos - 2, pad_len=pad_len, attend_len=cache_len,
        )
        nxt = pick_draft(logits[0, -1], 0)
        props, drows = [nxt], [logits[0, -1]]
        for i in range(1, k):  # k-1 single-token passes
            logits, dcache = decode_step(
                draft, draft_params, nxt[None, None], dcache,
                offset=pos - 1 + i, pad_len=pad_len, attend_len=cache_len,
            )
            nxt = pick_draft(logits[0, 0], i)
            props.append(nxt)
            drows.append(logits[0, 0])
        proposals = jnp.stack(props)  # [k]
        # row i is the draft distribution d_{i+1} was sampled from; the
        # rejection-sampling residual needs a (k+1)-th row only as an
        # indexing placeholder (never selected — see below)
        dlogits = jnp.concatenate([jnp.stack(drows), jnp.zeros((1,) + drows[0].shape, drows[0].dtype)])

        # --- target verifies y[pos-1], d_1..d_k in one pass ---
        x = jnp.concatenate([s["y"][pos - 1][None], proposals])[None]  # [1, k+1]
        tlogits, tcache = decode_step(
            target, target_params, x, s["tcache"],
            offset=pos - 1, pad_len=pad_len, attend_len=cache_len,
        )

        if not sampled:
            greedy = _greedy(tlogits[0])  # [k+1]: target tokens for pos..pos+k
            # longest matching prefix, then the target's correction token.
            # Wherever a proposal matched, proposal == greedy, so greedy[i]
            # IS the accepted token for every i <= n_accept (correction
            # included).
            match = proposals == greedy[:k]
            n_accept = jnp.argmin(jnp.concatenate([match, jnp.asarray([False])]))  # first miss
            new_tokens = jnp.where(jnp.arange(k + 1) <= n_accept, greedy, pad_id)
        else:
            # Rejection sampling (Leviathan et al. 2023): accept proposal
            # d_i with prob min(1, p_t(d_i)/p_d(d_i)); at the first
            # rejection, resample from the residual max(p_t - p_d, 0); if
            # all k accepted, sample the bonus token from the target's
            # (k+1)-th distribution. Preserves the target sampling
            # distribution EXACTLY (asserted statistically in tests).
            tlp = jax.nn.log_softmax(tlogits[0].astype(jnp.float32) / temperature)  # [k+1, V]
            dlp = jax.nn.log_softmax(dlogits.astype(jnp.float32) / temperature)  # [k+1, V]
            idx = jnp.arange(k)
            lp_t = tlp[idx, proposals]  # log p_t(d_i) at each proposal
            lp_d = dlp[idx, proposals]
            u = jax.random.uniform(jax.random.fold_in(round_key, k + 1), (k,))
            accept = jnp.log(u) < jnp.minimum(lp_t - lp_d, 0.0)
            n_accept = jnp.argmin(jnp.concatenate([accept, jnp.asarray([False])]))
            # the position-n_accept token: residual resample on rejection,
            # plain target sample when every proposal was accepted (the
            # dlp row there is the zero placeholder — never selected)
            p_t = jnp.exp(tlp[n_accept])
            residual = jnp.maximum(p_t - jnp.exp(dlp[n_accept]), 0.0)
            probs = jnp.where(n_accept == k, p_t, residual)
            probs = probs / jnp.maximum(probs.sum(), 1e-30)
            final_tok = jax.random.categorical(
                jax.random.fold_in(round_key, k + 2), jnp.log(probs + 1e-30)
            ).astype(jnp.int32)
            prop_pad = jnp.concatenate([proposals, jnp.asarray([pad_id], jnp.int32)])
            ar = jnp.arange(k + 1)
            new_tokens = jnp.where(
                ar < n_accept, prop_pad, jnp.where(ar == n_accept, final_tok, pad_id)
            )
        # tokens past the first eos inside the round must not count
        is_eos = new_tokens == eos_id
        seen_eos = jnp.cumsum(is_eos) - is_eos.astype(jnp.int32) > 0  # strictly after an eos
        new_tokens = jnp.where(seen_eos, pad_id, new_tokens)
        hit_eos = jnp.any(is_eos & ~seen_eos & (jnp.arange(k + 1) <= n_accept))
        # number of sequence positions actually advanced this round
        n_new = jnp.minimum(
            n_accept + 1,
            jnp.where(hit_eos, jnp.argmax(is_eos & ~seen_eos) + 1, k + 1),
        ).astype(jnp.int32)

        y_new = jax.lax.dynamic_update_slice(y, new_tokens, (pos,))
        done_row = s["done"]
        # caches are deliberately NOT done-masked (two whole-tree selects
        # per round): a done row's pos/y freeze below, so its repeated
        # writes are idempotent and never reach the output
        new_state = {
            "pos": jnp.where(done_row, pos, pos + n_new),
            "y": jnp.where(done_row, y, y_new),
            "rng": s["rng"],
            "tcache": tcache,
            "dcache": dcache,
            "done": done_row | hit_eos,
            "rounds": jnp.where(done_row, s["rounds"], s["rounds"] + 1),
            "accepted": jnp.where(done_row, s["accepted"], s["accepted"] + n_accept),
        }
        return new_state

    state = jax.lax.while_loop(cond, round_body, state)
    out = jax.lax.dynamic_slice(state["y"], (t,), (max_new_tokens,))
    # positions past the fill (loop exited with pos < t+max_new on eos)
    fill = state["pos"] - t
    out = jnp.where(jnp.arange(max_new_tokens) < fill, out, pad_id)
    extras = []
    if return_stats:
        # `fill` is the UNCLAMPED advance: the final round may overshoot
        # max_new_tokens by up to k (the surplus is masked out of `out`
        # above). `accepted` is the exact verifier acceptance count, so
        # accept_rate = accepted / (rounds * k) holds even under eos
        # truncation (where the advance-based algebra breaks).
        extras.append((state["rounds"], fill, state["accepted"]))
    if return_cache:
        # ONE rewind primitive discards both caches' stale speculative
        # tails. Rewind to pos - 1, NOT pos: slot pos-1 is the one slot the
        # loop's overwrite invariant does not reach — after a rejection it
        # holds the REJECTED draft's K/V (the correction token was emitted
        # but its slot is only rewritten by the next round's pass), and
        # after a fully-accepted round the bonus token's slot was never
        # written at all. The decode convention self-heals (the pass that
        # consumes y[p] writes slot p before attending), so zeroing it is
        # free for consumers and makes every KEPT slot provably correct.
        extras.append(
            (
                rewind_cache(state["tcache"], state["pos"] - 1),
                rewind_cache(state["dcache"], state["pos"] - 1),
            )
        )
    if extras:
        return (out, *extras)
    return out


@functools.partial(
    jax.jit,
    static_argnames=(
        "target", "draft", "max_new_tokens", "k", "eos_id", "pad_id", "sampled", "ragged",
        "return_stats", "return_cache",
    ),
)
def _spec_compiled(
    target, draft, target_params, draft_params, prompt, rng, pad_len, temperature,
    max_new_tokens, k, eos_id, pad_id, sampled, ragged, return_stats=False, return_cache=False,
):
    row_fn = functools.partial(
        _row_spec_decode, target, draft,
        max_new_tokens=max_new_tokens, k=k, eos_id=eos_id, pad_id=pad_id,
        temperature=temperature, sampled=sampled, ragged=ragged, return_stats=return_stats,
        return_cache=return_cache,
    )
    row_keys = jax.random.split(rng, prompt.shape[0])
    return jax.vmap(
        lambda p, key, pl: row_fn(target_params, draft_params, p, key, pl)
    )(prompt, row_keys, pad_len)


def speculative_generate(
    target: DecoderLM,
    target_params: Any,
    draft: DecoderLM,
    draft_params: Any,
    prompt,
    max_new_tokens: int = 32,
    *,
    k: int = 4,
    temperature: float = 0.0,
    rng: jax.Array | None = None,
    prompt_mask: jnp.ndarray | None = None,
    eos_id: int = -1,
    pad_id: int = 0,
    return_stats: bool = False,
    return_cache: bool = False,
):
    """Decode ``max_new_tokens`` continuations of ``prompt`` [B, T] using
    ``draft`` to propose ``k`` tokens per target verification pass: at
    ``temperature == 0`` (default) the output is token-identical to greedy
    ``generate(target, ...)``; at ``temperature > 0`` it is speculative
    SAMPLING via rejection (Leviathan et al. 2023) — accept each proposal
    with probability ``min(1, p_target/p_draft)``, resample rejections
    from the residual — distributed exactly as target-only sampling at
    that temperature (``rng`` seeds it). Speculation changes cost, never
    results.

    Both models must share the tokenizer/vocab; either params tree may be
    int8 weight-only quantized (models/quant.py). Ragged prompts work like
    ``generate``: LEFT-pad and pass ``prompt_mask`` ([B, T] {0,1}, zeros
    first). The temperature value is traced (sweeping it does not
    recompile); only the greedy-vs-sampled switch is compiled in.

    ``return_stats=True`` additionally returns ``(rounds, advanced,
    accepted)`` int32 arrays [B]: verification rounds run (= target decode
    passes), positions the decode loop advanced per row — ``advanced`` can
    exceed ``max_new_tokens`` by up to ``k`` when the final round
    overshoots (the surplus tokens are masked out of the returned
    sequence) — and the EXACT count of verifier-accepted draft proposals,
    so the per-row accept rate is ``accepted / (rounds * k)`` (exact even
    when an in-round eos truncates the advance; absent eos it equals the
    older ``(advanced - 1 - rounds) / (rounds * k)`` algebra).

    ``return_cache=True`` additionally returns ``(target_cache,
    draft_cache)`` with each row's cache REWOUND (one
    ``generate.rewind_cache`` masked select, not k re-dispatches) to
    ``advanced - 1`` valid positions: every kept slot is bit-identical to a
    non-speculative decode of the same tokens, and the speculative tail —
    including the final token's slot, which the loop's overwrite invariant
    never certifies — is zeroed. (The decode convention writes slot ``p``
    in the pass that consumes token ``p``, so a consumer resuming from the
    final token re-fills the zeroed slot before anything reads it.) Leaves
    are [B, S, KH, D], ``init_cache``'s layout (the vmap row axis replaces
    the per-row singleton batch axis)."""
    prompt = jnp.asarray(prompt, jnp.int32)
    _, t = prompt.shape
    if k < 1:
        raise ValueError(f"k (draft proposals per round) must be >= 1, got {k}")
    if temperature < 0:
        raise ValueError(f"temperature must be >= 0, got {temperature}")
    for m, name in ((target, "target"), (draft, "draft")):
        if t + max_new_tokens + k + 1 > m.cfg.max_seq_len:
            raise ValueError(
                f"prompt ({t}) + max_new_tokens ({max_new_tokens}) + k+1 ({k + 1}) exceeds the "
                f"{name} model's max_seq_len ({m.cfg.max_seq_len})"
            )
    if rng is None:
        rng = jax.random.PRNGKey(0)
    from .generate import _pad_len_from_mask

    pad_len = _pad_len_from_mask(prompt_mask, prompt.shape[0], t)
    ragged = pad_len is not None
    if not ragged:  # dummy zeros ride the vmap; the static flag drops them
        pad_len = jnp.zeros((prompt.shape[0],), jnp.int32)
    # greedy-vs-sampled is the only static switch; the temperature VALUE is
    # a traced operand so sweeping it never recompiles (generate()'s
    # convention). The 1e-6 clamp keeps the unused division safe at t == 0.
    out = _spec_compiled(
        target, draft, target_params, draft_params, prompt, rng, pad_len,
        jnp.float32(max(float(temperature), 1e-6)),
        int(max_new_tokens), int(k), int(eos_id), int(pad_id), float(temperature) > 0.0, ragged,
        return_stats=bool(return_stats), return_cache=bool(return_cache),
    )
    if return_cache:
        # vmap left each row's singleton batch axis inside: [B, 1, S, KH, D]
        # -> init_cache's [B, S, KH, D]
        *rest, caches = out
        caches = jax.tree_util.tree_map(lambda x: x.squeeze(1), caches)
        return (*rest, caches)
    return out
