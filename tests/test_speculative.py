"""Speculative decoding must be EXACT: same tokens as plain greedy
generate() on the target, whatever the draft proposes — a perfect draft
(the target itself), a random draft (low acceptance), across k values,
batch rows, and eos early-exit."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dmlcloud_tpu.models.generate import generate
from dmlcloud_tpu.models.speculative import speculative_generate
from dmlcloud_tpu.models.transformer import DecoderLM, TransformerConfig


def _lm(layers, seed, vocab=48, s=96):
    cfg = TransformerConfig(
        vocab_size=vocab, num_layers=layers, num_heads=2, num_kv_heads=1, head_dim=8,
        hidden_dim=16, mlp_dim=32, max_seq_len=s, dtype=jnp.float32,
    )
    model = DecoderLM(cfg)
    tokens = jnp.asarray(np.random.RandomState(0).randint(0, vocab, (1, 8)), jnp.int32)
    params = model.init(jax.random.PRNGKey(seed), tokens)["params"]
    return model, params


# spec_models (target + independent draft) comes from conftest.py,
# session-scoped: built once for the whole suite.


def test_random_draft_matches_plain_greedy(spec_models):
    target, tparams, draft, dparams = spec_models
    prompt = jnp.asarray(np.random.RandomState(1).randint(0, 48, (3, 10)), jnp.int32)
    want = np.asarray(generate(target, tparams, prompt, max_new_tokens=20))
    got = np.asarray(
        speculative_generate(target, tparams, draft, dparams, prompt, max_new_tokens=20, k=4)
    )
    np.testing.assert_array_equal(got, want)


def test_perfect_draft_matches_plain_greedy(spec_models):
    target, tparams, _, _ = spec_models
    prompt = jnp.asarray(np.random.RandomState(2).randint(0, 48, (2, 6)), jnp.int32)
    want = np.asarray(generate(target, tparams, prompt, max_new_tokens=16))
    got = np.asarray(
        speculative_generate(target, tparams, target, tparams, prompt, max_new_tokens=16, k=3)
    )
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("k", [1, 2, 5])
def test_k_values_all_exact(spec_models, k):
    target, tparams, draft, dparams = spec_models
    prompt = jnp.asarray(np.random.RandomState(3).randint(0, 48, (2, 7)), jnp.int32)
    want = np.asarray(generate(target, tparams, prompt, max_new_tokens=15))
    got = np.asarray(
        speculative_generate(target, tparams, draft, dparams, prompt, max_new_tokens=15, k=k)
    )
    np.testing.assert_array_equal(got, want)


def test_eos_early_exit_matches(spec_models):
    target, tparams, draft, dparams = spec_models
    prompt = jnp.asarray(np.random.RandomState(4).randint(0, 48, (2, 6)), jnp.int32)
    # find an eos id that actually occurs early in the greedy output so the
    # early-exit path is exercised rather than vacuously skipped
    plain = np.asarray(generate(target, tparams, prompt, max_new_tokens=14))
    eos = int(plain[0, 2])
    want = np.asarray(generate(target, tparams, prompt, max_new_tokens=14, eos_id=eos))
    got = np.asarray(
        speculative_generate(
            target, tparams, draft, dparams, prompt, max_new_tokens=14, k=4, eos_id=eos
        )
    )
    np.testing.assert_array_equal(got, want)


def test_sliding_window_target_matches(spec_models):
    """The target's windowed decode mask must hold under the verify pass's
    multi-token dynamic-offset reads too."""
    import dataclasses

    _, _, draft, dparams = spec_models
    cfg = dataclasses.replace(_lm(2, 0)[0].cfg, sliding_window=8)
    target = DecoderLM(cfg)
    tparams = target.init(
        jax.random.PRNGKey(3), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    prompt = jnp.asarray(np.random.RandomState(6).randint(0, 48, (2, 10)), jnp.int32)
    want = np.asarray(generate(target, tparams, prompt, max_new_tokens=16))
    got = np.asarray(
        speculative_generate(target, tparams, draft, dparams, prompt, max_new_tokens=16, k=3)
    )
    np.testing.assert_array_equal(got, want)


def test_quantized_target_runs(spec_models):
    from dmlcloud_tpu.models.quant import quantize_tree

    target, tparams, draft, dparams = spec_models
    prompt = jnp.asarray(np.random.RandomState(5).randint(0, 48, (1, 8)), jnp.int32)
    got = np.asarray(
        speculative_generate(
            target, quantize_tree(tparams), draft, dparams, prompt, max_new_tokens=8, k=2
        )
    )
    assert got.shape == (1, 8)


def test_sampled_mode_runs_and_is_deterministic_per_key(spec_models):
    target, tparams, draft, dparams = spec_models
    prompt = jnp.asarray(np.random.RandomState(7).randint(0, 48, (2, 6)), jnp.int32)
    a = np.asarray(
        speculative_generate(
            target, tparams, draft, dparams, prompt, max_new_tokens=10, k=3,
            temperature=0.9, rng=jax.random.PRNGKey(5),
        )
    )
    b = np.asarray(
        speculative_generate(
            target, tparams, draft, dparams, prompt, max_new_tokens=10, k=3,
            temperature=0.9, rng=jax.random.PRNGKey(5),
        )
    )
    c = np.asarray(
        speculative_generate(
            target, tparams, draft, dparams, prompt, max_new_tokens=10, k=3,
            temperature=0.9, rng=jax.random.PRNGKey(6),
        )
    )
    np.testing.assert_array_equal(a, b)  # same key -> same sample
    assert not (a == c).all()  # different key -> different sample
    assert a.shape == (2, 10) and (a >= 0).all() and (a < 48).all()


def test_sampled_distribution_matches_target_sampling(spec_models):
    """The rejection-sampling guarantee: speculative sampling with a
    DIFFERENT draft must be distributed like target-only sampling. Check
    the second generated token's marginal (the first comes from prefill
    sampling in both paths; the second exercises the accept/resample
    math) over many rows with a fixed seed — deterministic, not flaky."""
    from dmlcloud_tpu.models.generate import generate

    vocab = 16
    target, tparams = _lm(layers=2, seed=11, vocab=vocab, s=32)
    draft, dparams = _lm(layers=1, seed=12, vocab=vocab, s=32)
    n = 4000
    prompt = jnp.tile(jnp.asarray([[3, 7, 1]], jnp.int32), (n, 1))

    spec = np.asarray(
        speculative_generate(
            target, tparams, draft, dparams, prompt, max_new_tokens=3, k=2,
            temperature=1.0, rng=jax.random.PRNGKey(0),
        )
    )
    plain = np.asarray(
        generate(
            target, tparams, prompt, max_new_tokens=3, temperature=1.0,
            rng=jax.random.PRNGKey(1),
        )
    )
    for pos in range(3):
        p_spec = np.bincount(spec[:, pos], minlength=vocab) / n
        p_plain = np.bincount(plain[:, pos], minlength=vocab) / n
        tv = 0.5 * np.abs(p_spec - p_plain).sum()
        assert tv < 0.12, (pos, tv, p_spec, p_plain)


def test_ragged_prompts_match_plain_greedy(spec_models):
    """LEFT-padded ragged prompts decode exactly as plain generate's
    ragged path — pad slots masked, positions counted from each row's
    first real token."""
    target, tparams, draft, dparams = spec_models
    rng = np.random.RandomState(8)
    width = 10
    prompt = rng.randint(1, 48, (3, width)).astype(np.int32)
    mask = np.ones((3, width), np.int32)
    mask[1, :4] = 0
    prompt[1, :4] = 0
    mask[2, :7] = 0
    prompt[2, :7] = 0
    want = np.asarray(
        generate(target, tparams, jnp.asarray(prompt), max_new_tokens=12, prompt_mask=mask)
    )
    got = np.asarray(
        speculative_generate(
            target, tparams, draft, dparams, jnp.asarray(prompt), max_new_tokens=12, k=3,
            prompt_mask=mask,
        )
    )
    np.testing.assert_array_equal(got, want)


def test_length_guard(spec_models):
    target, tparams, draft, dparams = spec_models
    prompt = jnp.zeros((1, 90), jnp.int32)
    with pytest.raises(ValueError, match="max_seq_len"):
        speculative_generate(target, tparams, draft, dparams, prompt, max_new_tokens=10, k=4)


def test_return_stats_consistency(spec_models):
    """rounds/generated must obey the accept-rate algebra: every round emits
    between 1 and k+1 tokens (so rounds bounds generated-1 from both sides),
    a perfect draft needs the fewest rounds, and the derived accept rate for
    the SAME-model draft is exactly 1."""
    target, tparams, draft, dparams = spec_models
    k = 3
    prompt = jnp.asarray(np.random.RandomState(5).randint(0, 48, (3, 9)), jnp.int32)
    toks, (rounds, generated, accepted) = speculative_generate(
        target, tparams, draft, dparams, prompt, max_new_tokens=18, k=k, return_stats=True
    )
    want = np.asarray(
        speculative_generate(target, tparams, draft, dparams, prompt, max_new_tokens=18, k=k)
    )
    np.testing.assert_array_equal(np.asarray(toks), want)  # stats don't change tokens
    rounds, generated, accepted = np.asarray(rounds), np.asarray(generated), np.asarray(accepted)
    # no eos id in play: full fill, plus up to k overshoot in the last round
    assert ((generated >= 18) & (generated <= 18 + k)).all(), generated
    # each round advances 1..k+1 positions (first token costs no round)
    assert (rounds >= np.ceil((generated - 1) / (k + 1))).all(), (rounds, generated)
    assert (rounds <= generated - 1).all(), (rounds, generated)
    # absent eos, the exact counter and the advance algebra must agree
    np.testing.assert_array_equal(accepted, generated - 1 - rounds)
    rate = accepted / (rounds * k)
    assert ((rate >= 0) & (rate <= 1)).all(), rate

    # a perfect draft (the target itself) accepts every proposal
    _, (p_rounds, p_generated, p_accepted) = speculative_generate(
        target, tparams, target, tparams, prompt, max_new_tokens=18, k=k, return_stats=True
    )
    p_rounds, p_generated, p_accepted = (
        np.asarray(p_rounds), np.asarray(p_generated), np.asarray(p_accepted)
    )
    np.testing.assert_allclose(p_accepted / (p_rounds * k), 1.0)
    assert (p_rounds <= rounds).all(), (p_rounds, rounds)


def _np_reference_counters(target, tparams, draft, dparams, prompt_row, max_new, k):
    """Greedy speculative decoding re-implemented with full-sequence
    (cache-free) model applications and NumPy argmax — the independent
    reference for the on-device round/accept counters."""

    # one compiled shape a model: the decoder is causal, so right padding
    # cannot reach the rows that are read (an eager apply compiles per
    # primitive at every new length)
    width = len(prompt_row) + max_new + k

    def full_logits(model, params):
        apply = jax.jit(lambda seq: model.apply({"params": params}, seq[None])[0])

        def fn(seq):
            padded = np.zeros(width, np.int32)
            padded[: len(seq)] = seq
            return np.asarray(apply(jnp.asarray(padded)))[: len(seq)]

        return fn

    tlogits, dlogits = full_logits(target, tparams), full_logits(draft, dparams)

    y = [int(x) for x in prompt_row]
    t = len(y)
    y.append(int(np.argmax(tlogits(y)[-1])))  # first token costs no round
    rounds = accepted = 0
    pos = t + 1
    while pos < t + max_new:
        rounds += 1
        props, ctx = [], list(y)
        for _ in range(k):
            nxt = int(np.argmax(dlogits(ctx)[-1]))
            props.append(nxt)
            ctx.append(nxt)
        tl = tlogits(y + props)  # row pos-1+i predicts position pos+i
        n_acc, new = 0, []
        for i in range(k):
            t_i = int(np.argmax(tl[pos - 1 + i]))
            if props[i] == t_i:
                n_acc += 1
                new.append(props[i])
            else:
                new.append(t_i)
                break
        else:
            new.append(int(np.argmax(tl[pos - 1 + k])))  # bonus token
        accepted += n_acc
        y.extend(new)
        pos += len(new)
    return rounds, pos - t, accepted


def test_accept_counter_matches_numpy_reference(spec_models):
    """The on-device rounds/advanced/accepted counters must be EXACT —
    equal to a from-scratch NumPy reference of the greedy round structure,
    row by row (the r01-r05 receipts recorded accept 0.0 because the
    observable was never pinned to an independent implementation)."""
    target, tparams, draft, dparams = spec_models
    k, max_new = 3, 14
    prompt = jnp.asarray(np.random.RandomState(11).randint(0, 48, (3, 8)), jnp.int32)
    _, (rounds, advanced, accepted) = speculative_generate(
        target, tparams, draft, dparams, prompt, max_new_tokens=max_new, k=k, return_stats=True
    )
    rounds, advanced, accepted = (np.asarray(x) for x in (rounds, advanced, accepted))
    for row in range(prompt.shape[0]):
        want = _np_reference_counters(
            target, tparams, draft, dparams, np.asarray(prompt)[row], max_new, k
        )
        got = (int(rounds[row]), int(advanced[row]), int(accepted[row]))
        assert got == want, f"row {row}: device counters {got} != numpy reference {want}"


def test_rewound_cache_bit_identical_at_accepted_prefix(spec_models):
    """return_cache=True caches are rewound with ONE masked-select primitive:
    the stale speculative tail must be exactly zero, and the valid prefix
    must be bit-identical across runs with DIFFERENT drafts (different
    rejection patterns, different stale slots — same greedy tokens)."""
    target, tparams, draft, dparams = spec_models
    k, max_new = 3, 12
    prompt = jnp.asarray(np.random.RandomState(12).randint(0, 48, (2, 7)), jnp.int32)
    t = prompt.shape[1]

    toks_a, (_, fill_a, _), (tcache_a, dcache_a) = speculative_generate(
        target, tparams, draft, dparams, prompt, max_new_tokens=max_new, k=k,
        return_stats=True, return_cache=True,
    )
    toks_b, (_, fill_b, _), (tcache_b, _) = speculative_generate(
        target, tparams, target, tparams, prompt, max_new_tokens=max_new, k=k,
        return_stats=True, return_cache=True,
    )
    np.testing.assert_array_equal(np.asarray(toks_a), np.asarray(toks_b))

    # the contract: advanced - 1 valid positions per row (the final token's
    # slot is zeroed — the loop's overwrite invariant never certifies it)
    valid_a = np.asarray(fill_a) + t - 1
    for cache in (tcache_a, dcache_a):
        for leaf in jax.tree_util.tree_leaves(cache):
            arr = np.asarray(leaf)  # [B, S, KH, D]
            for row in range(arr.shape[0]):
                assert (arr[row, valid_a[row]:] == 0).all(), "stale tail not rewound"
    # valid prefix: bit-identical target caches wherever both runs decoded
    common = np.minimum(valid_a, np.asarray(fill_b) + t - 1)
    flat_a = jax.tree_util.tree_leaves(tcache_a)
    flat_b = jax.tree_util.tree_leaves(tcache_b)
    assert len(flat_a) == len(flat_b) and len(flat_a) > 0
    for la, lb in zip(flat_a, flat_b):
        a, b = np.asarray(la), np.asarray(lb)
        assert a.ndim == 4, "return_cache leaves must be [B, S, KH, D]"
        for row in range(a.shape[0]):
            np.testing.assert_array_equal(
                a[row, : common[row]], b[row, : common[row]],
                err_msg="accepted-prefix cache slots differ between drafts",
            )


class TestVerifyProposals:
    """verify_proposals: the batched per-row-params accept rule the
    serving engine's spec verify step runs (same math as the in-loop
    greedy/rejection rules above, B rows at once)."""

    def _inputs(self, b=3, k=4, v=17, seed=0):
        rng = jax.random.PRNGKey(seed)
        tlogits = jax.random.normal(jax.random.fold_in(rng, 1), (b, k + 1, v)) * 2.0
        dlogits = jax.random.normal(jax.random.fold_in(rng, 2), (b, k, v)) * 2.0
        proposals = jax.random.randint(jax.random.fold_in(rng, 3), (b, k), 0, v)
        return tlogits, dlogits, proposals.astype(jnp.int32)

    def test_greedy_rows_match_numpy_reference(self):
        from dmlcloud_tpu.models.speculative import verify_proposals

        b, k = 3, 4
        tlogits, dlogits, proposals = self._inputs(b, k)
        zeros = jnp.zeros(b)
        new_tokens, n_new, n_accept = verify_proposals(
            tlogits, dlogits, proposals, jax.random.PRNGKey(7),
            zeros, jnp.zeros(b, jnp.int32), jnp.ones(b), jnp.full(b, -1, jnp.int32),
        )
        tl = np.asarray(tlogits)
        props = np.asarray(proposals)
        for r in range(b):
            greedy = tl[r].argmax(-1)  # [k+1]
            acc = 0
            while acc < k and props[r, acc] == greedy[acc]:
                acc += 1
            assert int(n_accept[r]) == acc
            assert int(n_new[r]) == acc + 1
            # committed tokens are the target's greedy tokens through the
            # correction — exactly what serial greedy decode would emit
            np.testing.assert_array_equal(
                np.asarray(new_tokens)[r, : acc + 1], greedy[: acc + 1]
            )

    def test_eos_truncates_the_advance(self):
        from dmlcloud_tpu.models.speculative import verify_proposals

        b, k, v = 2, 3, 11
        # force full greedy acceptance: proposals == target argmax
        tlogits = jax.random.normal(jax.random.PRNGKey(4), (b, k + 1, v)) * 2.0
        proposals = jnp.argmax(tlogits[:, :k], axis=-1).astype(jnp.int32)
        dlogits = jnp.zeros((b, k, v))
        eos0 = int(proposals[0, 1])  # row 0's second committed token
        # the key must not draw that token first as well (PRNGKey(3) does under jax 0.9)
        assert int(proposals[0, 0]) != eos0
        new_tokens, n_new, n_accept = verify_proposals(
            tlogits, dlogits, proposals, jax.random.PRNGKey(8),
            jnp.zeros(b), jnp.zeros(b, jnp.int32), jnp.ones(b),
            jnp.asarray([eos0, -1], jnp.int32),
        )
        assert int(n_accept[0]) == k  # acceptance is eos-blind
        assert int(n_new[0]) == 2  # ...but the advance stops AT the eos
        assert int(np.asarray(new_tokens)[0, 1]) == eos0
        assert int(n_new[1]) == k + 1  # the other row is untouched

    def test_sampled_rows_accept_everything_when_draft_is_target(self):
        """When dlogits IS the truncated target distribution, the
        rejection test accepts with probability min(1, 1) = 1 — every
        proposal must be accepted (the engine's shared-model smoke)."""
        from dmlcloud_tpu.models.generate import _truncate_scaled
        from dmlcloud_tpu.models.speculative import verify_proposals

        b, k = 3, 4
        tlogits, _, _ = self._inputs(b, k)
        temp = jnp.full(b, 0.8)
        topk = jnp.zeros(b, jnp.int32)
        topp = jnp.ones(b)
        truncated = _truncate_scaled(tlogits[:, :k].astype(jnp.float32), temp, topk, topp)
        # proposals sampled from the draft's own rows (any supported token)
        proposals = jnp.argmax(truncated, axis=-1).astype(jnp.int32)
        _, n_new, n_accept = verify_proposals(
            tlogits, truncated, proposals, jax.random.PRNGKey(9),
            temp, topk, topp, jnp.full(b, -1, jnp.int32),
        )
        np.testing.assert_array_equal(np.asarray(n_accept), [k] * b)
        np.testing.assert_array_equal(np.asarray(n_new), [k + 1] * b)

    def test_mixed_greedy_and_sampled_rows_in_one_call(self):
        """Row 0 greedy, row 1 sampled: the greedy row's commitment is the
        argmax rule's regardless of the sampled row's dice."""
        from dmlcloud_tpu.models.speculative import verify_proposals

        b, k = 2, 3
        tlogits, dlogits, proposals = self._inputs(b, k, seed=4)
        new_tokens, n_new, n_accept = verify_proposals(
            tlogits, dlogits, proposals, jax.random.PRNGKey(11),
            jnp.asarray([0.0, 1.0]), jnp.zeros(b, jnp.int32), jnp.ones(b),
            jnp.full(b, -1, jnp.int32),
        )
        greedy = np.asarray(tlogits)[0].argmax(-1)
        acc = 0
        while acc < k and int(proposals[0, acc]) == greedy[acc]:
            acc += 1
        assert int(n_accept[0]) == acc
        np.testing.assert_array_equal(
            np.asarray(new_tokens)[0, : acc + 1], greedy[: acc + 1]
        )
        assert 0 <= int(n_accept[1]) <= k
        assert 1 <= int(n_new[1]) <= k + 1
