"""KV-cache generation (models/generate.py): the cached decode loop must
reproduce the no-cache model exactly (greedy), honor eos/pad semantics, and
run the MoE variant. fp32 config so CPU comparisons are exact-ish."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dmlcloud_tpu.models.generate import generate, init_cache
from dmlcloud_tpu.models.transformer import DecoderLM, TransformerConfig


def _tiny_cfg(**kw):
    base = dict(
        vocab_size=61,
        num_layers=2,
        num_heads=4,
        head_dim=8,
        hidden_dim=32,
        mlp_dim=64,
        max_seq_len=64,
        dtype=jnp.float32,
    )
    base.update(kw)
    return TransformerConfig(**base)


def _init(cfg, batch=2, t=7, seed=0):
    model = DecoderLM(cfg)
    rng = np.random.RandomState(seed)
    prompt = jnp.asarray(rng.randint(0, cfg.vocab_size, size=(batch, t)), jnp.int32)
    params = model.init(jax.random.PRNGKey(seed), prompt)["params"]
    return model, params, prompt


def _greedy_no_cache(model, params, prompt, n):
    """Reference: rerun the full model per token, argmax the last position."""
    tokens = prompt
    out = []
    for _ in range(n):
        logits = model.apply({"params": params}, tokens)
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        out.append(nxt)
        tokens = jnp.concatenate([tokens, nxt[:, None]], axis=1)
    return jnp.stack(out, axis=1)


def test_greedy_matches_no_cache():
    cfg = _tiny_cfg()
    model, params, prompt = _init(cfg)
    want = _greedy_no_cache(model, params, prompt, 8)
    got = generate(model, params, prompt, max_new_tokens=8)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_gqa_greedy_matches_no_cache():
    cfg = _tiny_cfg(num_kv_heads=2)
    model, params, prompt = _init(cfg)
    want = _greedy_no_cache(model, params, prompt, 6)
    got = generate(model, params, prompt, max_new_tokens=6)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_eos_rows_emit_pad():
    cfg = _tiny_cfg()
    model, params, prompt = _init(cfg)
    first = np.asarray(generate(model, params, prompt, max_new_tokens=1))[:, 0]
    out = np.asarray(
        generate(model, params, prompt, max_new_tokens=6, eos_id=int(first[0]), pad_id=59)
    )
    # row 0 hit eos at step 0: the eos token itself is emitted, then pad
    assert out[0, 0] == first[0]
    assert (out[0, 1:] == 59).all()


def test_sampling_deterministic_under_rng():
    cfg = _tiny_cfg()
    model, params, prompt = _init(cfg)
    a = generate(model, params, prompt, 5, temperature=0.8, top_k=10, rng=jax.random.PRNGKey(7))
    b = generate(model, params, prompt, 5, temperature=0.8, top_k=10, rng=jax.random.PRNGKey(7))
    c = generate(model, params, prompt, 5, temperature=0.8, top_k=10, rng=jax.random.PRNGKey(8))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert np.asarray(a).shape == (2, 5)
    assert ((np.asarray(a) >= 0) & (np.asarray(a) < cfg.vocab_size)).all()
    # different seed should (overwhelmingly) differ somewhere
    assert not np.array_equal(np.asarray(a), np.asarray(c))


def test_moe_decode_runs():
    cfg = _tiny_cfg(num_experts=2, num_dense_layers=1)
    model, params, prompt = _init(cfg)
    out = generate(model, params, prompt, max_new_tokens=4)
    assert np.asarray(out).shape == (2, 4)


def test_length_guard():
    cfg = _tiny_cfg(max_seq_len=16)
    model, params, prompt = _init(cfg, t=12)
    with pytest.raises(ValueError, match="max_seq_len"):
        generate(model, params, prompt, max_new_tokens=8)


def test_init_cache_shapes():
    cfg = _tiny_cfg(num_kv_heads=2)
    cache = init_cache(cfg, batch_size=3, max_len=32)
    assert set(cache) == {"layer_0", "layer_1"}
    assert cache["layer_0"]["k"].shape == (3, 32, 2, 8)


def test_top_p_sampling():
    cfg = _tiny_cfg()
    model, params, prompt = _init(cfg)
    # tiny nucleus -> effectively greedy (only the argmax survives the cutoff)
    tight = generate(model, params, prompt, 5, temperature=1.0, top_p=1e-6, rng=jax.random.PRNGKey(3))
    greedy = generate(model, params, prompt, 5)
    np.testing.assert_array_equal(np.asarray(tight), np.asarray(greedy))
    # permissive nucleus is deterministic under a fixed rng and in range
    a = generate(model, params, prompt, 5, temperature=1.0, top_p=0.9, rng=jax.random.PRNGKey(4))
    b = generate(model, params, prompt, 5, temperature=1.0, top_p=0.9, rng=jax.random.PRNGKey(4))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert ((np.asarray(a) >= 0) & (np.asarray(a) < cfg.vocab_size)).all()


class TestBeamSearch:
    def test_single_beam_equals_greedy(self):
        from dmlcloud_tpu.models.generate import beam_search

        cfg = _tiny_cfg()
        model, params, prompt = _init(cfg)
        greedy = generate(model, params, prompt, 7)
        beams, scores = beam_search(model, params, prompt, 7, num_beams=1)
        np.testing.assert_array_equal(np.asarray(beams), np.asarray(greedy))
        assert np.isfinite(np.asarray(scores)).all()

    def test_full_beam_finds_global_optimum(self):
        """With K = V^(N-1) beams, beam search is exhaustive: its winner must
        be the true argmax over all V^N continuations, scored by rerunning
        the full model."""
        from itertools import product

        from dmlcloud_tpu.models.generate import beam_search

        cfg = _tiny_cfg(vocab_size=16, max_seq_len=16)
        model, params, prompt = _init(cfg, batch=1, t=3)
        n = 2  # K = V^(N-1) = 16 beams make the search exhaustive
        beams, score = beam_search(model, params, prompt, n, num_beams=16)

        def seq_logprob(cont):
            toks = jnp.concatenate([prompt, jnp.asarray([cont], jnp.int32)], axis=1)
            logits = model.apply({"params": params}, toks)
            lp = jax.nn.log_softmax(logits[0].astype(jnp.float32))
            return sum(float(lp[prompt.shape[1] - 1 + j, cont[j]]) for j in range(n))

        all_scores = {cont: seq_logprob(cont) for cont in product(range(16), repeat=n)}
        best_cont = max(all_scores, key=all_scores.get)
        assert tuple(np.asarray(beams)[0].tolist()) == best_cont
        assert abs(float(score[0]) - all_scores[best_cont] / n) < 1e-4  # len-normalised

    def test_beam_scores_are_honest(self):
        """The reported score must equal rescoring the winning continuation
        with the full model (beam >= greedy is NOT asserted — the greedy
        prefix can legitimately be pruned mid-search)."""
        from dmlcloud_tpu.models.generate import beam_search

        cfg = _tiny_cfg(vocab_size=13)
        model, params, prompt = _init(cfg, batch=3, t=5, seed=2)
        beams, scores = beam_search(model, params, prompt, 6, num_beams=4)
        assert np.asarray(beams).shape == (3, 6)

        def score_cont(cont_row, prompt_row):
            toks = jnp.concatenate([prompt_row[None], cont_row[None]], axis=1)
            logits = model.apply({"params": params}, toks)
            lp = jax.nn.log_softmax(logits[0].astype(jnp.float32))
            t0 = prompt_row.shape[0]
            return sum(float(lp[t0 - 1 + j, int(cont_row[j])]) for j in range(6)) / 6

        for i in range(3):
            s_beam = score_cont(jnp.asarray(np.asarray(beams)[i]), prompt[i])
            assert abs(s_beam - float(scores[i])) < 1e-4  # reported score is honest

    def test_eos_freezes_beams(self):
        from dmlcloud_tpu.models.generate import beam_search

        cfg = _tiny_cfg()
        model, params, prompt = _init(cfg)
        first = np.asarray(generate(model, params, prompt, 1))[:, 0]
        beams, _ = beam_search(
            model, params, prompt, 6, num_beams=1, eos_id=int(first[0]), pad_id=59
        )
        out = np.asarray(beams)
        assert out[0, 0] == first[0]
        assert (out[0, 1:] == 59).all()

    def test_validation(self):
        from dmlcloud_tpu.models.generate import beam_search

        cfg = _tiny_cfg()
        model, params, prompt = _init(cfg)
        with pytest.raises(ValueError, match="num_beams"):
            beam_search(model, params, prompt, 4, num_beams=0)
        with pytest.raises(ValueError, match="vocab"):
            beam_search(model, params, prompt, 4, num_beams=100)

    def test_eos_freezes_multi_beam(self):
        """With k > 1, any beam that emits eos must continue as pure pad
        (exercises reorder + freeze interaction, not just the k=1 identity)."""
        from dmlcloud_tpu.models.generate import beam_search

        cfg = _tiny_cfg()
        for seed in range(3):
            model, params, prompt = _init(cfg, batch=2, t=5, seed=seed)
            first = int(np.asarray(generate(model, params, prompt, 1))[0, 0])
            beams, scores = beam_search(
                model, params, prompt, 6, num_beams=3, eos_id=first, pad_id=59
            )
            out = np.asarray(beams)
            assert np.isfinite(np.asarray(scores)).all()
            for row in out:
                hits = np.where(row == first)[0]
                if hits.size:
                    assert (row[hits[0] + 1 :] == 59).all()

    def test_pad_id_validated(self):
        from dmlcloud_tpu.models.generate import beam_search

        cfg = _tiny_cfg()
        model, params, prompt = _init(cfg)
        with pytest.raises(ValueError, match="pad_id"):
            beam_search(model, params, prompt, 4, num_beams=2, pad_id=-1)

    def test_length_penalty_does_not_recompile(self):
        from dmlcloud_tpu.models.generate import _beam_search_compiled, beam_search

        cfg = _tiny_cfg()
        model, params, prompt = _init(cfg)
        beam_search(model, params, prompt, 3, num_beams=2, length_penalty=0.7)
        misses = _beam_search_compiled._cache_size()
        beam_search(model, params, prompt, 3, num_beams=2, length_penalty=1.3)
        assert _beam_search_compiled._cache_size() == misses


class TestRaggedPrompts:
    def test_left_padded_rows_match_unpadded(self):
        """Each left-padded row must decode exactly as its unpadded self."""
        cfg = _tiny_cfg()
        model, params, _ = _init(cfg)
        rng = np.random.RandomState(11)
        p1 = rng.randint(1, 61, size=5)
        p2 = rng.randint(1, 61, size=9)
        t = 9
        batch = np.zeros((2, t), np.int32)
        mask = np.zeros((2, t), np.int32)
        batch[0, t - 5 :], mask[0, t - 5 :] = p1, 1
        batch[1, :], mask[1, :] = p2, 1

        got = generate(model, params, jnp.asarray(batch), 6, prompt_mask=jnp.asarray(mask))
        want1 = generate(model, params, jnp.asarray(p1[None]), 6)
        want2 = generate(model, params, jnp.asarray(p2[None]), 6)
        np.testing.assert_array_equal(np.asarray(got)[0], np.asarray(want1)[0])
        np.testing.assert_array_equal(np.asarray(got)[1], np.asarray(want2)[0])

    def test_windowed_ragged(self):
        cfg = _tiny_cfg(sliding_window=4)
        model, params, _ = _init(cfg)
        rng = np.random.RandomState(12)
        p1 = rng.randint(1, 61, size=3)
        p2 = rng.randint(1, 61, size=7)
        t = 7
        batch = np.zeros((2, t), np.int32)
        mask = np.zeros((2, t), np.int32)
        batch[0, t - 3 :], mask[0, t - 3 :] = p1, 1
        batch[1, :], mask[1, :] = p2, 1
        got = generate(model, params, jnp.asarray(batch), 5, prompt_mask=jnp.asarray(mask))
        want1 = generate(model, params, jnp.asarray(p1[None]), 5)
        np.testing.assert_array_equal(np.asarray(got)[0], np.asarray(want1)[0])

    def test_right_padding_rejected(self):
        cfg = _tiny_cfg()
        model, params, prompt = _init(cfg)
        mask = np.ones((2, 7), np.int32)
        mask[:, -2:] = 0  # right padding
        with pytest.raises(ValueError, match="LEFT"):
            generate(model, params, prompt, 4, prompt_mask=mask)

    def test_right_padding_rejected_for_jax_arrays_too(self):
        cfg = _tiny_cfg()
        model, params, prompt = _init(cfg)
        mask = np.ones((2, 7), np.int32)
        mask[:, -2:] = 0
        with pytest.raises(ValueError, match="LEFT"):
            generate(model, params, prompt, 4, prompt_mask=jnp.asarray(mask))

    def test_bad_mask_shape_message(self):
        cfg = _tiny_cfg()
        model, params, prompt = _init(cfg)
        with pytest.raises(ValueError, match=r"\[B, T\]"):
            generate(model, params, prompt, 4, prompt_mask=np.ones(7, np.int32))


def test_ragged_beam_rows_match_unpadded():
    from dmlcloud_tpu.models.generate import beam_search

    cfg = _tiny_cfg()
    model, params, _ = _init(cfg)
    rng = np.random.RandomState(13)
    p1 = rng.randint(1, 61, size=4)
    p2 = rng.randint(1, 61, size=8)
    t = 8
    batch, mask = np.zeros((2, t), np.int32), np.zeros((2, t), np.int32)
    batch[0, t - 4 :], mask[0, t - 4 :] = p1, 1
    batch[1], mask[1] = p2, 1

    got, scores = beam_search(model, params, jnp.asarray(batch), 5, num_beams=3,
                              prompt_mask=jnp.asarray(mask))
    want1, s1 = beam_search(model, params, jnp.asarray(p1[None]), 5, num_beams=3)
    want2, s2 = beam_search(model, params, jnp.asarray(p2[None]), 5, num_beams=3)
    np.testing.assert_array_equal(np.asarray(got)[0], np.asarray(want1)[0])
    np.testing.assert_array_equal(np.asarray(got)[1], np.asarray(want2)[0])
    np.testing.assert_allclose(np.asarray(scores), [float(s1[0]), float(s2[0])], atol=1e-5)


def test_rewind_cache_masks_exactly():
    """rewind_cache is ONE masked select over the tree: slots at position
    >= fill_len zero out, slots below are untouched bit for bit — with a
    per-row [B] fill, a scalar fill, and under jit (traced fill)."""
    from dmlcloud_tpu.models.generate import rewind_cache

    rng = np.random.RandomState(0)
    cache = {
        "layer_0": {
            "k": jnp.asarray(rng.randn(2, 16, 1, 4), jnp.float32),
            "v": jnp.asarray(rng.randn(2, 16, 1, 4), jnp.float32),
        }
    }
    fill = jnp.asarray([5, 11], jnp.int32)
    for rewound in (rewind_cache(cache, fill), jax.jit(rewind_cache)(cache, fill)):
        for name in ("k", "v"):
            got = np.asarray(rewound["layer_0"][name])
            want = np.asarray(cache["layer_0"][name]).copy()
            want[0, 5:] = 0
            want[1, 11:] = 0
            np.testing.assert_array_equal(got, want)
    # scalar fill broadcasts to every row
    got = np.asarray(rewind_cache(cache, 3)["layer_0"]["k"])
    assert (got[:, 3:] == 0).all()
    np.testing.assert_array_equal(got[:, :3], np.asarray(cache["layer_0"]["k"])[:, :3])


def test_attend_len_bounds_cache_reads():
    """With attend_len set, slots past it must never be READ: poison the
    cache tail with NaN and the logits must stay finite and equal to the
    clean-cache result. This is the property that makes decode cost scale
    with fill instead of max_len."""
    model, params, prompt = _init(_tiny_cfg(), batch=2, t=8)
    cache = init_cache(model.cfg, 2, 32, dtype=model.cfg.dtype)
    clean, _ = model.apply({"params": params}, prompt, cache=cache, offset=0, attend_len=8)
    poisoned = jax.tree_util.tree_map(lambda x: x.at[:, 8:].set(jnp.nan), cache)
    got, new_cache = model.apply({"params": params}, prompt, cache=poisoned, offset=0, attend_len=8)
    assert bool(jnp.all(jnp.isfinite(got)))
    np.testing.assert_allclose(np.asarray(got), np.asarray(clean), rtol=1e-6, atol=1e-6)
    # the returned cache is still the FULL buffer (writes are never bounded)
    assert new_cache["layer_0"]["k"].shape[1] == 32


def test_long_generation_exercises_multi_step_segments():
    """max_new_tokens > _DECODE_CHUNKS forces scan segments longer than one
    step, where attend_len runs AHEAD of the fill inside a segment — greedy
    must still match the no-cache reference and single-beam greedy."""
    from dmlcloud_tpu.models.generate import _DECODE_CHUNKS, beam_search

    n = 2 * _DECODE_CHUNKS + 4  # segment length >= 3
    model, params, prompt = _init(_tiny_cfg(max_seq_len=64), batch=2, t=6)
    got = generate(model, params, prompt, max_new_tokens=n)
    want = _greedy_no_cache(model, params, prompt, n)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    beam_toks, _ = beam_search(model, params, prompt, max_new_tokens=n, num_beams=1)
    np.testing.assert_array_equal(np.asarray(beam_toks), np.asarray(got))


class TestBatchedSampler:
    """sample_logits_batched: the per-row traced twin of sample_logits
    (the serving engine's mixed-tenant sampling path)."""

    def _logits(self, b=4, v=61, seed=6, scale=3.0):
        return jax.random.normal(jax.random.PRNGKey(seed), (b, v)) * scale

    @pytest.mark.parametrize(
        "t,k,p",
        [(0.0, 0, 1.0), (0.7, 0, 1.0), (1.0, 10, 1.0), (0.9, 0, 0.7), (1.2, 5, 0.9)],
    )
    def test_uniform_rows_match_scalar_sampler(self, t, k, p):
        """A batch whose rows all share one param set must sample the SAME
        tokens as the scalar sampler with those params (same rng, same
        truncation, same categorical)."""
        from dmlcloud_tpu.models.generate import sample_logits, sample_logits_batched

        logits = self._logits()
        rng = jax.random.PRNGKey(5)
        a = sample_logits(logits, rng, t, k, p)
        b = sample_logits_batched(
            logits, rng,
            jnp.full(4, t, jnp.float32), jnp.full(4, k, jnp.int32), jnp.full(4, p, jnp.float32),
        )
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_mixed_rows_greedy_is_exact_argmax(self):
        """Rows with temperature 0 in a mixed batch return the exact
        argmax regardless of the other rows' params."""
        from dmlcloud_tpu.models.generate import sample_logits_batched

        logits = self._logits()
        out = sample_logits_batched(
            logits, jax.random.PRNGKey(0),
            jnp.asarray([0.0, 1.5, 0.0, 0.8]),
            jnp.asarray([0, 5, 0, 0], jnp.int32),
            jnp.asarray([1.0, 1.0, 1.0, 0.6]),
        )
        greedy = np.argmax(np.asarray(logits), axis=-1)
        assert int(out[0]) == greedy[0] and int(out[2]) == greedy[2]

    def test_top_k_truncation_is_per_row(self):
        """top_k=1 rows must return the argmax (only one candidate
        survives) even at high temperature; top_k=0 rows stay untruncated."""
        from dmlcloud_tpu.models.generate import sample_logits_batched

        logits = self._logits(b=3)
        out = sample_logits_batched(
            logits, jax.random.PRNGKey(1),
            jnp.asarray([5.0, 5.0, 5.0]),
            jnp.asarray([1, 1, 0], jnp.int32),
            jnp.ones(3, jnp.float32),
        )
        greedy = np.argmax(np.asarray(logits), axis=-1)
        assert int(out[0]) == greedy[0] and int(out[1]) == greedy[1]

    def test_top_p_tiny_nucleus_is_argmax(self):
        """top_p small enough keeps only the head of the distribution —
        with a dominant logit the sample is forced to the argmax."""
        from dmlcloud_tpu.models.generate import sample_logits_batched

        logits = jnp.zeros((2, 8)).at[:, 3].set(10.0)
        out = sample_logits_batched(
            logits, jax.random.PRNGKey(2),
            jnp.asarray([1.0, 1.0]), jnp.zeros(2, jnp.int32), jnp.asarray([0.1, 0.1]),
        )
        np.testing.assert_array_equal(np.asarray(out), [3, 3])
