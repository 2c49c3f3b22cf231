"""The serving engine's speculative modes (dmlcloud_tpu/serve/engine.py):
draft-model speculation (``spec_k``) and Medusa heads (``medusa_k``).

Each tested here against serial ``generate()``: greedy output is
TOKEN-IDENTICAL at any accept rate, the accept counters are exact (1.0 when
the target drafts for itself), both pools drain clean, the signature budget
holds and a warm engine compiles nothing, and the modes compose with the
prefix cache, LoRA tenants and the chaos harness. (Split out of
test_serve.py so that tier-1's ``--dist loadfile`` run has no 500 s file.)
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dmlcloud_tpu.models.generate import generate
from dmlcloud_tpu.models.lora import lora_merge
from dmlcloud_tpu.models.speculative import init_medusa_heads
from dmlcloud_tpu.models.transformer import DecoderLM
from dmlcloud_tpu.serve import AdapterSet, ChaosMonkey, ServeEngine

from test_serve import _engine, _prompt, _randomized_adapter, _tiny_cfg
from test_serve_prefix import _template_prompt

# tiny_model (the shared 61-vocab serve LM) comes from conftest.py.


# ---------------------------------------------------------------------------
# speculative decoding inside the engine (draft/verify over paged KV)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny_draft():
    """An INDEPENDENT random-init draft (different arch): near-zero accept
    rate, so every round exercises the partial-accept rewind."""
    cfg = _tiny_cfg(num_layers=1, num_heads=2, num_kv_heads=1, hidden_dim=16, mlp_dim=32)
    model = DecoderLM(cfg)
    params = model.init(jax.random.PRNGKey(9), jnp.ones((1, 4), jnp.int32))["params"]
    return model, params


class TestSpeculativeEngine:
    def test_self_draft_identity_and_exact_full_accept(self, tiny_model):
        """Shared-model self-draft (the smoke config): greedy output
        token-identical to serial generate, accept rate EXACTLY 1.0, both
        pools drained clean."""
        model, params = tiny_model
        specs = [(7, 6), (13, 4), (5, 9), (22, 5)]
        engine = _engine(model, params, spec_k=3)
        rids = [engine.submit(_prompt(n, seed=i), m) for i, (n, m) in enumerate(specs)]
        out = engine.run(max_steps=5000)
        for rid, (n, m) in zip(rids, specs):
            ref = np.asarray(
                generate(model, params, jnp.asarray(_prompt(n, seed=rid))[None], m)
            )[0]
            np.testing.assert_array_equal(out[rid], ref)
        s = engine.ledger.summary()
        assert s["accept_rate"] == 1.0
        assert s["drafted_tokens"] > 0
        assert engine.pool.num_free == engine.pool.num_blocks
        assert engine.draft_pool.num_free == engine.draft_pool.num_blocks

    def test_partial_accepts_stay_token_identical(self, tiny_model, tiny_draft):
        """An independent random draft disagrees with the target almost
        everywhere — near-zero accept — yet greedy output must STILL be
        token-identical to serial generate: rejected proposals leave stale
        K/V that the rewind contract (fill counters roll back, contiguous
        rewrites beat the causal mask) must fully hide."""
        model, params = tiny_model
        draft, dparams = tiny_draft
        specs = [(7, 6), (13, 4), (5, 9), (22, 5), (3, 8)]
        engine = _engine(
            model, params, max_slots=3, spec_k=4, draft_model=draft, draft_params=dparams
        )
        rids = [engine.submit(_prompt(n, seed=i), m) for i, (n, m) in enumerate(specs)]
        out = engine.run(max_steps=5000)
        for rid, (n, m) in zip(rids, specs):
            ref = np.asarray(
                generate(model, params, jnp.asarray(_prompt(n, seed=rid))[None], m)
            )[0]
            np.testing.assert_array_equal(out[rid], ref)
        assert engine.ledger.summary()["accept_rate"] < 0.5  # genuinely partial

    def test_spec_random_load_invariants(self, tiny_model, tiny_draft):
        """The satellite property test: random spec-decode load with
        partial accepts — after EVERY engine step both pools hold
        free + live == capacity, admissions stay strict FIFO, every
        request finishes (starvation-free), and the drained pools are
        pristine."""
        model, params = tiny_model
        draft, dparams = tiny_draft
        rs = np.random.RandomState(13)
        engine = ServeEngine(
            model, params, num_blocks=28, block_size=4, max_slots=3, prefill_chunk=8,
            spec_k=3, draft_model=draft, draft_params=dparams,
        )
        specs = [(int(rs.randint(1, 18)), int(rs.randint(1, 8))) for _ in range(24)]
        rids = [
            engine.submit(_prompt(n, seed=300 + i), m) for i, (n, m) in enumerate(specs)
        ]
        steps = 0
        while not engine.idle and steps < 5000:
            engine.step()
            steps += 1
            for pool in (engine.pool, engine.draft_pool):
                assert pool.num_free + pool.num_live == pool.num_blocks
        out = engine.results()
        assert sorted(out) == sorted(rids), "an admitted request starved"
        for rid, (_, m) in zip(rids, specs):
            assert len(out[rid]) == m
        assert engine.pool.num_free == engine.pool.num_blocks
        assert engine.draft_pool.num_free == engine.draft_pool.num_blocks
        admits = [engine.ledger.records[r]["admitted"] for r in rids]
        assert admits == sorted(admits)  # strict FIFO held

    def test_spec_signature_budget_and_warm_replay(self, tiny_model):
        """Churning spec traffic stays inside the enlarged (draft +
        verify + two-model prefill) TraceGuard budget, and a warm engine
        replaying the same shapes compiles NOTHING new."""
        model, params = tiny_model
        engine = _engine(model, params, max_slots=4, spec_k=3, guard="raise")
        specs = [(5 + 3 * (i % 4), 3 + (i % 3)) for i in range(8)]
        for wave, assert_warm in ((0, False), (1, True)):
            before = engine.compiled_signatures()
            for i, (n, m) in enumerate(specs):
                engine.submit(_prompt(n, seed=100 * wave + i), m)
            engine.run(max_steps=5000)
            if assert_warm:
                assert engine.compiled_signatures() == before
        assert engine.compiled_signatures() <= engine.max_signatures

    def test_spec_eos_truncates_inside_a_round(self, tiny_model):
        """A row whose eos lands mid-round must stop at the eos token
        exactly (device-side in-round truncation + host finish)."""
        model, params = tiny_model
        prompt = _prompt(9, seed=3)
        ref = np.asarray(generate(model, params, jnp.asarray(prompt)[None], 8))[0]
        eos = int(ref[2])
        assert eos not in ref[:2]
        engine = _engine(model, params, spec_k=3, eos_id=eos)
        rid = engine.submit(prompt, 8)
        out = engine.run(max_steps=2000)[rid]
        np.testing.assert_array_equal(out, ref[:3])
        assert engine.pool.num_free == engine.pool.num_blocks
        assert engine.draft_pool.num_free == engine.draft_pool.num_blocks

    def test_reservation_accounts_spec_lookahead(self, tiny_model):
        """Admission reserves prompt + max_new + k worst case; the
        max_seq_len check carries the k+1 speculative slack; and
        needed_blocks covers this round's k-token overshoot."""
        from dmlcloud_tpu.serve.scheduler import _Sequence

        model, params = tiny_model
        engine = _engine(model, params, spec_k=3)  # block_size 4
        rid = engine.submit(_prompt(4), 4)
        seq = engine.scheduler.waiting[0]
        assert engine.scheduler.reservation(seq) == -(-(4 + 4 + 3) // 4)  # 11 slots
        # plain engine reserves less for the same request
        plain = _engine(model, params)
        plain.submit(_prompt(4), 4)
        assert plain.scheduler.reservation(plain.scheduler.waiting[0]) == 2
        # max_seq_len check is spec-aware: 31 + 30 fits plain (61 <= 64)
        # but not with the +k+1 speculative slack (65 > 64)
        with pytest.raises(ValueError, match="spec_k"):
            engine.submit(_prompt(31), 30)
        # needed_blocks: lookahead widens the table the round gathers
        s = _Sequence(req=seq.req, arrival=0.0)
        s.fill = 7
        assert s.needed_blocks(4) == 2  # plain: slots 0..7
        assert s.needed_blocks(4, lookahead=3) == 3  # spec: writes to 10

    def test_spec_rejects_bad_args(self, tiny_model):
        model, params = tiny_model
        with pytest.raises(ValueError, match="together"):
            _engine(model, params, spec_k=2, draft_model=model)
        with pytest.raises(ValueError, match="spec_k"):
            _engine(model, params, draft_model=model, draft_params=params)

    def test_ledger_accept_counters_are_exact(self, tiny_model):
        """Self-draft greedy accepts everything: drafted == rounds * k,
        accepted == drafted, per-request accept_rate == 1.0 — the exact
        on-device counters, fetched once per round with the tokens."""
        model, params = tiny_model
        engine = _engine(model, params, spec_k=3)
        rid = engine.submit(_prompt(6, seed=2), 9)
        engine.run(max_steps=2000)
        rec = engine.ledger.records[rid]
        assert rec["drafted"] > 0 and rec["drafted"] % 3 == 0
        assert rec["accepted"] == rec["drafted"]
        assert engine.ledger.accept_rate(rid) == 1.0
        s = engine.ledger.summary()
        assert s["mean_request_accept_rate"] == 1.0
        assert s["accepted_tokens"] == s["drafted_tokens"]

    def test_spec_journal_spans(self, tiny_model, tmp_path):
        from dmlcloud_tpu.telemetry import journal as journal_mod

        model, params = tiny_model
        j = journal_mod.SpanJournal(tmp_path, rank=0)
        journal_mod.activate(j)
        try:
            engine = _engine(model, params, spec_k=2)
            engine.submit(_prompt(12, seed=1), 5)
            engine.run(max_steps=2000)
        finally:
            journal_mod.deactivate()
        spans = j.tail(512)
        kinds = {rec["kind"] for rec in spans}
        assert {"queue_wait", "prefill", "draft", "verify"} <= kinds
        assert "decode_batch" not in kinds  # spec rounds replace plain decode
        # every verify round pairs with a draft call; prefill drafts are extra
        n_verify = sum(1 for r in spans if r["kind"] == "verify")
        n_draft = sum(1 for r in spans if r["kind"] == "draft")
        assert n_verify >= 1 and n_draft >= n_verify


# ---------------------------------------------------------------------------
# composition: speculative decoding x prefix cache, speculative x LoRA
# ---------------------------------------------------------------------------


class TestSpecPrefixCompose:
    def test_spec_prefix_identity_with_independent_draft(self, tiny_model, tiny_draft):
        """Spec engine + prefix cache: the draft pool has no radix tree —
        draft prefill skips via the TARGET's match length, leaving the
        skipped draft pages unwritten (zeros). Proposals degrade, accept
        rate pays, but the verifier keeps greedy output token-identical
        to serial generate for cold AND warm requests."""
        model, params = tiny_model
        draft, dparams = tiny_draft
        tmpl = _prompt(16, seed=51)
        prompts = [_template_prompt(tmpl, n, 900 + i) for i, n in enumerate((3, 5, 2))]
        engine = _engine(
            model, params, max_slots=1, spec_k=3,
            draft_model=draft, draft_params=dparams, prefix_cache=True,
        )
        rids = [engine.submit(p, 5) for p in prompts]
        engine.run(max_steps=4000)
        for rid, p in zip(rids, prompts):
            ref = np.asarray(generate(model, params, jnp.asarray(p)[None], 5))[0]
            np.testing.assert_array_equal(engine.output(rid), ref)
        # the warm requests really skipped: matched the template's blocks
        assert engine.ledger.records[rids[1]]["cached_tokens"] == 16
        assert engine.pool.num_free + engine.pool.num_live == engine.pool.num_blocks
        assert engine.draft_pool.num_free == engine.draft_pool.num_blocks

    def test_spec_prefix_self_draft_warm_replay(self, tiny_model):
        """Self-draft + prefix: warm template requests stay
        token-identical, and the draft pool (no tree) never leaks."""
        model, params = tiny_model
        tmpl = _prompt(12, seed=52)
        engine = _engine(model, params, max_slots=2, spec_k=3, prefix_cache=True)
        prompts = [_template_prompt(tmpl, n, 950 + i) for i, n in enumerate((2, 4, 3, 5))]
        rids = [engine.submit(p, 6) for p in prompts]
        engine.run(max_steps=4000)
        for rid, p in zip(rids, prompts):
            ref = np.asarray(generate(model, params, jnp.asarray(p)[None], 6))[0]
            np.testing.assert_array_equal(engine.output(rid), ref)
        assert engine.draft_pool.num_free == engine.draft_pool.num_blocks
        assert engine.pool.num_free + engine.pool.num_live == engine.pool.num_blocks


class TestSpecLora:
    """Speculative decoding x multi-tenant LoRA (the ROADMAP item 5
    leftover): the base-model draft proposes WITHOUT the tenant's delta;
    the verify pass scores WITH it — so output must be token-identical to
    the tenant's own (merged) model, at whatever accept rate the
    base-draft agreement yields."""

    def test_spec_tenant_identical_to_merged_model(self, tiny_model):
        model, params = tiny_model
        ad = _randomized_adapter(params, 1, 10)
        aset = AdapterSet({"a": ad}, alpha=4.0, base=params)
        engine = _engine(model, params, spec_k=3, adapters=aset)
        prompt = _prompt(9, seed=53)
        ra = engine.submit(prompt, 6, adapter="a")
        rb = engine.submit(prompt, 6)
        engine.run(max_steps=4000)
        merged = lora_merge(params, ad, alpha=4.0)
        ref_a = np.asarray(generate(model, merged, jnp.asarray(prompt)[None], 6))[0]
        ref_b = np.asarray(generate(model, params, jnp.asarray(prompt)[None], 6))[0]
        np.testing.assert_array_equal(engine.output(ra), ref_a)
        np.testing.assert_array_equal(engine.output(rb), ref_b)
        assert not np.array_equal(ref_a, ref_b)  # the delta genuinely bites
        # base row self-drafts against itself: accepts everything; the
        # tenant row pays accept rate for the delta-blind draft
        s = engine.ledger.summary()
        assert s["drafted_tokens"] > 0
        assert engine.ledger.accept_rate(rb) == 1.0

    def test_spec_lora_mixed_tenants_one_batch(self, tiny_model):
        """Two adapted tenants + base in ONE spec batch decode exactly
        what each decodes alone — no cross-row contamination through the
        shared draft/verify rounds."""
        model, params = tiny_model
        a = _randomized_adapter(params, 1, 10)
        b = _randomized_adapter(params, 2, 20)
        aset = AdapterSet({"a": a, "b": b}, alpha=4.0, base=params)
        prompt = _prompt(9, seed=54)

        def run(specs):
            eng = _engine(model, params, max_slots=4, spec_k=2, adapters=aset)
            rids = [eng.submit(prompt, 5, adapter=s) for s in specs]
            eng.run(max_steps=4000)
            return [eng.output(r) for r in rids]

        together = run(["a", "b", None])
        np.testing.assert_array_equal(together[0], run(["a"])[0])
        np.testing.assert_array_equal(together[1], run(["b"])[0])
        np.testing.assert_array_equal(together[2], run([None])[0])

    def test_spec_lora_prefix_all_compose(self, tiny_model):
        """All three: spec x LoRA x prefix cache. Tenant-namespaced
        sharing, delta-blind drafting, adapter-aware verification — and
        the output is still exactly the merged model's."""
        model, params = tiny_model
        ad = _randomized_adapter(params, 1, 10)
        aset = AdapterSet({"a": ad}, alpha=4.0, base=params)
        engine = _engine(
            model, params, max_slots=1, spec_k=2, adapters=aset, prefix_cache=True
        )
        tmpl = _prompt(12, seed=55)
        p1 = _template_prompt(tmpl, 3, 56)
        p2 = _template_prompt(tmpl, 4, 57)
        r1 = engine.submit(p1, 5, adapter="a")
        r2 = engine.submit(p2, 5, adapter="a")
        r3 = engine.submit(p2, 5)  # base tenant: must not hit "a"'s blocks
        engine.run(max_steps=4000)
        merged = lora_merge(params, ad, alpha=4.0)
        for rid, p in ((r1, p1), (r2, p2)):
            ref = np.asarray(generate(model, merged, jnp.asarray(p)[None], 5))[0]
            np.testing.assert_array_equal(engine.output(rid), ref)
        ref3 = np.asarray(generate(model, params, jnp.asarray(p2)[None], 5))[0]
        np.testing.assert_array_equal(engine.output(r3), ref3)
        assert engine.ledger.records[r2]["cached_tokens"] == 12  # tenant-a warm hit
        assert engine.ledger.records[r3]["cached_tokens"] == 0  # namespaced


# ---------------------------------------------------------------------------
# Medusa mode: draftless speculation off the target's own hidden state (PR 16)
# ---------------------------------------------------------------------------


class TestMedusaEngine:
    """``medusa_k``: up to k tokens per round from lightweight extra decode
    heads on the target's last hidden state — ONE model, ONE block pool,
    ONE k-position forward per round (the next round's proposals ride the
    current round's packed fetch). Same acceptance contract as spec mode
    (greedy survivors token-identical to serial generate), none of the
    draft model's memory."""

    def test_medusa_k1_identity_degenerates_to_plain_decode(self, tiny_model):
        """k=1 has no heads: every round is one 1-position forward through
        the medusa signature — exactly plain decode (nothing drafted, so
        the accept-rate observable is undefined), token-identical to
        serial generate."""
        model, params = tiny_model
        specs = [(7, 6), (13, 4), (5, 9), (22, 5)]
        engine = _engine(model, params, medusa_k=1)
        assert engine.draft_pool is None  # the deleted second pool
        rids = [engine.submit(_prompt(n, seed=i), m) for i, (n, m) in enumerate(specs)]
        out = engine.run(max_steps=5000)
        for rid, (n, m) in zip(rids, specs):
            ref = np.asarray(
                generate(model, params, jnp.asarray(_prompt(n, seed=rid))[None], m)
            )[0]
            np.testing.assert_array_equal(out[rid], ref)
        s = engine.ledger.summary()
        assert s["accept_rate"] is None
        assert s["drafted_tokens"] == 0
        assert engine.pool.num_free == engine.pool.num_blocks

    def test_medusa_random_heads_stay_token_identical(self, tiny_model):
        """Untrained random heads propose near-garbage — accept collapses
        toward zero — yet greedy output must STILL be token-identical:
        rejected proposals leave stale K/V that the fill-counter rewind
        must fully hide (the spec-mode contract, same verifier)."""
        model, params = tiny_model
        # no lm_head warm start: w2 is small random noise, proposals from
        # heads 1..k-1 are unrelated to the target's argmax
        heads = init_medusa_heads(model.cfg, 4, jax.random.PRNGKey(7))
        engine = _engine(model, params, max_slots=3, medusa_k=4, medusa_heads=heads)
        specs = [(7, 6), (13, 4), (5, 9), (22, 5), (3, 8)]
        rids = [engine.submit(_prompt(n, seed=i), m) for i, (n, m) in enumerate(specs)]
        out = engine.run(max_steps=5000)
        for rid, (n, m) in zip(rids, specs):
            ref = np.asarray(
                generate(model, params, jnp.asarray(_prompt(n, seed=rid))[None], m)
            )[0]
            np.testing.assert_array_equal(out[rid], ref)
        s = engine.ledger.summary()
        assert s["drafted_tokens"] > 0  # heads genuinely proposed
        assert s["accept_rate"] < 0.5  # ... and the garbage mostly rejected

    def test_medusa_warm_start_heads_accept_high_on_repetitive_chain(
        self, tiny_model
    ):
        """The accept≈1 end of the contract: lm_head-warm-started heads
        predict "the correction token repeats" — on a greedy chain that
        HAS entered its repeating cycle, that is mostly right, so accept
        climbs toward 1 while output stays token-identical (the identity
        proof must not depend on accept being low)."""
        model, params = tiny_model
        # walk a chain INTO its fixed point first. Which prompt's greedy
        # chain goes constant, and where, is the random model's business
        # (it moved with jax 0.9's init), so look instead of assuming:
        # the first seed whose chain repeats one token for n + 1 steps
        n = 24
        for seed in range(8):
            seed_prompt = _prompt(4, seed=seed)
            chain = np.asarray(generate(model, params, jnp.asarray(seed_prompt)[None], 56))[0]
            starts = [
                w for w in range(1, len(chain) - n + 1)
                if len(set(chain[w - 1 : w + n].tolist())) == 1
            ]
            if starts:
                break
        else:
            pytest.fail("no greedy chain of the tiny model reaches a fixed point: pick other seeds")
        w = starts[0]
        prompt = np.concatenate([seed_prompt, chain[:w]]).astype(np.int32)
        engine = _engine(model, params, medusa_k=3, num_blocks=48)
        rid = engine.submit(prompt, n)
        out = engine.run(max_steps=5000)
        np.testing.assert_array_equal(out[rid], chain[w : w + n])
        assert engine.ledger.summary()["accept_rate"] > 0.8

    def test_medusa_random_load_pool_invariants_per_step(self, tiny_model):
        """The drill property: random Medusa load — after EVERY engine step
        the single pool's ``stats()`` balance holds, ``leaked_blocks()`` is
        zero, and there is never a draft pool. FIFO + starvation-freedom +
        pristine drain, as in spec mode."""
        model, params = tiny_model
        rs = np.random.RandomState(13)
        engine = ServeEngine(
            model, params, num_blocks=28, block_size=4, max_slots=3,
            prefill_chunk=8, medusa_k=3,
        )
        specs = [(int(rs.randint(1, 18)), int(rs.randint(1, 8))) for _ in range(24)]
        rids = [
            engine.submit(_prompt(n, seed=300 + i), m) for i, (n, m) in enumerate(specs)
        ]
        steps = 0
        while not engine.idle and steps < 5000:
            engine.step()
            steps += 1
            st = engine.pool.stats()
            assert st["free"] + st["live"] == st["capacity"]
            assert engine.draft_pool is None
            if engine.idle:  # leak audit is defined at idle (in-flight != leak)
                assert engine.leaked_blocks() == 0
        assert engine.leaked_blocks() == 0
        out = engine.results()
        assert sorted(out) == sorted(rids), "an admitted request starved"
        for rid, (_, m) in zip(rids, specs):
            assert len(out[rid]) == m
        assert engine.pool.num_free == engine.pool.num_blocks
        admits = [engine.ledger.records[r]["admitted"] for r in rids]
        assert admits == sorted(admits)  # strict FIFO held

    def test_medusa_signature_budget_and_warm_replay(self, tiny_model):
        """Churning Medusa traffic stays inside its TraceGuard budget —
        which is SMALLER than spec mode's (no draft signatures, no second
        prefill mirror) — and a warm engine replaying the same shapes
        compiles NOTHING new."""
        model, params = tiny_model
        engine = _engine(model, params, max_slots=4, medusa_k=3, guard="raise")
        spec_engine = _engine(model, params, max_slots=4, spec_k=3)
        assert engine.max_signatures < spec_engine.max_signatures
        specs = [(5 + 3 * (i % 4), 3 + (i % 3)) for i in range(8)]
        for wave, assert_warm in ((0, False), (1, True)):
            before = engine.compiled_signatures()
            for i, (n, m) in enumerate(specs):
                engine.submit(_prompt(n, seed=100 * wave + i), m)
            engine.run(max_steps=5000)
            if assert_warm:
                assert engine.compiled_signatures() == before
        assert engine.compiled_signatures() <= engine.max_signatures

    def test_medusa_mixed_sampling_batch(self, tiny_model):
        """Per-request sampling params ride the Medusa round too: a greedy
        and a sampled row share a batch; the greedy row stays identical to
        serial generate, the sampled row stays in-vocab."""
        model, params = tiny_model
        engine = _engine(model, params, medusa_k=3)
        r_g = engine.submit(_prompt(8, seed=1), 6)
        r_s = engine.submit(_prompt(8, seed=2), 6, temperature=1.1)
        out = engine.run(max_steps=2000)
        ref = np.asarray(generate(model, params, jnp.asarray(_prompt(8, seed=1))[None], 6))[0]
        np.testing.assert_array_equal(out[r_g], ref)
        assert ((out[r_s] >= 0) & (out[r_s] < model.cfg.vocab_size)).all()

    def test_medusa_lora_prefix_all_compose(self, tiny_model):
        """All three: Medusa x LoRA x prefix cache (the Medusa mirror of
        ``TestSpecLora.test_spec_lora_prefix_all_compose``). The heads
        propose off the ADAPTED hidden state, verification is adapter-
        aware, sharing stays tenant-namespaced — and the output is still
        exactly the merged model's."""
        model, params = tiny_model
        ad = _randomized_adapter(params, 1, 10)
        aset = AdapterSet({"a": ad}, alpha=4.0, base=params)
        engine = _engine(
            model, params, max_slots=1, medusa_k=2, adapters=aset, prefix_cache=True
        )
        tmpl = _prompt(12, seed=55)
        p1 = _template_prompt(tmpl, 3, 56)
        p2 = _template_prompt(tmpl, 4, 57)
        r1 = engine.submit(p1, 5, adapter="a")
        r2 = engine.submit(p2, 5, adapter="a")
        r3 = engine.submit(p2, 5)  # base tenant: must not hit "a"'s blocks
        engine.run(max_steps=4000)
        merged = lora_merge(params, ad, alpha=4.0)
        for rid, p in ((r1, p1), (r2, p2)):
            ref = np.asarray(generate(model, merged, jnp.asarray(p)[None], 5))[0]
            np.testing.assert_array_equal(engine.output(rid), ref)
        ref3 = np.asarray(generate(model, params, jnp.asarray(p2)[None], 5))[0]
        np.testing.assert_array_equal(engine.output(r3), ref3)
        assert engine.ledger.records[r2]["cached_tokens"] == 12  # tenant-a warm hit
        assert engine.ledger.records[r3]["cached_tokens"] == 0  # namespaced
        assert engine.draft_pool is None
        assert engine.leaked_blocks() == 0

    def test_medusa_rejects_bad_args(self, tiny_model):
        model, params = tiny_model
        with pytest.raises(ValueError, match="medusa_k"):
            _engine(model, params, medusa_k=-1)
        with pytest.raises(ValueError, match="mutually exclusive"):
            _engine(model, params, spec_k=2, medusa_k=2)
        with pytest.raises(ValueError, match="medusa_heads"):
            heads = init_medusa_heads(model.cfg, 2, jax.random.PRNGKey(0))
            _engine(model, params, medusa_heads=heads)


# ---------------------------------------------------------------------------
# chaos x speculative decoding (PR 13 satellite)
# ---------------------------------------------------------------------------


class TestSpecChaos:
    def test_draft_fault_degrades_every_round_to_plain_decode(self, tiny_model, tiny_draft):
        """The draft is an optimization, not a dependency: with EVERY
        draft call failing, no round drafts a token (accept counters stay
        exactly zero) yet every request completes token-identical to
        serial generate."""
        model, params = tiny_model
        draft, dparams = tiny_draft
        engine = _engine(
            model, params, max_slots=2, spec_k=3, draft_model=draft, draft_params=dparams
        )
        monkey = ChaosMonkey(seed=53, p_fault=1.0, fault_points=("draft",))
        monkey.attach(engine)
        specs = [(5, 6), (9, 4), (4, 7)]
        rids = [engine.submit(_prompt(n, seed=900 + i), m) for i, (n, m) in enumerate(specs)]
        out = engine.run(max_steps=3000)
        monkey.detach()
        s = engine.ledger.summary()
        assert s["drafted_tokens"] == 0 and s["accepted_tokens"] == 0
        for i, (rid, (n, m)) in enumerate(zip(rids, specs)):
            assert engine.status(rid) == "ok"
            ref = np.asarray(
                generate(model, params, jnp.asarray(_prompt(n, seed=900 + i))[None], m)
            )[0]
            np.testing.assert_array_equal(out[rid], ref)
        assert engine.pool.num_free == engine.pool.num_blocks
        assert engine.draft_pool.num_free == engine.draft_pool.num_blocks

    def test_draft_fault_once_then_speculation_resumes(self, tiny_model):
        """After a single degraded round (self-draft engine), later rounds
        draft again — the accept counters move and output identity holds."""
        model, params = tiny_model
        engine = _engine(model, params, max_slots=2, spec_k=2)
        monkey = ChaosMonkey(seed=59, p_fault=1.0, fault_points=("draft",), max_faults=1)
        monkey.attach(engine)
        rids = [engine.submit(_prompt(5 + 2 * i, seed=950 + i), 6) for i in range(3)]
        out = engine.run(max_steps=3000)
        monkey.detach()
        assert monkey.faults == 1
        s = engine.ledger.summary()
        assert s["drafted_tokens"] > 0  # speculation resumed after the fault
        # self-draft: every drafted token the target still needs is accepted;
        # only end-of-sequence truncation (draft k, need < k) trims the rate
        assert s["accept_rate"] >= 0.8
        for i, rid in enumerate(rids):
            assert engine.status(rid) == "ok"
            ref = np.asarray(
                generate(model, params, jnp.asarray(_prompt(5 + 2 * i, seed=950 + i))[None], 6)
            )[0]
            np.testing.assert_array_equal(out[rid], ref)

    def test_verify_fault_errors_only_its_batch(self, tiny_model):
        """A verify failure is a REAL step failure: exactly the rows in
        that round error; requests outside the batch finish ok and both
        pools drain clean."""
        model, params = tiny_model
        engine = _engine(model, params, max_slots=2, spec_k=2)
        monkey = ChaosMonkey(seed=61, p_fault=1.0, fault_points=("verify",), max_faults=1)
        monkey.attach(engine)
        rids = [engine.submit(_prompt(4, seed=970 + i), 5) for i in range(3)]
        engine.run(max_steps=3000)
        monkey.detach()
        statuses = [engine.status(r) for r in rids]
        assert statuses.count("error") >= 1  # the faulted round's rows
        assert statuses.count("ok") == len(rids) - statuses.count("error")
        for i, rid in enumerate(rids):
            if statuses[i] == "ok":
                ref = np.asarray(
                    generate(model, params, jnp.asarray(_prompt(4, seed=970 + i))[None], 5)
                )[0]
                np.testing.assert_array_equal(engine.output(rid), ref)
        assert engine.pool.num_free == engine.pool.num_blocks
        assert engine.draft_pool.num_free == engine.draft_pool.num_blocks
