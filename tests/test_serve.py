"""Continuous-batching serving engine (dmlcloud_tpu/serve/).

The load-bearing contracts, each tested here:

- the block pool never leaks or double-frees (randomized 1k-op property
  test; the free+live==capacity invariant survives arbitrary admit/finish
  interleavings);
- greedy engine output is TOKEN-IDENTICAL to serial ``generate()`` for the
  same prompts — through slot churn, chunked prefill, and EOS early-exit;
- no starvation: every admitted request finishes, FIFO order holds, and
  the pool is clean when the queue drains;
- bounded signatures: churning traffic never compiles past the engine's
  TraceGuard budget, and a warm engine never recompiles mid-run;
- multi-tenant LoRA: two tenants in one batch decode exactly what each
  decodes alone (no cross-row contamination), and the null adapter is
  exactly the base model;
- the latency ledger and the ``queue_wait``/``prefill``/``decode_batch``
  journal spans record what actually happened.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dmlcloud_tpu.models.generate import decode_step, generate, init_cache
from dmlcloud_tpu.models.lora import LoraPair, lora_init, lora_merge
from dmlcloud_tpu.models.speculative import init_medusa_heads
from dmlcloud_tpu.models.transformer import DecoderLM, TransformerConfig
from dmlcloud_tpu.ops.paged_attention import gather_pages, scatter_tokens
from dmlcloud_tpu.serve import (
    AdapterSet,
    ChaosMonkey,
    KVBlockPool,
    PoolExhausted,
    PrefixCache,
    ServeEngine,
    TERMINAL_STATUSES,
)


def _tiny_cfg(**kw):
    base = dict(
        vocab_size=61,
        num_layers=2,
        num_heads=4,
        num_kv_heads=2,
        head_dim=8,
        hidden_dim=32,
        mlp_dim=64,
        max_seq_len=64,
        dtype=jnp.float32,  # exact arithmetic: token-identity is bitwise-ish
    )
    base.update(kw)
    return TransformerConfig(**base)


# tiny_model (the shared 61-vocab serve LM) comes from conftest.py,
# session-scoped: test_serve_router reuses the same instance.


def _prompt(n, seed=0):
    return np.random.RandomState(seed).randint(0, 61, size=(n,)).astype(np.int32)


def _engine(model, params, **kw):
    kw.setdefault("num_blocks", 64)
    kw.setdefault("block_size", 4)
    kw.setdefault("max_slots", 2)
    kw.setdefault("prefill_chunk", 8)
    return ServeEngine(model, params, **kw)


# ---------------------------------------------------------------------------
# block pool
# ---------------------------------------------------------------------------


class TestKVBlockPool:
    def _pool(self, n=8):
        return KVBlockPool(2, 2, 8, num_blocks=n, block_size=4, dtype=jnp.float32)

    def test_alloc_free_roundtrip(self):
        pool = self._pool()
        blocks = pool.alloc(3)
        assert len(blocks) == len(set(blocks)) == 3
        assert pool.num_free == 5 and pool.num_live == 3
        pool.free(blocks)
        assert pool.num_free == 8 and pool.num_live == 0

    def test_exhaustion_raises_and_allocates_nothing(self):
        pool = self._pool(4)
        pool.alloc(3)
        with pytest.raises(PoolExhausted):
            pool.alloc(2)
        assert pool.num_free == 1  # the failed alloc took nothing

    def test_double_free_raises(self):
        pool = self._pool()
        blocks = pool.alloc(2)
        pool.free(blocks)
        with pytest.raises(ValueError, match="not live"):
            pool.free([blocks[0]])

    def test_foreign_block_raises(self):
        pool = self._pool(4)
        pool.alloc(1)
        with pytest.raises(ValueError, match="not live"):
            pool.free([99])

    def test_blocks_for(self):
        pool = self._pool()
        assert pool.blocks_for(1) == 1
        assert pool.blocks_for(4) == 1
        assert pool.blocks_for(5) == 2

    def test_random_1k_ops_never_leak_or_double_hand(self):
        """1k random admit/finish operations: every handed-out block is
        unique among live blocks, free+live == capacity at every step, and
        a full drain restores the pristine pool."""
        rs = np.random.RandomState(7)
        pool = self._pool(16)
        live: list[list[int]] = []
        for _ in range(1000):
            if live and (rs.rand() < 0.45 or pool.num_free == 0):
                pool.free(live.pop(rs.randint(len(live))))
            else:
                want = int(rs.randint(1, 5))
                if want > pool.num_free:
                    with pytest.raises(PoolExhausted):
                        pool.alloc(want)
                else:
                    live.append(pool.alloc(want))
            handed = [b for seq in live for b in seq]
            assert len(handed) == len(set(handed)), "same block handed out twice"
            assert pool.num_free + pool.num_live == 16
            assert pool.num_live == len(handed)
        while live:
            pool.free(live.pop())
        assert pool.num_free == 16 and pool.num_live == 0


# ---------------------------------------------------------------------------
# paged gather/scatter indexing
# ---------------------------------------------------------------------------


class TestPagedIndexing:
    def test_scatter_gather_roundtrip(self):
        pool = jnp.zeros((5, 4, 2, 3), jnp.float32)
        tables = jnp.asarray([[3, 1]], jnp.int32)  # row 0 owns blocks 3 then 1
        vals = jnp.arange(6 * 2 * 3, dtype=jnp.float32).reshape(1, 6, 2, 3)
        positions = jnp.arange(6, dtype=jnp.int32)[None]  # fills block 3 + half of 1
        pool = scatter_tokens(pool, tables, positions, vals)
        got = gather_pages(pool, tables)  # [1, 8, 2, 3]
        np.testing.assert_array_equal(np.asarray(got[0, :6]), np.asarray(vals[0]))
        np.testing.assert_array_equal(np.asarray(got[0, 6:]), 0)

    def test_sentinel_writes_dropped(self):
        pool = jnp.ones((2, 4, 1, 1), jnp.float32)
        tables = jnp.asarray([[2, 2]], jnp.int32)  # sentinel-only row (OOB)
        vals = jnp.full((1, 3, 1, 1), 7.0)
        out = scatter_tokens(pool, tables, jnp.asarray([[0, 1, 2]], jnp.int32), vals)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(pool))  # untouched

    def test_position_past_table_width_redirects_to_sentinel(self):
        """A position whose logical block exceeds the table width must NOT
        clip into the row's last real block."""
        pool = jnp.zeros((3, 2, 1, 1), jnp.float32)
        tables = jnp.asarray([[1]], jnp.int32)  # one block: positions 0-1
        vals = jnp.full((1, 1, 1, 1), 5.0)
        out = scatter_tokens(pool, tables, jnp.asarray([[4]], jnp.int32), vals)
        np.testing.assert_array_equal(np.asarray(out), 0.0)  # dropped, block 1 intact

    def test_negative_position_dropped(self):
        """A negative position (a padded row of a spec round's 2-token
        draft pass) maps below the table and must be dropped, never
        wrapped into a real block."""
        pool = jnp.zeros((3, 2, 1, 1), jnp.float32)
        tables = jnp.asarray([[0, 1]], jnp.int32)
        vals = jnp.full((1, 2, 1, 1), 5.0)
        out = scatter_tokens(pool, tables, jnp.asarray([[-1, 0]], jnp.int32), vals)
        assert float(out[0, 0, 0, 0]) == 5.0  # position 0 landed
        assert float(np.asarray(out).sum()) == 5.0  # position -1 dropped

    def test_multi_token_scatter_through_tables(self):
        """The spec round's k+1-token write: several positions per row in
        ONE scatter land in the right (block, slot) pairs, across block
        boundaries."""
        pool = jnp.zeros((4, 2, 1, 1), jnp.float32)
        tables = jnp.asarray([[2, 0]], jnp.int32)  # logical 0-1 -> block 2, 2-3 -> block 0
        positions = jnp.asarray([[1, 2, 3]], jnp.int32)  # straddles the boundary
        vals = jnp.asarray([10.0, 20.0, 30.0]).reshape(1, 3, 1, 1)
        out = scatter_tokens(pool, tables, positions, vals)
        assert float(out[2, 1, 0, 0]) == 10.0  # position 1: block 2, slot 1
        assert float(out[0, 0, 0, 0]) == 20.0  # position 2: block 0, slot 0
        assert float(out[0, 1, 0, 0]) == 30.0  # position 3: block 0, slot 1
        got = gather_pages(out, tables)
        np.testing.assert_array_equal(
            np.asarray(got[0, 1:4, 0, 0]), [10.0, 20.0, 30.0]
        )


# ---------------------------------------------------------------------------
# engine vs serial generate: token identity
# ---------------------------------------------------------------------------


class TestEngineIdentity:
    def test_ragged_batch_matches_serial_generate(self, tiny_model):
        """Four ragged requests through 2 slots (continuous churn, chunked
        prefill for the 22-token prompt) — every output token-identical to
        serial generate of the same prompt."""
        model, params = tiny_model
        specs = [(7, 6), (13, 4), (5, 9), (22, 5)]
        engine = _engine(model, params)
        rids = [engine.submit(_prompt(n, seed=i), m) for i, (n, m) in enumerate(specs)]
        out = engine.run()
        for rid, (n, m) in zip(rids, specs):
            ref = np.asarray(
                generate(model, params, jnp.asarray(_prompt(n, seed=rid))[None], m)
            )[0]
            np.testing.assert_array_equal(out[rid], ref)
        # everything drained: slots and blocks all recycled
        assert engine.idle
        assert engine.pool.num_free == engine.pool.num_blocks

    def test_eos_frees_slot_early(self, tiny_model):
        model, params = tiny_model
        prompt = _prompt(9, seed=3)
        ref = np.asarray(generate(model, params, jnp.asarray(prompt)[None], 8))[0]
        eos = int(ref[2])
        assert eos not in ref[:2]  # the crafted eos fires at position 2
        engine = _engine(model, params, eos_id=eos)
        rid = engine.submit(prompt, 8)
        out = engine.run()[rid]
        np.testing.assert_array_equal(out, ref[:3])  # eos emitted, then stop
        assert engine.pool.num_free == engine.pool.num_blocks  # blocks freed

    def test_int8_quantized_params_serve_identically(self, tiny_model):
        """A quantize_tree'd params tree drops into the engine (which
        prepares it once via prepare_decode_params — the PR-6 fused-int8
        decode win, pre-paid) and decodes exactly what serial generate
        decodes from the same quantized tree."""
        from dmlcloud_tpu.models.quant import quantize_tree

        model, params = tiny_model
        qparams = quantize_tree(params)
        prompt = _prompt(8, seed=4)
        engine = _engine(model, qparams)
        rid = engine.submit(prompt, 5)
        out = engine.run()[rid]
        ref = np.asarray(generate(model, qparams, jnp.asarray(prompt)[None], 5))[0]
        np.testing.assert_array_equal(out, ref)

    def test_decode_step_is_the_shared_primitive(self, tiny_model):
        """decode_step == model.apply with a cache — generate, speculative
        and the engine all route through it."""
        model, params = tiny_model
        prompt = jnp.asarray(_prompt(6))[None]
        cache = init_cache(model.cfg, 1, 10, dtype=jnp.float32)
        logits, new_cache = decode_step(model, params, prompt, cache, offset=0, attend_len=6)
        ref_logits, ref_cache = model.apply(
            {"params": params}, prompt, cache=cache, offset=0, attend_len=6
        )
        np.testing.assert_array_equal(np.asarray(logits), np.asarray(ref_logits))
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)),
            new_cache, ref_cache,
        )


# ---------------------------------------------------------------------------
# scheduler properties
# ---------------------------------------------------------------------------


class TestSchedulerProperties:
    @pytest.mark.slow  # random-load property drill; per-step invariants also locked by the cheap FIFO/EOS unit tests
    def test_no_starvation_under_random_load(self, tiny_model):
        """30 random requests into 3 slots over a tight pool: every
        admitted request finishes, admissions are strict FIFO, the pool
        drains clean."""
        model, params = tiny_model
        rs = np.random.RandomState(11)
        engine = ServeEngine(
            model, params, num_blocks=24, block_size=4, max_slots=3, prefill_chunk=8
        )
        specs = [(int(rs.randint(1, 20)), int(rs.randint(1, 8))) for _ in range(30)]
        rids = [
            engine.submit(_prompt(n, seed=100 + i), m) for i, (n, m) in enumerate(specs)
        ]
        out = engine.run(max_steps=5000)
        assert sorted(out) == sorted(rids), "an admitted request starved"
        for rid, (_, m) in zip(rids, specs):
            assert len(out[rid]) == m
        assert engine.pool.num_free == engine.pool.num_blocks
        # FIFO: admission times are non-decreasing in submission order
        admits = [engine.ledger.records[r]["admitted"] for r in rids]
        assert admits == sorted(admits)

    def test_oversized_request_rejected_at_submit(self, tiny_model):
        model, params = tiny_model
        engine = ServeEngine(model, params, num_blocks=4, block_size=4, max_slots=2)
        with pytest.raises(ValueError, match="blocks worst-case"):
            engine.submit(_prompt(30), 30)  # needs 15 blocks, pool has 4

    def test_prompt_plus_new_validated_against_max_seq_len(self, tiny_model):
        model, params = tiny_model
        engine = _engine(model, params)
        with pytest.raises(ValueError, match="max_seq_len"):
            engine.submit(_prompt(40), 40)  # 80 > max_seq_len 64


# ---------------------------------------------------------------------------
# decode-shape bucketing: bounded signatures, zero mid-run recompiles
# ---------------------------------------------------------------------------


class TestBucketing:
    @pytest.mark.slow  # shape-churn property drill; the spec/medusa budget + warm-replay locks stay tier-1
    def test_churning_traffic_stays_inside_the_signature_budget(self, tiny_model):
        """Random churn (ragged prompts, ragged budgets, slots freeing and
        refilling) never compiles past max_signatures — TraceGuard is
        armed to RAISE, so a leak is an error, not a log line."""
        model, params = tiny_model
        engine = _engine(model, params, max_slots=4, guard="raise")
        rs = np.random.RandomState(5)
        for i in range(12):
            engine.submit(_prompt(int(rs.randint(1, 25)), seed=200 + i), int(rs.randint(1, 9)))
        engine.run(max_steps=5000)
        assert engine.idle
        assert engine.compiled_signatures() <= engine.max_signatures

    def test_warm_engine_never_recompiles(self, tiny_model):
        """After one pass of traffic, replaying the same request shapes
        (fresh token content) causes ZERO new compilations — the
        0-mid-run-recompiles contract for a warmed-up server."""
        model, params = tiny_model
        engine = _engine(model, params, max_slots=4)
        specs = [(5 + 3 * (i % 4), 3 + (i % 3)) for i in range(8)]
        for wave, assert_warm in ((0, False), (1, True)):
            before = engine.compiled_signatures()
            for i, (n, m) in enumerate(specs):
                engine.submit(_prompt(n, seed=100 * wave + i), m)
            engine.run(max_steps=5000)
            if assert_warm:
                assert engine.compiled_signatures() == before


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

    @pytest.mark.slow  # heavyweight random-draft drill; accept~0 identity also locked by the self-draft + eos round tests
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

    @pytest.mark.slow  # random-load property drill over both pools
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

    @pytest.mark.slow  # span-kind drill over a full spec run; journal emission locked by the cheap telemetry test
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
# per-request sampling params
# ---------------------------------------------------------------------------


class TestPerRequestSampling:
    def test_mixed_batch_greedy_rows_bit_identical(self, tiny_model):
        """Greedy and sampled tenants share one batch; the greedy rows
        must decode exactly what serial generate decodes — the
        batched-sampler lock."""
        model, params = tiny_model
        engine = _engine(model, params, max_slots=4)
        r_g = engine.submit(_prompt(8, seed=1), 6)
        r_s1 = engine.submit(_prompt(8, seed=2), 6, temperature=0.9, top_k=12)
        r_s2 = engine.submit(_prompt(8, seed=3), 6, temperature=1.3, top_p=0.8)
        out = engine.run()
        ref = np.asarray(generate(model, params, jnp.asarray(_prompt(8, seed=1))[None], 6))[0]
        np.testing.assert_array_equal(out[r_g], ref)
        for r in (r_s1, r_s2):
            assert out[r].shape == (6,)
            assert ((out[r] >= 0) & (out[r] < model.cfg.vocab_size)).all()

    def test_per_request_eos(self, tiny_model):
        """Two requests with the same prompt, different eos: each stops at
        its OWN eos — eos is per-row data, not engine state."""
        model, params = tiny_model
        prompt = _prompt(9, seed=3)
        ref = np.asarray(generate(model, params, jnp.asarray(prompt)[None], 8))[0]
        eos = int(ref[2])
        engine = _engine(model, params)
        ra = engine.submit(prompt, 8, eos_id=eos)
        rb = engine.submit(prompt, 8)
        out = engine.run()
        np.testing.assert_array_equal(out[ra], ref[:3])
        np.testing.assert_array_equal(out[rb], ref)

    def test_request_params_ride_the_request(self, tiny_model):
        """Request carries the overrides; unset knobs inherit the engine
        defaults."""
        model, params = tiny_model
        engine = _engine(model, params, temperature=0.5, top_k=7)
        rid = engine.submit(_prompt(4), 2, temperature=0.0)
        seq = engine.scheduler.waiting[0]
        assert seq.req.id == rid
        assert seq.temperature == 0.0  # override
        assert seq.top_k == 7  # engine default inherited
        assert seq.eos_id == -1

    @pytest.mark.slow  # mixed-sampling drill; greedy-row bit-identity and medusa mixed-sampling locks stay tier-1
    def test_spec_mixed_sampling_batch(self, tiny_model):
        """Per-row params flow through the spec verify step too: a greedy
        and a sampled row share a spec batch; the greedy row stays
        identical to serial generate."""
        model, params = tiny_model
        engine = _engine(model, params, spec_k=3)
        r_g = engine.submit(_prompt(8, seed=1), 6)
        r_s = engine.submit(_prompt(8, seed=2), 6, temperature=1.1)
        out = engine.run(max_steps=2000)
        ref = np.asarray(generate(model, params, jnp.asarray(_prompt(8, seed=1))[None], 6))[0]
        np.testing.assert_array_equal(out[r_g], ref)
        assert ((out[r_s] >= 0) & (out[r_s] < model.cfg.vocab_size)).all()


# ---------------------------------------------------------------------------
# multi-tenant LoRA serving
# ---------------------------------------------------------------------------


def _randomized_adapter(params, init_seed, b_seed):
    """lora_init zeroes b (merged == base); randomize b so deltas bite."""
    tree = lora_init(jax.random.PRNGKey(init_seed), params, rank=2, in_axes=1)
    key = [jax.random.PRNGKey(b_seed)]

    def f(x):
        if isinstance(x, LoraPair):
            key[0], sub = jax.random.split(key[0])
            return x.replace(b=jax.random.normal(sub, x.b.shape, jnp.float32) * 0.05)
        return x

    return jax.tree_util.tree_map(
        f, tree, is_leaf=lambda x: x is None or isinstance(x, LoraPair)
    )


class TestAdapterSet:
    @pytest.fixture(scope="class")
    def adapters(self, tiny_model):
        _, params = tiny_model
        a = _randomized_adapter(params, 1, 10)
        b = _randomized_adapter(params, 2, 20)
        return a, b, AdapterSet({"a": a, "b": b}, alpha=4.0, base=params)

    def _run(self, tiny_model, aset, specs):
        model, params = tiny_model
        engine = _engine(model, params, max_slots=4, adapters=aset)
        prompt = _prompt(9, seed=9)
        rids = [engine.submit(prompt, 6, adapter=s) for s in specs]
        out = engine.run()
        return [out[r] for r in rids]

    @pytest.mark.slow  # heavyweight two-tenant drill; adapter math locked by the lora-merge/null-adapter units
    def test_two_tenants_in_one_batch_match_each_alone(self, tiny_model, adapters):
        _, _, aset = adapters
        both = self._run(tiny_model, aset, ["a", "b", None])
        alone_a = self._run(tiny_model, aset, ["a"])[0]
        alone_b = self._run(tiny_model, aset, ["b"])[0]
        alone_base = self._run(tiny_model, aset, [None])[0]
        np.testing.assert_array_equal(both[0], alone_a)
        np.testing.assert_array_equal(both[1], alone_b)
        np.testing.assert_array_equal(both[2], alone_base)
        # and the tenants genuinely decode differently (non-vacuous)
        assert not np.array_equal(alone_a, alone_b)
        assert not np.array_equal(alone_a, alone_base)

    def test_null_adapter_is_exactly_the_base_model(self, tiny_model, adapters):
        model, params = tiny_model
        _, _, aset = adapters
        out = self._run(tiny_model, aset, [None])[0]
        ref = np.asarray(generate(model, params, jnp.asarray(_prompt(9, seed=9))[None], 6))[0]
        np.testing.assert_array_equal(out, ref)

    def test_batched_application_matches_lora_merge(self, tiny_model, adapters):
        """The merge-free (x@a)@b order decodes the same tokens as
        lora_merge + generate (fp32: associativity noise is far below the
        greedy argmax margins)."""
        model, params = tiny_model
        ad_a, _, aset = adapters
        out = self._run(tiny_model, aset, ["a"])[0]
        merged = lora_merge(params, ad_a, alpha=4.0)
        ref = np.asarray(generate(model, merged, jnp.asarray(_prompt(9, seed=9))[None], 6))[0]
        np.testing.assert_array_equal(out, ref)

    def test_wrong_factorization_rejected(self, tiny_model):
        _, params = tiny_model
        legacy = _randomized_adapter(params, 1, 10)
        bad = lora_init(jax.random.PRNGKey(3), params, rank=2)  # all-but-last split
        with pytest.raises(ValueError, match="in_axes=1"):
            AdapterSet({"bad": bad}, base=params)
        # sanity: the serving split passes the same check
        AdapterSet({"ok": legacy}, base=params)

    def test_unknown_adapter_name_raises(self, tiny_model, adapters):
        model, params = tiny_model
        _, _, aset = adapters
        engine = _engine(model, params, adapters=aset)
        with pytest.raises(KeyError, match="unknown adapter"):
            engine.submit(_prompt(4), 4, adapter="nope")
        engine2 = _engine(model, params)  # no AdapterSet at all
        with pytest.raises(ValueError, match="no AdapterSet"):
            engine2.submit(_prompt(4), 4, adapter="a")


# ---------------------------------------------------------------------------
# telemetry: ledger + journal spans
# ---------------------------------------------------------------------------


class TestServeTelemetry:
    def test_ledger_records_ttft_and_queue(self, tiny_model):
        model, params = tiny_model
        engine = _engine(model, params, max_slots=1)  # force queueing
        for i in range(3):
            engine.submit(_prompt(6, seed=i), 4)
        engine.run()
        s = engine.ledger.summary()
        assert s["requests"] == s["completed"] == 3
        assert s["total_tokens"] == 12
        assert s["p50_ttft_s"] > 0 and s["p99_ttft_s"] >= s["p50_ttft_s"]
        assert s["max_queue_depth"] >= 1  # slots=1: somebody waited
        assert s["tokens_per_sec"] > 0
        # queued requests waited longer than the first
        recs = engine.ledger.records
        assert recs[2]["admitted"] - recs[2]["arrival"] > 0

    def test_journal_spans_emitted(self, tiny_model, tmp_path):
        from dmlcloud_tpu.telemetry import journal as journal_mod

        model, params = tiny_model
        j = journal_mod.SpanJournal(tmp_path, rank=0)
        journal_mod.activate(j)
        try:
            engine = _engine(model, params)
            engine.submit(_prompt(12, seed=1), 4)
            engine.run()
        finally:
            journal_mod.deactivate()
        kinds = {rec["kind"] for rec in j.tail(256)}
        assert {"queue_wait", "prefill", "decode_batch"} <= kinds
        pre = [r for r in j.tail(256) if r["kind"] == "prefill"]
        assert sum(r["chunk"] for r in pre) == 12  # whole prompt, chunked


    def test_call_spans_tile_their_call_and_the_step_encloses_them(self, tiny_model, tmp_path):
        """Every device call's span is tiled by call_upload, call_launch and
        call_fetch, preceded by its call_build, and all of them lie inside
        the engine_step span of the step that made them."""
        from dmlcloud_tpu.telemetry import journal as journal_mod

        model, params = tiny_model
        j = journal_mod.activate(journal_mod.SpanJournal(tmp_path, ring_size=4096))
        try:
            engine = _engine(model, params)
            engine.submit(_prompt(12, seed=1), 4)
            engine.submit(_prompt(5, seed=2), 3)
            steps = 0
            while not engine.idle:
                engine.step()
                steps += 1
        finally:
            journal_mod.deactivate()
        recs = j.tail(4096)
        end = lambda r: r["ts"] + r["dur"]
        close = lambda a, b: abs(a - b) < 2e-6  # ts is rounded to the microsecond
        by_kind = lambda k: sorted((r for r in recs if r["kind"] == k), key=lambda r: r["ts"])
        calls = sorted((r for r in recs if r["kind"] in ("prefill", "decode_batch")), key=lambda r: r["ts"])
        parts = {k: by_kind(k) for k in ("call_build", "call_upload", "call_launch", "call_fetch")}
        assert len(calls) >= 4 and all(len(v) == len(calls) for v in parts.values())
        for i, call in enumerate(calls):
            build, up, launch, fetch = (parts[k][i] for k in ("call_build", "call_upload", "call_launch", "call_fetch"))
            for part in (build, up, launch, fetch):
                assert part["parent"] == call["kind"] and part["bucket"] == call["bucket"]
                assert part["blocks"] == call["blocks"]
            assert close(up["ts"], call["ts"]) and close(end(up), launch["ts"])
            assert close(end(launch), fetch["ts"]) and close(end(fetch), end(call))
            assert up["dur"] + launch["dur"] + fetch["dur"] == pytest.approx(call["dur"], abs=1e-8)
            assert close(end(build), call["ts"]) and build["ts"] <= call["ts"]
        engine_steps = by_kind("engine_step")
        assert len(engine_steps) == steps
        for r in calls + [x for v in parts.values() for x in v]:
            assert any(s["ts"] - 2e-6 <= r["ts"] and end(r) <= end(s) + 2e-6 for s in engine_steps), r
        assert {r["bucket"] for r in calls if r["kind"] == "prefill"} == {1}

    def test_no_journal_no_span_is_built(self, tiny_model, monkeypatch):
        """Off means off: with no journal armed a step reaches neither an
        emit nor the code that builds a call's labels and lists."""
        from dmlcloud_tpu.serve import engine as engine_mod
        from dmlcloud_tpu.telemetry import journal as journal_mod

        reached = []
        monkeypatch.setattr(engine_mod.ServeEngine, "_emit_call",
                            staticmethod(lambda *a, **k: reached.append("emit_call")))
        monkeypatch.setattr(journal_mod.SpanJournal, "emit", lambda *a, **k: reached.append("emit"))
        assert journal_mod.active_journal() is None
        model, params = tiny_model
        engine = _engine(model, params)
        engine.submit(_prompt(12, seed=1), 4)
        engine.run()
        assert engine.ledger.summary()["completed"] == 1 and reached == []

    def test_each_engine_owns_its_named_trace_cache(self, tiny_model):
        """The jitted step carries its function's name (the profile's module
        reads jit__paged_step) and is still a fresh object per engine: one
        engine's compiles never count against another's TraceGuard budget."""
        model, params = tiny_model
        a, b = _engine(model, params, guard="raise"), _engine(model, params, guard="raise")
        assert a._step_fn._fn is not b._step_fn._fn
        assert a._step_fn._fn.__name__ == "_paged_step"
        a.submit(_prompt(9, seed=3), 3)
        a.run()
        assert a.compiled_signatures() > 0 and b.compiled_signatures() == 0
        b.submit(_prompt(9, seed=3), 3)
        b.run()
        assert b.compiled_signatures() == a.compiled_signatures() <= b.max_signatures

    def test_phase_map_of_a_paged_step_signature(self, tiny_model):
        """engine.phase_map compiles one signature on demand: every scope of
        the serve step is a phase in it, none with a direction, the rest is
        reported, and the engine's own signature count does not move."""
        from dmlcloud_tpu.utils.profiling import _hlo_computations, phase_of

        model, params = tiny_model
        engine = _engine(model, params)
        engine.submit(_prompt(6, seed=4), 2)
        engine.run()
        before = engine.compiled_signatures()
        decode = engine.phase_map(2, 4)
        prefill = engine.phase_map(1, 4, prefill=True)
        assert engine.compiled_signatures() == before
        want = {"embed", "norm", "attn_proj", "kv_write", "kv_gather", "attention", "mlp", "head", "sampling"}
        for m in (decode, prefill):
            assert {p for p, _ in m.values() if p} == want
            assert {d for p, d in m.values() if p} == {"-"}
            rest = [n for n, (p, _) in m.items() if p is None]
            assert rest and len(rest) < len(m)
        specs = engine._paged_step_specs(2, 4, 1)
        text = engine._step_fn._fn.lower(*specs, model=engine.model).compile().as_text()
        assert text.startswith("HloModule jit__paged_step")
        for instructions in _hlo_computations(text).values():
            for name, op_name, _, _ in instructions:
                if op_name and phase_of(op_name)[0]:
                    assert decode[name] == phase_of(op_name), (name, op_name)

    def test_arrival_counts_from_when_the_request_was_due(self, tiny_model, tmp_path):
        """A caller that queued the request itself passes the time it was
        due: the ledger's arrival, the queue_wait span and TTFT count from
        it; a time after the engine's clock is refused."""
        import time

        from dmlcloud_tpu.telemetry import journal as journal_mod

        model, params = tiny_model
        engine = _engine(model, params)
        due = time.perf_counter() - 0.25
        j = journal_mod.activate(journal_mod.SpanJournal(tmp_path))
        try:
            late = engine.submit(_prompt(6, seed=1), 2, arrival=due)
            now = engine.submit(_prompt(6, seed=2), 2)
            engine.run()
        finally:
            journal_mod.deactivate()
        recs = engine.ledger.records
        assert recs[late]["arrival"] == due and recs[now]["arrival"] > due + 0.25
        assert recs[late]["admitted"] - recs[late]["arrival"] >= 0.25
        assert recs[late]["first_token"] - recs[late]["arrival"] >= 0.25
        waits = {r["request"]: r["dur"] for r in j.tail(256) if r["kind"] == "queue_wait"}
        assert waits[late] >= 0.25 > waits[now]
        with pytest.raises(ValueError, match="after the engine's clock"):
            engine.submit(_prompt(6, seed=3), 2, arrival=time.perf_counter() + 60.0)

    def test_ledger_counts_prompt_tokens_prefilled(self, tiny_model):
        model, params = tiny_model
        engine = _engine(model, params)  # prefill_chunk 8: a 12-token prompt takes two chunks
        engine.submit(_prompt(12, seed=1), 2)
        engine.submit(_prompt(5, seed=2), 2)
        assert engine.ledger.prefilled_tokens == 0
        engine.step()
        assert engine.ledger.prefilled_tokens == 8
        engine.run()
        assert engine.ledger.prefilled_tokens == 17


# ---------------------------------------------------------------------------
# refcounted pool: the free + unique-live == capacity invariant under sharing
# ---------------------------------------------------------------------------


class TestRefcountedPool:
    def _pool(self, n=8):
        return KVBlockPool(2, 2, 8, num_blocks=n, block_size=4, dtype=jnp.float32)

    def test_retain_release_roundtrip(self):
        pool = self._pool()
        [b] = pool.alloc(1)
        assert pool.refcount(b) == 1 and not pool.is_shared(b)
        pool.retain([b])
        assert pool.refcount(b) == 2 and pool.is_shared(b)
        pool.release([b])  # one holder left: still live
        assert pool.refcount(b) == 1 and pool.num_live == 1
        pool.release([b])  # last holder: back on the free list
        assert pool.refcount(b) == 0 and pool.num_free == 8 and pool.num_live == 0

    def test_release_below_zero_raises(self):
        pool = self._pool()
        [b] = pool.alloc(1)
        pool.release([b])
        with pytest.raises(ValueError, match="not live"):
            pool.release([b])  # refcount already hit zero

    def test_double_release_in_one_call_raises_and_releases_nothing(self):
        pool = self._pool()
        [b] = pool.alloc(1)
        with pytest.raises(ValueError, match="not live"):
            pool.release([b, b])  # one holder, two releases: below zero
        # validated atomically up front: NOTHING was released
        assert pool.refcount(b) == 1 and pool.num_live == 1
        assert pool.num_free + pool.num_live == 8
        # with two holders the same call is legal and drains both
        pool.retain([b])
        pool.release([b, b])
        assert pool.num_free == 8 and pool.num_live == 0

    def test_retain_free_block_raises(self):
        pool = self._pool()
        with pytest.raises(ValueError, match="retain"):
            pool.retain([3])  # never allocated: no content to share

    def test_shared_block_counts_once_in_live(self):
        pool = self._pool()
        blocks = pool.alloc(3)
        pool.retain(blocks)  # a second table maps all three
        pool.retain([blocks[0]])  # and the radix tree pins one
        assert pool.num_live == 3  # unique blocks, not references
        assert pool.num_free + pool.num_live == 8
        pool.release(blocks)
        pool.release(blocks)
        assert pool.num_live == 1  # the tree still pins blocks[0]
        pool.release([blocks[0]])
        assert pool.num_free == 8 and pool.num_live == 0

    def test_random_1k_ops_refcounted_invariant(self):
        """The satellite property test: 1k random admit/share/fork/finish
        operations over refcounted blocks. At every step ``free + (unique
        live) == capacity``, refcounts equal the number of holders, and a
        full drain restores the pristine pool."""
        rs = np.random.RandomState(23)
        pool = self._pool(16)
        holders: list[list[int]] = []  # each entry: one holder's block list
        for _ in range(1000):
            ops = ["admit", "finish", "share", "fork"]
            op = ops[rs.randint(4)]
            if op == "admit":
                want = int(rs.randint(1, 4))
                if want > pool.num_free:
                    with pytest.raises(PoolExhausted):
                        pool.alloc(want)
                else:
                    holders.append(pool.alloc(want))
            elif op == "finish" and holders:
                pool.release(holders.pop(rs.randint(len(holders))))
            elif op == "share" and holders:
                src = holders[rs.randint(len(holders))]
                take = [b for b in src if rs.rand() < 0.5] or src[:1]
                pool.retain(take)  # a prefix hit maps them into a new table
                holders.append(list(take))
            elif op == "fork" and holders:
                h = holders[rs.randint(len(holders))]
                i = rs.randint(len(h))
                if pool.is_shared(h[i]) and pool.num_free >= 1:
                    [new] = pool.alloc(1)  # COW: private copy...
                    pool.release([h[i]])  # ...drop the shared original
                    h[i] = new
            # the invariant, after EVERY operation
            refs: dict[int, int] = {}
            for h in holders:
                for b in h:
                    refs[b] = refs.get(b, 0) + 1
            assert pool.num_free + pool.num_live == 16
            assert pool.num_live == len(refs)
            for b, n in refs.items():
                assert pool.refcount(b) == n, f"block {b}: {pool.refcount(b)} != {n}"
        while holders:
            pool.release(holders.pop())
        assert pool.num_free == 16 and pool.num_live == 0


# ---------------------------------------------------------------------------
# prefix cache: radix tree, content addressing, LRU-over-refcount eviction
# ---------------------------------------------------------------------------


class TestPrefixCacheUnit:
    def _setup(self, n=16):
        pool = KVBlockPool(2, 2, 8, num_blocks=n, block_size=4, dtype=jnp.float32)
        return pool, PrefixCache(pool)

    def _toks(self, n, seed=0):
        return np.random.RandomState(seed).randint(0, 61, size=n).astype(np.int32)

    def test_insert_match_lock_roundtrip(self):
        pool, cache = self._setup()
        toks = self._toks(10)  # 2 full blocks + 2 trailing tokens
        blocks = pool.alloc(3)
        assert cache.insert(toks, blocks) == 2  # only FULL blocks cached
        assert pool.refcount(blocks[0]) == 2 and pool.refcount(blocks[2]) == 1
        m = cache.match(toks)
        assert m.tokens == 8 and m.blocks == blocks[:2]
        locked, n = cache.lock(m)
        assert (locked, n) == (blocks[:2], 8)
        assert pool.refcount(blocks[0]) == 3  # tree + owner + locker
        pool.release(locked)

    def test_match_is_block_granular_and_prefix_exact(self):
        pool, cache = self._setup()
        toks = self._toks(8, seed=1)
        cache.insert(toks, pool.alloc(2))
        # same first block, different second block: partial chain match
        other = np.concatenate([toks[:4], self._toks(4, seed=2)])
        assert cache.match(other).tokens == 4
        # divergence INSIDE a block: that block cannot match
        inner = toks.copy()
        inner[6] = (inner[6] + 1) % 61
        assert cache.match(inner).tokens == 4
        # shorter than a block: no match ever
        assert cache.match(toks[:3]).tokens == 0

    def test_content_address_chains_from_parent(self):
        """The same 4 tokens behind two different prefixes are two
        distinct nodes (chained hash): matching never teleports a block
        across prefixes."""
        pool, cache = self._setup()
        a, b = self._toks(4, seed=3), self._toks(4, seed=4)
        tail = self._toks(4, seed=5)
        cache.insert(np.concatenate([a, tail]), pool.alloc(2))
        cache.insert(np.concatenate([b, tail]), pool.alloc(2))
        ma = cache.match(np.concatenate([a, tail]))
        mb = cache.match(np.concatenate([b, tail]))
        assert ma.tokens == mb.tokens == 8
        assert ma.nodes[1].block != mb.nodes[1].block
        assert ma.nodes[1].key != mb.nodes[1].key

    def test_eviction_is_leaf_first_lru_and_respects_pins(self):
        pool, cache = self._setup(8)
        cold = self._toks(8, seed=6)
        hot = self._toks(8, seed=7)
        for toks in (cold, hot):  # insert, then the "request" finishes:
            blocks = pool.alloc(2)  # only the tree's reference remains
            cache.insert(toks, blocks)
            pool.release(blocks)
        locked, _ = cache.lock(cache.match(hot))  # pin the hot chain
        pool.alloc(4)  # pool now full: 4 cached + 4 private
        # ask for 2 free: must evict the COLD chain (leaf first), never
        # the pinned hot one
        assert cache.evict(2) >= 2
        assert cache.match(cold).tokens == 0  # gone
        assert cache.match(hot).tokens == 8  # pinned chain intact
        # with everything else pinned, eviction honestly gives up
        assert cache.evict(8) < 8

    def test_lock_survives_eviction_race(self):
        """The adversarial match->admit window: a match taken, then the
        matched chain evicted, then lock — lock must re-validate and
        return only the still-cached prefix, never a recycled page."""
        pool, cache = self._setup(8)
        toks = self._toks(12, seed=8)
        owned = pool.alloc(3)
        cache.insert(toks, owned)
        pool.release(owned)  # the inserting request finished: tree-only refs
        m = cache.match(toks)
        assert m.tokens == 12
        # eviction invalidates the whole chain between match and lock
        pool.alloc(pool.num_free)  # drain the free list
        cache.evict(3)
        locked, n = cache.lock(m)
        assert locked == [] and n == 0  # truncated at the first dead node
        # partial invalidation: re-insert, evict only the tail leaf
        pool2, cache2 = self._setup(8)
        blocks = pool2.alloc(3)
        cache2.insert(toks, blocks)
        pool2.release(blocks)
        m2 = cache2.match(toks)
        cache2._drop(m2.nodes[-1])  # the LRU leaf goes
        locked2, n2 = cache2.lock(m2)
        assert locked2 == blocks[:2] and n2 == 8
        pool2.release(locked2)

    def test_adapter_ids_namespace_the_tree(self):
        """LoRA deltas change the K/V projections: identical tokens under
        different adapters must NEVER share blocks."""
        pool, cache = self._setup()
        toks = self._toks(8, seed=9)
        cache.insert(toks, pool.alloc(2), adapter=0)
        assert cache.match(toks, adapter=0).tokens == 8
        assert cache.match(toks, adapter=1).tokens == 0


# ---------------------------------------------------------------------------
# prefix sharing through the engine: warm templates, COW, admission
# ---------------------------------------------------------------------------


def _template_prompt(tmpl, n_suffix, seed):
    return np.concatenate(
        [tmpl, np.random.RandomState(seed).randint(0, 61, n_suffix).astype(np.int32)]
    )


class TestPrefixEngine:
    def test_warm_template_identity_and_prefill_skip(self, tiny_model):
        """Requests sharing a 16-token template: outputs token-identical
        to serial generate AND to the uncached engine; the warm requests'
        ledger records show the skipped prefill."""
        model, params = tiny_model
        tmpl = _prompt(16, seed=40)
        specs = [(3, 41), (5, 42), (2, 43)]
        prompts = [_template_prompt(tmpl, n, s) for n, s in specs]
        engine = _engine(model, params, max_slots=1, prefix_cache=True)
        rids = [engine.submit(p, 5) for p in prompts]
        engine.run(max_steps=4000)
        plain = _engine(model, params, max_slots=1)
        prids = [plain.submit(p, 5) for p in prompts]
        plain.run(max_steps=4000)
        for rid, prid, p in zip(rids, prids, prompts):
            ref = np.asarray(generate(model, params, jnp.asarray(p)[None], 5))[0]
            np.testing.assert_array_equal(engine.output(rid), ref)
            np.testing.assert_array_equal(plain.output(prid), ref)
        recs = engine.ledger.records
        assert recs[rids[0]]["cached_tokens"] == 0  # cold: populated the tree
        for rid in rids[1:]:  # max_slots=1: strictly after the cold prefill
            assert recs[rid]["cached_tokens"] == 16
            assert recs[rid]["saved_tokens"] == 16
        s = engine.ledger.summary()
        assert s["prefix_hit_rate"] == pytest.approx(2 / 3, abs=1e-3)
        assert s["prefill_tokens_saved"] == 32
        assert engine.pool.num_free + engine.pool.num_live == engine.pool.num_blocks

    def test_exact_duplicate_prompt_takes_the_cow_fork(self, tiny_model):
        """A full-block prompt re-requested exactly: every block matches,
        prefill rolls back ONE token for its logits, and that token's
        write COW-forks the final shared block — output still
        token-identical, pools still clean, and the fork replays the one
        compiled copy signature."""
        model, params = tiny_model
        prompt = _prompt(16, seed=44)  # 4 full blocks @ block_size 4
        engine = _engine(model, params, max_slots=1, prefix_cache=True)
        r1 = engine.submit(prompt, 5)
        engine.run(max_steps=2000)
        r2 = engine.submit(prompt, 5)
        engine.run(max_steps=2000)
        ref = np.asarray(generate(model, params, jnp.asarray(prompt)[None], 5))[0]
        np.testing.assert_array_equal(engine.output(r1), ref)
        np.testing.assert_array_equal(engine.output(r2), ref)
        rec = engine.ledger.records[r2]
        assert rec["cached_tokens"] == 16 and rec["saved_tokens"] == 15
        assert engine._copy_fn.cache_size() == 1  # the fork compiled once
        assert engine.pool.num_free + engine.pool.num_live == engine.pool.num_blocks
        # a third exact duplicate forks again but compiles NOTHING new
        before = engine.compiled_signatures()
        r3 = engine.submit(prompt, 5)
        engine.run(max_steps=2000)
        np.testing.assert_array_equal(engine.output(r3), ref)
        assert engine.compiled_signatures() == before

    @pytest.mark.slow  # eviction-pressure drill; eviction-race lock lives in the prefix-cache unit tests
    def test_identity_under_eviction_pressure(self, tiny_model):
        """A pool too small to cache every prompt: LRU leaves evict to
        admit new requests, and every output stays token-identical."""
        model, params = tiny_model
        rs = np.random.RandomState(45)
        engine = ServeEngine(
            model, params, num_blocks=16, block_size=4, max_slots=2,
            prefill_chunk=8, prefix_cache=True,
        )
        prompts = [_prompt(int(rs.randint(4, 20)), seed=500 + i) for i in range(12)]
        rids = [engine.submit(p, 4) for p in prompts]
        engine.run(max_steps=5000)
        for rid, p in zip(rids, prompts):
            ref = np.asarray(generate(model, params, jnp.asarray(p)[None], 4))[0]
            np.testing.assert_array_equal(engine.output(rid), ref)
        assert engine.prefix.stats()["evictions"] > 0  # pressure was real
        assert engine.pool.num_free + engine.pool.num_live == engine.pool.num_blocks

    @pytest.mark.slow  # admission property drill under sharing
    def test_admission_property_under_sharing(self, tiny_model):
        """The satellite property test: random 80%-shared-template load
        through a TIGHT pool with shared blocks discounted from
        reservations — strict FIFO holds, nobody starves, and after EVERY
        engine step ``free + unique live == capacity``."""
        model, params = tiny_model
        rs = np.random.RandomState(46)
        templates = [_prompt(12, seed=600 + t) for t in range(3)]
        engine = ServeEngine(
            model, params, num_blocks=20, block_size=4, max_slots=3,
            prefill_chunk=8, prefix_cache=True,
        )
        prompts = []
        for i in range(24):
            if i % 5 != 4:  # 80% template-shaped
                tmpl = templates[int(rs.randint(len(templates)))]
                prompts.append(_template_prompt(tmpl, int(rs.randint(1, 5)), 700 + i))
            else:
                prompts.append(_prompt(int(rs.randint(2, 14)), seed=700 + i))
        rids = [engine.submit(p, int(rs.randint(1, 6))) for p in prompts]
        steps = 0
        while not engine.idle and steps < 5000:
            engine.step()
            steps += 1
            assert engine.pool.num_free + engine.pool.num_live == engine.pool.num_blocks
        out = engine.results()
        assert sorted(out) == sorted(rids), "an admitted request starved"
        for rid, p in zip(rids, prompts):
            ref = np.asarray(
                generate(model, params, jnp.asarray(p)[None], len(out[rid]))
            )[0]
            np.testing.assert_array_equal(out[rid], ref)
        admits = [engine.ledger.records[r]["admitted"] for r in rids]
        assert admits == sorted(admits)  # strict FIFO held
        assert engine.ledger.summary()["prefix_hit_rate"] > 0.3  # sharing was real

    def test_warm_engine_with_prefix_never_recompiles(self, tiny_model):
        model, params = tiny_model
        engine = _engine(model, params, max_slots=4, prefix_cache=True, guard="raise")
        tmpl = _prompt(12, seed=47)
        specs = [(2 + (i % 3), 3 + (i % 3)) for i in range(8)]
        # wave 0 is cold (populates the tree), wave 1 is the FIRST warm
        # pass — cache hits change batch dynamics, so it may legitimately
        # touch bucket pairs the cold wave never formed; wave 2 replays
        # warm-steady-state dynamics and must compile NOTHING
        for wave, assert_warm in ((0, False), (1, False), (2, True)):
            before = engine.compiled_signatures()
            for i, (n, m) in enumerate(specs):
                engine.submit(_template_prompt(tmpl, n, 800 + 100 * wave + i), m)
            engine.run(max_steps=5000)
            if assert_warm:
                assert engine.compiled_signatures() == before
        assert engine.compiled_signatures() <= engine.max_signatures

    @pytest.mark.slow  # engine-level tenant-isolation drill; the prefix-cache unit tests lock adapter namespacing
    def test_prefix_never_crosses_adapter_tenants(self, tiny_model):
        """Two tenants sending the SAME prompt must not share K/V: the
        adapter id namespaces the radix tree, so each tenant's output
        stays identical to that tenant served alone."""
        model, params = tiny_model
        a = _randomized_adapter(params, 1, 10)
        aset = AdapterSet({"a": a}, alpha=4.0, base=params)
        prompt = _prompt(16, seed=48)

        def run(specs):
            eng = _engine(
                model, params, max_slots=1, adapters=aset, prefix_cache=True
            )
            rids = [eng.submit(prompt, 6, adapter=s) for s in specs]
            eng.run(max_steps=4000)
            return [eng.output(r) for r in rids]

        mixed = run(["a", None, "a", None])  # warm hits inside each tenant
        alone_a = run(["a"])[0]
        alone_base = run([None])[0]
        np.testing.assert_array_equal(mixed[0], alone_a)
        np.testing.assert_array_equal(mixed[2], alone_a)
        np.testing.assert_array_equal(mixed[1], alone_base)
        np.testing.assert_array_equal(mixed[3], alone_base)
        assert not np.array_equal(alone_a, alone_base)  # non-vacuous

    def test_multi_turn_blocks_published_at_finish(self, tiny_model):
        """A finished request's decoded full blocks enter the tree: a
        follow-up whose prompt extends (prompt + output) hits past the
        original prompt — the multi-turn shape."""
        model, params = tiny_model
        prompt = _prompt(8, seed=49)
        engine = _engine(model, params, max_slots=1, prefix_cache=True)
        r1 = engine.submit(prompt, 8)
        engine.run(max_steps=2000)
        out1 = engine.output(r1)
        turn2 = np.concatenate([prompt, out1, _prompt(3, seed=50)])
        r2 = engine.submit(turn2, 4)
        engine.run(max_steps=2000)
        ref = np.asarray(generate(model, params, jnp.asarray(turn2)[None], 4))[0]
        np.testing.assert_array_equal(engine.output(r2), ref)
        # blocks past the first prompt were served from cache: the hit
        # covers prompt+output full blocks ((8 + 8 - 1) // 4 * 4 = 12)
        assert engine.ledger.records[r2]["cached_tokens"] == 12


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

    @pytest.mark.slow  # warm-replay drill; spec x prefix identity kept tier-1 via the independent-draft test
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

    @pytest.mark.slow  # mixed-tenant spec x LoRA drill; the all-compose lock stays tier-1
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
        # walk the chain INTO its fixed point first: this model's greedy
        # continuation of _prompt(4) goes constant after ~18 tokens, so a
        # prompt extended by that warmup decodes entirely inside the cycle
        seed_prompt = _prompt(4, seed=0)
        warm = np.asarray(
            generate(model, params, jnp.asarray(seed_prompt)[None], 18)
        )[0]
        prompt = np.concatenate([seed_prompt, warm]).astype(np.int32)
        engine = _engine(model, params, medusa_k=3, num_blocks=48)
        rid = engine.submit(prompt, 36)
        out = engine.run(max_steps=5000)
        ref = np.asarray(generate(model, params, jnp.asarray(prompt)[None], 36))[0]
        np.testing.assert_array_equal(out[rid], ref)
        assert engine.ledger.summary()["accept_rate"] > 0.8

    @pytest.mark.slow  # random-load property drill; medusa identity/budget/compose locks stay tier-1
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
# request lifecycle: cancel / deadlines / terminal statuses (PR 13)
# ---------------------------------------------------------------------------


class TestRequestLifecycle:
    def test_cancel_queued_and_running_releases_everything(self, tiny_model):
        """Cancellation at ANY phase: one request cancelled mid-decode,
        one cancelled while queued — both stamp ``cancelled``, release
        every block, and the survivor's output is untouched."""
        model, params = tiny_model
        engine = _engine(model, params, max_slots=1)
        r_run = engine.submit(_prompt(5, seed=1), 12)
        r_ok = engine.submit(_prompt(7, seed=2), 4)
        r_queued = engine.submit(_prompt(6, seed=3), 4)
        for _ in range(3):  # r_run admitted + prefilled + a decode step
            engine.step()
        assert engine.status(r_run) == "running"
        assert engine.status(r_queued) == "queued"
        assert engine.cancel(r_run) and engine.cancel(r_queued)
        assert engine.status(r_run) == "cancelled"
        assert engine.status(r_queued) == "cancelled"
        assert not engine.cancel(r_run)  # idempotent: lost the race, no double-free
        engine.run(max_steps=2000)
        assert engine.status(r_ok) == "ok"
        ref = np.asarray(generate(model, params, jnp.asarray(_prompt(7, seed=2))[None], 4))[0]
        np.testing.assert_array_equal(engine.output(r_ok), ref)
        assert engine.pool.num_free == engine.pool.num_blocks
        with pytest.raises(KeyError):
            engine.output(r_run) and None  # cancelled work has no output
        assert not engine.cancel(9999)  # unknown id: False, not a crash

    def test_deadline_expiry_with_fake_clock(self, tiny_model):
        """A deadline elapsing mid-flight terminates ``deadline_exceeded``
        and frees the blocks; the deadline-free neighbor is untouched."""
        model, params = tiny_model
        t = [0.0]
        engine = _engine(model, params, max_slots=2, clock=lambda: t[0])
        r_doomed = engine.submit(_prompt(5, seed=4), 20, deadline_s=1.0)
        r_ok = engine.submit(_prompt(5, seed=5), 4)
        for _ in range(3):
            engine.step()
        assert engine.status(r_doomed) == "running"
        t[0] = 2.0  # past the deadline at a mid-decode phase
        engine.run(max_steps=2000)
        assert engine.status(r_doomed) == "deadline_exceeded"
        assert engine.status(r_ok) == "ok"
        assert engine.pool.num_free == engine.pool.num_blocks
        assert engine.ledger.status_counts() == {"deadline_exceeded": 1, "ok": 1}

    def test_queued_deadline_expires_before_admission(self, tiny_model):
        """A deadline can expire while the request is still WAITING — it
        must terminate without ever holding a block."""
        model, params = tiny_model
        t = [0.0]
        engine = _engine(model, params, max_slots=1, clock=lambda: t[0])
        r_run = engine.submit(_prompt(5, seed=6), 16)
        r_waiting = engine.submit(_prompt(5, seed=7), 4, deadline_s=0.5)
        engine.step()
        assert engine.status(r_waiting) == "queued"
        t[0] = 1.0
        engine.step()
        assert engine.status(r_waiting) == "deadline_exceeded"
        engine.run(max_steps=2000)
        assert engine.status(r_run) == "ok"
        assert engine.pool.num_free == engine.pool.num_blocks

    def test_submit_validates_deadline(self, tiny_model):
        model, params = tiny_model
        engine = _engine(model, params)
        with pytest.raises(ValueError, match="deadline_s"):
            engine.submit(_prompt(4), 4, deadline_s=0.0)

    @pytest.mark.slow  # random cancel/expiry property drill; lifecycle units cover each terminal path
    def test_random_cancel_and_expiry_property(self, tiny_model):
        """The lifecycle property test: random cancels (seeded monkey) and
        random deadlines injected over random load — every request ends
        TERMINAL, ``free + unique-live == capacity`` holds in the pool
        after every step (the monkey audits it), nothing leaks."""
        model, params = tiny_model
        rs = np.random.RandomState(23)
        engine = ServeEngine(
            model, params, num_blocks=32, block_size=4, max_slots=3, prefill_chunk=8
        )
        monkey = ChaosMonkey(seed=29, p_cancel=0.2, p_stall=0.3, stall_s=0.02)
        monkey.attach(engine)
        rids = []
        for i in range(14):
            kw = {}
            if rs.random_sample() < 0.5:
                kw["deadline_s"] = float(rs.uniform(0.01, 5.0))
            rids.append(
                engine.submit(_prompt(int(rs.randint(1, 16)), seed=400 + i),
                              int(rs.randint(1, 8)), **kw)
            )
        engine.run(max_steps=3000)
        monkey.detach()
        statuses = [engine.status(r) for r in rids]
        assert all(s in TERMINAL_STATUSES for s in statuses), statuses
        assert engine.pool.num_free == engine.pool.num_blocks
        assert engine.leaked_blocks() == 0
        # ok requests really produced their full budget
        for rid, s in zip(rids, statuses):
            if s == "ok":
                assert len(engine.output(rid)) == engine._all[rid].req.max_new_tokens


# ---------------------------------------------------------------------------
# overload control: bounded queue, shedding, per-tenant fairness (PR 13)
# ---------------------------------------------------------------------------


class TestOverloadControl:
    def test_bounded_queue_reject_policy_sheds_arrivals(self, tiny_model):
        """``shed_policy="reject"``: once ``max_waiting`` is reached the
        ARRIVAL sheds on sight; earlier queued work is untouched."""
        model, params = tiny_model
        engine = _engine(model, params, max_slots=1, max_waiting=2)
        r_run = engine.submit(_prompt(5, seed=10), 10)
        engine.step()  # r_run leaves the queue for its slot
        kept = [engine.submit(_prompt(4, seed=11 + i), 3) for i in range(2)]
        shed = [engine.submit(_prompt(4, seed=13 + i), 3) for i in range(2)]
        assert [engine.status(r) for r in shed] == ["shed", "shed"]
        engine.run(max_steps=2000)
        assert engine.status(r_run) == "ok"
        assert [engine.status(r) for r in kept] == ["ok", "ok"]
        assert engine.pool.num_free == engine.pool.num_blocks

    def test_oldest_deadline_policy_sheds_doomed_victim(self, tiny_model):
        """``shed_policy="oldest-deadline"``: overflow sheds the waiting
        request with the EARLIEST deadline (most doomed) — the arrival
        wins its seat; lower priority sheds before any deadline compare."""
        model, params = tiny_model
        engine = _engine(
            model, params, max_slots=1, max_waiting=1, shed_policy="oldest-deadline"
        )
        engine.submit(_prompt(5, seed=20), 10)
        engine.step()
        r_doomed = engine.submit(_prompt(4, seed=21), 3, deadline_s=0.5)
        r_late = engine.submit(_prompt(4, seed=22), 3, deadline_s=60.0)
        assert engine.status(r_doomed) == "shed"  # earliest deadline lost
        assert engine.status(r_late) == "queued"
        r_low = engine.submit(_prompt(4, seed=23), 3, priority=-1, deadline_s=0.1)
        assert engine.status(r_low) == "shed"  # priority trumps deadline
        assert engine.status(r_late) == "queued"
        engine.run(max_steps=2000)
        assert engine.status(r_late) == "ok"
        assert engine.pool.num_free == engine.pool.num_blocks

    def test_tenant_fairness_interleaves_cold_tenant(self, tiny_model):
        """``fairness="tenant"``: a hot tenant's 8-deep backlog does not
        make a late cold tenant wait behind ALL of it — deficit
        round-robin admits cold work before the hot queue drains, and
        nobody starves."""
        model, params = tiny_model
        engine = _engine(model, params, max_slots=2, fairness="tenant")
        hot = [engine.submit(_prompt(5, seed=30 + i), 3, tenant="hot") for i in range(8)]
        cold = [engine.submit(_prompt(5, seed=40 + i), 3, tenant="cold") for i in range(2)]
        engine.run(max_steps=3000)
        assert all(engine.status(r) == "ok" for r in hot + cold)
        admitted = {r: engine.ledger.records[r]["admitted"] for r in hot + cold}
        order = sorted(admitted, key=admitted.get)
        # every cold request beats at least the hot tail to admission
        for rc in cold:
            assert order.index(rc) < order.index(hot[-1])
        assert engine.pool.num_free == engine.pool.num_blocks

    def test_priority_never_reorders_fifo_admission(self, tiny_model):
        """Priority is SHED-VICTIM metadata only: with no overload, the
        PR-8 strict-FIFO admission contract holds regardless of
        priorities."""
        model, params = tiny_model
        engine = _engine(model, params, max_slots=2)
        rids = [
            engine.submit(_prompt(4, seed=50 + i), 2, priority=int(p))
            for i, p in enumerate([5, -3, 9, 0, -7, 2])
        ]
        engine.run(max_steps=2000)
        admits = [engine.ledger.records[r]["admitted"] for r in rids]
        assert admits == sorted(admits)
        assert all(engine.status(r) == "ok" for r in rids)


# ---------------------------------------------------------------------------
# chaos drill: seeded fault injection over the full engine (PR 13)
# ---------------------------------------------------------------------------


def _chaos_specs(rs, n):
    return [(int(rs.randint(1, 16)), int(rs.randint(1, 8))) for _ in range(n)]


class TestChaosDrill:
    def test_seeded_drill_holds_every_contract(self, tiny_model):
        """THE acceptance drill: a seeded injector (step faults, pool
        squats, random cancels) over random load on a prefix-cache engine
        — every request terminal, both pools audited every step, zero
        prefix lock leaks, zero leaked blocks, and every SURVIVOR's
        greedy output token-identical to the fault-free reference."""
        model, params = tiny_model
        rs = np.random.RandomState(31)
        specs = _chaos_specs(rs, 16)
        ref = ServeEngine(
            model, params, num_blocks=48, block_size=4, max_slots=3, prefill_chunk=8
        )
        ref_rids = [ref.submit(_prompt(n, seed=500 + i), m) for i, (n, m) in enumerate(specs)]
        ref_out = ref.run(max_steps=4000)
        engine = ServeEngine(
            model, params, num_blocks=48, block_size=4, max_slots=3, prefill_chunk=8,
            prefix_cache=True,
        )
        monkey = ChaosMonkey(
            seed=37, p_fault=0.08, max_faults=4, p_exhaust=0.15,
            exhaust_blocks=6, exhaust_steps=2, p_cancel=0.08,
        )
        monkey.attach(engine)
        rids = [engine.submit(_prompt(n, seed=500 + i), m) for i, (n, m) in enumerate(specs)]
        engine.run(max_steps=4000)
        monkey.detach()
        statuses = [engine.status(r) for r in rids]
        assert all(s in TERMINAL_STATUSES for s in statuses), statuses
        for pool in (engine.pool,):
            pool.assert_consistent()
        assert engine.prefix.leaked_locks() == []
        assert engine.leaked_blocks() == 0
        survivors = [(r, rr) for r, rr, s in zip(rids, ref_rids, statuses) if s == "ok"]
        assert survivors, "drill too hot: no survivors to compare"
        for r, rr in survivors:
            np.testing.assert_array_equal(engine.output(r), ref_out[rr])

    @pytest.mark.slow  # replays the seeded drill twice; the single-run contract lock stays tier-1
    def test_drill_is_replayable(self, tiny_model):
        """Same seed, same trace -> same injected events and same terminal
        census: the drill is a deterministic regression test, not a fuzzer."""
        model, params = tiny_model
        logs, censuses = [], []
        for _ in range(2):
            engine = _engine(model, params, max_slots=2, num_blocks=32)
            monkey = ChaosMonkey(seed=41, p_fault=0.1, max_faults=3, p_cancel=0.1)
            monkey.attach(engine)
            for i in range(8):
                engine.submit(_prompt(4 + (i % 3) * 4, seed=600 + i), 3 + (i % 2))
            engine.run(max_steps=2000)
            monkey.detach()
            logs.append(list(monkey.log))
            censuses.append(engine.ledger.status_counts())
        assert logs[0] == logs[1]
        assert censuses[0] == censuses[1]

    def test_pool_exhaustion_squat_only_stalls(self, tiny_model):
        """Exhaustion injected through the pool's own alloc is a STALL,
        not a failure: admission waits the squat out, everyone finishes
        ``ok``, and the squat never broke the accounting."""
        model, params = tiny_model
        engine = _engine(model, params, max_slots=2, num_blocks=24)
        monkey = ChaosMonkey(seed=43, p_exhaust=0.5, exhaust_blocks=12, exhaust_steps=2)
        monkey.attach(engine)
        rids = [engine.submit(_prompt(5, seed=700 + i), 4) for i in range(6)]
        engine.run(max_steps=3000)
        monkey.detach()
        assert all(engine.status(r) == "ok" for r in rids)
        assert any(kind == "exhaust" for _, kind, _ in monkey.log)
        assert engine.pool.num_free == engine.pool.num_blocks

    def test_step_fault_isolated_to_its_rows(self, tiny_model):
        """One injected decode fault errors exactly the rows it was
        advancing; later requests decode normally on the freed blocks."""
        model, params = tiny_model
        engine = _engine(model, params, max_slots=1)
        monkey = ChaosMonkey(seed=47, p_fault=1.0, fault_points=("decode",), max_faults=1)
        monkey.attach(engine)
        r_hit = engine.submit(_prompt(5, seed=800), 6)
        r_ok = engine.submit(_prompt(5, seed=801), 6)
        engine.run(max_steps=2000)
        monkey.detach()
        assert engine.status(r_hit) == "error"
        assert engine.status(r_ok) == "ok"
        ref = np.asarray(generate(model, params, jnp.asarray(_prompt(5, seed=801))[None], 6))[0]
        np.testing.assert_array_equal(engine.output(r_ok), ref)
        assert engine.pool.num_free == engine.pool.num_blocks


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

    @pytest.mark.slow  # verify-fault drill; draft-fault degrade/resume + step-fault isolation locks stay tier-1
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


# ---------------------------------------------------------------------------
# graceful drain + requeue verdict + watchdog heartbeat (PR 13)
# ---------------------------------------------------------------------------


class TestDrainAndVerdict:
    def test_manual_drain_finishes_running_sheds_queued(self, tiny_model):
        """Drain contract: admission closes, the waiting queue sheds, the
        in-flight request finishes inside the budget, the verdict says
        ``completed`` / no requeue."""
        model, params = tiny_model
        engine = _engine(model, params, max_slots=1)
        r_run = engine.submit(_prompt(5, seed=70), 4)
        queued = [engine.submit(_prompt(4, seed=71 + i), 3) for i in range(2)]
        engine.step()
        verdict = engine.drain(max_steps=2000)
        assert engine.status(r_run) == "ok"
        assert [engine.status(r) for r in queued] == ["shed", "shed"]
        assert verdict["kind"] == "completed" and verdict["requeue"] is False
        assert verdict["serve"]["drained_clean"] is True
        assert verdict["serve"]["statuses"] == {"ok": 1, "shed": 2}
        # admission is closed for late arrivals too
        late = engine.submit(_prompt(4, seed=75), 3)
        assert engine.status(late) == "shed"
        assert engine.pool.num_free == engine.pool.num_blocks

    def test_drain_budget_sheds_inflight_work(self, tiny_model):
        """Past ``drain_budget_s`` the drain stops waiting: in-flight
        requests shed, their blocks release, the verdict reports the cut."""
        model, params = tiny_model
        t = [0.0]
        engine = _engine(
            model, params, max_slots=1, clock=lambda: t[0], drain_budget_s=1.0
        )
        r_long = engine.submit(_prompt(5, seed=80), 30)
        for _ in range(3):
            engine.step()
        assert engine.status(r_long) == "running"
        engine.request_drain("test shutdown")
        t[0] = 5.0  # blow the budget
        verdict = engine.drain(max_steps=100)
        assert engine.status(r_long) == "shed"
        assert verdict["serve"]["drained_clean"] is True
        assert engine.pool.num_free == engine.pool.num_blocks

    def test_preemption_guard_drives_requeue_verdict(self, tiny_model, tmp_path):
        """PR-7 composition: a tripped PreemptionGuard turns the next step
        into a drain and the verdict into ``kind="preemption"`` /
        ``requeue=True``, written as ``requeue.json`` under ``run_dir``
        in the schema every elasticity wrapper reads."""
        from dmlcloud_tpu.checkpoint import read_requeue_verdict
        from dmlcloud_tpu.parallel.runtime import PreemptionGuard

        model, params = tiny_model
        guard = PreemptionGuard()
        guard.triggered = True  # the documented out-of-band test path
        guard.signal_name = "SIGTERM"
        engine = _engine(
            model, params, max_slots=1, preemption=guard, run_dir=tmp_path
        )
        r1 = engine.submit(_prompt(5, seed=90), 4)
        verdict = engine.drain(max_steps=2000)
        assert verdict["kind"] == "preemption" and verdict["requeue"] is True
        assert verdict["reason"] == "preemption:SIGTERM"
        on_disk = read_requeue_verdict(tmp_path)
        assert on_disk is not None and on_disk["requeue"] is True
        assert on_disk["kind"] == "preemption"
        assert on_disk["serve"]["statuses"] == engine.ledger.status_counts()
        assert engine.status(r1) in ("ok", "shed")  # terminal either way
        assert engine.pool.num_free == engine.pool.num_blocks

    def test_watchdog_serve_guard_drains_on_hang(self, tiny_model, tmp_path):
        """The telemetry watchdog heartbeats the serve loop: a stall past
        the threshold dumps forensics AND requests a ``hang`` drain with
        requeue, so a wedged engine shuts down clean instead of silently."""
        from dmlcloud_tpu.telemetry.watchdog import HangWatchdog

        model, params = tiny_model
        engine = _engine(model, params, max_slots=1)
        wt = [0.0]
        wd = HangWatchdog(tmp_path, threshold_s=10.0, clock=lambda: wt[0])
        wd.serve_guard(engine)
        assert engine.watchdog is wd
        r1 = engine.submit(_prompt(5, seed=95), 3)
        engine.step()  # heartbeats: notify() rides every engine step
        wt[0] = 5.0
        assert wd.check() is None  # progress is fresh: no dump
        wt[0] = 100.0
        assert wd.check() is not None  # stall: forensics + drain request
        assert engine.draining
        assert engine._drain_kind == "hang" and engine._drain_requeue is True
        engine.drain(max_steps=2000)
        assert engine.status(r1) in ("ok", "shed")
        assert engine.pool.num_free == engine.pool.num_blocks


# ---------------------------------------------------------------------------
# ledger bounded retention (PR 13 satellite)
# ---------------------------------------------------------------------------


class TestLedgerRetention:
    def test_bounded_detail_exact_aggregates(self):
        """With ``max_records``, per-request detail evicts FIFO but every
        summary aggregate stays EXACT over the full history."""
        from dmlcloud_tpu.serve.ledger import ServeLedger

        led = ServeLedger(max_records=3)
        for i in range(10):
            t = float(i)
            led.arrived(i, t, tenant="t")
            led.admitted(i, t + 0.5)
            led.first_token(i, t + 1.0)
            for _ in range(4):
                led.token(i)
            led.finished(i, t + 3.0, status="ok" if i % 2 == 0 else "error")
        assert len(led.records) == 3  # detail bounded
        s = led.summary()
        assert s["requests"] == 10 and s["completed"] == 10
        assert s["statuses"] == {"ok": 5, "error": 5}
        assert s["total_tokens"] == 40
        assert s["mean_queue_wait_s"] == pytest.approx(0.5)
        # busy span first arrival (0.0) -> last finish (12.0); goodput
        # counts only the 5 ok requests' 20 tokens (summary rounds to 0.1)
        assert s["tokens_per_sec"] == pytest.approx(40 / 12.0, abs=0.05)
        assert s["goodput_tokens_per_sec"] == pytest.approx(20 / 12.0, abs=0.05)

    def test_live_records_never_evicted(self):
        from dmlcloud_tpu.serve.ledger import ServeLedger

        led = ServeLedger(max_records=2)
        for i in range(6):
            led.arrived(i, float(i))
        assert len(led.records) == 6  # all live: nothing evictable
        for i in range(6):
            led.finished(i, 10.0 + i, status="ok")
        assert len(led.records) == 2  # now terminal detail evicts FIFO
        assert set(led.records) == {4, 5}
        assert led.summary()["requests"] == 6  # aggregate unharmed

    def test_engine_retention_bounds_memory(self, tiny_model):
        """``ledger_max_records`` + ``max_done`` bound a long-running
        engine: old terminal requests vanish from the ledger, the output
        map and the status map, while the census stays exact."""
        model, params = tiny_model
        engine = _engine(
            model, params, max_slots=2, ledger_max_records=3, max_done=3
        )
        rids = [engine.submit(_prompt(4, seed=110 + i), 2) for i in range(8)]
        engine.run(max_steps=2000)
        assert len(engine.ledger.records) <= 3
        assert len(engine._all) <= 3
        assert engine.ledger.status_counts() == {"ok": 8}
        with pytest.raises(KeyError):
            engine.status(rids[0])  # evicted detail
        assert engine.status(rids[-1]) == "ok"  # fresh detail retained


# ---------------------------------------------------------------------------
# failed admits x chaos: the hardened-scheduler property (PR 13 satellite)
# ---------------------------------------------------------------------------


class TestFailedAdmitChaos:
    @pytest.mark.slow  # failed-admit x chaos property drill
    def test_failed_admits_interleaved_with_chaos(self, tiny_model):
        """Submissions that FAIL validation (oversized prompts) interleave
        with shed arrivals, injected faults and pool squats — failed
        admits record nothing, everything admitted ends terminal, and the
        pool accounting survives the whole mess."""
        model, params = tiny_model
        rs = np.random.RandomState(67)
        engine = ServeEngine(
            model, params, num_blocks=16, block_size=4, max_slots=2,
            prefill_chunk=8, max_waiting=3, shed_policy="oldest-deadline",
        )
        monkey = ChaosMonkey(
            seed=71, p_fault=0.05, max_faults=2, p_exhaust=0.2,
            exhaust_blocks=4, exhaust_steps=1, p_cancel=0.1,
        )
        monkey.attach(engine)
        accepted, failed = [], 0
        for i in range(18):
            if rs.random_sample() < 0.25:
                with pytest.raises(ValueError):  # oversized: exceeds max_seq_len
                    engine.submit(_prompt(50, seed=i), 20)
                failed += 1
            else:
                accepted.append(
                    engine.submit(_prompt(int(rs.randint(1, 10)), seed=1000 + i),
                                  int(rs.randint(1, 6)))
                )
            for _ in range(int(rs.randint(0, 3))):
                engine.step()
        engine.run(max_steps=3000)
        monkey.detach()
        assert failed > 0, "property needs failed admits in the mix"
        assert len(engine._all) == len(accepted)  # failed admits recorded NOTHING
        assert all(engine.status(r) in TERMINAL_STATUSES for r in accepted)
        engine.pool.assert_consistent()
        assert engine.pool.num_free == engine.pool.num_blocks
        assert engine.leaked_blocks() == 0
        census = engine.ledger.status_counts()
        assert sum(census.values()) == len(accepted)
