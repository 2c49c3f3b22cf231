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

The prefix cache is in test_serve_prefix.py, the speculative and Medusa
modes in test_serve_spec.py (both import this file's helpers).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dmlcloud_tpu.models.generate import decode_step, generate, init_cache
from dmlcloud_tpu.models.lora import LoraPair, lora_init, lora_merge
from dmlcloud_tpu.models.transformer import TransformerConfig
from dmlcloud_tpu.ops.paged_attention import gather_pages, scatter_tokens
from dmlcloud_tpu.serve import (
    AdapterSet,
    ChaosMonkey,
    KVBlockPool,
    PoolExhausted,
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
        assert not np.array_equal(alone_b, alone_base)

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
