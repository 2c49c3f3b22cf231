"""Prefix-cache sharing in the serving engine (dmlcloud_tpu/serve/
prefix_cache.py, the refcounted kv_pool.py): the ``free + unique-live ==
capacity`` invariant under sharing, the radix tree's block-granular match
and leaf-first LRU eviction, and the engine with ``prefix_cache=True`` —
token-identical to the uncached engine, hits and saved prefill counted on
fixed traces, copy-on-write forks, no compile on a warm engine. (Split out
of test_serve.py so that tier-1's ``--dist loadfile`` run has no 500 s file.)
"""

import numpy as np
import pytest

import jax.numpy as jnp

from dmlcloud_tpu.models.generate import generate
from dmlcloud_tpu.serve import AdapterSet, KVBlockPool, PoolExhausted, PrefixCache, ServeEngine

from test_serve import _engine, _prompt, _randomized_adapter

# tiny_model (the shared 61-vocab serve LM) comes from conftest.py.


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
