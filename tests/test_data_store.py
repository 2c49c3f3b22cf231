"""Disk-native data plane suite (doc/data.md, "On-disk shard format"):
corpus-builder round trip (build → mmap → bit-identical tokens), format
validation and corrupt-shard rejection (the error names the file), the
async ShardReader's world-size-aware assignment + seek-based elastic
cursor, mmap-vs-in-memory equivalence through ``pack_stream``, and the
window-FFD packer's determinism/conservation/pad-reclaim contracts."""

import os
import threading

import numpy as np
import pytest

from dmlcloud_tpu.data import DataPipeline
from dmlcloud_tpu.data.store import (
    HEADER_SIZE,
    CorpusBuilder,
    ShardCorruptError,
    ShardFile,
    ShardReader,
    ShardStore,
    build_corpus,
    reader_activity,
    write_shard,
)


def _docs(n=200, seed=0, vocab=512, median=64.0, sigma=0.6, lo=4, hi=256):
    rs = np.random.RandomState(seed)
    lengths = np.clip(np.round(rs.lognormal(np.log(median), sigma, n)), lo, hi).astype(int)
    return [rs.randint(1, vocab, size=int(k)).astype(np.int32) for k in lengths]


@pytest.fixture(scope="module")
def corpus(tmp_path_factory):
    """One shared on-disk corpus: (directory, docs, manifest)."""
    d = tmp_path_factory.mktemp("corpus")
    docs = _docs()
    manifest = build_corpus(d, docs, shard_tokens=4096)
    return str(d), docs, manifest


class TestShardFormat:
    def test_builder_round_trip_bit_identical(self, corpus):
        d, docs, manifest = corpus
        assert len(manifest["shards"]) > 1  # the corpus actually sharded
        store = ShardStore(d)
        assert store.total_records == len(docs)
        assert store.total_tokens == sum(a.size for a in docs)
        for g, doc in enumerate(docs):
            rec = store.record(g)
            assert rec.dtype == np.int32
            assert np.array_equal(rec, doc)

    def test_records_are_zero_copy_views(self, corpus):
        d, _, _ = corpus
        store = ShardStore(d)
        rec = store.record(0)
        assert not rec.flags.owndata  # a view over the mmap, not a copy
        assert not rec.flags.writeable

    def test_verify_passes_on_intact_corpus(self, corpus):
        d, _, _ = corpus
        ShardStore(d, verify=True)  # must not raise

    def test_manifest_written(self, corpus):
        d, docs, manifest = corpus
        assert os.path.isfile(os.path.join(d, "corpus.json"))
        assert manifest["total_records"] == len(docs)
        assert manifest["version"] == 1

    def test_empty_and_missing_dir_rejected(self, tmp_path):
        with pytest.raises(FileNotFoundError):
            ShardStore(tmp_path)  # exists but holds no shards
        with pytest.raises(FileNotFoundError):
            ShardStore(tmp_path / "nope")

    def test_locate_maps_global_to_shard(self, corpus):
        d, docs, _ = corpus
        store = ShardStore(d)
        base = 0
        for sid, shard in enumerate(store.shards):
            assert store.locate(base) == (sid, 0)
            assert store.locate(base + len(shard) - 1) == (sid, len(shard) - 1)
            base += len(shard)
        # one-past-the-end: the fully-consumed cursor
        assert store.locate(store.total_records) == (len(store.shards), 0)
        with pytest.raises(IndexError):
            store.locate(store.total_records + 1)


class TestCorruptRejection:
    def _copy_shard(self, corpus, tmp_path):
        d, _, _ = corpus
        src = os.path.join(d, sorted(n for n in os.listdir(d) if n.endswith(".dmlshard"))[0])
        dst = tmp_path / "corrupt-00000.dmlshard"
        dst.write_bytes(open(src, "rb").read())
        return str(dst)

    def test_payload_flip_fails_checksum_and_names_file(self, corpus, tmp_path):
        path = self._copy_shard(corpus, tmp_path)
        size = os.path.getsize(path)
        with open(path, "r+b") as f:
            f.seek(size - 3)
            f.write(b"\xa5")
        shard = ShardFile(path)  # structurally valid: open succeeds
        with pytest.raises(ShardCorruptError, match="corrupt-00000.dmlshard"):
            shard.verify()

    def test_truncation_rejected_at_open(self, corpus, tmp_path):
        path = self._copy_shard(corpus, tmp_path)
        with open(path, "r+b") as f:
            f.truncate(os.path.getsize(path) - 10)
        with pytest.raises(ShardCorruptError, match="truncated"):
            ShardFile(path)

    def test_bad_magic_rejected(self, corpus, tmp_path):
        path = self._copy_shard(corpus, tmp_path)
        with open(path, "r+b") as f:
            f.write(b"NOTSHARD")
        with pytest.raises(ShardCorruptError, match="magic"):
            ShardFile(path)

    def test_future_version_rejected(self, corpus, tmp_path):
        path = self._copy_shard(corpus, tmp_path)
        with open(path, "r+b") as f:
            f.seek(8)
            f.write((99).to_bytes(4, "little"))
        with pytest.raises(ShardCorruptError, match="version 99"):
            ShardFile(path)

    def test_header_smaller_than_minimum(self, tmp_path):
        p = tmp_path / "tiny.dmlshard"
        p.write_bytes(b"DMLSHRD1")
        with pytest.raises(ShardCorruptError, match=str(HEADER_SIZE)):
            ShardFile(p)


class TestShardReader:
    def test_single_rank_yields_corpus_order(self, corpus):
        d, docs, _ = corpus
        reader = ShardReader(d, rank=0, world_size=1, read_ahead=16)
        got = list(reader)
        assert len(got) == len(reader) == len(docs)
        assert all(np.array_equal(a, b) for a, b in zip(got, docs))

    def test_record_strided_assignment_partitions_corpus(self, corpus):
        d, docs, _ = corpus
        for ws in (2, 3, 4):
            per_rank = [list(ShardReader(d, rank=r, world_size=ws)) for r in range(ws)]
            assert sum(len(p) for p in per_rank) == len(docs)
            for r, part in enumerate(per_rank):
                assert all(np.array_equal(a, docs[r + i * ws]) for i, a in enumerate(part))

    def test_reader_runs_on_background_thread(self, corpus):
        d, _, _ = corpus
        before = reader_activity()
        it = iter(ShardReader(d, rank=0, world_size=1, read_ahead=8))
        next(it)
        assert reader_activity() > before  # the activity counter advanced
        names = [t.name for t in threading.enumerate()]
        assert any(n == "dml-shard-reader" for n in names)
        it.close()

    def test_state_dict_carries_disk_location(self, corpus):
        d, docs, _ = corpus
        reader = ShardReader(d, rank=0, world_size=2)
        it = iter(reader)
        for _ in range(7):
            next(it)
        st = reader.state_dict()
        assert st["kind"] == "shards"
        assert st["global_offset"] == 14
        assert st["world_size"] == 2
        sid, off = reader.store.locate(14)
        assert (st["shard_id"], st["record_offset"]) == (sid, off)
        it.close()

    @pytest.mark.parametrize("old_ws,new_ws", [(4, 2), (2, 4), (2, 1), (1, 2)])
    def test_resume_across_world_sizes_zero_replay(self, corpus, old_ws, new_ws):
        """Consume a prefix on old_ws, save, resume on new_ws: the union of
        the two phases covers every record exactly once."""
        d, docs, _ = corpus
        # per-rank records consumed before the "preemption"; chosen so
        # k * old_ws divides every new_ws — the exact-resume precondition
        k = 12
        seen = []
        readers = [ShardReader(d, rank=r, world_size=old_ws) for r in range(old_ws)]
        iters = [iter(r) for r in readers]
        for _ in range(k):
            for it in iters:
                seen.append(next(it))
        state = readers[0].state_dict()
        assert state["global_offset"] == k * old_ws
        for it in iters:
            it.close()
        for r in range(new_ws):
            reader = ShardReader(d, rank=r, world_size=new_ws)
            reader.load_state_dict(state)
            seen.extend(reader)
        assert len(seen) == len(docs)  # 0 replayed, 0 skipped
        counts: dict = {}
        for rec in seen:
            key = rec.tobytes()
            counts[key] = counts.get(key, 0) + 1
        expected: dict = {}
        for doc in docs:
            key = doc.tobytes()
            expected[key] = expected.get(key, 0) + 1
        assert counts == expected

    def test_indivisible_offset_warns_and_rounds_down(self, corpus, caplog):
        d, _, _ = corpus
        reader = ShardReader(d, rank=0, world_size=3)
        state = {"v": 1, "kind": "shards", "epoch": None, "global_offset": 7,
                 "world_size": 7, "shard_id": 0, "record_offset": 7}
        import logging

        with caplog.at_level(logging.WARNING, logger="dmlcloud_tpu"):
            reader.load_state_dict(state)
        assert any("not divisible" in r.message for r in caplog.records)
        assert reader._shard_resume == 2  # 7 // 3

    def test_plain_state_degrades_to_replay_skip(self, corpus):
        d, docs, _ = corpus
        reader = ShardReader(d, rank=0, world_size=1)
        reader.load_state_dict({"v": 1, "epoch": None, "global_offset": 5, "world_size": 1})
        got = list(reader)
        assert len(got) == len(docs) - 5
        assert np.array_equal(got[0], docs[5])

    def test_state_after_full_consumption(self, corpus):
        d, docs, _ = corpus
        reader = ShardReader(d, rank=0, world_size=1)
        list(reader)
        st = reader.state_dict()
        assert st["global_offset"] == len(docs)
        assert st["shard_id"] == len(reader.store.shards)
        assert st["record_offset"] == 0

    def test_ctor_validation(self, corpus):
        d, _, _ = corpus
        with pytest.raises(ValueError):
            ShardReader(d, buffers=0)
        with pytest.raises(ValueError):
            ShardReader(d, read_ahead=0)


class TestPackEquivalence:
    def test_mmap_reader_equals_in_memory_through_pack_stream(self, corpus):
        d, docs, _ = corpus
        mem = DataPipeline.from_source(docs).pack_stream(256, chunk_docs=64)
        dsk = ShardReader(d, rank=0, world_size=1).pack_stream(256, chunk_docs=64)
        rows_m, rows_d = list(mem), list(dsk)
        assert len(rows_m) == len(rows_d)
        for a, b in zip(rows_m, rows_d):
            assert np.array_equal(a["tokens"], b["tokens"])
            assert np.array_equal(a["segment_ids"], b["segment_ids"])

    def test_mmap_reader_equals_in_memory_through_ffd(self, corpus):
        d, docs, _ = corpus
        mem = DataPipeline.from_source(docs).pack_stream(256, pack_window=64)
        dsk = ShardReader(d, rank=0, world_size=1).pack_stream(256, pack_window=64)
        for a, b in zip(list(mem), list(dsk)):
            assert np.array_equal(a["tokens"], b["tokens"])
            assert np.array_equal(a["segment_ids"], b["segment_ids"])


class TestFFDPacking:
    def test_determinism_lock(self):
        """Bit-identical rows across repeated runs — the receipt's
        reproducibility contract."""
        docs = _docs(300, seed=3)
        runs = []
        for _ in range(2):
            p = DataPipeline.from_source(docs).pack_stream(256, pack_window=128)
            runs.append(list(p))
        assert len(runs[0]) == len(runs[1])
        for a, b in zip(*runs):
            assert np.array_equal(a["tokens"], b["tokens"])
            assert np.array_equal(a["segment_ids"], b["segment_ids"])

    def test_conserves_tokens_and_segments(self):
        docs = _docs(250, seed=5)
        p = DataPipeline.from_source(docs).pack_stream(256, pack_window=64)
        rows = list(p)
        real = np.concatenate([r["tokens"][r["segment_ids"] > 0] for r in rows])
        assert sorted(real.tolist()) == sorted(np.concatenate(docs).tolist())
        # every row's segment ids are 1..k contiguous, padding strictly 0
        for r in rows:
            segs = r["segment_ids"]
            present = sorted(set(segs.tolist()) - {0})
            assert present == list(range(1, len(present) + 1))
            assert np.all(r["tokens"][segs == 0] == 0)

    def test_reclaims_greedy_padding(self):
        """The tentpole number: window FFD beats the chunked greedy packer
        on the pinned lognormal corpus and lands under the 0.10 target."""
        docs = _docs(600, seed=0)
        greedy = DataPipeline.from_source(docs).pack_stream(256, chunk_docs=192)
        ffd = DataPipeline.from_source(docs).pack_stream(256, pack_window=512)
        list(greedy), list(ffd)
        assert ffd.pack_stats.pad_fraction < greedy.pack_stats.pad_fraction
        assert ffd.pack_stats.pad_fraction <= 0.10

    def test_long_docs_split_into_full_rows(self):
        rs = np.random.RandomState(1)
        docs = [rs.randint(1, 99, size=700).astype(np.int32), np.arange(1, 20, dtype=np.int32)]
        p = DataPipeline.from_source(docs).pack_stream(256, pack_window=8)
        rows = list(p)
        real = np.concatenate([r["tokens"][r["segment_ids"] > 0] for r in rows])
        assert real.size == 700 + 19  # split_long places every token
        # the two full 256-slot pieces of the long doc are single-segment rows
        full = [r for r in rows if np.all(r["segment_ids"] == 1)]
        assert len(full) >= 2

    def test_split_long_false_truncates(self):
        docs = [np.arange(1, 400, dtype=np.int32)]
        p = DataPipeline.from_source(docs).pack_stream(256, pack_window=4, split_long=False)
        rows = list(p)
        assert len(rows) == 1
        assert np.array_equal(rows[0]["tokens"], np.arange(1, 257, dtype=np.int32))

    def test_open_bin_cap_bounds_memory(self):
        """More unpackable-together docs than the bin cap: rows still emit
        (eviction) and every token still lands exactly once."""
        docs = [np.full(200, i + 1, np.int32) for i in range(100)]  # none pair up
        p = DataPipeline.from_source(docs).pack_stream(256, pack_window=4)
        rows = list(p)
        real = np.concatenate([r["tokens"][r["segment_ids"] > 0] for r in rows])
        assert real.size == 200 * 100

    def test_pack_window_zero_is_greedy_mode(self):
        docs = _docs(100, seed=2)
        a = DataPipeline.from_source(docs).pack_stream(256, chunk_docs=64)
        b = DataPipeline.from_source(docs).pack_stream(256, chunk_docs=64, pack_window=0)
        for ra, rb in zip(list(a), list(b)):
            assert np.array_equal(ra["tokens"], rb["tokens"])

    def test_validation(self):
        docs = _docs(10)
        with pytest.raises(ValueError):
            DataPipeline.from_source(docs).pack_stream(256, pack_window=-1)


class TestBuilderEdgeCases:
    def test_write_shard_empty(self, tmp_path):
        info = write_shard(tmp_path / "empty.dmlshard", [])
        assert info["records"] == 0 and info["tokens"] == 0
        shard = ShardFile(tmp_path / "empty.dmlshard")
        assert len(shard) == 0
        shard.verify()

    def test_builder_rolls_by_token_budget(self, tmp_path):
        b = CorpusBuilder(tmp_path, shard_tokens=100)
        for _ in range(10):
            b.add(np.ones(40, np.int32))
        manifest = b.finalize()
        assert len(manifest["shards"]) > 1
        assert all(s["tokens"] <= 120 for s in manifest["shards"])
        with pytest.raises(RuntimeError):
            b.add(np.ones(3, np.int32))

    def test_reader_activity_counter_is_module_level(self, corpus):
        d, _, _ = corpus
        a = reader_activity()
        list(ShardReader(d, rank=0, world_size=1, read_ahead=32))
        assert reader_activity() > a


class TestFixedShapeRows:
    """The data plane's compile contract: every arm hands the stage rows of
    ONE shape, so a step precompiled from the first batch is the only
    program the run ever builds."""

    @pytest.mark.parametrize("arm", ["pad", "packed", "disk"])
    def test_stream_through_a_precompiled_stage_never_recompiles(self, corpus, single_runtime, arm):
        import jax
        import jax.numpy as jnp
        import optax

        import dmlcloud_tpu as dml
        from dmlcloud_tpu.parallel import mesh as mesh_lib

        d, docs, _ = corpus
        seq_len = 64

        def pad_row(doc):
            doc = doc[:seq_len]
            tokens = np.zeros(seq_len, np.int32)
            segs = np.zeros(seq_len, np.int32)
            tokens[: doc.size] = doc
            segs[: doc.size] = 1
            return {"tokens": tokens, "segment_ids": segs}

        if arm == "pad":
            stream = DataPipeline.from_source(docs[:48]).map(pad_row)
        elif arm == "packed":
            stream = DataPipeline.from_source(docs[:48]).pack_stream(seq_len, chunk_docs=16)
        else:
            stream = ShardReader(d).pack_stream(seq_len, pack_window=32)
        ds = stream.batch(
            4, drop_remainder=True,
            collate=lambda rows: {k: np.stack([r[k] for r in rows]) for k in ("tokens", "segment_ids")},
        )

        class Stage(dml.TrainValStage):
            def pre_stage(self):
                self.pipeline.register_model(
                    "emb", apply_fn=lambda p, t: p["emb"][t], params={"emb": jnp.ones((512, 4))}, verbose=False
                )
                self.pipeline.register_optimizer("sgd", optax.sgd(0.01))
                self.pipeline.register_dataset("train", ds, verbose=False)

            def step(self, state, batch):
                real = (batch["segment_ids"] > 0)[..., None]
                return jnp.sum(jnp.where(real, state.apply_fn(state.params, batch["tokens"]) ** 2, 0.0)) / jnp.sum(real)

            def val_epoch(self):
                pass

        pipeline = dml.TrainingPipeline(name=f"rows-{arm}", precompile=True)
        pipeline.set_mesh(mesh_lib.create_mesh({"data": 1}, devices=jax.devices()[:1]))
        pipeline.append_stage(Stage(), max_epochs=2)
        pipeline.run()
        assert pipeline.tracker["misc/worker_train_batches"][-1] >= 3
        assert pipeline.tracker["misc/recompiles"] == [0, 0]
