"""dmlcloud_tpu.compile: bucket padding correctness (zero-weight padded
rows, grads identical to unpadded), AOT precompile through the stage
(bounded signatures, ``misc/compile_ms``/``misc/recompiles``, stage-start
sharding validation), and compile-cache stats plumbing in ``diag --json``.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import dmlcloud_tpu as dml
from dmlcloud_tpu.compile import aot, buckets as bk, cache as cache_lib
from dmlcloud_tpu.parallel import mesh as mesh_lib


def _one_device_mesh():
    return mesh_lib.create_mesh({"data": 1}, devices=jax.devices()[:1])


# --------------------------------------------------------------- bucketing


class TestBucketPadding:
    def test_pad_to_bucket_shapes_and_mask(self):
        batch = {"x": np.ones((5, 4), np.float32), "y": np.ones((5, 1), np.float32)}
        padded = bk.pad_to_bucket(batch, (4, 8))
        assert padded["x"].shape == (8, 4)
        assert padded["y"].shape == (8, 1)
        np.testing.assert_array_equal(padded["sample_mask"], [1, 1, 1, 1, 1, 0, 0, 0])
        # padding rows are zeros, real rows untouched
        np.testing.assert_array_equal(padded["x"][:5], batch["x"])
        np.testing.assert_array_equal(padded["x"][5:], 0.0)

    def test_exact_fit_needs_no_padding(self):
        batch = {"x": np.ones((4, 2), np.float32)}
        padded = bk.pad_to_bucket(batch, (4, 8))
        assert padded["x"].shape == (4, 2)
        np.testing.assert_array_equal(padded["sample_mask"], np.ones(4, np.float32))

    def test_oversized_batch_rejected(self):
        with pytest.raises(ValueError, match="exceeds the largest bucket"):
            bk.pad_to_bucket({"x": np.ones((9, 2), np.float32)}, (4, 8))

    def test_existing_mask_is_padded_not_overwritten(self):
        batch = {"x": np.ones((3, 2), np.float32), "sample_mask": np.array([1.0, 0.5, 1.0], np.float32)}
        padded = bk.pad_to_bucket(batch, (4,))
        np.testing.assert_array_equal(padded["sample_mask"], [1.0, 0.5, 1.0, 0.0])

    def test_non_mapping_batch_padded_without_mask(self):
        out = bk.pad_to_bucket(np.ones((3, 2), np.float32), (4,))
        assert out.shape == (4, 2)

    def test_ragged_leaves_rejected(self):
        with pytest.raises(ValueError, match="disagree"):
            bk.pad_to_bucket(
                {"x": np.ones((3, 2), np.float32), "y": np.ones((4,), np.float32)}, (8,)
            )

    def test_masked_mean_matches_unpadded_loss_and_grads(self):
        """The correctness contract: a masked step on the PADDED batch has
        the same loss and the same gradients as the plain step on the
        unpadded batch — padded rows contribute exactly zero."""
        rng = np.random.RandomState(0)
        w0 = jnp.asarray(rng.randn(4, 1).astype(np.float32))
        x = rng.randn(5, 4).astype(np.float32)
        y = rng.randn(5, 1).astype(np.float32)
        padded = bk.pad_to_bucket({"x": x, "y": y}, (8,))

        def plain_loss(w):
            per = jnp.sum((jnp.asarray(x) @ w - jnp.asarray(y)) ** 2, axis=-1)
            return jnp.mean(per)

        def masked_loss(w):
            per = jnp.sum((jnp.asarray(padded["x"]) @ w - jnp.asarray(padded["y"])) ** 2, axis=-1)
            return bk.masked_mean(per, jnp.asarray(padded["sample_mask"]))

        l0, g0 = jax.value_and_grad(plain_loss)(w0)
        l1, g1 = jax.value_and_grad(masked_loss)(w0)
        np.testing.assert_allclose(np.asarray(l0), np.asarray(l1), rtol=1e-6)
        np.testing.assert_allclose(np.asarray(g0), np.asarray(g1), rtol=1e-6)

    def test_masked_sum_counts_real_rows_only(self):
        vals = jnp.ones((6, 3))
        mask = jnp.asarray([1, 1, 1, 1, 0, 0], jnp.float32)
        assert float(bk.masked_sum(vals, mask)) == 12.0

    def test_bucket_iterator_bounds_signature_set(self):
        batches = [{"x": np.ones((s, 2), np.float32)} for s in (8, 5, 3, 8, 1)]
        shapes = {b["x"].shape for b in bk.bucket_iterator(batches, (4, 8))}
        assert shapes == {(4, 2), (8, 2)}

    def test_resolve_buckets_validates(self):
        assert bk.resolve_buckets([8, 4, 8]) == (4, 8)
        with pytest.raises(ValueError):
            bk.resolve_buckets([])
        with pytest.raises(ValueError):
            bk.resolve_buckets([0, 4])


# ----------------------------------------------------------- AOT machinery


class TestAotPrimitives:
    def test_abstract_spec_and_signature(self):
        batch = {"x": np.zeros((4, 3), np.float32), "n": np.int32(7)}
        spec = aot.abstract_spec(batch)
        assert spec["x"].shape == (4, 3) and spec["x"].dtype == np.float32
        assert aot.signature_of((batch,)) == aot.signature_of((spec,))
        other = {"x": np.zeros((8, 3), np.float32), "n": np.int32(7)}
        assert aot.signature_of((batch,)) != aot.signature_of((other,))

    def test_validate_global_batch_spec_divisibility(self, mesh8):
        good = {"x": jax.ShapeDtypeStruct((16, 2), np.float32)}
        aot.validate_global_batch_spec(good, mesh8)
        bad = {"x": jax.ShapeDtypeStruct((6, 2), np.float32)}
        with pytest.raises(ValueError, match="not divisible"):
            aot.validate_global_batch_spec(bad, mesh8)

    def test_precompiled_step_registry_and_fallback(self):
        mesh = _one_device_mesh()
        fn = jax.jit(lambda x: x * 2)
        ps = aot.PrecompiledStep(fn, name="double")
        spec = aot.global_batch_spec({"v": np.zeros((4,), np.float32)}, mesh)["v"]
        ms = ps.precompile(spec)
        assert ms > 0.0 and ps.signatures == 1
        assert ps.precompile(spec) == 0.0  # idempotent

        x = mesh_lib.make_global_batch(np.arange(4, dtype=np.float32), mesh)
        np.testing.assert_array_equal(np.asarray(ps(x)), [0, 2, 4, 6])
        assert ps.pop_recompiles() == 0  # matched the precompiled signature

        y = mesh_lib.make_global_batch(np.arange(8, dtype=np.float32), mesh)
        np.testing.assert_array_equal(np.asarray(ps(y)), np.arange(8) * 2)
        assert ps._cache_size() == 2
        assert ps.pop_recompiles() == 1  # new signature counted once...
        ps(y)
        assert ps.pop_recompiles() == 0  # ...and only once

    def test_precompiled_step_requires_jitted_fn(self):
        with pytest.raises(TypeError, match="jitted"):
            aot.PrecompiledStep(lambda x: x)


# --------------------------------------------------- stage-level integration


class _MaskedStage(dml.TrainValStage):
    """Linear regression whose step zero-weights padded rows via the
    injected sample mask."""

    def __init__(self, sizes=(8, 8, 5, 3), feature_dim=4):
        super().__init__()
        self._sizes = sizes
        self._dim = feature_dim

    def pre_stage(self):
        rng = np.random.RandomState(42)
        w_true = rng.randn(self._dim, 1).astype(np.float32)
        batches = []
        for s in self._sizes:
            x = rng.randn(s, self._dim).astype(np.float32)
            batches.append({"x": x, "y": x @ w_true})
        self.pipeline.register_model(
            "linear",
            apply_fn=lambda p, x: x @ p["w"],
            params={"w": jnp.zeros((self._dim, 1))},
            verbose=False,
        )
        self.pipeline.register_optimizer("sgd", optax.sgd(0.05))
        self.pipeline.register_dataset("train", batches, verbose=False)

    def step(self, state, batch):
        pred = state.apply_fn(state.params, batch["x"])
        per_sample = jnp.sum((pred - batch["y"]) ** 2, axis=-1)
        if "sample_mask" in batch:
            return bk.masked_mean(per_sample, batch["sample_mask"])
        return jnp.mean(per_sample)

    def val_epoch(self):
        pass


def _run_pipeline(stage, epochs=2, **pipeline_kw):
    pipeline = dml.TrainingPipeline(name="compile-test", **pipeline_kw)
    pipeline.set_mesh(_one_device_mesh())
    pipeline.append_stage(stage, max_epochs=epochs)
    pipeline.run()
    return pipeline


class TestStageIntegration:
    def test_precompile_with_buckets_bounds_signatures(self, single_runtime):
        stage = _MaskedStage(sizes=(8, 8, 5, 3))
        pipeline = _run_pipeline(stage, precompile=True, buckets=(4, 8))
        # every ragged batch landed in a precompiled bucket: zero mid-run compiles
        assert pipeline.tracker["misc/recompiles"] == [0, 0]
        assert stage._train_compiled.signatures == 2
        assert stage._train_compiled._cache_size() == 2
        compile_ms = pipeline.tracker["misc/compile_ms"]
        assert compile_ms[0] is not None and compile_ms[0] > 0.0

    def test_precompile_without_buckets_counts_recompiles(self, single_runtime):
        stage = _MaskedStage(sizes=(8, 5, 3))
        pipeline = _run_pipeline(stage, precompile=True)
        # only the peeked (size-8) signature was precompiled; 5 and 3 were
        # mid-run compiles in epoch 1, already-seen signatures in epoch 2
        assert pipeline.tracker["misc/recompiles"] == [2, 0]
        assert stage._train_compiled.signatures == 1
        assert stage._train_compiled._cache_size() == 3

    def test_buckets_without_precompile_still_bound_shapes(self, single_runtime):
        stage = _MaskedStage(sizes=(8, 5, 3, 2))
        pipeline = _run_pipeline(stage, buckets=(4, 8))
        # no precompile phase: the two bucket signatures compile lazily
        # (epoch 1) but the set stays bounded at len(buckets)
        assert pipeline.tracker["misc/recompiles"] == [2, 0]
        assert "misc/compile_ms" not in pipeline.tracker
        assert stage._train_compiled._cache_size() == 2

    def test_training_loss_decreases_under_bucketing(self, single_runtime):
        stage = _MaskedStage(sizes=(8, 8, 5, 3))
        pipeline = _run_pipeline(stage, epochs=4, precompile=True, buckets=(4, 8))
        losses = pipeline.tracker["train/loss"]
        assert losses[-1] < losses[0]

    def test_declared_batch_spec_mismatch_errors_at_stage_start(self, single_runtime):
        class BadSpec(_MaskedStage):
            def batch_spec(self):
                # 6 rows cannot shard over the 8-way data axis
                return {
                    "x": jax.ShapeDtypeStruct((6, 4), np.float32),
                    "y": jax.ShapeDtypeStruct((6, 1), np.float32),
                }

        pipeline = dml.TrainingPipeline(name="badspec", precompile=True)
        pipeline.append_stage(BadSpec(), max_epochs=1)  # default mesh: 8 devices
        with pytest.raises(ValueError, match="not divisible"):
            pipeline.run()

    def test_one_shot_iterator_requires_batch_spec(self, single_runtime):
        class OneShot(_MaskedStage):
            def pre_stage(self):
                super().pre_stage()
                batches = self.pipeline.datasets.pop("train")
                self.pipeline.register_dataset("train", iter(batches), verbose=False)

        pipeline = dml.TrainingPipeline(name="oneshot", precompile=True)
        pipeline.set_mesh(_one_device_mesh())
        pipeline.append_stage(OneShot(), max_epochs=1)
        with pytest.raises(ValueError, match="one-shot iterator"):
            pipeline.run()

    def test_default_path_keeps_raw_jit_fns(self, single_runtime):
        stage = _MaskedStage(sizes=(8, 8))
        pipeline = _run_pipeline(stage)
        assert stage._train_compiled is None
        assert "misc/recompiles" not in pipeline.tracker


# -------------------------------------------------------- cache stats / diag


class TestCacheStats:
    @pytest.fixture
    def cache_on(self, monkeypatch):
        """Turn jax's cache switch on for one test (the session keeps it off,
        conftest.py) with no directory inherited from the environment, and
        un-latch afterwards so later tests compile with the session's
        (disabled) configuration. NOTE: never call ``jax.clear_caches()``
        here — on this jax/XLA:CPU it destabilizes live collective
        executables and later tests segfault."""
        from jax.experimental.compilation_cache import compilation_cache as jax_cache

        monkeypatch.delenv(cache_lib.ENV_VAR, raising=False)
        prev_dir = jax.config.jax_compilation_cache_dir
        jax.config.update("jax_enable_compilation_cache", True)
        jax_cache.reset_cache()  # jax latched "cache unused" at the session's first compile
        yield
        jax.config.update("jax_enable_compilation_cache", False)
        jax.config.update("jax_compilation_cache_dir", prev_dir)
        jax_cache.reset_cache()

    def test_configure_and_stats(self, tmp_path, cache_on):
        resolved = cache_lib.configure_cache(str(tmp_path / "xla"))
        assert resolved == str(tmp_path / "xla")
        # a fresh lambda is a fresh jit object: compiles (and persists)
        jax.jit(lambda x: jnp.sin(x) @ jnp.cos(x).T)(jnp.ones((64, 64))).block_until_ready()
        stats = cache_lib.cache_stats()
        assert stats["enabled"] and stats["dir"] == resolved
        assert stats["entries"] >= 1
        assert stats["size_bytes"] > 0

    def test_disabled_values_resolve_to_none(self):
        assert cache_lib.resolve_cache_dir(None) is None
        assert cache_lib.resolve_cache_dir(False) is None

    def test_env_var_wins_over_explicit_path(self, tmp_path, monkeypatch):
        monkeypatch.setenv(cache_lib.ENV_VAR, str(tmp_path / "from-env"))
        assert cache_lib.resolve_cache_dir(True) == str(tmp_path / "from-env")
        assert cache_lib.resolve_cache_dir(str(tmp_path / "explicit")) == str(tmp_path / "from-env")

    def test_default_is_inside_the_checkout(self, monkeypatch):
        import os

        monkeypatch.delenv(cache_lib.ENV_VAR, raising=False)
        monkeypatch.setenv("DMLCLOUD_COMPILE_CACHE_DIR", "/nonexistent/retired-knob")
        checkout = os.path.dirname(os.path.dirname(os.path.abspath(dml.__file__)))
        default = cache_lib.resolve_cache_dir(True)
        assert default == os.path.join(checkout, ".jax_cache") == cache_lib.default_cache_dir()
        assert not default.startswith(os.path.expanduser("~/.cache"))
        # without the variable an explicit path is still honoured
        assert cache_lib.resolve_cache_dir("/tmp/explicit-xla") == "/tmp/explicit-xla"

    def test_jax_switch_off_disables_configure(self, tmp_path):
        # the session's own state (conftest.py): nothing is configured or created
        assert not jax.config.jax_enable_compilation_cache
        assert cache_lib.configure_cache(str(tmp_path / "xla")) is None
        assert not (tmp_path / "xla").exists()
        assert cache_lib.cache_stats()["enabled"] is False

    def test_aot_hit_recorded_on_second_precompile(self, tmp_path, single_runtime, cache_on):
        """The persistent cache turns the second process's compile into a
        deserialization; in-process we can at least assert the hit/miss
        accounting: an identical program compiled through a FRESH jit fn
        adds no new cache entry -> counted as a hit."""
        mesh = _one_device_mesh()
        spec = aot.global_batch_spec({"v": np.zeros((16,), np.float32)}, mesh)["v"]
        cache_lib.configure_cache(str(tmp_path / "xla"))
        cache_lib.reset_process_stats()
        # each PrecompiledStep wraps a FRESH jit object, so the second
        # .lower().compile() re-traces — only the persistent cache can
        # turn it into a deserialization (a hit, no new entry)
        aot.PrecompiledStep(jax.jit(lambda x: jnp.tanh(x) * 3)).precompile(spec)
        first = cache_lib.cache_stats()
        aot.PrecompiledStep(jax.jit(lambda x: jnp.tanh(x) * 3)).precompile(spec)
        second = cache_lib.cache_stats()
        assert first["aot_misses"] >= 1
        assert second["aot_hits"] >= first["aot_hits"] + 1

    def test_diag_json_includes_compile_cache(self, capsys):
        from dmlcloud_tpu.__main__ import main as cli_main

        rc = cli_main(["diag", "--json"])
        info = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert rc == 0
        cache = info["compile_cache"]
        assert set(cache) >= {"enabled", "dir", "entries", "size_bytes", "aot_hits", "aot_misses"}
        assert cache["dir"]  # always actionable: configured dir or the default


# ------------------------------------------- the phase map beside the journal


class _TinyLMStage(dml.TrainValStage):
    """A two-layer decoder LM on the flash path, clipped, AdamW: every phase
    of a real train step, at toy widths."""

    def pre_stage(self):
        from dmlcloud_tpu.models.transformer import DecoderLM, TransformerConfig

        cfg = TransformerConfig(vocab_size=64, hidden_dim=32, num_layers=2, num_heads=4, num_kv_heads=2, mlp_dim=64,
                                max_seq_len=32, attn_impl="flash", dtype=jnp.float32)
        tokens = np.random.RandomState(0).randint(0, 64, size=(2, 32)).astype(np.int32)
        self.pipeline.register_model("lm", DecoderLM(cfg), init_args=(jnp.asarray(tokens),), verbose=False)
        self.pipeline.register_optimizer("adamw", optax.adamw(1e-3))
        self.pipeline.register_dataset("train", [tokens, tokens], verbose=False)

    def gradient_clip(self):
        return 1.0

    def step(self, state, batch):
        from dmlcloud_tpu.models.transformer import lm_loss

        return lm_loss(state.apply_fn({"params": state.params}, batch), batch)

    def val_epoch(self):
        pass


class TestPhaseMapBesideTheJournal:
    def test_armed_journal_gets_the_compiled_steps_map(self, single_runtime, tmp_path):
        """With a journal armed, precompile writes the step's phase map beside
        it and names the file in its compile span; in the map every
        instruction under a known scope is in that scope's phase, forward and
        backward, and what has no phase is reported, not dropped."""
        from dmlcloud_tpu.telemetry import journal as journal_mod
        from dmlcloud_tpu.utils.profiling import PHASES

        j = journal_mod.activate(journal_mod.SpanJournal(tmp_path))
        try:
            stage = _TinyLMStage()
            _run_pipeline(stage, epochs=1, precompile=True)
        finally:
            journal_mod.deactivate()
        spans = [r for r in j.tail(1024) if r["kind"] == "compile" and "train_step" in r["label"]]
        assert len(spans) == 1 and spans[0]["signature"] == 1
        path = spans[0]["phases"]
        assert path == str(tmp_path / "phases-_TinyLMStage.train_step-1.json")
        doc = json.load(open(path))
        assert doc["program"] == "_TinyLMStage.train_step"
        seen = {tuple(pd) for pd in doc["phases"].values() if pd[0]}
        assert {p for p, _ in seen} <= set(PHASES)
        for phase in ("embed", "attn_proj", "attn_kernel", "mlp", "norm", "loss_head"):
            assert {(phase, "fwd"), (phase, "bwd")} <= seen, phase
        assert {("grad_clip", "-"), ("optimizer", "-")} <= seen
        rest = [n for n, (phase, _) in doc["phases"].items() if phase is None]
        assert rest and len(rest) < len(doc["phases"])  # parameters, constants, XLA's copies
        j.close()

    def test_no_journal_no_map_and_no_hlo_text(self, single_runtime, tmp_path, monkeypatch):
        from dmlcloud_tpu.utils import profiling

        calls = []
        monkeypatch.setattr(profiling, "write_phase_map", lambda *a, **k: calls.append(a))
        stage = _MaskedStage(sizes=(8, 8))
        _run_pipeline(stage, epochs=1, precompile=True)
        assert stage._train_compiled.signatures == 1 and calls == []
