"""IR-level program verifier (doc/lint.md DML6xx): the CPU tracer, the
rules over jaxpr + compiled artifact, the fixture corpus with EXACT
counts (including the dropped-donation case the AST pass provably passes
clean), the ``verify`` CLI, ``lint --ir`` integration with warm-cache
byte identity, the centralized :meth:`ServeEngine.signature_budget`
formula, and the runtime arms (``TrainingPipeline(verify=...)`` /
``ServeEngine(verify=...)``).
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import dmlcloud_tpu as dml
from dmlcloud_tpu.lint import LintError
from dmlcloud_tpu.lint.engine import expand_rule_ids, lint_paths
from dmlcloud_tpu.lint.ir import (
    ProgramSpec, run_ir_rules, trace_program, verify_file, verify_main,
    verify_programs,
)
from dmlcloud_tpu.serve import ServeEngine

FIXTURES = os.path.join(os.path.dirname(__file__), "verify_fixtures")


def _fx(name):
    return os.path.join(FIXTURES, name)


def _rules(findings):
    return sorted(f.rule for f in findings)


# ------------------------------------------------- signature budget formula


class TestSignatureBudget:
    """Satellite lock: ONE formula, equal to the historical inline math."""

    @pytest.mark.parametrize("n_bb,n_tb", [(1, 1), (2, 3), (3, 4), (5, 2)])
    def test_matches_historical_inline_math(self, n_bb, n_tb):
        # plain: decode grid + prefill per table bucket
        b = ServeEngine.signature_budget(n_bb, n_tb)
        assert b["step"] == n_bb * n_tb + n_tb
        assert b["total"] == n_bb * n_tb + n_tb
        # spec: doubled prefill, fallback decode, draft+verify per round
        b = ServeEngine.signature_budget(n_bb, n_tb, spec=True)
        assert b["step"] == 2 * n_tb + n_bb * n_tb
        assert b["spec"] == n_bb * n_tb
        assert b["total"] == (2 * n_tb + n_bb * n_tb) + 2 * (n_bb * n_tb)
        # medusa: target-only prefill, fallback decode, one fused round sig
        b = ServeEngine.signature_budget(n_bb, n_tb, medusa=True)
        assert b["step"] == n_bb * n_tb + n_tb
        assert b["medusa"] == n_bb * n_tb
        assert b["total"] == (n_bb * n_tb + n_tb) + n_bb * n_tb
        # prefix cache: exactly one extra COW-copy signature, any mode
        for kw in ({}, {"spec": True}, {"medusa": True}):
            base = ServeEngine.signature_budget(n_bb, n_tb, **kw)["total"]
            plus = ServeEngine.signature_budget(n_bb, n_tb, prefix_cache=True, **kw)
            assert plus["copy"] == 1 and plus["total"] == base + 1

    def test_spec_and_medusa_mutually_exclusive(self):
        with pytest.raises(ValueError, match="mutually exclusive"):
            ServeEngine.signature_budget(2, 2, spec=True, medusa=True)


# ----------------------------------------------------------------- tracer


class TestTracer:
    def test_dropped_donation_is_visible_in_the_artifact(self):
        def step(state, batch):
            # an output of another SIZE: equal-size buffers alias whatever their dtype
            return (state * 2.0 + batch).sum(axis=0)

        spec = jax.ShapeDtypeStruct((64, 64), jnp.float32)
        tp = trace_program(ProgramSpec(
            name="drop", fn=step, args=(spec, spec), donate_argnums=(0,),
        ))
        assert tp.trace_error is None
        assert tp.donated_bytes == 64 * 64 * 4
        assert tp.aliased_bytes == 0
        assert tp.donation_warnings  # jit said so, once, as a warning
        assert _rules(run_ir_rules(tp)) == ["DML601"]

    def test_clean_donation_aliases_fully(self):
        def step(state, batch):
            return state * 2.0 + batch

        spec = jax.ShapeDtypeStruct((64, 64), jnp.float32)
        tp = trace_program(ProgramSpec(
            name="clean", fn=step, args=(spec, spec), donate_argnums=(0,),
        ))
        assert tp.aliased_bytes == tp.donated_bytes == 64 * 64 * 4
        assert run_ir_rules(tp) == []

    def test_unbound_collective_axis_is_dml602(self):
        def step(x):
            return jax.lax.psum(x, axis_name="model")

        tp = trace_program(ProgramSpec(
            name="axes", fn=step,
            args=(jax.ShapeDtypeStruct((8,), jnp.float32),),
            mesh=(("data", 1),),
        ))
        findings = run_ir_rules(tp)
        assert _rules(findings) == ["DML602"]
        assert "model" in findings[0].message

    def test_host_callback_is_dml603(self):
        def step(x):
            y = jax.pure_callback(
                lambda v: np.asarray(v), jax.ShapeDtypeStruct(x.shape, x.dtype), x
            )
            return y + 1.0

        tp = trace_program(ProgramSpec(
            name="cb", fn=step, args=(jax.ShapeDtypeStruct((4,), jnp.float32),),
        ))
        assert tp.callback_prims.get("pure_callback") == 1
        assert _rules(run_ir_rules(tp)) == ["DML603"]

    def test_hbm_budget_dml604_fires_and_clears(self):
        def step(x):
            return x @ x.T

        spec = jax.ShapeDtypeStruct((64, 64), jnp.float32)
        over = trace_program(ProgramSpec(
            name="hog", fn=step, args=(spec,), hbm_budget_bytes=1024,
        ))
        assert over.peak_bytes is not None and over.peak_bytes > 1024
        assert _rules(run_ir_rules(over)) == ["DML604"]
        within = trace_program(ProgramSpec(
            name="hog", fn=step, args=(spec,), hbm_budget_bytes=1 << 30,
        ))
        assert run_ir_rules(within) == []

    def test_signature_surface_dml605_needs_no_fn(self):
        over = trace_program(ProgramSpec(
            name="surface", fn=None, signature_surface=12, signature_budget=8,
        ))
        assert over.trace_error is None
        assert _rules(run_ir_rules(over)) == ["DML605"]
        within = trace_program(ProgramSpec(
            name="surface", fn=None, signature_surface=8, signature_budget=8,
        ))
        assert run_ir_rules(within) == []

    def test_broken_program_is_dml999(self):
        def step(x):
            raise RuntimeError("user code explodes at trace time")

        tp = trace_program(ProgramSpec(
            name="boom", fn=step, args=(jax.ShapeDtypeStruct((2,), jnp.float32),),
        ))
        assert "user code explodes" in tp.trace_error
        assert _rules(run_ir_rules(tp)) == ["DML999"]


# --------------------------------------------------------- fixture corpus


class TestFixtureCorpus:
    def test_dml601_bad_exactly_one(self):
        findings = verify_file(_fx("dml601_bad.py"))
        assert _rules(findings) == ["DML601"]
        assert findings[0].context == "dropped_donation_step"

    def test_dml601_clean_exactly_zero(self):
        assert verify_file(_fx("dml601_clean.py")) == []

    def test_dml604_bad_exactly_one(self):
        findings = verify_file(_fx("dml604_bad.py"))
        assert _rules(findings) == ["DML604"]

    def test_suppression_comment_reaches_the_ir_pass(self):
        # two identical callback programs; the one whose def line carries
        # ``# dmllint: disable=DML603`` is silent
        findings = verify_file(_fx("dml603_suppressed.py"))
        assert _rules(findings) == ["DML603"]
        assert findings[0].context == "flagged_callback_step"

    def test_dml205_provably_passes_the_dropped_donation_clean(self):
        """THE tentpole contrast: the AST donation rule sees the declared
        ``donate_argnums`` and stays quiet; only the IR pass (DML601)
        catches that the compiled executable dropped it."""
        ast_findings = lint_paths([_fx("dml601_bad.py")])
        assert "DML205" not in _rules(ast_findings)
        ir_findings = lint_paths([_fx("dml601_bad.py")], ir=True)
        assert "DML601" in _rules(ir_findings)

    def test_wildcard_select_and_ignore(self):
        assert set(expand_rule_ids(["DML6xx"])[0]) == {
            "DML601", "DML602", "DML603", "DML604", "DML605"
        }
        assert _rules(verify_file(_fx("dml601_bad.py"), select=["DML6xx"])) == ["DML601"]
        assert verify_file(_fx("dml601_bad.py"), ignore=["DML6xx"]) == []
        assert verify_file(_fx("dml601_bad.py"), select=["DML604"]) == []


# -------------------------------------------------------------- verify CLI


class TestVerifyCli:
    def test_json_schema_and_exact_counts(self, capsys):
        rc = verify_main([FIXTURES, "--json"])
        out = json.loads(capsys.readouterr().out)
        assert rc == 1
        assert out["version"] == 1
        assert out["status"] == "findings"
        assert out["files_scanned"] == 4
        assert out["programs"] == 5
        assert out["counts"] == {"DML601": 1, "DML603": 1, "DML604": 1}

    def test_clean_file_exits_zero(self, capsys):
        rc = verify_main([_fx("dml601_clean.py"), "--json"])
        out = json.loads(capsys.readouterr().out)
        assert rc == 0 and out["status"] == "clean" and out["findings"] == []

    def test_text_mode_prints_findings(self, capsys):
        rc = verify_main([_fx("dml604_bad.py")])
        out = capsys.readouterr().out
        assert rc == 1 and "DML604" in out and "hbm_hog_step" in out

    def test_import_error_is_dml999_exit_2(self, tmp_path, capsys):
        bad = tmp_path / "broken_hook.py"
        bad.write_text(
            "raise RuntimeError('hook module explodes at import')\n"
            "def dml_verify_programs():\n    return []\n"
        )
        rc = verify_main([str(bad), "--json"])
        out = json.loads(capsys.readouterr().out)
        assert rc == 2
        assert out["status"] == "trace_error"
        assert out["counts"] == {"DML999": 1}

    def test_hbm_budget_flag_fills_unset_budgets(self, capsys):
        # dml601_clean declares no budget; --hbm-budget 1 makes its step
        # exceed it -> DML604 appears without touching the fixture
        rc = verify_main([_fx("dml601_clean.py"), "--json", "--hbm-budget", "1"])
        out = json.loads(capsys.readouterr().out)
        assert rc == 1 and out["counts"] == {"DML604": 1}


# -------------------------------------------------------- lint integration


class TestLintIrIntegration:
    def test_warm_ir_run_is_byte_identical_to_cold(self, tmp_path, capsys):
        from dmlcloud_tpu.lint.cli import main as lint_main

        cache = str(tmp_path / "cache.json")
        argv = [FIXTURES, "--ir", "--cache", cache, "--select", "DML6xx"]
        rc_cold = lint_main(argv)
        cold = capsys.readouterr().out
        rc_warm = lint_main(argv)
        warm = capsys.readouterr().out
        assert rc_cold == rc_warm == 1
        assert warm == cold  # byte-identical through the incremental cache
        assert "DML601" in cold and "DML604" in cold

    def test_plain_and_ir_cache_states_never_cross(self, tmp_path, capsys):
        from dmlcloud_tpu.lint.cli import main as lint_main

        cache = str(tmp_path / "cache.json")
        sel = ["--select", "DML6xx"]
        assert lint_main([FIXTURES, "--cache", cache] + sel) == 0  # no IR pass
        capsys.readouterr()
        # a warm --ir run must NOT reuse the plain run's entries
        assert lint_main([FIXTURES, "--ir", "--cache", cache] + sel) == 1
        assert "DML601" in capsys.readouterr().out


# ------------------------------------------------------------ runtime arms


class _LinearStage(dml.TrainValStage):
    def pre_stage(self):
        rng = np.random.RandomState(0)
        w_true = rng.randn(4, 1).astype(np.float32)
        batches = []
        for s in (8, 5):
            x = rng.randn(s, 4).astype(np.float32)
            batches.append({"x": x, "y": x @ w_true})
        self.pipeline.register_model(
            "linear", apply_fn=lambda p, x: x @ p["w"],
            params={"w": jnp.zeros((4, 1))}, verbose=False,
        )
        self.pipeline.register_optimizer("sgd", optax.sgd(0.05))
        self.pipeline.register_dataset("train", batches, verbose=False)

    def step(self, state, batch):
        from dmlcloud_tpu.compile import buckets as bk

        pred = state.apply_fn(state.params, batch["x"])
        per = jnp.sum((pred - batch["y"]) ** 2, axis=-1)
        if "sample_mask" in batch:
            return bk.masked_mean(per, batch["sample_mask"])
        return jnp.mean(per)

    def val_epoch(self):
        pass


def _pipeline(**kw):
    from dmlcloud_tpu.parallel import mesh as mesh_lib

    p = dml.TrainingPipeline(name="verify-test", precompile=True,
                             buckets=(8,), **kw)
    p.set_mesh(mesh_lib.create_mesh({"data": 1}, devices=jax.devices()[:1]))
    p.append_stage(_LinearStage(), max_epochs=1)
    return p


class TestPipelineArm:
    def test_warn_mode_clean_run_records_zero_findings(self, single_runtime):
        p = _pipeline(verify="warn")
        p.run()
        assert p.verify_findings == []

    def test_error_mode_raises_on_hbm_budget(self, single_runtime):
        p = _pipeline(verify="error", hbm_budget=1)
        with pytest.raises(LintError, match="DML604"):
            p.run()
        assert "DML604" in _rules(p.verify_findings)

    def test_invalid_mode_rejected(self):
        with pytest.raises(ValueError, match="verify"):
            dml.TrainingPipeline(name="bad", verify="loud")


class TestEngineArm:
    def test_clean_engine_verifies_with_zero_findings(self, tiny_model):
        model, params = tiny_model
        eng = ServeEngine(model, params, num_blocks=64, block_size=4,
                          max_slots=2, prefill_chunk=8, verify="warn")
        assert eng.verify_findings == []
        # the DML605 lock: the independently enumerated surface equals the
        # centralized budget the TraceGuards are armed with
        assert eng._enumerate_signature_surface() == eng.max_signatures

    def test_error_mode_raises_on_hbm_budget(self, tiny_model):
        model, params = tiny_model
        with pytest.raises(LintError, match="DML604"):
            ServeEngine(model, params, num_blocks=64, block_size=4,
                        max_slots=2, prefill_chunk=8,
                        verify="error", hbm_budget=1000)

    def test_invalid_mode_rejected(self, tiny_model):
        model, params = tiny_model
        with pytest.raises(ValueError, match="verify"):
            ServeEngine(model, params, num_blocks=64, block_size=4,
                        max_slots=2, verify="loud")

    def test_journal_records_preflight_spans(self, tmp_path):
        from dmlcloud_tpu.telemetry.journal import SpanJournal, activate, deactivate

        j = SpanJournal(tmp_path)
        activate(j)
        try:
            findings = verify_programs([ProgramSpec(
                name="journaled", fn=lambda x: x * 2.0,
                args=(jax.ShapeDtypeStruct((4,), jnp.float32),),
            )])
        finally:
            deactivate()
        j.close()
        recs = [json.loads(line) for line in
                (tmp_path / "journal-rank0.jsonl").read_text().splitlines()]
        assert findings == []
        pre = [r for r in recs if r["kind"] == "preflight"]
        assert len(pre) == 1
        assert pre[0]["label"] == "journaled"
