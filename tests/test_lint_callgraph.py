"""The PR-17 whole-program arm: DML5xx fixtures, the incremental cache,
baseline/autofix workflow, and the schema-v2 CLI contract.

Complements tests/test_lint.py (per-rule module fixtures): everything
here needs either the cross-file ProjectGraph pass, the LintCache, or the
new CLI flags. Cache tests build throwaway packages under tmp_path so
hash/graph invalidation can be exercised by actually editing files.
"""

import json
import os
import textwrap
from pathlib import Path
from unittest import mock

import pytest

from dmlcloud_tpu.lint import (
    DEFAULT_CACHE_PATH,
    FIXABLE_RULES,
    PROJECT_RULES,
    RULES,
    LintCache,
    apply_fixes,
    lint_paths,
)
from dmlcloud_tpu.lint.cli import main as lint_cli
from dmlcloud_tpu.lint.engine import expand_rule_ids

FIXTURES = Path(__file__).parent / "lint_fixtures"

#: package directory -> exact expected finding counts (and NOTHING else —
#: the clean companions in each package must stay silent)
PACKAGE_EXPECT = {
    "dml501": {"DML501": 2},
    "dml502": {"DML502": 3},
    "dml503": {"DML503": 2},
    "dml504": {"DML504": 2},
}


def _counts(findings):
    out = {}
    for f in findings:
        out[f.rule] = out.get(f.rule, 0) + 1
    return out


# --------------------------------------------------------------------------
# fixture corpus: one package per project rule
# --------------------------------------------------------------------------
class TestProjectRuleFixtures:
    @pytest.mark.parametrize("pkg", sorted(PACKAGE_EXPECT))
    def test_package_flags_exactly_its_rule(self, pkg):
        findings = lint_paths([FIXTURES / pkg])
        assert _counts(findings) == PACKAGE_EXPECT[pkg], [f.format() for f in findings]

    @pytest.mark.parametrize("pkg", sorted(PACKAGE_EXPECT))
    def test_clean_files_stay_clean(self, pkg):
        rule = pkg.upper()
        findings = lint_paths([FIXTURES / pkg])
        flagged = {Path(f.path).name for f in findings if f.rule == rule}
        assert "clean.py" not in flagged

    def test_no_callgraph_disables_project_rules(self):
        for pkg in PACKAGE_EXPECT:
            findings = lint_paths([FIXTURES / pkg], callgraph=False)
            assert not any(f.rule.startswith("DML5") for f in findings), pkg

    def test_registered_as_project_rules_not_module_rules(self):
        assert set(PACKAGE_EXPECT).issubset({r.lower() for r in PROJECT_RULES})
        assert not set(PROJECT_RULES) & set(RULES)

    def test_family_wildcard_expands_project_rules(self):
        expanded, unknown = expand_rule_ids(["DML5xx"])
        assert not unknown
        assert set(expanded) == set(PROJECT_RULES)

    def test_dml502_subsumes_renamed_dml211_pattern(self):
        # the import-rename shim (_alias.py re-exports scatter_tokens as
        # table_write) defeats DML211's vocabulary scoping; DML502 resolves
        # the reference through the graph and still fires
        findings = lint_paths([FIXTURES / "dml502"])
        renamed = [f for f in findings if Path(f.path).name == "renamed.py"]
        assert len(renamed) == 1 and renamed[0].rule == "DML502"
        assert not any(f.rule in ("DML211", "DML212") for f in findings)

    def test_pool_path_matches_serial(self):
        # the 1-CPU collapse is tested in test_lint.py; here we force a real
        # ProcessPoolExecutor and require identical output
        serial = lint_paths([FIXTURES / p for p in sorted(PACKAGE_EXPECT)])
        with mock.patch.object(os, "cpu_count", return_value=2):
            pooled = lint_paths([FIXTURES / p for p in sorted(PACKAGE_EXPECT)], jobs=2)
        assert pooled == serial

    def test_jobs_collapse_on_single_core(self):
        serial = lint_paths([FIXTURES / "dml501"])
        with mock.patch.object(os, "cpu_count", return_value=1):
            collapsed = lint_paths([FIXTURES / "dml501"], jobs=4)
        assert collapsed == serial


# --------------------------------------------------------------------------
# incremental cache
# --------------------------------------------------------------------------
PKG_FILES = {
    "__init__.py": "",
    "pools.py": """
        class KVBlockPool:
            def __init__(self, n):
                self.free = list(range(n))

            def alloc(self, k):
                blocks = [self.free.pop() for _ in range(k)]
                return blocks

            def release(self, blocks):
                self.free.extend(blocks)
        """,
    "app.py": """
        from .pools import KVBlockPool


        def run(n):
            pool = KVBlockPool(n)
            blocks = pool.alloc(2)
            pool.release(blocks)
            return len(blocks)
        """,
    "helpers.py": """
        def double(x):
            return 2 * x
        """,
    "threads.py": """
        from .helpers import double


        def run(x):
            return double(x)
        """,
    "timing.py": """
        import time


        class TimerStage:
            def train_epoch(self):
                t0 = time.time()
                return t0
        """,
}


@pytest.fixture
def pkg(tmp_path):
    root = tmp_path / "pkg"
    root.mkdir()
    for name, body in PKG_FILES.items():
        (root / name).write_text(textwrap.dedent(body).lstrip("\n"))
    return root


def _run(pkg, cache, **kw):
    stats = {}
    findings = lint_paths([pkg], cache=cache, stats=stats, **kw)
    linted = {Path(p).name for p in stats["linted"]}
    reused = {Path(p).name for p in stats["reused"]}
    return findings, linted, reused


class TestLintCache:
    def test_cold_then_warm(self, pkg, tmp_path):
        cache = tmp_path / "cache.json"
        cold, linted, reused = _run(pkg, cache)
        assert linted == set(PKG_FILES) and reused == set()
        assert _counts(cold) == {"DML108": 1}

        warm, linted, reused = _run(pkg, cache)
        assert linted == set() and reused == set(PKG_FILES)
        assert warm == cold  # cached findings replay byte-identically

    def test_leaf_edit_relints_only_reverse_importers(self, pkg, tmp_path):
        cache = tmp_path / "cache.json"
        _run(pkg, cache)
        leaf = pkg / "helpers.py"
        leaf.write_text(leaf.read_text() + "\n\ndef triple(x):\n    return 3 * x\n")
        _, linted, reused = _run(pkg, cache)
        assert linted == {"helpers.py", "threads.py"}
        assert reused == set(PKG_FILES) - linted

    def test_hub_edit_relints_transitive_importers(self, pkg, tmp_path):
        cache = tmp_path / "cache.json"
        _run(pkg, cache)
        hub = pkg / "pools.py"
        hub.write_text(hub.read_text() + "\n\ndef capacity(pool):\n    return len(pool.free)\n")
        _, linted, reused = _run(pkg, cache)
        assert linted == {"pools.py", "app.py"}
        assert reused == set(PKG_FILES) - linted

    def test_config_change_drops_cache(self, pkg, tmp_path):
        cache = tmp_path / "cache.json"
        _run(pkg, cache)
        _, linted, _ = _run(pkg, cache, ignore=["DML108"])
        assert linted == set(PKG_FILES)  # different signature: full cold run

    def test_corrupt_cache_degrades_to_cold(self, pkg, tmp_path):
        cache = tmp_path / "cache.json"
        cold, _, _ = _run(pkg, cache)
        cache.write_text("{definitely not json")
        again, linted, reused = _run(pkg, cache)
        assert linted == set(PKG_FILES) and reused == set()
        assert again == cold

    def test_warm_run_honors_cached_suppressions(self, pkg, tmp_path):
        # a DML5xx finding suppressed in a cached file must stay suppressed
        # when the project pass replays from the cache (family wildcard too)
        (pkg / "leak.py").write_text(
            textwrap.dedent(
                """
                from .pools import KVBlockPool


                def leaky(pool: KVBlockPool, flag):
                    blocks = pool.alloc(1)  # dmllint: disable=DML5xx -- test fixture
                    if flag:
                        pool.release(blocks)
                    return flag
                """
            ).lstrip("\n")
        )
        cache = tmp_path / "cache.json"
        cold, _, _ = _run(pkg, cache)
        assert not any(f.rule == "DML501" for f in cold)
        warm, linted, _ = _run(pkg, cache)
        assert "leak.py" not in linted
        assert not any(f.rule == "DML501" for f in warm)

    def test_project_findings_track_cached_summaries(self, pkg, tmp_path):
        # introduce a leak in ONE file: the project pass must see it even
        # though every OTHER file replays from the cache
        cache = tmp_path / "cache.json"
        _run(pkg, cache)
        (pkg / "app.py").write_text(
            textwrap.dedent(
                """
                from .pools import KVBlockPool


                def run(n, flag):
                    pool = KVBlockPool(n)
                    blocks = pool.alloc(2)
                    if flag:
                        pool.release(blocks)
                    return flag
                """
            ).lstrip("\n")
        )
        findings, linted, _ = _run(pkg, cache)
        assert "app.py" in linted and "helpers.py" not in linted
        assert any(f.rule == "DML501" and Path(f.path).name == "app.py" for f in findings)

    def test_plan_api_shapes(self, pkg, tmp_path):
        cache_path = tmp_path / "cache.json"
        lint_paths([pkg], cache=cache_path)
        cache = LintCache(cache_path)
        files = sorted(str(p) for p in pkg.glob("*.py"))
        to_lint, reuse = cache.plan(files)
        assert to_lint == [] and sorted(reuse) == files
        assert isinstance(DEFAULT_CACHE_PATH, str)


# --------------------------------------------------------------------------
# CLI: schema v2, exit codes, baseline, autofix
# --------------------------------------------------------------------------
class TestCliWorkflow:
    def _json(self, capsys, *argv):
        rc = lint_cli(["--json", *argv])
        return rc, json.loads(capsys.readouterr().out)

    def test_schema_v2_and_v1_compatibility(self, capsys):
        rc, payload = self._json(capsys, str(FIXTURES / "dml501"))
        assert rc == 1
        assert payload["version"] == 2
        assert payload["status"] == "findings"
        # v1 compatibility contract: every v1 key is still present with the
        # same shape and meaning
        assert {"version", "files_scanned", "findings", "counts"} <= set(payload)
        assert payload["counts"] == {"DML501": 2}
        for f in payload["findings"]:
            assert {"rule", "path", "line", "col", "message", "context"} <= set(f)

    def test_parse_error_status_and_exit_2(self, tmp_path, capsys):
        bad = tmp_path / "broken.py"
        bad.write_text("def oops(:\n")
        rc, payload = self._json(capsys, str(bad))
        assert rc == 2
        assert payload["status"] == "parse_error"
        assert payload["counts"] == {"DML999": 1}

    def test_clean_status(self, tmp_path, capsys):
        (tmp_path / "ok.py").write_text("x = 1\n")
        rc, payload = self._json(capsys, str(tmp_path))
        assert rc == 0 and payload["status"] == "clean"

    def test_select_and_ignore_family_wildcards(self, capsys):
        rc, payload = self._json(capsys, "--select", "DML5xx", str(FIXTURES / "dml503"))
        assert rc == 1 and payload["counts"] == {"DML503": 2}
        rc, payload = self._json(capsys, "--ignore", "DML5xx", str(FIXTURES / "dml503"))
        assert rc == 0 and payload["findings"] == []

    def test_baseline_roundtrip(self, tmp_path, capsys):
        target = tmp_path / "legacy.py"
        target.write_text(
            "import time\n\n\nclass LegacyStage:\n"
            "    def train_epoch(self):\n        return time.time()\n"
        )
        baseline = tmp_path / "baseline.json"
        assert lint_cli([str(target), "--write-baseline", str(baseline)]) == 0
        capsys.readouterr()
        # frozen findings are filtered out...
        rc, payload = self._json(capsys, "--baseline", str(baseline), str(target))
        assert rc == 0 and payload["status"] == "clean"
        # ...but NEW findings still surface
        target.write_text(
            target.read_text() + "\n    def val_epoch(self):\n        return time.time()\n"
        )
        rc, payload = self._json(capsys, "--baseline", str(baseline), str(target))
        assert rc == 1 and payload["counts"] == {"DML108": 1}

    def test_missing_baseline_is_usage_error(self, tmp_path, capsys):
        rc = lint_cli(["--baseline", str(tmp_path / "nope.json"), str(tmp_path)])
        assert rc == 2

    def test_fix_rewrites_and_is_idempotent(self, tmp_path, capsys):
        assert "DML108" in FIXABLE_RULES
        target = tmp_path / "fixme.py"
        target.write_text(
            "import time\n\n\nclass FixStage:\n    def train_epoch(self):\n"
            "        t0 = time.time()\n        return time.time() - t0\n"
        )
        rc, payload = self._json(capsys, "--fix", str(target))
        assert rc == 0 and payload["status"] == "clean"
        fixed = target.read_text()
        assert "time.time()" not in fixed and fixed.count("time.perf_counter()") == 2
        rc, _ = self._json(capsys, "--fix", str(target))
        assert rc == 0 and target.read_text() == fixed  # second run is a no-op

    def test_fix_suppress_inserts_directives(self, tmp_path, capsys):
        pkg = tmp_path / "pkg"
        pkg.mkdir()
        (pkg / "__init__.py").write_text("")
        (pkg / "machine.py").write_text((FIXTURES / "dml503" / "machine.py").read_text())
        rc = lint_cli(["--fix-suppress", str(pkg)])
        capsys.readouterr()
        assert rc == 0
        text = (pkg / "machine.py").read_text()
        assert text.count("# dmllint: disable=DML503") == 2
        assert lint_cli([str(pkg)]) == 0
        capsys.readouterr()

    def test_apply_fixes_only_touches_finding_lines(self, tmp_path):
        target = tmp_path / "partial.py"
        target.write_text(
            "import time\n\n\nclass MixStage:\n    def train_epoch(self):\n"
            "        clock = time.time  # reference on a non-finding line\n"
            "        t0 = time.time()\n        return clock, t0\n"
        )
        apply_fixes(lint_paths([target], callgraph=False))
        text = target.read_text()
        assert "clock = time.time  #" in text  # non-finding line untouched
        assert "t0 = time.perf_counter()" in text

    def test_cache_flag_end_to_end(self, tmp_path, capsys, monkeypatch):
        monkeypatch.chdir(tmp_path)
        (tmp_path / "mod.py").write_text("x = 1\n")
        assert lint_cli(["--cache", "--json", "mod.py"]) == 0
        capsys.readouterr()
        assert (tmp_path / DEFAULT_CACHE_PATH).is_file()
        assert lint_cli(["--cache", "--json", "mod.py"]) == 0
        capsys.readouterr()

    def test_list_rules_tags_project_scope(self, capsys):
        assert lint_cli(["--list-rules"]) == 0
        out = capsys.readouterr().out
        for rid in PROJECT_RULES:
            assert f"{rid}" in out
        assert "[project]" in out


# --------------------------------------------------------------------------
# self-analysis lock: the codebase itself must hold its own contracts
# --------------------------------------------------------------------------
class TestSelfAnalysis:
    def test_whole_program_pass_is_clean_on_repo(self):
        repo = Path(__file__).parent.parent
        targets = [repo / "dmlcloud_tpu", repo / "examples", repo / "chip_smoke.py", repo / "scripts"]
        findings = lint_paths([t for t in targets if t.exists()])
        dml5 = [f for f in findings if f.rule.startswith("DML5")]
        assert dml5 == [], [f.format() for f in dml5]
