"""Self-lint gate (tier-1): the framework, its examples, the chip smoke and
the scripts must satisfy the very contracts the linter enforces — zero
findings over ``dmlcloud_tpu/``, ``examples/``, ``chip_smoke.py``, ``scripts/``,
with ALL rule families enabled (sync-point DML1xx, sharding DML2xx,
concurrency DML3xx).

This is the CI tripwire the lint subsystem exists for: a future Stage
subclass, example, or hot-loop edit that reintroduces a host sync, an
undonated train step, a typo'd mesh axis, or a half-locked thread protocol
fails HERE, on CPU, at review time — not three PRs later on a chip.
Legitimate exceptions carry a ``# dmllint: disable=...`` with a
justification (see stage.py's eager bisection path for the canonical one).
``scripts/lint_gate.sh`` runs the same scan as a GitHub-annotating CI step.
"""

from pathlib import Path

import pytest

import dmlcloud_tpu
from dmlcloud_tpu.lint import lint_paths

PACKAGE_DIR = Path(dmlcloud_tpu.__file__).resolve().parent
REPO_ROOT = PACKAGE_DIR.parent


def _report(findings):
    return "\n".join(f.format() for f in findings)


def test_package_lints_clean():
    findings = lint_paths([PACKAGE_DIR])
    assert findings == [], (
        f"dmlcloud_tpu/ violates its own sync-point contract:\n{_report(findings)}\n"
        "Fix the hazard or suppress it with '# dmllint: disable=ID -- why'."
    )


def test_examples_lint_clean():
    examples = REPO_ROOT / "examples"
    if not examples.is_dir():  # installed-package runs have no examples tree
        pytest.skip("examples/ not present next to the package")
    findings = lint_paths([examples])
    assert findings == [], (
        f"examples/ violate the sync-point contract:\n{_report(findings)}\n"
        "Examples are copied verbatim by users — they must model the contract."
    )


def test_examples_and_scripts_verify_clean():
    """Self-VERIFY gate (PR 20): the IR-level pass over every example and
    script that registers a ``dml_verify_programs()`` hook — the programs
    users copy must clear the DML6xx contracts (donation effective in the compiled
    artifact, no baked-in host callbacks, axes resolving, budgets met).
    Any justified suppression carries a rationale comment at its anchor."""
    from dmlcloud_tpu.lint.ir import verify_paths

    targets = [p for p in (REPO_ROOT / "examples", REPO_ROOT / "scripts") if p.exists()]
    if not targets:  # installed-package runs carry neither
        pytest.skip("examples/ and scripts/ not present next to the package")
    stats: dict = {}
    findings = verify_paths(targets, stats=stats)
    assert findings == [], (
        f"examples/scripts programs violate the IR-verify contract:\n{_report(findings)}\n"
        "Fix the program or suppress with '# dmllint: disable=ID -- why'."
    )
    # the lock is meaningful only while hooks exist and programs trace
    assert stats["programs"] >= 1


def test_bench_and_scripts_lint_clean():
    """chip_smoke.py puts the train and serve paths on the chip and scripts/
    reads the traces — a host sync in a loop or a donated-buffer read THERE
    misleads whoever runs them, so they sit under the same gate as the
    framework."""
    targets = [p for p in (REPO_ROOT / "chip_smoke.py", REPO_ROOT / "scripts") if p.exists()]
    if not targets:  # installed-package runs carry neither
        pytest.skip("chip_smoke.py / scripts/ not present next to the package")
    findings = lint_paths(targets)
    assert findings == [], (
        f"chip_smoke.py / scripts/ violate the lint contract:\n{_report(findings)}\n"
        "Fix the hazard or suppress it with '# dmllint: disable=ID -- why'."
    )
