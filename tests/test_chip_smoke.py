"""chip_smoke.py's control flow, guarded without a chip: the rehearsal runs
the same phase functions at tiny widths on the CPU, and the verdict line
stays truthful — ``ok`` means "ran at full width on a TPU", so every run here
must end ``"ok": false`` with a non-zero exit code. The no-fallback rule is
itself under test: without ``--rehearse`` no phase may run off the TPU.
"""

import json
import math
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}


def _smoke(args, workdir):
    env = dict(os.environ)
    env.update(
        JAX_PLATFORMS="cpu",
        # one CPU device, as the one-chip machine has one chip (the session's
        # own flags ask for eight); compiled as cheaply as the session's programs
        XLA_FLAGS="--xla_backend_optimization_level=0",
        # the session keeps the persistent cache off (conftest.py); the
        # script is a chip-facing entry point and runs with it on
        JAX_ENABLE_COMPILATION_CACHE="true",
        JAX_COMPILATION_CACHE_DIR=str(workdir / "xla"),
    )
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), *args],
        env=env, cwd=workdir, capture_output=True, text=True, timeout=600,
    )
    lines = [json.loads(line) for line in proc.stdout.splitlines() if line.startswith("{")]
    assert lines, proc.stderr[-2000:]
    # the verdict is the very last line of standard output, not just the last JSON one
    assert json.loads(proc.stdout.rstrip().splitlines()[-1]) == lines[-1]
    return proc, lines


@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    proc, lines = _smoke(["--rehearse"], tmp_path_factory.mktemp("rehearse"))
    assert not any("phase_failed" in line for line in lines), proc.stderr[-4000:]
    return proc, lines


def _phase(lines, name):
    (line,) = [line for line in lines if line.get("phase") == name]
    return line


def test_rehearsal_train_phase(rehearsal):
    train = _phase(rehearsal[1], "train")
    losses = train["losses"]
    assert len(losses) >= 3 and all(math.isfinite(v) for v in losses) and losses[-1] < losses[0]
    assert train["recompiles_after_step_1"] == 0
    assert train["first_loss_rel_diff"] <= 2.0**-9
    assert train["reduced"]["num_layers"][1] < train["reduced"]["num_layers"][0]
    assert train["pallas_kernel_in_hlo"] is False  # the CPU takes the XLA twin; only a TPU must hold the kernel


def test_rehearsal_serve_phase(rehearsal):
    serve = _phase(rehearsal[1], "serve")
    assert serve["all_ok"] and serve["leaked_blocks"] == 0
    assert 0 < serve["signatures"] <= serve["signature_budget"]
    identical, total = map(int, serve["token_identical_to_generate"].split("/"))
    assert total == serve["requests"] and identical + len(serve["divergences"]) == total


def test_rehearsal_ends_not_ok(rehearsal):
    proc, lines = rehearsal
    assert lines[-2] == {"rehearsal": True, "phases_passed": ["train_phase", "serve_phase"]}
    assert lines[-1] == {"ok": False, "device": CPU}
    assert proc.returncode != 0


def test_compile_cache_is_where_the_environment_says(rehearsal, tmp_path_factory):
    caches = [line["compile_cache"] for line in rehearsal[1] if "compile_cache" in line]
    assert len({c["dir"] for c in caches}) == 1 and caches[0]["dir"].endswith("/xla")
    assert caches[0]["dir"].startswith(str(tmp_path_factory.getbasetemp()))
    assert caches[0]["entries"] == 0 < caches[-1]["entries"]


def test_refuses_to_run_a_phase_without_a_tpu(tmp_path):
    proc, lines = _smoke([], tmp_path)
    assert proc.returncode != 0
    assert lines[-1] == {"ok": False, "device": CPU}
    assert any("refused" in line for line in lines)
    assert not any("phase" in line or "rehearsal" in line for line in lines)
