"""The pod-scale recipes (BASELINE configs 4-5) must run end to end on the
8-device CPU mesh in toy mode — same code path as the v5p-64 invocations
documented in their module docstrings (mesh + partition rules + remat +
chunked loss + Orbax step checkpointing), only the sizes differ."""

import os
import sys

_EXAMPLES = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "examples")
sys.path.insert(0, _EXAMPLES)


def _run(module_name, argv, monkeypatch):
    import importlib

    mod = importlib.import_module(module_name)
    monkeypatch.setattr(sys, "argv", [f"{module_name}.py"] + argv)
    return mod.main()


def test_pod_clip_vit_toy(tmp_path, monkeypatch):
    stage = _run(
        "pod_clip_vit",
        ["--toy", "--mesh", "data=2,fsdp=4", "--checkpoint-dir", str(tmp_path)],
        monkeypatch,
    )
    loss = [float(v) for v in stage.tracker["train/loss"]]
    acc = [float(v) for v in stage.tracker["train/accuracy"]]
    assert len(loss) == 2  # toy caps at 2 epochs
    assert loss[-1] < loss[0], loss  # the contrastive objective has signal
    assert acc[-1] >= acc[0], acc
    run_dir = next(p for p in tmp_path.iterdir() if p.is_dir())
    assert (run_dir / "config.yaml").exists()
    assert (run_dir / "log.txt").stat().st_size > 0


def test_pod_llama_fsdp_toy(tmp_path, monkeypatch):
    stage = _run(
        "pod_llama_fsdp",
        [
            "--toy", "--mesh", "data=2,fsdp=4", "--remat", "--chunked-loss", "128",
            "--grad-accum", "2", "--epochs", "2",
            "--checkpoint-dir", str(tmp_path), "--save-every-steps", "3",
        ],
        monkeypatch,
    )
    loss = [float(v) for v in stage.tracker["train/loss"]]
    assert len(loss) == 2 and loss[-1] < loss[0], loss
    # the sharded params really follow llama_partition_rules on this mesh:
    # every rule names fsdp first, so at least the big kernels must be split
    spec = stage.state.params["lm_head"]["kernel"].sharding.spec
    assert "fsdp" in str(spec), spec
    run_dir = next(p for p in tmp_path.iterdir() if p.is_dir())
    assert (run_dir / "config.yaml").exists()
    # step-granular Orbax saves landed (cadence 3 over 4-step epochs)
    state_dir = run_dir / "state"
    assert state_dir.exists() and any(state_dir.iterdir())
