"""What the ``granite-train-8k`` per-layer metrics read: the window's clock, the
program's phase ``ssm_scan`` and the three flash kernels by name (``phases.py``;
the four ``train_ssm_*_share`` read their phases through it directly), against
``counts_granite.py``. A run with no such record (another driver's, an older
program's, or no device profile) gives None."""

from __future__ import annotations

from benchmark import counts, counts_granite, phases, reference_granite
from benchmark.readers_lfm2 import FLASH_KERNELS

def _spec(run):
    return dict(reference_granite.spec(run["config"]))


def train_step_mfu(run):
    """The step's operations times the window's steps over the window times the chips' bf16 peak, in per cent."""
    if not run.get("steps_in_window") or "mamba_n_heads" not in run.get("config", ()):
        return None
    shapes = run["train_shapes"]
    flops = counts_granite.train_flops_per_step(_spec(run), shapes["batch"], shapes["seq_len"])
    lo, hi = run["window"]
    return 100.0 * flops * run["steps_in_window"] / ((hi - lo) * run["peaks"]["bf16_flops"] * run["chips"])


def ssm_scan_roofline(run):
    """Least time a chip could take for the scans of a step (forward and
    backward, the chunked form at the published chunk, no recomputation) over
    the device time a traced step spent in the phase ``ssm_scan`` (forward,
    recomputed forward and backward alike), in per cent."""
    table = phases.by_phase(run, "train_step")
    if table is None or "config" not in run:
        return None
    took = sum(ns for (phase, _), ns in table["phases"].items() if phase == "ssm_scan") * 1e-9 / table["steps"]
    if not took:
        return None
    s, shapes = _spec(run), run["train_shapes"]
    least = counts.roofline_seconds(counts_granite.scan_flops_per_step(s, shapes["batch"], shapes["seq_len"]),
                                    counts_granite.scan_bytes_per_step(s, shapes["batch"], shapes["seq_len"]), run["peaks"])
    return 100.0 * least / took


def flash_attn_roofline(run):
    """Least time for the three flash kernels at these shapes over their device time in the traced steps, in per cent."""
    table = phases.kernels(run, "train_step")
    if table is None or not any(k in table["kernels"] for k in FLASH_KERNELS) or "mamba_n_heads" not in run.get("config", ()):
        return None
    took = sum(table["kernels"].get(k, 0) for k in FLASH_KERNELS) * 1e-9 / table["steps"]
    s, shapes = _spec(run), run["train_shapes"]
    least = counts.roofline_seconds(counts_granite.flash_flops_per_step(s, shapes["batch"], shapes["seq_len"]),
                                    counts_granite.flash_bytes_per_step(s, shapes["batch"], shapes["seq_len"]), run["peaks"])
    return 100.0 * least / took
