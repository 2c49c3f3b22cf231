"""What the ``lfm2-train-8k`` per-layer metrics read: the window's counters the
driver kept (``moe/pairs_held`` and ``moe/load_max_over_mean`` a step), the
program's phases ``conv_op`` / ``moe_route`` / ``moe_experts`` and the three
flash kernels by name (``phases.py``), against ``counts_lfm2.py``. A run with
no such record (another driver's, or no device profile) gives None."""

from __future__ import annotations

import statistics

from benchmark import counts, counts_lfm2, phases, reference_lfm2

FLASH_KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")
GROUPED_KERNELS = ("ragged-dot",)


def _spec(run):
    return dict(reference_lfm2.spec(run["config"]))


def pairs_per_step(run):
    """Mean over the window's steps of the live pairs of a step's expert layers."""
    return statistics.fmean(run["pairs_held"]) if run.get("pairs_held") else None


def train_step_mfu(run):
    """The step's operations (live pairs from the counter) times the window's
    steps over the window times the chips' bf16 peak, in per cent."""
    pairs = pairs_per_step(run)
    if pairs is None or not run.get("steps_in_window"):
        return None
    shapes = run["train_shapes"]
    flops = counts_lfm2.train_flops_per_step(_spec(run), shapes["batch"], shapes["seq_len"], pairs)
    lo, hi = run["window"]
    return 100.0 * flops * run["steps_in_window"] / ((hi - lo) * run["peaks"]["bf16_flops"] * run["chips"])


def moe_experts_roofline(run):
    """Least time a chip could take for the grouped products of the traced
    steps' live pairs over the device time of the grouped-product kernels
    (``jax.lax.ragged_dot`` is a custom call named ``ragged-dot-*`` on the
    TPU; a Pallas kernel of the program's own would be ``moe_grouped_*``), in
    per cent."""
    pairs, table = pairs_per_step(run), phases.kernels(run, "train_step")
    if pairs is None or table is None:
        return None
    took = sum(ns for name, ns in table["kernels"].items() if name.startswith(GROUPED_KERNELS)) * 1e-9 / table["steps"]
    if not took:
        return None
    s = _spec(run)
    least = counts.roofline_seconds(counts_lfm2.grouped_flops_per_step(s, pairs), counts_lfm2.grouped_bytes_per_step(s, pairs),
                                    run["peaks"])
    return 100.0 * least / took


def flash_attn_roofline(run):
    """Least time for the three flash kernels at these shapes over their device time in the traced steps, in per cent."""
    table = phases.kernels(run, "train_step")
    if table is None or not any(k in table["kernels"] for k in FLASH_KERNELS) or "config" not in run:
        return None
    took = sum(table["kernels"].get(k, 0) for k in FLASH_KERNELS) * 1e-9 / table["steps"]
    s, shapes = _spec(run), run["train_shapes"]
    least = counts.roofline_seconds(counts_lfm2.flash_flops_per_step(s, shapes["batch"], shapes["seq_len"]),
                                    counts_lfm2.flash_bytes_per_step(s, shapes["batch"], shapes["seq_len"]), run["peaks"])
    return 100.0 * least / took


def held_load_max_over_mean(run):
    """Median over the window's steps of the fullest held expert's load over the mean of the held."""
    return statistics.median(run["load_max_over_mean"]) if run.get("load_max_over_mean") else None
