"""What the ``laguna-train-8k`` per-layer metrics read: the window's counters the
driver kept (``moe/pairs_held``, ``moe/overflow_layers`` a step), the three flash
kernels by name and, for the window layers' calls alone, by the phase the
program's map gives them (``attn_window_kernel``; ``phases.py``), against
``counts_laguna.py``. A run with no such record (another driver's, an older
program's, or no device profile) gives None."""

from __future__ import annotations

import statistics

from benchmark import counts, counts_laguna, phases, reference_laguna
from benchmark.readers_lfm2 import FLASH_KERNELS, GROUPED_KERNELS, pairs_per_step  # noqa: F401  (the kernels' names and the counter are the same)


def _spec(run):
    return dict(reference_laguna.spec(run["config"]))


def overflow_layers_per_step(run):
    """Mean over the window's steps of the expert layers whose live rows overflowed their row bound."""
    return statistics.fmean(run["overflow_layers"]) if run.get("overflow_layers") else None


def train_step_mfu(run):
    """The step's operations (live pairs from the counter) times the window's
    steps over the window times the chips' bf16 peak, in per cent."""
    pairs = pairs_per_step(run)
    if pairs is None or not run.get("steps_in_window"):
        return None
    shapes = run["train_shapes"]
    flops = counts_laguna.train_flops_per_step(_spec(run), shapes["batch"], shapes["seq_len"], pairs)
    lo, hi = run["window"]
    return 100.0 * flops * run["steps_in_window"] / ((hi - lo) * run["peaks"]["bf16_flops"] * run["chips"])


def moe_experts_roofline(run):
    """Least time a chip could take for the grouped products of the traced
    steps' live pairs over the device time of the grouped-product kernels, in per cent."""
    pairs, table = pairs_per_step(run), phases.kernels(run, "train_step")
    if pairs is None or table is None:
        return None
    took = sum(ns for name, ns in table["kernels"].items() if name.startswith(GROUPED_KERNELS)) * 1e-9 / table["steps"]
    if not took:
        return None
    s = _spec(run)
    least = counts.roofline_seconds(counts_laguna.grouped_flops_per_step(s, pairs), counts_laguna.grouped_bytes_per_step(s, pairs),
                                    run["peaks"])
    return 100.0 * least / took


def flash_seconds_per_step(run, phase=None):
    """Device seconds a traced step spent in the three flash kernels; with
    ``phase``, in those of their calls the program's phase map puts there."""
    found = phases.program_ops(run, "train_step")
    if found is None:
        return None
    phase_map = phases.load_map(run, "train_step") if phase else {}
    if phase_map is None:
        return None
    steps, ops = found
    took = sum(ns for instruction, ns, is_call in ops if is_call and instruction.startswith(FLASH_KERNELS)
               and (phase is None or (phase_map.get(instruction) or (None,))[0] == phase))
    return took * 1e-9 / steps if took else None


def flash_roofline(run, kinds, phase=None):
    """Least time for the flash kernels of the layers of ``kinds`` over their device time in the traced steps, in per cent."""
    took = flash_seconds_per_step(run, phase)
    if took is None or "config" not in run:
        return None
    s, shapes = _spec(run), run["train_shapes"]
    least = counts.roofline_seconds(counts_laguna.flash_flops_per_step(s, shapes["batch"], shapes["seq_len"], kinds),
                                    counts_laguna.flash_bytes_per_step(s, shapes["batch"], shapes["seq_len"], kinds), run["peaks"])
    return 100.0 * least / took


def flash_attn_roofline(run):
    return flash_roofline(run, counts_laguna.KINDS)


def flash_window_roofline(run):
    """The window layers' kernel calls alone, against the elements their mask keeps."""
    return flash_roofline(run, ("sliding_attention",), phase="attn_window_kernel")
