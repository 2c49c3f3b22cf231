"""``counts.py``: agrees with XLA's own count for the dense parts at a small
size, and at the cells' shapes gives no share over 100 % against the peaks for
the fastest times this repo has measured on the chip."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmark import counts, peaks, reference  # noqa: E402

M7B = {k: v for k, v in json.load(open(os.path.join(HERE, "..", "configs", "mistral-7b-v0.1.json"))).items()
       if isinstance(v, (int, float)) or v is None}
SMALL = dict(hidden_size=128, num_attention_heads=4, num_key_value_heads=2, intermediate_size=352, vocab_size=512,
             sliding_window=None, rope_theta=10000.0, rms_norm_eps=1e-5)
V5E = peaks.for_kind("TPU v5 lite")


def test_dense_forward_agrees_with_xla_cost_analysis():
    """One layer and the head over 64 tokens, attention's scores and the
    elementwise work left to a tolerance: XLA counts the matrix products as
    2 x m x n x k, as ``counts`` does."""
    shapes = tuple(sorted(reference.train_shapes(SMALL, 1).items()))
    params = jax.eval_shape(lambda: reference._train_weights(jax.random.PRNGKey(0), shapes))
    tokens = jax.ShapeDtypeStruct((1, 64), jnp.int32)
    fwd = jax.jit(lambda p, t: reference.lm_loss(p, t, SMALL, 1, "reference"))
    xla = fwd.lower(params, tokens).compile().cost_analysis()["flops"]
    mine = counts.train_flops_per_step(SMALL, 1, 1, 64) / 3  # forward alone
    assert mine == pytest.approx(xla, rel=0.08)
    assert mine <= xla  # what the algorithm needs is never more than what a plain implementation runs


def test_parameter_count_is_the_published_model():
    assert counts.param_count(M7B, 32) == 7_241_732_096  # Mistral-7B-v0.1
    assert round(counts.param_count(M7B, 2) / 1e6, 1) == 698.4  # what chip_smoke reports at two layers (PR 21)


def test_window_limits_the_keys():
    assert counts.keys_attended(10, None) == 11 and counts.keys_attended(5000, 4096) == 4096
    brute = sum(counts.keys_attended(p, 4096) for p in range(100, 8192))
    assert counts.keys_attended_sum(100, 8092, 4096) == brute


@pytest.mark.parametrize("what,least,measured", [
    # (cell's shape, least seconds from counts and peaks, fastest seconds measured on the v5e)
    ("train step 1 x 8192, 2 layers", lambda: counts.train_flops_per_step(M7B, 2, 1, 8192) / V5E["bf16_flops"],
     8192 / 30_336.0),  # ledger, PR 22
    ("decode call, 16 rows at 1280", lambda: counts.roofline_seconds(*counts.decode_call(M7B, 16, [1280] * 16), V5E),
     0.0218),  # ledger, PR 22: serve_decode_call_ms_p50
    ("decode call, 1 row at 32", lambda: counts.roofline_seconds(*counts.decode_call(M7B, 16, [32]), V5E), 0.0218),
    ("prefill chunk of 32 at 3936", lambda: counts.roofline_seconds(*counts.prefill_call(M7B, 16, 3936, 32, True), V5E),
     0.0139),  # 35 ms engine step less the decode call (ISSUE 24)
    ("flash kernels, 1 x 8192", lambda: counts.roofline_seconds(
        counts.flash_flops_per_step(M7B, 2, 1, 8192), counts.flash_bytes_per_step(M7B, 2, 1, 8192), V5E), 0.02),
])
def test_no_share_over_100_percent_at_the_cells_shapes(what, least, measured):
    assert 0 < least() < measured, what


def test_decode_is_bound_by_bytes_and_a_train_step_by_operations():
    flops, nbytes = counts.decode_call(M7B, 16, [512] * 16)
    assert nbytes / V5E["hbm_bytes_per_s"] > flops / V5E["bf16_flops"]
    assert nbytes > 6.9e9  # the 16 layers' weights and the head are read once
    # a token: 2 x (two layers of 218,103,808 weights + a head of 131,072,000) in matrix products, and
    # 4 x 32 x 128 a key over 3072.25 keys on average (the 4096 window over 8192 positions) in two layers; x 3
    assert counts.train_flops_per_step(M7B, 2, 1, 8192) / 8192 == 3 * (2 * 567_279_616 + 2 * 16384 * 3072.25)
