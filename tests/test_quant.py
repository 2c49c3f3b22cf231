"""Weight-only int8 quantization: per-channel error bounds, tree matching,
size accounting, and quantized decode through the real generate path."""

import jax
import jax.numpy as jnp
import numpy as np

from dmlcloud_tpu.models.quant import (
    QuantizedTensor,
    dequant_tree,
    quantize,
    quantize_tree,
    quantized_size,
)


def test_quantize_roundtrip_error_bound():
    rng = np.random.RandomState(0)
    w = rng.randn(64, 32).astype(np.float32) * np.logspace(-2, 0, 32)  # per-channel ranges
    qt = quantize(jnp.asarray(w))
    back = np.asarray(qt.dequant(jnp.float32))
    # symmetric int8: error <= scale/2 per element, scale = col_max/127
    col_max = np.abs(w).max(axis=0)
    assert (np.abs(back - w) <= col_max / 127.0 / 2 + 1e-7).all()
    # per-channel beats per-tensor by construction on ranged columns
    assert qt.q.dtype == jnp.int8 and qt.scale.shape == (1, 32)


def test_quantize_zero_channel_safe():
    w = jnp.zeros((8, 4))
    qt = quantize(w)
    np.testing.assert_array_equal(np.asarray(qt.dequant(jnp.float32)), 0.0)


def test_quantize_tree_matches_kernels_only():
    params = {
        "dense": {"kernel": jnp.ones((8, 4)), "bias": jnp.ones(4)},
        "embed": {"embedding": jnp.ones((100, 8))},
        "norm": {"scale": jnp.ones(8)},
    }
    qtree = quantize_tree(params)
    assert isinstance(qtree["dense"]["kernel"], QuantizedTensor)
    assert not isinstance(qtree["embed"]["embedding"], QuantizedTensor)
    assert not isinstance(qtree["norm"]["scale"], QuantizedTensor)
    # dequant restores plain arrays everywhere
    back = dequant_tree(qtree, jnp.float32)
    assert all(
        isinstance(x, jax.Array) for x in jax.tree_util.tree_leaves(back)
    )
    q_bytes, full_bytes = quantized_size(qtree)
    assert q_bytes < full_bytes  # int8 kernels beat bf16 kernels


# quant_lm (the 64-vocab decode LM) comes from conftest.py, session-scoped.


def test_quantized_generate_matches_shapes_and_tracks_full(quant_lm):
    from dmlcloud_tpu.models.generate import generate

    model, params = quant_lm
    prompt = jnp.asarray(np.random.RandomState(1).randint(0, 64, (2, 8)), jnp.int32)
    full = np.asarray(generate(model, params, prompt, max_new_tokens=12))
    qparams = quantize_tree(params)
    quant = np.asarray(generate(model, qparams, prompt, max_new_tokens=12))
    assert quant.shape == full.shape == (2, 12)
    # int8 weights perturb logits slightly; greedy tokens should still
    # mostly agree on a tiny random model (identical for the vast majority
    # of positions; an occasional near-tie may flip)
    agreement = (quant == full).mean()
    assert agreement >= 0.75, (agreement, quant, full)


def test_quantized_logits_close_to_full(quant_lm):
    model, params = quant_lm
    tokens = jnp.asarray(np.random.RandomState(2).randint(0, 64, (2, 16)), jnp.int32)
    full = np.asarray(model.apply({"params": params}, tokens))
    deq = dequant_tree(quantize_tree(params), jnp.float32)
    quant = np.asarray(model.apply({"params": deq}, tokens))
    denom = np.abs(full).max()
    assert np.abs(quant - full).max() / denom < 0.05


def test_prepare_decode_params_is_exact_and_stays_quantized(quant_lm):
    """prepare_decode_params pre-pays the off-TPU GEMM-operand widen ONCE:
    kernels stay QuantizedTensor (scales still applied to the accumulator
    in the fused dot), q widens to fp32 exactly (int8 -> fp32 is lossless),
    and decode output is bit-identical to passing the raw int8 tree."""
    from dmlcloud_tpu.models.generate import generate
    from dmlcloud_tpu.models.quant import prepare_decode_params

    model, params = quant_lm
    qparams = quantize_tree(params)
    prepared = prepare_decode_params(qparams, jnp.float32)

    is_qt = lambda x: isinstance(x, QuantizedTensor)
    q_leaves = [x for x in jax.tree_util.tree_leaves(prepared, is_leaf=is_qt) if is_qt(x)]
    raw_leaves = [x for x in jax.tree_util.tree_leaves(qparams, is_leaf=is_qt) if is_qt(x)]
    assert q_leaves, "prepared tree lost its quantized kernels"
    assert len(q_leaves) == len(raw_leaves)
    for wide, raw in zip(q_leaves, raw_leaves):
        assert wide.q.dtype == jnp.float32  # off-TPU operand dtype (CPU CI)
        np.testing.assert_array_equal(np.asarray(wide.q), np.asarray(raw.q, np.float32))
        np.testing.assert_array_equal(np.asarray(wide.scale), np.asarray(raw.scale))

    prompt = jnp.asarray(np.random.RandomState(3).randint(0, 64, (2, 6)), jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(generate(model, qparams, prompt, max_new_tokens=8)),
        np.asarray(generate(model, prepared, prompt, max_new_tokens=8)),
    )


def test_widen_quant_tree_inside_jit_matches_per_step_path():
    """The in-program widen (decode entry points call it before the loop)
    must be a pure layout change: same QuantizedTensor structure, same
    values, fp32 q — and non-quantized leaves pass through untouched."""
    from dmlcloud_tpu.models.quant import widen_quant_tree

    rng = np.random.RandomState(4)
    tree = {
        "dense": {"kernel": quantize(jnp.asarray(rng.randn(16, 8), jnp.float32))},
        "bias": jnp.asarray(rng.randn(8), jnp.float32),
    }
    out = jax.jit(widen_quant_tree)(tree)
    assert isinstance(out["dense"]["kernel"], QuantizedTensor)
    assert out["dense"]["kernel"].q.dtype == jnp.float32
    np.testing.assert_array_equal(
        np.asarray(out["dense"]["kernel"].q), np.asarray(tree["dense"]["kernel"].q, np.float32)
    )
    np.testing.assert_array_equal(np.asarray(out["bias"]), np.asarray(tree["bias"]))
