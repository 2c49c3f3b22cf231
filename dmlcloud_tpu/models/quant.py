"""Weight-only int8 quantization for inference, TPU-first.

Decode is HBM-bandwidth-bound: every generated token streams the full
weight set from HBM once, so halving the bytes (bf16 -> int8 + per-channel
fp32 scales) is the lever on a memory-bound decode step (int8 decode is not
measured on the chip; PERF.md section 5 has the bf16 step's roofline
share). The reference has no inference path at all, let alone a quantized
one.

Design:

- ``QuantizedTensor`` is a pytree node carrying ``q`` (int8) + ``scale``
  (fp32, per-output-channel). It flows through jit like any array leaf,
  so quantized param trees drop into the existing ``generate`` /
  ``beam_search`` entry points unchanged.
- The dequant is FUSED into each consuming matmul (:class:`QuantDense` /
  :class:`QuantDenseGeneral`, :func:`_fused_quant_dot`): the int8 tensor
  feeds ``lax.dot_general`` directly and the per-channel scales multiply
  the fp32 accumulator — no dequantized weight copy is ever materialised,
  so the weight stream stays 1 byte/element end to end. (The pre-PR-6
  design dequantized the whole tree at program entry; XLA hoisted the
  copies and the weight stream was bf16 again.)
- Symmetric per-channel quantization: ``w ~= q * scale`` with the amax
  reduced over the kernel's leading input axes, so every trailing output
  coordinate keeps its own scale (see :func:`quantize`).
- Weight-only: activations stay in the model's compute dtype. This is the
  bandwidth-bound inference tradeoff — prefill (compute-bound) keeps full
  precision.

**Quantized training** (PR 16): the same fused-dot discipline applied to
the train step. :class:`QuantTrainTensor` pairs a MASTER fp32 weight with
a DELAYED per-channel scale (computed from the previous step's post-update
amax, carried in ``TrainState.extras[QUANT_AMAX_KEY]`` — no per-step amax
reduction on the forward's critical path, the fp8-recipe trick applied to
int8). :func:`quant_train_dot` is a ``custom_vjp`` whose forward AND
input-gradient matmuls consume the freshly-quantized int8 kernel through
the same ``lax.dot_general`` operand convention as
:func:`_fused_quant_dot`, while the WEIGHT gradient stays a full-precision
``x^T @ g`` into the fp32 master (straight-through estimator: the
round/clip's zero-a.e. derivative is replaced by identity). The optimizer,
EMA shadow and checkpoint layout never see any of this — they hold plain
fp32 params; ``TrainValStage(precision="int8")`` wraps kernels inside the
compiled step's loss closure (:func:`wrap_train_tree`) and refreshes the
amax tree from the post-update params (:func:`amax_tree`).
"""

from __future__ import annotations

from typing import Any, Callable

import flax.linen as nn
import jax
import jax.numpy as jnp
from flax import struct

__all__ = [
    "QuantizedTensor",
    "QuantTrainTensor",
    "QuantDense",
    "QuantDenseGeneral",
    "quantize",
    "quantize_tree",
    "dequant_tree",
    "widen_quant_tree",
    "prepare_decode_params",
    "quantized_size",
    "quant_train_dot",
    "amax_tree",
    "wrap_train_tree",
    "QUANT_AMAX_KEY",
]

#: extras key under which TrainValStage(precision="int8") carries the
#: delayed per-channel amax tree (see amax_tree / wrap_train_tree)
QUANT_AMAX_KEY = "quant_amax"


class QuantizedTensor(struct.PyTreeNode):
    """``w ~= q * scale`` with int8 ``q`` and broadcast-ready fp32 ``scale``."""

    q: jax.Array
    scale: jax.Array

    @property
    def shape(self):
        return self.q.shape

    @property
    def ndim(self):
        return self.q.ndim

    def dequant(self, dtype=jnp.bfloat16) -> jax.Array:
        # int8 -> f32 multiply keeps the scale exact; the cast to the
        # compute dtype happens last. Under jit this is one fused
        # elementwise chain feeding the consumer matmul.
        return (self.q.astype(jnp.float32) * self.scale).astype(dtype)


def _fused_quant_dot(x: jax.Array, qt: QuantizedTensor, dtype) -> jax.Array:
    """``x @ dequant(qt)`` WITHOUT a materialised dequantized weight copy:
    the int8 tensor feeds ``lax.dot_general`` directly (the int8->compute
    convert fuses into the matmul's operand read, so HBM streams 1 byte per
    weight instead of 2-4) and the per-output-channel scales multiply the
    fp32 ACCUMULATOR — O(out) work on the result instead of O(in*out) on
    the weight. int8 values are exact in bf16 (8 mantissa bits cover ±127),
    so this equals ``x @ (q * scale)`` up to the usual accumulation order.

    Contracts ``x``'s last axis with ``q``'s first (the nn.Dense /
    nn.DenseGeneral(axis=-1) convention); requires the quantization's
    reduced axis to be that same first axis (``scale.shape[0] == 1``)."""
    q = qt.q
    # Operand precision is a per-backend choice (static at trace time):
    # int8 is EXACT in both bf16 (8 mantissa bits cover ±127) and fp32, so
    # either is a faithful dequant. On TPU the operands stay in the compute
    # dtype — the narrow-operand MXU path is the fast one. Everywhere else
    # they promote to the fp32 accumulator's precision: XLA:CPU emulates
    # bf16 GEMMs (widen + fp32 GEMM + round EVERY step), so the quantized
    # decode runs the native fp32 GEMM directly. The widen itself is hoisted
    # out of the decode loop by :func:`widen_quant_tree` (q arrives here
    # already fp32 and the astype below is a no-op); the bf16 baseline
    # cannot hoist its emulation widen.
    if not jnp.issubdtype(q.dtype, jnp.integer):
        op_dtype = q.dtype  # pre-widened by widen_quant_tree — use as-is
    else:
        op_dtype = dtype if jax.default_backend() == "tpu" else jnp.promote_types(jnp.float32, dtype)
    acc = jax.lax.dot_general(
        x.astype(op_dtype),
        q.astype(op_dtype),
        (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [..., *out] fp32
    scale = qt.scale.reshape(q.shape[1:])  # drop the keepdims reduced axis
    return (acc * scale).astype(dtype)


class QuantTrainTensor(struct.PyTreeNode):
    """Quantized-TRAINING leaf: master fp32 weight ``w`` plus the DELAYED
    per-output-channel ``scale`` (previous step's post-update amax / 127,
    keepdims layout, exactly :class:`QuantizedTensor`'s). The wrapped leaf
    lives only INSIDE the compiled train step's loss closure
    (:func:`wrap_train_tree`); params, grads, optimizer state and
    checkpoints stay plain fp32 trees."""

    w: jax.Array
    scale: jax.Array


def _train_op_dtype(dtype):
    # the same per-backend operand choice _fused_quant_dot makes: int8 is
    # exact in bf16 and fp32, TPU MXUs eat narrow operands natively,
    # XLA:CPU widens to the fp32 accumulator dtype (skipping the bf16
    # GEMM-emulation tax)
    return dtype if jax.default_backend() == "tpu" else jnp.promote_types(jnp.float32, dtype)


@jax.custom_vjp
def quant_train_dot(x, w, scale):
    """``x @ fake_quant(w)`` with int8 matmuls on BOTH the forward and the
    input-gradient path, and a straight-through fp32 weight gradient.

    - forward: ``q = clip(round(w / scale))`` int8 feeds ``lax.dot_general``
      directly (the :func:`_fused_quant_dot` fusion — no dequantized copy),
      per-channel ``scale`` multiplies the fp32 accumulator.
    - ``dx = (g * scale) @ q^T``: the SAME int8 kernel re-feeds the
      transposed dot, so the backward's activation-gradient GEMM is
      quantized too (the residual holds ``q`` at 1 byte/element, not a
      second fp32 weight copy).
    - ``dw = x^T @ g`` in fp32 into the MASTER weight (straight-through:
      the quantizer's round/clip differentiates as identity) and
      ``dscale = 0`` — the scale is training STATE (delayed amax), never
      a trained parameter.

    Contracts ``x``'s last axis with ``w``'s first (the nn.Dense /
    DenseGeneral(axis=-1) convention, kernels ``[in, *out]``)."""
    y, _ = _quant_train_fwd(x, w, scale)
    return y


def _quant_train_fwd(x, w, scale):
    q = jnp.clip(jnp.round(w.astype(jnp.float32) / scale), -127, 127).astype(jnp.int8)
    op = _train_op_dtype(x.dtype)
    acc = jax.lax.dot_general(
        x.astype(op), q.astype(op),
        (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    y = (acc * scale.reshape(q.shape[1:])).astype(x.dtype)
    # the residual carries q int8 (1 byte/element), x, and a 0-size dtype
    # token so dw lands in the master weight's own dtype
    return y, (x, q, scale, jnp.zeros((0,), w.dtype))


def _quant_train_bwd(res, g):
    x, q, scale, wtok = res
    op = _train_op_dtype(x.dtype)
    n_out = q.ndim - 1
    gs = g.astype(jnp.float32) * scale.reshape(q.shape[1:])
    g_axes = tuple(range(g.ndim - n_out, g.ndim))
    dx = jax.lax.dot_general(
        gs.astype(op), q.astype(op),
        ((g_axes, tuple(range(1, q.ndim))), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(x.dtype)
    dw = jax.lax.dot_general(
        x.astype(jnp.float32), g.astype(jnp.float32),
        ((tuple(range(x.ndim - 1)), tuple(range(g.ndim - n_out))), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(wtok.dtype)
    return dx, dw, jnp.zeros_like(scale)


quant_train_dot.defvjp(_quant_train_fwd, _quant_train_bwd)


def amax_tree(params: Any, match: Callable[[str, Any], bool] | None = None) -> Any:
    """Per-output-channel ``max|w|`` of every matched kernel — the delayed-
    scale state ``TrainValStage(precision="int8")`` carries in
    ``extras[QUANT_AMAX_KEY]`` and refreshes from the POST-update params
    each step (so step N's forward quantizes with step N-1's statistics;
    step 0 seeds from the initial params in ``make_state``). Unmatched
    leaves hold a 0-d zero placeholder, keeping the tree structure
    identical to ``params`` for jit/donation/checkpointing. Default match:
    ``lora.default_match`` (matrix-shaped kernels)."""
    from .lora import _paths, default_match

    matcher = match or default_match

    def leaf_amax(path, leaf):
        if not matcher(path, leaf):
            return jnp.zeros((), jnp.float32)
        w = jnp.asarray(leaf)
        reduce_axes = tuple(range(min(1, w.ndim - 1)))
        return jnp.max(jnp.abs(w.astype(jnp.float32)), axis=reduce_axes, keepdims=True)

    return jax.tree_util.tree_map(leaf_amax, _paths(params), params)


def wrap_train_tree(
    params: Any, amax: Any, match: Callable[[str, Any], bool] | None = None
) -> Any:
    """Wrap every matched kernel as :class:`QuantTrainTensor` with the
    delayed scale ``amax / 127`` (1.0 for all-zero channels, mirroring
    :func:`quantize`). Called INSIDE the loss closure on the
    differentiated params, so grads keep the plain-params structure: the
    wrapper's ``w`` cotangent flows straight back to the leaf and the
    stop-gradient'd scale contributes nothing."""
    from .lora import _paths, default_match

    matcher = match or default_match

    def wrap(path, leaf, a):
        if not matcher(path, leaf):
            return leaf
        scale = jnp.where(a > 0, a / 127.0, 1.0)
        return QuantTrainTensor(w=leaf, scale=jax.lax.stop_gradient(scale))

    return jax.tree_util.tree_map(wrap, _paths(params), params, amax)


def _fusible(qt: QuantizedTensor) -> bool:
    """Whether the fused path applies: per-output-channel scales reduced
    over exactly the first (contracted) axis."""
    import math

    return qt.scale.shape[0] == 1 and qt.scale.size == math.prod(qt.q.shape[1:])


def widen_quant_tree(params: Any, dtype=jnp.float32) -> Any:
    """Hoist the int8 -> GEMM-operand widen OUT of a decode loop (CPU/GPU
    only; a no-op tree on TPU callers' side — don't call it there).

    On backends whose GEMMs cannot consume int8 operands, every
    ``_fused_quant_dot`` call widens ``q`` to fp32 — and when that call
    sits inside a ``scan``/``while_loop`` decode body, XLA:CPU re-runs the
    widen (write + read of a 4-byte copy) EVERY step, exactly the
    emulation tax the bf16 baseline pays. Calling this once before the
    loop (inside jit) converts each fusible kernel's ``q`` a single time;
    the ``optimization_barrier`` pins the widened buffers so XLA cannot
    sink the converts back into the loop body. Scales stay separate and
    still multiply the accumulator in :func:`_fused_quant_dot` —
    ``q * scale`` is never materialised, and the arithmetic is bit-for-bit
    the per-step path (int8 -> fp32 is exact)."""
    is_qt = lambda x: isinstance(x, QuantizedTensor)
    widened = jax.tree_util.tree_map(
        lambda x: x.replace(q=x.q.astype(dtype)) if is_qt(x) and _fusible(x) else x,
        params,
        is_leaf=is_qt,
    )
    return jax.lax.optimization_barrier(widened)


def prepare_decode_params(params: Any, dtype=jnp.bfloat16) -> Any:
    """ONE-TIME host-side preparation of a (possibly int8-quantized) tree
    for repeated decode calls: non-kernel quantized leaves rehydrate to
    ``dtype`` and, off-TPU, fusible int8 kernels pre-widen to the GEMM
    operand dtype so no per-call widen remains inside the compiled decode
    program (the in-program :func:`widen_quant_tree` then no-ops). On TPU
    kernels stay int8 — the MXU consumes them directly and pre-widening
    would only inflate HBM. Serving loops that decode from the same
    weights many times should call this once at model-load time; passing
    the raw quantized tree to :func:`~dmlcloud_tpu.models.generate.generate`
    stays correct and merely re-pays the widen each call."""
    params = dequant_tree(params, dtype, keep=lambda p: p.endswith("kernel"))
    if jax.default_backend() == "tpu":
        return params
    is_qt = lambda x: isinstance(x, QuantizedTensor)
    return jax.tree_util.tree_map(
        lambda x: x.replace(q=x.q.astype(jnp.float32)) if is_qt(x) and _fusible(x) else x,
        params,
        is_leaf=is_qt,
    )


class QuantDense(nn.Dense):
    """``nn.Dense`` that natively consumes an int8 :class:`QuantizedTensor`
    kernel via :func:`_fused_quant_dot` — decode-path layers use this so
    quantized param trees run without any dequantized weight copy. With an
    ordinary array kernel (including at init) it IS ``nn.Dense``."""

    @nn.compact
    def __call__(self, inputs):
        kernel = (
            self.get_variable("params", "kernel") if self.has_variable("params", "kernel") else None
        )
        if isinstance(kernel, QuantTrainTensor):  # quantized TRAINING path
            y = quant_train_dot(inputs.astype(self.dtype), kernel.w, kernel.scale)
            if self.use_bias:
                y = y + self.get_variable("params", "bias").astype(self.dtype)
            return y
        if not isinstance(kernel, QuantizedTensor):
            return super().__call__(inputs)
        if not _fusible(kernel):  # exotic scale layout: correctness over speed
            y = inputs.astype(self.dtype) @ kernel.dequant(self.dtype)
        else:
            y = _fused_quant_dot(inputs, kernel, self.dtype)
        if self.use_bias:
            y = y + self.get_variable("params", "bias").astype(self.dtype)
        return y


class QuantDenseGeneral(nn.DenseGeneral):
    """``nn.DenseGeneral`` twin of :class:`QuantDense` (supports the
    ``axis=-1`` single-contraction form the transformer uses; other axis
    configurations fall back to a dequantized matmul)."""

    @nn.compact
    def __call__(self, inputs):
        kernel = (
            self.get_variable("params", "kernel") if self.has_variable("params", "kernel") else None
        )
        if isinstance(kernel, QuantTrainTensor):
            if self.axis != -1 or self.batch_dims:
                raise NotImplementedError(
                    "quantized training supports the axis=-1 DenseGeneral form only"
                )
            y = quant_train_dot(inputs.astype(self.dtype), kernel.w, kernel.scale)
            if self.use_bias:
                y = y + self.get_variable("params", "bias").astype(self.dtype)
            return y
        if not isinstance(kernel, QuantizedTensor) or self.axis != -1 or self.batch_dims:
            if isinstance(kernel, QuantizedTensor):  # unsupported layout: dequantize locally
                kernel = kernel.dequant(self.dtype)
                contract = (((inputs.ndim - 1,), (0,)), ((), ()))
                return jax.lax.dot_general(inputs.astype(self.dtype), kernel, contract)
            return super().__call__(inputs)
        if not _fusible(kernel):
            y = jax.lax.dot_general(
                inputs.astype(self.dtype),
                kernel.dequant(self.dtype),
                (((inputs.ndim - 1,), (0,)), ((), ())),
            )
        else:
            y = _fused_quant_dot(inputs, kernel, self.dtype)
        if self.use_bias:
            y = y + self.get_variable("params", "bias").astype(self.dtype)
        return y


def quantize(w: jax.Array, *, num_input_axes: int = 1) -> QuantizedTensor:
    """Symmetric per-output-channel int8 quantization of ``w``.

    The amax is reduced over the leading ``num_input_axes`` axes (the dims a
    matmul collapses), so every trailing output coordinate keeps its own
    scale. For 2D ``[in, out]`` kernels that is the classic per-output-column
    scale; for DenseGeneral-style ``[in, heads, head_dim]`` kernels each
    (head, head_dim) output channel gets its own scale rather than sharing
    one across heads. Finer-than-per-channel scales (e.g. an out-projection
    ``[heads, head_dim, out]`` with the default ``num_input_axes=1``) are
    still exact elementwise and only cost a slightly larger scale tensor.
    """
    w = jnp.asarray(w)
    reduce_axes = tuple(range(min(num_input_axes, w.ndim - 1)))
    amax = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=reduce_axes, keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(w.astype(jnp.float32) / scale), -127, 127).astype(jnp.int8)
    return QuantizedTensor(q=q, scale=scale)


def quantize_tree(params: Any, match: Callable[[str, Any], bool] | None = None) -> Any:
    """Quantize every matched leaf of a param tree; the result drops into
    ``generate`` / ``beam_search`` directly (they dequantize in-program).
    Default match: matrix-shaped kernels (lora.default_match — embeddings,
    biases, and norm scales stay full precision)."""
    from .lora import _paths, default_match

    matcher = match or default_match
    return jax.tree_util.tree_map(
        lambda path, leaf: quantize(leaf) if matcher(path, leaf) else leaf, _paths(params), params
    )


def dequant_tree(params: Any, dtype=jnp.bfloat16, keep: Callable[[str], bool] | None = None) -> Any:
    """Rehydrate a (possibly partially) quantized tree to ``dtype`` arrays.
    Pure and cheap to call inside jit — a no-op tree_map when nothing is
    quantized.

    ``keep`` (path -> bool) leaves matching quantized leaves AS
    QuantizedTensor: the decode paths pass ``keep=lambda p:
    p.endswith("kernel")`` so matmul kernels stay int8 for the fused
    :class:`QuantDense` layers (no materialised weight copy) while any
    exotically-quantized leaf a custom matcher produced (an embedding, a
    bias) still rehydrates for its quant-unaware consumer."""
    is_qt = lambda x: isinstance(x, QuantizedTensor)
    if keep is None:
        return jax.tree_util.tree_map(
            lambda x: x.dequant(dtype) if is_qt(x) else x, params, is_leaf=is_qt
        )
    from .lora import _paths

    return jax.tree_util.tree_map(
        lambda path, x: x.dequant(dtype) if is_qt(x) and not keep(path) else x,
        _paths(params, is_leaf=is_qt),
        params,
        is_leaf=is_qt,
    )


def quantized_size(params: Any) -> tuple[int, int]:
    """(bytes_quantized, bytes_unquantized) for a bf16-deployed model — the
    per-token HBM weight-traffic ratio decode actually pays. Unquantized
    float leaves count as bf16 (2 bytes) on BOTH sides: they would stream
    at the compute dtype either way, whatever dtype the tree stores."""
    q_bytes = full_bytes = 0
    for leaf in jax.tree_util.tree_leaves(
        params, is_leaf=lambda x: isinstance(x, QuantizedTensor)
    ):
        if isinstance(leaf, QuantizedTensor):
            q_bytes += leaf.q.size + leaf.scale.size * 4
            full_bytes += leaf.q.size * 2
        else:
            n = 2 if jnp.issubdtype(leaf.dtype, jnp.floating) else leaf.dtype.itemsize
            q_bytes += leaf.size * n
            full_bytes += leaf.size * n
    return q_bytes, full_bytes
