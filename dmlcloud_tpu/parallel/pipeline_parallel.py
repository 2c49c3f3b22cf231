"""Pipeline parallelism: GPipe microbatch scheduling over a ``pipe`` mesh axis.

The reference has no pipeline parallelism (its ``TrainingPipeline`` stages run
sequentially — /root/reference/dmlcloud/pipeline.py:198-206; SURVEY.md §2.2).
This module is the TPU build's ``pipe`` axis implementation, designed for XLA
rather than as a scheduler translation:

- Every pipeline stage runs the SAME traced computation (``stage_fn``) on its
  own slice of the stacked stage parameters — SPMD, so one program serves all
  stages and the MXU sees identical shapes everywhere.
- Microbatches advance through the pipeline with ``lax.ppermute`` neighbour
  exchanges over ICI (stage i -> i+1), inside one ``lax.scan`` over
  ``n_micro + n_stages - 1`` ticks. There is no host-side scheduler: the
  whole GPipe schedule, bubbles and all, is a single compiled XLA program.
- Everything is differentiable (scan/ppermute/psum have transposes), so
  ``jax.grad`` through ``pipeline_apply`` yields the standard GPipe backward
  schedule automatically — no hand-written backward pipeline.
- Composes with the other axes: activations may be batch-sharded over
  ``data``/``fsdp`` and the per-stage computation may itself be tensor- or
  sequence-parallel (``model``/``seq`` axes) since those axes are untouched by
  the shard_map specs used here.

Bubble math is the classic GPipe one: efficiency = n_micro / (n_micro +
n_stages - 1); pick ``n_micro >= 4 * n_stages`` to keep the bubble under ~20%.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import mesh as mesh_lib

__all__ = ["pipeline_apply", "stack_pytrees", "microbatch", "unmicrobatch", "stage_sharding"]


def stack_pytrees(trees: list[Any]) -> Any:
    """Stack per-stage parameter pytrees into one pytree whose leaves gain a
    leading ``n_stages`` dim — the dim sharded over the ``pipe`` axis."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *trees)


def microbatch(batch: jnp.ndarray, n_micro: int) -> jnp.ndarray:
    """[B, ...] -> [n_micro, B/n_micro, ...] (B must divide evenly)."""
    b = batch.shape[0]
    if b % n_micro:
        raise ValueError(f"batch size {b} not divisible into {n_micro} microbatches")
    return batch.reshape(n_micro, b // n_micro, *batch.shape[1:])


def unmicrobatch(x: jnp.ndarray) -> jnp.ndarray:
    """Inverse of :func:`microbatch`."""
    return x.reshape(x.shape[0] * x.shape[1], *x.shape[2:])


def stage_sharding(mesh: Mesh, axis: str = mesh_lib.PIPE) -> NamedSharding:
    """Sharding for stacked stage params: leading (stage) dim over ``axis``."""
    return NamedSharding(mesh, P(axis))


def _squeeze_leading(tree: Any) -> Any:
    return jax.tree_util.tree_map(lambda x: x[0], tree)


def pipeline_apply(
    stage_fn: Callable[[Any, jnp.ndarray], jnp.ndarray],
    stacked_params: Any,
    x: jnp.ndarray,
    mesh: Mesh,
    axis: str = mesh_lib.PIPE,
) -> jnp.ndarray:
    """Run ``x`` through ``n_stages`` pipeline stages with GPipe microbatching.

    Args:
      stage_fn: ``(params_slice, act) -> act`` — one stage's computation; the
        activation shape must be preserved (homogeneous pipeline). Traced once;
        runs on every stage with that stage's params.
      stacked_params: pytree whose leaves have leading dim ``n_stages``
        (:func:`stack_pytrees`), laid out with :func:`stage_sharding`.
      x: ``[n_micro, micro_b, ...]`` microbatched activations
        (:func:`microbatch`). May be sharded over ``data``/``fsdp`` on the
        micro-batch dim.
      mesh: mesh containing ``axis``; other axes pass through untouched.
      axis: the pipeline mesh axis name.

    Returns ``[n_micro, micro_b, ...]`` outputs of the last stage, replicated
    over ``axis``.
    """
    n_stages = mesh.shape[axis]
    n_micro = x.shape[0]
    for path, leaf in jax.tree_util.tree_leaves_with_path(stacked_params):
        if leaf.shape[:1] != (n_stages,):
            raise ValueError(
                f"stacked_params leaf {jax.tree_util.keystr(path)} has leading dim "
                f"{leaf.shape[:1]}, expected ({n_stages},) == mesh.shape[{axis!r}] "
                "(a mismatch would silently drop stages)"
            )
    batch_axes = mesh_lib.data_axes(mesh) or None
    act_spec = P(None, batch_axes)  # [n_micro, micro_b, ...]

    fn = partial(_pipeline_local, stage_fn, n_stages=n_stages, n_micro=n_micro, axis=axis)
    # the stage loop's ppermutes produce per-shard values on purpose
    return jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=(P(axis), act_spec),
        out_specs=act_spec,
        check_vma=False,
    )(stacked_params, x)


def _pipeline_local(stage_fn, stacked_params, x, *, n_stages: int, n_micro: int, axis: str):
    """Per-device body: run the GPipe tick loop for this stage."""
    params = _squeeze_leading(stacked_params)  # this stage's slice
    stage = jax.lax.axis_index(axis)
    is_first = stage == 0
    is_last = stage == n_stages - 1
    micro_shape = x.shape[1:]

    # stage i -> i+1; stage 0 receives zeros (no cyclic wrap)
    shift_perm = [(i, i + 1) for i in range(n_stages - 1)]

    def tick(carry, t):
        recv, y = carry
        # stage 0 injects microbatch t (zeros once the batch is exhausted —
        # those ticks only drain the pipeline and their outputs are masked)
        x_t = jax.lax.dynamic_index_in_dim(x, jnp.minimum(t, n_micro - 1), keepdims=False)
        feed = jnp.where(t < n_micro, x_t, jnp.zeros_like(x_t))
        act = jnp.where(is_first, feed, recv)

        out = stage_fn(params, act)

        # the last stage commits finished microbatch t-(n_stages-1)
        out_idx = jnp.clip(t - (n_stages - 1), 0, n_micro - 1)
        prev = jax.lax.dynamic_index_in_dim(y, out_idx, keepdims=False)
        write = jnp.logical_and(is_last, t >= n_stages - 1)
        y = jax.lax.dynamic_update_index_in_dim(y, jnp.where(write, out, prev), out_idx, 0)

        recv = jax.lax.ppermute(out, axis, shift_perm)
        return (recv, y), None

    y0 = jnp.zeros((n_micro, *micro_shape), x.dtype)
    recv0 = jnp.zeros(micro_shape, x.dtype)
    (_, y), _ = jax.lax.scan(tick, (recv0, y0), jnp.arange(n_micro + n_stages - 1))

    # replicate the last stage's outputs to every pipe rank (all other stages
    # contribute zeros) so downstream specs see a pipe-invariant value
    return jax.lax.psum(jnp.where(is_last, y, jnp.zeros_like(y)), axis)
