"""The grouped product of a dropless expert layer, and the permutation pair
that carries tokens to their experts' rows and back.

``grouped_matmul(lhs [M, K], rhs [G, K, N], group_sizes [G]) -> [M, N]``: the
rows of ``lhs`` lie sorted by group, ``group_sizes[g]`` of them belonging to
group ``g``; row ``i`` of the result is ``lhs[i] @ rhs[group of i]``. The sizes
are data, the shapes static. ``sum(group_sizes)`` may be less than ``M``: the
rows past it belong to no group, the product does no work for them and what
it leaves there is unspecified (the caller masks them).

It is ``jax.lax.ragged_dot``: XLA's own ragged product on the TPU, whose
transposes (a ragged product for the rows' gradient, one with the ragged axis
contracted for the weights') jax derives itself. PERF.md section 6, PR 30,
has its times beside a Pallas grouped matmul's at the benchmark's shapes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def grouped_matmul(lhs, rhs, group_sizes):
    return jax.lax.ragged_dot(lhs, rhs, group_sizes.astype(jnp.int32), preferred_element_type=lhs.dtype)


# The ``N * k`` (token, expert) pairs in sorted order are a permutation of the
# pairs in token order, so both directions of both moves are gathers: no
# scatter-add runs, forward or backward.


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def spread(tokens, order, inverse, k: int):
    """``tokens [N, D] -> rows [N * k, D]``: row ``i`` is the token of sorted
    pair ``i`` (pair ``order[i]`` in token order, so token ``order[i] // k``)."""
    del inverse
    return tokens[order // k]


def _spread_fwd(tokens, order, inverse, k):
    return spread(tokens, order, inverse, k), (order, inverse, jnp.zeros((0,), tokens.dtype))


def _spread_bwd(k, saved, d_rows):
    order, inverse, like = saved
    return collect(d_rows, order, inverse, k).astype(like.dtype), None, None


spread.defvjp(_spread_fwd, _spread_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def collect(rows, order, inverse, k: int):
    """``rows [N * k, D] -> [N, D]``: each token's ``k`` rows added up in float32,
    the transpose of :func:`spread` (``inverse[order[i]] == i``)."""
    del order
    return rows[inverse].reshape(-1, k, rows.shape[-1]).astype(jnp.float32).sum(axis=1)


def _collect_fwd(rows, order, inverse, k):
    return collect(rows, order, inverse, k), (order, inverse, jnp.zeros((0,), rows.dtype))


def _collect_bwd(k, saved, d_out):
    order, inverse, like = saved
    return spread(d_out.astype(like.dtype), order, inverse, k), None, None


collect.defvjp(_collect_fwd, _collect_bwd)
