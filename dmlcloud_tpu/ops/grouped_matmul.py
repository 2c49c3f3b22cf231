"""The grouped product of a dropless expert layer, and the moves that carry
tokens to their experts' rows and back.

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

Two pairs of moves. ``spread`` / ``collect`` carry all ``N * k`` pairs (a
permutation, so both directions of both are gathers). ``spread_rows`` /
``collect_rows`` carry the first ``R`` sorted pairs only, for a layer whose
live rows fit a buffer of ``R`` (``models/moe.py``): ``R``-row gathers out,
and back ``k`` gathers of ``N`` rows from the ``R``-row buffer (PERF.md
section 6, PR 31, has the forms of that move timed alone). No scatter-add runs
in either pair, forward or backward.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def grouped_matmul(lhs, rhs, group_sizes):
    return jax.lax.ragged_dot(lhs, rhs, group_sizes.astype(jnp.int32), preferred_element_type=lhs.dtype)


# The ``N * k`` (token, expert) pairs in sorted order are a permutation of the
# pairs in token order, so both directions of both moves are gathers.


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


# The first ``R`` sorted pairs alone. Neither move is differentiated by jax: the
# expert layer's bounded path writes its own backward, in which each is the
# other's transpose.


def spread_rows(tokens, order, k: int, bound: int):
    """``tokens [N, D] -> rows [R, D]``: the tokens of the first ``R = bound`` sorted pairs."""
    return tokens[order[:bound] // k]


def collect_rows(rows, inverse, k: int):
    """``rows [R, D] -> [N, D]`` float32: each token's rows among the first ``R``
    sorted pairs added up (``inverse[p]`` is where pair ``p`` lies in sorted
    order; a pair that lies past ``R`` adds nothing)."""
    bound = rows.shape[0]
    place = inverse.reshape(-1, k)
    out = 0.0
    for j in range(k):
        held = (place[:, j] < bound)[:, None]
        out = out + jnp.where(held, rows[jnp.minimum(place[:, j], bound - 1)].astype(jnp.float32), 0.0)
    return out
