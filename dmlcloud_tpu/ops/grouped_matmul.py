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
live rows fit a buffer of ``R`` (``models/moe.py``): one ``R``-row gather out,
and back ``R + N`` row fetches whatever ``k`` is: the ``R`` rows gathered into
token order, where a token's rows are neighbours (:class:`Runs`), each run
added up by a small product on the matrix unit, one ``N``-row gather of the
runs' heads (PERF.md section 6, PRs 31 and 36, have the forms of that move
timed alone). No scatter-add runs in either pair, forward or backward.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

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
    """``tokens [N, D] -> rows [R, D]``: the tokens of the first ``R = bound`` sorted
    pairs (``order`` holds ``R`` of them at least)."""
    return tokens[order[:bound] // k]


#: Rows of a chunk of the token-order buffer: the matrix unit's width.
_RUN_TILE = 128


def run_layout(rows: int, longest: int) -> tuple[int, int, int]:
    """``(chunks, tile, heads)`` of the token-order buffer behind :class:`Runs` for
    ``rows`` live rows in runs no longer than ``longest``: chunks of ``tile``
    places, of which the first ``heads`` may start a run and the rest only end
    one, so that no run crosses a chunk. At least one place is left to end runs,
    so the buffer's last place stays empty while the live rows fit ``rows``."""
    longest = max(longest, 2)
    tile = _RUN_TILE
    while tile < 2 * longest:
        tile *= 2
    heads = tile - (longest - 1)
    return -(-rows // heads), tile, heads


class Runs(NamedTuple):
    """The live rows of an ``R``-row buffer laid out in token order, in chunks no
    run crosses (:func:`run_layout`): place ``p`` holds row ``row[p]`` of the
    buffer, which is ``token[p]``'s (``N`` where the place is empty, and its
    ``row`` means nothing); a token's live rows are the places from ``head[n]``
    on, and the head of a token that has none is the buffer's last place, which
    is empty."""

    row: jax.Array  # [chunks * tile]
    token: jax.Array  # [chunks, tile]
    head: jax.Array  # [N]


def collect_rows(rows, runs: Runs):
    """``rows [R, D] -> [N, D]`` float32: each token's live rows added up in
    float32 and rounded once to the rows' dtype, the transpose of
    :func:`spread_rows`. ``R + N`` row fetches: one gather brings the rows into
    token order, a product a chunk (a 0/1 matrix of which places share a token,
    on the matrix unit) gives every place its run's sum, an empty place nought,
    and one gather takes the runs' heads. A row no token's place names adds
    nothing, whatever it holds.

    Timed alone on a v5e (PERF.md section 6, PR 36): 0.32 ms where ``k`` gathers
    of ``N`` rows take 1.72 (``k`` = 10, 8,192 tokens, 5,120 rows of 3,072) and
    0.22 against 0.44 (``k`` = 4, 8,192 rows of 2,048): one form for both."""
    chunks, tile = runs.token.shape
    taken = (runs.token < runs.head.shape[0]).reshape(-1, 1)
    x = jnp.where(taken, rows[runs.row], 0).reshape(chunks, tile, rows.shape[1])
    same = (runs.token[:, :, None] == runs.token[:, None, :]).astype(rows.dtype)  # [chunks, tile, tile]
    sums = jnp.einsum("ctu,cud->ctd", same, x, precision=jax.lax.Precision.HIGHEST, preferred_element_type=rows.dtype)
    return sums.reshape(chunks * tile, rows.shape[1])[runs.head].astype(jnp.float32)
