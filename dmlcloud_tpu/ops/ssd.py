"""The chunked state-space scan (Mamba-2's SSD form, arXiv:2405.21060) in
plain ``jax.numpy``, differentiated by ``jax.grad``.

Per head ``h`` with a scalar decay and a state ``S`` in ``R^{P x N}``:

    S_t = exp(dt_t A_h) S_{t-1} + dt_t x_t B_t^T        (S before the sequence is 0)
    y_t = S_t C_t + D_h x_t

``B_t`` and ``C_t`` are shared by the ``H / G`` heads of a group. Token by token
that is ``T`` dependent steps of rank-one updates; cut into chunks of ``L``
tokens it is matrix products. With ``a_i`` the running sum of ``dt A`` inside a
chunk (``a_i <= 0``: ``A < 0 < dt``):

- within a chunk, ``y_i += sum_{j <= i} exp(a_i - a_j) (C_i . B_j) dt_j x_j``:
  the ``L x L`` matrix ``C B^T`` of a group, computed once a chunk, times each
  head's decay matrix, times the chunk's ``dt x``. The decay is the exponential
  of a DIFFERENCE, never ``exp(a_i) * exp(-a_j)``: a strong decay over a chunk
  would overflow the second factor and underflow the first;
- the state a chunk hands on, ``sum_j exp(a_L - a_j) dt_j x_j B_j^T``, and the
  states carried from chunk to chunk, ``S <- exp(a_L) S + (that sum)``, one
  ``lax.scan`` over the chunks in float32;
- what the carried state adds inside the next chunk, ``y_i += exp(a_i) S C_i``.

``dt``, ``A``, the running sums, every exponential and the carried state are
float32; the products take their operands in ``x``'s dtype (the model's compute
dtype) and accumulate in float32. A kernel with its own backward is ROADMAP
M5's next step; this is the form it has to reproduce.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def ssd_chunked(x, dt, A, Bm, Cm, D, chunk: int, return_carry: bool = False):
    """``x [B, T, H, P]``, ``dt [B, T, H]`` (positive: after its softplus),
    ``A [H]`` (negative), ``Bm`` / ``Cm [B, T, G, N]`` with ``H % G == 0``,
    ``D [H]`` -> ``y [B, T, H, P]`` in ``x``'s dtype. ``T`` must be a multiple
    of ``chunk``. With ``return_carry`` also the float32 states the chunks were
    handed, ``[B, T / chunk, H, P, N]`` (the first is 0)."""
    b, t, h, p = x.shape
    g, n = Bm.shape[2], Bm.shape[3]
    if t % chunk:
        raise ValueError(f"ssd_chunked: {t} positions are not a multiple of the chunk {chunk} (pad upstream)")
    if h % g:
        raise ValueError(f"ssd_chunked: {h} heads do not divide into {g} groups")
    nc, r, f32, cdt = t // chunk, h // g, jnp.float32, x.dtype
    # [B, chunks, L, groups, heads of a group, ...]
    xc = x.reshape(b, nc, chunk, g, r, p)
    dtc = dt.astype(f32).reshape(b, nc, chunk, g, r)
    Bc, Cc = Bm.reshape(b, nc, chunk, g, n), Cm.reshape(b, nc, chunk, g, n)
    a = jnp.cumsum(dtc * A.astype(f32).reshape(g, r), axis=2)  # [B, C, L, G, R], falling from <= 0
    xdt = (xc.astype(f32) * dtc[..., None]).astype(cdt)

    # within a chunk: (decay of the tokens between j and i) o (C_i . B_j), on the tokens at and before i
    cb = jnp.einsum("bcign,bcjgn->bcgij", Cc, Bc, preferred_element_type=f32)
    a_last = a.transpose(0, 1, 3, 4, 2)  # [B, C, G, R, L]
    between = a_last[..., :, None] - a_last[..., None, :]  # a_i - a_j, [B, C, G, R, i, j]
    decay = jnp.exp(jnp.where(jnp.tril(jnp.ones((chunk, chunk), bool)), between, -jnp.inf))
    y = jnp.einsum("bcgrij,bcjgrp->bcigrp", (decay * cb[:, :, :, None]).astype(cdt), xdt, preferred_element_type=f32)

    # the state each chunk hands on, and the carry from chunk to chunk
    to_end = jnp.exp(a[:, :, -1:] - a)  # [B, C, L, G, R]
    handed = jnp.einsum("bcjgrp,bcjgn->bcgrpn", (xc.astype(f32) * (dtc * to_end)[..., None]).astype(cdt), Bc,
                        preferred_element_type=f32)
    over_chunk = jnp.exp(a[:, :, -1])  # [B, C, G, R]

    def carry(state, step):
        decay_c, handed_c = step
        return decay_c[..., None, None] * state + handed_c, state  # the state this chunk was handed

    _, carried = jax.lax.scan(carry, jnp.zeros((b, g, r, p, n), f32),
                              (over_chunk.transpose(1, 0, 2, 3), handed.transpose(1, 0, 2, 3, 4, 5)))
    carried = carried.transpose(1, 0, 2, 3, 4, 5)  # [B, C, G, R, P, N]
    y = y + jnp.exp(a)[..., None] * jnp.einsum("bcign,bcgrpn->bcigrp", Cc, carried.astype(cdt), preferred_element_type=f32)

    y = (y + D.astype(f32).reshape(g, r)[:, :, None] * xc.astype(f32)).reshape(b, t, h, p).astype(cdt)
    return (y, carried.reshape(b, nc, h, p, n)) if return_carry else y
