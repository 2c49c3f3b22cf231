"""The chunked state-space scan (Mamba-2's SSD form, arXiv:2405.21060): two
Pallas kernels with a hand-written backward on the TPU, plain ``jax.numpy``
differentiated by ``jax.grad`` everywhere else.

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
  states carried from chunk to chunk, ``S <- exp(a_L) S + (that sum)``, in float32;
- what the carried state adds inside the next chunk, ``y_i += exp(a_i) S C_i``.

``dt``, ``A``, the running sums, every exponential, the carried state and its
gradient are float32; the products take their operands in ``x``'s dtype (the
model's compute dtype) and accumulate in float32. The same on both paths.

**Which path runs where** (``ssd_chunked`` chooses at trace time, from what it
can observe): on the TPU (``jax.default_backend() == "tpu"``), at shapes that
Mosaic has compiled for a described v5e (a chunk of 128 or 256, heads of 64
channels that fill lane groups of 128 inside a group of ``B`` / ``C``, a state
of 128, one or more whole chunks), where the trace is one device's (the
process sees one device, or the call is inside a ``shard_map``), the kernels;
every other call (the CPU, ``Mamba2Mixer.init``'s 8-token example, other
shapes, plain jit over several devices with no mesh named) the plain form
``_plain``, which is also what the kernels are tested against. A TPU call of
128 positions or more that falls to the plain form says so once in the log,
with the shape that refused it.

**On a mesh.** XLA cannot partition a Pallas call, so under plain jit on
several devices the kernels run inside ``ssd_chunked_sharded``'s ``shard_map``
over the batch and the heads (``Mamba2Mixer`` takes it when
``TransformerConfig.mesh`` names a mesh, as the flash path does; over a mesh of
one device the compiled step is the same with and without it, and the kernels
then run on one chip of a host that shows several). With no mesh named, a step
traced where the process sees several devices keeps the plain form, which XLA
partitions as it did.

**What the kernels hold in VMEM.** ``ssd_fwd`` walks a grid of (batch, block of
heads, chunk), the chunk axis last and in order. A step reads the chunk's ``x``,
the running sums ``a`` and what XLA made of them (``c = a - log dt``, so that
``dt_j exp(a_i - a_j) = exp(a_i - c_j)`` is still the exponential of a
difference and ``x`` is never scaled; ``w = exp(a_L - c)``; ``exp(a_L)``), ``C``
and ``B`` transposed; forms ``C B^T`` once for the block's group; for each head
builds the tile ``exp(a_i - c_j) (C_i . B_j)`` in float32 in 128 x 128 blocks
(the blocks above the diagonal are never built, the pairs above it inside a
diagonal block are sent to ``exp(-inf)``), casts it and takes the product with
``x``; adds the handed state's part ``exp(a_i) C_i S^T`` and ``D x``; and moves
the heads' states on in a float32 scratch that the first chunk zeroes. HBM sees
one read of the inputs, one write of ``y`` and the state each chunk was handed
(float32, ``[B, chunks, H P / 128, N, 128]`` as the kernels hold it: the
backward's residual, and, laid out ``[B, chunks, H, P, N]``, what
``return_carry`` returns). ``ssd_bwd`` walks the same grid from the last chunk to
the first with the state's gradient in the scratch; it rebuilds each tile
(transposed: rows ``j``, columns ``i >= j``) and emits ``dx``, the gradients of
``a``, ``c``, ``w`` and ``exp(a_L)`` (float32; XLA, which made them from ``dt``
and ``A``, takes them back to those), ``dB`` / ``dC`` as float32 partial sums a
block of heads and ``dD`` as float32 sums a lane. The ``L x L`` tile and its
gradient live and die in VMEM. The running sums' ``cumsum``, the ``log`` and
the three small exponentials stay XLA's, outside the ``custom_vjp``, for
``jax.grad`` to differentiate, as does whatever made ``dt``.

The states ``return_carry`` hands back are a reading (``stop_gradient``) on both
paths: the kernels' backward takes no cotangent for them.
"""

from __future__ import annotations

import functools
import logging

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

try:  # as flash_attention.py: without pltpu the kernels cannot be built and every call takes the plain form
    from jax.experimental.pallas import tpu as pltpu
except Exception:  # pragma: no cover
    pltpu = None

logger = logging.getLogger(__name__)

#: a decay tile is built in blocks of this many rows and columns; the blocks wholly above the diagonal are skipped
_SUB = 128
#: the heads are walked in groups that fill the chip's lanes: two heads of 64 channels
_LANES = 128
#: heads a grid step walks (a static loop): enough to hide a step's fixed cost, few enough to compile quickly
_HEADS_PER_STEP = 8


def ssd_chunked(x, dt, A, Bm, Cm, D, chunk: int, return_carry: bool = False):
    """``x [B, T, H, P]``, ``dt [B, T, H]`` (positive: after its softplus),
    ``A [H]`` (negative), ``Bm`` / ``Cm [B, T, G, N]`` with ``H % G == 0``,
    ``D [H]`` -> ``y [B, T, H, P]`` in ``x``'s dtype. ``T`` must be a multiple
    of ``chunk``. With ``return_carry`` also the float32 states the chunks were
    handed, ``[B, T / chunk, H, P, N]`` (the first is 0; not differentiable)."""
    b, t, h, p = x.shape
    g, n = Bm.shape[2], Bm.shape[3]
    if t % chunk:
        raise ValueError(f"ssd_chunked: {t} positions are not a multiple of the chunk {chunk} (pad upstream)")
    if h % g:
        raise ValueError(f"ssd_chunked: {h} heads do not divide into {g} groups")
    heads = 0
    if jax.default_backend() == "tpu" and t >= _SUB:  # a row under 128 positions (``init``'s example) fills no lane: nothing to say
        heads = _heads_per_step(h, g, p, n, chunk)
        if not heads:
            _say_once(f"ssd_chunked: x {x.shape}, B/C {Bm.shape}, chunk {chunk} do not tile the kernels (a chunk of 128 "
                      "or 256, heads of 64 that fill 128 lanes inside a group, a state of 128): the plain form runs")
        elif not _on_one_device():
            heads = 0
            _say_once(f"ssd_chunked: x {x.shape} is traced for {jax.device_count()} devices outside a shard_map, where XLA "
                      "cannot partition a Pallas call: the plain form runs (TransformerConfig.mesh, or ssd_chunked_sharded, "
                      "runs the kernels a shard)")
    y, carried = _kernels(x, dt, A, Bm, Cm, D, chunk, heads) if heads else _plain(x, dt, A, Bm, Cm, D, chunk)
    return (y, jax.lax.stop_gradient(carried)) if return_carry else y


def ssd_chunked_sharded(x, dt, A, Bm, Cm, D, chunk: int, mesh, *, head_axis: str = "model", return_carry: bool = False):
    """:func:`ssd_chunked` under plain jit on a multi-device mesh, as ``flash_attention_sharded`` is to the
    attention kernels: XLA cannot partition a Pallas call, so the scan shard_maps itself over ``mesh``, the batch
    on the data axes and the heads on ``head_axis``, each where it divides (the groups of ``B`` / ``C`` go with
    their heads, one group stays whole on every shard; ``init``'s one-row example stays replicated). The scan is
    independent a row and a head, so each shard runs the unchanged op on its slice and no collective is added but
    the sums, over their holders, of the gradients of what shards share (``A`` and ``D`` over the rows' holders, a
    shared group's ``B`` / ``C`` over the heads'), which shard_map's transpose places. The sequence stays whole on
    each device."""
    from jax.sharding import PartitionSpec as P

    from .flash_attention import dividing_batch_axes

    (h, g), batch = (x.shape[2], Bm.shape[2]), dividing_batch_axes(mesh, x.shape[0])
    shards = mesh.shape[head_axis] if head_axis in mesh.axis_names else 0
    heads = head_axis if shards and h % shards == 0 and (g == 1 or g % shards == 0) else None
    groups = heads if g > 1 else None
    fn = lambda *args: ssd_chunked(*args, chunk, return_carry=True)
    in_specs = (P(batch, None, heads, None), P(batch, None, heads), P(heads), P(batch, None, groups, None),
                P(batch, None, groups, None), P(heads))
    out_specs = (P(batch, None, heads, None), P(batch, None, heads, None, None))
    y, carried = jax.shard_map(fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False)(x, dt, A, Bm, Cm, D)
    return (y, carried) if return_carry else y


def _on_one_device() -> bool:
    """Whether what is traced here runs whole on one device: the process sees one, or the trace is inside a
    ``shard_map`` whose every axis is manual (a shard's program is one device's)."""
    mesh = jax.sharding.get_abstract_mesh()
    return jax.device_count() == 1 or (not mesh.empty and mesh.are_all_axes_manual)


def _heads_per_step(h: int, g: int, p: int, n: int, chunk: int) -> int:
    """Heads of one group a grid step of the kernels walks, or 0 where the
    shapes are not the kernels': they take heads of 64 channels in lane groups
    of 128 (two heads), and only what Mosaic has compiled for a described v5e
    (``tests/test_tpu_compile.py``) is let through: chunks of 128 and 256 and a
    state of 128 (the interpreter sees neither a layout Mosaic refuses nor the
    scoped VMEM limit, so a shape that merely tiles is no shape that runs)."""
    if pltpu is None or chunk not in (_SUB, 2 * _SUB) or p != _LANES // 2 or n != _LANES:
        return 0
    return next((k for k in range(min(_HEADS_PER_STEP, h // g), 0, -1) if (h // g) % k == 0 and k % 2 == 0), 0)


@functools.lru_cache(maxsize=None)
def _say_once(message: str) -> None:
    logger.warning(message)


def _plain(x, dt, A, Bm, Cm, D, chunk: int):
    """The scan in plain ``jax.numpy``: ``(y, states handed [B, C, H, P, N])``."""
    b, t, h, p = x.shape
    g, n = Bm.shape[2], Bm.shape[3]
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
    return y, carried.reshape(b, nc, h, p, n)


# ------------------------------------------------------------ the kernels
#
# What costs on the chip is not the tile's arithmetic but moving values ACROSS lanes (the XLU: a column
# broadcast along lanes, a slice at lane 64, a transposed operand), so the kernels are laid out to need almost
# none: heads are walked in lane groups of 128 (two heads of 64) whose products come out 128 wide and are
# picked apart by a select; per-position scalars come in as ROWS wherever a row will do (they broadcast along
# sublanes for nothing) and as one column a head where it must be a column; ``dt`` rides inside the
# exponential (``c = a - log dt``: ``dt_j exp(a_i - a_j) = exp(a_i - c_j)``) so that ``x`` is never scaled;
# ``B`` and ``C`` come transposed from XLA wherever the kernel would have to transpose them.

_NT = (((1,), (1,)), ((), ()))  # u v^T
_TN = (((0,), (0,)), ((), ()))  # u^T v


def _dot(u, v, dims=(((1,), (0,)), ((), ()))):
    return jax.lax.dot_general(u, v, dims, preferred_element_type=jnp.float32)


def _decay(column, row_ref, k, r0, c0s, keep, transposed=False):
    """``exp(a_i - c_j)`` for rows ``r0 .. r0 + 128`` and the 128-wide column blocks ``c0s`` of head ``k``'s tile,
    float32 ``[128, 128 len(c0s)]``: ``column [L, 128]`` is ``a`` broadcast along lanes and ``row_ref`` holds ``c = a -
    log dt`` a row a head; for the ``transposed`` tile ``[j, i]`` the column is ``c`` and the rows are ``a``. In the
    block on the diagonal the pairs the mask drops are sent to ``exp(-inf)``, as the plain form does."""
    blocks = []
    for c0 in c0s:
        col, row = column[r0:r0 + _SUB], row_ref[0, 0, 0, k:k + 1, c0:c0 + _SUB]
        between = row - col if transposed else col - row
        blocks.append(jnp.exp(jnp.where(keep, between, -jnp.inf) if r0 == c0 else between))
    return blocks[0] if len(blocks) == 1 else jnp.concatenate(blocks, axis=1)


def _triangle(upper: bool):
    """``[128, 128]`` bool, the pairs a block on the diagonal keeps: column <= row, or column >= row of the transposed tile."""
    row, column = (jax.lax.broadcasted_iota(jnp.int32, (_SUB, _SUB), axis) for axis in (0, 1))
    return column >= row if upper else column <= row


def _pick(parts, lane):
    """``[.., 128]`` from one ``[.., 128]`` a head of a lane group's two: each head's own lanes."""
    return jnp.where(lane < _LANES // 2, parts[0], parts[1])


def _rows_of(ref, held, p):
    """``[128, L]`` float32: the row ``ref`` holds for each head of a lane group, on that head's ``p`` rows of a block
    that has positions last (a broadcast along sublanes: it moves nothing across lanes)."""
    return jnp.concatenate([jnp.broadcast_to(ref[0, 0, 0, k:k + 1, :], (p, ref.shape[-1])) for k in held], axis=0)


def _fwd_kernel(xt_ref, a_ref, c_ref, w_ref, over_ref, cm_ref, bt_ref, d_ref, yt_ref, handed_ref, state,
                *, heads: int, p: int):
    """One chunk of ``heads`` heads of one group. ``xt_ref`` / ``yt_ref [1, heads p, L]`` (positions last: a block
    is turned round on its way in and out); ``a_ref [1, 1, L, heads]`` the running sums, a column a head; ``c_ref`` /
    ``w_ref [1, 1, 1, heads, L]`` rows a head: ``c = a - log dt`` and ``w = exp(a_L - c)``, what a token leaves in the
    state handed on; ``over_ref [1, 1, 1, heads p]`` the decay over the whole chunk ``exp(a_L)`` a lane; ``cm_ref [1, L,
    N]`` C, ``bt_ref [1, N, L]`` B transposed; ``d_ref [1, heads p]``; ``handed_ref [1, 1, lane groups, N, 128]``: the
    states, transposed, the heads of a lane group side by side; ``state`` the same, alive across the chunk axis."""
    f32, cdt, chunk, q = jnp.float32, xt_ref.dtype, xt_ref.shape[2], _LANES // p
    blocks = range(0, chunk, _SUB)

    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    c_in, b_t = cm_ref[0], bt_ref[0]
    cb = _dot(c_in, b_t)  # [i, j]
    keep = _triangle(upper=False)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, _LANES), 1)
    for group in range(heads // q):
        lanes, held = slice(group * _LANES, (group + 1) * _LANES), range(group * q, (group + 1) * q)
        x_t, s = xt_ref[0, lanes, :], state[group]  # [128, L], [N, 128]
        x_c = x_t.T
        handed_ref[0, 0, group] = s
        from_state = _dot(c_in, s.astype(cdt))  # C_i S^T of both heads, [L, 128]
        moved = _dot(b_t, (x_t.astype(f32) * _rows_of(w_ref, held, p)).astype(cdt).T)  # sum_j w_j B_j x_j^T, [N, 128]
        ys = []
        for k in held:
            a = jnp.broadcast_to(a_ref[0, 0, :, k:k + 1], (chunk, _LANES))  # the one column broadcast a head
            rows = []
            for r0 in blocks:  # rows i = r0 .. r0 + 128 of the tile reach columns j = 0 .. r0 + 128
                tile = _decay(a, c_ref, k, r0, range(0, r0 + _SUB, _SUB), keep) * cb[r0:r0 + _SUB, :r0 + _SUB]
                rows.append(_dot(tile.astype(cdt), x_c[:r0 + _SUB]))
            ys.append(jnp.concatenate(rows, axis=0) + jnp.exp(a) * from_state)
        yt_ref[0, lanes, :] = (_pick(ys, lane) + d_ref[:, lanes] * x_c.astype(f32)).astype(cdt).T
        state[group] = over_ref[0, 0, :, lanes] * s + moved


def _bwd_kernel(xt_ref, dyt_ref, c_ref, a_ref, w_ref, over_ref, b_ref, bt_ref, ct_ref, d_ref, handed_ref,
                dxt_ref, da_ref, dc_ref, dw_ref, dover_ref, dbt_ref, dct_ref, dd_ref, dstate, *, heads: int, p: int):
    """The same step for the gradients, the chunks walked from the last to the first (the index maps turn the
    axis round). The tile is built transposed, ``[j, i]`` on ``i >= j``. Here ``c_ref [1, 1, L, heads]`` is the
    column and ``a_ref`` / ``w_ref [1, 1, 1, heads, L]`` the rows; ``b_ref [1, L, N]`` B, ``bt_ref`` / ``ct_ref [1, N,
    L]`` B and C transposed; ``xt_ref``, ``dyt_ref`` and ``dxt_ref`` have positions last, as the forward's. Out
    beside ``dxt_ref``: ``da_ref`` / ``dw_ref`` rows a head, ``dc_ref`` a column a head;
    ``dover_ref [1, 1, 1, heads p]`` (summed over the state's rows, not yet over a head's lanes); ``dbt_ref`` /
    ``dct_ref [1, 1, N, L]`` this block of heads' partial sums, transposed; ``dd_ref [1, 1, heads p]`` (summed over
    the chunks here). ``a``, ``c``, ``w`` and ``over`` are the op's separate inputs: XLA, which made them from
    ``dt`` and ``A``, takes their gradients back. Every decay ``exp(a_i - c_j)`` sends the SAME number to
    ``a_i`` and, negated, to ``c_j`` (``pairs`` below, summed by rows and by columns): a head's ``A`` gathers
    them through a running sum, where a pair rounded two ways would leave its rounding instead of nought."""
    f32, cdt, chunk, q = jnp.float32, xt_ref.dtype, xt_ref.shape[2], _LANES // p
    blocks = range(0, chunk, _SUB)

    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate[...] = jnp.zeros_like(dstate)
        dd_ref[...] = jnp.zeros_like(dd_ref)

    b_in, b_t, c_t = b_ref[0], bt_ref[0], ct_ref[0]
    cb_t = _dot(b_in, c_t)  # [j, i]
    keep = _triangle(upper=True)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, _LANES), 1)
    head_lane = jax.lax.broadcasted_iota(jnp.int32, (chunk, heads), 1)
    dcb_t = [jnp.zeros((_SUB, chunk - r0), f32) for r0 in blocks]  # sum over the heads of exp(a_i - c_j) (x_j . dy_i)
    db_t, dc_t = jnp.zeros(b_t.shape, f32), jnp.zeros(c_t.shape, f32)
    dc_all = jnp.zeros((chunk, heads), f32)
    for group in range(heads // q):
        lanes, held = slice(group * _LANES, (group + 1) * _LANES), range(group * q, (group + 1) * q)
        x_t, dy_t = xt_ref[0, lanes, :], dyt_ref[0, lanes, :]  # [128, L]
        x_c, dy_c = x_t.T, dy_t.T  # [L, 128]
        s, ds = handed_ref[0, 0, group], dstate[group]  # [N, 128]
        s_c, ds_c = s.astype(cdt), ds.astype(cdt)
        # y_i += exp(a_i) C_i S^T: to C, to a_i (a row a head, below) and to the state's gradient
        grown_dy = dy_t.astype(f32) * jnp.exp(_rows_of(a_ref, held, p))  # exp(a_i) dy_i, [128, i]
        dc_t = dc_t + _dot(s_c, grown_dy.astype(cdt))  # both heads' sum, [N, i]
        to_grown = grown_dy * _dot(s_c, c_t, _TN)  # exp(a_i) dy_i o S C_i, [128, i]
        sent = _dot(c_t, grown_dy.astype(cdt).T)  # [N, 128]
        # S' += sum_j w_j B_j x_j^T: to B, to w_j (a row a head) and to x_j (below)
        db_t = db_t + _dot(ds_c, (x_t.astype(f32) * _rows_of(w_ref, held, p)).astype(cdt))  # [N, j]
        from_state = _dot(b_in, ds_c)  # B_j dS^T of both heads, [L, 128]
        to_w = x_t.astype(f32) * _dot(ds_c, b_t, _TN)  # x_j o dS B_j, [128, j]
        dxs = []
        for u, k in enumerate(held):
            own = (lane >= u * p) & (lane < (u + 1) * p)  # this head's lanes
            x_own = jnp.where(own, x_c, jnp.zeros_like(x_c))
            c = jnp.broadcast_to(c_ref[0, 0, :, k:k + 1], (chunk, _LANES))  # the one column broadcast a head
            a_row = a_ref[0, 0, 0, k:k + 1, :]
            da_row = jnp.sum(to_grown[u * p:(u + 1) * p], axis=0, keepdims=True)
            dw_ref[0, 0, 0, k:k + 1, :] = jnp.sum(to_w[u * p:(u + 1) * p], axis=0, keepdims=True)
            rows, dc_rows = [], []
            for i, r0 in enumerate(blocks):  # rows j = r0 .. r0 + 128 of the transposed tile reach columns i = r0 .. L
                decay = _decay(c, a_ref, k, r0, range(r0, chunk, _SUB), keep, transposed=True)
                tile = decay * cb_t[r0:r0 + _SUB, r0:]
                inner = _dot(x_own[r0:r0 + _SUB], dy_t[:, r0:])  # x_j . dy_i
                dcb_t[i] = dcb_t[i] + decay * inner
                pairs = tile * inner
                to_i = jnp.sum(pairs, axis=0, keepdims=True)
                da_row = da_row + (jnp.concatenate([jnp.zeros((1, r0), f32), to_i], axis=1) if r0 else to_i)
                dc_rows.append(-jnp.sum(pairs, axis=1, keepdims=True))
                rows.append(_dot(tile.astype(cdt), dy_c[r0:]))
            dxs.append(jnp.concatenate(rows, axis=0) + jnp.exp(_last(a_row) - c) * from_state)  # + w_j B_j dS^T
            da_ref[0, 0, 0, k:k + 1, :] = da_row
            dc_all = jnp.where(head_lane == k, jnp.concatenate(dc_rows, axis=0), dc_all)
        dy = dy_c.astype(f32)
        dxt_ref[0, lanes, :] = (_pick(dxs, lane) + d_ref[:, lanes] * dy).astype(cdt).T
        dd_ref[0, :, lanes] += jnp.sum(dy * x_c.astype(f32), axis=0, keepdims=True)
        dover_ref[0, 0, :, lanes] = jnp.sum(ds * s, axis=0, keepdims=True)
        dstate[group] = over_ref[0, 0, :, lanes] * ds + sent  # the gradient of the state this chunk was handed
    dc_ref[0, 0] = dc_all
    # C B^T's gradient, still transposed: rows j, columns i
    dcb_t = jnp.concatenate([block if not r0 else jnp.concatenate([jnp.zeros((_SUB, r0), f32), block], axis=1)
                             for r0, block in zip(blocks, dcb_t)], axis=0).astype(cdt)
    dbt_ref[0, 0] = db_t + _dot(c_t, dcb_t, _NT)  # sum_i C_i dcb[j, i]
    dct_ref[0, 0] = dc_t + _dot(b_t, dcb_t)  # sum_j B_j dcb[j, i]


def _last(row):
    """``[1, 1]``: the last lane of a ``[1, L]`` row, by a masked sum (a slice would
    leave the value at lane L - 1, a layout Mosaic does not broadcast from)."""
    lane = jax.lax.broadcasted_iota(jnp.int32, row.shape, 1)
    return jnp.sum(jnp.where(lane == row.shape[1] - 1, row, 0.0), axis=1, keepdims=True)


def _layouts(b, t, h, p, g, n, chunk, heads, chunk_of):
    """Block specs and the layouts they read, on a grid (batch, block of heads, step); ``chunk_of(step)`` is the
    chunk a step works on. Returns ``(specs, columns, rows)``: ``columns(v)`` lays a ``[B, T, H]`` array out a
    column a head ``[B, H / heads, T, heads]``, ``rows(v)`` a row a head ``[B, H / heads, chunks, heads, L]`` (1 MB
    arrays: XLA's to lay out), and back with ``inverse=True``."""
    nc, blocks = t // chunk, h // heads
    per_group = blocks // g  # blocks of heads to a group
    vmem = {"memory_space": pltpu.VMEM}
    specs = dict(
        wide=pl.BlockSpec((1, heads * p, chunk), lambda i, j, s: (i, j, chunk_of(s)), **vmem),  # x, y, dy, dx, transposed
        column=pl.BlockSpec((1, 1, chunk, heads), lambda i, j, s: (i, j, chunk_of(s), 0), **vmem),
        row=pl.BlockSpec((1, 1, 1, heads, chunk), lambda i, j, s: (i, j, chunk_of(s), 0, 0), **vmem),
        over=pl.BlockSpec((1, 1, 1, heads * p), lambda i, j, s: (i, chunk_of(s), 0, j), **vmem),  # a chunk's scalar a lane
        group=pl.BlockSpec((1, chunk, n), lambda i, j, s: (i, chunk_of(s), j // per_group), **vmem),  # B, C
        group_t=pl.BlockSpec((1, n, chunk), lambda i, j, s: (i, j // per_group, chunk_of(s)), **vmem),  # B, C transposed
        partial_t=pl.BlockSpec((1, 1, n, chunk), lambda i, j, s: (i, j, 0, chunk_of(s)), **vmem),
        skip=pl.BlockSpec((1, heads * p), lambda i, j, s: (0, j), **vmem),  # D a lane
        states=pl.BlockSpec((1, 1, heads * p // _LANES, n, _LANES), lambda i, j, s: (i, chunk_of(s), j, 0, 0), **vmem),
    )

    def columns(v, inverse=False):
        return v.transpose(0, 2, 1, 3).reshape(b, t, h) if inverse else v.reshape(b, t, blocks, heads).transpose(0, 2, 1, 3)

    def rows(v, inverse=False):
        if inverse:
            return v.transpose(0, 2, 4, 1, 3).reshape(b, t, h)
        return v.reshape(b, nc, chunk, blocks, heads).transpose(0, 3, 1, 4, 2)

    return specs, columns, rows


def _params(inputs: int, wide: int):
    """The first ``wide`` of the ``inputs`` are ``x`` (and ``dy``), cut out of the mixer's conv output: XLA may fuse
    that cut into the kernel's reads where it would else copy 33 MB a layer and direction to a buffer of its own."""
    return pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "arbitrary"),
                                allow_input_fusion=[i < wide for i in range(inputs)])


def _positions_last(v):
    """``[B, T, H, P]`` -> ``[B, H P, T]``: how the kernels read and write ``x``, ``y`` and their gradients. XLA lays
    the mixer's activations out positions-minor (its projections and its conv want the batch in lanes), so this
    is a bitcast there, where a kernel that took ``[B, T, H P]`` made XLA copy 33 MB a layer and direction; the
    kernels turn each ``[128, L]`` block round themselves, which costs them next to nothing."""
    b, t, h, p = v.shape
    return v.reshape(b, t, h * p).transpose(0, 2, 1)


def _a_lane(v, p):
    """``[B, C, H]`` -> ``[B, C, 1, H p]``: a head's scalar on each of its lanes."""
    return jnp.repeat(v, p, axis=-1)[:, :, None, :]


# jit keeps the traced kernels: nine layers call the scan in three directions, and a
# step is traced twice before it runs (flash_attention.py; PERF.md, PR 27: the set-up split)
@functools.partial(jax.jit, static_argnums=(8, 9, 10))
def _scan_fwd(x, a, c, w, over, Bm, Cm, D, chunk, heads, interpret):
    b, t, h, p = x.shape
    g, n = Bm.shape[2], Bm.shape[3]
    nc, f32 = t // chunk, jnp.float32
    s, columns, rows = _layouts(b, t, h, p, g, n, chunk, heads, lambda step: step)
    in_specs = [s["wide"], s["column"], s["row"], s["row"], s["over"], s["group"], s["group_t"], s["skip"]]
    y, handed = pl.pallas_call(
        functools.partial(_fwd_kernel, heads=heads, p=p),
        out_shape=[jax.ShapeDtypeStruct((b, h * p, t), x.dtype),
                   jax.ShapeDtypeStruct((b, nc, h * p // _LANES, n, _LANES), f32)],
        grid=(b, h // heads, nc),
        in_specs=in_specs,
        out_specs=[s["wide"], s["states"]],
        scratch_shapes=[pltpu.VMEM((heads * p // _LANES, n, _LANES), f32)],
        compiler_params=_params(len(in_specs), wide=1),
        interpret=interpret,
        name="ssd_fwd",
    )(_positions_last(x), columns(a), rows(c), rows(w), _a_lane(over, p), Cm.reshape(b, t, g * n),
      Bm.reshape(b, t, g * n).transpose(0, 2, 1), jnp.repeat(D, p).reshape(1, h * p))
    return y.transpose(0, 2, 1).reshape(b, t, h, p), handed


@functools.partial(jax.jit, static_argnums=(10, 11, 12))
def _scan_bwd(x, a, c, w, over, Bm, Cm, D, handed, dy, chunk, heads, interpret):
    b, t, h, p = x.shape
    g, n = Bm.shape[2], Bm.shape[3]
    nc, blocks, f32 = t // chunk, h // heads, jnp.float32
    s, columns, rows = _layouts(b, t, h, p, g, n, chunk, heads, lambda step: nc - 1 - step)
    flat = Bm.reshape(b, t, g * n), Cm.reshape(b, t, g * n)
    in_specs = [s["wide"], s["wide"], s["column"], s["row"], s["row"], s["over"], s["group"], s["group_t"], s["group_t"],
                s["skip"], s["states"]]
    dx, da, dc, dw, dover, db_t, dc_t, dd = pl.pallas_call(
        functools.partial(_bwd_kernel, heads=heads, p=p),
        out_shape=[jax.ShapeDtypeStruct((b, h * p, t), x.dtype),
                   *(jax.ShapeDtypeStruct(shape, f32) for shape in (
                       (b, blocks, nc, heads, chunk), (b, blocks, t, heads), (b, blocks, nc, heads, chunk), (b, nc, 1, h * p),
                       (b, blocks, n, t), (b, blocks, n, t), (b, 1, h * p)))],
        grid=(b, blocks, nc),
        in_specs=in_specs,
        out_specs=[s["wide"], s["row"], s["column"], s["row"], s["over"], s["partial_t"], s["partial_t"],
                   pl.BlockSpec((1, 1, heads * p), lambda i, j, step: (i, 0, j), memory_space=pltpu.VMEM)],
        scratch_shapes=[pltpu.VMEM((heads * p // _LANES, n, _LANES), f32)],
        compiler_params=_params(len(in_specs), wide=2),
        interpret=interpret,
        name="ssd_bwd",
    )(_positions_last(x), _positions_last(dy), columns(c), rows(a), rows(w), _a_lane(over, p), flat[0],
      flat[0].transpose(0, 2, 1), flat[1].transpose(0, 2, 1), jnp.repeat(D, p).reshape(1, h * p), handed)
    # the blocks of heads' partial sums [B, blocks, N, T] -> [B, T, G, N]
    of_group = lambda v: v.reshape(b, g, blocks // g, n, t).sum(axis=2).transpose(0, 3, 1, 2).astype(Bm.dtype)
    return (dx.transpose(0, 2, 1).reshape(b, t, h, p), rows(da, inverse=True), columns(dc, inverse=True),
            rows(dw, inverse=True), dover.reshape(b, nc, h, p).sum(axis=-1), of_group(db_t), of_group(dc_t),
            dd.reshape(b, h, p).sum(axis=(0, 2)))


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9, 10))
def _scan(x, a, c, w, over, Bm, Cm, D, chunk, heads, interpret):
    """The kernels' op. Float32 ``[B, T, H]``: ``a`` the in-chunk running sums of ``dt A``, ``c = a - log dt``,
    ``w = exp(a_L - c)``; ``over = exp(a_L) [B, chunks, H]``; ``Bm`` / ``Cm`` in ``x``'s dtype, ``D`` float32 ->
    ``(y, the states handed [B, chunks, H p / 128, N, 128])``. ``interpret`` runs the kernels in the Pallas
    interpreter (the tests, on the CPU)."""
    return _scan_fwd(x, a, c, w, over, Bm, Cm, D, chunk, heads, interpret)


def _scan_vjp_fwd(x, a, c, w, over, Bm, Cm, D, chunk, heads, interpret):
    y, handed = _scan_fwd(x, a, c, w, over, Bm, Cm, D, chunk, heads, interpret)
    return (y, handed), (x, a, c, w, over, Bm, Cm, D, handed)


def _scan_vjp_bwd(chunk, heads, interpret, residuals, cotangents):
    return _scan_bwd(*residuals, cotangents[0], chunk, heads, interpret)  # the handed states are a reading: no cotangent


_scan.defvjp(_scan_vjp_fwd, _scan_vjp_bwd)


def _kernels(x, dt, A, Bm, Cm, D, chunk: int, heads: int, interpret: bool = False):
    """``(y, states handed [B, C, H, P, N])`` by the kernels: what feeds them is made here, in XLA, for
    ``jax.grad`` to differentiate."""
    b, t, h, p = x.shape
    n, nc, f32 = Bm.shape[3], t // chunk, jnp.float32
    dt = dt.astype(f32)
    a = jnp.cumsum((dt * A.astype(f32)).reshape(b, nc, chunk, h), axis=2)
    c = a - jnp.log(jnp.maximum(dt, 1e-30)).reshape(a.shape)  # dt_j exp(a_i - a_j) = exp(a_i - c_j): still of a difference
    a_end = a[:, :, -1:]
    flat = lambda v: v.reshape(b, t, h)
    y, handed = _scan(x, flat(a), flat(c), flat(jnp.exp(a_end - c)), jnp.exp(a_end[:, :, 0]),
                      Bm.astype(x.dtype), Cm.astype(x.dtype), D.astype(f32), chunk, heads, interpret)
    # [B, C, lane groups, N, heads of a group, P] -> [B, C, H, P, N]
    handed = handed.reshape(b, nc, h * p // _LANES, n, _LANES // p, p).transpose(0, 1, 2, 4, 5, 3).reshape(b, nc, h, p, n)
    return y, handed
