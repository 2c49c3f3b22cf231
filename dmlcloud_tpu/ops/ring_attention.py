"""Ring attention: sequence/context parallelism over a mesh axis.

The reference has no sequence-parallel machinery (SURVEY.md §5.7); this is the
TPU build's long-context path. Activations are sharded along the sequence
dimension over the ``seq`` mesh axis; K/V blocks rotate around the ring with
``ppermute`` over ICI while each device merges its queries' attention against
each visiting block. Memory per device is O(T/n); no device ever holds the
full sequence — exact attention at arbitrary context length.

The per-block attention IS the fused Pallas flash kernel
(ops/flash_attention.py) called with ``return_lse=True``: operands stay in
their native dtype (bf16 on the MXU), no [Tl, Tk] score matrix ever reaches
HBM, and the visiting blocks' normalized outputs are merged with the
standard blockwise combination — running max over block LSEs, exp-corrected
weighted sum — carried in fp32. Under causal masking, ``lax.switch`` runs
the non-causal kernel for blocks behind this device, the causal kernel for
the diagonal block, and skips blocks ahead entirely (weight exp(-inf)).
Gradients flow through the merge AND through the kernel's lse output
(``_flash_lse`` custom_vjp).

Two entry points:

- ``ring_attention(q, k, v, axis_name=...)``: call *inside* an existing
  ``shard_map`` over the seq axis (the usual case when the whole train step is
  shard_mapped).
- ``ring_attention_sharded(q, k, v, mesh, axis_name=...)``: wraps itself in a
  ``shard_map`` over ``mesh`` for use under plain ``jit`` — activations get
  resharded to P(None, 'seq') around the call.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from .flash_attention import dividing_batch_axes, flash_attention

_NEG_INF = -1e30


def _merge_partials(m, w, acc, out_b, lse_b):
    """Blockwise combination of normalized attention partials:
    out = Σ_b exp(lse_b)·out_b / Σ_b exp(lse_b), carried with a running max
    for stability. The ONE numerically sensitive merge, shared by the
    scanned and the windowed-unrolled ring loops."""
    new_m = jnp.maximum(m, lse_b)
    c_prev = jnp.exp(m - new_m)
    c_new = jnp.exp(lse_b - new_m)
    acc = acc * c_prev[..., None] + out_b.astype(jnp.float32) * c_new[..., None]
    return new_m, w * c_prev + c_new, acc


def ring_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    axis_name: str = "seq",
    causal: bool = True,
    sm_scale: float | None = None,
    block_q: int | None = None,
    block_k: int | None = None,
    interpret: bool | None = None,
    window: int | None = None,
) -> jnp.ndarray:
    """Exact attention over a sequence sharded on ``axis_name``.

    Shapes (per device): q [B, Tl, H, D]; k/v [B, Tl, KH, D] where Tl is the
    local sequence block. Must be called inside shard_map/pmap with
    ``axis_name`` mapped. Returns [B, Tl, H, D].

    ``window`` = W (requires ``causal``) makes the attention sliding-window
    over GLOBAL positions — and because the ring step distance is static,
    the ring visits only ``1 + ceil((W-1)/Tl)`` blocks instead of all n:
    long-context windowed training communicates O(W), not O(T).
    """
    b, tl, h, d = q.shape
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)

    if window is not None:
        if not causal:
            raise ValueError("window (sliding-window ring attention) requires causal=True")
        if window < 1:
            raise ValueError(f"window must be >= 1, got {window}")
        return _ring_attention_windowed(
            q, k, v, axis_name, int(window), sm_scale, block_q, block_k, interpret
        )

    flash = partial(
        flash_attention,
        sm_scale=sm_scale,
        block_q=block_q,
        block_k=block_k,
        interpret=interpret,
        return_lse=True,
    )

    def behind_block(q, kb, vb):  # src strictly before this device: no mask
        return flash(q, kb, vb, causal=False)

    def diagonal_block(q, kb, vb):  # this device's own block: causal mask
        return flash(q, kb, vb, causal=True)

    def ahead_block(q, kb, vb):  # src strictly after: fully masked, skip
        return (
            jnp.zeros((b, tl, h, d), q.dtype),
            jnp.full((b, tl, h), _NEG_INF, jnp.float32),
        )

    m0 = jnp.full((b, tl, h), _NEG_INF, jnp.float32)
    w0 = jnp.zeros((b, tl, h), jnp.float32)
    acc0 = jnp.zeros((b, tl, h, d), jnp.float32)

    def body(carry, step):
        m, w, acc, kb, vb = carry
        src = (idx - step) % n  # which sequence block kb/vb holds

        if causal:
            branch = jnp.where(src == idx, 1, jnp.where(src < idx, 0, 2))
            out_b, lse_b = jax.lax.switch(
                branch, [behind_block, diagonal_block, ahead_block], q, kb, vb
            )
        else:
            out_b, lse_b = behind_block(q, kb, vb)

        m, w, acc = _merge_partials(m, w, acc, out_b, lse_b)

        # rotate K/V around the ring (ICI neighbour exchange, overlaps compute)
        perm = [(i, (i + 1) % n) for i in range(n)]
        kb = jax.lax.ppermute(kb, axis_name, perm)
        vb = jax.lax.ppermute(vb, axis_name, perm)
        return (m, w, acc, kb, vb), None

    # the scan is over ring HOPS, not layers: the carry is O(1) merge stats
    # (m/w/acc) and the heavy per-block attention is the flash custom-vjp,
    # which already recomputes instead of saving
    # dmllint: disable-next-line=DML206 -- ring hops, remat would re-run the whole ring
    (m, w, acc, _, _), _ = jax.lax.scan(body, (m0, w0, acc0, k, v), jnp.arange(n))
    return (acc / w[..., None]).astype(q.dtype)


def _ring_attention_windowed(q, k, v, axis_name, window, sm_scale, block_q, block_k, interpret):
    """Causal sliding-window ring attention.

    The ring step distance is STATIC (at hop ``step``, a device either holds
    the block exactly ``step`` positions behind it, or a wrapped-around
    ahead-block it must skip), so the loop unrolls in Python: hop 0 is the
    diagonal (causal + window), hop ``step`` uses the flash kernel with the
    distance-shifted relative cutoff ``window - step*Tl``, and hops whose
    nearest pair is already outside the window never run — the loop AND the
    ppermutes stop after ``1 + ceil((window-1)/Tl)`` hops. Dead rows (no
    valid key in a visiting block — every kernel block skipped) get a
    floored lse of ~ -1e30 from the kernel write, so their merge weight
    underflows to exactly zero, forward and backward."""
    import math as _math

    from .flash_attention import _auto_block, _flash_lse

    b, tl, h, d = q.shape
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    if sm_scale is None:
        sm_scale = 1.0 / _math.sqrt(d)
    from .flash_attention import _XLA_BLOCK_Q, _default_mode

    mode = _default_mode(interpret)
    if block_q is None:
        block_q = _XLA_BLOCK_Q if mode == "xla" else 512
    if block_k is None:
        block_k = 1024
    bq, bk = _auto_block(block_q, tl), _auto_block(block_k, tl)

    # hop `step` >= 1 participates iff its closest pair distance
    # (step-1)*Tl + 1 is still inside the window
    steps_needed = min(n, max(1, (window - 2) // tl + 2))

    m0 = jnp.full((b, tl, h), _NEG_INF, jnp.float32)
    w0 = jnp.zeros((b, tl, h), jnp.float32)
    acc0 = jnp.zeros((b, tl, h, d), jnp.float32)
    m, w, acc, kb, vb = m0, w0, acc0, k, v

    def to_bth(lse):  # [B*H, Tl] kernel residual -> [B, Tl, H]
        return lse.reshape(b, h, tl).transpose(0, 2, 1)

    for step in range(steps_needed):
        if step == 0:
            out_b, lse_b = _flash_lse(q, kb, vb, None, True, float(sm_scale), bq, bk, mode, window)
            lse_b = to_bth(lse_b)
        else:
            # a device holds the block `step` behind it iff idx >= step;
            # otherwise the wrapped block is AHEAD and fully masked
            w_eff = window - step * tl  # static relative cutoff in local coords

            def behind(q, kb, vb):
                o, l = _flash_lse(q, kb, vb, None, False, float(sm_scale), bq, bk, mode, w_eff)
                return o, to_bth(l)

            def ahead(q, kb, vb):
                return (
                    jnp.zeros((b, tl, h, d), q.dtype),
                    jnp.full((b, tl, h), _NEG_INF, jnp.float32),
                )

            out_b, lse_b = jax.lax.cond(idx >= step, behind, ahead, q, kb, vb)
        m, w, acc = _merge_partials(m, w, acc, out_b, lse_b)

        if step < steps_needed - 1:  # no rotation after the last used hop
            perm = [(i, (i + 1) % n) for i in range(n)]
            kb = jax.lax.ppermute(kb, axis_name, perm)
            vb = jax.lax.ppermute(vb, axis_name, perm)

    return (acc / w[..., None]).astype(q.dtype)


def ring_attention_sharded(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    mesh: Mesh,
    axis_name: str = "seq",
    causal: bool = True,
    sm_scale: float | None = None,
    block_q: int | None = None,
    block_k: int | None = None,
    interpret: bool | None = None,
    window: int | None = None,
) -> jnp.ndarray:
    """Ring attention callable under plain jit: shard_maps itself over
    ``mesh`` with the sequence dim (axis 1) split on ``axis_name`` and batch
    on the data axes when they divide it (a batch too small for the data
    axes — e.g. module.init's example input — stays replicated)."""
    if axis_name in mesh.shape and q.shape[1] % mesh.shape[axis_name]:
        raise ValueError(
            f"sequence length {q.shape[1]} is not divisible by mesh axis "
            f"{axis_name!r} of size {mesh.shape[axis_name]}"
        )
    spec_q = P(dividing_batch_axes(mesh, q.shape[0]), axis_name, None, None)

    fn = partial(
        ring_attention,
        axis_name=axis_name,
        causal=causal,
        sm_scale=sm_scale,
        block_q=block_q,
        block_k=block_k,
        interpret=interpret,
        window=window,
    )
    # the ring's collectives produce per-shard values on purpose
    return jax.shard_map(
        fn, mesh=mesh, in_specs=(spec_q, spec_q, spec_q), out_specs=spec_q, check_vma=False
    )(q, k, v)
