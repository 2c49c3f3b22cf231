"""Flash attention as Pallas TPU kernels (forward AND backward).

The reference framework has no attention code at all (SURVEY.md §5.7); models
were user-space. The TPU build ships attention as a first-class fused op
because it is *the* hot op of the transformer configs in BASELINE.json.

Kernel design (online-softmax, Dao-style but TPU-shaped):

- A block plan (``_BlockPlan``), made once at trace time from
  ``(T, S, block_q, block_k, causal, window)``: for each query block the band
  of key blocks that hold a pair the mask keeps (and the mirror, for each key
  block its band of query blocks), and for each such pair whether the mask's
  edge crosses it. Plain integer arithmetic; the three kernels, their index
  maps and the tests all read it, so forward and backward cannot drift.
- Forward grid: ``(batch*heads, T/block_q, widest band)`` — K/V stream through
  the innermost *grid* axis, offset by the band's first block, so VMEM holds
  one [block_k, D] tile of each at a time (Mosaic double-buffers the
  pipeline); sequence length never enters the VMEM footprint, and key blocks
  past the diagonal or older than the window are not grid steps at all (the
  few padded steps of bands shorter than the widest skip their body and
  re-request the band's last block, which elides their DMA). The
  online-softmax carry (running max/denominator/output accumulator, fp32)
  lives in VMEM scratch, persisting across the band. No [T, S] score matrix
  ever materialises. The differentiable path also writes the per-row
  logsumexp (the FlashAttention-2 residual: O and LSE, nothing else).
- Backward: two kernels sharing the saved LSE and the precomputed
  ``delta = rowsum(dO * O)``. The dQ kernel mirrors the forward grid
  (one query block, its band of K/V on the innermost grid axis, dq in
  scratch); the dK/dV kernel transposes it (one KV block, its band of Q/dO
  on the innermost axis). Probabilities are recomputed as ``exp(s - lse)`` —
  no second softmax pass, no saved [T, S] matrix.
- MXU does the matmuls with fp32 accumulation (``preferred_element_type``);
  VPU does the exp/renormalisation. Each kernel holds two bodies and a
  scalar from the plan picks one per step: the causal-and-window mask (two
  iotas, two compares, an and, a select per element) is computed only in the
  pairs its edge crosses; a pair wholly inside the mask runs the same body
  without it (and, in the forward of an unpacked batch, without the dead-row
  select). The segment mask stays in every pair of a packed batch: its edges
  are data.
- Block shapes: 512 x 1024 unless a sweep on the v5e chose another for exactly
  the call's (head_dim, T, S, causal, window) (``_SWEPT_BLOCKS``; constants —
  nothing is tuned or timed at run time or import time). Explicit
  ``block_q`` / ``block_k`` win.
- GQA: the K/V block index map folds the query head onto its KV head, so
  grouped heads reread the same VMEM block instead of materialising repeats;
  the backward accumulates per-query-head dK/dV and group-sums outside the
  kernel.

Off-TPU the op does NOT interpret the Pallas kernels by default any more:
interpret mode emulates the grid step by step, far slower than the unfused
einsum path on a CPU. Instead ``impl="xla"`` (the off-TPU default) lowers the
SAME blockwise algorithm to plain XLA ops: a static Python loop over query
blocks, causal/window K-truncation per block (the compute saving survives),
the identical LSE residual, and the identical recompute-from-statistics
custom backward — so training off-TPU pays the flash algorithm, not the
interpreter. ``impl="pallas"`` with ``interpret=True`` keeps the bit-exact
kernel emulation for kernel-logic tests. Both Pallas modes need
``jax.experimental.pallas.tpu`` importable — the scratch accumulators are
``pltpu.VMEM`` allocations even under interpretation.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

try:  # degrade to a clear RuntimeError at call time if this jax lacks pltpu
    from jax.experimental.pallas import tpu as pltpu

    _VMEM = pltpu.VMEM
except Exception:  # pragma: no cover
    _VMEM = None

_NEG_INF = -1e30
#: TPU vector lane count: per-row stats (LSE, delta) are stored broadcast
#: across one lane tile, the layout Mosaic can store without dynamic
#: sublane indexing (same scheme as jax.experimental.pallas.ops.tpu).
_LANES = 128

#: default query-block of the XLA (off-TPU) path: small enough that causal
#: K-truncation prunes ~40% of the score matmuls at tier-1 sequence
#: lengths, large enough to keep per-block dispatch negligible (a single
#: 512 block prunes nothing at S=512). A CPU-side choice: tier-1's wall time
#: is all it moves.
_XLA_BLOCK_Q = 128


def _default_mode(interpret: bool | None):
    """Resolve the execution mode shared by this module and ring_attention:
    an explicit ``interpret`` pins the Pallas kernels (compiled or
    emulated); otherwise TPU runs them compiled and every other backend
    takes the blockwise-XLA path."""
    if interpret is not None:
        return bool(interpret)
    return False if jax.default_backend() == "tpu" else "xla"


def _window_mask(s, plan: "_BlockPlan", qb, kb):
    """Apply causal (and optional sliding-window) masking to the [bq, bk] score
    block of pair (``qb``, ``kb``) of a masked plan. ``window`` = W keeps
    ``q_pos - k_pos < W`` (self + W-1 predecessors), the Mistral convention."""
    shape = (plan.block_q, plan.block_k)
    q_pos = qb * plan.block_q + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    k_pos = kb * plan.block_k + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    keep = q_pos >= k_pos if plan.causal else None
    if plan.window is not None:
        wkeep = (q_pos - k_pos) < plan.window
        keep = wkeep if keep is None else keep & wkeep
    return jnp.where(keep, s, _NEG_INF)


#: sublane count for the kv-side segment-id layout ([B, _SUBLANES, S]): a
#: (1, 8, block_k) block yields the [1, bk] ROW the mask comparison needs
#: without an in-kernel transpose (the q side is lane-broadcast instead).
_SUBLANES = 8


def _segment_mask(s, seg_q_ref, seg_kv_ref):
    """Mask cross-segment pairs: seg_q_ref [1, bq, _LANES] (lane-broadcast),
    seg_kv_ref [1, _SUBLANES, bk] (sublane-broadcast)."""
    if seg_q_ref is None:
        return s
    q_ids = seg_q_ref[0][:, :1]  # [bq, 1]
    k_ids = seg_kv_ref[0][:1, :]  # [1, bk]
    return jnp.where(q_ids == k_ids, s, _NEG_INF)


def _least(a, b):
    """min() over Python ints (the tests' and the wrappers' reading of the
    plan) or traced scalars (the kernels' and index maps')."""
    return min(a, b) if isinstance(a, int) and isinstance(b, int) else jnp.minimum(a, b)


def _most(a, b):
    return max(a, b) if isinstance(a, int) and isinstance(b, int) else jnp.maximum(a, b)


@dataclasses.dataclass(frozen=True)
class _BlockPlan:
    """Which (query block, key block) pairs the causal-and-window mask keeps,
    and which of those its edge crosses — made once at trace time from the
    shapes and the mask arguments, and read by all three kernels, their index
    maps and the tests, so forward and backward can never drift (a divergence
    would feed exp(s - lse) garbage into whichever side still ran the block).

    The mask keeps (q, k) iff ``k <= q`` (causal) and ``q - k < window``
    (window; the ring's behind-hops call with ``causal=False`` and a shifted,
    possibly negative, window). The pairs a query block holds are one band of
    consecutive key blocks, and the mirror for a key block: the grids' inner
    axis runs over the widest band only, offset by the band's first block.
    Every method is plain integer arithmetic that takes Python ints or traced
    scalars alike.
    """

    t: int
    s: int
    block_q: int
    block_k: int
    causal: bool
    window: int | None

    @property
    def masked(self) -> bool:
        """False when no pair is ever masked or skipped (the full rectangle)."""
        return self.causal or self.window is not None

    @property
    def num_qb(self) -> int:
        return self.t // self.block_q

    @property
    def num_kb(self) -> int:
        return self.s // self.block_k

    def kv_band(self, qi):
        """(first, last) key block holding a pair of query block ``qi``;
        ``last < first`` when none does. ``last`` is always a valid block."""
        first, last = 0, self.num_kb - 1
        if self.window is not None:
            first = _most(qi * self.block_q - self.window + 1, 0) // self.block_k
        if self.causal:
            last = _least((qi * self.block_q + self.block_q - 1) // self.block_k, last)
        return first, last

    def q_band(self, kb):
        """The mirror, for the dK/dV kernel: (first, last) query block holding
        a pair of key block ``kb``. ``first`` is always a valid block; ``last``
        is negative when a negative window empties the band."""
        first, last = 0, self.num_qb - 1
        if self.causal:
            first = (kb * self.block_k) // self.block_q
        if self.window is not None:
            last = _least((kb * self.block_k + self.block_k + self.window - 2) // self.block_q, last)
        return first, last

    @functools.cached_property
    def kv_width(self) -> int:
        """Key blocks in the widest band: the forward and dQ grids' inner axis."""
        return max(1, max(last - first + 1 for first, last in map(self.kv_band, range(self.num_qb))))

    @functools.cached_property
    def q_width(self) -> int:
        return max(1, max(last - first + 1 for first, last in map(self.q_band, range(self.num_kb))))

    def interior(self, qi, kb):
        """True when no element of pair (``qi``, ``kb``) is masked: its body
        needs no :func:`_window_mask`."""
        inside = True
        if self.causal:
            inside = kb * self.block_k + self.block_k - 1 <= qi * self.block_q
        if self.window is not None:
            inside &= qi * self.block_q + self.block_q - 1 - kb * self.block_k < self.window
        return inside

    @staticmethod
    def _step(band, j):
        first, last = band
        blk = first + j
        return blk, blk <= last, _most(_least(blk, last), 0)

    def kv_step(self, qi, j):
        """Inner grid step ``j`` of query block ``qi``'s band: (the key block
        it stands for, whether the band holds it, the block its DMA asks for).
        The padded steps of a short band re-request the band's last block —
        Mosaic elides the DMA when consecutive steps map to the same block,
        saving the HBM traffic that ``pl.when`` alone would still copy and
        discard."""
        return self._step(self.kv_band(qi), j)

    def q_step(self, kb, j):
        """The mirror: step ``j`` of key block ``kb``'s band of query blocks."""
        return self._step(self.q_band(kb), j)


def _visit(plan: _BlockPlan, held, interior, body):
    """Run ``body(masked)`` for one grid step: not at all for a padded step,
    without the window mask for an interior pair, with it for a pair the
    mask's edge crosses. The conditions are scalars, so exactly one body runs."""
    if not plan.masked:
        body(False)
        return
    pl.when(held & interior)(lambda: body(False))
    pl.when(held & jnp.logical_not(interior))(lambda: body(True))


def _attn_kernel(
    q_ref, k_ref, v_ref, *rest, plan: _BlockPlan, sm_scale: float, with_segments: bool = False
):
    # Grid (B*H, T/block_q, plan.kv_width) — K/V STREAM through the innermost
    # grid axis, one step per key block of the query block's band, so VMEM
    # holds one [block_k, D] tile of each at a time (plus Mosaic's pipeline
    # double-buffer) regardless of sequence length. The online-softmax carry
    # (m, l, acc) lives in VMEM scratch, persisting across the band for a
    # fixed (bh, qi).
    #
    # q_ref: [1, block_q, D]; k_ref/v_ref: [1, block_k, D]; o_ref: [1, block_q, D];
    # optional lse_ref: [1, block_q, _LANES] — the FlashAttention-2 residual,
    # lane-broadcast (TPU tiling forbids (1, bq) blocks); scratch m/l are
    # lane-broadcast too, acc is [block_q, D] fp32.
    if with_segments:
        seg_q_ref, seg_kv_ref, *rest = rest
    else:
        seg_q_ref = seg_kv_ref = None
    o_ref, *rest = rest
    if len(rest) == 4:
        lse_ref, m_ref, l_ref, acc_ref = rest
    else:
        (m_ref, l_ref, acc_ref), lse_ref = rest, None
    qi = pl.program_id(1)
    j = pl.program_id(2)
    kb, held, _ = plan.kv_step(qi, j)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _accumulate(masked: bool):
        q = q_ref[0]  # [bq, D] — native dtype: bf16 operands keep the MXU fast
        k = k_ref[0]  # [bk, D]
        v = v_ref[0]
        s = (
            jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
            * sm_scale
        )  # [bq, bk] fp32
        if masked:
            s = _window_mask(s, plan, qi, kb)
        s = _segment_mask(s, seg_q_ref, seg_kv_ref)
        m_prev = m_ref[:, :1]  # [bq, 1]
        l_prev = l_ref[:, :1]
        blk_max = jnp.max(s, axis=-1, keepdims=True)  # [bq, 1]
        new_m = jnp.maximum(m_prev, blk_max)
        correction = jnp.exp(m_prev - new_m)
        p = jnp.exp(s - new_m)  # [bq, bk]
        if masked or with_segments:
            # a row fully masked within this visited block has s == new_m ==
            # _NEG_INF, making p == exp(0) == 1 per masked entry — zero it so
            # dead rows really keep l == 0 / out == 0 (not a mean of V). An
            # interior pair of an unpacked batch masks nothing: no dead rows
            p = jnp.where(blk_max > _NEG_INF / 2, p, 0.0)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_ref[...] = jnp.broadcast_to(new_m, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_prev * correction + jnp.sum(p, axis=-1, keepdims=True), l_ref.shape)
        acc_ref[...] = acc_ref[...] * correction + pv

    # only the key blocks of this query block's band are grid steps at all:
    # none past the diagonal (causal) or entirely older than the window
    # (window applies without causal too: the ring's behind-hops call with
    # causal=False and a shifted window)
    _visit(plan, held, plan.interior(qi, kb), _accumulate)

    @pl.when(j == plan.kv_width - 1)
    def _write():
        # dead rows (an empty band, or fully masked in every block actually
        # visited — both possible for windowed non-causal ring hops) keep
        # l == 0 thanks to the dead-row p-zeroing above: the tiny floor makes
        # their output 0 and their lse ~ -1e30 - 69 (FINITE, so the ring merge
        # weight underflows to exactly 0 and the backward's exp(s - lse) stays
        # finite); live rows always have l >~ 1, untouched
        l_safe = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l_safe[:, :1]).astype(o_ref.dtype)
        if lse_ref is not None:
            lse_ref[0] = m_ref[...] + jnp.log(l_safe)


def _dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
    plan: _BlockPlan, sm_scale: float, with_segments: bool = False
):
    if with_segments:
        seg_q_ref, seg_kv_ref, dq_ref, acc_ref = rest
    else:
        (dq_ref, acc_ref), seg_q_ref, seg_kv_ref = rest, None, None
    # Grid (B*H, T/block_q, plan.kv_width): the forward's band of K/V blocks
    # on the innermost grid axis (same VMEM-bounded layout); dq accumulates in
    # fp32 VMEM scratch across the band and is written once at its last step.
    # lse_ref/delta_ref: [1, block_q, _LANES], lane-broadcast per-row stats.
    qi = pl.program_id(1)
    j = pl.program_id(2)
    kb, held, _ = plan.kv_step(qi, j)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _accumulate(masked: bool):
        q = q_ref[0]  # [bq, D] — native dtype operands, fp32 accumulation
        do = do_ref[0]  # [bq, D]
        lse = lse_ref[0][:, :1]  # [bq, 1]
        delta = delta_ref[0][:, :1]  # [bq, 1]
        k = k_ref[0]  # [bk, D]
        v = v_ref[0]
        s = (
            jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
            * sm_scale
        )  # [bq, bk]
        if masked:
            s = _window_mask(s, plan, qi, kb)
        s = _segment_mask(s, seg_q_ref, seg_kv_ref)
        p = jnp.exp(s - lse)  # [bq, bk] fp32; masked entries underflow to 0
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        ds = (p * (dp - delta) * sm_scale).astype(k.dtype)
        acc_ref[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    _visit(plan, held, plan.interior(qi, kb), _accumulate)

    @pl.when(j == plan.kv_width - 1)
    def _write():
        dq_ref[0] = acc_ref[...].astype(dq_ref.dtype)


def _dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
    plan: _BlockPlan, sm_scale: float, with_segments: bool = False
):
    if with_segments:
        seg_q_ref, seg_kv_ref, dk_ref, dv_ref = rest
    else:
        (dk_ref, dv_ref), seg_q_ref, seg_kv_ref = rest, None, None
    # grid (B*H, S/block_k, plan.q_width): one KV block accumulates across
    # its band of q blocks on the innermost axis (dk/dv output blocks are
    # revisited — they stay resident in VMEM until kb advances). Q/dO/stats
    # stream per step, so VMEM use is O(block) regardless of sequence length.
    kb = pl.program_id(1)
    j = pl.program_id(2)
    qb, held, _ = plan.q_step(kb, j)

    @pl.when(j == 0)
    def _init():
        dk_ref[0] = jnp.zeros_like(dk_ref[0])
        dv_ref[0] = jnp.zeros_like(dv_ref[0])

    def _accumulate(masked: bool):
        k = k_ref[0]  # [bk, D] — native dtype operands, fp32 accumulation
        v = v_ref[0]
        q = q_ref[0]  # [bq, D]
        do = do_ref[0]
        lse = lse_ref[0][:, :1]  # [bq, 1]
        delta = delta_ref[0][:, :1]
        s = (
            jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
            * sm_scale
        )  # [bq, bk]
        if masked:
            s = _window_mask(s, plan, qb, kb)
        s = _segment_mask(s, seg_q_ref, seg_kv_ref)
        p = jnp.exp(s - lse)  # [bq, bk] fp32
        dv_ref[0] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        ).astype(dv_ref.dtype)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        ds = (p * (dp - delta) * sm_scale).astype(q.dtype)
        dk_ref[0] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        ).astype(dk_ref.dtype)

    # only q blocks that hold a pair are steps: none entirely above the
    # diagonal (causal — their p is all zero) or entirely past
    # k_last + window (windowed, causal or not)
    _visit(plan, held, plan.interior(qb, kb), _accumulate)


def _auto_block(requested: int, seq: int) -> int:
    """Largest block <= requested that divides ``seq`` (halving the request
    until it divides), so the large default blocks serve any seq len that is
    a multiple of 64 — e.g. a 640-token sequence gets 128-blocks instead of
    an error, and a 384-token one uses a single 384 block. Never shrinks
    below 64 (or below an explicit smaller request): a seq len not divisible
    by 64 still raises, instead of silently degrading to a tile too small
    for the MXU — pad upstream."""
    blk = min(requested, seq)
    floor = min(requested, 64)
    while blk > floor and seq % blk:
        blk //= 2
    return blk


#: the block shape of every call whose shapes no sweep covered
_DEFAULT_BLOCKS = (512, 1024)
#: (block_q, block_k) where a sweep on the v5e chose them (PERF.md section 6,
#: PR 27: each of the three kernels was fastest at this shape), keyed on what
#: the call can see: (head_dim, T, S, causal, window). Constants: nothing is
#: timed at run time.
_SWEPT_BLOCKS: dict[tuple, tuple[int, int]] = {
    (128, 8192, 8192, True, 4096): (1024, 1024),
}


def _plan_for(block_q, block_k, d, t, s, causal, window) -> _BlockPlan:
    """The block plan of one call's kernels: an explicit block wins, then the
    sweep's choice for exactly these shapes, then the default; each shrunk to
    divide its sequence."""
    swept = _SWEPT_BLOCKS.get((d, t, s, causal, window), _DEFAULT_BLOCKS)
    block_q = _auto_block(swept[0] if block_q is None else block_q, t)
    block_k = _auto_block(swept[1] if block_k is None else block_k, s)
    if t % block_q or s % block_k:
        raise ValueError(f"seq lens ({t}, {s}) must be multiples of block sizes ({block_q}, {block_k})")
    return _BlockPlan(t, s, block_q, block_k, causal, window)


def _reference_attention(
    q, k, v, causal: bool, sm_scale: float, window: int | None = None, segment_ids=None
):
    """Unfused GQA attention (fp32 softmax) — the numerical reference for tests."""
    b, t, h, d = q.shape
    s, kh = k.shape[1], k.shape[2]
    group = h // kh
    qg = q.reshape(b, t, kh, group, d)
    scores = jnp.einsum("btkgd,bskd->bkgts", qg, k).astype(jnp.float32) * sm_scale
    if causal or window is not None:
        mask = jnp.tril(jnp.ones((t, s), dtype=bool), k=s - t) if causal else jnp.ones((t, s), bool)
        if window is not None:
            dist = jnp.arange(t)[:, None] - jnp.arange(s)[None, :] + (s - t)
            mask = mask & (dist < window)
        scores = jnp.where(mask[None, None, None], scores, _NEG_INF)
    if segment_ids is not None:
        same = segment_ids[:, :, None] == segment_ids[:, None, :]  # [B, T, S], T == S
        scores = jnp.where(same[:, None, None], scores, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgts,bskd->btkgd", probs, v)
    return out.reshape(b, t, h, d)


def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    causal: bool = True,
    sm_scale: float | None = None,
    block_q: int | None = None,
    block_k: int | None = None,
    interpret: bool | None = None,
    return_lse: bool = False,
    window: int | None = None,
    segment_ids: jnp.ndarray | None = None,
    impl: str | None = None,
) -> jnp.ndarray | tuple[jnp.ndarray, jnp.ndarray]:
    """q: [B, T, H, D]; k/v: [B, S, KH, D] with H % KH == 0. Returns [B, T, H, D].

    ``window`` = W enables sliding-window attention (requires ``causal``):
    each query attends to itself and its W-1 predecessors
    (``q_pos - k_pos < W``, the Mistral convention). K/V blocks entirely
    older than the window are not grid steps at all, so compute and HBM
    traffic scale with O(T·W) instead of O(T²).

    ``segment_ids`` ([B, T] int32, requires T == S) masks cross-segment
    pairs for packed-sequence training; composes with ``causal`` and
    ``window``. The ids ride into the kernels lane-/sublane-broadcast
    (extra ~(128+8)·4 bytes/token of HBM), and fully-masked rows follow
    the same lse-floor self-healing as windowed calls.

    Sequence lengths must be multiples of the block sizes (pad upstream);
    block sizes auto-shrink for short sequences. Differentiable end-to-end in
    Pallas: the forward saves only O and the per-row logsumexp, and the
    backward recomputes probabilities flash-style in two kernels (dQ;
    dK/dV) — activations never materialise in HBM.

    ``impl`` picks the lowering: ``"pallas"`` (the TPU kernels; off-TPU it
    raises unless ``interpret`` says whether to emulate or to lower them) or
    ``"xla"`` (the same blockwise algorithm as plain XLA ops — the off-TPU
    default, since interpret mode loses to the unfused path; see the module
    docstring). ``None`` auto-selects, except an explicit ``interpret`` pins
    ``"pallas"``.

    Default Pallas blocks are large (512x1024) because the grid-step
    overhead, not VMEM, is the binding constraint on TPU: 256-wide blocks
    lose most in the v5e sweep of PERF.md section 6 (PR 27). Where a sweep
    on the v5e covered exactly this call's shapes, the kernels take the
    sweep's choice instead (``_SWEPT_BLOCKS``); an explicit ``block_q`` /
    ``block_k`` wins over both. The XLA path defaults to
    128-row query blocks (block_k is ignored there: each query block reads
    its causally/window-truncated K slice in one piece).

    With ``return_lse=True`` returns ``(out, lse)`` where ``lse`` is the
    per-row logsumexp of the scaled scores, shape [B, T, H] — the residual a
    blockwise/ring combiner needs to merge partial attention outputs. This
    path is differentiable in BOTH outputs (the lse cotangent folds into the
    backward kernels' delta term, since d lse/d s = p).
    """
    b, t, h, d = q.shape
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    if impl is None:
        mode = _default_mode(interpret)
    elif impl == "xla":
        mode = "xla"
    elif impl == "pallas":
        if interpret is None and jax.default_backend() != "tpu":
            raise ValueError(
                f'impl="pallas" on the {jax.default_backend()!r} backend needs an explicit interpret=: '
                "True emulates the kernels (slow; kernel-logic tests), False lowers them for a TPU "
                "(ahead-of-time compiles for a described chip)"
            )
        mode = bool(interpret)
    else:
        raise ValueError(f"impl must be 'pallas', 'xla' or None, got {impl!r}")
    if causal and t != k.shape[1]:
        # the kernels mask with top-left alignment (q_pos >= k_pos); a
        # KV-cache-style bottom-right alignment for T != S is a different
        # mask — reject instead of silently attending to the wrong keys
        raise ValueError(
            f"causal flash attention requires equal Q/KV sequence lengths, got {t} != {k.shape[1]}"
        )
    if window is not None:
        if not causal:
            raise ValueError("window (sliding-window attention) requires causal=True")
        if window < 1:
            raise ValueError(f"window must be >= 1, got {window}")
        window = int(window)
    if segment_ids is not None:
        segment_ids = jnp.asarray(segment_ids, jnp.int32)
        if segment_ids.shape != (b, t):
            raise ValueError(f"segment_ids must be [B, T] == {(b, t)}, got {segment_ids.shape}")
        if t != k.shape[1]:
            raise ValueError("segment_ids require equal Q/KV sequence lengths (self-attention packing)")
    if mode == "xla":
        block_q = _auto_block(_XLA_BLOCK_Q if block_q is None else block_q, t)
    # the Pallas impls resolve a block left None themselves (_plan_for)
    if return_lse:
        out, lse = _flash_lse(q, k, v, segment_ids, causal, float(sm_scale), block_q, block_k, mode, window)
        return out, lse.reshape(b, h, t).transpose(0, 2, 1)  # [B, T, H]
    return _flash(q, k, v, segment_ids, causal, float(sm_scale), block_q, block_k, mode, window)


def dividing_batch_axes(mesh, batch_size: int) -> tuple | None:
    """The data axes of ``mesh`` (``data``, then ``fsdp``) that divide a
    batch dimension, as a PartitionSpec entry — None when none does, so a
    batch too small for them (module.init's example input) stays replicated.
    Shared by the two attention ops that shard_map themselves."""
    axes, rem = [], batch_size
    for a in ("data", "fsdp"):
        if a in mesh.axis_names and rem % mesh.shape[a] == 0:
            axes.append(a)
            rem //= mesh.shape[a]
    return tuple(axes) or None


def flash_attention_sharded(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    mesh,
    *,
    head_axis: str = "model",
    segment_ids: jnp.ndarray | None = None,
    **kwargs,
) -> jnp.ndarray:
    """:func:`flash_attention` callable under plain jit on a multi-device
    mesh. XLA cannot partition a Mosaic kernel ("Mosaic kernels cannot be
    automatically partitioned"), so the call shard_maps itself over
    ``mesh``: batch on the data axes and heads on ``head_axis``, each where
    it divides (module.init's size-1 example batch stays replicated).
    Attention is independent per batch row and per KV-head group, so every
    shard runs the unchanged kernel on its slice — with heads laid out as the
    q/k/v projection rules leave them, no collective is added. The sequence
    stays whole on each device; splitting it is ``ring_attention``'s job.
    Keyword arguments are :func:`flash_attention`'s (without ``return_lse``)."""
    from jax.sharding import PartitionSpec as P

    batch = dividing_batch_axes(mesh, q.shape[0])
    heads = head_axis if head_axis in mesh.axis_names and k.shape[2] % mesh.shape[head_axis] == 0 else None
    spec = P(batch, None, heads, None)
    if segment_ids is None:
        fn = lambda q, k, v: flash_attention(q, k, v, **kwargs)
        args, in_specs = (q, k, v), (spec, spec, spec)
    else:
        fn = lambda q, k, v, seg: flash_attention(q, k, v, segment_ids=seg, **kwargs)
        args, in_specs = (q, k, v, jnp.asarray(segment_ids, jnp.int32)), (spec, spec, spec, P(batch, None))
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs, out_specs=spec, check_vma=False)(*args)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _flash(q, k, v, seg, causal, sm_scale, block_q, block_k, mode, window):
    # ``mode`` is the static lowering selector: False/True run the Pallas
    # kernels (compiled/interpreted), "xla" the blockwise-XLA twin
    return _flash_fwd_impl(q, k, v, causal, sm_scale, block_q, block_k, mode, window, seg)


def _flash_vjp_fwd(q, k, v, seg, causal, sm_scale, block_q, block_k, mode, window):
    out, lse = _flash_fwd_impl(
        q, k, v, causal, sm_scale, block_q, block_k, mode, window, seg, with_residuals=True
    )
    return out, (q, k, v, seg, out, lse)


def _flash_vjp_bwd(causal, sm_scale, block_q, block_k, mode, window, residuals, g):
    q, k, v, seg, out, lse = residuals
    dq, dk, dv = _flash_bwd_impl(
        q, k, v, out, lse, g, causal, sm_scale, block_q, block_k, mode, window, seg
    )
    return dq, dk, dv, None  # integer segment ids carry no cotangent


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _flash_lse(q, k, v, seg, causal, sm_scale, block_q, block_k, mode, window):
    """(out, lse[B*H, T]) variant for blockwise/ring combiners."""
    return _flash_fwd_impl(
        q, k, v, causal, sm_scale, block_q, block_k, mode, window, seg, with_residuals=True
    )


def _flash_lse_vjp_fwd(q, k, v, seg, causal, sm_scale, block_q, block_k, mode, window):
    out, lse = _flash_fwd_impl(
        q, k, v, causal, sm_scale, block_q, block_k, mode, window, seg, with_residuals=True
    )
    return (out, lse), (q, k, v, seg, out, lse)


def _flash_lse_vjp_bwd(causal, sm_scale, block_q, block_k, mode, window, residuals, gs):
    g_out, g_lse = gs
    q, k, v, seg, out, lse = residuals
    # d lse_i / d s_ij = p_ij, so the lse cotangent enters the existing
    # backward as ds += p * g_lse — algebraically a shift of the delta term:
    # ds = p * (dp - (delta - g_lse)). Zero kernel changes needed.
    dq, dk, dv = _flash_bwd_impl(
        q, k, v, out, lse, g_out, causal, sm_scale, block_q, block_k, mode, window, seg,
        lse_cotangent=g_lse,
    )
    return dq, dk, dv, None


_flash_lse.defvjp(_flash_lse_vjp_fwd, _flash_lse_vjp_bwd)


def _fold_heads(x):
    """[B, T, H, D] -> [B*H, T, D] (grid leading axis = one (batch, head))."""
    b, t, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, t, d)


def _make_kv_index(h: int, kh: int):
    """Block index map folding a query head onto its KV head (GQA) — shared
    by the forward and both backward pallas_calls so the folding can never
    desynchronise."""
    group = h // kh

    def kv_index(bh, *_):
        return (bh // h) * kh + (bh % h) // group

    return kv_index


def _seg_layouts(seg, b, t, s):
    """[B, T] segment ids -> the two kernel layouts (see _SUBLANES note)."""
    seg = jnp.asarray(seg, jnp.int32)
    seg_q3 = jnp.broadcast_to(seg[:, :, None], (b, t, _LANES))
    seg_kv3 = jnp.broadcast_to(seg[:, None, :], (b, _SUBLANES, s))
    return seg_q3, seg_kv3


def _xla_bounds(q0: int, block_q: int, s: int, causal: bool, window: int | None):
    """Static K-range [lo, hi) a query block [q0, q0+block_q) can attend to —
    the XLA path's analogue of the kernels' grid skipping (causal prunes
    everything past the diagonal block, a window everything older than the
    FIRST row's reach; a negative ring-shifted window can empty the range)."""
    hi = min(s, q0 + block_q) if causal else s
    lo = 0
    if window is not None:
        lo = max(0, q0 - window + 1)
    return min(lo, hi), hi


def _xla_keep(q0, block_q, lo, hi, causal, window, seg):
    """Boolean keep-mask [1 or B, block_q, hi-lo] for one query block, or
    None when nothing is masked. Mirrors _window_mask/_segment_mask."""
    keep = None
    if causal or window is not None:
        q_pos = q0 + jnp.arange(block_q)[:, None]
        k_pos = lo + jnp.arange(hi - lo)[None, :]
        if causal:
            keep = q_pos >= k_pos
        if window is not None:
            wkeep = (q_pos - k_pos) < window
            keep = wkeep if keep is None else keep & wkeep
        keep = keep[None]
    if seg is not None:
        same = (
            jax.lax.slice_in_dim(seg, q0, q0 + block_q, axis=1)[:, :, None]
            == jax.lax.slice_in_dim(seg, lo, hi, axis=1)[:, None, :]
        )
        keep = same if keep is None else keep & same
    return keep


def _xla_fwd(q, k, v, causal, sm_scale, block_q, window=None, seg=None, with_residuals=False):
    """Blockwise flash attention as plain XLA ops (the off-TPU lowering):
    a static loop over query blocks, each reading only its causally/window-
    truncated K/V slice. Same GQA einsum grouping as the reference (K/V are
    never materialised per query head), same dead-row self-healing and LSE
    residual semantics as the kernels."""
    b, t, h, d = q.shape
    s, kh = k.shape[1], k.shape[2]
    if h % kh:
        raise ValueError(f"query heads {h} not a multiple of kv heads {kh}")
    if t % block_q:
        raise ValueError(f"seq len {t} must be a multiple of block size {block_q}")
    group = h // kh
    qf = q.reshape(b, t, kh, group, d)
    outs, lses = [], []
    for q0 in range(0, t, block_q):
        lo, hi = _xla_bounds(q0, block_q, s, causal, window)
        if lo >= hi:  # fully dead block (ring hop outside the window)
            outs.append(jnp.zeros((b, block_q, h, d), q.dtype))
            lses.append(jnp.full((b, block_q, h), _NEG_INF + math.log(1e-30), jnp.float32))
            continue
        qb = jax.lax.slice_in_dim(qf, q0, q0 + block_q, axis=1)
        kb = jax.lax.slice_in_dim(k, lo, hi, axis=1)
        vb = jax.lax.slice_in_dim(v, lo, hi, axis=1)
        sc = (
            jnp.einsum("btkgd,bskd->bkgts", qb, kb, preferred_element_type=jnp.float32)
            * sm_scale
        )  # [B, KH, G, bq, hi-lo] fp32
        keep = _xla_keep(q0, block_q, lo, hi, causal, window, seg)
        if keep is not None:
            sc = jnp.where(keep[:, None, None], sc, _NEG_INF)
        m = jnp.max(sc, axis=-1)  # [B, KH, G, bq]
        p = jnp.exp(sc - m[..., None])
        # dead rows (fully masked): zero p so out == 0, matching the kernels
        p = jnp.where((m > _NEG_INF / 2)[..., None], p, 0.0)
        l_safe = jnp.maximum(jnp.sum(p, axis=-1), 1e-30)
        o = jnp.einsum(
            "bkgts,bskd->btkgd", (p / l_safe[..., None]).astype(v.dtype), vb
        )
        outs.append(o.reshape(b, block_q, h, d).astype(q.dtype))
        if with_residuals:
            lses.append((m + jnp.log(l_safe)).transpose(0, 3, 1, 2).reshape(b, block_q, h))
    out = jnp.concatenate(outs, axis=1) if len(outs) > 1 else outs[0]
    if with_residuals:
        lse = jnp.concatenate(lses, axis=1) if len(lses) > 1 else lses[0]
        return out, lse.transpose(0, 2, 1).reshape(b * h, t)  # kernel residual layout
    return out


def _xla_bwd(
    q, k, v, out, lse, g, causal, sm_scale, block_q, window=None, seg=None, lse_cotangent=None
):
    """Backward of the XLA path: per query block, recompute the probabilities
    from the saved LSE (never a forward replay), then the standard
    dq/dk/dv flash formulas with dk/dv accumulated into their static K
    slices. fp32 accumulation, operands in the input dtype — mirrors the
    Pallas backward kernels' dataflow."""
    b, t, h, d = q.shape
    s, kh = k.shape[1], k.shape[2]
    group = h // kh
    # delta_i = rowsum(dO_i * O_i); an lse cotangent folds in as a shift
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)  # [B, T, H]
    if lse_cotangent is not None:
        delta = delta - lse_cotangent.astype(jnp.float32).reshape(b, h, t).transpose(0, 2, 1)
    lse_bth = lse.reshape(b, h, t).transpose(0, 2, 1)  # [B, T, H]
    qf = q.reshape(b, t, kh, group, d)
    gf = g.reshape(b, t, kh, group, d)
    dq_blocks = []
    dk = jnp.zeros((b, s, kh, d), jnp.float32)
    dv = jnp.zeros((b, s, kh, d), jnp.float32)
    for q0 in range(0, t, block_q):
        lo, hi = _xla_bounds(q0, block_q, s, causal, window)
        if lo >= hi:
            dq_blocks.append(jnp.zeros((b, block_q, h, d), q.dtype))
            continue
        qb = jax.lax.slice_in_dim(qf, q0, q0 + block_q, axis=1)
        dob = jax.lax.slice_in_dim(gf, q0, q0 + block_q, axis=1)
        kb = jax.lax.slice_in_dim(k, lo, hi, axis=1)
        vb = jax.lax.slice_in_dim(v, lo, hi, axis=1)
        to_kg = lambda x: x.transpose(0, 2, 3, 1)  # [B,bq,KH,G] -> [B,KH,G,bq]
        lse_b = to_kg(
            jax.lax.slice_in_dim(lse_bth, q0, q0 + block_q, axis=1).reshape(b, block_q, kh, group)
        )
        delta_b = to_kg(
            jax.lax.slice_in_dim(delta, q0, q0 + block_q, axis=1).reshape(b, block_q, kh, group)
        )
        sc = (
            jnp.einsum("btkgd,bskd->bkgts", qb, kb, preferred_element_type=jnp.float32)
            * sm_scale
        )
        keep = _xla_keep(q0, block_q, lo, hi, causal, window, seg)
        if keep is not None:
            sc = jnp.where(keep[:, None, None], sc, _NEG_INF)
        p = jnp.exp(sc - lse_b[..., None])  # masked entries underflow to 0
        dp = jnp.einsum("btkgd,bskd->bkgts", dob, vb, preferred_element_type=jnp.float32)
        ds = (p * (dp - delta_b[..., None]) * sm_scale).astype(k.dtype)
        dqb = jnp.einsum("bkgts,bskd->btkgd", ds, kb, preferred_element_type=jnp.float32)
        dq_blocks.append(dqb.reshape(b, block_q, h, d).astype(q.dtype))
        # group (GQA) summation happens inside the einsum contraction
        dk = dk.at[:, lo:hi].add(
            jnp.einsum("bkgts,btkgd->bskd", ds, qb, preferred_element_type=jnp.float32)
        )
        dv = dv.at[:, lo:hi].add(
            jnp.einsum("bkgts,btkgd->bskd", p.astype(g.dtype), dob, preferred_element_type=jnp.float32)
        )
    dq = jnp.concatenate(dq_blocks, axis=1) if len(dq_blocks) > 1 else dq_blocks[0]
    return dq, dk.astype(k.dtype), dv.astype(v.dtype)


# The two impls are jitted for set-up's sake, not for speed: under an outer
# jit the call is inlined all the same, but jit keeps the traced kernels, so a
# step that calls the op once a layer — and is traced twice before it runs,
# by the stage's eval_shape and by .lower() — traces and lowers each kernel
# once, not once a layer and a trace (PERF.md, PR 27: the set-up split).
@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6, 7, 8), static_argnames=("with_residuals",))
def _flash_fwd_impl(
    q, k, v, causal, sm_scale, block_q, block_k, mode, window=None, seg=None, with_residuals=False
):
    if mode == "xla":
        return _xla_fwd(q, k, v, causal, sm_scale, block_q, window, seg, with_residuals)
    interpret = bool(mode)
    if _VMEM is None:
        raise RuntimeError(
            "flash_attention needs jax.experimental.pallas.tpu (VMEM scratch accumulators); "
            "it failed to import in this jax build"
        )
    if q.shape[2] % k.shape[2]:
        raise ValueError(f"query heads {q.shape[2]} not a multiple of kv heads {k.shape[2]}")
    b, t, h, d = q.shape
    s, kh = k.shape[1], k.shape[2]
    plan = _plan_for(block_q, block_k, d, t, s, causal, window)
    block_q, block_k = plan.block_q, plan.block_k

    qt = _fold_heads(q)
    kt = _fold_heads(k)
    vt = _fold_heads(v)
    kv_index = _make_kv_index(h, kh)
    vmem = {"memory_space": _VMEM}

    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda bh, qi, j: (bh, qi, 0), **vmem),
        pl.BlockSpec((1, block_k, d), lambda bh, qi, j: (kv_index(bh), plan.kv_step(qi, j)[2], 0), **vmem),
        pl.BlockSpec((1, block_k, d), lambda bh, qi, j: (kv_index(bh), plan.kv_step(qi, j)[2], 0), **vmem),
    ]
    operands = [qt, kt, vt]
    if seg is not None:
        seg_q3, seg_kv3 = _seg_layouts(seg, b, t, s)
        in_specs.append(pl.BlockSpec((1, block_q, _LANES), lambda bh, qi, j: (bh // h, qi, 0), **vmem))
        in_specs.append(
            pl.BlockSpec((1, _SUBLANES, block_k), lambda bh, qi, j: (bh // h, 0, plan.kv_step(qi, j)[2]), **vmem)
        )
        operands += [seg_q3, seg_kv3]

    out_shape = [jax.ShapeDtypeStruct((b * h, t, d), q.dtype)]
    out_specs = [pl.BlockSpec((1, block_q, d), lambda bh, qi, j: (bh, qi, 0), **vmem)]
    if with_residuals:
        out_shape.append(jax.ShapeDtypeStruct((b * h, t, _LANES), jnp.float32))
        out_specs.append(pl.BlockSpec((1, block_q, _LANES), lambda bh, qi, j: (bh, qi, 0), **vmem))
    results = pl.pallas_call(
        functools.partial(_attn_kernel, plan=plan, sm_scale=sm_scale, with_segments=seg is not None),
        out_shape=out_shape,
        grid=(b * h, plan.num_qb, plan.kv_width),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),  # running max m
            pltpu.VMEM((block_q, _LANES), jnp.float32),  # running denominator l
            pltpu.VMEM((block_q, d), jnp.float32),  # output accumulator
        ],
        interpret=interpret,
        name="flash_fwd",
    )(*operands)

    out = results[0].reshape(b, h, t, d).transpose(0, 2, 1, 3)
    if with_residuals:
        # slim the residual to [B*H, T]: the lane-broadcast copy need not
        # live for the whole backward graph
        return out, results[1][:, :, 0]
    return out


@functools.partial(jax.jit, static_argnums=(6, 7, 8, 9, 10, 11))
def _flash_bwd_impl(
    q, k, v, out, lse, g, causal, sm_scale, block_q, block_k, mode, window=None, seg=None,
    lse_cotangent=None,
):
    if mode == "xla":
        return _xla_bwd(q, k, v, out, lse, g, causal, sm_scale, block_q, window, seg, lse_cotangent)
    interpret = bool(mode)
    b, t, h, d = q.shape
    s, kh = k.shape[1], k.shape[2]
    group = h // kh

    qt = _fold_heads(q)
    kt = _fold_heads(k)
    vt = _fold_heads(v)
    dot = _fold_heads(g)
    ot = _fold_heads(out)
    # delta_i = rowsum(dO_i * O_i) — the softmax-jacobian diagonal term;
    # stats enter the kernels lane-broadcast ([B*H, T, _LANES], TPU tiling)
    delta = jnp.sum(dot.astype(jnp.float32) * ot.astype(jnp.float32), axis=-1)  # [B*H, T]
    if lse_cotangent is not None:
        # lse's own cotangent folds in as a delta shift (see _flash_lse_vjp_bwd)
        delta = delta - lse_cotangent.astype(jnp.float32)
    delta3 = jnp.broadcast_to(delta[:, :, None], (b * h, t, _LANES))
    lse3 = jnp.broadcast_to(lse[:, :, None], (b * h, t, _LANES))
    operands = [qt, kt, vt, dot, lse3, delta3]
    if seg is not None:
        operands += _seg_layouts(seg, b, t, s)
    kv_index = _make_kv_index(h, kh)
    vmem = {"memory_space": _VMEM}

    plan = _plan_for(block_q, block_k, d, t, s, causal, window)
    bq, bk = plan.block_q, plan.block_k

    q_rows = lambda bh, qi, j: (bh, qi, 0)
    kv_rows = lambda bh, qi, j: (kv_index(bh), plan.kv_step(qi, j)[2], 0)
    dq_in_specs = [
        pl.BlockSpec((1, bq, d), q_rows, **vmem),  # q
        pl.BlockSpec((1, bk, d), kv_rows, **vmem),  # k
        pl.BlockSpec((1, bk, d), kv_rows, **vmem),  # v
        pl.BlockSpec((1, bq, d), q_rows, **vmem),  # dO
        pl.BlockSpec((1, bq, _LANES), q_rows, **vmem),  # lse
        pl.BlockSpec((1, bq, _LANES), q_rows, **vmem),  # delta
    ]
    if seg is not None:
        dq_in_specs.append(pl.BlockSpec((1, bq, _LANES), lambda bh, qi, j: (bh // h, qi, 0), **vmem))
        dq_in_specs.append(
            pl.BlockSpec((1, _SUBLANES, bk), lambda bh, qi, j: (bh // h, 0, plan.kv_step(qi, j)[2]), **vmem)
        )
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, plan=plan, sm_scale=sm_scale, with_segments=seg is not None),
        out_shape=jax.ShapeDtypeStruct((b * h, t, d), q.dtype),
        grid=(b * h, plan.num_qb, plan.kv_width),
        in_specs=dq_in_specs,
        out_specs=pl.BlockSpec((1, bq, d), q_rows, **vmem),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],  # dq accumulator
        interpret=interpret,
        name="flash_bwd_dq",
    )(*operands)

    # per-query-head dK/dV; group-summed below for GQA. 3D grid: the q-block
    # axis is innermost so dk/dv output blocks accumulate in VMEM.
    q_rows = lambda bh, kb, j: (bh, plan.q_step(kb, j)[2], 0)
    kv_rows = lambda bh, kb, j: (kv_index(bh), kb, 0)
    dkv_in_specs = [
        pl.BlockSpec((1, bq, d), q_rows, **vmem),  # q
        pl.BlockSpec((1, bk, d), kv_rows, **vmem),  # k
        pl.BlockSpec((1, bk, d), kv_rows, **vmem),  # v
        pl.BlockSpec((1, bq, d), q_rows, **vmem),  # dO
        pl.BlockSpec((1, bq, _LANES), q_rows, **vmem),  # lse
        pl.BlockSpec((1, bq, _LANES), q_rows, **vmem),  # delta
    ]
    if seg is not None:
        dkv_in_specs.append(
            pl.BlockSpec((1, bq, _LANES), lambda bh, kb, j: (bh // h, plan.q_step(kb, j)[2], 0), **vmem)
        )
        dkv_in_specs.append(pl.BlockSpec((1, _SUBLANES, bk), lambda bh, kb, j: (bh // h, 0, kb), **vmem))
    dk_h, dv_h = pl.pallas_call(
        functools.partial(_dkv_kernel, plan=plan, sm_scale=sm_scale, with_segments=seg is not None),
        out_shape=[
            jax.ShapeDtypeStruct((b * h, s, d), jnp.float32),
            jax.ShapeDtypeStruct((b * h, s, d), jnp.float32),
        ],
        grid=(b * h, plan.num_kb, plan.q_width),
        in_specs=dkv_in_specs,
        out_specs=[
            pl.BlockSpec((1, bk, d), lambda bh, kb, j: (bh, kb, 0), **vmem),
            pl.BlockSpec((1, bk, d), lambda bh, kb, j: (bh, kb, 0), **vmem),
        ],
        interpret=interpret,
        name="flash_bwd_dkv",
    )(*operands)

    dq = dq.reshape(b, h, t, d).transpose(0, 2, 1, 3)
    dk = dk_h.reshape(b, kh, group, s, d).sum(axis=2).transpose(0, 2, 1, 3).astype(k.dtype)
    dv = dv_h.reshape(b, kh, group, s, d).sum(axis=2).transpose(0, 2, 1, 3).astype(v.dtype)
    return dq, dk, dv
