"""Manifold-constrained hyper-connections (mHC, arXiv:2512.24880) over a stream
of ``n`` streams held lane-flat, ``X [B, T, n C]``: stream ``j`` is lanes
``[j C, (j + 1) C)``. Each sublayer reads the stream through :func:`mhc_pre` and
writes it back through :func:`mhc_merge`:

    u = vec(X) / rms(vec(X))                      (over all n C values, no learned scale)
    H_pre  = sigmoid(a_pre u phi_pre + b_pre)                   (n)
    H_post = 2 sigmoid(a_post u phi_post + b_post)              (n)
    H_res  = SinkhornKnopp(exp(clamp(a_res mat(u phi_res) + b_res)))   (n x n)
    x_in   = H_pre X                              (the sublayer's input, [B, T, C])
    X'     = H_res X + H_post^T F                 (F: the sublayer's output)

The maps are float32, the stream bf16, the ``phi`` product takes bf16 operands
and accumulates in float32; Sinkhorn runs all its rounds. The same on both paths.

**Which path runs where** (chosen at trace time, from what the call observes):
on the TPU (``jax.default_backend() == "tpu"``), where ``C`` is whole lane
tiles (a multiple of 128), the tokens ``B T`` are 128 or more in whole token
tiles, and the trace is one device's (the process sees one device, or the model
names a mesh of one), four Pallas kernels with a hand-written backward:
``mhc_pre``, ``mhc_pre_bwd``, ``mhc_merge``, ``mhc_merge_bwd``. Every other call
(the CPU, ``HyperConnection.init``'s 8-token example, a mesh of several devices,
several devices with no mesh named: XLA cannot partition a Pallas call) runs the
plain ``jax.numpy`` form, which is also what the kernels are tested against. A
TPU call of 128 tokens or more that falls to the plain form says so once in the
log. Each call journals an ``mhc`` record (path, stream shape, why the plain
form) and :func:`recording` collects the same for a caller's log line.

**What the kernels hold in VMEM.** Each walks a grid of token tiles, a tile's
``n C`` stream rows read once. ``mhc_pre`` forms the tile's float32 sum of
squares, the product ``phi^T X^T`` (``[2n + n^2, tokens]``: the maps with the
tokens on lanes), the sigmoids and Sinkhorn's rounds on ``n`` blocks of
``[n, tokens]``, and ``x_in``; it writes ``x_in``, ``H_post`` / ``H_res`` a token
(``[tokens, n + n^2]``), the raw product and ``1 / rms`` (the backward's
residual, a few dozen numbers a token) and each token's largest row gap.
``mhc_merge`` reads ``X`` and ``F`` once and writes ``X'`` once.
``mhc_merge_bwd`` gives ``dX``, ``dF`` and the maps' gradients in one pass;
``mhc_pre_bwd`` gives ``dX`` with the norm's term and the product's, re-runs
Sinkhorn's rounds from the saved product to take their backward, and sums the
gradients of ``phi``, the scales and the biases over the token grid in float32
accumulators that stay in VMEM until the last tile. The streams' ``dX`` of the
two sublayer ends are added by XLA.
"""

from __future__ import annotations

import contextlib
import contextvars
import functools
import logging
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..telemetry import journal as _journal

try:  # as flash_attention.py: without pltpu the kernels cannot be built and every call takes the plain form
    from jax.experimental.pallas import tpu as pltpu
except Exception:  # pragma: no cover
    pltpu = None

logger = logging.getLogger(__name__)

#: a stream's channels are walked in lane chunks of at most this many
_CHUNK = 512
#: the token tiles the kernels take, largest first: the largest whose blocks fit the VMEM budget runs
_TILES = (256, 128, 64, 32, 16)
#: bytes of a kernel's blocks and chunk temporaries: under the 16 MiB Mosaic gives a kernel without asking
_VMEM_BUDGET = 14 * 2**20


class _Tiles(NamedTuple):
    """Where the kernels run: the pre kernels' token tile, the merge kernels', and whether they run in the Pallas
    interpreter (the tests, on the CPU)."""

    pre: int
    merge: int
    interpret: bool = False


class _Spec(NamedTuple):
    """The static part of a call: streams, Sinkhorn's rounds and ``eps``, the norm's ``eps``, the clamp, the
    token tile, and whether the kernels run in the Pallas interpreter (the tests, on the CPU)."""

    n: int
    iters: int = 20
    eps: float = 1e-6
    norm_eps: float = 1e-6
    lo: float = -30.0
    hi: float = 30.0
    tile: int = 0
    interpret: bool = False


# ------------------------------------------------------------------ the entry points


def mhc_pre(stream, phi, bias, alpha, n: int, *, iters: int, eps: float, norm_eps: float, sinkhorn, clamp=(-30.0, 30.0),
            mesh=None):
    """``stream [B, T, n C]`` (bf16), ``phi [n C, 2n + n^2]``, ``bias [2n + n^2]``, ``alpha [3]`` (float32) ->
    ``(x_in [B, T, C]`` in the stream's dtype, ``post [B, T, n]``, ``res [B, T, n, n]``, ``row_sum_gap)``: the
    largest ``|sum_j H_res[i, j] - 1|`` over the tokens, a reading (no gradient). ``mesh`` is the model's (None: the
    process's devices); ``sinkhorn(m, iters, eps)`` is the plain form's rounds (the model's
    ``sinkhorn_knopp``; the kernels run the same rounds in VMEM). On the TPU, where ``C`` is a multiple of 128, the
    tokens 128 or more in whole token tiles and the trace one device's, the kernels ``mhc_pre`` / ``mhc_pre_bwd``;
    everywhere else the plain ``jax.numpy`` form. Journals an ``mhc`` record of the path taken."""
    spec = _Spec(n, iters, eps, norm_eps, float(clamp[0]), float(clamp[1]))
    with jax.named_scope("mhc"):
        tiles, why = _plan(stream.shape, stream.dtype, n, mesh)
        _record("kernels" if tiles else "plain", stream.shape, why)
        if not tiles:
            return _pre_plain(stream, phi, bias, alpha, spec, sinkhorn)
        return _pre_kernels(stream, phi, bias, alpha, spec._replace(tile=tiles.pre, interpret=tiles.interpret))


def mhc_merge(stream, out, post, res, *, mesh=None):
    """``X <- H_res X + H_post^T out`` a token: ``stream [B, T, n C]``, ``out [B, T, C]`` (both in the stream's
    dtype), ``post [B, T, n]``, ``res [B, T, n, n]`` (float32) -> the new stream, lane-flat. The kernels
    ``mhc_merge`` / ``mhc_merge_bwd`` where :func:`mhc_pre`'s would run, else the plain form."""
    n = post.shape[-1]
    with jax.named_scope("mhc"):
        tiles, _ = _plan(stream.shape, stream.dtype, n, mesh)
        if not tiles:
            return _merge_plain(stream, out, post, res)
        return _merge_kernels(stream, out, post, res, tiles.merge, tiles.interpret)


# ------------------------------------------------------------------ which path, and what it says


_recorder: contextvars.ContextVar[list | None] = contextvars.ContextVar("mhc_recorder", default=None)


@contextlib.contextmanager
def recording():
    """Yields a list that collects ``(path, stream shape, why)`` of every :func:`mhc_pre` traced inside."""
    seen: list = []
    token = _recorder.set(seen)
    try:
        yield seen
    finally:
        _recorder.reset(token)


def summary(seen: list) -> str:
    """What a list of :func:`recording` says, for a log line: ``"12 kernels [1, 8192, 14336]"`` and the like."""
    counts: dict = {}
    for path, shape, why in seen:
        key = f"{path} {list(shape)}" + (f" ({why})" if why else "")
        counts[key] = counts.get(key, 0) + 1
    return ", ".join(f"{k} x {v}" for k, v in counts.items())


def _record(path: str, shape, why: str | None) -> None:
    t = _journal.now()
    _journal.emit("mhc", t, t, path=path, shape=list(shape), why=why)
    seen = _recorder.get()
    if seen is not None:
        seen.append((path, tuple(shape), why))


@functools.lru_cache(maxsize=None)
def _say_once(message: str) -> None:
    logger.warning(message)


def _plan(shape, dtype, n: int, mesh) -> tuple[_Tiles | None, str | None]:
    """``(tiles, None)`` where the kernels run, else ``(None, why)``."""
    b, t, k = shape
    c, tokens = k // n, b * t
    if jax.default_backend() != "tpu":
        return None, f"backend {jax.default_backend()}"
    if pltpu is None:  # pragma: no cover
        return None, "no Pallas TPU"
    if tokens < 128:  # HyperConnection.init's example: nothing to say
        return None, f"{tokens} tokens"
    devices = mesh.size if mesh is not None else jax.device_count()
    why = None
    if c % 128:
        why = f"C {c} is not whole lane tiles of 128"
    elif devices != 1:
        why = f"traced for {devices} devices, where XLA cannot partition a Pallas call"
    size = jnp.dtype(dtype).itemsize
    tiles = None if why else _Tiles(_tile(tokens, lambda tm: _pre_bytes(tm, n, c, size)),
                                    _tile(tokens, lambda tm: _merge_bytes(tm, n, c, size)))
    if not why and not (tiles.pre and tiles.merge):
        why = f"{tokens} tokens make no token tile whose blocks fit VMEM"
    if why:
        _say_once(f"mhc: stream {list(shape)}: {why}: the plain form runs")
        return None, why
    return tiles, None


def _tile(tokens: int, need) -> int:
    return next((tm for tm in _TILES if tokens % tm == 0 and need(tm) <= _VMEM_BUDGET), 0)


def _f(n: int) -> int:
    return 2 * n + n * n


def _pre_bytes(tm: int, n: int, c: int, size: int) -> int:
    """VMEM of ``mhc_pre_bwd``, the larger of the two pre kernels: double-buffered blocks of the stream, its
    gradient, ``dx_in``, ``phi`` (bf16, 16 rows a tile), the ``phi`` gradient's accumulator (f32) and the chunk
    temporaries."""
    k, f = n * c, _f(n)
    rows = -(-f // 16) * 16
    return 2 * (2 * tm * k * size + tm * c * size + rows * k * size + f * k * 4) + (2 * n + 4) * tm * min(c, _CHUNK) * 4


def _merge_bytes(tm: int, n: int, c: int, size: int) -> int:
    """VMEM of ``mhc_merge_bwd``, the larger of the two merge kernels: double-buffered blocks of ``X``, ``dX'``,
    ``dX``, ``F``, ``dF`` and the chunk temporaries."""
    k = n * c
    return 2 * (3 * tm * k * size + 2 * tm * c * size) + (3 * n + 4) * tm * min(c, _CHUNK) * 4


# ------------------------------------------------------------------ the plain form


def _pre_plain(stream, phi, bias, alpha, spec: _Spec, sinkhorn):
    """The maps in plain ``jax.numpy``, as the model computed them over a stream ``[B, T, n, C]``: the same
    expressions in the same order (XLA rounds a differently shaped graph differently), the stream a view."""
    f32, n = jnp.float32, spec.n
    b, t, k = stream.shape
    stream = stream.reshape(b, t, n, k // n)
    flat = stream.reshape(b, t, k)
    # the norm has no scale of its own: it is one number a token, applied after the product
    inv_rms = jax.lax.rsqrt(jnp.mean(jnp.square(flat.astype(f32)), axis=-1, keepdims=True) + spec.norm_eps)
    mix = jnp.einsum("btk,kf->btf", flat, phi.astype(stream.dtype), preferred_element_type=f32) * inv_rms
    pre = jax.nn.sigmoid(alpha[0] * mix[..., :n] + bias[:n])
    post = 2.0 * jax.nn.sigmoid(alpha[1] * mix[..., n : 2 * n] + bias[n : 2 * n])
    logits = (alpha[2] * mix[..., 2 * n :] + bias[2 * n :]).reshape(b, t, n, n)
    res = sinkhorn(jnp.exp(jnp.clip(logits, spec.lo, spec.hi)), spec.iters, spec.eps)
    gap = jax.lax.stop_gradient(jnp.max(jnp.abs(jnp.sum(res, axis=-1) - 1.0)))
    # n multiply-adds a channel, written out so that XLA fuses them into one pass over the stream
    x_in = sum(pre[..., j, None] * stream[:, :, j].astype(f32) for j in range(n))
    return x_in.astype(stream.dtype), post, res, gap


def _merge_plain(stream, out, post, res):
    """``X <- H_res X + H_post^T out`` in plain ``jax.numpy``, as the model computed it over ``[B, T, n, C]``."""
    f32, n = jnp.float32, post.shape[-1]
    b, t, k = stream.shape
    stream = stream.reshape(b, t, n, k // n)
    mixed = sum(res[..., :, j, None] * stream[:, :, None, j].astype(f32) for j in range(n))
    return (mixed + post[..., None] * out[:, :, None, :].astype(f32)).astype(stream.dtype).reshape(b, t, k)


# ------------------------------------------------------------------ the kernels' arithmetic on a tile


def _chunks(c: int):
    """Lane chunks ``(start, width)`` of a stream's ``c`` channels: whole lane tiles of at most ``_CHUNK``."""
    w = next(w for w in range(min(c, _CHUNK), 0, -128) if c % w == 0)
    return [(a, w) for a in range(0, c, w)]


def _sum(terms):
    terms = list(terms)
    total = terms[0]
    for term in terms[1:]:
        total = total + term
    return total


def _maps(mix, coef, bias, spec: _Spec):
    """The maps of a tile with its tokens on lanes: ``mix [2n + n^2, tokens]`` -> ``(pre [n, .], sigmoid of the
    post logits [n, .], the clamped exponentials [n^2, .], the pre-activations [2n + n^2, .])``."""
    n = spec.n
    a = coef * mix + bias
    pre = jax.nn.sigmoid(a[:n])
    half_post = jax.nn.sigmoid(a[n : 2 * n])
    e = jnp.exp(jnp.clip(a[2 * n :], spec.lo, spec.hi))
    return pre, half_post, e, a


def _rounds(e, spec: _Spec, keep: bool = False):
    """Sinkhorn's rounds on ``e [n^2, tokens]`` (row ``i n + j`` is entry ``(i, j)``), held as ``n`` blocks
    ``[n, tokens]`` (block ``i`` is row ``i`` of each token's matrix, its entries on sublanes). Returns the last
    blocks, and with ``keep`` every half-round's blocks, first to last."""
    n = spec.n
    blocks = [e[i * n : (i + 1) * n] for i in range(n)]
    kept = [blocks]
    for _ in range(spec.iters):
        blocks = [b / (jnp.sum(b, axis=0, keepdims=True) + spec.eps) for b in blocks]
        kept.append(blocks)
        cols = _sum(blocks) + spec.eps
        blocks = [b / cols for b in blocks]
        kept.append(blocks)
    return (blocks, kept) if keep else blocks


def _rounds_bwd(d, kept, spec: _Spec):
    """The rounds' backward: ``d`` the last blocks' gradient, ``kept`` the half-rounds' blocks -> the gradient of
    the first blocks. A division by a sum ``y = x / (sum(x) + eps)`` takes ``dx = (dy - sum(dy y)) / (sum(x) + eps)``."""
    for r in range(spec.iters, 0, -1):
        y, x = kept[2 * r], kept[2 * r - 1]  # the columns' half of round r
        cols = _sum(x) + spec.eps
        back = _sum(di * yi for di, yi in zip(d, y))
        d = [(di - back) / cols for di in d]
        y, x = kept[2 * r - 1], kept[2 * r - 2]  # the rows' half
        d = [(di - jnp.sum(di * yi, axis=0, keepdims=True)) / (jnp.sum(xi, axis=0, keepdims=True) + spec.eps)
             for di, yi, xi in zip(d, y, x)]
    return d


def _clip_grad(a, lo: float, hi: float):
    """What ``jnp.clip``'s gradient passes: 1 inside, 0 outside, and a half at either bound (``maximum`` and
    ``minimum`` split a tie)."""
    below = jnp.where(a > lo, 1.0, jnp.where(a == lo, 0.5, 0.0))
    above = jnp.where(a < hi, 1.0, jnp.where(a == hi, 0.5, 0.0))
    return below * above


def _pre_fwd_kernel(x_ref, phit_ref, coef_ref, bias_ref, xin_ref, maps_ref, gap_ref, rows_ref, *, spec: _Spec):
    f32, n = jnp.float32, spec.n
    k = x_ref.shape[1]
    c = k // n
    squares = _sum(jnp.sum(jnp.square(x_ref[:, a : a + w].astype(f32)), axis=1, keepdims=True) for a, w in _chunks(k))
    inv_rms = jax.lax.rsqrt(squares / k + spec.norm_eps).T  # [1, tokens]
    m = jax.lax.dot_general(phit_ref[...], x_ref[...], (((1,), (1,)), ((), ())), preferred_element_type=f32)
    pre, half_post, e, _ = _maps(m * inv_rms, coef_ref[...], bias_ref[...], spec)
    blocks = _rounds(e, spec)
    gap_ref[0] = functools.reduce(jnp.maximum, [jnp.abs(jnp.sum(b, axis=0, keepdims=True) - 1.0) for b in blocks])
    maps_ref[...] = jnp.concatenate([2.0 * half_post, *blocks], axis=0).T
    rows_ref[0] = jnp.concatenate([m, inv_rms], axis=0)
    pre = pre.T  # [tokens, n]
    for a, w in _chunks(c):
        x_in = _sum(pre[:, j : j + 1] * x_ref[:, j * c + a : j * c + a + w].astype(f32) for j in range(n))
        xin_ref[:, a : a + w] = x_in.astype(xin_ref.dtype)


def _pre_bwd_kernel(x_ref, dxin_ref, dmaps_ref, rows_ref, phit_ref, coef_ref, bias_ref,
                    dx_ref, dphit_ref, dcoef_ref, dbias_ref, *, spec: _Spec):
    f32, n, f = jnp.float32, spec.n, _f(spec.n)
    k = x_ref.shape[1]
    c = k // n

    @pl.when(pl.program_id(0) == 0)
    def _():
        dphit_ref[...] = jnp.zeros_like(dphit_ref)
        dcoef_ref[...] = jnp.zeros_like(dcoef_ref)
        dbias_ref[...] = jnp.zeros_like(dbias_ref)

    rows = rows_ref[0]
    m, inv_rms = rows[:f], rows[f:]
    mix = m * inv_rms
    coef = coef_ref[...]
    pre, half_post, e, a = _maps(mix, coef, bias_ref[...], spec)
    # x_in = sum_j pre_j X_j: the gradient of pre_j is a token's dot of dx_in with stream j
    dpre = jnp.concatenate([
        _sum(jnp.sum(dxin_ref[:, a0 : a0 + w].astype(f32) * x_ref[:, j * c + a0 : j * c + a0 + w].astype(f32), axis=1,
                     keepdims=True) for a0, w in _chunks(c))
        for j in range(n)], axis=1).T
    dmaps = dmaps_ref[...].T  # [n + n^2, tokens]: post, then res
    blocks, kept = _rounds(e, spec, keep=True)
    de = jnp.concatenate(_rounds_bwd([dmaps[n + i * n : n + (i + 1) * n] for i in range(n)], kept, spec), axis=0)
    da = jnp.concatenate([dpre * pre * (1.0 - pre), 2.0 * dmaps[:n] * half_post * (1.0 - half_post),
                          de * e * _clip_grad(a[2 * n :], spec.lo, spec.hi)], axis=0)
    dcoef_ref[...] += da * mix
    dbias_ref[...] += da
    dmix = coef * da
    dm = dmix * inv_rms
    # 1 / rms = (sum(X^2) / k + eps)^(-1/2): X takes -(d(1 / rms) (1 / rms)^3 / k) X
    norm = (-jnp.sum(dmix * m, axis=0, keepdims=True) * inv_rms * inv_rms * inv_rms / k).T  # [tokens, 1]
    pre = pre.T
    dm_bf, dm_cols = dm.astype(x_ref.dtype), dm.T.astype(x_ref.dtype)
    for j in range(n):
        dphit_ref[:, j * c : (j + 1) * c] += jax.lax.dot_general(
            dm_bf, x_ref[:, j * c : (j + 1) * c], (((1,), (0,)), ((), ())), preferred_element_type=f32)
        for a0, w in _chunks(c):
            at = j * c + a0
            dx = (pre[:, j : j + 1] * dxin_ref[:, a0 : a0 + w].astype(f32)
                  + norm * x_ref[:, at : at + w].astype(f32)
                  + jnp.dot(dm_cols, phit_ref[:, at : at + w], preferred_element_type=f32))
            dx_ref[:, at : at + w] = dx.astype(dx_ref.dtype)


def _merge_fwd_kernel(x_ref, f_ref, maps_ref, y_ref, *, n: int):
    f32 = jnp.float32
    c = f_ref.shape[1]
    maps = maps_ref[...]
    for a, w in _chunks(c):
        xs = [x_ref[:, j * c + a : j * c + a + w].astype(f32) for j in range(n)]
        out = f_ref[:, a : a + w].astype(f32)
        for i in range(n):
            y = _sum(maps[:, n + i * n + j : n + i * n + j + 1] * xs[j] for j in range(n)) + maps[:, i : i + 1] * out
            y_ref[:, i * c + a : i * c + a + w] = y.astype(y_ref.dtype)


def _merge_bwd_kernel(x_ref, f_ref, maps_ref, dy_ref, dx_ref, df_ref, dmaps_ref, *, n: int):
    f32 = jnp.float32
    c = f_ref.shape[1]
    maps = maps_ref[...]
    dpost, dres = [0.0] * n, [0.0] * (n * n)
    for a, w in _chunks(c):
        xs = [x_ref[:, j * c + a : j * c + a + w].astype(f32) for j in range(n)]
        dys = [dy_ref[:, i * c + a : i * c + a + w].astype(f32) for i in range(n)]
        out = f_ref[:, a : a + w].astype(f32)
        for j in range(n):
            dx = _sum(maps[:, n + i * n + j : n + i * n + j + 1] * dys[i] for i in range(n))
            dx_ref[:, j * c + a : j * c + a + w] = dx.astype(dx_ref.dtype)
        df_ref[:, a : a + w] = _sum(maps[:, i : i + 1] * dys[i] for i in range(n)).astype(df_ref.dtype)
        for i in range(n):
            dpost[i] = dpost[i] + jnp.sum(dys[i] * out, axis=1, keepdims=True)
            for j in range(n):
                dres[i * n + j] = dres[i * n + j] + jnp.sum(dys[i] * xs[j], axis=1, keepdims=True)
    dmaps_ref[...] = jnp.concatenate([*dpost, *dres], axis=1)


# ------------------------------------------------------------------ the calls


def _params(semantics: str, inputs: int):
    return pltpu.CompilerParams(dimension_semantics=(semantics,), allow_input_fusion=[True] * inputs)


def _vmem(block, index):
    return pl.BlockSpec(block, index, memory_space=pltpu.VMEM)


# jit keeps the traced kernels: twelve sublayers call each map, and a step is traced twice before it runs
@functools.partial(jax.jit, static_argnums=(4,))
def _pre_fwd(x, phi, coef, bias, spec: _Spec):
    """``x [N, n C]`` -> ``(x_in [N, C], maps [N, n + n^2]`` (post, res), ``gap [N / tile, 1, tile]``, ``rows
    [N / tile, 2n + n^2 + 1, tile]`` (the raw product and ``1 / rms``: the backward's residual))``."""
    tokens, k = x.shape
    n, tm, f = spec.n, spec.tile, _f(spec.n)
    c, g = k // n, tokens // tm
    whole = lambda shape: _vmem(shape, lambda i: (0,) * len(shape))
    return pl.pallas_call(
        functools.partial(_pre_fwd_kernel, spec=spec),
        out_shape=[jax.ShapeDtypeStruct((tokens, c), x.dtype), jax.ShapeDtypeStruct((tokens, n + n * n), jnp.float32),
                   jax.ShapeDtypeStruct((g, 1, tm), jnp.float32), jax.ShapeDtypeStruct((g, f + 1, tm), jnp.float32)],
        grid=(g,),
        in_specs=[_vmem((tm, k), lambda i: (i, 0)), whole((f, k)), whole((f, 1)), whole((f, 1))],
        out_specs=[_vmem((tm, c), lambda i: (i, 0)), _vmem((tm, n + n * n), lambda i: (i, 0)),
                   _vmem((1, 1, tm), lambda i: (i, 0, 0)), _vmem((1, f + 1, tm), lambda i: (i, 0, 0))],
        compiler_params=_params("parallel", 4),
        interpret=spec.interpret,
        name="mhc_pre",
    )(x, phi.T.astype(x.dtype), coef.reshape(f, 1), bias.reshape(f, 1))


@functools.partial(jax.jit, static_argnums=(7,))
def _pre_bwd(x, phi, coef, bias, rows, dxin, dmaps, spec: _Spec):
    tokens, k = x.shape
    n, tm, f = spec.n, spec.tile, _f(spec.n)
    c, g = k // n, tokens // tm
    whole = lambda shape: _vmem(shape, lambda i: (0,) * len(shape))
    dx, dphit, dcoef, dbias = pl.pallas_call(
        functools.partial(_pre_bwd_kernel, spec=spec),
        out_shape=[jax.ShapeDtypeStruct((tokens, k), x.dtype), jax.ShapeDtypeStruct((f, k), jnp.float32),
                   jax.ShapeDtypeStruct((f, tm), jnp.float32), jax.ShapeDtypeStruct((f, tm), jnp.float32)],
        grid=(g,),
        in_specs=[_vmem((tm, k), lambda i: (i, 0)), _vmem((tm, c), lambda i: (i, 0)), _vmem((tm, n + n * n), lambda i: (i, 0)),
                  _vmem((1, f + 1, tm), lambda i: (i, 0, 0)), whole((f, k)), whole((f, 1)), whole((f, 1))],
        out_specs=[_vmem((tm, k), lambda i: (i, 0)), whole((f, k)), whole((f, tm)), whole((f, tm))],
        compiler_params=_params("arbitrary", 7),
        interpret=spec.interpret,
        name="mhc_pre_bwd",
    )(x, dxin, dmaps, rows, phi.T.astype(x.dtype), coef.reshape(f, 1), bias.reshape(f, 1))
    return dx, dphit.T, jnp.sum(dcoef, axis=1), jnp.sum(dbias, axis=1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _pre(x, phi, coef, bias, spec: _Spec):
    """The pre kernels' op: ``x [N, n C]``, ``phi [n C, 2n + n^2]``, ``coef`` (each row's ``alpha``) and ``bias``
    ``[2n + n^2]`` -> ``(x_in [N, C], maps [N, n + n^2], gap [N / tile, 1, tile])``."""
    return _pre_fwd(x, phi, coef, bias, spec)[:3]


def _pre_vjp_fwd(x, phi, coef, bias, spec):
    with jax.named_scope("mhc"):
        x_in, maps, gap, rows = _pre_fwd(x, phi, coef, bias, spec)
    return (x_in, maps, gap), (x, phi, coef, bias, rows)


def _pre_vjp_bwd(spec, saved, cotangents):
    dxin, dmaps, _ = cotangents  # the gap is a reading
    with jax.named_scope("mhc"):
        return _pre_bwd(*saved, dxin.astype(saved[0].dtype), dmaps, spec)


_pre.defvjp(_pre_vjp_fwd, _pre_vjp_bwd)


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _merge_fwd(x, out, maps, n: int, tm: int, interpret: bool):
    tokens, k = x.shape
    c = k // n
    return pl.pallas_call(
        functools.partial(_merge_fwd_kernel, n=n),
        out_shape=jax.ShapeDtypeStruct((tokens, k), x.dtype),
        grid=(tokens // tm,),
        in_specs=[_vmem((tm, k), lambda i: (i, 0)), _vmem((tm, c), lambda i: (i, 0)), _vmem((tm, n + n * n), lambda i: (i, 0))],
        out_specs=_vmem((tm, k), lambda i: (i, 0)),
        compiler_params=_params("parallel", 3),
        interpret=interpret,
        name="mhc_merge",
    )(x, out, maps)


@functools.partial(jax.jit, static_argnums=(4, 5, 6))
def _merge_bwd(x, out, maps, dy, n: int, tm: int, interpret: bool):
    tokens, k = x.shape
    c = k // n
    rows = lambda width: _vmem((tm, width), lambda i: (i, 0))
    return pl.pallas_call(
        functools.partial(_merge_bwd_kernel, n=n),
        out_shape=[jax.ShapeDtypeStruct((tokens, k), x.dtype), jax.ShapeDtypeStruct((tokens, c), out.dtype),
                   jax.ShapeDtypeStruct((tokens, n + n * n), jnp.float32)],
        grid=(tokens // tm,),
        in_specs=[rows(k), rows(c), rows(n + n * n), rows(k)],
        out_specs=[rows(k), rows(c), rows(n + n * n)],
        compiler_params=_params("parallel", 4),
        interpret=interpret,
        name="mhc_merge_bwd",
    )(x, out, maps, dy)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _merge(x, out, maps, n, tm, interpret):
    """The merge kernels' op: ``x [N, n C]``, ``out [N, C]``, ``maps [N, n + n^2]`` (post, res) -> ``[N, n C]``."""
    return _merge_fwd(x, out, maps, n, tm, interpret)


def _merge_vjp_fwd(x, out, maps, n, tm, interpret):
    with jax.named_scope("mhc"):
        return _merge_fwd(x, out, maps, n, tm, interpret), (x, out, maps)


def _merge_vjp_bwd(n, tm, interpret, saved, dy):
    with jax.named_scope("mhc"):
        return _merge_bwd(*saved, dy.astype(saved[0].dtype), n, tm, interpret)


_merge.defvjp(_merge_vjp_fwd, _merge_vjp_bwd)


def _pre_kernels(stream, phi, bias, alpha, spec: _Spec):
    """:func:`mhc_pre` by the kernels: each row's scale (``alpha`` of its map) is made here, in XLA, for
    ``jax.grad`` to differentiate."""
    b, t, k = stream.shape
    n = spec.n
    coef = jnp.concatenate([jnp.broadcast_to(alpha[0], (n,)), jnp.broadcast_to(alpha[1], (n,)),
                            jnp.broadcast_to(alpha[2], (n * n,))]).astype(jnp.float32)
    x_in, maps, gap = _pre(stream.reshape(b * t, k), phi, coef, bias.astype(jnp.float32), spec)
    post, res = maps[:, :n].reshape(b, t, n), maps[:, n:].reshape(b, t, n, n)
    return x_in.reshape(b, t, k // n), post, res, jax.lax.stop_gradient(jnp.max(gap))


def _merge_kernels(stream, out, post, res, tm: int, interpret: bool = False):
    b, t, k = stream.shape
    n = post.shape[-1]
    maps = jnp.concatenate([post.reshape(b * t, n), res.reshape(b * t, n * n)], axis=1).astype(jnp.float32)
    y = _merge(stream.reshape(b * t, k), out.astype(stream.dtype).reshape(b * t, k // n), maps, n, tm, interpret)
    return y.reshape(b, t, k)
