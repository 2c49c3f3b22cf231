"""Weights from ``--seed`` for ``granite-4.0-h-micro``: ``weights.py``'s rule (a
leaf is a function of seed, name and shape) with what a share of the heads needs.

- Attention's heads are drawn as ``weights_laguna`` draws them: under their
  PUBLISHED indices, ``o_proj``'s rows scaled by the published fan-in, so the
  share's ``o_proj`` gives the partial sum it would give in the deployment.
- The mixer's heads likewise. ``in_proj``'s columns are ``[z | x | B | C | dt]``:
  head ``j``'s ``z``, ``x`` and ``dt`` columns are drawn under ``(.../z, j)``,
  ``(.../x, j)``, ``(.../dt, j)`` (one vectorised draw over the heads held),
  ``B`` and ``C`` (one group: every holder has them whole) under ``.../B``,
  ``.../C``; the conv's taps and bias follow the same channels; ``out_proj``'s
  rows of head ``j`` likewise, scaled by the published fan-in (heads x head size).
- Values the published initialiser gives a range, so that decays are neither 0
  nor 1: ``A_log = log(U[1, 16])``, ``dt_bias = softplus^-1(logU[1e-3, 1e-1])``,
  each head's under its published index; ``D = 1``; norm scales 1; the conv's
  bias N(0, 1/16).
- The embedding is tied to the head: rows N(0, 1/hidden_size), as
  ``weights_lfm2`` draws its tied embedding. Times ``embedding_multiplier`` it
  enters the stream at an rms of 0.27, beside twenty branches of 0.13-0.16
  each, and the head's logits stay small (N(0, 1/64) but for the input token's
  own, about 2), so the loss starts near log V. Rows N(0, 64 / hidden_size)
  ("logits about N(0, 1)") were this file's first rule and a mistake: the
  stream was then the input token's own row, its logit 45, the softmax one
  spike and the loss 43, and no precision could be told from another.
"""

from __future__ import annotations

import math
import zlib
from typing import NamedTuple

import jax
import jax.numpy as jnp

from benchmark import weights, weights_laguna


class Share(NamedTuple):
    """Which heads of every layer are held: ``kv_held`` of ``kv_published`` KV
    heads from ``first_kv`` on, each with its query group; the mixer's heads
    ``[first_ssm, first_ssm + ssm_held)`` of ``ssm_published``, of ``ssm_head``
    channels each."""

    head_dim: int
    ssm_head: int
    first_kv: int = 0
    kv_held: int = 1
    kv_published: int = 1
    first_ssm: int = 0
    ssm_held: int = 1
    ssm_published: int = 1

    @property
    def attention(self) -> weights_laguna.Share:
        return weights_laguna.Share(self.head_dim, 0, self.first_kv, self.kv_held, self.kv_published)


def leaf(key: jax.Array, name: str, shape, dtype, share: Share) -> jax.Array:
    """The value of parameter ``name`` as this ``share`` of the model holds it."""
    shape = tuple(int(s) for s in shape)
    heads, p = jnp.arange(share.first_ssm, share.first_ssm + share.ssm_held), share.ssm_head
    d_inner = share.ssm_held * p
    named = lambda sub: jax.random.fold_in(key, zlib.crc32(f"{name}/{sub}".encode()) & 0x7FFFFFFF)

    def per_head(sub, rows, width):
        """``[rows, held heads x width]`` of N(0, 1/rows), head ``j``'s columns drawn under ``(sub, j)``: one draw for
        all the heads held (a draw a head is a program that takes the chip's compiler a quarter of an hour)."""
        k = named(sub)
        x = jax.vmap(lambda j: jax.random.normal(jax.random.fold_in(k, j), (rows, width), jnp.float32))(heads)
        return x.transpose(1, 0, 2).reshape(rows, share.ssm_held * width) * float(rows) ** -0.5

    def by_channel(rows, with_z_and_dt):
        """``[rows, columns]`` whose columns follow the in-projection's: per head ``z`` (where asked), per head ``x``, ``B``,
        ``C``, per head ``dt`` (where asked); ``B`` and ``C`` share what is left of the columns."""
        gn = (shape[-1] - d_inner * (2 if with_z_and_dt else 1) - (share.ssm_held if with_z_and_dt else 0)) // 2
        whole = lambda sub: jax.random.normal(named(sub), (rows, gn), jnp.float32) * float(rows) ** -0.5
        parts = [per_head("z", rows, p)] if with_z_and_dt else []
        parts += [per_head("x", rows, p), whole("B"), whole("C")]
        parts += [per_head("dt", rows, 1)] if with_z_and_dt else []
        return jnp.concatenate(parts, axis=-1)

    if name.endswith("mamba/in_proj/kernel"):
        x = by_channel(shape[0], True)
    elif name.endswith("mamba/conv_weight"):
        x = by_channel(shape[0], False)
    elif name.endswith("mamba/conv_bias"):
        x = by_channel(16, False)[0]  # a row of N(0, 1/16)
    elif name.endswith("mamba/out_proj/kernel"):  # a head's rows drawn at 1/head size, the sum is over every published head
        x = per_head("rows", shape[1], p).T * (float(shape[1]) / (p * share.ssm_published)) ** 0.5
    elif name.endswith(("mamba/A_log", "mamba/dt_bias")):
        u = jax.random.uniform(named("heads"), (share.ssm_published,), jnp.float32)
        if name.endswith("A_log"):
            x = jnp.log(1.0 + 15.0 * u)
        else:
            step = jnp.exp(math.log(1e-3) + u * (math.log(1e-1) - math.log(1e-3)))
            x = step + jnp.log(-jnp.expm1(-step))  # softplus^-1
        x = x[share.first_ssm : share.first_ssm + share.ssm_held]
    elif name.endswith("mamba/D"):
        x = jnp.ones(shape, jnp.float32)
    elif name.endswith("embedding"):
        x = weights.leaf(key, name, shape, jnp.float32) * float(shape[1]) ** -0.5
    else:
        return weights_laguna.leaf(key, name, shape, dtype, share.attention)
    assert x.shape == shape, (name, x.shape, shape)
    return x.astype(dtype)


def tree_like(seed: int, shapes, dtype, share: Share):
    """The whole tree of ``shapes`` (a pytree of things with ``.shape``) made on the device in one jitted call."""

    def build(key):
        return jax.tree_util.tree_map_with_path(
            lambda p, s: leaf(key, weights.path_name(p), s.shape, dtype, share), shapes)

    return jax.jit(build)(weights.seed_key(seed))
