"""Weights from ``--seed``, one rule for the program's tree and the reference.

A leaf is a function of (seed, its name, its shape): the drivers build the
program's whole tree in one jitted call, and ``reference.py`` asks for the same
names layer by layer, so the two sides hold equal values without either taking
an array from the other. Names are the model's parameter paths joined by ``/``
(``layer_3/attn/q_proj/kernel``, ``embed/embedding``, ``final_norm/scale``).

Scales: embedding rows N(0, 1); a kernel N(0, 1/fan_in) with fan_in its first
axis (every kernel of this family is stored ``[in, ...]``); a norm's scale 1.
The final norm then gives unit rows and the head logits of about N(0, 1).
Values are drawn in float32 and rounded once to the type they are held in.
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp


def seed_key(seed: int) -> jax.Array:
    """A key for any whole-number seed (the driver's pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed % (2**31 - 1)), seed // (2**31 - 1))


def leaf(key: jax.Array, name: str, shape, dtype) -> jax.Array:
    """The value of parameter ``name``; traceable, so callers jit around it."""
    shape = tuple(int(s) for s in shape)
    if name.endswith("scale"):
        return jnp.ones(shape, dtype)
    k = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
    x = jax.random.normal(k, shape, jnp.float32)
    if not name.endswith("embedding"):
        x = x * (float(shape[0]) ** -0.5)
    return x.astype(dtype)


def path_name(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "name", p))) for p in path)


def tree_like(seed: int, shapes, dtype, out_shardings=None):
    """The whole tree of ``shapes`` (a pytree of things with ``.shape``) made
    on the device in one jitted call."""

    def build(key):
        return jax.tree_util.tree_map_with_path(lambda p, s: leaf(key, path_name(p), s.shape, dtype), shapes)

    return jax.jit(build, out_shardings=out_shardings)(seed_key(seed))
