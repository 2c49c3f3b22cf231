"""Weights from ``--seed`` for ``lfm2-24b-a2b``: ``weights.py``'s rule (a leaf
is a function of seed, name and shape) with three things of its own.

- The three matrices of the experts a layer holds are stored ``[held, in,
  out]``. Each expert is drawn under its PUBLISHED index (``.../expert_<e>``),
  so a chip that holds experts 8-15 holds what the whole model holds there, and
  scaled by its own input axis (``weights.leaf`` would take the expert axis).
- The embedding is tied to the head: rows N(0, 1/hidden_size), so that the
  head's logits of unit-norm rows stay about N(0, 1).
- ``expert_bias`` is not drawn: ``reference_lfm2.balanced_expert_bias`` sets it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark import weights

EXPERT_LEAVES = ("moe/gate_proj", "moe/up_proj", "moe/down_proj")


def leaf(key: jax.Array, name: str, shape, dtype, first_expert: int = 0) -> jax.Array:
    """The value of parameter ``name``; ``first_expert`` is the published index
    of the first expert a layer's expert matrices hold."""
    shape = tuple(int(s) for s in shape)
    if name.endswith(EXPERT_LEAVES):
        return jnp.stack([weights.leaf(key, f"{name}/expert_{first_expert + e}", shape[1:], dtype)
                          for e in range(shape[0])])
    if name.endswith("embedding"):
        return (weights.leaf(key, name, shape, jnp.float32) * float(shape[1]) ** -0.5).astype(dtype)
    return weights.leaf(key, name, shape, dtype)


def tree_like(seed: int, shapes, dtype, first_expert: int = 0):
    """The whole tree of ``shapes`` made on the device in one jitted call."""

    def build(key):
        return jax.tree_util.tree_map_with_path(
            lambda p, s: leaf(key, weights.path_name(p), s.shape, dtype, first_expert), shapes)

    return jax.jit(build)(weights.seed_key(seed))
