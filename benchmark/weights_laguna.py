"""Weights from ``--seed`` for ``laguna-s-2.1``: ``weights.py``'s rule (a leaf
is a function of seed, name and shape) with what a chip's share needs.

- The three matrices of the experts a layer holds are stored ``[held, in,
  out]``; each expert is drawn under its PUBLISHED index (``.../expert_<e>``)
  and scaled by its own input axis, as ``weights_lfm2`` does.
- Heads too are drawn under their published indices: query head ``j`` of a
  layer's ``q_proj`` / ``g_proj`` / ``o_proj`` is ``.../head_<j>``, KV head
  ``j`` of ``k_proj`` / ``v_proj`` likewise, so a chip that holds KV head 3 and
  its query group holds what the whole model holds there. ``o_proj`` sums over
  ALL the layer's heads in the published model, so a head's rows are scaled by
  the published fan-in (heads x head size), not by the rows held: the share's
  ``o_proj`` then gives the partial sum it would give in the deployment.
- Untied: the embedding's rows are N(0, 1), the head a kernel of its own.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from benchmark import weights

EXPERT_LEAVES = ("moe/gate_proj", "moe/up_proj", "moe/down_proj")


class Share(NamedTuple):
    """Which part of every layer is held: experts from ``first_expert`` on,
    ``kv_held`` of the ``kv_published`` KV heads from ``first_kv`` on, each with
    its query group; ``head_dim`` tells ``o_proj``'s rows apart by head."""

    head_dim: int
    first_expert: int = 0
    first_kv: int = 0
    kv_held: int = 1
    kv_published: int = 1


def leaf(key: jax.Array, name: str, shape, dtype, share: Share) -> jax.Array:
    """The value of parameter ``name`` as this ``share`` of the model holds it."""
    shape = tuple(int(s) for s in shape)
    one = lambda sub, sh: weights.leaf(key, f"{name}/{sub}", sh, jnp.float32)
    if name.endswith(EXPERT_LEAVES):
        parts, axis = [one(f"expert_{share.first_expert + e}", shape[1:]) for e in range(shape[0])], 0
    elif name.endswith(("attn/k_proj/kernel", "attn/v_proj/kernel")):  # [d, KV heads, head size]
        parts, axis = [one(f"head_{share.first_kv + j}", (shape[0], shape[2])) for j in range(shape[1])], 1
    elif name.endswith(("attn/q_proj/kernel", "attn/g_proj/kernel")):  # [d, heads, head size] and [d, heads]
        first = share.first_kv * (shape[1] // share.kv_held)
        parts, axis = [one(f"head_{first + j}", (shape[0], *shape[2:])) for j in range(shape[1])], 1
    elif name.endswith("attn/o_proj/kernel"):  # [heads * head size, d]
        group = shape[0] // share.head_dim // share.kv_held
        scale = float(group * share.kv_published) ** -0.5  # a head's rows drawn at 1/head size, the sum is over every head
        parts = [one(f"head_{share.first_kv * group + j}", (share.head_dim, shape[1])) * scale for j in range(group * share.kv_held)]
        return jnp.concatenate(parts, axis=0).astype(dtype)
    else:
        return weights.leaf(key, name, shape, dtype)
    return jnp.stack(parts, axis=axis).astype(dtype)


def tree_like(seed: int, shapes, dtype, share: Share):
    """The whole tree of ``shapes`` (a pytree of things with ``.shape``) made on the device in one jitted call."""

    def build(key):
        return jax.tree_util.tree_map_with_path(
            lambda p, s: leaf(key, weights.path_name(p), s.shape, dtype, share), shapes)

    return jax.jit(build)(weights.seed_key(seed))
