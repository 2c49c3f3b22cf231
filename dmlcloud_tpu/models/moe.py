"""Mixture-of-Experts, dropless: every (token, expert) pair the router picks
is computed, whatever the routing.

The layer routes over ALL ``num_experts`` (sigmoid scores, an optional
selection bias and normalised top-k weights, as the DeepSeek-V3 / LFM2 family
publishes them), and computes the part of the result that the
experts it HOLDS give: ``experts_held = (a, b)`` keeps the three expert
matrices of experts ``[a, b)`` only, as one chip of an expert-parallel
deployment would, and returns ``sum over sel ∩ [a, b)`` of ``g_e * FFN_e(x)``.
The shares of all holders of one layer add up to the whole layer's output;
nothing stands in for the absent experts. ``None`` holds them all.

How: the ``N * k`` pairs are sorted by expert (pairs of experts not held sort
to the end), the tokens of the sorted pairs gathered once, the gate/up and
down products run as grouped products over the ragged groups
(``ops/grouped_matmul.py``), the gate weights applied and the rows added back
to their tokens (a permutation, so both moves and both their transposes are
gathers: no scatter-add runs). Shapes are static (``N * k`` rows: the
worst routing sends every pair to a held expert and none may be dropped); the
grouped products do work for the live rows only, and every other pass (the two
gathers, the SwiGLU, the masks) touches all ``N * k`` rows: outside the
grouped products the layer is O(N * k), whatever share of the pairs is live
(PERF.md section 7 has what compacting the buffers would take).

- ``expert_bias`` (``use_expert_bias``) takes part in the choice of experts
  only. It is a buffer, not a parameter (collection ``buffers``, as the
  published checkpoints register it): no gradient reaches it and an optimizer
  never sees it. ``TrainingPipeline.register_model`` keeps every collection
  but ``params`` in ``state.extras``.
- Counters, sown into the collection ``moe_stats`` (read with
  ``mutable=["moe_stats"]`` and :func:`moe_counters`): the pairs sent to held
  experts and the fullest held expert's load over the mean of the held.
- The load-balancing auxiliary loss (Switch Transformer eq. 4) and router
  z-loss are sown under ``losses`` as before (:func:`total_aux_loss`).
- With the expert axis of the three matrices sharded over the ``expert`` mesh
  axis (:func:`moe_partition_rules`) the same code runs under plain jit; XLA
  places the collectives. A hand-written all-to-all is not here (ROADMAP M3).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..ops.grouped_matmul import collect, grouped_matmul, spread


def moe_partition_rules() -> list[tuple[str, P]]:
    """Sharding rules for MoE layers: expert dim over ``expert``, per-expert
    matrices over ``fsdp``/``model`` like their dense counterparts. Compose
    with the base model's rules (earlier rules win)."""
    return [
        ("moe/(gate|up)_proj", P("expert", "fsdp", "model")),
        ("moe/down_proj", P("expert", "model", "fsdp")),
        ("moe/router/kernel", P()),
    ]


@dataclass(frozen=True)
class MoEConfig:
    num_experts: int = 8  # the router's width: every expert of the layer, held here or not
    top_k: int = 2
    hidden_dim: int = 512
    mlp_dim: int = 1408  # one expert's width
    use_expert_bias: bool = False
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    experts_held: tuple[int, int] | None = None  # [a, b) of the experts; None = all
    dtype: Any = jnp.bfloat16
    router_z_coef: float = 1e-3
    balance_coef: float = 1e-2

    def __post_init__(self):
        a, b = self.held
        if not 0 <= a < b <= self.num_experts:
            raise ValueError(f"experts_held {self.experts_held!r} is no range of the {self.num_experts} experts")

    @property
    def held(self) -> tuple[int, int]:
        return (0, self.num_experts) if self.experts_held is None else tuple(self.experts_held)


def route(cfg: MoEConfig, logits, bias=None):
    """``(scores [N, E], chosen experts [N, k], their weights [N, k])`` from the
    router's float32 logits. ``bias`` [E] shifts the choice and nothing else."""
    scores = jax.nn.sigmoid(logits)
    choice = scores if bias is None else scores + jax.lax.stop_gradient(bias)
    _, chosen = jax.lax.top_k(choice, min(cfg.top_k, cfg.num_experts))
    # the chosen scores by a one-hot product: elementwise forward and backward, where a gather's transpose is a scatter
    gates = jnp.sum(jax.nn.one_hot(chosen, cfg.num_experts, dtype=scores.dtype) * scores[:, None, :], axis=-1)
    if cfg.norm_topk_prob:
        gates = gates / (jnp.sum(gates, -1, keepdims=True) + 1e-6)
    return scores, chosen, gates * cfg.routed_scaling_factor


def sort_pairs(chosen, gates, held: tuple[int, int]):
    """The ``N * k`` pairs in the order of their expert, the pairs of experts
    outside ``held`` last: ``(order, its inverse, the sorted pairs' weights (0
    where not held), group_sizes [held experts])``; sorted pair ``i`` is pair
    ``order[i]`` of token ``order[i] // k``."""
    a, b = held
    n, k = chosen.shape
    expert = chosen.reshape(n * k)
    live = (expert >= a) & (expert < b)
    key = jnp.where(live, expert - a, b - a)
    order = jnp.argsort(key, stable=True)
    group_sizes = jnp.sum(key[:, None] == jnp.arange(b - a)[None, :], axis=0, dtype=jnp.int32)
    weight = jnp.where(live, gates.reshape(n * k), 0.0)[order]
    return order, jnp.argsort(order), weight, group_sizes


class MoEMLP(nn.Module):
    """Dropless expert SwiGLU block: ``[B, T, D] -> [B, T, D]``, the share of
    the experts held (module docstring)."""

    cfg: MoEConfig

    @nn.compact
    def __call__(self, x):
        from .quant import QuantDense

        cfg = self.cfg
        b, t, d = x.shape
        if d != cfg.hidden_dim:
            raise ValueError(f"MoEMLP input dim {d} != cfg.hidden_dim {cfg.hidden_dim}")
        n_tok, e = b * t, cfg.num_experts
        lo, hi = cfg.held
        held = hi - lo
        tokens = x.reshape(n_tok, d)

        bias = None
        if cfg.use_expert_bias:
            bias = self.variable("buffers", "expert_bias", jnp.zeros, (e,), jnp.float32).value
        with jax.named_scope("moe_route"):
            logits = QuantDense(e, use_bias=False, dtype=jnp.float32, param_dtype=jnp.float32, precision="highest",
                                name="router")(tokens.astype(jnp.float32))  # [N, E]
            scores, chosen, gates = route(cfg, logits, bias)
            k = chosen.shape[1]
            order, inverse, weight, group_sizes = sort_pairs(chosen, gates, (lo, hi))
            # rows past the live ones belong to no group: a grouped product leaves there whatever it likes, forward
            # and backward, so they are cut off on the way in (their gradient) and on the way out (their value)
            live = (jnp.arange(n_tok * k) < jnp.sum(group_sizes))[:, None]
            rows = jnp.where(live, spread(tokens.astype(cfg.dtype), order, inverse, k), 0)  # [N*k, D], held experts first

        wi_init = nn.initializers.variance_scaling(1.0, "fan_in", "truncated_normal", in_axis=-2, out_axis=-1, batch_axis=0)
        gate_w = self.param("moe/gate_proj", wi_init, (held, d, cfg.mlp_dim), jnp.float32)
        up_w = self.param("moe/up_proj", wi_init, (held, d, cfg.mlp_dim), jnp.float32)
        down_w = self.param("moe/down_proj", wi_init, (held, cfg.mlp_dim, d), jnp.float32)
        with jax.named_scope("moe_experts"):
            gate = grouped_matmul(rows, gate_w.astype(cfg.dtype), group_sizes)
            up = grouped_matmul(rows, up_w.astype(cfg.dtype), group_sizes)
            out_rows = grouped_matmul(nn.silu(gate) * up, down_w.astype(cfg.dtype), group_sizes)

        with jax.named_scope("moe_route"):
            out_rows = jnp.where(live, out_rows.astype(jnp.float32) * weight[:, None], 0.0)
            out = collect(out_rows.astype(cfg.dtype), order, inverse, k)  # [N, D] float32

            load = group_sizes.astype(jnp.float32)
            self.sow("moe_stats", "pairs_held", jnp.sum(load), init_fn=lambda: jnp.zeros(()), reduce_fn=jnp.add)
            self.sow("moe_stats", "load_max_over_mean", jnp.max(load) / jnp.maximum(jnp.mean(load), 1e-9),
                     init_fn=lambda: jnp.zeros(()), reduce_fn=jnp.maximum)
            # Switch balance loss: E * sum_e (fraction routed to e) * (mean score share of e)
            picks = jnp.sum(jax.nn.one_hot(chosen, e, dtype=jnp.float32), axis=(0, 1))
            share = scores / jnp.sum(scores, -1, keepdims=True)
            balance = e * jnp.sum(picks / (n_tok * k) * jnp.mean(share, axis=0))
            z_loss = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
            self.sow("losses", "moe_aux", cfg.balance_coef * balance + cfg.router_z_coef * z_loss,
                     init_fn=lambda: jnp.zeros(()), reduce_fn=jnp.add)
        return out.reshape(b, t, d).astype(x.dtype)


def moe_counters(variables: Any) -> dict:
    """``{"moe/pairs_held", "moe/load_max_over_mean"}`` of one forward, from the
    variables a ``mutable=["moe_stats"]`` apply returned: the pairs sent to
    held experts summed over the expert layers, and the largest ratio of the
    fullest held expert to the mean of the held. Device scalars: a train step
    returns them beside its loss and the tracker fetches them with it."""
    stats = variables.get("moe_stats", {}) if isinstance(variables, dict) else {}
    flat = jax.tree_util.tree_flatten_with_path(stats)[0]
    pairs = [v for p, v in flat if "pairs_held" in jax.tree_util.keystr(p)]
    ratio = [v for p, v in flat if "load_max_over_mean" in jax.tree_util.keystr(p)]
    if not pairs:
        return {}
    return {"moe/pairs_held": sum(pairs), "moe/load_max_over_mean": jnp.max(jnp.stack(ratio))}


def total_aux_loss(variables: Any) -> jnp.ndarray:
    """Sum every sown ``losses`` entry of a ``mutable=['losses']`` apply."""
    losses = variables.get("losses", {}) if isinstance(variables, dict) else {}
    leaves = jax.tree_util.tree_leaves(losses)
    if not leaves:
        return jnp.zeros(())
    return sum(jnp.sum(l) for l in leaves)
