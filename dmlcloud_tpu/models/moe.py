"""Mixture-of-Experts, dropless: every (token, expert) pair the router picks
is computed, whatever the routing.

The layer routes over ALL ``num_experts`` (``scoring_func``: sigmoid scores,
an optional selection bias and normalised top-k weights, as the DeepSeek-V3 /
LFM2 family publishes them, or a softmax over all the experts, as the families
that publish ``norm_topk_prob`` beside a shared expert do), and computes the
part of the result that the experts it HOLDS give: ``experts_held = (a, b)`` keeps the three expert
matrices of experts ``[a, b)`` only, as one chip of an expert-parallel
deployment would, and returns ``sum over sel ∩ [a, b)`` of ``g_e * FFN_e(x)``.
The shares of all holders of one layer add up to the whole layer's output;
nothing stands in for the absent experts. ``None`` holds them all. With
``shared_expert_intermediate_size`` > 0 one more SwiGLU of that width runs on
every token, ungated, and is added to the held experts' share: what every
holder of a layer computes alike (phase ``moe_shared``).

How: the tokens of the pairs are gathered into rows that lie sorted by expert
(pairs of experts not held sort to the end, so the live rows are a prefix of
the sorted order), the gate/up and down products run as grouped products over
the ragged groups (``ops/grouped_matmul.py``), the gate weights applied and the
rows added back to their tokens (gathers both ways, forward and backward: no
scatter-add of rows runs). No pair may be dropped and the worst routing sends
all ``N * k`` to held experts, but a layer that holds ``h`` of ``E`` experts
expects ``N * k * h / E``. So the layer works in a buffer of
``R = row_bound(N * k, h, E)`` rows, twice that even share, whenever a step's
live rows fit it, and in the full ``N * k`` buffer when they do not: both
compiled, chosen on the device by a ``jax.lax.cond`` on the step's own
``group_sizes``, the same output and gradients wherever both apply. On the
usual path everything after the choice of experts costs what the buffer holds
(``R`` rows and one pass over the ``N`` tokens), not what the router chose
from: :func:`sort_pairs` finds the first ``R`` sorted pairs by counting the live
pairs into a token-order buffer and sorting that many places (nothing as long
as ``N * k`` is sorted or fetched by index), the way out is one ``R``-row
gather, the way back ``R + N`` row fetches whatever ``k`` is
(``collect_rows``), the weights and their gradient an ``R``-sized gather and
its transpose; it keeps ``R``-row residuals for its hand-written backward. No
shape, trip count or branch follows the number of live pairs: a step that
fits pays the buffer's cost whatever its routing. The rare path sorts all
``N * k`` pairs for itself inside its branch and recomputes its forward in
the backward, so a step that fits pays nothing for the buffer behind it. Where
the layer holds every expert (or half of them) ``R`` is ``N * k``: one path,
no ``cond``, the ``N * k`` sort.

- ``expert_bias`` (``use_expert_bias``) takes part in the choice of experts
  only. It is a buffer, not a parameter (collection ``buffers``, as the
  published checkpoints register it): no gradient reaches it and an optimizer
  never sees it. ``TrainingPipeline.register_model`` keeps every collection
  but ``params`` in ``state.extras``.
- Counters, sown into the collection ``moe_stats`` (read with
  ``mutable=["moe_stats"]`` and :func:`moe_counters`): the pairs sent to held
  experts, the fullest held expert's load over the mean of the held, and
  whether the layer's live rows overflowed ``R`` (it then took the full path).
- The load-balancing auxiliary loss (Switch Transformer eq. 4) and router
  z-loss are sown under ``losses`` as before (:func:`total_aux_loss`).
- With the expert axis of the three matrices sharded over the ``expert`` mesh
  axis (:func:`moe_partition_rules`) the same code runs under plain jit; XLA
  places the collectives. A hand-written all-to-all is not here (ROADMAP M3).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..ops.grouped_matmul import Runs, collect, collect_rows, grouped_matmul, run_layout, spread, spread_rows


def moe_partition_rules() -> list[tuple[str, P]]:
    """Sharding rules for MoE layers: expert dim over ``expert``, per-expert
    matrices over ``fsdp``/``model`` like their dense counterparts. Compose
    with the base model's rules (earlier rules win)."""
    return [
        ("moe/(gate|up)_proj", P("expert", "fsdp", "model")),
        ("moe/down_proj", P("expert", "model", "fsdp")),
        ("moe/router/kernel", P()),
        ("shared_expert/(gate|up)_proj/kernel", P("fsdp", "model")),
        ("shared_expert/down_proj/kernel", P("model", "fsdp")),
    ]


@dataclass(frozen=True)
class MoEConfig:
    num_experts: int = 8  # the router's width: every expert of the layer, held here or not
    top_k: int = 2
    hidden_dim: int = 512
    mlp_dim: int = 1408  # one expert's width
    use_expert_bias: bool = False
    scoring_func: str = "sigmoid"  # "sigmoid" | "softmax" over all the experts
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    shared_expert_intermediate_size: int = 0  # 0 = no shared expert
    experts_held: tuple[int, int] | None = None  # [a, b) of the experts; None = all
    dtype: Any = jnp.bfloat16
    router_z_coef: float = 1e-3
    balance_coef: float = 1e-2

    def __post_init__(self):
        a, b = self.held
        if not 0 <= a < b <= self.num_experts:
            raise ValueError(f"experts_held {self.experts_held!r} is no range of the {self.num_experts} experts")
        if self.scoring_func not in ("sigmoid", "softmax"):
            raise ValueError(f"scoring_func must be 'sigmoid' or 'softmax', got {self.scoring_func!r}")

    @property
    def held(self) -> tuple[int, int]:
        return (0, self.num_experts) if self.experts_held is None else tuple(self.experts_held)


def route(cfg: MoEConfig, logits, bias=None):
    """``(scores [N, E], chosen experts [N, k], their weights [N, k])`` from the
    router's float32 logits. ``bias`` [E] shifts the choice and nothing else."""
    softmax = cfg.scoring_func == "softmax"
    scores = jax.nn.softmax(logits, axis=-1) if softmax else jax.nn.sigmoid(logits)
    choice = scores if bias is None else scores + jax.lax.stop_gradient(bias)
    _, chosen = jax.lax.top_k(choice, min(cfg.top_k, cfg.num_experts))
    # the chosen scores by a one-hot product: elementwise forward and backward, where a gather's transpose is a scatter
    gates = jnp.sum(jax.nn.one_hot(chosen, cfg.num_experts, dtype=scores.dtype) * scores[:, None, :], axis=-1)
    if cfg.norm_topk_prob:
        # sigmoid scores can all lie near 0, and their family publishes the guard; a softmax's k largest sum to k / E at least
        gates = gates / (jnp.sum(gates, -1, keepdims=True) + (0.0 if softmax else 1e-6))
    return scores, chosen, gates * cfg.routed_scaling_factor


def _tokens_of(ends, slots):
    """For sorted ``ends [N]``, how many lie at or below each of ``slots``: the
    token whose live pairs a slot falls among. A search in two levels of
    comparisons (blocks of 128 ends, then one block's), no loop and no sort:
    0.014 ms on a v5e for 5,504 slots among 8,192 ends, where
    ``jnp.searchsorted`` takes 0.52 by its loop and 0.13 by its sort
    (PERF.md section 6, PR 36)."""
    table = jnp.pad(ends, (0, -ends.shape[0] % 128), constant_values=jnp.iinfo(ends.dtype).max).reshape(-1, 128)
    block = jnp.sum(table[:, -1][None, :] <= slots[:, None], axis=1, dtype=jnp.int32)
    block = jnp.minimum(block, table.shape[0] - 1)
    return block * 128 + jnp.sum(table[block] <= slots[:, None], axis=1, dtype=jnp.int32)


def sort_pairs(chosen, gates, held: tuple[int, ...]):
    """The ``N * k`` pairs in the order of their expert, stably, the pairs of
    experts outside ``[a, b)`` last; sorted pair ``i`` is pair ``order[i]`` of
    token ``order[i] // k``.

    ``held = (a, b)``: all of them, by a stable sort of the ``N * k`` keys and a
    second one for its inverse: ``(order, its inverse, the sorted pairs' weights
    (0 where not held), group_sizes [held experts])``.

    ``held = (a, b, rows)``, for a layer that works in ``rows`` rows: the first
    ``rows`` sorted pairs alone, right wherever the live pairs fit them, and
    nothing sorted or fetched that is longer than the buffer: ``(order [rows],
    Runs, weights [rows], group_sizes)``. Over the ``N * k`` pairs run comparisons
    and cumulative sums of integers only: a token's live pairs are counted, its
    first slot in token order is the live pairs before it, a search gives every
    place of the token-order buffer (``ops.grouped_matmul.run_layout``) its
    token and pair, and two sorts of that many places, by expert and back, give
    the expert order and each place's row in it. The ``Runs`` are what
    :func:`~dmlcloud_tpu.ops.grouped_matmul.collect_rows` goes back by."""
    a, b, *bound = held
    n, k = chosen.shape
    live = (chosen >= a) & (chosen < b)
    key = jnp.where(live, chosen - a, b - a)
    group_sizes = jnp.sum(key.reshape(n * k, 1) == jnp.arange(b - a)[None, :], axis=0, dtype=jnp.int32)
    if not bound:
        order = jnp.argsort(key.reshape(n * k), stable=True)
        weight = jnp.where(live, gates, 0.0).reshape(n * k)[order]
        return order, jnp.argsort(order), weight, group_sizes
    (rows,) = bound
    chunks, tile, heads = run_layout(rows, k)  # a token's live pairs are k at most
    count = jnp.sum(live, axis=1, dtype=jnp.int32)
    ends = jnp.cumsum(count)
    head = ends - count  # a token's first slot: the live pairs before it
    slot = jnp.where(live, head[:, None] + jnp.cumsum(live, axis=1, dtype=jnp.int32) - 1, -1)  # [N, k]
    # chunk c of the buffer holds, whole, the runs whose heads are slots [c * heads, (c + 1) * heads)
    chunk = jnp.repeat(jnp.arange(chunks, dtype=jnp.int32), tile)
    at = chunk * heads + jnp.tile(jnp.arange(tile, dtype=jnp.int32), chunks)  # the slot a place stands for
    token = jnp.minimum(_tokens_of(ends, at), n - 1)
    mine = jnp.concatenate([slot, head[:, None]], axis=1)[token]  # one fetch a place: its token's slots, and the first of them
    match = mine[:, :k] == at[:, None]  # [places, k]: which of its token's pairs a place holds
    taken = jnp.any(match, axis=1) & (mine[:, k] // heads == chunk)
    pair = token * k + jnp.argmax(match, axis=1).astype(jnp.int32)
    expert = jnp.where(taken, jnp.sum(jnp.where(match, key[token], 0), axis=1), b - a)
    # the places lie in token order: sorted stably by expert they are the expert order, empty places last
    place = jnp.arange(chunks * tile, dtype=jnp.int32)
    _, order, came_from = jax.lax.sort((expert, pair, place), num_keys=1, is_stable=True)
    _, row = jax.lax.sort((came_from, place), num_keys=1)
    order = order[:rows]
    weight = jnp.where(jnp.arange(rows) < ends[-1], gates.reshape(n * k)[order], 0.0)
    runs = Runs(row, jnp.where(taken, token, n).reshape(chunks, tile),
                jnp.where(count > 0, head // heads * tile + head % heads, chunks * tile - 1))
    return order, runs, weight, group_sizes


#: The bounded buffer holds this many times the even share of the held experts.
#: With the loads levelled by ``expert_bias`` the live rows of a layer stay within
#: a tenth of that share in every step (15,348-17,461 pairs a step over four
#: layers against 16,384 in ``lfm2-train-8k``; PERF.md section 6, PRs 30 and 31),
#: so twice is far off there, while a routing that drifts or a skewed batch has
#: room before it pays for the full path. What the layer does outside the grouped
#: kernels goes with the buffer's rows, the kernels hardly at all (they skip dead
#: tiles): PERF.md section 6, PR 31, has the layer timed at other factors.
_ROW_BOUND_FACTOR = 2
_ROW_TILE = 512  # the grouped product's row tile on the TPU (``ragged_dot_tiling``)


def row_bound(pairs: int, held: int, num_experts: int) -> int:
    """Rows of the buffer a layer with ``held`` of ``num_experts`` experts works
    in when a step's live rows fit it: ``_ROW_BOUND_FACTOR`` times the even
    share of its ``pairs``, rounded up to the row tile, and never more than ``pairs``."""
    tiles = -(-_ROW_BOUND_FACTOR * pairs * held // (num_experts * _ROW_TILE))
    return min(pairs, tiles * _ROW_TILE)


def _ffn(rows, gate_w, up_w, down_w, group_sizes):
    with jax.named_scope("moe_experts"):
        gate = grouped_matmul(rows, gate_w, group_sizes)
        up = grouped_matmul(rows, up_w, group_sizes)
        return gate, up, grouped_matmul(nn.silu(gate) * up, down_w, group_sizes)


def _live_rows(group_sizes, rows: int):
    # rows past the live ones belong to no group: a grouped product leaves there whatever it likes, forward
    # and backward, so they are cut off on the way in (their gradient) and on the way out (their value)
    return (jnp.arange(rows) < jnp.sum(group_sizes))[:, None]


def _full_path(tokens, weight, gate_w, up_w, down_w, order, inverse, group_sizes, k):
    """The held experts' share on the ``N * k`` buffer: ``[N, D]`` float32."""
    with jax.named_scope("moe_route"):
        live = _live_rows(group_sizes, order.shape[0])
        rows = jnp.where(live, spread(tokens, order, inverse, k), 0)  # [N*k, D], held experts first
    _, _, out_rows = _ffn(rows, gate_w, up_w, down_w, group_sizes)
    with jax.named_scope("moe_route"):
        out_rows = jnp.where(live, out_rows.astype(jnp.float32) * weight[:, None], 0.0)
        return collect(out_rows.astype(tokens.dtype), order, inverse, k)


def _sorted_full_path(tokens, gates, gate_w, up_w, down_w, chosen, group_sizes, held):
    """:func:`_full_path` for a bounded layer: the rare path sorts all the pairs for itself."""
    with jax.named_scope("moe_route"):
        order, inverse, weight, _ = sort_pairs(chosen, gates, held)
    return _full_path(tokens, weight, gate_w, up_w, down_w, order, inverse, group_sizes, chosen.shape[1])


# The two paths of a bounded layer, forward and backward: each body is traced once
# a process (an inner jit, inlined under the step's) and shared by the expert
# layers and by the traces a step goes through before it runs. The usual path reads
# the weights ``sort_pairs`` gave it and gives them their gradient; the rare path
# reads the gates they were taken from and gives those theirs.


@functools.partial(jax.jit, static_argnames=("bound",))
def _usual_fwd(tokens, weight, gates, gate_w, up_w, down_w, order, runs, chosen, group_sizes, *, bound):
    del gates
    k = chosen.shape[1]
    with jax.named_scope("moe_route"):
        live = _live_rows(group_sizes, bound)
        rows = jnp.where(live, spread_rows(tokens, order, k, bound), 0)  # [R, D]
    gate, up, out_rows = _ffn(rows, gate_w, up_w, down_w, group_sizes)
    with jax.named_scope("moe_route"):
        weighted = jnp.where(live, out_rows.astype(jnp.float32) * weight[:, None], 0.0)
        out = collect_rows(weighted.astype(tokens.dtype), runs)
        return out, (rows, gate, up, out_rows)


@functools.partial(jax.jit, static_argnames=("bound", "held"))
def _full_fwd(tokens, weight, gates, gate_w, up_w, down_w, order, runs, chosen, group_sizes, *, bound, held):
    del weight, order, runs
    out = _sorted_full_path(tokens, gates, gate_w, up_w, down_w, chosen, group_sizes, held)
    rows = jnp.zeros((bound, tokens.shape[1]), tokens.dtype)  # nothing is kept: the backward computes this path again
    wide = jnp.zeros((bound, gate_w.shape[2]), tokens.dtype)
    return out, (rows, wide, wide, rows)


@functools.partial(jax.jit, static_argnames=("bound",))
def _usual_bwd(saved, tokens, weight, gates, gate_w, up_w, down_w, order, runs, chosen, group_sizes, d_out, *, bound):
    del tokens
    rows, gate, up, out_rows = saved
    k = chosen.shape[1]
    product = lambda lhs, rhs: grouped_matmul(lhs, rhs, group_sizes)
    with jax.named_scope("moe_route"):
        live = _live_rows(group_sizes, bound)
        d_weighted = jnp.where(live, spread_rows(d_out.astype(rows.dtype), order, k, bound).astype(jnp.float32), 0.0)
        d_weight = jnp.sum(d_weighted * out_rows.astype(jnp.float32), axis=-1)
        d_out_rows = (d_weighted * weight[:, None]).astype(rows.dtype)
    with jax.named_scope("moe_experts"):
        hidden, swiglu_vjp = jax.vjp(lambda g, u: nn.silu(g) * u, gate, up)
        d_hidden, d_down = jax.vjp(product, hidden, down_w)[1](d_out_rows)  # a product's own result is not needed
        d_gate, d_up = swiglu_vjp(d_hidden)
        d_rows_gate, d_gate_w = jax.vjp(product, rows, gate_w)[1](d_gate)
        d_rows_up, d_up_w = jax.vjp(product, rows, up_w)[1](d_up)
    with jax.named_scope("moe_route"):
        d_rows = jnp.where(live, d_rows_gate + d_rows_up, 0)
        d_tokens = collect_rows(d_rows, runs).astype(rows.dtype)
        return d_tokens, d_weight, jnp.zeros_like(gates), d_gate_w, d_up_w, d_down


@functools.partial(jax.jit, static_argnames=("bound", "held"))
def _full_bwd(saved, tokens, weight, gates, gate_w, up_w, down_w, order, runs, chosen, group_sizes, d_out, *, bound, held):
    del saved, order, runs, bound
    path = lambda *diff: _sorted_full_path(*diff, chosen, group_sizes, held)
    d_tokens, d_gates, *d_matrices = jax.vjp(path, tokens, gates, gate_w, up_w, down_w)[1](d_out)
    return d_tokens, jnp.zeros_like(weight), d_gates, *d_matrices


@functools.partial(jax.custom_vjp, nondiff_argnums=(10, 11))
def _bounded_path(tokens, weight, gates, gate_w, up_w, down_w, order, runs, chosen, group_sizes, bound, held):
    """:func:`_full_path`'s result, computed in ``bound`` rows when the live rows fit them:
    ``order``, ``runs`` and ``weight`` are ``sort_pairs``' for ``(*held, bound)``."""
    return _bounded_fwd(tokens, weight, gates, gate_w, up_w, down_w, order, runs, chosen, group_sizes, bound, held)[0]


def _bounded_fwd(tokens, weight, gates, gate_w, up_w, down_w, order, runs, chosen, group_sizes, bound, held):
    args = (tokens, weight, gates, gate_w, up_w, down_w, order, runs, chosen, group_sizes)
    # no scope of the layer's own round a cond: XLA's grouped kernel takes the path of names round it for its
    # ``op_name``, and is given its phase by its instruction's name only where that path holds none
    out, saved = jax.lax.cond(jnp.sum(group_sizes) <= bound, functools.partial(_usual_fwd, bound=bound),
                              functools.partial(_full_fwd, bound=bound, held=held), *args)
    return out, (saved, *args)


def _bounded_bwd(bound, held, residuals, d_out):
    group_sizes = residuals[-1]
    grads = jax.lax.cond(jnp.sum(group_sizes) <= bound, functools.partial(_usual_bwd, bound=bound),
                         functools.partial(_full_bwd, bound=bound, held=held), *residuals, d_out)
    return (*grads, None, None, None, None)


_bounded_path.defvjp(_bounded_fwd, _bounded_bwd)


class SwiGLU(nn.Module):
    """``down(silu(gate(x)) * up(x))``, ``width`` wide: the shared expert."""

    width: int
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        from .quant import QuantDense

        dense = lambda feats, name: QuantDense(feats, use_bias=False, dtype=self.dtype, param_dtype=jnp.float32, name=name)
        return dense(x.shape[-1], "down_proj")(nn.silu(dense(self.width, "gate_proj")(x)) * dense(self.width, "up_proj")(x))


class MoEMLP(nn.Module):
    """Dropless expert SwiGLU block: ``[B, T, D] -> [B, T, D]``, the share of
    the experts held, and the shared expert where the layer has one (module docstring)."""

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
            bound = row_bound(n_tok * k, held, e)
            bounded = bound < n_tok * k
            order, inverse, weight, group_sizes = sort_pairs(chosen, gates, (lo, hi, bound) if bounded else (lo, hi))

        wi_init = nn.initializers.variance_scaling(1.0, "fan_in", "truncated_normal", in_axis=-2, out_axis=-1, batch_axis=0)
        gate_w = self.param("moe/gate_proj", wi_init, (held, d, cfg.mlp_dim), jnp.float32)
        up_w = self.param("moe/up_proj", wi_init, (held, d, cfg.mlp_dim), jnp.float32)
        down_w = self.param("moe/down_proj", wi_init, (held, cfg.mlp_dim, d), jnp.float32)
        with jax.named_scope("moe_experts"):  # cast once a step, for either path
            matrices = gate_w.astype(cfg.dtype), up_w.astype(cfg.dtype), down_w.astype(cfg.dtype)
        if bounded:  # ``inverse`` is the rows' runs in token order
            out = _bounded_path(tokens.astype(cfg.dtype), weight, gates, *matrices, order, inverse, chosen, group_sizes, bound, (lo, hi))
        else:
            out = _full_path(tokens.astype(cfg.dtype), weight, *matrices, order, inverse, group_sizes, k)

        with jax.named_scope("moe_route"):
            load = group_sizes.astype(jnp.float32)
            self.sow("moe_stats", "pairs_held", jnp.sum(load), init_fn=lambda: jnp.zeros(()), reduce_fn=jnp.add)
            self.sow("moe_stats", "load_max_over_mean", jnp.max(load) / jnp.maximum(jnp.mean(load), 1e-9),
                     init_fn=lambda: jnp.zeros(()), reduce_fn=jnp.maximum)
            self.sow("moe_stats", "overflow", (jnp.sum(group_sizes) > bound).astype(jnp.float32),
                     init_fn=lambda: jnp.zeros(()), reduce_fn=jnp.add)
            # Switch balance loss: E * sum_e (fraction routed to e) * (mean score share of e)
            picks = jnp.sum(jax.nn.one_hot(chosen, e, dtype=jnp.float32), axis=(0, 1))
            share = scores / jnp.sum(scores, -1, keepdims=True)
            balance = e * jnp.sum(picks / (n_tok * k) * jnp.mean(share, axis=0))
            z_loss = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
            self.sow("losses", "moe_aux", cfg.balance_coef * balance + cfg.router_z_coef * z_loss,
                     init_fn=lambda: jnp.zeros(()), reduce_fn=jnp.add)
        out = out.reshape(b, t, d).astype(x.dtype)
        if cfg.shared_expert_intermediate_size:
            with jax.named_scope("moe_shared"):
                out = out + SwiGLU(cfg.shared_expert_intermediate_size, cfg.dtype, name="shared_expert")(x)
        return out


def moe_counters(variables: Any) -> dict:
    """``{"moe/pairs_held", "moe/load_max_over_mean", "moe/overflow_layers"}`` of
    one forward, from the variables a ``mutable=["moe_stats"]`` apply returned:
    the pairs sent to held experts summed over the expert layers, the largest
    ratio of the fullest held expert to the mean of the held, and how many
    expert layers' live rows overflowed their row bound and took the full
    ``N * k`` path (0 where the loads are level; nothing is dropped either way).
    Device scalars: a train step returns them beside its loss and the tracker
    fetches them with it."""
    stats = variables.get("moe_stats", {}) if isinstance(variables, dict) else {}
    flat = jax.tree_util.tree_flatten_with_path(stats)[0]
    named = lambda name: [v for p, v in flat if name in jax.tree_util.keystr(p)]
    pairs = named("pairs_held")
    if not pairs:
        return {}
    return {"moe/pairs_held": sum(pairs), "moe/load_max_over_mean": jnp.max(jnp.stack(named("load_max_over_mean"))),
            "moe/overflow_layers": sum(named("overflow"))}


def total_aux_loss(variables: Any) -> jnp.ndarray:
    """Sum every sown ``losses`` entry of a ``mutable=['losses']`` apply."""
    losses = variables.get("losses", {}) if isinstance(variables, dict) else {}
    leaves = jax.tree_util.tree_leaves(losses)
    if not leaves:
        return jnp.zeros(())
    return sum(jnp.sum(l) for l in leaves)
