"""The plain reference of ``laguna-s-2.1``: Laguna-S-2.1's decoder (``model_type``
``laguna``) in straightforward ``jax.numpy``, float32 under
``jax.default_matmul_precision("highest")``, no kernel; it imports nothing of
the program. Weights come from ``weights_laguna.leaf`` under the program's names.

Pre-norm residual blocks, RMSNorm with ``rms_norm_eps``, no biases, embedding
in, one final RMSNorm, an untied head. Block ``i``: ``h = x + Attn_i(norm(x))``,
``y = h + FFN_i(norm(h))``.

- ``Attn_i``: ``H_i = num_attention_heads_per_layer[i]`` query heads over the
  model's KV heads, head size ``head_dim``. ``q, k, v`` by linear maps of the
  normed input ``u``; rotary by the table of the layer's kind
  (``rope_parameters[layer_types[i]]``): theta, YaRN where ``rope_type`` says so
  (``rope_table``), on the first ``head_dim * partial_rotary_factor`` dimensions,
  the rest unrotated; causal softmax of ``q k^T / sqrt(head_dim)``, on
  ``sliding_attention`` layers only where ``q_pos - k_pos < sliding_window``;
  ``g = sigmoid(u W_g)``, one scalar a head, multiplies that head's output;
  then ``o_proj``.
- ``FFN_i``: SwiGLU of ``intermediate_size`` where ``mlp_layer_types[i]`` is
  ``dense``; else ``p = softmax(u W_r)`` over ALL ``num_experts`` in float32,
  ``sel`` the ``num_experts_per_tok`` largest, ``w_e = p_e / sum over sel`` times
  ``moe_routed_scaling_factor``, and ``FFN(u) = sum over e in sel and held of
  w_e * W2_e(silu(W1_e u) * W3_e u)`` plus one shared SwiGLU of
  ``shared_expert_intermediate_size`` on every token, ungated: a loop over the
  held experts, each computed for every token and masked. No token dropped,
  nothing in the place of the experts and heads not held.

Departures from the published model, each an assumption of the configuration's
file (``assumed``): softmax scoring without a selection bias; the shared expert
added ungated; the gate a sigmoid of the block's normed input, before
``o_proj``; no norm on q and k; the rotating half is the FIRST half of the head,
on interleaved pairs (2i, 2i+1), the program's convention (a permutation of the
projections' columns away from the half-split pairing, the same for q and k).
One of scale, not of mathematics: only the experts ``experts_held``, the KV
heads ``kv_heads_held`` with their query groups and the first ``vocab_size``
rows of the vocabulary are here, as on one chip of the deployment the file states.

``precision="int8"`` is the control of "How correct is decided" (PERF.md): every
matrix product's operands rounded to 8-bit integers, forward and backward.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmark import reference as base, weights, weights_laguna
from benchmark.reference_lfm2 import tree  # noqa: F401  (flat names -> the nested tree the program holds; no mathematics)


def spec(config: dict) -> tuple:
    """What the mathematics needs of a configuration's file, hashable: the
    published keys as the file runs them, the router's and the KV heads'
    published counts, and the share held."""
    role, published = config.get("train") or {}, config.get("published") or {}
    kh = config["num_key_value_heads"]
    out = dict(
        d=config["hidden_size"], kh=kh, hd=config["head_dim"], heads=tuple(config["num_attention_heads_per_layer"]),
        f=config["intermediate_size"], fe=config["moe_intermediate_size"], fs=config["shared_expert_intermediate_size"],
        v=config["vocab_size"], eps=float(config["rms_norm_eps"]), window=config["sliding_window"],
        layers=tuple(config["layer_types"]), ffn=tuple(config["mlp_layer_types"]),
        rope=tuple((kind, tuple(sorted(p.items()))) for kind, p in sorted(config["rope_parameters"].items())),
        experts=published.get("num_experts", config["num_experts"]), top_k=config["num_experts_per_tok"],
        scaling=float(config["moe_routed_scaling_factor"]),
        held=tuple(role.get("experts_held") or (0, config["num_experts"])),
        kv_first=(role.get("kv_heads_held") or (0, kh))[0], kv_published=published.get("num_key_value_heads", kh),
    )
    return tuple(sorted(out.items()))


def share(s: dict) -> weights_laguna.Share:
    return weights_laguna.Share(s["hd"], s["held"][0], s["kv_first"], s["kh"], s["kv_published"])


def layer_shapes(s: dict, i: int) -> dict:
    d, f, fe, fs, h, kh, hd = s["d"], s["f"], s["fe"], s["fs"], s["heads"][i], s["kh"], s["hd"]
    out = {"attn_norm/scale": (d,), "attn/q_proj/kernel": (d, h, hd), "attn/k_proj/kernel": (d, kh, hd),
           "attn/v_proj/kernel": (d, kh, hd), "attn/g_proj/kernel": (d, h), "attn/o_proj/kernel": (h * hd, d),
           "mlp_norm/scale": (d,)}
    if s["ffn"][i] == "dense":
        out.update({"mlp/gate_proj/kernel": (d, f), "mlp/up_proj/kernel": (d, f), "mlp/down_proj/kernel": (f, d)})
    else:
        n = s["held"][1] - s["held"][0]
        out.update({"moe/router/kernel": (d, s["experts"]), "moe/moe/gate_proj": (n, d, fe),
                    "moe/moe/up_proj": (n, d, fe), "moe/moe/down_proj": (n, fe, d),
                    "moe/shared_expert/gate_proj/kernel": (d, fs), "moe/shared_expert/up_proj/kernel": (d, fs),
                    "moe/shared_expert/down_proj/kernel": (fs, d)})
    return out


def all_shapes(s: dict) -> dict:
    shapes = {"embed/embedding": (s["v"], s["d"]), "final_norm/scale": (s["d"],), "lm_head/kernel": (s["d"], s["v"])}
    for i in range(len(s["layers"])):
        shapes.update({f"layer_{i}/{n}": shape for n, shape in layer_shapes(s, i).items()})
    return shapes


@functools.partial(jax.jit, static_argnames=("shapes", "held"))
def _make(key, shapes, held):
    return {n: weights_laguna.leaf(key, n, shape, jnp.float32, held) for n, shape in shapes}


def make_weights(s: dict, seed: int, names=None) -> dict:
    shapes = all_shapes(s)
    if names is not None:
        shapes = {n: shapes[n] for n in names}
    return _make(weights.seed_key(seed), tuple(sorted(shapes.items())), share(s))


# ------------------------------------------------------------------ the layers


def rope_table(positions: int, head_dim: int, params: dict):
    """``(cos, sin) [T, rot / 2]`` of one layer kind's ``rope_parameters`` entry,
    ``rot = head_dim * partial_rotary_factor``. Pair ``j`` turns by ``position *
    theta**(-2j / rot)``; with ``rope_type`` ``yarn`` (arXiv:2309.00071) a pair
    that turns more than ``beta_fast`` times over the original context keeps
    that, one that turns fewer than ``beta_slow`` times is slowed by ``factor``,
    the pairs between are blended linearly, and cos and sin carry ``attention_factor``."""
    rot = int(head_dim * params.get("partial_rotary_factor", 1.0))
    theta = float(params["rope_theta"])
    inv = [theta ** (-2.0 * j / rot) for j in range(rot // 2)]
    amplitude = 1.0
    if params.get("rope_type", "default") == "yarn":
        factor, original = float(params["factor"]), params["original_max_position_embeddings"]
        amplitude = float(params.get("attention_factor") or 0.1 * math.log(factor) + 1.0)
        turns_at = lambda turns: rot * math.log(original / (turns * 2 * math.pi)) / (2 * math.log(theta))  # the pair that turns so often
        low, high = max(math.floor(turns_at(params["beta_fast"])), 0), min(math.ceil(turns_at(params["beta_slow"])), rot - 1)
        for j in range(rot // 2):
            slowed = min(max((j - low) / max(high - low, 1e-3), 0.0), 1.0)
            inv[j] = inv[j] * (1.0 - slowed) + inv[j] / factor * slowed
    elif params.get("rope_type", "default") != "default":
        raise ValueError(f"no such rope_type here: {params['rope_type']!r}")
    ang = jnp.arange(positions, dtype=jnp.float32)[:, None] * jnp.asarray(inv, jnp.float32)[None, :]
    return jnp.cos(ang) * amplitude, jnp.sin(ang) * amplitude


def rope(x, cos, sin):
    """x [B, T, H, D]: the pairs (2j, 2j+1) of the first ``2 * cos.shape[-1]`` dimensions rotated, the rest as they are."""
    rot = 2 * cos.shape[-1]
    cos, sin = cos[None, :, None, :], sin[None, :, None, :]
    a, b = x[..., 0:rot:2], x[..., 1:rot:2]
    turned = jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1).reshape(*x.shape[:-1], rot)
    return jnp.concatenate([turned, x[..., rot:]], axis=-1)


def attention_op(u, w, s, i, precision):
    kind, h, hd = s["layers"][i], s["heads"][i], s["hd"]
    cos, sin = rope_table(u.shape[1], hd, dict(dict(s["rope"])[kind]))
    q = rope(base.matmul(u, w["attn/q_proj/kernel"], precision), cos, sin)
    k = rope(base.matmul(u, w["attn/k_proj/kernel"], precision), cos, sin)
    v = base.matmul(u, w["attn/v_proj/kernel"], precision)
    out = base.attention_blocks(q, k, v, s["window"] if kind == "sliding_attention" else None)  # [B, T, H * D]
    gate = jax.nn.sigmoid(base.matmul(u, w["attn/g_proj/kernel"], precision))  # [B, T, H]
    out = (out.reshape(*out.shape[:2], h, hd) * gate[..., None]).reshape(out.shape)
    return base.matmul(out, w["attn/o_proj/kernel"], precision)


def swiglu(u, w1, w3, w2, precision):
    return base.matmul(jax.nn.silu(base.matmul(u, w1, precision)) * base.matmul(u, w3, precision), w2, precision)


def route(logits, s):
    """``(chosen [.., k], weights [.., k])`` of the router's float32 ``logits [.., E]``."""
    p = jax.nn.softmax(logits, axis=-1)
    g, chosen = jax.lax.top_k(p, s["top_k"])
    return chosen, g / jnp.sum(g, -1, keepdims=True) * s["scaling"]


def expert_layer(u, w, s, precision, shared=True):
    """The held experts' part of the expert layer, each held expert computed
    for every token and masked to the tokens that chose it, and (``shared``)
    the shared expert, which every holder of the layer computes alike."""
    chosen, g = route(base.matmul(u, w["moe/router/kernel"], precision), s)

    @jax.checkpoint
    def one(out, expert):  # a loop over the held experts, one body for all of them (``jax.lax.scan``: a shorter program to compile)
        e, w1, w3, w2 = expert
        weight = jnp.sum(jnp.where(chosen == e, g, 0.0), axis=-1)  # 0 where the token did not choose e
        return out + weight[..., None] * swiglu(u, w1, w3, w2, precision), None

    held = jnp.arange(*s["held"])
    out, _ = jax.lax.scan(one, jnp.zeros_like(u), (held, w["moe/moe/gate_proj"], w["moe/moe/up_proj"], w["moe/moe/down_proj"]))
    if shared:
        out = out + swiglu(u, w["moe/shared_expert/gate_proj/kernel"], w["moe/shared_expert/up_proj/kernel"],
                           w["moe/shared_expert/down_proj/kernel"], precision)
    return out


def block(x, w, s, i, precision):
    h = x + attention_op(base.rms_norm(x, w["attn_norm/scale"], s["eps"]), w, s, i, precision)
    u = base.rms_norm(h, w["mlp_norm/scale"], s["eps"])
    if s["ffn"][i] == "dense":
        return h + swiglu(u, w["mlp/gate_proj/kernel"], w["mlp/up_proj/kernel"], w["mlp/down_proj/kernel"], precision)
    return h + expert_layer(u, w, s, precision)


def layer_of(params: dict, i: int) -> dict:
    prefix = f"layer_{i}/"
    return {n[len(prefix):]: x for n, x in params.items() if n.startswith(prefix)}


def hidden(params, tokens, s, precision):
    x = params["embed/embedding"][tokens]
    for i in range(len(s["layers"])):
        x = jax.checkpoint(functools.partial(block, s=s, i=i, precision=precision))(x, layer_of(params, i))
    return base.rms_norm(x, params["final_norm/scale"], s["eps"])


@functools.partial(jax.jit, static_argnames=("spec_items", "precision"))
def logits(params, tokens, spec_items, precision="reference"):
    """tokens [B, T] -> logits [B, T, V] (the tests' forward; a step uses ``lm_loss``)."""
    return base.matmul(hidden(params, tokens, dict(spec_items), precision), params["lm_head/kernel"], precision)


def lm_loss(params, tokens, s, precision):
    """Mean next-token cross entropy over rows x (T - 1) positions."""
    x = hidden(params, tokens, s, precision)

    @jax.checkpoint
    def chunk_loss(xc, targets):
        lg = base.matmul(xc, params["lm_head/kernel"], precision)
        return (jax.nn.logsumexp(lg, axis=-1) - jnp.take_along_axis(lg, targets[..., None], -1)[..., 0]).sum()

    b, t = tokens.shape
    xs, ys = x[:, :-1], tokens[:, 1:]
    step = 1024
    return sum(chunk_loss(xs[:, a : a + step], ys[:, a : a + step]) for a in range(0, t - 1, step)) / (b * (t - 1))


# ------------------------------------------------------------------ training


@functools.partial(jax.jit, static_argnames=("spec_items", "precision", "clip"))
def _loss_and_clipped_grad(params, tokens, spec_items, precision, clip):
    loss, grads = jax.value_and_grad(lm_loss)(params, tokens, dict(spec_items), precision)
    if clip > 0:
        norm2 = sum(jnp.sum(g * g) for g in grads.values())
        scale = jnp.minimum(1.0, clip * jax.lax.rsqrt(jnp.maximum(norm2, 1e-12)))
        grads = {n: g * scale for n, g in grads.items()}
    return loss, grads


def train_steps(config, seed, batches, job, precision="reference", fault=None) -> dict:
    """``reference.train_steps`` for this decoder: follow ``batches`` from the
    seed's weights, and return each step's loss, the norm of every leaf of the
    first gradient as the optimizer gets it, and of every leaf's change after
    the last step. ``fault="half_batch"`` leaves the second half of each row's
    positions out."""
    items = spec(config)
    s = dict(items)
    with jax.default_matmul_precision("highest"):
        params = make_weights(s, seed)
        mu = jax.tree_util.tree_map(jnp.zeros_like, params)
        nu = jax.tree_util.tree_map(jnp.zeros_like, params)
        o = job["optimizer"]
        out = {"loss": [], "grad_norm": None, "delta_norm": None}
        for t, tokens in enumerate(batches):
            tokens = jnp.asarray(tokens)
            if fault == "half_batch":
                tokens = tokens[: tokens.shape[0] // 2] if tokens.shape[0] > 1 else tokens[:, : tokens.shape[1] // 2]
            loss, grads = _loss_and_clipped_grad(params, tokens, items, precision, float(job["gradient_clip"]))
            out["loss"].append(float(loss))
            if t == 0:
                out["grad_norm"] = {n: float(v) for n, v in base._norms(grads).items()}
            params, mu, nu = base._adamw(params, mu, nu, grads, base.learning_rate(job, t), float(t + 1),
                                         o["b1"], o["b2"], o["eps"], o["weight_decay"])
        del mu, nu
        out["delta_norm"] = {n: float(v) for n, v in base._delta_norms(params, make_weights(s, seed)).items()}
    return out
