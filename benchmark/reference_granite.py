"""The plain reference of ``granite-4.0-h-micro``: Granite 4.0-H's decoder
(``model_type`` ``granitemoehybrid``, no experts) in straightforward
``jax.numpy``, float32 under ``jax.default_matmul_precision("highest")``, no
kernel and no chunked scan; it imports nothing of the program. Weights come
from ``weights_granite.leaf`` under the program's names.

``h = embed(ids) * embedding_multiplier``. Block ``i``: ``h = h +
residual_multiplier * Mixer_i(RMSNorm(h))``, then ``h = h + residual_multiplier
* MLP(RMSNorm(h))``, both norms with ``rms_norm_eps``; ``MLP(x) = (silu(a) * b)
W_out`` with ``[a | b] = x W_in`` of width ``shared_intermediate_size`` (the
published ``input_linear`` is ``gate_proj`` and ``up_proj`` side by side);
``logits = RMSNorm(h) E^T / logits_scaling`` with ``E`` the embedding.

- ``attention`` layer: grouped-query attention, heads of ``hidden_size /
  (published) num_attention_heads``, no bias, causal, NO position signal
  (``position_embedding_type`` ``nope``), scores times ``attention_multiplier``
  in place of ``1/sqrt(head size)``.
- ``mamba`` layer (Mamba-2): ``[z | x | B | C | dt] = u W_in`` with widths
  ``[d_inner | d_inner | G N | G N | heads]``, ``d_inner = heads x
  mamba_d_head``; ``[x | B | C] = silu(conv1d([x | B | C]) + b)``, depthwise,
  causal, ``mamba_d_conv`` taps, zeros before the sequence; per head ``A =
  -exp(A_log)``, ``dt_t = softplus(dt_t + dt_bias)``; the recurrence TOKEN BY
  TOKEN under ``lax.scan``: ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T``
  (``S`` in ``R^{d_head x d_state}``, 0 before the sequence), ``y_t = S_t C_t +
  D x_t``; ``g = y * silu(z)``, ``g * rsqrt(mean(g^2) + eps) * w`` over all the
  channels held at once; ``out = g W_out``. The scan runs in blocks of
  ``SCAN_BLOCK`` tokens under ``jax.checkpoint`` so that its backward keeps
  ``SCAN_BLOCK`` states a head at a time, not 8192; ``mamba_chunk_size`` is the
  published implementation's way to the same numbers and is not used here.

Departures from the published model, each an assumption of the configuration's
file (``assumed``): the in-projection's column order; ``dt`` unclamped; the
gated norm multiplies by ``silu(z)`` before normalising, over all held channels
at once (on one chip the mean is over the channels held). One of scale, not of
mathematics: only the KV heads ``kv_heads_held`` with their query groups, the
mixer's heads ``mamba_heads_held`` and the first ``vocab_size`` rows of the
vocabulary are here, as on one chip of the deployment the file states; B, C,
the MLP and the norms are whole.

``precision="int8"`` is the control of "How correct is decided" (PERF.md): every
matrix product's operands rounded to 8-bit integers, forward and backward.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark import reference as base, weights, weights_granite
from benchmark.reference_laguna import layer_of, swiglu
from benchmark.reference_lfm2 import tree  # noqa: F401  (flat names -> the nested tree the program holds; no mathematics)

#: tokens to a checkpointed block of the token-by-token scan
SCAN_BLOCK = 256


def spec(config: dict) -> tuple:
    """What the mathematics needs of a configuration's file, hashable: the
    published keys as the file runs them, the published head counts and the
    share held."""
    role, published = config.get("train") or {}, config.get("published") or {}
    kh, mh = config["num_key_value_heads"], config["mamba_n_heads"]
    out = dict(
        d=config["hidden_size"], h=config["num_attention_heads"], kh=kh,
        hd=config["hidden_size"] // published.get("num_attention_heads", config["num_attention_heads"]),
        f=config["shared_intermediate_size"], v=config["vocab_size"], eps=float(config["rms_norm_eps"]),
        layers=tuple(config["layer_types"]), position=config["position_embedding_type"],
        mh=mh, mp=config["mamba_d_head"], n=config["mamba_d_state"], g=config["mamba_n_groups"], taps=config["mamba_d_conv"],
        chunk=config["mamba_chunk_size"],  # the counts' (counts_granite.py), not the reference's
        attn_mult=float(config["attention_multiplier"]), emb_mult=float(config["embedding_multiplier"]),
        res_mult=float(config["residual_multiplier"]), logits_scaling=float(config["logits_scaling"]),
        kv_first=(role.get("kv_heads_held") or (0, kh))[0], kv_published=published.get("num_key_value_heads", kh),
        ssm_first=(role.get("mamba_heads_held") or (0, mh))[0], ssm_published=published.get("mamba_n_heads", mh),
    )
    if out["position"] != "nope" or config.get("num_local_experts"):
        raise ValueError("this reference is of the position-less, expert-less granitemoehybrid decoder")
    return tuple(sorted(out.items()))


def share(s: dict) -> weights_granite.Share:
    return weights_granite.Share(s["hd"], s["mp"], s["kv_first"], s["kh"], s["kv_published"], s["ssm_first"], s["mh"], s["ssm_published"])


def layer_shapes(s: dict, i: int) -> dict:
    d, f, h, kh, hd = s["d"], s["f"], s["h"], s["kh"], s["hd"]
    out = {"mlp_norm/scale": (d,), "mlp/gate_proj/kernel": (d, f), "mlp/up_proj/kernel": (d, f), "mlp/down_proj/kernel": (f, d)}
    if s["layers"][i] == "attention":
        out.update({"attn_norm/scale": (d,), "attn/q_proj/kernel": (d, h, hd), "attn/k_proj/kernel": (d, kh, hd),
                    "attn/v_proj/kernel": (d, kh, hd), "attn/o_proj/kernel": (h * hd, d)})
    else:
        d_inner, gn = s["mh"] * s["mp"], s["g"] * s["n"]
        out.update({"mamba_norm/scale": (d,), "mamba/in_proj/kernel": (d, 2 * d_inner + 2 * gn + s["mh"]),
                    "mamba/conv_weight": (s["taps"], d_inner + 2 * gn), "mamba/conv_bias": (d_inner + 2 * gn,),
                    "mamba/A_log": (s["mh"],), "mamba/dt_bias": (s["mh"],), "mamba/D": (s["mh"],),
                    "mamba/norm_scale": (d_inner,), "mamba/out_proj/kernel": (d_inner, d)})
    return out


def all_shapes(s: dict) -> dict:
    shapes = {"embed/embedding": (s["v"], s["d"]), "final_norm/scale": (s["d"],)}
    for i in range(len(s["layers"])):
        shapes.update({f"layer_{i}/{n}": shape for n, shape in layer_shapes(s, i).items()})
    return shapes


@functools.partial(jax.jit, static_argnames=("shapes", "held"))
def _make(key, shapes, held):
    return {n: weights_granite.leaf(key, n, shape, jnp.float32, held) for n, shape in shapes}


def make_weights(s: dict, seed: int, names=None) -> dict:
    shapes = all_shapes(s)
    if names is not None:
        shapes = {n: shapes[n] for n in names}
    return _make(weights.seed_key(seed), tuple(sorted(shapes.items())), share(s))


# ------------------------------------------------------------------ the layers


def attention_op(u, w, s, precision):
    q = base.matmul(u, w["attn/q_proj/kernel"], precision)
    k = base.matmul(u, w["attn/k_proj/kernel"], precision)
    v = base.matmul(u, w["attn/v_proj/kernel"], precision)
    # ``base.attention_blocks`` divides the scores by sqrt(head size): q carries what makes that attention_multiplier
    out = base.attention_blocks(q * (s["attn_mult"] * s["hd"] ** 0.5), k, v, None)  # [B, T, H * D], no rotation anywhere
    return base.matmul(out, w["attn/o_proj/kernel"], precision)


def recurrence(x, dt, a, b_in, c_in, skip):
    """``x [B, T, H, P]``, ``dt [B, T, H]``, ``a [H]``, ``b_in`` / ``c_in [B, T,
    G, N]``, ``skip [H]`` -> ``y [B, T, H, P]``: one token at a time."""
    bsz, t, h, p = x.shape
    g, n = b_in.shape[2:]
    b_h, c_h = jnp.repeat(b_in, h // g, axis=2), jnp.repeat(c_in, h // g, axis=2)  # each head its group's

    def token(state, inputs):
        x_t, dt_t, b_t, c_t = inputs
        state = jnp.exp(dt_t * a)[..., None, None] * state + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :]
        return state, jnp.sum(state * c_t[:, :, None, :], axis=-1) + skip[:, None] * x_t

    block = SCAN_BLOCK if t % SCAN_BLOCK == 0 else t
    by_block = lambda v: jnp.moveaxis(v, 1, 0).reshape(t // block, block, *v.shape[:1], *v.shape[2:])

    @jax.checkpoint
    def tokens_of_a_block(state, inputs):
        return jax.lax.scan(token, state, inputs)

    _, y = jax.lax.scan(tokens_of_a_block, jnp.zeros((bsz, h, p, n), x.dtype),
                        (by_block(x), by_block(dt), by_block(b_h), by_block(c_h)))
    return jnp.moveaxis(y.reshape(t, bsz, h, p), 0, 1)


def mixer(u, w, s, precision):
    bsz, t, _ = u.shape
    mh, mp, g, n, taps = s["mh"], s["mp"], s["g"], s["n"], s["taps"]
    d_inner = mh * mp
    z, xbc, dt = jnp.split(base.matmul(u, w["mamba/in_proj/kernel"], precision), [d_inner, 2 * d_inner + 2 * g * n], axis=-1)
    padded = jnp.pad(xbc, ((0, 0), (taps - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(w["mamba/conv_weight"][j] * padded[:, j : j + t] for j in range(taps)) + w["mamba/conv_bias"])
    x, b_in, c_in = jnp.split(xbc, [d_inner, d_inner + g * n], axis=-1)
    y = recurrence(x.reshape(bsz, t, mh, mp), jax.nn.softplus(dt + w["mamba/dt_bias"]), -jnp.exp(w["mamba/A_log"]),
                   b_in.reshape(bsz, t, g, n), c_in.reshape(bsz, t, g, n), w["mamba/D"])
    gated = y.reshape(bsz, t, d_inner) * jax.nn.silu(z)
    return base.matmul(base.rms_norm(gated, w["mamba/norm_scale"], s["eps"]), w["mamba/out_proj/kernel"], precision)


def block(x, w, s, i, precision):
    if s["layers"][i] == "attention":
        op = attention_op(base.rms_norm(x, w["attn_norm/scale"], s["eps"]), w, s, precision)
    else:
        op = mixer(base.rms_norm(x, w["mamba_norm/scale"], s["eps"]), w, s, precision)
    h = x + s["res_mult"] * op
    u = base.rms_norm(h, w["mlp_norm/scale"], s["eps"])
    return h + s["res_mult"] * swiglu(u, w["mlp/gate_proj/kernel"], w["mlp/up_proj/kernel"], w["mlp/down_proj/kernel"], precision)


def hidden(params, tokens, s, precision):
    x = params["embed/embedding"][tokens] * s["emb_mult"]
    for i in range(len(s["layers"])):
        x = jax.checkpoint(functools.partial(block, s=s, i=i, precision=precision))(x, layer_of(params, i))
    return base.rms_norm(x, params["final_norm/scale"], s["eps"])


@functools.partial(jax.jit, static_argnames=("spec_items", "precision"))
def logits(params, tokens, spec_items, precision="reference"):
    """tokens [B, T] -> logits [B, T, V] (the tests' forward; a step uses ``lm_loss``)."""
    s = dict(spec_items)
    return base.matmul(hidden(params, tokens, s, precision), params["embed/embedding"].T, precision) / s["logits_scaling"]


def lm_loss(params, tokens, s, precision):
    """Mean next-token cross entropy over rows x (T - 1) positions."""
    x = hidden(params, tokens, s, precision)

    @jax.checkpoint
    def chunk_loss(xc, targets):
        lg = base.matmul(xc, params["embed/embedding"].T, precision) / s["logits_scaling"]  # the logits divided, as published
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
