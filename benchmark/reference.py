"""The plain reference: the published decoder in straightforward ``jax.numpy``.

Mistral-family decoder as Mistral AI's reference code (``mistral-src``,
``model.py``) and the configurations' ``config.json`` describe it: token
embedding; per layer RMSNorm (``rms_norm_eps``), grouped-query attention with
rotary positions on interleaved pairs (the complex-number form of the
reference code), causal and limited to ``sliding_window`` keys where the
configuration has one, output projection, RMSNorm, SwiGLU; a final RMSNorm and
an untied head. No kernel, no cache, no batching across requests' positions:
one full forward over each row.

It imports nothing of the program and takes nothing the program made. Weights
come from ``weights.leaf`` under the same names the drivers use, one layer at a
time, so sixteen float32 layers never sit on the chip together. Arithmetic is
float32 with ``jax.default_matmul_precision("highest")``; attention runs in
blocks of queries so that a row of 4k tokens fits.

``precision="int8"`` is the control of "How correct is decided": the same
mathematics with every matrix product's two operands rounded to 8-bit integers
(weights per output channel, activations per row, symmetric), the step below
the bfloat16 the configurations state.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import weights

Q_BLOCK = 512


def layer_shapes(hf: dict) -> dict:
    d, h, kh = hf["hidden_size"], hf["num_attention_heads"], hf["num_key_value_heads"]
    hd = hf.get("head_dim") or d // h
    f = hf["intermediate_size"]
    return {
        "attn_norm/scale": (d,), "mlp_norm/scale": (d,),
        "attn/q_proj/kernel": (d, h, hd), "attn/k_proj/kernel": (d, kh, hd), "attn/v_proj/kernel": (d, kh, hd),
        "attn/o_proj/kernel": (h * hd, d),
        "mlp/gate_proj/kernel": (d, f), "mlp/up_proj/kernel": (d, f), "mlp/down_proj/kernel": (f, d),
    }


def top_shapes(hf: dict) -> dict:
    d, v = hf["hidden_size"], hf["vocab_size"]
    return {"embed/embedding": (v, d), "final_norm/scale": (d,), "lm_head/kernel": (d, v)}


@functools.partial(jax.jit, static_argnames=("prefix", "shapes", "held"))
def _make(key, prefix, shapes, held):
    """Leaves as the program holds them (``held``), then widened to float32."""
    return {n: weights.leaf(key, prefix + n, s, held).astype(jnp.float32) for n, s in shapes}


def layer_weights(hf, seed, i, held):
    return _make(weights.seed_key(seed), f"layer_{i}/", tuple(sorted(layer_shapes(hf).items())), held)


def top_weights(hf, seed, held):
    return _make(weights.seed_key(seed), "", tuple(sorted(top_shapes(hf).items())), held)


def fake_int8(x, axis):
    """Round to 127 symmetric levels along ``axis`` and back."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.round(x / scale) * scale


@jax.custom_vjp
def int8_matmul(x, w):
    """``x [M, K] @ w [K, N]`` as an 8-bit integer path would compute it,
    forward and backward: every product's two operands rounded to 127 levels
    (an activation or a gradient per row, a weight per output channel)."""
    return jnp.matmul(fake_int8(x, -1), fake_int8(w, 0), precision="highest")


def _int8_matmul_fwd(x, w):
    return int8_matmul(x, w), (x, w)


def _int8_matmul_bwd(saved, dy):
    x, w = saved
    dy = fake_int8(dy, -1)
    dx = jnp.matmul(dy, fake_int8(w, 0).T, precision="highest")
    dw = jnp.matmul(fake_int8(x, -1).T, dy, precision="highest")
    return dx, dw


int8_matmul.defvjp(_int8_matmul_fwd, _int8_matmul_bwd)


def matmul(x, w, precision):
    """``x [..., K] @ w [K, ...]`` over the first axis of ``w``."""
    w2 = w.reshape(w.shape[0], -1)
    if precision == "int8":
        y = int8_matmul(x.reshape(-1, x.shape[-1]), w2)
    else:
        y = jnp.matmul(x, w2, precision="highest")
    return y.reshape(*x.shape[:-1], *w.shape[1:])


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def rope(x, theta):
    """x [B, T, H, D]: rotate the pairs (2i, 2i+1) by position x theta**(-2i/D)."""
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1).reshape(x.shape)


def attention(q, k, v, window):
    """q [B,T,H,D], k/v [B,T,K,D] -> [B,T,H*D]; causal, ``window`` keys at most."""
    b, t, h, d = q.shape
    kh = k.shape[2]
    q = q.reshape(b, t, kh, h // kh, d)
    k_pos = jnp.arange(t)
    out = []
    for start in range(0, t, Q_BLOCK):
        qb = q[:, start : start + Q_BLOCK]
        q_pos = start + jnp.arange(qb.shape[1])
        keep = k_pos[None, :] <= q_pos[:, None]
        if window is not None:
            keep &= (q_pos[:, None] - k_pos[None, :]) < window
        s = jnp.einsum("btkgd,bskd->bkgts", qb, k, precision="highest") / jnp.sqrt(jnp.float32(d))
        p = jax.nn.softmax(jnp.where(keep[None, None, None], s, -jnp.inf), axis=-1)
        out.append(jnp.einsum("bkgts,bskd->btkgd", p, v, precision="highest"))
    return jnp.concatenate(out, axis=1).reshape(b, t, h * d)


@functools.partial(jax.jit, static_argnames=("theta", "window", "eps", "precision"))
def layer(x, w, *, theta, window, eps, precision):
    h = rms_norm(x, w["attn_norm/scale"], eps)
    q = rope(matmul(h, w["attn/q_proj/kernel"], precision), theta)
    k = rope(matmul(h, w["attn/k_proj/kernel"], precision), theta)
    v = matmul(h, w["attn/v_proj/kernel"], precision)
    x = x + matmul(attention(q, k, v, window), w["attn/o_proj/kernel"], precision)
    h = rms_norm(x, w["mlp_norm/scale"], eps)
    gate = matmul(h, w["mlp/gate_proj/kernel"], precision)
    up = matmul(h, w["mlp/up_proj/kernel"], precision)
    return x + matmul(jax.nn.silu(gate) * up, w["mlp/down_proj/kernel"], precision)


def layer_kwargs(hf, precision):
    return dict(theta=float(hf["rope_theta"]), window=hf.get("sliding_window"),
                eps=float(hf["rms_norm_eps"]), precision=precision)


def hidden_states(hf, num_layers, seed, tokens, precision="reference", held=jnp.bfloat16):
    """tokens [B, T] -> the last layer's output [B, T, D], before the final norm."""
    top = top_weights(hf, seed, held)
    x = top["embed/embedding"][tokens]
    for i in range(num_layers):
        x = layer(x, layer_weights(hf, seed, i, held), **layer_kwargs(hf, precision))
    return x, top


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _logits(x, top, *, eps, precision):
    return matmul(rms_norm(x, top["final_norm/scale"], eps), top["lm_head/kernel"], precision)


def served_logits(hf, num_layers, seed, sample, precision="reference", rows_per_block=4):
    """For each (prompt, served tokens) of ``sample``: the logits [n_served, V]
    that each served token was chosen from, from one full forward over prompt +
    served tokens (causal, so the right padding to the block's common length
    changes nothing before it)."""
    out = [None] * len(sample)
    order = sorted(range(len(sample)), key=lambda i: -(len(sample[i][0]) + len(sample[i][1])))
    for at in range(0, len(order), rows_per_block):
        block = order[at : at + rows_per_block]
        longest = max(len(sample[i][0]) + len(sample[i][1]) for i in block)
        t = -(-longest // Q_BLOCK) * Q_BLOCK
        toks = np.zeros((len(block), t), np.int32)
        for row, i in enumerate(block):
            seq = np.concatenate([sample[i][0], sample[i][1]])
            toks[row, : len(seq)] = seq
        x, top = hidden_states(hf, num_layers, seed, jnp.asarray(toks), precision)
        for row, i in enumerate(block):
            first = len(sample[i][0]) - 1  # the position whose logits gave served token 0
            n = len(sample[i][1])
            rows = jax.lax.dynamic_slice_in_dim(x[row], first, min(-(-n // 64) * 64, t - first))  # few shapes to compile
            out[i] = np.asarray(_logits(rows, top, eps=float(hf["rms_norm_eps"]), precision=precision))[:n]
    return out


def served_token_gaps(hf, num_layers, seed, sample, precision="reference"):
    """Per request, per served token: the reference's best logit at that
    position minus its logit of the served token. With ``precision`` other than
    "reference" the token judged is the one that precision puts first at each
    position of the same prompts and tokens (the control need not decode)."""
    full = served_logits(hf, num_layers, seed, sample)
    judged = [s[1] for s in sample]
    if precision != "reference":
        judged = [lg.argmax(-1) for lg in served_logits(hf, num_layers, seed, sample, precision)]
    return [lg.max(-1) - lg[np.arange(len(tok)), tok] for lg, tok in zip(full, judged)]


# ------------------------------------------------------------------ training
#
# The same decoder with its loss, its gradient and three steps of AdamW, as
# the job's file states them: next-token cross entropy averaged over every
# position but the last of each row; the gradient scaled to a global norm of
# ``gradient_clip`` at most; AdamW (decoupled weight decay on every leaf, bias
# correction, no eps_root) on float32 weights; a learning rate that rises
# linearly from ``init_lr`` to ``peak_lr`` over ``warmup_steps``.
# Attention runs a block of queries at a time under ``jax.checkpoint`` and
# each layer is checkpointed, so that a step of 8k tokens fits beside the
# weights, the gradient and the two moments.


def train_shapes(hf, num_layers) -> dict:
    shapes = dict(top_shapes(hf))
    for i in range(num_layers):
        shapes.update({f"layer_{i}/{n}": s for n, s in layer_shapes(hf).items()})
    return shapes


@functools.partial(jax.jit, static_argnames=("shapes",))
def _train_weights(key, shapes):
    return {n: weights.leaf(key, n, s, jnp.float32) for n, s in shapes}


def attention_blocks(q, k, v, window):
    """``attention`` with one block of queries live at a time, forward and backward."""
    b, t, h, d = q.shape
    kh = k.shape[2]
    nblk = t // Q_BLOCK if t % Q_BLOCK == 0 else None
    if nblk is None or nblk <= 1:
        return attention(q, k, v, window)
    qb = q.reshape(b, nblk, Q_BLOCK, kh, h // kh, d).transpose(1, 0, 2, 3, 4, 5)
    k_pos = jnp.arange(t)

    @jax.checkpoint
    def one(args):
        qi, start = args
        q_pos = start + jnp.arange(Q_BLOCK)
        keep = k_pos[None, :] <= q_pos[:, None]
        if window is not None:
            keep &= (q_pos[:, None] - k_pos[None, :]) < window
        s = jnp.einsum("btkgd,bskd->bkgts", qi, k, precision="highest") / jnp.sqrt(jnp.float32(d))
        p = jax.nn.softmax(jnp.where(keep[None, None, None], s, -jnp.inf), axis=-1)
        return jnp.einsum("bkgts,bskd->btkgd", p, v, precision="highest")

    out = jax.lax.map(one, (qb, jnp.arange(nblk) * Q_BLOCK))
    return out.transpose(1, 0, 2, 3, 4, 5).reshape(b, t, h * d)


def train_layer(x, w, *, theta, window, eps, precision):
    h = rms_norm(x, w["attn_norm/scale"], eps)
    q = rope(matmul(h, w["attn/q_proj/kernel"], precision), theta)
    k = rope(matmul(h, w["attn/k_proj/kernel"], precision), theta)
    v = matmul(h, w["attn/v_proj/kernel"], precision)
    x = x + matmul(attention_blocks(q, k, v, window), w["attn/o_proj/kernel"], precision)
    h = rms_norm(x, w["mlp_norm/scale"], eps)
    gate = matmul(h, w["mlp/gate_proj/kernel"], precision)
    up = matmul(h, w["mlp/up_proj/kernel"], precision)
    return x + matmul(jax.nn.silu(gate) * up, w["mlp/down_proj/kernel"], precision)


def lm_loss(params, tokens, hf, num_layers, precision, rows=None):
    """Mean next-token cross entropy of ``tokens`` [B, T] over rows x (T - 1)
    positions; ``rows`` (a fault for the tests) keeps only the first rows."""
    kw = layer_kwargs(hf, precision)
    if rows is not None:
        tokens = tokens[:rows]
    x = params["embed/embedding"][tokens]
    for i in range(num_layers):
        w = {n: params[f"layer_{i}/{n}"] for n in layer_shapes(hf)}
        x = jax.checkpoint(functools.partial(train_layer, **kw))(x, w)
    x = rms_norm(x, params["final_norm/scale"], kw["eps"])

    @jax.checkpoint
    def chunk_loss(xc, targets):
        logits = matmul(xc, params["lm_head/kernel"], precision)
        return (jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(logits, targets[..., None], -1)[..., 0]).sum()

    b, t = tokens.shape
    xs, ys = x[:, :-1], tokens[:, 1:]
    step = 1024
    total = sum(chunk_loss(xs[:, a : a + step], ys[:, a : a + step]) for a in range(0, t - 1, step))
    return total / (b * (t - 1))


def learning_rate(job, step: int) -> float:
    o = job["optimizer"]
    if step >= o["warmup_steps"]:
        raise ValueError("the reference follows the first steps only, inside the warm-up")
    return o["init_lr"] + (o["peak_lr"] - o["init_lr"]) * step / o["warmup_steps"]


@functools.partial(jax.jit, static_argnames=("hf_items", "num_layers", "precision", "clip", "rows"))
def _loss_and_clipped_grad(params, tokens, hf_items, num_layers, precision, clip, rows):
    hf = dict(hf_items)
    loss, grads = jax.value_and_grad(lm_loss)(params, tokens, hf, num_layers, precision, rows)
    if clip > 0:
        norm2 = sum(jnp.sum(g * g) for g in grads.values())
        scale = jnp.minimum(1.0, clip * jax.lax.rsqrt(jnp.maximum(norm2, 1e-12)))
        grads = {n: g * scale for n, g in grads.items()}
    return loss, grads


@functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3))
def _adamw(params, mu, nu, grads, lr, t, b1, b2, eps, wd):
    def one(p, m, v, g):
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        update = (m / (1 - b1**t)) / (jnp.sqrt(v / (1 - b2**t)) + eps) + wd * p
        return p - lr * update, m, v

    out = {n: one(params[n], mu[n], nu[n], grads[n]) for n in params}
    return ({n: o[0] for n, o in out.items()}, {n: o[1] for n, o in out.items()}, {n: o[2] for n, o in out.items()})


@jax.jit
def _norms(tree):
    return {n: jnp.sqrt(jnp.sum(x.astype(jnp.float32) ** 2)) for n, x in tree.items()}


@jax.jit
def _delta_norms(a, b):
    return {n: jnp.sqrt(jnp.sum((a[n] - b[n]) ** 2)) for n in a}


def train_steps(hf, num_layers, seed, batches, job, precision="reference", fault=None) -> dict:
    """Follow ``batches`` (the first three the program was fed) from the seed's
    weights: each step's loss, the norm of every leaf of the first gradient as
    the optimizer gets it, and of every leaf's change after the last step.

    ``fault`` plants, for the tests and the readings of "How correct is
    decided", what a broken program would do: ``"unchanged_state"`` applies no
    update, ``"half_batch"`` leaves the second half of each row's positions (of
    the rows, where there are several) out of the loss and takes the mean over
    the rest."""
    hf_num = {k: v for k, v in hf.items() if isinstance(v, (int, float)) or v is None}
    shapes = tuple(sorted(train_shapes(hf_num, num_layers).items()))
    key = weights.seed_key(seed)
    params = _train_weights(key, shapes)
    mu = jax.tree_util.tree_map(jnp.zeros_like, params)
    nu = jax.tree_util.tree_map(jnp.zeros_like, params)
    o = job["optimizer"]
    out = {"loss": [], "grad_norm": None, "delta_norm": None}
    for t, tokens in enumerate(batches):
        tokens = jnp.asarray(tokens)
        rows = None
        if fault == "half_batch":
            if tokens.shape[0] > 1:
                rows = tokens.shape[0] // 2
            else:
                tokens = tokens[:, : tokens.shape[1] // 2]
        loss, grads = _loss_and_clipped_grad(params, tokens, tuple(sorted(hf_num.items())), num_layers, precision,
                                             float(job["gradient_clip"]), rows)
        out["loss"].append(float(loss))
        if t == 0:
            out["grad_norm"] = {n: float(v) for n, v in _norms(grads).items()}
        if fault == "unchanged_state":
            continue
        params, mu, nu = _adamw(params, mu, nu, grads, learning_rate(job, t), float(t + 1),
                                o["b1"], o["b2"], o["eps"], o["weight_decay"])
    del mu, nu  # room for the weights the steps started from, made again from the seed
    out["delta_norm"] = {n: float(v) for n, v in _delta_norms(params, _train_weights(key, shapes)).items()}
    return out
