"""Decoder-only transformer LM (Llama-style) — the BASELINE.json
"Llama-3-8B pretrain (FSDP -> pjit named-sharding)" config family, built
TPU-first:

- RMSNorm (fp32 accumulation), rotary position embeddings, grouped-query
  attention, SwiGLU MLP — the modern decoder recipe.
- bf16 activations / fp32 params; every matmul shaped for the MXU.
- Tensor parallelism is expressed as data, not code: ``partition_rules()``
  returns T5X-style (regex -> PartitionSpec) rules that shard attention heads
  and MLP hidden over the ``model`` axis and everything else over ``fsdp``.
  XLA inserts the all-reduces; no Megatron-style manual f/g collectives.
- Attention pluggability: ``attn_impl`` picks 'dot' (reference einsum path),
  'flash' (Pallas TPU kernel, ops/flash_attention.py), or 'ring'
  (sequence-parallel ring attention over the ``seq`` axis,
  ops/ring_attention.py) — the long-context path.
"""

from __future__ import annotations

import contextlib
import contextvars
import math

from dataclasses import dataclass, field
from typing import Any, NamedTuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P


#: the operators a block can have, by the names published configs give them
LAYER_KINDS = ("full_attention", "sliding_attention", "conv", "mamba", "mla")


class LayerAttention(NamedTuple):
    """What one attention layer is told of itself (``TransformerConfig.attention_layer``)."""

    kind: str  # "full_attention" | "sliding_attention" | "mla"
    num_heads: int  # query heads; the KV heads are the model's
    window: int | None
    rope: tuple  # (theta, scaling, partial_rotary_factor): the key of its rotary table


@dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    num_layers: int = 8
    num_heads: int = 8
    num_kv_heads: int | None = None  # None => MHA; < num_heads => GQA
    head_dim: int = 64
    hidden_dim: int = 512
    mlp_dim: int = 1408  # ~8/3 * hidden, SwiGLU convention
    max_seq_len: int = 2048
    rope_theta: float = 10000.0
    # RoPE context-extension scaling, as a HASHABLE tuple (the config is a
    # jit-static aux of the model): ("linear", factor) or
    # ("llama3", factor, low_freq_factor, high_freq_factor, original_len).
    rope_scaling: tuple | None = None
    # A rotary table per layer kind, where a config publishes ``rope_parameters``
    # keyed by ``layer_types``' names: ``((kind, theta, scaling, partial_rotary_factor),
    # ...)``, hashable; ``partial_rotary_factor`` is the share of the head that
    # rotates, from its first element on. A kind it does not name takes the two
    # fields above and rotates the whole head.
    rope_parameters: tuple | None = None
    dtype: Any = jnp.bfloat16
    tie_embeddings: bool = False
    attn_impl: str = "dot"  # 'dot' | 'flash' | 'ring'
    # Sliding-window attention (Mistral convention): each token attends to
    # itself + the previous W-1. Supported by every impl: 'dot'/'flash'
    # (stale K/V blocks skipped — O(T*W) compute), 'ring' (the ring visits
    # only 1 + ceil((W-1)/Tl) blocks — O(W) communication), and the decode
    # cache. With ``layer_types`` None it is the whole model's; with
    # ``layer_types`` it is the window of the ``sliding_attention`` layers, and
    # the ``full_attention`` layers have none (``attention_layer``).
    sliding_window: int | None = None
    # query heads of each layer (published as ``num_attention_heads_per_layer``); None = ``num_heads`` everywhere
    num_heads_per_layer: tuple[int, ...] | None = None
    # "per-head": each head's attention output is multiplied by ``sigmoid(x @ W_g)``, one
    # scalar a head from the block's normed input, before ``o_proj``. None = no gate.
    gating: str | None = None
    # RMSNorm's epsilon, every norm of the model (published as rms_norm_eps / norm_eps)
    norm_eps: float = 1e-6
    # RMSNorm with a learned scale over the head dimension of q and of k, before RoPE
    qk_norm: bool = False
    # The operator of each block, by the published names: "full_attention" and
    # "sliding_attention" (Attention, without and with the window) or "conv"
    # (ShortConv, the gated short convolution of the LFM2 family, kernel length
    # ``conv_L_cache``) or "mamba" (Mamba2Mixer, the state-space mixer of the
    # granitemoehybrid family) or "mla" (LatentAttention, the DeepSeek-V2/V3
    # family's latent attention). None = attention everywhere.
    layer_types: tuple[str, ...] | None = None
    conv_L_cache: int = 3
    # The "mamba" layers, under their published names: heads of ``mamba_d_head``
    # channels (``d_inner`` is their product: a share of the heads needs no other
    # code), each with a state ``mamba_d_head x mamba_d_state``; ``mamba_n_groups``
    # groups of heads share B and C; a causal depthwise conv of ``mamba_d_conv``
    # taps; ``mamba_chunk_size`` tokens to a chunk of the scan (ops/ssd.py).
    mamba_n_heads: int = 0
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_n_groups: int = 1
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 256
    # "rope": q and k rotate by position. "nope": no position signal at all in
    # attention (published as position_embedding_type; the mamba layers order the tokens).
    position_embedding: str = "rope"
    # attention's scores times this in place of 1/sqrt(head_dim); None = 1/sqrt(head_dim)
    attention_multiplier: float | None = None
    # h = embed(ids) * embedding_multiplier; every residual branch times
    # residual_multiplier before it is added; logits / logits_scaling. 1 = not there.
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    # Mesh axis over which each layer's heads are shared when the step runs under
    # shard_map (or vmap) with explicit collectives: the operator's partial result
    # (o_proj's, out_proj's) is summed over it, and so are the sum of squares and the
    # channel count of the mamba layer's gated norm. None on one chip, and under plain
    # jit, where XLA places the sums the partition rules imply.
    heads_axis: str | None = None
    # MoE (models/moe.py): with ``num_experts`` > 0 every block after the first
    # ``num_dense_layers`` has the dropless expert layer in the dense MLP's
    # place. ``num_experts`` is the router's width; ``experts_held = (a, b)``
    # keeps experts [a, b) only, one chip's share of an expert-parallel layer
    # (None = all). Names and meanings are the published configs'. Experts
    # shard over the ``expert`` mesh axis via moe_partition_rules().
    num_experts: int = 0
    num_dense_layers: int = 0
    num_experts_per_tok: int = 2
    moe_intermediate_size: int | None = None  # one expert's width; None = mlp_dim
    use_expert_bias: bool = False
    scoring_func: str = "sigmoid"  # how the router's logits become scores: "sigmoid" | "softmax" (over all experts)
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    shared_expert_intermediate_size: int = 0  # > 0: one SwiGLU of this width on every token, added to the routed experts'
    experts_held: tuple[int, int] | None = None
    seq_axis: str = "seq"  # mesh axis used when attn_impl == 'ring'
    # Mesh for attn_impl='ring' and 'flash' and for the "mamba" layers' scan
    # under plain jit: each wraps itself in shard_map over it (ring to split
    # the sequence; flash and the scan because XLA cannot partition a Pallas
    # kernel: on a multi-device TPU mesh flash does not compile without, and
    # the scan keeps its plain form wherever the process sees several
    # devices). Leave None when the step is already shard_mapped.
    mesh: Any = None
    # Residual-stream sharding constraint ([B, T, D] activations), applied
    # after the embedding and every block. Pin this (e.g. a NamedSharding of
    # P(('data','fsdp'))) on multi-axis meshes so XLA's sharding propagation
    # keeps one layout instead of involuntarily rematerialising between
    # conflicting choices. None = let XLA decide (fine on 1-axis meshes).
    act_sharding: Any = None
    # Gradient rematerialisation: recompute each block in the backward pass
    # instead of saving its activations — trades ~1/3 more FLOPs for O(1)
    # blocks of live activation memory, the standard lever for long-context
    # training (composes with flash/ring attention, which already avoid the
    # [T, S] score matrix). A block keeps its input; under a stage's traced
    # train step the first dense-MLP blocks also keep their MLP's two products
    # (``mlp_gate``, ``mlp_up``: 2 x B x T x mlp_dim a block), as many as the
    # device's free bytes less a fifth of its memory hold (``remat_kept_blocks``,
    # ``RematRoom``); none where the device reports no memory.
    remat: bool = False
    # The "mla" layers, under their published names: q from a latent of
    # ``q_lora_rank``, k and v from one latent of ``kv_lora_rank`` beside a
    # ``qk_rope_head_dim`` key all heads share; q and k are ``qk_nope_head_dim +
    # qk_rope_head_dim`` wide a head, v ``v_head_dim``. The heads held are
    # ``num_heads`` (a share needs no other code); ``attention_multiplier`` is
    # the published scale of the scores.
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    # Manifold-constrained hyper-connections (arXiv:2512.24880): with ``hc_mult``
    # n > 1 the residual stream is n streams of ``hidden_dim`` and every sublayer
    # reads and writes them through its own maps (``HyperConnection``), the
    # residual mix a Sinkhorn-Knopp projection (``hc_sinkhorn_iters``, ``hc_eps``)
    # of ``exp`` of its logits clamped to [mhc_h_res_clamp_min, _max]. 1 = x + F(norm(x)).
    hc_mult: int = 1
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    mhc_h_res_clamp_min: float = -30.0
    mhc_h_res_clamp_max: float = 30.0
    # The model's own next-token-prediction module (DeepSeek-V3 section 2.2): with
    # 1, a training forward also returns the logits of token i + 2 from one more
    # block (``NextTokenPrediction``); pair with ``mtp_lm_loss``. 0 = none.
    num_nextn_predict_layers: int = 0

    def __post_init__(self):
        if self.attn_impl not in ("dot", "flash", "ring"):
            # a typo here would otherwise silently run the unfused path
            raise ValueError(f"attn_impl must be 'dot', 'flash' or 'ring', got {self.attn_impl!r}")
        if self.sliding_window is not None and self.sliding_window < 1:
            raise ValueError(f"sliding_window must be >= 1, got {self.sliding_window}")
        if self.layer_types is not None:
            if len(self.layer_types) != self.num_layers:
                raise ValueError(f"layer_types names {len(self.layer_types)} layers, num_layers is {self.num_layers}")
            unknown = sorted(set(self.layer_types) - set(LAYER_KINDS))
            if unknown:
                raise ValueError(f"layer_types holds {unknown}; the kinds this model has are {LAYER_KINDS}")
            if "sliding_attention" in self.layer_types and self.sliding_window is None:
                raise ValueError("layer_types names sliding_attention layers and sliding_window is None")
        if self.num_heads_per_layer is not None:
            if len(self.num_heads_per_layer) != self.num_layers:
                raise ValueError(
                    f"num_heads_per_layer names {len(self.num_heads_per_layer)} layers, num_layers is {self.num_layers}")
            if any(h % self.kv_heads for h in self.num_heads_per_layer):
                raise ValueError(f"num_heads_per_layer {self.num_heads_per_layer} are not all multiples of {self.kv_heads} KV heads")
        if self.gating not in (None, "per-head"):
            raise ValueError(f"gating must be None or 'per-head', got {self.gating!r}")
        if self.position_embedding not in ("rope", "nope"):
            raise ValueError(f"position_embedding must be 'rope' or 'nope', got {self.position_embedding!r}")
        if "mamba" in (self.layer_types or ()) and (self.mamba_n_heads < 1 or self.mamba_n_heads % self.mamba_n_groups):
            raise ValueError(f"layer_types names mamba layers and mamba_n_heads is {self.mamba_n_heads} "
                             f"({self.mamba_n_groups} groups)")
        if "mla" in (self.layer_types or ()) and {"full_attention", "sliding_attention"} & set(self.layer_types):
            raise ValueError("a model's attention layers are all latent (mla) or none: the rotary table is one width")
        if self.hc_mult < 1 or self.num_nextn_predict_layers not in (0, 1):
            raise ValueError(f"hc_mult must be >= 1 and num_nextn_predict_layers 0 or 1, got {self.hc_mult}, "
                             f"{self.num_nextn_predict_layers}")

    @property
    def kv_heads(self) -> int:
        return self.num_kv_heads or self.num_heads

    @property
    def rope_dim(self) -> int:
        """The width the rotary tables turn: a latent-attention model's shared key, else the head."""
        return self.qk_rope_head_dim if "mla" in (self.layer_types or ()) else self.head_dim

    def layer_kind(self, i: int) -> str:
        return "full_attention" if self.layer_types is None else self.layer_types[i]

    def attention_layer(self, i: int | None = None) -> LayerAttention:
        """The one place that decides an attention layer's query heads, window
        and rotary table. ``i`` None: the values of a model that names no layers."""
        kind = "full_attention" if i is None else self.layer_kind(i)
        heads = self.num_heads if i is None or self.num_heads_per_layer is None else self.num_heads_per_layer[i]
        windowed = self.layer_types is None or i is None or kind == "sliding_attention"
        rope = (self.rope_theta, self.rope_scaling, 1.0)
        rope = next((tuple(params) for k, *params in self.rope_parameters or () if k == kind), rope)
        return LayerAttention(kind, heads, self.sliding_window if windowed else None, rope)

    def is_expert_layer(self, i: int) -> bool:
        return self.num_experts > 0 and i >= self.num_dense_layers

    def require_attention_only(self, what: str) -> None:
        """Decoding keeps keys and values per sequence and nothing else, in one
        page shape and under one window for the whole model: a layer kind with
        state of another sort (``conv``, ``mamba``: ROADMAP M5), or with a window of its own beside
        layers without (ROADMAP M2), a latent-attention layer (ROADMAP M4), a residual stream of
        several streams or a next-token-prediction head (ROADMAP M10), cannot be served yet."""
        other = sorted({k for k in self.layer_types or () if k != "full_attention"})
        if other:
            why = {"sliding_attention": "a KV cache or pool holds one window and one page shape for the whole model, "
                                        "not a layer kind's own (ROADMAP M2)",
                   "mla": "its cache is a latent of kv_lora_rank and a shared rope key a token, a page shape no KV cache "
                          "or pool has (ROADMAP M4)"}.get(other[0], "their per-sequence state is not a KV cache (ROADMAP M5)")
            raise NotImplementedError(
                f"{what} cannot run a model with layers of kind {other[0]!r}: {why}; only the training path runs them"
            )
        if self.hc_mult > 1:
            raise NotImplementedError(
                f"{what} cannot run a model with hyper-connections (hc_mult {self.hc_mult}): its residual stream is "
                f"{self.hc_mult} streams a token, which no decode step carries (ROADMAP M10); only the training path runs it")
        if self.num_nextn_predict_layers:
            raise NotImplementedError(
                f"{what} cannot run a model's next-token-prediction module (num_nextn_predict_layers "
                f"{self.num_nextn_predict_layers}): its second head reads the next token's embedding, which a decode "
                "step does not have (ROADMAP M6); only the training path runs it")


def llama_partition_rules() -> list[tuple[str, P]]:
    """T5X-style sharding rules for this model family: embeddings and heads
    over ``model`` (tensor parallel), with ``fsdp`` sharding the other large
    axis. Axes missing from the active mesh are dropped automatically
    (parallel/mesh.py make_param_policy). Includes the MoE rules so
    expert-parallel configs shard out of the box."""
    from .moe import moe_partition_rules

    return list(moe_partition_rules()) + [
        # vocab over fsdp, features over model: the token gather then never
        # crosses the model axis (each TP shard gathers its feature slice)
        ("embed/embedding", P("fsdp", "model")),
        ("attn/(q|k|v|g)_proj/kernel", P("fsdp", "model")),
        # latent attention: the two down-projections whole on every holder of the heads, the two up-projections by head
        ("attn/(q_a_proj|kv_a_proj_with_mqa)/kernel", P("fsdp", None)),
        ("attn/(q_b_proj|kv_b_proj)/kernel", P("fsdp", "model")),
        ("attn/o_proj/kernel", P("model", "fsdp")),
        ("mlp/(gate|up)_proj/kernel", P("fsdp", "model")),
        ("mlp/down_proj/kernel", P("model", "fsdp")),
        ("lm_head/kernel", P("fsdp", "model")),
        ("conv/in_proj/kernel", P("fsdp", "model")),
        ("conv/out_proj/kernel", P("model", "fsdp")),
        ("mamba/in_proj/kernel", P("fsdp", "model")),
        ("mamba/out_proj/kernel", P("model", "fsdp")),
        ("mtp/eh_proj/kernel", P("fsdp", None)),
        ("norm", P()),
        (".*", P()),
    ]


class RMSNorm(nn.Module):
    eps: float = 1e-6

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones_init(), (x.shape[-1],), jnp.float32)
        x32 = x.astype(jnp.float32)
        normed = x32 * jax.lax.rsqrt(jnp.mean(x32**2, axis=-1, keepdims=True) + self.eps)
        return (normed * scale).astype(x.dtype)


def rope_frequencies(
    head_dim: int, max_len: int, theta: float, scaling: tuple | None = None, partial_rotary_factor: float = 1.0
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Rotary cos/sin tables ``[max_len, rot / 2]`` over the ``rot = head_dim *
    partial_rotary_factor`` dimensions that rotate; ``scaling`` applies a
    context-extension transform to the base frequencies:

    - ``("linear", factor)`` — positions interpolated by 1/factor;
    - ``("llama3", factor, low_freq_factor, high_freq_factor, orig_len)`` —
      Llama-3's wavelength-banded scheme: high-frequency components kept,
      low-frequency ones divided by ``factor``, a smooth ramp between;
    - ``("yarn", factor, beta_fast, beta_slow, orig_len, attention_factor)`` —
      YaRN (arXiv:2309.00071) as published configs state it: pairs that turn
      more than ``beta_fast`` times over ``orig_len`` positions kept, those that
      turn fewer than ``beta_slow`` times divided by ``factor``, a linear ramp
      over the pairs between; cos and sin times ``attention_factor``.
    """
    rot = int(head_dim * partial_rotary_factor)
    freqs = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    amplitude = None
    if scaling is not None:
        kind = scaling[0]
        if kind == "linear":
            freqs = freqs / float(scaling[1])
        elif kind == "llama3":
            _, factor, low_ff, high_ff, orig_len = scaling
            wavelen = 2.0 * math.pi / freqs
            low_wl = orig_len / float(low_ff)
            high_wl = orig_len / float(high_ff)
            smooth = (orig_len / wavelen - low_ff) / (high_ff - low_ff)
            scaled = jnp.where(
                wavelen > low_wl,
                freqs / factor,  # long wavelengths: fully interpolated
                jnp.where(
                    wavelen < high_wl,
                    freqs,  # short wavelengths: untouched
                    (1 - smooth) * freqs / factor + smooth * freqs,
                ),
            )
            freqs = scaled
        elif kind == "yarn":
            _, factor, beta_fast, beta_slow, orig_len, amplitude = scaling
            # the (fractional) pair that turns ``rotations`` times over the original context
            pair = lambda rotations: rot * math.log(orig_len / (rotations * 2.0 * math.pi)) / (2.0 * math.log(theta))
            low, high = max(math.floor(pair(beta_fast)), 0), min(math.ceil(pair(beta_slow)), rot - 1)
            ramp = jnp.clip((jnp.arange(rot // 2, dtype=jnp.float32) - low) / max(high - low, 1e-3), 0.0, 1.0)
            freqs = freqs * (1.0 - ramp) + freqs / factor * ramp
        else:
            raise ValueError(f"unsupported rope scaling kind {kind!r}")
    t = jnp.arange(max_len, dtype=jnp.float32)
    angles = jnp.outer(t, freqs)  # [T, rot/2]
    if amplitude is not None:
        return jnp.cos(angles) * amplitude, jnp.sin(angles) * amplitude
    return jnp.cos(angles), jnp.sin(angles)


def apply_rope(
    x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray, offset: int = 0, positions: jnp.ndarray | None = None
) -> jnp.ndarray:
    """x: [B, T, H, D]. Rotates pairs (even, odd) of the head dim, of its
    first ``2 * cos.shape[-1]`` elements where the table is narrower than the
    head (``partial_rotary_factor``); the rest passes as it is.
    ``positions`` [B, T] overrides the contiguous ``offset`` window —
    packed rows use it to restart positions at each segment boundary."""
    rot = 2 * cos.shape[-1]
    if rot < x.shape[-1]:
        return jnp.concatenate([apply_rope(x[..., :rot], cos, sin, offset, positions), x[..., rot:]], axis=-1)
    if positions is not None:
        cos = cos[positions][:, :, None, :]  # [B, T, 1, D/2]
        sin = sin[positions][:, :, None, :]
    else:
        seq_len = x.shape[1]
        cos = jax.lax.dynamic_slice_in_dim(cos, offset, seq_len)[None, :, None, :]
        sin = jax.lax.dynamic_slice_in_dim(sin, offset, seq_len)[None, :, None, :]
    x1, x2 = x[..., ::2], x[..., 1::2]
    rotated = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return rotated.reshape(x.shape).astype(x.dtype)


def _window_keep(q_pos, k_pos, window: int) -> jnp.ndarray:
    """The sliding-window predicate, defined ONCE (Mistral convention:
    attend to self + the previous window-1 → ``q_pos - k_pos < window``).
    Broadcasts over whatever position shapes the caller derived."""
    return (q_pos - k_pos) < window


@jax.named_scope("attention")
def _dot_attention(q, k, v, causal: bool = True, mask: jnp.ndarray | None = None, sm_scale: float | None = None):
    """Reference attention: fp32 softmax, bf16 matmuls. q/k:[B,T,H,D] / [B,S,K,D], v:[B,S,K,Dv].
    ``mask`` ([T, S] or [B, T, S] bool, True = attend) REPLACES the causal
    triangle entirely — callers must bake causality into it (the decode path
    does for unwritten KV-cache slots, packed training for segment
    isolation). ``sm_scale`` multiplies the scores in place of ``1/sqrt(D)``."""
    b, t, h, d = q.shape
    s, kh = k.shape[1], k.shape[2]
    group = h // kh
    q = q.reshape(b, t, kh, group, d)
    scores = jnp.einsum("btkgd,bskd->bkgts", q, k).astype(jnp.float32)
    scores = scores / jnp.sqrt(d) if sm_scale is None else scores * sm_scale
    if mask is None and causal:
        mask = jnp.tril(jnp.ones((t, s), dtype=bool), k=s - t)
    if mask is not None:
        if mask.ndim == 2:
            mask = mask[None]  # [B(1), T, S]
        scores = jnp.where(mask[:, None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgts,bskd->btkgd", probs, v)
    return out.reshape(b, t, h, v.shape[-1])


def _flash_attention(cfg: TransformerConfig, layer: LayerAttention, q, k, v, segment_ids=None):
    """The flash path of every training branch: the kernel as it is on one
    device, shard_mapped over ``cfg.mesh`` on several. The kernels of a
    ``sliding_attention`` layer run under a phase of their own."""
    from ..ops.flash_attention import flash_attention, flash_attention_sharded

    kwargs = dict(causal=True, window=layer.window, segment_ids=segment_ids, sm_scale=cfg.attention_multiplier)
    with jax.named_scope("attn_window_kernel" if layer.kind == "sliding_attention" else "attn_kernel"):
        if cfg.mesh is not None and cfg.mesh.size > 1:
            return flash_attention_sharded(q, k, v, cfg.mesh, **kwargs)
        return flash_attention(q, k, v, **kwargs)


def _adapter_add(y, inp, name, adapters):
    """Add the per-row LoRA delta for dense ``name`` when ``adapters``
    carries a stacked pair for it (multi-tenant serving; see
    ``serve.AdapterSet``). ``adapters`` is ``(subtree, ids)`` — the
    lora-init-shaped subtree for the enclosing module and the per-row
    adapter ids — or None. Rows gather their own factors by id; the delta
    is the merge-free ``(x @ a) @ b`` order (``lora.batched_lora_delta``,
    ``b`` pre-scaled by alpha/rank at stacking time)."""
    if adapters is None:
        return y
    from .lora import LoraPair, batched_lora_delta

    sub, ids = adapters
    pair = (sub or {}).get(name)
    if isinstance(pair, dict):
        pair = pair.get("kernel")
    if not isinstance(pair, LoraPair):
        return y
    delta = batched_lora_delta(inp, pair.a[ids], pair.b[ids])
    return y + delta.reshape(y.shape).astype(y.dtype)


class Attention(nn.Module):
    cfg: TransformerConfig
    layer: LayerAttention | None = None  # None: the model-wide values (``cfg.attention_layer()``)

    @nn.compact
    def __call__(
        self, x, cos, sin, cache=None, offset=0, seg_info=None, decode_pad=None, attend_len=None,
        paged=None, adapters=None,
    ):
        from .quant import QuantDenseGeneral

        cfg = self.cfg
        layer = self.layer or cfg.attention_layer()
        num_heads, window = layer.num_heads, layer.window
        # quant-aware: int8 weight-only trees (models/quant.py) feed the
        # matmuls directly, scales applied to the fp32 accumulator
        dense = lambda feats, name: QuantDenseGeneral(
            feats, axis=-1, use_bias=False, dtype=cfg.dtype, param_dtype=jnp.float32, name=name
        )
        b, t, _ = x.shape
        q = _adapter_add(dense((num_heads, cfg.head_dim), "q_proj")(x), x, "q_proj", adapters)
        k = _adapter_add(dense((cfg.kv_heads, cfg.head_dim), "k_proj")(x), x, "k_proj", adapters)
        v = _adapter_add(dense((cfg.kv_heads, cfg.head_dim), "v_proj")(x), x, "v_proj", adapters)
        if cfg.qk_norm:
            q = RMSNorm(eps=cfg.norm_eps, name="q_norm")(q)
            k = RMSNorm(eps=cfg.norm_eps, name="k_norm")(k)

        # "nope": q and k go as they are, whatever the branch
        rotate = (lambda a, **at: apply_rope(a, cos, sin, **at)) if cfg.position_embedding == "rope" else (lambda a, **at: a)
        scale = cfg.attention_multiplier
        if seg_info is None and decode_pad is None and paged is None:
            q = rotate(q, offset=offset)
            k = rotate(k, offset=offset)
        elif paged is not None:
            # paged decode: every row sits at its own absolute position
            # (fill + step offset) — precomputed once in DecoderLM
            _, _, positions = paged
            q = rotate(q, positions=positions)
            k = rotate(k, positions=positions)
        elif decode_pad is not None:
            # left-padded ragged prompts: per-row positions (real tokens
            # count from 0 at each row's first real slot)
            _, positions = decode_pad
            q = rotate(q, positions=positions)
            k = rotate(k, positions=positions)

        new_cache = None
        if seg_info is not None:
            # Packed sequences (precomputed once in DecoderLM): rotary
            # positions restart at each segment's first token and attention
            # is causal AND same-segment (the flash kernel takes the raw ids,
            # the dot path the precomputed mask).
            positions, mask, seg_ids = seg_info
            q = rotate(q, positions=positions)
            k = rotate(k, positions=positions)
            if cfg.attn_impl == "flash":
                out = _flash_attention(cfg, layer, q, k, v, segment_ids=seg_ids)
            else:
                out = _dot_attention(q, k, v, mask=mask, sm_scale=scale)
        elif paged is not None:
            # Paged decode (the serving engine's path): the cache leaves
            # are the POOL pages [num_blocks, block_size, KH, D]. Write the
            # new K/V into the pages each row's block table names, then
            # gather the table back into a contiguous [B, NB*bs, KH, D]
            # view and run the SAME masked attention as the dense path —
            # identical math, memory owned by the pool. Sentinel table
            # entries drop the writes of padded rows and clip the gathers
            # into masked positions (ops/paged_attention.py).
            from ..ops.paged_attention import gather_pages, scatter_tokens

            tables, fill, positions = paged
            k_pool = scatter_tokens(cache["k"], tables, positions, k)
            v_pool = scatter_tokens(cache["v"], tables, positions, v)
            new_cache = {"k": k_pool, "v": v_pool}
            gk = gather_pages(k_pool, tables)
            gv = gather_pages(v_pool, tables)
            kv_pos = jnp.arange(gk.shape[1])[None, None, :]  # [1, 1, L]
            q_pos = positions[:, :, None]  # [B, t, 1] absolute positions
            mask = kv_pos <= q_pos  # causal AND only this row's filled slots
            if window is not None:
                mask = mask & _window_keep(q_pos, kv_pos, window)
            out = _dot_attention(q, gk, gv, mask=mask, sm_scale=scale)
        elif cache is not None:
            # Autoregressive decode: write this call's K/V into the static-
            # shape cache at ``offset`` and attend over the FILLED prefix
            # with the unwritten tail masked out. ``attend_len`` (STATIC,
            # chunk-rounded by the caller — generate.py grows it as the
            # cache fills) bounds the slots actually read, so per-token
            # attention cost scales with fill instead of max_len while
            # every shape stays static for XLA.
            k = jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype), (0, offset, 0, 0))
            v = jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype), (0, offset, 0, 0))
            new_cache = {"k": k, "v": v}
            s = k.shape[1]
            if attend_len is not None and attend_len < s:
                s = int(attend_len)
                k = jax.lax.slice_in_dim(k, 0, s, axis=1)
                v = jax.lax.slice_in_dim(v, 0, s, axis=1)
            q_pos = offset + jnp.arange(t)[:, None]  # [t, 1]
            kv_pos = jnp.arange(s)[None, :]  # [1, s]
            mask = kv_pos <= q_pos  # causal AND only written slots
            if window is not None:
                mask = mask & _window_keep(q_pos, kv_pos, window)
            if decode_pad is not None:
                # left-pad slots hold garbage K/V — mask them per row
                pad_len, _ = decode_pad
                mask = mask[None] & (kv_pos[None] >= pad_len[:, None, None])
            out = _dot_attention(q, k, v, mask=mask, sm_scale=scale)
        elif cfg.attn_impl == "flash":
            out = _flash_attention(cfg, layer, q, k, v)
        elif cfg.attn_impl == "ring":
            with jax.named_scope("attn_kernel"):
                if cfg.mesh is not None:
                    from ..ops.ring_attention import ring_attention_sharded

                    out = ring_attention_sharded(
                        q, k, v, cfg.mesh, axis_name=cfg.seq_axis, causal=True, window=window, sm_scale=scale
                    )
                else:
                    from ..ops.ring_attention import ring_attention

                    out = ring_attention(
                        q, k, v, axis_name=cfg.seq_axis, causal=True, window=window, sm_scale=scale
                    )
        elif window is not None:
            pos = jnp.arange(t)
            q_pos, k_pos = pos[:, None], pos[None, :]
            out = _dot_attention(
                q, k, v, mask=(q_pos >= k_pos) & _window_keep(q_pos, k_pos, window), sm_scale=scale
            )
        else:
            out = _dot_attention(q, k, v, causal=True, sm_scale=scale)

        if cfg.gating == "per-head":
            with jax.named_scope("attn_gate"):
                gate = jax.nn.sigmoid(dense((num_heads,), "g_proj")(x).astype(jnp.float32))  # [B, T, H]
                out = out * gate[..., None].astype(out.dtype)
        out = out.reshape(b, t, num_heads * cfg.head_dim)
        from .quant import QuantDenseGeneral

        proj = QuantDenseGeneral(
            cfg.hidden_dim, use_bias=False, dtype=cfg.dtype, param_dtype=jnp.float32, name="o_proj"
        )(out)
        proj = _adapter_add(proj, out, "o_proj", adapters)
        return proj if new_cache is None else (proj, new_cache)


def rotate_halves(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray) -> jnp.ndarray:
    """x [B, T, H, R] at positions 0..T-1: pair ``j`` is ``(j, j + R / 2)``, the
    half-split convention of the latent-attention family (``apply_rope`` turns
    interleaved pairs); ``cos`` / ``sin`` are ``[max_len, R / 2]``."""
    t = x.shape[1]
    cos, sin = cos[None, :t, None, :], sin[None, :t, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1).astype(x.dtype)


class LatentAttention(nn.Module):
    """Latent attention (MLA, the DeepSeek-V2/V3 form) of a training forward, on
    the ``num_heads`` heads held. With ``x`` the block's normed input:

    ``c_q = RMSNorm(x W_qa)`` (``q_lora_rank``), ``[q_nope | q_rope] = c_q W_qb``
    a head; ``[c_kv | k_r] = x W_kva``, ``[k_nope | v] = RMSNorm(c_kv) W_kvb`` a
    head; ``q = [q_nope | rot(q_rope)]``, ``k = [k_nope | rot(k_r)]`` with the one
    rotated ``k_r`` shared by every head; causal ``softmax(q k^T *
    attention_multiplier) v`` by the flash, dot or ring path (q and k
    ``qk_nope_head_dim + qk_rope_head_dim`` wide, v ``v_head_dim``), then
    ``o_proj``, whose partial sum over a share of the heads the block adds up
    (``heads_axis``). Everything up to the attention call is the profile's phase
    ``mla_latent``. No cache, no packed rows: decoding refuses the kind
    (``TransformerConfig.require_attention_only``)."""

    cfg: TransformerConfig
    layer: LayerAttention | None = None

    @nn.compact
    def __call__(self, x, cos, sin):
        from .quant import QuantDenseGeneral

        cfg = self.cfg
        layer = self.layer or cfg.attention_layer()
        h, nope, rope, dv = layer.num_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
        dense = lambda feats, name: QuantDenseGeneral(
            feats, axis=-1, use_bias=False, dtype=cfg.dtype, param_dtype=jnp.float32, name=name
        )
        norm = lambda name: RMSNorm(eps=cfg.norm_eps, name=name)
        b, t, _ = x.shape
        with jax.named_scope("mla_latent"):
            q = dense((h, nope + rope), "q_b_proj")(norm("q_a_layernorm")(dense(cfg.q_lora_rank, "q_a_proj")(x)))
            latent, k_rope = jnp.split(dense(cfg.kv_lora_rank + rope, "kv_a_proj_with_mqa")(x), [cfg.kv_lora_rank], axis=-1)
            k_nope, v = jnp.split(dense((h, nope + dv), "kv_b_proj")(norm("kv_a_layernorm")(latent)), [nope], axis=-1)
            q = jnp.concatenate([q[..., :nope], rotate_halves(q[..., nope:], cos, sin)], axis=-1)
            k_rope = jnp.broadcast_to(rotate_halves(k_rope[:, :, None, :], cos, sin), (b, t, h, rope))
            k = jnp.concatenate([k_nope, k_rope], axis=-1)
        scale = cfg.attention_multiplier
        if cfg.attn_impl == "flash":
            out = _flash_attention(cfg, layer, q, k, v)
        elif cfg.attn_impl == "ring":
            from ..ops.ring_attention import ring_attention, ring_attention_sharded

            with jax.named_scope("attn_kernel"):
                if cfg.mesh is not None:
                    out = ring_attention_sharded(q, k, v, cfg.mesh, axis_name=cfg.seq_axis, causal=True, sm_scale=scale)
                else:
                    out = ring_attention(q, k, v, axis_name=cfg.seq_axis, causal=True, sm_scale=scale)
        else:
            out = _dot_attention(q, k, v, causal=True, sm_scale=scale)
        return dense(cfg.hidden_dim, "o_proj")(out.reshape(b, t, h * dv))


def sinkhorn_knopp(m: jnp.ndarray, iters: int, eps: float) -> jnp.ndarray:
    """``iters`` rounds over the last two axes of the positive ``m``: each row
    divided by its sum, then each column by its own, ``eps`` added to every sum."""
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)
    return m


class HyperConnection(nn.Module):
    """One sublayer's manifold-constrained hyper-connection (mHC,
    arXiv:2512.24880) over the stream ``X [B, T, n C]`` (``hc_mult`` = n, held
    lane-flat: stream ``j`` is lanes ``[j C, (j + 1) C)``): with
    ``u = vec(X) / rms(vec(X))`` (over all ``n C`` values, no learned scale),
    ``H_pre = sigmoid(a_pre u phi_pre + b_pre)`` (n), ``H_post = 2 sigmoid(a_post u
    phi_post + b_post)`` (n), ``H_res = SinkhornKnopp(exp(clamp(a_res mat(u phi_res)
    + b_res)))`` (n x n), all float32, by ``ops/hyper_connection.mhc_pre``: four
    Pallas kernels with their own backward on one TPU device, the plain form with
    :func:`sinkhorn_knopp`'s rounds everywhere else. Returns the sublayer's input
    ``H_pre X`` ``[B, T, C]`` and what :func:`hc_merge` writes the stream back
    with, ``X <- H_res X + H_post^T F``: the two maps and the model's mesh, which
    chooses the path. Sows ``mhc_stats/row_sum_gap``, the largest ``|sum_j H_res[i,
    j] - 1|`` after the last round (:func:`mhc_counters`). The profile's phase
    ``mhc``, the kernels' time included (``mhc_pre`` and ``mhc_merge`` forward and
    recomputed, ``mhc_pre_bwd`` and ``mhc_merge_bwd`` backward)."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, stream):
        from ..ops.hyper_connection import mhc_pre

        cfg, f32 = self.cfg, jnp.float32
        n, k = cfg.hc_mult, stream.shape[-1]
        phi = self.param("phi", nn.initializers.normal(k**-0.5), (k, 2 * n + n * n), f32)
        bias = self.param("bias", nn.initializers.zeros_init(), (2 * n + n * n,), f32)
        alpha = self.param("alpha", nn.initializers.constant(0.01), (3,), f32)
        with jax.named_scope("mhc"):
            x_in, post, res, gap = mhc_pre(
                stream, phi, bias, alpha, n, iters=cfg.hc_sinkhorn_iters, eps=cfg.hc_eps, norm_eps=cfg.norm_eps,
                clamp=(cfg.mhc_h_res_clamp_min, cfg.mhc_h_res_clamp_max), mesh=cfg.mesh, sinkhorn=sinkhorn_knopp)
            self.sow("mhc_stats", "row_sum_gap", gap, init_fn=lambda: jnp.zeros(()), reduce_fn=jnp.maximum)
        return x_in, post, res, cfg.mesh


def hc_merge(stream, out, post, res, mesh=None):
    """``X <- H_res X + H_post^T out`` a token: ``stream [B, T, n C]``, ``out [B, T, C]``
    (``ops/hyper_connection.mhc_merge``: its kernels where ``mhc_pre``'s ran, on
    the same ``mesh``; the plain form elsewhere)."""
    from ..ops.hyper_connection import mhc_merge

    with jax.named_scope("mhc"):
        return mhc_merge(stream, out, post, res, mesh=mesh)


def mhc_counters(variables: Any) -> dict:
    """``{"mhc/row_sum_gap"}`` of one forward, from the variables a
    ``mutable=["mhc_stats"]`` apply returned: the largest gap of a row of a
    residual map from 1 after the last Sinkhorn round, over the step's tokens
    and sublayers (how far from doubly stochastic the rounds left it). A device
    scalar the train step returns beside its loss. Empty without hyper-connections."""
    stats = variables.get("mhc_stats", {}) if isinstance(variables, dict) else {}
    gaps = jax.tree_util.tree_leaves(stats)
    return {"mhc/row_sum_gap": jnp.max(jnp.stack(gaps))} if gaps else {}


class MLP(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x, adapters=None):
        from .quant import QuantDense

        cfg = self.cfg
        dense = lambda feats, name: QuantDense(
            feats, use_bias=False, dtype=cfg.dtype, param_dtype=jnp.float32, name=name
        )
        # named for the remat policy of a block that keeps them (DecoderLM); nothing outside a checkpoint
        gate = checkpoint_name(_adapter_add(dense(cfg.mlp_dim, "gate_proj")(x), x, "gate_proj", adapters), "mlp_gate")
        up = checkpoint_name(_adapter_add(dense(cfg.mlp_dim, "up_proj")(x), x, "up_proj", adapters), "mlp_up")
        h = nn.silu(gate) * up
        return _adapter_add(dense(cfg.hidden_dim, "down_proj")(h), h, "down_proj", adapters)


class ShortConv(nn.Module):
    """The gated short convolution (LFM2): ``(B, C, X) = split3(u @ W_in)``,
    ``z_t = sum_j w_j * (B * X)_{t-(L-1)+j}`` (depthwise, causal, zeros before
    the sequence starts, ``L = conv_L_cache``), ``out = (C * z) @ W_out``. No
    bias, no activation inside. Three shifted multiply-adds that XLA fuses;
    the whole operator is the profile's phase ``conv_op``."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x):
        from .quant import QuantDense

        cfg = self.cfg
        taps, d = cfg.conv_L_cache, cfg.hidden_dim
        dense = lambda feats, name: QuantDense(
            feats, use_bias=False, dtype=cfg.dtype, param_dtype=jnp.float32, name=name
        )
        with jax.named_scope("conv_op"):
            b_gate, c_gate, xs = jnp.split(dense(3 * d, "in_proj")(x), 3, axis=-1)
            w = self.param("conv_weight", nn.initializers.normal(taps**-0.5), (taps, d), jnp.float32).astype(cfg.dtype)
            bx = jnp.pad(b_gate * xs, ((0, 0), (taps - 1, 0), (0, 0)))
            t = x.shape[1]
            z = sum(w[j] * jax.lax.slice_in_dim(bx, j, j + t, axis=1) for j in range(taps))
            return dense(d, "out_proj")(c_gate * z)


class Mamba2Mixer(nn.Module):
    """The Mamba-2 mixer as the ``granitemoehybrid`` family runs it, on ``H =
    mamba_n_heads`` heads of ``P = mamba_d_head`` channels (``d_inner = H P``),
    ``G`` groups, a state of ``N = mamba_d_state``:

    ``[z | x | B | C | dt] = u W_in`` (widths ``d_inner, d_inner, G N, G N, H``);
    ``[x | B | C] = silu(conv1d([x | B | C]) + b)``, depthwise, causal, zeros
    before the sequence; ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``;
    ``y`` by the state-space scan (``ops/ssd.py``: ``S_t = exp(dt_t A_h) S_{t-1}
    + dt_t x_t B_t^T``, ``y_t = S_t C_t + D_h x_t``); the gated norm ``g = y *
    silu(z)``, ``g * rsqrt(mean(g^2) + eps) * w`` over all of ``d_inner`` at
    once; ``out = g W_out``. No bias but the conv's.

    With ``cfg.heads_axis`` the heads held here are a share of the layer's: the
    norm's mean is then over every holder's channels (one ``psum`` of the sum of
    squares and of the count), and the caller sums ``out`` over the holders.
    Four phases of the profile: ``ssm_proj``, ``ssm_conv``, ``ssm_scan``,
    ``ssm_gate_norm``. Sows ``ssm_stats/state_absmax``, the largest magnitude
    of a state carried from chunk to chunk (:func:`ssm_counters`)."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, u):
        from ..ops import ssd
        from .quant import QuantDense

        cfg = self.cfg
        h, p, n, g, taps = cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state, cfg.mamba_n_groups, cfg.mamba_d_conv
        d_inner, f32 = h * p, jnp.float32
        conv_dim = d_inner + 2 * g * n
        b, t, _ = u.shape
        dense = lambda feats, name: QuantDense(feats, use_bias=False, dtype=cfg.dtype, param_dtype=f32, name=name)
        with jax.named_scope("ssm_proj"):
            z, xbc, dt = jnp.split(dense(d_inner + conv_dim + h, "in_proj")(u), [d_inner, d_inner + conv_dim], axis=-1)
        with jax.named_scope("ssm_conv"):
            w = self.param("conv_weight", nn.initializers.normal(taps**-0.5), (taps, conv_dim), f32).astype(cfg.dtype)
            bias = self.param("conv_bias", nn.initializers.zeros_init(), (conv_dim,), f32).astype(cfg.dtype)
            padded = jnp.pad(xbc, ((0, 0), (taps - 1, 0), (0, 0)))
            xbc = nn.silu(sum(w[j] * jax.lax.slice_in_dim(padded, j, j + t, axis=1) for j in range(taps)) + bias)
            x, b_in, c_in = jnp.split(xbc, [d_inner, d_inner + g * n], axis=-1)
        with jax.named_scope("ssm_scan"):
            # the published initialiser's ranges: decays neither 0 nor 1, steps of 1e-3 to 1e-1
            a_log = self.param("A_log", lambda key, shape: jnp.log(jax.random.uniform(key, shape, f32, 1.0, 16.0)), (h,))
            dt_bias = self.param("dt_bias", _inverse_softplus_log_uniform(1e-3, 1e-1), (h,))
            skip = self.param("D", nn.initializers.ones_init(), (h,), f32)
            # a row shorter than a chunk (``init``'s example input) is one chunk of its own length; with a mesh named
            # the scan shard_maps itself over it, as the flash path does (XLA cannot partition its kernels; over a mesh
            # of one device the compiled step is the same, and the scan knows its trace is one device's)
            scan = ssd.ssd_chunked
            if cfg.mesh is not None:
                scan = lambda *args, **kwargs: ssd.ssd_chunked_sharded(*args, cfg.mesh, **kwargs)
            y, carried = scan(
                x.reshape(b, t, h, p), jax.nn.softplus(dt.astype(f32) + dt_bias), -jnp.exp(a_log),
                b_in.reshape(b, t, g, n), c_in.reshape(b, t, g, n), skip, min(cfg.mamba_chunk_size, t), return_carry=True)
            self.sow("ssm_stats", "state_absmax", jnp.max(jnp.abs(carried)),  # a reading: the scan stops its gradient
                     init_fn=lambda: jnp.zeros(()), reduce_fn=jnp.maximum)
        with jax.named_scope("ssm_gate_norm"):
            scale = self.param("norm_scale", nn.initializers.ones_init(), (d_inner,), f32)
            gated = y.reshape(b, t, d_inner).astype(f32) * nn.silu(z.astype(f32))
            squares, channels = jnp.sum(gated * gated, axis=-1, keepdims=True), d_inner
            if cfg.heads_axis is not None:
                squares, channels = jax.lax.psum(squares, cfg.heads_axis), jax.lax.psum(channels, cfg.heads_axis)
            gated = (gated * jax.lax.rsqrt(squares / channels + cfg.norm_eps) * scale).astype(cfg.dtype)
        with jax.named_scope("ssm_proj"):
            return dense(cfg.hidden_dim, "out_proj")(gated)


def _inverse_softplus_log_uniform(low: float, high: float):
    """An initialiser: ``x`` with ``softplus(x)`` log-uniform in ``[low, high]``."""

    def init(key, shape):
        step = jnp.exp(jax.random.uniform(key, shape, jnp.float32, math.log(low), math.log(high)))
        return step + jnp.log(-jnp.expm1(-step))

    return init


def ssm_counters(variables: Any) -> dict:
    """``{"ssm/state_absmax"}`` of one forward, from the variables a
    ``mutable=["ssm_stats"]`` apply returned: the largest magnitude of a state
    the scan carried from one chunk to the next, over the model's mamba layers
    (what a carry in fewer bits, or a kernel, has to hold). A device scalar: a
    train step returns it beside its loss and the tracker fetches it with it.
    Empty for a model without such layers."""
    stats = variables.get("ssm_stats", {}) if isinstance(variables, dict) else {}
    peaks = jax.tree_util.tree_leaves(stats)
    return {"ssm/state_absmax": jnp.max(jnp.stack(peaks))} if peaks else {}


class DecoderBlock(nn.Module):
    """``h = x + Op(norm(x))``, ``y = h + FFN(norm(h))``. ``kind`` names the
    operator (``LAYER_KINDS``), which owns its projections and its call;
    ``use_moe`` puts the expert layer in the dense MLP's place; ``attention``
    is what an attention operator is told of itself (None: the model-wide values).
    With ``hc_mult`` n > 1, ``x`` is the stream ``[B, T, n C]`` and each of the
    two sublayers reads and writes it through its own ``HyperConnection``
    (``attn_hc``, ``mlp_hc``): ``X <- H_res X + H_post^T F(norm(H_pre X))``."""

    cfg: TransformerConfig
    use_moe: bool = False
    kind: str = "full_attention"
    attention: LayerAttention | None = None

    @nn.compact
    def __call__(
        self, x, cos, sin, cache=None, offset=0, seg_info=None, decode_pad=None, attend_len=None,
        paged=None, adapters=None,
    ):
        cfg = self.cfg
        # split the lora-init-shaped adapter subtree for this layer into the
        # attn/mlp halves its submodules consume (ids ride along unchanged)
        attn_ad = mlp_ad = None
        if adapters is not None:
            sub, ids = adapters
            attn_ad = ((sub or {}).get("attn"), ids)
            mlp_ad = ((sub or {}).get("mlp"), ids)
        norm = lambda name: RMSNorm(eps=cfg.norm_eps, name=name)
        new_cache = None
        # the sublayer's input, and the maps that write its output back to a stream of several (None: x + F)
        inp, maps = x, None
        if cfg.hc_mult > 1:
            inp, *maps = HyperConnection(cfg, name="attn_hc")(x)
        if self.kind in ("conv", "mamba", "mla"):
            if cache is not None or seg_info is not None or paged is not None or attn_ad is not None:
                raise NotImplementedError(f"a {self.kind!r} layer takes no cache, packed rows, pages or attention adapters")
            if self.kind == "conv":
                out = ShortConv(cfg, name="conv")(norm("conv_norm")(inp))
            elif self.kind == "mamba":
                out = Mamba2Mixer(cfg, name="mamba")(norm("mamba_norm")(inp))
            else:
                out = LatentAttention(cfg, self.attention, name="attn")(norm("attn_norm")(inp), cos, sin)
        elif cache is not None:
            out, new_cache = Attention(cfg, self.attention, name="attn")(
                norm("attn_norm")(inp), cos, sin, cache=cache, offset=offset,
                decode_pad=decode_pad, attend_len=attend_len, paged=paged, adapters=attn_ad,
            )
        else:
            out = Attention(cfg, self.attention, name="attn")(
                norm("attn_norm")(inp), cos, sin, seg_info=seg_info, adapters=attn_ad
            )
        if cfg.heads_axis is not None:  # the heads held here gave a partial result
            out = jax.lax.psum(out, cfg.heads_axis)
        x = _residual(x, _branch(cfg, out), maps)
        inp = x
        if cfg.hc_mult > 1:
            inp, *maps = HyperConnection(cfg, name="mlp_hc")(x)
        if self.use_moe:
            from .moe import MoEConfig, MoEMLP

            moe_cfg = MoEConfig(
                num_experts=cfg.num_experts,
                top_k=cfg.num_experts_per_tok,
                hidden_dim=cfg.hidden_dim,
                mlp_dim=cfg.moe_intermediate_size or cfg.mlp_dim,
                use_expert_bias=cfg.use_expert_bias,
                scoring_func=cfg.scoring_func,
                norm_topk_prob=cfg.norm_topk_prob,
                routed_scaling_factor=cfg.routed_scaling_factor,
                shared_expert_intermediate_size=cfg.shared_expert_intermediate_size,
                experts_held=cfg.experts_held,
                dtype=cfg.dtype,
            )
            # MoE blocks carry no per-request adapters (expert routing and
            # LoRA-per-tenant compose poorly; dense layers cover serving)
            x = _residual(x, _branch(cfg, MoEMLP(moe_cfg, name="moe")(norm("mlp_norm")(inp))), maps)
        else:
            x = _residual(x, _branch(cfg, MLP(cfg, name="mlp")(norm("mlp_norm")(inp), adapters=mlp_ad)), maps)
        return x if new_cache is None else (x, new_cache)


def _residual(x, branch, maps):
    """The stream after a sublayer: ``x + branch``, or through its hyper-connection's ``maps``."""
    return x + branch if maps is None else hc_merge(x, branch, *maps)


def _scaled(x, factor: float):
    """``x * factor`` with the factor in float32: 0.22 held in bfloat16 is 0.1 % off."""
    return x if factor == 1.0 else (x.astype(jnp.float32) * factor).astype(x.dtype)


def _branch(cfg: TransformerConfig, out):
    """A residual branch as it is added to the stream (``residual_multiplier``)."""
    return _scaled(out, cfg.residual_multiplier)


def remat_kept_blocks(per_block_bytes: int, num_blocks: int, free_bytes: int | None, reserve_bytes: int) -> int:
    """How many of ``num_blocks`` rematerialised blocks keep their MLP's two products for the backward:
    as many as ``free_bytes`` less ``reserve_bytes`` hold at ``per_block_bytes`` each; none where the
    device reported no room (``free_bytes`` None) or the reserve takes it all."""
    if free_bytes is None:
        return 0
    return max(0, min(num_blocks, (free_bytes - reserve_bytes) // per_block_bytes))


@dataclass
class RematRoom:
    """The room a traced step's rematerialised ``DecoderLM`` forward has on the device, read once
    (``of``: ``TrainValStage`` reads the first device of its mesh as it traces the train step, when the
    state and the registry's copy are already on it), and what the forward made of it (``plan``: the
    fields of the journal's ``remat`` record).

    ``free_bytes`` is ``bytes_limit - bytes_in_use``; None where the device reports no
    ``memory_stats()`` (the CPU, a described topology), and nothing is kept. ``reserve_bytes`` is
    the room left for the step's own working set, a fifth of ``bytes_limit``: 3.38 GB of a v5e's
    15.75 GiB, where the step of ``granite-train-8k`` keeping nothing reads 2.32 GB of temporaries
    in ``memory_analysis()`` (PR 38), a margin of 46 % for the allocator and the feed's batches."""

    free_bytes: int | None = None
    reserve_bytes: int = 0
    plan: dict | None = None

    @classmethod
    def of(cls, device) -> "RematRoom":
        stats = device.memory_stats()
        if not stats or "bytes_limit" not in stats:
            return cls()
        limit = int(stats["bytes_limit"])
        return cls(limit - int(stats.get("bytes_in_use", 0)), limit // 5)


_remat_room: contextvars.ContextVar[RematRoom | None] = contextvars.ContextVar("remat_room", default=None)


@contextlib.contextmanager
def remat_room(room: RematRoom):
    """``room`` is what every ``DecoderLM`` forward traced inside reads; outside one a block keeps
    nothing but its input, as it always did."""
    token = _remat_room.set(room)
    try:
        yield room
    finally:
        _remat_room.reset(token)


def _blocks_keeping(cfg: TransformerConfig, tokens_shape) -> set[int]:
    """The dense-MLP blocks of a rematerialised training forward that keep their MLP's products: the
    first ``remat_kept_blocks`` of them under the room of the step being traced. Per block 2 x B x T x
    ``mlp_dim`` at ``cfg.dtype``, from the whole batch's shape (on a mesh that shares the batch or
    the MLP's columns a device holds less: the count errs low). An expert layer keeps nothing."""
    room = _remat_room.get()
    if room is None:
        return set()
    b, t = tokens_shape
    per_block = 2 * b * t * cfg.mlp_dim * jnp.dtype(cfg.dtype).itemsize
    dense = [i for i in range(cfg.num_layers) if not cfg.is_expert_layer(i)]
    kept = remat_kept_blocks(per_block, len(dense), room.free_bytes, room.reserve_bytes)
    room.plan = dict(kept_blocks=kept, blocks=len(dense), kept_bytes=kept * per_block,
                     free_bytes=room.free_bytes, reserve_bytes=room.reserve_bytes)
    return set(dense[:kept])


class DecoderLM(nn.Module):
    """Causal LM: tokens [B, T] int32 -> logits [B, T, vocab] fp32.

    With ``cache``/``offset`` (see ``models/generate.py``) runs in
    autoregressive-decode mode and returns ``(logits, new_cache)``. With
    ``cache`` holding pool pages and ``pages=(block_tables, fill)`` the
    decode is PAGED (the serving engine's path, ``dmlcloud_tpu/serve/``):
    each row reads/writes the pool blocks its table names at its own
    absolute position. With ``segment_ids`` [B, T] int32, rows hold
    multiple packed examples and attention never crosses segment
    boundaries (pair with ``lm_loss(..., segment_ids=...)``).
    ``adapters=(stacked_tree, ids)`` applies per-row LoRA deltas gathered
    by adapter id inside every dense layer (multi-tenant serving; see
    ``serve.AdapterSet``).

    ``return_hidden=True`` without a cache returns the final hidden states
    instead of logits (the chunked-vocab loss path); WITH a cache it
    returns ``((logits, hidden), new_cache)`` — one decode forward feeding
    both the base distribution and any extra decode heads (Medusa).

    With ``hc_mult`` n > 1 the embedding is copied into n streams, every block
    reads and writes them through its hyper-connections, and the final hidden
    state is their sum. With ``num_nextn_predict_layers`` 1 a training forward
    returns ``(logits, mtp_logits)``: the second from ``NextTokenPrediction``
    over that hidden state and the next token's embedding, through the same
    head (pair with ``mtp_lm_loss``)."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(
        self, tokens, cache=None, offset=0, segment_ids=None, pad_len=None, attend_len=None,
        return_hidden=False, pages=None, adapters=None,
    ):
        cfg = self.cfg
        if pad_len is not None and cache is None:
            raise ValueError("pad_len (left-padded ragged prompts) is a decode-mode feature")
        if attend_len is not None and cache is None:
            raise ValueError("attend_len (bounded cache reads) is a decode-mode feature")
        if cache is not None or segment_ids is not None:
            cfg.require_attention_only("decoding" if cache is not None else "a packed row")
        paged = None
        if pages is not None:
            # Paged decode (serving engine): ``cache`` holds the POOL pages
            # per layer and ``pages = (block_tables [B, NB], fill [B])``
            # says where each row's tokens live and how many are filled.
            # Rows sit at their own absolute positions (no left-padding —
            # ragged prompts need no pad path here), so positions derive
            # from fill, not from a batch-wide offset.
            if cache is None:
                raise ValueError("pages (paged KV decode) requires the pool cache")
            if pad_len is not None or attend_len is not None:
                raise ValueError("pages replaces pad_len/attend_len: positions come from fill")
            tables, fill = pages
            positions = fill[:, None] + jnp.arange(tokens.shape[1])[None, :]
            paged = (tables, fill, positions)
        decode_pad = None
        if pad_len is not None:
            positions = jnp.maximum(jnp.arange(tokens.shape[1])[None, :] + offset - pad_len[:, None], 0)
            decode_pad = (pad_len, positions)
        seg_info = None
        if segment_ids is not None:
            if cache is not None:
                raise ValueError("segment_ids are a packed-training feature; unsupported in decode mode")
            if cfg.attn_impl == "ring":
                raise ValueError("segment_ids are not supported with attn_impl='ring'")
            # computed ONCE here, shared by every layer: per-segment rotary
            # positions (restart at each segment's first token) and the
            # causal-AND-same-segment attention mask
            t = tokens.shape[1]
            same = segment_ids[:, :, None] == segment_ids[:, None, :]  # [B, T, S]
            seg_start = jnp.argmax(same, axis=-1)  # first index of own segment
            positions = jnp.arange(t)[None, :] - seg_start
            if cfg.attn_impl == "flash":
                mask = None  # the flash kernels mask from the raw ids
            else:
                mask = jnp.tril(jnp.ones((t, t), dtype=bool))[None] & same
                window = cfg.attention_layer(0).window  # every layer's: a packed row runs full_attention layers only
                if window is not None:
                    pos = jnp.arange(t)
                    mask = mask & _window_keep(pos[:, None], pos[None, :], window)[None]
            seg_info = (positions, mask, segment_ids)
        mtp = cfg.num_nextn_predict_layers > 0 and cache is None
        if mtp and return_hidden:
            raise ValueError("return_hidden (the chunked-vocab loss path) has no next-token-prediction head")
        embed = nn.Embed(cfg.vocab_size, cfg.hidden_dim, dtype=cfg.dtype, param_dtype=jnp.float32, name="embed")
        x = _scaled(embed(tokens), cfg.embedding_multiplier)
        # one rotary table, unless ``rope_parameters`` gives the layer kinds their own
        layers = [cfg.attention_layer(i) for i in range(cfg.num_layers)]
        rotary = lambda rope: rope_frequencies(cfg.rope_dim, cfg.max_seq_len, *rope) if cfg.position_embedding == "rope" else (None, None)
        tables = {rope: rotary(rope) for rope in dict.fromkeys(a.rope for a in layers)}

        def constrain(x):
            if cfg.act_sharding is None:
                return x
            if hasattr(cfg.act_sharding, "shard_shape"):
                try:  # skip when the (static) shape isn't divisible, e.g. module.init on a size-1 batch
                    cfg.act_sharding.shard_shape(x.shape)
                except (ValueError, ZeroDivisionError):
                    return x
            return jax.lax.with_sharding_constraint(x, cfg.act_sharding)

        x = constrain(_streams(cfg, x))
        block_cls = nn.remat(DecoderBlock, prevent_cse=True) if cfg.remat else DecoderBlock
        keeping = _blocks_keeping(cfg, tokens.shape) if cfg.remat and cache is None else set()
        if keeping:
            keep_cls = nn.remat(DecoderBlock, prevent_cse=True,
                                policy=jax.checkpoint_policies.save_only_these_names("mlp_gate", "mlp_up"))
        new_cache = {} if cache is not None else None
        adapter_tree, adapter_ids = adapters if adapters is not None else (None, None)
        for i in range(cfg.num_layers):
            use_moe, kind = cfg.is_expert_layer(i), cfg.layer_kind(i)
            cos, sin = tables[layers[i].rope]
            name = f"layer_{i}"
            layer_ad = None
            if adapter_tree is not None and adapter_tree.get(name) is not None:
                layer_ad = (adapter_tree[name], adapter_ids)
            if cache is not None:
                x, new_cache[name] = DecoderBlock(cfg, use_moe=use_moe, kind=kind, attention=layers[i], name=name)(
                    x, cos, sin, cache=cache[name], offset=offset, decode_pad=decode_pad,
                    attend_len=attend_len, paged=paged, adapters=layer_ad,
                )
                x = constrain(x)
            else:
                x = constrain(
                    (keep_cls if i in keeping else block_cls)(cfg, use_moe=use_moe, kind=kind, attention=layers[i], name=name)(
                        x, cos, sin, seg_info=seg_info, adapters=layer_ad
                    )
                )

        hidden = _stream_sum(cfg, x)
        # published as logits / logits_scaling: dividing the head's input is the same product,
        # and leaves lm_loss's hand-written backward as it is
        x = _scaled(RMSNorm(eps=cfg.norm_eps, name="final_norm")(hidden), 1.0 / cfg.logits_scaling)
        if return_hidden and new_cache is None:
            # the chunked-vocab loss path (chunked_lm_loss) consumes the
            # final hidden states directly and never materializes logits
            return x
        from .quant import QuantDense

        head = None if cfg.tie_embeddings else QuantDense(
            cfg.vocab_size, use_bias=False, dtype=jnp.float32, param_dtype=jnp.float32, name="lm_head")

        def project(x):
            if cfg.tie_embeddings:
                table = self.variables["params"]["embed"]["embedding"]
                return jnp.einsum("btd,vd->btv", x.astype(jnp.float32), table.astype(jnp.float32))
            return head(x)

        # the phase of the head's product (utils/profiling.PHASES): part of the loss
        # in a training or evaluation forward, the decode step's own ``head``
        with jax.named_scope("loss_head" if new_cache is None else "head"):
            logits = project(x)
            if adapter_tree is not None and not cfg.tie_embeddings:
                logits = _adapter_add(logits, x, "lm_head", (adapter_tree, adapter_ids))
        if mtp:
            after = _next_in_row(tokens)  # token i + 1 at position i; the last position's target weighs nothing
            x = NextTokenPrediction(cfg, layers[-1], name="mtp")(
                hidden, _scaled(embed(after), cfg.embedding_multiplier), *tables[layers[-1].rope])
            with jax.named_scope("loss_head"):
                return logits, project(_scaled(x, 1.0 / cfg.logits_scaling))
        if new_cache is None:
            return logits
        if return_hidden:
            # cache-stepping callers (Medusa decode heads) need the final
            # hidden states NEXT TO the base logits — one forward feeds the
            # base distribution and every extra head
            return (logits, x), new_cache
        return logits, new_cache


def _streams(cfg: TransformerConfig, x):
    """The embedding as the residual stream: itself, or ``hc_mult`` copies of it side by side ``[B, T, n C]``."""
    if cfg.hc_mult == 1:
        return x
    with jax.named_scope("mhc"):
        return jnp.concatenate([x] * cfg.hc_mult, axis=-1)


def _stream_sum(cfg: TransformerConfig, x):
    """The final hidden state of the stream: itself, or the sum of its ``hc_mult`` streams."""
    if cfg.hc_mult == 1:
        return x
    with jax.named_scope("mhc"):
        c = x.shape[-1] // cfg.hc_mult
        return sum(x[..., j * c : (j + 1) * c].astype(jnp.float32) for j in range(cfg.hc_mult)).astype(x.dtype)


class NextTokenPrediction(nn.Module):
    """The model's own next-token-prediction module (DeepSeek-V3 section 2.2),
    one of them: ``h'_i = W_eh [RMSNorm_e(Emb(t_{i+1})) ; RMSNorm_h(h_i)]`` from
    the main model's final hidden state ``h`` (before its final norm), then one
    block of the model's last kind (experts where the model has them, its own
    hyper-connections, ``remat`` as the others), then a final RMSNorm of its own;
    the caller applies the shared head. ``W_eh`` and its two norms are the
    profile's phase ``mtp``; the block's operators keep their own phases."""

    cfg: TransformerConfig
    layer: LayerAttention

    @nn.compact
    def __call__(self, hidden, next_embedded, cos, sin):
        from .quant import QuantDense

        cfg = self.cfg
        with jax.named_scope("mtp"):
            joined = jnp.concatenate([RMSNorm(eps=cfg.norm_eps, name="enorm")(next_embedded),
                                      RMSNorm(eps=cfg.norm_eps, name="hnorm")(hidden)], axis=-1)
            x = QuantDense(cfg.hidden_dim, use_bias=False, dtype=cfg.dtype, param_dtype=jnp.float32, name="eh_proj")(joined)
        block_cls = nn.remat(DecoderBlock, prevent_cse=True) if cfg.remat else DecoderBlock
        i = cfg.num_layers - 1
        x = block_cls(cfg, use_moe=cfg.num_experts > 0, kind=cfg.layer_kind(i), attention=self.layer, name="block")(
            _streams(cfg, x), cos, sin)
        return RMSNorm(eps=cfg.norm_eps, name="final_norm")(_stream_sum(cfg, x))


@jax.named_scope("loss_head")
def chunked_lm_loss(
    hidden: jnp.ndarray,
    kernel: jnp.ndarray,
    tokens: jnp.ndarray,
    *,
    vocab_chunk: int = 8192,
    segment_ids: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """``lm_loss`` without ever materializing the ``[B, T, vocab]`` logits.

    At vocab 32k+, the logits of a training step can dominate activation
    memory (8k tokens x 32k vocab x 4B = 1 GB fp32 — more than the rest of
    a small model's activations combined). This computes the identical
    next-token cross entropy by streaming the vocab in chunks of
    ``vocab_chunk``: per chunk, ``hidden @ kernel[:, c]`` feeds an ONLINE
    logsumexp (the flash-attention trick applied to the loss) and a gather
    of the target logit; the ``lax.scan`` body is ``jax.checkpoint``-ed so
    the backward recomputes each chunk's logits instead of storing them.
    Peak extra memory is O(B*T*vocab_chunk) regardless of vocab size.

    ``hidden`` is the final-norm output (``DecoderLM(..., return_hidden=
    True)``), ``kernel`` the ``[hidden_dim, vocab]`` projection —
    ``params["lm_head"]["kernel"]``, or ``embed.T`` for tied embeddings.
    The kernel is consumed chunk by chunk (full chunks via a scanned
    dynamic slice, a non-divisible tail as one static epilogue), so no
    padded or re-typed copy of it is ever built. Matches ``lm_loss(logits,
    tokens, segment_ids)`` to float32 accuracy (asserted fwd AND grad in
    tests/test_models.py)."""
    h = hidden[:, :-1].astype(jnp.float32)
    targets = tokens[:, 1:]
    d, v = kernel.shape
    neg = jnp.float32(-1e30)  # finite sentinel: -inf would NaN the rescale

    def online_update(carry, logits, base):
        """Fold one chunk's logits [B, T-1, width] starting at vocab index
        ``base`` into (running max, running sum(exp(logit - m)), target
        logit)."""
        m, s, tl = carry
        new_m = jnp.maximum(m, logits.max(-1))
        s = s * jnp.exp(m - new_m) + jnp.exp(logits - new_m[..., None]).sum(-1)
        width = logits.shape[-1]
        in_chunk = (targets >= base) & (targets < base + width)
        local = jnp.clip(targets - base, 0, width - 1)
        picked = jnp.take_along_axis(logits, local[..., None], axis=-1)[..., 0]
        return new_m, s, jnp.where(in_chunk, picked, tl)

    @jax.checkpoint
    def body(carry, c):
        w = jax.lax.dynamic_slice(kernel, (0, c * vocab_chunk), (d, vocab_chunk))
        # [B, T-1, chunk] — the only logits ever live; the astype fuses
        # into the matmul's operand read
        return online_update(carry, h @ w.astype(jnp.float32), c * vocab_chunk), None

    carry = (
        jnp.full(h.shape[:-1], neg, jnp.float32),
        jnp.zeros(h.shape[:-1], jnp.float32),
        jnp.zeros(h.shape[:-1], jnp.float32),
    )
    n_full = v // vocab_chunk
    if n_full:
        carry, _ = jax.lax.scan(body, carry, jnp.arange(n_full))
    if v % vocab_chunk:  # static epilogue for the non-divisible tail
        tail = kernel[:, n_full * vocab_chunk :]
        carry = jax.checkpoint(
            lambda c: online_update(c, h @ tail.astype(jnp.float32), n_full * vocab_chunk)
        )(carry)
    m, s, tl = carry
    losses = (m + jnp.log(s)) - tl  # logsumexp - target logit
    return (losses * _target_weights(tokens.shape, segment_ids)[:, :-1]).sum()


@jax.named_scope("loss_head")
def lm_loss(
    logits: jnp.ndarray, tokens: jnp.ndarray, segment_ids: jnp.ndarray | None = None
) -> jnp.ndarray:
    """Next-token cross entropy, read off the logits where they lie.

    Nothing of the ``[B, T, vocab]`` logits is sliced or copied: the TARGETS
    are shifted (position ``t`` is held to ``tokens[t + 1]``) and every one of
    the ``T`` positions gets a weight (``_target_weights``): 0 in the last
    column, and with ``segment_ids`` (packed rows) 0 wherever the target is
    not in the SAME segment (no predicting across a packing boundary) or the
    segment is padding (id 0). The weights sum to 1 over the positions that
    count, so the loss, the weighted sum of ``logsumexp(row) - row[target]``,
    is their mean.

    The backward is written by hand (``jax.custom_vjp``): ``(softmax -
    onehot) * weight`` in one expression over all ``T`` rows, computed in
    float32 and returned in the logits' dtype; on the TPU it is written once
    in bf16, which is what the head's two backward products round it to
    anyway. Forward-mode derivatives (``jax.jvp``, ``jax.jacfwd``) of this
    loss are therefore not defined; nothing in the tree takes one."""
    targets = _next_in_row(tokens)  # any id does in the last column: its weight is 0
    return _weighted_cross_entropy(logits, targets, _target_weights(tokens.shape, segment_ids))


def _next_in_row(ids: jnp.ndarray, ahead: int = 1) -> jnp.ndarray:
    """``ids[:, t + ahead]`` at column ``t``; 0 in the last ``ahead`` columns."""
    return jnp.pad(ids[:, ahead:], ((0, 0), (0, ahead)))


def mtp_lm_loss(logits: jnp.ndarray, mtp_logits: jnp.ndarray, tokens: jnp.ndarray, weight: float):
    """``(CE_main + weight * CE_mtp, CE_mtp)`` of a forward with a
    next-token-prediction module: ``CE_main`` is :func:`lm_loss`, ``CE_mtp`` the
    mean cross entropy of ``mtp_logits`` at position ``t`` against ``tokens[t +
    2]`` over the ``T - 2`` positions that have one, through the same hand-written
    backward (``_weighted_cross_entropy``)."""
    t = tokens.shape[1]
    with jax.named_scope("loss_head"):
        counts = jnp.broadcast_to(jnp.arange(t) < t - 2, tokens.shape).astype(jnp.float32)
        mtp = _weighted_cross_entropy(mtp_logits, _next_in_row(tokens, 2), counts / jnp.maximum(counts.sum(), 1))
    return lm_loss(logits, tokens) + weight * mtp, mtp


def _target_weights(shape: tuple[int, int], segment_ids: jnp.ndarray | None) -> jnp.ndarray:
    """``[B, T]`` float32 weights of the next-token losses, summing to 1 (to 0
    where no position counts): the last column has no target; with packed
    ``segment_ids``, a position only counts when its target is in the SAME
    non-pad segment. Shared by both loss paths so the packing convention
    cannot diverge."""
    if segment_ids is None:
        counts = jnp.broadcast_to(jnp.arange(shape[1]) < shape[1] - 1, shape)
    else:
        target_segment = _next_in_row(segment_ids)  # the pad id in the last column: it counts for nothing
        counts = (target_segment == segment_ids) & (target_segment != 0)
    w = counts.astype(jnp.float32)
    return w / jnp.maximum(w.sum(), 1)


def _row_stats(logits: jnp.ndarray, targets: jnp.ndarray):
    """float32 logits and, as a mask over the vocabulary, where each row's
    target lies: a masked sum reads the target's logit in the pass that sums
    the exponentials, and over a vocabulary sharded on ``model`` it is a
    partial sum and an all-reduce of ``[B, T]``, where a gather would cross
    shards."""
    x = logits.astype(jnp.float32)
    return x, jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1) == targets[..., None]


@jax.custom_vjp
def _weighted_cross_entropy(logits: jnp.ndarray, targets: jnp.ndarray, weights: jnp.ndarray) -> jnp.ndarray:
    """``sum(weights * (logsumexp(logits) - logits[targets]))`` over ``[B, T]``
    rows; differentiable in ``logits`` only."""
    return _weighted_cross_entropy_fwd(logits, targets, weights)[0]


def _weighted_cross_entropy_fwd(logits, targets, weights):
    x, hit = _row_stats(logits, targets)
    m = x.max(-1)
    lse = m + jnp.log(jnp.exp(x - m[..., None]).sum(-1))
    picked = jnp.where(hit, x, 0).sum(-1)
    # kept: the logits the model returned anyway and three [B, T] arrays
    return ((lse - picked) * weights).sum(), (logits, lse, targets, weights)


def _weighted_cross_entropy_bwd(kept, g):
    logits, lse, targets, weights = kept
    x, hit = _row_stats(logits, targets)
    grad = (jnp.exp(x - lse[..., None]) - hit) * (weights * g)[..., None]
    # On the TPU the head's two backward products round this operand to bf16
    # themselves (default precision), and computing it as they read the logits
    # costs them more than one pass that writes it in bf16 and two reads of
    # that (m7b-train-8k: 27.8 against 25.5 ms a step; PERF.md section 6,
    # PR 33): held once in bf16 there, the same numbers. Elsewhere it stays
    # float32 and XLA's to place.
    grad = jax.lax.platform_dependent(
        grad,
        tpu=lambda a: jax.lax.optimization_barrier(a.astype(jnp.bfloat16)).astype(a.dtype),
        default=lambda a: a,
    )
    return grad.astype(logits.dtype), None, None


_weighted_cross_entropy.defvjp(_weighted_cross_entropy_fwd, _weighted_cross_entropy_bwd)
