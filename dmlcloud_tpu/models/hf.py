"""HuggingFace Llama checkpoint import for :class:`DecoderLM`.

The reference framework trains only user-supplied modules; this gives the
TPU build a real-world on-ramp: load any HF Llama-family checkpoint
(``LlamaForCausalLM`` state dict) into the jax model and get bit-equal
logits (pinned by ``tests/test_hf_import.py`` against a live HF forward).

Two conversions happen beyond plain transposes:

- flax kernels are ``[in, out]`` while torch ``nn.Linear`` stores
  ``[out, in]``;
- HF stores rotary q/k projections in the half-split layout
  (``[r_0..r_{D/2-1}, i_0..i_{D/2-1}]`` per head) while this model rotates
  interleaved pairs (``[r_0, i_0, r_1, i_1, ...]``, the Meta convention) —
  the q/k output rows are permuted accordingly, which is exactly how the
  two RoPE conventions are made to agree.
"""

from __future__ import annotations

import math
from typing import Any, Mapping

import jax.numpy as jnp
import numpy as np

from .transformer import TransformerConfig


def _np(t: Any) -> np.ndarray:
    """torch tensor / numpy array -> float32 numpy."""
    if hasattr(t, "detach"):
        t = t.detach().cpu().float().numpy()
    return np.asarray(t, np.float32)


def _interleave_rope_rows(w: np.ndarray) -> np.ndarray:
    """[..., D] half-split rotary layout -> interleaved pairs."""
    d = w.shape[-1]
    out = np.empty_like(w)
    out[..., 0::2] = w[..., : d // 2]
    out[..., 1::2] = w[..., d // 2 :]
    return out


def transformer_config_from_hf(hf_config: Any, **overrides) -> TransformerConfig:
    """Build a :class:`TransformerConfig` from a HF ``LlamaConfig`` /
    ``MistralConfig`` (same architecture; Mistral's ``sliding_window``
    carries over into the model's windowed attention paths) or an
    ``Lfm2MoeConfig`` (``model_type`` ``lfm2_moe``: per-layer operators from
    ``layer_types``, RMSNorm over the head dimension of q and k, sigmoid-scored
    experts with a selection bias after ``num_dense_layers`` dense layers) or a
    ``laguna`` config (:func:`_laguna_keys`) or a ``granitemoehybrid`` config
    (:func:`_granite_keys`). ``experts_held`` is no published key: pass it as an
    override for one chip's share of the experts."""
    get = lambda key, default=None: getattr(hf_config, key, default)
    rope = get("rope_parameters") or {}
    scaling = get("rope_scaling") or (rope if rope.get("rope_type", "default") != "default" else None)
    base = dict(
        vocab_size=hf_config.vocab_size,
        num_layers=hf_config.num_hidden_layers,
        num_heads=hf_config.num_attention_heads,
        num_kv_heads=get("num_key_value_heads"),
        # some Mistral-family configs decouple head_dim from hidden/heads
        head_dim=get("head_dim") or hf_config.hidden_size // hf_config.num_attention_heads,
        hidden_dim=hf_config.hidden_size,
        mlp_dim=hf_config.intermediate_size,
        max_seq_len=hf_config.max_position_embeddings,
        rope_theta=float(get("rope_theta") or rope.get("rope_theta", 10000.0)),
        tie_embeddings=bool(get("tie_word_embeddings", False)),
        sliding_window=get("sliding_window"),
        rope_scaling=_rope_scaling_from_hf(scaling),
        norm_eps=float(get("rms_norm_eps") or get("norm_eps") or 1e-6),
    )
    if get("model_type") == "lfm2_moe":
        if get("conv_bias", False):
            raise ValueError("conv_bias=True is not supported: ShortConv has no bias")
        base.update(
            layer_types=tuple(hf_config.layer_types),
            conv_L_cache=int(hf_config.conv_L_cache),
            qk_norm=True,
            # the family ties its embeddings; a config that says otherwise is followed
            tie_embeddings=bool(get("tie_word_embeddings", True)),
            num_experts=int(hf_config.num_experts),
            num_dense_layers=int(hf_config.num_dense_layers),
            num_experts_per_tok=int(hf_config.num_experts_per_tok),
            moe_intermediate_size=int(hf_config.moe_intermediate_size),
            use_expert_bias=bool(get("use_expert_bias", False)),
            norm_topk_prob=bool(get("norm_topk_prob", True)),
            routed_scaling_factor=float(get("routed_scaling_factor", 1.0)),
        )
    if get("model_type") == "laguna":
        base.update(_laguna_keys(hf_config))
    if get("model_type") == "granitemoehybrid":
        base.update(_granite_keys(hf_config))
    base.update(overrides)
    return TransformerConfig(**base)


def _laguna_keys(hf_config: Any) -> dict:
    """The ``laguna`` family (poolside): ``full_attention`` and
    ``sliding_attention`` layers (``layer_types``; ``sliding_window`` is the
    latter's) with their own query-head counts
    (``num_attention_heads_per_layer``) and rotary tables (``rope_parameters``
    keyed by layer kind), a per-head sigmoid gate on attention's output
    (``gating``), and after the leading dense layers (``mlp_layer_types``)
    experts scored by a softmax over all of them beside one shared expert.
    The config names neither the scoring nor how the shared expert is added:
    softmax with no selection bias and an ungated sum are the convention of
    the configs that publish ``norm_topk_prob`` with
    ``shared_expert_intermediate_size``; a config that publishes
    ``scoring_func`` is followed. What this model cannot honour is refused."""
    get = lambda key, default=None: getattr(hf_config, key, default)
    n = int(hf_config.num_hidden_layers)
    kinds = list(get("mlp_layer_types") or ["dense" if i in (get("mlp_only_layers") or ()) else "sparse" for i in range(n)])
    dense = kinds.index("sparse") if "sparse" in kinds else n
    if kinds != ["dense"] * dense + ["sparse"] * (n - dense) or int(get("decoder_sparse_step", 1)) != 1:
        raise ValueError("dense MLPs anywhere but in the leading layers are not supported (num_dense_layers)")
    if get("attention_bias", False) or get("moe_apply_router_weight_on_input", False) or get("moe_router_logit_softcapping"):
        raise ValueError("attention_bias, moe_apply_router_weight_on_input and moe_router_logit_softcapping are not supported")
    gating = get("gating")
    if set(get("gating_types") or ["per_head"]) != {"per_head"} or gating not in (None, False, True, "per-head"):
        raise ValueError(f"the only gate on attention's output this model has is per head, got {gating!r} / {get('gating_types')!r}")
    return dict(
        layer_types=tuple(hf_config.layer_types),
        num_heads_per_layer=tuple(int(h) for h in get("num_attention_heads_per_layer") or ()) or None,
        rope_parameters=tuple(
            (kind, float(rope["rope_theta"]), _rope_scaling_from_hf(rope), float(rope.get("partial_rotary_factor", 1.0)))
            for kind, rope in sorted((get("rope_parameters") or {}).items()) if isinstance(rope, dict)
        ) or None,
        gating="per-head" if gating else None,
        num_experts=int(hf_config.num_experts),
        num_dense_layers=dense,
        num_experts_per_tok=int(hf_config.num_experts_per_tok),
        moe_intermediate_size=int(hf_config.moe_intermediate_size),
        shared_expert_intermediate_size=int(get("shared_expert_intermediate_size") or 0),
        scoring_func=get("scoring_func", "softmax"),
        norm_topk_prob=bool(get("norm_topk_prob", True)),
        routed_scaling_factor=float(get("moe_routed_scaling_factor", 1.0)),
    )


def _granite_keys(hf_config: Any) -> dict:
    """The ``granitemoehybrid`` family (IBM Granite 4.0-H) without experts:
    ``mamba`` layers (Mamba-2 mixers, the ``mamba_*`` keys) beside ``attention``
    layers (the program's ``full_attention``) that may carry no position signal
    (``position_embedding_type`` ``nope``) and scale their scores by
    ``attention_multiplier``; one SwiGLU of ``shared_intermediate_size`` in every
    block; ``embedding_multiplier``, ``residual_multiplier``, ``logits_scaling``.
    What this model cannot honour is refused: experts (``num_local_experts`` >
    0, a sparse layer beside the shared MLP), biases on the projections, a conv
    without its bias, another norm or activation. ``mamba_expand`` is not read:
    the mixer's inner width is ``mamba_n_heads * mamba_d_head``, which is the
    published ``mamba_expand * hidden_size`` for the whole model and less for a
    share of its heads."""
    get = lambda key, default=None: getattr(hf_config, key, default)
    if int(get("num_local_experts") or 0) > 0:
        raise ValueError(f"num_local_experts = {get('num_local_experts')}: this model has no expert layer beside the shared MLP "
                         "(granitemoehybrid's sparse block is not supported)")
    if get("attention_bias", False) or get("mamba_proj_bias", False) or not get("mamba_conv_bias", True):
        raise ValueError("attention_bias, mamba_proj_bias and a conv without bias (mamba_conv_bias false) are not supported")
    if get("normalization_function", "rmsnorm") != "rmsnorm" or get("hidden_act", "silu") != "silu":
        raise ValueError(f"only rmsnorm and silu are supported, got {get('normalization_function')!r} / {get('hidden_act')!r}")
    position = get("position_embedding_type", "rope")
    if position not in ("rope", "nope"):
        raise ValueError(f"position_embedding_type {position!r} is not supported (rope, nope)")
    kinds = {"attention": "full_attention", "mamba": "mamba"}
    unknown = sorted(set(hf_config.layer_types) - set(kinds))
    if unknown:
        raise ValueError(f"layer_types holds {unknown}; a granitemoehybrid config names {sorted(kinds)}")
    heads, d_head = int(hf_config.mamba_n_heads), int(hf_config.mamba_d_head)
    return dict(
        layer_types=tuple(kinds[k] for k in hf_config.layer_types),
        mlp_dim=int(get("shared_intermediate_size") or hf_config.intermediate_size),
        tie_embeddings=bool(get("tie_word_embeddings", True)),
        position_embedding=position,
        attention_multiplier=None if get("attention_multiplier") is None else float(hf_config.attention_multiplier),
        embedding_multiplier=float(get("embedding_multiplier") or 1.0),
        residual_multiplier=float(get("residual_multiplier") or 1.0),
        logits_scaling=float(get("logits_scaling") or 1.0),
        mamba_n_heads=heads, mamba_d_head=d_head, mamba_d_state=int(hf_config.mamba_d_state),
        mamba_n_groups=int(get("mamba_n_groups") or 1), mamba_d_conv=int(get("mamba_d_conv") or 4),
        mamba_chunk_size=int(get("mamba_chunk_size") or 256),
    )


def granite_params_from_hf(state_dict: Mapping[str, Any], cfg: TransformerConfig, dtype=jnp.float32):
    """A ``GraniteMoeHybridForCausalLM`` state dict (no experts) as this model's
    params. Beyond the transposes: ``shared_mlp.input_linear`` is the gate's and
    the up projection's rows one after the other and is split into ``gate_proj``
    and ``up_proj``; ``mamba.conv1d.weight [C, 1, K]`` becomes ``[K, C]``; q and
    k keep their columns (no rotary pairs to interleave where nothing rotates;
    with ``position_embedding`` ``rope`` they are permuted as a Llama's are)."""
    sd = dict(state_dict)
    hd, hid = cfg.head_dim, cfg.hidden_dim

    def take(key: str) -> np.ndarray:
        if key not in sd:
            raise KeyError(f"HF state dict is missing {key!r}")
        return _np(sd.pop(key))

    def heads_kernel(key: str, heads: int, rope: bool) -> np.ndarray:
        w = take(key).reshape(heads, hd, hid)
        if rope and cfg.position_embedding == "rope":
            w = _interleave_rope_rows(w.transpose(0, 2, 1)).transpose(0, 2, 1)
        return np.ascontiguousarray(w.transpose(2, 0, 1))

    params: dict[str, Any] = {"embed": {"embedding": take("model.embed_tokens.weight")},
                              "final_norm": {"scale": take("model.norm.weight")}}
    lm_head = sd.pop("lm_head.weight", None)
    if not cfg.tie_embeddings:
        params["lm_head"] = {"kernel": _np(params["embed"]["embedding"] if lm_head is None else lm_head).T}
    for i in range(cfg.num_layers):
        p = f"model.layers.{i}."
        gate_up = take(p + "shared_mlp.input_linear.weight")  # [2 f, hid]: silu(first half) * second half
        layer = {
            "mlp_norm": {"scale": take(p + "post_attention_layernorm.weight")},
            "mlp": {"gate_proj": {"kernel": gate_up[: cfg.mlp_dim].T}, "up_proj": {"kernel": gate_up[cfg.mlp_dim:].T},
                    "down_proj": {"kernel": take(p + "shared_mlp.output_linear.weight").T}},
        }
        if cfg.layer_kind(i) == "mamba":
            layer["mamba_norm"] = {"scale": take(p + "input_layernorm.weight")}
            layer["mamba"] = {
                "in_proj": {"kernel": take(p + "mamba.in_proj.weight").T},
                "conv_weight": take(p + "mamba.conv1d.weight")[:, 0, :].T, "conv_bias": take(p + "mamba.conv1d.bias"),
                "A_log": take(p + "mamba.A_log"), "dt_bias": take(p + "mamba.dt_bias"), "D": take(p + "mamba.D"),
                "norm_scale": take(p + "mamba.norm.weight"), "out_proj": {"kernel": take(p + "mamba.out_proj.weight").T},
            }
        else:
            heads = cfg.attention_layer(i).num_heads
            layer["attn_norm"] = {"scale": take(p + "input_layernorm.weight")}
            layer["attn"] = {
                "q_proj": {"kernel": heads_kernel(p + "self_attn.q_proj.weight", heads, rope=True)},
                "k_proj": {"kernel": heads_kernel(p + "self_attn.k_proj.weight", cfg.kv_heads, rope=True)},
                "v_proj": {"kernel": heads_kernel(p + "self_attn.v_proj.weight", cfg.kv_heads, rope=False)},
                "o_proj": {"kernel": take(p + "self_attn.o_proj.weight").T},
            }
        params[f"layer_{i}"] = layer
    leftovers = [k for k in sd if "rotary_emb" not in k]
    if leftovers:
        raise ValueError(f"unconverted HF weights: {leftovers[:8]}{'...' if len(leftovers) > 8 else ''}")

    import jax

    return jax.tree_util.tree_map(lambda x: jnp.asarray(x, dtype), params)


def _rope_scaling_from_hf(rs: Any) -> tuple | None:
    """HF ``rope_scaling`` dict -> the config's hashable tuple. Unsupported
    schemes raise — a silently-dropped scaling would import a Llama-3
    checkpoint with wrong positional geometry."""
    if rs is None:
        return None
    kind = rs.get("rope_type", rs.get("type"))
    if kind is None:
        # a scaling dict with no recognizable type key must not silently
        # import as plain RoPE
        raise ValueError(f"rope_scaling dict has no 'rope_type'/'type' key: {rs!r}")
    if kind == "default":
        return None
    if kind == "linear":
        return ("linear", float(rs["factor"]))
    if kind == "llama3":
        return (
            "llama3",
            float(rs["factor"]),
            float(rs["low_freq_factor"]),
            float(rs["high_freq_factor"]),
            int(rs["original_max_position_embeddings"]),
        )
    if kind == "yarn":
        if not rs.get("original_max_position_embeddings"):
            raise ValueError(f"yarn rope_scaling needs original_max_position_embeddings: {rs!r}")
        factor = float(rs["factor"])
        return (
            "yarn",
            factor,
            float(rs.get("beta_fast") or 32.0),
            float(rs.get("beta_slow") or 1.0),
            int(rs["original_max_position_embeddings"]),
            float(rs.get("attention_factor") or 0.1 * math.log(factor) + 1.0),  # YaRN's own default
        )
    raise ValueError(f"unsupported HF rope_scaling type {kind!r} (supported: linear, llama3, yarn)")


def llama_params_from_hf(state_dict: Mapping[str, Any], cfg: TransformerConfig, dtype=jnp.float32):
    """Convert a ``LlamaForCausalLM`` state dict into this model's params.

    ``state_dict`` values may be torch tensors or numpy arrays. Returns the
    flax params pytree for ``DecoderLM(cfg)``.
    """
    sd = {k: v for k, v in state_dict.items()}
    h, kh, d, hid = cfg.num_heads, cfg.kv_heads, cfg.head_dim, cfg.hidden_dim

    def take(key: str) -> np.ndarray:
        if key not in sd:
            raise KeyError(f"HF state dict is missing {key!r}")
        return _np(sd.pop(key))

    def qkv_kernel(key: str, heads: int, rope: bool) -> np.ndarray:
        w = take(key)  # [heads*d, hid]
        w = w.reshape(heads, d, hid)
        if rope:
            w = _interleave_rope_rows(w.transpose(0, 2, 1)).transpose(0, 2, 1)
        return np.ascontiguousarray(w.transpose(2, 0, 1))  # [hid, heads, d]

    params: dict[str, Any] = {
        "embed": {"embedding": take("model.embed_tokens.weight")},
        "final_norm": {"scale": take("model.norm.weight")},
    }
    if not cfg.tie_embeddings:
        lm_head = sd.pop("lm_head.weight", None)
        if lm_head is None:  # tied checkpoint loaded into an untied config
            lm_head = np.array(params["embed"]["embedding"])
        params["lm_head"] = {"kernel": _np(lm_head).T}
    else:
        sd.pop("lm_head.weight", None)

    for i in range(cfg.num_layers):
        p = f"model.layers.{i}."
        params[f"layer_{i}"] = {
            "attn_norm": {"scale": take(p + "input_layernorm.weight")},
            "mlp_norm": {"scale": take(p + "post_attention_layernorm.weight")},
            "attn": {
                "q_proj": {"kernel": qkv_kernel(p + "self_attn.q_proj.weight", h, rope=True)},
                "k_proj": {"kernel": qkv_kernel(p + "self_attn.k_proj.weight", kh, rope=True)},
                "v_proj": {"kernel": qkv_kernel(p + "self_attn.v_proj.weight", kh, rope=False)},
                # o_proj consumes the flattened [H*D] heads: [hid, H*D] -> flax [H*D, hid]
                "o_proj": {"kernel": take(p + "self_attn.o_proj.weight").T},
            },
            "mlp": {
                "gate_proj": {"kernel": take(p + "mlp.gate_proj.weight").T},
                "up_proj": {"kernel": take(p + "mlp.up_proj.weight").T},
                "down_proj": {"kernel": take(p + "mlp.down_proj.weight").T},
            },
        }

    leftovers = [k for k in sd if "rotary_emb" not in k]
    if leftovers:
        raise ValueError(f"unconverted HF weights: {leftovers[:8]}{'...' if len(leftovers) > 8 else ''}")

    import jax

    return jax.tree_util.tree_map(lambda x: jnp.asarray(x, dtype), params)


def _split_rope_rows(w: np.ndarray) -> np.ndarray:
    """[..., D] interleaved rotary layout -> half-split (inverse of
    :func:`_interleave_rope_rows`)."""
    d = w.shape[-1]
    out = np.empty_like(w)
    out[..., : d // 2] = w[..., 0::2]
    out[..., d // 2 :] = w[..., 1::2]
    return out


def hf_state_dict_from_params(params: Any, cfg: TransformerConfig) -> dict:
    """The inverse of :func:`llama_params_from_hf`: export this model's
    params as a ``LlamaForCausalLM``/``MistralForCausalLM`` state dict of
    float32 numpy arrays (wrap in ``torch.from_numpy`` to ``load_state_dict``
    into a HF model) — train on TPU, serve anywhere HF runs."""
    h, kh, d = cfg.num_heads, cfg.kv_heads, cfg.head_dim

    def qkv_weight(kernel, heads: int, rope: bool) -> np.ndarray:
        w = _np(kernel).transpose(1, 2, 0)  # [heads, d, hid]
        if rope:
            w = _split_rope_rows(w.transpose(0, 2, 1)).transpose(0, 2, 1)
        return np.ascontiguousarray(w.reshape(heads * d, -1))

    sd: dict[str, np.ndarray] = {
        "model.embed_tokens.weight": _np(params["embed"]["embedding"]),
        "model.norm.weight": _np(params["final_norm"]["scale"]),
    }
    if cfg.tie_embeddings:
        # HF tied models still materialise the tied key in their state dict,
        # and a strict load_state_dict requires it
        sd["lm_head.weight"] = sd["model.embed_tokens.weight"]
    else:
        sd["lm_head.weight"] = np.ascontiguousarray(_np(params["lm_head"]["kernel"]).T)
    for i in range(cfg.num_layers):
        layer = params[f"layer_{i}"]
        p = f"model.layers.{i}."
        sd[p + "input_layernorm.weight"] = _np(layer["attn_norm"]["scale"])
        sd[p + "post_attention_layernorm.weight"] = _np(layer["mlp_norm"]["scale"])
        attn, mlp = layer["attn"], layer["mlp"]
        sd[p + "self_attn.q_proj.weight"] = qkv_weight(attn["q_proj"]["kernel"], h, rope=True)
        sd[p + "self_attn.k_proj.weight"] = qkv_weight(attn["k_proj"]["kernel"], kh, rope=True)
        sd[p + "self_attn.v_proj.weight"] = qkv_weight(attn["v_proj"]["kernel"], kh, rope=False)
        sd[p + "self_attn.o_proj.weight"] = np.ascontiguousarray(_np(attn["o_proj"]["kernel"]).T)
        sd[p + "mlp.gate_proj.weight"] = np.ascontiguousarray(_np(mlp["gate_proj"]["kernel"]).T)
        sd[p + "mlp.up_proj.weight"] = np.ascontiguousarray(_np(mlp["up_proj"]["kernel"]).T)
        sd[p + "mlp.down_proj.weight"] = np.ascontiguousarray(_np(mlp["down_proj"]["kernel"]).T)
    return sd
