"""granite-4.0-h-micro on the training path, at a tiny preset that keeps the
published pattern (Mamba-2 mixers beside position-less GQA, one group, the four
multipliers, tied embeddings): ``ops/ssd.py`` against the token-by-token
recurrence, the program against ``benchmark/reference_granite.py`` on seeded
weights, the shares of one layer against the uncut layer, the published
config's keys and a checkpoint's layout, and what still refuses a ``mamba`` layer."""

import dataclasses
import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference_granite as ref, weights
from dmlcloud_tpu.models.hf import granite_params_from_hf, transformer_config_from_hf
from dmlcloud_tpu.models.transformer import (
    DecoderBlock, DecoderLM, TransformerConfig, llama_partition_rules, lm_loss, ssm_counters,
)
from dmlcloud_tpu.ops.ssd import ssd_chunked

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LAYERS = ["mamba", "mamba", "attention", "mamba"]
KV, SSM, GROUP, D, T, V = 4, 8, 2, 32, 32, 64  # published KV heads, mixer heads, query heads to a KV head


def tiny_config(kv_held=(0, KV), ssm_held=(0, SSM), **changed):
    """A configuration file's dict, as ``benchmark/configs/granite-4.0-h-micro.json`` is laid out."""
    kv, ssm = kv_held[1] - kv_held[0], ssm_held[1] - ssm_held[0]
    return {**dict(
        model_type="granitemoehybrid", hidden_size=D, num_attention_heads=GROUP * kv, num_key_value_heads=kv, intermediate_size=64,
        shared_intermediate_size=64, vocab_size=V, rms_norm_eps=1e-5, layer_types=LAYERS, num_hidden_layers=len(LAYERS),
        num_local_experts=0, num_experts_per_tok=0, position_embedding_type="nope", attention_multiplier=0.2,
        embedding_multiplier=12, residual_multiplier=0.22, logits_scaling=8, mamba_n_heads=ssm, mamba_d_head=4, mamba_d_state=16,
        mamba_n_groups=1, mamba_d_conv=4, mamba_chunk_size=8, mamba_expand=1, mamba_conv_bias=True, mamba_proj_bias=False,
        attention_bias=False, tie_word_embeddings=True, max_position_embeddings=T, hidden_act="silu",
        normalization_function="rmsnorm", rope_theta=10000, rope_scaling=None,
        published=dict(num_attention_heads=GROUP * KV, num_key_value_heads=KV, mamba_n_heads=SSM),
        train=dict(kv_heads_held=list(kv_held), mamba_heads_held=list(ssm_held)),
    ), **changed}


def program_config(config, **overrides):
    return transformer_config_from_hf(types.SimpleNamespace(**config), dtype=jnp.float32,
                                      head_dim=config["hidden_size"] // config["published"]["num_attention_heads"], **overrides)


def seeded(kv_held=(0, KV), ssm_held=(0, SSM), seed=7):
    config = tiny_config(kv_held, ssm_held)
    flat = ref.make_weights(dict(ref.spec(config)), seed)
    tokens = np.random.default_rng(seed).integers(0, V, (2, T), dtype=np.int32)
    return config, flat, tokens


CASES = {"whole": ((0, KV), (0, SSM)), "share": ((2, 4), (4, 8))}


# ------------------------------------------------------------ the operator


def scan_inputs(g, strong, b=2, t=32, h=4, p=8, n=16, seed=0):
    rng = np.random.default_rng(seed)
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    dt = jax.nn.softplus(f(b, t, h)) * (10.0 if strong else 1.0)
    a = -jnp.exp(jnp.asarray(rng.uniform(0.0, 2.7, size=(h,)), jnp.float32))
    return f(b, t, h, p), dt, a, f(b, t, g, n), f(b, t, g, n), f(h)


@pytest.mark.parametrize("groups, chunk, strong", [(1, 32, False), (1, 8, False), (2, 8, False), (1, 16, True), (2, 4, True)],
                         ids=["one-chunk", "chunks", "two-groups", "strong-decay", "strong-decay-two-groups"])
def test_the_chunked_scan_is_the_token_by_token_recurrence(groups, chunk, strong):
    args = scan_inputs(groups, strong)
    if strong:  # the decay over a chunk is beyond float32's exponent: exp(a_i) * exp(-a_j) would be 0 * inf
        assert float(jnp.min(jnp.cumsum((args[1] * args[2]).reshape(2, -1, chunk, 4), axis=2))) < -100.0
    with jax.default_matmul_precision("highest"):
        y = jax.jit(lambda *a: ssd_chunked(*a, chunk))(*args)
        want = jax.jit(ref.recurrence)(*args)
        np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=2e-5 * float(jnp.abs(want).max()))
        grad = lambda fn: jax.jit(jax.grad(lambda *a: jnp.sum(jnp.sin(fn(*a))), argnums=tuple(range(6))))
        for name, got, exp in zip("x dt A B C D".split(), grad(lambda *a: ssd_chunked(*a, chunk))(*args), grad(ref.recurrence)(*args)):
            assert bool(jnp.isfinite(got).all()), name
            np.testing.assert_allclose(np.asarray(got), np.asarray(exp), atol=1e-3 * float(jnp.abs(exp).max()), err_msg=name)


def test_the_scan_hands_back_the_states_it_carried_and_refuses_a_ragged_length():
    x, dt, a, b_in, c_in, skip = scan_inputs(1, False)
    with jax.default_matmul_precision("highest"):
        y, carried = ssd_chunked(x, dt, a, b_in, c_in, skip, 8, return_carry=True)
    assert carried.shape == (2, 4, 4, 8, 16) and float(jnp.abs(carried[:, 0]).max()) == 0.0
    state = jnp.zeros((2, 4, 8, 16))  # the recurrence's own state after the first chunk is what the second is handed
    for t in range(8):
        state = jnp.exp(dt[:, t] * a)[..., None, None] * state + (dt[:, t, :, None] * x[:, t])[..., None] * b_in[:, t, 0][:, None, None, :]
    np.testing.assert_allclose(np.asarray(carried[:, 1]), np.asarray(state), atol=1e-5)
    with pytest.raises(ValueError, match="multiple of the chunk"):
        ssd_chunked(x, dt, a, b_in, c_in, skip, 12)
    with pytest.raises(ValueError, match="groups"):
        ssd_chunked(x, dt, a, jnp.zeros((2, 32, 3, 16)), jnp.zeros((2, 32, 3, 16)), skip, 8)


def test_the_scan_takes_its_products_in_the_compute_dtype_and_its_decays_in_float32():
    args = scan_inputs(1, False)
    y = jax.jit(lambda x, dt, a, b, c, d: ssd_chunked(x.astype(jnp.bfloat16), dt, a, b.astype(jnp.bfloat16), c.astype(jnp.bfloat16), d, 8))(*args)
    assert y.dtype == jnp.bfloat16
    want = ref.recurrence(*args)
    assert float(jnp.abs(y.astype(jnp.float32) - want).max()) < 0.05 * float(jnp.abs(want).max())
    text = jax.jit(lambda *a: ssd_chunked(*a, 8)).lower(args[0].astype(jnp.bfloat16), *args[1:]).as_text()
    assert "exponential" in text and "bf16" in text
    for line in text.splitlines():  # every exponential is float32
        if "stablehlo.exponential" in line:
            assert "f32" in line and "bf16" not in line, line


# ------------------------------------------------------------ the model against the reference


@pytest.fixture(scope="module")
def both_sides():
    """Loss and gradients of program and reference, once for every case that reads them."""
    out = {}
    for case, (kv_held, ssm_held) in CASES.items():
        config, flat, tokens = seeded(kv_held, ssm_held)
        model = DecoderLM(program_config(config, remat=case == "share"))

        def loss(p):
            logits, stats = model.apply({"params": p}, tokens, mutable=["ssm_stats"])
            return lm_loss(logits, tokens), ssm_counters(stats)

        (l, counters), g = jax.jit(jax.value_and_grad(loss, has_aux=True))(ref.tree(flat))
        want_l, want_g = jax.jit(jax.value_and_grad(lambda p: ref.lm_loss(p, tokens, dict(ref.spec(config)), "reference")))(flat)
        got = {weights.path_name(p): x for p, x in jax.tree_util.tree_flatten_with_path(g)[0]}
        out[case] = (float(l), float(want_l), got, want_g, counters)
    return out


@pytest.mark.parametrize("case", list(CASES))
def test_logits_match_the_reference(case):
    config, flat, tokens = seeded(*CASES[case])
    model = DecoderLM(program_config(config))
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0), tokens)["params"]
    params = ref.tree(flat)
    assert jax.tree_util.tree_map(lambda x: x.shape, shapes) == jax.tree_util.tree_map(lambda x: x.shape, params)
    got = jax.jit(model.apply)({"params": params}, tokens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref.logits(flat, tokens, ref.spec(config))), atol=2e-4)


@pytest.mark.parametrize("impl", ["dot", "flash"])
def test_the_flash_path_takes_the_score_scale_and_no_positions(impl):
    config, flat, tokens = seeded(*CASES["share"])
    config = {**config, "mamba_chunk_size": 16}
    tokens = np.concatenate([tokens, tokens[:, ::-1]], axis=1)  # 64 positions: the kernels' smallest block
    got = jax.jit(DecoderLM(program_config(config, attn_impl=impl, max_seq_len=64)).apply)({"params": ref.tree(flat)}, tokens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref.logits(flat, tokens, ref.spec(config))), atol=2e-4)


@pytest.mark.parametrize("case", list(CASES))
def test_loss_matches_the_reference(both_sides, case):
    got, want, _, _, counters = both_sides[case]
    assert got == pytest.approx(want, rel=1e-5)
    assert float(counters["ssm/state_absmax"]) > 0


@pytest.mark.parametrize("case", list(CASES))
@pytest.mark.parametrize("kind", ["attn/q_proj", "attn/k_proj", "attn/v_proj", "attn/o_proj", "mlp/", "mamba/in_proj", "mamba/conv_weight",
                                  "mamba/conv_bias", "mamba/A_log", "mamba/dt_bias", "mamba/D", "mamba/norm_scale", "mamba/out_proj",
                                  "_norm/scale", "embed"])
def test_gradients_match_the_reference(both_sides, case, kind):
    _, _, got, want, _ = both_sides[case]
    names = [n for n in want if kind in n]
    assert names and set(got) == set(want)
    for n in names:
        scale = float(jnp.abs(want[n]).max())
        np.testing.assert_allclose(np.asarray(got[n]), np.asarray(want[n]), atol=3e-4 * scale + 1e-9, err_msg=n)


@pytest.mark.parametrize("changed, moved", [({"residual_multiplier": 1.0}, True), ({"embedding_multiplier": 1.0}, True),
                                            ({"logits_scaling": 1.0}, True), ({"attention_multiplier": 0.5}, True), ({}, False)],
                         ids=["residual", "embedding", "logits", "attention", "none"])
def test_each_multiplier_reaches_the_logits(changed, moved):
    config, flat, tokens = seeded()
    want = ref.logits(flat, tokens, ref.spec(config))
    got = jax.jit(DecoderLM(program_config({**config, **changed})).apply)({"params": ref.tree(flat)}, tokens)
    assert (float(jnp.abs(got - want).max()) > 1e-2) == moved


def test_the_defaults_leave_a_model_without_these_keys_as_it_was():
    cfg = TransformerConfig(num_layers=1, hidden_dim=16, num_heads=2, head_dim=8, mlp_dim=32, vocab_size=32, dtype=jnp.float32)
    assert (cfg.position_embedding, cfg.attention_multiplier, cfg.embedding_multiplier, cfg.residual_multiplier, cfg.logits_scaling,
            cfg.heads_axis) == ("rope", None, 1.0, 1.0, 1.0, None)
    tokens = jnp.arange(8)[None] % 32
    params = DecoderLM(cfg).init(jax.random.PRNGKey(0), tokens)["params"]
    explicit = dataclasses.replace(cfg, attention_multiplier=8**-0.5)
    np.testing.assert_allclose(np.asarray(DecoderLM(cfg).apply({"params": params}, tokens)),
                               np.asarray(DecoderLM(explicit).apply({"params": params}, tokens)), atol=1e-6)
    with pytest.raises(ValueError, match="position_embedding"):
        TransformerConfig(position_embedding="alibi")
    with pytest.raises(ValueError, match="mamba_n_heads"):
        TransformerConfig(num_layers=1, layer_types=("mamba",))


# ------------------------------------------------------------ the share and the model


@pytest.mark.parametrize("chips", [2, 4])
@pytest.mark.parametrize("layer", [0, 2], ids=["mamba", "attention"])
def test_the_shares_of_a_layer_add_up_to_the_uncut_layer(chips, layer):
    """Each of ``chips`` holders runs the block on its heads under one axis
    name: the gated norm's sum of squares and channel count and the operator's
    partial results are summed by the block's own ``psum``, the MLP is every
    holder's alike, and each holder's output is the uncut reference's."""
    whole = tiny_config()
    s = dict(ref.spec(whole))
    x = jax.random.normal(jax.random.PRNGKey(3), (2, T, D), jnp.float32)
    prefix = f"layer_{layer}/"
    want = ref.block(x, ref.layer_of(ref.make_weights(s, 11), layer), s, layer, "reference")
    shares = []
    for c in range(chips):
        config = tiny_config((c * KV // chips, (c + 1) * KV // chips), (c * SSM // chips, (c + 1) * SSM // chips))
        flat = ref.make_weights(dict(ref.spec(config)), 11, names=[n for n in ref.all_shapes(dict(ref.spec(config))) if n.startswith(prefix)])
        shares.append(ref.tree(flat)[f"layer_{layer}"])
    cfg = program_config(config, heads_axis="heads")
    block = DecoderBlock(cfg, kind=cfg.layer_kind(layer), attention=cfg.attention_layer(layer))
    stacked = jax.tree_util.tree_map(lambda *leaves: jnp.stack(leaves), *shares)
    got = jax.jit(jax.vmap(lambda p: block.apply({"params": p}, x, None, None), axis_name="heads"))(stacked)
    for c in range(chips):
        np.testing.assert_allclose(np.asarray(got[c]), np.asarray(want), atol=2e-5)
    # and alone, without the exchange, a share is NOT the layer: the sums are what ties it to the model
    alone = DecoderBlock(program_config(config), kind=cfg.layer_kind(layer), attention=cfg.attention_layer(layer))
    assert float(jnp.abs(alone.apply({"params": shares[-1]}, x, None, None) - want).max()) > 1e-3


# ------------------------------------------------------------ the published keys


def published_config():
    with open(os.path.join(ROOT, "benchmark", "configs", "granite-4.0-h-micro.json")) as f:
        return json.load(f)


def test_the_published_keys_become_the_programs_configuration():
    config = published_config()
    cfg = transformer_config_from_hf(types.SimpleNamespace(**config), head_dim=64)
    assert cfg.layer_types == ("mamba",) * 5 + ("full_attention",) + ("mamba",) * 4 and cfg.num_layers == 10
    assert (cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state, cfg.mamba_n_groups, cfg.mamba_d_conv, cfg.mamba_chunk_size) == (32, 64, 128, 1, 4, 256)
    assert (cfg.embedding_multiplier, cfg.residual_multiplier, cfg.logits_scaling, cfg.attention_multiplier) == (12.0, 0.22, 8.0, 0.015625)
    assert (cfg.position_embedding, cfg.tie_embeddings, cfg.mlp_dim, cfg.norm_eps, cfg.num_experts) == ("nope", True, 8192, 1e-5, 0)
    assert (cfg.num_heads, cfg.kv_heads, cfg.head_dim, cfg.hidden_dim, cfg.vocab_size) == (16, 4, 64, 2048, 12544)
    whole = {**config, **{k: config["published"][k] for k in config["reduced"]}}
    assert transformer_config_from_hf(types.SimpleNamespace(**whole)).head_dim == 64  # the published head count gives the head size
    assert whole["mamba_n_heads"] * whole["mamba_d_head"] == whole["mamba_expand"] * whole["hidden_size"]


@pytest.mark.parametrize("changed, match", [({"num_local_experts": 8}, "num_local_experts"), ({"mamba_proj_bias": True}, "bias"),
                                            ({"mamba_conv_bias": False}, "bias"), ({"position_embedding_type": "alibi"}, "alibi"),
                                            ({"layer_types": ["mamba", "conv", "attention", "mamba"]}, "conv"),
                                            ({"normalization_function": "layernorm"}, "rmsnorm")])
def test_what_the_model_cannot_honour_is_refused(changed, match):
    with pytest.raises(ValueError, match=match):
        transformer_config_from_hf(types.SimpleNamespace(**{**tiny_config(), **changed}))


def test_a_checkpoints_layout_becomes_the_programs_tree():
    """``input_linear`` split into gate and up, the conv's ``[C, 1, K]`` as ``[K, C]``, every matrix transposed."""
    config, flat, tokens = seeded()
    cfg = program_config(config)
    t = lambda x: np.asarray(x).T
    sd = {"model.embed_tokens.weight": np.asarray(flat["embed/embedding"]), "model.norm.weight": np.asarray(flat["final_norm/scale"]),
          "lm_head.weight": np.asarray(flat["embed/embedding"])}
    for i, kind in enumerate(LAYERS):
        w, p = ref.layer_of(flat, i), f"model.layers.{i}."
        sd[p + "post_attention_layernorm.weight"] = np.asarray(w["mlp_norm/scale"])
        sd[p + "shared_mlp.input_linear.weight"] = np.concatenate([t(w["mlp/gate_proj/kernel"]), t(w["mlp/up_proj/kernel"])], axis=0)
        sd[p + "shared_mlp.output_linear.weight"] = t(w["mlp/down_proj/kernel"])
        if kind == "mamba":
            sd[p + "input_layernorm.weight"] = np.asarray(w["mamba_norm/scale"])
            sd[p + "mamba.in_proj.weight"], sd[p + "mamba.out_proj.weight"] = t(w["mamba/in_proj/kernel"]), t(w["mamba/out_proj/kernel"])
            sd[p + "mamba.conv1d.weight"], sd[p + "mamba.conv1d.bias"] = t(w["mamba/conv_weight"])[:, None, :], np.asarray(w["mamba/conv_bias"])
            for name, key in (("A_log", "A_log"), ("dt_bias", "dt_bias"), ("D", "D"), ("norm.weight", "norm_scale")):
                sd[p + "mamba." + name] = np.asarray(w["mamba/" + key])
        else:
            sd[p + "input_layernorm.weight"] = np.asarray(w["attn_norm/scale"])
            for name in "qkv":
                sd[p + f"self_attn.{name}_proj.weight"] = t(np.asarray(w[f"attn/{name}_proj/kernel"]).reshape(D, -1))
            sd[p + "self_attn.o_proj.weight"] = t(w["attn/o_proj/kernel"])
    params = granite_params_from_hf(sd, cfg)
    want = ref.tree(flat)
    assert jax.tree_util.tree_structure(params) == jax.tree_util.tree_structure(want)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(params)[0], jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=weights.path_name(path))
    with pytest.raises(ValueError, match="unconverted"):
        granite_params_from_hf({**sd, "model.layers.0.block_sparse_moe.router.weight": np.zeros((2, 2))}, cfg)


# ------------------------------------------------------------ what still refuses a mamba layer


def test_serving_generate_and_a_packed_row_refuse_a_mamba_layer_by_name():
    from dmlcloud_tpu.models.generate import generate, init_cache
    from dmlcloud_tpu.serve import ServeEngine

    config, flat, tokens = seeded()
    cfg = program_config(config)
    model, params = DecoderLM(cfg), ref.tree(flat)
    with pytest.raises(NotImplementedError, match="'mamba'.*not a KV cache"):
        ServeEngine(model, params, num_blocks=4, block_size=4, max_slots=2)
    with pytest.raises(NotImplementedError, match="'mamba'"):
        init_cache(cfg, 1, 16)
    with pytest.raises(NotImplementedError, match="'mamba'"):
        generate(model, {"params": params}, jnp.asarray(tokens[:, :8]), max_new_tokens=2)
    with pytest.raises(NotImplementedError, match="a packed row cannot run a model with layers of kind 'mamba'"):
        model.apply({"params": params}, jnp.asarray(tokens), segment_ids=jnp.ones_like(tokens))


@pytest.mark.parametrize("extra", [dict(cache=(None, None)), dict(seg_info=object()), dict(paged=object()),
                                   dict(adapters=({"attn": {}}, jnp.zeros((1,), jnp.int32)))],
                         ids=["cache", "packed", "paged", "attn-adapters"])
def test_a_mamba_block_built_outside_the_model_refuses_what_it_cannot_honour(extra):
    cfg = TransformerConfig(num_layers=1, hidden_dim=16, dtype=jnp.float32, layer_types=("mamba",), mamba_n_heads=2, mamba_d_head=8,
                            mamba_d_state=4, mamba_chunk_size=4)
    block = DecoderBlock(cfg, kind="mamba")
    x = jnp.zeros((1, 4, 16))
    block.init(jax.random.PRNGKey(0), x, None, None)  # alone it runs
    with pytest.raises(NotImplementedError, match="'mamba' layer"):
        block.init(jax.random.PRNGKey(0), x, None, None, **extra)


# ------------------------------------------------------------ sharding rules, phases, the counter


def test_the_partition_rules_shard_the_mixers_projections():
    import re

    first = lambda path: next(spec for pattern, spec in llama_partition_rules() if re.search(pattern, path))
    assert tuple(first("layer_0/mamba/in_proj/kernel")) == ("fsdp", "model")
    assert tuple(first("layer_0/mamba/out_proj/kernel")) == ("model", "fsdp")
    assert tuple(first("layer_0/mamba/norm_scale")) == () and tuple(first("layer_0/mamba/A_log")) == ()


def test_the_mixers_phases_have_names_of_their_own_forward_backward_and_recomputed():
    from dmlcloud_tpu.utils.profiling import PHASES, phase_map, phase_of

    assert {"ssm_proj", "ssm_conv", "ssm_scan", "ssm_gate_norm"} <= set(PHASES)
    assert phase_of("jit(train_step)/jvp(DecoderLM)/layer_2/mamba/ssm_proj/in_proj/dot_general") == ("ssm_proj", "fwd")
    assert phase_of("jit(train_step)/transpose(jvp(DecoderLM))/layer_2/mamba/ssm_scan/while/body/mul") == ("ssm_scan", "bwd")
    config, flat, tokens = seeded()
    model = DecoderLM(program_config(config, remat=True))
    step = jax.jit(jax.grad(lambda p: lm_loss(model.apply({"params": p}, tokens), tokens)))
    found = set(phase_map(step.lower(ref.tree(flat)).compile()).values())
    for phase in ("ssm_proj", "ssm_conv", "ssm_scan", "ssm_gate_norm"):
        assert {(phase, "fwd"), (phase, "bwd")} <= found or {(phase, "recompute"), (phase, "bwd")} <= found, (phase, found)
    assert ("ssm_scan", "recompute") in found  # the block's recomputed forward lands in the phase, not in ``unattributed``


def test_the_counter_is_the_largest_carried_state_of_any_mamba_layer():
    config, flat, tokens = seeded()
    model = DecoderLM(program_config(config))
    _, stats = model.apply({"params": ref.tree(flat)}, tokens, mutable=["ssm_stats"])
    per_layer = jax.tree_util.tree_leaves(stats["ssm_stats"])
    assert len(per_layer) == LAYERS.count("mamba")
    assert float(ssm_counters(stats)["ssm/state_absmax"]) == max(float(v) for v in per_layer) > 0
    assert ssm_counters({}) == {}
