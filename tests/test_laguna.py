"""Laguna-S-2.1 on the training path, at a tiny preset that keeps the published
pattern (``[full, sliding, sliding, sliding, full]``, one leading dense layer,
softmax top-4-of-16 experts beside a shared expert, window layers with more
query heads than full layers, a rotary table per layer kind, per-head gates):
the program against ``benchmark/reference_laguna.py`` on seeded weights, the
YaRN table and the half rotation against a direct formula, the gate, the
shares of one layer against the uncut layer, the published config's shapes,
and the serving engine's refusal."""

import json
import math
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference_laguna as ref, weights
from dmlcloud_tpu.models.hf import transformer_config_from_hf
from dmlcloud_tpu.models.moe import MoEConfig, MoEMLP, moe_counters
from dmlcloud_tpu.models.transformer import (
    Attention, DecoderLM, TransformerConfig, apply_rope, lm_loss, rope_frequencies,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LAYERS = ["full_attention", "sliding_attention", "sliding_attention", "sliding_attention", "full_attention"]
E, K, KV, HD, D, T = 16, 4, 4, 8, 32, 32
GROUPS = {"full_attention": 2, "sliding_attention": 3}  # query heads to a KV head
ROPE = {
    "full_attention": {"rope_theta": 5000.0, "rope_type": "yarn", "factor": 8, "original_max_position_embeddings": 16,
                       "beta_slow": 1, "beta_fast": 4, "attention_factor": 0.1 * math.log(8) + 1, "partial_rotary_factor": 0.5},
    "sliding_attention": {"rope_type": "default", "rope_theta": 100.0, "partial_rotary_factor": 1},
}


def tiny_config(held=None, kv_held=None, experts=E, kv_heads=KV):
    """A configuration file's dict, as ``benchmark/configs/laguna-s-2.1.json`` is laid out."""
    held, kv_held = held or (0, experts), kv_held or (0, kv_heads)
    kv = kv_held[1] - kv_held[0]
    heads = [GROUPS[k] * kv for k in LAYERS]
    return dict(
        model_type="laguna", hidden_size=D, num_attention_heads=heads[0], num_key_value_heads=kv, head_dim=HD,
        num_attention_heads_per_layer=heads, intermediate_size=64, moe_intermediate_size=16,
        shared_expert_intermediate_size=24, vocab_size=64, rms_norm_eps=1e-6, rope_parameters=ROPE, sliding_window=8,
        layer_types=LAYERS, mlp_layer_types=["dense"] + ["sparse"] * 4, mlp_only_layers=[0], decoder_sparse_step=1,
        gating="per-head", gating_types=["per_head"] * 5, num_hidden_layers=5, num_experts=held[1] - held[0],
        num_experts_per_tok=K, norm_topk_prob=True, moe_routed_scaling_factor=2.5, moe_router_logit_softcapping=0,
        moe_apply_router_weight_on_input=False, attention_bias=False, tie_word_embeddings=False, max_position_embeddings=T,
        published={"num_experts": experts, "num_key_value_heads": kv_heads},
        train={"experts_held": list(held), "kv_heads_held": list(kv_held)},
    )


def program_config(config, **overrides):
    return transformer_config_from_hf(
        types.SimpleNamespace(**{**config, "num_experts": config["published"]["num_experts"]}),
        experts_held=tuple(config["train"]["experts_held"]), dtype=jnp.float32, **overrides)


def seeded(held=(0, E), kv_held=(0, KV), seed=7):
    config = tiny_config(held, kv_held)
    flat = ref.make_weights(dict(ref.spec(config)), seed)
    tokens = np.random.default_rng(seed).integers(0, 64, (2, T), dtype=np.int32)
    return config, flat, tokens


CASES = {"whole": ((0, E), (0, KV)), "share": ((4, 8), (3, 4))}


@pytest.fixture(scope="module")
def both_sides():
    """Loss and gradients of program and reference, once for every case that reads them."""
    out = {}
    for case, (held, kv_held) in CASES.items():
        config, flat, tokens = seeded(held, kv_held)
        model = DecoderLM(program_config(config))

        def loss(p):
            logits, stats = model.apply({"params": p}, tokens, mutable=["moe_stats"])
            return lm_loss(logits, tokens), moe_counters(stats)

        (l, counters), g = jax.jit(jax.value_and_grad(loss, has_aux=True))(ref.tree(flat))
        want_l, want_g = jax.jit(jax.value_and_grad(lambda p: ref.lm_loss(p, tokens, dict(ref.spec(config)), "reference")))(flat)
        got = {weights.path_name(p): x for p, x in jax.tree_util.tree_flatten_with_path(g)[0]}
        out[case] = (float(l), float(want_l), got, want_g, counters)
    return out


@pytest.mark.parametrize("case", ["whole", "share", "two-kv-heads"])
def test_logits_match_the_reference(case):
    held, kv_held = CASES.get(case, ((0, 4), (1, 3)))
    config, flat, tokens = seeded(held, kv_held)
    model = DecoderLM(program_config(config))
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0), tokens)["params"]
    params = ref.tree(flat)
    assert jax.tree_util.tree_map(lambda x: x.shape, shapes) == jax.tree_util.tree_map(lambda x: x.shape, params)
    got = jax.jit(model.apply)({"params": params}, tokens)
    want = ref.logits(flat, tokens, ref.spec(config))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4)


@pytest.mark.parametrize("impl", ["dot", "flash"])
def test_the_flash_path_gives_the_dot_paths_logits(impl):
    config, flat, tokens = seeded(*CASES["share"])
    tokens = np.concatenate([tokens, tokens[:, ::-1]], axis=1)  # 64 positions: the kernels' smallest block
    got = jax.jit(DecoderLM(program_config(config, attn_impl=impl, max_seq_len=64)).apply)({"params": ref.tree(flat)}, tokens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref.logits(flat, tokens, ref.spec(config))), atol=2e-4)


@pytest.mark.parametrize("case", list(CASES))
def test_loss_matches_the_reference(both_sides, case):
    got, want, _, _, counters = both_sides[case]
    assert got == pytest.approx(want, rel=1e-5)
    if case == "whole":  # every pair of a held expert is counted: all N * k where all are held
        assert float(counters["moe/pairs_held"]) == 4 * 2 * T * K
    assert float(counters["moe/overflow_layers"]) == 0


@pytest.mark.parametrize("case", list(CASES))
@pytest.mark.parametrize("kind", ["attn/q_proj", "attn/k_proj", "attn/v_proj", "attn/g_proj", "attn/o_proj", "mlp/", "moe/router",
                                  "moe/moe/", "moe/shared_expert", "norm", "embed", "lm_head"])
def test_gradients_match_the_reference(both_sides, case, kind):
    _, _, got, want, _ = both_sides[case]
    names = [n for n in want if kind in n]
    assert names and set(got) == set(want)
    for n in names:
        scale = float(jnp.abs(want[n]).max())
        np.testing.assert_allclose(np.asarray(got[n]), np.asarray(want[n]), atol=2e-4 * scale + 1e-9, err_msg=n)


# ------------------------------------------------------------ rotary tables


def direct_yarn(position, j, rot, p):
    """Pair ``j``'s angle at ``position``, written out for one pair."""
    base = p["rope_theta"] ** (-2.0 * j / rot)
    turns = p["original_max_position_embeddings"] * base / (2 * math.pi)  # over the original context
    find = lambda n: rot * math.log(p["original_max_position_embeddings"] / (n * 2 * math.pi)) / (2 * math.log(p["rope_theta"]))
    low, high = max(math.floor(find(p["beta_fast"])), 0), min(math.ceil(find(p["beta_slow"])), rot - 1)
    blend = min(max((j - low) / (high - low), 0.0), 1.0)
    if turns > p["beta_fast"]:
        assert blend == 0.0
    return position * (base * (1 - blend) + base / p["factor"] * blend)


@pytest.mark.parametrize("params", [ROPE["full_attention"],
                                    {"rope_theta": 500000, "rope_type": "yarn", "factor": 128, "original_max_position_embeddings": 8192,
                                     "beta_slow": 1, "beta_fast": 32, "attention_factor": 1.4852030263919618, "partial_rotary_factor": 0.5}],
                         ids=["tiny", "published"])
def test_the_yarn_table_is_the_direct_formula(params):
    from dmlcloud_tpu.models.hf import _rope_scaling_from_hf

    hd = 128 if params["factor"] == 128 else HD
    rot = hd // 2
    cos, sin = rope_frequencies(hd, 64, float(params["rope_theta"]), _rope_scaling_from_hf(params), params["partial_rotary_factor"])
    assert cos.shape == (64, rot // 2)
    want = np.array([[direct_yarn(t, j, rot, params) for j in range(rot // 2)] for t in range(64)])
    np.testing.assert_allclose(np.asarray(cos), np.cos(want) * params["attention_factor"], atol=2e-5)
    np.testing.assert_allclose(np.asarray(sin), np.sin(want) * params["attention_factor"], atol=2e-5)
    ref_cos, ref_sin = ref.rope_table(64, hd, params)
    np.testing.assert_allclose(np.asarray(ref_cos), np.asarray(cos), atol=2e-5)
    np.testing.assert_allclose(np.asarray(ref_sin), np.asarray(sin), atol=2e-5)
    if params["factor"] == 128:  # the published table: its fastest pairs untouched, its slowest slowed by the whole factor
        assert want[1, 0] == pytest.approx(1.0) and want[1, -1] == pytest.approx(500000 ** (-62 / 64) / 128)
        assert params["attention_factor"] == pytest.approx(0.1 * math.log(128) + 1)  # YaRN's own default: the config publishes it


def test_half_of_the_head_rotates_and_the_rest_passes():
    cos, sin = rope_frequencies(HD, T, 100.0, None, 0.5)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, T, 3, HD))
    y = apply_rope(x, cos, sin)
    np.testing.assert_array_equal(np.asarray(y[..., HD // 2 :]), np.asarray(x[..., HD // 2 :]))
    full_cos, full_sin = rope_frequencies(HD // 2, T, 100.0)
    np.testing.assert_allclose(np.asarray(y[..., : HD // 2]), np.asarray(apply_rope(x[..., : HD // 2], full_cos, full_sin)), atol=1e-6)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref.rope(x, *ref.rope_table(T, HD, {"rope_theta": 100.0, "partial_rotary_factor": 0.5}))), atol=1e-6)
    # positions past the first turn the rotating half, and only it
    assert float(jnp.abs(y[0, 1:, :, : HD // 2] - x[0, 1:, :, : HD // 2]).max()) > 0.1


# ------------------------------------------------------------ one layer: kinds, gate, shares


def layer_weights(s, i, seed=3):
    return ref.layer_of(ref.make_weights(s, seed, [f"layer_{i}/{n}" for n in ref.layer_shapes(s, i)]), i)


def attention_params(w):
    return {name.split("/")[1]: {"kernel": x} for name, x in w.items() if name.startswith("attn/")}


def test_a_window_layer_and_a_full_layer_differ_in_heads_window_and_table():
    cfg = program_config(tiny_config())
    full, window = cfg.attention_layer(0), cfg.attention_layer(1)
    assert (full.kind, full.num_heads, full.window) == ("full_attention", 8, None)
    assert (window.kind, window.num_heads, window.window) == ("sliding_attention", 12, 8)
    assert full.rope[0] == 5000.0 and full.rope[1][0] == "yarn" and full.rope[2] == 0.5 and window.rope == (100.0, None, 1.0)
    shapes = jax.eval_shape(DecoderLM(cfg).init, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    assert shapes["layer_0"]["attn"]["q_proj"]["kernel"].shape == (D, 8, HD) and shapes["layer_1"]["attn"]["q_proj"]["kernel"].shape == (D, 12, HD)
    assert shapes["layer_1"]["attn"]["g_proj"]["kernel"].shape == (D, 12) and shapes["layer_1"]["attn"]["o_proj"]["kernel"].shape == (12 * HD, D)
    # the whole-model meaning stays for a config that names no layers
    plain = TransformerConfig(num_layers=2, sliding_window=4)
    assert plain.attention_layer(1).window == 4 and plain.attention_layer().window == 4
    named = TransformerConfig(num_layers=2, sliding_window=4, layer_types=("full_attention", "sliding_attention"))
    assert [named.attention_layer(i).window for i in range(2)] == [None, 4]


@pytest.mark.parametrize("i", [0, 1], ids=["full", "window"])
def test_one_attention_layer_is_the_references(i):
    config = tiny_config()
    s, cfg = dict(ref.spec(config)), program_config(config)
    w = layer_weights(s, i)
    u = jax.random.normal(jax.random.PRNGKey(i), (2, T, D))
    layer = cfg.attention_layer(i)
    cos, sin = rope_frequencies(HD, T, *layer.rope)
    got = Attention(cfg, layer).apply({"params": attention_params(w)}, u, cos, sin)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref.attention_op(u, w, s, i, "reference")), atol=2e-5)
    if i == 1:  # a key further back than the window changes nothing
        far = Attention(cfg, layer).apply({"params": attention_params(w)}, u.at[:, 0].add(1.0), cos, sin)
        np.testing.assert_allclose(np.asarray(far[:, 8:]), np.asarray(got[:, 8:]), atol=1e-6)


def test_the_gate_is_one_sigmoid_a_head_on_the_heads_output():
    config = tiny_config()
    s, cfg = dict(ref.spec(config)), program_config(config)
    w = layer_weights(s, 1)
    u = jax.random.normal(jax.random.PRNGKey(5), (1, T, D))
    layer = cfg.attention_layer(1)
    cos, sin = rope_frequencies(HD, T, *layer.rope)
    params = attention_params(w)
    gated = Attention(cfg, layer).apply({"params": params}, u, cos, sin)
    no_gate = {k: v for k, v in params.items() if k != "g_proj"}
    # with W_g = 0 every gate is a half
    halves = Attention(cfg, layer).apply({"params": {**params, "g_proj": {"kernel": jnp.zeros_like(w["attn/g_proj/kernel"])}}}, u, cos, sin)
    plain = Attention(program_config(config, gating=None), layer).apply({"params": no_gate}, u, cos, sin)
    np.testing.assert_allclose(np.asarray(halves), 0.5 * np.asarray(plain), atol=1e-6)
    assert float(jnp.abs(gated - plain).max()) > 1e-3


@pytest.mark.parametrize("i", [0, 2], ids=["dense-full-layer", "expert-window-layer"])
def test_the_shares_of_one_layer_add_up_to_the_uncut_layer(i):
    """32 expert shares (one expert each) and 8 head shares (one KV head and its
    query group each), the shared expert and the router counted once, against
    the uncut reference's whole window layer with experts; and the dense MLP of
    layer 0, whole on every chip, counted once."""
    experts, kv_heads = 32, 8
    whole = tiny_config(experts=experts, kv_heads=kv_heads)
    s = dict(ref.spec(whole))
    w = layer_weights(s, i)
    x = jax.random.normal(jax.random.PRNGKey(i), (2, T, D))
    want = ref.block(x, w, s, i, "reference")
    cfg = program_config(whole)
    norm = lambda v, scale: ref.base.rms_norm(v, scale, 1e-6)
    u = norm(x, w["attn_norm/scale"])
    attn = 0
    for g in range(kv_heads):  # a head share's model has one KV head and its group of query heads; its weights are the whole layer's there
        share_config = tiny_config(kv_held=(g, g + 1), experts=experts, kv_heads=kv_heads)
        share_s, share_cfg = dict(ref.spec(share_config)), program_config(share_config)
        sw = layer_weights(share_s, i)
        group = GROUPS[LAYERS[i]]
        np.testing.assert_array_equal(np.asarray(sw["attn/q_proj/kernel"]), np.asarray(w["attn/q_proj/kernel"][:, g * group : (g + 1) * group]))
        np.testing.assert_array_equal(np.asarray(sw["attn/o_proj/kernel"]), np.asarray(w["attn/o_proj/kernel"][g * group * HD : (g + 1) * group * HD]))
        layer = share_cfg.attention_layer(i)
        assert layer.num_heads == group
        attn = attn + Attention(share_cfg, layer).apply({"params": attention_params(sw)}, u, *rope_frequencies(HD, T, *layer.rope))
    h = x + attn
    u2 = norm(h, w["mlp_norm/scale"])
    if i == 0:
        ffn = ref.swiglu(u2, w["mlp/gate_proj/kernel"], w["mlp/up_proj/kernel"], w["mlp/down_proj/kernel"], "reference")
    else:
        ffn, pairs = 0, 0
        for e in range(experts):
            moe_cfg = MoEConfig(num_experts=experts, top_k=K, hidden_dim=D, mlp_dim=16, scoring_func="softmax", routed_scaling_factor=2.5,
                                shared_expert_intermediate_size=24 if e == 0 else 0, experts_held=(e, e + 1), dtype=jnp.float32)
            params = {"router": {"kernel": w["moe/router/kernel"]}, "moe/gate_proj": w["moe/moe/gate_proj"][e : e + 1],
                      "moe/up_proj": w["moe/moe/up_proj"][e : e + 1], "moe/down_proj": w["moe/moe/down_proj"][e : e + 1]}
            if e == 0:
                params["shared_expert"] = {n: {"kernel": w[f"moe/shared_expert/{n}/kernel"]} for n in ("gate_proj", "up_proj", "down_proj")}
            y, stats = MoEMLP(moe_cfg).apply({"params": params}, u2, mutable=["moe_stats"])
            ffn, pairs = ffn + y, pairs + float(moe_counters(stats)["moe/pairs_held"])
        assert pairs == 2 * T * K  # every pair lies in exactly one share
    np.testing.assert_allclose(np.asarray(h + ffn), np.asarray(want), atol=3e-5)


@pytest.mark.parametrize("heads, window", [(3, 32), (2, None)], ids=["window-under-a-block-group-3", "full-group-2"])
def test_the_kernels_take_one_kv_head_with_its_group_and_a_window_shorter_than_a_block(heads, window):
    """The cell's calls in small: a window a quarter of the key block, so an edge pair is mostly masked."""
    from dmlcloud_tpu.ops.flash_attention import _reference_attention, flash_attention

    q, k, v = (jax.random.normal(jax.random.PRNGKey(i), (1, 256, h, 128), jnp.float32) for i, h in enumerate((heads, 1, 1)))
    kernels = lambda q, k, v: flash_attention(q, k, v, window=window, block_q=64, block_k=128, impl="pallas", interpret=True)
    plain = lambda q, k, v: _reference_attention(q, k, v, True, 128**-0.5, window)
    weigh = jax.random.normal(jax.random.PRNGKey(9), (1, 256, heads, 128))
    got = jax.jit(jax.value_and_grad(lambda *a: jnp.sum(kernels(*a) * weigh), argnums=(0, 1, 2)))(q, k, v)
    want = jax.jit(jax.value_and_grad(lambda *a: jnp.sum(plain(*a) * weigh), argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-3 * float(jnp.abs(b).max()))


# ------------------------------------------------------------ configuration


def published_config():
    with open(os.path.join(ROOT, "benchmark", "configs", "laguna-s-2.1.json")) as f:
        config = json.load(f)
    skip = ("published", "train", "limits", "limits_why", "reduced", "reduced_why", "deployment", "assumed", "source")
    return {**{k: v for k, v in config.items() if k not in skip}, **config["published"]}, config


def test_the_published_config_keys_give_the_published_shapes():
    published, _ = published_config()
    cfg = transformer_config_from_hf(types.SimpleNamespace(**published))
    assert (cfg.num_layers, cfg.hidden_dim, cfg.num_heads, cfg.kv_heads, cfg.head_dim) == (48, 3072, 48, 8, 128)
    assert (cfg.mlp_dim, cfg.moe_intermediate_size, cfg.shared_expert_intermediate_size) == (12288, 1024, 1024)
    assert (cfg.num_experts, cfg.num_experts_per_tok, cfg.num_dense_layers, cfg.vocab_size) == (256, 10, 1, 100352)
    assert (cfg.scoring_func, cfg.norm_topk_prob, cfg.routed_scaling_factor, cfg.gating) == ("softmax", True, 2.5, "per-head")
    assert (cfg.sliding_window, cfg.norm_eps, cfg.tie_embeddings, cfg.use_expert_bias, cfg.qk_norm) == (512, 1e-6, False, False, False)
    assert cfg.layer_types.count("sliding_attention") == 36 and cfg.layer_types.count("full_attention") == 12
    assert [cfg.attention_layer(i).num_heads for i in (0, 1, 4, 47)] == [48, 72, 48, 72]
    assert [cfg.attention_layer(i).window for i in (0, 1, 4, 47)] == [None, 512, None, 512]
    assert cfg.attention_layer(0).rope == (500000.0, ("yarn", 128.0, 32.0, 1.0, 8192, 1.4852030263919618), 0.5)
    assert cfg.attention_layer(3).rope == (10000.0, None, 1.0) and cfg.experts_held is None
    assert [cfg.is_expert_layer(i) for i in (0, 1, 47)] == [False, True, True]


def test_the_benchmark_configuration_is_one_chips_share_at_published_widths():
    from benchmark import counts_laguna
    from benchmark.drivers import train_laguna

    published, config = published_config()
    cfg = train_laguna.model_config(config, {"seq_len": 8192})
    whole = transformer_config_from_hf(types.SimpleNamespace(**published))
    for width in ("hidden_dim", "head_dim", "mlp_dim", "moe_intermediate_size", "shared_expert_intermediate_size", "num_experts",
                  "num_experts_per_tok", "sliding_window", "norm_eps", "routed_scaling_factor", "scoring_func", "gating"):
        assert getattr(cfg, width) == getattr(whole, width), width
    assert cfg.layer_types == whole.layer_types[:5] and cfg.num_dense_layers == 1 and cfg.experts_held == (0, 8)
    assert cfg.kv_heads * 8 == whole.kv_heads and cfg.vocab_size * 8 == whole.vocab_size and cfg.attn_impl == "flash"
    for i in range(5):  # the KV head's whole query group, the kind's own table and window
        assert cfg.attention_layer(i).num_heads * 8 == whole.attention_layer(i).num_heads
        assert cfg.attention_layer(i)[2:] == whole.attention_layer(i)[2:]
    shapes = jax.eval_shape(DecoderLM(cfg).init, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    n = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(shapes))
    assert n == counts_laguna.param_count(dict(ref.spec(config))) and 567e6 < n < 569e6
    assert set(config["reduced"]) == {k for k, v in config["published"].items() if config[k] != v}


@pytest.mark.parametrize("change, match", [
    (dict(mlp_layer_types=["dense", "sparse", "dense", "sparse", "sparse"]), "leading"),
    (dict(attention_bias=True), "attention_bias"),
    (dict(moe_router_logit_softcapping=30.0), "softcapping"),
    (dict(gating_types=["per_head"] * 4 + ["per_channel"]), "per head"),
    (dict(num_attention_heads_per_layer=[8, 12, 12, 12]), "num_heads_per_layer"),
    (dict(sliding_window=None), "sliding_window"),
    (dict(rope_parameters={**ROPE, "full_attention": {**ROPE["full_attention"], "rope_type": "longrope"}}), "rope_scaling"),
], ids=["dense-in-the-middle", "bias", "softcap", "gate-kind", "heads-per-layer", "no-window", "rope-kind"])
def test_what_the_model_cannot_honour_is_refused(change, match):
    with pytest.raises(ValueError, match=match):
        program_config({**tiny_config(), **change})


# ------------------------------------------------------------ serving, phases


def test_the_serving_engine_refuses_a_sliding_attention_layer_by_name():
    from dmlcloud_tpu.models.generate import generate, init_cache
    from dmlcloud_tpu.serve import ServeEngine

    config, flat, tokens = seeded()
    cfg = program_config(config)
    model = DecoderLM(cfg)
    with pytest.raises(NotImplementedError, match="'sliding_attention'.*ROADMAP M2"):
        ServeEngine(model, ref.tree(flat), num_blocks=4, block_size=4, max_slots=2)
    with pytest.raises(NotImplementedError, match="'sliding_attention'"):
        init_cache(cfg, 1, 16)
    with pytest.raises(NotImplementedError, match="'sliding_attention'"):
        generate(model, {"params": ref.tree(flat)}, jnp.asarray(tokens[:, :8]), max_new_tokens=2)
    with pytest.raises(NotImplementedError, match="'sliding_attention'"):
        model.apply({"params": ref.tree(flat)}, tokens, segment_ids=jnp.ones_like(tokens))


def test_the_phases_of_the_new_mechanisms_have_names_of_their_own():
    from dmlcloud_tpu.utils.profiling import PHASES, phase_map, phase_of

    assert {"attn_gate", "moe_shared", "attn_window_kernel"} <= set(PHASES)
    assert phase_of("jit(train_step)/jvp(DecoderLM)/layer_1/attn/attn_gate/g_proj/dot_general") == ("attn_gate", "fwd")
    assert phase_of("jit(train_step)/transpose(jvp(DecoderLM))/layer_1/moe/moe_shared/shared_expert/up_proj/dot_general") == ("moe_shared", "bwd")
    assert phase_of("jit(train_step)/jvp(DecoderLM)/layer_1/attn/attn_window_kernel/flash_fwd")[0] == "attn_window_kernel"
    assert phase_of("jit(train_step)/jvp(DecoderLM)/layer_0/attn/attn_kernel/flash_fwd")[0] == "attn_kernel"
    # and the compiled step carries them: the window layers' attention apart from the full layers'
    config, flat, tokens = seeded(*CASES["share"])
    tokens = np.concatenate([tokens, tokens], axis=1)
    model = DecoderLM(program_config(config, attn_impl="flash", max_seq_len=64))
    compiled = jax.jit(jax.grad(lambda p: lm_loss(model.apply({"params": p}, tokens), tokens))).lower(ref.tree(flat)).compile()
    found = {phase for phase, _ in phase_map(compiled).values()}
    assert {"attn_gate", "moe_shared", "attn_window_kernel", "attn_kernel", "moe_route", "moe_experts"} <= found
