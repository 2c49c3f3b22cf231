"""LFM2-24B-A2B on the training path, at a tiny preset that keeps the published
pattern (``[conv, full_attention, conv, conv, conv]``, one leading dense layer,
16 experts top-4, sigmoid scores and a selection bias): the program against
``benchmark/reference_lfm2.py`` on seeded weights, the shares of one expert
layer against the uncut layer, dropless routing, the bias as a buffer, the conv
operator's causality, the published config's shapes, and the serving engine's
refusal."""

import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import dmlcloud_tpu as dml
from benchmark import reference_lfm2 as ref, weights
from dmlcloud_tpu.models.hf import transformer_config_from_hf
from dmlcloud_tpu.models.moe import MoEConfig, MoEMLP, moe_counters
from dmlcloud_tpu.models.transformer import DecoderLM, ShortConv, TransformerConfig, lm_loss

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LAYERS = ["conv", "full_attention", "conv", "conv", "conv"]
E, K = 16, 4


def tiny_config(held=(0, E)):
    """A configuration file's dict, as ``benchmark/configs/lfm2-24b-a2b.json`` is laid out."""
    return dict(
        model_type="lfm2_moe", hidden_size=32, num_attention_heads=4, num_key_value_heads=2, intermediate_size=64,
        moe_intermediate_size=24, vocab_size=64, norm_eps=1e-5, rope_parameters={"rope_theta": 1e6, "rope_type": "default"},
        conv_L_cache=3, conv_bias=False, layer_types=LAYERS, num_hidden_layers=5, num_dense_layers=1,
        num_experts=held[1] - held[0], num_experts_per_tok=K, norm_topk_prob=True, use_expert_bias=True,
        routed_scaling_factor=1.0, max_position_embeddings=64, published={"num_experts": E},
        train={"experts_held": list(held)},
    )


def program_config(config, **overrides):
    return transformer_config_from_hf(
        types.SimpleNamespace(**{**config, "num_experts": config["published"]["num_experts"]}),
        experts_held=tuple(config["train"]["experts_held"]), dtype=jnp.float32, **overrides)


def seeded(held, seed=7):
    """(config, spec, the reference's flat weights, biases by layer, tokens)."""
    config = tiny_config(held)
    flat = ref.make_weights(dict(ref.spec(config)), seed)
    biases = {i: jnp.asarray(np.random.default_rng(i).normal(0, 0.05, E), jnp.float32) for i in range(1, 5)}
    tokens = np.random.default_rng(seed).integers(0, 64, (2, 16), dtype=np.int32)
    return config, flat, biases, tokens


@pytest.fixture(scope="module")
def both_sides():
    """Loss and gradients of program and reference, once for every case that reads them."""
    out = {}
    for held in [(0, E), (4, 8)]:
        config, flat, biases, tokens = seeded(held)
        model = DecoderLM(program_config(config))
        buffers = ref.bias_tree(biases)

        def loss(p):
            logits, stats = model.apply({"params": p, "buffers": buffers}, tokens, mutable=["moe_stats"])
            return lm_loss(logits, tokens), moe_counters(stats)

        (l, counters), g = jax.jit(jax.value_and_grad(loss, has_aux=True))(ref.tree(flat))
        want_l, want_g = jax.jit(jax.value_and_grad(lambda p: ref.lm_loss(p, biases, tokens, dict(ref.spec(config)), "reference")))(flat)
        got = {weights.path_name(p): x for p, x in jax.tree_util.tree_flatten_with_path(g)[0]}
        out[held] = (float(l), float(want_l), got, want_g, counters)
    return out


@pytest.mark.parametrize("held", [(0, E), (0, 4), (8, 16), (5, 6)])
def test_logits_match_the_reference(held):
    config, flat, biases, tokens = seeded(held)
    cfg = program_config(config)
    model = DecoderLM(cfg)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0), tokens)["params"]
    params = ref.tree(flat)
    assert jax.tree_util.tree_map(lambda x: x.shape, shapes) == jax.tree_util.tree_map(lambda x: x.shape, params)
    got = jax.jit(model.apply)({"params": params, "buffers": ref.bias_tree(biases)}, tokens)
    want = ref.logits(flat, biases, tokens, ref.spec(config))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4)


@pytest.mark.parametrize("held", [(0, E), (4, 8)])
def test_loss_matches_the_reference(both_sides, held):
    got, want, _, _, counters = both_sides[held]
    assert got == pytest.approx(want, rel=1e-5)
    # every pair of a held expert is counted: all N * k where all are held
    if held == (0, E):
        assert float(counters["moe/pairs_held"]) == 4 * 2 * 16 * K
    assert float(counters["moe/load_max_over_mean"]) >= 1.0


@pytest.mark.parametrize("held", [(0, E), (4, 8)])
@pytest.mark.parametrize("kind", ["conv/", "attn/", "mlp/", "moe/router", "moe/moe/", "norm", "embed"])
def test_gradients_match_the_reference(both_sides, held, kind):
    _, _, got, want, _ = both_sides[held]
    names = [n for n in want if kind in n]
    assert names and set(got) == set(want)
    for n in names:
        scale = float(jnp.abs(want[n]).max())
        np.testing.assert_allclose(np.asarray(got[n]), np.asarray(want[n]), atol=2e-4 * scale + 1e-9, err_msg=n)


def test_expert_bias_is_no_parameter_and_gets_no_gradient(both_sides):
    _, _, got, want, _ = both_sides[(0, E)]
    assert not any("expert_bias" in n for n in got) and not any("expert_bias" in n for n in want)
    config, _, _, tokens = seeded((0, E))
    variables = jax.eval_shape(DecoderLM(program_config(config)).init, jax.random.PRNGKey(0), tokens)
    assert sorted(variables["buffers"]) == ["layer_1", "layer_2", "layer_3", "layer_4"]
    assert variables["buffers"]["layer_1"]["moe"]["expert_bias"].shape == (E,)


# ------------------------------------------------------------ the expert layer


def layer_sides(seed=3, n=96, d=32, fe=24):
    """One expert layer: the reference's weights with all 16 experts, inputs, a bias."""
    config = tiny_config((0, E))
    s = dict(ref.spec(config))
    w = ref.layer_of(ref.make_weights(s, seed, [f"layer_2/{name}" for name in ref.layer_shapes(s, 2)]), 2)
    x = jax.random.normal(jax.random.PRNGKey(seed), (2, n // 2, d), jnp.float32)
    bias = jnp.asarray(np.random.default_rng(seed).normal(0, 0.05, E), jnp.float32)
    return s, w, x, bias


def share_of(w, x, bias, held):
    a, b = held
    cfg = MoEConfig(num_experts=E, top_k=K, hidden_dim=x.shape[-1], mlp_dim=24, use_expert_bias=True,
                    experts_held=held, dtype=jnp.float32)
    params = {"router": {"kernel": w["moe/router/kernel"]}, "moe/gate_proj": w["moe/moe/gate_proj"][a:b],
              "moe/up_proj": w["moe/moe/up_proj"][a:b], "moe/down_proj": w["moe/moe/down_proj"][a:b]}
    return MoEMLP(cfg).apply({"params": params, "buffers": {"expert_bias": bias}}, x, mutable=["moe_stats"])


@pytest.mark.parametrize("shares", [1, 2, 4, 8])
def test_the_shares_of_one_layer_add_up_to_the_uncut_layer(shares):
    s, w, x, bias = layer_sides()
    want = ref.expert_layer(x, w, bias, s, "reference")
    width = E // shares
    parts = [share_of(w, x, bias, (a, a + width)) for a in range(0, E, width)]
    np.testing.assert_allclose(np.asarray(sum(y for y, _ in parts)), np.asarray(want), atol=2e-5)
    # every pair lies in exactly one share
    assert sum(float(moe_counters(v)["moe/pairs_held"]) for _, v in parts) == x.shape[0] * x.shape[1] * K
    if shares > 1:  # and one share alone is the reference's share, nothing in the absent experts' place
        sliced = {**w, **{n: w[n][width : 2 * width] for n in ("moe/moe/gate_proj", "moe/moe/up_proj", "moe/moe/down_proj")}}
        alone = ref.expert_layer(x, sliced, bias, {**s, "held": (width, 2 * width)}, "reference")
        np.testing.assert_allclose(np.asarray(parts[1][0]), np.asarray(alone), atol=2e-5)


@pytest.mark.parametrize("held", [(0, 4), (12, 16)])
def test_dropless_under_the_worst_routing(held):
    """A bias that sends every token's every pick to the four held experts: all
    N * k pairs are computed, none dropped, and the output is the reference's."""
    s, w, x, _ = layer_sides()
    bias = jnp.zeros((E,)).at[held[0] : held[1]].set(10.0)
    y, stats = share_of(w, x, bias, held)
    n = x.shape[0] * x.shape[1]
    assert float(moe_counters(stats)["moe/pairs_held"]) == n * K
    assert float(moe_counters(stats)["moe/load_max_over_mean"]) == 1.0  # each of the four gets every token
    sliced = {**w, **{name: w[name][held[0] : held[1]] for name in ("moe/moe/gate_proj", "moe/moe/up_proj", "moe/moe/down_proj")}}
    want = ref.expert_layer(x, sliced, bias, {**s, "held": held}, "reference")
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=2e-5)
    assert float(jnp.abs(y).min(axis=-1).max()) > 0  # and no token's row came back empty


def test_a_held_range_outside_the_experts_is_refused():
    with pytest.raises(ValueError, match="experts_held"):
        MoEConfig(num_experts=16, experts_held=(12, 20))


# ------------------------------------------------------------ through the stage


def test_expert_bias_is_unchanged_by_three_optimizer_steps_and_the_counters_reach_the_tracker():
    config, flat, biases, _ = seeded((0, 8))
    model = DecoderLM(program_config(config))
    batches = [np.random.default_rng(k).integers(0, 64, (2, 16), dtype=np.int32) for k in range(3)]
    seen = {}

    class Stage(dml.TrainValStage):
        def pre_stage(self):
            self.pipeline.register_model("lm", model, params={"params": ref.tree(flat), "buffers": ref.bias_tree(biases)},
                                         verbose=False)
            self.pipeline.register_optimizer("adamw", optax.adamw(1e-2, weight_decay=0.1))
            self.pipeline.register_dataset("train", batches, verbose=False)

        def step(self, state, batch):
            logits, stats = state.apply_fn({"params": state.params, **state.extras}, batch, mutable=["moe_stats"])
            return lm_loss(logits, batch), moe_counters(stats)

        def run_epoch(self):
            super().run_epoch()
            seen["pairs"] = [float(v) for v in self.tracker.reducers["train/moe/pairs_held"].values]
            seen["ratio"] = [float(v) for v in self.tracker.reducers["train/moe/load_max_over_mean"].values]
            seen["state"] = jax.device_get(self.state)

    from dmlcloud_tpu.parallel import mesh as mesh_lib

    pipe = dml.TrainingPipeline(name="lfm2-bias")
    pipe.set_mesh(mesh_lib.create_mesh({"data": 1}, devices=jax.devices()[:1]))  # one chip's share, as the benchmark runs it
    pipe.append_stage(Stage(), max_epochs=1)
    pipe.run()
    state = seen["state"]
    assert int(state.step) == 3
    for i, b in biases.items():
        np.testing.assert_array_equal(state.extras["buffers"][f"layer_{i}"]["moe"]["expert_bias"], np.asarray(b))
    moved = np.abs(state.params["layer_1"]["moe"]["router"]["kernel"] - np.asarray(flat["layer_1/moe/router/kernel"])).max()
    assert moved > 0  # while the parameters did move
    assert not any("expert_bias" in jax.tree_util.keystr(p) for p, _ in jax.tree_util.tree_flatten_with_path(state.opt_state)[0])
    assert len(seen["pairs"]) == 3 and all(0 < p <= 4 * 32 * K for p in seen["pairs"]) and all(r >= 1 for r in seen["ratio"])


# ------------------------------------------------------------ the conv operator


def test_the_conv_operator_is_causal():
    cfg = TransformerConfig(hidden_dim=16, dtype=jnp.float32, conv_L_cache=3)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 12, 16))
    conv = ShortConv(cfg)
    params = conv.init(jax.random.PRNGKey(1), x)
    y = conv.apply(params, x)
    t = 7
    y2 = conv.apply(params, x.at[:, t].add(1.0))
    np.testing.assert_array_equal(np.asarray(y[:, :t]), np.asarray(y2[:, :t]))
    # position t reaches t, t+1 and t+2 (kernel 3) and nothing further
    changed = np.abs(np.asarray(y2 - y)).max(axis=(0, 2)) > 0
    assert changed[t : t + 3].all() and not changed[t + 3 :].any()


def test_the_conv_operator_is_the_references():
    config = tiny_config()
    s = dict(ref.spec(config))
    w = ref.layer_of(ref.make_weights(s, 11, [f"layer_0/{n}" for n in ref.layer_shapes(s, 0)]), 0)
    u = jax.random.normal(jax.random.PRNGKey(2), (2, 10, 32))
    params = {"in_proj": {"kernel": w["conv/in_proj/kernel"]}, "conv_weight": w["conv/conv_weight"],
              "out_proj": {"kernel": w["conv/out_proj/kernel"]}}
    got = ShortConv(program_config(config)).apply({"params": params}, u)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref.conv_op(u, w, s, "reference")), atol=1e-5)


# ------------------------------------------------------------ configuration


def published_config():
    with open(os.path.join(ROOT, "benchmark", "configs", "lfm2-24b-a2b.json")) as f:
        config = json.load(f)
    return {**{k: v for k, v in config.items() if k not in ("published", "train", "limits")}, **config["published"]}, config


def test_the_published_config_keys_give_the_published_shapes():
    published, _ = published_config()
    cfg = transformer_config_from_hf(types.SimpleNamespace(**published))
    assert (cfg.num_layers, cfg.hidden_dim, cfg.num_heads, cfg.kv_heads, cfg.head_dim) == (40, 2048, 32, 8, 64)
    assert (cfg.mlp_dim, cfg.moe_intermediate_size, cfg.num_experts, cfg.num_experts_per_tok) == (11776, 1536, 64, 4)
    assert (cfg.num_dense_layers, cfg.vocab_size, cfg.norm_eps, cfg.rope_theta, cfg.conv_L_cache) == (2, 65536, 1e-5, 1e6, 3)
    assert cfg.qk_norm and cfg.tie_embeddings and cfg.use_expert_bias and cfg.norm_topk_prob
    assert cfg.layer_types.count("conv") == 30 and cfg.layer_types.count("full_attention") == 10 and cfg.experts_held is None
    assert [cfg.layer_kind(i) for i in (0, 1, 2, 39)] == ["conv", "conv", "full_attention", "conv"]
    assert [cfg.is_expert_layer(i) for i in (0, 1, 2)] == [False, False, True]
    shapes = jax.eval_shape(DecoderLM(cfg).init, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    shape = lambda tree: jax.tree_util.tree_map(lambda x: x.shape, tree)
    p = shape(shapes["params"])
    assert p["layer_0"]["conv"] == {"in_proj": {"kernel": (2048, 6144)}, "conv_weight": (3, 2048), "out_proj": {"kernel": (2048, 2048)}}
    assert p["layer_0"]["mlp"]["gate_proj"]["kernel"] == (2048, 11776) and "moe" not in p["layer_1"]
    assert p["layer_2"]["attn"]["q_proj"]["kernel"] == (2048, 32, 64) and p["layer_2"]["attn"]["k_norm"]["scale"] == (64,)
    assert p["layer_2"]["moe"] == {"router": {"kernel": (2048, 64)}, "moe/gate_proj": (64, 2048, 1536),
                                   "moe/up_proj": (64, 2048, 1536), "moe/down_proj": (64, 1536, 2048)}
    assert "lm_head" not in p and p["embed"]["embedding"] == (65536, 2048)
    assert shape(shapes["buffers"])["layer_39"]["moe"]["expert_bias"] == (64,)


def test_the_benchmark_configuration_is_one_chips_share_at_published_widths():
    from benchmark import counts_lfm2
    from benchmark.drivers import train_lfm2

    published, config = published_config()
    cfg = train_lfm2.model_config(config, {"seq_len": 8192})
    whole = transformer_config_from_hf(types.SimpleNamespace(**published))
    for width in ("hidden_dim", "num_heads", "num_kv_heads", "head_dim", "mlp_dim", "moe_intermediate_size", "num_experts",
                  "num_experts_per_tok", "conv_L_cache", "rope_theta", "norm_eps"):
        assert getattr(cfg, width) == getattr(whole, width), width
    assert cfg.layer_types == whole.layer_types[1:6] and cfg.num_dense_layers == 1 and cfg.experts_held == (0, 8)
    assert cfg.vocab_size * 8 == whole.vocab_size and cfg.attn_impl == "flash"
    shapes = jax.eval_shape(DecoderLM(cfg).init, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    n = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(shapes))
    assert n == counts_lfm2.param_count(dict(ref.spec(config))) and 468e6 < n < 470e6
    assert set(config["reduced"]) == {k for k, v in config["published"].items() if config[k] != v}


def test_norm_eps_comes_from_the_config():
    llama = types.SimpleNamespace(vocab_size=64, num_hidden_layers=1, num_attention_heads=2, hidden_size=16, intermediate_size=32,
                                  max_position_embeddings=32, rms_norm_eps=1e-5)
    assert transformer_config_from_hf(llama).norm_eps == 1e-5
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 4, 16)) * 1e-3
    from dmlcloud_tpu.models.transformer import RMSNorm

    a = RMSNorm(eps=1e-5).apply({"params": {"scale": jnp.ones(16)}}, x)
    np.testing.assert_allclose(np.asarray(a), np.asarray(x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-5)), rtol=1e-6)


def test_unknown_layer_kinds_and_a_conv_bias_are_refused():
    with pytest.raises(ValueError, match="layer_types"):
        TransformerConfig(num_layers=2, layer_types=("conv", "mamba"))
    with pytest.raises(ValueError, match="layer_types"):
        TransformerConfig(num_layers=3, layer_types=("conv", "conv"))
    published, _ = published_config()
    with pytest.raises(ValueError, match="conv_bias"):
        transformer_config_from_hf(types.SimpleNamespace(**{**published, "conv_bias": True}))


# ------------------------------------------------------------ serving


def test_the_serving_engine_refuses_a_conv_layer_by_name():
    from dmlcloud_tpu.models.generate import generate, init_cache
    from dmlcloud_tpu.serve import ServeEngine

    config, flat, biases, tokens = seeded((0, E))
    cfg = program_config(config)
    model = DecoderLM(cfg)
    with pytest.raises(NotImplementedError, match="'conv'"):
        ServeEngine(model, ref.tree(flat), num_blocks=4, block_size=4, max_slots=2)
    with pytest.raises(NotImplementedError, match="'conv'"):
        init_cache(cfg, 1, 16)
    with pytest.raises(NotImplementedError, match="'conv'"):
        generate(model, {"params": ref.tree(flat), "buffers": ref.bias_tree(biases)}, jnp.asarray(tokens), max_new_tokens=2)


@pytest.mark.parametrize("extra", [dict(cache=(None, None)), dict(seg_info=object()), dict(paged=object()),
                                   dict(adapters=({"attn": {}}, jnp.zeros((1,), jnp.int32)))],
                         ids=["cache", "packed", "paged", "attn-adapters"])
def test_a_conv_block_built_outside_the_model_refuses_what_it_cannot_honour(extra):
    from dmlcloud_tpu.models.transformer import DecoderBlock

    block = DecoderBlock(TransformerConfig(num_layers=1, hidden_dim=16, dtype=jnp.float32), kind="conv")
    x = jnp.zeros((1, 4, 16))
    block.init(jax.random.PRNGKey(0), x, None, None)  # alone it runs
    with pytest.raises(NotImplementedError, match="'conv' layer"):
        block.init(jax.random.PRNGKey(0), x, None, None, **extra)


def test_the_phases_of_the_new_layers_have_names_of_their_own():
    from dmlcloud_tpu.utils.profiling import PHASES, phase_of

    assert {"conv_op", "moe_route", "moe_experts"} <= set(PHASES)
    assert phase_of("jit(train_step)/jvp(DecoderLM)/layer_2/conv/conv_op/in_proj/dot_general")[0] == "conv_op"
    assert phase_of("jit(train_step)/jvp(DecoderLM)/layer_2/moe/moe_experts/ragged_dot") == ("moe_experts", "fwd")
    assert phase_of("jit(train_step)/transpose(jvp(DecoderLM))/layer_2/moe/moe_route/sort") == ("moe_route", "bwd")
    assert phase_of("jit(train_step)/jvp(DecoderLM)/layer_2/moe/reshape")[0] is None  # nothing of it hides in mlp
