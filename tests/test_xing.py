"""Xing4.0-29B-A4B on the training path, at a tiny preset that keeps the
published pattern (latent attention on every layer, four hyper-connected
streams, one leading dense layer, sigmoid top-4 experts with a shared expert,
one next-token-prediction module): latent attention against its decompressed
form, the flash kernels at a value width of their own, Sinkhorn-Knopp, the
program against ``benchmark/reference_xing.py`` on seeded weights (loss and
every gradient leaf), the shares of a sublayer against the uncut sublayer, the
published config's keys, what still refuses the new mechanisms, and that the
configurations without them compile as they did."""

import dataclasses
import json
import os
import re
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference_xing as ref, weights
from dmlcloud_tpu.models.hf import transformer_config_from_hf
from dmlcloud_tpu.models.transformer import (
    DecoderLM, LatentAttention, MLP, TransformerConfig, llama_partition_rules, mhc_counters, mtp_lm_loss, sinkhorn_knopp,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HEADS, EXPERTS, DENSE, SHARED, D, T, V = 8, 16, 96, 32, 32, 32, 64  # published heads, routed experts, widths


def tiny_config(heads=(0, HEADS), experts=(0, EXPERTS), dense=DENSE, shared=SHARED, **changed):
    """A configuration file's dict, as ``benchmark/configs/xing4.0-29b-a4b.json`` is laid out."""
    return {**dict(
        model_type="xing4_0", attention_bias=False, ep_size=1, first_k_dense_replace=1, hidden_act="silu", hidden_size=D,
        intermediate_size=DENSE, kv_lora_rank=16, max_position_embeddings=T, moe_intermediate_size=16, moe_layer_freq=1, n_group=1,
        n_routed_experts=experts[1] - experts[0], n_shared_experts=2, norm_topk_prob=True, num_attention_heads=heads[1] - heads[0],
        num_experts_per_tok=4, num_hidden_layers=2, num_key_value_heads=heads[1] - heads[0], num_nextn_predict_layers=1, hc_mult=4,
        hc_sinkhorn_iters=20, hc_eps=1e-6, mhc_h_res_clamp_min=-30, mhc_h_res_clamp_max=30, q_lora_rank=24, qk_nope_head_dim=8,
        qk_rope_head_dim=8, rms_norm_eps=1e-6, rope_theta=10000,
        rope_scaling=dict(beta_fast=32, beta_slow=1, factor=64, mscale=1, mscale_all_dim=1, original_max_position_embeddings=8,
                          type="yarn"),
        routed_scaling_factor=2, scoring_func="sigmoid", tie_word_embeddings=False, topk_group=1, topk_method="noaux_tc",
        v_head_dim=8, vocab_size=V,
        published=dict(num_attention_heads=HEADS, n_routed_experts=EXPERTS),
        train=dict(heads_held=list(heads), experts_held=list(experts), dense_width_held=dense, shared_width_held=shared,
                   attn_impl="dot", remat=False),
    ), **changed}


def program_config(config, **overrides):
    from benchmark.drivers.train_xing import model_config

    cfg = model_config(config, {"seq_len": T})
    return dataclasses.replace(cfg, **{"dtype": jnp.float32, "attn_impl": "dot", "remat": False, **overrides})


def seeded(heads=(0, HEADS), experts=(0, EXPERTS), dense=DENSE, shared=SHARED, seed=7):
    config = tiny_config(heads, experts, dense, shared)
    flat = ref.make_weights(dict(ref.spec(config)), seed)
    tokens = np.random.default_rng(seed).integers(0, V, (2, T), dtype=np.int32)
    return config, flat, tokens


def program_params(flat):
    tree = ref.tree(flat)
    buffers = {name: {"moe": {"expert_bias": jnp.zeros((EXPERTS,))}} for name in tree if "moe" in tree[name]}
    if "moe" in tree["mtp"]["block"]:
        buffers["mtp"] = {"block": {"moe": {"expert_bias": jnp.zeros((EXPERTS,))}}}
    return tree, buffers


CASES = {"whole": ((0, HEADS), (0, EXPERTS), DENSE, SHARED), "share": ((2, 4), (4, 8), DENSE // 4, SHARED // 4)}


# ------------------------------------------------------------ the operators


@pytest.mark.parametrize("impl", ["dot", "flash"])
def test_latent_attention_is_its_decompressed_form(impl):
    """The module at the heads held, with the published scale and YaRN over the rope dimensions, against the
    reference's q = [q_nope | rot(q_rope)], k = [k_nope | rot(k_r)] of one shared k_r."""
    config, flat, _ = seeded(*CASES["share"])
    s = dict(ref.spec(config))
    cfg = program_config(config, attn_impl=impl)
    assert cfg.attention_multiplier == pytest.approx(16**-0.5 * (0.1 * np.log(64) + 1) ** 2)
    w = ref.layer_of(flat, 0)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, T, D), jnp.float32)
    from dmlcloud_tpu.models.transformer import rope_frequencies

    cos, sin = rope_frequencies(cfg.rope_dim, T, cfg.rope_theta, cfg.rope_scaling)
    params = ref.tree({k[len("attn/"):]: v for k, v in w.items() if k.startswith("attn/")})
    got = LatentAttention(cfg, cfg.attention_layer(0)).apply({"params": params}, x, cos, sin)
    with jax.default_matmul_precision("highest"):
        want = ref.latent_attention(x, w, s, "reference")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("kv_heads", [4, 2], ids=["mha", "gqa"])
def test_the_flash_kernels_take_a_value_width_of_their_own(kv_heads):
    """The Pallas kernels (interpreted) at q and k 48 wide, v 16: values and all three gradients against the dot path."""
    from dmlcloud_tpu.ops.flash_attention import _reference_attention, flash_attention

    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(keys[0], (1, 128, 4, 48))
    k = jax.random.normal(keys[1], (1, 128, kv_heads, 48))
    v = jax.random.normal(keys[2], (1, 128, kv_heads, 16))
    g = jax.random.normal(keys[3], (1, 128, 4, 16))
    kernel = lambda q, k, v: jnp.sum(flash_attention(q, k, v, sm_scale=0.3, impl="pallas", interpret=True, block_q=64, block_k=64) * g)
    plain = lambda q, k, v: jnp.sum(_reference_attention(q, k, v, True, 0.3) * g)
    out = flash_attention(q, k, v, sm_scale=0.3, impl="pallas", interpret=True, block_q=64, block_k=64)
    assert out.shape == (1, 128, 4, 16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(_reference_attention(q, k, v, True, 0.3)), atol=2e-5)
    for got, want in zip(jax.jit(jax.grad(kernel, (0, 1, 2)))(q, k, v), jax.grad(plain, (0, 1, 2))(q, k, v)):
        assert got.shape == want.shape
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)


def test_sinkhorn_makes_rows_and_columns_sum_to_one():
    m = jnp.exp(jax.random.normal(jax.random.PRNGKey(0), (64, 4, 4)))
    done = sinkhorn_knopp(m, 20, 1e-6)
    np.testing.assert_allclose(np.asarray(done.sum(-2)), 1.0, atol=1e-5)  # the last division is the columns'
    np.testing.assert_allclose(np.asarray(done.sum(-1)), 1.0, atol=1e-3)
    once = sinkhorn_knopp(m, 1, 1e-6)
    assert float(jnp.abs(once.sum(-1) - 1).max()) > 0.05  # one round leaves the rows off
    assert float(jnp.abs(done - 0.25).max()) > 0.05  # and twenty are no uniform matrix


def test_the_counter_is_the_largest_row_gap_of_any_residual_map():
    config, flat, tokens = seeded()
    tree, buffers = program_params(flat)
    _, stats = jax.jit(lambda v: DecoderLM(program_config(config)).apply(v, tokens, mutable=["mhc_stats"]))(
        {"params": tree, "buffers": buffers})
    per_map = jax.tree_util.tree_leaves(stats["mhc_stats"])
    assert len(per_map) == 2 * 3  # two sublayers a block, two blocks and the MTP module's
    assert float(mhc_counters(stats)["mhc/row_sum_gap"]) == max(float(v) for v in per_map) < 1e-3
    assert mhc_counters({}) == {}


def test_the_mtp_loss_reads_the_token_after_next():
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    logits, mtp_logits = jax.random.normal(keys[0], (2, 8, 16)), jax.random.normal(keys[1], (2, 8, 16))
    tokens = jax.random.randint(keys[2], (2, 8), 0, 16)
    total, mtp = mtp_lm_loss(logits, mtp_logits, tokens, 0.3)
    ce = lambda lg, tg: jnp.mean(jax.nn.logsumexp(lg, -1) - jnp.take_along_axis(lg, tg[..., None], -1)[..., 0])
    assert float(mtp) == pytest.approx(float(ce(mtp_logits[:, :-2], tokens[:, 2:])), rel=1e-6)
    assert float(total) == pytest.approx(float(ce(logits[:, :-1], tokens[:, 1:]) + 0.3 * mtp), rel=1e-6)


# ------------------------------------------------------------ the program against the reference


@pytest.fixture(scope="module")
def both_sides():
    """Loss, MTP loss and gradients of program and reference, once for every case that reads them."""
    out = {}
    for case, held in CASES.items():
        config, flat, tokens = seeded(*held)
        model = DecoderLM(program_config(config, remat=case == "share"))
        tree, buffers = program_params(flat)

        def loss(p):
            (logits, mtp_logits), _ = model.apply({"params": p, "buffers": buffers}, tokens, mutable=["moe_stats", "mhc_stats"])
            return mtp_lm_loss(logits, mtp_logits, tokens, ref.MTP_WEIGHT)

        (l, mtp), g = jax.jit(jax.value_and_grad(loss, has_aux=True))(tree)
        with jax.default_matmul_precision("highest"):
            (want_l, want_mtp), want_g = jax.jit(jax.value_and_grad(
                lambda p: ref.loss(p, tokens, dict(ref.spec(config)), "reference"), has_aux=True))(flat)
        got = {weights.path_name(p): x for p, x in jax.tree_util.tree_flatten_with_path(g)[0]}
        out[case] = (float(l), float(want_l), float(mtp), float(want_mtp), got, want_g)
    return out


@pytest.mark.parametrize("case", list(CASES))
def test_loss_and_mtp_loss_match_the_reference(both_sides, case):
    got, want, mtp, want_mtp, _, _ = both_sides[case]
    assert mtp == pytest.approx(want_mtp, rel=1e-5)
    assert got == pytest.approx(want, rel=1e-5)


@pytest.mark.parametrize("case", list(CASES))
@pytest.mark.parametrize("kind", ["attn/q_a_proj", "attn/q_b_proj", "attn/kv_a_proj_with_mqa", "attn/kv_b_proj", "attn/o_proj",
                                  "_layernorm", "_hc/phi", "_hc/bias", "_hc/alpha", "mlp/", "moe/moe/", "moe/router",
                                  "shared_expert", "mtp/eh_proj", "mtp/block/", "_norm/scale", "embed", "lm_head"])
def test_gradients_match_the_reference(both_sides, case, kind):
    *_, got, want = both_sides[case]
    names = [n for n in want if kind in n]
    assert names and set(got) == set(want)
    for n in names:
        scale = float(jnp.abs(want[n]).max())
        np.testing.assert_allclose(np.asarray(got[n]), np.asarray(want[n]), atol=3e-4 * scale + 1e-9, err_msg=n)


# ------------------------------------------------------------ the shares and the uncut layer


@pytest.mark.parametrize("sublayer", ["attention", "dense_mlp", "experts"])
def test_the_eight_shares_of_a_sublayer_add_up_to_the_uncut_sublayer(sublayer):
    """Eight holders of a layer, each with its heads, its slice of the dense MLP's and the shared expert's columns
    or its experts: their partial results summed are the uncut reference's sublayer (what every holder computes
    alike, the router's choice, counted once), and no one share alone is."""
    chips, layer = 8, 0 if sublayer == "dense_mlp" else 1
    whole = tiny_config()
    s = dict(ref.spec(whole))
    w = ref.layer_of(ref.make_weights(s, 11), layer)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, T, D), jnp.float32)
    with jax.default_matmul_precision("highest"):
        if sublayer == "attention":
            want = ref.latent_attention(x, w, s, "reference")
        elif sublayer == "dense_mlp":
            want = ref.swiglu(x, w["mlp/gate_proj/kernel"], w["mlp/up_proj/kernel"], w["mlp/down_proj/kernel"], "reference")
        else:
            want = ref.expert_layer(x, w, s, "reference")
    parts = []
    for c in range(chips):
        config = tiny_config((c * HEADS // chips, (c + 1) * HEADS // chips), (c * EXPERTS // chips, (c + 1) * EXPERTS // chips),
                             DENSE // chips, SHARED // chips)
        cfg = program_config(config)
        cut = lambda a, axis, n: jnp.split(a, chips, axis=axis)[c] if n else a
        if sublayer == "attention":
            from dmlcloud_tpu.models.transformer import rope_frequencies

            params = {"q_a_proj": {"kernel": w["attn/q_a_proj/kernel"]}, "q_a_layernorm": {"scale": w["attn/q_a_layernorm/scale"]},
                      "q_b_proj": {"kernel": cut(w["attn/q_b_proj/kernel"], 1, True)},
                      "kv_a_proj_with_mqa": {"kernel": w["attn/kv_a_proj_with_mqa/kernel"]},
                      "kv_a_layernorm": {"scale": w["attn/kv_a_layernorm/scale"]},
                      "kv_b_proj": {"kernel": cut(w["attn/kv_b_proj/kernel"], 1, True)},
                      "o_proj": {"kernel": cut(w["attn/o_proj/kernel"], 0, True)}}
            cos, sin = rope_frequencies(cfg.rope_dim, T, cfg.rope_theta, cfg.rope_scaling)
            parts.append(LatentAttention(cfg, cfg.attention_layer(layer)).apply({"params": params}, x, cos, sin))
        elif sublayer == "dense_mlp":
            params = {n: {"kernel": cut(w[f"mlp/{n}/kernel"], 0 if n == "down_proj" else 1, True)}
                      for n in ("gate_proj", "up_proj", "down_proj")}
            parts.append(MLP(cfg).apply({"params": params}, x))
        else:
            from dmlcloud_tpu.models.moe import MoEConfig, MoEMLP

            moe = MoEMLP(MoEConfig(num_experts=EXPERTS, top_k=4, hidden_dim=D, mlp_dim=16, use_expert_bias=True,
                                   routed_scaling_factor=2.0, shared_expert_intermediate_size=SHARED // chips,
                                   experts_held=cfg.experts_held, dtype=jnp.float32))
            params = {"router": {"kernel": w["moe/router/kernel"]},
                      **{f"moe/{n}": cut(w[f"moe/moe/{n}"], 0, True) for n in ("gate_proj", "up_proj", "down_proj")},
                      "shared_expert": {n: {"kernel": cut(w[f"moe/shared_expert/{n}/kernel"], 0 if n == "down_proj" else 1, True)}
                                        for n in ("gate_proj", "up_proj", "down_proj")}}
            parts.append(moe.apply({"params": params, "buffers": {"expert_bias": jnp.zeros((EXPERTS,))}}, x))
    np.testing.assert_allclose(np.asarray(sum(parts)), np.asarray(want), atol=2e-5 * float(jnp.abs(want).max()))
    assert float(jnp.abs(parts[-1] - want).max()) > 1e-3


# ------------------------------------------------------------ the published keys


def published_config():
    with open(os.path.join(ROOT, "benchmark", "configs", "xing4.0-29b-a4b.json")) as f:
        return json.load(f)


def test_the_published_keys_become_the_programs_configuration():
    from benchmark.drivers.train_xing import model_config

    config = published_config()
    cfg = model_config(config, {"seq_len": 8192})
    assert cfg.layer_types == ("mla",) * 5 and cfg.num_dense_layers == 1 and cfg.num_nextn_predict_layers == 1
    assert (cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim, cfg.rope_dim) == (768, 512, 128, 64, 128, 64)
    assert cfg.attention_multiplier == pytest.approx(0.1446796, rel=1e-6)
    assert cfg.rope_scaling == ("yarn", 64.0, 32.0, 1.0, 4096, 1.0)
    assert (cfg.hc_mult, cfg.hc_sinkhorn_iters, cfg.hc_eps, cfg.mhc_h_res_clamp_min, cfg.mhc_h_res_clamp_max) == (4, 20, 1e-6, -30, 30)
    assert (cfg.num_experts, cfg.experts_held, cfg.num_experts_per_tok, cfg.moe_intermediate_size, cfg.routed_scaling_factor) == (64, (0, 8), 4, 1024, 2.0)
    assert (cfg.scoring_func, cfg.use_expert_bias, cfg.norm_topk_prob, cfg.shared_expert_intermediate_size) == ("sigmoid", True, True, 128)
    assert (cfg.num_heads, cfg.hidden_dim, cfg.mlp_dim, cfg.vocab_size, cfg.tie_embeddings, cfg.remat) == (4, 3584, 1152, 16384, False, True)
    whole = {k: v for k, v in config.items() if k not in ("published", "reduced", "reduced_why", "deployment", "assumed", "train",
                                                            "limits", "limits_why", "source")}
    whole.update(config["published"])
    cfg = transformer_config_from_hf(types.SimpleNamespace(**whole))
    assert (cfg.num_layers, cfg.num_dense_layers, cfg.num_heads, cfg.mlp_dim, cfg.shared_expert_intermediate_size) == (40, 2, 32, 9216, 1024)


@pytest.mark.parametrize("changed, match", [({"n_group": 8, "topk_group": 4}, "group-limited"), ({"attention_bias": True}, "bias"),
                                            ({"hidden_act": "gelu"}, "silu"), ({"moe_layer_freq": 2}, "moe_layer_freq"),
                                            ({"topk_method": "group_limited_greedy"}, "topk_method"),
                                            ({"use_sliding_window": True}, "does not map"), ({"q_lora_rank_extra": 1}, "does not map")])
def test_what_the_model_cannot_honour_is_refused(changed, match):
    config = {k: v for k, v in tiny_config().items() if k not in ("published", "train")}
    with pytest.raises(ValueError, match=match):
        transformer_config_from_hf(types.SimpleNamespace(**{**config, **changed}))


def test_a_model_without_the_keys_has_none_of_the_mechanisms():
    cfg = TransformerConfig(num_layers=1, hidden_dim=16, num_heads=2, head_dim=8, mlp_dim=32, vocab_size=32, dtype=jnp.float32)
    assert (cfg.hc_mult, cfg.num_nextn_predict_layers, cfg.rope_dim) == (1, 0, 8)
    names = [weights.path_name(p) for p, _ in jax.tree_util.tree_flatten_with_path(
        jax.eval_shape(DecoderLM(cfg).init, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])[0]]
    assert not [n for n in names if re.search(r"_hc/|mtp|q_a_proj|kv_a_proj", n)]
    with pytest.raises(ValueError, match="all latent"):
        TransformerConfig(num_layers=2, layer_types=("mla", "full_attention"))
    with pytest.raises(ValueError, match="hc_mult"):
        TransformerConfig(hc_mult=0)


# ------------------------------------------------------------ what still refuses the new mechanisms


@pytest.mark.parametrize("mechanism, match", [
    (dict(), "'mla'.*latent of kv_lora_rank"),
    (dict(layer_types=None, hc_mult=4, num_nextn_predict_layers=0), "hyper-connections"),
    (dict(layer_types=None, hc_mult=1, num_nextn_predict_layers=1), "next-token-prediction"),
], ids=["mla", "streams", "mtp"])
def test_serving_generate_and_a_packed_row_refuse_each_mechanism_by_name(mechanism, match):
    from dmlcloud_tpu.models.generate import generate, init_cache
    from dmlcloud_tpu.serve import ServeEngine

    config, flat, tokens = seeded()
    cfg = dataclasses.replace(program_config(config), **mechanism)
    if cfg.layer_types is None:  # plain attention layers of the same width, whose parameters init gives
        cfg = dataclasses.replace(cfg, num_experts=0, head_dim=8)
    model = DecoderLM(cfg)
    params = model.init(jax.random.PRNGKey(0), jnp.asarray(tokens[:, :8]))["params"] if cfg.layer_types is None else ref.tree(flat)
    with pytest.raises(NotImplementedError, match=match):
        ServeEngine(model, params, num_blocks=4, block_size=4, max_slots=2)
    with pytest.raises(NotImplementedError, match=match):
        init_cache(cfg, 1, 16)
    with pytest.raises(NotImplementedError, match=match):
        generate(model, {"params": params}, jnp.asarray(tokens[:, :8]), max_new_tokens=2)
    with pytest.raises(NotImplementedError, match=f"a packed row cannot run .*{match}"):
        model.apply({"params": params}, jnp.asarray(tokens), segment_ids=jnp.ones_like(tokens))


def test_the_partition_rules_shard_the_latent_projections_by_head():
    first = lambda path: next(spec for pattern, spec in llama_partition_rules() if re.search(pattern, path))
    assert tuple(first("layer_0/attn/q_b_proj/kernel")) == ("fsdp", "model")
    assert tuple(first("layer_0/attn/kv_b_proj/kernel")) == ("fsdp", "model")
    assert tuple(first("layer_0/attn/o_proj/kernel")) == ("model", "fsdp")
    assert tuple(first("layer_0/attn/q_a_proj/kernel")) == ("fsdp", None)
    assert tuple(first("layer_0/attn/kv_a_proj_with_mqa/kernel")) == ("fsdp", None)
    assert tuple(first("mtp/eh_proj/kernel")) == ("fsdp", None)
    assert tuple(first("layer_0/attn_hc/phi")) == () and tuple(first("layer_0/attn/kv_a_layernorm/scale")) == ()


# ------------------------------------------------------------ phases


def test_the_new_phases_forward_backward_and_recomputed(monkeypatch):
    """The new mechanisms' phases in a ``remat`` step's forward, recomputed forward and backward; with the
    hyper-connections on ``ops/hyper_connection.py``'s kernels (interpreted here), each of the four kernels by its
    name in ``mhc``: ``mhc_pre`` and ``mhc_merge`` forward and recomputed, their ``_bwd`` backward."""
    from dmlcloud_tpu.ops import hyper_connection
    from dmlcloud_tpu.utils.profiling import PHASES, phase_map, phase_of

    assert {"mla_latent", "mhc", "mtp"} <= set(PHASES)
    assert phase_of("jit(step)/jvp(DecoderLM)/layer_1/attn/mla_latent/q_a_layernorm/mul") == ("mla_latent", "fwd")
    assert phase_of("jit(step)/transpose(jvp(DecoderLM))/mtp/enorm/mul") == ("mtp", "bwd")
    config, flat, tokens = seeded()
    model = DecoderLM(program_config(config, remat=True))
    tree, buffers = program_params(flat)
    step = jax.jit(jax.grad(lambda p: mtp_lm_loss(*model.apply({"params": p, "buffers": buffers}, tokens), tokens, 0.3)[0]))
    found = set(phase_map(step.lower(tree).compile()).values())
    for phase in ("mla_latent", "mhc", "mtp"):
        assert {(phase, "fwd"), (phase, "bwd")} <= found or {(phase, "recompute"), (phase, "bwd")} <= found, (phase, found)
    assert {("mhc", "recompute"), ("mla_latent", "recompute")} <= found  # a block's recomputed forward keeps its phases
    monkeypatch.setattr(hyper_connection, "_plan", lambda shape, dtype, n, mesh: (hyper_connection._Tiles(32, 32, True), None))
    step = jax.jit(jax.grad(lambda p: mtp_lm_loss(*model.apply({"params": p, "buffers": buffers}, tokens), tokens, 0.3)[0]))
    names = re.findall(r'op_name="([^"]*)"', step.lower(tree).compile().as_text())
    for kernel, directions in (("mhc_pre", {"fwd", "recompute"}), ("mhc_merge", {"fwd", "recompute"}),
                               ("mhc_pre_bwd", {"bwd"}), ("mhc_merge_bwd", {"bwd"})):
        assert {phase_of(name) for name in names if f"/{kernel}/" in name} == {("mhc", d) for d in directions}, kernel


@pytest.mark.parametrize("which", ["lfm2", "laguna", "granite"])
def test_the_configurations_without_them_see_no_new_phase_and_keep_their_trees(which):
    """The published configurations of the other cells build the trees their references name, leaf for leaf and
    shape for shape, with nothing of the new mechanisms; at a tiny size of their pattern the compiled step has no
    operation in the phases ``mhc``, ``mla_latent`` or ``mtp``."""
    import importlib

    from dmlcloud_tpu.utils.profiling import phase_map

    driver = importlib.import_module(f"benchmark.drivers.train_{which}")
    reference = importlib.import_module(f"benchmark.reference_{which}")
    name = {"lfm2": "lfm2-24b-a2b", "laguna": "laguna-s-2.1", "granite": "granite-4.0-h-micro"}[which]
    with open(os.path.join(ROOT, "benchmark", "configs", f"{name}.json")) as f:
        config = json.load(f)
    cfg = driver.model_config(config, {"seq_len": 8192})
    assert (cfg.hc_mult, cfg.num_nextn_predict_layers) == (1, 0) and "mla" not in (cfg.layer_types or ())
    shapes = jax.eval_shape(DecoderLM(cfg).init, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    held = {weights.path_name(p): tuple(x.shape) for p, x in jax.tree_util.tree_flatten_with_path(shapes)[0]}
    assert held == {n: tuple(s) for n, s in reference.all_shapes(dict(reference.spec(config))).items()}
    small = dataclasses.replace(cfg, hidden_dim=32, head_dim=8, mlp_dim=32, moe_intermediate_size=16, vocab_size=64, max_seq_len=32,
                                mamba_n_heads=min(cfg.mamba_n_heads, 4), mamba_d_head=8, mamba_d_state=8, mamba_chunk_size=16,
                                shared_expert_intermediate_size=min(cfg.shared_expert_intermediate_size, 16), attn_impl="dot",
                                sliding_window=cfg.sliding_window and 8, dtype=jnp.float32)
    model = DecoderLM(small)
    tokens = jnp.zeros((1, 32), jnp.int32)
    variables = model.init(jax.random.PRNGKey(0), tokens)
    rest = {k: v for k, v in variables.items() if k == "buffers"}
    step = jax.jit(jax.grad(lambda p: jnp.sum(model.apply({"params": p, **rest}, tokens, mutable=["moe_stats", "ssm_stats"])[0])))
    found = {phase for phase, _ in phase_map(step.lower(variables["params"]).compile()).values()}
    assert not found & {"mhc", "mla_latent", "mtp"}, found
