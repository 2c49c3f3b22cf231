"""The dropless MoE layer: routing (sigmoid scores, with and without the selection bias), every pair
computed whatever the routing, one chip's share of the experts, counters, aux
losses, expert parallelism over the mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dmlcloud_tpu.models.moe import (
    MoEConfig, MoEMLP, moe_counters, moe_partition_rules, route, sort_pairs, total_aux_loss,
)
from dmlcloud_tpu.ops.grouped_matmul import collect, grouped_matmul, spread
from dmlcloud_tpu.parallel import mesh as mesh_lib

B, T, D = 2, 16, 8


def make_layer(**overrides):
    kwargs = dict(num_experts=4, top_k=2, hidden_dim=D, mlp_dim=16, dtype=jnp.float32)
    kwargs.update(overrides)
    cfg = MoEConfig(**kwargs)
    model = MoEMLP(cfg)
    x = jax.random.normal(jax.random.PRNGKey(0), (B, T, D))
    variables = model.init(jax.random.PRNGKey(1), x)
    return model, {k: v for k, v in variables.items() if k in ("params", "buffers")}, x


def by_hand(cfg, variables, x):
    """The layer as a loop over the held experts, every expert computed for every token."""
    p = variables["params"]
    tokens = x.reshape(-1, x.shape[-1])
    logits = tokens @ p["router"]["kernel"]
    bias = variables.get("buffers", {}).get("expert_bias")
    _, chosen, gates = route(cfg, logits, bias)
    out = jnp.zeros_like(tokens)
    for n, e in enumerate(range(*cfg.held)):
        weight = jnp.sum(jnp.where(chosen == e, gates, 0.0), axis=-1)
        h = jax.nn.silu(tokens @ p["moe/gate_proj"][n]) * (tokens @ p["moe/up_proj"][n])
        out = out + weight[:, None] * (h @ p["moe/down_proj"][n])
    return out.reshape(x.shape)


class TestMoEMLP:
    @pytest.mark.slow
    def test_forward_shape_and_finite(self):
        model, params, x = make_layer()
        y = model.apply(params, x)
        assert y.shape == x.shape
        assert np.isfinite(np.asarray(y)).all()

    @pytest.mark.parametrize("overrides", [
        dict(), dict(top_k=1), dict(top_k=4), dict(use_expert_bias=True),
        dict(norm_topk_prob=False, routed_scaling_factor=2.5), dict(num_experts=8, experts_held=(2, 5)),
    ], ids=["top2", "top1", "top4-of-4", "bias", "unnormalised-scaled", "share"])
    def test_output_is_the_loop_over_experts(self, overrides):
        model, variables, x = make_layer(**overrides)
        if "buffers" in variables:
            variables["buffers"] = {"expert_bias": jnp.asarray(np.random.default_rng(0).normal(0, 0.3, model.cfg.num_experts), jnp.float32)}
        np.testing.assert_allclose(np.asarray(model.apply(variables, x)), np.asarray(by_hand(model.cfg, variables, x)), atol=1e-5)

    def test_no_token_is_dropped_when_one_expert_takes_them_all(self):
        # the old layer's capacity would have kept 8 of these 32 tokens
        model, variables, x = make_layer(use_expert_bias=True, top_k=1)
        variables["buffers"] = {"expert_bias": jnp.asarray([0.0, 9.0, 0.0, 0.0])}
        y, stats = model.apply(variables, x, mutable=["moe_stats"])
        counters = moe_counters(stats)
        assert float(counters["moe/pairs_held"]) == B * T and float(counters["moe/load_max_over_mean"]) == 4.0
        np.testing.assert_allclose(np.asarray(y), np.asarray(by_hand(model.cfg, variables, x)), atol=1e-5)
        assert (np.abs(np.asarray(y)).sum(axis=-1) > 0).all()

    def test_the_bias_moves_the_choice_and_not_the_weights(self):
        cfg = MoEConfig(num_experts=4, top_k=2, use_expert_bias=True)
        logits = jnp.asarray([[2.0, 1.0, 0.0, -1.0]])
        _, chosen, gates = route(cfg, logits, jnp.asarray([0.0, 0.0, 0.0, 5.0]))
        assert sorted(np.asarray(chosen)[0].tolist()) == [0, 3]
        s = jax.nn.sigmoid(logits)[0]
        want = {0: s[0] / (s[0] + s[3] + 1e-6), 3: s[3] / (s[0] + s[3] + 1e-6)}
        for e, g in zip(np.asarray(chosen)[0], np.asarray(gates)[0]):
            assert g == pytest.approx(float(want[int(e)]), rel=1e-6)

    def test_aux_losses_sown(self):
        model, params, x = make_layer()
        y, state = model.apply(params, x, mutable=["losses"])
        aux = total_aux_loss(state)
        assert np.isfinite(float(aux))
        assert float(aux) > 0.0

    def test_gradients_flow_to_all_param_groups(self):
        model, params, x = make_layer()

        def loss_fn(p):
            y, state = model.apply(p, x, mutable=["losses"])
            return jnp.sum(y**2) + total_aux_loss(state)

        grads = jax.grad(loss_fn)(params)
        flat = jax.tree_util.tree_leaves_with_path(grads)
        assert len(flat) == 4  # router + gate/up/down
        for path, g in flat:
            assert np.abs(np.asarray(g)).sum() > 0, f"zero grad at {path}"

    def test_gradients_are_the_loop_over_experts(self):
        model, variables, x = make_layer(num_experts=8, experts_held=(4, 8))
        got = jax.grad(lambda v: jnp.sum(model.apply(v, x) ** 2))(variables)
        want = jax.grad(lambda v: jnp.sum(by_hand(model.cfg, v, x) ** 2))(variables)
        for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got), jax.tree_util.tree_leaves(want)):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=1e-4, err_msg=str(path))

    def test_a_share_holds_its_own_experts_matrices_only(self):
        _, variables, _ = make_layer(num_experts=8, experts_held=(2, 5))
        assert variables["params"]["moe/gate_proj"].shape == (3, D, 16)
        assert variables["params"]["router"]["kernel"].shape == (D, 8)  # the router keeps its width


class TestSortedPairs:
    def test_pairs_lie_sorted_by_held_expert_with_the_rest_last(self):
        chosen = jnp.asarray([[0, 5], [5, 2], [3, 0], [2, 7]])
        gates = jnp.arange(8, dtype=jnp.float32).reshape(4, 2) + 1
        order, inverse, weight, sizes = sort_pairs(chosen, gates, (2, 6))
        experts = np.asarray(chosen).reshape(-1)[np.asarray(order)]
        assert experts[:5].tolist() == [2, 2, 3, 5, 5] and set(experts[5:].tolist()) == {0, 7}
        assert np.asarray(sizes).tolist() == [2, 1, 0, 2]
        assert (np.asarray(weight)[5:] == 0).all() and (np.asarray(weight)[:5] > 0).all()
        assert np.asarray(inverse)[np.asarray(order)].tolist() == list(range(8))

    def test_spread_and_collect_are_each_others_transposes(self):
        n, k, d = 6, 2, 4
        order = jax.random.permutation(jax.random.PRNGKey(0), n * k)
        inverse = jnp.argsort(order)
        tokens = jax.random.normal(jax.random.PRNGKey(1), (n, d))
        rows = jax.random.normal(jax.random.PRNGKey(2), (n * k, d))
        np.testing.assert_array_equal(np.asarray(spread(tokens, order, inverse, k)), np.asarray(tokens)[np.asarray(order) // k])
        lhs = jnp.vdot(spread(tokens, order, inverse, k), rows)
        np.testing.assert_allclose(float(lhs), float(jnp.vdot(tokens, collect(rows, order, inverse, k))), rtol=1e-5)
        g = jax.grad(lambda t: jnp.vdot(spread(t, order, inverse, k), rows))(tokens)
        np.testing.assert_allclose(np.asarray(g), np.asarray(collect(rows, order, inverse, k)), rtol=1e-5)
        g = jax.grad(lambda r: jnp.vdot(collect(r, order, inverse, k), tokens))(rows)
        np.testing.assert_allclose(np.asarray(g), np.asarray(spread(tokens, order, inverse, k)), rtol=1e-5)

    @pytest.mark.parametrize("sizes", [[3, 0, 5], [8, 0, 0], [1, 1, 1]], ids=["ragged", "one-group", "rows-left-over"])
    def test_grouped_matmul_multiplies_each_row_by_its_groups_matrix(self, sizes):
        lhs = jax.random.normal(jax.random.PRNGKey(0), (8, 4))
        rhs = jax.random.normal(jax.random.PRNGKey(1), (3, 4, 5))
        got = np.asarray(grouped_matmul(lhs, rhs, jnp.asarray(sizes)))
        group = np.repeat(np.arange(3), sizes)
        for i, g in enumerate(group):  # rows past the groups are unspecified, and not read
            np.testing.assert_allclose(got[i], np.asarray(lhs[i] @ rhs[g]), atol=1e-5)


class TestExpertParallel:
    def test_sharded_matches_single_device(self):
        """The same layer, experts sharded over the mesh, must be numerically
        identical to the unsharded apply."""
        model, params, x = make_layer(num_experts=8)
        y_ref = model.apply(params, x)

        mesh = mesh_lib.create_mesh({"data": 2, "expert": 4})
        rules = moe_partition_rules()
        sharded_params = mesh_lib.shard_pytree(params, mesh, rules)
        x_sharded = jax.device_put(x, mesh_lib.batch_sharding(mesh))

        y = jax.jit(model.apply)(sharded_params, x_sharded)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=1e-5)

    def test_partition_rules_shard_expert_dim(self):
        model, params, _ = make_layer(num_experts=8)
        mesh = mesh_lib.create_mesh({"expert": 8})
        shardings = mesh_lib.sharding_for(params, mesh, moe_partition_rules())
        flat = jax.tree_util.tree_leaves_with_path(shardings)
        expert_sharded = [s for path, s in flat if "proj" in jax.tree_util.keystr(path)]
        assert len(expert_sharded) == 3
        for s in expert_sharded:
            assert s.spec[0] == "expert"


class TestMoETransformer:
    @pytest.mark.slow
    def test_decoder_lm_with_moe(self):
        from dmlcloud_tpu.models.transformer import DecoderLM, TransformerConfig, lm_loss

        cfg = TransformerConfig(
            vocab_size=64,
            num_layers=2,
            num_heads=2,
            head_dim=8,
            hidden_dim=16,
            mlp_dim=32,
            max_seq_len=32,
            dtype=jnp.float32,
            num_experts=4,
            num_dense_layers=1,
        )
        model = DecoderLM(cfg)
        tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 16), 0, 64)
        params = model.init(jax.random.PRNGKey(1), tokens)
        # layer_0 is the leading dense layer, layer_1 has experts
        assert "moe" in params["params"]["layer_1"]
        assert "mlp" in params["params"]["layer_0"]

        loss = lm_loss(model.apply(params, tokens), tokens)
        assert np.isfinite(float(loss))

        grads = jax.grad(lambda p: lm_loss(model.apply(p, tokens), tokens))(params)
        gate_g = grads["params"]["layer_1"]["moe"]["moe/gate_proj"]
        assert np.abs(np.asarray(gate_g)).sum() > 0

    def test_expert_layers_follow_the_leading_dense_ones(self):
        from dmlcloud_tpu.models.transformer import DecoderLM, TransformerConfig

        cfg = TransformerConfig(vocab_size=32, num_layers=3, num_heads=2, head_dim=4, hidden_dim=8, mlp_dim=16,
                                dtype=jnp.float32, num_experts=4, num_dense_layers=2, moe_intermediate_size=12)
        shapes = jax.eval_shape(DecoderLM(cfg).init, jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))["params"]
        assert ["moe" in shapes[f"layer_{i}"] for i in range(3)] == [False, False, True]
        assert shapes["layer_2"]["moe"]["moe/gate_proj"].shape == (4, 8, 12)
