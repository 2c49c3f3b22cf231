"""The dropless MoE layer: routing (sigmoid scores, with and without the selection bias, or a softmax over
all the experts), every pair computed whatever the routing, one chip's share of the experts, the shared
expert beside them, counters, aux losses, expert parallelism over the mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dmlcloud_tpu.models import moe
from dmlcloud_tpu.models.moe import (
    MoEConfig, MoEMLP, moe_counters, moe_partition_rules, route, row_bound, sort_pairs, total_aux_loss,
)
from dmlcloud_tpu.ops.grouped_matmul import collect, collect_rows, grouped_matmul, run_layout, spread, spread_rows
from dmlcloud_tpu.parallel import mesh as mesh_lib
from dmlcloud_tpu.utils.profiling import phase_of

B, T, D = 2, 16, 8
TRUE_ROUTE = route


def make_layer(tokens=T, **overrides):
    kwargs = dict(num_experts=4, top_k=2, hidden_dim=D, mlp_dim=16, dtype=jnp.float32)
    kwargs.update(overrides)
    cfg = MoEConfig(**kwargs)
    model = MoEMLP(cfg)
    x = jax.random.normal(jax.random.PRNGKey(0), (B, tokens, D))
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
    if "shared_expert" in p:  # on every token, ungated
        kernel = lambda name: p["shared_expert"][name]["kernel"]
        out = out + (jax.nn.silu(tokens @ kernel("gate_proj")) * (tokens @ kernel("up_proj"))) @ kernel("down_proj")
    return out.reshape(x.shape)


class TestMoEMLP:
    def test_forward_shape_and_finite(self):
        model, params, x = make_layer()
        y = model.apply(params, x)
        assert y.shape == x.shape
        assert np.isfinite(np.asarray(y)).all()

    @pytest.mark.parametrize("overrides", [
        dict(), dict(top_k=1), dict(top_k=4), dict(use_expert_bias=True),
        dict(norm_topk_prob=False, routed_scaling_factor=2.5), dict(num_experts=8, experts_held=(2, 5)),
        dict(scoring_func="softmax"), dict(scoring_func="softmax", num_experts=8, top_k=3, routed_scaling_factor=2.5),
        dict(scoring_func="softmax", norm_topk_prob=False), dict(shared_expert_intermediate_size=12),
        dict(scoring_func="softmax", shared_expert_intermediate_size=12, num_experts=8, experts_held=(2, 5)),
    ], ids=["top2", "top1", "top4-of-4", "bias", "unnormalised-scaled", "share", "softmax", "softmax-top3-of-8-scaled",
            "softmax-unnormalised", "shared-expert", "softmax-shared-share"])
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

    @pytest.mark.parametrize("norm, scaling", [(True, 1.0), (True, 2.5), (False, 1.0)])
    def test_softmax_scores_are_over_all_the_experts_and_the_weights_over_the_chosen(self, norm, scaling):
        cfg = MoEConfig(num_experts=6, top_k=2, scoring_func="softmax", norm_topk_prob=norm, routed_scaling_factor=scaling)
        logits = jnp.asarray([[2.0, 1.0, 0.0, -1.0, 0.5, -3.0], [0.0, 0.0, 4.0, 0.0, 3.0, 0.0]])
        scores, chosen, gates = route(cfg, logits)
        p = np.exp(np.asarray(logits)) / np.exp(np.asarray(logits)).sum(-1, keepdims=True)
        np.testing.assert_allclose(np.asarray(scores), p, rtol=1e-6)
        assert [sorted(row) for row in np.asarray(chosen).tolist()] == [[0, 1], [2, 4]]
        for row, (e, g) in enumerate(zip(np.asarray(chosen), np.asarray(gates))):
            want = p[row, e] / (p[row, e].sum() if norm else 1.0) * scaling  # no guard in the sum: it is k / E at least
            np.testing.assert_allclose(g, want, rtol=1e-6)

    def test_an_unknown_scoring_is_refused(self):
        with pytest.raises(ValueError, match="scoring_func"):
            MoEConfig(scoring_func="tanh")

    @pytest.mark.parametrize("held", [None, (0, 2), (2, 4)], ids=["all", "first-half", "second-half"])
    def test_the_shared_expert_is_added_to_every_token_whatever_the_share(self, held):
        model, variables, x = make_layer(shared_expert_intermediate_size=12, experts_held=held)
        without = MoEMLP(MoEConfig(**{**model.cfg.__dict__, "shared_expert_intermediate_size": 0}))
        routed = {"params": {k: v for k, v in variables["params"].items() if k != "shared_expert"}}
        kernel = lambda name: variables["params"]["shared_expert"][name]["kernel"]
        assert kernel("gate_proj").shape == (D, 12) and kernel("down_proj").shape == (12, D)
        shared = (jax.nn.silu(x @ kernel("gate_proj")) * (x @ kernel("up_proj"))) @ kernel("down_proj")
        np.testing.assert_allclose(np.asarray(model.apply(variables, x)), np.asarray(without.apply(routed, x) + shared), atol=1e-5)

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


# A layer that holds 2 of 8 experts over 512 tokens with top-2: 1,024 pairs, 256 of them live with even
# loads, and a row bound of 512 = row_bound(1024, 2, 8), so the layer has two compiled paths.
BOUNDED = dict(tokens=256, num_experts=8, experts_held=(0, 2))
PAIRS, BOUND = 2 * B * 256, 512


def routed_as(chosen):
    """``route`` with the experts ``chosen [N, k]`` whatever the scores, the weights the chosen experts' scores normalised."""
    def fixed(cfg, logits, bias=None):
        scores, _, _ = TRUE_ROUTE(cfg, logits, bias)
        gates = jnp.sum(jax.nn.one_hot(chosen, cfg.num_experts) * scores[:, None, :], axis=-1)
        return scores, jnp.asarray(chosen, jnp.int32), gates / (jnp.sum(gates, -1, keepdims=True) + 1e-6)
    return fixed


def experts_with(n, k, experts, held, live):
    """``[n, k]`` experts, ``k`` different ones a token, with ``live`` of the pairs sent to experts in ``held``, spread
    over tokens drawn at random; ``"one token"``: one token alone sends the held experts all the pairs it can."""
    rng = np.random.default_rng(0)
    inside, most = np.arange(*held), min(k, held[1] - held[0])
    outside = np.setdiff1d(np.arange(experts), inside)
    if live == "one token":
        counts = np.where(np.arange(n) == n // 3, most, 0)
    else:
        counts = np.bincount(rng.permutation(np.repeat(np.arange(n), most))[:live], minlength=n)
    return np.stack([rng.permutation(np.concatenate([rng.choice(inside, c, replace=False), rng.choice(outside, k - c, replace=False)]))
                     for c in counts]).astype(np.int32)


def routed_to(live_pairs):
    """``route`` with ``live_pairs`` of the 1,024 pairs sent to the held experts 0 and 1, whatever the scores."""
    n = PAIRS // 2
    first = np.where(np.arange(n) < min(live_pairs, n), np.arange(n) % 2, 2 + np.arange(n) % 6)
    second = np.where(np.arange(n) < live_pairs - n, 1 - np.arange(n) % 2, 2 + (np.arange(n) + 1) % 6)
    return routed_as(np.stack([first, second], axis=1))


def value_and_grads(fn, variables, x):
    return jax.value_and_grad(lambda v, x: jnp.sum(fn(v, x) ** 2), argnums=(0, 1))(variables, x)


def assert_same_as_the_loop(model, variables, x, some=True):
    """Output, and the gradients of every parameter and of the input (none of them all zero, where ``some`` pairs
    are live), against the loop over experts; returns the layer's counters."""
    (y, stats) = model.apply(variables, x, mutable=["moe_stats"])
    np.testing.assert_allclose(np.asarray(y), np.asarray(by_hand(model.cfg, variables, x)), atol=1e-5)
    _, got = value_and_grads(model.apply, variables, x)
    _, want = value_and_grads(lambda v, x: by_hand(model.cfg, v, x), variables, x)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got), jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=2e-4, err_msg=str(path))
        assert not some or np.abs(np.asarray(w)).sum() > 0, path
    return {name: float(v) for name, v in moe_counters(stats).items()}


def eqns_in(jaxpr):
    """Every equation of a jaxpr, its inner jaxprs' included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for inner in jax.core.jaxprs_in_params(eqn.params):
            yield from eqns_in(inner)


def shapes_in(jaxpr):
    return {tuple(var.aval.shape) for eqn in eqns_in(jaxpr) for var in eqn.outvars}


class TestRowBound:
    """The layer that holds a share of the experts works in ``row_bound`` rows when the live rows fit them,
    and in all ``N * k`` when they do not: no pair dropped either way."""

    @pytest.mark.parametrize("pairs, held, experts, want", [
        (32768, 8, 64, 8192), (32768, 64, 64, 32768), (32768, 32, 64, 32768), (1024, 2, 8, 512), (2048, 4, 16, 1024),
        (64, 2, 8, 64), (384, 4, 16, 384), (5000, 1, 64, 512),
    ])
    def test_the_bound_is_twice_the_even_share_in_whole_tiles_and_never_over_all_pairs(self, pairs, held, experts, want):
        assert row_bound(pairs, held, experts) == want

    @pytest.mark.parametrize("overrides", [dict(), dict(scoring_func="softmax", shared_expert_intermediate_size=12)],
                             ids=["sigmoid", "softmax-shared"])
    def test_random_routing_takes_the_usual_path_and_is_the_loop_over_experts(self, overrides):
        model, variables, x = make_layer(**BOUNDED, **overrides)
        counters = assert_same_as_the_loop(model, variables, x)
        assert 0 < counters["moe/pairs_held"] <= BOUND and counters["moe/overflow_layers"] == 0

    def test_every_pair_to_a_held_expert_takes_the_full_path_and_none_is_dropped(self, monkeypatch):
        model, variables, x = make_layer(**BOUNDED)
        monkeypatch.setattr(moe, "route", routed_to(PAIRS))
        monkeypatch.setitem(globals(), "route", routed_to(PAIRS))  # by_hand routes the same way
        counters = assert_same_as_the_loop(model, variables, x)
        assert counters["moe/pairs_held"] == PAIRS and counters["moe/overflow_layers"] == 1
        assert (np.abs(np.asarray(model.apply(variables, x))).sum(axis=-1) > 0).all()

    @pytest.mark.parametrize("live_pairs, overflow", [(BOUND, 0), (BOUND + 1, 1), (BOUND - 1, 0), (0, 0)],
                             ids=["L=R", "L=R+1", "L=R-1", "none-live"])
    def test_the_edge_of_the_bound(self, monkeypatch, live_pairs, overflow):
        model, variables, x = make_layer(**BOUNDED)
        monkeypatch.setattr(moe, "route", routed_to(live_pairs))
        monkeypatch.setitem(globals(), "route", routed_to(live_pairs))
        if live_pairs == 0:  # nothing held: a zero output, zero gradients, and nothing not finite
            y, stats = model.apply(variables, x, mutable=["moe_stats"])
            grads = jax.grad(lambda v: jnp.sum(model.apply(v, x) ** 2))(variables)
            assert not np.asarray(y).any() and all(np.isfinite(np.asarray(g)).all() for g in jax.tree_util.tree_leaves(grads))
            counters = {name: float(v) for name, v in moe_counters(stats).items()}
        else:
            counters = assert_same_as_the_loop(model, variables, x)
        assert counters["moe/pairs_held"] == live_pairs and counters["moe/overflow_layers"] == overflow

    @pytest.mark.parametrize("overrides, conds", [(dict(), 0), (dict(num_experts=8, experts_held=(2, 6)), 0), (BOUNDED, 2)],
                             ids=["all-held", "half-held", "quarter-held"])
    def test_a_layer_whose_bound_is_all_pairs_has_one_path_and_no_cond(self, overrides, conds):
        model, variables, x = make_layer(**overrides)
        jaxpr = jax.make_jaxpr(jax.grad(lambda v: jnp.sum(model.apply(v, x) ** 2)))(variables)
        conditionals = [eqn for eqn in eqns_in(jaxpr.jaxpr) if eqn.primitive.name == "cond"]
        assert len(conditionals) == conds  # one forward, one backward
        # on the TPU the grouped kernel's op_name is the path of names round it, and it is given its phase by its
        # instruction's name only where that path holds none: no scope of the layer's own may lie round a cond
        for eqn in conditionals:
            assert phase_of(f"jit(step)/{eqn.source_info.name_stack}/cond/branch_1_fun/ragged-dot-none")[0] is None

    @pytest.mark.parametrize("body", ["forward", "backward"])
    def test_the_usual_path_holds_no_array_of_all_pairs_rows_with_a_feature_axis(self, body):
        n, k, f = B * 256, 2, 16
        chosen = jnp.asarray(experts_with(n, k, 8, (0, 2), 220))
        gates = jnp.ones((n, k)) / k
        order, runs, weight, group_sizes = sort_pairs(chosen, gates, (0, 2, BOUND))
        args = (jnp.ones((n, D)), weight, gates, jnp.ones((2, D, f)), jnp.ones((2, D, f)), jnp.ones((2, f, D)), order, runs, chosen, group_sizes)
        if body == "forward":
            jaxpr = jax.make_jaxpr(lambda *a: moe._usual_fwd(*a, bound=BOUND))(*args)
        else:
            saved = (jnp.ones((BOUND, D)), jnp.ones((BOUND, f)), jnp.ones((BOUND, f)), jnp.ones((BOUND, D)))
            jaxpr = jax.make_jaxpr(lambda *a: moe._usual_bwd(*a, bound=BOUND))(saved, *args, jnp.ones((n, D)))
        shapes = shapes_in(jaxpr.jaxpr)
        # index vectors and [N, k] arrays may have N * k entries; nothing may have N * k rows of features
        assert max(int(np.prod(shape)) for shape in shapes) <= max(n * k, BOUND * f, n * D, int(np.prod(runs.token.shape)) * 128)
        assert not [shape for shape in shapes if len(shape) > 1 and shape[0] == n * k]
        # nothing is sorted, and rows are fetched once on the way out and twice on the way back, whatever k is
        kinds = [eqn.primitive.name for eqn in eqns_in(jaxpr.jaxpr)]
        assert "sort" not in kinds and "scatter-add" not in kinds and "scatter_add" not in kinds
        fetches = [eqn for eqn in eqns_in(jaxpr.jaxpr) if eqn.primitive.name == "gather" and eqn.outvars[0].aval.shape[-1] == D]
        assert len(fetches) == 3 and sorted(eqn.outvars[0].aval.shape[0] for eqn in fetches)[0] == BOUND
        # and the same checks do see the full path's buffers and its sorts
        full = jax.make_jaxpr(lambda *a: moe._full_fwd(*a, bound=BOUND, held=(0, 2)))(*args)
        assert (n * k, D) in shapes_in(full.jaxpr)
        assert [eqn for eqn in eqns_in(full.jaxpr) if eqn.primitive.name == "sort" and eqn.outvars[0].aval.shape == (n * k,)]

    def test_outside_the_rare_path_no_sort_is_as_long_as_the_pairs(self):
        model, variables, x = make_layer(**BOUNDED)
        jaxpr = jax.make_jaxpr(jax.grad(lambda v: jnp.sum(model.apply(v, x) ** 2)))(variables)

        def sorts(jaxpr, inside_a_cond=False):
            for eqn in jaxpr.eqns:
                if eqn.primitive.name == "sort":
                    yield eqn.outvars[0].aval.shape[0], inside_a_cond
                for inner in jax.core.jaxprs_in_params(eqn.params):
                    yield from sorts(inner, inside_a_cond or eqn.primitive.name == "cond")

        found = list(sorts(jaxpr.jaxpr))
        places = int(np.prod(run_layout(BOUND, 2)[:2]))
        assert sorted({length for length, inside in found if not inside}) == [places] and places < PAIRS
        assert {length for length, inside in found if inside} == {PAIRS}  # the full branches sort all the pairs for themselves

    @pytest.mark.parametrize("live_pairs", [200, PAIRS - 24], ids=["R-row-buffer", "N*k-row-buffer"])
    def test_rows_past_the_live_ones_are_not_read(self, monkeypatch, live_pairs):
        """On the TPU a grouped product leaves the rows past its groups as they were, forward and backward
        (PR 30's NaN): whatever it leaves there must reach neither the output nor a gradient, in either buffer."""
        model, variables, x = make_layer(**BOUNDED)
        monkeypatch.setattr(moe, "route", routed_to(live_pairs))
        want = value_and_grads(model.apply, variables, x)

        @jax.custom_vjp
        def poisoned(lhs, rhs, group_sizes):
            out = grouped_matmul(lhs, rhs, group_sizes)
            return jnp.where((jnp.arange(out.shape[0]) < jnp.sum(group_sizes))[:, None], out, jnp.nan)

        def fwd(lhs, rhs, group_sizes):
            return poisoned(lhs, rhs, group_sizes), (lhs, rhs, group_sizes)

        def bwd(saved, d_out):
            lhs, rhs, group_sizes = saved
            live = (jnp.arange(lhs.shape[0]) < jnp.sum(group_sizes))[:, None]
            d_lhs, d_rhs = jax.vjp(lambda a, b: grouped_matmul(a, b, group_sizes), lhs, rhs)[1](jnp.where(live, d_out, 0))
            # a NaN among the dead rows of either operand of the weights' product would show in d_rhs already
            return jnp.where(live, d_lhs, jnp.nan), d_rhs, None

        poisoned.defvjp(fwd, bwd)
        monkeypatch.setattr(moe, "grouped_matmul", poisoned)
        for body in (moe._usual_fwd, moe._usual_bwd, moe._full_fwd, moe._full_bwd):
            body.clear_cache()  # the bodies are traced once a process: not with the poison, and not kept with it
        try:
            got = value_and_grads(model.apply, variables, x)
        finally:
            for body in (moe._usual_fwd, moe._usual_bwd, moe._full_fwd, moe._full_bwd):
                body.clear_cache()
        for g, w in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-5, atol=1e-6)

    @pytest.mark.parametrize("live", [0, "one token", "random", "R", "R + 1"])
    @pytest.mark.parametrize("length, routing", [(256, dict(top_k=2, num_experts=8, experts_held=(0, 2))),
                                                 (256, dict(top_k=10, num_experts=256, experts_held=(16, 24))),
                                                 (1024, dict(top_k=1, num_experts=16, experts_held=(4, 6)))],
                             ids=["top-2-of-8-a-quarter-held", "top-10-of-256-eight-held", "top-1-of-16-two-held"])
    def test_the_moves_of_the_first_rows_are_each_others_transposes(self, monkeypatch, length, routing, live):
        """The index of the first ``R`` rows against the stable sort of all the pairs, the way back against ``collect``
        on the padded buffer and as the transpose of ``spread_rows``, and the layer round them against the loop over
        experts: for no live pair, one token's run alone, a random routing, a full buffer, and one pair more (the full path)."""
        n, k, experts, held, d = B * length, routing["top_k"], routing["num_experts"], routing["experts_held"], 4
        rows = row_bound(n * k, held[1] - held[0], experts)
        assert rows == BOUND < n * k
        if live == "random":
            chosen = np.asarray(TRUE_ROUTE(MoEConfig(**routing), jax.random.normal(jax.random.PRNGKey(3), (n, experts)))[1])
            live = int(((chosen >= held[0]) & (chosen < held[1])).sum())
            assert 0 < live < rows
        else:
            chosen = experts_with(n, k, experts, held, {"R": rows, "R + 1": rows + 1}.get(live, live))
            live = {"one token": min(k, held[1] - held[0]), "R": rows, "R + 1": rows + 1}.get(live, live)
        gates = jax.random.uniform(jax.random.PRNGKey(4), (n, k), minval=0.1)
        order_all, inverse_all, weight_all, sizes_all = sort_pairs(jnp.asarray(chosen), gates, held)
        assert int(sizes_all.sum()) == live
        if live <= rows:
            order, runs, weight, sizes = sort_pairs(jnp.asarray(chosen), gates, (*held, rows))
            np.testing.assert_array_equal(np.asarray(sizes), np.asarray(sizes_all))
            assert order.shape == weight.shape == (rows,)
            np.testing.assert_array_equal(np.asarray(order)[:live], np.asarray(order_all)[:live])  # today's stable order
            np.testing.assert_array_equal(np.asarray(weight)[:live], np.asarray(weight_all)[:live])
            assert not np.asarray(weight)[live:].any()
            # a token's rows are neighbours in one chunk of the token-order buffer, and the places name each live row once
            chunks, tile, heads = run_layout(rows, k)
            head, token = np.asarray(runs.head), np.asarray(runs.token).reshape(-1)
            count = ((chosen >= held[0]) & (chosen < held[1])).sum(axis=1)
            assert runs.token.shape == (chunks, tile) and token[-1] == n  # the last place is empty: what a token with no row reads
            for t in range(n):
                if count[t]:
                    assert head[t] // tile == (head[t] + count[t] - 1) // tile and head[t] % tile < heads
                    assert (token[head[t]:head[t] + count[t]] == t).all()
                else:
                    assert head[t] == chunks * tile - 1
            assert sorted(np.asarray(runs.row)[token < n].tolist()) == list(range(live)) and (token < n).sum() == live
            tokens = jax.random.normal(jax.random.PRNGKey(1), (n, d))
            buffer = jax.random.normal(jax.random.PRNGKey(2), (rows, d))
            alive = (jnp.arange(rows) < live)[:, None]
            np.testing.assert_array_equal(np.asarray(spread_rows(tokens, order, k, rows))[:live],
                                          np.asarray(spread(tokens, order_all, inverse_all, k))[:live])
            padded = jnp.concatenate([jnp.where(alive, buffer, 0), jnp.zeros((n * k - rows, d))])
            back = collect_rows(jnp.where(alive, buffer, jnp.nan), runs)  # what lies past the live rows is not read
            np.testing.assert_allclose(np.asarray(back), np.asarray(collect(padded, order_all, inverse_all, k)), rtol=1e-6, atol=1e-6)
            np.testing.assert_allclose(float(jnp.vdot(jnp.where(alive, spread_rows(tokens, order, k, rows), 0), jnp.where(alive, buffer, 0))),
                                       float(jnp.vdot(tokens, back)), rtol=1e-5, atol=1e-5)
        model, variables, x = make_layer(tokens=length, **routing)
        monkeypatch.setattr(moe, "route", routed_as(chosen))
        monkeypatch.setitem(globals(), "route", routed_as(chosen))  # by_hand routes the same way
        counters = assert_same_as_the_loop(model, variables, x, some=live > 0)
        assert counters["moe/pairs_held"] == live and counters["moe/overflow_layers"] == (live > rows)

    @pytest.mark.parametrize("rows, longest, want", [(5120, 10, (44, 128, 119)), (8192, 4, (66, 128, 125)), (512, 2, (5, 128, 127)),
                                                      (512, 1, (5, 128, 127)), (4096, 100, (27, 256, 157))])
    def test_the_token_order_buffer_is_cut_into_chunks_no_run_crosses(self, rows, longest, want):
        chunks, tile, heads = run_layout(rows, longest)
        assert (chunks, tile, heads) == want and heads + max(longest, 2) - 1 == tile
        assert (chunks - 1) * heads + tile - 1 >= rows  # the slot the last place stands for is never a live row's


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

    def test_partition_rules_shard_the_shared_expert_as_a_dense_mlp(self):
        _, params, _ = make_layer(num_experts=8, shared_expert_intermediate_size=16)
        mesh = mesh_lib.create_mesh({"fsdp": 2, "model": 4})
        shardings = mesh_lib.sharding_for(params, mesh, moe_partition_rules())["params"]["shared_expert"]
        assert tuple(shardings["gate_proj"]["kernel"].spec) == ("fsdp", "model") == tuple(shardings["up_proj"]["kernel"].spec)
        assert tuple(shardings["down_proj"]["kernel"].spec) == ("model", "fsdp")


class TestMoETransformer:
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
