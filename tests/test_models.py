"""Model zoo: shape/dtype checks, a real sharded train step for the decoder
LM under dp+fsdp+tp rules, and ring-attention parity inside the full model."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

from dmlcloud_tpu.models.cnn import MnistCNN
from dmlcloud_tpu.models.resnet import ResNet18, ResNet50
from dmlcloud_tpu.models.transformer import (
    DecoderLM,
    TransformerConfig,
    lm_loss,
    llama_partition_rules,
)
from dmlcloud_tpu.parallel import mesh as mesh_lib
from dmlcloud_tpu.train_state import TrainState


SMALL = TransformerConfig(
    vocab_size=256,
    num_layers=2,
    num_heads=4,
    num_kv_heads=2,
    head_dim=16,
    hidden_dim=64,
    mlp_dim=128,
    max_seq_len=64,
    dtype=jnp.float32,
)


def test_mnist_cnn_shapes():
    model = MnistCNN()
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((2, 28, 28, 1)))
    out = model.apply(params, jnp.zeros((2, 28, 28, 1)))
    assert out.shape == (2, 10)


def test_resnet18_forward():
    model = ResNet18(num_classes=10, dtype=jnp.float32)
    vars_ = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 32, 32, 3)), train=False)
    assert "batch_stats" in vars_
    out = model.apply(vars_, jnp.zeros((2, 32, 32, 3)), train=False)
    assert out.shape == (2, 10)


def test_resnet50_param_count():
    model = ResNet50(num_classes=1000, dtype=jnp.float32)
    vars_ = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 224, 224, 3)), train=False)
    )
    n = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(vars_["params"]))
    assert 25.0e6 < n < 26.0e6  # ResNet-50 is ~25.6M params


def test_decoder_lm_forward_and_loss():
    model = DecoderLM(SMALL)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 32), 0, SMALL.vocab_size)
    params = model.init(jax.random.PRNGKey(1), tokens)
    logits = model.apply(params, tokens)
    assert logits.shape == (2, 32, SMALL.vocab_size)
    assert logits.dtype == jnp.float32
    loss = lm_loss(logits, tokens)
    assert np.isfinite(float(loss))
    assert float(loss) == pytest.approx(np.log(SMALL.vocab_size), rel=0.2)


def _plain_lm_loss(logits, tokens, segment_ids=None):
    """The form ``lm_loss`` had before it read the logits where they lie:
    optax's loss on the shifted copy ``logits[:, :-1]``, the packed mean over
    ``T - 1`` columns. Kept here as the reference."""
    losses = optax.softmax_cross_entropy_with_integer_labels(logits[:, :-1], tokens[:, 1:])
    if segment_ids is None:
        return losses.mean()
    w = (segment_ids[:, 1:] == segment_ids[:, :-1]) & (segment_ids[:, 1:] != 0)
    w = w.astype(losses.dtype)
    return (losses * w).sum() / jnp.maximum(w.sum(), 1)


# (batch, logits dtype, segment ids a row or None)
LM_LOSS_CASES = {
    "plain": (1, jnp.float32, None),
    "batch_of_3": (3, jnp.float32, None),
    # a pad segment in the middle of nothing, a boundary at the last column
    # (its target opens segment 4: not counted), pads at the end of a row
    "packed": (2, jnp.float32, [[1, 1, 1, 2, 2, 2, 2, 3, 3, 3, 3, 4], [1, 1, 2, 2, 2, 3, 3, 3, 3, 0, 0, 0]]),
    "no_position_counts": (2, jnp.float32, [[0] * 12, [1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11, 12]]),
    "bf16_logits": (2, jnp.bfloat16, [[1, 1, 1, 1, 2, 2, 2, 2, 2, 0, 0, 0], [1] * 12]),
}


@pytest.mark.parametrize("case", sorted(LM_LOSS_CASES))
def test_lm_loss_equals_the_plain_form_it_replaced(case):
    """Value and gradient with respect to the logits, against optax's loss on
    the shifted copy. The arithmetic is float32 whatever the logits' dtype, and
    the gradient comes back in the logits' dtype."""
    batch, dtype, segs = LM_LOSS_CASES[case]
    rng = np.random.RandomState(3)
    logits = jnp.asarray(rng.randn(batch, 12, 50) * 3, dtype)
    tokens = jnp.asarray(rng.randint(0, 50, (batch, 12)), jnp.int32)
    segs = None if segs is None else jnp.asarray(segs, jnp.int32)
    got, got_grad = jax.jit(jax.value_and_grad(lm_loss))(logits, tokens, segs)
    want, want_grad = jax.value_and_grad(_plain_lm_loss)(logits.astype(jnp.float32), tokens, segs)
    assert got.dtype == jnp.float32 and got_grad.dtype == dtype
    np.testing.assert_allclose(float(got), float(want), rtol=1e-6, atol=1e-7)
    if dtype == jnp.float32:
        np.testing.assert_allclose(np.asarray(got_grad), np.asarray(want_grad), rtol=1e-5, atol=1e-8)
    else:  # the float32 gradient, rounded once on the way out: within one bf16 step
        np.testing.assert_allclose(np.asarray(got_grad, np.float32), np.asarray(want_grad), rtol=2.0**-7, atol=1e-8)
    if case == "no_position_counts":
        assert float(got) == 0.0 and not np.asarray(got_grad).any()


def test_decoder_causality():
    """Changing a future token must not affect earlier logits."""
    model = DecoderLM(SMALL)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (1, 16), 0, SMALL.vocab_size)
    params = model.init(jax.random.PRNGKey(1), tokens)
    logits_a = model.apply(params, tokens)
    tokens_b = tokens.at[0, -1].set((tokens[0, -1] + 1) % SMALL.vocab_size)
    logits_b = model.apply(params, tokens_b)
    np.testing.assert_allclose(
        np.asarray(logits_a[0, :-1]), np.asarray(logits_b[0, :-1]), atol=1e-5
    )


def test_decoder_sharded_train_step_dp_fsdp_tp():
    """Full dp+fsdp+tp train step on a 2x2x2 mesh: compiles, runs, loss drops."""
    mesh = mesh_lib.create_mesh({"data": 2, "fsdp": 2, "model": 2})
    model = DecoderLM(SMALL)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (8, 32), 0, SMALL.vocab_size)
    params = model.init(jax.random.PRNGKey(1), tokens[:1])

    state = TrainState.create(
        apply_fn=model.apply,
        params=params,
        tx=optax.adam(1e-2),
        mesh=mesh,
        policy=llama_partition_rules(),
    )
    # param shardings actually use the model axis somewhere
    specs = jax.tree_util.tree_leaves(
        jax.tree_util.tree_map(lambda x: x.sharding.spec, state.params),
        is_leaf=lambda x: isinstance(x, P),
    )
    assert any("model" in str(spec) for spec in specs)

    batch = mesh_lib.make_global_batch(tokens, mesh)

    @jax.jit
    def train_step(state, batch):
        def loss_fn(params):
            return lm_loss(state.apply_fn(params, batch), batch)

        loss, grads = jax.value_and_grad(loss_fn)(state.params)
        return state.apply_gradients(grads), loss

    losses = []
    for _ in range(5):
        state, loss = train_step(state, batch)
        losses.append(float(loss))
    assert losses[-1] < losses[0]


def test_decoder_ring_attention_matches_dot():
    """The full model with ring attention over the seq axis == dot attention."""
    mesh = mesh_lib.create_mesh({"data": 2, "seq": 4})
    cfg_ring = TransformerConfig(
        **{**SMALL.__dict__, "attn_impl": "ring", "mesh": mesh}
    )
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 32), 0, SMALL.vocab_size)

    params = DecoderLM(SMALL).init(jax.random.PRNGKey(1), tokens)
    logits_dot = DecoderLM(SMALL).apply(params, tokens)
    logits_ring = DecoderLM(cfg_ring).apply(params, tokens)
    np.testing.assert_allclose(np.asarray(logits_dot), np.asarray(logits_ring), atol=2e-4, rtol=2e-4)


def test_decoder_flash_attention_matches_dot():
    cfg_flash = TransformerConfig(**{**SMALL.__dict__, "attn_impl": "flash"})
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 64), 0, SMALL.vocab_size)
    params = DecoderLM(SMALL).init(jax.random.PRNGKey(1), tokens)
    logits_dot = DecoderLM(SMALL).apply(params, tokens)
    logits_flash = DecoderLM(cfg_flash).apply(params, tokens)
    np.testing.assert_allclose(np.asarray(logits_dot), np.asarray(logits_flash), atol=2e-4, rtol=2e-4)


def test_decoder_remat_matches_no_remat():
    """Gradient rematerialisation must be numerics-neutral: same logits,
    same gradients, only the backward memory schedule changes."""
    cfg_remat = TransformerConfig(**{**SMALL.__dict__, "remat": True})
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 64), 0, SMALL.vocab_size)
    params = DecoderLM(SMALL).init(jax.random.PRNGKey(1), tokens)

    def loss_fn(cfg):
        return lambda p: lm_loss(DecoderLM(cfg).apply(p, tokens), tokens)

    base_loss, base_grads = jax.value_and_grad(loss_fn(SMALL))(params)
    rm_loss, rm_grads = jax.value_and_grad(loss_fn(cfg_remat))(params)
    np.testing.assert_allclose(float(base_loss), float(rm_loss), rtol=1e-6)
    for g1, g2 in zip(jax.tree_util.tree_leaves(base_grads), jax.tree_util.tree_leaves(rm_grads)):
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), atol=1e-5, rtol=1e-5)


def test_encoder_remat_matches_no_remat():
    from dmlcloud_tpu.models.encoder import EncoderConfig, TransformerEncoder

    cfg = EncoderConfig(hidden_dim=32, num_layers=2, num_heads=4, mlp_dim=64, dtype=jnp.float32)
    cfg_rm = EncoderConfig(**{**cfg.__dict__, "remat": True})
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, 32))
    params = TransformerEncoder(cfg).init(jax.random.PRNGKey(1), x)

    def loss(c):
        return lambda p: jnp.sum(TransformerEncoder(c).apply(p, x) ** 2)

    l1, g1 = jax.value_and_grad(loss(cfg))(params)
    l2, g2 = jax.value_and_grad(loss(cfg_rm))(params)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(g1), jax.tree_util.tree_leaves(g2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5, rtol=1e-5)


class TestChunkedLMLoss:
    """chunked_lm_loss must match lm_loss to f32 accuracy, forward AND
    backward, without materializing [B, T, V] logits."""

    def _setup(self, b=2, t=12, d=16, v=1000):
        from dmlcloud_tpu.models.transformer import chunked_lm_loss, lm_loss

        rng = np.random.RandomState(0)
        hidden = jnp.asarray(rng.randn(b, t, d), jnp.float32)
        kernel = jnp.asarray(rng.randn(d, v) * 0.2, jnp.float32)
        tokens = jnp.asarray(rng.randint(0, v, (b, t)), jnp.int32)
        return chunked_lm_loss, lm_loss, hidden, kernel, tokens

    def test_matches_full_loss_nondivisible_chunk(self):
        chunked, full, hidden, kernel, tokens = self._setup()
        logits = hidden.astype(jnp.float32) @ kernel
        want = full(logits, tokens)
        got = chunked(hidden, kernel, tokens, vocab_chunk=256)  # 1000 % 256 != 0
        np.testing.assert_allclose(float(got), float(want), rtol=1e-6)

    def test_gradients_match(self):
        chunked, full, hidden, kernel, tokens = self._setup()

        g_full = jax.grad(lambda h, w: full(h.astype(jnp.float32) @ w, tokens), argnums=(0, 1))(
            hidden, kernel
        )
        g_chunk = jax.grad(lambda h, w: chunked(h, w, tokens, vocab_chunk=128), argnums=(0, 1))(
            hidden, kernel
        )
        for a, b in zip(g_full, g_chunk):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)

    def test_segment_ids_match(self):
        chunked, full, hidden, kernel, tokens = self._setup()
        segs = jnp.asarray([[1, 1, 1, 1, 2, 2, 2, 0, 0, 0, 0, 0],
                            [1, 1, 2, 2, 2, 2, 3, 3, 3, 3, 0, 0]], jnp.int32)
        logits = hidden.astype(jnp.float32) @ kernel
        want = full(logits, tokens, segment_ids=segs)
        got = chunked(hidden, kernel, tokens, vocab_chunk=300, segment_ids=segs)
        np.testing.assert_allclose(float(got), float(want), rtol=1e-6)

    def test_through_decoder_lm_return_hidden(self):
        from dmlcloud_tpu.models.transformer import (
            DecoderLM,
            TransformerConfig,
            chunked_lm_loss,
            lm_loss,
        )

        cfg = TransformerConfig(
            vocab_size=260, num_layers=2, num_heads=2, num_kv_heads=1, head_dim=8,
            hidden_dim=16, mlp_dim=32, max_seq_len=32, dtype=jnp.float32,
        )
        model = DecoderLM(cfg)
        tokens = jnp.asarray(np.random.RandomState(1).randint(0, 260, (2, 16)), jnp.int32)
        params = model.init(jax.random.PRNGKey(0), tokens)["params"]
        want = lm_loss(model.apply({"params": params}, tokens), tokens)
        hidden = model.apply({"params": params}, tokens, return_hidden=True)
        got = chunked_lm_loss(hidden, params["lm_head"]["kernel"], tokens, vocab_chunk=64)
        np.testing.assert_allclose(float(got), float(want), rtol=2e-6)
