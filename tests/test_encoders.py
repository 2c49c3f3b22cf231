"""Encoder model families: ViT, BERT, CLIP — shapes, losses, mask semantics,
sharded training, and the global-batch contrastive gather."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from dmlcloud_tpu.models.bert import (
    IGNORE_INDEX,
    BertConfig,
    BertForMaskedLM,
    BertForSequenceClassification,
    mlm_loss,
)
from dmlcloud_tpu.models.clip import CLIP, CLIPConfig, CLIPTextConfig, clip_loss
from dmlcloud_tpu.models.encoder import encoder_partition_rules
from dmlcloud_tpu.models.vit import ViT, ViTConfig
from dmlcloud_tpu.parallel import mesh as mesh_lib
from dmlcloud_tpu.train_state import TrainState

VIT_TINY = ViTConfig(
    image_size=32, patch_size=8, hidden_dim=64, num_layers=2, num_heads=4,
    mlp_dim=128, num_classes=10, dtype=jnp.float32,
)
BERT_TINY = BertConfig(
    vocab_size=128, max_seq_len=32, hidden_dim=64, num_layers=2, num_heads=4,
    mlp_dim=128, dtype=jnp.float32,
)


def test_vit_forward_shapes():
    model = ViT(VIT_TINY)
    images = jnp.zeros((2, 32, 32, 3))
    params = model.init(jax.random.PRNGKey(0), images)
    out = model.apply(params, images)
    assert out.shape == (2, 10)
    assert out.dtype == jnp.float32


def test_vit_gap_and_features():
    import dataclasses

    cfg = dataclasses.replace(VIT_TINY, pooling="gap", num_classes=0)
    model = ViT(cfg)
    images = jnp.ones((2, 32, 32, 3))
    params = model.init(jax.random.PRNGKey(0), images)
    feats = model.apply(params, images)
    assert feats.shape == (2, 64)


def test_vit_b16_param_count():
    from dmlcloud_tpu.models.vit import ViT_B16

    model = ViT_B16(num_classes=1000)
    vars_ = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 224, 224, 3)))
    )
    n = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(vars_["params"]))
    assert 85e6 < n < 88e6  # ViT-B/16 is ~86.6M params


def test_bert_mlm_loss_at_init():
    model = BertForMaskedLM(BERT_TINY)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 16), 0, BERT_TINY.vocab_size)
    params = model.init(jax.random.PRNGKey(1), tokens)
    logits = model.apply(params, tokens)
    assert logits.shape == (2, 16, BERT_TINY.vocab_size)
    labels = tokens.at[:, ::2].set(IGNORE_INDEX)  # mask out half the positions
    loss = mlm_loss(logits, labels)
    assert float(loss) == pytest.approx(np.log(BERT_TINY.vocab_size), rel=0.2)


def test_mlm_loss_ignores_masked_positions():
    logits = jnp.zeros((1, 4, 8)).at[0, 0, 3].set(100.0)
    labels_all_ignored = jnp.full((1, 4), IGNORE_INDEX)
    assert float(mlm_loss(logits, labels_all_ignored)) == 0.0
    labels = labels_all_ignored.at[0, 0].set(3)
    assert float(mlm_loss(logits, labels)) == pytest.approx(0.0, abs=1e-5)


def test_bert_attention_mask_blocks_padding():
    """Masked-out padding tokens must not influence other positions."""
    model = BertForMaskedLM(BERT_TINY)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (1, 16), 0, BERT_TINY.vocab_size)
    params = model.init(jax.random.PRNGKey(1), tokens)
    mask = jnp.ones((1, 16)).at[0, 8:].set(0)

    logits_a = model.apply(params, tokens, attention_mask=mask)
    garbage = tokens.at[0, 8:].set((tokens[0, 8:] + 7) % BERT_TINY.vocab_size)
    logits_b = model.apply(params, garbage, attention_mask=mask)
    np.testing.assert_allclose(
        np.asarray(logits_a[0, :8]), np.asarray(logits_b[0, :8]), atol=1e-5
    )


def test_bert_classifier_shapes():
    model = BertForSequenceClassification(BERT_TINY, num_classes=3)
    tokens = jnp.zeros((2, 16), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens)
    out = model.apply(params, tokens)
    assert out.shape == (2, 3)


def test_bert_sharded_finetune_step():
    """BERT fine-tune (the BASELINE ladder rung) on a data+model mesh."""
    mesh = mesh_lib.create_mesh({"data": 4, "model": 2})
    model = BertForSequenceClassification(BERT_TINY, num_classes=2)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (8, 16), 0, BERT_TINY.vocab_size)
    labels = jax.random.randint(jax.random.PRNGKey(1), (8,), 0, 2)
    params = model.init(jax.random.PRNGKey(2), tokens[:1])

    state = TrainState.create(
        apply_fn=model.apply,
        params=params,
        tx=optax.adam(1e-3),
        mesh=mesh,
        policy=encoder_partition_rules(),
    )
    batch = mesh_lib.make_global_batch(tokens, mesh)
    y = mesh_lib.make_global_batch(labels, mesh)

    @jax.jit
    def step(state, batch, y):
        def loss_fn(p):
            logits = state.apply_fn(p, batch)
            return optax.softmax_cross_entropy_with_integer_labels(logits, y).mean()

        loss, grads = jax.value_and_grad(loss_fn)(state.params)
        return state.apply_gradients(grads), loss

    losses = []
    for _ in range(5):
        state, loss = step(state, batch, y)
        losses.append(float(loss))
    assert losses[-1] < losses[0]


def test_bert_fsdp_mesh_placement():
    """Regression: rules matching indivisible dims (the 2-row type-embedding
    table vs P('fsdp', ...)) must relocate the axis to a divisible dim — or
    replicate — instead of crashing placement."""
    mesh = mesh_lib.create_mesh({"data": 2, "fsdp": 4})
    model = BertForMaskedLM(BERT_TINY)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (8, 16), 0, BERT_TINY.vocab_size)
    params = model.init(jax.random.PRNGKey(1), tokens[:1])

    state = TrainState.create(
        apply_fn=model.apply,
        params=params,
        tx=optax.adam(1e-3),
        mesh=mesh,
        policy=encoder_partition_rules(),
    )
    # the word-embedding table (128 rows) is sharded over fsdp on dim 0...
    embeddings = state.params["params"]["bert"]["embeddings"]
    word_spec = embeddings["word"]["embedding"].sharding.spec
    assert word_spec[0] == "fsdp"
    # ...while the 2-row type table had its fsdp shards relocated to the
    # (divisible) hidden dim instead of crashing or silently replicating
    type_spec = embeddings["type"]["embedding"].sharding.spec
    assert tuple(type_spec) == (None, "fsdp")

    batch = mesh_lib.make_global_batch(tokens, mesh)
    logits = jax.jit(state.apply_fn)(state.params, batch)
    assert logits.shape == (8, 16, BERT_TINY.vocab_size)


CLIP_TINY = CLIPConfig(
    embed_dim=32,
    vision=ViTConfig(
        image_size=16, patch_size=8, hidden_dim=32, num_layers=1, num_heads=2,
        mlp_dim=64, num_classes=0, dtype=jnp.float32,
    ),
    text=CLIPTextConfig(
        vocab_size=64, max_seq_len=12, hidden_dim=32, num_layers=1, num_heads=2,
        mlp_dim=64, dtype=jnp.float32,
    ),
)


def _clip_batch(n):
    rng = np.random.RandomState(0)
    images = jnp.asarray(rng.rand(n, 16, 16, 3), jnp.float32)
    tokens = jnp.asarray(rng.randint(1, 63, (n, 12)), jnp.int32)
    tokens = tokens.at[:, -1].set(63)  # EOT = highest id
    return images, tokens


def test_clip_forward_and_loss():
    model = CLIP(CLIP_TINY)
    images, tokens = _clip_batch(4)
    params = model.init(jax.random.PRNGKey(0), images, tokens)
    img, txt, scale = model.apply(params, images, tokens)
    assert img.shape == (4, 32) and txt.shape == (4, 32)
    np.testing.assert_allclose(np.linalg.norm(np.asarray(img), axis=-1), 1.0, atol=1e-5)
    loss = clip_loss(img, txt, scale)
    assert np.isfinite(float(loss))
    # at init the large logit scale (1/0.07) spreads random similarities, so
    # just bound it near the uniform value rather than pin it
    assert 0.0 < float(loss) < 4.0 * np.log(4)


def test_clip_global_batch_loss_matches_single_device():
    """shard_mapped clip_loss with all_gather over 'data' == unsharded loss."""
    from jax.experimental.shard_map import shard_map

    mesh = mesh_lib.create_mesh({"data": 8})
    rng = np.random.RandomState(1)
    img = jnp.asarray(rng.randn(16, 8), jnp.float32)
    txt = jnp.asarray(rng.randn(16, 8), jnp.float32)
    img = img / jnp.linalg.norm(img, axis=-1, keepdims=True)
    txt = txt / jnp.linalg.norm(txt, axis=-1, keepdims=True)
    scale = jnp.float32(10.0)

    expected = float(clip_loss(img, txt, scale))

    sharded = shard_map(
        lambda i, t: jax.lax.pmean(clip_loss(i, t, scale, axis_name="data"), "data")[None],
        mesh=mesh,
        in_specs=(P("data"), P("data")),
        out_specs=P(None),
    )
    got = float(sharded(img, txt)[0])
    assert got == pytest.approx(expected, rel=1e-5)


def test_encoder_flash_attention_matches_dot():
    """attn_impl='flash' (unmasked path) must match the einsum softmax, in
    both directions, causal and not."""
    import jax
    import numpy as np

    from dmlcloud_tpu.models.encoder import EncoderConfig, TransformerEncoder

    for causal in (False, True):
        cfg = EncoderConfig(hidden_dim=32, num_layers=2, num_heads=2, mlp_dim=64,
                            dtype=jnp.float32, causal=causal)
        cfg_flash = EncoderConfig(**{**cfg.__dict__, "attn_impl": "flash"})
        x = jax.random.normal(jax.random.PRNGKey(0), (2, 64, 32))
        params = TransformerEncoder(cfg).init(jax.random.PRNGKey(1), x)

        out_dot = TransformerEncoder(cfg).apply(params, x)
        out_flash = TransformerEncoder(cfg_flash).apply(params, x)
        np.testing.assert_allclose(np.asarray(out_dot), np.asarray(out_flash), atol=2e-4, rtol=2e-4)

        g_dot = jax.grad(lambda p: jnp.sum(TransformerEncoder(cfg).apply(p, x) ** 2))(params)
        g_flash = jax.grad(lambda p: jnp.sum(TransformerEncoder(cfg_flash).apply(p, x) ** 2))(params)
        for a, b in zip(jax.tree_util.tree_leaves(g_dot), jax.tree_util.tree_leaves(g_flash)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4, rtol=5e-4)


def test_encoder_flash_with_padding_mask_falls_back():
    """A padding mask routes through the bias path even under attn_impl='flash'
    — same numbers as 'dot' with the same mask."""
    import jax
    import numpy as np

    from dmlcloud_tpu.models.encoder import EncoderConfig, TransformerEncoder, padding_mask_bias

    cfg = EncoderConfig(hidden_dim=32, num_layers=1, num_heads=2, mlp_dim=64, dtype=jnp.float32)
    cfg_flash = EncoderConfig(**{**cfg.__dict__, "attn_impl": "flash"})
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 64, 32))
    mask = jnp.asarray(np.repeat([[1] * 48 + [0] * 16], 2, axis=0))
    bias = padding_mask_bias(mask)
    params = TransformerEncoder(cfg).init(jax.random.PRNGKey(1), x)
    out_dot = TransformerEncoder(cfg).apply(params, x, bias)
    out_flash = TransformerEncoder(cfg_flash).apply(params, x, bias)
    np.testing.assert_allclose(np.asarray(out_dot), np.asarray(out_flash), atol=1e-5, rtol=1e-5)


def test_invalid_attn_impl_rejected():
    import pytest

    from dmlcloud_tpu.models.encoder import EncoderConfig
    from dmlcloud_tpu.models.transformer import TransformerConfig

    with pytest.raises(ValueError, match="attn_impl"):
        EncoderConfig(attn_impl="Flash")
    with pytest.raises(ValueError, match="attn_impl"):
        TransformerConfig(attn_impl="pallas")


def test_bert_padded_flash_matches_dot_on_real_positions():
    """A padded batch on the flash path (keep-mask as kernel segment ids)
    must match the dot/bias path at every REAL position (pad outputs differ
    by design and are masked downstream)."""
    from dmlcloud_tpu.models.bert import BertConfig, BertEncoder

    kw = dict(vocab_size=61, hidden_dim=32, num_heads=2, mlp_dim=64,
              num_layers=2, max_seq_len=64, dtype=jnp.float32)
    cfg_dot = BertConfig(**kw, attn_impl="dot")
    cfg_flash = BertConfig(**kw, attn_impl="flash")
    rng = np.random.RandomState(0)
    tokens = rng.randint(0, 61, size=(2, 64)).astype(np.int32)
    mask = np.ones((2, 64), np.int32)
    mask[0, 50:] = 0
    mask[1, 33:] = 0

    model_dot, model_flash = BertEncoder(cfg_dot), BertEncoder(cfg_flash)
    params = model_dot.init(jax.random.PRNGKey(0), jnp.asarray(tokens))["params"]
    out_dot = model_dot.apply({"params": params}, jnp.asarray(tokens), jnp.asarray(mask))
    out_flash = model_flash.apply({"params": params}, jnp.asarray(tokens), jnp.asarray(mask))
    for r in range(2):
        real = mask[r].astype(bool)
        np.testing.assert_allclose(
            np.asarray(out_dot)[r][real], np.asarray(out_flash)[r][real], atol=2e-4, rtol=2e-4
        )
