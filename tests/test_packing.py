"""Packed-sequence training (segment_ids): a packed row must be numerically
identical to running its examples unpacked — segment-isolated attention AND
per-segment rotary position restart — and lm_loss must skip cross-boundary
and padding targets. fp32 config for exact CPU comparison."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dmlcloud_tpu.models.transformer import DecoderLM, TransformerConfig, lm_loss


def _cfg(**kw):
    base = dict(
        vocab_size=37,
        num_layers=2,
        num_heads=4,
        head_dim=8,
        hidden_dim=32,
        mlp_dim=64,
        max_seq_len=32,
        dtype=jnp.float32,
    )
    base.update(kw)
    return TransformerConfig(**base)


@pytest.fixture(scope="module")
def packed_setup():
    cfg = _cfg()
    model = DecoderLM(cfg)
    rng = np.random.RandomState(0)
    a = rng.randint(1, cfg.vocab_size, size=5)
    b = rng.randint(1, cfg.vocab_size, size=6)
    row = np.concatenate([a, b, [0]])[None]  # [1, 12], trailing pad
    segs = np.asarray([1] * 5 + [2] * 6 + [0])[None]
    params = model.init(jax.random.PRNGKey(0), jnp.asarray(row))["params"]
    return cfg, model, params, a, b, row, segs


def test_packed_logits_match_unpacked(packed_setup):
    cfg, model, params, a, b, row, segs = packed_setup
    packed = model.apply({"params": params}, jnp.asarray(row), segment_ids=jnp.asarray(segs))
    la = model.apply({"params": params}, jnp.asarray(a[None]))
    lb = model.apply({"params": params}, jnp.asarray(b[None]))
    np.testing.assert_allclose(np.asarray(packed[0, :5]), np.asarray(la[0]), atol=1e-5)
    np.testing.assert_allclose(np.asarray(packed[0, 5:11]), np.asarray(lb[0]), atol=1e-5)


def test_packed_loss_matches_unpacked(packed_setup):
    cfg, model, params, a, b, row, segs = packed_setup
    packed_logits = model.apply({"params": params}, jnp.asarray(row), segment_ids=jnp.asarray(segs))
    loss_packed = lm_loss(packed_logits, jnp.asarray(row), segment_ids=jnp.asarray(segs))

    la = model.apply({"params": params}, jnp.asarray(a[None]))
    lb = model.apply({"params": params}, jnp.asarray(b[None]))
    loss_a = lm_loss(la, jnp.asarray(a[None]))  # mean over 4 pairs
    loss_b = lm_loss(lb, jnp.asarray(b[None]))  # mean over 5 pairs
    want = (4 * float(loss_a) + 5 * float(loss_b)) / 9
    assert abs(float(loss_packed) - want) < 1e-5


def test_segment_ids_reject_ring():
    from dmlcloud_tpu.parallel import mesh as mesh_lib

    mesh = mesh_lib.create_mesh({"seq": 4}, devices=jax.devices()[:4])
    cfg = _cfg(attn_impl="ring", mesh=mesh)
    model = DecoderLM(cfg)
    row = jnp.zeros((1, 8), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), row)["params"]
    with pytest.raises(ValueError, match="ring"):
        model.apply({"params": params}, row, segment_ids=jnp.ones((1, 8), jnp.int32))


def test_segment_ids_reject_decode_mode(packed_setup):
    cfg, model, params, a, b, row, segs = packed_setup
    from dmlcloud_tpu.models.generate import init_cache

    cache = init_cache(cfg, 1, 16, dtype=jnp.float32)
    with pytest.raises(ValueError, match="decode"):
        model.apply(
            {"params": params}, jnp.asarray(row), cache=cache, segment_ids=jnp.asarray(segs)
        )


def test_gradients_flow_through_packed_path(packed_setup):
    cfg, model, params, a, b, row, segs = packed_setup

    def loss_fn(p):
        logits = model.apply({"params": p}, jnp.asarray(row), segment_ids=jnp.asarray(segs))
        return lm_loss(logits, jnp.asarray(row), segment_ids=jnp.asarray(segs))

    grads = jax.grad(loss_fn)(params)
    gnorm = sum(float(jnp.sum(g**2)) for g in jax.tree_util.tree_leaves(grads))
    assert np.isfinite(gnorm) and gnorm > 0


class TestSlidingWindow:
    """cfg.sliding_window across the model's attention paths."""

    def test_dot_vs_flash_windowed(self):
        cfg_dot = _cfg(sliding_window=7, max_seq_len=64)
        cfg_flash = _cfg(sliding_window=7, max_seq_len=64, attn_impl="flash")
        model_dot, model_flash = DecoderLM(cfg_dot), DecoderLM(cfg_flash)
        rng = np.random.RandomState(3)
        toks = jnp.asarray(rng.randint(0, 37, size=(2, 64)), jnp.int32)
        params = model_dot.init(jax.random.PRNGKey(0), toks)["params"]
        out_dot = model_dot.apply({"params": params}, toks)
        out_flash = model_flash.apply({"params": params}, toks)
        np.testing.assert_allclose(np.asarray(out_dot), np.asarray(out_flash), atol=2e-4, rtol=2e-4)

    def test_windowed_decode_matches_no_cache(self):
        from dmlcloud_tpu.models.generate import generate

        cfg = _cfg(sliding_window=5, max_seq_len=32)
        model = DecoderLM(cfg)
        rng = np.random.RandomState(4)
        prompt = jnp.asarray(rng.randint(0, 37, size=(2, 9)), jnp.int32)
        params = model.init(jax.random.PRNGKey(1), prompt)["params"]

        tokens = prompt
        want = []
        for _ in range(6):
            logits = model.apply({"params": params}, tokens)
            nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
            want.append(nxt)
            tokens = jnp.concatenate([tokens, nxt[:, None]], axis=1)
        got = generate(model, params, prompt, max_new_tokens=6)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(jnp.stack(want, axis=1)))

    def test_windowed_packed_matches_unpacked(self):
        cfg = _cfg(sliding_window=3)
        model = DecoderLM(cfg)
        rng = np.random.RandomState(5)
        a = rng.randint(1, 37, size=6)
        b = rng.randint(1, 37, size=5)
        row = np.concatenate([a, b])[None]
        segs = np.asarray([1] * 6 + [2] * 5)[None]
        params = model.init(jax.random.PRNGKey(2), jnp.asarray(row))["params"]
        packed = model.apply({"params": params}, jnp.asarray(row), segment_ids=jnp.asarray(segs))
        la = model.apply({"params": params}, jnp.asarray(a[None]))
        lb = model.apply({"params": params}, jnp.asarray(b[None]))
        np.testing.assert_allclose(np.asarray(packed[0, :6]), np.asarray(la[0]), atol=1e-5)
        np.testing.assert_allclose(np.asarray(packed[0, 6:]), np.asarray(lb[0]), atol=1e-5)

    def test_ring_windowed_matches_dot(self):
        """ring + sliding_window on a real seq mesh equals the dot path."""
        from dmlcloud_tpu.parallel import mesh as mesh_lib

        mesh = mesh_lib.create_mesh({"seq": 4}, devices=jax.devices()[:4])
        cfg_dot = _cfg(sliding_window=9, max_seq_len=32)
        cfg_ring = _cfg(sliding_window=9, max_seq_len=32, attn_impl="ring", mesh=mesh)
        rng = np.random.RandomState(6)
        toks = jnp.asarray(rng.randint(0, 37, size=(2, 32)), jnp.int32)
        params = DecoderLM(cfg_dot).init(jax.random.PRNGKey(0), toks)["params"]
        out_dot = DecoderLM(cfg_dot).apply({"params": params}, toks)
        out_ring = DecoderLM(cfg_ring).apply({"params": params}, toks)
        np.testing.assert_allclose(np.asarray(out_dot), np.asarray(out_ring), atol=2e-4, rtol=2e-4)


def test_packed_flash_matches_packed_dot():
    """attn_impl='flash' now honors segment_ids: logits equal the dot path."""
    cfg_dot = _cfg(max_seq_len=64)
    cfg_flash = _cfg(max_seq_len=64, attn_impl="flash")
    rng = np.random.RandomState(9)
    row = rng.randint(1, 37, size=(2, 64)).astype(np.int32)
    segs = np.repeat(np.arange(1, 9)[None], 2, 0).repeat(8, axis=1).astype(np.int32)  # 8 segs x 8
    params = DecoderLM(cfg_dot).init(jax.random.PRNGKey(0), jnp.asarray(row))["params"]
    out_dot = DecoderLM(cfg_dot).apply(
        {"params": params}, jnp.asarray(row), segment_ids=jnp.asarray(segs)
    )
    out_flash = DecoderLM(cfg_flash).apply(
        {"params": params}, jnp.asarray(row), segment_ids=jnp.asarray(segs)
    )
    np.testing.assert_allclose(np.asarray(out_dot), np.asarray(out_flash), atol=2e-4, rtol=2e-4)


def test_packed_flash_grads_flow():
    cfg = _cfg(max_seq_len=64, attn_impl="flash")
    rng = np.random.RandomState(10)
    row = rng.randint(1, 37, size=(1, 64)).astype(np.int32)
    segs = np.concatenate([np.full(40, 1), np.full(24, 2)])[None].astype(np.int32)
    model = DecoderLM(cfg)
    params = model.init(jax.random.PRNGKey(1), jnp.asarray(row))["params"]

    def loss_fn(p):
        logits = model.apply({"params": p}, jnp.asarray(row), segment_ids=jnp.asarray(segs))
        return lm_loss(logits, jnp.asarray(row), segment_ids=jnp.asarray(segs))

    grads = jax.grad(loss_fn)(params)
    gnorm = sum(float(jnp.sum(g**2)) for g in jax.tree_util.tree_leaves(grads))
    assert np.isfinite(gnorm) and gnorm > 0
