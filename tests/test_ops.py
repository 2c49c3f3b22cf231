"""Attention ops: flash (Pallas, interpret mode on CPU) and ring attention
(real 8-device shard_map + ppermute) against the reference einsum path."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dmlcloud_tpu.models.transformer import _dot_attention
from dmlcloud_tpu.ops.flash_attention import flash_attention
from dmlcloud_tpu.ops.ring_attention import ring_attention_sharded
from dmlcloud_tpu.parallel import mesh as mesh_lib


def _qkv(b=2, t=128, h=4, kh=None, d=32, seed=0, dtype=jnp.float32):
    kh = kh or h
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(b, t, h, d), dtype) * 0.5
    k = jnp.asarray(rng.randn(b, t, kh, d), dtype) * 0.5
    v = jnp.asarray(rng.randn(b, t, kh, d), dtype)
    return q, k, v


class TestFlashAttention:
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_reference(self, causal):
        q, k, v = _qkv(t=128)
        expected = _dot_attention(q, k, v, causal=causal)
        out = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expected), atol=2e-5, rtol=2e-5)

    def test_gqa(self):
        q, k, v = _qkv(h=8, kh=2)
        expected = _dot_attention(q, k, v, causal=True)
        out = flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expected), atol=2e-5, rtol=2e-5)

    def test_dead_rows_write_exact_zero(self):
        """A row fully masked inside VISITED blocks (possible only through
        the internal shifted-window path the ring's behind-hops use) must
        write out == 0 and an effectively -inf lse — not a mean of V."""
        from dmlcloud_tpu.ops.flash_attention import _flash_lse

        q, k, v = _qkv(b=1, t=64, h=1, d=16)
        # internal call: causal=False, window=0 keeps only k_pos > q_pos,
        # so the LAST row attends to nothing while its K blocks are visited
        out, lse = _flash_lse(q, k, v, None, False, 1.0, 32, 32, True, 0)
        out = np.asarray(out)
        lse = np.asarray(lse).reshape(1, 1, 64)  # raw [B*H, T]
        assert np.all(out[0, -1, 0] == 0.0)
        assert lse[0, 0, -1] < -1e29
        # live rows match a reference softmax over their keys (k > q)
        s = np.einsum("td,sd->ts", np.asarray(q)[0, :, 0], np.asarray(k)[0, :, 0])
        mask = np.arange(64)[None, :] > np.arange(64)[:, None]
        s = np.where(mask, s, -np.inf)
        p = np.exp(s[:-1] - s[:-1].max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        expected = p @ np.asarray(v)[0, :, 0]
        np.testing.assert_allclose(out[0, :-1, 0], expected, atol=2e-5, rtol=2e-5)

    def test_block_divisibility_enforced(self):
        q, k, v = _qkv(t=100)
        with pytest.raises(ValueError):
            flash_attention(q, k, v, block_q=64, block_k=64)

    @pytest.mark.parametrize("t", [384, 192])
    def test_default_blocks_auto_shrink(self, t):
        """Seq lens that are multiples of 128/64 but not of the default 256
        block must auto-select the largest dividing block, not raise."""
        q, k, v = _qkv(t=t, h=2, d=16)
        expected = _dot_attention(q, k, v, causal=True)
        out = flash_attention(q, k, v, causal=True)  # default block sizes
        np.testing.assert_allclose(np.asarray(out), np.asarray(expected), atol=2e-5, rtol=2e-5)

    def test_causal_cross_length_rejected(self):
        """Causal with T != S would silently use the wrong mask alignment —
        must raise, not return top-left-masked garbage."""
        q, _, _ = _qkv(t=64, h=2, d=16)
        _, k, v = _qkv(t=128, h=2, d=16, seed=1)
        with pytest.raises(ValueError, match="equal Q/KV sequence lengths"):
            flash_attention(q, k, v, causal=True, block_q=32, block_k=32)
        # non-causal cross-length is fine
        out = flash_attention(q, k, v, causal=False, block_q=32, block_k=32)
        expected = _dot_attention(q, k, v, causal=False)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expected), atol=2e-5, rtol=2e-5)

    def test_grad_flows(self):
        q, k, v = _qkv(t=64, h=2, d=16)

        def loss(q):
            return jnp.sum(flash_attention(q, k, v, causal=True, block_q=32, block_k=32) ** 2)

        g = jax.grad(loss)(q)
        assert g.shape == q.shape
        assert bool(jnp.all(jnp.isfinite(g)))

    @pytest.mark.parametrize("causal", [True, False])
    def test_backward_matches_reference(self, causal):
        """The Pallas backward kernels (dQ; dK/dV) against autodiff through
        the reference einsum path — multi-block grids in both directions."""
        from dmlcloud_tpu.ops.flash_attention import _reference_attention

        q, k, v = _qkv(t=128, h=4, d=32)
        cot = jnp.asarray(np.random.RandomState(7).randn(*q.shape), q.dtype)

        def flash_loss(q, k, v):
            return jnp.vdot(flash_attention(q, k, v, causal=causal, block_q=32, block_k=64), cot)

        def ref_loss(q, k, v):
            return jnp.vdot(_reference_attention(q, k, v, causal, 1.0 / np.sqrt(q.shape[-1])), cot)

        got = jax.grad(flash_loss, argnums=(0, 1, 2))(q, k, v)
        want = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
        for g, w, name in zip(got, want, "qkv"):
            np.testing.assert_allclose(
                np.asarray(g), np.asarray(w), atol=5e-5, rtol=5e-5, err_msg=f"d{name}"
            )

    def test_backward_gqa_matches_reference(self):
        """GQA backward: grouped heads must accumulate into shared dK/dV."""
        from dmlcloud_tpu.ops.flash_attention import _reference_attention

        q, k, v = _qkv(t=64, h=8, kh=2, d=16)
        cot = jnp.asarray(np.random.RandomState(8).randn(*q.shape), q.dtype)

        def flash_loss(q, k, v):
            return jnp.vdot(flash_attention(q, k, v, causal=True, block_q=32, block_k=32), cot)

        def ref_loss(q, k, v):
            return jnp.vdot(_reference_attention(q, k, v, True, 1.0 / np.sqrt(q.shape[-1])), cot)

        got = jax.grad(flash_loss, argnums=(0, 1, 2))(q, k, v)
        want = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
        for g, w, name in zip(got, want, "qkv"):
            np.testing.assert_allclose(
                np.asarray(g), np.asarray(w), atol=5e-5, rtol=5e-5, err_msg=f"d{name}"
            )

    @pytest.mark.parametrize("window", [1, 17, 32, 100, 128])
    def test_sliding_window_matches_reference(self, window):
        """Window values spanning sub-block, block-multiple, and full-seq —
        exercises the stale-block skip and both mask boundaries."""
        from dmlcloud_tpu.ops.flash_attention import _reference_attention

        q, k, v = _qkv(t=128, h=2, d=16, seed=5)
        expected = _reference_attention(q, k, v, True, 1.0 / np.sqrt(16), window=window)
        out = flash_attention(q, k, v, causal=True, block_q=32, block_k=32, window=window)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expected), atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("window", [24, 64])
    def test_sliding_window_backward_matches_reference(self, window):
        """Windowed backward in both kernels (dq stale-block skip; dkv
        past-window skip), with uneven blocks."""
        from dmlcloud_tpu.ops.flash_attention import _reference_attention

        q, k, v = _qkv(t=128, h=4, kh=2, d=16, seed=6)
        cot = jnp.asarray(np.random.RandomState(9).randn(*q.shape), q.dtype)

        def flash_loss(q, k, v):
            return jnp.vdot(
                flash_attention(q, k, v, causal=True, block_q=64, block_k=32, window=window), cot
            )

        def ref_loss(q, k, v):
            return jnp.vdot(_reference_attention(q, k, v, True, 1.0 / np.sqrt(16), window=window), cot)

        got = jax.grad(flash_loss, argnums=(0, 1, 2))(q, k, v)
        want = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
        for g, w, name in zip(got, want, "qkv"):
            np.testing.assert_allclose(
                np.asarray(g), np.asarray(w), atol=5e-5, rtol=5e-5, err_msg=f"d{name}"
            )

    def test_sliding_window_requires_causal(self):
        q, k, v = _qkv(t=64, h=2, d=16)
        with pytest.raises(ValueError, match="causal"):
            flash_attention(q, k, v, causal=False, window=16)
        with pytest.raises(ValueError, match=">= 1"):
            flash_attention(q, k, v, causal=True, window=0)

    def test_backward_uneven_qk_blocks(self):
        """block_q != block_k exercises the diagonal-skip bounds in both
        backward kernels (dq upper bound, dkv lower bound)."""
        from dmlcloud_tpu.ops.flash_attention import _reference_attention

        q, k, v = _qkv(t=128, h=2, d=16, seed=3)

        def flash_loss(q, k, v):
            return jnp.sum(flash_attention(q, k, v, causal=True, block_q=64, block_k=16) ** 2)

        def ref_loss(q, k, v):
            return jnp.sum(_reference_attention(q, k, v, True, 1.0 / np.sqrt(q.shape[-1])) ** 2)

        got = jax.grad(flash_loss, argnums=(0, 1, 2))(q, k, v)
        want = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
        for g, w, name in zip(got, want, "qkv"):
            np.testing.assert_allclose(
                np.asarray(g), np.asarray(w), atol=5e-5, rtol=5e-5, err_msg=f"d{name}"
            )


class TestRingAttention:
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_full_attention(self, causal):
        """seq sharded 8 ways; ring result == unsharded reference."""
        mesh = mesh_lib.create_mesh({"seq": 8})
        q, k, v = _qkv(b=1, t=64, h=2, d=16)
        expected = _dot_attention(q, k, v, causal=causal)
        out = ring_attention_sharded(q, k, v, mesh, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expected), atol=2e-5, rtol=2e-5)

    def test_gqa_ring(self):
        mesh = mesh_lib.create_mesh({"seq": 4}, devices=jax.devices()[:4])
        q, k, v = _qkv(b=1, t=32, h=4, kh=2, d=16)
        expected = _dot_attention(q, k, v, causal=True)
        out = ring_attention_sharded(q, k, v, mesh, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expected), atol=2e-5, rtol=2e-5)

    def test_data_and_seq_axes(self):
        mesh = mesh_lib.create_mesh({"data": 2, "seq": 4})
        q, k, v = _qkv(b=2, t=32, h=2, d=16)
        expected = _dot_attention(q, k, v, causal=True)
        out = ring_attention_sharded(q, k, v, mesh, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expected), atol=2e-5, rtol=2e-5)

    def test_grad_flows(self):
        mesh = mesh_lib.create_mesh({"seq": 4}, devices=jax.devices()[:4])
        q, k, v = _qkv(b=1, t=32, h=2, d=16)

        def loss(q, k, v):
            return jnp.sum(ring_attention_sharded(q, k, v, mesh) ** 2)

        grads = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        for g, ref_arr in zip(grads, (q, k, v)):
            assert g.shape == ref_arr.shape
            assert bool(jnp.all(jnp.isfinite(g)))

    @pytest.mark.parametrize("causal", [True, False])
    def test_grads_match_reference_on_mesh(self, causal):
        """Ring grads == unsharded einsum grads on the 8-device mesh. This
        also validates the lse-cotangent path of the flash backward: the
        blockwise merge differentiates through each block's logsumexp."""
        mesh = mesh_lib.create_mesh({"seq": 8})
        q, k, v = _qkv(b=1, t=64, h=2, d=16, seed=5)
        cot = jnp.asarray(np.random.RandomState(9).randn(*q.shape), q.dtype)

        def ring_loss(q, k, v):
            return jnp.vdot(ring_attention_sharded(q, k, v, mesh, causal=causal), cot)

        def ref_loss(q, k, v):
            return jnp.vdot(_dot_attention(q, k, v, causal=causal), cot)

        got = jax.grad(ring_loss, argnums=(0, 1, 2))(q, k, v)
        want = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
        for g, w, name in zip(got, want, "qkv"):
            np.testing.assert_allclose(
                np.asarray(g), np.asarray(w), atol=1e-4, rtol=1e-4, err_msg=f"d{name}"
            )


class TestWindowedRing:
    """Sliding-window ring attention: global-position window over the sharded
    sequence, truncated ring rotation."""

    @pytest.mark.parametrize("window", [1, 5, 8, 13, 40, 64])
    def test_matches_windowed_reference(self, window):
        """Windows smaller than, equal to, and spanning multiple local
        blocks (Tl=8 at 8 devices), incl. full-seq."""
        from dmlcloud_tpu.ops.flash_attention import _reference_attention

        mesh = mesh_lib.create_mesh({"seq": 8})
        q, k, v = _qkv(b=1, t=64, h=2, d=16, seed=11)
        expected = _reference_attention(q, k, v, True, 1.0 / np.sqrt(16), window=window)
        out = ring_attention_sharded(q, k, v, mesh, causal=True, window=window)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expected), atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("window", [5, 13])
    def test_grads_match_windowed_reference(self, window):
        from dmlcloud_tpu.ops.flash_attention import _reference_attention

        mesh = mesh_lib.create_mesh({"seq": 8})
        q, k, v = _qkv(b=1, t=64, h=2, d=16, seed=12)
        cot = jnp.asarray(np.random.RandomState(13).randn(*q.shape), q.dtype)

        def ring_loss(q, k, v):
            return jnp.vdot(ring_attention_sharded(q, k, v, mesh, causal=True, window=window), cot)

        def ref_loss(q, k, v):
            return jnp.vdot(_reference_attention(q, k, v, True, 1.0 / np.sqrt(16), window=window), cot)

        got = jax.grad(ring_loss, argnums=(0, 1, 2))(q, k, v)
        want = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
        for g, w, name in zip(got, want, "qkv"):
            np.testing.assert_allclose(
                np.asarray(g), np.asarray(w), atol=1e-4, rtol=1e-4, err_msg=f"d{name}"
            )

    def test_gqa_windowed_ring(self):
        from dmlcloud_tpu.ops.flash_attention import _reference_attention

        mesh = mesh_lib.create_mesh({"seq": 4}, devices=jax.devices()[:4])
        q, k, v = _qkv(b=1, t=32, h=4, kh=2, d=16, seed=14)
        expected = _reference_attention(q, k, v, True, 1.0 / np.sqrt(16), window=11)
        out = ring_attention_sharded(q, k, v, mesh, causal=True, window=11)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expected), atol=2e-5, rtol=2e-5)

    def test_window_requires_causal(self):
        mesh = mesh_lib.create_mesh({"seq": 4}, devices=jax.devices()[:4])
        q, k, v = _qkv(b=1, t=32, h=2, d=16)
        with pytest.raises(ValueError, match="causal"):
            ring_attention_sharded(q, k, v, mesh, causal=False, window=8)


class TestFlashLse:
    def test_lse_value(self):
        """return_lse must equal the actual logsumexp of scaled scores."""
        q, k, v = _qkv(b=1, t=64, h=2, d=16)
        out, lse = flash_attention(q, k, v, causal=False, block_q=32, block_k=32, return_lse=True)
        scale = 1.0 / np.sqrt(16)
        scores = jnp.einsum("bthd,bshd->bhts", q, k) * scale
        expected = jax.scipy.special.logsumexp(scores.astype(jnp.float32), axis=-1)  # [B,H,T]
        np.testing.assert_allclose(
            np.asarray(lse), np.asarray(expected.transpose(0, 2, 1)), atol=2e-5, rtol=2e-5
        )

    def test_lse_grad(self):
        """Gradients THROUGH the lse output alone (d lse/d s = softmax) —
        the delta-shift in the backward kernels."""
        q, k, v = _qkv(b=1, t=32, h=2, d=16)
        glse = jnp.asarray(np.random.RandomState(3).randn(1, 32, 2), jnp.float32)
        scale = 1.0 / np.sqrt(16)

        def flash_loss(q, k, v):
            _, lse = flash_attention(q, k, v, causal=True, block_q=16, block_k=16, return_lse=True)
            return jnp.vdot(lse, glse)

        def ref_loss(q, k, v):
            scores = jnp.einsum("bthd,bshd->bhts", q, k).astype(jnp.float32) * scale
            mask = jnp.tril(jnp.ones((32, 32), bool))
            scores = jnp.where(mask[None, None], scores, -1e30)
            lse = jax.scipy.special.logsumexp(scores, axis=-1).transpose(0, 2, 1)
            return jnp.vdot(lse, glse)

        got = jax.grad(flash_loss, argnums=(0, 1))(q, k, v)
        want = jax.grad(ref_loss, argnums=(0, 1))(q, k, v)
        for g, w, name in zip(got, want, "qk"):
            np.testing.assert_allclose(
                np.asarray(g), np.asarray(w), atol=5e-5, rtol=5e-5, err_msg=f"d{name}"
            )


class TestFlashSegments:
    """Packed-sequence (segment_ids) masking in the flash kernels."""

    @staticmethod
    def _segs(b, t, seed):
        rng = np.random.RandomState(seed)
        segs = np.zeros((b, t), np.int32)
        for r in range(b):
            pos, sid = 0, 1
            while pos < t:
                ln = int(rng.randint(8, 40))
                segs[r, pos : pos + ln] = sid
                pos += ln
                sid += 1
        return jnp.asarray(segs)

    @staticmethod
    def _ref(q, k, v, segs, causal, window=None):
        from dmlcloud_tpu.ops.flash_attention import _NEG_INF

        b, t, h, d = q.shape
        kh = k.shape[2]
        group = h // kh
        qg = q.reshape(b, t, kh, group, d)
        scores = jnp.einsum("btkgd,bskd->bkgts", qg, k).astype(jnp.float32) / np.sqrt(d)
        mask = segs[:, :, None] == segs[:, None, :]
        if causal:
            mask = mask & jnp.tril(jnp.ones((t, t), bool))[None]
        if window is not None:
            pos = jnp.arange(t)
            mask = mask & ((pos[:, None] - pos[None, :]) < window)[None]
        scores = jnp.where(mask[:, None, None], scores, _NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
        return jnp.einsum("bkgts,bskd->btkgd", probs, v).reshape(b, t, h, d)

    @pytest.mark.parametrize("causal", [True, False])
    def test_fwd_matches_reference(self, causal):
        q, k, v = _qkv(b=2, t=128, h=2, d=16, seed=21)
        segs = self._segs(2, 128, 5)
        want = self._ref(q, k, v, segs, causal)
        got = flash_attention(q, k, v, causal=causal, block_q=32, block_k=32, segment_ids=segs)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)

    def test_fwd_gqa_with_window(self):
        q, k, v = _qkv(b=1, t=128, h=4, kh=2, d=16, seed=22)
        segs = self._segs(1, 128, 6)
        want = self._ref(q, k, v, segs, True, window=23)
        got = flash_attention(
            q, k, v, causal=True, block_q=32, block_k=64, window=23, segment_ids=segs
        )
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)

    def test_backward_matches_reference(self):
        q, k, v = _qkv(b=1, t=128, h=2, d=16, seed=23)
        segs = self._segs(1, 128, 7)
        cot = jnp.asarray(np.random.RandomState(24).randn(*q.shape), q.dtype)

        def flash_loss(q, k, v):
            return jnp.vdot(
                flash_attention(q, k, v, causal=True, block_q=64, block_k=32, segment_ids=segs), cot
            )

        def ref_loss(q, k, v):
            return jnp.vdot(self._ref(q, k, v, segs, True), cot)

        got = jax.grad(flash_loss, argnums=(0, 1, 2))(q, k, v)
        want = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
        for g, w, name in zip(got, want, "qkv"):
            np.testing.assert_allclose(
                np.asarray(g), np.asarray(w), atol=5e-5, rtol=5e-5, err_msg=f"d{name}"
            )

    def test_shape_validation(self):
        q, k, v = _qkv(t=64, h=2, d=16)
        with pytest.raises(ValueError, match="segment_ids must be"):
            flash_attention(q, k, v, segment_ids=jnp.ones((2, 32), jnp.int32))
