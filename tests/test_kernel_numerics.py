"""Kernel-numerics property tests (PR 6's raw-speed pass, tier-1).

The optimisation sweep rewrote the hot kernels' lowerings — these tests pin
the numerics so the speed can't drift away from correctness:

- flash attention fwd AND fwd+bwd must match the unfused einsum reference
  within per-dtype tolerance across dtypes (bf16/fp32), causal/window
  variants, ragged (non-block-multiple) lengths, and BOTH lowerings — the
  blockwise-XLA off-TPU default and the interpreted Pallas kernels;
- speculative decode must stay token-identical to plain greedy decode when
  draft == target (the provably-accept-everything contract whose breakage
  produced the r05 receipts' 0.0 accept rate).

Shapes are kept small so the whole module runs inside tier-1.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dmlcloud_tpu.ops.flash_attention import _reference_attention, flash_attention

# (impl kwarg, interpret kwarg): the blockwise-XLA lowering and the
# bit-exact interpreted Pallas kernels — both must hold the same contract
LOWERINGS = [("xla", None), ("pallas", True)]

TOL = {
    jnp.float32: dict(atol=5e-5, rtol=5e-5),
    # bf16 inputs: both sides accumulate in fp32 but round operands/outputs
    # to 8 mantissa bits; gradients compound one extra rounding
    jnp.bfloat16: dict(atol=6e-2, rtol=6e-2),
}


def _qkv(b=2, t=64, h=4, kh=None, d=16, seed=0, dtype=jnp.float32):
    kh = kh or h
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(b, t, h, d), dtype) * 0.5
    k = jnp.asarray(rng.randn(b, t, kh, d), dtype) * 0.5
    v = jnp.asarray(rng.randn(b, t, kh, d), dtype)
    return q, k, v


def _grads(attn, q, k, v, cot):
    loss = lambda q, k, v: jnp.vdot(attn(q, k, v).astype(jnp.float32), cot.astype(jnp.float32))
    return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)


class TestFlashFwdBwdProperty:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["fp32", "bf16"])
    @pytest.mark.parametrize("impl,interp", LOWERINGS, ids=["xla", "pallas"])
    @pytest.mark.parametrize("causal,window", [(True, None), (False, None), (True, 24)],
                             ids=["causal", "full", "window24"])
    def test_fwd_and_grads_match_reference(self, dtype, impl, interp, causal, window):
        q, k, v = _qkv(dtype=dtype)
        sm = 1.0 / np.sqrt(q.shape[-1])
        tol = TOL[dtype]

        flash = lambda q, k, v: flash_attention(
            q, k, v, causal=causal, window=window, block_q=32, block_k=32,
            impl=impl, interpret=interp,
        )
        ref = lambda q, k, v: _reference_attention(q, k, v, causal, sm, window=window)

        np.testing.assert_allclose(
            np.asarray(flash(q, k, v), np.float32), np.asarray(ref(q, k, v), np.float32),
            err_msg="forward", **tol,
        )
        cot = jnp.asarray(np.random.RandomState(7).randn(*q.shape), jnp.float32)
        got = _grads(flash, q, k, v, cot)
        want = _grads(ref, q, k, v, cot)
        for g, w, name in zip(got, want, "qkv"):
            np.testing.assert_allclose(
                np.asarray(g, np.float32), np.asarray(w, np.float32),
                err_msg=f"d{name}", **tol,
            )

    @pytest.mark.parametrize("t", [40, 56, 96], ids=lambda t: f"t{t}")
    @pytest.mark.parametrize("impl,interp", LOWERINGS, ids=["xla", "pallas"])
    def test_ragged_lengths(self, t, impl, interp):
        """Non-block-multiple sequence lengths: the auto-shrunk block grid
        (40 -> blocks of 8, 56 -> 8, 96 -> 32) must stay exact fwd+bwd."""
        q, k, v = _qkv(t=t)
        sm = 1.0 / np.sqrt(q.shape[-1])

        flash = lambda q, k, v: flash_attention(q, k, v, causal=True, impl=impl, interpret=interp)
        ref = lambda q, k, v: _reference_attention(q, k, v, True, sm)

        np.testing.assert_allclose(
            np.asarray(flash(q, k, v)), np.asarray(ref(q, k, v)), atol=5e-5, rtol=5e-5
        )
        cot = jnp.asarray(np.random.RandomState(3).randn(*q.shape), jnp.float32)
        got = _grads(flash, q, k, v, cot)
        want = _grads(ref, q, k, v, cot)
        for g, w, name in zip(got, want, "qkv"):
            np.testing.assert_allclose(
                np.asarray(g), np.asarray(w), atol=5e-5, rtol=5e-5, err_msg=f"d{name}"
            )

    def test_gqa_grads_both_lowerings_agree(self):
        """The two lowerings of the SAME algorithm must agree with each
        other (not just each within tolerance of the reference) — GQA
        grouping included."""
        q, k, v = _qkv(t=64, h=8, kh=2)
        cot = jnp.asarray(np.random.RandomState(5).randn(*q.shape), jnp.float32)
        xla = _grads(lambda q, k, v: flash_attention(q, k, v, causal=True, impl="xla"), q, k, v, cot)
        pal = _grads(
            lambda q, k, v: flash_attention(q, k, v, causal=True, impl="pallas", interpret=True,
                                            block_q=32, block_k=32),
            q, k, v, cot,
        )
        for g, w, name in zip(xla, pal, "qkv"):
            np.testing.assert_allclose(
                np.asarray(g), np.asarray(w), atol=1e-5, rtol=1e-5, err_msg=f"d{name}"
            )


    def test_pallas_off_tpu_needs_an_explicit_interpret(self):
        """No silent interpreter: ``impl="pallas"`` on a CPU says whether to
        emulate the kernels or to lower them, or it raises."""
        q, k, v = _qkv()
        with pytest.raises(ValueError, match="explicit interpret"):
            flash_attention(q, k, v, causal=True, impl="pallas")


class TestFlashSharded:
    """``flash_attention_sharded`` (what ``attn_impl="flash"`` runs on a
    multi-device mesh) against the unsharded op, on the suite's virtual CPU
    devices: batch over fsdp, heads over model, GQA groups kept together."""

    @pytest.mark.parametrize("packed", [False, True], ids=["plain", "segment_ids"])
    def test_fwd_and_grads_match_unsharded(self, packed):
        from dmlcloud_tpu.ops.flash_attention import flash_attention_sharded
        from dmlcloud_tpu.parallel import mesh as mesh_lib

        mesh = mesh_lib.create_mesh({"fsdp": 2, "model": 2}, devices=jax.devices()[:4])
        q, k, v = _qkv(t=128, h=8, kh=2)
        seg = jnp.asarray(np.repeat([[1, 2, 3, 0]], 2, 0).repeat(32, axis=1), jnp.int32) if packed else None
        cot = jnp.asarray(np.random.RandomState(7).randn(*q.shape), jnp.float32)
        kwargs = dict(causal=True, window=48, segment_ids=seg)
        plain = lambda q, k, v: flash_attention(q, k, v, **kwargs)
        sharded = jax.jit(lambda q, k, v: flash_attention_sharded(q, k, v, mesh, **kwargs))
        np.testing.assert_allclose(
            np.asarray(sharded(q, k, v)), np.asarray(plain(q, k, v)), atol=1e-5, rtol=1e-5
        )
        for g, w, name in zip(_grads(sharded, q, k, v, cot), _grads(plain, q, k, v, cot), "qkv"):
            np.testing.assert_allclose(
                np.asarray(g), np.asarray(w), atol=1e-5, rtol=1e-5, err_msg=f"d{name}"
            )

    def test_indivisible_batch_and_heads_stay_replicated(self):
        """module.init's size-1 example batch, and KV heads the model axis
        does not divide, run whole on every device instead of failing."""
        from dmlcloud_tpu.ops.flash_attention import flash_attention_sharded
        from dmlcloud_tpu.parallel import mesh as mesh_lib

        mesh = mesh_lib.create_mesh({"fsdp": 2, "model": 2}, devices=jax.devices()[:4])
        q, k, v = _qkv(b=1, t=64, h=3, kh=3)
        got = jax.jit(lambda q, k, v: flash_attention_sharded(q, k, v, mesh, causal=True))(q, k, v)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(flash_attention(q, k, v, causal=True)), atol=1e-5, rtol=1e-5
        )


class TestSpeculativeExactness:
    def test_shared_model_token_identical(self):
        """draft == target: every proposal must be accepted and the output
        must equal plain greedy decode token for token."""
        from dmlcloud_tpu.models.generate import generate
        from dmlcloud_tpu.models.speculative import speculative_generate
        from dmlcloud_tpu.models.transformer import DecoderLM, TransformerConfig

        cfg = TransformerConfig(
            vocab_size=32, num_layers=2, num_heads=2, num_kv_heads=1, head_dim=8,
            hidden_dim=16, mlp_dim=32, max_seq_len=48, dtype=jnp.float32,
        )
        model = DecoderLM(cfg)
        prompt = jnp.asarray(np.random.RandomState(0).randint(0, 32, (2, 6)), jnp.int32)
        params = model.init(jax.random.PRNGKey(0), prompt)["params"]

        want = np.asarray(generate(model, params, prompt, max_new_tokens=12))
        got, (rounds, _, accepted) = speculative_generate(
            model, params, model, params, prompt, max_new_tokens=12, k=3, return_stats=True
        )
        np.testing.assert_array_equal(np.asarray(got), want)
        rounds, accepted = np.asarray(rounds, np.float64), np.asarray(accepted, np.float64)
        np.testing.assert_allclose(accepted / (rounds * 3), 1.0)
