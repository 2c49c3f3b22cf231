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
    return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)  # one compile, not one per primitive


def _segments(b, t, seed=5):
    """Packed rows: runs of 5-23 tokens, so a segment's edge crosses blocks."""
    rng = np.random.RandomState(seed)
    segs = np.zeros((b, t), np.int32)
    for row in segs:
        pos, sid = 0, 1
        while pos < t:
            n = int(rng.randint(5, 24))
            row[pos : pos + n] = sid
            pos, sid = pos + n, sid + 1
    return jnp.asarray(segs)


def _dense_keep(t, s, causal, window):
    q, k = np.arange(t)[:, None], np.arange(s)[None, :]
    keep = np.ones((t, s), bool)
    if causal:
        keep &= q >= k
    if window is not None:
        keep &= q - k < window
    return keep


def _dense_lse(q, k, sm, causal, window, seg):
    """Per-row logsumexp of the masked scaled scores, [B, T, H], fp32."""
    b, t, h, d = q.shape
    s, kh = k.shape[1], k.shape[2]
    kr = jnp.repeat(k, h // kh, axis=2)
    scores = jnp.einsum("bthd,bshd->bhts", q, kr).astype(jnp.float32) * sm
    keep = jnp.broadcast_to(_dense_keep(t, s, causal, window), (b, t, s))
    if seg is not None:
        keep &= seg[:, :, None] == seg[:, None, :]
    scores = jnp.where(keep[:, None], scores, -jnp.inf)
    return jax.scipy.special.logsumexp(scores, axis=-1).transpose(0, 2, 1)


#: what the mask can be, as the block plan sees it (blocks of 32 unless given):
#: no window, one smaller than a block, not a multiple of the block, a block
#: multiple, larger than S; uneven blocks both ways; T != S; lane-tile key
#: blocks; Mistral's GQA 32/8; packed rows with and without a window (the
#: encoder's call)
MASK_CASES = {
    "causal": dict(causal=True),
    "full": dict(causal=False),
    "window24": dict(causal=True, window=24),
    "window5": dict(causal=True, window=5),
    "window32": dict(causal=True, window=32),
    "window100": dict(causal=True, window=100),
    "window40-blocks16x32": dict(causal=True, window=40, block_q=16, block_k=32),
    "window24-blocks32x16": dict(causal=True, window=24, block_q=32, block_k=16),
    "causal-blocks16x64": dict(causal=True, block_q=16, block_k=64),
    "full-t32-s96": dict(causal=False, t=32, s=96),
    # whole lane tiles, as every TPU shape has them: several vregs a row, bands of 1-2 key blocks
    "window200-blocks128x256": dict(causal=True, window=200, block_q=128, block_k=256, t=512, b=1, h=2),
    "gqa32_8-window24": dict(causal=True, window=24, h=32, kh=8, b=1, d=8),
    "segments-window24": dict(causal=True, window=24, packed=True),
    "segments-full": dict(causal=False, packed=True),
}


class TestFlashFwdBwdProperty:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["fp32", "bf16"])
    @pytest.mark.parametrize("impl,interp", LOWERINGS, ids=["xla", "pallas"])
    @pytest.mark.parametrize("case", sorted(MASK_CASES))
    def test_fwd_and_grads_match_reference(self, dtype, impl, interp, case):
        """Forward, LSE and all three gradients against the unfused reference."""
        spec = dict(MASK_CASES[case])
        causal, window = spec.pop("causal"), spec.pop("window", None)
        block_q, block_k = spec.pop("block_q", 32), spec.pop("block_k", 32)
        packed, s_len = spec.pop("packed", False), spec.pop("s", None)
        q, k, v = _qkv(dtype=dtype, **spec)
        if s_len is not None:
            _, k, v = _qkv(dtype=dtype, **dict(spec, t=s_len), seed=1)
        seg = _segments(q.shape[0], q.shape[1]) if packed else None
        sm = 1.0 / np.sqrt(q.shape[-1])
        tol = TOL[dtype]

        flash_lse = lambda q, k, v: flash_attention(
            q, k, v, causal=causal, window=window, block_q=block_q, block_k=block_k,
            impl=impl, interpret=interp, segment_ids=seg, return_lse=True,
        )
        flash = lambda q, k, v: flash_lse(q, k, v)[0]
        ref = lambda q, k, v: _reference_attention(q, k, v, causal, sm, window=window, segment_ids=seg)

        out, lse = jax.jit(flash_lse)(q, k, v)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(jax.jit(ref)(q, k, v), np.float32),
            err_msg="forward", **tol,
        )
        np.testing.assert_allclose(
            np.asarray(lse), np.asarray(_dense_lse(q, k, sm, causal, window, seg)),
            err_msg="lse", **tol,
        )
        cot = jnp.asarray(np.random.RandomState(7).randn(*q.shape), jnp.float32)
        got = _grads(flash, q, k, v, cot)
        want = _grads(ref, q, k, v, cot)
        for g, w, name in zip(got, want, "qkv"):
            np.testing.assert_allclose(
                np.asarray(g, np.float32), np.asarray(w, np.float32),
                err_msg=f"d{name}", **tol,
            )

    @pytest.mark.parametrize("shift", [-40, -8, 0, 5, 24, 100], ids=lambda w: f"w{w}")
    @pytest.mark.parametrize("t,s", [(64, 64), (32, 64)], ids=["t64-s64", "t32-s64"])
    def test_shifted_window_hops_match_the_xla_twin(self, shift, t, s):
        """What the ring's behind-hops send: ``causal=False`` and a shifted,
        possibly negative, window through ``_flash_lse``. Empty bands and rows
        dead inside visited blocks must come out as today — out 0, a finite
        lse near -1e30 — and out, lse and every gradient (through both
        outputs) must equal the blockwise-XLA twin's, which this PR's plan
        does not touch."""
        from dmlcloud_tpu.ops.flash_attention import _flash_lse

        q, _, _ = _qkv(b=1, t=t, h=2, d=16)
        _, k, v = _qkv(b=1, t=s, h=2, d=16, seed=1)
        sm = 0.25
        live = (jnp.arange(t)[:, None] - jnp.arange(s)[None, :] < shift).any(axis=1)  # [T]
        # a dead row's merge weight is exactly 0 in the ring, so no cotangent reaches it
        cot = jnp.asarray(np.random.RandomState(7).randn(*q.shape), jnp.float32) * live[None, :, None, None]
        glse = jnp.asarray(np.random.RandomState(8).randn(2, t), jnp.float32) * live[None]

        def run(mode, block):
            def loss(q, k, v):
                out, lse = _flash_lse(q, k, v, None, False, sm, block, block, mode, shift)
                return jnp.vdot(out, cot) + jnp.vdot(lse, glse), (out, lse)

            grads, (out, lse) = jax.jit(jax.grad(loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)
            return out, lse, grads

        out, lse, grads = run(True, 16)
        want_out, want_lse, want_grads = run("xla", 16)
        dead = ~np.asarray(live)
        assert np.all(np.asarray(out)[0, dead] == 0.0)
        assert np.all(np.asarray(lse)[:, dead] < -1e29) and np.all(np.isfinite(np.asarray(lse)))
        np.testing.assert_allclose(np.asarray(out), np.asarray(want_out), atol=2e-5, rtol=2e-5)
        np.testing.assert_allclose(
            np.asarray(lse)[:, ~dead], np.asarray(want_lse)[:, ~dead], atol=2e-5, rtol=2e-5
        )
        for g, w, name in zip(grads, want_grads, "qkv"):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=5e-5, rtol=5e-5, err_msg=f"d{name}")

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


#: (causal, window): none, smaller than a block, not a multiple of one, a
#: multiple, larger than S; and as the ring sends them, shifted to and below 0
PLAN_MASKS = [(True, None), (False, None), (True, 5), (True, 24), (True, 32), (True, 1000),
              (False, 24), (False, 0), (False, -8), (False, -40), (False, -1000)]
#: (T, S, block_q, block_k): square, uneven both ways, and T != S
PLAN_SHAPES = [(64, 64, 16, 16), (64, 64, 16, 32), (64, 64, 32, 8), (32, 96, 16, 32)]
#: causal needs T == S (flash_attention rejects the call otherwise)
PLAN_CASES = [(m, sh) for m in PLAN_MASKS for sh in PLAN_SHAPES if not m[0] or sh[0] == sh[1]]


def _walk(plan, kernel):
    """The steps the kernel's grid makes, as (query block, key block, held,
    DMA block, inner blocks) — forward and dQ walk a query block's band of key
    blocks, dK/dV a key block's band of query blocks."""
    if kernel == "dkv":
        for kb in range(plan.num_kb):
            for j in range(plan.q_width):
                qb, held, dma = plan.q_step(kb, j)
                yield qb, kb, held, dma, plan.num_qb
    else:
        for qi in range(plan.num_qb):
            for j in range(plan.kv_width):
                kb, held, dma = plan.kv_step(qi, j)
                yield qi, kb, held, dma, plan.num_kb


class TestBlockPlan:
    """The plan all three kernels read, against the dense mask, exhaustively
    at small shapes. Counts only: nothing here runs a kernel."""

    @pytest.mark.parametrize("kernel", ["fwd", "dq", "dkv"])
    @pytest.mark.parametrize("mask,shape", PLAN_CASES, ids=lambda x: "-".join(map(str, x)))
    def test_plan_visits_exactly_the_live_pairs(self, kernel, mask, shape):
        from dmlcloud_tpu.ops.flash_attention import _BlockPlan

        (causal, window), (t, s, bq, bk) = mask, shape
        plan = _BlockPlan(t, s, bq, bk, causal, window)
        keep = _dense_keep(t, s, causal, window)
        tile = lambda qb, kb: keep[qb * bq : (qb + 1) * bq, kb * bk : (kb + 1) * bk]
        live = {(qb, kb) for qb in range(t // bq) for kb in range(s // bk) if tile(qb, kb).any()}
        held_pairs = []
        for qb, kb, held, dma, n_inner in _walk(plan, kernel):
            assert 0 <= dma < n_inner  # a padded step still asks for a block that exists
            if held:
                held_pairs.append((qb, kb))
                assert dma == (qb if kernel == "dkv" else kb)
                # interior exactly when nothing in the pair is masked
                assert bool(plan.interior(qb, kb)) == bool(tile(qb, kb).all())
        assert len(held_pairs) == len(set(held_pairs))  # no pair twice
        assert set(held_pairs) == live  # every live pair visited, no empty pair held
        widest = max([sum(1 for p in live if p[kernel == "dkv"] == o) for o in range(max(t, s))] + [1])
        assert (plan.q_width if kernel == "dkv" else plan.kv_width) == widest
        assert plan.masked == (causal or window is not None)

    @pytest.mark.parametrize("kernel", ["fwd", "dq", "dkv"])
    @pytest.mark.parametrize("blocks,steps,held,edge", [((512, 1024), 80, 60, 24), (None, 40, 30, 12)],
                             ids=["512x1024", "swept"])
    def test_the_benchmark_cells_shapes(self, kernel, blocks, steps, held, edge):
        """m7b-train-8k: T = S = 8192, window 4096. At 512 x 1024 the
        rectangle has 128 pairs; 60 hold a pair, 24 of them on the mask's
        edge and 36 inside; the band grids make 80 steps where the rectangle
        made 128. At the shape the v5e sweep chose for this call (1024 x 1024,
        what a call that names no blocks gets) 30 of 64 pairs hold a pair, 12
        on the edge, in 40 steps."""
        from dmlcloud_tpu.ops.flash_attention import _BlockPlan, _plan_for

        plan = _plan_for(None, None, 128, 8192, 8192, True, 4096) if blocks is None else _BlockPlan(8192, 8192, *blocks, True, 4096)
        if blocks is None:
            assert (plan.block_q, plan.block_k) == (1024, 1024)
            # another window, length or head size was not swept: today's blocks
            assert _plan_for(None, None, 128, 8192, 8192, True, 2048).block_k == 1024
            assert _plan_for(None, None, 128, 8192, 8192, True, 2048).block_q == 512
            assert _plan_for(None, None, 64, 8192, 8192, True, 4096).block_q == 512
            assert _plan_for(256, None, 128, 8192, 8192, True, 4096).block_q == 256  # an explicit block wins
        walked = list(_walk(plan, kernel))
        pairs = [(qb, kb) for qb, kb, is_held, _, _ in walked if is_held]
        assert (len(walked), len(pairs)) == (steps, held)
        assert sum(1 for qb, kb in pairs if not plan.interior(qb, kb)) == edge


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
