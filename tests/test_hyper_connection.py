"""``ops/hyper_connection.py``'s four Pallas kernels in interpret mode on the
CPU against the plain form: ``x_in``, the maps, the largest row gap and the
merged stream, and the gradients of the stream, the sublayer's output, ``phi``,
the biases and the scales through ``mhc_pre`` and ``mhc_merge`` chained as a
sublayer chains them, in float32 and on a bf16 stream, at two and four streams
of 256 channels over several token tiles, and with Sinkhorn's inputs at the
clamp; which path a call takes where, and what it journals; and that no
configuration without hyper-connections reaches the module. That the kernels
compile for the chip and land in the phase ``mhc`` is
``tests/test_tpu_compile_xing.py``'s."""

import dataclasses
import functools
import json
import logging
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from dmlcloud_tpu.models.transformer import sinkhorn_knopp
from dmlcloud_tpu.ops import hyper_connection as hc

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: name -> (streams, channels, batch, positions, token tile): 128 tokens in four tiles of 32
CASES = {"four-streams": (4, 256, 1, 128, 32), "two-streams": (2, 256, 2, 64, 32)}
NAMES = "stream out phi bias alpha".split()


def inputs(case, dtype, at_clamp=False, seed=0):
    n, c, b, t, _ = CASES[case]
    k, f = n * c, 2 * n + n * n
    rng = np.random.default_rng(seed)
    normal = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    bias = 0.5 * normal(f)
    if at_clamp:  # half of each token's residual logits past the clamp, either way; the others inside it
        bias = bias.at[2 * n :].set(jnp.asarray([45.0, -45.0, 0.0, 0.0] * (n * n // 4) + [45.0, -45.0][: n * n % 4]))
    return (normal(b, t, k).astype(dtype), normal(b, t, c).astype(dtype), normal(k, f) * k**-0.5, bias,
            jnp.asarray([0.8, 0.6, 1.5]))


def sublayer(case, kernels: bool, stream, out, phi, bias, alpha):
    """One sublayer's maps round a stand-in for its operator (``x_in`` scaled a channel, plus ``out``), as
    ``DecoderBlock`` chains them: ``(merged stream, x_in, post, res, gap)``."""
    n, c, _, _, tile = CASES[case]
    spec = hc._Spec(n, 20, 1e-6, 1e-6, -30.0, 30.0, tile, True)
    if kernels:
        x_in, post, res, gap = hc._pre_kernels(stream, phi, bias, alpha, spec)
    else:
        x_in, post, res, gap = hc._pre_plain(stream, phi, bias, alpha, spec, sinkhorn_knopp)
    scale = jnp.cos(jnp.arange(c) * 0.1)
    branch = (x_in.astype(jnp.float32) * scale + out.astype(jnp.float32)).astype(stream.dtype)
    merged = hc._merge_kernels(stream, branch, post, res, tile, True) if kernels else hc._merge_plain(stream, branch, post, res)
    return merged, x_in, post, res, gap


@functools.lru_cache(maxsize=None)
def evaluated(case, dtype_name, kernels, at_clamp=False):
    """``(forward outputs, the five gradients of a weighted sum of the merged stream)`` of one path, as numpy."""
    dtype = jnp.dtype(dtype_name)
    args = inputs(case, dtype, at_clamp)
    fn = functools.partial(sublayer, case, kernels)
    weights = jnp.asarray(np.random.default_rng(1).normal(size=args[0].shape), jnp.float32)
    loss = lambda *a: jnp.sum(fn(*a)[0].astype(jnp.float32) * weights)
    with jax.default_matmul_precision("highest"):
        outs = jax.jit(fn)(*args)
        grads = jax.jit(jax.grad(loss, argnums=tuple(range(5))))(*args)
    as_np = lambda tree: [np.asarray(jnp.asarray(v, jnp.float32)) for v in tree]
    return as_np(outs), as_np(grads)


def gap(a, b):
    """The distance between two arrays over the first one's norm."""
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(a), 1e-30))


#: (float32, bf16 stream): the maps are float32 on both paths; on a bf16 stream x_in, the merged stream and the
#: gradients differ by the bf16 roundings the plain form's casts take where the kernels sum in float32
LIMITS = {"maps": (1e-5, 1e-5), "stream": (1e-5, 1e-2), "grads": (1e-5, 2e-2)}


@pytest.mark.parametrize("dtype_name", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", CASES)
def test_the_forward_is_the_plain_form(case, dtype_name):
    (merged, x_in, post, res, row_gap), _ = evaluated(case, dtype_name, True)
    (merged_p, x_in_p, post_p, res_p, row_gap_p), _ = evaluated(case, dtype_name, False)
    which = dtype_name == "bfloat16"
    assert gap(x_in_p, x_in) < LIMITS["stream"][which] and gap(merged_p, merged) < LIMITS["stream"][which]
    assert gap(post_p, post) < LIMITS["maps"][which] and gap(res_p, res) < LIMITS["maps"][which]
    assert abs(float(row_gap) - float(row_gap_p)) < 1e-6
    assert np.allclose(res.sum(axis=-2), 1.0, atol=1e-5)  # the rounds end on the columns


@pytest.mark.parametrize("dtype_name", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", CASES)
def test_every_gradient_is_the_plain_forms(case, dtype_name):
    _, grads = evaluated(case, dtype_name, True)
    _, grads_p = evaluated(case, dtype_name, False)
    limit = LIMITS["grads"][dtype_name == "bfloat16"]
    for name, g, g_p in zip(NAMES, grads, grads_p):
        assert np.all(np.isfinite(g)) and np.linalg.norm(g_p) > 0, name
        assert gap(g_p, g) < limit, (name, gap(g_p, g))


def test_a_tile_whose_sinkhorn_inputs_sit_at_the_clamp():
    """Half of every token's residual logits lie past +-30: the clamp holds them there and passes no gradient
    to them, the same on both paths."""
    case, n = "four-streams", CASES["four-streams"][0]
    stream, _, phi, bias, alpha = inputs(case, jnp.float32, at_clamp=True)
    mix = jnp.einsum("btk,kf->btf", stream, phi) * jax.lax.rsqrt(jnp.mean(stream**2, axis=-1, keepdims=True) + 1e-6)
    logits = alpha[2] * mix[..., 2 * n :] + bias[2 * n :]
    assert float(jnp.mean(logits > 30.0)) > 0.2 and float(jnp.mean(logits < -30.0)) > 0.2
    (merged, _, post, res, _), grads = evaluated(case, "float32", True, True)
    (merged_p, _, post_p, res_p, _), grads_p = evaluated(case, "float32", False, True)
    assert gap(res_p, res) < 1e-5 and gap(merged_p, merged) < 1e-5
    for name, g, g_p in zip(NAMES, grads, grads_p):
        assert gap(g_p, g) < 1e-4, (name, gap(g_p, g))
    clamped = [j for j in range(n * n) if j % 4 in (0, 1)]
    assert np.all(grads[3][2 * n :][clamped] == 0.0) and np.all(grads_p[3][2 * n :][clamped] == 0.0)


def test_the_rounds_backward_is_jax_s():
    """Sinkhorn's backward as the kernel takes it, on ``n`` blocks with the tokens on lanes, against ``jax.vjp`` of
    :func:`sinkhorn_knopp` on the same matrices."""
    n, tokens = 3, 16
    spec = hc._Spec(n, iters=7, eps=1e-6)
    rng = np.random.default_rng(0)
    e = jnp.asarray(np.exp(rng.normal(size=(n * n, tokens))), jnp.float32)
    d = jnp.asarray(rng.normal(size=(n * n, tokens)), jnp.float32)
    blocks, kept = hc._rounds(e, spec, keep=True)
    mats = e.T.reshape(tokens, n, n)
    want, vjp = jax.vjp(lambda m: sinkhorn_knopp(m, spec.iters, spec.eps), mats)
    assert np.allclose(jnp.concatenate(blocks).T.reshape(tokens, n, n), want, rtol=1e-6, atol=1e-7)
    got = jnp.concatenate(hc._rounds_bwd([d[i * n : (i + 1) * n] for i in range(n)], kept, spec)).T.reshape(tokens, n, n)
    assert np.allclose(got, vjp(d.T.reshape(tokens, n, n))[0], rtol=1e-5, atol=1e-6)


# ------------------------------------------------------------------ which path, where


@pytest.fixture
def one_tpu(monkeypatch):
    """What a process on a one-chip TPU machine observes."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    hc._say_once.cache_clear()
    yield
    hc._say_once.cache_clear()


@pytest.mark.parametrize("shape, devices, mesh_size, want", [
    ((1, 8192, 4 * 3584), 1, None, hc._Tiles(64, 32)),  # xing-train-8k: a tile's blocks under the VMEM budget
    ((2, 64, 4 * 256), 1, None, hc._Tiles(128, 128)),
    ((1, 8192, 4 * 3584), 4, 1, hc._Tiles(64, 32)),  # a one-device mesh named on a host of four
    ((1, 8192, 4 * 3584), 4, None, "traced for 4 devices"),
    ((1, 8192, 4 * 3584), 1, 2, "traced for 2 devices"),
    ((1, 8192, 4 * 96), 1, None, "C 96 is not whole lane tiles"),
    ((1, 200, 4 * 256), 1, None, "200 tokens make no token tile"),
])
def test_on_the_tpu_what_a_call_observes_chooses_the_path(one_tpu, monkeypatch, caplog, shape, devices, mesh_size, want):
    monkeypatch.setattr(jax, "device_count", lambda: devices)
    mesh = None if mesh_size is None else Mesh(np.array(jax.devices()[:mesh_size]), ("data",))
    with caplog.at_level(logging.WARNING, logger=hc.__name__):
        tiles, why = hc._plan(shape, jnp.bfloat16, 4, mesh)
        hc._plan(shape, jnp.bfloat16, 4, mesh)
    if isinstance(want, hc._Tiles):
        assert (tiles, why) == (want, None) and not caplog.records
    else:
        assert tiles is None and why.startswith(want)
        assert [r.getMessage() for r in caplog.records] == [f"mhc: stream {list(shape)}: {why}: the plain form runs"]


def test_off_the_tpu_and_under_128_tokens_the_plain_form_runs_and_says_nothing(monkeypatch, caplog):
    with caplog.at_level(logging.WARNING, logger=hc.__name__):
        assert hc._plan((1, 8192, 4 * 3584), jnp.bfloat16, 4, None) == (None, f"backend {jax.default_backend()}")
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert hc._plan((1, 8, 4 * 3584), jnp.bfloat16, 4, None) == (None, "8 tokens")
    assert not caplog.records


def test_a_call_journals_its_path_and_recording_collects_it(tmp_path):
    from dmlcloud_tpu.telemetry import journal as journal_mod

    stream, out, phi, bias, alpha = inputs("two-streams", jnp.float32)
    j = journal_mod.activate(journal_mod.SpanJournal(tmp_path))
    try:
        with hc.recording() as seen:
            x_in, post, res, _ = hc.mhc_pre(stream, phi, bias, alpha, 2, iters=20, eps=1e-6, norm_eps=1e-6, sinkhorn=sinkhorn_knopp)
            hc.mhc_merge(stream, out, post, res)
    finally:
        journal_mod.deactivate()
    why = f"backend {jax.default_backend()}"
    records = [r for r in j.tail(64) if r["kind"] == "mhc"]
    assert [(r["path"], r["shape"], r["why"], r["dur"]) for r in records] == [("plain", [2, 64, 512], why, 0.0)]
    assert seen == [("plain", (2, 64, 512), why)]
    assert hc.summary(seen * 12) == f"plain [2, 64, 512] ({why}) x 12"
    assert hc.summary([("kernels", (1, 8192, 14336), None)] * 12) == "kernels [1, 8192, 14336] x 12"
    j.close()


# ------------------------------------------------------------------ nothing else reaches it


def _tiny(which):
    """The program's configuration of one of the four cells without hyper-connections, built as the benchmark
    builds it, at a tiny size of its pattern."""
    import importlib

    from dmlcloud_tpu.models.hf import transformer_config_from_hf

    name = {"m7b": "mistral-7b-v0.1", "lfm2": "lfm2-24b-a2b", "laguna": "laguna-s-2.1", "granite": "granite-4.0-h-micro"}[which]
    with open(os.path.join(ROOT, "benchmark", "configs", f"{name}.json")) as f:
        config = json.load(f)
    if which == "m7b":  # as benchmark/drivers/train.py builds it
        published = {k: v for k, v in config.items() if not isinstance(v, (dict, list, str))}
        cfg = transformer_config_from_hf(types.SimpleNamespace(**{**published, "num_hidden_layers": 2}), num_layers=2,
                                         max_seq_len=8192, attn_impl="flash", dtype=jnp.bfloat16)
    else:
        cfg = importlib.import_module(f"benchmark.drivers.train_{which}").model_config(config, {"seq_len": 8192})
    assert (cfg.hc_mult, cfg.num_nextn_predict_layers) == (1, 0)
    return dataclasses.replace(cfg, hidden_dim=32, head_dim=8, mlp_dim=32, moe_intermediate_size=16, vocab_size=64, max_seq_len=32,
                               mamba_n_heads=min(cfg.mamba_n_heads, 4), mamba_d_head=8, mamba_d_state=8, mamba_chunk_size=16,
                               shared_expert_intermediate_size=min(cfg.shared_expert_intermediate_size, 16), attn_impl="dot",
                               sliding_window=cfg.sliding_window and 8, dtype=jnp.float32)


@pytest.mark.parametrize("which", ["m7b", "lfm2", "laguna", "granite"])
def test_without_hyper_connections_a_train_step_never_reaches_the_module(which, monkeypatch):
    """With every entry point of ``ops/hyper_connection.py`` made to raise, the train steps of tiny m7b-, lfm2-,
    laguna- and granite-like models (``hc_mult`` 1) trace and run."""
    from dmlcloud_tpu.models import transformer
    from dmlcloud_tpu.models.transformer import DecoderLM, lm_loss

    def refuse(*args, **kwargs):
        raise AssertionError("an hc_mult = 1 model reached ops/hyper_connection.py")

    for module in (hc, transformer):
        for name in ("mhc_pre", "mhc_merge", "sinkhorn_knopp"):
            if hasattr(module, name):
                monkeypatch.setattr(module, name, refuse)
    for name in ("_plan", "_record", "_pre_plain", "_merge_plain", "_pre_kernels", "_merge_kernels"):
        monkeypatch.setattr(hc, name, refuse)
    cfg = _tiny(which)
    model = DecoderLM(cfg)
    tokens = jnp.asarray(np.random.default_rng(0).integers(0, cfg.vocab_size, (1, 32)), jnp.int32)
    variables = model.init(jax.random.PRNGKey(0), tokens)
    rest = {k: v for k, v in variables.items() if k != "params"}

    def loss(p):
        logits, _ = model.apply({"params": p, **rest}, tokens, mutable=["moe_stats", "ssm_stats"])
        return lm_loss(logits, tokens)

    value, grads = jax.jit(jax.value_and_grad(loss)).lower(variables["params"]).compile()(variables["params"])
    assert np.isfinite(float(value)) and all(np.all(np.isfinite(g)) for g in jax.tree_util.tree_leaves(grads))
