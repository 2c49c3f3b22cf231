"""The main path's kernels, compiled for a TPU v5e that is described, not
attached (``jax.experimental.topologies``): what the chip's compiler would
refuse — a misaligned slice, too much VMEM, a kernel XLA cannot partition —
it refuses here, at no chip time. Shapes are chip_smoke.py's: Mistral-7B
heads (32 query / 8 KV of 128) at 8192 tokens, and the 4096 x 14336 MLP dot.

A compile that passes says nothing about results or times, and is not a chip
run. Skipped where the installation cannot describe the topology.
"""

import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or the TPU compiler logs under /tmp

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from dmlcloud_tpu.models import quant
from dmlcloud_tpu.ops.flash_attention import flash_attention, flash_attention_sharded

B, T, H, KH, D = 1, 8192, 32, 8, 128
WINDOW = 4096


@pytest.fixture(scope="session")
def v5e():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler on this installation
        pytest.skip(f"cannot describe a v5e topology here: {type(e).__name__}: {e}")


@pytest.fixture(autouse=True)
def no_persistent_cache():
    # a compile for a described device can be written to the persistent
    # cache but not read back without the chip (it warns and recompiles);
    # the session keeps the cache off (conftest.py) — hold it to that
    assert not jax.config.jax_enable_compilation_cache


def _compile(fn, *specs):
    compiled = jax.jit(fn).lower(*specs).compile()
    return compiled.as_text()


def _qkv(sharding, batch=B):
    sds = lambda heads: jax.ShapeDtypeStruct((batch, T, heads, D), jnp.bfloat16, sharding=sharding)
    return sds(H), sds(KH), sds(KH)


def _pallas(**kwargs):
    # jax.default_backend() is the CPU here: name the lowering instead
    return lambda q, k, v, *seg: flash_attention(
        q, k, v, causal=True, impl="pallas", interpret=False,
        segment_ids=seg[0] if seg else None, **kwargs,
    )


def _sum_grad(attn):
    return jax.grad(lambda q, k, v, *rest: attn(q, k, v, *rest).astype(jnp.float32).sum(), argnums=(0, 1, 2))


# windowed at 8192 is the benchmark cell's call: its kernels take the block
# shapes the sweep chose (flash_attention._SWEPT_BLOCKS); the explicit case
# keeps every other call's 512 x 1024 compiled at the same shapes
FLASH_CASES = {
    "fwd": (_pallas(), False),
    "fwd_bwd": (_sum_grad(_pallas()), False),
    "windowed_fwd_bwd": (_sum_grad(_pallas(window=WINDOW)), False),
    "windowed_fwd_bwd_512x1024": (_sum_grad(_pallas(window=WINDOW, block_q=512, block_k=1024)), False),
    "segment_ids_fwd_bwd": (_sum_grad(_pallas(window=WINDOW)), True),
}


@pytest.mark.parametrize("case", sorted(FLASH_CASES))
def test_flash_kernel_compiles_for_v5e(v5e, case):
    fn, packed = FLASH_CASES[case]
    one_chip = SingleDeviceSharding(v5e.devices[0])
    specs = list(_qkv(one_chip))
    if packed:
        specs.append(jax.ShapeDtypeStruct((B, T), jnp.int32, sharding=one_chip))
    assert "tpu_custom_call" in _compile(fn, *specs)


# laguna-train-8k's calls: one KV head and its query group (9 heads on a window layer, 6 on a full one), a window of
# 512 where no sweep chose blocks, so the kernels run on the default 512 x 1024 and an edge pair is mostly masked
@pytest.mark.parametrize("heads, window", [(9, 512), (6, None)], ids=["window-512-group-9", "full-group-6"])
def test_flash_kernels_compile_for_one_kv_head_and_its_query_group(v5e, heads, window):
    from dmlcloud_tpu.ops.flash_attention import _plan_for

    one_chip = SingleDeviceSharding(v5e.devices[0])
    sds = lambda h: jax.ShapeDtypeStruct((B, T, h, D), jnp.bfloat16, sharding=one_chip)
    hlo = _compile(_sum_grad(_pallas(window=window)), sds(heads), sds(1), sds(1))
    assert hlo.count("tpu_custom_call") >= 3
    plan = _plan_for(None, None, D, T, T, True, window)
    assert (plan.block_q, plan.block_k) == (512, 1024)
    # a window layer's query block holds a pair with two key blocks at most, a key block with three query blocks:
    # 15 of 16 key blocks' worth of the rectangle is never a grid step; a full layer's band is the whole triangle
    assert (plan.kv_width, plan.q_width) == ((2, 3) if window else (8, 16))
    for qi in range(plan.num_qb):
        first, last = plan.kv_band(qi)
        kept = [kb for kb in range(plan.num_kb) if kb * 1024 <= qi * 512 + 511 and (window is None or qi * 512 - (kb * 1024 + 1023) < window)]
        assert list(range(first, last + 1)) == kept


def test_flash_kernel_compiles_on_a_four_chip_mesh(v5e):
    """XLA refuses to partition a Mosaic kernel; ``flash_attention_sharded``
    is what lets ``attn_impl="flash"`` compile on an fsdp x model mesh."""
    mesh = Mesh(np.array(v5e.devices).reshape(2, 2), ("fsdp", "model"))
    laid_out = NamedSharding(mesh, P("fsdp", None, "model", None))
    attn = lambda q, k, v: flash_attention_sharded(
        q, k, v, mesh, causal=True, window=WINDOW, impl="pallas", interpret=False
    )
    hlo = _compile(_sum_grad(attn), *_qkv(laid_out, batch=2))
    assert "tpu_custom_call" in hlo
    # batch and heads arrive laid out as the kernel's shard_map wants them
    assert " all-gather(" not in hlo and " all-to-all(" not in hlo

    unwrapped = lambda q, k, v: _pallas(window=WINDOW)(q, k, v)
    with pytest.raises(NotImplementedError, match="Mosaic kernels cannot be automatically partitioned"):
        _compile(unwrapped, *_qkv(laid_out, batch=2))


def _count_pallas_calls(jaxpr) -> int:
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name == "pallas_call"
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n += _count_pallas_calls(sub)
    return n


@pytest.mark.parametrize("packed", [False, True], ids=["plain", "segment_ids"])
def test_a_differentiated_flash_attention_is_three_pallas_calls(monkeypatch, packed):
    """The lowering guard of the set-up budget: one forward, one dQ and one
    dK/dV kernel a layer — no call per band, none for edge blocks — and
    nothing but the caller's own trace ever builds a kernel: importing the op
    builds none, tracing a layer builds those three (a tuner trying block
    shapes would build more), and a second layer of the same shapes, or a
    second trace of the step, builds none again (the jitted impls keep them).
    Counts, not times; nothing runs."""
    import importlib.util
    import sys

    from jax.experimental import pallas as pl

    from dmlcloud_tpu.ops import flash_attention as fa

    built = []
    real = pl.pallas_call
    monkeypatch.setattr(pl, "pallas_call", lambda *a, **k: built.append(k.get("name")) or real(*a, **k))
    # a second import of the file, under a name of its own: it has traced nothing
    # yet, and the session's module stays as it is
    spec = importlib.util.spec_from_file_location("flash_attention_import_probe", fa.__file__)
    probe = importlib.util.module_from_spec(spec)
    monkeypatch.setitem(sys.modules, spec.name, probe)
    spec.loader.exec_module(probe)
    assert built == []
    specs = [jax.ShapeDtypeStruct((B, T, heads, D), jnp.bfloat16) for heads in (H, KH, KH)]
    if packed:
        specs.append(jax.ShapeDtypeStruct((B, T), jnp.int32))
    attn = lambda q, k, v, *seg: probe.flash_attention(
        q, k, v, causal=True, window=WINDOW, impl="pallas", interpret=False,
        segment_ids=seg[0] if seg else None,
    )
    jaxpr = jax.make_jaxpr(_sum_grad(attn))(*specs)
    assert _count_pallas_calls(jaxpr.jaxpr) == 3
    assert sorted(built) == ["flash_bwd_dkv", "flash_bwd_dq", "flash_fwd"]
    two_layers = lambda q, k, v, *seg: attn(attn(q, k, v, *seg), k, v, *seg)
    jaxpr = jax.make_jaxpr(_sum_grad(two_layers))(*specs)
    assert _count_pallas_calls(jaxpr.jaxpr) == 6
    assert len(built) == 3


def test_int8_training_dot_compiles_for_v5e(v5e, monkeypatch):
    """``quant_train_dot`` at the MLP's 4096 x 14336, forward and backward,
    with the TPU's narrow operands (the module picks them from the backend)."""
    monkeypatch.setattr(quant.jax, "default_backend", lambda: "tpu")
    one_chip = SingleDeviceSharding(v5e.devices[0])
    x = jax.ShapeDtypeStruct((T, 4096), jnp.bfloat16, sharding=one_chip)
    w = jax.ShapeDtypeStruct((4096, 14336), jnp.float32, sharding=one_chip)
    scale = jax.ShapeDtypeStruct((1, 14336), jnp.float32, sharding=one_chip)
    loss = lambda x, w, scale: quant.quant_train_dot(x, w, scale).astype(jnp.float32).sum()
    hlo = _compile(jax.grad(loss, argnums=(0, 1)), x, w, scale)
    assert "s8[" in hlo  # the quantized kernel really is int8 in the program


@pytest.mark.parametrize("n, k, d, f, held, experts, bound, overrides", [
    (8192, 4, 2048, 1536, 8, 64, 8192, dict(use_expert_bias=True)),
    (8192, 10, 3072, 1024, 8, 256, 5120, dict(scoring_func="softmax", routed_scaling_factor=2.5)),
], ids=["lfm2-train-8k", "laguna-train-8k"])
def test_a_bounded_expert_layer_writes_no_array_of_all_pairs_rows_on_its_usual_path(v5e, n, k, d, f, held, experts, bound, overrides):
    """One expert layer of ``lfm2-train-8k`` (8 of 64 experts held, 8192 tokens, top-4 by sigmoid) and of
    ``laguna-train-8k`` (8 of 256, top-10 by softmax), forward and backward: the grouped products are the
    TPU's own kernel, each direction is one conditional, and the branch taken when the live rows fit the row
    bound holds no ``[N * k, features]`` array and sorts nothing; outside the conditionals no sort is as long
    as the ``N * k`` pairs either (the router's top-k sorts rows of ``experts``)."""
    import re

    from dmlcloud_tpu.models.moe import MoEConfig, MoEMLP, row_bound
    from dmlcloud_tpu.utils.profiling import phase_map

    assert row_bound(n * k, held, experts) == bound
    model = MoEMLP(MoEConfig(num_experts=experts, top_k=k, hidden_dim=d, mlp_dim=f, experts_held=(0, held), **overrides))
    one_chip = SingleDeviceSharding(v5e.devices[0])
    x = jax.ShapeDtypeStruct((1, n, d), jnp.bfloat16, sharding=one_chip)
    variables = jax.eval_shape(model.init, jax.random.PRNGKey(0), x)
    variables = jax.tree_util.tree_map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), variables)
    loss = lambda v, x: model.apply(v, x).astype(jnp.float32).sum()
    text = _compile(jax.value_and_grad(loss, argnums=(0, 1)), {name: variables[name] for name in variables if name in ("params", "buffers")}, x)
    # XLA's grouped kernel carries the names round it and none of the layer's own: its phase goes by its
    # instruction's name (``_KERNEL_PHASES``), which a scope of the layer's round the conditional would override
    kernels = {name: phase for name, (phase, _) in phase_map(text).items() if name.startswith("ragged-dot")}
    assert kernels and set(kernels.values()) == {"moe_experts"}, kernels
    computations = {}
    for block in re.split(r"\n(?=(?:ENTRY )?%?[\w.\-]+ \([^\n]*\) -> [^\n]*\{\n)", text):
        computations[re.match(r"(?:ENTRY )?%?([\w.\-]+)", block).group(1)] = block
    conditionals = re.findall(r"conditional\([^\n]*?(?:branch_computations=\{%?([\w.\-]+), %?([\w.\-]+)\}|"
                              r"true_computation=%?([\w.\-]+), false_computation=%?([\w.\-]+))", text)
    assert len(conditionals) == 2, conditionals  # forward and backward

    def reach(name, seen):
        if name in seen or name not in computations:
            return seen
        seen.add(name)
        for called in re.findall(r"(?:calls|to_apply|body|condition)=%?([\w.\-]+)", computations[name]):
            reach(called, seen)
        return seen

    wide = re.compile(rf"= \(?\w+\[{n * k},(?:{d}|{f})\]")
    long_sort = re.compile(rf"= \(?\w+\[{n * k}\][^\n]* sort\(")
    inside = set()
    for first, second, true, false in conditionals:
        full, usual = (first, second) if first else (false, true)  # branch 0 is the false one: the full path
        assert not [c for c in reach(usual, set()) if wide.search(computations[c]) or " sort(" in computations[c]]
        assert [c for c in reach(full, set()) if wide.search(computations[c])]  # the patterns do see such arrays
        inside |= reach(full, set())
    assert [c for c in inside if long_sort.search(computations[c])]  # the rare path sorts all the pairs for itself
    assert not [c for c in set(computations) - inside if long_sort.search(computations[c])]


@pytest.mark.parametrize("layout", ["one_chip", "vocab_over_model"])
def test_the_loss_reads_the_logits_where_they_lie(v5e, layout):
    """The head and ``lm_loss`` of ``m7b-train-8k`` (``[1, 8192, 4096]`` bf16 into a float32 ``[4096, 32000]``
    kernel), gradient to both and the kernel's squared norm as the clip takes it: the compiled program holds
    no shifted copy of the logits (nothing ``T - 1`` long but the targets' own integers), no scatter for the
    target logit's gradient, and on one chip under 2 GB of temporaries (3.15 GB with the shifted copy). The
    logits' gradient is written once, in bf16, for the two backward products to read (the TPU's branch of
    ``platform_dependent``, taken for a described chip too)."""
    import re

    from dmlcloud_tpu.models.transformer import lm_loss

    hidden, vocab = 4096, 32000
    if layout == "one_chip":
        rows = cols = SingleDeviceSharding(v5e.devices[0])
    else:
        mesh = Mesh(np.array(v5e.devices).reshape(2, 2), ("fsdp", "model"))
        rows, cols = NamedSharding(mesh, P()), NamedSharding(mesh, P(None, "model"))
    x = jax.ShapeDtypeStruct((B, T, hidden), jnp.bfloat16, sharding=rows)
    kernel = jax.ShapeDtypeStruct((hidden, vocab), jnp.float32, sharding=cols)
    tokens = jax.ShapeDtypeStruct((B, T), jnp.int32, sharding=rows)

    def head_and_loss(x, kernel, tokens):
        loss = lambda x, kernel: lm_loss(jnp.einsum("btd,dv->btv", x.astype(jnp.float32), kernel), tokens)
        value, (dx, dkernel) = jax.value_and_grad(loss, argnums=(0, 1))(x, kernel)
        return value, dx, dkernel, jnp.sum(dkernel**2)

    compiled = jax.jit(head_and_loss).lower(x, kernel, tokens).compile()
    text = compiled.as_text()
    shifted = {shape for shape in re.findall(r"\w+\[[\d,]+\]", text) if str(T - 1) in re.findall(r"\d+", shape)}
    assert shifted <= {f"s32[{B},{T - 1}]"}, shifted
    assert "scatter" not in text
    if layout == "one_chip":
        assert compiled.memory_analysis().temp_size_in_bytes < 2e9
        assert f"bf16[{B},{T},{vocab}]" in text
    else:  # the target's logit is a partial sum a shard and an all-reduce of [B, T], no gather across shards
        assert " all-gather(" not in text and " all-to-all(" not in text


def _as_on_a_tpu(monkeypatch, devices: int = 1):
    """What a process on a TPU machine observes: the flash path and the scan ask the backend which kernels to lower,
    and the scan asks how many devices its trace is for (the test session's CPU shows eight)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "device_count", lambda: devices)


def _granite():
    """``granite-train-8k``'s configuration file, its job and the program's configuration of the model."""
    import json

    from benchmark.drivers import train_granite

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs", "granite-4.0-h-micro.json")) as f:
        config = json.load(f)
    with open(os.path.join(root, "benchmark", "traffic", "train-granite-8k.json")) as f:
        job = json.load(f)
    return config, job, train_granite.model_config(config, job)


def _scan_args(b, t, h, g, sharding):
    """``ssd_chunked``'s six inputs as the mixer hands them over: heads of 64, a state of 128, bf16 beside float32."""
    sds = lambda shape, dtype, *spec: jax.ShapeDtypeStruct(shape, dtype, sharding=sharding(*spec))
    groups = "model" if g > 1 else None
    return (sds((b, t, h, 64), jnp.bfloat16, "fsdp", None, "model", None), sds((b, t, h), jnp.float32, "fsdp", None, "model"),
            sds((h,), jnp.float32, "model"), sds((b, t, g, 128), jnp.bfloat16, "fsdp", None, groups, None),
            sds((b, t, g, 128), jnp.bfloat16, "fsdp", None, groups, None), sds((h,), jnp.float32, "model"))


@pytest.mark.parametrize("b, t, h, g, chunk, heads, on", [
    (1, 8192, 32, 1, 256, 8, "one"),  # granite-train-8k: the largest a grid step holds in VMEM
    (2, 1024, 16, 2, 128, 8, "one"), (1, 1024, 4, 2, 256, 2, "one"), (1, 1024, 12, 1, 128, 6, "one"),
    (2, 8192, 32, 1, 256, 8, "mesh"), (2, 1024, 16, 4, 128, 4, "mesh"),
], ids=lambda v: str(v))
def test_the_scans_kernels_compile_at_every_class_of_shapes_the_dispatch_lets_through(v5e, monkeypatch, b, t, h, g, chunk, heads, on):
    """``ssd_fwd`` and ``ssd_bwd`` through Mosaic for a described v5e, which sees what the interpreter does not (a
    layout it cannot broadcast from, the scoped VMEM limit): both chunks ``_heads_per_step`` admits, one group
    and two, two to eight heads a grid step; on one chip, and on the 2 x 2 mesh through ``ssd_chunked_sharded``
    (heads over ``model``, rows over ``fsdp``; what shards share has its gradient summed over them, nothing is gathered)."""
    from dmlcloud_tpu.ops import ssd

    shards = 2 if on == "mesh" else 1
    assert ssd._heads_per_step(h // shards, max(g // shards, 1), 64, 128, chunk) == heads
    if on == "one":
        _as_on_a_tpu(monkeypatch)
        args = _scan_args(b, t, h, g, lambda *spec: SingleDeviceSharding(v5e.devices[0]))
        scan = lambda *a: ssd.ssd_chunked(*a, chunk)
    else:
        _as_on_a_tpu(monkeypatch, devices=4)
        mesh = Mesh(np.array(v5e.devices).reshape(2, 2), ("fsdp", "model"))
        args = _scan_args(b, t, h, g, lambda *spec: NamedSharding(mesh, P(*spec)))
        scan = lambda *a: ssd.ssd_chunked_sharded(*a, chunk, mesh)
    text = _compile(jax.grad(lambda *a: jnp.sum(scan(*a).astype(jnp.float32) ** 2), argnums=tuple(range(6))), *args)
    assert "ssd_fwd" in text and "ssd_bwd" in text
    assert " all-gather(" not in text and " all-to-all(" not in text
    assert (" all-reduce(" in text) == (on == "mesh")  # what the shards share: ``A`` and ``D`` over the rows' holders, one group's ``B`` / ``C``


@pytest.mark.parametrize("mesh_named", [True, False], ids=["cfg.mesh", "no-mesh-named"])
def test_a_mamba_layer_compiles_on_a_four_chip_mesh_under_plain_jit(v5e, monkeypatch, mesh_named):
    """A ``mamba`` block of ``granite-train-8k``'s widths, loss and gradient, on the described fsdp x model mesh with
    the repo's partition rules and plain jit. With ``TransformerConfig.mesh`` named the scan shard_maps itself and
    the step holds the kernels; with none named it holds the plain form, which XLA partitions as it did before
    there were kernels (and says so in the log); the kernels as they are it would refuse, as it refuses flash."""
    import dataclasses

    from dmlcloud_tpu.models.transformer import DecoderBlock, llama_partition_rules
    from dmlcloud_tpu.ops import ssd
    from dmlcloud_tpu.parallel.mesh import sharding_for

    _as_on_a_tpu(monkeypatch, devices=4)
    _, job, cfg = _granite()
    mesh = Mesh(np.array(v5e.devices).reshape(2, 2), ("fsdp", "model"))
    block = DecoderBlock(dataclasses.replace(cfg, remat=False, mesh=mesh if mesh_named else None), kind="mamba")
    example = jnp.zeros((1, 8, cfg.hidden_dim), jnp.bfloat16)
    shapes = jax.eval_shape(block.init, jax.random.PRNGKey(0), example, None, None)["params"]
    params = jax.tree_util.tree_map(lambda a, sharding: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
                                    shapes, sharding_for(shapes, mesh, llama_partition_rules()))
    assert params["mamba"]["in_proj"]["kernel"].sharding.spec == P("fsdp", "model")
    u = jax.ShapeDtypeStruct((2, job["seq_len"], cfg.hidden_dim), jnp.bfloat16, sharding=NamedSharding(mesh, P("fsdp")))

    def loss(p, u):
        out, _ = block.apply({"params": p}, u, None, None, mutable=["ssm_stats"])
        return jnp.sum(out[0].astype(jnp.float32) ** 2)

    step = jax.grad(loss, argnums=(0, 1))
    text = _compile(step, params, u)
    assert ("ssd_fwd" in text, "ssd_bwd" in text) == (mesh_named, mesh_named)
    if not mesh_named:
        monkeypatch.setattr(ssd, "_on_one_device", lambda: True)  # what the dispatch would do if it did not look
        with pytest.raises(NotImplementedError, match="Mosaic kernels cannot be automatically partitioned"):
            _compile(lambda p, u: step(p, u), params, u)  # a function jit has not traced yet


def test_the_granite_cells_step_fits_one_chip_at_the_two_chip_head_share_with_remat(v5e, monkeypatch):
    """``granite-train-8k``'s step at its published widths (10 layers, 32 of 64 Mamba-2 heads, 16 / 4 attention
    heads of 64, MLP 8192, 1 x 8192 tokens, fp32 weights + AdamW, ``remat``), as the stage builds it: loss and
    gradient, the clip, the update, state donated. Its ``memory_analysis()`` is the number the configuration's
    decision rule reads: arguments + temporaries + the registry's float32 copy of the parameters under the chip's
    15.75 GiB, else the cell takes the four-chip share. The flash kernels are in it, and the block's recomputed
    forward lands in the mixer's own phases."""
    import dataclasses

    import optax

    from benchmark import counts_granite, reference_granite
    from dmlcloud_tpu.models.transformer import DecoderLM, lm_loss, ssm_counters
    from dmlcloud_tpu.utils.profiling import phase_map

    _as_on_a_tpu(monkeypatch, devices=4)  # a host of four chips, of which the cell takes one and says so: ``cfg.mesh``, as its driver
    config, job, cfg = _granite()
    cfg = dataclasses.replace(cfg, mesh=Mesh(np.array(v5e.devices[:1]), ("data",)))
    assert cfg.remat and cfg.attn_impl == "flash" and (cfg.mamba_n_heads, cfg.num_heads, cfg.kv_heads) == (32, 16, 4)
    model = DecoderLM(cfg)
    one_chip = SingleDeviceSharding(v5e.devices[0])
    on_chip = lambda tree: jax.tree_util.tree_map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), tree)
    params = on_chip(jax.eval_shape(model.init, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    held = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(params))
    assert held == counts_granite.param_count(dict(reference_granite.spec(config))) == 652_970_080
    o = job["optimizer"]
    tx = optax.adamw(optax.warmup_cosine_decay_schedule(o["init_lr"], o["peak_lr"], o["warmup_steps"], o["decay_steps"]),
                     b1=o["b1"], b2=o["b2"], eps=o["eps"], weight_decay=o["weight_decay"])
    tokens = jax.ShapeDtypeStruct((job["batch"], job["seq_len"]), jnp.int32, sharding=one_chip)

    def step(params, opt_state, tokens):
        def loss_fn(p):
            logits, stats = model.apply({"params": p}, tokens, mutable=["ssm_stats"])
            return lm_loss(logits, tokens), ssm_counters(stats)

        (loss, counters), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        norm = jnp.sqrt(sum(jnp.sum(g**2) for g in jax.tree_util.tree_leaves(grads)))
        grads = jax.tree_util.tree_map(lambda g: g * jnp.minimum(1.0, job["gradient_clip"] / jnp.maximum(norm, 1e-6)), grads)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss, counters

    compiled = jax.jit(step, donate_argnums=(0, 1)).lower(params, on_chip(jax.eval_shape(tx.init, params)), tokens).compile()
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes == pytest.approx(12 * held, rel=0.001)  # weights and two moments
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes + 4 * held < 15.75 * 2**30
    assert memory.temp_size_in_bytes < 3.5e9  # 2.53 GB when this was written; without remat the step's activations alone pass that
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 3
    phases = phase_map(text)
    found = set(phases.values())
    assert {(p, "recompute") for p in ("ssm_proj", "ssm_conv", "ssm_scan", "ssm_gate_norm")} <= found
    # the scan is ops/ssd.py's two kernels, in the phase ``ssm_scan`` in all three directions (so that
    # ``train_ssm_scan_share`` and ``ssm_scan_roofline.granite`` go on reading the whole scan), and no
    # [.., chunk, chunk] decay matrix of the plain form is left among the step's arrays in HBM
    kernels = {name: where for name, where in phases.items() if name.startswith(("ssd_fwd", "ssd_bwd"))}
    assert {where for name, where in kernels.items() if name.startswith("ssd_fwd")} == {("ssm_scan", "fwd"), ("ssm_scan", "recompute")}
    assert {where for name, where in kernels.items() if name.startswith("ssd_bwd")} == {("ssm_scan", "bwd")}
    calls = re.findall(r"%(ssd_(?:fwd|bwd))[.\d]* = [^\n]* custom-call\(", text)  # a kernel XLA fused a cut of its input into is listed twice
    assert (calls.count("ssd_fwd"), calls.count("ssd_bwd")) == (2 * cfg.layer_types.count("mamba"), cfg.layer_types.count("mamba"))
    chunk = cfg.mamba_chunk_size
    assert not re.findall(rf"(?:f32|bf16)\[[\d,]*{chunk},{chunk}\]", text)
