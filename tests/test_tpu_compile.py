"""The main path's kernels, compiled for a TPU v5e that is described, not
attached (``jax.experimental.topologies``): what the chip's compiler would
refuse — a misaligned slice, too much VMEM, a kernel XLA cannot partition —
it refuses here, at no chip time. Shapes are chip_smoke.py's: Mistral-7B
heads (32 query / 8 KV of 128) at 8192 tokens, and the 4096 x 14336 MLP dot.

A compile that passes says nothing about results or times, and is not a chip
run. Skipped where the installation cannot describe the topology.
"""

import os

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


FLASH_CASES = {
    "fwd": (_pallas(), False),
    "fwd_bwd": (_sum_grad(_pallas()), False),
    "windowed_fwd_bwd": (_sum_grad(_pallas(window=WINDOW)), False),
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
