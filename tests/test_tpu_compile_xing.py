"""``xing-train-8k``'s whole step compiled for a TPU v5e that is described, not
attached (``jax.experimental.topologies``), in a file of its own: at about
150 s it is the longest compile of the chip's stand-ins. A compile that passes
says nothing about results or times, and is not a chip run."""

import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or the TPU compiler logs under /tmp

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, SingleDeviceSharding


@pytest.fixture(scope="session")
def v5e():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler on this installation
        pytest.skip(f"cannot describe a v5e topology here: {type(e).__name__}: {e}")


def _as_on_a_tpu(monkeypatch, devices: int = 1):
    """What a process on a TPU machine observes: the flash path asks the backend which kernels to lower."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "device_count", lambda: devices)


def _xing():
    """``xing-train-8k``'s configuration file, its job and the program's configuration of the model."""
    import json

    from benchmark.drivers import train_xing

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs", "xing4.0-29b-a4b.json")) as f:
        config = json.load(f)
    with open(os.path.join(root, "benchmark", "traffic", "train-xing-8k.json")) as f:
        job = json.load(f)
    return config, job, train_xing.model_config(config, job)


#: the xing cell's parameters at the 8-chip share (``counts_xing.param_count``)
XING_HELD = 654_737_220


def test_the_xing_cells_step_fits_one_chip_with_remat(v5e, monkeypatch):
    """``xing-train-8k``'s step at its published widths (latent attention at 4 of 32 heads, 4 streams of 3584, the
    dense layer's 1152 of 9216 columns, 8 of 64 experts, the MTP module, 1 x 8192 tokens, fp32 weights + AdamW,
    ``remat``), as the stage builds it: both losses and the gradient, the clip, the update, state donated. Its
    ``memory_analysis()`` is the number the configuration's head and width share rests on: arguments + temporaries
    + the registry's float32 copy of the parameters under the chip's 15.75 GiB. The flash kernels run at qk 192 /
    v 128, and the recomputed forward lands in the new mechanisms' own phases. The twelve sublayers' hyper-connection
    maps are ``ops/hyper_connection.py``'s four kernels, each in the phase ``mhc`` in every direction it runs in, and
    the step's temporaries are no more than those of the maps in plain ``jax.numpy``."""
    import dataclasses

    import optax

    from benchmark import counts_xing, reference_xing
    from dmlcloud_tpu.models.moe import moe_counters
    from dmlcloud_tpu.models.transformer import DecoderLM, mhc_counters, mtp_lm_loss
    from dmlcloud_tpu.utils.profiling import phase_map

    _as_on_a_tpu(monkeypatch, devices=4)
    config, job, cfg = _xing()
    cfg = dataclasses.replace(cfg, mesh=Mesh(np.array(v5e.devices[:1]), ("data",)))
    assert cfg.remat and cfg.attn_impl == "flash" and (cfg.hc_mult, cfg.num_nextn_predict_layers) == (4, 1)
    model = DecoderLM(cfg)
    one_chip = SingleDeviceSharding(v5e.devices[0])
    on_chip = lambda tree: jax.tree_util.tree_map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), tree)
    variables = on_chip(jax.eval_shape(model.init, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))
    params, buffers = variables["params"], variables["buffers"]
    held = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(params))
    assert held == counts_xing.param_count(dict(reference_xing.spec(config))) == XING_HELD
    o = job["optimizer"]
    tx = optax.adamw(optax.warmup_cosine_decay_schedule(o["init_lr"], o["peak_lr"], o["warmup_steps"], o["decay_steps"]),
                     b1=o["b1"], b2=o["b2"], eps=o["eps"], weight_decay=o["weight_decay"])
    tokens = jax.ShapeDtypeStruct((job["batch"], job["seq_len"]), jnp.int32, sharding=one_chip)

    def step(params, opt_state, buffers, tokens):
        def loss_fn(p):
            (logits, mtp_logits), stats = model.apply({"params": p, "buffers": buffers}, tokens, mutable=["moe_stats", "mhc_stats"])
            loss, mtp = mtp_lm_loss(logits, mtp_logits, tokens, 0.3)
            return loss, {**moe_counters(stats), **mhc_counters(stats), "mtp/loss": mtp}

        (loss, counters), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        norm = jnp.sqrt(sum(jnp.sum(g**2) for g in jax.tree_util.tree_leaves(grads)))
        grads = jax.tree_util.tree_map(lambda g: g * jnp.minimum(1.0, job["gradient_clip"] / jnp.maximum(norm, 1e-6)), grads)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss, counters

    compiled = jax.jit(step, donate_argnums=(0, 1)).lower(params, on_chip(jax.eval_shape(tx.init, params)), buffers, tokens).compile()
    memory = compiled.memory_analysis()
    print(f"xing-train-8k step: arguments {memory.argument_size_in_bytes}, temporaries {memory.temp_size_in_bytes}")
    assert memory.argument_size_in_bytes == pytest.approx(12 * held, rel=0.001)  # weights and two moments
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes + 4 * held < 15.75 * 2**30
    assert memory.temp_size_in_bytes <= 5_922_267_648  # the maps in plain jax.numpy, as the chip read the step before the kernels
    text = compiled.as_text()
    calls = re.findall(r"%(flash_(?:fwd|bwd_dq|bwd_dkv))[.\d]* = ([^\n]*?) custom-call\(", text)
    assert {name for name, _ in calls} == {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"}
    assert any("[4,8192,128]" in shapes for name, shapes in calls if name == "flash_fwd")  # the output is v's 128 wide
    phases = phase_map(text)
    found = set(phases.values())
    assert {("mhc", "recompute"), ("mla_latent", "recompute"), ("mhc", "bwd"), ("mla_latent", "bwd"), ("mtp", "fwd"), ("mtp", "bwd")} <= found
    # five blocks and the MTP block, two sublayers each: every map is a kernel, forward, recomputed and backward
    kernels = {name: where for name, where in phases.items() if re.match(r"mhc_(pre|merge)(_bwd)?\b", name)}
    for kernel, directions in (("mhc_pre", {"fwd", "recompute"}), ("mhc_merge", {"fwd", "recompute"}),
                               ("mhc_pre_bwd", {"bwd"}), ("mhc_merge_bwd", {"bwd"})):
        assert {where for name, where in kernels.items() if re.match(rf"{kernel}(\.\d+)?$", name)} == {("mhc", d) for d in directions}
    calls = set(re.findall(r"%(mhc_(?:pre|merge)(?:_bwd)?(?:\.\d+)?) = [^\n]* custom-call\(", text))
    count = lambda kernel: sum(re.fullmatch(rf"{kernel}(\.\d+)?", name) is not None for name in calls)
    # the recomputed forward of a block needs no merge of its last sublayer: the block's output is the next one's saved input
    assert (count("mhc_pre"), count("mhc_merge"), count("mhc_pre_bwd"), count("mhc_merge_bwd")) == (24, 18, 12, 12)

