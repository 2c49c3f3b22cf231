"""``ops/ssd.py``'s Pallas kernels in interpret mode on the CPU, at the
smallest shapes that tile the chip's lanes (chunks of 128 and 256, heads of 64,
a state of 128): ``y``, the states the chunks were handed and the gradients of
all six inputs against the plain form and against the token-by-token
recurrence of ``benchmark/reference_granite.py``, in float32 and on bf16
inputs; the precision of what the kernels compute; the scan shard_mapped over a
mesh; and which path ``ssd_chunked`` takes where. That the kernels compile for
the chip (one, and the four of a mesh) at every class of shapes the dispatch
lets through, fit its VMEM and leave no ``L x L`` array in HBM is
``tests/test_tpu_compile.py``'s."""

import functools
import logging
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference_granite as ref
from dmlcloud_tpu.ops import ssd

N = 128
#: name -> (batch, positions, heads, head size, groups, chunk, dt's scale)
CASES = {
    "one-chunk": (1, 128, 2, 64, 1, 128, 0.1),
    "chunks": (2, 384, 2, 64, 1, 128, 0.1),
    "two-groups": (1, 256, 4, 64, 2, 128, 0.1),
    "two-row-blocks": (1, 512, 2, 64, 1, 256, 0.1),  # the published chunk: the tile is built in two row blocks, one quarter never
    "strong-decay": (1, 256, 4, 64, 1, 128, 1.0),  # the running sum falls under -100 inside a chunk
    "four-lane-groups": (1, 128, 8, 64, 1, 128, 0.1),  # eight heads a grid step, as the cell walks them
}
NAMES = "x dt A B C D".split()


def inputs(case, dtype=jnp.float32, seed=0):
    b, t, h, p, g, chunk, scale = CASES[case]
    rng = np.random.default_rng(seed)
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    dt = jax.nn.softplus(f(b, t, h)) * scale
    a = -jnp.exp(jnp.asarray(rng.uniform(0.0, 2.7, size=(h,)), jnp.float32))
    return (f(b, t, h, p).astype(dtype), dt, a, f(b, t, g, N).astype(dtype), f(b, t, g, N).astype(dtype), f(h)), chunk


def kernels(x, dt, a_head, b_in, c_in, skip, chunk):
    """What ``ssd_chunked`` does on the TPU, with the kernels interpreted."""
    b, t, h, p = x.shape
    heads = ssd._heads_per_step(h, b_in.shape[2], p, b_in.shape[3], chunk)
    assert heads, "the case has to tile"
    return ssd._kernels(x, dt, a_head, b_in, c_in, skip, chunk, heads, True)


@functools.lru_cache(maxsize=None)
def evaluated(case, which):
    """``(y, states | None, the six gradients of sum(sin(y)))`` of one form on one case, float32."""
    args, chunk = inputs(case)
    fn = {"kernels": lambda *a: kernels(*a, chunk), "plain": lambda *a: ssd._plain(*a, chunk),
          "recurrence": lambda *a: (ref.recurrence(*a), None)}[which]
    with jax.default_matmul_precision("highest"):
        y, states = jax.jit(fn)(*args)
        grads = jax.jit(jax.grad(lambda *a: jnp.sum(jnp.sin(fn(*a)[0])), argnums=tuple(range(6))))(*args)
    return y, states, grads


@pytest.mark.parametrize("against", ["plain", "recurrence"])
@pytest.mark.parametrize("case", list(CASES))
def test_the_kernels_are_the_plain_form_and_the_recurrence(case, against):
    if case == "strong-decay":  # exp(a_i) * exp(-a_j) would be 0 * inf
        (_, dt, a, *_), chunk = inputs(case)
        assert float(jnp.min(jnp.cumsum((dt * a).reshape(dt.shape[0], -1, chunk, dt.shape[2]), axis=2))) < -100.0
    y, states, grads = evaluated(case, "kernels")
    want_y, want_states, want_grads = evaluated(case, against)
    assert bool(jnp.isfinite(y).all()) and bool(jnp.isfinite(states).all())
    np.testing.assert_allclose(np.asarray(y), np.asarray(want_y), atol=2e-5 * float(jnp.abs(want_y).max()))
    if want_states is not None:
        assert states.shape == want_states.shape and float(jnp.abs(states[:, 0]).max()) == 0.0
        np.testing.assert_allclose(np.asarray(states), np.asarray(want_states), atol=2e-5 * float(jnp.abs(want_states).max()) + 1e-30)
    for name, got, want in zip(NAMES, grads, want_grads):
        assert got.shape == want.shape and got.dtype == want.dtype, name
        assert bool(jnp.isfinite(got).all()), name
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-3 * float(jnp.abs(want).max()), err_msg=name)


def test_bf16_inputs_give_bf16_outputs_and_every_exponential_is_float32():
    args, chunk = inputs("two-row-blocks", jnp.bfloat16)
    loss = lambda *a: jnp.sum(kernels(*a, chunk)[0].astype(jnp.float32))
    (y, states), grads = jax.jit(lambda *a: kernels(*a, chunk))(*args), jax.jit(jax.grad(loss, argnums=tuple(range(6))))(*args)
    assert y.dtype == jnp.bfloat16 and states.dtype == jnp.float32
    assert [g.dtype for g in grads] == [jnp.bfloat16, jnp.float32, jnp.float32, jnp.bfloat16, jnp.bfloat16, jnp.float32]
    wide, _ = inputs("two-row-blocks")
    want = ref.recurrence(*(v.astype(jnp.bfloat16).astype(jnp.float32) for v in wide))
    assert float(jnp.abs(y.astype(jnp.float32) - want).max()) < 0.05 * float(jnp.abs(want).max())
    text = str(jax.make_jaxpr(jax.grad(loss, argnums=(0,)))(*args))
    exponentials = re.findall(r"(\w+):(\w+)\[[\d,]*\] = exp ", text)
    assert len(exponentials) >= 8 and {dtype for _, dtype in exponentials} == {"f32"}, exponentials
    assert "bf16" in text and "ssd_fwd" in text and "ssd_bwd" in text
    assert "f32[1,128,128]" in text and "bf16[1,128,128]" not in text  # the carried state and its gradient, a lane group


#: the largest error of a gradient on bf16 inputs, as a share of the float32 recurrence's largest entry (``A``: of each
#: head's own value). Read here: the kernels x 3.1e-3 - 4.2e-3, dt 1.9e-4 - 6.9e-4, A 2.4e-4 - 1.1e-3, B 3.7e-3 -
#: 4.7e-3, C 2.4e-3 - 3.8e-3, D 1.2e-6; the plain form x 6.7e-3, dt 3.2e-3, A 1.7e-2, B 4.5e-3, C 3.8e-3; a backward
#: that sends a decay's gradient to ``a_i`` and to ``c_j`` from two differently rounded products A 0.32 - 6.3, dt 3e-3 - 1.5e-2
BF16_BOUNDS = {"x": 8e-3, "dt": 2e-3, "A": 3e-3, "B": 8e-3, "C": 8e-3, "D": 1e-4}


@pytest.mark.parametrize("case", ["chunks", "two-row-blocks", "strong-decay"])
def test_on_bf16_inputs_every_gradient_stays_by_the_float32_recurrence(case):
    """What the float32 cases cannot see: a head's ``A`` gathers each decay's gradient twice, at ``a_i`` and negated
    at ``c_j``, through a running sum, and the two cancel only if they are ONE rounded number (``pairs`` in
    ``_bwd_kernel``). Summed from two products rounded apart they leave their roundings: ``dA`` off by a third
    and more at these shapes (1.7 % at the cell's on the chip, ``PERF.md`` section 6, PR 38). The loss is linear in ``y``
    with weights bf16 holds, so the cotangent is the same number on both sides."""
    wide, chunk = inputs(case)
    args = tuple(v.astype(jnp.bfloat16) if name in "xBC" else v for name, v in zip(NAMES, wide))
    weight = jnp.asarray(np.random.default_rng(7).normal(size=args[0].shape), jnp.bfloat16).astype(jnp.float32)
    grads = lambda fn: jax.jit(jax.grad(lambda *a: jnp.sum(weight * fn(*a).astype(jnp.float32)), argnums=tuple(range(6))))
    got = grads(lambda *a: kernels(*a, chunk)[0])(*args)
    with jax.default_matmul_precision("highest"):
        want = grads(ref.recurrence)(*(v.astype(jnp.float32) for v in args))
    for name, g, w in zip(NAMES, got, want):
        error = jnp.abs(g.astype(jnp.float32) - w) / (jnp.abs(w) if name == "A" else jnp.abs(w).max())
        assert float(error.max()) < BF16_BOUNDS[name], (name, float(error.max()))


@pytest.mark.parametrize("case", ["eight-heads", "two-groups"])
def test_the_scan_shard_maps_itself_over_batch_and_heads(case, monkeypatch):
    """``ssd_chunked_sharded`` on an fsdp x model mesh of the CPU's devices, the kernels interpreted a shard: the
    heads split over ``model`` (a shared group whole on every shard, its ``dB`` / ``dC`` summed over them; two
    groups each with its heads), the rows over ``fsdp``; equal to the plain form on one device."""
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("fsdp", "model"))
    b, t, h, p, g, chunk = (2, 256, 8, 64, 1, 128) if case == "eight-heads" else (2, 256, 4, 64, 2, 128)
    monkeypatch.setitem(CASES, case, (b, t, h, p, g, chunk, 0.1))
    args, _ = inputs(case)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(ssd, "_kernels", functools.partial(ssd._kernels, interpret=True))
    sharded = lambda *a: ssd.ssd_chunked_sharded(*a, chunk, mesh, return_carry=True)
    loss = lambda fn: jax.grad(lambda *a: jnp.sum(jnp.sin(fn(*a)[0])), argnums=tuple(range(6)))
    with jax.default_matmul_precision("highest"):
        text = str(jax.make_jaxpr(loss(sharded))(*args))
        assert "shard_map" in text and "ssd_fwd" in text and "ssd_bwd" in text
        assert f"f32[1,{t},{h // 2},{p}]" in text and f"f32[{b},{t},{h},{p}]" in text  # a shard's ``x`` and the whole
        (y, states), grads = jax.jit(sharded)(*args), jax.jit(loss(sharded))(*args)
        (want_y, want_states), want_grads = jax.jit(lambda *a: ssd._plain(*a, chunk))(*args), jax.jit(loss(lambda *a: ssd._plain(*a, chunk)))(*args)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want_y), atol=2e-5 * float(jnp.abs(want_y).max()))
    np.testing.assert_allclose(np.asarray(states), np.asarray(want_states), atol=2e-5 * float(jnp.abs(want_states).max()))
    for name, got, want in zip(NAMES, grads, want_grads):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-3 * float(jnp.abs(want).max()), err_msg=name)


@pytest.mark.parametrize("h, g, p, n, chunk, heads", [
    (32, 1, 64, 128, 256, 8),  # granite-train-8k
    (64, 1, 64, 128, 256, 8), (16, 1, 64, 128, 256, 8), (4, 2, 64, 128, 128, 2), (12, 1, 64, 128, 128, 6),
    (3, 1, 64, 128, 128, 0),  # three heads of 64 fill no whole number of 128 lanes
    (32, 1, 64, 128, 8, 0), (32, 1, 64, 128, 192, 0), (32, 1, 32, 128, 256, 0), (32, 1, 64, 16, 256, 0), (2, 2, 64, 128, 128, 0),
    # what tiles and Mosaic never compiled: heads of 128, a longer chunk, a wider state
    (3, 1, 128, 128, 128, 0), (32, 1, 64, 128, 512, 0), (6, 1, 64, 256, 384, 0), (32, 1, 64, 256, 256, 0),
])
def test_the_heads_a_grid_step_walks_follow_from_the_shapes(h, g, p, n, chunk, heads):
    assert ssd._heads_per_step(h, g, p, n, chunk) == heads


def _lowered(fn, *args):
    return jax.jit(fn).lower(*args).as_text()


@pytest.mark.parametrize("case", ["two-groups", "odd"])
def test_off_the_tpu_every_call_lowers_to_the_plain_form(case):
    if case == "odd":
        rng = np.random.default_rng(1)
        f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
        args, chunk = (f(2, 32, 4, 8), jax.nn.softplus(f(2, 32, 4)), -jnp.exp(f(4)), f(2, 32, 1, 16), f(2, 32, 1, 16), f(4)), 8
    else:
        args, chunk = inputs(case)
    assert jax.default_backend() != "tpu"
    for carry in (False, True):
        text = _lowered(lambda *a: ssd.ssd_chunked(*a, chunk, return_carry=carry), *args)
        assert "custom_call" not in text
        assert text == _lowered(lambda *a: ssd._plain(*a, chunk) if carry else ssd._plain(*a, chunk)[0], *args)


@pytest.fixture
def one_tpu(monkeypatch):
    """What a process on a one-chip TPU machine observes (the test session's CPU shows eight devices)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    ssd._say_once.cache_clear()


def _said(caplog):
    return [r.getMessage() for r in caplog.records if "ssd_chunked" in r.getMessage()]


def test_on_the_tpu_shapes_choose_the_path_and_a_refused_shape_is_named_once(one_tpu, caplog):
    args, chunk = inputs("two-groups")
    text = str(jax.make_jaxpr(lambda *a: ssd.ssd_chunked(*a, chunk, return_carry=True))(*args))
    assert "ssd_fwd" in text and "pallas_call" in text
    assert "ssd_bwd" in str(jax.make_jaxpr(jax.grad(lambda *a: jnp.sum(ssd.ssd_chunked(*a, chunk))))(*args))
    with caplog.at_level(logging.DEBUG, logger=ssd.__name__):
        short = tuple(v[:, :24] if v.ndim > 1 else v for v in args)  # ``init``'s kind of row: under 128 positions nothing could engage
        assert "pallas_call" not in str(jax.make_jaxpr(lambda *a: ssd.ssd_chunked(*a, 8, return_carry=True))(*short))
        assert not _said(caplog)  # the expected path says nothing: the one line is for a full-length call that fell back
        for _ in range(2):  # 256 positions in chunks of 64: a chunk fills no 128 lanes
            text = str(jax.make_jaxpr(lambda *a: ssd.ssd_chunked(*a, 64, return_carry=True))(*args))
    assert "pallas_call" not in text
    said = _said(caplog)
    assert len(said) == 1 and "(1, 256, 4, 64)" in said[0] and "chunk 64" in said[0] and "plain form" in said[0]
    assert [r.levelno for r in caplog.records if "ssd_chunked" in r.getMessage()] == [logging.WARNING]


def test_traced_for_several_devices_the_kernels_run_only_inside_a_shard_map(monkeypatch, caplog):
    """XLA cannot partition a Pallas call (``tests/test_tpu_compile.py`` holds the compiler's refusal): under plain
    jit where the process sees several devices the plain form stays, which XLA partitions as it always did, and the
    log says once how to get the kernels; a shard's trace is one device's."""
    from jax.sharding import Mesh, PartitionSpec as P

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    ssd._say_once.cache_clear()
    assert jax.device_count() > 1
    args, chunk = inputs("two-groups")
    scan = lambda *a: ssd.ssd_chunked(*a, chunk, return_carry=True)
    with caplog.at_level(logging.WARNING, logger=ssd.__name__):
        for _ in range(2):
            assert "pallas_call" not in str(jax.make_jaxpr(scan)(*args))
    said = _said(caplog)
    assert len(said) == 1 and f"{jax.device_count()} devices" in said[0] and "mesh" in said[0] and "plain form" in said[0]
    mesh = Mesh(np.array(jax.devices()[:2]), ("data",))
    inside = jax.shard_map(scan, mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False)
    assert "ssd_fwd" in str(jax.make_jaxpr(inside)(*args))
    one = Mesh(np.array(jax.devices()[:1]), ("data",))  # a cell that takes one chip of a host names it: ``TransformerConfig.mesh``
    assert "ssd_fwd" in str(jax.make_jaxpr(lambda *a: ssd.ssd_chunked_sharded(*a, chunk, one, return_carry=True))(*args))
    some_axes = jax.shard_map(scan, mesh=Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("data", "model")), in_specs=P(),
                              out_specs=P(), axis_names={"data"}, check_vma=False)
    assert "pallas_call" not in str(jax.make_jaxpr(some_axes)(*args))  # XLA still has the other axis to partition over


@pytest.mark.parametrize("backend", ["cpu", "tpu"])
def test_the_handed_states_are_a_reading_on_both_paths(backend, one_tpu, monkeypatch):
    args, chunk = inputs("one-chunk")
    through_the_states = jax.grad(lambda *a: jnp.sum(ssd.ssd_chunked(*a, chunk, return_carry=True)[1]), argnums=(0, 1))
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    text = str(jax.make_jaxpr(through_the_states)(*args))
    assert ("ssd_fwd" in text) == (backend == "tpu") and "ssd_bwd" not in text
    if backend == "cpu":
        assert all(float(jnp.abs(g).max()) == 0.0 for g in through_the_states(*args))
