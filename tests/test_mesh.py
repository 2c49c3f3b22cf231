"""Mesh construction, sharding policies, and real multi-device psum on the
8-device CPU mesh — stronger than the reference's world-1 trick (SURVEY.md §4)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from dmlcloud_tpu.parallel import mesh as mesh_lib


def test_create_default_mesh():
    m = mesh_lib.create_mesh()
    assert m.axis_names == ("data",)
    assert m.shape["data"] == 8


def test_create_mesh_with_minus_one():
    m = mesh_lib.create_mesh({"data": -1, "model": 2})
    assert m.shape["data"] == 4
    assert m.shape["model"] == 2


def test_create_mesh_wrong_product():
    with pytest.raises(ValueError):
        mesh_lib.create_mesh({"data": 3})


def test_auto_mesh_factorization():
    m = mesh_lib.auto_mesh(8, ("data", "fsdp", "model"))
    sizes = [m.shape[a] for a in ("data", "fsdp", "model")]
    assert np.prod(sizes) == 8
    assert sizes == [2, 2, 2]


def test_batch_pspec_with_fsdp():
    m = mesh_lib.create_mesh({"data": 2, "fsdp": 4})
    assert mesh_lib.batch_pspec(m) == P(("data", "fsdp"))
    assert mesh_lib.data_parallel_size(m) == 8


def test_replicate_policy(mesh8):
    params = {"w": jnp.ones((16, 4)), "b": jnp.zeros((4,))}
    sharded = mesh_lib.shard_pytree(params, mesh8, "replicate")
    for leaf in jax.tree_util.tree_leaves(sharded):
        assert leaf.sharding.is_fully_replicated


def test_fsdp_policy_shards_large_params():
    m = mesh_lib.create_mesh({"fsdp": 8})
    params = {"big": jnp.ones((1024, 64)), "tiny": jnp.ones((4,))}
    shardings = mesh_lib.sharding_for(params, m, "fsdp")
    assert shardings["big"].spec == P("fsdp", None)
    assert shardings["tiny"].spec == P()


def test_rule_policy():
    m = mesh_lib.create_mesh({"data": 4, "model": 2})
    params = {"attn": {"kernel": jnp.ones((8, 16))}, "mlp": {"kernel": jnp.ones((8, 16))}}
    rules = [("attn/kernel", P(None, "model")), (".*", P())]
    shardings = mesh_lib.sharding_for(params, m, rules)
    assert shardings["attn"]["kernel"].spec == P(None, "model")
    assert shardings["mlp"]["kernel"].spec == P()


def test_rule_policy_drops_missing_axes():
    m = mesh_lib.create_mesh({"data": -1})  # no 'model' axis
    params = {"attn": {"kernel": jnp.ones((8, 16))}}
    rules = [("attn/kernel", P(None, "model"))]
    shardings = mesh_lib.sharding_for(params, m, rules)
    assert shardings["attn"]["kernel"].spec == P(None, None)


def test_make_global_batch_shards_batch_dim(mesh8):
    batch = {"x": np.arange(32, dtype=np.float32).reshape(16, 2), "y": np.arange(16)}
    global_batch = mesh_lib.make_global_batch(batch, mesh8)
    assert global_batch["x"].shape == (16, 2)
    # 8 shards of 2 rows each
    assert len(global_batch["x"].sharding.device_set) == 8
    np.testing.assert_array_equal(np.asarray(global_batch["x"]), batch["x"])


def test_sharded_psum_executes(mesh8):
    """A real 8-way psum through shard_map — the collective path DDP used to own."""
    x = jnp.arange(8.0)

    def global_sum(x):
        return jax.lax.psum(jnp.sum(x), "data")

    global_sum = jax.shard_map(global_sum, mesh=mesh8, in_specs=P("data"), out_specs=P())
    assert float(global_sum(x)) == 28.0


def test_grad_mean_matches_single_device(mesh8):
    """Data-parallel grad via sharded jit == single-device grad on full batch."""
    w = jnp.ones((4,))
    x = np.random.RandomState(0).randn(16, 4).astype(np.float32)

    def loss(w, x):
        return jnp.mean((x @ w) ** 2)

    expected = jax.grad(loss)(w, jnp.asarray(x))

    xs = mesh_lib.make_global_batch(x, mesh8)
    sharded_grad = jax.jit(jax.grad(loss))(w, xs)
    np.testing.assert_allclose(np.asarray(sharded_grad), np.asarray(expected), rtol=1e-5)


def test_parse_mesh_axes():
    from dmlcloud_tpu.parallel.mesh import parse_mesh_axes

    assert parse_mesh_axes("data=2,fsdp=4") == {"data": 2, "fsdp": 4}
    assert parse_mesh_axes("data=-1") == {"data": -1}


def test_parse_mesh_axes_rejects_malformed():
    import pytest

    from dmlcloud_tpu.parallel.mesh import parse_mesh_axes

    with pytest.raises(ValueError, match="malformed"):
        parse_mesh_axes("data")
    with pytest.raises(ValueError, match="malformed"):
        parse_mesh_axes("data=two")


def test_parse_mesh_axes_rejects_duplicate_axis():
    """'data=2,data=4' used to silently become {'data': 4} — a dict overwrite
    that dropped the first size without a word."""
    import pytest

    from dmlcloud_tpu.parallel.mesh import parse_mesh_axes

    with pytest.raises(ValueError, match="more than once"):
        parse_mesh_axes("data=2,data=4")
