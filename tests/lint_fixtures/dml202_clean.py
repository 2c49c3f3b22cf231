"""DML202 clean fixture: matching arity, declared axes, specs resolved
through an assignment, unresolvable meshes checked against the registry.

Static lint corpus — never imported or executed.
"""

import jax
from jax.sharding import PartitionSpec as P

from dmlcloud_tpu.parallel.mesh import create_mesh


def body2(a, b):
    return a + b


def body1(x):
    return x * 2


mesh = create_mesh({"data": 4, "model": 2})

# fine: one spec per argument, axes on the mesh
f = jax.shard_map(body2, mesh=mesh, in_specs=(P("data"), P("model")), out_specs=P("data"))

# fine: specs through one level of assignment (the dataflow pass)
specs = (P("data"), P(None))
g = jax.shard_map(body2, mesh=mesh, in_specs=specs, out_specs=P())

# fine: mesh unresolvable (function parameter) — axes checked against the
# registry, and 'data' is declared
def wrap(some_mesh):
    return jax.shard_map(body1, mesh=some_mesh, in_specs=(P("data"),), out_specs=P("data"))


# fine: lambda wrapped, arity matches
h = jax.shard_map(lambda x: x, mesh=mesh, in_specs=(P("data"),), out_specs=P("data"))
