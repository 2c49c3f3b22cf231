"""DML202 bad fixture: shard_map specs that don't match the wrapped
function or the mesh.

Static lint corpus — never imported or executed.
"""

import jax
from jax.sharding import PartitionSpec as P

from dmlcloud_tpu.parallel.mesh import create_mesh


def body3(a, b, c):
    return a + b + c


def body1(x):
    return x * 2


mesh = create_mesh({"data": 8})

# BAD: 2 specs for a 3-argument function
f = jax.shard_map(body3, mesh=mesh, in_specs=(P("data"), P("data")), out_specs=P("data"))

# BAD: P('model') but the (locally resolvable) mesh only has 'data'
g = jax.shard_map(body1, mesh=mesh, in_specs=(P("model"),), out_specs=P("data"))

# BAD: out_specs names an axis nothing declares anywhere
h = jax.shard_map(body1, mesh=unknown_mesh, in_specs=(P("data"),), out_specs=P("qrst"))
