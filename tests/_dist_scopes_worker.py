"""Subprocess worker for the phase-scope tests of the sharded median.

Run as:  python tests/_dist_scopes_worker.py <n_devices>
Sets XLA_FLAGS *before* importing jax (preserving caller flags other than a
stale device-count), compiles ``distributed.sharded_median`` of a 2^16
array split over a 1-D mesh, and prints the ``op_name`` of every
instruction of the compiled text as one JSON list.
"""
import json
import re
import sys

from _dist_env import force_device_count

n_dev = int(sys.argv[1]) if len(sys.argv) > 1 else 4
force_device_count(n_dev)  # must run BEFORE the jax import below

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.core import _compat, distributed  # noqa: E402

assert jax.device_count() == n_dev, jax.devices()
# compile afresh: the persistent cache's key leaves the scopes out
jax.config.update("jax_enable_compilation_cache", False)
mesh = _compat.make_mesh((n_dev,), ("data",))
x = jax.ShapeDtypeStruct((1 << 16,), jnp.float32,
                         sharding=NamedSharding(mesh, P("data")))
text = jax.jit(lambda v: distributed.sharded_median(v, mesh, P("data"))
               ).lower(x).compile().as_text()
print(json.dumps(re.findall(r'op_name="([^"]*)"', text)))
