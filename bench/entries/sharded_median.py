"""``distributed.sharded_median`` of one array of the pool per call, each
array split evenly over a 1-D mesh of every chip of the cell."""
from __future__ import annotations

from bench.entries._array import ArrayEntry


def build(cfg, mix, seed, devices):
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.core import distributed

    mesh = Mesh(np.asarray(devices), (cfg["mesh_axis"],))
    spec = P(cfg["mesh_axis"])
    n = int(cfg["n"])
    return ArrayEntry(cfg, mix, seed,
                      lambda x: distributed.sharded_median(x, mesh, spec),
                      [(n + 1) // 2], sharding=NamedSharding(mesh, spec))
