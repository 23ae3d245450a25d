"""Distributed selection demo over every device of the host (the paper's
multi-GPU scenario, Sec. V-D): the array never leaves its shards; each CP
iteration communicates four scalars; the finalize gathers only the tiny
pivot-interval buffers.  Also demos Byzantine-robust gradient aggregation.

On CPU the host platform is split into 8 devices; on an accelerator host
the mesh spans its chips.

  PYTHONPATH=src python examples/distributed_selection.py
"""
import os

os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                           + os.environ.get("XLA_FLAGS", ""))

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.core import _compat, distributed, robust  # noqa: E402


def main():
    n_dev = jax.device_count()
    mesh = _compat.make_mesh((n_dev,), ("data",))
    rng = np.random.default_rng(0)
    n = 1 << 22
    x = rng.standard_normal(n).astype(np.float32)
    x[0] = 1e9  # outlier: CP does not care

    res = distributed.sharded_median(jnp.asarray(x), mesh, P("data"),
                                     cap_local=4096)
    truth = np.partition(x, (n + 1) // 2 - 1)[(n + 1) // 2 - 1]
    print(f"sharded median over {n_dev} devices: {float(res.value):+.6f} "
          f"exact={np.float32(res.value) == truth} "
          f"iters={int(res.iters)} |z|={int(res.n_in)}")

    if n_dev < 3:
        print("robust aggregation needs >= 3 replicas; skipped")
        return
    # Byzantine-robust aggregation: one device sends garbage gradients
    g = np.tile(np.linspace(-1, 1, 128, dtype=np.float32), (n_dev, 1))
    g += 0.01 * rng.standard_normal(g.shape).astype(np.float32)
    g[n_dev // 2] = 1e6  # corrupted replica

    def agg(gl, method):
        return robust.robust_aggregate({"g": gl}, "data", method=method)

    for method in ["mean", "median", "trimmed"]:
        out = _compat.shard_map(
            lambda gl: agg(gl, method), mesh=mesh,
            in_specs=P("data"), out_specs=P("data"), check=False,
        )(jnp.asarray(g))
        err = float(jnp.max(jnp.abs(np.asarray(out["g"])[0]
                                    - np.linspace(-1, 1, 128))))
        print(f"aggregate[{method:7s}]: max deviation from truth = {err:.4f}"
              f"  {'(poisoned!)' if err > 1 else '(robust)'}")


if __name__ == "__main__":
    main()
