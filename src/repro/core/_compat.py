"""Mesh and shard_map entry points shared by the selection, model and test
code, with the repository's defaults in one place."""
from __future__ import annotations

import functools

import jax


def make_mesh(shape, axes) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with Auto axis types."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def shard_map(f=None, *, mesh, in_specs, out_specs, check: bool = False):
    """``jax.shard_map``; ``check=False`` disables the static varying-axis
    analysis (``check_vma``) — the distributed selection results are
    semantically replicated (built from psum/all_gather outputs) but the
    analysis cannot prove it.
    """
    if f is None:
        return functools.partial(shard_map, mesh=mesh, in_specs=in_specs,
                                 out_specs=out_specs, check=check)
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check)


def scan_in_shard_map(body, init, n: int):
    """``lax.scan(body, init, jnp.arange(n))``, returning the carry."""
    import jax.numpy as jnp

    carry, _ = jax.lax.scan(body, init, jnp.arange(n))
    return carry
