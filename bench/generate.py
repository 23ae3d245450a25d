"""The one general generator: a traffic mix's data pool, made on the device.

A mix names a pool of arrays, each a mixture of parts laid end to end as in
arXiv:1104.2732 Sec. V-A (the first ``floor(n * frac)`` elements from the
first part, and so on; the last part takes the rest).  A part is one of

    uniform     lo + (hi - lo) * U
    normal      loc + scale * N
    halfnormal  loc + scale * |N|
    beta        Beta(a, b) for whole a, b: Ga / (Ga + Gb), with
                Gm = -log(U_1 ... U_m) a Gamma(m) variate
    const       value

Every array of the pool is made by one jitted call from a key derived from
the run's seed, in the dtype and sharding it is served in, so the same seed
gives the same pool on any chip count.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole ``seed``: both 32-bit words of
    ``seed mod 2**64`` go into the key, so seeds past 2**32 stay apart."""
    s = int(seed) % (1 << 64)
    return jax.random.wrap_key_data(
        np.array([s >> 32, s & 0xFFFFFFFF], np.uint32))


def _part(key, kind, p, n, dtype):
    g = lambda k: jax.random.normal(k, (n,), jnp.float32)

    def gamma(k, m):  # Gamma(m), whole m: one (n,) uniform at a time
        tiny = jnp.finfo(jnp.float32).tiny
        return -sum(jnp.log(jax.random.uniform(jax.random.fold_in(k, i),
                                               (n,), minval=tiny))
                    for i in range(m))

    if kind == "uniform":
        v = p["lo"] + (p["hi"] - p["lo"]) * jax.random.uniform(key, (n,))
    elif kind == "normal":
        v = p["loc"] + p["scale"] * g(key)
    elif kind == "halfnormal":
        v = p["loc"] + p["scale"] * jnp.abs(g(key))
    elif kind == "beta":
        ka, kb = jax.random.split(key)
        ga, gb = gamma(ka, int(p["a"])), gamma(kb, int(p["b"]))
        v = ga / (ga + gb)
    elif kind == "const":
        v = jnp.full((n,), p["value"], jnp.float32)
    else:
        raise ValueError(f"unknown part kind {kind!r}")
    return v.astype(dtype)


def _frozen(parts):
    """Parts as a hashable static argument: ((kind, ((key, value), ...),
    end), ...) with each part's cumulative end fraction."""
    out, end = [], 0.0
    for i, p in enumerate(parts):
        end = 1.0 if i == len(parts) - 1 else end + float(p["frac"])
        params = tuple(sorted((k, v) for k, v in p.items()
                              if k not in ("dist", "frac")))
        out.append((p["dist"], params, end))
    return tuple(out)


@functools.partial(jax.jit, static_argnames=("parts", "n", "dtype",
                                             "sharding"))
def _mixture(key, *, parts, n, dtype, sharding):
    idx = jax.lax.iota(jnp.int32, n)
    if sharding is not None:
        idx = jax.lax.with_sharding_constraint(idx, sharding)
    out, start = None, 0
    for j, (kind, params, end) in enumerate(parts):
        stop = n if end >= 1.0 else int(n * end)
        v = _part(jax.random.fold_in(key, j), kind, dict(params), n, dtype)
        out = v if out is None else jnp.where(idx >= start, v, out)
        start = stop
    if sharding is not None:
        out = jax.lax.with_sharding_constraint(out, sharding)
    return out


def array_pool(seed, n, dtype, pool, sharding=None):
    """One device array of ``n`` elements per pool entry."""
    key = seed_key(seed)
    out = []
    for i, entry in enumerate(pool):
        out.append(_mixture(jax.random.fold_in(key, i),
                            parts=_frozen(entry["parts"]), n=n,
                            dtype=jnp.dtype(dtype).name, sharding=sharding))
    return out
