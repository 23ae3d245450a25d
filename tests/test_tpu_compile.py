"""Compile-only checks of the Pallas kernels for a TPU v5e.

The TPU compiler compiles for a described (not attached) v5e, so these
tests catch what interpret mode cannot: memory spaces, block shapes,
vector layouts and VMEM limits.  Shapes are the main path's real sizes
(n = 2^28 f32 arrays, 256 rows of 2^20, K = 9 brackets, 128 bins), plus
the widest ones that put more into SMEM: K = 64 brackets (percentile
vectors) and 4096 short rows (query batches, elemental starts).
Nothing runs; each test asserts that the compiled program holds the
kernel (``tpu_custom_call``) and fits the chip's HBM.
"""
import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels import cp_objective as K

N = 1 << 28
B, NROW = 256, 1 << 20
WIDE_B, WIDE_NROW = 4096, 1 << 14
NB = 128  # bins per sweep (nbins + 1 edges)
V5E_HBM = 16 * 1024 ** 3


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _kernel(name):
    """``(fn, arg shapes)`` of one public kernel at the main path's size."""
    f32 = lambda *s: (s, jnp.float32)
    return {
        "cp_partials": (K.cp_partials, [f32(N), f32()]),
        "cp_partials_multi": (K.cp_partials_multi, [f32(N), f32(9)]),
        "cp_partials_batched": (K.cp_partials_batched,
                                [f32(B, NROW), f32(B)]),
        "wcp_partials": (K.wcp_partials, [f32(N), f32(N), f32()]),
        "wcp_partials_multi": (K.wcp_partials_multi,
                               [f32(N), f32(N), f32(9)]),
        "wcp_partials_batched": (K.wcp_partials_batched,
                                 [f32(B, NROW), f32(B, NROW), f32(B)]),
        "cp_histogram": (K.cp_histogram, [f32(N), f32(NB + 1)]),
        "cp_histogram_multi": (K.cp_histogram_multi,
                               [f32(N), f32(9, NB + 1)]),
        "cp_histogram_multi_k64": (K.cp_histogram_multi,
                                   [f32(N), f32(64, NB + 1)]),
        "cp_partials_multi_k64": (K.cp_partials_multi, [f32(N), f32(64)]),
        "cp_partials_batched_wide": (K.cp_partials_batched,
                                     [f32(WIDE_B, WIDE_NROW), f32(WIDE_B)]),
        "cp_histogram_batched_wide": (K.cp_histogram_batched,
                                      [f32(WIDE_B, WIDE_NROW),
                                       f32(WIDE_B, NB + 1)]),
        "cp_histogram_batched": (K.cp_histogram_batched,
                                 [f32(B, NROW), f32(B, NB + 1)]),
        "wcp_histogram": (K.wcp_histogram, [f32(N), f32(N), f32(NB + 1)]),
        "wcp_histogram_multi": (K.wcp_histogram_multi,
                                [f32(N), f32(N), f32(9, NB + 1)]),
        "wcp_histogram_batched": (K.wcp_histogram_batched,
                                  [f32(B, NROW), f32(B, NROW),
                                   f32(B, NB + 1)]),
    }[name]


@pytest.mark.parametrize("name", [
    "cp_partials", "cp_partials_multi", "cp_partials_batched",
    "wcp_partials", "wcp_partials_multi", "wcp_partials_batched",
    "cp_histogram", "cp_histogram_multi", "cp_histogram_batched",
    "wcp_histogram", "wcp_histogram_multi", "wcp_histogram_batched",
    "cp_partials_multi_k64", "cp_histogram_multi_k64",
    "cp_partials_batched_wide", "cp_histogram_batched_wide",
])
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, specs = _kernel(name)
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in specs]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < V5E_HBM, used


def test_histogram_without_sums_compiles_for_v5e(one_chip):
    """The plain binned sweep (``want_sums=False``) drops an output."""
    x = jax.ShapeDtypeStruct((N,), jnp.bfloat16, sharding=one_chip)
    e = jax.ShapeDtypeStruct((NB + 1,), jnp.float32, sharding=one_chip)
    compiled = jax.jit(
        lambda a, b: K.cp_histogram(a, b, want_sums=False)[0]
    ).lower(x, e).compile()
    assert "tpu_custom_call" in compiled.as_text()
