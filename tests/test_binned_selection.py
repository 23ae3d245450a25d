"""Exactness + scheduling tests for the binned bracket-descent method.

``method='binned'`` must match ``np.partition`` bit-for-bit everywhere the
cutting-plane engine does — duplicate-heavy rows, constant rows, extreme
magnitudes, the log1p monotone guard — while resolving in a handful of
histogram sweeps (the perf claim: ~3 data passes where cp needs ~15).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import selection

jax.config.update("jax_platform_name", "cpu")


def kth_rows(x, ks):
    x = np.asarray(x)
    ks = np.broadcast_to(np.asarray(ks), (x.shape[0],))
    return np.array([np.partition(row, k - 1)[k - 1]
                     for row, k in zip(x, ks)], x.dtype)


# ---------------------------------------------------------------------------
# rows mode: property sweep vs np.partition
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("b,n", [(1, 1000), (8, 4096), (33, 257),
                                 (4, 100_000)])
def test_binned_rows_match_partition(b, n):
    rng = np.random.default_rng(b * n)
    x = rng.standard_normal((b, n)).astype(np.float32)
    ks = rng.integers(1, n + 1, size=b).astype(np.int32)
    res = selection.select_rows(jnp.asarray(x), jnp.asarray(ks),
                                method="binned")
    np.testing.assert_array_equal(np.asarray(res.value), kth_rows(x, ks))
    assert int(jnp.max(res.status)) <= selection.TIE_FALLBACK


def test_binned_duplicate_heavy_rows_tiny_cap():
    """Mostly ties, answers inside tie blocks, cap far below tie counts."""
    rng = np.random.default_rng(1)
    b, n = 6, 5000
    x = rng.integers(0, 4, size=(b, n)).astype(np.float32)
    ks = rng.integers(1, n + 1, size=b).astype(np.int32)
    res = selection.select_rows(jnp.asarray(x), jnp.asarray(ks),
                                method="binned", cap=8)
    np.testing.assert_array_equal(np.asarray(res.value), kth_rows(x, ks))
    assert np.all(np.asarray(res.status) != selection.NOT_CONVERGED)


def test_binned_constant_rows_and_extreme_k():
    rng = np.random.default_rng(2)
    n = 3000
    x = np.stack([
        np.full(n, 3.25),
        rng.standard_normal(n),
        np.full(n, -7.0),
        rng.standard_normal(n),
    ]).astype(np.float32)
    for ks in ([1] * 4, [n] * 4, [1, 2, n - 1, n], [n // 2] * 4):
        res = selection.select_rows(jnp.asarray(x),
                                    jnp.asarray(ks, jnp.int32),
                                    method="binned", cap=16)
        np.testing.assert_array_equal(np.asarray(res.value), kth_rows(x, ks))


def test_binned_extreme_magnitudes_with_log1p():
    """1e20-scale components: binned sweeps run on the log1p image and the
    bracket maps back count-preservingly — answers stay bit-exact."""
    rng = np.random.default_rng(3)
    b, n = 4, 16_384
    x = rng.standard_normal((b, n)).astype(np.float32)
    x[:, :16] = 1e20
    x[2] *= 1e10
    ks = np.array([n // 2, 1, n // 3, n], np.int32)
    res = selection.select_rows(jnp.asarray(x), jnp.asarray(ks),
                                method="binned", transform="log1p")
    np.testing.assert_array_equal(np.asarray(res.value), kth_rows(x, ks))


def test_binned_extreme_magnitudes_without_transform():
    """Raw 1e9 outlier: value-space bisection would stall; 128 bins per
    sweep keep the sweep count in the single digits and the result exact."""
    rng = np.random.default_rng(4)
    n = 200_000
    x = rng.standard_normal(n).astype(np.float32)
    x[0] = 1e9
    res = selection.order_statistic(jnp.asarray(x), n // 2, method="binned")
    np.testing.assert_equal(np.float32(res.value),
                            np.partition(x, n // 2 - 1)[n // 2 - 1])
    assert int(res.iters) <= 10


def test_binned_full_float_range_bracket():
    """Data spanning ±3e38: the naive bin width (hi-lo)/nbins overflows f32
    to inf — bin_edges must divide before differencing so the descent stays
    exact (and must never mint EXACT_HIT off inconsistent counts)."""
    rng = np.random.default_rng(40)
    n = 100_000
    x = rng.standard_normal(n).astype(np.float32)
    x[0], x[1] = 3e38, -3e38
    for k in [1, 2, n // 2, n - 1, n]:
        res = selection.order_statistic(jnp.asarray(x), k, method="binned")
        np.testing.assert_equal(np.float32(res.value),
                                np.partition(x, k - 1)[k - 1])
        assert int(res.status) != selection.NOT_CONVERGED


def test_binned_edges_overflow_safe():
    """bin_edges stays finite, monotone and inside [lo, hi] at full range."""
    from repro.kernels.ref import bin_edges

    e = np.asarray(bin_edges(jnp.float32(-3.4e38), jnp.float32(3.4e38), 128))
    assert np.all(np.isfinite(e))
    assert np.all(np.diff(e) >= 0)
    assert e[0] == np.float32(-3.4e38) and e[-1] == np.float32(3.4e38)


def test_binned_descent_step_fails_safe_on_bad_counts():
    """A cum vector that never reaches k (violated invariant) must stall,
    not certify: argmax-of-all-False must not masquerade as hit_lo."""
    from repro.kernels.ref import bin_edges

    cum = jnp.asarray([[0, 1, 2, 3]], jnp.int32)     # count(x<=yR) = 3 < k
    yL = jnp.asarray([0.0], jnp.float32)
    yR = jnp.asarray([1.0], jnp.float32)
    kk = jnp.asarray([10], jnp.int32)
    *_, hit_lo, exact, stall = selection.binned_descent_step(
        cum, bin_edges(yL, yR, 3), yL, yR, kk)
    assert not bool(exact[0])
    assert not bool(hit_lo[0])
    assert bool(stall[0])


def test_binned_tiny_normal_magnitudes():
    """Smallest-normal-scale data (1.2e-38): bin arithmetic stays exact."""
    rng = np.random.default_rng(5)
    x = (rng.integers(0, 3, 4096).astype(np.float32)) * 1.2e-38
    for k in [1, 2048, 4096]:
        res = selection.order_statistic(jnp.asarray(x), k, method="binned",
                                        cap=8)
        np.testing.assert_equal(np.float32(res.value),
                                np.partition(x, k - 1)[k - 1])


def test_binned_denormals_consistent_with_cp():
    """True denormals are flushed by XLA:CPU's counting reductions (FTZ;
    ``jnp.sort`` itself does NOT flush, so the sort baseline is excluded) —
    the honest invariant is self-consistency of the two count-based
    engines: binned must agree with cp on whatever the platform's
    comparisons see."""
    rng = np.random.default_rng(6)
    x = (rng.integers(0, 3, 2048).astype(np.float32)) * 1e-44
    for k in [1, 1024, 2048]:
        vb = selection.order_statistic(jnp.asarray(x), k,
                                       method="binned").value
        vc = selection.order_statistic(jnp.asarray(x), k,
                                       method="cp").value
        assert float(vb) == float(vc), k


def test_binned_sweep_count_vs_cp():
    """The tentpole claim at 1M elements: binned uses <= half the fused
    data passes of cp (typically 2 vs ~9)."""
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.standard_normal(1 << 20).astype(np.float32))
    k = (x.size + 1) // 2
    sweeps = int(selection.order_statistic(x, k, method="binned").iters)
    cp_iters = int(selection.order_statistic(x, k, method="cp").iters)
    assert sweeps * 2 <= cp_iters, (sweeps, cp_iters)
    assert sweeps <= 4


def test_binned_iters_are_per_row():
    rng = np.random.default_rng(8)
    n = 100_000
    easy = np.full(n, 1.0)                      # exact at min on sweep 1
    hard = rng.standard_normal(n)
    x = np.stack([easy, hard]).astype(np.float32)
    res = selection.select_rows(jnp.asarray(x), (n + 1) // 2,
                                method="binned", cap=64)
    iters = np.asarray(res.iters)
    assert iters[0] <= iters[1]
    assert int(res.status[0]) == selection.EXACT_HIT


def test_method_resolution_is_backend_aware():
    """None/'auto' picks binned for large n on EVERY backend (the verified
    arithmetic pass made the CPU sweep competitive — the acceptance flip);
    explicit wins; nbins stays backend-tuned."""
    big = selection.BINNED_MIN_N
    assert selection._resolve_method(None, big, "pallas") == "binned"
    assert selection._resolve_method("auto", big, "pallas") == "binned"
    assert selection._resolve_method(None, big - 1, "pallas") == "cp"
    # the jnp path now flips to binned too (ROADMAP open item closed: the
    # CPU histogram pass is no longer scatter/searchsorted-bound)
    assert selection._resolve_method(None, big, None) == "binned"
    assert selection._resolve_method(None, 1 << 20, "jnp") == "binned"
    assert selection._resolve_method(None, big - 1, None) == "cp"
    assert selection._resolve_method("binned", 10, None) == "binned"
    # sweep width: wide on the kernel path, narrow on the jnp path (the
    # factored reduction's cost scales with the slot count)
    assert selection._resolve_nbins(None, "pallas") == selection.DEF_NBINS
    assert selection._resolve_nbins(None, "jnp") == selection.DEF_NBINS_JNP
    assert selection._resolve_nbins(None, None) in (
        selection.DEF_NBINS, selection.DEF_NBINS_JNP)  # TPU-dependent
    assert selection._resolve_nbins(64, "pallas") == 64
    # f64 data is rerouted off the kernels by ops, so its sweeps get the
    # jnp-tuned width even when the kernel path was requested ...
    assert selection._resolve_nbins(None, "pallas", jnp.float64) == \
        selection.DEF_NBINS_JNP
    assert selection._resolve_nbins(None, "pallas", jnp.float32) == \
        selection.DEF_NBINS
    # ... except pallas_interpret, which is deliberately not rerouted
    assert selection._resolve_nbins(None, "pallas_interpret",
                                    jnp.float64) == selection.DEF_NBINS
    with pytest.raises(ValueError):
        selection._resolve_method("nope", big, None)


def test_binned_nbins_sweep():
    """Any nbins >= 2 is exact (nbins trades sweeps for bin bookkeeping)."""
    rng = np.random.default_rng(9)
    x = rng.standard_normal(20_000).astype(np.float32)
    k = 7777
    want = np.partition(x, k - 1)[k - 1]
    for nbins in [2, 8, 32, 128, 512]:
        res = selection.order_statistic(jnp.asarray(x), k, method="binned",
                                        nbins=nbins)
        np.testing.assert_equal(np.float32(res.value), want)


# ---------------------------------------------------------------------------
# shared-x mode (multi_order_statistic / quantiles)
# ---------------------------------------------------------------------------


def test_binned_shared_exact():
    rng = np.random.default_rng(10)
    n = 50_001
    x = rng.standard_normal(n).astype(np.float32)
    ks = np.array([1, 7, n // 4, n // 2, n - 1, n], np.int32)
    res = selection.multi_order_statistic(jnp.asarray(x), jnp.asarray(ks),
                                          method="binned")
    want = np.partition(x, ks - 1)[ks - 1]
    np.testing.assert_array_equal(np.asarray(res.value), want)
    assert np.all(np.asarray(res.status) != selection.NOT_CONVERGED)


def test_binned_shared_duplicate_heavy():
    rng = np.random.default_rng(11)
    x = rng.integers(0, 5, 30_000).astype(np.float32)
    ks = np.array([1, 10_000, 15_000, 29_999], np.int32)
    res = selection.multi_order_statistic(jnp.asarray(x), jnp.asarray(ks),
                                          method="binned", cap=8)
    want = np.partition(x, ks - 1)[ks - 1]
    np.testing.assert_array_equal(np.asarray(res.value), want)


def test_binned_shared_log1p():
    rng = np.random.default_rng(12)
    n = 32_768
    x = rng.standard_normal(n).astype(np.float32)
    x[:16] = 1e20
    ks = np.array([n // 4, n // 2, n], np.int32)
    res = selection.multi_order_statistic(jnp.asarray(x), jnp.asarray(ks),
                                          method="binned", transform="log1p")
    want = np.partition(x, ks - 1)[ks - 1]
    np.testing.assert_array_equal(np.asarray(res.value), want)


def test_binned_shared_interpret_kernel_parity():
    """Shared-x binned solve driven by the multi-bracket Pallas kernel
    (interpret mode) matches the jnp-oracle-driven solve bit for bit."""
    rng = np.random.default_rng(13)
    n = 4096
    x = rng.standard_normal(n).astype(np.float32)
    ks = np.array([1, 100, 2048, 4096], np.int32)
    res_jnp = selection.multi_order_statistic(
        jnp.asarray(x), jnp.asarray(ks), method="binned", backend="jnp")
    res_pal = selection.multi_order_statistic(
        jnp.asarray(x), jnp.asarray(ks), method="binned",
        backend="pallas_interpret")
    np.testing.assert_array_equal(np.asarray(res_jnp.value),
                                  np.asarray(res_pal.value))
    want = np.partition(x, ks - 1)[ks - 1]
    np.testing.assert_array_equal(np.asarray(res_jnp.value), want)


# ---------------------------------------------------------------------------
# x64: the f64 dispatch fix (kernels would downcast; ops must reroute)
# ---------------------------------------------------------------------------


def test_x64_parity_sub_f32_resolution():
    """f64 data distinguishable only below f32 resolution must select
    exactly — the Pallas backend reroutes to the dtype-preserving oracle."""
    import jax.experimental

    from repro.kernels import ops

    with jax.enable_x64(True):
        base = 1.0
        eps = 1e-12  # far below f32 ulp at 1.0 (~1.2e-7)
        vals = np.array([base + i * eps for i in range(-40, 41)], np.float64)
        rng = np.random.default_rng(14)
        rng.shuffle(vals)
        x = jnp.asarray(vals)
        assert x.dtype == jnp.float64
        for k in [1, 3, 41, 80, 81]:
            want = np.partition(vals, k - 1)[k - 1]
            for method in ["cp", "binned"]:
                res = selection.order_statistic(x, k, method=method, cap=4)
                assert float(res.value) == want, (method, k)
        # explicit pallas request on f64 lands on the oracle: counts see
        # sub-f32 structure (an f32 kernel would collapse all ties onto y)
        y = jnp.float64(base + eps / 2)
        sp, sn, lt, le = ops.fused_partials(x, y, backend="pallas")
        assert int(lt) == int(np.sum(vals < base + eps / 2))
        assert int(le) == int(lt)
        from repro.kernels.ref import bin_edges
        edges64 = bin_edges(jnp.float64(base - 50 * eps),
                            jnp.float64(base + 50 * eps), 64)
        cnt, bsum = ops.fused_histogram(x, edges64, backend="pallas")
        assert bsum.dtype == jnp.float64
        assert int(jnp.sum(cnt)) == vals.size


# ---------------------------------------------------------------------------
# across-axis binned / auto (single-device mesh; multi-device in
# tests/_dist_worker.py)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("method", ["binned", "auto"])
def test_across_axis_binned_single_device(method):
    from jax.sharding import PartitionSpec as P

    from repro.core import _compat, distributed

    mesh = _compat.make_mesh((1,), ("data",))
    rng = np.random.default_rng(15)
    v = rng.standard_normal((1, 17)).astype(np.float32)

    def run(vl):
        return distributed.median_across_axis(vl, "data", method=method)

    got = _compat.shard_map(run, mesh=mesh, in_specs=P("data"),
                            out_specs=P("data"), check=False)(jnp.asarray(v))
    np.testing.assert_allclose(np.asarray(got)[0], v[0])


def test_sharded_binned_single_device():
    from jax.sharding import PartitionSpec as P

    from repro.core import _compat, distributed

    mesh = _compat.make_mesh((1,), ("data",))
    rng = np.random.default_rng(16)
    x = rng.standard_normal(10_000).astype(np.float32)
    for k in [1, 2500, 10_000]:
        res = distributed.sharded_order_statistic(
            jnp.asarray(x), k, mesh, P("data"), method="binned")
        assert np.float32(res.value) == np.partition(x, k - 1)[k - 1]
