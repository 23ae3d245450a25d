"""Weighted order statistics: differential tests against a numpy
sorted-cumsum oracle, plus the weighted-regression consumers.

Exactness contract under test: with exactly-summable weights (integers /
dyadic rationals with bounded total — including the uniform case) every
mass comparison is exact, so all engine methods must be BIT-IDENTICAL to
the oracle, and uniform weights must reproduce today's unweighted answers
exactly.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import robust, selection

jax.config.update("jax_platform_name", "cpu")


def weighted_oracle(x, w, wk):
    """Smallest element v with sum(w[x <= v]) >= wk, by sorted cumsum
    (f64 accumulation: the reference is order-independent for the
    exactly-summable weights the tests generate)."""
    o = np.argsort(x, kind="stable")
    xs, ws = np.asarray(x)[o], np.asarray(w)[o]
    c = np.cumsum(ws.astype(np.float64))
    i = np.searchsorted(c, wk, side="left")
    return xs[min(i, len(xs) - 1)]


def weighted_oracle_rows(x, w, wks):
    return np.array([weighted_oracle(x[i], w[i], wks[i])
                     for i in range(x.shape[0])], x.dtype)


ENGINE_METHODS = ["cp", "binned", "bisection", "sort"]


# ---------------------------------------------------------------------------
# scalar (B=1): uniform parity + integer-weight differential sweep
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("method", ENGINE_METHODS)
def test_uniform_weights_reproduce_unweighted(method):
    """w == 1, wk == k must give exactly order_statistic / np.partition."""
    rng = np.random.default_rng(0)
    n = 4097
    x = rng.standard_normal(n).astype(np.float32)
    w = jnp.ones((n,), jnp.float32)
    for k in [1, 2, n // 3, (n + 1) // 2, n - 1, n]:
        res = selection.weighted_order_statistic(
            jnp.asarray(x), w, float(k), method=method)
        want = np.partition(x, k - 1)[k - 1]
        assert np.float32(res.value) == want, (method, k)
        unw = selection.order_statistic(jnp.asarray(x), k).value
        assert np.float32(res.value) == np.float32(unw), (method, k)


@pytest.mark.parametrize("method", ENGINE_METHODS)
@pytest.mark.parametrize("n", [1, 2, 50, 1000, 20_000])
def test_integer_weights_match_oracle(method, n):
    rng = np.random.default_rng(n)
    x = (rng.integers(-(2**20), 2**20, n).astype(np.float32)) * 2.0**-10
    w = rng.integers(0, 8, n).astype(np.float32)
    w[0] = 1.0  # at least some mass
    W = w.sum()
    for frac in [0.0005, 0.25, 0.5, 0.9, 1.0]:
        wk = max(frac * W, 0.5)
        res = selection.weighted_order_statistic(
            jnp.asarray(x), jnp.asarray(w), wk, method=method, cap=16)
        assert np.float32(res.value) == weighted_oracle(x, w, wk), \
            (method, n, frac)
        assert int(res.status) != selection.NOT_CONVERGED


def test_duplicate_storm_and_zero_weights():
    """Tie blocks with zero-weight members: the answer skips massless
    elements exactly like the cumsum oracle."""
    rng = np.random.default_rng(1)
    x = np.repeat(np.array([1.0, 2.0, 3.0, 4.0], np.float32), 1000)
    w = np.tile(np.array([0.0, 1.0, 2.0, 1.0], np.float32), 1000)
    p = rng.permutation(4000)
    x, w = x[p], w[p]
    for frac in [0.01, 0.3, 0.5, 0.75, 0.99]:
        wk = frac * w.sum()
        for method in ["cp", "binned"]:
            res = selection.weighted_order_statistic(
                jnp.asarray(x), jnp.asarray(w), wk, method=method, cap=4)
            assert np.float32(res.value) == weighted_oracle(x, w, wk), \
                (frac, method)


def test_wk_edges_and_clipping():
    """wk <= 0 pins the minimum; wk > total mass clips to the maximum."""
    rng = np.random.default_rng(2)
    x = rng.standard_normal(1000).astype(np.float32)
    w = rng.integers(1, 4, 1000).astype(np.float32)
    lo = selection.weighted_order_statistic(
        jnp.asarray(x), jnp.asarray(w), 0.0)
    assert np.float32(lo.value) == x.min()
    hi = selection.weighted_order_statistic(
        jnp.asarray(x), jnp.asarray(w), 10.0 * w.sum())
    assert np.float32(hi.value) == x.max()


def test_weighted_extreme_magnitudes():
    """1e9-scale outliers: the binned sweeps localize mass without a
    transform, bit-exact vs the oracle."""
    rng = np.random.default_rng(3)
    n = 100_000
    x = rng.standard_normal(n).astype(np.float32)
    x[:4] = [1e9, -1e9, 3e8, -7e8]
    w = rng.integers(1, 3, n).astype(np.float32)
    wk = 0.5 * w.sum()
    for method in ["cp", "binned"]:
        res = selection.weighted_order_statistic(
            jnp.asarray(x), jnp.asarray(w), wk, method=method)
        assert np.float32(res.value) == weighted_oracle(x, w, wk), method


# ---------------------------------------------------------------------------
# rows mode + shared-x mode
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("method", ["cp", "binned", "sort"])
@pytest.mark.parametrize("b,n", [(1, 1000), (8, 4096), (33, 257)])
def test_weighted_rows_match_oracle(method, b, n):
    rng = np.random.default_rng(b * n)
    x = (rng.integers(-1000, 1000, (b, n))).astype(np.float32)
    w = rng.integers(0, 5, (b, n)).astype(np.float32)
    w[:, 0] = 1.0
    W = w.sum(axis=1)
    wks = (rng.uniform(0.05, 1.0, b) * W).astype(np.float32)
    res = selection.weighted_select_rows(
        jnp.asarray(x), jnp.asarray(w), jnp.asarray(wks), method=method,
        cap=8)
    np.testing.assert_array_equal(np.asarray(res.value),
                                  weighted_oracle_rows(x, w, wks))
    assert np.all(np.asarray(res.status) != selection.NOT_CONVERGED)


@pytest.mark.parametrize("method", ["cp", "binned", "sort"])
def test_weighted_shared_match_oracle(method):
    rng = np.random.default_rng(10)
    n = 30_000
    x = (rng.integers(-500, 500, n)).astype(np.float32)
    w = rng.integers(0, 4, n).astype(np.float32)
    w[0] = 1.0
    W = w.sum()
    wks = np.array([1e-3, 0.1, 0.25, 0.5, 0.75, 0.999, 1.0],
                   np.float32) * W
    res = selection.weighted_multi_order_statistic(
        jnp.asarray(x), jnp.asarray(w), jnp.asarray(wks), method=method,
        cap=8)
    want = np.array([weighted_oracle(x, w, t) for t in wks], np.float32)
    np.testing.assert_array_equal(np.asarray(res.value), want)


def test_weighted_quantiles_and_median_wrappers():
    rng = np.random.default_rng(11)
    n = 5000
    x = rng.standard_normal(n).astype(np.float32)
    w = rng.integers(1, 6, n).astype(np.float32)
    med = selection.weighted_median(jnp.asarray(x), jnp.asarray(w))
    assert np.float32(med.value) == weighted_oracle(x, w, 0.5 * w.sum())
    qs = [0.1, 0.5, 0.9]
    res = selection.weighted_quantiles(jnp.asarray(x), jnp.asarray(w), qs)
    want = np.array([weighted_oracle(x, w, q * w.sum()) for q in qs],
                    np.float32)
    np.testing.assert_array_equal(np.asarray(res.value), want)
    # uniform weights: weighted median == unweighted median exactly
    ones = jnp.ones((n,), jnp.float32)
    assert np.float32(selection.weighted_median(jnp.asarray(x), ones).value) \
        == np.float32(selection.median(jnp.asarray(x)).value)


def test_weighted_kernel_backend_parity():
    """Weighted solves driven by the Pallas (interpret) kernels match the
    jnp-oracle-driven solves bit for bit."""
    rng = np.random.default_rng(12)
    n = 4096
    x = (rng.integers(-1000, 1000, n)).astype(np.float32)
    w = rng.integers(0, 4, n).astype(np.float32)
    w[0] = 1.0
    wk = 0.4 * w.sum()
    for method in ["cp", "binned"]:
        a = selection.weighted_order_statistic(
            jnp.asarray(x), jnp.asarray(w), wk, method=method,
            backend="jnp")
        b = selection.weighted_order_statistic(
            jnp.asarray(x), jnp.asarray(w), wk, method=method,
            backend="pallas_interpret")
        assert np.float32(a.value) == np.float32(b.value), method
        assert np.float32(a.value) == weighted_oracle(x, w, wk), method


def test_weighted_x64_sub_f32_resolution():
    """f64 data/weights distinguishable only below f32 resolution: the
    dispatch reroutes to the dtype-preserving oracles and stays exact."""
    import jax.experimental

    with jax.enable_x64(True):
        base, eps = 1.0, 1e-12
        vals = np.array([base + i * eps for i in range(-30, 31)], np.float64)
        rng = np.random.default_rng(13)
        rng.shuffle(vals)
        w = rng.integers(1, 4, vals.size).astype(np.float64)
        for frac in [0.1, 0.5, 0.9]:
            wk = frac * w.sum()
            for method in ["cp", "binned"]:
                res = selection.weighted_order_statistic(
                    jnp.asarray(vals), jnp.asarray(w), wk, method=method,
                    cap=4)
                assert float(res.value) == weighted_oracle(vals, w, wk), \
                    (frac, method)


def test_weighted_binned_sweep_count():
    """The weighted binned descent keeps the ~3-sweep schedule at 1M."""
    rng = np.random.default_rng(14)
    n = 1 << 20
    x = rng.standard_normal(n).astype(np.float32)
    w = rng.integers(1, 3, n).astype(np.float32)
    res = selection.weighted_order_statistic(
        jnp.asarray(x), jnp.asarray(w), 0.5 * float(w.sum()),
        method="binned")
    assert np.float32(res.value) == weighted_oracle(x, w, 0.5 * w.sum())
    assert int(res.iters) <= 3, int(res.iters)


# ---------------------------------------------------------------------------
# distributed weighted selection (single-device mesh; multi-device in
# tests/_dist_worker.py)
# ---------------------------------------------------------------------------


def test_sharded_weighted_single_device():
    from jax.sharding import PartitionSpec as P

    from repro.core import _compat, distributed

    mesh = _compat.make_mesh((1,), ("data",))
    rng = np.random.default_rng(15)
    x = rng.standard_normal(10_000).astype(np.float32)
    w = rng.integers(0, 5, 10_000).astype(np.float32)
    w[0] = 1.0
    for frac in [0.01, 0.5, 0.99]:
        wk = frac * w.sum()
        res = distributed.sharded_weighted_order_statistic(
            jnp.asarray(x), jnp.asarray(w), wk, mesh, P("data"))
        assert np.float32(res.value) == weighted_oracle(x, w, wk), frac
    res = distributed.sharded_weighted_median(
        jnp.asarray(x), jnp.asarray(w), mesh, P("data"))
    assert np.float32(res.value) == weighted_oracle(x, w, 0.5 * w.sum())


# ---------------------------------------------------------------------------
# regression consumers: Theil-Sen + IRLS
# ---------------------------------------------------------------------------


def _contaminated_line(rng, n=200, frac=0.3, slope=2.5, intercept=-1.0):
    """30% of points moved onto an adversarial WRONG line (slope -10):
    slope-destroying contamination, not just an intercept shift."""
    x = rng.uniform(-5, 5, n).astype(np.float32)
    y = (slope * x + intercept
         + 0.01 * rng.standard_normal(n)).astype(np.float32)
    bad = rng.choice(n, size=int(frac * n), replace=False)
    y[bad] = (60.0 - 10.0 * x[bad]
              + rng.standard_normal(bad.size)).astype(np.float32)
    return x, y


def test_theil_sen_recovers_contaminated_line():
    """Acceptance bar: 30% gross contamination — Theil-Sen recovers the
    true slope, OLS does not."""
    rng = np.random.default_rng(16)
    x, y = _contaminated_line(rng)
    fit = robust.theil_sen_fit(jnp.asarray(x), jnp.asarray(y))
    assert abs(float(fit.slope) - 2.5) < 0.05
    assert abs(float(fit.intercept) + 1.0) < 0.2
    X = np.stack([np.ones_like(x), x], 1)
    ols = np.linalg.lstsq(X, y, rcond=None)[0]
    assert abs(ols[1] - 2.5) > 0.5  # OLS destroyed by the outliers
    np.testing.assert_array_equal(
        np.asarray(fit.theta),
        np.array([float(fit.intercept), float(fit.slope)], np.float32))


def test_theil_sen_uniform_weighting_and_clean_data():
    rng = np.random.default_rng(17)
    x = rng.uniform(0, 10, 100).astype(np.float32)
    y = (0.5 * x + 3.0).astype(np.float32)
    for weighting in ["sen", "uniform"]:
        fit = robust.theil_sen_fit(jnp.asarray(x), jnp.asarray(y),
                                   weighting=weighting)
        assert abs(float(fit.slope) - 0.5) < 1e-4, weighting
        assert abs(float(fit.intercept) - 3.0) < 1e-3, weighting


@pytest.mark.parametrize("loss", ["huber", "tukey"])
def test_irls_recovers_contaminated_line(loss):
    rng = np.random.default_rng(18)
    x, y = _contaminated_line(rng)
    X = jnp.asarray(np.stack([np.ones_like(x), x], 1))
    fit = robust.irls_fit(X, jnp.asarray(y), loss=loss)
    assert abs(float(fit.theta[1]) - 2.5) < 0.05, (loss, fit.theta)
    assert abs(float(fit.theta[0]) + 1.0) < 0.2, (loss, fit.theta)
    # outliers end up down-weighted, inliers keep weight ~1
    wts = np.asarray(fit.weights)
    r = np.abs(np.asarray(X) @ np.asarray(fit.theta) - y)
    assert wts[np.argsort(r)[: 100]].min() > 0.5
    assert wts[np.argmax(r)] < 0.1
    assert float(fit.scale) > 0


def test_irls_clean_data_matches_ls():
    rng = np.random.default_rng(19)
    n = 150
    x = rng.uniform(-2, 2, n).astype(np.float32)
    y = (1.5 * x + 0.25).astype(np.float32)
    X = jnp.asarray(np.stack([np.ones_like(x), x], 1))
    for loss in ["huber", "tukey"]:
        fit = robust.irls_fit(X, jnp.asarray(y), loss=loss)
        assert abs(float(fit.theta[1]) - 1.5) < 1e-3
        assert abs(float(fit.theta[0]) - 0.25) < 1e-3
