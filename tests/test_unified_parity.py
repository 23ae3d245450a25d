"""Unified-engine parity suite (the PR-4 refactor contract).

One engine now serves both measures: counts are the exact-integer
specialization of weight mass (``objective.py``), and exactly one bracket
loop / binned loop / compaction / finalize chain remains in
``core.selection``.  These tests pin the refactor's behavioral contract:

* the counting path reproduces ``np.partition`` bit-for-bit across methods
  {cp, binned, binned_polish}, backends {jnp, pallas_interpret} and dtypes
  {f32, f64} — including the certificate stress shapes (tie storms, ulp
  clusters) from ``test_certificates.py``;
* uniform weights with ``wk = k`` reproduce the counting path bit-for-bit
  (measure comparisons become exact integer-valued comparisons);
* exactly-summable integer weights reproduce the f64 sorted-cumsum oracle
  bit-for-bit on every method;
* every EXACT_HIT the engine reports survives an independent recount of
  its measure invariant (the fail-safe contract transfers to the unified
  loops and to the polish).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import robust, selection

jax.config.update("jax_platform_name", "cpu")

METHODS = ["cp", "binned", "binned_polish"]


def _cases(rng, n=4000):
    """Adversarial data shapes: smooth, heavy-tailed, dup-storms, extremes,
    near-constant."""
    half = n // 2
    return [
        rng.standard_normal(n).astype(np.float32),
        rng.lognormal(0, 6, n).astype(np.float32),
        rng.integers(0, 4, n).astype(np.float32),
        np.full(n, -3.25, np.float32),
        np.concatenate([np.full(n - 2, -1e38), [0.0], [1e38]]
                       ).astype(np.float32),
        np.concatenate([rng.standard_normal(half),
                        np.full(n - half, 0.5)]).astype(np.float32),
    ]


def _weighted_oracle(x, w, wk):
    o = np.argsort(x, kind="stable")
    c = np.cumsum(w[o].astype(np.float64))
    return x[o][min(np.searchsorted(c, wk, "left"), x.size - 1)]


def _assert_exact_hit_verified(x, w, kk, res):
    """Any EXACT_HIT must satisfy an independently recounted measure
    invariant (w=None: counts; else masses)."""
    v = np.float32(res.value)
    if int(res.status) != selection.EXACT_HIT:
        return
    if w is None:
        m_lt, m_le = int((x < v).sum()), int((x <= v).sum())
    else:
        m_lt = float(w[x < v].sum())
        m_le = float(w[x <= v].sum())
    assert m_lt < kk <= m_le, (kk, v, m_lt, m_le)


# ---------------------------------------------------------------------------
# counting path: np.partition parity across methods x backends
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("method", METHODS)
def test_counting_parity_vs_partition(method):
    rng = np.random.default_rng(40)
    for x in _cases(rng):
        n = x.size
        for k in [1, 2, n // 3, (n + 1) // 2, n - 1, n]:
            res = selection.order_statistic(jnp.asarray(x), k,
                                            method=method)
            np.testing.assert_equal(np.float32(res.value),
                                    np.partition(x, k - 1)[k - 1])
            _assert_exact_hit_verified(x, None, k, res)


@pytest.mark.parametrize("method", METHODS)
def test_counting_parity_interpret_backend(method):
    """The Pallas-interpret backend (TPU kernel emulation) must agree with
    the jnp oracle backend on the unified loops (smaller n: interpret mode
    is a Python emulator)."""
    rng = np.random.default_rng(41)
    x = np.concatenate([rng.standard_normal(1500),
                        rng.integers(0, 3, 500).astype(np.float64)]
                       ).astype(np.float32)
    n = x.size
    for k in [1, n // 4, (n + 1) // 2, n]:
        want = np.partition(x, k - 1)[k - 1]
        for backend in ["jnp", "pallas_interpret"]:
            res = selection.order_statistic(
                jnp.asarray(x), k, method=method, backend=backend,
                nbins=32)
            np.testing.assert_equal(np.float32(res.value), want, err_msg=f"{method}/{backend}/k={k}")


@pytest.mark.parametrize("method", METHODS)
def test_rows_and_shared_modes_parity(method):
    rng = np.random.default_rng(42)
    xb = rng.standard_normal((6, 3000)).astype(np.float32)
    ks = np.array([1, 5, 700, 1500, 2999, 3000], np.int32)
    res = selection.select_rows(jnp.asarray(xb), jnp.asarray(ks),
                                method=method)
    want = np.take_along_axis(np.sort(xb, axis=1), ks[:, None] - 1,
                              axis=1)[:, 0]
    np.testing.assert_array_equal(np.asarray(res.value), want)

    x = xb[0]
    resm = selection.multi_order_statistic(jnp.asarray(x), jnp.asarray(ks),
                                           method=method)
    wantm = np.sort(x)[ks - 1]
    np.testing.assert_array_equal(np.asarray(resm.value), wantm)


@pytest.mark.parametrize("method", ["binned", "binned_polish"])
def test_x64_sub_f32_resolution(method):
    """f64 data whose gaps vanish at f32 resolution: the ops-layer reroute
    must keep the unified binned loops exact under x64."""
    with jax.enable_x64(True):
        base = np.float64(1.0)
        x = base + np.arange(2000, dtype=np.float64) * 1e-12
        rng = np.random.default_rng(43)
        rng.shuffle(x)
        for k in [1, 700, 1999, 2000]:
            res = selection.order_statistic(jnp.asarray(x), k,
                                            method=method)
            np.testing.assert_equal(np.float64(res.value),
                                    np.partition(x, k - 1)[k - 1])


# ---------------------------------------------------------------------------
# uniform weights == counting path, bit for bit
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("method", METHODS)
def test_uniform_weights_reproduce_counting_path(method):
    rng = np.random.default_rng(44)
    for x in _cases(rng, n=2500):
        n = x.size
        ones = jnp.ones((n,), jnp.float32)
        for k in [1, n // 3, (n + 1) // 2, n]:
            a = selection.order_statistic(jnp.asarray(x), k, method=method)
            b = selection.weighted_order_statistic(
                jnp.asarray(x), ones, float(k), method=method)
            np.testing.assert_equal(np.float32(b.value),
                                    np.float32(a.value))
            _assert_exact_hit_verified(x, np.ones(n, np.float32),
                                       float(k), b)


# ---------------------------------------------------------------------------
# exactly-summable weights == f64 sorted-cumsum oracle, bit for bit
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("method", METHODS)
def test_integer_weights_vs_sorted_cumsum_oracle(method):
    rng = np.random.default_rng(45)
    for x in _cases(rng, n=2500):
        n = x.size
        w = rng.integers(0, 5, n).astype(np.float32)
        w[0] = 1.0
        W = float(w.sum())
        for frac in [0.01, 0.33, 0.5, 0.999]:
            wk = float(np.float32(max(frac * W, 0.5)))
            res = selection.weighted_order_statistic(
                jnp.asarray(x), jnp.asarray(w), wk, method=method)
            np.testing.assert_equal(np.float32(res.value),
                                    _weighted_oracle(x, w, wk))
            _assert_exact_hit_verified(x, w, wk, res)
            assert int(res.status) != selection.NOT_CONVERGED


# ---------------------------------------------------------------------------
# certificate stress shapes under the polish (the fail-safe gates carry
# over: tie storms + ulp clusters with adversarially tiny caps)
# ---------------------------------------------------------------------------


def test_polish_tie_storm_exact_hits_verified():
    rng = np.random.default_rng(46)
    n = 5000
    storms = [
        rng.integers(0, 3, n).astype(np.float32),
        np.full(n, 2.5, np.float32),
        np.concatenate([np.full(n - 2, -1e9), [0.0], [1e9]]
                       ).astype(np.float32),
    ]
    for x in storms:
        for k in [1, 2, (n + 1) // 2, n - 1, n]:
            res = selection.order_statistic(
                jnp.asarray(x), k, method="binned_polish", cap=4, nbins=8)
            np.testing.assert_equal(np.float32(res.value),
                                    np.partition(x, k - 1)[k - 1])
            _assert_exact_hit_verified(x, None, k, res)


def test_polish_ulp_cluster_and_ftz_floor():
    """Ulp-collapsed brackets and the FTZ floor: the polish must inherit
    the stall gates — honest statuses, never a minted certificate."""
    rng = np.random.default_rng(47)
    for base in [np.float32(1.0), np.float32(-255.1234),
                 np.float32(1.2e-38)]:
        levels = [base]
        for _ in range(3):
            levels.append(np.nextafter(levels[-1], np.float32(np.inf),
                                       dtype=np.float32))
        x = np.asarray(levels, np.float32)[rng.integers(0, 4, 4000)]
        n = x.size
        for k in [1, n // 4, (n + 1) // 2, n]:
            want = np.partition(x, k - 1)[k - 1]
            res = selection.order_statistic(jnp.asarray(x), k,
                                            method="binned_polish")
            np.testing.assert_equal(np.float32(res.value), want)
            _assert_exact_hit_verified(x, None, k, res)
            # undersized cap: fail-safe statuses only
            res = selection.order_statistic(jnp.asarray(x), k,
                                            method="binned_polish", cap=2)
            _assert_exact_hit_verified(x, None, k, res)
            if int(res.status) != selection.NOT_CONVERGED:
                np.testing.assert_equal(np.float32(res.value), want)


def test_polish_weighted_stress():
    rng = np.random.default_rng(48)
    n = 4000
    x = rng.integers(-20, 20, n).astype(np.float32) * 0.5
    w = rng.integers(0, 3, n).astype(np.float32)
    w[0] = 1.0
    W = float(w.sum())
    for frac in [0.001, 0.5, 0.999]:
        wk = float(np.float32(max(frac * W, 0.5)))
        res = selection.weighted_order_statistic(
            jnp.asarray(x), jnp.asarray(w), wk, method="binned_polish",
            cap=4)
        np.testing.assert_equal(np.float32(res.value),
                                _weighted_oracle(x, w, wk))
        _assert_exact_hit_verified(x, w, wk, res)


def test_polish_log1p_transform_roundtrip():
    """The polish runs in the transformed domain too; the count-preserving
    map-back + original-space finalize must stay exact."""
    rng = np.random.default_rng(49)
    x = np.exp(rng.uniform(-40, 80, 3000)).astype(np.float32)
    n = x.size
    for k in [1, n // 2, n]:
        res = selection.order_statistic(
            jnp.asarray(x), k, method="binned_polish", transform="log1p")
        np.testing.assert_equal(np.float32(res.value),
                                np.partition(x, k - 1)[k - 1])


# ---------------------------------------------------------------------------
# polish telemetry: the CP-centered edges must not COST sweeps
# ---------------------------------------------------------------------------


def test_polish_sweep_count_no_worse_than_binned():
    rng = np.random.default_rng(50)
    for gen in [lambda: rng.standard_normal(1 << 17),
                lambda: rng.lognormal(0, 8, 1 << 17)]:
        x = gen().astype(np.float32)
        k = (x.size + 1) // 2
        plain = selection.select_rows(jnp.asarray(x)[None, :], k,
                                      method="binned")
        pol = selection.select_rows(jnp.asarray(x)[None, :], k,
                                    method="binned_polish")
        want = np.partition(x, k - 1)[k - 1]
        np.testing.assert_equal(np.float32(plain.value[0]), want)
        np.testing.assert_equal(np.float32(pol.value[0]), want)
        assert int(pol.iters[0]) <= int(plain.iters[0])


# ---------------------------------------------------------------------------
# Theil-Sen blocked pair-subsample mode
# ---------------------------------------------------------------------------


def test_theil_sen_blocked_equals_full_on_small_n():
    """max_pairs >= n(n-1) enumerates every ordered pair exactly once; the
    (slope, weight) multiset then matches the full (n, n) matrix (whose
    diagonal carries weight 0), so the two modes agree exactly (integer x
    grid: pair weights |dx| sum exactly in any order)."""
    rng = np.random.default_rng(51)
    n = 48
    x = np.arange(n, dtype=np.float32)
    y = 2.5 * x - 3.0 + 0.25 * rng.integers(-2, 3, n).astype(np.float32)
    full = robust.theil_sen_fit(jnp.asarray(x), jnp.asarray(y))
    blocked = robust.theil_sen_fit(jnp.asarray(x), jnp.asarray(y),
                                   max_pairs=n * (n - 1))
    np.testing.assert_equal(np.float32(blocked.slope),
                            np.float32(full.slope))
    np.testing.assert_equal(np.float32(blocked.intercept),
                            np.float32(full.intercept))


def test_theil_sen_subsampled_recovers_slope_under_contamination():
    """The O(max_pairs)-memory mode keeps the robustness story: exact slope
    recovery at 30% slope-destroying contamination with ~25x fewer pairs
    than the full matrix."""
    rng = np.random.default_rng(52)
    n = 400
    x = rng.standard_normal(n).astype(np.float32)
    y = (4.0 * x + 1.0).astype(np.float32)
    bad = rng.choice(n, int(0.3 * n), replace=False)
    y[bad] = rng.standard_normal(bad.size).astype(np.float32) * 50.0
    fit = robust.theil_sen_fit(jnp.asarray(x), jnp.asarray(y),
                               max_pairs=n * 16)
    assert abs(float(fit.slope) - 4.0) < 0.05
    assert abs(float(fit.intercept) - 1.0) < 0.2


def _jaxpr_shapes(jaxpr, acc):
    """All intermediate shapes, recursing into pjit/scan/cond sub-jaxprs."""
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            if hasattr(v.aval, "shape"):
                acc.add(tuple(v.aval.shape))
        for val in eqn.params.values():
            sub = getattr(val, "jaxpr", None)
            if sub is not None:
                _jaxpr_shapes(sub, acc)
    return acc


def test_theil_sen_blocked_never_materializes_nxn():
    """Shape check on the traced computation (recursing through the jit
    call boundary): with max_pairs << n^2 the largest intermediate is
    (p, n), p = max_pairs // n — the (n, n) slope matrix never exists."""
    n, max_pairs = 256, 1024
    x = jnp.arange(n, dtype=jnp.float32)
    y = 2.0 * x
    jaxpr = jax.make_jaxpr(
        lambda a, b: robust.theil_sen_fit(a, b, max_pairs=max_pairs)
    )(x, y)
    shapes = _jaxpr_shapes(jaxpr.jaxpr, set())
    assert any(s[0] * s[1] >= n for s in shapes if len(s) == 2), shapes
    biggest = max((int(np.prod(s)) for s in shapes), default=0)
    assert 0 < biggest < n * n, (biggest, sorted(shapes)[-5:])


def test_theil_sen_full_coverage_blocked_branch_is_taken():
    """max_pairs == n(n-1) must route through the BLOCKED branch (offsets
    1..n-1, a (n-1, n) block) — the regime where the offset schedule
    enumerates every ordered pair and the equality test above is
    meaningful, not a second run of the full-matrix branch."""
    n = 48
    jaxpr = jax.make_jaxpr(
        lambda a, b: robust.theil_sen_fit(a, b, max_pairs=n * (n - 1))
    )(jnp.arange(n, dtype=jnp.float32), jnp.arange(n, dtype=jnp.float32))
    shapes = _jaxpr_shapes(jaxpr.jaxpr, set())
    assert (n - 1, n) in shapes, sorted(shapes)[-5:]
    assert (n, n) not in shapes
