"""Property-based differential suite: hypothesis strategies drive every
public selection API against ``np.partition`` / a numpy weighted oracle and
assert BIT-EXACTNESS, not closeness.

Strategy notes (shared with tests/test_property.py): float values are
derived from integer strategies (scaled by powers of two) because XLA:CPU
runs with FTZ/fast-math processor flags that trip hypothesis's strict
float-bound validation — and because integer-derived dyadic floats maximize
tie coverage (the hardest case for selection) while keeping every weight
mass EXACTLY summable, which is what makes bit-exact weighted comparisons
well-defined.  ``scale_exp`` stretches magnitudes from denormal-adjacent
(2^-30) to ±inf-adjacent (2^97 * 2^20 ~ 1.6e35, within a few octaves of
f32 max), covering the overflow-safe bin-edge and log1p regimes.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core import selection  # noqa: E402

jax.config.update("jax_platform_name", "cpu")


def to_f32(ints, scale_exp=0):
    x = np.asarray(ints, np.float64) * (2.0 ** (scale_exp - 10))
    return x.astype(np.float32)


def weighted_oracle(x, w, wk):
    """Smallest element v with sum(w[x <= v]) >= wk (f64 sorted cumsum —
    order-independent for the exactly-summable weights generated here)."""
    o = np.argsort(x, kind="stable")
    xs, ws = np.asarray(x)[o], np.asarray(w)[o]
    c = np.cumsum(ws.astype(np.float64))
    i = np.searchsorted(c, wk, side="left")
    return xs[min(i, len(xs) - 1)]


ints_small = st.lists(st.integers(-(2**20), 2**20), min_size=1, max_size=300)
# duplicate-heavy: values drawn from a handful of levels
ints_dupes = st.lists(st.integers(-4, 4), min_size=1, max_size=300)
scale_exps = st.integers(min_value=-20, max_value=97)  # denormal..inf-adjacent
methods = st.sampled_from(["cp", "binned", "bisection"])


# ---------------------------------------------------------------------------
# unweighted: order_statistic / select_rows / multi_order_statistic
# ---------------------------------------------------------------------------


@settings(max_examples=60, deadline=None)
@given(ints=ints_small, scale_exp=scale_exps,
       kf=st.integers(min_value=0, max_value=1000), method=methods)
def test_order_statistic_bit_exact(ints, scale_exp, kf, method):
    x = to_f32(ints, scale_exp)
    n = x.size
    k = max(1, min(n, 1 + (kf * n) // 1001))
    expected = np.partition(x, k - 1)[k - 1]
    res = selection.order_statistic(jnp.asarray(x), k, method=method,
                                    maxit=256, cap=8)
    np.testing.assert_equal(np.float32(res.value), expected)


@settings(max_examples=40, deadline=None)
@given(ints=ints_dupes, scale_exp=scale_exps,
       kf=st.integers(min_value=0, max_value=1000))
def test_order_statistic_duplicate_storms(ints, scale_exp, kf):
    """Handfuls of levels (ties dominate) across the magnitude range."""
    x = to_f32(ints, scale_exp)
    n = x.size
    k = max(1, min(n, 1 + (kf * n) // 1001))
    expected = np.partition(x, k - 1)[k - 1]
    for method in ["cp", "binned"]:
        res = selection.order_statistic(jnp.asarray(x), k, method=method,
                                        maxit=256, cap=4)
        np.testing.assert_equal(np.float32(res.value), expected)


@settings(max_examples=30, deadline=None)
@given(
    ints=st.lists(st.integers(-(2**16), 2**16), min_size=4, max_size=120),
    b=st.integers(min_value=1, max_value=6),
    scale_exp=scale_exps,
    method=st.sampled_from(["cp", "binned"]),
    data=st.data(),
)
def test_select_rows_bit_exact(ints, b, scale_exp, method, data):
    base = to_f32(ints, scale_exp)
    n = base.size
    rng = np.random.default_rng(abs(hash((tuple(ints), b))) % (2**31))
    x = np.stack([rng.permutation(base) for _ in range(b)])
    ks = np.asarray(
        data.draw(st.lists(st.integers(1, n), min_size=b, max_size=b)),
        np.int32)
    res = selection.select_rows(jnp.asarray(x), jnp.asarray(ks),
                                method=method, cap=8, maxit=256)
    want = np.array([np.partition(x[i], ks[i] - 1)[ks[i] - 1]
                     for i in range(b)], np.float32)
    np.testing.assert_array_equal(np.asarray(res.value), want)


@settings(max_examples=30, deadline=None)
@given(
    ints=st.lists(st.integers(-(2**18), 2**18), min_size=2, max_size=200),
    scale_exp=scale_exps,
    data=st.data(),
)
def test_multi_order_statistic_bit_exact(ints, scale_exp, data):
    x = to_f32(ints, scale_exp)
    n = x.size
    ks = np.asarray(
        data.draw(st.lists(st.integers(1, n), min_size=1, max_size=6)),
        np.int32)
    for method in ["cp", "binned"]:
        res = selection.multi_order_statistic(
            jnp.asarray(x), jnp.asarray(ks), method=method, cap=8,
            maxit=256)
        want = np.partition(x, ks - 1)[ks - 1]
        np.testing.assert_array_equal(np.asarray(res.value), want)


@settings(max_examples=25, deadline=None)
@given(
    ints=st.lists(st.integers(0, 2**30), min_size=4, max_size=200),
    scale_exp=st.integers(min_value=0, max_value=60),
    kf=st.integers(min_value=0, max_value=1000),
)
def test_log1p_transform_bit_exact(ints, scale_exp, kf):
    """The monotone guard stays exact on huge-range data, both methods."""
    x = to_f32(ints, scale_exp)
    n = x.size
    k = max(1, min(n, 1 + (kf * n) // 1001))
    expected = np.partition(x, k - 1)[k - 1]
    for method in ["cp", "binned"]:
        res = selection.order_statistic(jnp.asarray(x), k, method=method,
                                        transform="log1p", maxit=256, cap=8)
        np.testing.assert_equal(np.float32(res.value), expected)


# ---------------------------------------------------------------------------
# weighted APIs vs the numpy weighted oracle
# ---------------------------------------------------------------------------


@settings(max_examples=60, deadline=None)
@given(
    ints=ints_small,
    scale_exp=scale_exps,
    wf=st.integers(min_value=0, max_value=1000),
    method=st.sampled_from(["cp", "binned", "sort"]),
    data=st.data(),
)
def test_weighted_order_statistic_bit_exact(ints, scale_exp, wf, method,
                                            data):
    x = to_f32(ints, scale_exp)
    n = x.size
    w = np.asarray(
        data.draw(st.lists(st.integers(0, 7), min_size=n, max_size=n)),
        np.float32)
    w[0] = max(w[0], 1.0)  # some mass
    W = float(w.sum())
    # the target mass must be the SAME f32 value on both sides of the
    # differential (the engine compares masses in f32; a python-float wk
    # could round across an integer mass boundary)
    wk = float(np.float32(max(W * wf / 1000.0, 0.5)))
    res = selection.weighted_order_statistic(
        jnp.asarray(x), jnp.asarray(w), wk, method=method, maxit=256,
        cap=8)
    np.testing.assert_equal(np.float32(res.value),
                            weighted_oracle(x, w, wk))


@settings(max_examples=40, deadline=None)
@given(ints=ints_small, scale_exp=scale_exps,
       kf=st.integers(min_value=1, max_value=1000))
def test_weighted_uniform_equals_unweighted(ints, scale_exp, kf):
    """The property the whole weighted stack hangs on: w == 1, wk == k
    reproduces the unweighted engine bit for bit."""
    x = to_f32(ints, scale_exp)
    n = x.size
    k = max(1, min(n, 1 + (kf * n) // 1001))
    ones = jnp.ones((n,), jnp.float32)
    for method in ["cp", "binned"]:
        a = selection.weighted_order_statistic(
            jnp.asarray(x), ones, float(k), method=method, maxit=256,
            cap=8)
        b = selection.order_statistic(jnp.asarray(x), k, method=method,
                                      maxit=256, cap=8)
        np.testing.assert_equal(np.float32(a.value), np.float32(b.value))
        np.testing.assert_equal(np.float32(a.value),
                                np.partition(x, k - 1)[k - 1])


@settings(max_examples=30, deadline=None)
@given(
    ints=ints_dupes,
    scale_exp=scale_exps,
    wf=st.integers(min_value=0, max_value=1000),
    data=st.data(),
)
def test_weighted_duplicate_storm_with_zero_mass(ints, scale_exp, wf, data):
    """Tie blocks where some members carry zero weight: the answer must
    skip massless elements exactly like the oracle."""
    x = to_f32(ints, scale_exp)
    n = x.size
    w = np.asarray(
        data.draw(st.lists(st.integers(0, 2), min_size=n, max_size=n)),
        np.float32)
    w[0] = max(w[0], 1.0)
    wk = float(np.float32(max(float(w.sum()) * wf / 1000.0, 0.5)))
    for method in ["cp", "binned"]:
        res = selection.weighted_order_statistic(
            jnp.asarray(x), jnp.asarray(w), wk, method=method, maxit=256,
            cap=4)
        np.testing.assert_equal(np.float32(res.value),
                                weighted_oracle(x, w, wk))


@settings(max_examples=25, deadline=None)
@given(
    ints=st.lists(st.integers(-(2**16), 2**16), min_size=4, max_size=120),
    b=st.integers(min_value=1, max_value=5),
    scale_exp=scale_exps,
    data=st.data(),
)
def test_weighted_select_rows_bit_exact(ints, b, scale_exp, data):
    base = to_f32(ints, scale_exp)
    n = base.size
    rng = np.random.default_rng(abs(hash((tuple(ints), b, 7))) % (2**31))
    x = np.stack([rng.permutation(base) for _ in range(b)])
    w = rng.integers(0, 5, (b, n)).astype(np.float32)
    w[:, 0] = np.maximum(w[:, 0], 1.0)
    fracs = np.asarray(
        data.draw(st.lists(st.integers(1, 1000), min_size=b, max_size=b)),
        np.float64)
    wks = np.maximum(w.sum(1) * fracs / 1000.0, 0.5).astype(np.float32)
    res = selection.weighted_select_rows(
        jnp.asarray(x), jnp.asarray(w), jnp.asarray(wks), method="binned",
        maxit=256, cap=8)
    want = np.array([weighted_oracle(x[i], w[i], wks[i]) for i in range(b)],
                    np.float32)
    np.testing.assert_array_equal(np.asarray(res.value), want)


@settings(max_examples=25, deadline=None)
@given(
    ints=st.lists(st.integers(-(2**18), 2**18), min_size=2, max_size=150),
    scale_exp=scale_exps,
    data=st.data(),
)
def test_weighted_multi_order_statistic_bit_exact(ints, scale_exp, data):
    x = to_f32(ints, scale_exp)
    n = x.size
    rng = np.random.default_rng(abs(hash(tuple(ints))) % (2**31))
    w = rng.integers(0, 4, n).astype(np.float32)
    w[0] = max(w[0], 1.0)
    fracs = data.draw(st.lists(st.integers(0, 1000), min_size=1,
                               max_size=5))
    wks = np.maximum(np.asarray(fracs, np.float64) / 1000.0 * w.sum(),
                     0.5).astype(np.float32)
    for method in ["cp", "binned"]:
        res = selection.weighted_multi_order_statistic(
            jnp.asarray(x), jnp.asarray(w), jnp.asarray(wks),
            method=method, maxit=256, cap=8)
        want = np.array([weighted_oracle(x, w, t) for t in wks], np.float32)
        np.testing.assert_array_equal(np.asarray(res.value), want)


# ---------------------------------------------------------------------------
# polish_edges: direct property coverage (previously only end-to-end)
# ---------------------------------------------------------------------------


@settings(max_examples=80, deadline=None)
@given(
    a=st.integers(-(2**20), 2**20),
    b=st.integers(-(2**20), 2**20),
    scale_exp=scale_exps,
    degen=st.sampled_from(["none", "collapsed", "ulp", "inf_adjacent"]),
    tkind=st.sampled_from(["inside", "below", "above", "nan", "inf"]),
    tq=st.integers(min_value=0, max_value=1000),
    nbins=st.sampled_from([2, 3, 4, 8, 128]),
)
def test_polish_edges_contract(a, b, scale_exp, degen, tkind, tq, nbins):
    """The realized-edge contract of ``polish_edges``, pinned directly:
    monotone-sorted output of ``nbins + 1`` values, ``e_0 == lo`` and
    ``e_nbins == hi`` EXACTLY, every value a realized fp number inside
    ``[lo, hi]`` — under degenerate brackets (lo == hi, ulp-wide,
    ±inf-adjacent) and degenerate cuts (outside the bracket, NaN, inf),
    which the engine feeds it whenever a bin's centroid is garbage."""
    lo, hi = np.sort(to_f32([min(a, b), max(a, b)], scale_exp))
    if degen == "collapsed":
        hi = lo
    elif degen == "ulp":
        hi = np.nextafter(lo, np.float32(np.inf))
    elif degen == "inf_adjacent":
        lo = np.float32(-3.4e38)
        hi = np.float32(3.4e38)
    if tkind == "inside":
        t = np.float32(lo + (np.float64(hi) - np.float64(lo)) * tq / 1000.0)
    elif tkind == "below":
        # f64 intermediate: the f32 cast may overflow to -inf, which is a
        # legitimate garbage-cut input the clamp must absorb
        with np.errstate(over="ignore"):
            t = np.float32(np.float64(lo) - abs(np.float64(lo)) - 1.0)
    elif tkind == "above":
        with np.errstate(over="ignore"):
            t = np.float32(np.float64(hi) + abs(np.float64(hi)) + 1.0)
    elif tkind == "nan":
        t = np.float32(np.nan)
    else:
        t = np.float32(np.inf)
    ej = selection.polish_edges(
        jnp.asarray(lo), jnp.asarray(hi), jnp.asarray(t), nbins)
    e = np.asarray(ej)
    assert e.shape == (nbins + 1,)
    assert np.all(np.isfinite(e)), e
    # monotone under the PLATFORM's comparison semantics (the ones the
    # histogram pass and descent step actually use): on FTZ hardware
    # denormal-scale edges compare DAZ-equal, which numpy would misread
    assert bool(jnp.all(ej[1:] >= ej[:-1])), "edges must be monotone-sorted"
    # exact endpoint anchoring: the descent step and the finalize compare
    # against e_0/e_nbins as the bracket itself
    assert e[0] == lo and e[-1] == hi, (e[0], e[-1], lo, hi)
    assert bool(jnp.all(ej >= lo)) and bool(jnp.all(ej <= hi))
    # realized values: the array IS the fp truth (f32 round-trip identity)
    np.testing.assert_array_equal(e, e.astype(np.float32))


@settings(max_examples=40, deadline=None)
@given(
    ints=ints_small,
    scale_exp=scale_exps,
    kf=st.integers(min_value=0, max_value=1000),
    impl=st.sampled_from(["searchsorted", "arithmetic"]),
)
def test_binned_polish_bit_exact_both_impls(ints, scale_exp, kf, impl):
    """binned_polish rides hypothesis data through both slotting impls —
    the polish must stay np.partition-exact whatever edges it places."""
    x = to_f32(ints, scale_exp)
    n = x.size
    k = max(1, min(n, 1 + (kf * n) // 1001))
    expected = np.partition(x, k - 1)[k - 1]
    res = selection.order_statistic(jnp.asarray(x), k,
                                    method="binned_polish",
                                    binned_impl=impl, maxit=256, cap=8)
    np.testing.assert_equal(np.float32(res.value), expected)


@settings(max_examples=20, deadline=None)
@given(
    ints=st.lists(st.integers(-(2**20), 2**20), min_size=1, max_size=200),
    use_f64=st.booleans(),
    wf=st.integers(min_value=0, max_value=1000),
)
def test_weighted_dtype_sweep(ints, use_f64, wf):
    """dtype leg: f32 vs (rerouted, dtype-preserving) f64 both bit-exact."""
    import jax.experimental

    x32 = to_f32(ints)
    n = x32.size
    rng = np.random.default_rng(abs(hash(tuple(ints))) % (2**31))
    w32 = rng.integers(1, 5, n).astype(np.float32)
    wk = float(np.float32(max(float(w32.sum()) * wf / 1000.0, 0.5)))
    if use_f64:
        with jax.enable_x64(True):
            x = x32.astype(np.float64)
            w = w32.astype(np.float64)
            res = selection.weighted_order_statistic(
                jnp.asarray(x), jnp.asarray(w), wk, method="binned",
                maxit=256, cap=8)
            np.testing.assert_equal(float(res.value),
                                    float(weighted_oracle(x, w, wk)))
    else:
        res = selection.weighted_order_statistic(
            jnp.asarray(x32), jnp.asarray(w32), wk, method="binned",
            maxit=256, cap=8)
        np.testing.assert_equal(np.float32(res.value),
                                weighted_oracle(x32, w32, wk))


# ---------------------------------------------------------------------------
# warm-start prior leg (PR 10): arbitrary-prior invariance + sweep economy
# ---------------------------------------------------------------------------

# priors drawn INDEPENDENTLY of the data: special values + dyadic floats
weird_floats = st.one_of(
    st.sampled_from([float("nan"), float("inf"), float("-inf"), 0.0, -0.0]),
    st.integers(-(2**20), 2**20).map(lambda i: i * 2.0 ** -10),
    st.integers(-(2**20), 2**20).map(lambda i: i * 2.0 ** 20),
)


def _mk_prior(pv, plo, phi, pcut):
    return selection.Prior(
        value=jnp.asarray(np.float32(pv)), y_lo=jnp.asarray(np.float32(plo)),
        y_hi=jnp.asarray(np.float32(phi)), cut=jnp.asarray(np.float32(pcut)))


@settings(max_examples=60, deadline=None)
@given(ints=ints_small, scale_exp=scale_exps,
       kf=st.integers(min_value=0, max_value=1000), method=methods,
       pv=weird_floats, plo=weird_floats, phi=weird_floats,
       pcut=weird_floats)
def test_arbitrary_prior_invariance(ints, scale_exp, kf, method,
                                    pv, plo, phi, pcut):
    """The result is pinned to ``np.partition`` for EVERY prior — the
    prior only steers edge placement, never the answer."""
    x = to_f32(ints, scale_exp)
    n = x.size
    k = max(1, min(n, 1 + (kf * n) // 1001))
    expected = np.partition(x, k - 1)[k - 1]
    res = selection.order_statistic(
        jnp.asarray(x), k, method=method, maxit=256, cap=8,
        prior=_mk_prior(pv, plo, phi, pcut))
    np.testing.assert_equal(np.float32(res.value), expected)
    assert int(res.status) != selection.NOT_CONVERGED


@settings(max_examples=40, deadline=None)
@given(ints=ints_dupes, scale_exp=scale_exps,
       wf=st.integers(min_value=0, max_value=1000),
       pv=weird_floats, pcut=weird_floats, data=st.data())
def test_arbitrary_prior_invariance_weighted(ints, scale_exp, wf, pv, pcut,
                                             data):
    """Weighted leg pinned to the f64 sorted-cumsum oracle under arbitrary
    priors, on duplicate-storm data (the hardest tie case)."""
    x = to_f32(ints, scale_exp)
    n = x.size
    w = np.asarray(
        data.draw(st.lists(st.integers(0, 3), min_size=n, max_size=n)),
        np.float32)
    w[0] = max(w[0], 1.0)
    wk = float(np.float32(max(float(w.sum()) * wf / 1000.0, 0.5)))
    prior = _mk_prior(pv, pv, pv, pcut)
    for method in ["cp", "binned"]:
        res = selection.weighted_order_statistic(
            jnp.asarray(x), jnp.asarray(w), wk, method=method, maxit=256,
            cap=4, prior=prior)
        np.testing.assert_equal(np.float32(res.value),
                                weighted_oracle(x, w, wk))


@settings(max_examples=50, deadline=None)
@given(ints=ints_small, scale_exp=scale_exps,
       kf=st.integers(min_value=0, max_value=1000))
def test_exact_prior_sweep_economy(ints, scale_exp, kf):
    """An exact prior (the previous run's own result) resolves in <= 1
    binned sweep: the ``prev_float(v)``/``v`` collapse pair certifies an
    unchanged answer immediately."""
    x = to_f32(ints, scale_exp)
    n = x.size
    k = max(1, min(n, 1 + (kf * n) // 1001))
    expected = np.partition(x, k - 1)[k - 1]
    # answers at exactly 0.0 cannot form a collapse pair under FTZ
    # (prev_float(0) is a denormal the CPU flushes) — exactness holds but
    # the 1-sweep economy legitimately does not
    hypothesis.assume(expected != 0.0)
    cold = selection.order_statistic(jnp.asarray(x), k, method="binned",
                                     maxit=256, cap=8)
    warm = selection.order_statistic(jnp.asarray(x), k, method="binned",
                                     maxit=256, cap=8, prior=cold)
    np.testing.assert_equal(np.float32(warm.value), expected)
    assert int(warm.iters) <= 1


@settings(max_examples=80, deadline=None)
@given(
    a=st.integers(-(2**20), 2**20),
    b=st.integers(-(2**20), 2**20),
    scale_exp=scale_exps,
    pv=weird_floats, plo=weird_floats, phi=weird_floats,
    pcut=weird_floats,
    nbins=st.sampled_from([2, 3, 4, 8, 128]),
)
def test_prior_edges_contract(a, b, scale_exp, pv, plo, phi, pcut, nbins):
    """``prior_edges`` honors the realized-edge contract for ANY prior:
    sorted ``nbins + 1`` output, endpoints pinned to lo/hi EXACTLY, every
    edge a finite realized fp value inside ``[lo, hi]``."""
    lo, hi = np.sort(to_f32([min(a, b), max(a, b)], scale_exp))
    e = np.asarray(selection.prior_edges(
        jnp.asarray(np.float32(lo)), jnp.asarray(np.float32(hi)),
        _mk_prior(pv, plo, phi, pcut), nbins))
    assert e.shape == (nbins + 1,)
    assert e[0] == lo and e[-1] == hi
    assert np.all(np.diff(e) >= 0)
    assert np.all((e >= lo) & (e <= hi))
    assert np.all(np.isfinite(e)) or (not np.isfinite(lo)
                                      or not np.isfinite(hi))
