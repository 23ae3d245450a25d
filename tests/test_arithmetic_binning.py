"""Differential suite for the verified arithmetic binning pass.

The contract under test is ELEMENT-WISE: ``kernels.ref.bin_slots(...,
impl='arithmetic')`` must equal the searchsorted slot oracle for every
element, not just produce the same final order statistics — PR 2 proved
recomputed edge arithmetic unsound exactly in the regimes generated here
(full-f32-range brackets where the realized edges clip-collapse, denormal/
FTZ floors, tie-storms, ulp-wide bins where consecutive edges round
together), so the equality must come from the verified ±1 widening + the
self-certifying rescue, not from luck.

The adversarial leg disables the widening (``arithmetic_slots(...,
widen=False)``) and proves the suite WOULD catch an unverified
implementation: raw candidates provably misplace boundary elements.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import selection
from repro.kernels import ops, ref

# The deterministic adversarial tests below run everywhere; the hypothesis
# strategies only where it is installed (same policy as test_property.py,
# but without skipping the whole module).
try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - exercised on minimal containers
    HAVE_HYPOTHESIS = False

    def given(*a, **k):  # noqa: D103 - stub so decorators still apply
        return pytest.mark.skip(reason="hypothesis not installed")

    def settings(*a, **k):  # noqa: D103
        return lambda f: f

    class _St:
        def __getattr__(self, name):
            return lambda *a, **k: None

    st = _St()

jax.config.update("jax_platform_name", "cpu")


def to_f32(ints, scale_exp=0):
    x = np.asarray(ints, np.float64) * (2.0 ** (scale_exp - 10))
    return x.astype(np.float32)


def slot_oracle(x, edges):
    """The differential target: the searchsorted slot oracle under the
    PLATFORM's comparison semantics (``ref.searchsorted_slots``).  On FTZ
    hardware (XLA:CPU) denormal values compare as zero in BOTH the oracle
    and the arithmetic path — the equality under test is bit-identity with
    the oracle the engine actually narrows against, which numpy (non-FTZ)
    deliberately is not in the denormal regime."""
    return np.asarray(ref.searchsorted_slots(jnp.asarray(x),
                                             jnp.asarray(edges)))


def np_slot_oracle(x, edges):
    """Pure-numpy count(edges < x) — used where the data is normal-range
    (there the platform and numpy agree, making the test independent of
    the jnp implementation)."""
    return np.searchsorted(np.asarray(edges), np.asarray(x),
                           side="left").astype(np.int32)


# integer-derived dyadic floats (FTZ-safe, tie-heavy); scale_exp stretches
# from denormal-adjacent to within a few octaves of f32 max
ints_small = st.lists(st.integers(-(2**20), 2**20), min_size=1, max_size=300)
ints_dupes = st.lists(st.integers(-4, 4), min_size=1, max_size=300)
scale_exps = st.integers(min_value=-20, max_value=97)
nbins_s = st.sampled_from([2, 3, 8, 16, 128])


@settings(max_examples=80, deadline=None)
@given(ints=ints_small, scale_exp=scale_exps, nbins=nbins_s,
       data=st.data())
def test_arithmetic_slots_elementwise(ints, scale_exp, nbins, data):
    """bin_slots('arithmetic') == searchsorted slots ELEMENT-WISE, with the
    bracket drawn from the data itself (the engine's regime: realized
    bin_edges of an in-range bracket, including lo == hi collapses)."""
    x = to_f32(ints, scale_exp)
    i = data.draw(st.integers(0, x.size - 1))
    j = data.draw(st.integers(0, x.size - 1))
    lo, hi = np.float32(min(x[i], x[j])), np.float32(max(x[i], x[j]))
    edges = ref.bin_edges(jnp.float32(lo), jnp.float32(hi), nbins)
    got = np.asarray(ref.bin_slots(jnp.asarray(x), edges, "arithmetic"))
    np.testing.assert_array_equal(got, slot_oracle(x, edges))
    # normal-range dyadic data: the platform oracle and numpy agree, so the
    # equality is also pinned against an independent implementation
    np.testing.assert_array_equal(got, np_slot_oracle(x, edges))


@settings(max_examples=50, deadline=None)
@given(ints=ints_dupes, scale_exp=scale_exps, nbins=nbins_s)
def test_arithmetic_slots_tie_storms_full_bracket(ints, scale_exp, nbins):
    """Handfuls of duplicated levels, bracket = [min, max] (the first-sweep
    regime, including the full-f32-range clip-collapsed edges)."""
    x = to_f32(ints, scale_exp)
    edges = ref.bin_edges(jnp.float32(x.min()), jnp.float32(x.max()), nbins)
    got = np.asarray(ref.bin_slots(jnp.asarray(x), edges, "arithmetic"))
    np.testing.assert_array_equal(got, slot_oracle(x, edges))


def test_arithmetic_slots_adversarial_regimes():
    """Deterministic worst cases: full-range brackets (edges clip-collapse
    at the top — candidates land ~30 bins out), ulp-wide brackets
    (consecutive edges round together), denormal-scale widths (inv_w
    overflows f32), ±inf data, and edge-exact values."""
    cases = []
    # full f32 range: w*j overflows for large j, top edges collapse to hi
    x = np.array([-3.4e38, -1e38, -1.0, 0.0, 1.0, 2e38, 3.4e38, np.inf,
                  -np.inf], np.float32)
    cases.append((x, np.float32(-3.4e38), np.float32(3.4e38), 128))
    # ulp-wide bracket: duplicate realized edges
    lo = np.float32(1.0)
    hi = np.nextafter(lo, np.float32(np.inf))
    cases.append((np.array([0.5, lo, hi, 2.0], np.float32), lo, hi, 128))
    # denormal-scale width: 1/w overflows f32 (candidate must rescue)
    cases.append((np.linspace(0, 1e-38, 64, dtype=np.float32),
                  np.float32(0.0), np.float32(1e-38), 128))
    # collapsed bracket lo == hi
    cases.append((np.array([-1.0, 0.0, 1.0], np.float32),
                  np.float32(0.0), np.float32(0.0), 8))
    # values exactly ON interior edges (the inherent ±1 boundary case)
    edges8 = np.asarray(ref.bin_edges(jnp.float32(-2.0), jnp.float32(2.0),
                                      8))
    cases.append((edges8.astype(np.float32), np.float32(-2.0),
                  np.float32(2.0), 8))
    for x, lo, hi, nbins in cases:
        edges = ref.bin_edges(jnp.asarray(lo), jnp.asarray(hi), nbins)
        got = np.asarray(ref.bin_slots(jnp.asarray(x), edges, "arithmetic"))
        np.testing.assert_array_equal(got, slot_oracle(x, edges),
                                      err_msg=f"lo={lo} hi={hi}")


def test_unverified_arithmetic_is_caught():
    """The adversarial leg: with the ±1 widening DISABLED the raw clipped
    candidate misplaces edge-exact elements — proving this suite would
    catch an unverified implementation — while the widened version is
    already exact in this (non-degenerate) regime without any rescue."""
    edges = ref.bin_edges(jnp.float32(-2.0), jnp.float32(2.0), 8)
    x = jnp.asarray(edges)[1:-1]  # interior edge-exact values
    want = slot_oracle(x, edges)
    raw = np.asarray(ref.arithmetic_slots(x, edges, widen=False))
    assert np.any(raw != want), "raw candidates unexpectedly exact"
    widened = np.asarray(ref.arithmetic_slots(x, edges, widen=True))
    np.testing.assert_array_equal(widened, want)


@settings(max_examples=40, deadline=None)
@given(ints=ints_small, scale_exp=scale_exps)
def test_batched_and_multi_slot_paths(ints, scale_exp):
    """The batched (per-row edges) and shared-x (per-pivot edges) slot
    paths run the same verified code: element-wise equality there too."""
    x = to_f32(ints, scale_exp)
    n = x.size
    lo = np.float32(x.min())
    hi = np.float32(x.max())
    mid = np.float32(lo / 2 + hi / 2)
    los = jnp.asarray([lo, lo, mid])
    his = jnp.asarray([hi, mid if mid > lo else hi, hi])
    edges = ref.bin_edges(los, jnp.maximum(his, los), 16)
    got = np.asarray(ref.bin_slots(jnp.asarray(x), edges, "arithmetic"))
    for r in range(3):
        np.testing.assert_array_equal(got[r],
                                      slot_oracle(x, np.asarray(edges)[r]))
    # batched rows: each row binned against its own edges
    xb = jnp.asarray(np.stack([x, x[::-1], x]))
    gotb = np.asarray(ref.bin_slots(xb, edges, "arithmetic"))
    for r, row in enumerate([x, x[::-1], x]):
        np.testing.assert_array_equal(gotb[r],
                                      slot_oracle(row, np.asarray(edges)[r]))


@settings(max_examples=30, deadline=None)
@given(ints=ints_small, scale_exp=scale_exps, nbins=st.sampled_from([8, 16]),
       data=st.data())
def test_polish_edges_slots_rescue(ints, scale_exp, nbins, data):
    """Non-uniform (polish) edge arrays break the uniform candidate by
    construction — the verification must detect it and the rescue must
    still return bit-exact slots."""
    x = to_f32(ints, scale_exp)
    lo = np.float32(x.min())
    hi = np.float32(x.max())
    tq = data.draw(st.integers(0, 1000))
    t = np.float32(lo + (hi - lo) * (tq / 1000.0))
    edges = selection.polish_edges(jnp.asarray(lo), jnp.asarray(hi),
                                   jnp.asarray(t), nbins)
    got = np.asarray(ref.bin_slots(jnp.asarray(x), edges, "arithmetic"))
    np.testing.assert_array_equal(got, slot_oracle(x, np.asarray(edges)))


@settings(max_examples=40, deadline=None)
@given(ints=ints_small, scale_exp=scale_exps,
       kf=st.integers(min_value=0, max_value=1000))
def test_binned_impl_differential_engine(ints, scale_exp, kf):
    """End-to-end: the two slotting impls drive the binned engine to the
    same (np.partition-exact) answers."""
    x = to_f32(ints, scale_exp)
    n = x.size
    k = max(1, min(n, 1 + (kf * n) // 1001))
    expected = np.partition(x, k - 1)[k - 1]
    for impl in ["searchsorted", "arithmetic"]:
        res = selection.order_statistic(jnp.asarray(x), k, method="binned",
                                        binned_impl=impl, maxit=256, cap=8)
        np.testing.assert_equal(np.float32(res.value), expected)


@settings(max_examples=25, deadline=None)
@given(ints=ints_dupes, scale_exp=scale_exps,
       wf=st.integers(min_value=0, max_value=1000), data=st.data())
def test_binned_impl_differential_weighted(ints, scale_exp, wf, data):
    """Weighted leg: both impls equal the f64 sorted-cumsum oracle under
    tie storms with zero-mass members."""
    x = to_f32(ints, scale_exp)
    n = x.size
    w = np.asarray(
        data.draw(st.lists(st.integers(0, 3), min_size=n, max_size=n)),
        np.float32)
    w[0] = max(w[0], 1.0)
    wk = float(np.float32(max(float(w.sum()) * wf / 1000.0, 0.5)))
    o = np.argsort(x, kind="stable")
    c = np.cumsum(w[o].astype(np.float64))
    want = x[o][min(np.searchsorted(c, wk, "left"), n - 1)]
    for impl in ["searchsorted", "arithmetic"]:
        res = selection.weighted_order_statistic(
            jnp.asarray(x), jnp.asarray(w), wk, method="binned",
            binned_impl=impl, maxit=256, cap=8)
        np.testing.assert_equal(np.float32(res.value), want)


def test_histogram_counts_match_and_msum_demand():
    """ops-layer contract: both impls produce identical counts; the
    arithmetic pass skips the per-slot sums unless asked (want_sums)."""
    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.standard_normal(4096).astype(np.float32))
    e = ref.bin_edges(jnp.float32(-0.9), jnp.float32(1.1), 16)
    c_ss, b_ss = ops.fused_histogram(x, e, backend="jnp",
                                     impl="searchsorted")
    c_ar, b_ar = ops.fused_histogram(x, e, backend="jnp",
                                     impl="arithmetic")
    np.testing.assert_array_equal(np.asarray(c_ss), np.asarray(c_ar))
    np.testing.assert_allclose(np.asarray(b_ss), np.asarray(b_ar),
                               rtol=2e-5, atol=1e-4)
    c_no, b_no = ops.fused_histogram(x, e, backend="jnp",
                                     impl="arithmetic", want_sums=False)
    assert b_no is None
    np.testing.assert_array_equal(np.asarray(c_ss), np.asarray(c_no))
    # weighted: the mass vector always rides, only wsum is demand-driven
    w = jnp.asarray(rng.integers(0, 4, 4096).astype(np.float32))
    cw, ww, sw = ops.fused_weighted_histogram(x, w, e, backend="jnp",
                                              impl="arithmetic",
                                              want_sums=False)
    cw2, ww2, sw2 = ops.fused_weighted_histogram(x, w, e, backend="jnp",
                                                 impl="searchsorted")
    assert sw is None
    np.testing.assert_array_equal(np.asarray(cw), np.asarray(cw2))
    np.testing.assert_array_equal(np.asarray(ww), np.asarray(ww2))


def test_bad_impl_rejected():
    x = jnp.zeros((8,), jnp.float32)
    e = ref.bin_edges(jnp.float32(0.0), jnp.float32(1.0), 4)
    with pytest.raises(ValueError):
        ops.fused_histogram(x, e, backend="jnp", impl="florble")
    with pytest.raises(ValueError):
        selection.order_statistic(x, 1, method="binned",
                                  binned_impl="florble")


@pytest.mark.parametrize("use_x64", [False, True])
def test_x64_reroute_keeps_arithmetic_exact(use_x64):
    """The f64 reroute lands on the jnp oracle with the arithmetic impl:
    sub-f32-resolution data must still slot exactly."""
    import jax.experimental

    if use_x64:
        with jax.enable_x64(True):
            base = np.float64(1.0)
            eps = np.finfo(np.float64).eps
            x = jnp.asarray(base + np.arange(64) * 50 * eps)
            edges = ref.bin_edges(jnp.float64(base),
                                  jnp.float64(base + 3200 * eps), 8)
            got = np.asarray(ref.bin_slots(x, edges, "arithmetic"))
            np.testing.assert_array_equal(got, slot_oracle(x, edges))
    else:
        x = jnp.asarray(np.linspace(-1, 1, 64, dtype=np.float32))
        edges = ref.bin_edges(jnp.float32(-1.0), jnp.float32(1.0), 8)
        got = np.asarray(ref.bin_slots(x, edges, "arithmetic"))
        np.testing.assert_array_equal(got, slot_oracle(x, edges))
