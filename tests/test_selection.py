"""Unit + property tests for repro.core.selection against numpy oracles.

Covers the paper's nine data distributions (Sec. V-A), the outlier stress
cases (Sec. V-D), ties, tiny arrays, and all iterative methods.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import selection
from repro.core.objective import eval_fg

jax.config.update("jax_platform_name", "cpu")


def paper_distributions(rng, n):
    """The nine datasets of Sec. V-A."""
    half = lambda m: np.abs(rng.standard_normal(m))
    mix = lambda a, b, frac: np.concatenate(
        [a[: int(n * frac)], b[: n - int(n * frac)]]
    )
    return {
        "uniform": rng.random(n),
        "normal": rng.standard_normal(n),
        "halfnormal": half(n),
        "beta25": rng.beta(2, 5, n),
        "mix1": mix(rng.standard_normal(n), rng.normal(100, 1, n), 2 / 3),
        "mix2": mix(rng.standard_normal(n) + 1, rng.normal(100, 1, n), 0.5),
        "mix3": mix(half(n), np.full(n, 10.0), 0.9),
        "mix4": mix(half(n), rng.normal(100, 1, n), 2 / 3),
        "mix5": mix(half(n) + 1, rng.normal(100, 1, n), 0.5),
    }


def exact_kth(x, k):
    return np.partition(np.asarray(x), k - 1)[k - 1]


@pytest.mark.parametrize("name", [
    "uniform", "normal", "halfnormal", "beta25",
    "mix1", "mix2", "mix3", "mix4", "mix5",
])
def test_median_all_distributions(name):
    rng = np.random.default_rng(0)
    n = 100_001
    x = paper_distributions(rng, n)[name].astype(np.float32)
    k = (n + 1) // 2
    res = selection.median(jnp.asarray(x))
    assert res.status != selection.NOT_CONVERGED
    np.testing.assert_equal(np.float32(res.value), exact_kth(x, k))


@pytest.mark.parametrize("method", ["cp", "bisection", "golden", "brent", "sort"])
@pytest.mark.parametrize("k_frac", [0.1, 0.25, 0.5, 0.9])
def test_order_statistics_methods(method, k_frac):
    rng = np.random.default_rng(1)
    n = 20_000
    x = rng.standard_normal(n).astype(np.float32)
    k = max(1, int(k_frac * n))
    maxit = 64 if method in ("cp", "sort") else 256
    res = selection.order_statistic(jnp.asarray(x), k, method=method, maxit=maxit)
    np.testing.assert_equal(np.float32(res.value), exact_kth(x, k))


def test_cp_converges_in_few_iterations():
    """Paper: <30 iterations for n up to 32M; we check a 1M array."""
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal(1 << 20).astype(np.float32))
    res = selection.median(x)
    assert int(res.iters) <= 30
    assert res.status != selection.NOT_CONVERGED


def test_cp_insensitive_to_outliers_bisection_is_not():
    """Fig. 5: one element at 1e9 stalls bisection, not cutting planes."""
    rng = np.random.default_rng(3)
    x = rng.standard_normal(100_000).astype(np.float32)
    x[0] = 1e9
    k = (x.size + 1) // 2
    xa = jnp.asarray(x)
    cap = 4096
    r_cp = selection.order_statistic(xa, k, method="cp", cap=cap)
    r_bi = selection.order_statistic(xa, k, method="bisection", maxit=64, cap=cap)
    np.testing.assert_equal(np.float32(r_cp.value), exact_kth(x, k))
    assert int(r_cp.iters) <= 25
    # bisection spends its budget walking the huge empty range
    assert int(r_bi.iters) > int(r_cp.iters)


def test_extreme_values_log_transform():
    """Sec. V-D: components ~1e20 break plain f32 summation; log1p guard."""
    rng = np.random.default_rng(4)
    x = rng.standard_normal(65_536).astype(np.float32)
    x[:16] = 1e20
    k = (x.size + 1) // 2
    res = selection.order_statistic(jnp.asarray(x), k, transform="log1p")
    np.testing.assert_equal(np.float32(res.value), exact_kth(x, k))


def test_ties_heavier_than_cap():
    """> cap duplicates of the answer exercise the tie fallback."""
    rng = np.random.default_rng(5)
    x = np.concatenate([
        rng.standard_normal(10_000),
        np.full(30_000, 0.5, np.float32),
        rng.standard_normal(10_000) + 50.0,
    ]).astype(np.float32)
    rng.shuffle(x)
    k = (x.size + 1) // 2  # the median sits inside the tie block
    res = selection.order_statistic(jnp.asarray(x), k, cap=256, maxit=64)
    np.testing.assert_equal(np.float32(res.value), exact_kth(x, k))
    assert res.status in (selection.EXACT_HIT, selection.TIE_FALLBACK,
                          selection.HYBRID_SORT)


# the scalar default cap of the jnp path, n -> cap
JNP_SCALAR_CAP = {1: 4096, 1000: 4096, 1 << 16: 4096, 1 << 18: 4096,
                  1 << 19: 8192, 1 << 20: 16384, 1 << 22: 65536,
                  1 << 24: 262144, 1 << 25: 524288, 1 << 28: 524288,
                  1 << 30: 524288}


@pytest.mark.parametrize("n", sorted(JNP_SCALAR_CAP))
def test_default_cap_by_path(n):
    """The jnp path keeps its generous buffer; the kernel path bounds it by
    KERNEL_CAP from n = 2^19 up and agrees with the jnp path below."""
    assert selection._default_cap(n) == JNP_SCALAR_CAP[n]
    assert selection._default_cap(n, "jnp") == JNP_SCALAR_CAP[n]
    for backend in ("pallas", "pallas_interpret"):
        got = selection._default_cap(n, backend)
        if n <= 1 << 18:
            assert got == JNP_SCALAR_CAP[n]
        else:
            assert got == selection.KERNEL_CAP == 4096


def _small_cap_cases(n):
    rng = np.random.default_rng(14)
    cases = paper_distributions(rng, n)
    m = (n - n // 10) // 2
    tie = np.concatenate([rng.random(m), np.full(n - 2 * m, 1.5),
                          rng.random(m) + 2.0])  # the median in a 10% tie
    rng.shuffle(tie)
    cases["tie10_at_median"] = tie
    cases["three_values"] = rng.choice([-1.0, 0.25, 7.0], n)
    return {k: v.astype(np.float32) for k, v in cases.items()}


@pytest.mark.parametrize("name", [
    "uniform", "normal", "halfnormal", "beta25", "mix1", "mix2", "mix3",
    "mix4", "mix5", "tie10_at_median", "three_values",
])
def test_kernel_path_small_cap_exact(name):
    """The kernel path's regime: sweeps run until at most a small cap of
    survivors is left (or a tie or an ulp-collapse certifies), and every
    answer is still np.partition's, bit for bit."""
    n, cap = 1 << 16, 64
    x = _small_cap_cases(n)[name]
    want = exact_kth(x, (n + 1) // 2)
    small = selection.median(jnp.asarray(x), backend="pallas_interpret",
                             cap=cap)
    wide = selection.median(jnp.asarray(x), backend="pallas_interpret",
                            cap=4096)
    for res in (small, wide):
        assert int(res.status) != selection.NOT_CONVERGED
        assert np.float32(res.value).view(np.uint32) == want.view(np.uint32)
    assert int(small.iters) >= int(wide.iters)


def test_integer_valued_data_all_ties():
    rng = np.random.default_rng(6)
    x = rng.integers(0, 7, 50_001).astype(np.float32)
    for k in [1, 2, 25_000, 25_001, 50_000, 50_001]:
        res = selection.order_statistic(jnp.asarray(x), k, cap=128)
        np.testing.assert_equal(np.float32(res.value), exact_kth(x, k),
                                err_msg=f"k={k}")


def test_all_equal_and_tiny():
    x = jnp.full((1000,), 3.25, jnp.float32)
    assert float(selection.median(x).value) == 3.25
    for n in [1, 2, 3, 5]:
        rng = np.random.default_rng(n)
        x = rng.standard_normal(n).astype(np.float32)
        for k in range(1, n + 1):
            res = selection.order_statistic(jnp.asarray(x), k, cap=4)
            np.testing.assert_equal(np.float32(res.value), exact_kth(x, k))


def test_permutation_invariance():
    """Expression (1) is permutation invariant (paper Sec. V-D)."""
    rng = np.random.default_rng(7)
    x = rng.standard_normal(9_999).astype(np.float32)
    v1 = selection.median(jnp.asarray(x)).value
    v2 = selection.median(jnp.asarray(np.sort(x))).value
    v3 = selection.median(jnp.asarray(np.sort(x)[::-1].copy())).value
    assert float(v1) == float(v2) == float(v3)


def test_subgradient_certificate():
    """0 in [g_lo, g_hi] at y  <=>  n_lt < k <= n_le  <=>  y = x_(k)."""
    rng = np.random.default_rng(8)
    x = rng.standard_normal(101).astype(np.float32)
    k = 51
    xk = exact_kth(x, k)
    fg = eval_fg(jnp.asarray(x), xk, k)
    assert float(fg.g_lo) <= 0.0 <= float(fg.g_hi)
    assert int(fg.n_lt) < k <= int(fg.n_le)
    fg2 = eval_fg(jnp.asarray(x), exact_kth(x, k + 3), k)
    assert not (float(fg2.g_lo) <= 0.0 <= float(fg2.g_hi))


def test_quantile_and_topk():
    rng = np.random.default_rng(9)
    x = rng.random(12_345).astype(np.float32)
    r = selection.quantile(jnp.asarray(x), 0.99)
    k = int(np.ceil(0.99 * x.size))
    np.testing.assert_equal(np.float32(r.value), exact_kth(x, k))
    r2 = selection.topk_threshold(jnp.asarray(x), 10)
    np.testing.assert_equal(np.float32(r2.value), np.sort(x)[-10])


def test_jit_and_traced_k():
    """k may be a traced value; whole pipeline is jit-compatible."""
    rng = np.random.default_rng(10)
    x = jnp.asarray(rng.standard_normal(4096).astype(np.float32))

    @jax.jit
    def f(x, k):
        return selection.order_statistic(x, k).value

    for k in [1, 17, 2048, 4096]:
        np.testing.assert_equal(np.float32(f(x, k)),
                                exact_kth(np.asarray(x), k))


def test_multi_order_statistic():
    """Batched selection: several k against the same array in one solve."""
    rng = np.random.default_rng(11)
    x = rng.standard_normal(30_000).astype(np.float32)
    ks = [1, 300, 15_000, 29_700, 30_000]
    res = selection.multi_order_statistic(jnp.asarray(x), ks)
    for i, k in enumerate(ks):
        np.testing.assert_equal(np.float32(res.value[i]), exact_kth(x, k),
                                err_msg=f"k={k}")


def test_quantiles_vector():
    rng = np.random.default_rng(12)
    x = np.abs(rng.standard_normal(10_000)).astype(np.float32)
    qs = [0.25, 0.5, 0.75, 0.99]
    res = selection.quantiles(jnp.asarray(x), qs)
    for i, q in enumerate(qs):
        k = int(np.ceil(q * x.size))
        np.testing.assert_equal(np.float32(res.value[i]), exact_kth(x, k))
