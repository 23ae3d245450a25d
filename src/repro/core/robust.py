"""Robust statistics built on selection — the paper's Sec. VI applications,
plus the training-framework integrations (robust aggregation, quantile clip).

* LMS  (Least Median of Squares, Rousseeuw 1984): minimize Med(r_i^2).
* LTS  (Least Trimmed Squares): minimize the sum of the h smallest squared
  residuals — evaluated WITHOUT sorting via the paper's rho/(a,b)
  median-multiplicity trick (Eq. 4): with m = |r|_(h), b_L = count(|r| < m),
  b = count(|r| = m), a = h - b_L:

      F(theta) = sum_{|r|<m} r^2 + a * m^2

  which equals the sum of exactly h smallest squared residuals.
* FAST-LTS style fitting: random elemental starts + concentration steps
  (Rousseeuw & Van Driessen, ref [28] of the paper); the h-th order
  statistic threshold comes from the CP selector, the trimmed LS refit is a
  weighted least squares with fractional tie weights a/b (so ties do not
  break exactness).
* kNN by order statistic (no sort): indicator weights from d_(k).
* Robust gradient aggregation + quantile clipping for distributed training.

Batched-first wiring: every multi-problem selection here rides the rows-mode
engine (``selection.select_rows`` over a ``(B, n)`` residual/distance
matrix) — one batched bracket loop for ALL elemental starts / queries per
step, instead of lock-stepping B scalar solvers under ``jax.vmap``.  The
concentration scan is therefore structured *starts-inside, steps-outside*:
``lax.scan`` over C-steps carries the whole (n_starts, p) theta block, and
each step does one batched selection + one batched weighted refit.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import distributed, selection
from repro.core.objective import fg_from_partials

# Residuals and least-squares refits feed EXACT selections, so every
# matmul here runs at full f32 precision: the TPU default rounds f32
# operands to bf16, which would put ~1e-3 relative error into every residual
# (a no-op on CPU).
_mm = functools.partial(jnp.matmul, precision=jax.lax.Precision.HIGHEST)


# ---------------------------------------------------------------------------
# LMS / LTS objectives
# ---------------------------------------------------------------------------


def residuals(theta, X, y):
    return _mm(X, theta) - y


def lms_objective(theta, X, y, **kw):
    """Med(r^2) (Rousseeuw's LMS criterion)."""
    r2 = residuals(theta, X, y) ** 2
    return selection.median(r2, **kw).value


def lts_objective_from_residuals(r, h, **kw):
    """Sum of the h smallest squared residuals via the rho/(a,b) trick.

    One selection + one fused masked reduction; no sort, no partial sort.
    The B=1 view of :func:`lts_objective_rows`.
    """
    return lts_objective_rows(r.reshape(1, -1), h, **kw)[0]


def lts_objective_rows(R, h, **kw):
    """Row-wise LTS criterion: ``R`` is (B, n) residuals, one scalar per
    row — the rho/(a,b) trick on top of one rows-mode batched selection."""
    a2 = R * R
    m = selection.select_rows(a2, h, **kw).value[:, None]
    below = jnp.sum(jnp.where(a2 < m, a2, 0.0), axis=1, dtype=a2.dtype)
    b_lo = jnp.sum(a2 < m, axis=1, dtype=jnp.int32)
    a = (jnp.asarray(h, jnp.int32) - b_lo).astype(a2.dtype)
    return below + a * m[:, 0]


def lts_objective(theta, X, y, h=None, **kw):
    n, p = X.shape
    if h is None:
        h = (n + p + 1) // 2  # [(n+p)/2] + parity-safe default
    return lts_objective_from_residuals(residuals(theta, X, y), h, **kw)


# ---------------------------------------------------------------------------
# Fitting: random elemental starts + concentration steps
# ---------------------------------------------------------------------------


class RobustFit(NamedTuple):
    theta: jax.Array
    objective: jax.Array
    inlier_weights: jax.Array  # LTS: 1 below cutoff, a/b at cutoff, 0 above
    # per-concentration-step selection sweep counts, (c_steps, n_starts)
    # int32 (None where the fit has no iterative selection): the
    # warm-start instrumentation — steady state is 1 sweep per step
    sweeps: Optional[jax.Array] = None


def _elemental_thetas(key, X, y, n_starts):
    """Solve p x p systems on random p-subsets (PROGRESS-style starts)."""
    n, p = X.shape
    keys = jax.random.split(key, n_starts)

    def solve_one(kk):
        idx = jax.random.choice(kk, n, shape=(p,), replace=False)
        A = X[idx]
        b = y[idx]
        # ridge-regularized solve for degenerate subsets
        G = _mm(A.T, A) + 1e-8 * jnp.eye(p, dtype=X.dtype)
        return jnp.linalg.solve(G, _mm(A.T, b))

    return jax.vmap(solve_one)(keys)


def _lts_weights(r, h):
    """Fractional trimming weights: 1 / (a/b) / 0 per the paper's rho."""
    return _lts_weights_rows(r[None, :], h)[0][0]


def _lts_weights_rows(R, h, method=None, prior=None):
    """Row-wise fractional trimming weights for (B, n) residual blocks.

    One rows-mode batched selection yields every row's cutoff m = |r|^2_(h)
    at once; ties at the cutoff get weight a/b so each row keeps EXACTLY h
    points in total weight.  ``prior`` warm-starts the cutoff selection
    from the previous concentration step's result.  Returns
    ``(weights, SelectResult)`` — the result feeds the next step's prior
    and the sweep-count instrumentation.
    """
    a2 = R * R
    res = selection.select_rows(a2, h, method=method, prior=prior)
    m = res.value[:, None]
    b_lo = jnp.sum(a2 < m, axis=1, keepdims=True, dtype=jnp.int32)
    b_eq = jnp.sum(a2 == m, axis=1, keepdims=True, dtype=jnp.int32)
    a = jnp.asarray(h, jnp.int32) - b_lo
    frac = a.astype(a2.dtype) / jnp.maximum(b_eq, 1).astype(a2.dtype)
    return jnp.where(a2 < m, 1.0, jnp.where(a2 == m, frac, 0.0)), res


def _carry_prior(res, shape, pdt) -> selection.Prior:
    """SelectResult -> fixed-structure scan carry (shape/dtype pinned so a
    cp-leg result and a binned-leg result produce the same carry pytree)."""
    pr = selection.as_prior(res)
    return selection.Prior(
        *(jnp.broadcast_to(jnp.asarray(f, pdt), shape) for f in pr))


def _nan_prior(shape, pdt) -> selection.Prior:
    """Cold-start carry seed: all-NaN fields are sanitized away inside the
    engine (a NaN prior degrades to the analytic/uniform layout), so step 1
    of a warm scan behaves like a cold solve — exactly, on the counting
    leg."""
    nanv = jnp.full(shape, jnp.nan, pdt)
    return selection.Prior(nanv, nanv, nanv, nanv)


def _weighted_ls(X, y, w):
    Xw = X * w[:, None]
    G = _mm(X.T, Xw) + 1e-8 * jnp.eye(X.shape[1], dtype=X.dtype)
    return jnp.linalg.solve(G, _mm(Xw.T, y))


def _weighted_ls_rows(X, y, W):
    """Batched weighted LS: ``W`` is (B, n) weights, one solve per row."""
    return jax.vmap(lambda w: _weighted_ls(X, y, w))(W)


@functools.partial(jax.jit, static_argnames=("n_starts", "c_steps", "h",
                                             "method", "warm"))
def lts_fit(key, X, y, *, h: Optional[int] = None, n_starts: int = 64,
            c_steps: int = 10, method: Optional[str] = None,
            warm: bool = True) -> RobustFit:
    """FAST-LTS: elemental starts -> concentration steps -> best fit.

    Concentration runs starts-inside, steps-outside: each ``lax.scan`` step
    thresholds ALL starts' squared residuals at their h-th order statistic
    in ONE rows-mode batched selection (no sort), then refits every start by
    weighted LS.  The objective is monotone non-increasing along C-steps
    (Rousseeuw & Van Driessen), so the final best-of-starts is a
    high-breakdown estimate.

    ``method`` threads through to the batched selections (None = auto:
    'binned' for large n — every C-step then costs ~3 data passes over the
    (n_starts, n) residual block instead of ~15).

    ``warm`` (default on): the scan carries each start's selection result
    as a ``prior`` into the next step's cutoff selection — residuals
    barely move between concentration steps, so steady-state steps take 1
    binned sweep instead of a cold ~2-3 (the warm-started repeated
    selection the engine's ``prior=`` leg exists for).  Results are
    bit-identical to ``warm=False`` (the prior steers edge placement
    only); ``RobustFit.sweeps`` records the per-step counts.
    """
    n, p = X.shape
    hh = (n + p + 1) // 2 if h is None else h
    pdt = jnp.promote_types(X.dtype, jnp.float32)

    thetas0 = _elemental_thetas(key, X, y, n_starts)

    def c_step(carry, _):
        thetas, pr = carry
        R = _mm(thetas, X.T) - y[None, :]      # (n_starts, n) residuals
        W, res = _lts_weights_rows(R, hh, method,
                                   prior=pr if warm else None)
        pr_n = _carry_prior(res, (n_starts,), pdt)
        return (_weighted_ls_rows(X, y, W), pr_n), res.iters

    (thetas, prf), sweeps = jax.lax.scan(
        c_step, (thetas0, _nan_prior((n_starts,), pdt)), None,
        length=c_steps)
    objs = lts_objective_rows(_mm(thetas, X.T) - y[None, :], hh,
                              method=method,
                              prior=prf if warm else None)
    best = jnp.argmin(objs)
    theta = thetas[best]
    return RobustFit(
        theta=theta,
        objective=objs[best],
        inlier_weights=_lts_weights(residuals(theta, X, y), hh),
        sweeps=sweeps,
    )


@functools.partial(jax.jit, static_argnames=("n_starts", "method"))
def lms_fit(key, X, y, *, n_starts: int = 256,
            method: Optional[str] = None) -> RobustFit:
    """LMS by best-of-elemental-starts (the classical PROGRESS approach).

    Every start's criterion Med(r^2) is one row of a single rows-mode
    batched selection — thousands of concurrent selection problems in one
    bracket loop, the workload the paper's GPU method targets.  ``method``
    threads through to the selections (None = auto: 'binned' for large n).
    """
    n = X.shape[0]
    thetas = _elemental_thetas(key, X, y, n_starts)
    R2 = (_mm(thetas, X.T) - y[None, :]) ** 2  # (n_starts, n)
    objs = selection.select_rows(R2, (n + 1) // 2, method=method).value
    best = jnp.argmin(objs)
    theta = thetas[best]
    r2 = residuals(theta, X, y) ** 2
    med = selection.median(r2, method=method).value
    return RobustFit(
        theta=theta, objective=objs[best],
        inlier_weights=(r2 <= med).astype(X.dtype),
    )


# ---------------------------------------------------------------------------
# Weighted-median regression: Theil-Sen and IRLS M-estimation
# ---------------------------------------------------------------------------
#
# Both estimators are consumers of the WEIGHTED selection engine (PR 3): the
# weighted median is the exact primitive behind Theil-Sen slopes (Sen's
# |dx|-weighted median of pairwise slopes) and behind the IRLS scale step
# (weighted MAD under the current robustness weights) — the regime where
# GPU-side convex minimization replaces sort-based weighted quantiles
# (Zhou, Lange & Suchard 2010 make the same argument for LAD).


class TheilSenFit(NamedTuple):
    intercept: jax.Array
    slope: jax.Array
    theta: jax.Array        # (2,) = [intercept, slope]
    # (slope Prior, intercept Prior) carry for warm refits on drifted data;
    # pass the whole fit back as ``prior=`` to the next call
    prior: object = None


@functools.partial(jax.jit, static_argnames=("weighting", "method",
                                             "max_pairs"))
def theil_sen_fit(x, y, *, weighting: str = "sen",
                  method: Optional[str] = None,
                  max_pairs: Optional[int] = None,
                  prior=None) -> TheilSenFit:
    """Theil-Sen simple regression via the weighted median of pairwise
    slopes.

    All pairwise slopes ride ONE weighted selection (degenerate pairs
    ``x_i == x_j`` get weight 0, so they never influence the mass target);
    ``weighting='sen'`` weights each slope by ``|x_j - x_i|`` (Sen 1968's
    variance-reducing choice — a long-baseline pair estimates the slope
    better than a short one), ``'uniform'`` recovers the classical median
    of slopes.  The intercept is the (unweighted) median of the residuals
    at the fitted slope.  Breakdown ~29%: the acceptance bar is exact slope
    recovery at 30% random contamination, where OLS is destroyed.

    ``max_pairs=None`` materializes the full (n, n) slope matrix — fine for
    the paper-scale regression workloads (n up to a few thousand).  For
    larger n pass ``max_pairs``: slopes are generated in a BLOCKED
    offset-strided layout — ``p = max_pairs // n`` cyclic offsets ``d``
    spread over ``1..n-1``, pairing every ``x_i`` with ``x_{(i+d) mod n}``
    into a ``(p, n)`` block — O(max_pairs) memory, no (n, n) anywhere.
    Each offset contributes every index once, so the subsample is balanced
    (every observation appears in exactly ``2p`` pairs); with
    ``max_pairs >= n*(n-1)`` the offsets enumerate EVERY ordered pair
    exactly once, which has the same (slope, weight) multiset as the full
    matrix (whose diagonal carries weight 0) — the two modes then agree
    exactly, which is the property the tests pin on small n.
    """
    x = jnp.asarray(x).reshape(-1)
    y = jnp.asarray(y).reshape(-1)
    n = x.shape[0]
    # blocked whenever it is no larger than the full (n, n) materialization
    # — max_pairs == n*(n-1) then yields offsets 1..n-1 (every ordered
    # pair), the exact-equality regime the parity tests pin
    if max_pairs is not None and n > 2 and max_pairs < n * n:
        import numpy as np  # static offset schedule (n, max_pairs static)

        p = int(max(1, min(n - 1, max_pairs // n)))
        offsets = np.unique(
            np.round(np.linspace(1, n - 1, p)).astype(np.int64))
        idx = (jnp.arange(n)[None, :]
               + jnp.asarray(offsets)[:, None]) % n     # (p, n)
        dx = x[idx] - x[None, :]
        dy = y[idx] - y[None, :]
    else:
        dx = x[None, :] - x[:, None]
        dy = y[None, :] - y[:, None]
    valid = dx != 0
    slopes = jnp.where(valid, dy / jnp.where(valid, dx, 1.0), 0.0)
    if weighting == "sen":
        w = jnp.where(valid, jnp.abs(dx), 0.0)
    elif weighting == "uniform":
        w = valid.astype(x.dtype)
    else:
        raise ValueError(f"unknown weighting {weighting!r}")
    # warm start: accept a previous TheilSenFit (its ``prior`` carry, or —
    # if that is absent — its point estimates) or an explicit
    # (slope_prior, intercept_prior) pair; each leg is normalized through
    # ``selection.as_prior`` so results, SelectResults, Priors and bare
    # scalars all work.  Exactness never depends on the prior.
    spr = ipr = None
    if prior is not None:
        if isinstance(prior, TheilSenFit):
            if prior.prior is not None:
                spr, ipr = prior.prior
            else:
                spr, ipr = prior.slope, prior.intercept
        else:
            spr, ipr = prior
        spr = selection.as_prior(spr)
        ipr = selection.as_prior(ipr)
    sres = selection.weighted_median(
        slopes.reshape(-1), w.reshape(-1), method=method, prior=spr)
    slope = sres.value
    ires = selection.median(y - slope * x, method=method, prior=ipr)
    intercept = ires.value
    return TheilSenFit(intercept=intercept, slope=slope,
                       theta=jnp.stack([intercept, slope]),
                       prior=(selection.as_prior(sres),
                              selection.as_prior(ires)))


class IRLSFit(NamedTuple):
    theta: jax.Array
    scale: jax.Array        # final robust scale (weighted MAD estimate)
    weights: jax.Array      # final robustness weights (n,)
    objective: jax.Array    # sum of rho(r / scale) at the final iterate
    # per-iteration weighted-median sweep counts, (iters,) int32 — the
    # warm-start instrumentation — steady state is 1 sweep per iteration
    sweeps: Optional[jax.Array] = None


def _rho_weights(u, loss: str, c):
    """IRLS weight function w(u) = psi(u)/u for the supported losses."""
    au = jnp.abs(u)
    if loss == "huber":
        return jnp.minimum(1.0, c / jnp.maximum(au, 1e-20))
    if loss == "tukey":
        t = jnp.clip(1.0 - (u / c) ** 2, 0.0, None)
        return t * t
    raise ValueError(f"unknown loss {loss!r}")


def _rho(u, loss: str, c):
    au = jnp.abs(u)
    if loss == "huber":
        quad = 0.5 * u * u
        return jnp.where(au <= c, quad, c * au - 0.5 * c * c)
    # tukey bisquare
    t = jnp.clip(1.0 - (u / c) ** 2, 0.0, None)
    return (c * c / 6.0) * (1.0 - t ** 3)


@functools.partial(jax.jit, static_argnames=("loss", "iters", "method",
                                             "warm"))
def irls_fit(X, y, *, loss: str = "huber", c: Optional[float] = None,
             iters: int = 30, method: Optional[str] = None,
             min_scale: float = 1e-12, warm: bool = True) -> IRLSFit:
    """IRLS M-estimator (Huber / Tukey bisquare) with a weighted-engine
    scale step.

    Each reweighting iteration calls the WEIGHTED selection engine for its
    scale: a weighted MAD-about-zero (1.4826 x the weighted median of
    |residuals| under the current robustness weights) — down-weighted
    outliers stop corrupting their own rejection threshold, and centering
    at zero (the regression convention: location is the intercept's job)
    keeps a biased start from shrinking the scale below the residual
    offset, which would zero every redescending-psi weight.  Then the
    standard w(u) = psi(u)/u reweighting and a weighted LS refit.

    ``c`` defaults to the 95%-efficiency constants (Huber 1.345, Tukey
    4.685).  ``method`` threads to the weighted selections.

    ``warm`` (default on): the scan carries each iteration's weighted
    median result as the next iteration's ``prior`` — residuals and
    robustness weights move little between reweighting steps, so
    steady-state scale steps take 1 binned sweep (bit-identical results,
    see ``selection.Prior``).  ``IRLSFit.sweeps`` records the per-
    iteration counts.
    """
    if c is None:
        c = 1.345 if loss == "huber" else 4.685
    n, p = X.shape
    dt = X.dtype
    pdt = jnp.promote_types(dt, jnp.float32)
    theta0 = _weighted_ls(X, y, jnp.ones((n,), dt))

    def step(carry, _):
        theta, w, pr = carry
        r = y - _mm(X, theta)
        res = selection.weighted_median(jnp.abs(r), w, method=method,
                                        prior=pr if warm else None)
        mad = res.value
        sigma = jnp.maximum(1.4826 * mad, min_scale)
        u = r / sigma
        w_new = _rho_weights(u, loss, c)
        theta_new = _weighted_ls(X, y, w_new)
        return (theta_new, w_new, _carry_prior(res, (), pdt)), \
            (sigma, res.iters)

    (theta, w, prf), (_sigmas, sweeps) = jax.lax.scan(
        step, (theta0, jnp.ones((n,), dt), _nan_prior((), pdt)), None,
        length=iters)
    # re-evaluate scale/weights/objective AT the returned theta (the scan
    # carries them one iterate stale: sigma was measured on the pre-refit
    # residuals, which would make objectives incomparable across iters)
    r = y - _mm(X, theta)
    mad = selection.weighted_median(jnp.abs(r), w, method=method,
                                    prior=prf if warm else None).value
    scale = jnp.maximum(1.4826 * mad, min_scale)
    u = r / scale
    return IRLSFit(theta=theta, scale=scale, weights=_rho_weights(u, loss, c),
                   objective=jnp.sum(_rho(u, loss, c)), sweeps=sweeps)


def knn_predict(train_x, train_y, query_x, k: int, *, classify: bool = False,
                n_classes: int = 0, method: Optional[str] = None):
    """kNN regression/classification without sorting the distances.

    Distances by one MXU-friendly matmul; the k-NN cutoffs for ALL queries
    come from one rows-mode batched selection over the (Q, n) distance
    matrix; ties at the cutoff get fractional weight so exactly k neighbors
    are counted.
    """
    # squared euclidean distances via ||a-b||^2 expansion (one matmul)
    d2 = (
        jnp.sum(query_x**2, -1, keepdims=True)
        - 2.0 * _mm(query_x, train_x.T)
        + jnp.sum(train_x**2, -1)[None, :]
    )

    dk = selection.select_rows(d2, k, method=method).value[:, None]
    lt = (d2 < dk).astype(d2.dtype)
    eq = (d2 == dk).astype(d2.dtype)
    n_lt = jnp.sum(lt, -1, keepdims=True)
    n_eq = jnp.sum(eq, -1, keepdims=True)
    frac = (k - n_lt) / jnp.maximum(n_eq, 1.0)
    w = lt + eq * frac  # sums to exactly k per query
    if classify:
        onehot = jax.nn.one_hot(train_y, n_classes, dtype=d2.dtype)
        votes = _mm(w, onehot)
        return jnp.argmax(votes, -1)
    return _mm(w, train_y) / k


# ---------------------------------------------------------------------------
# Distributed-training integrations
# ---------------------------------------------------------------------------


def robust_aggregate(tree, axes, *, method: str = "median",
                     trim: float = 0.25, agg_impl: str = "gather"):
    """Byzantine/straggler-robust combine of per-replica gradient pytrees.

    Call inside shard_map where each device along ``axes`` holds one
    replica's gradients.  method: 'mean' | 'median' | 'trimmed'.
    'median'/'trimmed' use coordinate-wise order statistics across the mesh
    axis (impl 'gather' or 'cp', see ``distributed.order_statistic_across_axis``).
    """
    axes_t = (axes,) if isinstance(axes, str) else tuple(axes)
    n_rep = jax.lax.psum(jnp.asarray(1, jnp.int32), axes_t)

    if method == "mean":
        return jax.tree.map(
            lambda g: jax.lax.pmean(g, axes_t), tree)

    if method == "median":
        return jax.tree.map(
            lambda g: distributed.median_across_axis(g, axes_t,
                                                     method=agg_impl), tree)

    if method == "trimmed":
        def tmean(g):
            k_lo = jnp.maximum((trim * n_rep).astype(jnp.int32), 1)
            k_hi = n_rep - k_lo + 1
            lo = distributed.order_statistic_across_axis(
                g, k_lo, axes_t, method=agg_impl)
            hi = distributed.order_statistic_across_axis(
                g, k_hi, axes_t, method=agg_impl)
            keep = (g >= lo) & (g <= hi)
            num = jax.lax.psum(jnp.where(keep, g, 0.0), axes_t)
            den = jax.lax.psum(keep.astype(g.dtype), axes_t)
            return num / jnp.maximum(den, 1.0)

        return jax.tree.map(tmean, tree)

    raise ValueError(f"unknown method {method!r}")


def pytree_quantile(tree, q, *, maxit: int = 16, abs_values: bool = True):
    """Approximate global q-quantile over all entries of a pytree.

    The CP loop runs with the pytree as one logical array: each iteration is
    one fused pass over every leaf (additive partials summed across leaves).
    Under pjit/GSPMD the leaf reductions lower to local reductions plus
    all-reduces of four scalars per iteration — communication-free in data
    volume, exactly the paper's multi-device argument.

    Returns the bracket midpoint on non-exact exit (tight after ~16 its for
    clipping purposes); exact on exact-hit / extreme shortcuts.
    """
    # Keep every leaf in its native shape AND sharding: a reshape(-1) here
    # would force GSPMD to all-gather each (sharded) gradient tensor.  The
    # abs+f32 conversion happens inside the reduction pass so XLA fuses it
    # (no materialized |g| copies); reductions over sharded dims lower to
    # local reductions + all-reduces of four scalars.
    leaves = list(jax.tree.leaves(tree))
    n = sum(l.size for l in leaves)

    def absf(l):
        l = l.astype(jnp.float32)
        return jnp.abs(l) if abs_values else l

    # counts in f32: gradient pytrees exceed int32 range (n > 2^31 for
    # multi-B-param models); the ~1e-7 relative count error is irrelevant
    # for a clipping threshold (and TPUs have no int64/f64).
    k = jnp.clip(jnp.ceil(jnp.float32(q) * jnp.float32(n)),
                 jnp.float32(1.0), jnp.float32(n))

    def partials(y):
        sp = sn = jnp.float32(0.0)
        lt = le = jnp.float32(0.0)
        for l in leaves:
            d = absf(l) - y
            sp = sp + jnp.sum(jnp.maximum(d, 0))
            sn = sn + jnp.sum(jnp.maximum(-d, 0))
            lt = lt + jnp.sum(d < 0, dtype=jnp.float32)
            le = le + jnp.sum(d <= 0, dtype=jnp.float32)
        return sp, sn, lt, le

    xmin = functools.reduce(jnp.minimum, [jnp.min(absf(l)) for l in leaves])
    xmax = functools.reduce(jnp.maximum, [jnp.max(absf(l)) for l in leaves])
    xsum = functools.reduce(jnp.add, [jnp.sum(absf(l)) for l in leaves])
    nf = jnp.asarray(n, jnp.float32)
    alpha = (nf - k + 0.5) / nf
    beta = (k - 0.5) / nf

    state = dict(
        yL=xmin, fL=beta * (xsum / nf - xmin),
        gL=alpha / nf - beta * (nf - 1.0) / nf,
        yR=xmax, fR=alpha * (xmax - xsum / nf),
        gR=alpha * (nf - 1.0) / nf - beta / nf,
        t=0.5 * (xmin + xmax), exact=jnp.asarray(False), it=jnp.asarray(0),
    )

    def cond(s):
        return (~s["exact"]) & (s["it"] < maxit) & (s["yR"] > s["yL"])

    def body(s):
        t = (s["fR"] - s["fL"] + s["yL"] * s["gL"] - s["yR"] * s["gR"]) / (
            s["gL"] - s["gR"])
        bad = ~jnp.isfinite(t) | (t <= s["yL"]) | (t >= s["yR"])
        t = jnp.where(bad, 0.5 * (s["yL"] + s["yR"]), t)
        fg = fg_from_partials(partials(t), n, k)
        exact = (fg.n_lt < k) & (k <= fg.n_le)
        move_left = fg.g_hi < 0
        return dict(
            yL=jnp.where(move_left, t, s["yL"]),
            fL=jnp.where(move_left, fg.f, s["fL"]),
            gL=jnp.where(move_left, fg.g_hi, s["gL"]),
            yR=jnp.where(move_left | exact, s["yR"], t),
            fR=jnp.where(move_left | exact, s["fR"], fg.f),
            gR=jnp.where(move_left | exact, s["gR"], fg.g_lo),
            t=t, exact=s["exact"] | exact, it=s["it"] + 1,
        )

    s = jax.lax.while_loop(cond, body, state)
    return jnp.where(s["exact"], s["t"], 0.5 * (s["yL"] + s["yR"]))


def pytree_quantile_per_leaf(tree, q, *, abs_values: bool = True,
                             method: Optional[str] = None,
                             maxit: int = 64):
    """EXACT per-leaf q-quantiles of a pytree in ONE segmented solve.

    Flattens the tree to one concatenated array with a leaf-id segment
    vector (leaf boundaries are static, so the per-leaf target ranks
    resolve host-side at f64) and runs a single
    ``selection.segmented_order_statistic`` — every engine data pass is
    shared by all leaves, so K per-layer thresholds cost the passes of one
    scalar quantile, not K of them.  Returns a pytree with the same
    structure holding one scalar threshold per leaf.

    Unlike :func:`pytree_quantile` (which never reshapes its leaves, so
    sharded gradients stay sharded), the concatenation materializes the
    flattened |tree| once — the per-leaf regime is the single-device /
    replicated-clip path; see ``benchmarks/clip_bench.py`` for the
    head-to-head.
    """
    leaves = list(jax.tree.leaves(tree))
    if not leaves:
        return tree
    sizes = [int(l.size) for l in leaves]

    def absf(l):
        l = l.astype(jnp.float32)
        return jnp.abs(l) if abs_values else l

    x = jnp.concatenate([absf(l).reshape(-1) for l in leaves])
    seg = jnp.concatenate([jnp.full((s,), i, jnp.int32)
                           for i, s in enumerate(sizes)])
    res = selection.segmented_quantiles(x, seg, q, sizes, method=method,
                                        maxit=maxit)
    return jax.tree.unflatten(jax.tree.structure(tree),
                              [res.value[i] for i in range(len(sizes))])


def hist_quantile(tree, q, *, bins: int = 512, abs_values: bool = True):
    """Two-pass histogram quantile over a pytree (|x| by default) —
    APPROXIMATE, by bin resolution.

    Pass 1: min/max; pass 2: one 512-bin histogram (log-spaced) built with
    scatter-adds; the quantile is read from the cumulative histogram.  Bin
    resolution ~1.8% relative — plenty for clipping — at 2 data sweeps vs
    the CP solver's ~maxit sweeps.  The histogram is additive across shards
    (one psum of 512 floats under GSPMD), preserving the paper's
    scalar-ish-communication property.

    For EXACT thresholds at a comparable pass count, use the engine's
    binned descent instead: :func:`pytree_quantile` (global, ~maxit CP
    passes), or :func:`pytree_quantile_per_leaf` / the underlying
    ``selection.segmented_quantiles`` (exact per-leaf thresholds, 2-3
    histogram sweeps + an O(cap) finalize) — measured head-to-head in
    ``benchmarks/clip_bench.py``.
    """
    leaves = list(jax.tree.leaves(tree))
    n = sum(l.size for l in leaves)

    def absf(l):
        l = l.astype(jnp.float32)
        return jnp.abs(l) if abs_values else l

    lo = functools.reduce(jnp.minimum, [jnp.min(absf(l)) for l in leaves])
    hi = functools.reduce(jnp.maximum, [jnp.max(absf(l)) for l in leaves])
    lo = jnp.maximum(lo, 1e-12)
    hi = jnp.maximum(hi, lo * (1 + 1e-6))
    llo, lhi = jnp.log(lo), jnp.log(hi)
    scale = (bins - 1) / jnp.maximum(lhi - llo, 1e-12)

    hist = jnp.zeros((bins,), jnp.float32)
    for l in leaves:
        v = jnp.clip(jnp.log(jnp.maximum(absf(l), 1e-12)), llo, lhi)
        idx = ((v - llo) * scale).astype(jnp.int32).reshape(-1)
        hist = hist.at[idx].add(1.0)
    cum = jnp.cumsum(hist)
    k = jnp.float32(q) * jnp.float32(n)
    bin_idx = jnp.argmax(cum >= k)  # first bin reaching the target count
    # upper edge of the bin (conservative for clipping)
    return jnp.exp(llo + (bin_idx.astype(jnp.float32) + 1.0) / scale)


def clip_by_quantile(tree, q: float = 0.99, *, maxit: int = 16,
                     min_scale: float = 1e-8, per_leaf: bool = False):
    """Clip gradient magnitudes at their q-quantile (paper-primitive
    alternative to global-norm clipping; robust to exploding coordinates).

    ``per_leaf=False`` (default): ONE global threshold from
    :func:`pytree_quantile`; returns ``(clipped_tree, threshold)``.

    ``per_leaf=True``: per-LAYER thresholds — every leaf is clipped at its
    own exact q-quantile, all resolved by one segmented multi-k solve
    (:func:`pytree_quantile_per_leaf`: the engine's data passes are shared
    across leaves, so K thresholds cost the passes of one).  Returns
    ``(clipped_tree, thresholds_tree)`` with one scalar per leaf.
    """
    if per_leaf:
        thrs = pytree_quantile_per_leaf(tree, q)
        thrs = jax.tree.map(lambda t: jnp.maximum(t, min_scale), thrs)
        clipped = jax.tree.map(
            lambda g, t: jnp.clip(g, -t.astype(g.dtype), t.astype(g.dtype)),
            tree, thrs)
        return clipped, thrs
    thr = pytree_quantile(tree, q, maxit=maxit)
    thr = jnp.maximum(thr, min_scale)
    clipped = jax.tree.map(
        lambda g: jnp.clip(g, -thr.astype(g.dtype), thr.astype(g.dtype)),
        tree)
    return clipped, thr
