"""Selection (k-th order statistic) by convex minimization — Beliakov (2011).

Batched-first, measure-unified architecture
-------------------------------------------
The engine is *batched-first*: the bracket loop, the exact-hit certificates
and the hybrid finalize all operate on ``(B,)`` state vectors, fed by an
:class:`repro.core.objective.Evaluator` (pivots ``(B,)`` -> :class:`FG`
partials ``(B,)``).  Scalar selection is the ``B = 1`` view.  Two batched
regimes:

* **rows mode** (:func:`select_rows` / :func:`weighted_select_rows`) —
  ``(B, n)`` independent problems with per-row targets, driven by the
  row-wise fused kernels.  This is the production workload: coordinate-wise
  medians, LMS/LTS concentration over elemental starts, kNN cutoff rows,
  Theil-Sen / IRLS weighted medians.
* **shared-x mode** (:func:`multi_order_statistic` / :func:`quantiles` and
  the weighted variants) — ONE array, ``(K,)`` targets, driven by the
  multi-pivot Pallas kernels that read each ``x`` tile into VMEM once and
  emit partials for all K live pivots — K× less HBM traffic than K
  lock-stepped independent solves.

There is ONE engine for counts and weights (see ``objective.py``): the
loops compare the evaluator's measure fields (``m_lt``/``m_le`` — int32
counts on the counting leg, fp weight masses on the weighted leg) against
the target measure ``k``, while the int32 element counts keep driving the
cap-based stopping rule on both legs (buffer capacity is a count, not a
mass).  Uniform weights with ``wk = k`` make every mass comparison an exact
integer-valued comparison, reproducing the counting decisions bit for bit —
weighted selection is not a second code path, and the counting leg still
rides the smaller four-partial kernels (no weights array read from HBM).

Methods (shared skeleton, they differ only in the next-pivot proposal):

* ``binned``    — binned bracket descent (default for large n): each data
  pass histograms the live bracket into ``nbins`` sub-intervals, so one
  sweep buys log2(nbins) bisection-equivalents of narrowing (Tibshirani's
  successive-binning, arXiv:0806.3301, generalized to any order statistic,
  any weight measure, and to batched/sharded data).  Phase 1 runs ~2-3
  histogram sweeps until every row's in-bracket count is under ``cap``;
  phase 2 compacts the survivors into the ``(B, cap)`` buffer and finalizes
  exactly — O(cap) work on O(n) data touched ~3 times instead of ~15.
* ``binned_polish`` — binned descent + in-bin CP polish: every sweep
  centers half its bins geometrically around the cutting-plane cut derived
  from the PREVIOUS sweep's per-bin sums (the support-line intersection
  inside the straddling bin — see :func:`binned_loop_batched`), so the
  next sweep resolves the answer's neighborhood at ~2^-30 of the bracket
  instead of 1/nbins.  Fewer sweeps on hard mass distributions, same
  certificates: the polish only chooses WHERE the realized edges go; every
  narrowing decision still runs through the measured-count invariants.
* ``cp``        — Kelley's cutting-plane method (Algorithm 1 of the paper).
* ``bisection`` — classical bisection on the subgradient sign (paper Sec. III).
* ``golden``    — golden-section-style bracket shrink (paper baseline).
* ``brent``     — parabolic fit with bisection safeguard (paper baseline).
* ``sort``      — full ``jnp.sort`` (the paper's "GPU radix sort" baseline).

Each iteration costs exactly one fused pass over the data — the paper's
``maxit + O(1)`` parallel reductions — regardless of how many problems ride
in the batch; ``binned`` needs ~3 such passes where ``cp`` needs ~15.
``method=None`` (the default) resolves to ``binned`` for
``n >= BINNED_MIN_N`` on EVERY backend: a kernel sweep reads the data once,
like an FG pass (its device time is not measured yet), and the jnp path's
verified arithmetic binning (``kernels.ref.bin_slots``: multiply/floor/clip
slots checked against the realized edges, factored one-hot reduction)
brought the CPU sweep from ~25-70x a fused pass down to ~2-4x (below one
cp engine-iteration at engine granularity) — so 2-3 sweeps beat ~9 cp
passes end-to-end at 1M where binned used to lose 10x — see
``_resolve_method`` / ``_resolve_nbins`` and BENCH_selection.json.

Exactness: unlike the paper (which stops on a float tolerance and then scans
for the largest ``x_i <= y~``), we carry the measures through the loop PER
ROW, which yields

  1. an *exact-hit* certificate ``m_lt < k <= m_le  =>  pivot == x_(k)``;
  2. a count-based stopping rule ``count(y_L < x <= y_R) <= cap`` that turns
     the paper's dynamic-size ``copy_if`` into a *static-shape* fixed-capacity
     compaction (required for ``jit``), performed row-wise into a
     ``(B, cap)`` buffer sorted in one batched sort;
  3. a tie-safe fallback: if more than ``cap`` duplicates of ``x_(k)`` exist
     in a row, the next distinct value above that row's ``y_L`` is verified
     by one extra counting pass.

Rows stop independently (per-row live mask); the loop exits when every row
has either certified an exact hit or shrunk its pivot interval under ``cap``.

Invariants maintained per row (proved by the subdifferential signs, see
``objective.py``):   measure(x <= y_L) < k <= measure(x <= y_R).

fp contract for the weighted leg: masses accumulate in floating point, so
results are bit-identical to the f64 sorted-cumsum oracle exactly when the
weights are exactly summable (integers / bounded dyadics, incl. uniform ==
the counting engine bit-for-bit); otherwise the answer is a data element
certified by the engine's own measured invariant, within one mass-rounding
of the oracle.  The late-sweep ``hit_lo`` binned certificate is demoted to
a stall (only sweep 1 may pin ``xmin``): with inexact masses an ulp-flip
could otherwise mint a non-element edge value — on the counting leg the
demotion is provably dead code (exact integer prefix counts make a late
fire impossible), so the one gate serves both legs.

``transform='log1p'`` and the batched finalize: the loop runs on the
monotone image ``F(x) = log1p(x - min(x))`` (per row in rows mode), and the
final bracket is mapped back to original values *data-consistently* before
the exact finalize — ``y_orig = max{x_i : F(x_i) <= y_t}`` preserves counts
exactly, so the row invariants transfer and the compaction/tie logic runs on
untransformed data.  Exact-hit certificates do NOT survive the fp roundtrip
(F is not injective in fp): they are dropped per row and re-derived by the
original-space finalize.

Phase names: every engine phase runs under one ``jax.named_scope``, the
same on the local and the distributed path, so each compiled op's
``op_name`` metadata names its phase (metadata only: no op, no host work):
``sel.seed`` (extreme/mean stats and the analytic bracket seed),
``sel.sweep`` (the bracket loop: edges, data pass, prefix measures,
narrowing, psum rounds), ``sel.compact`` (survivor mask, rank cumsum and
search, gathers), ``sel.probe`` (certificate passes: ``cL``, ``vnext``,
``m_le_v``, ``m_lt_max``) and ``sel.sort`` (the cap-buffer sort and the
answer/status cascade).  The scopes never nest.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.objective import (
    FG,
    Evaluator,
    RowsEvaluator,
    SharedEvaluator,
    _weight_accum_dtype,
    os_weights,
)
from repro.core import transforms

METHODS = ("binned", "binned_polish", "cp", "cp_hybrid", "bisection",
           "golden", "brent", "sort")

# method=None resolution: histogram sweeps win once the O(n) data pass
# dominates (~3 sweeps vs ~15 CP passes); below this the per-sweep bin
# bookkeeping isn't worth it and Kelley cuts converge in microseconds.
BINNED_MIN_N = 1 << 16

# Sub-intervals per histogram sweep on the Pallas kernel path (one sweep =
# log2(128) = 7 bisection-equivalents of bracket narrowing); the kernels
# take the bin count from the edge array the engine builds.
DEF_NBINS = 128

# Scalar survivor-buffer bound on the kernel path (``_default_cap``).  The
# rank search of ``rank_compact`` costs log2(n) dependent gathers a slot
# (~17 ns each on a TPU v5e): at n = 2^28, 2^19 slots took 245 ms of a
# 383 ms median and 4096 take 1.9 ms for 0.76 more 28 ms sweeps; 4096 was
# the fastest of 2^12..2^15 there (PERF.md, the cap calibration).
KERNEL_CAP = 4096

# jnp-path default: the verified-arithmetic histogram's factored one-hot
# reduction scales with the slot count, and a 16-bin sweep (4 bisection
# equivalents) already resolves 1M -> cap in 2 sweeps — the CPU-measured
# knee (see BENCH_selection.json hist_pass).
DEF_NBINS_JNP = 16

BINNED_IMPLS = (None, "searchsorted", "arithmetic")


def _kernel_path(backend: Optional[str]) -> bool:
    from repro.kernels.ops import _on_tpu  # deferred: core <-> kernels

    return backend in ("pallas", "pallas_interpret") or (
        backend is None and _on_tpu())


def _resolve_method(method: Optional[str], n: int,
                    backend: Optional[str] = None) -> str:
    """``None``/``'auto'`` -> 'binned' for large n on EVERY backend.

    The binned descent is a bandwidth trade: each sweep touches the data
    once but buys log2(nbins) bisection steps.  On the Pallas kernel path a
    sweep costs the same HBM traffic as a fused FG pass; on the CPU jnp
    path the verified arithmetic-binning pass (multiply/floor/clip slots +
    factored one-hot reduction, see ``kernels.ref.bin_slots``) brought the
    sweep from ~25-70x a fused pass down to ~2-4x at 1M
    (BENCH_selection.json, ``hist_pass``), so 2-3 sweeps beat ~9 cp
    passes end-to-end (binned used to lose ~10x on CPU) and auto picks
    'binned' everywhere above ``BINNED_MIN_N`` — the schedule whose pass
    count scales as log(nbins) per data touch.  Auto stays on plain
    'binned' (not 'binned_polish') until the polish schedule is
    TPU-validated (see ROADMAP).
    """
    if method in (None, "auto"):
        return "binned" if n >= BINNED_MIN_N else "cp"
    if method not in METHODS:
        raise ValueError(f"unknown method {method!r}; one of {METHODS}")
    return method


def _resolve_nbins(nbins: Optional[int], backend: Optional[str],
                   dtype=None) -> int:
    """``None`` -> the backend's sweep width: ``DEF_NBINS`` (128) on the
    kernel path (not yet timed on a chip: each slot costs the kernel a
    compare and a row reduction per element tile, so 128 bins may make the
    sweep compute-bound rather than HBM-bound), ``DEF_NBINS_JNP`` (16) on
    the jnp path where the factored reduction's cost is ~linear in the slot
    count.  Both resolve 1M -> cap in 2 sweeps; explicit values always
    win.

    ``dtype``: the data's (promoted) dtype — f64 inputs are rerouted by
    ``kernels.ops`` to the jnp oracle even when the kernel path was
    requested (``pallas_interpret`` deliberately excepted), so their
    sweeps get the jnp-tuned width too.
    """
    if nbins is not None:
        return int(nbins)
    kernel = _kernel_path(backend)
    if (kernel and backend != "pallas_interpret" and dtype is not None
            and jnp.dtype(dtype) == jnp.float64):
        kernel = False  # the f64 reroute lands this pass on the jnp oracle
    return DEF_NBINS if kernel else DEF_NBINS_JNP


def _check_binned_impl(binned_impl: Optional[str]) -> Optional[str]:
    if binned_impl not in BINNED_IMPLS:
        raise ValueError(f"unknown binned_impl {binned_impl!r}; one of "
                         f"{BINNED_IMPLS}")
    return binned_impl

# Status codes for SelectResult.status
EXACT_HIT = 0       # pivot certified equal to x_(k) during iterations
HYBRID_SORT = 1     # answer from compact+sort of the pivot interval
TIE_FALLBACK = 2    # answer = next distinct value, certified by counts
NOT_CONVERGED = 3   # approximate answer (bracket right end)


class SelectResult(NamedTuple):
    value: jax.Array        # the order statistic (exact unless status==3)
    iters: jax.Array        # number of f/g evaluations this row was live for
    status: jax.Array       # see codes above
    y_lo: jax.Array         # final bracket
    y_hi: jax.Array
    n_in: jax.Array         # count(y_lo < x <= y_hi) at exit


class Prior(NamedTuple):
    """Warm-start carry for repeated selection (``prior=`` on every public
    API): the previous answer, its realized bracket, and the last polish
    cut.  All fields are arrays broadcastable to the solve's batch shape
    ((B,) rows / (K,) shared-x / scalar distributed).

    The prior steers only the FIRST pivot (cp family) or the FIRST sweep's
    edge PLACEMENT (binned family, :func:`prior_edges`) — exactly the
    polish-cut contract: every narrowing decision and every certificate
    still runs off measured prefix invariants, so a stale, garbage, NaN or
    wrong-array prior costs sweeps (or a psum round), never exactness.
    Build one from a previous :class:`SelectResult` with :func:`as_prior`
    (also accepted directly as the ``prior=`` argument)."""
    value: jax.Array   # previous answer
    y_lo: jax.Array    # realized final bracket, reused verbatim as edges
    y_hi: jax.Array
    cut: jax.Array     # last polish cut (seeds the carried in-bin CP cut)


def as_prior(prior) -> Optional["Prior"]:
    """Normalize a ``prior=`` argument: ``None`` | :class:`Prior` |
    :class:`SelectResult` (the natural carry — bracket reused verbatim,
    the answer doubles as the cut) | bare value (answer-only seed)."""
    if prior is None:
        return None
    if isinstance(prior, Prior):
        return prior
    if isinstance(prior, SelectResult):
        return Prior(value=prior.value, y_lo=prior.y_lo, y_hi=prior.y_hi,
                     cut=prior.value)
    v = jnp.asarray(prior)
    return Prior(value=v, y_lo=v, y_hi=v, cut=v)


class BatchState(NamedTuple):
    """Bracket-loop state; every field is (B,)-shaped except the scalar
    global iteration counter ``it`` (frozen rows stop updating but the batch
    iterates until all rows are done)."""
    yL: jax.Array
    fL: jax.Array
    gL: jax.Array   # right one-sided derivative at yL (< 0)
    yR: jax.Array
    fR: jax.Array
    gR: jax.Array   # left one-sided derivative at yR (> 0)
    cleL: jax.Array  # lower bound on count(x <= yL)  (exact after 1st move)
    cleR: jax.Array  # exact count(x <= yR)
    t_exact: jax.Array
    found_exact: jax.Array
    iters: jax.Array  # per-row live-iteration count
    it: jax.Array     # global (batch) iteration count
    # golden/brent bookkeeping: previous probe (for parabolic fit); the
    # binned polish reuses it as the carried in-bin CP cut
    tp: jax.Array
    fp: jax.Array


def _propose_cp(s: BatchState):
    """Kelley cut intersection: minimizer of max of the two support lines."""
    return (s.fR - s.fL + s.yL * s.gL - s.yR * s.gR) / (s.gL - s.gR)


def _propose_bisection(s: BatchState):
    return 0.5 * (s.yL + s.yR)


_INV_GOLDEN = 0.381966011250105  # 2 - golden ratio


def _propose_golden(s: BatchState):
    # Shrink from the side whose objective value is larger (descent side).
    left = s.fL > s.fR
    w = jnp.where(left, _INV_GOLDEN, 1.0 - _INV_GOLDEN)
    return s.yL + w * (s.yR - s.yL)


def _propose_brent(s: BatchState):
    """Parabola through (yL,fL), (tp,fp), (yR,fR); midpoint safeguard."""
    x1, f1, x2, f2, x3, f3 = s.yL, s.fL, s.tp, s.fp, s.yR, s.fR
    num = (x2 - x1) ** 2 * (f2 - f3) - (x2 - x3) ** 2 * (f2 - f1)
    den = (x2 - x1) * (f2 - f3) - (x2 - x3) * (f2 - f1)
    ok = jnp.abs(den) > 1e-30
    t = x2 - 0.5 * num / jnp.where(ok, den, 1.0)
    mid = 0.5 * (s.yL + s.yR)
    inside = (t > s.yL) & (t < s.yR)
    return jnp.where(ok & inside, t, mid)


_PROPOSALS = {
    "cp": _propose_cp,
    "cp_hybrid": _propose_cp,
    "bisection": _propose_bisection,
    "golden": _propose_golden,
    "brent": _propose_brent,
}


def _live(s: BatchState, cap):
    return (~s.found_exact) & (s.cleR - s.cleL > cap) & (s.yR > s.yL)


@jax.named_scope("sel.seed")
def _seed_state(ev: Evaluator, found0, t0):
    """Shared loop seed: analytic bracket/cut init from one stats pass.

    Returns ``(s0, xmin, xmax, kk, dtype)``; used by both the cutting-plane
    loop and the binned histogram loop (the f/g fields seed the former's
    cuts and the polish's first in-bin jump).

    Counting leg: the slopes use the paper's normalized weights with the
    conservative tie count 1, which keeps the support lines *lower* bounds
    (valid cuts) even with duplicated extremes.  Weighted leg: the
    mass-normalized coefficients ``alpha = (W - wk)/W`` and ``beta = wk/W``
    (zero-crossing exactly at mass ``wk``) with the conservative extreme
    slopes ``-wk/W`` / ``(W - wk)/W`` (no mass assumed at the extremes —
    flatter than the truth, so the support lines stay lower bounds); ``f``
    seeds anchor on the weighted mean.
    """
    xmin, xmax, xmean = ev.init_stats()
    k = ev.k
    shape = jnp.broadcast_shapes(jnp.shape(xmin), jnp.shape(k))
    dtype = xmin.dtype
    kk = jnp.broadcast_to(jnp.asarray(k), shape)
    bc = lambda v: jnp.broadcast_to(jnp.asarray(v, dtype), shape)
    xmin, xmax, xmean = bc(xmin), bc(xmax), bc(xmean)

    if ev.weighted:
        Wf = jnp.broadcast_to(jnp.asarray(ev.W, kk.dtype), shape)
        Wsafe = jnp.maximum(Wf, jnp.asarray(1e-30, Wf.dtype))
        alpha = ((Wf - kk) / Wsafe).astype(dtype)
        beta = (kk / Wsafe).astype(dtype)
        gL0, gR0 = -beta, alpha
    else:
        nf = jnp.broadcast_to(jnp.asarray(ev.n, dtype), shape)
        alpha, beta = os_weights(nf, kk, dtype)
        gL0 = alpha * (1.0 / nf) - beta * (nf - 1.0) / nf
        gR0 = alpha * (nf - 1.0) / nf - beta * (1.0 / nf)

    # Analytic init at the extremes (paper: single fused reduction).
    fL0 = beta * (xmean - xmin)
    fR0 = alpha * (xmax - xmean)

    if found0 is None:
        found0 = jnp.zeros(shape, bool)
    if t0 is None:
        t0 = jnp.full(shape, jnp.nan, dtype)
    s0 = BatchState(
        yL=xmin, fL=fL0, gL=gL0,
        yR=xmax, fR=fR0, gR=gR0,
        cleL=jnp.ones(shape, jnp.int32),   # count(x<=min) >= 1 (conservative)
        cleR=jnp.broadcast_to(jnp.asarray(ev.n, jnp.int32), shape),
        t_exact=t0,
        found_exact=jnp.broadcast_to(found0, shape),
        iters=jnp.zeros(shape, jnp.int32),
        it=jnp.asarray(0, jnp.int32),
        tp=0.5 * (xmin + xmax), fp=jnp.maximum(fL0, fR0),
    )
    return s0, xmin, xmax, kk, dtype


def bracket_loop_batched(
    ev: Evaluator,
    *,
    method: str = "cp",
    maxit: int = 64,
    cap=0,
    found0: Optional[jax.Array] = None,
    t0: Optional[jax.Array] = None,
    prior: Optional[Prior] = None,
):
    """Run the batched bracket-shrinking loop against an evaluator.

    ``ev`` owns the data AND the measure (counts or weight masses — see
    ``objective.py``); this loop only sees ``(B,)`` vectors and compares
    the returned measure fields against the target ``ev.k``:

    * ``m_lt < k <= m_le`` certifies the pivot as the (weighted) order
      statistic (on the counting leg this is the classic count invariant;
      on the weighted leg ``m_lt < m_le`` forces positive mass at the
      pivot, so a certified pivot is a data element);
    * ``m_le < k`` means the pivot is strictly left of the minimizer
      (``== g_hi < 0`` in exact arithmetic, but compared in the measure's
      own dtype — exact int32 on the counting leg).

    ``cap`` is the per-row stopping count (0 = iterate to exact hit /
    maxit, the distributed across-axis regime); ``cleL``/``cleR`` carry
    INTEGER counts on both legs — the compaction buffer is sized in
    elements, not mass.  ``found0``/``t0`` pre-seed rows whose answer is
    already certified (e.g. extreme ranks) so they never go live.

    ``prior``: warm-start carry — the prior answer overrides the FIRST
    proposal only, and only where it is finite and strictly inside the
    open bracket; the measured partials decide every move, so an exact
    prior certifies in one pass and a wrong one costs passes, never
    exactness.

    Returns ``(final BatchState, xmin, xmax)`` with per-row extremes.
    """
    propose = _PROPOSALS[method]
    s0, xmin, xmax, kk, dtype = _seed_state(ev, found0, t0)
    pv0 = None
    if prior is not None:
        pv0 = jnp.broadcast_to(jnp.asarray(prior.value, dtype),
                               s0.yL.shape)

    def cond(s: BatchState):
        return (s.it < maxit) & jnp.any(_live(s, cap))

    def body(s: BatchState):
        lv = _live(s, cap)
        t = propose(s)
        # numerical safeguard: keep strictly inside the open bracket (frozen
        # rows get the midpoint — their updates are masked out anyway)
        bad = ~jnp.isfinite(t) | (t <= s.yL) | (t >= s.yR)
        t = jnp.where(bad, 0.5 * (s.yL + s.yR), t).astype(dtype)
        if pv0 is not None:
            use = ((s.it == 0) & jnp.isfinite(pv0)
                   & (pv0 > s.yL) & (pv0 < s.yR))
            t = jnp.where(use, pv0, t)
        fg: FG = ev(t)
        exact = (fg.m_lt < kk) & (kk <= fg.m_le) & lv
        # exact => 0 in [g_lo, g_hi] => g_hi >= 0, so the two are disjoint:
        move_left = (fg.m_le < kk) & lv  # t strictly left of the minimizer
        move_right = lv & ~move_left & ~exact  # then m_lt >= k: right of it
        return BatchState(
            yL=jnp.where(move_left, t, s.yL),
            fL=jnp.where(move_left, fg.f, s.fL),
            gL=jnp.where(move_left, fg.g_hi, s.gL),
            yR=jnp.where(move_right, t, s.yR),
            fR=jnp.where(move_right, fg.f, s.fR),
            gR=jnp.where(move_right, fg.g_lo, s.gR),
            cleL=jnp.where(move_left, fg.n_le, s.cleL),
            cleR=jnp.where(move_right, fg.n_le, s.cleR),
            t_exact=jnp.where(exact, t, s.t_exact),
            found_exact=s.found_exact | exact,
            iters=s.iters + lv.astype(jnp.int32),
            it=s.it + 1,
            tp=jnp.where(lv, t, s.tp), fp=jnp.where(lv, fg.f, s.fp),
        )

    with jax.named_scope("sel.sweep"):
        return jax.lax.while_loop(cond, body, s0), xmin, xmax


def binned_descent_step(cum, edges, yL, yR, kk):
    """One binned-descent narrowing decision from prefix measures.

    ``cum[..., j] = measure(x <= e_j)`` at the realized ``edges``
    ``(..., nbins+1)`` of the bracket ``[yL, yR]`` (leading dims = batch,
    possibly none) — int32 prefix counts on the counting leg, fp prefix
    masses on the weighted leg (the comparisons below are ordering-only,
    so both take the same path); ``edges`` MUST be the same array the
    histogram pass binned against — it is computed once per sweep and
    shared, never recomputed (XLA FMA contraction makes recomputed edge
    arithmetic fusion-context-dependent).  Returns
    ``(yLn, yRn, cLn, cRn, jm1, jstar, hit_lo, exact, stall)``:

    * ``jstar`` — first edge whose prefix measure reaches ``kk``; the
      answer lies in the single bin ``(e_{jstar-1}, e_jstar]``;
    * ``hit_lo`` — ``jstar == 0``, i.e. ``measure(x <= yL) >= k``: possible
      only while ``yL`` is the initial minimum (afterwards the invariant
      ``measure(x <= yL) < k`` forbids it), and certifies ``x_(k) == yL``;
    * ``exact`` — ``hit_lo`` or ulp-collapse: ``(yLn, yRn]`` holds a single
      representable value, so the invariant certifies ``x_(k) == yRn``;
    * ``stall`` — the chosen bin IS the whole bracket (bin width underflowed
      against denormal-scale data), or the prefix measures are inconsistent
      with the bracket invariant (``cum[-1] < k`` — NaN data, a kernel
      miscount): no trustworthy progress is possible, the caller should
      freeze this problem and let its finalize fallback resolve it.

    This is the exactness-critical core of the binned method, shared by the
    batched loop below and the distributed loop in ``core.distributed`` —
    keep it the single implementation.
    """
    reached = cum >= kk[..., None]
    jstar = jnp.argmax(reached, axis=-1).astype(jnp.int32)
    jm1 = jnp.maximum(jstar - 1, 0)
    take = lambda a, i: jnp.take_along_axis(a, i[..., None], axis=-1)[..., 0]
    yLn, yRn = take(edges, jm1), take(edges, jstar)
    cLn, cRn = take(cum, jm1), take(cum, jstar)
    # measure-invariant sanity: measure(x <= yR) >= k must hold; if it
    # doesn't, argmax over all-False returned 0 and NOTHING below may
    # certify — a violated invariant must fail safe (stall), never mint
    # EXACT_HIT.
    ok = reached[..., -1]
    hit_lo = (jstar == 0) & reached[..., 0]
    collapse = transforms.next_float(yLn) >= yRn
    exact = (hit_lo | collapse) & ok
    stall = ~exact & (~ok | ((yLn == yL) & (yRn == yR)))
    return yLn, yRn, cLn, cRn, jm1, jstar, hit_lo, exact, stall


def polish_edges(lo, hi, t, nbins: int):
    """CP-centered realized bin edges for one polish sweep.

    Half the edges cover ``[lo, hi]`` uniformly (worst-case factor
    ``nbins/2`` shrink, exactly like a plain sweep with fewer bins); the
    other half sit geometrically around the carried cut ``t`` at offsets
    ``halfwidth * 2^-j`` down to ``~2^-(nbins/4)`` of the bracket — when
    ``t`` is near the answer (it is: ``t`` is the in-bin support-line
    intersection of the previous sweep), the straddling bin comes out
    orders of magnitude narrower than ``1/nbins`` of the bracket.

    Exactness is inherited, not re-proven: the output is a monotone
    (sorted) array of realized fp values in ``[lo, hi]`` with
    ``e_0 == lo`` and ``e_nbins == hi`` exactly, built ONCE per sweep and
    shared by the histogram pass and the narrowing decision — the same
    contract as ``kernels.ref.bin_edges``, which supplies the uniform
    half.  A garbage cut (NaN / out of bracket) degrades to the bracket
    midpoint; the certificates never trust the cut itself.  The endpoint
    anchoring is pinned AFTER the sort: on FTZ hardware a denormal-scale
    bracket makes the ladder values compare DAZ-equal, and the sort may
    otherwise scramble which bit pattern lands at the ends (every value is
    already clipped into ``[lo, hi]``, so the pin preserves the platform
    ordering).
    """
    from repro.kernels.ref import bin_edges  # deferred: core <-> kernels

    lo = jnp.asarray(lo)
    hi = jnp.asarray(hi, lo.dtype)
    nu = nbins // 2
    m = (nbins - nu) // 2
    extra = nbins - nu - 2 * m
    base = bin_edges(lo, hi, nu)                       # (..., nu + 1)
    mid = 0.5 * lo + 0.5 * hi
    t = jnp.asarray(t, lo.dtype)
    tc = jnp.clip(jnp.where(jnp.isfinite(t), t, mid), lo, hi)
    half = hi / 2 - lo / 2   # overflow-safe half-width (divide BEFORE diff)
    j = jnp.arange(1, m + 1, dtype=lo.dtype)
    d = half[..., None] * jnp.asarray(2.0, lo.dtype) ** (-j)
    lo1, hi1 = lo[..., None], hi[..., None]
    ladder = jnp.concatenate(
        [jnp.clip(tc[..., None] - d, lo1, hi1),
         jnp.clip(tc[..., None] + d, lo1, hi1)], axis=-1)
    parts = [base, ladder]
    if extra:
        parts.append(jnp.broadcast_to(tc[..., None], tc.shape + (extra,)))
    e = jnp.sort(jnp.concatenate(parts, axis=-1), axis=-1)
    return e.at[..., 0].set(lo).at[..., -1].set(hi)


def prior_edges(lo, hi, prior: Prior, nbins: int):
    """Prior-seeded realized bin edges for the FIRST sweep of a warm solve.

    Layout (``nbins + 1`` edges total, same realized-edges contract as
    :func:`polish_edges` — sorted, clipped into ``[lo, hi]``, endpoints
    pinned after the sort, built ONCE and shared by the histogram pass and
    the narrowing decision):

    * half the edges cover ``[lo, hi]`` uniformly — the worst-case
      guarantee: a garbage prior still buys a factor ``nbins/2`` shrink;
    * the prior's realized bracket endpoints ``y_lo``/``y_hi`` are placed
      VERBATIM — when the data is unchanged, the carried bracket's
      in-bracket count is already under cap, so the sweep-1 straddling bin
      lands inside it and the row stops after ONE sweep;
    * the pair ``(prev_float(value), value)`` — an unchanged answer makes
      the straddling bin a single-representable-value bin, so the existing
      ulp-collapse certificate in :func:`binned_descent_step` fires:
      steady-state re-selection is 1 sweep WITH an exact-hit certificate;
    * the rest is a geometric ladder around ``value`` at offsets
      ``w0 * 2^j`` with ``w0 = max(y_hi - y_lo, 1 ulp)`` — small drift
      lands in a bin about one prior-bracket wide (still ~cap elements).

    Soundness is inherited, not re-proven: like the polish cut, the prior
    chooses WHERE edges go; NaN/inf fields degrade to the bracket midpoint
    and every certificate runs off measured prefix measures.
    """
    from repro.kernels.ref import bin_edges  # deferred: core <-> kernels

    lo = jnp.asarray(lo)
    hi = jnp.asarray(hi, lo.dtype)
    dt = lo.dtype
    mid = 0.5 * lo + 0.5 * hi
    san = lambda v: jnp.clip(
        jnp.where(jnp.isfinite(v), jnp.asarray(v, dt), mid), lo, hi)
    pv = san(jnp.asarray(prior.value, dt))
    plo = san(jnp.asarray(prior.y_lo, dt))
    phi = san(jnp.asarray(prior.y_hi, dt))
    nu = max(nbins // 2, 1)
    r = nbins - nu
    base = bin_edges(lo, hi, nu)                       # (..., nu + 1)
    sharp = [pv, jnp.clip(transforms.prev_float(pv), lo, hi), plo, phi][:r]
    m = (r - len(sharp)) // 2
    extra = r - len(sharp) - 2 * m
    parts = [base]
    if sharp:
        parts.append(jnp.stack(jnp.broadcast_arrays(*sharp), axis=-1))
    if m > 0:
        fmax = jnp.asarray(jnp.finfo(dt).max, dt)
        w0 = jnp.maximum(phi - plo, transforms.next_float(pv) - pv)
        w0 = jnp.clip(w0, jnp.asarray(jnp.finfo(dt).tiny, dt), fmax)
        j = jnp.arange(m, dtype=dt)
        d = jnp.clip(w0[..., None] * jnp.asarray(2.0, dt) ** j, 0, fmax)
        lo1, hi1 = lo[..., None], hi[..., None]
        parts.append(jnp.clip(pv[..., None] - d, lo1, hi1))
        parts.append(jnp.clip(pv[..., None] + d, lo1, hi1))
    if extra:
        parts.append(jnp.broadcast_to(pv[..., None], pv.shape + (extra,)))
    e = jnp.sort(jnp.concatenate(parts, axis=-1), axis=-1)
    return e.at[..., 0].set(lo).at[..., -1].set(hi)


def binned_loop_batched(
    ev: Evaluator,
    *,
    nbins: int = DEF_NBINS,
    maxit: int = 16,
    cap=0,
    found0: Optional[jax.Array] = None,
    t0: Optional[jax.Array] = None,
    polish: bool = False,
    prior: Optional[Prior] = None,
):
    """Phase 1 of the binned two-phase schedule: histogram bracket descent.

    Each sweep builds the bracket's realized edges once
    (``kernels.ref.bin_edges``; :func:`polish_edges` when ``polish``),
    calls ``ev.histogram(edges)`` — ONE fused data pass — and narrows every
    live row's bracket to the single sub-interval ``(e_{j-1}, e_j]`` whose
    prefix MEASURE straddles that row's target
    (``measure(x <= e_{j-1}) < k <= measure(x <= e_j)``), a factor-``nbins``
    shrink per pass where the cutting-plane loop gets one pivot.  The
    measure is the evaluator's: int32 counts or fp weight masses — the
    narrowing decision (:func:`binned_descent_step`) is ordering-only, so
    both legs take the same path and the fail-safe certificate gates carry
    over verbatim.  Integer prefix counts at the chosen edges keep feeding
    the cap-based stopping rule on both legs.  Rows stop independently once
    their in-bracket count is under ``cap`` (phase 2, the survivor
    compaction + exact finalize, takes over), on the exact certificates
    below, or at ``maxit``.

    Exactness bookkeeping mirrors the cutting-plane loop: brackets only move
    to REALIZED fp edge values whose prefix measures were measured, so the
    row invariant ``measure(x <= yL) < k <= measure(x <= yR)`` holds exactly
    at every step and transfers to the finalize (and across the log1p
    roundtrip).  Two in-loop certificates short-circuit a row: a first-sweep
    ``measure(x <= xmin) >= k`` pins ``x_(k) = xmin``, and a bracket
    collapsed to one representable value ``(yL, nextafter(yL)]`` pins
    ``x_(k) = yR``.  A LATE ``hit_lo`` is demoted to a stall: with inexact
    masses it can only be a summation-order ulp-flip (the invariant forbids
    it in exact arithmetic) and must never mint a non-element edge value;
    on the counting leg the exact integer prefix counts make a late fire
    impossible, so the one gate serves both legs for free.

    The in-bin CP polish (``polish=True``): the histogram pass already
    emits per-slot sums ``Σ (w·)x``, so the convex objective's support
    lines at the straddling bin's edges come free — with prefix measures
    ``M`` and prefix sums ``S``, the support line anchored at edge ``e`` is
    ``ψ(e) + (M(e) - k)·(y - e)`` with ``ψ(e) = e·M(e) - S(e) - k·e``
    (+const), and the Kelley intersection of the two bin-edge lines
    algebraically collapses to the bin's mass centroid
    ``(S_R - S_L)/(M_R - M_L) = Σ_bin w·x / Σ_bin w``.  The loop carries
    that cut (seeded from the analytic extreme cuts before sweep 1) and
    hands it to :func:`polish_edges`, so the NEXT sweep already has
    near-ulp resolution around the minimizer — typically saving the last
    uniform sweep.  The cut steers only edge PLACEMENT; every certificate
    still runs off measured prefix invariants, so a bad cut costs a sweep,
    never exactness.

    ``prior`` (warm start): sweep 1's edges come from :func:`prior_edges`
    instead of the uniform/polish layout — the prior's realized bracket
    endpoints are reused verbatim and the ``(prev_float(value), value)``
    pair makes an unchanged answer collapse-certify in exactly one sweep;
    the prior's carried cut also seeds ``tp`` (overriding the analytic
    polish seed).  Same contract as the polish cut: placement only.

    Returns ``(BatchState, xmin, xmax)`` like :func:`bracket_loop_batched`;
    the f/g cut fields keep their analytic seeds (only the polish seed
    reads them), and ``iters`` counts histogram sweeps.
    """
    from repro.kernels.ref import bin_edges  # deferred: core <-> kernels

    s0, xmin, xmax, kk, dtype = _seed_state(ev, found0, t0)
    # Brackets narrow to realized fp edge values and the finalize recounts
    # against exactly those values, so the loop state must not round edges
    # through a storage dtype below the kernels' f32 accumulation (bf16
    # data would otherwise round yL up and break the count invariant).
    dt = jnp.promote_types(dtype, jnp.float32)
    s0 = s0._replace(yL=s0.yL.astype(dt), yR=s0.yR.astype(dt),
                     t_exact=s0.t_exact.astype(dt), tp=s0.tp.astype(dt))
    if polish:
        # seed the carried cut with the analytic CP intersection so even
        # sweep 1 concentrates half its bins near the expected minimizer
        t_seed = _propose_cp(s0)
        bad = ~jnp.isfinite(t_seed) | (t_seed <= s0.yL) | (t_seed >= s0.yR)
        s0 = s0._replace(
            tp=jnp.where(bad, 0.5 * (s0.yL + s0.yR), t_seed).astype(dt))
    pb = None
    if prior is not None:
        pb = Prior(*(jnp.broadcast_to(jnp.asarray(f, dt), s0.yL.shape)
                     for f in prior))
        # the prior's carried cut beats the analytic seed where usable
        okc = jnp.isfinite(pb.cut) & (pb.cut > s0.yL) & (pb.cut < s0.yR)
        s0 = s0._replace(tp=jnp.where(okc, pb.cut, s0.tp))
    stalled0 = jnp.zeros(s0.found_exact.shape, bool)

    def live(s, stalled):
        return _live(s, cap) & ~stalled

    def cond(carry):
        s, stalled = carry
        return (s.it < maxit) & jnp.any(live(s, stalled))

    def body(carry):
        s, stalled = carry
        lv = live(s, stalled)
        # the realized edges are computed ONCE here and shared by the data
        # pass and the narrowing decision (the exactness contract)
        if polish:
            edges = polish_edges(s.yL, s.yR, s.tp, nbins)
        else:
            edges = bin_edges(s.yL, s.yR, nbins)
        if pb is not None:
            # warm start: sweep 1 places its edges from the prior (the
            # realized carried bracket verbatim + the collapse pair around
            # the prior answer); later sweeps revert to the normal layout
            edges = jnp.where(s.it == 0,
                              prior_edges(s.yL, s.yR, pb, nbins), edges)
        cnt, mass, msum = ev.histogram(edges, need_msum=polish)
        # prefix measures at the realized edges drive the narrowing:
        # cum[..., j] = measure(x <= e_j)
        cum = jnp.cumsum(mass[..., :-1], axis=-1)
        yLn, yRn, cLm, cRm, jm1, jstar, hit_lo, exact, stall = \
            binned_descent_step(cum, edges, s.yL, s.yR, kk)
        take = lambda a, i: jnp.take_along_axis(
            a, i[..., None], axis=-1)[..., 0]
        if mass is cnt:
            # counting leg: the prefix measures ARE the integer counts
            cLn, cRn = cLm, cRm
        else:
            # integer prefix counts at the same edges feed the cap rule
            cumn = jnp.cumsum(cnt[..., :-1], axis=-1)
            cLn, cRn = take(cumn, jm1), take(cumn, jstar)
        # late hit_lo can only be an inexact-mass ulp-flip: fail safe (dead
        # code on the counting leg — exact prefixes cannot fire it late)
        late_hit_lo = hit_lo & (s.it > 0)
        exact = lv & exact & ~late_hit_lo
        t_ex = jnp.where(hit_lo, s.yL, yRn)
        # stalled rows freeze; the finalize's fallback chain resolves them
        # from the current bracket instead of burning sweeps to maxit
        stall_n = lv & (stall | late_hit_lo)
        upd = lv & ~exact & ~stall_n
        if polish:
            if msum is None:
                raise ValueError(
                    "binned polish needs the per-bin sums; this evaluator's "
                    "histogram pass returns msum=None")
            # the in-bin support-line intersection == the straddling bin's
            # mass centroid (see the docstring); guard degenerate bins
            mbin = take(mass, jstar).astype(msum.dtype)
            sbin = take(msum, jstar)
            tcut = sbin / jnp.where(mbin > 0, mbin, 1)
            good = (mbin > 0) & jnp.isfinite(tcut)
            tcut = jnp.where(good, jnp.clip(tcut, yLn, yRn),
                             0.5 * (yLn + yRn)).astype(dt)
            tp_n = jnp.where(upd, tcut, s.tp)
        else:
            tp_n = s.tp
        s = s._replace(
            yL=jnp.where(upd, yLn, s.yL),
            yR=jnp.where(upd, yRn, s.yR),
            cleL=jnp.where(upd, cLn, s.cleL),
            cleR=jnp.where(upd, cRn, s.cleR),
            t_exact=jnp.where(exact, t_ex, s.t_exact),
            found_exact=s.found_exact | exact,
            iters=s.iters + lv.astype(jnp.int32),
            it=s.it + 1,
            tp=tp_n,
        )
        return s, stalled | stall_n

    with jax.named_scope("sel.sweep"):
        s, _ = jax.lax.while_loop(cond, body, (s0, stalled0))
    return s, xmin, xmax


def _run_bracket_phase(ev, method, maxit, cap, nbins, prior=None):
    """Dispatch the phase-1 loop for a resolved method (any evaluator leg).

    ``prior`` threads the warm-start carry into whichever loop runs (first
    sweep's edge placement / first proposal pivot — see the loops)."""
    if method in ("binned", "binned_polish"):
        return binned_loop_batched(ev, nbins=nbins, maxit=maxit, cap=cap,
                                   polish=method == "binned_polish",
                                   prior=prior)
    return bracket_loop_batched(ev, method=method, maxit=maxit, cap=cap,
                                prior=prior)


@jax.named_scope("sel.compact")
def rank_compact(mask_in, cap: int, cols):
    """First-``cap`` survivors of a 1-D mask by RANK GATHER.

    The paper's ``copy_if`` as a static-shape gather: ``pos`` is each
    element's inclusive survivor rank (a cumsum of the mask), so the i-th
    survivor's index is ``searchsorted(pos, i + 1)`` — O(cap log n)
    gathers where a full-length scatter lowers to an O(n) serialized loop
    on XLA:CPU.  Runs under the ``sel.compact`` scope, whose device time
    the benchmark reads as ``finalize.compact_ms_per_call``.
    ``cols`` is a sequence of ``(values, pad)`` pairs gathered at the same
    survivor indices (aligned buffers; ``pad`` fills slots past the last
    survivor).  Returns ``(buffers, n_in)``.  Shared by the local finalize
    (:func:`_compact_interval`) and the distributed per-shard finalize —
    keep it the single implementation.
    """
    n_in = jnp.sum(mask_in, dtype=jnp.int32)
    pos = jnp.cumsum(mask_in.astype(jnp.int32))
    idx = jnp.minimum(
        jnp.searchsorted(pos, jnp.arange(1, cap + 1, dtype=jnp.int32),
                         side="left"),
        mask_in.size - 1).astype(jnp.int32)
    have = jnp.arange(cap) < n_in
    return [jnp.where(have, v[idx], pad) for v, pad in cols], n_in


def _compact_interval(x, w, yL, yR, cap):
    """ONE problem's phase-2 survivor compaction + fallback probes (1-D x).

    The open pivot interval ``(yL, yR]`` lands in a ``(cap,)`` buffer via
    :func:`rank_compact` (first ``cap`` survivors in data order, +inf
    pad), alongside the measure certificates the answer assembly needs —
    ``cLm = measure(x <= yL)``, the in-bracket count, the next distinct
    value above ``yL`` and its inclusive measure (tie fallback
    verification).  Everything downstream is O(cap), not O(n).

    ``w=None`` is the counting leg: the measures are the int32 counts and
    the weight buffer comes back ``None`` (no weight reads).  With
    weights, the (value, weight) PAIRS land in aligned buffers via the
    same rank indices (pad values +inf, pad weights 0 so sorted prefix
    masses are unaffected).
    """
    big = jnp.asarray(jnp.inf, x.dtype)
    with jax.named_scope("sel.compact"):
        mask_in = (x > yL) & (x <= yR)
    with jax.named_scope("sel.probe"):
        cL = jnp.sum(x <= yL, dtype=jnp.int32)
        vnext = jnp.min(jnp.where(x > yL, x, big))
    if w is None:
        (z,), n_in = rank_compact(mask_in, cap, [(x, big)])
        with jax.named_scope("sel.probe"):
            m_le_v = jnp.sum(x <= vnext, dtype=jnp.int32)
        return z, None, cL, n_in, vnext, m_le_v
    dtw = w.dtype
    (z, zw), n_in = rank_compact(mask_in, cap,
                                 [(x, big), (w, jnp.zeros((), dtw))])
    with jax.named_scope("sel.probe"):
        cLw = jnp.sum(jnp.where(x <= yL, w, 0), dtype=dtw)
        w_le_v = jnp.sum(jnp.where(x <= vnext, w, 0), dtype=dtw)
    return z, zw, cLw, n_in, vnext, w_le_v


@jax.named_scope("sel.sort")
def _assemble_answers(kk, s: BatchState, cap, zs, zws, cLm, n_in, vnext,
                      m_le_v, m_lt_max, xmin, xmax) -> SelectResult:
    """Per-problem answer/status cascade from compacted buffers + measures.

    Shared by the rows-mode and shared-x finalizes on BOTH measure legs —
    all inputs are batch-shaped except the value-sorted ``(B, cap)`` buffer
    ``zs`` and its aligned weights ``zws`` (``None`` on the counting leg).

    Counting leg (``zws is None``): the in-buffer answer is direct indexing
    at ``k - cL - 1`` and the extreme shortcuts fire off the exact integer
    measures alone.  Weighted leg: the answer is the first survivor whose
    cumulative mass (on top of the below-bracket mass ``cLm``) reaches
    ``k`` — the sorted-prefix-weight generalization — and, because the
    masses here are RE-MEASURED by a differently-ordered sum than the
    loop's histogram passes, the buffer certifies only when its total mass
    actually reaches ``k`` and the extreme shortcuts are gated on the seed
    bracket (a rounding flip near ``k`` with the bracket off the extreme
    falls through to the sort/fallback chain — fail safe).
    """
    if zws is None:
        # exact integer measure: index straight into the sorted buffer
        sort_idx = jnp.clip(kk - cLm - 1, 0, cap - 1)
        ans_sort = jnp.take_along_axis(zs, sort_idx[..., None],
                                       axis=-1)[..., 0]
        sort_ok = n_in <= cap
        at_min = cLm >= kk
        at_max = m_lt_max < kk
    else:
        cumw = cLm[..., None] + jnp.cumsum(zws, axis=-1)
        reach = cumw >= kk[..., None]
        sidx = jnp.argmax(reach, axis=-1).astype(jnp.int32)
        ans_sort = jnp.take_along_axis(zs, sidx[..., None], axis=-1)[..., 0]
        # the buffer certifies only when it holds every survivor AND its
        # total mass actually reaches k (all-False argmax must not certify)
        sort_ok = (n_in <= cap) & reach[..., -1]
        at_min = (cLm >= kk) & (s.yL == xmin)
        at_max = (m_lt_max < kk) & (s.yR == xmax)
    fallback_ok = (cLm < kk) & (kk <= m_le_v)

    value = jnp.where(
        s.found_exact,
        s.t_exact,
        jnp.where(sort_ok, ans_sort,
                  jnp.where(fallback_ok, vnext, s.yR)),
    )
    status = jnp.where(
        s.found_exact,
        EXACT_HIT,
        jnp.where(
            sort_ok,
            HYBRID_SORT,
            jnp.where(fallback_ok, TIE_FALLBACK, NOT_CONVERGED),
        ),
    )
    # Extreme shortcuts (the bracket invariant measure(y_L) < k only holds
    # for answers strictly inside the data range): if measure(x <= y_L) >= k
    # the answer is at or below y_L, which can only be the minimum (y_L
    # starts at the min and only moves to points certified < k).  Symmetric
    # test at the max.  Also covers k==1, k==n and all-equal rows.
    value = jnp.where(at_min, xmin, jnp.where(at_max, xmax, value))
    status = jnp.where(at_min | at_max, EXACT_HIT, status)
    return SelectResult(
        value=value, iters=s.iters, status=status.astype(jnp.int32),
        y_lo=s.yL, y_hi=s.yR, n_in=n_in,
    )


def _finalize_rows(x, kk, s: BatchState, cap, xmin, xmax,
                   w=None) -> SelectResult:
    """Exact per-row recovery from the final brackets.  Two fused passes.

    Pass 1 (the paper's ``copy_if`` + count, row-wise): compact each row's
    open pivot interval into a fixed ``(B, cap)`` buffer, measure
    ``cLm = measure(x<=y_L)`` and find the next distinct value above
    ``y_L``; one batched sort of the (B, cap) buffer (carrying the aligned
    weights through on the weighted leg).
    Pass 2 (tie fallback verification): ``measure(x <= vnext)`` per row.
    """
    if w is None:
        z, _, cLm, n_in, vnext, m_le_v = jax.vmap(
            lambda xi, lo, hi: _compact_interval(xi, None, lo, hi, cap)
        )(x, s.yL, s.yR)
        with jax.named_scope("sel.sort"):
            zs = jnp.sort(z, axis=-1)
        zws = None
        with jax.named_scope("sel.probe"):
            m_lt_max = jnp.sum(x < xmax[:, None], axis=1, dtype=jnp.int32)
    else:
        z, zw, cLm, n_in, vnext, m_le_v = jax.vmap(
            lambda xi, wi, lo, hi: _compact_interval(xi, wi, lo, hi, cap)
        )(x, w, s.yL, s.yR)
        with jax.named_scope("sel.sort"):
            order = jnp.argsort(z, axis=-1)
            zs = jnp.take_along_axis(z, order, axis=-1)
            zws = jnp.take_along_axis(zw, order, axis=-1)
        with jax.named_scope("sel.probe"):
            m_lt_max = jnp.sum(jnp.where(x < xmax[:, None], w, 0), axis=1,
                               dtype=w.dtype)
    return _assemble_answers(kk, s, cap, zs, zws, cLm, n_in, vnext, m_le_v,
                             m_lt_max, xmin, xmax)


def _finalize_shared(x, kk, s: BatchState, cap, xmin, xmax,
                     w=None) -> SelectResult:
    """Shared-x exact finalize on per-pivot compacted buffers.

    The compaction runs per pivot against the ONE ``(n,)`` array (pair on
    the weighted leg), sequential ``lax.map`` over the K brackets, so peak
    memory stays O(n + K*cap) — the hot iterations (multi-bracket kernel)
    and the finalize both avoid materializing ``(K, n)``.
    """
    x = x.reshape(-1)
    if w is None:
        z, _, cLm, n_in, vnext, m_le_v = jax.lax.map(
            lambda args: _compact_interval(x, None, args[0], args[1], cap),
            (s.yL, s.yR))
        with jax.named_scope("sel.sort"):
            zs = jnp.sort(z, axis=-1)
        zws = None
        # one shared pass: xmin/xmax are (K,) broadcasts of global extremes
        with jax.named_scope("sel.probe"):
            m_lt_max = jnp.broadcast_to(
                jnp.sum(x < jnp.max(xmax), dtype=jnp.int32), kk.shape)
    else:
        w = w.reshape(-1)
        z, zw, cLm, n_in, vnext, m_le_v = jax.lax.map(
            lambda args: _compact_interval(x, w, args[0], args[1], cap),
            (s.yL, s.yR))
        with jax.named_scope("sel.sort"):
            order = jnp.argsort(z, axis=-1)
            zs = jnp.take_along_axis(z, order, axis=-1)
            zws = jnp.take_along_axis(zw, order, axis=-1)
        with jax.named_scope("sel.probe"):
            m_lt_max = jnp.broadcast_to(
                jnp.sum(jnp.where(x < jnp.max(xmax), w, 0), dtype=w.dtype),
                kk.shape)
    return _assemble_answers(kk, s, cap, zs, zws, cLm, n_in, vnext, m_le_v,
                             m_lt_max, xmin, xmax)


def _default_cap(n: int, backend: Optional[str] = None) -> int:
    """Scalar survivor-buffer size: the loop sweeps until at most ``cap``
    elements stay in the bracket, then compacts them into ``cap`` slots.

    jnp path: generous, ``n // 64`` within ``[4096, 2^19]`` (a binary
    search is cheap there and a sweep costs 2-4 fused passes).  Kernel
    path: at most ``KERNEL_CAP``, since each slot costs the rank search
    ``log2(n)`` dependent gathers over the n-long rank array, far more
    than the extra sweep a small buffer needs (see ``KERNEL_CAP``).
    """
    cap = min(max(4096, n // 64), 1 << 19)
    if _kernel_path(backend):
        cap = min(cap, KERNEL_CAP)
    return int(cap)


def _default_cap_rows(n: int) -> int:
    # Batched regimes keep a (B, cap) compaction buffer, so the per-row cap
    # is tighter than the scalar default: a few more bracket iterations
    # (cheap fused passes, shared by the whole batch) buy a much smaller
    # batched sort.  Benchmarked in benchmarks/batched_selection_bench.py.
    return int(min(max(256, n // 64), 4096))


def _map_bracket_back_rows(x, xt, s: BatchState) -> BatchState:
    """Map a transformed-domain bracket back to original values, row-wise.

    F is monotone non-decreasing in fp on the data, so
        y_orig = max{x_i : F(x_i) <= y_t}
    preserves counts exactly: count(x <= y_orig) == count(F(x) <= y_t).
    Both loop invariants (c(y_L) < k <= c(y_R)) therefore transfer to the
    original domain, and the finalize stays exact.  On an exact hit the
    t-space image may merge several distinct originals (F is not injective
    in fp): collapse the bracket to the image's preimage set and drop the
    certificate — the original-space finalize re-resolves it.
    """
    neg = jnp.asarray(-jnp.inf, x.dtype)
    yL_t = jnp.where(s.found_exact, s.t_exact, s.yL)[:, None]
    yR_t = jnp.where(s.found_exact, s.t_exact, s.yR)[:, None]
    yL = jnp.where(
        s.found_exact,
        jnp.max(jnp.where(xt < yL_t, x, neg), axis=1),  # strict: preimage
        jnp.max(jnp.where(xt <= yL_t, x, neg), axis=1),
    )
    yR = jnp.max(jnp.where(xt <= yR_t, x, neg), axis=1)
    return s._replace(
        yL=yL, yR=yR,
        # exactness certificates do not survive the fp roundtrip:
        found_exact=jnp.zeros_like(s.found_exact),
    )


def _map_bracket_back_shared(x, xt, s: BatchState) -> BatchState:
    """Shared-x analogue of :func:`_map_bracket_back_rows`: one ``(n,)``
    array, (K,) transformed brackets, mapped back by the same
    count-preserving preimage reductions — per pivot via ``lax.map`` so the
    ``(K, n)`` broadcast never materializes."""
    neg = jnp.asarray(-jnp.inf, x.dtype)
    x = x.reshape(-1)
    xt = xt.reshape(-1)

    def one(args):
        yL_t, yR_t, t_ex, found = args
        lo_t = jnp.where(found, t_ex, yL_t)
        hi_t = jnp.where(found, t_ex, yR_t)
        yL = jnp.where(
            found,
            jnp.max(jnp.where(xt < lo_t, x, neg)),  # strict: preimage
            jnp.max(jnp.where(xt <= lo_t, x, neg)),
        )
        yR = jnp.max(jnp.where(xt <= hi_t, x, neg))
        return yL, yR

    yL, yR = jax.lax.map(one, (s.yL, s.yR, s.t_exact, s.found_exact))
    return s._replace(
        yL=yL, yR=yR,
        # exactness certificates do not survive the fp roundtrip:
        found_exact=jnp.zeros_like(s.found_exact),
    )


@functools.partial(
    jax.jit,
    static_argnames=("method", "maxit", "cap", "transform", "backend",
                     "nbins", "binned_impl"),
)
def select_rows(
    x: jax.Array,
    k,
    *,
    method: Optional[str] = None,
    maxit: int = 64,
    cap: Optional[int] = None,
    transform: Optional[str] = None,
    backend: Optional[str] = None,
    nbins: Optional[int] = None,
    binned_impl: Optional[str] = None,
    prior=None,
) -> SelectResult:
    """Rows-mode batched selection: ``x`` is (B, n), ``k`` scalar or (B,).

    Every field of the returned :class:`SelectResult` is (B,)-shaped; row
    ``i`` solves the independent problem ``x[i], k[i]`` with the same
    exactness guarantees as the scalar solver (which is the B=1 view of this
    function).  ``method=None`` resolves to 'binned' for n >= BINNED_MIN_N
    and 'cp' otherwise (see ``_resolve_method``); ``nbins`` sizes the
    binned histogram sweeps (``None``: backend-tuned, see
    ``_resolve_nbins``); ``binned_impl`` routes the jnp histogram slotting
    ('searchsorted' | 'arithmetic' — bit-identical, for differential
    testing).  ``backend`` selects the fused data pass ('jnp' | 'pallas' |
    'pallas_interpret', default: pallas on TPU).

    ``prior``: warm-start carry for repeated selection — ``None``, a
    previous :class:`SelectResult` (fields (B,) or scalar), a
    :class:`Prior`, or a bare value.  The result is bit-identical to a
    cold solve under the engine's exactness contract (only sweep counts
    change); an unchanged answer re-certifies in 1 sweep / 1 cp pass.
    """
    if x.ndim != 2:
        raise ValueError(f"select_rows wants (B, n) data, got {x.shape}")
    b, n = x.shape
    prior = as_prior(prior)
    method = _resolve_method(method, n, backend)
    nbins = _resolve_nbins(nbins, backend, x.dtype)
    binned_impl = _check_binned_impl(binned_impl)
    if cap is None:
        cap = _default_cap_rows(n)
    cap = min(cap, n)
    ks = jnp.broadcast_to(jnp.clip(jnp.asarray(k, jnp.int32), 1, n), (b,))

    if method == "sort":
        xs = jnp.sort(x, axis=1)
        value = jnp.take_along_axis(xs, (ks - 1)[:, None], axis=1)[:, 0]
        zero = jnp.zeros((b,), jnp.int32)
        return SelectResult(
            value=value, iters=zero,
            status=jnp.full((b,), EXACT_HIT, jnp.int32),
            y_lo=xs[:, 0], y_hi=xs[:, -1],
            n_in=jnp.full((b,), n, jnp.int32),
        )

    if transform == "log1p":
        xt = transforms.log1p_transform_rows(x)
        if prior is not None:
            # map the (original-space) prior through the row anchors; a
            # value below the anchor maps to NaN and is sanitized away
            # inside prior_edges — the prior is advisory either way
            x0 = jnp.min(x, axis=1)
            ft = lambda v: jnp.log1p(jnp.asarray(v, x.dtype) - x0)
            prior = Prior(ft(prior.value), ft(prior.y_lo),
                          ft(prior.y_hi), ft(prior.cut))
        s, _, _ = _run_bracket_phase(
            RowsEvaluator(xt, ks, backend=backend,
                          binned_impl=binned_impl), method, maxit, cap,
            nbins, prior=prior)
        s = _map_bracket_back_rows(x, xt, s)
        return _finalize_rows(x, ks, s, cap,
                              jnp.min(x, axis=1), jnp.max(x, axis=1))
    elif transform is not None:
        raise ValueError(f"unknown transform {transform!r}")

    ev = RowsEvaluator(x, ks, backend=backend, binned_impl=binned_impl)
    s, xmin, xmax = _run_bracket_phase(ev, method, maxit, cap, nbins,
                                       prior=prior)
    return _finalize_rows(x, ks, s, cap, xmin, xmax)


def order_statistic(
    x: jax.Array,
    k,
    *,
    method: Optional[str] = None,
    maxit: int = 64,
    cap: Optional[int] = None,
    transform: Optional[str] = None,
    backend: Optional[str] = None,
    nbins: Optional[int] = None,
    binned_impl: Optional[str] = None,
    prior=None,
) -> SelectResult:
    """k-th smallest element of ``x`` (k is 1-indexed, may be traced).

    The ``B = 1`` view of :func:`select_rows`.  ``method`` in {"binned",
    "binned_polish", "cp", "cp_hybrid", "bisection", "golden", "brent",
    "sort"}; ``None`` resolves to 'binned' for large n, 'cp' otherwise
    (see ``_resolve_method``).
    ``cp`` and ``cp_hybrid`` are aliases (the hybrid finalize is always on —
    it is what makes the result exact).  ``transform='log1p'`` applies the
    paper's monotone guard for extreme-valued data (Sec. V-D).
    """
    x = x.reshape(-1)
    if cap is None:
        # scalar policy: one buffer, generous on the jnp path, small on the
        # kernel path where each slot's rank search costs log2(n) gathers
        cap = _default_cap(x.size, backend)
    res = select_rows(
        x[None, :], jnp.asarray(k, jnp.int32).reshape(1),
        method=method, maxit=maxit, cap=cap, transform=transform,
        backend=backend, nbins=nbins, binned_impl=binned_impl,
        prior=as_prior(prior),
    )
    return jax.tree.map(lambda a: a[0], res)


def median(x: jax.Array, **kw) -> SelectResult:
    """Med(x) = x_([(n+1)/2]) (paper Sec. I convention)."""
    n = x.size
    return order_statistic(x, (n + 1) // 2, **kw)


def ranks_from_quantiles(qs, n: int):
    """Target ranks ``ceil(q * n)`` clipped to ``[1, n]``, resolved in f64
    BEFORE tracing whenever ``qs`` is concrete.

    Under default x64-off the traced product rounds ``q`` and ``q * n``
    through f32, whose spacing at ``n ~ 2^25`` is 4 ulps of an integer —
    a high quantile (q = 0.999999) can land on the wrong rank entirely.
    Concrete ``qs`` (the overwhelmingly common call) are resolved host-side
    in numpy f64, where every rank below 2^53 is exact; traced ``qs`` fall
    back to the on-device product (exact whenever ``q * n`` is
    f32-representable).
    """
    if isinstance(qs, jax.core.Tracer):
        return jnp.clip(jnp.ceil(jnp.asarray(qs) * n).astype(jnp.int32),
                        1, n)
    qv = np.asarray(qs, np.float64)
    return jnp.asarray(np.clip(np.ceil(qv * float(n)), 1, n)
                       .astype(np.int32))


def quantile(x: jax.Array, q, **kw) -> SelectResult:
    """Lower empirical q-quantile: x_(ceil(q*n)) clipped to [1, n]."""
    return order_statistic(x, ranks_from_quantiles(q, x.size), **kw)


def topk_threshold(x: jax.Array, m, **kw) -> SelectResult:
    """Value of the m-th largest element (for kNN / trimming)."""
    n = x.size
    return order_statistic(x, n - jnp.asarray(m, jnp.int32) + 1, **kw)


@functools.partial(
    jax.jit,
    static_argnames=("method", "maxit", "cap", "transform", "backend",
                     "nbins", "binned_impl"),
)
def multi_order_statistic(
    x: jax.Array,
    ks,
    *,
    method: Optional[str] = None,
    maxit: int = 64,
    cap: Optional[int] = None,
    transform: Optional[str] = None,
    backend: Optional[str] = None,
    nbins: Optional[int] = None,
    binned_impl: Optional[str] = None,
    prior=None,
) -> SelectResult:
    """Several order statistics of the SAME array at once (shared-x mode).

    All K brackets iterate together against the multi-pivot fused kernel:
    each iteration reads ``x`` ONCE and evaluates every live pivot from the
    resident tile (on TPU: one VMEM load per tile for all K pivots) — the
    cheap way to get (p25, p50, p75, p99, ...) telemetry sets.  The finalize
    compacts survivors per pivot straight from the ``(n,)`` array
    (:func:`_finalize_shared`), so neither the hot iterations nor the
    finalize ever materialize ``(K, n)``.  ``prior`` warm-starts every
    target's bracket from a previous ``(K,)`` result (see
    :func:`select_rows`).
    """
    x = x.reshape(-1)
    n = x.size
    prior = as_prior(prior)
    method = _resolve_method(method, n, backend)
    nbins = _resolve_nbins(nbins, backend, x.dtype)
    binned_impl = _check_binned_impl(binned_impl)
    ks = jnp.clip(jnp.asarray(ks, jnp.int32).reshape(-1), 1, n)
    nk = ks.shape[0]
    if cap is None:
        cap = _default_cap_rows(n)
    cap = min(cap, n)

    if method == "sort":
        xs = jax.lax.sort(x)
        zero = jnp.zeros((nk,), jnp.int32)
        return SelectResult(
            value=xs[ks - 1], iters=zero,
            status=jnp.full((nk,), EXACT_HIT, jnp.int32),
            y_lo=jnp.broadcast_to(xs[0], (nk,)),
            y_hi=jnp.broadcast_to(xs[-1], (nk,)),
            n_in=jnp.full((nk,), n, jnp.int32),
        )

    if transform == "log1p":
        xt, _ = transforms.log1p_transform(x)
        if prior is not None:
            x0 = jnp.min(x)
            ft = lambda v: jnp.log1p(jnp.asarray(v, x.dtype) - x0)
            prior = Prior(ft(prior.value), ft(prior.y_lo),
                          ft(prior.y_hi), ft(prior.cut))
        s, _, _ = _run_bracket_phase(
            SharedEvaluator(xt, ks, backend=backend,
                            binned_impl=binned_impl), method, maxit, cap,
            nbins, prior=prior)
        s = _map_bracket_back_shared(x, xt, s)
        bcast = lambda v: jnp.broadcast_to(v, (nk,))
        return _finalize_shared(x, ks, s, cap,
                                bcast(jnp.min(x)), bcast(jnp.max(x)))
    elif transform is not None:
        raise ValueError(f"unknown transform {transform!r}")

    ev = SharedEvaluator(x, ks, backend=backend, binned_impl=binned_impl)
    s, xmin, xmax = _run_bracket_phase(ev, method, maxit, cap, nbins,
                                       prior=prior)
    return _finalize_shared(x, ks, s, cap, xmin, xmax)


def quantiles(x: jax.Array, qs, **kw) -> SelectResult:
    """Lower empirical quantiles at each q in ``qs`` (one shared-x solve).

    With ``method='binned'``/``'binned_polish'`` the K brackets narrow
    simultaneously from ONE histogram sweep per round (the shared-x
    multi-bracket pass), so a decile vector costs the data traffic of a
    single binned median, not ~K× it.
    """
    return multi_order_statistic(x, ranks_from_quantiles(qs, x.size), **kw)


# ---------------------------------------------------------------------------
# Segmented selection: per-segment order statistics of ONE concatenated
# array — the per-leaf regime (gradient-clip thresholds over a pytree)
# ---------------------------------------------------------------------------


def _finalize_segmented(x, seg, kk, s: BatchState, cap, xmin,
                        xmax) -> SelectResult:
    """Per-segment exact finalize: :func:`_finalize_shared` with every
    reduction masked to its own segment.  Sequential ``lax.map`` over the K
    segments keeps peak memory O(n + K*cap) — no ``(K, n)`` broadcast."""
    x = x.reshape(-1)
    big = jnp.asarray(jnp.inf, x.dtype)
    sids = jnp.arange(kk.shape[0], dtype=jnp.int32)

    def one(args):
        sid, lo, hi, xm = args
        inseg = seg == sid
        with jax.named_scope("sel.compact"):
            mask_in = inseg & (x > lo) & (x <= hi)
        with jax.named_scope("sel.probe"):
            cL = jnp.sum(inseg & (x <= lo), dtype=jnp.int32)
            vnext = jnp.min(jnp.where(inseg & (x > lo), x, big))
        (z,), n_in = rank_compact(mask_in, cap, [(x, big)])
        with jax.named_scope("sel.probe"):
            m_le_v = jnp.sum(inseg & (x <= vnext), dtype=jnp.int32)
            m_lt_max = jnp.sum(inseg & (x < xm), dtype=jnp.int32)
        return z, cL, n_in, vnext, m_le_v, m_lt_max

    z, cLm, n_in, vnext, m_le_v, m_lt_max = jax.lax.map(
        one, (sids, s.yL, s.yR, xmax))
    with jax.named_scope("sel.sort"):
        zs = jnp.sort(z, axis=-1)
    return _assemble_answers(kk, s, cap, zs, None, cLm, n_in, vnext,
                             m_le_v, m_lt_max, xmin, xmax)


@functools.partial(
    jax.jit,
    static_argnames=("nsegs", "method", "maxit", "cap", "nbins"),
)
def segmented_order_statistic(
    x: jax.Array,
    seg: jax.Array,
    ks,
    *,
    nsegs: int,
    method: Optional[str] = None,
    maxit: int = 64,
    cap: Optional[int] = None,
    nbins: Optional[int] = None,
    prior=None,
) -> SelectResult:
    """Per-segment order statistics of one concatenated array.

    ``x`` (n,) holds K segments' data interleaved/concatenated, ``seg``
    (n,) int32 gives each element's segment id in ``[0, nsegs)``, and
    ``ks`` (nsegs,) the 1-indexed target rank WITHIN each segment (clipped
    to the segment size).  Every segment must be non-empty.  Returns a
    :class:`SelectResult` with (nsegs,) fields — segment ``i`` solves the
    independent problem ``x[seg == i], ks[i]`` with the engine's full
    exactness guarantees.

    This is the per-leaf regime: per-layer gradient-clip thresholds solve
    ONE of these over the flattened pytree instead of one scalar selection
    per leaf.  All data passes are shared: the FG pass is a handful of
    ``segment_sum`` reductions, and the binned pass buys every segment a
    factor-``nbins`` narrowing from one chunked sweep
    (``kernels.ref.segmented_histogram_ref`` — per-element binary search
    into its own segment's realized edge ladder, no ``(K, n)``
    intermediate).  ``method``/``maxit``/``cap``/``nbins`` as in
    :func:`multi_order_statistic`; the segmented data pass is jnp-only
    (XLA fuses it), so there is no ``backend`` knob.
    """
    from repro.kernels import ref as kref  # deferred: core <-> kernels

    x = x.reshape(-1)
    n = x.size
    seg = jnp.asarray(seg, jnp.int32).reshape(-1)
    method = _resolve_method(method, n, None)
    nbins = _resolve_nbins(nbins, None, x.dtype)
    if cap is None:
        cap = _default_cap_rows(n)
    cap = min(cap, n)
    ones = jnp.ones(n, jnp.int32)
    counts = jax.ops.segment_sum(ones, seg, num_segments=nsegs)
    kk = jnp.clip(jnp.asarray(ks, jnp.int32).reshape(-1), 1,
                  jnp.maximum(counts, 1))

    if method == "sort":
        # per-segment rank via one global sort on (seg, x) lexicographic
        order = jnp.lexsort((x, seg))
        xs = x[order]
        starts = jnp.cumsum(counts) - counts
        value = xs[jnp.clip(starts + kk - 1, 0, n - 1)]
        zero = jnp.zeros((nsegs,), jnp.int32)
        xmin = jax.ops.segment_min(x, seg, num_segments=nsegs)
        xmax = jax.ops.segment_max(x, seg, num_segments=nsegs)
        return SelectResult(
            value=value, iters=zero,
            status=jnp.full((nsegs,), EXACT_HIT, jnp.int32),
            y_lo=xmin, y_hi=xmax,
            n_in=counts,
        )

    def partials(y):
        d = x - y[seg]
        ssum = lambda v: jax.ops.segment_sum(v, seg, num_segments=nsegs)
        return (ssum(jnp.maximum(d, 0)), ssum(jnp.maximum(-d, 0)),
                ssum((d < 0).astype(jnp.int32)),
                ssum((d <= 0).astype(jnp.int32)))

    def init_stats():
        xmin = jax.ops.segment_min(x, seg, num_segments=nsegs)
        xmax = jax.ops.segment_max(x, seg, num_segments=nsegs)
        mean = jax.ops.segment_sum(x, seg, num_segments=nsegs) \
            / jnp.maximum(counts, 1).astype(x.dtype)
        return xmin, xmax, mean.astype(x.dtype)

    def histogram(edges, need_msum=False):
        out = kref.segmented_histogram_ref(
            x, seg, edges, rows=(x,) if need_msum else ())
        cnt = out[0]
        return cnt, cnt, (out[1] if need_msum else None)

    from repro.core.objective import FnEvaluator

    ev = FnEvaluator(partials, counts, kk, init_stats, histogram=histogram)
    s, xmin, xmax = _run_bracket_phase(ev, method, maxit, cap, nbins,
                                       prior=as_prior(prior))
    return _finalize_segmented(x, seg, kk, s, cap, xmin, xmax)


def segmented_quantiles(x: jax.Array, seg: jax.Array, q, sizes,
                        **kw) -> SelectResult:
    """Per-segment lower q-quantile from STATIC segment sizes.

    ``sizes`` (a python sequence — the leaf sizes are static in the
    per-leaf regime) turns ``q`` into per-segment ranks host-side at f64
    (:func:`ranks_from_quantiles` per segment), then runs ONE
    :func:`segmented_order_statistic` solve.  ``q`` may be a scalar (same
    quantile every segment, the clip-threshold case) or a length-``nsegs``
    sequence.
    """
    sizes = [int(v) for v in np.asarray(sizes).reshape(-1)]
    qv = np.broadcast_to(np.asarray(q, np.float64).reshape(-1),
                         (len(sizes),))
    ks = np.asarray([int(np.clip(np.ceil(qi * ni), 1, max(ni, 1)))
                     for qi, ni in zip(qv, sizes)], np.int32)
    return segmented_order_statistic(x, seg, jnp.asarray(ks),
                                     nsegs=len(sizes), **kw)


# ---------------------------------------------------------------------------
# Weighted selection: the weight-measure leg of the SAME engine
# ---------------------------------------------------------------------------
#
# The weighted k-th order statistic is the smallest element ``v`` whose
# cumulative weight ``W_le(v) = sum(w_i : x_i <= v)`` reaches the target
# mass ``wk`` — the minimizer of F_w(y) = sum_i w_i * rho(x_i - y) (see
# ``objective.py``).  There is NO weighted engine: the public functions
# below construct a weighted evaluator (whose measure fields carry masses)
# and run the very same bracket/binned loops and finalize chain as the
# counting path.  Uniform weights w_i == 1 with wk = k make every mass
# comparison an exact integer-valued comparison, reproducing the counting
# decisions bit for bit.  The fp contract for inexact masses is documented
# in the module docstring.


def _weighted_sort_cumsum(xs, cumw, wkk):
    """Answer/validity of the full-sort baseline: first sorted value whose
    cumulative mass reaches the target."""
    reach = cumw >= wkk[..., None]
    idx = jnp.argmax(reach, axis=-1).astype(jnp.int32)
    value = jnp.take_along_axis(xs, idx[..., None], axis=-1)[..., 0]
    # nothing reaches wk (all-False argmax): the target mass exceeds the
    # measured total — take the maximum, the limit of the definition
    value = jnp.where(reach[..., -1], value, xs[..., -1])
    return value


@functools.partial(
    jax.jit,
    static_argnames=("method", "maxit", "cap", "backend", "nbins",
                     "binned_impl"),
)
def weighted_select_rows(
    x: jax.Array,
    w: jax.Array,
    wk,
    *,
    method: Optional[str] = None,
    maxit: int = 64,
    cap: Optional[int] = None,
    backend: Optional[str] = None,
    nbins: Optional[int] = None,
    binned_impl: Optional[str] = None,
    prior=None,
) -> SelectResult:
    """Rows-mode weighted selection: ``x``/``w`` (B, n), ``wk`` scalar or
    (B,) target cumulative weights.

    Row ``i`` returns the smallest element ``v`` of ``x[i]`` with
    ``sum(w[i, x[i] <= v]) >= wk[i]`` (``wk`` is clipped to the row's total
    mass).  Weights must be non-negative; uniform weights with ``wk = k``
    reproduce :func:`select_rows` exactly.  ``method`` as in
    :func:`select_rows` minus ``transform`` support; ``'sort'`` is the
    weighted sort-cumsum baseline.
    """
    if x.ndim != 2:
        raise ValueError(f"weighted_select_rows wants (B, n) data, got "
                         f"{x.shape}")
    b, n = x.shape
    w = jnp.broadcast_to(jnp.asarray(w), x.shape)
    method = _resolve_method(method, n, backend)
    # either-operand f64 triggers the jnp reroute, so promote for nbins
    nbins = _resolve_nbins(nbins, backend,
                           jnp.promote_types(x.dtype, w.dtype))
    binned_impl = _check_binned_impl(binned_impl)
    if cap is None:
        cap = _default_cap_rows(n)
    cap = min(cap, n)
    ev = RowsEvaluator(x, wk, backend=backend, weights=w,
                       binned_impl=binned_impl)
    wkk = ev.k  # clipped target masses, accumulation dtype, (B,)

    if method == "sort":
        order = jnp.argsort(x, axis=1)
        xs = jnp.take_along_axis(x, order, axis=1)
        ws = jnp.take_along_axis(w.astype(wkk.dtype), order, axis=1)
        value = _weighted_sort_cumsum(xs, jnp.cumsum(ws, axis=1), wkk)
        zero = jnp.zeros((b,), jnp.int32)
        return SelectResult(
            value=value, iters=zero,
            status=jnp.full((b,), EXACT_HIT, jnp.int32),
            y_lo=xs[:, 0], y_hi=xs[:, -1],
            n_in=jnp.full((b,), n, jnp.int32),
        )

    s, xmin, xmax = _run_bracket_phase(ev, method, maxit, cap, nbins,
                                       prior=as_prior(prior))
    return _finalize_rows(x, wkk, s, cap, xmin, xmax,
                          w=w.astype(wkk.dtype))


def weighted_order_statistic(
    x: jax.Array,
    w: jax.Array,
    wk,
    *,
    method: Optional[str] = None,
    maxit: int = 64,
    cap: Optional[int] = None,
    backend: Optional[str] = None,
    nbins: Optional[int] = None,
    binned_impl: Optional[str] = None,
    prior=None,
) -> SelectResult:
    """Smallest element of ``x`` whose cumulative weight reaches ``wk``.

    The B = 1 view of :func:`weighted_select_rows`.  With ``w = ones`` and
    ``wk = k`` this is exactly :func:`order_statistic`.
    """
    x = x.reshape(-1)
    if cap is None:
        # scalar policy: one buffer, generous on the jnp path, small on the
        # kernel path where each slot's rank search costs log2(n) gathers
        cap = _default_cap(x.size, backend)
    res = weighted_select_rows(
        x[None, :], jnp.asarray(w).reshape(1, -1),
        jnp.asarray(wk).reshape(1),
        method=method, maxit=maxit, cap=cap, backend=backend, nbins=nbins,
        binned_impl=binned_impl, prior=as_prior(prior),
    )
    return jax.tree.map(lambda a: a[0], res)


def _total_mass(x, w):
    """Total weight at the mass-accumulation dtype (the wk/W reference)."""
    return jnp.sum(w, dtype=_weight_accum_dtype(jnp.asarray(x), w))


def weighted_median(x: jax.Array, w: jax.Array, **kw) -> SelectResult:
    """Lower weighted median: smallest v with ``mass(x <= v) >= W/2``.

    Uniform weights reproduce :func:`median` (= x_([(n+1)/2])) exactly.
    """
    w = jnp.asarray(w).reshape(-1)
    return weighted_order_statistic(x, w, 0.5 * _total_mass(x, w), **kw)


def weighted_quantile(x: jax.Array, w: jax.Array, q, **kw) -> SelectResult:
    """Lower weighted q-quantile: smallest v with ``mass(x <= v) >= q*W``."""
    w = jnp.asarray(w).reshape(-1)
    W = _total_mass(x, w)
    return weighted_order_statistic(x, w, jnp.asarray(q, W.dtype) * W, **kw)


@functools.partial(
    jax.jit,
    static_argnames=("method", "maxit", "cap", "backend", "nbins",
                     "binned_impl"),
)
def weighted_multi_order_statistic(
    x: jax.Array,
    w: jax.Array,
    wks,
    *,
    method: Optional[str] = None,
    maxit: int = 64,
    cap: Optional[int] = None,
    backend: Optional[str] = None,
    nbins: Optional[int] = None,
    binned_impl: Optional[str] = None,
    prior=None,
) -> SelectResult:
    """Several weighted order statistics of the SAME array at once.

    Shared-x mode: all K target masses iterate together against the
    weighted multi-pivot kernels (each x/w tile read once per sweep for
    every live bracket), exactly like :func:`multi_order_statistic`.
    """
    x = x.reshape(-1)
    n = x.size
    w = jnp.broadcast_to(jnp.asarray(w).reshape(-1), x.shape)
    method = _resolve_method(method, n, backend)
    # either-operand f64 triggers the jnp reroute, so promote for nbins
    nbins = _resolve_nbins(nbins, backend,
                           jnp.promote_types(x.dtype, w.dtype))
    binned_impl = _check_binned_impl(binned_impl)
    if cap is None:
        cap = _default_cap_rows(n)
    cap = min(cap, n)
    ev = SharedEvaluator(x, wks, backend=backend, weights=w,
                         binned_impl=binned_impl)
    wkk = ev.k
    nk = wkk.shape[0]

    if method == "sort":
        order = jnp.argsort(x)
        xs = x[order]
        cumw = jnp.cumsum(w.astype(wkk.dtype)[order])
        value = _weighted_sort_cumsum(xs[None, :], cumw[None, :],
                                      wkk)  # broadcast over K targets
        zero = jnp.zeros((nk,), jnp.int32)
        return SelectResult(
            value=value, iters=zero,
            status=jnp.full((nk,), EXACT_HIT, jnp.int32),
            y_lo=jnp.broadcast_to(xs[0], (nk,)),
            y_hi=jnp.broadcast_to(xs[-1], (nk,)),
            n_in=jnp.full((nk,), n, jnp.int32),
        )

    s, xmin, xmax = _run_bracket_phase(ev, method, maxit, cap, nbins,
                                       prior=as_prior(prior))
    return _finalize_shared(x, wkk, s, cap, xmin, xmax,
                            w=w.astype(wkk.dtype))


def weighted_quantiles(x: jax.Array, w: jax.Array, qs, **kw) -> SelectResult:
    """Lower weighted quantiles at each q in ``qs`` (one shared-x solve).

    The target masses ``q * W`` are formed at f64 host-side whenever both
    ``qs`` and the measured total mass are concrete (a single rounding into
    the accumulation dtype instead of the double-rounded f32 product —
    same rationale as :func:`ranks_from_quantiles`); traced operands fall
    back to the on-device product.
    """
    x = jnp.asarray(x).reshape(-1)
    w = jnp.asarray(w).reshape(-1)
    W = _total_mass(x, w)
    if isinstance(W, jax.core.Tracer) or isinstance(qs, jax.core.Tracer):
        wks = jnp.asarray(qs, W.dtype).reshape(-1) * W
    else:
        wks = jnp.asarray(
            np.asarray(qs, np.float64).reshape(-1) * float(W), W.dtype)
    return weighted_multi_order_statistic(x, w, wks, **kw)
