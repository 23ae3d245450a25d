"""Distributed selection over sharded arrays (the paper's Sec. V-D multi-GPU
story, mapped to TPU meshes).

Two primitives, both ``shard_map``-native:

* :func:`local_order_statistic` — the k-th (or weighted, via ``weights=``)
  order statistic of a 1-D array sharded over one or more mesh axes.  ONE
  round loop serves both measures: each binned round is one local histogram
  pass + a psum of the ``(nbins + 2,)`` slot-MEASURE vector (int counts on
  the counting leg, fp masses on the weighted leg — the same vector is both
  on the counting leg, so the wire carries it once), each cutting-plane
  round psums the additive FG partials — the paper's "partial sums from
  several GPUs are added together", except the combine is an ICI all-reduce
  instead of a CPU hop.  ``method='binned_polish'`` additionally psums the
  per-slot SUM vector and drives the next round's edge placement with the
  globally-reconstructed straddling-bin centroid (``selection.polish_edges``)
  — one round saved at large n for ``nbins + 2`` extra wire scalars per
  round.  The hybrid finalize compacts *per shard* (fixed local capacity),
  ``all_gather``s the tiny buffers and sorts — the paper's small-array
  ``z`` step (carrying the aligned weight buffers on the weighted leg).

* :func:`median_across_axis` — vectorized coordinate-wise order statistics
  *across* a mesh axis (n = axis size per coordinate, millions of
  coordinates).  This is the robust-gradient-aggregation workhorse: per-
  replica gradient shards never leave their device; the solver only psums
  per-coordinate count/sum vectors.  For small replica counts an
  ``all_gather`` + local sort is cheaper in ICI bytes (crossover benchmarked
  in ``benchmarks/``); both methods are provided.

Both primitives ride the batched-first selection engine: the psum combine is
just another :class:`~repro.core.objective.Evaluator`.  The 1-D primitive
wraps a ``ShardedEvaluator`` (local fused pass + psum of the additive
partials); the across-axis primitive builds an :func:`axis_evaluator` whose
batch dimension is the coordinate set and hands it to
``selection.bracket_loop_batched`` — the same loop that runs rows-mode and
shared-x selection on a single device.

Every function here must be called INSIDE ``shard_map`` (they take the mesh
axis name(s)).  ``sharded_order_statistic`` is the user-facing wrapper.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import _compat, selection
from repro.core.objective import (
    FG,
    FnEvaluator,
    ShardedEvaluator,
    os_weights,
)

AxisNames = Sequence[str] | str

# round schedules of the 1-D distributed primitive ('auto' resolves
# statically by the global element count, mirroring the local engine)
DIST_METHODS = ("binned", "binned_polish", "cp", "auto")


def _axes_tuple(axes) -> tuple:
    return (axes,) if isinstance(axes, str) else tuple(axes)


def _psum(v, axes):
    return jax.lax.psum(v, axes)


def _pmax(v, axes):
    return jax.lax.pmax(v, axes)


def _pmin(v, axes):
    return jax.lax.pmin(v, axes)


def _pcast_varying(v, axes_t):
    # jax >= 0.7 wants device-varying values marked explicitly for the
    # static varying-axis analysis; older versions have no pcast (and no
    # analysis), where the cast is a no-op.
    pcast = getattr(jax.lax, "pcast", None)
    return v if pcast is None else pcast(v, axes_t, to="varying")


def eval_fg_sharded(x_local, y, k, n_global, axes, *, backend=None) -> FG:
    """Fused local pass + psum combine — one ShardedEvaluator call.

    ``n_global`` overrides the psum-derived element count (callers that pad
    shards to equal size pass the true count so the weights stay honest);
    ``None`` derives it from the shards.
    """
    ev = ShardedEvaluator(x_local, k, axes, backend=backend)
    if n_global is not None:
        ev.n = jnp.asarray(n_global, jnp.int32)
        ev.k = jnp.clip(jnp.asarray(k, jnp.int32), 1, ev.n)
    return ev(y)


class _DistState(NamedTuple):
    yL: jax.Array
    fL: jax.Array
    gL: jax.Array
    yR: jax.Array
    fR: jax.Array
    gR: jax.Array
    loc_cleL: jax.Array   # per-shard count(x_local <= yL)  (not replicated)
    loc_cleR: jax.Array
    max_in: jax.Array     # replicated: pmax over shards of local in-bracket
    t_exact: jax.Array
    found_exact: jax.Array
    it: jax.Array
    tp: jax.Array         # carried in-bin CP cut (drives the polish edges)


def local_order_statistic(
    x_local: jax.Array,
    k,
    axes: AxisNames,
    *,
    maxit: int = 64,
    cap_local: int = 4096,
    backend: Optional[str] = None,
    method: str = "binned",
    nbins: int = selection.DEF_NBINS,
    weights: Optional[jax.Array] = None,
    binned_impl: Optional[str] = None,
    prior=None,
) -> selection.SelectResult:
    """k-th smallest of the *global* (sharded) array; call inside shard_map.

    The result is replicated (identical on every shard).  Exact under the
    same guarantees as ``selection.order_statistic``; the count-based
    stopping rule bounds the *per-shard* in-bracket count so the local
    fixed-capacity compaction never overflows regardless of shard imbalance.

    With ``weights`` (sharded exactly like the data), ``k`` is the target
    cumulative MASS and the result is the weighted order statistic — the
    measure swap happens inside the :class:`ShardedEvaluator`; the round
    loop and finalize below are shared by both legs.

    ``method='binned'`` (default) narrows by histogram sweeps: each round is
    one local binned pass + a psum of the ``(nbins + 2,)`` slot-measure
    vector — the bracket shrinks by a factor of ``nbins`` per collective
    round, so the whole solve is ~3 rounds where the cutting-plane loop
    (``'cp'``) takes ~15-40 psums of the additive partials.  The slot
    COUNTS always stay per-shard (they feed the local cap bookkeeping); on
    the counting leg the psum'd counts double as the measure vector, so the
    wire cost is unchanged from the pre-unification engine on both legs.

    ``method='binned_polish'`` drives the rounds with the in-bin CP cut:
    each round ALSO psums the ``(nbins + 2,)`` per-slot sum vector (the
    only extra wire cost), reconstructs the straddling bin's mass centroid
    ``Σ_bin (w·)x / Σ_bin mass`` globally, and hands the cut to
    ``selection.polish_edges`` for the NEXT round's realized edges — the
    answer's neighborhood is then resolved at ~``2^-(nbins/4)`` of the
    bracket instead of ``1/nbins``, trading ``nbins + 2`` wire scalars per
    round for a round saved (2 -> 1 psum rounds at n = 1M, both measures —
    see BENCH_selection.json ``distributed``).  Same fp contract as the
    local engine: the cut steers edge PLACEMENT only, narrowing and
    certificates run on the psum'd measured prefixes through the one
    ``selection.binned_descent_step``, so a garbage centroid costs at most
    a round, never exactness.

    ``method='auto'`` mirrors the local engine's resolution (static by the
    global element count): 'binned' for ``n >= selection.BINNED_MIN_N``,
    'cp' below — and stays on plain 'binned' until the polish schedule is
    TPU-validated.  ``binned_impl`` routes the LOCAL histogram pass's jnp
    slotting exactly as in ``selection.select_rows``.

    ``prior`` (warm start, replicated scalar fields — a previous
    replicated result or ``selection.Prior``): round 1's psum'd slot
    vector is laid out by ``selection.prior_edges`` — the carried bracket
    verbatim plus the collapse pair around the prior answer — so an
    unchanged answer re-certifies in ONE psum round; the cp schedule
    spends its first psum at the prior pivot instead of the analytic cut.
    Same contract as the polish centroid: a stale/garbage/NaN prior costs
    psum rounds, never exactness.
    """
    x_local = x_local.reshape(-1)
    pr = selection.as_prior(prior)
    n_local = x_local.size
    axes_t = _axes_tuple(axes)
    if method == "auto":
        # psum of a python int constant-folds to the static global count
        n_glob = jax.lax.psum(n_local, axes_t)
        method = "binned" if n_glob >= selection.BINNED_MIN_N else "cp"
    weighted = weights is not None
    if weighted:
        weights = jnp.asarray(weights).reshape(-1)
    with jax.named_scope("sel.seed"):
        # the evaluator owns the data layout AND the measure: local fused
        # pass (Pallas on TPU) + psum of the additive partials is the whole
        # multi-device story
        ev = ShardedEvaluator(x_local, k, axes, backend=backend,
                              weights=weights, binned_impl=binned_impl)
        kk = ev.k
        dtype = x_local.dtype
        wl = weights.astype(kk.dtype) if weighted else None

        xmin, xmax, xmean = ev.init_stats()

        # analytic cut seeds, mirroring selection._seed_state's two measure
        # legs
        if weighted:
            Wsafe = jnp.maximum(ev.W, jnp.asarray(1e-30, ev.W.dtype))
            alpha = ((ev.W - kk) / Wsafe).astype(dtype)
            beta = (kk / Wsafe).astype(dtype)
            gL0, gR0 = -beta, alpha
        else:
            nf = ev.n.astype(dtype)
            alpha, beta = os_weights(nf, kk, dtype)
            gL0 = alpha * (1.0 / nf) - beta * (nf - 1.0) / nf
            gR0 = alpha * (nf - 1.0) / nf - beta * (1.0 / nf)

        fL0 = beta * (xmean - xmin)
        fR0 = alpha * (xmax - xmean)
        # analytic Kelley intersection seeds the polish's first in-bin cut
        # (mirrors selection.binned_loop_batched's polish seeding)
        t0 = (fR0 - fL0 + xmin * gL0 - xmax * gR0) / (gL0 - gR0)
        bad0 = ~jnp.isfinite(t0) | (t0 <= xmin) | (t0 >= xmax)
        t0 = jnp.where(bad0, 0.5 * (xmin + xmax), t0).astype(dtype)
        s0 = _DistState(
            yL=xmin,
            fL=fL0,
            gL=gL0,
            yR=xmax,
            fR=fR0,
            gR=gR0,
            loc_cleL=_pcast_varying(jnp.asarray(0, jnp.int32), axes_t),
            loc_cleR=_pcast_varying(jnp.asarray(n_local, jnp.int32), axes_t),
            max_in=jnp.asarray(n_local, jnp.int32),
            t_exact=jnp.asarray(jnp.nan, dtype),
            found_exact=jnp.asarray(False),
            it=jnp.asarray(0, jnp.int32),
            tp=t0,
        )

    def cond(carry):
        s, stalled = carry
        return ((~s.found_exact) & ~stalled & (s.max_in > cap_local)
                & (s.it < maxit) & (s.yR > s.yL))

    def cp_body(carry):
        s, stalled = carry
        t = (s.fR - s.fL + s.yL * s.gL - s.yR * s.gR) / (s.gL - s.gR)
        bad = ~jnp.isfinite(t) | (t <= s.yL) | (t >= s.yR)
        t = jnp.where(bad, 0.5 * (s.yL + s.yR), t).astype(s.yL.dtype)
        if pr is not None:
            # warm start: the prior answer takes the FIRST psum round only
            # (finite + strictly inside the bracket); the psum'd partials
            # decide every move, so a wrong prior costs rounds, not
            # exactness — an exact one certifies in one round
            pv = jnp.asarray(pr.value, s.yL.dtype).reshape(())
            use = ((s.it == 0) & jnp.isfinite(pv)
                   & (pv > s.yL) & (pv < s.yR))
            t = jnp.where(use, pv, t)
        # local partials kept un-psum'd too: the stopping rule bounds the
        # PER-SHARD in-bracket count so the local compaction never overflows
        loc = ev.local_partials(t)
        le_loc = loc[-1]   # n_le is the trailing partial on both legs
        fg = ev.combine(loc)
        # the measure decisions ARE the engine's (see bracket_loop_batched)
        exact = (fg.m_lt < kk) & (kk <= fg.m_le)
        move_left = fg.m_le < kk
        loc_cleL = jnp.where(move_left, le_loc, s.loc_cleL)
        loc_cleR = jnp.where(move_left | exact, s.loc_cleR, le_loc)
        max_in = _pmax(loc_cleR - loc_cleL, axes)
        return _DistState(
            yL=jnp.where(move_left, t, s.yL),
            fL=jnp.where(move_left, fg.f, s.fL),
            gL=jnp.where(move_left, fg.g_hi, s.gL),
            yR=jnp.where(move_left | exact, s.yR, t),
            fR=jnp.where(move_left | exact, s.fR, fg.f),
            gR=jnp.where(move_left | exact, s.gR, fg.g_lo),
            loc_cleL=loc_cleL, loc_cleR=loc_cleR, max_in=max_in,
            t_exact=jnp.where(exact, t, s.t_exact),
            found_exact=s.found_exact | exact,
            it=s.it + 1,
            tp=s.tp,
        ), stalled

    polish = method == "binned_polish"
    pb = None  # dt-converted prior for the binned rounds (set below)

    def binned_body(carry):
        from repro.kernels.ref import bin_edges  # deferred: core <-> kernels

        s, stalled = carry
        # realized edges computed ONCE, shared by the local data pass and
        # the narrowing decision (the exactness contract); the cross-device
        # combine is a psum of the slot-measure vector (additive, exactly
        # like the FG partials) — the slot counts stay local for the
        # per-shard cap bookkeeping.  Polish rounds place the edges around
        # the carried cut instead of uniformly.
        if polish:
            edges = selection.polish_edges(s.yL, s.yR, s.tp, nbins)
        else:
            edges = bin_edges(s.yL, s.yR, nbins)
        if pb is not None:
            # warm start: round 1's slot vector is laid out by the prior
            # (carried bracket verbatim + the collapse pair); later rounds
            # revert to the uniform/polish layout
            edges = jnp.where(s.it == 0,
                              selection.prior_edges(s.yL, s.yR, pb, nbins),
                              edges)
        cnt_loc, mass_loc, msum_loc = ev.local_histogram(edges,
                                                         need_msum=polish)
        mass = _psum(mass_loc, axes)
        cum = jnp.cumsum(mass[:-1])
        # the narrowing decision + exactness certificates are the one shared
        # implementation in selection.binned_descent_step
        yLn, yRn, _, _, jm1, jstar, hit_lo, exact, stall = \
            selection.binned_descent_step(cum, edges, s.yL, s.yR, kk)
        # late hit_lo can only be an inexact-mass ulp-flip: fail safe (dead
        # code on the counting leg — see selection.binned_loop_batched)
        late_hit_lo = hit_lo & (s.it > 0)
        exact = exact & ~late_hit_lo
        stall = stall | late_hit_lo
        # local prefix counts at the chosen edges: the per-shard analogue of
        # the CP loop's le_loc bookkeeping (bounds the local compaction)
        cum_loc = jnp.cumsum(cnt_loc[:-1])
        locL, locR = cum_loc[jm1], cum_loc[jstar]
        upd = ~exact & ~stall
        loc_cleL = jnp.where(upd, locL, s.loc_cleL)
        loc_cleR = jnp.where(upd, locR, s.loc_cleR)
        if polish:
            # one extra (nbins + 2,) psum reconstructs the straddling bin's
            # GLOBAL mass centroid — the in-bin support-line intersection
            # (see selection.binned_loop_batched); guard degenerate bins
            msum = _psum(msum_loc, axes)
            mbin = mass[jstar].astype(msum.dtype)
            sbin = msum[jstar]
            tcut = sbin / jnp.where(mbin > 0, mbin, 1)
            good = (mbin > 0) & jnp.isfinite(tcut)
            tcut = jnp.where(good, jnp.clip(tcut, yLn, yRn),
                             0.5 * (yLn + yRn)).astype(s.yL.dtype)
            tp_n = jnp.where(upd, tcut, s.tp)
        else:
            tp_n = s.tp
        return _DistState(
            yL=jnp.where(upd, yLn, s.yL), fL=s.fL, gL=s.gL,
            yR=jnp.where(upd, yRn, s.yR), fR=s.fR, gR=s.gR,
            loc_cleL=loc_cleL, loc_cleR=loc_cleR,
            max_in=_pmax(loc_cleR - loc_cleL, axes),
            t_exact=jnp.where(exact, jnp.where(hit_lo, s.yL, yRn),
                              s.t_exact),
            found_exact=s.found_exact | exact,
            it=s.it + 1,
            tp=tp_n,
        ), stalled | stall

    if method in ("binned", "binned_polish"):
        # brackets narrow to realized f32 edge values — keep the bracket
        # state at (at least) the kernels' f32 accumulation precision
        dt = jnp.promote_types(dtype, jnp.float32)
        s0 = s0._replace(yL=s0.yL.astype(dt), yR=s0.yR.astype(dt),
                         t_exact=s0.t_exact.astype(dt),
                         tp=s0.tp.astype(dt))
        if pr is not None:
            pb = selection.Prior(
                *(jnp.asarray(f, dt).reshape(()) for f in pr))
            # the prior's carried cut beats the analytic polish seed
            okc = (jnp.isfinite(pb.cut) & (pb.cut > s0.yL)
                   & (pb.cut < s0.yR))
            s0 = s0._replace(tp=jnp.where(okc, pb.cut, s0.tp))
        body = binned_body
    elif method == "cp":
        body = cp_body
    else:
        raise ValueError(f"unknown method {method!r}; one of "
                         f"{DIST_METHODS}")

    with jax.named_scope("sel.sweep"):
        s, _ = jax.lax.while_loop(cond, body, (s0, jnp.asarray(False)))

    # ---- distributed hybrid finalize (compact per shard, gather, sort) ----
    # per-shard compaction by selection.rank_compact (the one rank-gather
    # implementation), then the tiny buffers ride an all_gather
    big = jnp.asarray(jnp.inf, dtype)
    with jax.named_scope("sel.compact"):
        mask_in = (x_local > s.yL) & (x_local <= s.yR)
    cols = [(x_local, big)]
    if weighted:
        cols.append((wl, jnp.zeros((), wl.dtype)))
    bufs, loc_in = selection.rank_compact(mask_in, cap_local, cols)
    with jax.named_scope("sel.compact"):
        n_in = _psum(loc_in, axes)
        z_all = bufs[0]
        for ax in axes_t:
            z_all = jax.lax.all_gather(z_all, ax).reshape(-1)
        ok_gather = _pmax(loc_in, axes) <= cap_local
    with jax.named_scope("sel.probe"):
        vnext = _pmin(jnp.min(jnp.where(x_local > s.yL, x_local, big)),
                      axes)

    if weighted:
        # gather the aligned weight buffers and resolve by sorted prefix
        # masses — the weighted generalization of indexing at k - cL
        with jax.named_scope("sel.compact"):
            zw_all = bufs[1]
            for ax in axes_t:
                zw_all = jax.lax.all_gather(zw_all, ax).reshape(-1)
        with jax.named_scope("sel.sort"):
            order = jnp.argsort(z_all)
            zs = z_all[order]
        with jax.named_scope("sel.probe"):
            cLm = _psum(jnp.sum(jnp.where(x_local <= s.yL, wl, 0),
                                dtype=wl.dtype), axes)
        with jax.named_scope("sel.sort"):
            cumw = cLm + jnp.cumsum(zw_all[order])
            reach = cumw >= kk
            ans_sort = zs[jnp.argmax(reach).astype(jnp.int32)]
            # the buffer certifies only when its total mass actually
            # reaches wk
            ok_sort = ok_gather & reach[-1]
        with jax.named_scope("sel.probe"):
            m_le_v = _psum(jnp.sum(jnp.where(x_local <= vnext, wl, 0),
                                   dtype=wl.dtype), axes)
            m_lt_max = _psum(jnp.sum(jnp.where(x_local < xmax, wl, 0),
                                     dtype=wl.dtype), axes)
            # extreme shortcuts gated on the seed bracket (see the engine
            # finalize: re-measured masses can rounding-flip near wk; only
            # a bracket still AT the extreme may certify through them)
            at_min = (cLm >= kk) & (s.yL == xmin)
            at_max = (m_lt_max < kk) & (s.yR == xmax)
        t_hit = s.t_exact.astype(dtype)
        y_hi = s.yR.astype(dtype)
    else:
        with jax.named_scope("sel.sort"):
            zs = jax.lax.sort(z_all)
        with jax.named_scope("sel.probe"):
            cLm = _psum(jnp.sum(x_local <= s.yL, dtype=jnp.int32), axes)
        with jax.named_scope("sel.sort"):
            ans_sort = zs[jnp.clip(kk - cLm - 1, 0, z_all.size - 1)]
        ok_sort = ok_gather
        with jax.named_scope("sel.probe"):
            m_le_v = _psum(jnp.sum(x_local <= vnext, dtype=jnp.int32), axes)
            m_lt_max = _psum(jnp.sum(x_local < xmax, dtype=jnp.int32), axes)
            at_min = cLm >= kk
            at_max = m_lt_max < kk
        t_hit = s.t_exact
        y_hi = s.yR

    with jax.named_scope("sel.sort"):
        fallback_ok = (cLm < kk) & (kk <= m_le_v)
        value = jnp.where(
            s.found_exact, t_hit,
            jnp.where(ok_sort, ans_sort,
                      jnp.where(fallback_ok, vnext, y_hi)),
        )
        status = jnp.where(
            s.found_exact, selection.EXACT_HIT,
            jnp.where(ok_sort, selection.HYBRID_SORT,
                      jnp.where(fallback_ok, selection.TIE_FALLBACK,
                                selection.NOT_CONVERGED)),
        )
        value = jnp.where(at_min, xmin, jnp.where(at_max, xmax, value))
        status = jnp.where(at_min | at_max, selection.EXACT_HIT, status)
    return selection.SelectResult(
        value=value, iters=s.it, status=status.astype(jnp.int32),
        y_lo=s.yL, y_hi=s.yR, n_in=n_in,
    )


def local_weighted_order_statistic(
    x_local: jax.Array,
    w_local: jax.Array,
    wk,
    axes: AxisNames,
    *,
    maxit: int = 64,
    cap_local: int = 4096,
    backend: Optional[str] = None,
    method: str = "binned",
    nbins: int = selection.DEF_NBINS,
    binned_impl: Optional[str] = None,
    prior=None,
) -> selection.SelectResult:
    """Weighted order statistic of the *global* sharded array: the smallest
    element whose global cumulative weight reaches ``wk``.  Call inside
    shard_map; weights are sharded exactly like the data.

    Thin wrapper over :func:`local_order_statistic` — the measure swap is
    the evaluator's ``weights`` leg, not a second round loop: each binned
    round psums the ``(nbins + 2,)`` slot MASS vector (the slot counts stay
    per-shard for the cap bookkeeping), and the finalize all_gathers
    per-shard (value, weight) pair buffers and resolves by sorted prefix
    weights — the weighted analogue of the paper's small-array ``z`` step.
    ``method`` in {'binned', 'binned_polish', 'cp', 'auto'} as in
    :func:`local_order_statistic` (the cp rounds psum the six weighted
    partials; the polish psums the per-slot ``Σ w·x`` vector too and
    saves a round at large n; 'auto' may resolve to 'cp' below
    ``BINNED_MIN_N``).
    """
    if method not in DIST_METHODS:
        raise ValueError(f"unknown method {method!r}; one of "
                         f"{DIST_METHODS}")
    return local_order_statistic(
        x_local, wk, axes, maxit=maxit, cap_local=cap_local,
        backend=backend, method=method, nbins=nbins, weights=w_local,
        binned_impl=binned_impl, prior=prior)


def sharded_order_statistic(
    x: jax.Array,
    k,
    mesh: jax.sharding.Mesh,
    in_spec: P,
    **kwargs,
) -> selection.SelectResult:
    """User-facing wrapper: shard_map the distributed selection.

    ``in_spec`` is the PartitionSpec of ``x`` (1-D).  The result is fully
    replicated.
    """
    axes = tuple(
        a for ax in in_spec for a in
        ((ax,) if isinstance(ax, str) else tuple(ax or ()))
    )

    @functools.partial(
        _compat.shard_map, mesh=mesh, in_specs=(in_spec,),
        out_specs=jax.tree.map(lambda _: P(), selection.SelectResult(
            *(0,) * 6)),
        # outputs are semantically replicated (built from psum/all_gather
        # results), but the static varying-axis analysis cannot prove it
        check=False,
    )
    def run(x_local):
        return local_order_statistic(x_local, k, axes, **kwargs)

    return run(x)


def sharded_median(x, mesh, in_spec, **kw):
    n = x.size
    return sharded_order_statistic(x, (n + 1) // 2, mesh, in_spec, **kw)


def sharded_weighted_order_statistic(
    x: jax.Array,
    w: jax.Array,
    wk,
    mesh: jax.sharding.Mesh,
    in_spec: P,
    **kwargs,
) -> selection.SelectResult:
    """User-facing wrapper: shard_map the weighted distributed selection.

    ``x`` and ``w`` share ``in_spec`` (weights live with their data).  The
    result is fully replicated.
    """
    axes = tuple(
        a for ax in in_spec for a in
        ((ax,) if isinstance(ax, str) else tuple(ax or ()))
    )

    @functools.partial(
        _compat.shard_map, mesh=mesh, in_specs=(in_spec, in_spec),
        out_specs=jax.tree.map(lambda _: P(), selection.SelectResult(
            *(0,) * 6)),
        # outputs are semantically replicated (built from psum/all_gather
        # results), but the static varying-axis analysis cannot prove it
        check=False,
    )
    def run(x_local, w_local):
        return local_weighted_order_statistic(x_local, w_local, wk, axes,
                                              **kwargs)

    return run(x, w)


def sharded_weighted_median(x, w, mesh, in_spec, **kw):
    """Lower weighted median of the sharded array (global mass / 2)."""
    # same dtype rule as selection._total_mass: the target mass must live
    # at the evaluator's accumulation dtype or the two can desynchronize
    W = selection._total_mass(x, jnp.asarray(w))
    return sharded_weighted_order_statistic(x, w, 0.5 * W, mesh, in_spec,
                                            **kw)


def sharded_quantile(x, q, mesh, in_spec, **kw):
    # ranks resolve host-side at f64 (the traced f32 product mis-lands
    # high quantiles at n ~ 2^25 — see selection.ranks_from_quantiles)
    return sharded_order_statistic(
        x, selection.ranks_from_quantiles(q, x.size), mesh, in_spec, **kw)


def multi_order_statistic_across_shards(
    x_local: jax.Array,
    ks,
    axes: AxisNames,
    *,
    maxit: int = 64,
    cap_local: int = 4096,
    backend: Optional[str] = None,
    method: str = "binned",
    nbins: int = selection.DEF_NBINS,
    weights: Optional[jax.Array] = None,
    binned_impl: Optional[str] = None,
    prior=None,
) -> selection.SelectResult:
    """K order statistics of the *global* sharded array in ONE round loop;
    call inside shard_map.  Returns a replicated ``(K,)`` SelectResult.

    The K brackets narrow simultaneously: each binned round is one LOCAL
    shared-x multi-bracket histogram pass (``fused_histogram_multi`` — the
    x tile is read once for all K edge ladders) plus ONE psum of the
    ``(K, nbins + 2)`` slot matrix, so a sharded decile vector costs the
    same collective rounds as a sharded median — not ~K× them.  With
    ``weights`` the targets are cumulative masses and the mass matrix rides
    the wire next to the count matrix (two ``(K, nbins+2)`` psums — the
    counts feed the cap rule); ``method='binned_polish'`` psums the
    per-slot sum matrix too and steers each k's next edge ladder from its
    own straddling-bin centroid.  ``method='cp'`` psums the stacked
    ``(K,)`` additive partials per round; ``'auto'`` resolves by the global
    element count exactly like :func:`local_order_statistic`.

    The loop IS the local engine's (``selection.binned_loop_batched`` /
    ``bracket_loop_batched``) over an :class:`FnEvaluator` whose closures
    psum the local multi-bracket passes — the stopping rule compares the
    GLOBAL in-bracket counts against ``cap_local``, which conservatively
    bounds every shard's compaction buffer.  The finalize compacts per
    shard per k (``selection.rank_compact``), all_gathers the tiny
    ``(cap_local,)`` buffers and resolves through the engine's one answer
    cascade (``selection._assemble_answers``).
    """
    from repro.kernels import ops as kops  # deferred: core <-> kernels

    x_local = x_local.reshape(-1)
    axes_t = _axes_tuple(axes)
    n_glob = jax.lax.psum(x_local.size, axes_t)  # constant-folds (static)
    if method == "auto":
        method = ("binned" if n_glob >= selection.BINNED_MIN_N else "cp")
    weighted = weights is not None
    dtype = x_local.dtype
    bigloc = jnp.asarray(jnp.inf, dtype)

    if weighted:
        wl = jnp.asarray(weights).reshape(-1)
        from repro.kernels.ref import _waccum_dtype
        mdt = _waccum_dtype(x_local, wl)
        W = _psum(jnp.sum(wl, dtype=mdt), axes_t)
        kk = jnp.minimum(jnp.asarray(ks, mdt).reshape(-1), W)
        wl = wl.astype(mdt)

        def partials(y):
            wsp, wsn, wlt, wle, lt, le = kops.fused_weighted_partials_multi(
                x_local, wl, y, backend=backend)
            f = _psum(jnp.stack([wsp, wsn, wlt, wle]), axes_t)
            c = _psum(jnp.stack([lt, le]), axes_t)
            return f[0], f[1], f[2], f[3], c[0], c[1]

        def histogram(edges, need_msum=False):
            cnt, wcnt, wsum = kops.fused_weighted_histogram_multi(
                x_local, wl, edges, backend=backend, impl=binned_impl,
                want_sums=need_msum)
            # count matrix rides a pmax: its prefix differences then bound
            # the WORST shard's in-bracket count (sum of per-slot maxima >=
            # max of per-shard sums), so the engine's cap rule sizes the
            # per-shard compaction buffers — mirroring local_order_statistic
            return (_pmax(cnt, axes_t), _psum(wcnt, axes_t),
                    _psum(wsum, axes_t) if need_msum else None)
    else:
        wl = None
        W = None
        kk = jnp.clip(jnp.asarray(ks, jnp.int32).reshape(-1), 1, n_glob)

        def partials(y):
            sp, sn, lt, le = kops.fused_partials_multi(x_local, y,
                                                       backend=backend)
            f = _psum(jnp.stack([sp, sn]), axes_t)
            c = _psum(jnp.stack([lt, le]), axes_t)
            return f[0], f[1], c[0], c[1]

        def histogram(edges, need_msum=False):
            # ONE psum of the (K, nbins + 2) slot matrix per round drives
            # the narrowing; the count matrix additionally rides a pmax —
            # its prefix differences bound the WORST shard's in-bracket
            # count (sum of per-slot maxima >= max of per-shard sums), so
            # the engine's cap rule sizes the per-shard compaction buffers
            # exactly like local_order_statistic's max_in bookkeeping
            cnt, bsum = kops.fused_histogram_multi(
                x_local, edges, backend=backend, impl=binned_impl,
                want_sums=need_msum)
            return (_pmax(cnt, axes_t), _psum(cnt, axes_t),
                    _psum(bsum, axes_t) if need_msum else None)

    nk = kk.shape[0]
    bc = lambda v: jnp.broadcast_to(v, (nk,))

    def init_stats():
        gmin = _pmin(jnp.min(x_local), axes_t)
        gmax = _pmax(jnp.max(x_local), axes_t)
        if weighted:
            wx = _psum(jnp.sum(wl * x_local, dtype=mdt), axes_t)
            mean = (wx / jnp.maximum(W, 1e-30)).astype(dtype)
        else:
            mean = (_psum(jnp.sum(x_local, dtype=dtype), axes_t)
                    / jnp.asarray(n_glob, dtype))
        return bc(gmin), bc(gmax), bc(mean)

    ev = FnEvaluator(partials, jnp.asarray(n_glob, jnp.int32), kk,
                     init_stats, histogram=histogram,
                     weights_total=W if weighted else None)
    s, xmin, xmax = selection._run_bracket_phase(
        ev, method, maxit, cap_local, nbins,
        prior=selection.as_prior(prior))

    # ---- distributed finalize: compact per shard per k, gather, assemble
    cols = [(x_local, bigloc)]
    if weighted:
        cols.append((wl, jnp.zeros((), wl.dtype)))

    def one(args):
        lo, hi = args
        with jax.named_scope("sel.compact"):
            mask_in = (x_local > lo) & (x_local <= hi)
        bufs, loc_in = selection.rank_compact(mask_in, cap_local, cols)
        with jax.named_scope("sel.compact"):
            gathered = []
            for b in bufs:
                for ax in axes_t:
                    b = jax.lax.all_gather(b, ax)
                gathered.append(b.reshape(-1))
            ok = _pmax(loc_in, axes_t) <= cap_local
            n_in = _psum(loc_in, axes_t)
        with jax.named_scope("sel.probe"):
            vnext = _pmin(jnp.min(jnp.where(x_local > lo, x_local, bigloc)),
                          axes_t)
            if weighted:
                cLm = _psum(jnp.sum(jnp.where(x_local <= lo, wl, 0),
                                    dtype=mdt), axes_t)
                m_le_v = _psum(jnp.sum(jnp.where(x_local <= vnext, wl, 0),
                                       dtype=mdt), axes_t)
            else:
                cLm = _psum(jnp.sum(x_local <= lo, dtype=jnp.int32), axes_t)
                m_le_v = _psum(jnp.sum(x_local <= vnext, dtype=jnp.int32),
                               axes_t)
        return (*gathered, cLm, n_in, ok, vnext, m_le_v)

    out = jax.lax.map(one, (s.yL, s.yR))
    if weighted:
        z, zw, cLm, n_in, ok, vnext, m_le_v = out
        with jax.named_scope("sel.sort"):
            order = jnp.argsort(z, axis=-1)
            zs = jnp.take_along_axis(z, order, axis=-1)
            zws = jnp.take_along_axis(zw, order, axis=-1)
        with jax.named_scope("sel.probe"):
            m_lt_max = bc(_psum(jnp.sum(
                jnp.where(x_local < jnp.max(xmax), wl, 0), dtype=mdt),
                axes_t))
    else:
        z, cLm, n_in, ok, vnext, m_le_v = out
        with jax.named_scope("sel.sort"):
            zs = jnp.sort(z, axis=-1)
        zws = None
        with jax.named_scope("sel.probe"):
            m_lt_max = bc(_psum(jnp.sum(x_local < jnp.max(xmax),
                                        dtype=jnp.int32), axes_t))
    gcap = zs.shape[-1]
    # a per-shard buffer overflow must fail the sort path even when the
    # GLOBAL count fits the gathered width (survivors were dropped locally)
    n_in_eff = jnp.where(ok, n_in, gcap + 1)
    res = selection._assemble_answers(kk, s, gcap, zs, zws, cLm, n_in_eff,
                                      vnext, m_le_v, m_lt_max, xmin, xmax)
    return res._replace(n_in=n_in)


def sharded_multi_order_statistic(
    x: jax.Array,
    ks,
    mesh: jax.sharding.Mesh,
    in_spec: P,
    **kwargs,
) -> selection.SelectResult:
    """User-facing wrapper: shard_map the multi-k distributed selection.

    ``in_spec`` is the PartitionSpec of ``x`` (1-D); ``ks`` the (K,) target
    ranks (or masses via ``weights=`` in ``kwargs``, sharded like ``x``).
    The ``(K,)`` result is fully replicated.
    """
    axes = tuple(
        a for ax in in_spec for a in
        ((ax,) if isinstance(ax, str) else tuple(ax or ()))
    )
    weights = kwargs.pop("weights", None)
    in_specs = (in_spec,) if weights is None else (in_spec, in_spec)

    @functools.partial(
        _compat.shard_map, mesh=mesh, in_specs=in_specs,
        out_specs=jax.tree.map(lambda _: P(), selection.SelectResult(
            *(0,) * 6)),
        # outputs are semantically replicated (built from psum/all_gather
        # results), but the static varying-axis analysis cannot prove it
        check=False,
    )
    def run(x_local, *w_local):
        return multi_order_statistic_across_shards(
            x_local, ks, axes,
            weights=w_local[0] if w_local else None, **kwargs)

    return run(x) if weights is None else run(x, weights)


def sharded_quantiles(x, qs, mesh, in_spec, **kw):
    """Lower empirical quantiles of the sharded array (one multi-k solve:
    a decile vector costs the same psum rounds as a sharded median)."""
    return sharded_multi_order_statistic(
        x, selection.ranks_from_quantiles(qs, x.size), mesh, in_spec, **kw)


# ---------------------------------------------------------------------------
# Vectorized selection ACROSS a mesh axis (coordinate-wise order statistics)
# ---------------------------------------------------------------------------


def axis_evaluator(v_local: jax.Array, k, axes: AxisNames) -> FnEvaluator:
    """Evaluator for coordinate-wise selection ACROSS a mesh axis.

    The batch dimension is the coordinate set (this shard's array shape S);
    each coordinate's data is the ``n_rep`` replica values living one per
    device along ``axes``.  The psum combine of the four additive partials
    is the whole communication story — per iteration the wire carries four
    S-shaped vectors, never the replica data.

    The histogram pass (``method='binned'``) works the same way: each
    device one-hots its single replica value against the per-coordinate bin
    edges and the psum of the ``(S..., nbins + 2)`` count vectors is the
    full cross-replica histogram — one collective round buys log2(nbins)
    bisection steps for every coordinate at once.
    """
    axes_t = _axes_tuple(axes)
    v = v_local.astype(jnp.float32)
    n_rep = _psum(jnp.asarray(1, jnp.int32), axes_t)
    kk = jnp.broadcast_to(jnp.clip(jnp.asarray(k, jnp.int32), 1, n_rep),
                          v.shape)

    def partials(y):
        d = v - y
        return (_psum(jnp.maximum(d, 0), axes_t),
                _psum(jnp.maximum(-d, 0), axes_t),
                _psum((d < 0).astype(jnp.int32), axes_t),
                _psum((d <= 0).astype(jnp.int32), axes_t))

    def histogram(edges, need_msum=False):             # (S..., nbins + 1)
        cap = jnp.full_like(edges[..., :1], jnp.inf)
        lower = jnp.concatenate([-cap, edges], axis=-1)
        upper = jnp.concatenate([edges, cap], axis=-1)
        # slot 0 escapes the strict lower test (`v > -inf` would drop
        # v == -inf), matching the kernels' slot layout
        first = jnp.arange(edges.shape[-1] + 1) == 0
        m = ((v[..., None] > lower) | first) & (v[..., None] <= upper)
        # the counting measure: the psum'd counts serve as both the count
        # and the mass vector; the per-bin sums stay None (psumming them
        # would double the wire bytes, and the across-axis regime never
        # runs the polish)
        cnt = _psum(m.astype(jnp.int32), axes_t)
        return cnt, cnt, None

    def init_stats():
        return (_pmin(v, axes_t), _pmax(v, axes_t),
                _psum(v, axes_t) / n_rep.astype(jnp.float32))

    return FnEvaluator(partials, n_rep, kk, init_stats, histogram=histogram)


def order_statistic_across_axis(
    v_local: jax.Array,
    k: int,
    axes: AxisNames,
    *,
    maxit: int = 48,
    method: str = "auto",
    gather_threshold: int = 32,
    nbins: int = 32,
) -> jax.Array:
    """Coordinate-wise k-th order statistic across a mesh axis.

    ``v_local``: this shard's replica values, any shape S; conceptually the
    data is ``n_rep`` stacked S-arrays, one per device along ``axes``.
    Returns S-shaped array (replicated along ``axes``) with the k-th
    smallest across replicas, per coordinate.  This is the building block of
    robust gradient aggregation.

    method='gather' all-gathers the replica dimension and sorts locally;
    method='binned' runs histogram bracket descent over an
    :func:`axis_evaluator` — each collective round psums per-coordinate
    ``(nbins + 2,)`` count vectors and shrinks every bracket by a factor of
    ``nbins``, resolving in ~3 rounds where the cutting-plane loop
    (method='cp') psums four scalars per coordinate for ~n_rep-ish rounds;
    method='cp' is the paper's O(1)-memory cutting-plane iteration.

    method='auto' resolves STATICALLY (mesh axis sizes are trace-time
    constants) by replica count: 'gather' when ``n_rep <= gather_threshold``
    (default 32), else 'binned'.  Rationale: the all-gather materializes an
    ``(n_rep, S)`` buffer and sorts it — unbeatable while that buffer is a
    few shard-sizes, a memory blowup beyond; binned keeps O(S) memory and a
    round count independent of ``n_rep``.  Callers can override either the
    threshold or the method outright.

    Caveat: the count-based methods ('cp' and 'binned') see values through
    the platform's comparison/arithmetic semantics, so on FTZ hardware
    (XLA:CPU, some accelerator modes) coordinates whose replica values are
    DENORMAL-scale collapse to 0 — 'gather' (sort-based) keeps them.
    Gradient coordinates at 1e-44 carry no usable signal, so 'auto' does
    not branch on this; pass ``method='gather'`` explicitly if sub-normal
    resolution matters.
    """
    axes_t = _axes_tuple(axes)

    if method == "auto":
        # lax.psum of a python int constant-folds to the (static) axis size
        method = ("gather" if jax.lax.psum(1, axes_t) <= gather_threshold
                  else "binned")

    if method == "gather":
        g = v_local
        for ax in axes_t:
            g = jax.lax.all_gather(g, ax)  # leading replica dims
        g = g.reshape((-1,) + v_local.shape)
        gs = jnp.sort(g, axis=0)
        idx = jnp.clip(jnp.asarray(k, jnp.int32) - 1, 0, g.shape[0] - 1)
        return jnp.take(gs, idx, axis=0)

    if method not in ("cp", "binned"):
        raise ValueError(f"unknown method {method!r}")

    v = v_local.astype(jnp.float32)
    ev = axis_evaluator(v_local, k, axes_t)
    kk = ev.k

    # pre-seed coordinates whose answer sits at the extremes (incl. k==1,
    # k==n_rep and all-equal coordinates): they can never exact-hit at an
    # interior pivot, so certify them before the loop and keep them frozen
    yL0, yR0, _ = ev.init_stats()
    cle_min = _psum((v <= yL0).astype(jnp.int32), axes_t)
    clt_max = _psum((v < yR0).astype(jnp.int32), axes_t)
    at_min = cle_min >= kk
    at_max = clt_max < kk
    found0 = at_min | at_max
    t0 = jnp.where(at_min, yL0, jnp.where(at_max, yR0, jnp.nan))

    if method == "binned":
        # cap=1: a round ends for a coordinate once a single replica value
        # is bracketed (the vnext fallback below recovers it exactly) or a
        # binned certificate fires; ~3 psum rounds of (nbins+2,) counts
        # replace ~n_rep-ish rounds of scalar-quadruple psums
        s, _, _ = selection.binned_loop_batched(
            ev, nbins=nbins, maxit=maxit, cap=1, found0=found0, t0=t0)
    else:
        # cap=0: iterate to exact hit (or maxit) — there is no compaction
        # stage here (the replica data never leaves its device), so the
        # finalize is certificate + tie-fallback only
        s, _, _ = selection.bracket_loop_batched(
            ev, method="cp", maxit=maxit, cap=0, found0=found0, t0=t0)

    # tie fallback for coordinates that did not exact-hit: next distinct
    # value above yL, certified by counts (one extra pair of psums).
    big = jnp.asarray(jnp.inf, jnp.float32)
    vnext = _pmin(jnp.where(v > s.yL, v, big), axes_t)
    n_le_v = _psum((v <= vnext).astype(jnp.int32), axes_t)
    fb_ok = (s.cleL < kk) & (kk <= n_le_v)
    ans = jnp.where(s.found_exact, s.t_exact,
                    jnp.where(fb_ok, vnext, s.yR))
    return ans.astype(v_local.dtype)


def median_across_axis(v_local, axes, **kw):
    axes_t = (axes,) if isinstance(axes, str) else tuple(axes)
    n_rep = _psum(jnp.asarray(1, jnp.int32), axes_t)
    k = (n_rep + 1) // 2
    return order_statistic_across_axis(v_local, k, axes, **kw)
