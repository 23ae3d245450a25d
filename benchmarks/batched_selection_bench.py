"""Batched engine (cp vs binned) vs vmap-of-scalar-solver vs jnp.sort.

Three tentpole claims ride this bench:

* PR 1 (batched-first): one engine iterating a (B,) state block beats B
  lock-stepped scalar solvers (``jax.vmap`` of the public scalar API) and
  the full-sort baseline, bit-identical to ``np.partition`` row-wise.
* PR 2 (binned bracket descent): ``method='binned'`` resolves a solve in
  ~2-3 histogram sweeps where ``method='cp'`` needs ~10-20 fused passes —
  the ``sweeps_binned`` / ``iters_cp`` columns are the data-pass counts per
  solve (each binned sweep and each cp iteration is exactly one pass over
  the (B, n) block).
* PR 3 (weighted order statistics): the weighted-binned engine keeps the
  ~3-sweep schedule against a target cumulative MASS (the ``weighted_grid``
  records, bit-identical to the numpy sorted-cumsum oracle), vs the
  weighted sort-cumsum baseline (argsort + weight gather + cumsum +
  searchsorted — the thing every sort-based weighted median pays).
* PR 4 (in-bin CP polish): ``method='binned_polish'`` centers each sweep's
  bins on the cutting-plane cut recovered free from the previous sweep's
  per-bin sums — the ``sweeps_polish`` column records the data-pass
  reduction vs plain ``binned`` (2 -> 1 at n = 1M on normal data), still
  bit-identical to ``np.partition``.
* PR 5 (verified arithmetic binning): the ``hist_pass`` record compares one
  CPU histogram sweep against one fused FG pass at n = 1M — the
  searchsorted/scatter pass was ~25x a fused pass (why auto kept 'cp' on
  CPU); the verified arithmetic pass (multiply/floor/clip slots + factored
  one-hot reduction, counting-leg configuration) is what flipped
  ``method=None`` to 'binned' everywhere.  The ``distributed`` record
  (subprocess, forced host devices) tracks the psum-round counts:
  polish-driven rounds solve the 1M median in 1 round vs binned's 2, both
  measures.
* PR 6 (one-sweep multi-k): the ``multi_k`` record times a K-vector of
  quantiles of ONE array (K in {4, 16, 64} at n = 1M) against the K = 1
  binned median — every data pass is shared across the K ladders, so the
  sweep count stays ~flat in K (<= 2x the single-median sweeps at K = 16)
  where naive per-k dispatch would pay ~K x the HBM traffic.

Emits the usual CSV rows plus one ``BENCH_JSON`` line; ``run(json_path=...)``
(the ``benchmarks/run.py --json`` path) additionally writes the records to a
machine-readable perf-trajectory file (``BENCH_selection.json``).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np

import jax
import jax.numpy as jnp

from benchmarks.common import emit, timeit
from repro.core import selection
from repro.kernels import ops, ref


def _hist_pass_record(rows):
    """One-histogram-sweep vs one-fused-FG-pass timings at n = 1M (jnp/CPU
    path), interleaved medians at matched jit-call granularity."""
    n = 1 << 20
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal(n).astype(np.float32))
    nbins_jnp = selection.DEF_NBINS_JNP
    lo, hi = jnp.float32(-4.0), jnp.float32(4.0)
    e_jnp = ref.bin_edges(lo, hi, nbins_jnp)
    e_128 = ref.bin_edges(lo, hi, selection.DEF_NBINS)
    y = jnp.float32(0.01)

    fg = jax.jit(lambda v: ops.fused_partials(v, y, backend="jnp"))
    # the auto path's sweep: arithmetic slots, counting leg, no sums
    arith = jax.jit(lambda v: ops.fused_histogram(
        v, e_jnp, backend="jnp", impl="arithmetic", want_sums=False)[0])
    # yesterday's pass: binary-search slots + scatter at the kernel nbins
    ss128 = jax.jit(lambda v: ops.fused_histogram(
        v, e_128, backend="jnp", impl="searchsorted"))
    # interleave to share the machine's thermal/quota state
    t_fg = min(timeit(fg, x), timeit(fg, x))
    t_ar = min(timeit(arith, x), timeit(arith, x))
    t_ss = timeit(ss128, x, reps=3)
    t_fg = min(t_fg, timeit(fg, x))
    # engine granularity, tightly interleaved (shared-instant machine
    # state — CI/container CPU quotas swing several x over a bench run):
    # one binned sweep vs one cp iteration as the solver pays them
    k = jnp.asarray(n // 2 + 1, jnp.int32)
    x2 = x.reshape(1, -1)
    f_cp = jax.jit(lambda v: selection.select_rows(
        v, k, method="cp", backend="jnp").value)
    f_bin = jax.jit(lambda v: selection.select_rows(
        v, k, method="binned", backend="jnp").value)
    t_ecp = min(timeit(f_cp, x2, reps=3), timeit(f_cp, x2, reps=3))
    t_ebin = min(timeit(f_bin, x2, reps=3), timeit(f_bin, x2, reps=3))
    iters_cp = int(selection.select_rows(x2, k, method="cp",
                                         backend="jnp").iters[0])
    sweeps = int(selection.select_rows(x2, k, method="binned",
                                       backend="jnp").iters[0])
    per_sweep = t_ebin / max(sweeps, 1)
    per_pass = t_ecp / max(iters_cp, 1)
    rec = dict(
        n=n, nbins_jnp=nbins_jnp, nbins_searchsorted=selection.DEF_NBINS,
        us_fg_pass=t_fg * 1e6,
        us_hist_arith=t_ar * 1e6,
        us_hist_searchsorted_128=t_ss * 1e6,
        ratio_arith_over_fg=t_ar / t_fg,
        ratio_searchsorted_over_fg=t_ss / t_fg,
        us_engine_cp_total=t_ecp * 1e6,
        us_engine_binned_total=t_ebin * 1e6,
        engine_iters_cp=iters_cp,
        engine_sweeps_binned=sweeps,
        ratio_engine_sweep_over_cp_pass=per_sweep / per_pass,
        auto_method_jnp_1m=selection._resolve_method(None, n, "jnp"),
    )
    rows.append(("hist_arith_vs_fg/n=1M", t_ar * 1e6,
                 f"{t_ar / t_fg:.2f}x fg (searchsorted: "
                 f"{t_ss / t_fg:.1f}x)"))
    rows.append(("engine_binned_vs_cp/n=1M", t_ebin * 1e6,
                 f"cp={t_ecp * 1e6:.0f}us sweep/pass="
                 f"{per_sweep / per_pass:.2f}x"))
    return rec


def _multi_k_record(rows, full: bool = False):
    """One-sweep multi-k economics (PR 6): a K-vector of quantiles on ONE
    array shares every histogram sweep, so the sweep count stays ~flat in K
    (vs the naive K independent descents paying ~K x the HBM traffic).
    Records K in {4, 16, 64} at n = 1M against the K = 1 binned median
    baseline: total sweeps, us per call, and us per k."""
    n = 1 << 20
    rng = np.random.default_rng(7)
    x = rng.standard_normal(n).astype(np.float32)
    xj = jnp.asarray(x)
    k_med = (n + 1) // 2

    base = jax.jit(lambda v: selection.multi_order_statistic(
        v, jnp.asarray([k_med], jnp.int32), method="binned",
        backend="jnp").value)
    want_med = np.partition(x, k_med - 1)[k_med - 1]
    assert np.float32(np.asarray(base(xj))[0]) == want_med
    t_base = timeit(base, xj, reps=3)
    sweeps_base = int(jnp.max(selection.multi_order_statistic(
        xj, jnp.asarray([k_med], jnp.int32), method="binned",
        backend="jnp").iters))

    recs = []
    for kk in [4, 16, 64]:
        qs = [(i + 1) / (kk + 1) for i in range(kk)]
        ks = np.asarray([int(np.ceil(q * n)) for q in qs], np.int32)
        want = np.partition(x, ks - 1)[ks - 1]
        fn = jax.jit(lambda v, kv=jnp.asarray(ks): selection
                     .multi_order_statistic(v, kv, method="binned",
                                            backend="jnp").value)
        got = np.asarray(fn(xj))
        assert np.array_equal(got, want), ("multi_k", kk)
        t = timeit(fn, xj, reps=3)
        sweeps = int(jnp.max(selection.multi_order_statistic(
            xj, jnp.asarray(ks), method="binned", backend="jnp").iters))
        recs.append(dict(
            K=kk, n=n, sweeps=sweeps, sweeps_k1=sweeps_base,
            us_per_call=t * 1e6, us_per_k=t * 1e6 / kk,
            us_k1_baseline=t_base * 1e6,
            sweep_ratio_vs_k1=sweeps / max(sweeps_base, 1),
            time_ratio_vs_k1=t / t_base,
        ))
        rows.append((f"multi_k_binned/K={kk}/n={n}", t * 1e6,
                     f"sweeps={sweeps} (K=1: {sweeps_base}) "
                     f"{t * 1e6 / kk:.0f}us/k"))
    return recs


def _distributed_rounds_record(rows, n_dev=4, log2_n=20):
    """Psum-round counts from the forced-host-device subprocess worker.

    The worker counts rounds on virtual CPU devices, so it is pinned to
    the CPU: this process may already hold an accelerator, which a child
    could not open.  A worker that fails or times out fails the bench."""
    worker = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "_dist_rounds_worker.py")
    env = dict(os.environ)
    root = os.path.dirname(os.path.dirname(os.path.abspath(worker)))
    env["PYTHONPATH"] = os.path.join(root, "src")
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, worker, str(n_dev), str(log2_n)],
        capture_output=True, text=True, env=env, timeout=600)
    for line in out.stdout.splitlines():
        if line.startswith("DIST_ROUNDS_JSON "):
            rec = json.loads(line[len("DIST_ROUNDS_JSON "):])
            rows.append((
                f"dist_rounds_polish/n_dev={n_dev}/n={1 << log2_n}",
                rec["rounds_binned_polish"],
                f"binned={rec['rounds_binned']} weighted_polish="
                f"{rec['rounds_binned_polish_weighted']}"))
            return rec
    raise RuntimeError(f"distributed rounds worker failed (rc="
                       f"{out.returncode}):\n{out.stdout}\n{out.stderr}")


def _warm_start_record(rows, full: bool = False):
    """Warm-vs-cold grids for the prior leg: ``lts_fit``/``irls_fit``
    wall-clock at n = 1M plus drifting-stream re-select sweep counts.

    Warm and cold runs are bit-identical by contract (asserted here); the
    record captures the economy — steady-state sweeps and the wall-clock
    ratio — for the perf trajectory and the CI warm <= cold smoke."""
    from repro.core import robust, stream

    n = 1 << 20
    rng = np.random.default_rng(7)
    xs = rng.standard_normal(n).astype(np.float32)
    X = np.stack([np.ones_like(xs), xs], axis=1)
    y = (2.0 + 3.0 * xs + 0.1 * rng.standard_normal(n)).astype(np.float32)
    y = np.where(rng.random(n) < 0.2,
                 50.0 * rng.standard_normal(n).astype(np.float32), y)
    Xj, yj = jnp.asarray(X), jnp.asarray(y)
    rec = {"n": n}

    # --- IRLS: warm carry across scale steps ------------------------------
    irls = lambda warm: robust.irls_fit(Xj, yj, loss="huber", iters=6,
                                        method="binned", warm=warm)
    fw, fc = irls(True), irls(False)
    assert np.array_equal(np.asarray(fw.theta), np.asarray(fc.theta))
    us_w = timeit(lambda: irls(True), reps=3, warmup=1) * 1e6
    us_c = timeit(lambda: irls(False), reps=3, warmup=1) * 1e6
    rec["irls"] = dict(
        iters=6, us_warm=us_w, us_cold=us_c, speedup=us_c / us_w,
        sweeps_warm=[int(s) for s in np.asarray(fw.sweeps)],
        sweeps_cold=[int(s) for s in np.asarray(fc.sweeps)])
    rows.append((f"irls_warm_vs_cold/n={n}", us_w,
                 f"cold={us_c:.0f}us speedup={us_c / us_w:.2f}x"))

    # --- LTS: warm carry across concentration steps -----------------------
    key = jax.random.PRNGKey(0)
    lts = lambda warm: robust.lts_fit(key, Xj, yj, n_starts=2, c_steps=5,
                                      method="binned", warm=warm)
    lw, lc = lts(True), lts(False)
    assert np.array_equal(np.asarray(lw.theta), np.asarray(lc.theta))
    us_w = timeit(lambda: lts(True), reps=3, warmup=1) * 1e6
    us_c = timeit(lambda: lts(False), reps=3, warmup=1) * 1e6
    rec["lts"] = dict(
        n_starts=2, c_steps=5, us_warm=us_w, us_cold=us_c,
        speedup=us_c / us_w,
        sweeps_warm=[int(s) for s in np.asarray(lw.sweeps).max(axis=1)],
        sweeps_cold=[int(s) for s in np.asarray(lc.sweeps).max(axis=1)])
    rows.append((f"lts_warm_vs_cold/n={n}", us_w,
                 f"cold={us_c:.0f}us speedup={us_c / us_w:.2f}x"))

    # --- drifting stream: re-select sweeps per tick -----------------------
    ticks = 6
    tr = stream.QuantileTracker(0.5, method="binned")
    cold_sweeps = []
    for t in range(ticks):
        xt = xs + 1e-3 * t * rng.standard_normal(n).astype(np.float32)
        res = tr.update(xt)
        coldr = selection.quantile(jnp.asarray(xt), 0.5, method="binned")
        assert np.asarray(res.value) == np.asarray(coldr.value)
        cold_sweeps.append(int(coldr.iters))
    rec["stream"] = dict(ticks=ticks, sweeps_warm=tr.sweeps,
                         sweeps_cold=cold_sweeps)
    rows.append((f"stream_reselect/n={n}", float(sum(tr.sweeps)),
                 f"cold_sweeps={sum(cold_sweeps)} per-tick={tr.sweeps}"))
    return rec


def run(full: bool = False, json_path: str | None = None):
    # quick mode keeps CI under a minute but still covers an n >= 1e6 point
    # (where the binned pass-count advantage is the whole story)
    grid = [(1, 1 << 12), (8, 1 << 12), (64, 1 << 12),
            (1, 1 << 16), (8, 1 << 16), (64, 1 << 16),
            (1, 1 << 20), (8, 1 << 20)]
    if full:
        grid += [(256, 1 << 16), (64, 1 << 20), (1, 1 << 24)]
    rng = np.random.default_rng(0)
    rows, records = [], []
    for b, n in grid:
        x = rng.standard_normal((b, n)).astype(np.float32)
        xj = jnp.asarray(x)
        k = (n + 1) // 2
        want = np.partition(x, k - 1, axis=1)[:, k - 1]

        vmapped = jax.jit(jax.vmap(
            lambda xi: selection.order_statistic(xi, k, method="cp").value))
        batched_cp = jax.jit(
            lambda v: selection.select_rows(v, k, method="cp").value)
        batched_binned = jax.jit(
            lambda v: selection.select_rows(v, k, method="binned").value)
        batched_polish = jax.jit(
            lambda v: selection.select_rows(v, k,
                                            method="binned_polish").value)
        sort = jax.jit(lambda v: jnp.sort(v, axis=1)[:, k - 1])

        impls = {"vmap_scalar": vmapped, "batched_cp": batched_cp,
                 "batched_binned": batched_binned,
                 "batched_polish": batched_polish, "sort": sort}
        times = {}
        for name, fn in impls.items():
            got = np.asarray(fn(xj))
            assert np.array_equal(got, want), (name, b, n)
            times[name] = timeit(fn, xj, reps=3)

        # data-pass counts per solve: one fused pass per cp iteration, one
        # histogram sweep per binned iteration (max over rows)
        iters_cp = int(jnp.max(
            selection.select_rows(xj, k, method="cp").iters))
        sweeps_binned = int(jnp.max(
            selection.select_rows(xj, k, method="binned").iters))
        sweeps_polish = int(jnp.max(
            selection.select_rows(xj, k, method="binned_polish").iters))
        speedup = times["vmap_scalar"] / times["batched_cp"]
        for name, t in times.items():
            rows.append((
                f"{name}/B={b}/n={n}", t * 1e6,
                f"{b * n / t / 1e6:.1f}Melem/s",
            ))
        rows.append((f"speedup_batched_over_vmap/B={b}/n={n}",
                     speedup, f"iters={iters_cp}"))
        rows.append((f"passes_binned_vs_cp/B={b}/n={n}",
                     sweeps_binned, f"cp={iters_cp}"))
        rows.append((f"sweeps_polish_vs_binned/B={b}/n={n}",
                     sweeps_polish, f"binned={sweeps_binned}"))
        records.append(dict(
            B=b, n=n, k=k,
            iters_cp=iters_cp, sweeps=sweeps_binned,
            sweeps_polish=sweeps_polish,
            us_vmap=times["vmap_scalar"] * 1e6,
            us_batched_cp=times["batched_cp"] * 1e6,
            us_per_call=times["batched_binned"] * 1e6,  # the binned engine
            us_batched_polish=times["batched_polish"] * 1e6,
            us_sort=times["sort"] * 1e6,
            speedup_batched_over_vmap=speedup,
            speedup_binned_over_cp=times["batched_cp"]
            / times["batched_binned"],
        ))
    # ---- weighted rows: weighted-binned vs weighted sort-cumsum ----------
    wgrid = [(1, 1 << 16), (8, 1 << 16), (1, 1 << 20)]
    if full:
        wgrid += [(8, 1 << 20)]
    wrecords = []
    for b, n in wgrid:
        x = rng.standard_normal((b, n)).astype(np.float32)
        # integer weights: masses exactly summable, so every method must be
        # bit-identical to the f64 sorted-cumsum oracle
        w = rng.integers(1, 4, (b, n)).astype(np.float32)
        wks = (0.5 * w.sum(axis=1)).astype(np.float32)
        xj, wj, wkj = jnp.asarray(x), jnp.asarray(w), jnp.asarray(wks)
        want = np.empty(b, np.float32)
        for i in range(b):
            o = np.argsort(x[i], kind="stable")
            c = np.cumsum(w[i][o].astype(np.float64))
            want[i] = x[i][o][np.searchsorted(c, wks[i], "left")]

        impls = {
            "weighted_binned": jax.jit(lambda v, wv, t: selection
                                       .weighted_select_rows(
                                           v, wv, t, method="binned").value),
            "weighted_cp": jax.jit(lambda v, wv, t: selection
                                   .weighted_select_rows(
                                       v, wv, t, method="cp").value),
            "weighted_sort_cumsum": jax.jit(
                lambda v, wv, t: selection.weighted_select_rows(
                    v, wv, t, method="sort").value),
        }
        times = {}
        for name, fn in impls.items():
            got = np.asarray(fn(xj, wj, wkj))
            assert np.array_equal(got, want), (name, b, n)
            times[name] = timeit(fn, xj, wj, wkj, reps=3)

        sweeps_w = int(jnp.max(selection.weighted_select_rows(
            xj, wj, wkj, method="binned").iters))
        iters_wcp = int(jnp.max(selection.weighted_select_rows(
            xj, wj, wkj, method="cp").iters))
        res_wp = selection.weighted_select_rows(xj, wj, wkj,
                                                method="binned_polish")
        assert np.array_equal(np.asarray(res_wp.value), want), (b, n)
        sweeps_w_polish = int(jnp.max(res_wp.iters))
        for name, t in times.items():
            rows.append((f"{name}/B={b}/n={n}", t * 1e6,
                         f"{b * n / t / 1e6:.1f}Melem/s"))
        rows.append((f"weighted_sweeps_binned_vs_cp/B={b}/n={n}",
                     sweeps_w, f"cp={iters_wcp}"))
        rows.append((f"weighted_sweeps_polish_vs_binned/B={b}/n={n}",
                     sweeps_w_polish, f"binned={sweeps_w}"))
        wrecords.append(dict(
            B=b, n=n,
            sweeps=sweeps_w, iters_cp=iters_wcp,
            sweeps_polish=sweeps_w_polish,
            us_per_call=times["weighted_binned"] * 1e6,
            us_weighted_cp=times["weighted_cp"] * 1e6,
            us_weighted_sort=times["weighted_sort_cumsum"] * 1e6,
            speedup_binned_over_sort=times["weighted_sort_cumsum"]
            / times["weighted_binned"],
        ))

    # ---- multi-k sweep sharing + histogram-pass microbench + distributed
    # round counts ---------------------------------------------------------
    multi_k_recs = _multi_k_record(rows, full=full)
    hist_rec = _hist_pass_record(rows)
    dist_rec = _distributed_rounds_record(rows)
    warm_rec = _warm_start_record(rows, full=full)

    emit(rows)
    # schema 2: adds the schema field itself + the warm_start grids (PR 10)
    payload = {"schema": 2, "bench": "batched_selection", "exact": True,
               "backend": jax.default_backend(), "grid": records,
               "weighted_grid": wrecords, "multi_k": multi_k_recs,
               "hist_pass": hist_rec, "distributed": dist_rec,
               "warm_start": warm_rec}
    print("BENCH_JSON " + json.dumps(payload))
    if json_path is not None:
        with open(json_path, "w") as f:
            json.dump(payload, f, indent=1, sort_keys=True)
            f.write("\n")
        print(f"wrote {json_path}")
    return rows


if __name__ == "__main__":
    run(full=False)
