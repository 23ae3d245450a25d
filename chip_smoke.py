"""End-to-end smoke of the exact-selection engine on TPU.

Drives the public API once per phase, at full size, on data generated from
``--seed``, and checks every answer against a plain host reference:

  selection.median           n = 2^28 f32 (1 GiB)        np.partition
  selection.select_rows      B = 256 rows of n = 2^20     np.partition per row
  selection.quantiles        9 deciles of n = 2^28        np.partition
  selection.weighted_median  n = 2^26                     f64 sorted-cumsum oracle
  robust.lts_fit             n = 2^20, p = 8, 64 starts,  theta, and the trimmed
                             30% outliers                 sum recomputed on host;
                                                          its trimming cutoff
                                                          against np.partition

``--chips 4`` runs only the sharded path instead: ``distributed.
sharded_median`` and ``sharded_quantiles`` (9 deciles) of n = 2^28 global
f32, sharded ``P("data")`` over a mesh of every device, against
``np.partition``.

Each compiled program prints one line (name, shape, dtype, status counts,
whether the answer equals its reference bit for bit, whether the program
holds a Pallas kernel); wall times on those lines are information, not
metrics.  The last line is ``{"ok": true, "device": {...}}``, whose
``count`` is the number of chips the phases used.  The script exits
non-zero without that line when JAX finds no TPU, when an answer differs
from its reference, when any status is NOT_CONVERGED, or when a compiled
program holds no ``tpu_custom_call``.  Everything runs in this one process,
which holds the chip(s).

    python chip_smoke.py [--seed 0] [--chips 4]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
STATUS = ("EXACT_HIT", "HYBRID_SORT", "TIE_FALLBACK", "NOT_CONVERGED")
DECILES = tuple(i / 10 for i in range(1, 10))

N = 1 << 28                # median, deciles, sharded: 1 GiB of f32
ROWS = (256, 1 << 20)      # select_rows: B rows of n
N_WEIGHTED = 1 << 26       # weighted median
LTS = (1 << 20, 8, 64)     # lts_fit: n, p, starts


class SmokeError(RuntimeError):
    pass


def _same_bits(got, want) -> bool:
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return got.shape == want.shape and np.array_equal(
        got.view(np.uint32), want.view(np.uint32))


def _ranks(qs, n):
    """``ceil(q * n)`` clipped to ``[1, n]``, in f64 (the engine's rule)."""
    return np.clip(np.ceil(np.asarray(qs, np.float64) * n), 1, n).astype(
        np.int64)


def _run(fn, *args):
    """Compile ``fn`` for ``args``, run that compiled program, and return
    ``(out, hlo_text, compile_s, run_s)``."""
    import jax

    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    t1 = time.perf_counter()
    out = jax.block_until_ready(compiled(*args))
    return out, compiled.as_text(), t1 - t0, time.perf_counter() - t1


def _report(name, shape, status, exact, hlo, times, extra="", passed=True):
    """Print the phase line; return whether the phase passed.

    ``status``/``exact`` are None for a program with no SelectResult or no
    bit-exact reference; ``passed`` carries such a program's own checks."""
    counts = "n/a"
    if status is not None:
        codes = np.asarray(status).reshape(-1)
        counts = {STATUS[c]: int(np.sum(codes == c))
                  for c in np.unique(codes)}
    kernel = "tpu_custom_call" in hlo
    ok = (exact is not False and passed and kernel
          and "NOT_CONVERGED" not in counts)
    shown = "n/a" if exact is None else bool(exact)
    print(f"phase={name} shape={tuple(shape)} dtype=float32 "
          f"status={counts} exact={shown} tpu_custom_call={kernel}"
          f"{extra} compile_wall_s={times[0]:.1f} run_wall_s={times[1]:.2f}"
          f" ok={ok}", flush=True)
    return ok


# ---------------------------------------------------------------------------
# One-chip phases
# ---------------------------------------------------------------------------


def phase_median(rng):
    import jax
    from repro.core import selection

    n = N
    x = rng.standard_normal(n, dtype=np.float32)
    xd = jax.device_put(x)
    res, hlo, *t = _run(selection.median, xd)
    del xd
    k = (n + 1) // 2
    want = np.partition(x, k - 1)[k - 1]
    return _report("selection.median", x.shape, res.status,
                   _same_bits(res.value, want), hlo, t)


def phase_select_rows(rng):
    import jax
    from repro.core import selection

    b, n = ROWS
    scale = rng.uniform(1e-3, 1e3, (b, 1)).astype(np.float32)
    shift = rng.uniform(-1e2, 1e2, (b, 1)).astype(np.float32)
    x = rng.standard_normal((b, n), dtype=np.float32) * scale + shift
    ks = rng.integers(1, n + 1, b).astype(np.int32)
    xd, kd = jax.device_put(x), jax.device_put(ks)
    res, hlo, *t = _run(selection.select_rows, xd, kd)
    del xd
    want = np.array([np.partition(x[i], ks[i] - 1)[ks[i] - 1]
                     for i in range(b)], np.float32)
    return _report("selection.select_rows", x.shape, res.status,
                   _same_bits(res.value, want), hlo, t)


def phase_quantiles(rng):
    import jax
    from repro.core import selection

    # latency-like: lognormal milliseconds on a 1/8 ms grid, so every
    # decile sits inside a long run of ties
    n = N
    x = (np.round(rng.lognormal(3.0, 0.8, n) * 8.0) / 8.0).astype(np.float32)
    xd = jax.device_put(x)
    res, hlo, *t = _run(lambda v: selection.quantiles(v, DECILES), xd)
    del xd
    r = _ranks(DECILES, n) - 1
    want = np.partition(x, r)[r]
    return _report("selection.quantiles", x.shape, res.status,
                   _same_bits(res.value, want), hlo, t, " K=9")


def phase_weighted_median(rng):
    import jax
    from repro.core import selection

    # integer weights whose total stays below 2^24: every partial mass is
    # exact in f32, the weighted contract's bit-exact case
    n = N_WEIGHTED
    x = rng.standard_normal(n, dtype=np.float32)
    w = np.where(rng.random(n) < 0.125, rng.integers(1, 3, n), 0).astype(
        np.float32)
    total = float(np.sum(w, dtype=np.float64))
    if not 0 < total < 2 ** 24:
        raise SmokeError(f"weights not exactly summable in f32: {total}")
    xd, wd = jax.device_put(x), jax.device_put(w)
    res, hlo, *t = _run(selection.weighted_median, xd, wd)
    del xd, wd
    order = np.argsort(x, kind="stable")
    cum = np.cumsum(w[order].astype(np.float64))
    i = min(int(np.searchsorted(cum, 0.5 * total, side="left")), n - 1)
    return _report("selection.weighted_median", x.shape, res.status,
                   _same_bits(res.value, x[order][i]), hlo, t)


def phase_lts(rng, seed):
    import jax
    from repro.core import robust, selection

    n, p, starts = LTS
    X = np.concatenate([np.ones((n, 1), np.float32),
                        rng.standard_normal((n, p - 1), dtype=np.float32)],
                       axis=1)
    theta_true = rng.standard_normal(p).astype(np.float32)
    y = X @ theta_true + 0.05 * rng.standard_normal(n, dtype=np.float32)
    bad = rng.choice(n, int(0.3 * n), replace=False)
    y[bad] += rng.normal(50.0, 10.0, bad.size).astype(np.float32)
    Xd, yd = jax.device_put(X), jax.device_put(y)
    fit, hlo, *t = _run(
        lambda key, a, b: robust.lts_fit(key, a, b, n_starts=starts),
        jax.random.key(seed), Xd, yd)
    theta = np.asarray(fit.theta)
    h = (n + p + 1) // 2

    # the fit's reported objective against the trimmed sum recomputed on
    # the host from the returned theta, in f64
    r64 = X.astype(np.float64) @ theta.astype(np.float64) - y
    obj_host = float(np.sum(np.partition(r64 * r64, h - 1)[:h]))
    obj_err = abs(float(fit.objective) - obj_host) / obj_host
    theta_err = float(np.max(np.abs(theta - theta_true)))

    # the trimming cutoff of the returned theta, bit for bit: the h-th
    # smallest squared residual, selected on the chip, against np.partition
    # of the same squared residuals
    def cutoff(th, a, b):
        r = robust.residuals(th, a, b)
        a2 = r * r
        return a2, selection.order_statistic(a2, h)

    (a2, cut), hlo_c, *t_c = _run(cutoff, fit.theta, Xd, yd)
    a2 = np.asarray(a2)
    exact = _same_bits(cut.value, np.partition(a2, h - 1)[h - 1])
    del Xd, yd
    # theta: the inliers' noise is 0.05, the outliers sit ~50 off
    ok = _report("robust.lts_fit", X.shape, None, None, hlo, t,
                 f" theta_err={theta_err:.3g} objective_rel_err="
                 f"{obj_err:.3g}", passed=theta_err < 1e-2 and obj_err < 1e-4)
    return _report("robust.lts_fit.cutoff", (n,), cut.status, exact, hlo_c,
                   t_c) and ok


# ---------------------------------------------------------------------------
# Four-chip phases
# ---------------------------------------------------------------------------


def phase_sharded(rng, devices):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core import _compat, distributed

    n = N
    mesh = _compat.make_mesh((len(devices),), ("data",))
    x = rng.standard_normal(n, dtype=np.float32)
    xd = jax.device_put(x, NamedSharding(mesh, P("data")))
    med, hlo_m, *tm = _run(
        lambda v: distributed.sharded_median(v, mesh, P("data")), xd)
    qs, hlo_q, *tq = _run(
        lambda v: distributed.sharded_quantiles(v, DECILES, mesh, P("data")),
        xd)
    del xd
    k = (n + 1) // 2
    r = _ranks(DECILES, n) - 1
    want = np.partition(x, np.concatenate([[k - 1], r]))
    ok = _report("distributed.sharded_median", x.shape, med.status,
                 _same_bits(med.value, want[k - 1]), hlo_m, tm,
                 f" chips={len(devices)}")
    return _report("distributed.sharded_quantiles", x.shape, qs.status,
                   _same_bits(qs.value, want[r]), hlo_q, tq,
                   f" chips={len(devices)} K=9") and ok


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded phases, on four chips")
    args = ap.parse_args()

    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.compile_cache import use_compile_cache

    use_compile_cache()
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        raise SmokeError(f"no TPU: JAX found {platform} devices")
    if len(devices) < args.chips:
        raise SmokeError(f"--chips {args.chips} needs {args.chips} chips; "
                         f"JAX found {len(devices)}")
    print(f"device kind={devices[0].device_kind} count={len(devices)} "
          f"jax={jax.__version__}", flush=True)

    rng = np.random.default_rng(args.seed)
    if args.chips == 4:
        oks = [phase_sharded(rng, devices[:args.chips])]
    else:
        oks = [phase_median(rng), phase_select_rows(rng),
               phase_quantiles(rng), phase_weighted_median(rng),
               phase_lts(rng, args.seed)]
    if not all(oks):
        raise SmokeError("a phase failed (see its ok=False line)")
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": args.chips}}))
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeError as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        sys.exit(1)
