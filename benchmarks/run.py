"""Benchmark harness entry point — one bench per paper table/figure.

  selection_bench      Tables I/II (method x size x dtype)
  batched_selection    batched engine vs vmap-of-scalar vs sort, (B, n) grid
  distribution_bench   Sec. V-C (nine distributions)
  outlier_bench        Sec. V-D / Fig. 5 (extreme values)
  hybrid_breakdown     Sec. IV (CP iterations vs pivot-interval handoff)
  regression_bench     Sec. VI (LMS/LTS/kNN)

Prints ``name,us_per_call,derived`` CSV.  ``--full`` uses paper-scale sizes.
``--json`` additionally writes the selection perf trajectory (grid point,
us_per_call, binned sweeps vs cp iterations) to repo-root
``BENCH_selection.json`` — the machine-readable record each perf PR updates.
"""
from __future__ import annotations

import argparse
import os
import sys
import traceback

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale array sizes (slow on CPU)")
    ap.add_argument("--only", default=None)
    ap.add_argument("--json", action="store_true",
                    help="write repo-root BENCH_selection.json from the "
                         "batched_selection grid")
    args = ap.parse_args()

    # f64 columns of Table II need x64 (benchmarks run in their own process;
    # tests and smoke runs keep the default f32)
    import jax
    jax.config.update("jax_enable_x64", True)

    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.compile_cache import use_compile_cache
    use_compile_cache()

    from benchmarks import (
        batched_selection_bench,
        clip_bench,
        distribution_bench,
        hybrid_breakdown_bench,
        outlier_bench,
        regression_bench,
        selection_bench,
    )

    benches = {
        "selection": selection_bench,
        "batched_selection": batched_selection_bench,
        "distribution": distribution_bench,
        "outlier": outlier_bench,
        "hybrid": hybrid_breakdown_bench,
        "regression": regression_bench,
        "clip": clip_bench,
    }
    failed = []
    for name, mod in benches.items():
        if args.only and name != args.only:
            continue
        print(f"\n### bench: {name}")
        kw = {}
        if args.json and name == "batched_selection":
            kw["json_path"] = os.path.join(ROOT, "BENCH_selection.json")
        try:
            mod.run(full=args.full, **kw)
        except Exception:
            traceback.print_exc()
            failed.append(name)
    if failed:
        print(f"\nFAILED benches: {failed}")
        sys.exit(1)


if __name__ == "__main__":
    main()
