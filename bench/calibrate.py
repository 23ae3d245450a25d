"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 -m bench.calibrate --workload <cell> --seconds <s> \\
        --seeds 11,12,... --control-seeds 21,22,23

In one process that holds the cell's chips, runs the cell once per seed as
the benchmark does (pool, warm-up, window, check), then once per control
seed with the entry's control in the program's place, and prints one JSON
line per run with every number compared.  The last line gives, for each
number, the lower reading (the largest over the program's seeds) and the
upper reading (the smallest over the control's).  The benchmark's own runs
never serve the control.
"""
from __future__ import annotations

import argparse
import json
import os
import sys


def main(argv=None) -> int:
    from bench import run

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(run.ROOT, "src"))
    cell = run.load_cell(args.workload)
    seeds = lambda s: [int(v) for v in s.split(",") if v]
    lower, upper = {}, {}
    for kind, plan in (("program", seeds(args.seeds)),
                       ("control", seeds(args.control_seeds))):
        for seed in plan:
            try:
                out = run.run(cell, seed, args.seconds, False,
                              control=kind == "control", log=lambda *a: None)
            except Exception as e:  # a control that crashes has failed
                print(json.dumps({"kind": kind, "seed": seed,
                                  "error": f"{type(e).__name__}: {e}"[:500]}),
                      flush=True)
                continue
            vals = {k: c["value"] for k, c in out["checks"].items()}
            print(json.dumps({"kind": kind, "seed": seed,
                              "correct": out["correct"],
                              "attempted": out["attempted"],
                              "failed": out["failed"], "numbers": vals,
                              "metrics": out["metrics"]}), flush=True)
            for k, v in vals.items():
                if kind == "program":
                    lower[k] = max(lower.get(k, v), v)
                else:
                    upper[k] = min(upper.get(k, v), v)
    print(json.dumps({"lower": lower, "upper": upper}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
