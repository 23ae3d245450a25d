"""Runs of the cut four-chip cell on four host devices: prints, as JSON,
whether each run (sound, each fault, the control) came out correct."""
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
import conftest  # noqa: E402,F401  (puts the checkout on the path)
from helpers import run_small  # noqa: E402


def main():
    from repro.core import distributed

    import test_faults as tf

    orig_psum, orig_median = distributed._psum, distributed.sharded_median
    got = {"sound": run_small("median_mix9_x4")["correct"]}
    distributed._psum = lambda v, axes: v
    got["exchange_left_out"] = run_small("median_mix9_x4")["correct"]
    distributed._psum = orig_psum
    distributed.sharded_median = tf._altered(orig_median)
    got["answer_altered"] = run_small("median_mix9_x4")["correct"]
    distributed.sharded_median = lambda x, mesh, spec, **kw: orig_median(
        x[: x.size // 2], mesh, spec, **kw)
    got["half_left_out"] = run_small("median_mix9_x4")["correct"]
    distributed.sharded_median = orig_median
    got["control"] = run_small("median_mix9_x4", control=True)["correct"]
    print(json.dumps(got))


if __name__ == "__main__":
    main()
