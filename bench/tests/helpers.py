"""Cells of BENCHMARK.json cut to sizes a CPU test holds."""
from bench import run

SMALL = {
    "median_mix9": {"n": 1 << 17},
    "median_mix9_x4": {"n": 1 << 18},
}
SEED = 2**33 + 12345  # past 32 bits: a seed may be any whole number


def small_cell(name):
    cell = run.load_cell(name)
    cell["cfg"].update(SMALL[name])
    return cell


def run_small(name, seed=SEED, seconds=0.3, **kw):
    """A whole run of the cut cell on the CPU, less the look for a chip."""
    return run.run(small_cell(name), seed, seconds, False, require_tpu=False,
                   log=lambda *a: None, **kw)
