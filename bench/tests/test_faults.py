"""Whole runs of each cell on the CPU, less the look for a chip, with the
timed path broken underneath: every fault the cell can have turns
``correct`` false, and so does the cell's control; a sound run is
correct."""
import json
import os
import subprocess
import sys

import jax.numpy as jnp
import pytest

from helpers import run_small

HERE = os.path.dirname(os.path.abspath(__file__))


def _altered(fn):
    """The answer altered where it is produced: one ulp up."""
    def f(*a, **kw):
        r = fn(*a, **kw)
        return r._replace(value=jnp.nextafter(r.value, jnp.inf))
    return f


def _half(fn):
    """Half of the data left out."""
    return lambda x, *a, **kw: fn(x[: x.size // 2], *a, **kw)


def _unchanged_loop(ev, **kw):
    """A bracket loop whose step returns its state unchanged: the seed
    state, never narrowed."""
    from repro.core import selection

    s0, xmin, xmax, _, _ = selection._seed_state(ev, None, None)
    return s0, xmin, xmax


@pytest.fixture(autouse=True)
def fresh_programs():
    """Patches reach into jitted functions: trace them anew."""
    import jax

    jax.clear_caches()
    yield
    jax.clear_caches()


@pytest.mark.parametrize("fault", [None, "answer_altered", "half_left_out",
                                   "step_unchanged"])
def test_median_cell_faults(monkeypatch, fault):
    from repro.core import selection

    if fault == "answer_altered":
        monkeypatch.setattr(selection, "median", _altered(selection.median))
    elif fault == "half_left_out":
        monkeypatch.setattr(selection, "median", _half(selection.median))
    elif fault == "step_unchanged":
        monkeypatch.setattr(selection, "binned_loop_batched",
                            _unchanged_loop)
    out = run_small("median_mix9")
    assert out["correct"] is (fault is None), out["checks"]
    assert (out["failed"] == 0) is (fault is None)
    assert list(out)[-1] == "checks"


def test_median_cell_control_fails():
    out = run_small("median_mix9", control=True)
    assert out["correct"] is False
    assert out["checks"]["wrong_answers"]["value"] > 0


def test_sharded_cell_faults():
    """On four host devices, in a process of its own: the exchange between
    chips left out, the answer altered, half the data left out."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable,
                           os.path.join(HERE, "_sharded_faults.py")],
                          capture_output=True, text=True, env=env,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    assert got == {"sound": True, "exchange_left_out": False,
                   "answer_altered": False, "half_left_out": False,
                   "control": False}
