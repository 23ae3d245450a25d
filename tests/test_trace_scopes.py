"""The engine's phases are named inside the compiled program.

Every phase runs under one ``jax.named_scope`` (``sel.seed``,
``sel.sweep``, ``sel.compact``, ``sel.probe``, ``sel.sort``), the same on
the local and the sharded path.  The scopes reach the compiled text only
as ``op_name`` metadata: each must appear there, and no instruction may sit
in two of them (the scopes never nest).  The sharded median compiles on
four virtual CPU devices in a subprocess, so that this process keeps its
one device.  Every program compiles afresh: the persistent compilation
cache leaves metadata out of its key, so a hit could return the same
program compiled without the scopes.
"""
import json
import os
import re
import subprocess
import sys

import pytest

import jax
import jax.numpy as jnp

from repro.core import selection

jax.config.update("jax_platform_name", "cpu")

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCOPES = ("sel.seed", "sel.sweep", "sel.compact", "sel.probe", "sel.sort")
_SCOPE = re.compile(r"\bsel\.(?:seed|sweep|compact|probe|sort)\b")
N = 1 << 16


def op_names(text):
    return re.findall(r'op_name="([^"]*)"', text)


def check_phases(names):
    found = {s for n in names for s in _SCOPE.findall(n)}
    assert found == set(SCOPES), sorted(found)
    nested = [n for n in names if len(_SCOPE.findall(n)) > 1]
    assert not nested, nested[:5]


_PROGRAMS = {
    "median": lambda x, w: selection.median(x, method="binned"),
    "quantiles": lambda x, w: selection.quantiles(x, [0.1, 0.5, 0.9],
                                                  method="binned"),
    "weighted_median": lambda x, w: selection.weighted_median(
        x, w, method="binned"),
}


@pytest.fixture
def no_compile_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.mark.parametrize("program", sorted(_PROGRAMS))
def test_local_program_names_every_phase_once(program, no_compile_cache):
    x = jax.ShapeDtypeStruct((N,), jnp.float32)
    text = jax.jit(_PROGRAMS[program]).lower(x, x).compile().as_text()
    check_phases(op_names(text))


def test_sharded_median_names_every_phase_once():
    from _dist_env import subprocess_env

    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tests",
                                      "_dist_scopes_worker.py"), "4"],
        capture_output=True, text=True, env=subprocess_env(ROOT),
        timeout=600)
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    check_phases(json.loads(out.stdout.strip().splitlines()[-1]))
