"""Every op of the ``pctl_k64`` program runs inside one engine phase.

The benchmark buckets device time by the ``sel.*`` scope of each op's
instruction (``bench/scopes.py``); an op left ``unscoped`` is time no phase
metric sees.  The program is ``selection.quantiles`` at K = 64 (``q =
j / 63``) of the cell's 10.5M-element column, a ragged n, compiled here
for a described TPU v5e, the program the chip runs.  Nothing runs.  The
ops checked are those of the entry computation and of its loops' bodies
and conditions; parameters, constants (and their copies), tuples, their
elements and bitcasts move no data and are not ops on the chip (a recorded
trace of ``median_mix9_x4`` holds none of them).
"""
import os
import re
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import scopes  # noqa: E402

N = 10_500_000
QS = np.arange(64) / 63
NO_DATA = ("parameter", "constant", "tuple", "get-tuple-element", "bitcast")

_COMP = re.compile(r"^(ENTRY )?%([\w.\-]+) .*\{\s*$")
_INSTR = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = (.*)$")
_LOOP = re.compile(r"\b(?:body|condition)=%([\w.\-]+)")


def _opcode(rest):
    """The opcode of an instruction's text after ``=``: the shape (a tuple
    shape in parentheses, or one word), then ``opcode(``."""
    i = 0
    if rest.startswith("("):
        depth = 0
        for i, c in enumerate(rest):
            depth += {"(": 1, ")": -1}.get(c, 0)
            if depth == 0:
                break
    m = re.match(r"\S*\s+([\w\-]+)\(", rest[i:])
    return m.group(1) if m else None


def run_ops(text):
    """``{instruction: (computation, text)}`` of the ops that run: those
    of the entry computation and, transitively, of its loops' bodies and
    conditions, less the instructions that move no data."""
    comps, entry, comp = {}, None, None
    for line in text.splitlines():
        m = _COMP.match(line)
        if m:
            comp = m.group(2)
            comps[comp] = []
            entry = comp if m.group(1) else entry
            continue
        m = _INSTR.match(line)
        if m and comp:
            comps[comp].append(m.groups())
    todo, seen = [entry], set()
    while todo:
        c = todo.pop()
        if c in seen:
            continue
        seen.add(c)
        for _, rest in comps[c]:
            todo += _LOOP.findall(rest)
    codes = {n: _opcode(r) for c in seen for n, r in comps[c]}
    const = {n for n, op in codes.items() if op == "constant"}
    out = {}
    for c in seen:
        for n, rest in comps[c]:
            op = codes[n]
            args = re.findall(r"%([\w.\-]+)", rest.split(", metadata")[0])
            if op in NO_DATA or (op == "copy" and set(args) <= const):
                continue
            out[n] = (c, rest)
    return out


@pytest.fixture(scope="module")
def v5e_text():
    """The optimized HLO of the cell's program, compiled for one v5e."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    from repro.core import selection
    from repro.kernels import ops

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep the cache out of it, and
    # the metadata that the cache's key leaves out in the program
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    on_tpu = ops._on_tpu
    ops._on_tpu = lambda: True  # the engine takes its chip branch
    try:
        x = jax.ShapeDtypeStruct((N,), jnp.float32,
                                 sharding=SingleDeviceSharding(
                                     topo.devices[0]))
        yield jax.jit(lambda v: selection.quantiles(v, QS)).lower(
            x).compile().as_text()
    finally:
        ops._on_tpu = on_tpu
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
        jax.clear_caches()  # no trace of the chip branch outlives the test


def test_every_op_of_the_k64_program_has_a_phase(v5e_text):
    smap = scopes.scope_map(v5e_text)
    ops = run_ops(v5e_text)
    assert len(ops) > 100
    unscoped = sorted(n for n in ops if smap[n] == scopes.UNSCOPED)
    assert not unscoped, [ops[n][1][:160] for n in unscoped]
    assert set(smap[n] for n in ops) == set(scopes.SCOPES)


def test_the_ragged_sweep_and_the_finalize_map_are_named(v5e_text):
    smap = scopes.scope_map(v5e_text)
    ops = run_ops(v5e_text)
    pad = 161 * 512  # rows of the 161 whole tiles of 512 x 128
    # the kernel, and the pad of x to whole tiles, in every sweep
    kernel = [n for n, (_, r) in ops.items() if "tpu_custom_call" in r]
    assert kernel and all(smap[n] == "sel.sweep" for n in kernel)
    assert any(f"f32[{pad},128]" in r for n, (_, r) in ops.items()
               if n in kernel)
    padded = [n for n, (_, r) in ops.items()
              if r.startswith(f"f32[{pad},128]") and n not in kernel]
    assert padded and all(smap[n] == "sel.sweep" for n in padded)
    # the (nblocks, K, width) block sum of the kernel's partials
    assert all(ops[n][1].startswith("s32[161,64,256]") for n in kernel)
    blocks = [n for n, (_, r) in ops.items()
              if set(kernel) & set(re.findall(r"%([\w.\-]+)", r))]
    assert blocks and all(smap[n] == "sel.sweep" for n in blocks)
    # the finalize's map over the 64 brackets: its loop, the stacking of
    # its (64, 4096) survivor buffers and its counter
    stacks = [n for n, (_, r) in ops.items()
              if _opcode(r) == "dynamic-update-slice"
              and r.startswith("f32[64,4096]")]
    assert stacks and all(smap[n] == "sel.compact" for n in stacks)
    loop = {ops[n][0] for n in stacks}
    body_ops = [n for n, (c, _) in ops.items() if c in loop]
    assert all(smap[n] == "sel.compact" for n in body_ops)
