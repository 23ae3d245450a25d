"""Device time by engine phase (``bench/scopes.py``): the scope rule on
hand-written HLO, the phase counters on hand-made results, and the
buckets on a recorded cut of a chip trace of the scoped program
(``data/median_mix9_scoped_2calls.json``: two calls of ``median_mix9`` on
one TPU v5 lite; ``data/median_mix9_x4_scoped_1call.json``: one call of
``median_mix9_x4`` on four; names of ops other than kernels cut to 200
characters.  Beside each, ``*_scoped_scopes.json``: the phase of each
instruction in it, from the compiled text of the program that ran)."""
import importlib.util
import json
import os
from typing import NamedTuple

import numpy as np
import pytest

from bench import peaks, run, scopes, trace
from bench.tests import helpers

HERE = os.path.dirname(os.path.abspath(__file__))
METRICS = os.path.join(os.path.dirname(HERE), "metrics")
DATA = os.path.join(HERE, "data")
SCOPE_METRICS = {"finalize.compact_ms_per_call": "sel.compact",
                 "finalize.probe_ms_per_call": "sel.probe",
                 "finalize.sort_ms_per_call": "sel.sort",
                 "engine.seed_ms_per_call": "sel.seed"}


def metric(name):
    spec = importlib.util.spec_from_file_location(
        "m_" + name.replace(".", "_"), os.path.join(METRICS, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# The survivor cumsum as XLA emits it: the reduce-window and its relayout
# copy carry no scope path, and sit between two sel.compact instructions.
CUMSUM = """\
HloModule jit_median

%region_4.10 (a: s32[], b: s32[]) -> s32[] {
  %a = s32[] parameter(0)
  %b = s32[] parameter(1)
  ROOT %add.3 = s32[] add(s32[] %a, s32[] %b)
}

ENTRY %main.5 (x.1: f32[1024]) -> s32[8] {
  %x.1 = f32[1024]{0} parameter(0)
  %constant.58 = s32[] constant(0)
  %while.38 = (f32[], f32[]) while(f32[1024]{0} %x.1), condition=%c, body=%b, metadata={op_name="jit(median)/sel.sweep/while"}
  %get-tuple-element.1 = f32[] get-tuple-element((f32[], f32[]) %while.38), index=0, metadata={op_name="jit(median)/sel.sweep/while"}
  %convert_bitcast_fusion = s32[1024]{0} fusion(f32[1024]{0} %x.1, f32[] %get-tuple-element.1), kind=kLoop, calls=%fused_computation.34, metadata={op_name="jit(median)/shard_map/reshape.15"}
  %copy.8 = s32[1024]{0} copy(s32[1024]{0} %convert_bitcast_fusion), metadata={op_name="jit(median)/vmap(sel.compact)/convert_element_type"}
  %reduce-window.2 = s32[1024]{0} reduce-window(s32[1024]{0} %copy.8, s32[] %constant.58), window={size=1024 pad=1023_0}, to_apply=%region_4.10
  %copy.9 = s32[1024]{0} copy(s32[1024]{0} %reduce-window.2)
  %reduce_window_sum.20 = s32[1024]{0} add(s32[1024]{0} %copy.9, s32[1024]{0} %copy.9), metadata={op_name="reduce_window_sum" stack_frame_id=91}
  %fusion.24 = s32[8]{0} fusion(s32[1024]{0} %reduce_window_sum.20), kind=kCustom, calls=%fused_computation, metadata={op_name="jit(median)/vmap(sel.compact)/jit(searchsorted)/while/body/gather"}
  %iota.3 = s32[8]{0} iota(), iota_dimension=0
  ROOT %sort.7 = s32[8]{0} sort(s32[8]{0} %fusion.24), dimensions={0}, metadata={op_name="jit(median)/sel.sort/jit(sort)/sort"}
}
"""


def test_scope_is_the_innermost_sel_component():
    assert scopes.scope_of("jit(f)/vmap(sel.compact)/gather") == "sel.compact"
    assert scopes.scope_of("jit(f)/sel.sweep/while/body/jit(cp)/x") == (
        "sel.sweep")
    assert scopes.scope_of("jit(f)/sel.sweep/while/body/sel.probe/y") == (
        "sel.probe")
    assert scopes.scope_of("reduce_window_sum") is None
    assert scopes.scope_of("jit(f)/sel.sweeps/x") is None


def test_unscoped_cumsum_takes_the_phase_around_it():
    smap = scopes.scope_map(CUMSUM)
    # own scopes
    assert smap["while.38"] == "sel.sweep"
    assert smap["copy.8"] == "sel.compact"
    assert smap["fusion.24"] == "sel.compact"
    assert smap["sort.7"] == "sel.sort"
    # the cumsum chain between two sel.compact instructions
    for name in ("reduce-window.2", "copy.9", "reduce_window_sum.20"):
        assert smap[name] == "sel.compact", name
    # the survivor mask reads the loop's result and x; its user decides
    assert smap["convert_bitcast_fusion"] == "sel.compact"
    # a constant takes its user's phase; the data feeds two phases, and a
    # dead iota has no neighbour at all
    assert smap["constant.58"] == "sel.compact"
    for name in ("x.1", "iota.3"):
        assert smap[name] == scopes.UNSCOPED, name
    # instructions of other computations are mapped too, and computations
    # are never taken for operands
    assert scopes.parse_hlo(CUMSUM)["reduce-window.2"][1] == [
        "copy.8", "constant.58"]


SPLIT = """\
ENTRY %main.2 (x.1: s32[64]) -> (s32[64], s32[64]) {
  %x.1 = s32[64]{0} parameter(0)
  %copy.8 = s32[64]{0} copy(s32[64]{0} %x.1), metadata={op_name="jit(f)/sel.compact/convert_element_type"}
  %copy.9 = s32[64]{0} copy(s32[64]{0} %copy.8)
  %negate.1 = s32[64]{0} negate(s32[64]{0} %copy.9), metadata={op_name="jit(f)/sel.compact/neg"}
  %negate.2 = s32[64]{0} negate(s32[64]{0} %copy.9), metadata={op_name="jit(f)/sel.probe/neg"}
  ROOT %tuple.1 = (s32[64], s32[64]) tuple(s32[64]{0} %negate.1, s32[64]{0} %negate.2)
}
"""


def test_producers_decide_where_users_disagree():
    smap = scopes.scope_map(SPLIT)
    # copy.9 feeds sel.compact and sel.probe: its producer decides
    assert smap["copy.9"] == "sel.compact"
    assert smap["tuple.1"] == scopes.UNSCOPED


class _Out(NamedTuple):
    value: object
    status: object
    n_in: object


def test_phase_counters_read_the_returned_results():
    ctx = run.TraceContext(None, 0, 1, 3, [
        (0, _Out(np.float32(1), np.int32(1), np.int32(100))),
        (1, _Out(np.float32(1), np.int32(0), np.int32(4))),
        (2, _Out(np.float32(1), np.int32(2), np.int32(7)))], None, None)
    assert metric("finalize.survivors_per_call").read(ctx) == pytest.approx(
        37.0)
    # HYBRID_SORT and TIE_FALLBACK needed the compaction; EXACT_HIT did not
    assert metric("finalize.useful_pct").read(ctx) == pytest.approx(
        200.0 / 3)
    empty = run.TraceContext(None, 0, 1, 0, [], None, None)
    assert metric("finalize.survivors_per_call").read(empty) is None
    assert metric("finalize.useful_pct").read(empty) is None


class _Stub:
    """An entry whose program is ``fn`` on one small array."""

    def __init__(self, fn, n=64):
        import jax
        import jax.numpy as jnp

        self.fn = jax.jit(fn)
        self._x = jnp.arange(n, dtype=jnp.float32)

    def args(self, i, call):
        return (self._x,)


def _xla_trace():
    ev = trace.Event("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop",
                     10, 50)
    return trace.Trace({"/device:TPU:0": [ev]},
                       [trace.Event("bench.window", 0, 100)])


def test_a_program_that_names_no_phase_reads_nothing():
    import jax.numpy as jnp

    ctx = run.TraceContext(_xla_trace(), 0, 100, 1, [], _Stub(jnp.sort),
                           None)
    for name in SCOPE_METRICS:
        assert metric(name).read(ctx) is None, name


def test_the_benchmarks_program_names_its_phases():
    import jax

    cell = helpers.small_cell("median_mix9")
    entry = run.build_entry(cell, helpers.SEED, jax.devices()[:1])
    smap = scopes.entry_map(entry)
    assert scopes.names_phases(smap)
    assert set(smap.values()) == set(scopes.SCOPES + (scopes.UNSCOPED,))
    assert scopes.entry_map(entry) is smap  # one compile per program
    # an op the map does not know is unscoped; a phase with no op reads 0
    ctx = run.TraceContext(_xla_trace(), 0, 100, 1, [], entry, None)
    assert metric("finalize.sort_ms_per_call").read(ctx) == 0.0


@pytest.fixture(scope="module", params=[
    ("median_mix9_scoped_2calls.json", "median_mix9_scoped_scopes.json"),
    ("median_mix9_x4_scoped_1call.json", "median_mix9_x4_scoped_scopes.json"),
], ids=["median_mix9", "median_mix9_x4"])
def recorded(request):
    cut, scope_file = request.param
    t = trace.load_json(os.path.join(DATA, cut))
    with open(os.path.join(DATA, scope_file)) as f:
        smap = json.load(f)
    lo, hi = trace.window(t)
    calls = sum(1 for s in t.spans if s.name == "bench.call")
    return t, smap, lo, hi, calls


def test_recorded_buckets_add_up_to_the_xla_time(recorded):
    t, smap, lo, hi, calls = recorded
    by = scopes.ns_by_scope(t, lo, hi, smap)
    xla = metric("engine.xla_ms_per_call").read(
        run.TraceContext(t, lo, hi, calls, [], None, None))
    total = sum(by.values()) * 1e-6 / calls
    assert total == pytest.approx(xla, rel=1e-3)
    # the scoped program leaves (almost) nothing outside its phases
    assert by[scopes.UNSCOPED] * 1e-6 / calls < 0.02 * xla
    # the survivor compaction, its n-long cumsum included, holds most of
    # the finalize on one chip and on four
    assert by["sel.compact"] > 0.9 * sum(by.values())
    # the n-long survivor cumsum, the costliest reduce-window, has no scope
    # path of its own (op_name "reduce_window_sum" or none) and lands there
    cumsum = max((e for evs in t.ops.values() for e in evs
                  if " reduce-window(" in e.name), key=lambda e: e.dur)
    assert smap[trace.op_name(cumsum)] == "sel.compact"


def test_recorded_kernels_keep_their_histogram_name(recorded):
    t, smap, lo, hi, calls = recorded
    kernels = [e for e in trace.started_in(t, lo, hi)
               if trace.op_kind(e) == "pallas"]
    assert kernels
    assert all("histogram" in trace.op_name(e) for e in kernels)
    ctx = run.TraceContext(t, lo, hi, calls, [], None,
                           peaks.PEAKS["TPU v5 lite"])
    assert 0 < metric("hist_roofline").read(ctx) < 100
