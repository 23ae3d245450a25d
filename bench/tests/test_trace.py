"""Trace reduction: busy union, idle share, kernel attribution and the
histogram roofline arithmetic, on hand-made events and on events cut from a
recorded chip trace (``data/median_mix9_2calls.json``: two calls of the
``median_mix9`` cell on one TPU v5 lite, op names cut to 400 characters;
``data/median_mix9_x4_1call.json``: one call of ``median_mix9_x4`` on four,
the window that call's span, names of ops other than kernels cut to
200)."""
import importlib.util
import os

import pytest

from bench import peaks, run, trace
from bench.trace import Event, Trace

HERE = os.path.dirname(os.path.abspath(__file__))
METRICS = os.path.join(os.path.dirname(HERE), "metrics")
V5E = peaks.PEAKS["TPU v5 lite"]

# Op events as the chip names them: the op's HLO text
HIST = ('%cp_histogram_batched.7 = s32[1,4096,1,256]{3,2,1,0} custom-call('
        'f32[1,2,130]{2,1,0} %p, f32[1,2097152,128]{2,1,0} %b), '
        'custom_call_target="tpu_custom_call", operand_layout_constraints='
        '{f32[1,2,130]{2,1,0}, f32[1,2097152,128]{2,1,0}}, '
        'frontend_attributes={kernel_metadata={}}')
FG = ('%cp_partials.3 = f32[8,1,128]{2,1,0} custom-call(f32[1]{0} %e, '
      'bf16[4096,128]{1,0} %f), custom_call_target="tpu_custom_call", '
      'operand_layout_constraints={f32[1]{0}, bf16[4096,128]{1,0}}, '
      'metadata={op_name="x"}')
WHILE = '%while.3 = (s32[], f32[8]{0}) while((s32[], f32[8]{0}) %t), body=%b'
FUSION = '%fusion.2 = f32[8]{0} fusion(f32[8]{0} %all-reduce.1), kind=kLoop'
ALLREDUCE = '%all-reduce.1 = f32[130]{0} all-reduce(f32[130]{0} %a)'
# a psum as the chip names it: a tiled layout puts parentheses in the shape
PSUM = ('%psum.49 = s32[]{:T(128)} all-reduce(s32[]{:T(128)} %copy.63), '
        'channel_id=1, replica_groups={{0,1,2,3}}, to_apply=%region_4.5')


def metric(name):
    spec = importlib.util.spec_from_file_location(
        "m_" + name.replace(".", "_"), os.path.join(METRICS, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def ctx(t, lo, hi, calls=2):
    return run.TraceContext(t, lo, hi, calls, [], None, V5E)


def _hand_trace():
    """Two chips over a 100 ns window [0, 100]; chip 0 is busy 60 ns (a
    while holds two ops, one op spills over the window's end), chip 1 is
    busy 40 ns."""
    chip0 = [Event(WHILE, 0, 30), Event(FUSION, 2, 10),
             Event(FUSION, 14, 12), Event(HIST, 40, 20),
             Event(ALLREDUCE, 90, 30)]
    chip1 = [Event(HIST, 0, 30), Event(ALLREDUCE, 50, 10)]
    spans = [Event("bench.window", 0, 100), Event("bench.call", 0, 45),
             Event("bench.wait", 5, 40), Event("bench.call", 46, 54),
             Event("bench.dispatch", 46, 2)]
    return Trace({"/device:TPU:0": chip0, "/device:TPU:1": chip1}, spans)


def test_union_merges_overlaps_and_clips_to_the_window():
    assert trace.union_ns([(0, 20), (10, 30), (40, 60)]) == 50
    assert trace.union_ns([(0, 100), (10, 20)]) == 100
    assert trace.union_ns([]) == 0
    t = _hand_trace()
    lo, hi = trace.window(t)
    assert (lo, hi) == (0, 100)
    # chip 0: [0,30] + [40,60] + [90,100] = 60; chip 1: 30 + 10 = 40
    assert trace.busy_ns(t, lo, hi) == pytest.approx(50)
    assert metric("device.idle_pct").read(ctx(t, lo, hi)) == pytest.approx(
        50.0)


def test_self_time_counts_nested_ops_once():
    t = _hand_trace()
    got = {trace.op_name(e): ns
           for e, ns in trace.self_times(t.ops["/device:TPU:0"])}
    assert got["while.3"] == 30 - 10 - 12
    assert trace.op_kind(Event(HIST, 0, 1)) == "pallas"
    assert trace.op_kind(Event(ALLREDUCE, 0, 1)) == "collective"
    assert trace.op_kind(Event(PSUM, 0, 1)) == "collective"
    # an operand named after a collective does not make a fusion one
    assert trace.op_kind(Event(FUSION, 0, 1)) == "xla"


def test_time_by_kind_attributes_kernels_collectives_and_the_rest():
    t = _hand_trace()
    k = trace.time_by_kind(t, 0, 100)
    # per-chip averages of self time of the ops starting in the window:
    # pallas (20 + 30) / 2, collective (30 + 10) / 2, xla (8 + 10 + 12) / 2
    assert k == {"pallas": 25.0, "collective": 20.0, "xla": 15.0}
    c = ctx(t, 0, 100)
    assert metric("engine.xla_ms_per_call").read(c) == pytest.approx(
        15e-6 / 2)
    assert metric("collective.ms_per_call").read(c) == pytest.approx(
        20e-6 / 2)
    one_chip = Trace({"/device:TPU:0": t.ops["/device:TPU:0"][:4]}, t.spans)
    assert metric("collective.ms_per_call").read(ctx(one_chip, 0, 100)) \
        is None


def test_operand_bytes_read_from_the_kernel_event():
    assert trace.operand_bytes(Event(HIST, 0, 1)) == 4 * (260 + (1 << 28))
    assert trace.operand_bytes(Event(FG, 0, 1)) == 4 + 2 * 4096 * 128
    assert trace.operand_bytes(Event(FUSION, 0, 1)) is None


def test_hist_roofline_is_bytes_over_bandwidth_over_kernel_time():
    t = Trace({"/device:TPU:0": [
        Event(HIST, 0, 2e6), Event(HIST, 3e6, 2e6),
        Event(FG, 5.2e6, 1e5),           # a kernel, but no histogram
        Event(FUSION, 5.4e6, 1e6)]}, [Event("bench.window", 0, 7e6)])
    read = 2 * 4 * (260 + (1 << 28))
    assert metric("hist_roofline").read(ctx(t, 0, 7e6)) == pytest.approx(
        100 * read / 819e9 / 4e-3)
    empty = Trace({"/device:TPU:0": [Event(FUSION, 0, 1)]}, t.spans)
    assert metric("hist_roofline").read(ctx(empty, 0, 7e6)) is None


def test_idle_gaps_name_what_the_host_was_doing():
    t = _hand_trace()
    gaps = trace.idle_gaps(t, 0, 100)
    # chip 0 is idle in [60,90] (midpoint 75: the second call) and [30,40]
    # (the host waiting)
    assert gaps[0] == ["bench.call", pytest.approx(30e-9)]
    assert gaps[1] == ["bench.wait", pytest.approx(10e-9)]
    (top,) = trace.top_ops(t, 0, 100, k=1)
    assert top[0].startswith("cp_histogram_batched.7 = s32[1,4096,1,256] "
                             "custom-call(f32[1,2,130] %p")


def test_save_and_load_keep_every_event(tmp_path):
    t = _hand_trace()
    trace.save(t, tmp_path / "t.json")
    assert trace.load_json(tmp_path / "t.json") == t


# ---------------------------------------------------------------------------
# The recorded chip trace
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def recorded():
    t = trace.load_json(os.path.join(HERE, "data", "median_mix9_2calls.json"))
    lo, hi = trace.window(t)
    return t, lo, hi


def test_recorded_busy_union_against_a_brute_force_sweep(recorded):
    t, lo, hi = recorded
    (evs,) = t.ops.values()
    edges = sorted({lo, hi} | {min(max(x, lo), hi) for e in evs
                               for x in (e.start, e.end)})
    busy = sum(b - a for a, b in zip(edges, edges[1:])
               if any(e.start <= a and b <= e.end for e in evs))
    assert trace.busy_ns(t, lo, hi) == pytest.approx(busy)
    idle = metric("device.idle_pct").read(ctx(t, lo, hi))
    assert idle == pytest.approx(100 * (1 - busy / (hi - lo)))
    assert 0 < idle < 5


def test_recorded_self_times_add_up_to_busy_time(recorded):
    t, lo, hi = recorded
    k = trace.time_by_kind(t, lo, hi)
    assert k["collective"] == 0
    # the ops of the two calls leave no overlap on one chip's op line
    assert k["pallas"] + k["xla"] == pytest.approx(
        trace.busy_ns(t, lo, hi), rel=2e-3)


def test_recorded_histogram_kernels_and_their_roofline(recorded):
    t, lo, hi = recorded
    hist = [e for e in trace.started_in(t, lo, hi)
            if "histogram" in trace.op_name(e)]
    # two calls of one to two sweeps each, each sweep reading the 1 GiB
    # array once (plus its slot bounds)
    assert 2 <= len(hist) <= 4
    assert {trace.operand_bytes(e) for e in hist} == {4 * (260 + (1 << 28))}
    want = (100 * len(hist) * 4 * (260 + (1 << 28)) / 819e9
            / (sum(e.dur for e in hist) * 1e-9))
    got = metric("hist_roofline").read(ctx(t, lo, hi))
    assert got == pytest.approx(want)
    assert 0 < got < 100


def test_recorded_four_chip_collectives():
    """Every exchange of a sharded median call, the psum rounds included,
    is attributed to the collectives, on each of the four chips."""
    t = trace.load_json(os.path.join(HERE, "data",
                                     "median_mix9_x4_1call.json"))
    lo, hi = trace.window(t)
    assert len(t.ops) == 4
    named = ("psum.", "pmax.", "pmin.", "all-reduce.", "all-gather.")
    want = 0.0
    for evs in t.ops.values():
        coll = [e for e in evs if trace.op_kind(e) == "collective"]
        assert sorted(map(trace.op_name, coll)) == sorted(
            trace.op_name(e) for e in evs
            if trace.op_name(e).startswith(named))
        # psum, pmax and pmin rounds, one all-reduce and the survivors'
        # all-gather
        assert len(coll) == 13
        assert any(" s32[130]" in e.name for e in coll)
        want += sum(e.dur for e in coll) / 4
    k = trace.time_by_kind(t, lo, hi)
    assert k["collective"] == pytest.approx(want)
    assert k["pallas"] + k["xla"] + k["collective"] == pytest.approx(
        trace.busy_ns(t, lo, hi), rel=2e-3)
    ms = metric("collective.ms_per_call").read(ctx(t, lo, hi, calls=1))
    assert ms == pytest.approx(want * 1e-6)
    assert 0.01 < ms < 1

