"""The ``pctl_k64`` cell's per-layer metrics (``bench/metrics/multik.*``)
on small contexts: hand-made traces and results, and the recorded cut of a
chip trace of ``median_mix9_x4`` (one call on four TPU v5 lite chips,
whose sharded sweeps run the ``histogram_multi`` kernel at K = 1)."""
import importlib.util
import json
import os
import sys
from typing import NamedTuple

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import run, scopes, trace  # noqa: E402

DATA = os.path.join(ROOT, "bench", "tests", "data")


def metric(name):
    spec = importlib.util.spec_from_file_location(
        "m_" + name.replace(".", "_"),
        os.path.join(ROOT, "bench", "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def kernel_event(name, k, nslots, rows, start, dur):
    """A Pallas histogram event as a v5e names it: its HLO text."""
    return trace.Event(
        f"%{name} = s32[161,{k},256]{{2,1,0:T(8,128)S(1)}} custom-call("
        f"f32[2,{k},{nslots}]{{2,1,0:T(8,128)}} %pad_maximum_fusion.2, "
        f"f32[{rows},128]{{1,0:T(8,128)S(1)}} %pad_bitcast_fusion.19), "
        f'custom_call_target="tpu_custom_call", operand_layout_constraints='
        f"{{f32[2,{k},{nslots}]{{2,1,0}}, f32[{rows},128]{{1,0}}}}, "
        f"frontend_attributes={{kernel_metadata={{}}}}", start, dur)


def hand_trace():
    ops = [kernel_event("histogram_multi.7", 64, 130, 82432, 10, 80e6),
           kernel_event("histogram_multi.7", 64, 130, 82432, 90e6, 70e6),
           # another kernel, an xla op, and a sweep after the window
           kernel_event("histogram_batched.3", 1, 130, 82432, 170e6, 5e6),
           trace.Event("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), "
                       "kind=kLoop", 180e6, 1e6),
           kernel_event("histogram_multi.7", 64, 130, 82432, 400e6, 70e6)]
    return trace.Trace({"/device:TPU:0": ops},
                       [trace.Event("bench.window", 0, 300e6)])


def ctx_of(t, calls=2, outputs=(), entry=None):
    lo, hi = trace.window(t)
    return run.TraceContext(t, lo, hi, calls, list(outputs), entry, None)


def test_kernel_time_per_call_counts_the_multi_kernel_in_the_window():
    got = metric("multik.kernel_ms_per_call").read(ctx_of(hand_trace()))
    assert got == pytest.approx((80 + 70) / 2)


def test_kernel_rate_is_work_from_the_shapes_over_kernel_time():
    got = metric("multik.kernel_gslots_per_s").read(ctx_of(hand_trace()))
    work = 64 * 130 * 82432 * 128  # K x nslots x the padded tile grid
    assert got == pytest.approx(2 * work / 150e6)


def test_kernel_metrics_read_nothing_without_the_kernel():
    t = trace.Trace({"/device:TPU:0": hand_trace().ops["/device:TPU:0"][2:4]},
                    [trace.Event("bench.window", 0, 300e6)])
    for name in ("multik.kernel_ms_per_call", "multik.kernel_gslots_per_s"):
        assert metric(name).read(ctx_of(t)) is None, name
        assert metric(name).read(ctx_of(trace.Trace({}, t.spans))) is None


def test_recorded_sharded_sweeps_at_k1():
    t = trace.load_json(os.path.join(DATA, "median_mix9_x4_scoped_1call.json"))
    ctx = ctx_of(t, calls=1)
    events = [e for e in trace.started_in(t, ctx.lo, ctx.hi)
              if trace.op_name(e).startswith("histogram_multi")]
    assert events
    ms = metric("multik.kernel_ms_per_call").read(ctx)
    assert ms == pytest.approx(sum(e.dur for e in events) * 1e-6 / 4)
    # 130 slots x 2^28 elements a sweep at about 28.7 ms: ~1.2e12 a second
    rate = metric("multik.kernel_gslots_per_s").read(ctx)
    assert rate == pytest.approx(130 * 2**28 * len(events)
                                 / sum(e.dur for e in events))
    assert 1000 < rate < 1500


class _Out(NamedTuple):
    iters: object


def test_live_brackets_are_open_ranks_over_the_sweeps_run():
    read = metric("multik.live_bracket_pct").read
    outs = [(0, _Out(np.int32([1, 2, 2, 4]))), (1, _Out(np.int32([1, 1])))]
    # (1 + 2 + 2 + 4) + (1 + 1) live of 4 x 4 + 2 x 1 evaluated
    assert read(ctx_of(hand_trace(), outputs=outs)) == pytest.approx(
        100 * 11 / 18)
    assert read(ctx_of(hand_trace(), outputs=outs[1:])) == 100.0
    assert read(ctx_of(hand_trace(), outputs=[])) is None
    assert read(ctx_of(hand_trace(), outputs=[
        (0, _Out(np.int32([0, 0])))])) is None


class _Entry:
    ranks = np.arange(1, 65)
    fn = None

    def args(self, i, call):
        return ()


def test_finalize_per_rank_sums_the_finalize_phases(monkeypatch):
    smap = {"fusion.1": "sel.compact", "fusion.2": "sel.probe",
            "sort.3": "sel.sort", "fusion.4": "sel.sweep",
            "fusion.5": "sel.seed"}
    ev = lambda name, start, dur: trace.Event(
        f"%{name} = f32[8]{{0}} fusion(f32[8]{{0}} %p), kind=kLoop",
        start, dur)
    t = trace.Trace({"/device:TPU:0": [
        ev("fusion.1", 0, 6e6), ev("fusion.2", 10e6, 2e6),
        ev("sort.3", 20e6, 0.8e6), ev("fusion.4", 30e6, 9e6),
        ev("fusion.5", 40e6, 1e6), ev("fusion.1", 50e6, 6e6)]},
        [trace.Event("bench.window", 0, 100e6)])
    monkeypatch.setattr(scopes, "entry_map", lambda entry: smap)
    read = metric("multik.finalize_ms_per_rank").read
    # (12 + 2 + 0.8) ms over 2 calls, over 64 ranks
    assert read(ctx_of(t, entry=_Entry())) == pytest.approx(14.8 / 2 / 64)
    # a program that names no phase reads nothing
    monkeypatch.setattr(scopes, "entry_map",
                        lambda entry: dict.fromkeys(smap, scopes.UNSCOPED))
    assert read(ctx_of(t, entry=_Entry())) is None


def test_recorded_finalize_per_rank_at_k1(monkeypatch):
    """On the recorded median, one rank: the finalize metrics' sum."""
    t = trace.load_json(os.path.join(DATA, "median_mix9_scoped_2calls.json"))
    with open(os.path.join(DATA, "median_mix9_scoped_scopes.json")) as f:
        smap = json.load(f)
    monkeypatch.setattr(scopes, "entry_map", lambda entry: smap)
    calls = sum(1 for s in t.spans if s.name == "bench.call")

    class One(_Entry):
        ranks = np.array([1])

    ctx = ctx_of(t, calls=calls, entry=One())
    got = metric("multik.finalize_ms_per_rank").read(ctx)
    want = sum(metric(f"finalize.{m}_ms_per_call").read(ctx)
               for m in ("compact", "probe", "sort"))
    assert got == pytest.approx(want)
    assert got > 60  # the n-long survivor cumsum of 2^28 alone is 49 ms
