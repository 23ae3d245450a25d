"""The harness's own arithmetic and plumbing, on the CPU."""
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from bench import generate, peaks, run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def test_call_ms_and_p90_read_every_call():
    times = [0.1] * 18 + [0.5, 2.0]
    w = run.Window(times, [None] * 20, seconds=sum(times))
    assert run.END_TO_END["call_ms"](w, 0) == pytest.approx(1e3 * 4.3 / 20)
    # nearest rank: the 18th of 20 sorted calls
    assert run.END_TO_END["call_p90_ms"](w, 0) == pytest.approx(100.0)
    w = run.Window([0.1] * 9 + [3.0], [None] * 10, seconds=3.9)
    assert run.END_TO_END["call_p90_ms"](w, 0) == pytest.approx(100.0)
    assert run.p90([0.3, 0.1, 0.2]) == 0.3


class _Sleeper:
    """An entry whose call takes a known host time."""
    pool = [0, 1, 2]

    def __init__(self):
        self.fn = lambda i: (time.sleep(0.01 * (i + 1)), i)[1]

    def args(self, i, call):
        return (i,)


def test_closed_loop_counts_the_last_call_and_the_whole_window():
    order = run.call_order(5, 3)
    w = run.closed_loop(_Sleeper(), order, 0.2)
    assert w.seconds >= 0.2
    assert len(w.times) == len(w.calls)
    assert sum(w.times) <= w.seconds
    assert sum(w.times) == pytest.approx(w.seconds, rel=0.05)
    assert {i for i, _ in w.calls} == {0, 1, 2}


def test_slowest_calls_tell_dispatch_from_wait():
    w = run.closed_loop(_Sleeper(), iter([0, 2, 0, 1] * 50), 0.1)
    assert len(w.starts) == len(w.dispatch) == len(w.times)
    assert w.starts == sorted(w.starts)
    (j, start, dispatch, wait), = run.slowest_calls(w, k=1)
    assert w.calls[j][0] == 2           # the 30 ms sleeper
    assert start == w.starts[j]
    # the sleeper blocks while it is dispatched; its answer is ready
    assert dispatch == pytest.approx(0.03, abs=0.02)
    assert 0 <= wait < 0.005


def test_host_watch_reads_differences_over_the_window():
    import gc

    watch = run.HostWatch()
    gc.collect()
    got = watch.stop()
    assert set(got) == {"steal_s", "run_delay_s", "gc_s", "nivcsw"}
    assert got["gc_s"] > 0
    assert got["nivcsw"] >= 0
    for k in ("steal_s", "run_delay_s"):
        assert got[k] is None or got[k] >= 0
    assert watch._on_gc not in gc.callbacks


def test_compile_cache_is_the_programs_fixed_path(monkeypatch):
    import jax

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        assert run.use_compile_cache() == os.path.join(ROOT, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == os.path.join(
            ROOT, ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
    assert run.use_compile_cache() == "/elsewhere"


def test_call_order_rounds_are_seeded_permutations():
    a = run.call_order(2**33 + 1, 9)
    b = run.call_order(2**33 + 1, 9)
    first = [next(a) for _ in range(90)]
    assert first == [next(b) for _ in range(90)]
    for r in range(10):
        assert sorted(first[9 * r: 9 * r + 9]) == list(range(9))
    c = run.call_order(1, 9)
    assert [next(c) for _ in range(90)] != first


MIX = {"pool": [
    {"name": "mix3", "parts": [
        {"dist": "halfnormal", "loc": 0.0, "scale": 1.0, "frac": 0.9},
        {"dist": "const", "value": 10.0}]},
    {"name": "beta25", "parts": [{"dist": "beta", "a": 2, "b": 5}]},
]}


def _bits(xs):
    return [np.asarray(x).view(np.uint32) for x in xs]


def test_generators_are_determined_by_the_seed():
    n = 4096
    a = _bits(generate.array_pool(2**33 + 7, n, "float32", MIX["pool"]))
    b = _bits(generate.array_pool(2**33 + 7, n, "float32", MIX["pool"]))
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    # seeds that agree in their low 32 bits still differ
    c = _bits(generate.array_pool(7, n, "float32", MIX["pool"]))
    assert not np.array_equal(a[0], c[0])


def test_generated_values_follow_the_mix():
    n = 10_000
    mix3, beta = (np.asarray(x) for x in generate.array_pool(
        11, n, "float32", MIX["pool"]))
    assert np.all(mix3[9000:] == 10.0) and np.all(mix3[:9000] >= 0)
    assert np.all(mix3[:9000] != 10.0)
    assert 0 < beta.min() and beta.max() < 1
    assert abs(beta.mean() - 2 / 7) < 0.01


def test_unknown_device_is_an_error():
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks("cpu")


def test_no_tpu_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-m", "bench.run", "--workload", "median_mix9",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, capture_output=True, text=True, env=env, timeout=300)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert "metrics" not in proc.stdout


def test_every_cell_is_found_by_name():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    for w in spec["workloads"]:
        cell = run.load_cell(w["name"])
        entry = cell["mix"]["entry"]
        assert os.path.exists(os.path.join(run.BENCH, "entries",
                                           entry + ".py"))
        assert cell["cfg"]["chips"] == w["chips"]
        assert any(m["name"] == "setup_s" for m in cell["end_to_end"])
        for m in cell["per_layer"]:
            assert os.path.exists(os.path.join(run.BENCH, "metrics",
                                               m["name"] + ".py"))
    with pytest.raises(run.BenchError):
        run.load_cell("no_such_cell")
