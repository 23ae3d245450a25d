"""Benchmark harness: time to an exact answer on the chip.

    python3 -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  The cell is an entry of ``workloads`` in
``BENCHMARK.json``; it names a configuration (``bench/configs/<name>.json``,
the deployment's sizes) and a traffic mix (``bench/traffic/<name>.json``,
the data pool and the entry it is served to, ``bench/entries/<entry>.py``).
One process, holding the cell's chips, then does four things in order:

1. Set up (``setup_s``, from the start of this module): point JAX's
   persistent compilation cache at a fixed directory, make the data pool on
   the device from ``--seed``, and warm up the cell's one program.
2. Measure: a closed loop with one caller for ``--seconds``.  Each call is
   one call of the entry on the next array of the pool, in a seeded order,
   and ends in ``block_until_ready``; results stay on the device.
   ``call_ms`` is the whole window over the calls completed in it,
   ``call_p90_ms`` the 90th percentile (nearest rank) of all its calls.
3. Check every answer of the window against the entry's plain reference.
4. Print each number compared beside its limit as the last lines of
   standard error, and one JSON object as the last line of standard output.

With ``--trace 1`` the loop runs for at most ``TRACE_SECONDS`` under the
profiler instead, the trace is reduced to the cell's per-layer metrics
(``bench/metrics/<name>.py``), and one ``jnp.sort`` of the cell's data is
timed as a baseline on an earlier line.  The run fails, printing no result,
where JAX finds no TPU, fewer chips than the cell asks for, or a chip that
``bench/peaks.py`` does not know.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import importlib.util  # noqa: E402
import itertools  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import traceback  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
TRACE_SECONDS = 10.0
WARMUP_CALLS = 2


class BenchError(RuntimeError):
    pass


# ---------------------------------------------------------------------------
# The cell, found by name
# ---------------------------------------------------------------------------


def _json(path):
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not os.path.exists(path):
        raise BenchError(f"no module at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def for_cell(metrics, cell_name):
    """The metrics of ``BENCHMARK.json`` that ``cell_name`` reports."""
    return [m for m in metrics
            if cell_name in m.get("workloads", [cell_name])]


def load_cell(name: str, root: str = ROOT) -> dict:
    """The cell's spec entry, configuration and traffic mix."""
    spec = _json(os.path.join(root, "BENCHMARK.json"))
    cells = {c["name"]: c for c in spec["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    cfg_entry = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    return {
        "cell": cell,
        "cfg": _json(os.path.join(root, cfg_entry["file"])),
        "mix": _json(os.path.join(BENCH, "traffic",
                                  cell["traffic"] + ".json")),
        "end_to_end": for_cell(spec["end_to_end"], name),
        "per_layer": for_cell(spec["per_layer"], name),
    }


# ---------------------------------------------------------------------------
# Chips and the compile cache
# ---------------------------------------------------------------------------


def use_compile_cache() -> str:
    """JAX's persistent compilation cache where ``repro.compile_cache``
    puts it (``JAX_COMPILATION_CACHE_DIR`` where it is set, else
    ``.jax_cache`` at the root of the checkout), with every program cached,
    however quickly it compiled."""
    import jax

    from repro import compile_cache

    path = compile_cache.use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def select_devices(chips: int, require_tpu: bool = True):
    """The first ``chips`` devices, and their peaks; no TPU, too few chips,
    or a chip not in the peaks table is a :class:`BenchError`."""
    import jax

    from bench import peaks

    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise BenchError(f"no TPU: JAX found {devices[0].platform} devices")
    if len(devices) < chips:
        raise BenchError(f"the cell needs {chips} chips; JAX found "
                         f"{len(devices)}")
    try:
        pk = peaks.peaks(devices[0].device_kind)
    except peaks.UnknownDevice as e:
        if require_tpu:
            raise BenchError(str(e)) from None
        pk = None
    return devices[:chips], pk


def memory_peak_bytes(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


# ---------------------------------------------------------------------------
# The closed loop
# ---------------------------------------------------------------------------


def call_order(seed: int, pool_size: int):
    """Pool indices forever: seeded permutations laid end to end, so every
    seed calls every array of the pool equally often."""
    import numpy as np

    rng = np.random.default_rng([int(seed) % (1 << 64), 1])
    while True:
        yield from rng.permutation(pool_size).tolist()


@dataclasses.dataclass
class Window:
    times: list    # seconds of each call, host clock
    calls: list    # (pool index, output) of each call
    seconds: float  # from the first dispatch to the last answer
    starts: list = dataclasses.field(default_factory=list)  # s after t0
    dispatch: list = dataclasses.field(default_factory=list)  # s in dispatch


def closed_loop(entry, order, seconds: float, annotate: bool = False
                ) -> Window:
    """Call the entry back to back until ``seconds`` have passed; the last
    call started inside the window is waited for and counted."""
    import jax

    span = (jax.profiler.TraceAnnotation if annotate
            else lambda name: contextlib.nullcontext())
    w = Window([], [], 0.0)
    t0 = time.perf_counter()
    for c in itertools.count():
        i = next(order)
        with span("bench.call"):
            t = time.perf_counter()
            with span("bench.dispatch"):
                out = entry.fn(*entry.args(i, c))
            d = time.perf_counter()
            with span("bench.wait"):
                jax.block_until_ready(out)
            end = time.perf_counter()
        w.times.append(end - t)
        w.starts.append(t - t0)
        w.dispatch.append(d - t)
        w.calls.append((i, out))
        if end - t0 >= seconds:
            w.seconds = end - t0
            return w


def slowest_calls(w: Window, k: int = 3) -> list:
    """The ``k`` slowest calls as ``(call, start s, dispatch s, wait s)``:
    where a stall lies in the window, and whether the host was stuck
    dispatching or waiting for the answer."""
    idx = sorted(range(len(w.times)), key=lambda j: -w.times[j])[:k]
    return [(j, w.starts[j], w.dispatch[j], w.times[j] - w.dispatch[j])
            for j in idx]


class HostWatch:
    """What may hold the host up during the window, as differences between
    its start and end: CPU time the hypervisor took from this machine
    (``steal_s``, from ``/proc/stat``), time the main thread waited for a
    CPU (``run_delay_s``, ``/proc/self/schedstat``), the process's
    involuntary context switches, and the garbage collector's pauses
    (``gc_s``).  A reading the machine does not offer is None."""

    def __init__(self):
        import gc

        self._gc, self._t = 0.0, None
        gc.callbacks.append(self._on_gc)
        self.start = self._read()

    def _on_gc(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self._gc += time.perf_counter() - self._t
            self._t = None

    def _read(self) -> dict:
        import resource

        out = {"steal_s": None, "run_delay_s": None, "gc_s": self._gc,
               "nivcsw": resource.getrusage(resource.RUSAGE_SELF).ru_nivcsw}
        with contextlib.suppress(OSError, ValueError, IndexError):
            with open("/proc/stat") as f:
                cpu = f.readline().split()
            out["steal_s"] = int(cpu[8]) / os.sysconf("SC_CLK_TCK")
        with contextlib.suppress(OSError, ValueError, IndexError):
            with open("/proc/self/schedstat") as f:
                out["run_delay_s"] = int(f.read().split()[1]) * 1e-9
        return out

    def stop(self) -> dict:
        import gc

        gc.callbacks.remove(self._on_gc)
        end = self._read()
        return {k: None if end[k] is None or self.start[k] is None
                else end[k] - self.start[k] for k in end}


def p90(values) -> float:
    """The 90th percentile by nearest rank: a time some call took."""
    s = sorted(values)
    return s[max(math.ceil(0.9 * len(s)) - 1, 0)]


END_TO_END = {
    "call_ms": lambda w, setup: 1e3 * w.seconds / len(w.times),
    "call_p90_ms": lambda w, setup: 1e3 * p90(w.times),
    "setup_s": lambda w, setup: setup,
}


# ---------------------------------------------------------------------------
# Tracing
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class TraceContext:
    """What a per-layer metric reads (``bench/metrics/<name>.py``)."""
    trace: object   # bench.trace.Trace
    lo: float       # the traced window on the trace's clock, ns
    hi: float
    calls: int
    outputs: list   # (pool index, output) of each call of the window
    entry: object
    peaks: dict


class _CompileCounter:
    """Counts backend compilations while it is active."""

    def __init__(self):
        import jax.monitoring

        self.n, self.active = 0, False
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if self.active and event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


def traced_window(entry, order, seconds):
    """The closed loop under the profiler; returns the window and the
    reduced trace."""
    import jax

    from bench import trace

    tdir = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        jax.profiler.start_trace(tdir)
        try:
            with jax.profiler.TraceAnnotation("bench.window"):
                w = closed_loop(entry, order, seconds, annotate=True)
        finally:
            jax.profiler.stop_trace()
        return w, trace.load_xplane(tdir)
    finally:
        shutil.rmtree(tdir, ignore_errors=True)


def time_baseline(entry):
    """Seconds of one ``jnp.sort`` of the cell's data, after one warm-up;
    None where the entry has no baseline."""
    import jax

    base = entry.baseline()
    if base is None:
        return None
    fn, args = base
    jax.block_until_ready(fn(*args))
    t = time.perf_counter()
    jax.block_until_ready(fn(*args))
    return time.perf_counter() - t


def per_layer(cell, ctx) -> dict:
    out = {}
    for m in cell["per_layer"]:
        mod = load_module(os.path.join(BENCH, "metrics", m["name"] + ".py"),
                          "bench_metric_" + m["name"].replace(".", "_"))
        v = mod.read(ctx)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------


def build_entry(cell, seed, devices):
    mix = cell["mix"]
    mod = load_module(os.path.join(BENCH, "entries", mix["entry"] + ".py"),
                      "bench_entry_" + mix["entry"])
    return mod.build(cell["cfg"], mix, seed, devices)


def run(cell: dict, seed: int, seconds: float, trace: bool,
        require_tpu: bool = True, control: bool = False, log=print) -> dict:
    """One run of ``cell`` (as :func:`load_cell` gives it); returns the
    result object.  ``control`` serves the window from the entry's control
    (``bench/calibrate.py`` and the tests; the benchmark's runs never do)."""
    import jax

    from bench import trace as tr

    chips = int(cell["cell"]["chips"])
    devices, pk = select_devices(chips, require_tpu)
    use_compile_cache()
    entry = build_entry(cell, seed, devices)
    for _ in range(WARMUP_CALLS):
        jax.block_until_ready(entry.fn(*entry.args(0, 0)))
    order = call_order(seed, len(entry.pool))
    compiles = _CompileCounter()
    setup_s = time.perf_counter() - T_START
    log(f"setup_s={setup_s!r} pool={len(entry.pool)}")

    with entry.control() if control else contextlib.nullcontext():
        if control:
            jax.block_until_ready(entry.fn(*entry.args(0, 0)))
        compiles.active = True
        watch = HostWatch()
        if trace:
            w, t = traced_window(entry, order, min(seconds, TRACE_SECONDS))
        else:
            w = closed_loop(entry, order, seconds)
        host = watch.stop()
        compiles.active = False
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": chips,
              "memory_peak_bytes": memory_peak_bytes(devices)}
    log(f"calls={len(w.times)} window_s={w.seconds!r} "
        f"slowest_call_s={max(w.times)!r} compiles_in_window={compiles.n}")
    log(f"host {json.dumps(host)} slowest (call, start_s, dispatch_s, "
        f"wait_s): {json.dumps(slowest_calls(w))}")

    out = {"correct": False, "attempted": len(w.calls), "failed": None}
    if trace:
        base = time_baseline(entry)
        log(f"baseline jnp.sort of the cell's data: "
            f"{'not measured' if base is None else repr(base * 1e3) + ' ms'}")
        lo, hi = tr.window(t)
        out["metrics"] = per_layer(cell, TraceContext(
            t, lo, hi, len(w.calls), w.calls, entry, pk))
        if t.ops:
            device["busy_s"] = tr.busy_ns(t, lo, hi) * 1e-9
            device["window_s"] = (hi - lo) * 1e-9
            out["breakdown"] = {"device_ops": tr.top_ops(t, lo, hi),
                                "idle_gaps": tr.idle_gaps(t, lo, hi)}
    else:
        out["metrics"] = {
            m["name"]: {"value": float(END_TO_END[m["name"]](w, setup_s)),
                        "unit": m["unit"]}
            for m in cell["end_to_end"]}
    out["device"] = device

    failed, checks = entry.check(w.calls)
    out["failed"] = failed
    out["correct"] = failed == 0 and all(v <= lim
                                         for v, lim in checks.values())
    out["checks"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in checks.items()}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    # libtpu's logs go under the run's own temporary directory
    os.environ.setdefault("TPU_LOG_DIR",
                          os.path.join(tempfile.gettempdir(), "tpu_logs"))
    try:
        cell = load_cell(args.workload)
        result = run(cell, args.seed, args.seconds, bool(args.trace))
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    except Exception:  # a run that breaks prints its cause, and no result
        traceback.print_exc()
        return 1
    sys.stdout.flush()
    for k, c in result["checks"].items():
        print(f"check {k}={c['value']!r} limit={c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
