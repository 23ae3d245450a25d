"""Reduction of a profiler trace to device time, by the benchmark's rules.

A trace is read into :class:`Trace`: for each chip, the op events of its
``XLA Ops`` line, and the host's annotation spans (``bench.window`` around
the traced window, ``bench.call`` around each call).  Every number below is
taken from those events alone, so a small cut of a recorded trace
(:func:`save` / :func:`load_json`) tests the same code the chip runs.

    python3 -m bench.trace <trace dir or .xplane.pb>   # what a trace holds
"""
from __future__ import annotations

import dataclasses
import glob
import json
import os
import re
import sys

OPS_LINE = "XLA Ops"
DEVICE_PREFIX = "/device:TPU:"
HOST_SPANS = ("bench.window", "bench.call", "bench.dispatch", "bench.wait")


@dataclasses.dataclass
class Event:
    name: str     # on a chip: the op's HLO text
    start: float  # ns, on the trace's common clock
    dur: float    # ns

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclasses.dataclass
class Trace:
    ops: dict    # chip plane name -> [Event] of its op line
    spans: list  # host annotation spans, [Event]


def find_xplane(path: str) -> str:
    if path.endswith(".xplane.pb"):
        return path
    found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return found[-1]


def load_xplane(path: str) -> Trace:
    """Read the chips' op events and the host spans from a profile."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(find_xplane(path))
    ops, spans = {}, []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops[plane.name] = [
                        Event(e.name, float(e.start_ns), float(e.duration_ns))
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [Event(e.name, float(e.start_ns),
                                float(e.duration_ns))
                          for e in line.events if e.name in HOST_SPANS]
    return Trace(ops, sorted(spans, key=lambda e: e.start))


def save(trace: Trace, path: str) -> None:
    with open(path, "w") as f:
        json.dump({"ops": {p: [dataclasses.astuple(e) for e in evs]
                           for p, evs in trace.ops.items()},
                   "spans": [dataclasses.astuple(e) for e in trace.spans]},
                  f)


def load_json(path: str) -> Trace:
    with open(path) as f:
        d = json.load(f)
    return Trace({p: [Event(*e) for e in evs] for p, evs in d["ops"].items()},
                 [Event(*e) for e in d["spans"]])


# ---------------------------------------------------------------------------
# What each kernel reads
# ---------------------------------------------------------------------------

_BYTES = {"f64": 8, "s64": 8, "u64": 8, "f32": 4, "s32": 4, "u32": 4,
          "bf16": 2, "f16": 2, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
          "pred": 1}
_OPERANDS = re.compile(r"operand_layout_constraints=\{(.*?)\}, \w+=")
_SHAPE = re.compile(r"\b([a-z]+\d*)\[([\d,]*)\]")


def operand_bytes(e: Event):
    """Bytes of all operands of a Pallas kernel event, from the shapes that
    its HLO text (the event's name on the chip) gives; None for any other
    op."""
    if "tpu_custom_call" not in e.name:
        return None
    ops = _OPERANDS.search(e.name)
    if ops is None:
        return None
    total = 0
    for dt, dims in _SHAPE.findall(ops.group(1)):
        size = 1
        for d in filter(None, dims.split(",")):
            size *= int(d)
        total += size * _BYTES[dt]
    return total


# ---------------------------------------------------------------------------
# Reductions
# ---------------------------------------------------------------------------


def window(trace: Trace, name: str = "bench.window") -> tuple[float, float]:
    """``(start, end)`` of the host span that brackets the traced window."""
    spans = [e for e in trace.spans if e.name == name]
    if len(spans) != 1:
        raise ValueError(f"expected one {name!r} span, found {len(spans)}")
    return spans[0].start, spans[0].end


def clipped(events, lo: float, hi: float):
    """Each event's interval clipped to ``[lo, hi]``; empty ones dropped."""
    out = []
    for e in events:
        a, b = max(e.start, lo), min(e.end, hi)
        if b > a:
            out.append((a, b))
    return out


def union_ns(intervals) -> float:
    """Length of the union of ``(start, end)`` intervals."""
    total, cur_a, cur_b = 0.0, None, None
    for a, b in sorted(intervals):
        if cur_b is None or a > cur_b:
            if cur_b is not None:
                total += cur_b - cur_a
            cur_a, cur_b = a, b
        else:
            cur_b = max(cur_b, b)
    if cur_b is not None:
        total += cur_b - cur_a
    return total


def busy_ns(trace: Trace, lo: float, hi: float) -> float:
    """Time in ``[lo, hi]`` in which some op ran, averaged over chips."""
    per_chip = [union_ns(clipped(evs, lo, hi)) for evs in trace.ops.values()]
    return sum(per_chip) / len(per_chip)


_COLLECTIVE = re.compile(
    r" (?:all-reduce|all-gather|reduce-scatter|collective-permute"
    r"|all-to-all)(?:-start|-done)?\(")


def op_kind(e: Event) -> str:
    """``pallas`` for a Pallas kernel, ``collective`` for an exchange
    between chips, ``xla`` for every other op.  On the chip an op's event
    is named by its HLO text, ``%name = shape opcode(operands), ...``: a
    collective is known by its opcode, whatever its name (``psum``,
    ``pmax`` and ``pmin`` lower to ``all-reduce``); the shape may hold
    parentheses of its own (tuples, tiled layouts such as ``{:T(128)}``),
    and an operand is a ``%name``, never an opcode."""
    if "tpu_custom_call" in e.name:
        return "pallas"
    if _COLLECTIVE.search(e.name):
        return "collective"
    return "xla"


def self_times(events) -> list:
    """``(event, self ns)`` for each op of one chip's line: its duration
    less that of the ops nested in it (a ``while`` holds its body's ops;
    leaves keep their whole duration), so that summed self times do not
    count nested time twice."""
    order = sorted(events, key=lambda e: (e.start, -e.end))
    child_ns = [0.0] * len(order)
    stack = []
    for i, e in enumerate(order):
        while stack and order[stack[-1]].end <= e.start:
            stack.pop()
        if stack and e.end <= order[stack[-1]].end:
            child_ns[stack[-1]] += e.dur
        stack.append(i)
    return [(e, max(e.dur - c, 0.0)) for e, c in zip(order, child_ns)]


def time_by_kind(trace: Trace, lo: float, hi: float) -> dict:
    """Self time of the ops that start in ``[lo, hi]``, by
    :func:`op_kind`, in ns, averaged over chips."""
    out = {"pallas": 0.0, "collective": 0.0, "xla": 0.0}
    for evs in trace.ops.values():
        for e, ns in self_times(evs):
            if lo <= e.start < hi:
                out[op_kind(e)] += ns
    nchips = max(len(trace.ops), 1)
    return {k: v / nchips for k, v in out.items()}


def started_in(trace: Trace, lo: float, hi: float) -> list:
    """Op events of every chip that start in ``[lo, hi]``."""
    return [e for evs in trace.ops.values() for e in evs
            if lo <= e.start < hi]


_LAYOUT = re.compile(r"\{[^{}]*\}")


def op_name(e: Event) -> str:
    """An op's HLO instruction name: ``%fusion.24 = ...`` -> ``fusion.24``."""
    return e.name.split(" = ", 1)[0].lstrip("%")


def op_label(e: Event) -> str:
    """An op's HLO text without layouts, cut to 100 characters: its name,
    shape, opcode and operands, as the breakdown shows them."""
    return _LAYOUT.sub("", e.name).lstrip("%")[:100]


def top_ops(trace: Trace, lo: float, hi: float, k: int = 10) -> list:
    """The ``k`` ops with the most self time in the window, as
    ``[name, seconds]`` averaged over chips."""
    tot = {}
    for evs in trace.ops.values():
        for e, ns in self_times(evs):
            if lo <= e.start < hi:
                tot[op_label(e)] = tot.get(op_label(e), 0.0) + ns
    nchips = max(len(trace.ops), 1)
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [[name, ns / nchips * 1e-9] for name, ns in best]


def idle_gaps(trace: Trace, lo: float, hi: float, k: int = 10) -> list:
    """The ``k`` longest stretches of the window in which the first chip ran
    nothing, as ``[host activity, seconds]``: the innermost host span
    (dispatch, wait, or between calls) that covers the gap's midpoint."""
    evs = next(iter(trace.ops.values()))
    ivs = sorted(clipped(evs, lo, hi))
    gaps, t = [], lo
    for a, b in ivs:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    inner = [s for s in trace.spans if s.name != "bench.window"]
    out = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:k]:
        mid = 0.5 * (a + b)
        cover = [s for s in inner if s.start <= mid <= s.end]
        what = min(cover, key=lambda s: s.dur).name if cover else "between"
        out.append([what, (b - a) * 1e-9])
    return out


def summarize(path: str, top: int = 25) -> None:
    """Print the planes and lines of a profile, and each chip's ops with
    the most self time."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(find_xplane(path))
    for plane in data.planes:
        print(f"plane {plane.name!r}: " + ", ".join(
            f"{ln.name!r} ({sum(1 for _ in ln.events)})"
            for ln in plane.lines))
    tr = load_xplane(path)
    lo, hi = window(tr)
    for name, ops in top_ops(tr, lo, hi, top):
        print(f"  {ops:.6f} s  {name}")
    print("spans:", [(s.name, s.dur) for s in tr.spans[:8]])


if __name__ == "__main__":
    summarize(sys.argv[1])
