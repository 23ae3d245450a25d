"""collective.ms_per_call: device time per call of the exchanges between
chips, in ms, averaged over chips.

Layer: distributed rounds (core/distributed.py).  Moves: call_ms.
Source: the device trace.  On a TPU v5e the exchanges run as synchronous
ops of each chip's ``XLA Ops`` line: the psum rounds (``psum``, ``pmax``,
``pmin``, lowered to ``all-reduce``) and the survivor ``all-gather``; the
``Async XLA Ops`` line holds only the copies that overlap them
(``bench/tests/data/median_mix9_x4_1call.json``).  A run with no such
event reads nothing."""


def read(ctx):
    from bench import trace

    if not ctx.trace.ops or not ctx.calls:
        return None
    ns = trace.time_by_kind(ctx.trace, ctx.lo, ctx.hi)["collective"]
    if ns == 0:
        return None
    return ns * 1e-6 / ctx.calls
