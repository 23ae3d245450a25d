"""engine.xla_ms_per_call: device time per call of every op that is
neither a Pallas kernel nor an exchange between chips, in ms.

Layer: engine finalize and glue (seed stats, survivor compaction, cumsum,
finalize sort; for LTS also the matmuls, solves and elemental starts).
Moves: call_ms.  Source: the device trace, averaged over chips."""


def read(ctx):
    from bench import trace

    if not ctx.trace.ops or not ctx.calls:
        return None
    ns = trace.time_by_kind(ctx.trace, ctx.lo, ctx.hi)["xla"]
    return ns * 1e-6 / ctx.calls
