"""device.idle_pct: share of the traced window in which no op ran on the
chip, averaged over chips, in %.

Layer: device.  Moves: call_ms.  Source: the device trace (1 minus the
union of op intervals over the window)."""


def read(ctx):
    from bench import trace

    if not ctx.trace.ops:
        return None
    span = ctx.hi - ctx.lo
    return 100.0 * (1.0 - trace.busy_ns(ctx.trace, ctx.lo, ctx.hi) / span)
