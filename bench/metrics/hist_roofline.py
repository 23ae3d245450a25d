"""hist_roofline: the histogram kernels' share of the HBM roofline, in %.

Layer: kernels (kernels/cp_objective.py).  Moves: call_ms.  Source: the
device trace.  Each histogram kernel event is one sweep that reads each of
its operands once, so its least time is the bytes of its operands, from the
shapes in the event's HLO text, over the chip's peak HBM bandwidth.  The
share is that least time summed over the window's events, over their summed
device time.  The kernels are the Pallas custom calls named after the
jitted histogram entry points (``cp_histogram_batched``,
``cp_histogram_multi``, and their weighted ``wcp_`` twins).
"""

KERNEL = "histogram"


def read(ctx):
    from bench import trace

    events = [e for e in trace.started_in(ctx.trace, ctx.lo, ctx.hi)
              if KERNEL in trace.op_name(e)
              and trace.operand_bytes(e) is not None]
    if not events:
        return None
    least = (sum(trace.operand_bytes(e) for e in events)
             / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / (sum(e.dur for e in events) * 1e-9)
