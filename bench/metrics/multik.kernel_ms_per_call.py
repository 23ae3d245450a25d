"""multik.kernel_ms_per_call: device time per call of the shared-x
multi-bracket histogram kernel, in ms, averaged over chips.

Layer: kernels (``kernels/cp_objective.py``, ``_hist_kernel_multi``: one
sweep bins every element against all K brackets' slot bounds).  Moves:
call_ms.  Source: the device trace, the summed time of the Pallas events
named ``histogram_multi`` that start in the window, over the window's
calls.  A run with no such event reads nothing."""

KERNEL = "histogram_multi"


def read(ctx):
    from bench import trace

    if not ctx.calls:
        return None
    events = [e for e in trace.started_in(ctx.trace, ctx.lo, ctx.hi)
              if trace.op_kind(e) == "pallas"
              and trace.op_name(e).startswith(KERNEL)]
    if not events:
        return None
    nchips = len(ctx.trace.ops)
    return sum(e.dur for e in events) * 1e-6 / nchips / ctx.calls
