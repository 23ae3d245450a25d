"""multik.kernel_gslots_per_s: slot compares per second that the shared-x
multi-bracket histogram kernel achieves, in 10^9 per s.

Layer: kernels (``kernels/cp_objective.py``, ``_hist_kernel_multi``).
Moves: call_ms.  Source: the device trace.  Each ``histogram_multi`` event
is one sweep that compares every element of its tile grid with each slot
bound of each of its K brackets, so its work is K x nslots x (rows x 128)
compares, all three read from the shapes of the event's own HLO operands
(the ``(2, K, nslots)`` slot bounds, then the ``(rows, 128)`` tile grid,
padded to whole tiles: the masked tail is compared like any element), as
``hist_roofline`` reads their bytes.  The rate is that work summed over
the window's events, over their summed time.

This is how the kernel's work is read at K > 1: its HBM share
(``hist_roofline``'s definition) is not its bound there (about 0.1% at
K = 64).  A share of a VPU peak waits until ``bench/peaks.py`` holds a
published one.  A run with no such event reads nothing."""

KERNEL = "histogram_multi"


def compares(e):
    """K x nslots x tile-grid elements of one kernel event; None where its
    HLO text gives no operand shapes."""
    from bench import trace

    ops = trace._OPERANDS.search(e.name)
    if ops is None:
        return None
    shapes = [[int(d) for d in dims.split(",") if d]
              for _, dims in trace._SHAPE.findall(ops.group(1))]
    if len(shapes) < 2 or len(shapes[0]) != 3:
        return None
    (_, k, nslots), tile = shapes[0], shapes[1]
    work = k * nslots
    for d in tile:
        work *= d
    return work


def read(ctx):
    from bench import trace

    events = [e for e in trace.started_in(ctx.trace, ctx.lo, ctx.hi)
              if trace.op_kind(e) == "pallas"
              and trace.op_name(e).startswith(KERNEL)
              and compares(e) is not None]
    if not events:
        return None
    # compares per ns of kernel time are 10^9 compares per s
    return sum(compares(e) for e in events) / sum(e.dur for e in events)
