"""multik.live_bracket_pct: share of the K-wide sweeps' bracket
evaluations that served a rank still open, in %.

Layer: engine loops (``core/selection.py``, ``binned_loop_batched`` on the
shared-x path: every sweep bins all K brackets, while the loop runs until
its last rank is done).  Moves: call_ms.  Source: the counts the program
returns, ``SelectResult.iters`` (the sweeps each rank was live for):
100 x the sum over ranks of ``iters`` over K x their maximum, summed over
the window's calls before the division."""
import numpy as np


def read(ctx):
    if not ctx.outputs or not hasattr(ctx.outputs[0][1], "iters"):
        return None
    live = evaluated = 0
    for _, out in ctx.outputs:
        iters = np.asarray(out.iters).reshape(-1)
        live += int(iters.sum())
        evaluated += iters.size * int(iters.max())
    if evaluated == 0:
        return None
    return 100.0 * live / evaluated
