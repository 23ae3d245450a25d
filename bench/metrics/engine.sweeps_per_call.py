"""engine.sweeps_per_call: histogram sweeps (psum rounds, when sharded) per
call, as the program counts them.

Layer: engine loops (core/selection.py, core/distributed.py,
core/robust.py).  Moves: call_ms.  Source: the counts the program returns,
``SelectResult.iters`` (its maximum over the ranks of one solve, since the
loop runs until the last is done) or ``RobustFit.sweeps`` (the maximum
over starts, summed over concentration steps; the fit's final objective
selection is not returned, so it is not counted)."""


def read(ctx):
    if not ctx.outputs:
        return None
    return sum(ctx.entry.sweeps(out) for _, out in ctx.outputs) / len(
        ctx.outputs)
