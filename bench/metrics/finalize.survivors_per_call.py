"""finalize.survivors_per_call: elements left in the final bracket per
call, the survivors the compaction gathers, as the program counts them.

Layer: finalize: survivor compaction.  Moves: call_ms.  Source: the counts
the program returns, ``SelectResult.n_in`` (the psum'd global count when
sharded), summed over the answers of a call and averaged over the window's
calls.  Its base is the compaction buffer: 2^19 slots for one array on one
chip, 4096 per shard on four."""
import numpy as np


def read(ctx):
    if not ctx.outputs or not hasattr(ctx.outputs[0][1], "n_in"):
        return None
    return sum(int(np.sum(np.asarray(out.n_in)))
               for _, out in ctx.outputs) / len(ctx.outputs)
