"""finalize.useful_pct: share of the window's answers that the survivor
compaction decided, in %.

Layer: finalize: survivor compaction.  Moves: call_ms.  Source: the
statuses the program returns, ``SelectResult.status``: an answer with
status ``HYBRID_SORT`` (read from the sorted buffer) or ``TIE_FALLBACK``
(the next distinct value, verified) needed the compaction; the compiled
program runs it for every answer, ``EXACT_HIT`` ones too."""
import numpy as np


def read(ctx):
    from repro.core.selection import HYBRID_SORT, TIE_FALLBACK

    if not ctx.outputs or not hasattr(ctx.outputs[0][1], "status"):
        return None
    status = np.concatenate([np.asarray(out.status).reshape(-1)
                             for _, out in ctx.outputs])
    return 100.0 * float(np.mean(np.isin(status, (HYBRID_SORT,
                                                  TIE_FALLBACK))))
