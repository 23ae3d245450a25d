"""multik.finalize_ms_per_rank: device time of the finalize per call and
per rank, in ms, averaged over chips.

Layer: finalize (``core/selection.py``, ``_finalize_shared``: one survivor
compaction and its probes per rank, in sequence, then the buffers' sort
and the answers).  Moves: call_ms.  Source: the device trace, the ``xla``
ops of the ``sel.compact``, ``sel.probe`` and ``sel.sort`` scopes
(``bench/scopes.py``) per call, over the ranks a call asks for.  A program
that names no phase reads nothing."""

PHASES = ("sel.compact", "sel.probe", "sel.sort")


def read(ctx):
    from bench import scopes

    ranks = len(getattr(ctx.entry, "ranks", ()))
    if ranks == 0:
        return None
    parts = [scopes.ms_per_call(ctx, phase) for phase in PHASES]
    if any(p is None for p in parts):
        return None
    return sum(parts) / ranks
