"""finalize.compact_ms_per_call: device time per call of the survivor
compaction, in ms, averaged over chips.

Layer: finalize: survivor compaction (``selection.rank_compact`` and the
survivor mask; the sharded survivor ``all_gather`` counts as an exchange).
Moves: call_ms.  Source: the device trace, the ``xla`` ops of the
``sel.compact`` scope (``bench/scopes.py``): the mask, the n-long cumsum,
the rank search and the gathers.  A program that names no phase reads
nothing."""


def read(ctx):
    from bench import scopes

    return scopes.ms_per_call(ctx, "sel.compact")
