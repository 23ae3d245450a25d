"""finalize.sort_ms_per_call: device time per call of the cap-buffer sort
and the answer assembly, in ms, averaged over chips.

Layer: finalize: cap sort and assembly.  Moves: call_ms.  Source: the
device trace, the ``xla`` ops of the ``sel.sort`` scope
(``bench/scopes.py``).  A program that names no phase reads nothing."""


def read(ctx):
    from bench import scopes

    return scopes.ms_per_call(ctx, "sel.sort")
