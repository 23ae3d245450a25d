"""finalize.probe_ms_per_call: device time per call of the finalize's
certificate passes, in ms, averaged over chips.

Layer: finalize: certificate passes (``cL``, ``vnext``, ``m_le_v`` and
``m_lt_max``, counted over the whole array; their psums count as
exchanges).  Moves: call_ms.  Source: the device trace, the ``xla`` ops of
the ``sel.probe`` scope (``bench/scopes.py``).  A program that names no
phase reads nothing."""


def read(ctx):
    from bench import scopes

    return scopes.ms_per_call(ctx, "sel.probe")
