"""engine.seed_ms_per_call: device time per call of the seed stats pass
(extremes and mean, the analytic bracket seed), in ms, averaged over
chips.

Layer: engine: seed stats.  Moves: call_ms.  Source: the device trace, the
``xla`` ops of the ``sel.seed`` scope (``bench/scopes.py``); its
``pmin``/``pmax`` count as exchanges.  A program that names no phase reads
nothing."""


def read(ctx):
    from bench import scopes

    return scopes.ms_per_call(ctx, "sel.seed")
