"""``selection.quantiles`` of one array of the pool per call: the
``bins + 1`` equal-frequency edges ``q = j / bins``, ``j = 0..bins``, of a
feature column, as a histogram tree booster bins it."""
from __future__ import annotations

from bench.entries._array import ArrayEntry, ranks_of


def build(cfg, mix, seed, devices):
    import numpy as np
    from repro.core import selection

    bins = int(cfg["bins"])
    qs = np.arange(bins + 1, dtype=np.float64) / bins
    return ArrayEntry(cfg, mix, seed, lambda x: selection.quantiles(x, qs),
                      ranks_of(qs, int(cfg["n"])))
