"""``selection.median`` of one array of the pool per call."""
from __future__ import annotations

from bench.entries._array import ArrayEntry


def build(cfg, mix, seed, devices):
    from repro.core import selection

    n = int(cfg["n"])
    return ArrayEntry(cfg, mix, seed, selection.median,
                      [(n + 1) // 2])
