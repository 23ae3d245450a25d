"""Per-layer metrics: one file each, named as the metric, holding
``read(ctx)``.  ``ctx`` is ``bench.run.TraceContext``: the reduced trace of
the traced window, the calls made in it with their outputs, the entry and
the chip's peaks.  A reader that finds nothing to read returns None and the
metric is left out of the result."""
