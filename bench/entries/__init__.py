"""Entries: one module per public API the benchmark calls, named by a
traffic mix's ``entry``.  Each module has ``build(cfg, mix, seed,
devices)``, which makes the pool and returns the object the harness drives
(see ``bench/run.py``)."""
