"""The chip benchmark's harness: one run of one cell of ``BENCHMARK.json``.

Every configuration, traffic mix, per-layer metric, reference family and
cost model is a file of its own, found by the name ``BENCHMARK.json`` gives
it (see ``spec.Layout``); this package holds only what all cells share.
"""
