"""The on-chip benchmark: cells, traffic, references and trace readers.

``bench/run.py`` is the entry point; every configuration, traffic mix,
per-layer metric, kernel work count and limit is a file of its own
under this directory, found by the name ``BENCHMARK.json`` gives it.
"""
