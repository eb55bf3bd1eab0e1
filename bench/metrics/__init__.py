"""One reader per per-layer metric: ``read(measures) -> float | None``.

The harness finds a reader by the metric's name in ``BENCHMARK.json``.  A reader
that finds nothing to read returns None, and the metric is left out of the line.
"""
