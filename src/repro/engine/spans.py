"""Host spans of a worker's own work: per-name totals, on the profiler's clock.

Each span is a ``jax.profiler.TraceAnnotation``, so whenever a profile is running
it lands in the profiler's host plane on the same clock as the device's ops, with
its arguments (a lane's ``seq_id``) as event stats.  Each span also adds its
``perf_counter_ns`` duration and one call to its name's totals, which
``RolloutWorker.dispatch_stats()`` exports as ``span_<name>_ns`` and
``span_<name>_n``.  A span adds no device work and no sync: without a profile the
annotation is a no-op, and the totals are two clock reads and two dict updates.
"""

from __future__ import annotations

import time

import jax


class Span:
    """One timed region; ``ns`` holds its duration once it has closed."""

    __slots__ = ("_totals", "_name", "_annotation", "_t0", "ns")

    def __init__(self, totals: "SpanTotals", name: str, args: dict):
        self._totals = totals
        self._name = name
        self._annotation = jax.profiler.TraceAnnotation(name, **args)
        self.ns = 0

    def __enter__(self) -> "Span":
        self._annotation.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        self.ns = time.perf_counter_ns() - self._t0
        t = self._totals
        t.ns[self._name] = t.ns.get(self._name, 0) + self.ns
        t.n[self._name] = t.n.get(self._name, 0) + 1
        self._annotation.__exit__(*exc)


class SpanTotals:
    """Nanoseconds and calls per span name, summed since the worker was built."""

    def __init__(self):
        self.ns: dict[str, int] = {}
        self.n: dict[str, int] = {}

    def span(self, name: str, **args) -> Span:
        return Span(self, name, args)

    def stats(self) -> dict[str, int]:
        out = {}
        for name in self.ns:
            out[f"span_{name}_ns"] = self.ns[name]
            out[f"span_{name}_n"] = self.n[name]
        return out
