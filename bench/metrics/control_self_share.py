"""Control plane (core/orchestrator.py, core/controller.py): the share of batch
wall spent outside every data-plane call of the backend, in %."""

from bench.spans import BACKEND_CALLS
from bench.trace import merge


def read(m):
    wall_ns = sum(b["t1"] - b["t0"] for b in m.batches)
    if wall_ns <= 0:
        return None
    inside = sum(e - s for s, e in merge((s, e) for name, s, e in m.rec.spans
                                         if name in BACKEND_CALLS))
    return 100.0 * (wall_ns - inside) / wall_ns
