"""Worker admission (RolloutWorker.prefill -> the fixed-shape chunk program):
milliseconds of admission wall per chunk dispatched, over every batch of the
window (each ends in a sync); the page sharing of reused lanes counts in."""


def read(m):
    ns = sum(e - s for name, s, e in m.rec.spans if name == "admit")
    chunks = m.counter("prefill_dispatches")
    if chunks == 0:
        return None
    return ns * 1e-6 / chunks
