"""Worker admission (RolloutWorker.prefill): host milliseconds per admitted lane
in the program's ``prefill`` span less its ``chunk_dispatch`` span (the chunk
loop, which blocks while the device's queue is full) and its ``seq_key`` span
(admission's one device sync, which waits for the lane's chunks): the radix
walk and insert, slot and page mapping, during which the device may have
nothing queued.  From the worker's span totals (``dispatch_stats``, ``drained``)
over the window's batches after the first, which the profiler did not trace;
the admission harness keeps one worker for the run, so the totals add up."""

EXCLUDED = ("chunk_dispatch", "seq_key")


def _total(batch, key):
    return sum(d.get(key, 0) for d in batch["drained"])


def read(m):
    if len(m.batches) < 2:
        return None
    first, last = m.batches[0], m.batches[-1]

    def delta(key):
        return _total(last, key) - _total(first, key)

    lanes = delta("span_prefill_n")
    if lanes <= 0:
        return None
    ns = delta("span_prefill_ns") - sum(delta(f"span_{s}_ns") for s in EXCLUDED)
    return ns * 1e-6 / lanes
