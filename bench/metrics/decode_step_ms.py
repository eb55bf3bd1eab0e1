"""Worker decode (RolloutWorker.decode -> _decode_loop): host milliseconds per
decode step, over every decode call of the window (each ends in a sync)."""


def read(m):
    ns = sum(e - s for name, s, e in m.rec.spans if name == "decode")
    steps = sum(n for _, n, _, _ in m.rec.decode_calls)
    if steps == 0:
        return None
    return ns * 1e-6 / steps
