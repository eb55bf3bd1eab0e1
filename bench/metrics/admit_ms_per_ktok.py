"""Worker admission (RolloutWorker.prefill / extend): host milliseconds in the
backend's ``admit`` and ``tool_absorb`` calls, which end in a sync in the traced
run, per 1,000 tokens admitted or absorbed."""


def read(m):
    ns = sum(e - s for name, s, e in m.rec.spans if name in ("admit", "tool_absorb"))
    tokens = (m.counter("reused_tokens") + m.counter("prefilled_tokens")
              + m.counter("absorbed_tokens"))
    if tokens == 0:
        return None
    return ns * 1e-6 / (tokens / 1000.0)
