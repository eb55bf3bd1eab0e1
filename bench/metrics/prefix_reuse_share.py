"""Worker admission: the share of admitted prompt tokens implanted from the radix
cache instead of computed, in % (``dispatch_stats``)."""


def read(m):
    reused, filled = m.counter("reused_tokens"), m.counter("prefilled_tokens")
    if reused + filled == 0:
        return None
    return 100.0 * reused / (reused + filled)
