"""Device: the share of the traced batch in which no operation ran, in %."""


def read(m):
    if m.trace is None or m.trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - m.trace["busy_s"] / m.trace["window_s"])
