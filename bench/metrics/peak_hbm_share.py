"""Device: the chip's peak bytes in use over its HBM bytes, in %."""


def read(m):
    if m.memory_peak_bytes <= 0:
        return None
    return 100.0 * m.memory_peak_bytes / m.peaks["hbm_bytes"]
