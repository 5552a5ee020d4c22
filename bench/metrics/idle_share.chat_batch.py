"""Percent of the traced window in which no operation ran on the chip
(device layer)."""

from bench import trace as tr


def compute(f):
    if f.reduced is None or f.hi <= f.lo:
        return None
    busy = tr.busy_seconds(f.ops(), f.lo, f.hi)
    return 100.0 * (1.0 - busy / (f.hi - f.lo))
