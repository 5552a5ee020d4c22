"""Percent of the traced window in which no operation ran on the chip and
the engine was not waiting for an arrival (``arrival_wait``): idle time
the engine could have filled, apart from arrival slack, read from the
engine's own spans in the profile (engine layer)."""

from bench import engine_spans as es


def compute(f):
    if f.hi <= f.lo or not es.events(f, es.LOOP):
        return None
    stall = es.idle_outside(f, lambda name: name == es.WAIT)
    return 100.0 * stall / (f.hi - f.lo)
