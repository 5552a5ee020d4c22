"""The engine's own host spans, as the profiler recorded them.

``ContinuousEngine`` opens every phase of its serve loop as a
``TraceAnnotation`` of the span's name (``repro.runtime.telemetry``):
``reap``, ``admit`` (children ``admit.dispatch``, ``admit.sync``),
``arrival_wait``, ``fault_hook``, ``decode_chunk`` (children
``decode_chunk.prep``, ``.dispatch``, ``.sync``), ``absorb``, ``emit``.
They lie among ``bench/trace.py``'s host events, on the device trace's
clock, so no offset is needed. A program that records none of them
yields no events here, and the metrics that read them report nothing.
"""

from __future__ import annotations

from typing import List, Sequence, Tuple

from bench import trace as tr

WAIT = "arrival_wait"
CHUNK = "decode_chunk"
CHUNK_SYNC = "decode_chunk.sync"
LOOP = "reap"                   # opened by every iteration of the loop

Interval = Tuple[float, float]


def events(f, name: str) -> List[tr.Event]:
    """Host events called ``name`` wholly inside the traced window."""
    if f.reduced is None:
        return []
    return [e for e in f.reduced.host
            if e.name == name and e.start >= f.lo and e.end <= f.hi]


def cover(f, names) -> List[Interval]:
    """Merged intervals of the traced window in which the host was inside
    a span whose name ``names(name)`` accepts."""
    if f.reduced is None:
        return []
    return tr.union((e for e in f.reduced.host if names(e.name)),
                    f.lo, f.hi)


def idle(f) -> List[Interval]:
    """The chip's idle gaps in the traced window, in time order."""
    return sorted(tr.idle_gaps(f.ops(), f.lo, f.hi))


def overlap(a: Sequence[Interval], b: Sequence[Interval]) -> float:
    """Seconds in which both of two time-ordered lists of disjoint
    intervals hold."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo = max(a[i][0], b[j][0])
        hi = min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def idle_outside(f, names) -> float:
    """Seconds of the traced window in which the chip ran nothing and the
    host was in no span that ``names`` accepts."""
    gaps = idle(f)
    return sum(b - a for a, b in gaps) - overlap(gaps, cover(f, names))
