"""Device-idle milliseconds per traced decode chunk in which the host was
neither waiting on the chip (a ``*.sync`` span) nor for an arrival
(``arrival_wait``): the host's own work between programs, read from the
engine's spans in the profile (engine layer)."""

from bench import engine_spans as es


def compute(f):
    chunks = es.events(f, es.CHUNK)
    if not chunks:
        return None
    work = es.idle_outside(
        f, lambda name: name.endswith(".sync") or name == es.WAIT)
    return 1e3 * work / len(chunks)
