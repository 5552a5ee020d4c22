"""Device milliseconds per decode step: the traced decode-chunk programs
over the steps they ran (model step, decode)."""

from bench import readers


def compute(f):
    dec = readers.decode_chunks(f)
    steps = sum(len(s) for _, s in dec)
    if not steps:
        return None
    return 1e3 * sum(m.dur for m, _ in dec) / steps
