"""Device milliseconds of the admission programs per 1,000 prompt tokens
admitted, over the traced admissions (model step, prefill)."""

from bench import readers


def compute(f):
    adm = readers.admissions(f)
    tokens = sum(S for _, S in adm)
    if not tokens:
        return None
    return 1e3 * sum(m.dur for m, _ in adm) / (tokens / 1e3)
