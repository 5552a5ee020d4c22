"""Percent of the HBM roofline of the decode step: the weight bytes as
stored and the live rows' KV bytes each step must read, at the chip's
bandwidth, over the traced decode-chunk programs' device time (model
step, decode)."""

from bench import readers


def compute(f):
    dec = readers.decode_chunks(f)
    took = sum(m.dur for m, _ in dec)
    if took <= 0:
        return None
    least = sum(f.arch.decode_step(f.shapes, step).bytes
                for _, steps in dec for step in steps if step)
    return 100.0 * least / f.peaks.hbm_bytes / took
