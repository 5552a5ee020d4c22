"""Whole-step share of the chip's bf16 peak over the traced admissions
and decode chunks (model step)."""

from bench import readers


def compute(f):
    return readers.step_mfu(f)
