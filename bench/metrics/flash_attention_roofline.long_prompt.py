"""Percent of roofline of the Pallas ``flash_attention`` kernel at
prefill: causal attention over each admitted prompt (kernels layer)."""

from bench import readers


def compute(f):
    return readers.kernel_roofline(f, "flash_attention")
