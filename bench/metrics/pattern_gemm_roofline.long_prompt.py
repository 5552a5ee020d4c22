"""Percent of roofline of the Pallas ``pattern_gemm`` kernel at prefill:
every packed block GEMM of an admission at M = S (kernels layer)."""

from bench import readers


def compute(f):
    return readers.kernel_roofline(f, "pattern_gemm")
