"""Percent of roofline of the Pallas ``pattern_gemm`` kernel at prefill:
every packed block GEMM of an admission at M = S (kernels layer)."""

from bench import readers, work


def compute(f):
    names = [n for n in work.BLOCK_GEMMS if n in f.shapes.packed]

    def need(S):
        return sum((work.gemm(f.shapes, n, S) for n in names), work.Work())

    return readers.kernel_roofline(f, "pattern_gemm", len(names), need)
