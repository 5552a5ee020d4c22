"""Percent of roofline of the Pallas ``flash_attention`` kernel at
prefill: causal attention over each admitted prompt (kernels layer)."""

from bench import readers, work


def compute(f):
    s = f.shapes
    return readers.kernel_roofline(
        f, "flash_attention", 1,
        lambda S: work.flash_prefill(S, s.heads, s.kv_heads, s.head_dim))
