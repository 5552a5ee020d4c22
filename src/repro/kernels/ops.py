"""Jit'd public wrappers for the Pallas kernels.

``interpret`` defaults to True off-TPU (interpret mode runs the kernel body
as ordinary JAX ops, for correctness checks on CPU) and False on a TPU.
``_default_interpret`` is the one place that decides it: every kernel entry
point, the packed dispatch and the tuner call it when ``interpret`` is None.

NOTE: the hand-driven pack functions here are DEPRECATED for model-facing
use — ``repro.sparse`` owns packing now (``PrunedArtifact.pack()`` resolves
the right packer per ``LayerSpec.scheme`` through the scheme→kernel
registry, handles stacked leaves and records scheme metadata for
save/load). The wrappers keep their exact signatures and behavior so
existing benchmarks/experiments run unchanged; they emit a
DeprecationWarning pointing at the registry.
"""

from __future__ import annotations

import functools
import warnings

import jax

from repro.kernels import column_gemm as _cg
from repro.kernels import flash_attention as _fa
from repro.kernels import pattern_conv as _pc
from repro.kernels import pattern_gemm as _pg


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _deprecated_pack(fn):
    """Shim: keep the ops-level pack signature, point at repro.sparse."""

    @functools.wraps(fn)
    def wrapper(*args, **kw):
        warnings.warn(
            f"kernels.ops.{fn.__name__} is deprecated for model-facing "
            "packing; use repro.sparse (PrunedArtifact.pack / "
            "SPARSE_SCHEMES) which dispatches per LayerSpec.scheme",
            DeprecationWarning, stacklevel=2,
        )
        return fn(*args, **kw)

    return wrapper


# -- tile-pattern sparse GEMM -------------------------------------------------

@_deprecated_pack
def pack_tile_pattern(w, **kw):
    return _pg.pack_tile_pattern(w, **kw)


def tile_pattern_matmul(x, w_packed, lane_idx, **kw):
    return _pg.pattern_gemm(x, w_packed, lane_idx, **kw)


# -- column-pruned GEMM -------------------------------------------------------

@_deprecated_pack
def pack_columns(w, **kw):
    return _cg.pack_columns(w, **kw)


def column_matmul(x, w_packed, kept_idx, **kw):
    return _cg.column_gemm(x, w_packed, kept_idx, **kw)


# -- flash attention ----------------------------------------------------------

def flash_attention(q, k, v, **kw):
    return _fa.flash_attention(q, k, v, **kw)


# -- pattern conv ---------------------------------------------------------------

@_deprecated_pack
def assign_channel_patterns(w4, patterns=None):
    return _pc.assign_channel_patterns(w4, patterns)


@_deprecated_pack
def pack_pattern_conv(w4, pat_ids, patterns=None):
    return _pc.pack_pattern_conv(w4, pat_ids, patterns)


def pattern_conv(x, w_packed, taps, bias=None, *, interpret=None,
                 activation=None):
    return _pc.pattern_conv(x, w_packed, taps, bias, interpret=interpret,
                            activation=activation)
