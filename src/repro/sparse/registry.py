"""Scheme → kernel registry: pack / packed-matmul / dense-reference per scheme.

Every pruning scheme that has a packed execution path registers a
``SchemeHandler`` here (reusing ``utils.registry.Registry``). The handler is
the single seam between the algorithm level (``LayerSpec`` describing how a
tensor was pruned) and the deployment level (the Pallas kernels in
``repro.kernels``):

    handler = handler_for(spec.scheme)
    pt      = handler.pack(w, spec)          # None -> not packable, stay dense
    y       = dispatch_matmul(x2d, pt)       # plan-cached hot path
    w_back  = handler.to_dense(pt)           # exact dense reconstruction

Schemes without a packed path (``irregular``, ``filter``) resolve to the
``dense`` fallback handler, whose "pack" is the identity — the registry
always answers, so callers never special-case.

Hot-path geometry contract (the pack-time dispatch refactor)
------------------------------------------------------------

All per-call decisions — block sizes, M padding, weight layout, handler
lookup — are made exactly once:

  * at PACK time the packer chooses the kernel geometry and records it in
    ``PackedTensor.meta`` (``w_ndim``, ``block_p``, ``block_k``,
    ``small_m``), and lays the buffers out the way the kernels want them
    (tile_pattern stores the blocked (nb, Kp, bp) panel layout);
  * at FIRST dispatch for a given (scheme, shapes, dtype, M, epilogue)
    tuple, ``dispatch_matmul``/``dispatch_conv`` build one jitted closure
    with that geometry baked in and memoize it in ``_PLAN_CACHE``; every
    later call is a dict lookup;
  * requests with M ≤ ``small_m`` (decode: M = batch) take a fast path
    that skips the Pallas grid entirely — a fused XLA gather + batched
    dot over the SAME compressed buffers, with no M padding.

Large-M (prefill) regime + the tuner
------------------------------------

Requests with M > ``small_m`` pick ONE of two implementations per plan
(both over the same compressed buffers, bit-identical results):

  * ``pallas`` — the tiled kernel with a tunable (block_m, block_k,
    grid order) geometry: multi-row output panels and a rows-resident
    (``mp``) or weight-panel-resident (``pm``) streaming order;
  * ``gather`` — a fused XLA gather + dense dot (no grid, no M padding;
    the right call in interpret mode and for skinny shapes).

Resolution order (``sparse.tune.resolve``): a plan persisted in
``PackedTensor.meta`` (``plan:<kind>:m<bucket>`` — written by the
autotuner at pack time and shipped in the artifact manifest) → an
in-process tuned winner → a first-dispatch search when
``REPRO_AUTOTUNE=1`` → the per-backend heuristic default (gather in
interpret mode, Pallas on real TPU backends).

All matmul plans accept activations of shape (M, I) for a dense leaf of
shape (I, O) (the model's ``y = x @ w`` layout); an optional fused
epilogue (bias + relu/silu/gelu, see ``kernels.epilogue``) runs on the
fp32 accumulator before the result is cast back. ``interpret`` defaults
to True off-TPU exactly like ``kernels.ops``.

``DISPATCH_STATS`` counts plan-cache events per (kind, scheme, M-bucket)
and each built plan's resolved implementation — trace-time counts (one
per dispatch site per compiled graph), the per-scheme attribution that
``benchmarks/packed_serve.py --profile`` prints.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# `from <module path> import <name>` forms (resolved through sys.modules):
# kernels/__init__ re-exports a `pattern_conv` FUNCTION that shadows the
# submodule attribute of the same name on the package
from repro.kernels.column_gemm import column_gemm as _column_gemm
from repro.kernels.column_gemm import pack_columns as _pack_columns
from repro.kernels.epilogue import apply_epilogue, check_activation
from repro.kernels.ops import _default_interpret
from repro.kernels.pattern_conv import gather_taps as _gather_taps
from repro.kernels.pattern_conv import (
    pattern_conv_gemm as _pattern_conv_gemm,
)
from repro.kernels.pattern_gemm import (
    pack_tile_pattern_blocked as _pack_tile_blocked,
)
from repro.kernels.pattern_gemm import pattern_gemm as _pattern_gemm
from repro.runtime import profiler as _profiler
from repro.runtime import telemetry as _telemetry
from repro.sparse import tune as _tune
from repro.sparse.packed import PackedTensor
from repro.utils.registry import Registry

SPARSE_SCHEMES = Registry("sparse scheme")

# decode fast path: below this M the Pallas grid (and its M padding) costs
# more than it saves — dispatch a fused XLA gather+dot over the same
# compressed buffers instead. Decode has M = batch (1 token/slot).
SMALL_M = 32


def _block_of(n: int, cap: int = 128) -> int:
    """Largest power-of-two block <= cap that divides n (>=1)."""
    b = min(cap, n)
    while b > 1 and n % b:
        b //= 2
    return max(b, 1)


def _row_block(n: int, cap: int = 128) -> int:
    """Row-tile size for the activation M axis (rows are padded to it)."""
    return n if n <= cap else cap


def _dot_operands(interpret: bool, *ops: jnp.ndarray):
    """Interpret mode runs on the CPU backend, whose dot lacks
    BF16×BF16→F32: upcast there (exact — a bf16 product fits in f32).
    On a TPU the MXU takes bf16 with f32 accumulation as is."""
    if interpret and any(o.dtype == jnp.bfloat16 for o in ops):
        return tuple(o.astype(jnp.float32) for o in ops)
    return ops


def _pad_rows(x: jnp.ndarray, block: int):
    pad = (-x.shape[0]) % block
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    return x, pad


@dataclasses.dataclass(frozen=True)
class SchemeHandler:
    """One scheme's deployment triple: pack, packed matmul, dense reference.

    ``plan`` builds the jitted dispatch closure for one (pt, M, epilogue)
    geometry — ``dispatch_matmul`` memoizes what it returns. ``matmul``
    keeps the per-scheme call signature but delegates to the same
    plan-cached dispatch (there is one hot path, not two).
    """

    name: str
    # pack(w, spec) -> PackedTensor | None (None: leaf not packable, e.g.
    # shape not tiled by the scheme's blocks — caller keeps the dense leaf)
    pack: Callable[[jnp.ndarray, Any], Optional[PackedTensor]]
    # matmul(x (M, I), pt, bias=None, activation=None) -> (M, O)
    matmul: Callable[..., jnp.ndarray]
    # to_dense(pt) -> the exact dense (pruned) weight the buffers encode
    to_dense: Callable[[PackedTensor], jnp.ndarray]
    # conv(x (B, H, W, C), pt, bias=, activation=) -> (B, H, W, A)
    conv: Optional[Callable[..., jnp.ndarray]] = None
    # plan(pt, M, has_bias, activation, interpret, exec_plan=None)
    #   -> fn(x, pt, bias); exec_plan (a tune.Plan) forces one candidate —
    #   None resolves through tune.resolve / the heuristic default
    plan: Optional[Callable[..., Callable]] = None


def handler_for(scheme: str) -> SchemeHandler:
    """Resolve a scheme name; unpackable schemes fall back to ``dense``."""
    if scheme in SPARSE_SCHEMES:
        return SPARSE_SCHEMES.get(scheme)
    return SPARSE_SCHEMES.get("dense")


# ---------------------------------------------------------------------------
# plan cache: (scheme, geometry, M, dtype, epilogue) -> jitted closure
# ---------------------------------------------------------------------------

_PLAN_CACHE: Dict[Tuple, Callable] = {}

# trace-time dispatch accounting: every dispatch increments its
# (kind, scheme, M-bucket) counter; every plan BUILD also records the
# resolved implementation. Since dispatch runs at trace time inside jitted
# callers, counts are per compiled graph (dispatch sites), not per step —
# exactly the attribution --profile wants.
DISPATCH_STATS: "collections.Counter[str]" = collections.Counter()


def dispatch_stats() -> Dict[str, int]:
    return dict(DISPATCH_STATS)


def reset_dispatch_stats():
    DISPATCH_STATS.clear()


@contextlib.contextmanager
def dispatch_stats_scope():
    """Measure dispatches in isolation: snapshot the module counter,
    start the block from zero, and RESTORE the snapshot (plus whatever
    the block added) on exit — concurrent benches and tests each read
    only their own counts without clobbering each other's.

    Yields the live ``Counter``; read it inside the block (or call
    ``dispatch_stats()``)."""
    snap = collections.Counter(DISPATCH_STATS)
    DISPATCH_STATS.clear()
    try:
        yield DISPATCH_STATS
    finally:
        DISPATCH_STATS.update(snap)


def _count_dispatch(kind: str, pt: PackedTensor, M: int):
    small = int(pt.meta_dict.get("small_m", SMALL_M))
    bucket = _tune.m_bucket(M, small)
    DISPATCH_STATS[f"{kind}:{pt.scheme}:m{bucket}"] += 1
    # same event into the process-wide telemetry registry: one snapshot
    # covers kernel dispatch next to serve latency and prune health
    _telemetry.get_registry().counter(
        "sparse.dispatch_total", kind=kind, scheme=pt.scheme,
        bucket=bucket).inc()


def _count_plan_build(kind: str, pt: PackedTensor, plan: "_tune.Plan"):
    DISPATCH_STATS[f"plan_build:{kind}:{pt.scheme}:{plan.to_str()}"] += 1
    _telemetry.get_registry().counter(
        "sparse.plan_build_total", kind=kind, scheme=pt.scheme,
        plan=plan.to_str()).inc()


def _plan_label(pt: PackedTensor, kind: str, M: int) -> str:
    """Plan tag for profiler keys — meta lookup only (never triggers an
    autotune search from inside the profiling hook)."""
    plan = _tune.plan_from_meta(pt, kind, M)
    return plan.to_str() if plan is not None else "heuristic"


def _scoped(fn: Callable, scheme: str, impl: str) -> Callable:
    """``fn`` with its ops under ``packed/<scheme>/<impl>`` in the
    program's op_name metadata, so a profile names the plan that ran."""
    scope = f"packed/{scheme}/{impl}"

    @functools.wraps(fn)
    def run(*args, **kwargs):
        with jax.named_scope(scope):
            return fn(*args, **kwargs)

    return run


def _plan_key(pt: PackedTensor, M: int, dtype, has_bias: bool,
              activation: Optional[str], interpret: bool, kind: str) -> Tuple:
    bufs = tuple((n, tuple(b.shape), str(b.dtype))
                 for n, b in zip(pt.names, pt.buffers))
    return (kind, pt.scheme, pt.shape, pt.meta, bufs, M,
            str(dtype), has_bias, activation, interpret)


def dispatch_matmul(x: jnp.ndarray, pt: PackedTensor, *,
                    bias: Optional[jnp.ndarray] = None,
                    activation: Optional[str] = None,
                    interpret: Optional[bool] = None) -> jnp.ndarray:
    """y = act(x @ dense(pt) + bias) through the plan-cached packed kernel."""
    if interpret is None:
        interpret = _default_interpret()
    check_activation(activation)
    _count_dispatch("matmul", pt, x.shape[0])
    key = _plan_key(pt, x.shape[0], x.dtype, bias is not None, activation,
                    interpret, "matmul")
    fn = _PLAN_CACHE.get(key)
    if fn is None:
        handler = SPARSE_SCHEMES.get(pt.scheme)
        if handler.plan is None:
            raise TypeError(f"scheme {pt.scheme!r} has no matmul plan")
        fn = jax.jit(handler.plan(pt, x.shape[0], bias is not None,
                                  activation, interpret))
        # don't memoize a heuristic closure built while TRACING with
        # autotune pending (tune.resolve skips its search on tracers) —
        # a later eager dispatch of this geometry must still get to
        # search and cache the tuned closure
        if not _tune.resolution_deferred(pt, "matmul", x.shape[0],
                                         interpret):
            _PLAN_CACHE[key] = fn
    prof = _profiler.get_profiler()
    if prof.active and not isinstance(x, jax.core.Tracer):
        # eager dispatch only: under a jit trace this runs at TRACE time
        # (walling a tracer is meaningless and block_until_ready would
        # fail).  The wall adds a host sync, never a dispatch.
        return prof.wall_dispatch("matmul", pt, int(x.shape[0]),
                                  _plan_label(pt, "matmul", x.shape[0]),
                                  fn, (x, pt, bias))
    return fn(x, pt, bias)


def dispatch_conv(x: jnp.ndarray, pt: PackedTensor, *,
                  bias: Optional[jnp.ndarray] = None,
                  activation: Optional[str] = None,
                  interpret: Optional[bool] = None) -> jnp.ndarray:
    """Packed conv with fused epilogue (conv-shaped schemes only)."""
    if interpret is None:
        interpret = _default_interpret()
    check_activation(activation)
    _count_dispatch("conv", pt, int(np.prod(x.shape[:-1])))
    handler = SPARSE_SCHEMES.get(pt.scheme)
    if handler.conv is None:
        raise TypeError(f"scheme {pt.scheme!r} has no conv dispatch")
    prof = _profiler.get_profiler()
    if prof.active and not isinstance(x, jax.core.Tracer):
        fn = lambda x_, pt_, bias_: handler.conv(
            x_, pt_, bias=bias_, activation=activation, interpret=interpret)
        m = int(np.prod(x.shape[:-1]))
        return prof.wall_dispatch("conv", pt, m,
                                  _plan_label(pt, "conv", m), fn,
                                  (x, pt, bias))
    return handler.conv(x, pt, bias=bias, activation=activation,
                        interpret=interpret)


# ---------------------------------------------------------------------------
# dense fallback (irregular / filter / anything without a packed kernel)
# ---------------------------------------------------------------------------

def _dense_pack(w: jnp.ndarray, spec: Any) -> Optional[PackedTensor]:
    # Identity "packing": no compressed form exists for unstructured
    # sparsity on the MXU — by convention the caller keeps the raw leaf
    # (cheaper than a wrapper), so packing to dense returns None.
    return None


def _dense_plan(pt, M, has_bias, activation, interpret, exec_plan=None):
    # one implementation only: nothing to tune (exec_plan ignored)
    def fn(x, pt, bias):
        a, w = _dot_operands(interpret, x, pt.buf("w_packed"))
        y = jnp.dot(a, w, preferred_element_type=jnp.float32)
        return apply_epilogue(y, bias, activation).astype(x.dtype)

    return _scoped(fn, pt.scheme, "xla")


def _dense_matmul(x, pt, bias=None, *, activation=None, interpret=None):
    return dispatch_matmul(x, pt, bias=bias, activation=activation,
                           interpret=interpret)


def _dense_to_dense(pt):
    return pt.buf("w_packed")


SPARSE_SCHEMES.register(
    "dense",
    SchemeHandler("dense", _dense_pack, _dense_matmul, _dense_to_dense,
                  plan=_dense_plan),
)


# ---------------------------------------------------------------------------
# tile_pattern: keep-of-group_q contraction lanes per (group_q x block_p) tile
# ---------------------------------------------------------------------------

def _map_stacked(fn: Callable, w: jnp.ndarray, canonical_ndim: int):
    """Apply a per-matrix numpy pack over any leading stack axes.

    Returns a list of per-layer results (tuples of arrays) plus the stack
    shape, or (None, ()) when ``w`` is already canonical.
    """
    lead = w.shape[: w.ndim - canonical_ndim]
    if not lead:
        return None, ()
    flat = np.asarray(w).reshape((-1,) + w.shape[w.ndim - canonical_ndim:])
    return [fn(jnp.asarray(m)) for m in flat], lead


def _stack_packed(results, lead, names, scheme, shape, meta):
    bufs = []
    for i in range(len(names)):
        stacked = np.stack([np.asarray(r[i]) for r in results])
        bufs.append(jnp.asarray(stacked.reshape(lead + stacked.shape[1:])))
    return PackedTensor(scheme, shape, names, tuple(bufs), meta)


def _tile_pack(w: jnp.ndarray, spec: Any) -> Optional[PackedTensor]:
    """Pack a tile-pattern-pruned leaf (I, O) (or stacked (L, I, O)).

    Stores the BLOCKED (nb, Kp, block_p) weight layout and records the
    dispatch geometry in meta — layout and block sizes are decided here,
    once, not per matmul call.
    """
    block_p = spec.tile_block_p
    group_q = spec.tile_group_q
    keep = spec.tile_keep
    I, O = w.shape[-2], w.shape[-1]
    if I % group_q or O % block_p or keep >= group_q:
        return None
    meta = (("block_p", block_p), ("group_q", group_q), ("keep", keep),
            ("w_ndim", 3), ("small_m", SMALL_M))
    names = ("w_packed", "lane_idx")

    def one(m):
        return _pack_tile_blocked(
            m, block_p=block_p, group_q=group_q, keep=keep
        )

    results, lead = _map_stacked(one, w, 2)
    if results is None:
        wp, li = one(w)
        return PackedTensor("tile_pattern", tuple(w.shape), names,
                            (wp, li), meta)
    return _stack_packed(results, lead, names, "tile_pattern",
                         tuple(w.shape), meta)


def _tile_wpb(pt) -> jnp.ndarray:
    """Blocked (nb, Kp, bp) view of the panel buffer (handles the legacy
    flat (Kp, P) layout of artifacts packed before the geometry refactor)."""
    wp = pt.buf("w_packed")
    if pt.canonical_w_ndim == 3:
        return wp
    nb = pt.buf("lane_idx").shape[0]
    Kp, P = wp.shape
    return jnp.transpose(wp.reshape(Kp, nb, P // nb), (1, 0, 2))


def _tile_plan(pt, M, has_bias, activation, interpret, exec_plan=None):
    if pt.stacked:
        raise ValueError(
            "tile_pattern matmul wants per-layer buffers; scan over the "
            f"stacked leaf first (got w_packed {pt.buf('w_packed').shape})"
        )
    wpb = _tile_wpb(pt)
    nb, Kp, bp = wpb.shape
    P = nb * bp
    small_m = int(pt.meta_dict.get("small_m", SMALL_M))

    resolved = exec_plan is None
    if exec_plan is None:
        exec_plan = _tune.resolve(pt, "matmul", M, interpret=interpret)
    if exec_plan is None:
        # heuristic default: the fused XLA gather+dot wins at decode M
        # (no grid, no padding) and in interpret mode (the Pallas grid is
        # a Python loop there); real TPU prefill defaults to the kernel
        if M <= small_m or interpret:
            exec_plan = _tune.Plan("gather")
        else:
            exec_plan = _tune.Plan("pallas", block_m=_row_block(M))
    if resolved:
        # count only dispatch-resolved builds, not tuner candidate probes
        _count_plan_build("matmul", pt, exec_plan)

    if exec_plan.impl in ("gather", "gather_t", "gather_tb", "gather_e"):
        # fused XLA gather + dense dot over the blocked panels — no Pallas
        # grid, no M padding, CWS preserved (only w_packed bytes are
        # read). Valid at ANY M. The gather FORMULATIONS compete in the
        # tuner because XLA lowers them very differently (all
        # bit-identical — same kept values contracted in the same order):
        #   gather    — column gather of x (axis=1) + row-major dot;
        #   gather_t  — ROW gather of x.T (contiguous rows beat strided
        #               columns on most backends) + a dot_general
        #               contracting the leading axis (no materialized
        #               transpose);
        #   gather_tb — gather_t with the per-panel dots batched over nb;
        #   gather_e  — NO indexed gather at all: the lane selection is
        #               block-LOCAL (keep-of-group_q within each group),
        #               so it runs as a tiny batched einsum against an
        #               on-the-fly one-hot selector (M·nb·ng·group_q·keep
        #               mul-adds — vectorized, which scalarized backend
        #               gathers are not).
        impl = exec_plan.impl
        group_q = int(pt.meta_dict.get("group_q", 8))
        keep = int(pt.meta_dict.get("keep", Kp))
        Q = pt.shape[-2]
        ng = Q // group_q if group_q else 0
        if impl == "gather_e" and (not ng or ng * keep != Kp):
            impl = "gather"               # defensive: odd geometry

        def fn(x_in, pt, bias):
            x, wpb = _dot_operands(interpret, x_in, _tile_wpb(pt))
            li = pt.buf("lane_idx")
            if impl == "gather":
                if nb == 1:
                    xg = jnp.take(x, li[0], axis=1)
                    y = jnp.dot(xg, wpb[0],
                                preferred_element_type=jnp.float32)
                else:
                    xg = jnp.take(x, li.reshape(-1), axis=1)
                    xg = xg.reshape(M, nb, Kp)
                    y = jax.lax.dot_general(
                        xg, wpb, (((2,), (1,)), ((1,), (0,))),
                        preferred_element_type=jnp.float32)   # (nb, M, bp)
                    y = jnp.transpose(y, (1, 0, 2)).reshape(M, P)
            elif impl == "gather_e":
                # lane_idx rows live in group g's [g·group_q, (g+1)·group_q)
                # band; selecting them is a per-group (group_q → keep)
                # projection: S[n,g,l,j] = 1 iff group-local lane l is the
                # j-th kept lane of panel n — xg = x ⋅ S, one batched GEMM
                loc = (li.reshape(nb, ng, keep)
                       - (jnp.arange(ng, dtype=li.dtype) * group_q)[None, :,
                                                                    None])
                sel = jax.nn.one_hot(loc, group_q, dtype=x.dtype,
                                     axis=-1)                # (nb,ng,keep,gq)
                xg = jnp.einsum("mgl,ngjl->mngj",
                                x.reshape(M, ng, group_q), sel)
                if nb == 1:
                    y = jnp.dot(xg.reshape(M, Kp), wpb[0],
                                preferred_element_type=jnp.float32)
                else:
                    y = jax.lax.dot_general(
                        xg.reshape(M, nb, Kp), wpb,
                        (((2,), (1,)), ((1,), (0,))),
                        preferred_element_type=jnp.float32)   # (nb, M, bp)
                    y = jnp.transpose(y, (1, 0, 2)).reshape(M, P)
            elif impl == "gather_t" or nb == 1:
                xT = x.T
                ys = [jax.lax.dot_general(
                        jnp.take(xT, li[j], axis=0), wpb[j],
                        (((0,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32)
                      for j in range(nb)]
                y = ys[0] if nb == 1 else jnp.concatenate(ys, axis=1)
            else:                                         # gather_tb
                g = jnp.take(x.T, li.reshape(-1), axis=0).reshape(nb, Kp, M)
                y = jax.lax.dot_general(
                    g, wpb, (((1,), (1,)), ((0,), (0,))),
                    preferred_element_type=jnp.float32)       # (nb, M, bp)
                y = jnp.transpose(y, (1, 0, 2)).reshape(M, P)
            return apply_epilogue(y, bias, activation).astype(x_in.dtype)

        return _scoped(fn, pt.scheme, impl)

    bm = exec_plan.block_m or _row_block(M)
    if bm > M:                    # don't pad M past one row tile
        bm = _row_block(M)
    go = exec_plan.grid
    pad = (-M) % bm

    def fn(x, pt, bias):
        xp = jnp.pad(x, ((0, pad), (0, 0))) if pad else x
        y = _pattern_gemm(xp, _tile_wpb(pt), pt.buf("lane_idx"), bias,
                          block_m=bm, interpret=interpret,
                          activation=activation, grid_order=go)
        return y[:M] if pad else y

    return _scoped(fn, pt.scheme, "pallas")


def _tile_matmul(x, pt, bias=None, *, activation=None, interpret=None):
    return dispatch_matmul(x, pt, bias=bias, activation=activation,
                           interpret=interpret)


def _stacked_to_dense(one_fn, bufs, canonical_ndim: int = 2):
    """vmap a per-layer to_dense over any leading stack axes (jit-safe)."""
    extra = bufs[0].ndim - canonical_ndim
    fn = one_fn
    for _ in range(extra):
        fn = jax.vmap(fn)
    return fn(*bufs)


def _tile_to_dense(pt):
    """Exact dense reconstruction, pure jnp (usable inside jit)."""
    Q = pt.shape[-2]

    def one(wpb, li):                       # (nb, Kp, bp), (nb, Kp)
        nb, Kp, bp = wpb.shape
        onehot = jax.nn.one_hot(li, Q, dtype=wpb.dtype)       # (nb, Kp, Q)
        dense = jnp.einsum("jkq,jkb->qjb", onehot, wpb)
        return dense.reshape(Q, nb * bp).astype(wpb.dtype)

    if pt.canonical_w_ndim == 3:
        return _stacked_to_dense(one, (pt.buf("w_packed"),
                                       pt.buf("lane_idx")), 3)

    def one_flat(wp, li):                   # legacy flat (Kp, P) layout
        Kp, P = wp.shape
        nb = li.shape[0]
        return one(jnp.transpose(wp.reshape(Kp, nb, P // nb), (1, 0, 2)), li)

    return _stacked_to_dense(one_flat, (pt.buf("w_packed"),
                                        pt.buf("lane_idx")), 2)


SPARSE_SCHEMES.register(
    "tile_pattern",
    SchemeHandler("tile_pattern", _tile_pack, _tile_matmul, _tile_to_dense,
                  plan=_tile_plan),
)


# ---------------------------------------------------------------------------
# column: whole contraction rows pruned (paper Eqn. 15 / connectivity Eqn. 18)
# ---------------------------------------------------------------------------

def _column_pack(w: jnp.ndarray, spec: Any) -> Optional[PackedTensor]:
    """Pack a column-pruned leaf (I, O): keep surviving contraction rows.

    Stacked leaves may keep different row COUNTS per layer (top-k ties);
    the pack pads every layer to the max count with index-0 rows of zero
    weight — zero rows contribute nothing, so the packed matmul is exact.
    Kernel geometry (block_p over O, block_k over K) is chosen here.
    """
    group = spec.column_group
    O = w.shape[-1]
    meta = (("group", group), ("block_p", _block_of(O)),
            ("small_m", SMALL_M))
    names = ("w_packed", "kept_idx")

    def one(m):
        return _pack_columns(m, group=group)

    results, lead = _map_stacked(one, w, 2)
    if results is None:
        wp, kept = one(w)
        if kept.shape[0] >= w.shape[0]:
            return None                          # nothing pruned: stay dense
        return PackedTensor("column", tuple(w.shape), names, (wp, kept), meta)
    kmax = max(r[1].shape[0] for r in results)
    if kmax >= w.shape[-2]:
        return None
    padded = []
    for wp, kept in results:
        pad = kmax - kept.shape[0]
        if pad:
            wp = jnp.pad(wp, ((0, pad), (0, 0)))
            kept = jnp.pad(kept, (0, pad))
        padded.append((wp, kept))
    return _stack_packed(padded, lead, names, "column", tuple(w.shape), meta)


def _column_plan(pt, M, has_bias, activation, interpret, exec_plan=None):
    wp = pt.buf("w_packed")
    if wp.ndim != 2:
        raise ValueError(
            "column matmul wants per-layer buffers; scan over the "
            f"stacked leaf first (got w_packed {wp.shape})"
        )
    small_m = int(pt.meta_dict.get("small_m", SMALL_M))

    resolved = exec_plan is None
    if exec_plan is None:
        exec_plan = _tune.resolve(pt, "matmul", M, interpret=interpret)
    if exec_plan is None:
        if M <= small_m or interpret:
            exec_plan = _tune.Plan("gather")
        else:
            exec_plan = _tune.Plan("pallas", block_m=_row_block(M))
    if resolved:
        _count_plan_build("matmul", pt, exec_plan)

    if exec_plan.impl in ("gather", "gather_t"):
        # gather the surviving features, one dense dot — valid at any M.
        # gather_t gathers ROWS of x.T instead of columns of x (contiguous
        # rows beat strided columns) and contracts the leading axis.
        impl = exec_plan.impl

        def fn(x_in, pt, bias):
            x, w = _dot_operands(interpret, x_in, pt.buf("w_packed"))
            if impl == "gather":
                xg = jnp.take(x, pt.buf("kept_idx"), axis=1)
                y = jnp.dot(xg, w, preferred_element_type=jnp.float32)
            else:
                g = jnp.take(x.T, pt.buf("kept_idx"), axis=0)   # (K, M)
                y = jax.lax.dot_general(
                    g, w, (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
            return apply_epilogue(y, bias, activation).astype(x_in.dtype)

        return _scoped(fn, pt.scheme, impl)

    bp = (exec_plan.block_p
          or int(pt.meta_dict.get("block_p", 0))
          or _block_of(wp.shape[-1]))
    bk = exec_plan.block_k or 512
    bm = exec_plan.block_m or _row_block(M)
    if bm > M:
        bm = _row_block(M)
    go = exec_plan.grid
    pad = (-M) % bm

    def fn(x, pt, bias):
        xp = jnp.pad(x, ((0, pad), (0, 0))) if pad else x
        y = _column_gemm(xp, pt.buf("w_packed"), pt.buf("kept_idx"), bias,
                         block_m=bm, block_p=bp, block_k=bk,
                         interpret=interpret, activation=activation,
                         grid_order=go)
        return y[:M] if pad else y

    return _scoped(fn, pt.scheme, "pallas")


def _column_matmul(x, pt, bias=None, *, activation=None, interpret=None):
    return dispatch_matmul(x, pt, bias=bias, activation=activation,
                           interpret=interpret)


def _column_to_dense(pt):
    """Exact dense reconstruction, pure jnp (usable inside jit)."""
    w_packed, kept = pt.buf("w_packed"), pt.buf("kept_idx")

    def one(wp, ki):
        I = pt.shape[-2]
        # scatter-by-onehot: padded rows are zero-weight duplicates of
        # index 0, so the additive scatter stays exact
        onehot = jax.nn.one_hot(ki, I, dtype=wp.dtype)        # (K, I)
        return jnp.einsum("ki,ko->io", onehot, wp).astype(wp.dtype)

    return _stacked_to_dense(one, (w_packed, kept))


SPARSE_SCHEMES.register(
    "column",
    SchemeHandler("column", _column_pack, _column_matmul, _column_to_dense,
                  plan=_column_plan),
)


# ---------------------------------------------------------------------------
# pattern: 3x3 conv kernels with channel-shared tap patterns (paper SIV-D-4)
# ---------------------------------------------------------------------------

def _pattern_pack(w4: jnp.ndarray, spec: Any) -> Optional[PackedTensor]:
    """Pack a pattern-pruned conv (A, C, 3, 3) with channel-shared taps.

    The Pallas pattern-conv kernel requires the SAME tap set for every
    filter of a channel (the FKR grouping). Per-kernel pattern pruning can
    violate that, so the pack derives each channel's tap UNION across
    filters and only packs when it fits ``pattern_keep`` taps — otherwise
    the leaf stays dense (the caller's fallback). Channels fully removed by
    connectivity pruning pack as zero-weight taps.
    """
    if w4.ndim != 4 or w4.shape[-2:] != (3, 3):
        return None
    keep = spec.pattern_keep
    wf = np.asarray(w4)
    A, C = wf.shape[0], wf.shape[1]
    nz = (wf != 0).any(axis=0).reshape(C, 9)          # (C, 9)
    if (nz.sum(axis=1) > keep).any():
        return None                  # taps not channel-shared: unpackable
    taps = np.zeros((C, keep), np.int32)
    w_packed = np.zeros((C * keep, A), wf.dtype)
    for c in range(C):
        t = np.nonzero(nz[c])[0]
        taps[c, : t.shape[0]] = t    # remaining slots: tap 0 with zero weight
        w_packed[c * keep: c * keep + t.shape[0], :] = (
            wf[:, c, t // 3, t % 3].T
        )
    return PackedTensor(
        "pattern", tuple(w4.shape), ("w_packed", "taps"),
        (jnp.asarray(w_packed), jnp.asarray(taps)),
        (("keep", keep),),
    )


def conv_gemm_runner(pt, plan, *, interpret: bool,
                     activation: Optional[str] = None) -> Callable:
    """fn(xg, w_packed) for one conv-GEMM plan (the tuner's timing unit).

    ``xla`` runs the gathered-taps GEMM as one XLA dot (+ fp32 epilogue);
    ``pallas`` runs ``pattern_conv_gemm`` with the plan's block_m. Both
    contract the same K values in the same order — bit-identical.
    """
    if plan.impl == "xla":
        def fn(xg, w, bias=None):
            a, b = _dot_operands(interpret, xg, w)
            y = jnp.dot(a, b, preferred_element_type=jnp.float32)
            return apply_epilogue(y, bias, activation).astype(xg.dtype)

        return fn

    bm = plan.block_m or 256
    bk = plan.block_k or 512
    go = plan.grid

    def fn(xg, w, bias=None):
        return _pattern_conv_gemm(xg, w, bias, block_m=bm, block_k=bk,
                                  interpret=interpret, activation=activation,
                                  grid_order=go)

    return fn


def _pattern_conv(x, pt, bias=None, *, activation=None, interpret=None):
    """Stride-1 SAME 3x3 pattern conv: x (B, H, W, C) -> (B, H, W, A).

    The tap gather (LRE) always runs in XLA; the hot GEMM resolves its
    plan like the matmul path — persisted/tuned plan per M-bucket, else
    XLA dot in interpret mode and the Pallas kernel on TPU.
    """
    if interpret is None:
        interpret = _default_interpret()
    B, H, W, C = x.shape
    M = B * H * W
    plan = _tune.resolve(pt, "conv", M, interpret=interpret)
    if plan is None:
        plan = _tune.Plan("xla") if interpret else _tune.Plan("pallas")
    # no conv plan cache exists: dispatch_conv's _count_dispatch already
    # counts traced conv dispatches, so no plan_build event here
    with jax.named_scope(f"packed/{pt.scheme}/{plan.impl}"):
        xg = _gather_taps(x, pt.buf("taps"))
        run = conv_gemm_runner(pt, plan, interpret=interpret,
                               activation=activation)
        y = run(xg, pt.buf("w_packed"), bias)
    return y.reshape(B, H, W, -1)


def _pattern_matmul(x, pt, bias=None, *, activation=None, interpret=None):
    raise TypeError(
        "scheme 'pattern' packs a conv tensor; use conv dispatch "
        "(models.cnn.conv_apply), not a GEMM matmul"
    )


def _pattern_to_dense(pt):
    """Exact dense reconstruction, pure jnp (usable inside jit)."""
    wp, taps = pt.buf("w_packed"), pt.buf("taps")
    A, C = pt.shape[0], pt.shape[1]
    keep = taps.shape[1]
    # zero-weight pad slots scatter zeros: harmless even on tap 0
    onehot = jax.nn.one_hot(taps, 9, dtype=wp.dtype)          # (C, keep, 9)
    wck = wp.reshape(C, keep, A)
    dense = jnp.einsum("ckt,cka->act", onehot, wck)
    return dense.reshape(A, C, 3, 3).astype(wp.dtype)


SPARSE_SCHEMES.register(
    "pattern",
    SchemeHandler("pattern", _pattern_pack, _pattern_matmul,
                  _pattern_to_dense, conv=_pattern_conv),
)

# pattern_shared (channel-shared library patterns, the packable deployment
# composition) packs through the same handler — its pack ALWAYS succeeds
# because the projection enforces channel-shared taps; plain `pattern`
# (per-kernel top-4) packs only when the taps happen to be channel-shared.
SPARSE_SCHEMES.register(
    "pattern_shared",
    SchemeHandler("pattern_shared", _pattern_pack, _pattern_matmul,
                  _pattern_to_dense, conv=_pattern_conv),
)
