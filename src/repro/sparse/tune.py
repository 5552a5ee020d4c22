"""Autotuner + persistent execution-plan cache for packed dispatch.

The paper's deployment story is COMPILER-level: the compressed layout ships
with a tuned execution plan (PatDNN's compile-time block/unroll search),
so serving never pays a search or a heuristic miss. This module is that
search for the Pallas/XLA packed kernels:

  * a ``Plan`` names one concrete execution strategy for a packed GEMM or
    conv — the implementation (``pallas`` grid vs fused XLA ``gather``/
    ``xla`` dot over the SAME compressed buffers) plus the Pallas tile
    geometry (``block_m``/``block_p``/``block_k``) and grid order
    (``mp`` rows-resident vs ``pm`` panels-resident);
  * ``tune_plan`` times the candidate plans for one (PackedTensor,
    M-bucket) and returns the winner;
  * the winner PERSISTS: ``tune_packed_tree`` (used by
    ``PrunedArtifact.tune`` / ``pack(tune_for=...)``) records it in
    ``PackedTensor.meta`` under ``plan:<kind>:m<bucket>``, which rides the
    artifact manifest through save/load — re-serving a saved artifact
    skips the search entirely;
  * ``resolve`` is the registry's seam: meta plan → in-process tuned
    cache → (optionally, ``REPRO_AUTOTUNE=1``) a first-dispatch search —
    otherwise ``None`` and the per-backend heuristic default applies.

M-BUCKETS: plans are keyed by the power-of-two bucket of M (floored at
``small_m``), not exact M — decode (M = batch) and prefill (M = batch ×
prompt) land in different buckets and get independently tuned plans, while
nearby prompt lengths share one.

CORRECTNESS CONTRACT: every candidate computes bit-identical results (all
impls contract the same kept values in the same order with fp32
accumulation — zeros never participate), so tuning can never change
served tokens, only their latency. ``tests/test_tune.py`` enforces this.
"""

from __future__ import annotations

import dataclasses
import logging
import os
import time
from typing import Any, Dict, Iterable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.sparse.packed import PackedTensor, is_packed

# matmul schemes tuned through SchemeHandler.plan; conv schemes through the
# pattern-conv GEMM candidates below
_MATMUL_SCHEMES = ("tile_pattern", "column")
_CONV_SCHEMES = ("pattern", "pattern_shared")

_DEFAULT_SMALL_M = 32

# gather_t emits one gather+dot per output panel: past this many panels
# (an lm_head over a 150k vocabulary has ~1,200) compiling the unrolled
# program costs more than any timing it could win
_UNROLLED_PANELS_MAX = 128

_log = logging.getLogger(__name__)


# ---------------------------------------------------------------------------
# Plan: one execution strategy, serializable to a flat meta/manifest string
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Plan:
    """One candidate execution plan for a packed GEMM/conv.

    ``impl``:
      pallas — the tiled Pallas kernel (``pattern_gemm``/``column_gemm``/
               ``pattern_conv_gemm``) with this plan's tile geometry;
      gather — fused XLA gather + dense dot over the same compressed
               buffers (no Pallas grid, no M padding);
      xla    — plain XLA dot on already-gathered operands (conv GEMM).

    Zero-valued block fields mean "use the pack-time/per-call default".
    Serialized as a flat string (``pallas:bm=256:go=pm``) because it lives
    inside ``PackedTensor.meta``, which must stay hashable and must
    round-trip through the JSON checkpoint manifest.
    """

    impl: str
    block_m: int = 0
    block_p: int = 0
    block_k: int = 0
    grid: str = "mp"

    def to_str(self) -> str:
        if self.impl != "pallas":
            return self.impl
        parts = [self.impl]
        for tag, val in (("bm", self.block_m), ("bp", self.block_p),
                         ("bk", self.block_k)):
            if val:
                parts.append(f"{tag}={val}")
        if self.grid != "mp":
            parts.append(f"go={self.grid}")
        return ":".join(parts)

    @classmethod
    def from_str(cls, s: str) -> "Plan":
        parts = s.split(":")
        kw: Dict[str, Any] = {}
        names = {"bm": "block_m", "bp": "block_p", "bk": "block_k",
                 "go": "grid"}
        for p in parts[1:]:
            tag, val = p.split("=")
            kw[names[tag]] = val if tag == "go" else int(val)
        return cls(parts[0], **kw)


# ---------------------------------------------------------------------------
# M-buckets and meta keys
# ---------------------------------------------------------------------------

def m_bucket(M: int, small_m: int = _DEFAULT_SMALL_M) -> int:
    """Power-of-two bucket of M, floored at the decode threshold."""
    b = max(int(small_m), 1)
    while b < M:
        b <<= 1
    return b


def plan_meta_key(kind: str, bucket: int) -> str:
    return f"plan:{kind}:m{bucket}"


def _small_m_of(pt: PackedTensor) -> int:
    return int(pt.meta_dict.get("small_m", _DEFAULT_SMALL_M))


def plan_from_meta(pt: PackedTensor, kind: str, M: int) -> Optional[Plan]:
    """The persisted plan for this (kind, M-bucket), if one was tuned."""
    s = pt.meta_dict.get(plan_meta_key(kind, m_bucket(M, _small_m_of(pt))))
    return Plan.from_str(s) if isinstance(s, str) else None


def plans_in_meta(pt: PackedTensor) -> Dict[str, str]:
    """All persisted plan entries of a packed leaf (for reporting)."""
    return {k: v for k, v in pt.meta_dict.items() if k.startswith("plan:")}


# ---------------------------------------------------------------------------
# resolve(): the registry's lookup chain
# ---------------------------------------------------------------------------

# in-process winners from first-dispatch autotuning (REPRO_AUTOTUNE=1):
# geometry-keyed so every later plan build with the same shape skips the
# search. Persisted plans (PackedTensor.meta) take precedence.
_TUNED: Dict[Tuple, str] = {}


def autotune_enabled() -> bool:
    return os.environ.get("REPRO_AUTOTUNE", "0") not in ("", "0", "false")


def _tuned_key(pt: PackedTensor, kind: str, M: int, interpret: bool) -> Tuple:
    bufs = tuple((n, tuple(b.shape), str(b.dtype))
                 for n, b in zip(pt.names, pt.buffers))
    return (kind, pt.scheme, pt.shape, bufs,
            m_bucket(M, _small_m_of(pt)), interpret)


def _tuned_for_interpret(pt: PackedTensor) -> Optional[bool]:
    """Which execution mode the leaf's persisted plans were tuned in."""
    mode = pt.meta_dict.get("plan_mode")
    if mode == "interpret":
        return True
    if mode == "compiled":
        return False
    return None


def resolve(pt: PackedTensor, kind: str, M: int, *,
            interpret: bool) -> Optional[Plan]:
    """Plan for one dispatch: meta → in-process cache → optional search.

    Returns ``None`` when nothing was tuned and first-dispatch autotuning
    is off — the registry then applies its per-backend heuristic default.
    Persisted plans are consulted only when the artifact was tuned in the
    SAME execution mode (``plan_mode`` meta): a CPU-tuned artifact must
    not pin a real TPU to the gather path (or vice versa force the
    Python-interpreted Pallas grid) — the heuristic default is better
    than a plan timed on different hardware.
    """
    tuned_interp = _tuned_for_interpret(pt)
    if tuned_interp is None or tuned_interp == interpret:
        plan = plan_from_meta(pt, kind, M)
        if plan is not None:
            return plan
    key = _tuned_key(pt, kind, M, interpret)
    s = _TUNED.get(key)
    if s is not None:
        return Plan.from_str(s)
    if not autotune_enabled():
        return None
    if any(isinstance(b, jax.core.Tracer) for b in pt.buffers):
        # first dispatch happened while TRACING a jitted caller: the
        # candidate runs would inline into the outer trace (timings of
        # tracing overhead, dead computations in the graph). Skip the
        # search; the heuristic default applies. Pack-time tuning
        # (PrunedArtifact.pack(tune_for=...)) is the supported path for
        # jitted serving.
        return None
    plan, _ = tune_plan(pt, kind, M, interpret=interpret)
    if plan is not None:
        _TUNED[key] = plan.to_str()
    return plan


def clear_tuned_cache():
    _TUNED.clear()


def resolution_deferred(pt: PackedTensor, kind: str, M: int,
                        interpret: bool) -> bool:
    """True when a first-dispatch search WOULD run but cannot yet: autotune
    is on, nothing is tuned for this geometry, and the dispatch is being
    traced (the tracer guard in ``resolve`` skips the search). Callers
    should not memoize the heuristic closure in that case, so a later
    eager dispatch of the same geometry still gets to search."""
    if not autotune_enabled():
        return False
    if not any(isinstance(b, jax.core.Tracer) for b in pt.buffers):
        return False
    tuned_interp = _tuned_for_interpret(pt)
    if ((tuned_interp is None or tuned_interp == interpret)
            and plan_from_meta(pt, kind, M) is not None):
        return False
    return _tuned_key(pt, kind, M, interpret) not in _TUNED


# ---------------------------------------------------------------------------
# candidate plans per (scheme, kind, M)
# ---------------------------------------------------------------------------

def candidate_plans(pt: PackedTensor, kind: str, M: int,
                    interpret: bool = False) -> List[Plan]:
    """The search space: small by design (a handful of plans per bucket).

    In interpret mode (no TPU) the Pallas grid is a Python-simulated
    correctness tool, not a deployment path — its standalone timings do
    not transfer to the jitted graph, so only the fused-XLA impls compete
    there. On real TPU backends the full (impl × block_m × block_k ×
    grid-order) space is searched.
    """
    if kind == "conv":
        cands = [Plan("xla")]
        if interpret:
            return cands
        for bm in (128, 256, 512):
            for go in ("mp", "pm"):
                cands.append(Plan("pallas", block_m=bm, grid=go))
        return cands
    # In interpret mode (no TPU) exactly ONE deployment-grade impl exists
    # — the fused XLA gather+dot. The serving engine bakes the weights
    # into the prefill executable there (ServeEngine bake_weights), which
    # makes the index tables static and the plain gather the best-lowered
    # formulation; candidate variants timed UNBAKED rank by box noise and
    # would poison the persisted plan. On real TPU backends the full
    # space competes: the Pallas grids plus the gather FORMULATION
    # variants (strided column gather, contiguous row gather, batched vs
    # unrolled panel dots — XLA lowers each very differently).
    if interpret:
        return [Plan("gather")]
    if pt.scheme == "tile_pattern":
        cands = [Plan("gather"), Plan("gather_e")]
        nb = pt.buf("lane_idx").shape[-2] if pt.buf(
            "lane_idx").ndim >= 2 else 1
        if nb <= _UNROLLED_PANELS_MAX:
            cands.append(Plan("gather_t"))
        if nb > 1:
            cands.append(Plan("gather_tb"))
    else:
        cands = [Plan("gather"), Plan("gather_t")]
    bms: List[int] = []
    for bm in (128, 256):
        if bm <= max(M, 128) and bm not in bms:
            bms.append(bm)
    if pt.scheme == "tile_pattern":
        for bm in bms:
            for go in ("mp", "pm"):
                cands.append(Plan("pallas", block_m=bm, grid=go))
    elif pt.scheme == "column":
        K = pt.buf("w_packed").shape[-2]
        bks = sorted({min(256, K), min(512, K)})
        for bm in bms:
            for bk in bks:
                for go in ("mp", "pm"):
                    cands.append(Plan("pallas", block_m=bm, block_k=bk,
                                      grid=go))
    return cands


# ---------------------------------------------------------------------------
# timing
# ---------------------------------------------------------------------------

def _time_candidates(fns: Dict[str, Any], iters: int) -> Dict[str, float]:
    """Median seconds per candidate, timed in INTERLEAVED rounds.

    Candidates are warmed up first (compile excluded), each sample spans
    enough repetitions to clear the per-call dispatch floor, and every
    timing round cycles through ALL candidates before the next — a load
    spike on the box hits every candidate equally instead of whichever
    one was being timed sequentially.
    """
    reps: Dict[str, int] = {}
    for name, fn in fns.items():
        jax.block_until_ready(fn())                  # compile
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        dt = time.perf_counter() - t0
        reps[name] = max(1, min(64, int(1e-3 / max(dt, 1e-6))))
    samples: Dict[str, list] = {n: [] for n in fns}
    for _ in range(max(iters, 1)):
        for name, fn in fns.items():
            t0 = time.perf_counter()
            out = None
            for _ in range(reps[name]):
                out = fn()
            jax.block_until_ready(out)
            samples[name].append((time.perf_counter() - t0) / reps[name])
    return {n: float(np.median(ts)) for n, ts in samples.items()}


def canonical_slice(pt: PackedTensor) -> PackedTensor:
    """Layer-0 slice of a scan-stacked leaf (plans apply to every layer —
    all layers of a stacked leaf share one geometry). Public: the
    profiler/attribution layer dispatches per-layer views of stacked
    leaves exactly as the serve scan does."""
    return _canonical_slice(pt)


def _canonical_slice(pt: PackedTensor) -> PackedTensor:
    n = pt.stacked
    if not n:
        return pt
    idx = (0,) * n
    return PackedTensor(pt.scheme, pt.shape[n:], pt.names,
                        tuple(b[idx] for b in pt.buffers), pt.meta)


def _candidate_failed(pt: PackedTensor, kind: str, M: int, plan: Plan,
                      err: Exception) -> float:
    """Log and count a candidate that failed to build or run."""
    from repro.runtime.telemetry import get_registry

    get_registry().counter("tune.candidate_failures_total", kind=kind,
                           scheme=pt.scheme, plan=plan.to_str()).inc()
    first = str(err).strip().splitlines()[0] if str(err).strip() else ""
    _log.warning("tune: %s %s candidate %s failed at M=%d: %s: %s", kind,
                 pt.scheme, plan.to_str(), M, type(err).__name__, first)
    return -1.0


def tune_plan(pt: PackedTensor, kind: str, M: int, *,
              interpret: Optional[bool] = None, iters: int = 3,
              ) -> Tuple[Optional[Plan], Dict[str, float]]:
    """Time every candidate plan; return (winner, per-plan median ms).

    Timing uses a bias/activation-free GEMM as the proxy for all epilogue
    variants of the bucket (the epilogue cost is plan-invariant). A
    candidate that fails to build or run is logged, counted in
    ``tune.candidate_failures_total{plan}`` and recorded as -1 in the
    report; it never wins.
    """
    from repro.kernels.ops import _default_interpret
    from repro.sparse import registry as reg

    if interpret is None:
        interpret = _default_interpret()
    pt = _canonical_slice(pt)
    report: Dict[str, float] = {}
    best: Optional[Plan] = None
    best_t = float("inf")
    rng = np.random.default_rng(0)

    fns: Dict[str, Any] = {}
    if kind == "conv":
        w = pt.buf("w_packed")
        K, A = w.shape
        xg = jnp.asarray(rng.standard_normal((M, K)), w.dtype)
        for c in candidate_plans(pt, kind, M, interpret):
            try:
                fn = jax.jit(reg.conv_gemm_runner(pt, c,
                                                  interpret=interpret))
                jax.block_until_ready(fn(xg, w))           # builds + runs
            except Exception as e:
                report[c.to_str()] = _candidate_failed(pt, kind, M, c, e)
                continue
            fns[c.to_str()] = (lambda fn=fn: fn(xg, w))
    else:
        handler = reg.SPARSE_SCHEMES.get(pt.scheme)
        if handler.plan is None:
            return None, report
        x = jnp.asarray(rng.standard_normal((M, pt.shape[-2])), pt.dtype)
        for c in candidate_plans(pt, kind, M, interpret):
            try:
                fn = jax.jit(handler.plan(pt, M, False, None, interpret,
                                          exec_plan=c))
                jax.block_until_ready(fn(x, pt, None))
            except Exception as e:
                report[c.to_str()] = _candidate_failed(pt, kind, M, c, e)
                continue
            fns[c.to_str()] = (lambda fn=fn: fn(x, pt, None))
    from repro.runtime.telemetry import get_registry

    with get_registry().timer("tune.search_seconds", kind=kind,
                              scheme=pt.scheme):
        timed = _time_candidates(fns, iters)
    get_registry().counter("tune.candidates_total", kind=kind,
                           scheme=pt.scheme).inc(len(fns))
    for name, t in timed.items():
        report[name] = round(t * 1e3, 4)
        if t < best_t:
            best, best_t = Plan.from_str(name), t
    return best, report


# ---------------------------------------------------------------------------
# tree-level tuning (pack-time entry point)
# ---------------------------------------------------------------------------

def tune_packed_tree(tree: Any, ms: Iterable[int], *,
                     interpret: Optional[bool] = None, iters: int = 3,
                     ) -> Tuple[Any, Dict[str, Any]]:
    """Tune every packable leaf for the given M values; bake plans into meta.

    ``ms`` are GEMM row counts to serve (decode: batch; prefill: batch ×
    prompt; conv: batch × H × W), deduplicated by bucket. Returns
    (new tree, report) where the report maps ``<leaf path>:<meta key>`` to
    the winning plan and the per-candidate timings — the artifact stores
    it as ``meta['tuned_plans']`` so the manifest documents its own plans.
    """
    from repro.kernels.ops import _default_interpret
    from repro.utils.tree import tree_map_with_path_str

    if interpret is None:
        interpret = _default_interpret()
    ms = tuple(int(m) for m in ms)    # materialize: iterated once PER LEAF
    report: Dict[str, Any] = {}

    def leaf(path: str, x):
        if not is_packed(x):
            return x
        if x.scheme in _MATMUL_SCHEMES:
            kind = "matmul"
        elif x.scheme in _CONV_SCHEMES:
            kind = "conv"
        else:
            return x
        small = _small_m_of(x)
        meta = [kv for kv in x.meta]
        seen = set()
        wrote = False
        for M in ms:
            M = int(M)
            bucket = m_bucket(M, small)
            if M <= 0 or bucket in seen:
                continue
            seen.add(bucket)
            plan, times = tune_plan(x, kind, M, interpret=interpret,
                                    iters=iters)
            if plan is None:
                continue
            key = plan_meta_key(kind, bucket)
            _log.info("tune: %s %s -> %s (candidate ms %s)", path, key,
                      plan.to_str(), times)
            meta = [kv for kv in meta if kv[0] != key]
            meta.append((key, plan.to_str()))
            wrote = True
            report[f"{path}:{key}"] = {"plan": plan.to_str(),
                                       "candidates_ms": times}
        if wrote:
            # stamp the execution mode the plans were timed in: resolve()
            # ignores them when serving in the other mode (CPU-tuned
            # artifacts never pin a TPU, and vice versa)
            meta = [kv for kv in meta if kv[0] != "plan_mode"]
            meta.append(("plan_mode",
                         "interpret" if interpret else "compiled"))
        return dataclasses.replace(x, meta=tuple(meta))

    new_tree = tree_map_with_path_str(leaf, tree, is_leaf=is_packed)
    return new_tree, report


def describe_plans(tree: Any) -> Dict[str, Dict[str, str]]:
    """Per-leaf persisted plan table (for ``--profile`` reporting)."""
    from repro.utils.tree import tree_map_with_path_str

    out: Dict[str, Dict[str, str]] = {}

    def leaf(path, x):
        if is_packed(x):
            plans = plans_in_meta(x)
            if plans:
                out[path] = plans
        return x

    tree_map_with_path_str(leaf, tree, is_leaf=is_packed)
    return out
