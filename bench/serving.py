"""Serving cells: prune → pack → ``ContinuousEngine.stream`` on one chip.

Set-up (timed as ``setup_s``): the weights of the seed on the device
(made by the configuration's architecture module, ``bench/archs``), the
one-shot projection (``greedy_prune``, one leaf at a time so that a leaf
and its copy are the only doubled bytes), packing with the registry's
default plans (no tuning), the engine, and a warm-up that compiles each
prompt bucket's admission program and every decode scan length up to
``chunk_steps``.

Window: the traffic's requests through ``ContinuousEngine.stream`` on the
benchmark's clock. An open-loop mix stops arriving at the window's end and
drains; a request still outstanding ``drain_cap_s`` after the window is cut
and counts as failed. A batch mix is cut at the window's end, and what the
cut requests emitted counts.

Check (after the window, with the program's state freed): a sample drawn
from the seed of the finished requests, the longest among them, each run
once through the float32 reference over its prompt and served tokens.
"""

from __future__ import annotations

import dataclasses
import gc
import io
import json
import shutil
import tempfile
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import numpy as np

from bench import reference, spec, traffic

TRACE_MARK = "bench_clock"


@dataclasses.dataclass
class Outcome:
    uid: int
    prompt_len: int
    max_new: int
    arrival: float
    status: str = "missing"
    tokens: List[int] = dataclasses.field(default_factory=list)
    t_first: Optional[float] = None       # first token, benchmark clock
    t_done: Optional[float] = None        # result received, benchmark clock


@dataclasses.dataclass
class Served:
    """What set-up leaves for the window."""

    engine: Any
    shapes: Any                 # the architecture module's ``Shapes``
    arch: Any                   # the configuration's architecture module


def prune_leafwise(params: Dict[str, Any], pcfg) -> Tuple[Any, Any]:
    """``greedy_prune`` of every leaf on its own, under its own path.

    Returns (pruned params, specs). Each source leaf is dropped as soon as
    its pruned copy exists, so the device holds one doubled leaf at a time.
    """
    from repro.core import greedy_prune

    def walk(tree, path):
        out_p, out_s = {}, {}
        for k in list(tree):
            v = tree.pop(k)
            if isinstance(v, dict):
                out_p[k], out_s[k] = walk(v, path + (k,))
                continue
            sub: Dict[str, Any] = {}
            node = sub
            for p in path:
                node = node.setdefault(p, {})
            node[k] = v
            res = greedy_prune(sub, pcfg)
            del v, sub, node
            pp, ss = res.params, res.specs
            for p in path:
                pp, ss = pp[p], ss[p]
            out_p[k], out_s[k] = pp[k], ss[k]
            del res, pp, ss
        return out_p, out_s

    return walk(params, ())


def setup(cfg: Dict[str, Any], mix: Dict[str, Any], seed: int,
          log: Callable[[str], None]) -> Served:
    from repro.core.schemes import DEFAULT_EXCLUDE
    from repro.launch.prune import prune_config_for
    from repro.models import build_model
    from repro.runtime.telemetry import get_registry
    from repro.serve.engine import ContinuousEngine
    from repro.sparse import PrunedArtifact

    arch = spec.load_arch(cfg["arch"])
    shapes = arch.Shapes.of(cfg)
    model = build_model(arch.model_config(cfg))
    p = cfg["prune"]
    pcfg = prune_config_for(
        scheme=p["scheme"], rate=p["rate"], iters=1,
        tile_block=p["tile_block"],
        exclude=tuple(DEFAULT_EXCLUDE) + tuple(p["extra_exclude"]))

    t = time.perf_counter()
    params = arch.make_params(shapes, seed)
    jax.block_until_ready(params)
    log(f"weights made on the device in {time.perf_counter() - t:.3f} s")
    t = time.perf_counter()
    pruned, specs = prune_leafwise(params, pcfg)
    del params
    jax.block_until_ready(pruned)
    log(f"one-shot projection in {time.perf_counter() - t:.3f} s")
    t = time.perf_counter()
    art = PrunedArtifact(params=pruned, masks=None, specs=specs,
                         meta={"arch": cfg["name"]}).pack()
    del pruned
    s = art.summary()
    log(f"packed {s['packed_leaves']}/{s['total_leaves']} leaves in "
        f"{time.perf_counter() - t:.3f} s; weight bytes dense "
        f"{s['dense_bytes']} packed {s['packed_bytes']}")
    eng = mix["engine"]
    engine = ContinuousEngine(
        model, art, batch_size=eng["batch_size"],
        max_seq_len=eng["max_seq_len"], chunk_steps=eng["chunk_steps"],
        packed=True)
    if engine.bind_report and engine.bind_report["fallbacks"]:
        raise RuntimeError(f"packed leaves served dense: "
                           f"{engine.bind_report['fallbacks']}")
    packed_leaves = sorted(
        path for path, leaf in _paths(engine.params).items()
        if type(leaf).__name__ == "PackedTensor")
    log(f"packed leaves: {packed_leaves}")
    del art
    gc.collect()

    t = time.perf_counter()
    warm(engine, mix, shapes.vocab)
    log(f"warm-up in {time.perf_counter() - t:.3f} s")
    plans = get_registry().counter_family("sparse.plan_build_total")
    for lk, n in sorted(plans.items()):
        log(f"plan resolved: {','.join(f'{k}={v}' for k, v in lk)}: "
            f"{int(n)} build(s)")
    return Served(engine=engine, shapes=shapes, arch=arch)


def _paths(tree) -> Dict[str, Any]:
    from repro.sparse.packed import is_packed
    from repro.utils.tree import tree_paths

    return dict(zip(tree_paths(tree, is_leaf=is_packed),
                    jax.tree.leaves(tree, is_leaf=is_packed)))


def scan_lengths(chunk_steps: int) -> List[int]:
    """The decode scan lengths the scheduler can ask for: powers of two
    up to ``chunk_steps``, and ``chunk_steps`` itself."""
    ks, k = {chunk_steps}, 1
    while k < chunk_steps:
        ks.add(k)
        k *= 2
    return sorted(ks, reverse=True)


def warm(engine, mix: Dict[str, Any], vocab: int) -> None:
    """Compile every program the window can run: each bucket's admission
    program and each decode scan length."""
    from repro.serve.engine import Request

    buckets = sorted(int(k) for k in mix["prompt_len"])
    rng = np.random.default_rng(0)

    def one(S: int, max_new: int):
        prompt = rng.integers(0, vocab, S, dtype=np.int32)
        res = engine.generate([Request(uid=0, prompt=prompt,
                                       max_new_tokens=max_new)])
        if res[0].status != "ok":
            raise RuntimeError(f"warm-up request S={S} {res[0].status}")

    ks = scan_lengths(engine.chunk_steps)
    for i, S in enumerate(buckets):
        one(S, ks[i % len(ks)] + 1)
    for K in ks[len(buckets):]:
        one(buckets[0], K + 1)


class Profiler:
    """Starts the device trace at a chunk edge once the benchmark clock
    passes ``start``, and stops it ``seconds`` later (the engine's
    per-chunk hook; it returns None, so the cache is never touched)."""

    def __init__(self, clock: Callable[[], float], start: float,
                 seconds: float, log_dir: str):
        self.clock, self.start, self.seconds = clock, start, seconds
        self.log_dir = log_dir
        self.on = False
        self.done = False
        self.t_on: Optional[float] = None
        self.t_off: Optional[float] = None

    def __call__(self, cache, sched):
        t = self.clock()
        if not self.on and not self.done and t >= self.start:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(self.log_dir, profiler_options=opts)
            self.t_on = self.clock()
            with jax.profiler.TraceAnnotation(TRACE_MARK):
                pass
            self.on = True
        elif self.on and t >= self.start + self.seconds:
            self.stop()
        return None

    def stop(self):
        if self.on:
            self.t_off = self.clock()
            with jax.profiler.TraceAnnotation(TRACE_MARK + "_end"):
                pass
            jax.profiler.stop_trace()
            self.on, self.done = False, True


@dataclasses.dataclass
class Window:
    """What the window produced, on the benchmark's clock (0 = open)."""

    outcomes: List[Outcome]
    seconds: float              # the planned window
    t_end: float                # when the last result came back
    spans: List[Dict[str, Any]]  # the engine's tracer records
    busy_slot_steps: float
    total_slot_steps: float
    compiles: int
    trace_dir: Optional[str] = None
    t_trace: Optional[Tuple[float, float]] = None


def run_window(served: Served, mix: Dict[str, Any], plan: List,
               seconds: float, *, trace: Optional[Dict[str, Any]],
               compile_count: Callable[[], int]) -> Window:
    from repro.runtime.telemetry import Telemetry, Tracer
    from repro.serve.engine import Request

    open_loop = mix["arrivals"] == "poisson"
    cut = seconds + (mix["drain_cap_s"] if open_loop else 0.0)
    requests = [Request(uid=p.uid, prompt=p.prompt, max_new_tokens=p.max_new,
                        deadline=cut) for p in plan]
    outcomes = {p.uid: Outcome(uid=p.uid, prompt_len=len(p.prompt),
                               max_new=p.max_new, arrival=p.arrival)
                for p in plan}
    sink = io.StringIO()
    engine = served.engine
    c0 = compile_count()
    t0 = time.perf_counter()

    def clock() -> float:
        return time.perf_counter() - t0

    engine.telemetry = tel = Telemetry(tracer=Tracer(sink), clock=clock)
    prof = None
    if trace is not None:
        prof = Profiler(clock, trace["start_s"], trace["seconds"],
                        tempfile.mkdtemp(prefix="bench_trace_"))
    engine.fault_hook = prof
    try:
        for res in engine.stream(requests, arrivals=[p.arrival for p in plan],
                                 clock=clock):
            o = outcomes[res.uid]
            o.status, o.tokens, o.t_done = res.status, list(res.tokens), clock()
    finally:
        if prof is not None:
            prof.stop()
        engine.fault_hook = None
    t_end = clock()
    compiles = compile_count() - c0
    spans = [json.loads(line) for line in sink.getvalue().splitlines()]
    for rec in spans:
        if rec["name"] == "first_token":
            outcomes[rec["uid"]].t_first = rec["ts"]
    reg = tel.metrics
    w = Window(outcomes=[outcomes[p.uid] for p in plan], seconds=seconds,
               t_end=t_end, spans=spans,
               busy_slot_steps=reg.value("serve.busy_slot_steps_total",
                                         engine="continuous"),
               total_slot_steps=reg.value("serve.total_slot_steps_total",
                                          engine="continuous"),
               compiles=compiles)
    if prof is not None and prof.t_on is not None:
        w.trace_dir, w.t_trace = prof.log_dir, (prof.t_on, prof.t_off)
    return w


def free(served: Served) -> None:
    """Drop the program's weights, cache and compiled programs."""
    served.engine.params = None
    served.engine = None
    gc.collect()
    jax.clear_caches()


def drop_trace(w: Window) -> None:
    if w.trace_dir:
        shutil.rmtree(w.trace_dir, ignore_errors=True)


# ---------------------------------------------------------------------------
# the check
# ---------------------------------------------------------------------------

def check_sample(w: Window, plan: List, seed: int, n: int) -> List[int]:
    """Indices into ``plan`` of the requests the check compares: the
    longest finished one, and up to ``n - 1`` more drawn from the seed."""
    done = [i for i, o in enumerate(w.outcomes)
            if o.status == "ok" and o.tokens]
    if not done:
        return []
    longest = max(done, key=lambda i: (w.outcomes[i].prompt_len
                                       + len(w.outcomes[i].tokens), -i))
    rest = [i for i in done if i != longest]
    rng = np.random.default_rng([int(seed), 7])
    pick = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False)
    return [longest] + sorted(rest[j] for j in pick)


def pad_bucket(T: int, mix: Dict[str, Any]) -> int:
    """A reference length that sequences of one prompt bucket share."""
    top = int(mix["max_new"]["hi"])
    for S in sorted(int(k) for k in mix["prompt_len"]):
        if T <= S + top:
            return S + top
    return T


def served_gaps(served: Served, seed: int, w: Window, plan: List,
                picks: List[int], mix: Dict[str, Any], *,
                control: bool = False) -> Dict[str, Any]:
    """Reference gaps of the served tokens of ``picks`` (and, with
    ``control``, the fp8 control's at the same positions), on the weights
    of the seed made anew after ``free(served)``."""
    arch, shapes = served.arch, served.shapes
    params = arch.make_params(shapes, seed)
    widest, ctrl, tokens = 0.0, 0.0, 0
    for i in picks:
        prompt = plan[i].prompt.tolist()
        toks = w.outcomes[i].tokens
        pad = pad_bucket(len(prompt) + len(toks), mix)
        g = reference.served_gaps(arch, shapes, params, prompt, toks,
                                  pad_to=pad)
        widest = max(widest, max(g))
        tokens += len(toks)
        if control:
            c = reference.control_gaps(arch, shapes, params, prompt, toks,
                                       pad_to=pad)
            ctrl = max(ctrl, max(c))
    del params
    out = {"widest_gap": float(widest), "tokens": tokens,
           "requests": len(picks)}
    if control:
        out["control_gap"] = float(ctrl)
    return out


def short_answers(w: Window) -> int:
    """Finished requests that served fewer tokens than their budget."""
    return sum(1 for o in w.outcomes
               if o.status == "ok" and len(o.tokens) != o.max_new)


def checks(gaps: Dict[str, Any], short: int, limit: float
           ) -> Dict[str, Tuple[float, float]]:
    """The numbers ``correct`` compares, each beside its limit: the widest
    reference gap of the served tokens, short answers, and whether any
    request was checked at all."""
    return {
        "widest_gap": (gaps["widest_gap"], limit),
        "short_answers": (short, 0),
        "unchecked": (0 if gaps["requests"] else 1, 0),
    }


def percentile(values: List[float], q: float) -> float:
    """Nearest-rank ``q``-th percentile."""
    v = sorted(values)
    return v[max(0, int(np.ceil(q / 100.0 * len(v))) - 1)]


# ---------------------------------------------------------------------------
# one run of a serving cell
# ---------------------------------------------------------------------------

def run_cell(ctx, end_to_end: Callable[[Window, Dict[str, Any]],
                                        Dict[str, float]],
             attempted_failed: Callable[[Window], Tuple[int, int]]):
    """Set up, run the window, free the program, check: a ``CellRun``."""
    from bench.harness import CellRun
    from bench.readers import Facts

    wl, cfg, mix = ctx.workload, ctx.config, ctx.mix
    served = setup(cfg, mix, ctx.seed, ctx.log)
    setup_s = time.perf_counter() - ctx.t_start
    ctx.log(f"setup_s {setup_s}")
    plan = traffic.generate(mix, ctx.seconds, ctx.seed, served.shapes.vocab)
    ctx.log(f"traffic {wl['traffic']}: {len(plan)} requests, prompt tokens "
            f"{sum(len(p.prompt) for p in plan)}, token budget "
            f"{sum(p.max_new for p in plan)}")
    w = run_window(served, mix, plan, ctx.seconds,
                   trace=wl["trace"] if ctx.trace else None,
                   compile_count=ctx.compile_count)
    ctx.log(f"window: {w.seconds} s planned, last result at {w.t_end:.6f} s; "
            f"compilations inside the window: {w.compiles}")
    ctx.log("statuses: " + json.dumps(
        {s: sum(o.status == s for o in w.outcomes)
         for s in sorted({o.status for o in w.outcomes})}))
    memory_peak = ctx.memory_peak()
    free(served)
    run = CellRun(setup_s=setup_s, memory_peak_bytes=memory_peak)
    run.attempted, run.failed = attempted_failed(w)
    run.end_to_end = end_to_end(w, mix)

    picks = check_sample(w, plan, ctx.seed, wl["check"]["requests"])
    short = short_answers(w)
    t = time.perf_counter()
    gaps = served_gaps(served, ctx.seed, w, plan, picks, mix) \
        if picks else {"widest_gap": -1.0, "tokens": 0, "requests": 0}
    ctx.log(f"check: {gaps['requests']} requests, {gaps['tokens']} served "
            f"tokens against the reference in "
            f"{time.perf_counter() - t:.3f} s")
    run.checks = checks(gaps, short, wl["check"]["widest_gap"])
    if ctx.trace and w.trace_dir:
        from bench import trace as tr

        t = time.perf_counter()
        red = tr.load(w.trace_dir)
        drop_trace(w)
        mark = tr.marker(red.host, TRACE_MARK)
        mark_end = tr.marker(red.host, TRACE_MARK + "_end")
        offset = mark.start - w.t_trace[0]
        lo = mark.start
        hi = mark_end.start if mark_end is not None else max(
            e.end for e in red.ops[red.chips[0]])
        run.facts = Facts(shapes=served.shapes, arch=served.arch,
                          peaks=ctx.peaks, window=w, reduced=red,
                          offset=offset, lo=lo, hi=hi)
        ctx.log(f"trace read in {time.perf_counter() - t:.3f} s: "
                f"{sum(len(v) for v in red.ops.values())} device operations,"
                f" {sum(len(v) for v in red.modules.values())} programs")
    else:
        drop_trace(w)
    return run
