"""One run of one cell: device check, the cell's kind, metrics, result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace 0|1

Earlier lines of standard output are the run's facts. The numbers the
check compared, each beside its limit, are the last lines of standard
error, and the last line of standard output is one JSON object: with
``--trace 0`` the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics, read from a profiler trace of part of the window.
"""

from __future__ import annotations

import dataclasses
import json
import sys
from typing import Any, Callable, Dict, List, Optional, Tuple

from bench import spec


@dataclasses.dataclass
class Ctx:
    """What a kind's ``run`` is given."""

    workload: Dict[str, Any]
    config: Dict[str, Any]
    mix: Dict[str, Any]
    seed: int
    seconds: float
    trace: bool
    t_start: float
    peaks: Any
    log: Callable[[str], None]
    compile_count: Callable[[], int]
    memory_peak: Callable[[], Optional[int]]


@dataclasses.dataclass
class CellRun:
    """What a kind's ``run`` returns."""

    setup_s: float
    memory_peak_bytes: Optional[int]
    attempted: int = 0
    failed: int = 0
    end_to_end: Dict[str, float] = dataclasses.field(default_factory=dict)
    checks: Dict[str, Tuple[float, float]] = dataclasses.field(
        default_factory=dict)
    facts: Any = None

    @property
    def correct(self) -> bool:
        return all(v <= lim for v, lim in self.checks.values())


class CompileCounter:
    """JAX compilations (tracing, lowering, backend compiles) so far."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self) -> None:
        import jax

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_: Any) -> None:
        if event in self.EVENTS:
            self.n += 1

    def __call__(self) -> int:
        return self.n


def log(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


def find_devices(chips: int):
    """The chips this run holds; exits 2 when JAX finds fewer TPUs."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        print(f"bench: needs {chips} TPU chip(s); JAX finds {len(devs)} "
              f"{devs[0].platform!r} device(s)", file=sys.stderr)
        raise SystemExit(2)
    return devs


def memory_peak(devs) -> Optional[int]:
    peaks = []
    for d in devs:
        stats = d.memory_stats()
        if stats and "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def per_layer(cell: str, facts) -> Dict[str, Dict[str, Any]]:
    out = {}
    for m in spec.cell_metrics(cell, "per_layer"):
        v = spec.load_metric(m["name"]).compute(facts)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def breakdown(facts) -> Dict[str, List[List[Any]]]:
    """The device operations that took most time, and the longest idle
    gaps by what the engine's host loop was doing."""
    from bench import trace as tr

    ops = [e for e in facts.ops() if e.end > facts.lo and e.start < facts.hi]
    top = sorted(tr.time_by_name(ops).items(), key=lambda kv: -kv[1])[:10]
    host = [(rec["ts"] + facts.offset, rec["ts"] + rec["dur"] + facts.offset,
             rec["name"]) for rec in facts.window.spans
            if rec.get("kind") == "span"]
    gaps = []
    for a, b in tr.idle_gaps(ops, facts.lo, facts.hi)[:10]:
        mid = 0.5 * (a + b)
        what = [n for s, e, n in host if s <= mid <= e]
        gaps.append([f"host in {what[0]}" if what
                     else "host outside admit and decode_chunk spans", b - a])
    return {"device_ops": [[k, v] for k, v in top], "idle_gaps": gaps}


def execute(ctx: Ctx, devices) -> Dict[str, Any]:
    """Run the cell's kind; return the result line."""
    res: CellRun = spec.load_kind(ctx.workload["kind"]).run(ctx)
    name = ctx.workload["name"]
    if ctx.trace:
        metrics = per_layer(name, res.facts) if res.facts else {}
    else:
        units = {m["name"]: m["unit"]
                 for m in spec.cell_metrics(name, "end_to_end")}
        vals = dict(res.end_to_end, setup_s=res.setup_s)
        metrics = {k: {"value": vals[k], "unit": u} for k, u in units.items()}
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": res.memory_peak_bytes}
    line: Dict[str, Any] = {"correct": res.correct, "attempted": res.attempted,
                            "failed": res.failed, "metrics": metrics,
                            "device": device}
    if ctx.trace and res.facts is not None:
        from bench import trace as tr

        f = res.facts
        busy = [tr.busy_seconds(f.reduced.ops[c], f.lo, f.hi)
                for c in f.reduced.chips]
        device["busy_s"] = sum(busy) / len(busy)
        device["window_s"] = f.hi - f.lo
        line["breakdown"] = breakdown(f)
    line["checks"] = {k: {"value": v, "limit": lim}
                      for k, (v, lim) in res.checks.items()}
    return line


def run(argv: List[str], t_start: float) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import traffic
    from bench.peaks import peaks_of

    wl = spec.load_workload(args.workload)
    devs = find_devices(wl["chips"])
    import jax

    dev = devs[0]
    log(f"device {dev.platform} {dev.device_kind!r}, {len(devs)} device(s); "
        f"cell {wl['name']} seed {args.seed} seconds {args.seconds} "
        f"trace {args.trace}")
    from repro.runtime.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    log(f"compile cache {cache}")
    ctx = Ctx(workload=wl, config=spec.load_config(wl["config"]),
              mix=traffic.load_traffic(wl["traffic"]), seed=args.seed,
              seconds=args.seconds, trace=bool(args.trace),
              t_start=t_start, peaks=peaks_of(dev.device_kind), log=log,
              compile_count=CompileCounter(),
              memory_peak=lambda: memory_peak(devs))
    line = execute(ctx, devs)
    for k, c in line["checks"].items():
        print(f"check {k}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0
