"""Benchmark harness entry point — one sub-benchmark per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run                 # all tables
    PYTHONPATH=src python -m benchmarks.run --only table1,fig3
    REPRO_BENCH_FAST=1 PYTHONPATH=src python -m benchmarks.run   # CI smoke

Artifacts land in experiments/bench/<table>.json; a combined summary is
printed and written to experiments/bench/summary.json.

Paper-table map (DESIGN.md §6):
    table1  — CIFAR-10 4-scheme grid, ADMM† vs privacy-preserving
    table2  — CIFAR-100-style pattern pruning @ 8/12/16x
    table4  — problem (3) layer-wise vs problem (2) whole-model (+runtime)
    table5  — greedy ("Uniform") vs ADMM on synthetic data
    fig3    — sparse kernel acceleration (CPU measured + TPU roofline est.)
    privacy_mia — membership-inference attacks on dense / ADMM†-real /
            privacy-preserving-synthetic targets (the privacy claim)
    fault_injection — the reliability layer under seeded faults: typed
            shedding/timeouts, quarantine isolation, degraded-mode cost
    prune_resilience — the ADMM pruning reliability layer: kill+resume
            bit-identity and cost, NaN divergence recovery, corrupt-
            checkpoint fallback
    (table3 — ImageNet ResNet-18 — is covered by the scheme sweep of
     table1/table2 at matching compression rates; no ImageNet on the box.)
"""

from __future__ import annotations

import argparse
import json
import os
import time


SERVE_SUITES = ("packed_serve", "continuous_serve", "speculative_serve")
# quick mode runs the gated suites: serving + privacy MIA + reliability
# + telemetry (observability overhead and span completeness) + profiler
# (sampling overhead, dispatch identity, roofline attribution)
GATED_SUITES = SERVE_SUITES + ("privacy_mia", "fault_injection",
                               "prune_resilience", "telemetry", "profiler")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="all",
                    help="comma list: table1,table2,table4,table5,fig3,"
                         "packed_serve,continuous_serve,speculative_serve,"
                         "privacy_mia,fault_injection,prune_resilience,"
                         "telemetry,profiler")
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke: REPRO_BENCH_FAST=1 and only the "
                         "suites check_regression.py gates on")
    args = ap.parse_args()
    from repro.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    if args.quick:
        os.environ["REPRO_BENCH_FAST"] = "1"
        if args.only == "all":
            args.only = ",".join(GATED_SUITES)
    want = None if args.only == "all" else set(args.only.split(","))

    from benchmarks import (
        common,
        continuous_serve,
        fault_injection,
        fig3_kernels,
        packed_serve,
        privacy_mia,
        profiler_overhead,
        prune_resilience,
        speculative_serve,
        table1_schemes,
        table2_pattern,
        table4_formulations,
        table5_greedy,
        telemetry_overhead,
    )

    suites = {
        "table1": table1_schemes.run,
        "table2": table2_pattern.run,
        "table4": table4_formulations.run,
        "table5": table5_greedy.run,
        "fig3": fig3_kernels.run,
        "packed_serve": packed_serve.run,
        "continuous_serve": continuous_serve.run,
        "speculative_serve": speculative_serve.run,
        "privacy_mia": privacy_mia.run,
        "fault_injection": fault_injection.run,
        "prune_resilience": prune_resilience.run,
        "telemetry": telemetry_overhead.run,
        "profiler": profiler_overhead.run,
    }

    # provenance stamp shared by every suite this invocation runs: the
    # same wall-clock/git-SHA pair common.emit stamps onto BENCH rows,
    # plus per-suite duration — summary.json alone reconstructs when and
    # on what commit each point of the perf trajectory was measured
    sha = common.git_sha()
    summary = {}
    for name, fn in suites.items():
        if want is not None and name not in want:
            continue
        print(f"\n### {name} " + "#" * (70 - len(name)))
        wall = time.time()
        t0 = time.perf_counter()
        rows = fn()
        dt = time.perf_counter() - t0
        summary[name] = {
            "rows": len(rows),
            "seconds": round(dt, 1),
            "timestamp": round(wall, 3),
            "git_sha": sha,
        }
        print(f"### {name} done: {len(rows)} rows in {dt:.1f}s")

    os.makedirs(common.OUT_DIR, exist_ok=True)
    with open(os.path.join(common.OUT_DIR, "summary.json"), "w") as f:
        json.dump(summary, f, indent=1)
    print("\nbenchmark summary:", json.dumps(summary))


if __name__ == "__main__":
    main()
