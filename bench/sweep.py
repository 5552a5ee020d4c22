"""The rate sweep that fixed an open-loop cell's arrival rate, on the chip.

    python3 bench/sweep.py --workload <cell> --seeds 5,6,7 --seconds 51 \
        --rates 1.2,1.6,2.0

One set-up, then one window per rate and seed of the cell's own mix. For
each window it prints the request count, TTFT and TPOT tails, how long the
queue took to drain after arrivals stopped, and the median queue wait of
the first and the second half of the arrivals: a backlog that grows
through the window shows as a second half that waits far longer than the
first, and as a drain that grows with the rate. After each rate, one line
sums the seeds up: the median of each number and the tails' quartile
spread as a share of their median. The benchmark's own runs never run
this.
"""

import time

T_START = time.perf_counter()

import json  # noqa: E402
import os  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)

from bench import harness, serving, spec, traffic  # noqa: E402


def spread(values):
    """Quartile spread as a share of the median."""
    if len(values) < 2:
        return None
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def window(served, kind, mix, seconds, seed, count) -> dict:
    plan = traffic.generate(mix, seconds, seed, served.shapes.vocab)
    w = serving.run_window(served, mix, plan, seconds, trace=None,
                           compile_count=count)
    e2e = kind.end_to_end(w, mix)
    waits = {rec["uid"]: rec["ts"] - rec["arrival"] for rec in w.spans
             if rec.get("kind") == "span" and rec["name"] == "admit"}
    half = len(plan) // 2
    first = [waits[p.uid] for p in plan[:half] if p.uid in waits]
    second = [waits[p.uid] for p in plan[half:] if p.uid in waits]
    return {
        "rate_per_s": mix["rate_per_s"], "seed": seed,
        "requests": len(plan),
        "ok": sum(o.status == "ok" for o in w.outcomes),
        **e2e, "drain_s": w.t_end - seconds,
        "queue_wait_median_first_half_s": statistics.median(first),
        "queue_wait_median_second_half_s": statistics.median(second),
        "compiles": w.compiles}


def main(argv) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    wl = spec.load_workload(args.workload)
    harness.find_devices(wl["chips"])
    import jax

    from repro.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    seeds = [int(s) for s in args.seeds.split(",")]
    base = traffic.load_traffic(wl["traffic"])
    served = serving.setup(spec.load_config(wl["config"]), base, seeds[0],
                           harness.log)
    kind = spec.load_kind(wl["kind"])
    count = harness.CompileCounter()
    for rate in [float(r) for r in args.rates.split(",")]:
        mix = dict(base, rate_per_s=rate)
        rows = []
        for seed in seeds:
            rows.append(window(served, kind, mix, args.seconds, seed, count))
            print(json.dumps(rows[-1]), flush=True)
        tails = [k for k in rows[0] if k.startswith(("ttft", "tpot"))]
        summary = {"rate_per_s": rate, "seeds": len(rows)}
        for k in ["requests", "drain_s", "queue_wait_median_first_half_s",
                  "queue_wait_median_second_half_s"] + tails:
            summary[k + "_median"] = statistics.median(r[k] for r in rows)
        for k in tails:
            summary[k + "_spread"] = spread([r[k] for r in rows])
        print(json.dumps({"summary": summary}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
