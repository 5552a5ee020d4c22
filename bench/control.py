"""The control of the serving cells' check, on the chip.

    python3 bench/control.py --workload <cell> --seeds 11,12,13 --seconds 51

For each seed, one process-local run of the cell at its own size, load
and window, then over the same sample of served requests: the program's
widest gap against the float32 reference, and the control's, the tokens
that the reference computed with float8 GEMMs puts first at the same
positions. Both go through the harness's own check (``serving.checks``,
``CellRun.correct``): the control has to come out not correct, and the
program's gaps over many seeds set the limit's lower end. The benchmark's
own runs never run this.
"""

import time

T_START = time.perf_counter()

import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)

from bench import harness, serving, spec, traffic  # noqa: E402


def readings(ctx) -> dict:
    """Program and control widest gaps of one seed, and what the harness's
    check says of each: the control's tokens are those the float8
    reference puts first at the served positions, judged by the same
    ``serving.checks`` and ``CellRun.correct`` as a run's."""
    mix = ctx.mix
    served = serving.setup(ctx.config, mix, ctx.seed, ctx.log)
    plan = traffic.generate(mix, ctx.seconds, ctx.seed, served.shapes.vocab)
    w = serving.run_window(served, mix, plan, ctx.seconds, trace=None,
                           compile_count=ctx.compile_count)
    serving.free(served)
    picks = serving.check_sample(w, plan, ctx.seed,
                                 ctx.workload["check"]["requests"])
    out = serving.served_gaps(served, ctx.seed, w, plan, picks, mix,
                              control=True)
    short = serving.short_answers(w)
    limit = ctx.workload["check"]["widest_gap"]

    def judged(gaps):
        return harness.CellRun(setup_s=0.0, memory_peak_bytes=None,
                               checks=serving.checks(gaps, short, limit))

    out["program_correct"] = judged(out).correct
    out["control_correct"] = judged(
        dict(out, widest_gap=out["control_gap"])).correct
    out["seed"] = ctx.seed
    return out


def main(argv) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    wl = spec.load_workload(args.workload)
    devs = harness.find_devices(wl["chips"])
    import jax

    from repro.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from bench.peaks import peaks_of

    rows = []
    for seed in [int(s) for s in args.seeds.split(",")]:
        ctx = harness.Ctx(
            workload=wl, config=spec.load_config(wl["config"]),
            mix=traffic.load_traffic(wl["traffic"]), seed=seed,
            seconds=args.seconds, trace=False, t_start=time.perf_counter(),
            peaks=peaks_of(devs[0].device_kind), log=harness.log,
            compile_count=harness.CompileCounter(),
            memory_peak=lambda: harness.memory_peak(devs))
        r = readings(ctx)
        print(json.dumps({"control": wl["name"], **r}), flush=True)
        rows.append(r)
    lim = wl["check"]["widest_gap"]
    print(json.dumps({
        "cell": wl["name"], "limit": lim,
        "program_max": max(r["widest_gap"] for r in rows),
        "control_min": min(r["control_gap"] for r in rows),
        "program_correct_every_seed": all(r["program_correct"]
                                          for r in rows),
        "control_correct_on_no_seed": not any(r["control_correct"]
                                              for r in rows)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
