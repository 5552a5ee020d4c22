"""A cell cut to a size the CPU runs in seconds, for the tests.

The configuration keeps its file's keys with the small widths of its
architecture module's ``small_config``; the traffic mix keeps its kind
with short prompts and few tokens.
"""

import time

import jax

from bench import harness, spec


def small_config(name):
    cfg = spec.load_config(name)
    return spec.load_arch(cfg["arch"]).small_config(cfg)


def small_mix(arrivals):
    engine = {"batch_size": 4, "chunk_steps": 4, "max_seq_len": 80}
    if arrivals == "poisson":
        return {"arrivals": "poisson", "rate_per_s": 16.0,
                "prompt_len": {"32": 0.5, "64": 0.5},
                "max_new": {"dist": "uniform", "lo": 4, "hi": 8},
                "drain_cap_s": 30.0, "engine": engine}
    return {"arrivals": "batch", "requests": 64,
            "prompt_len": {"16": 0.5, "32": 0.5},
            "max_new": {"dist": "lognormal", "median": 12, "sigma": 0.5,
                        "lo": 4, "hi": 16}, "engine": engine}


def small_ctx(cell, seed=2**33 + 11, seconds=1.5, limit=0.05):
    """The context of one run of ``cell`` at small size, on the CPU."""
    wl = dict(spec.load_workload(cell))
    wl["check"] = dict(wl["check"], widest_gap=limit)
    cfg = small_config(wl["config"])
    mix = small_mix("poisson" if wl["kind"] == "serve_open" else "batch")
    return harness.Ctx(workload=wl, config=cfg, mix=mix, seed=seed,
                       seconds=seconds, trace=False,
                       t_start=time.perf_counter(), peaks=None,
                       log=lambda msg: None,
                       compile_count=harness.CompileCounter(),
                       memory_peak=lambda: None)


def run(ctx):
    return harness.execute(ctx, jax.devices()[:1])
