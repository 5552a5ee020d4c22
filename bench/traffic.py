"""One generator for every traffic mix: ``bench/traffic/<name>.json``.

A mix is data: how requests arrive, the buckets their prompt lengths come
from, and how many tokens each asks for. Every seed gets the same work:
the same number of requests, the same multiset of prompt lengths (each
bucket's share of the count) and of token budgets (evenly spaced
quantiles of their distribution), in an order drawn from the seed, with
prompt tokens of its own. In an open loop the arrival times are ``n``
uniform draws over the window, sorted: a Poisson process at the mix's
rate, conditioned on its count, so arrivals bunch as a Poisson process
does. Drawing the count too would change the work from seed to seed, and
the tail with it.

Keys of a mix:

    arrivals      "poisson": open loop, ``rate_per_s`` × the window's
                  seconds requests over the window; "batch": ``requests``
                  requests all due at t = 0
    prompt_len    {length: share}; lengths are the only prompt sizes sent
    max_new       {"dist": "uniform", "lo", "hi"} or
                  {"dist": "lognormal", "median", "sigma", "lo", "hi"}
    engine        batch_size, chunk_steps, max_seq_len of the engine
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
from statistics import NormalDist
from typing import Any, Dict, List

import numpy as np

from bench.spec import BENCH


@dataclasses.dataclass
class Planned:
    """One request as the traffic plans it."""

    uid: int
    arrival: float           # seconds after the window opens
    prompt: np.ndarray       # (S,) int32
    max_new: int


def load_traffic(name: str) -> Dict[str, Any]:
    with open(os.path.join(BENCH, "traffic", f"{name}.json")) as f:
        return json.load(f)


def request_count(mix: Dict[str, Any], seconds: float) -> int:
    if mix["arrivals"] == "poisson":
        return max(1, int(round(mix["rate_per_s"] * seconds)))
    if mix["arrivals"] == "batch":
        return int(mix["requests"])
    raise ValueError(f"unknown arrivals {mix['arrivals']!r}")


def bucket_lengths(shares: Dict[str, float], n: int) -> np.ndarray:
    """``n`` lengths, each bucket's count its share of ``n`` (largest
    remainder), in bucket order."""
    lens = [int(k) for k in shares]
    want = np.asarray([shares[k] for k in shares], float)
    want = want / want.sum() * n
    counts = np.floor(want).astype(int)
    for i in np.argsort(-(want - counts))[: n - counts.sum()]:
        counts[i] += 1
    return np.repeat(np.asarray(lens, np.int64), counts)


def budgets(spec: Dict[str, Any], n: int) -> np.ndarray:
    """``n`` token budgets at evenly spaced quantiles of their
    distribution, in ascending order."""
    q = (np.arange(n) + 0.5) / n
    lo, hi = int(spec["lo"]), int(spec["hi"])
    if spec["dist"] == "uniform":
        v = lo + np.floor(q * (hi - lo + 1))
    elif spec["dist"] == "lognormal":
        z = np.asarray([NormalDist().inv_cdf(x) for x in q])
        v = np.round(np.exp(math.log(spec["median"]) + spec["sigma"] * z))
    else:
        raise ValueError(f"unknown max_new dist {spec['dist']!r}")
    return np.clip(v, lo, hi).astype(np.int64)


def arrival_times(mix: Dict[str, Any], n: int, seconds: float,
                  rng: np.random.Generator) -> np.ndarray:
    if mix["arrivals"] == "batch":
        return np.zeros((n,))
    return np.sort(rng.uniform(0.0, seconds, n))


def generate(mix: Dict[str, Any], seconds: float, seed: int,
             vocab: int) -> List[Planned]:
    """The requests of one run, in arrival order."""
    rng = np.random.default_rng(int(seed))
    n = request_count(mix, seconds)
    lens = rng.permutation(bucket_lengths(mix["prompt_len"], n))
    news = rng.permutation(budgets(mix["max_new"], n))
    arr = arrival_times(mix, n, seconds, rng)
    return [Planned(uid=i, arrival=float(arr[i]),
                    prompt=rng.integers(0, vocab, int(lens[i]),
                                        dtype=np.int32),
                    max_new=int(news[i]))
            for i in range(n)]
