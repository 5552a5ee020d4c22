"""The traffic generator: seeded, bucketed, the same work on every seed."""

import numpy as np
import pytest

from bench import traffic


@pytest.mark.parametrize("name", ["long_prompt", "chat_batch"])
def test_same_seed_same_requests(name):
    mix = traffic.load_traffic(name)
    a = traffic.generate(mix, 10.0, 2**33 + 17, 1000)
    b = traffic.generate(mix, 10.0, 2**33 + 17, 1000)
    assert len(a) == len(b)
    for x, y in zip(a, b):
        assert (x.uid, x.arrival, x.max_new) == (y.uid, y.arrival, y.max_new)
        np.testing.assert_array_equal(x.prompt, y.prompt)


@pytest.mark.parametrize("name", ["long_prompt", "chat_batch"])
def test_lengths_only_from_buckets(name):
    mix = traffic.load_traffic(name)
    reqs = traffic.generate(mix, 10.0, 5, 1000)
    buckets = {int(k) for k in mix["prompt_len"]}
    assert {len(r.prompt) for r in reqs} <= buckets
    lo, hi = mix["max_new"]["lo"], mix["max_new"]["hi"]
    assert all(lo <= r.max_new <= hi for r in reqs)
    assert all(0 <= int(r.prompt.min()) and int(r.prompt.max()) < 1000
               for r in reqs)
    cap = mix["engine"]["max_seq_len"]
    assert all(len(r.prompt) + r.max_new - 1 <= cap for r in reqs)


@pytest.mark.parametrize("name", ["long_prompt", "chat_batch"])
def test_seeds_share_the_work(name):
    """Another seed gives the same sizes, and as many requests, in
    another order."""
    mix = traffic.load_traffic(name)
    a = traffic.generate(mix, 10.0, 1, 1000)
    b = traffic.generate(mix, 10.0, 2, 1000)
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt) for r in b)
    assert sorted(r.max_new for r in a) == sorted(r.max_new for r in b)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]


def test_bucket_shares_and_window():
    mix = traffic.load_traffic("long_prompt")
    reqs = traffic.generate(mix, 30.0, 3, 1000)
    assert len(reqs) == round(mix["rate_per_s"] * 30.0)
    counts = {int(k): sum(len(r.prompt) == int(k) for r in reqs)
              for k in mix["prompt_len"]}
    for k, share in mix["prompt_len"].items():
        assert abs(counts[int(k)] - share * len(reqs)) <= 1
    arr = [r.arrival for r in reqs]
    assert arr == sorted(arr) and 0.0 <= arr[0] and arr[-1] < 30.0


def test_arrivals_are_poisson():
    """Given its count, a Poisson process's gaps are exponential: over a
    long window the share of gaps above their mean is 1/e, and short
    gaps bunch (some run of three gaps is under a third of the mean)."""
    mix = dict(traffic.load_traffic("long_prompt"), rate_per_s=50.0)
    arr = np.asarray([r.arrival for r in
                      traffic.generate(mix, 100.0, 3, 1000)])
    gaps = np.diff(arr)
    assert abs(gaps.mean() - 1 / 50.0) < 0.05 / 50.0
    assert abs((gaps > gaps.mean()).mean() - np.exp(-1)) < 0.03
    runs = gaps[:-2] + gaps[1:-1] + gaps[2:]
    assert runs.min() < gaps.mean() / 3


def test_batch_mix_all_due_at_zero():
    mix = traffic.load_traffic("chat_batch")
    reqs = traffic.generate(mix, 10.0, 3, 1000)
    assert len(reqs) == mix["requests"]
    assert all(r.arrival == 0.0 for r in reqs)
    med = float(np.median([r.max_new for r in reqs]))
    assert abs(med - mix["max_new"]["median"]) <= 2
