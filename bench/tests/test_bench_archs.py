"""A configuration of a new architecture is files only: a copy of
``bench/`` takes an architecture module, a configuration and a workload,
runs the cell through the harness, and no file it had before changes."""

import collections
import filecmp
import json
import os
import shutil

from bench import spec
from bench.tests import small

# Qwen2 under another name, counting each call the benchmark makes of it.
TOY = '''
import collections
import functools

from bench import spec

_base = spec.load_arch("qwen2")
CALLS = collections.Counter()


def _counted(name):
    fn = getattr(_base, name)

    @functools.wraps(fn)
    def call(*args, **kwargs):
        CALLS[name] += 1
        return fn(*args, **kwargs)

    return call


class Shapes:
    @staticmethod
    def of(cfg):
        CALLS["Shapes.of"] += 1
        return _base.Shapes.of(cfg)


for _name in ("model_config", "make_params", "_make", "logits_at",
              "prefill", "decode_token_flops", "decode_step",
              "weight_bytes", "prefill_kernels", "small_config"):
    globals()[_name] = _counted(_name)
'''

ADDED = {os.path.join("archs", "toy.py"), os.path.join("configs", "toy.json"),
         os.path.join("workloads", "toy.chat_batch.json")}


def _differences(a, b, rel=""):
    """Files only in ``a``, only in ``b``, or in both with other bytes."""
    d = filecmp.dircmp(a, b, ignore=["__pycache__"])
    out = {os.path.join(rel, n) for n in d.left_only + d.right_only}
    out |= {os.path.join(rel, n)
            for n in filecmp.cmpfiles(a, b, d.common_files, shallow=False)[1]}
    for sub in d.common_dirs:
        out |= _differences(os.path.join(a, sub), os.path.join(b, sub),
                            os.path.join(rel, sub))
    return out


def test_a_new_architecture_is_files_only(tmp_path, monkeypatch):
    original, copy = spec.BENCH, str(tmp_path / "bench")
    shutil.copytree(original, copy,
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(copy, "archs", "toy.py"), "w") as f:
        f.write(TOY)
    cfg = dict(spec.load_config("qwen2-1.5b"), name="toy", arch="toy")
    wl = dict(spec.load_workload("qwen2-1.5b.chat_batch"),
              name="toy.chat_batch", config="toy")
    for path, obj in (("configs/toy.json", cfg),
                      ("workloads/toy.chat_batch.json", wl)):
        with open(os.path.join(copy, path), "w") as f:
            json.dump(obj, f)

    monkeypatch.setattr(spec, "BENCH", copy)
    loaded = []
    load_arch = spec.load_arch

    def recorded(name):
        mod = load_arch(name)
        loaded.append((name, mod))
        return mod

    monkeypatch.setattr(spec, "load_arch", recorded)
    line = small.run(small.small_ctx("toy.chat_batch"))
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["checks"]["unchecked"]["value"] == 0

    # qwen2 was loaded only by the toy module, and every call the harness
    # made of an architecture reached the toy
    names = [n for n, _ in loaded]
    assert set(names) == {"toy", "qwen2"}
    assert names.count("qwen2") == names.count("toy")
    calls = collections.Counter()
    for n, m in loaded:
        if n == "toy":
            calls.update(m.CALLS)
    assert calls["small_config"] == 1
    assert calls["Shapes.of"] >= 1 and calls["model_config"] >= 1
    assert calls["make_params"] == 2          # set-up, then the check
    assert calls["logits_at"] >= 1

    assert _differences(original, copy) == ADDED
