"""``BENCHMARK.json`` against the files it names and the contract's
cross-references."""

import functools
import os
import re

from bench import spec

B = spec.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
ARCH_INTERFACE = ("Shapes.of", "model_config", "make_params", "_make",
                  "logits_at", "prefill", "decode_token_flops",
                  "decode_step", "weight_bytes", "prefill_kernels",
                  "small_config")


def test_files_named_exist():
    assert B["command"][1].startswith(B["paths"][0] + "/")
    assert os.path.isfile(os.path.join(spec.ROOT, B["command"][1]))
    for c in B["configs"]:
        assert os.path.isfile(os.path.join(spec.ROOT, c["file"]))
        cfg = spec.load_config(c["name"])
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]
        for k in c["reduced"]:
            assert k in cfg["published"] and cfg[k] != cfg["published"][k]
        arch = spec.load_arch(cfg["arch"])
        for fn in ARCH_INTERFACE:
            assert callable(functools.reduce(getattr, fn.split("."), arch)), (
                cfg["arch"], fn)
    for w in B["workloads"]:
        wl = spec.load_workload(w["name"])
        assert (wl["config"], wl["traffic"], wl["chips"], wl["why"]) == (
            w["config"], w["traffic"], w["chips"], w["why"])
        assert os.path.isfile(os.path.join(spec.BENCH, "kinds",
                                           wl["kind"] + ".py"))
        assert os.path.isfile(os.path.join(spec.BENCH, "traffic",
                                           wl["traffic"] + ".json"))
    for m in B["per_layer"]:
        mod = spec.load_metric(m["name"])
        assert callable(mod.compute)


def test_names_and_units():
    for sec in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [x["name"] for x in B[sec]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names)
    for m in B["end_to_end"] + B["per_layer"]:
        assert re.match(r"^[A-Za-z0-9_/%.\-]{1,16}$", m["unit"])
        assert m["better"] in ("lower", "higher")
    assert any(m["name"] == "setup_s" and "workloads" not in m
               for m in B["end_to_end"])


def test_every_layer_metric_moves_a_metric_its_cells_report():
    e2e = {m["name"]: m for m in B["end_to_end"]}
    cells = {w["name"] for w in B["workloads"]}
    for m in B["per_layer"]:
        target = e2e[m["moves"]]
        for cell in m["workloads"]:
            assert cell in cells
            assert "workloads" not in target or cell in target["workloads"]
    for cell in cells:
        reported = [m for m in B["end_to_end"]
                    if "workloads" not in m or cell in m["workloads"]]
        assert len(reported) >= 2
        assert spec.cell_metrics(cell, "per_layer")


def test_roofline_and_mfu_units_are_percent():
    for m in B["per_layer"]:
        if "roofline" in m["name"] or "mfu" in m["name"]:
            assert m["unit"] == "%"
