"""Configurations and workloads of the benchmark, found by name.

    bench/configs/<config>.json     a model configuration as it is run
    bench/archs/<arch>.py           what the benchmark knows of the shape of
                                    the configurations of one architecture
    bench/workloads/<cell>.json     one cell: configuration, kind, traffic
    bench/kinds/<kind>.py           the driver of one kind of cell
    bench/metrics/<metric>.py       the reader of one per-layer metric

Adding a cell, a metric or an architecture adds files; no file here
needs an edit.
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys
from types import ModuleType
from typing import Any, Dict, List

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def _load_json(*parts: str) -> Dict[str, Any]:
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def load_config(name: str) -> Dict[str, Any]:
    return _load_json("configs", f"{name}.json")


def load_workload(name: str) -> Dict[str, Any]:
    wl = _load_json("workloads", f"{name}.json")
    if wl["name"] != name:
        raise ValueError(f"workload file {name}.json names {wl['name']!r}")
    return wl


def load_benchmark() -> Dict[str, Any]:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _module(kind: str, name: str) -> ModuleType:
    path = os.path.join(BENCH, kind, f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod        # dataclasses look their module up
    spec.loader.exec_module(mod)
    return mod


def load_kind(name: str) -> ModuleType:
    return _module("kinds", name)


def load_metric(name: str) -> ModuleType:
    return _module("metrics", name)


def load_arch(name: str) -> ModuleType:
    return _module("archs", name)


def cell_metrics(cell: str, section: str) -> List[Dict[str, Any]]:
    """The metrics of ``BENCHMARK.json[section]`` that ``cell`` reports."""
    return [m for m in load_benchmark()[section]
            if "workloads" not in m or cell in m["workloads"]]
