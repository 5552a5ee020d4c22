"""Configurations and workloads of the benchmark, found by name.

    bench/configs/<config>.json     a model configuration as it is run
    bench/workloads/<cell>.json     one cell: configuration, kind, traffic
    bench/kinds/<kind>.py           the driver of one kind of cell
    bench/metrics/<metric>.py       the reader of one per-layer metric

Adding a cell or a metric adds files; no file here needs an edit.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
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
    spec.loader.exec_module(mod)
    return mod


def load_kind(name: str) -> ModuleType:
    return _module("kinds", name)


def load_metric(name: str) -> ModuleType:
    return _module("metrics", name)


def cell_metrics(cell: str, section: str) -> List[Dict[str, Any]]:
    """The metrics of ``BENCHMARK.json[section]`` that ``cell`` reports."""
    return [m for m in load_benchmark()[section]
            if "workloads" not in m or cell in m["workloads"]]


def model_config(cfg: Dict[str, Any]):
    """The program's ``ModelConfig`` at the sizes this file states."""
    from repro.configs import get_config

    base = get_config(cfg["repo_config"])
    heads = cfg["num_attention_heads"]
    return dataclasses.replace(
        base,
        num_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"],
        num_heads=heads,
        num_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["assumed"]["head_dim"],
        d_ff=cfg["intermediate_size"],
        vocab_size=cfg["vocab_size"],
        qkv_bias=cfg["assumed"]["qkv_bias"],
        rope_theta=cfg["rope_theta"],
        norm_eps=cfg["rms_norm_eps"],
        tie_embeddings=cfg["tie_word_embeddings"],
        param_dtype=cfg["torch_dtype"],
        sliding_window=None,
        family="dense",
        ffn_type="swiglu",
    )


@dataclasses.dataclass(frozen=True)
class Shapes:
    """The sizes the yardstick computes with, read from a config file."""

    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    qkv_bias: bool
    rope_theta: float
    eps: float
    group: int
    keep: int
    block: int
    packed: tuple
    tied: bool = False       # the output head is the embedding, transposed

    @classmethod
    def of(cls, cfg: Dict[str, Any]) -> "Shapes":
        p = cfg["prune"]
        return cls(
            layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
            heads=cfg["num_attention_heads"],
            kv_heads=cfg["num_key_value_heads"],
            head_dim=cfg["assumed"]["head_dim"],
            d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
            qkv_bias=cfg["assumed"]["qkv_bias"],
            rope_theta=float(cfg["rope_theta"]),
            eps=float(cfg["rms_norm_eps"]), group=p["group"],
            keep=p["keep"], block=p["tile_block"],
            packed=tuple(cfg["packed_leaves"]),
            tied=bool(cfg["tie_word_embeddings"]))

    def gemms(self) -> Dict[str, tuple]:
        """(K, O) of every GEMM of one block, and of the output head
        (``lm_head``, the embedding's transpose where ``tied``)."""
        D, A, KV, F = (self.d_model, self.heads * self.head_dim,
                       self.kv_heads * self.head_dim, self.d_ff)
        return {"wq": (D, A), "wk": (D, KV), "wv": (D, KV), "wo": (A, D),
                "w_gate": (D, F), "w_up": (D, F), "w_down": (F, D),
                "lm_head": (D, self.vocab)}
