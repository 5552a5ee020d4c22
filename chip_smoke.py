"""Bring-up check on one TPU: prune → pack → serve qwen2-1.5b at full width.

    python chip_smoke.py

One process holds the chip for the whole run. It drives the system's main
path once, through the repo's own entry points, at the published width of
qwen2-1.5b (28 layers, d_model 1536, 12 query / 2 KV heads of 128, d_ff
8960, vocab 151,936, bf16) with random weights made from a seed:

  device  the platform must be ``tpu``; otherwise exit 2 before any phase
  prune   ``PrivacyPreservingPruner`` (tile_pattern 4-of-8, 2 ADMM
          iterations) on synthetic tokens. Where the layer-wise ADMM state
          of all 28 blocks does not fit the chip's HBM, the ADMM iterations
          run on the first blocks only and the rest take the one-shot
          projection (``greedy_prune``); the cut is printed on its own line
  pack    ``PruneResult.to_artifact().pack(tune_for=...)`` at the served row
          counts, saved under ``chip_smoke_out/artifact``
  serve   ``ContinuousEngine`` dense, then packed, on the same 8 requests
  check   every request ``ok``; no packed leaf fell back to dense; no tuner
          candidate failed. For each tiled prompt length, a solo prefill
          dense, packed with the tuned plans, and packed with the Pallas
          plan pinned: each compiled program holds flash attention, the
          pinned one also the tile-pattern GEMM kernel, and both packed
          variants' last-token logits agree with dense within a bf16
          tolerance. Greedy-token agreement is reported, not required.

Earlier lines give each phase's wall seconds, compile seconds and
``peak_bytes_in_use``: bring-up facts, not benchmark metrics. The last line
is one JSON object, ``{"ok": true, "device": {...}}``. A failed check exits
non-zero and prints no such line.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.metadata
import json
import logging
import os
import re
import shutil
import sys
import time
from collections import Counter
from typing import Any, Dict, Iterator, List, Optional, Sequence

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.configs.base import ModelConfig  # noqa: E402
from repro.core import (  # noqa: E402
    LMAdapter,
    PrivacyPreservingPruner,
    PruneResult,
    greedy_prune,
)
from repro.launch.prune import prune_config_for  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.runtime.compile_cache import enable_compile_cache  # noqa: E402
from repro.runtime.telemetry import get_registry  # noqa: E402
from repro.serve.engine import ContinuousEngine, Request, Result  # noqa: E402
from repro.sparse import PrunedArtifact  # noqa: E402
from repro.sparse.packed import is_packed  # noqa: E402
from repro.sparse.tune import m_bucket, plan_meta_key  # noqa: E402

ARCH = "qwen2-1.5b"
SEED = 0
RATE = 2.0                 # tile_pattern keep 4 of 8 contraction lanes
PRUNE_ITERS = 2
TILE_BLOCK = 128
SYNTH_SEQ = 64             # synthetic-token sequence length of the pruner
BATCH = 8
MAX_NEW = 32
# two lengths tiled by the 128-row blocks (flash and packed GEMM engage) and
# one ragged length (flash declines it: blockwise attention)
PROMPT_LENS = (512, 384, 203, 512, 384, 203, 512, 384)
CHUNK_STEPS = 32           # one decode program: all 31 decode steps
OUT_DIR = os.path.join(ROOT, "chip_smoke_out")
# relative L2 distance allowed between packed and dense last-token logits:
# eight bf16 epsilons (2^-8 each) for rounding that differs per layer
LOGIT_RTOL = 8 * 2.0 ** -8
# kernel names (``pallas_call(name=...)``) a tiled prefill with the Pallas
# plan pinned must hold, and that plan
PREFILL_KERNELS = ("flash_attention", "pattern_gemm")
PALLAS_PLAN = "pallas:bm=128"


class SmokeFailure(RuntimeError):
    """A check of the bring-up path failed."""


def require(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


# ---------------------------------------------------------------------------
# bring-up facts: wall, compile seconds and device memory per phase
# ---------------------------------------------------------------------------

class CompileClock:
    """Backend-compile seconds of this process, summed from JAX's events."""

    def __init__(self) -> None:
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_: Any) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration


def _peak_bytes() -> Optional[int]:
    stats = jax.devices()[0].memory_stats()
    return None if not stats else int(stats["peak_bytes_in_use"])


@contextlib.contextmanager
def phase(name: str, clock: CompileClock) -> Iterator[None]:
    """Report the phase's wall and backend-compile seconds when it ends."""
    t0, c0 = time.perf_counter(), clock.seconds
    yield
    print(f"[{name}] wall {time.perf_counter() - t0:.3f} s, compile "
          f"{clock.seconds - c0:.3f} s, peak_bytes_in_use "
          f"{_peak_bytes()}", flush=True)


# ---------------------------------------------------------------------------
# phases (each takes a config: the CPU tests run them at reduced size)
# ---------------------------------------------------------------------------

def device_phase() -> jax.Device:
    """The chip this run holds; exit 2 when JAX finds no TPU."""
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU: JAX's first device is {dev.platform!r}; "
              "this check runs only on a TPU", file=sys.stderr)
        sys.exit(2)
    versions = {p: importlib.metadata.version(p)
                for p in ("jax", "jaxlib", "libtpu")}
    print(f"[device] {dev.device_kind}, {len(jax.devices())} device(s), "
          + ", ".join(f"{p} {v}" for p, v in versions.items()), flush=True)
    return dev


def _tree_bytes(tree: Any) -> int:
    return sum(int(np.prod(l.shape)) * jnp.dtype(l.dtype).itemsize
               for l in jax.tree.leaves(tree))


def admm_layer_budget(cfg: ModelConfig, hbm_bytes: int) -> int:
    """Blocks whose layer-wise ADMM state fits ``hbm_bytes`` of HBM.

    Resident during the run: the whole teacher, plus about ten copies of
    each block the ADMM iterations update — its teacher slice, the iterate
    (the committed one, and the new one twice while the stacked tree is
    rewritten) and Z and U three times over (the run's entry snapshot, the
    committed state, the state being built). A quarter of HBM is left for
    activations, temporaries and fragmentation.
    """
    shapes = jax.eval_shape(build_model(cfg).init, jax.random.PRNGKey(0))
    block = _tree_bytes(shapes["blocks"]) // cfg.num_layers
    room = int(0.75 * hbm_bytes) - _tree_bytes(shapes)
    return max(1, min(cfg.num_layers, room // (10 * block)))


def prune_phase(cfg: ModelConfig, *, seed: int, admm_layers: int,
                iters: int = PRUNE_ITERS, tile_block: int = TILE_BLOCK,
                ) -> PruneResult:
    """ADMM-prune a seeded random teacher, tile_pattern at ``RATE``.

    With ``admm_layers < cfg.num_layers`` the layer-wise ADMM iterations
    run on blocks ``[0, admm_layers)``; every other prunable tensor takes
    the one-shot projection onto the same constraint set.
    """
    model = build_model(cfg)
    teacher = model.init(jax.random.PRNGKey(seed))
    pcfg = prune_config_for(scheme="tile_pattern", rate=RATE, iters=iters,
                            tile_block=tile_block)
    key = jax.random.PRNGKey(seed + 1)
    if admm_layers >= cfg.num_layers:
        return PrivacyPreservingPruner(
            LMAdapter(model, seq_len=SYNTH_SEQ), pcfg).run(key, teacher)
    head = dataclasses.replace(cfg, num_layers=admm_layers)
    head_teacher = {**teacher, "blocks": jax.tree.map(
        lambda x: x[:admm_layers], teacher["blocks"])}
    res = PrivacyPreservingPruner(
        LMAdapter(build_model(head), seq_len=SYNTH_SEQ), pcfg).run(
            key, head_teacher)
    del head_teacher
    blocks = jax.tree.map(lambda a, t: jnp.concatenate([a, t[admm_layers:]]),
                          res.params["blocks"], teacher["blocks"])
    whole = greedy_prune({**res.params, "blocks": blocks}, pcfg)
    return dataclasses.replace(whole, history=res.history,
                               seconds_per_iter=res.seconds_per_iter,
                               provenance=res.provenance)


def pack_phase(result: PruneResult, cfg: ModelConfig, out_dir: str,
               tune_for: Sequence[int]) -> PrunedArtifact:
    """Pack, tune at the served row counts, and save under ``out_dir``."""
    art = result.to_artifact(arch=cfg.name, scheme="tile_pattern",
                             rate=RATE).pack(tune_for=tuple(tune_for))
    if os.path.isdir(out_dir):
        shutil.rmtree(out_dir)
    art.save(out_dir)
    return art


def make_requests(cfg: ModelConfig, prompt_lens: Sequence[int],
                  max_new: int, seed: int) -> List[Request]:
    key = jax.random.PRNGKey(seed + 7)
    return [Request(uid=i, max_new_tokens=max_new,
                    prompt=jax.random.randint(jax.random.fold_in(key, i),
                                              (S,), 0, cfg.vocab_size))
            for i, S in enumerate(prompt_lens)]


def serve_phase(model, artifact: PrunedArtifact, requests: List[Request], *,
                packed: bool, batch: int = BATCH,
                chunk_steps: int = CHUNK_STEPS):
    """Serve ``requests`` through ``ContinuousEngine``; (results, engine)."""
    max_seq = max(int(r.prompt.shape[0]) + r.max_new_tokens
                  for r in requests)
    engine = ContinuousEngine(model, artifact, batch_size=batch,
                              max_seq_len=max_seq, chunk_steps=chunk_steps,
                              packed=packed)
    return engine.generate(requests), engine


# ---------------------------------------------------------------------------
# checks
# ---------------------------------------------------------------------------

def tuner_failures() -> Dict[str, float]:
    """Tuner candidates that failed to build or run, by label set."""
    fam = get_registry().counter_family("tune.candidate_failures_total")
    return {",".join(f"{k}={v}" for k, v in lk): n
            for lk, n in fam.items() if n}


def token_agreement(dense: List[Result], packed: List[Result]
                    ) -> Dict[str, Any]:
    """How far greedy tokens agree: per request, the common prefix."""
    firsts = {}
    same = 0
    for d, p in zip(dense, packed):
        n = 0
        while n < min(len(d.tokens), len(p.tokens)) \
                and d.tokens[n] == p.tokens[n]:
            n += 1
        if n == len(d.tokens) == len(p.tokens):
            same += 1
        else:
            firsts[d.uid] = n
    total = sum(len(d.tokens) for d in dense)
    agree = sum(firsts.get(d.uid, len(d.tokens)) for d in dense)
    return {"identical_requests": same, "requests": len(dense),
            "prefix_tokens_agreeing": agree, "tokens": total,
            "first_divergence": firsts}


def check_serve(dense: List[Result], packed: List[Result],
                packed_engine: ContinuousEngine) -> Dict[str, Any]:
    for mode, results in (("dense", dense), ("packed", packed)):
        bad = {r.uid: r.status for r in results if r.status != "ok"}
        require(not bad, f"{mode} requests not ok: {bad}")
    falls = packed_engine.bind_report["fallbacks"]
    require(not falls, f"packed leaves served dense: {falls}")
    fails = tuner_failures()
    require(not fails, f"tuner candidates failed: {fails}")
    return token_agreement(dense, packed)


_KERNEL_CALL = re.compile(
    r"%([A-Za-z_][\w-]*?)(?:\.\d+)? = .*custom_call_target=\"tpu_custom_call\"")


def kernel_calls(hlo_text: str) -> Counter:
    """Pallas kernels in compiled HLO, counted by kernel name."""
    return Counter(m.group(1) for m in _KERNEL_CALL.finditer(hlo_text))


def prefill_logits(model, params: Any, prompt: jnp.ndarray):
    """Compile one solo prefill; return (last-token logits, compiled HLO)."""
    fn = jax.jit(lambda p, x: model.prefill(p, x, x.shape[1])[1])
    compiled = fn.lower(params, prompt[None]).compile()
    return compiled(params, prompt[None]), compiled.as_text()


def pin_prefill_plan(artifact: PrunedArtifact, prompt_lens: Sequence[int],
                     plan: str = PALLAS_PLAN) -> PrunedArtifact:
    """``artifact`` with ``plan`` in place of the tuned plan of every
    tile_pattern leaf at the M buckets of ``prompt_lens``."""

    def leaf(x):
        if not is_packed(x) or x.scheme != "tile_pattern":
            return x
        small = int(x.meta_dict.get("small_m", 32))
        keys = {plan_meta_key("matmul", m_bucket(S, small))
                for S in prompt_lens}
        meta = [kv for kv in x.meta if kv[0] not in keys]
        return dataclasses.replace(
            x, meta=tuple(meta + [(k, plan) for k in sorted(keys)]))

    return dataclasses.replace(artifact, packed=jax.tree.map(
        leaf, artifact.packed, is_leaf=is_packed))


def check_prefill(model, artifact: PrunedArtifact, prompt: jnp.ndarray, *,
                  expect_kernels: bool = True) -> Dict[str, Any]:
    """Packed vs dense prefill of one prompt, packed twice: with the tuned
    plans the engine served, and with the Pallas plan pinned. Last-token
    logits must agree with dense; where ``expect_kernels``, the compiled
    programs must hold the Pallas kernels each variant calls for."""
    S = int(prompt.shape[0])
    variants = {
        "dense": (artifact, False, ("flash_attention",)),
        "packed": (artifact, True, ("flash_attention",)),
        "pallas": (pin_prefill_plan(artifact, (S,)), True, PREFILL_KERNELS),
    }
    out = {"S": S}
    for mode, (art, packed, want) in variants.items():
        logits, hlo = prefill_logits(model, art.bind(model, packed=packed),
                                     prompt)
        logits = np.asarray(logits, np.float32)
        require(logits.shape == (1, 1, model.config.vocab_size)
                and bool(np.isfinite(logits).all()),
                f"{mode} prefill logits {logits.shape} not finite")
        calls = kernel_calls(hlo)
        missing = [k for k in want if expect_kernels and not calls[k]]
        require(not missing, f"{mode} prefill S={S} lacks kernels "
                             f"{missing} (has {dict(calls)})")
        out[mode] = {"kernels": dict(calls)}
        if mode == "dense":
            dense = logits
            continue
        rel = float(np.linalg.norm(logits - dense)
                    / max(np.linalg.norm(dense), 1e-30))
        require(rel <= LOGIT_RTOL, f"{mode} vs dense logits at S={S}: "
                                   f"relative L2 {rel} > {LOGIT_RTOL}")
        out[mode].update(rel_l2=rel,
                         max_abs=float(np.abs(logits - dense).max()),
                         argmax_equal=bool(dense.argmax() == logits.argmax()))
    return out


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

def main() -> None:
    dev = device_phase()
    cache = enable_compile_cache()
    entries = len(os.listdir(cache)) if os.path.isdir(cache) else 0
    print(f"[device] compile cache {cache}: {entries} entries at start",
          flush=True)
    logging.basicConfig(level=logging.WARNING)
    logging.getLogger("repro.sparse.tune").setLevel(logging.INFO)
    clock = CompileClock()
    cfg = get_config(ARCH)
    hbm = int(dev.memory_stats()["bytes_limit"])

    with phase("prune", clock):
        layers = admm_layer_budget(cfg, hbm)
        if layers < cfg.num_layers:
            print(f"[prune] cut: ADMM iterations on blocks 0-{layers - 1} "
                  f"of {cfg.num_layers} (their layer-wise state fills "
                  f"{hbm} bytes of HBM); blocks {layers}-"
                  f"{cfg.num_layers - 1} and lm_head take the one-shot "
                  "projection", flush=True)
        result = prune_phase(cfg, seed=SEED, admm_layers=layers)
        print(f"[prune] {PRUNE_ITERS} iterations, loss "
              f"{result.history['loss']}, {result.seconds_per_iter:.3f} "
              "s/iteration", flush=True)

    with phase("pack", clock):
        artifact = pack_phase(result, cfg, os.path.join(OUT_DIR, "artifact"),
                              tune_for=(BATCH,) + PROMPT_LENS)
        del result
        gc.collect()
        s = artifact.summary()
        print(f"[pack] {s['packed_leaves']}/{s['total_leaves']} leaves "
              f"packed; weight bytes dense {s['dense_bytes']} packed "
              f"{s['packed_bytes']} (ratio {s['bytes_ratio']:.4f})",
              flush=True)
        for leaf, plan in sorted(artifact.meta["tuned_plans"].items()):
            print(f"[pack] plan {leaf} = {plan}", flush=True)

    model = build_model(cfg)
    requests = make_requests(cfg, PROMPT_LENS, MAX_NEW, SEED)
    with phase("serve dense", clock):
        dense, _ = serve_phase(model, artifact, requests, packed=False)
    with phase("serve packed", clock):
        packed, engine = serve_phase(model, artifact, requests, packed=True)

    with phase("check", clock):
        agree = check_serve(dense, packed, engine)
        print(f"[check] greedy tokens: {agree['identical_requests']}/"
              f"{agree['requests']} requests identical; "
              f"{agree['prefix_tokens_agreeing']}/{agree['tokens']} tokens "
              f"before the first divergence; first divergence by uid "
              f"{agree['first_divergence']}", flush=True)
        tiled = sorted({S for S in PROMPT_LENS if S % 128 == 0})
        for S in tiled:
            r = check_prefill(model, artifact, requests[
                PROMPT_LENS.index(S)].prompt)
            print(f"[check] prefill S={S}: dense kernels "
                  f"{r['dense']['kernels']}", flush=True)
            for mode in ("packed", "pallas"):
                v = r[mode]
                print(f"[check] prefill S={S} {mode}: logits relative L2 "
                      f"{v['rel_l2']:.6g} (limit {LOGIT_RTOL}), max |diff| "
                      f"{v['max_abs']:.6g}, argmax equal "
                      f"{v['argmax_equal']}; kernels {v['kernels']}",
                      flush=True)

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
