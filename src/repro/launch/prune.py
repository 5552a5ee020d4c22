"""The SYSTEM DESIGNER's pruning service — the paper's Fig. 2b left box.

Inputs: the client's pre-trained checkpoint (never her data). Outputs: a
pruned checkpoint + the mask function, both saved atomically for the client
to pick up for masked retraining (launch/train.py --masks).

    PYTHONPATH=src python -m repro.launch.prune --arch qwen2-1.5b --reduced \
        --scheme tile_pattern --rate 2 --iters 60 --out /tmp/pruned_qwen2

On a real fleet this service runs data-parallel over synthetic batches
(pure jit — the batch dimension shards over the data axis) with weights
TP-sharded; on this box it runs single-host. Privacy property is structural:
the only inputs are (checkpoint, PRNG key, config).
"""

from __future__ import annotations

import argparse
import logging
import os
import time

import jax

from repro.checkpoint import save_pytree, restore_pytree
from repro.configs import get_config, reduced_config
from repro.core import (
    DEFAULT_EXCLUDE,
    LMAdapter,
    PruneConfig,
    PrivacyPreservingPruner,
    compression_rate,
    sparsity,
)
from repro.models import build_model
from repro.runtime.compile_cache import enable_compile_cache

log = logging.getLogger(__name__)


def prune_config_for(
    *,
    scheme: str,
    rate: float,
    iters: int,
    batch: int = 16,
    tile_block: int = 128,
    layerwise: bool = True,
    exclude=None,
) -> PruneConfig:
    """The service's PruneConfig policy, shared by this CLI and
    ``launch/pipeline.py``: tile_pattern lanes quantize the rate to
    keep-of-8, ρ steps three times over the run."""
    overrides = {}
    if scheme == "tile_pattern":
        keep = max(1, min(7, round(8 / rate)))
        if abs(8 / keep - rate) > 1e-9:
            log.warning(
                "tile_pattern lanes quantize to keep %d-of-8 (%.2fx), not "
                "the requested %.2fx", keep, 8 / keep, rate)
        overrides = {".*": {"tile_block_p": tile_block, "tile_keep": keep}}
    return PruneConfig(
        scheme=scheme, alpha=1.0 / rate,
        exclude=tuple(DEFAULT_EXCLUDE) if exclude is None else tuple(exclude),
        iterations=iters, batch_size=batch, lr=1e-3,
        rho_every_iters=max(iters // 3, 1),
        layerwise=layerwise,
        overrides=overrides,
    )


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--scheme", default="irregular",
                    choices=["irregular", "filter", "column", "tile_pattern"])
    ap.add_argument("--rate", type=float, default=4.0)
    ap.add_argument("--iters", type=int, default=60)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--teacher-ckpt", default=None,
                    help="client checkpoint dir (else random init, demo mode)")
    ap.add_argument("--out", required=True)
    ap.add_argument("--artifact-out", default=None,
                    help="also save a PACKED PrunedArtifact here "
                         "(servable via launch/serve.py --artifact ... "
                         "--packed)")
    ap.add_argument("--layerwise", action=argparse.BooleanOptionalAction,
                    default=True, help="problem (3) vs problem (2)")
    ap.add_argument("--tile-block", type=int, default=128,
                    help="tile_pattern block_p; must divide every GEMM "
                         "output dim (reduced configs want 32)")
    ap.add_argument("--save-every", type=int, default=0,
                    help="checkpoint the full ADMM run state every N "
                         "iterations (0 = off); a killed run resumed "
                         "with --resume is bit-identical to an "
                         "uninterrupted one")
    ap.add_argument("--resume", action="store_true",
                    help="resume from the latest run-state checkpoint "
                         "under --ckpt-dir (fresh start if none/stale)")
    ap.add_argument("--ckpt-dir", default=None,
                    help="run-state checkpoint directory "
                         "(default <out>/prune_ckpt)")
    ap.add_argument("--chaos-kill-at", type=int, default=None,
                    help="TEST SEAM: SIGKILL this process once ADMM "
                         "iteration N has committed — the deterministic "
                         "mid-run death the CI kill-and-resume smoke "
                         "drives")
    args = ap.parse_args()

    logging.basicConfig(level=logging.INFO)
    enable_compile_cache()
    cfg = reduced_config(args.arch) if args.reduced else get_config(args.arch)
    model = build_model(cfg)

    params = model.init(jax.random.PRNGKey(0))
    if args.teacher_ckpt:
        params = restore_pytree(args.teacher_ckpt, params)
        log.info("restored client checkpoint from %s", args.teacher_ckpt)
    else:
        log.warning("no --teacher-ckpt: using random init (demo mode)")

    config = prune_config_for(
        scheme=args.scheme, rate=args.rate, iters=args.iters,
        batch=args.batch, tile_block=args.tile_block,
        layerwise=args.layerwise,
    )
    adapter = LMAdapter(model, seq_len=args.seq)
    ckpt_dir = None
    if args.save_every > 0 or args.resume:
        ckpt_dir = args.ckpt_dir or os.path.join(args.out, "prune_ckpt")
    callback = None
    if args.chaos_kill_at is not None:
        from repro.testing.chaos import kill_at_iteration

        callback = kill_at_iteration(args.chaos_kill_at, hard=True)
    t0 = time.time()
    result = PrivacyPreservingPruner(adapter, config).run(
        jax.random.PRNGKey(1), params,
        checkpoint_dir=ckpt_dir, save_every=args.save_every,
        resume=args.resume, callback=callback)
    log.info("pruned %.2fx (sparsity %.1f%%) in %.1fs — client data never "
             "touched", compression_rate(result.masks),
             100 * sparsity(result.masks), time.time() - t0)

    save_pytree(args.out + "/pruned", result.params,
                extra={"arch": args.arch, "scheme": args.scheme,
                       "rate": args.rate})
    # densify: None (unpruned) → all-ones mask, so the client can restore
    # with a params-congruent template (launch/train.py --masks)
    import jax.numpy as jnp

    dense_masks = jax.tree.map(
        lambda m, p: (jnp.ones(p.shape, jnp.bfloat16) if m is None
                      else m.astype(jnp.bfloat16)),
        result.masks, result.params,
        is_leaf=lambda x: x is None,
    )
    save_pytree(args.out + "/masks", dense_masks,
                extra={"arch": args.arch})
    if args.artifact_out:
        artifact = result.to_artifact(arch=args.arch, scheme=args.scheme,
                                      rate=args.rate).pack()
        artifact.save(args.artifact_out)
        s = artifact.summary()
        log.info("packed artifact -> %s (%d/%d leaves, %.2fx weight bytes)",
                 args.artifact_out, s["packed_leaves"], s["total_leaves"],
                 s["bytes_ratio"])
    print(f"pruned model -> {args.out}/pruned ; mask function -> "
          f"{args.out}/masks")
    print(f"compression {compression_rate(result.masks):.2f}x "
          f"({config.scheme} @ alpha={config.alpha:.3f}, "
          f"{'layer-wise (3)' if config.layerwise else 'whole-model (2)'})")


if __name__ == "__main__":
    main()
