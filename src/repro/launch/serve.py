"""Serving launcher: batched generation with a (pruned) LM.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b --reduced \
        --requests 8 --max-new 16 [--ckpt /tmp/pruned_qwen2/pruned]
    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b --reduced \
        --artifact /tmp/qwen2_artifact --packed
    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b --reduced \
        --speculative /tmp/qwen2_artifact --draft-k 4

Loads either a raw checkpoint (``--ckpt``, e.g. the output of
launch/prune.py after client retraining) or a saved ``PrunedArtifact``
directory (``--artifact``) and serves a batch of random-prompt requests
through the continuous-batching engine. ``--packed`` (artifact only) binds
the compressed representation: every block GEMM runs through the
scheme→kernel registry instead of dense matmuls. The decode step is the
same program the dry-run's decode_32k/long_500k cells lower; on TPU
backends the prefill path routes attention through the Pallas flash kernel.

``--speculative <artifact-dir>`` serves SPECULATIVELY: the saved pruned
artifact drafts ``--draft-k`` tokens per round (packed) and the engine's
own params verify them in one chunked dispatch — greedy output is
bit-identical to serving the engine params alone, and the acceptance
numbers print after the run (see ``serve/speculative.py``).
"""

from __future__ import annotations

import argparse
import logging
import time

import jax

from repro.checkpoint import restore_pytree
from repro.configs import get_config, reduced_config
from repro.models import build_model
from repro.runtime.compile_cache import enable_compile_cache
from repro.serve.engine import Request, ServeEngine

log = logging.getLogger(__name__)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--artifact", default=None,
                    help="saved PrunedArtifact directory (see sparse/)")
    ap.add_argument("--packed", action="store_true",
                    help="serve the packed representation (needs --artifact)")
    ap.add_argument("--speculative", default=None, metavar="DRAFT_ARTIFACT",
                    help="saved PrunedArtifact directory to DRAFT with: the "
                         "packed drafter proposes --draft-k tokens/round, "
                         "the engine params verify (output bit-identical "
                         "to serving without it)")
    ap.add_argument("--draft-k", type=int, default=4,
                    help="draft tokens per speculative round")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-seq", type=int, default=256)
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write a metrics snapshot after the run: "
                         "Prometheus text exposition if PATH ends in "
                         ".prom/.txt, JSON otherwise. Includes kernel "
                         "dispatch counts and autotune timings (the "
                         "process-wide registry), not just serve latency")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="append request-lifecycle trace events (schema-"
                         "versioned JSONL spans: prefill/decode chunks, "
                         "per-request retire) to PATH")
    args = ap.parse_args()

    logging.basicConfig(level=logging.INFO)
    enable_compile_cache()
    cfg = reduced_config(args.arch) if args.reduced else get_config(args.arch)
    if cfg.encoder_only:
        raise SystemExit(f"{args.arch} is encoder-only; no decode serving")
    if args.packed and not args.artifact:
        raise SystemExit("--packed requires --artifact")
    if args.artifact and args.ckpt:
        raise SystemExit("--artifact and --ckpt are mutually exclusive: the "
                         "artifact already carries its weights")
    model = build_model(cfg)

    if args.artifact:
        from repro.sparse import PrunedArtifact

        params = PrunedArtifact.load(args.artifact)
        log.info("loaded artifact %s: %s", args.artifact, params.summary())
    else:
        params = model.init(jax.random.PRNGKey(0))
        if args.ckpt:
            params = restore_pytree(args.ckpt, params)
            log.info("restored %s", args.ckpt)

    draft = None
    if args.speculative:
        from repro.sparse import PrunedArtifact

        draft = PrunedArtifact.load(args.speculative)
        log.info("loaded draft artifact %s: %s", args.speculative,
                 draft.summary())

    telemetry = None
    if args.metrics_out or args.trace_out:
        from repro.runtime.telemetry import Telemetry, get_registry

        # record into the process-wide registry so kernel dispatch and
        # autotune events land in the same snapshot as serve latency
        telemetry = Telemetry(metrics=get_registry(),
                              trace_path=args.trace_out)

    engine = ServeEngine(model, params, batch_size=args.batch,
                         max_seq_len=args.max_seq, packed=args.packed,
                         speculative=draft, draft_k=args.draft_k,
                         telemetry=telemetry)
    key = jax.random.PRNGKey(7)
    reqs = [
        Request(uid=i,
                prompt=jax.random.randint(
                    jax.random.fold_in(key, i),
                    (args.prompt_len,), 0, cfg.vocab_size),
                max_new_tokens=args.max_new)
        for i in range(args.requests)
    ]
    t0 = time.time()
    results = engine.generate(reqs)
    dt = time.time() - t0
    n_tok = sum(len(r.tokens) for r in results)
    mode = "packed" if args.packed else "dense"
    if args.speculative:
        mode += f"+speculative(k={args.draft_k})"
    print(f"{len(results)} requests, {n_tok} tokens in {dt:.2f}s "
          f"({n_tok / dt:.1f} tok/s, batch={args.batch}, {mode})")
    if args.speculative:
        st = engine.speculative.stats
        print(f"  speculative: {st['rounds']} rounds, acceptance "
              f"{st['acceptance_rate']:.3f} "
              f"({st['accepted']}/{st['drafted']} drafts)")
    for r in results[:4]:
        print(f"  uid={r.uid}: {r.tokens[:12]}{'...' if len(r.tokens) > 12 else ''}")

    if telemetry is not None:
        telemetry.close()
        if args.metrics_out:
            from repro.runtime import telemetry_export

            if args.metrics_out.endswith((".prom", ".txt")):
                telemetry_export.write_prometheus(args.metrics_out,
                                                  telemetry.metrics)
            else:
                telemetry_export.write_json(
                    args.metrics_out, telemetry.metrics,
                    arch=args.arch, mode=mode)
            log.info("metrics snapshot -> %s", args.metrics_out)
        if args.trace_out:
            log.info("trace -> %s", args.trace_out)


if __name__ == "__main__":
    main()
