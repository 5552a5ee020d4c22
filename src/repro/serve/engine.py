"""Serving engines: chunked batches (``ServeEngine``) and continuous
batching (``ContinuousEngine``).

Two engines share the device-resident hot path (one jitted
``LM.decode_many`` scan per token block, on-device sampling, one
device→host transfer) and differ in how requests map onto batch slots:

``ServeEngine`` — FIXED chunked batches: ``generate`` splits the request
list into chunks of ``batch_size``; each chunk is prefilled together and
decoded together to the chunk's longest ``max_new_tokens``. A finished
slot idles (masked) until its chunk completes, and mixed-length chunks
left-pad prompts with zero tokens the model attends to (bucketing by
prompt length minimizes this; equal-length chunks are pad-free). It is
the single-compile, simplest-geometry path: best when requests arrive in
homogeneous batches, and the bit-identical fallback the continuous
engine is tested against.

``ContinuousEngine`` — SLOT-MANAGED continuous batching: each batch slot
owns its KV rows (per-slot write position, per-slot valid-length mask,
per-slot rotary offsets — see ``serve/slots.py``), decode runs in fixed
micro-chunks of ``chunk_steps`` scanned steps, and BETWEEN chunks the
scheduler retires slots that hit their own ``max_new_tokens``/``eos_id``
and admits queued requests into freed slots via ``LM.prefill_into_slot``
— a solo (1, S) prefill written into one row of the live cache, so
admitted prompts are never distorted by chunk-mates' padding and live
slots never notice the admission. Results stream per-request as they
finish. Best under arrival processes and mixed-length/mixed-budget
workloads — the batch stays full instead of draining to its slowest
member.

Pruned models serve two ways on either engine:
  * dense sparse — weights are already exactly sparse; no mask logic needed
    (the paper's baseline deployment: prune → retrain → deploy);
  * PACKED — pass a ``sparse.PrunedArtifact`` with ``packed=True`` and the
    engine binds the compressed representation: every GEMM dispatches
    through the scheme→kernel registry's pack-time plans (compressed weight
    storage on the hot path, the paper's compiler-level deployment).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.transformer import LM
from repro.runtime.profiler import get_profiler
from repro.runtime.telemetry import MetricsRegistry, Telemetry, Timeline
from repro.serve.sampler import (
    fold_key_grid,
    greedy_sample,
    request_key,
    temperature_sample,
)
from repro.serve.scheduler import Scheduler
from repro.serve.slots import trim_at_eos


class CancelToken:
    """Host-side cancel handle: the submitter flips it, the engine reads
    it between micro-chunks (never mid-scan — a dispatched chunk always
    finishes; cancellation costs at most one chunk of extra decode)."""

    __slots__ = ("_cancelled",)

    def __init__(self) -> None:
        self._cancelled = False

    def cancel(self) -> None:
        self._cancelled = True

    @property
    def cancelled(self) -> bool:
        return self._cancelled


@dataclasses.dataclass
class Request:
    uid: int
    prompt: jnp.ndarray              # (S,) int32 (or (S, D) embeddings)
    max_new_tokens: int = 16
    eos_id: Optional[int] = None     # stop after emitting this token
    temperature: Optional[float] = None   # None or <= 0 → greedy
    seed: Optional[int] = None       # per-request PRNG stream: token i draws
    # from fold_in(PRNGKey(seed), i) on every engine, so a stochastic
    # request reproduces regardless of engine seed or batch-mates
    deadline: Optional[float] = None  # absolute seconds on the ENGINE clock
    # (same clock as ``arrivals``); past it the request is reaped between
    # chunks with status "timeout" — queued requests before ever costing a
    # prefill, live ones keeping the tokens emitted so far
    cancel_token: CancelToken = dataclasses.field(default_factory=CancelToken)

    def cancel(self) -> None:
        """Request-scoped cancellation; honored at the next chunk edge."""
        self.cancel_token.cancel()

    @property
    def cancelled(self) -> bool:
        return self.cancel_token.cancelled


@dataclasses.dataclass
class Result:
    uid: int
    tokens: List[int]
    # terminal disposition — the reliability state machine:
    #   ok        ran to its own stop (max_new_tokens / eos)
    #   shed      never queued: bounded queue (or capacity check) rejected it
    #   timeout   deadline passed (tokens = partial output, possibly [])
    #   cancelled cancel() fired   (tokens = partial output, possibly [])
    #   failed    slot poisoned (non-finite logits) or engine gave up on it
    status: str = "ok"


def _bucketed_generate(requests: List[Request], batch_size: int,
                       generate_batch: Callable[[List[Request]],
                                                List["Result"]]
                       ) -> List["Result"]:
    """The chunking loop the chunked AND speculative engines share:
    bucket by prompt length (stable sort — same-length requests keep
    arrival order), serve ``batch_size`` chunks, restore results to the
    ORIGINAL request order. One implementation, because the speculative
    engine's bit-identity guarantee rests on composing chunks exactly
    like ``ServeEngine`` does."""
    order = sorted(range(len(requests)),
                   key=lambda i: int(requests[i].prompt.shape[0]))
    results: List[Optional[Result]] = [None] * len(requests)
    for i in range(0, len(order), batch_size):
        idxs = order[i : i + batch_size]
        out = generate_batch([requests[j] for j in idxs])
        for j, res in zip(idxs, out):
            results[j] = res
    return results  # type: ignore[return-value]


def _pad_prompts(requests: List[Request], batch_size: int):
    """Left-pad a chunk's prompts to its longest and stack to a full
    ``(B, S)`` batch (empty slots get zero prompts). Returns
    ``(prompts, slot_mask)`` — the shared prefill geometry of the chunked
    and speculative engines (identical padding ⇒ identical tokens)."""
    n = len(requests)
    S = max(int(r.prompt.shape[0]) for r in requests)

    def pad(r: Request):
        p = r.prompt
        if p.shape[0] < S:
            pad_width = [(S - p.shape[0], 0)] + [(0, 0)] * (p.ndim - 1)
            p = jnp.pad(p, pad_width)
        return p

    padded = [pad(r) for r in requests]
    prompts = jnp.stack(padded
                        + [jnp.zeros_like(padded[0])] * (batch_size - n))
    slot_mask = jnp.asarray([1] * n + [0] * (batch_size - n), jnp.int32)
    return prompts, slot_mask


def _tree_nbytes(tree: Any) -> int:
    """Total device bytes of a pytree's array leaves (profiler
    bytes-streamed accounting: KV caches, weight trees)."""
    return sum(int(getattr(l, "nbytes", 0))
               for l in jax.tree_util.tree_leaves(tree))


def _stochastic_rows(requests: List[Request], batch_size: int,
                     engine_key: jax.Array):
    """Per-slot temperatures and per-REQUEST base keys for a chunk:
    ``(temps (B,), row_keys (B, 2), new_engine_key)``. Shared by the
    chunked and speculative engines so ``Request.seed`` reproduces
    identically on both (request_key per row, 0.0-temp and PRNGKey(0)
    fill for empty slots)."""
    n = len(requests)
    temps = jnp.asarray(
        [r.temperature if r.temperature is not None else 0.0
         for r in requests] + [0.0] * (batch_size - n), jnp.float32)
    keys = []
    for r in requests:
        k, engine_key = request_key(r.seed, engine_key)
        keys.append(k)
    row_keys = jnp.stack(
        keys + [jax.random.PRNGKey(0)] * (batch_size - n))
    return temps, row_keys, engine_key


def _scan_decode_fns(model: LM, sampler: Callable, with_flags: bool = False):
    """The masked decode-scan wrappers both engines jit: free/pad slots'
    sampled tokens pin to 0 under ``mask``; the temp variant threads
    per-slot temperatures and per-step keys (all traced arguments, so
    new requests never retrace). ``with_flags`` forwards to
    ``decode_many`` — the continuous engine's per-slot NaN guard; the
    flags observe the logits without touching token math, so flagged and
    unflagged programs emit bit-identical tokens."""

    def scan_decode(p, cache, tok, mask, num_steps):
        samp = lambda logits: sampler(logits) * mask[:, None]
        return model.decode_many(p, cache, tok, num_steps, sampler=samp,
                                 with_flags=with_flags)

    def scan_decode_temp(p, cache, tok, mask, temps, keys, num_steps):
        samp = lambda logits, key: (
            temperature_sample(logits, key, temps) * mask[:, None])
        return model.decode_many(p, cache, tok, num_steps, sampler=samp,
                                 keys=keys, with_flags=with_flags)

    return scan_decode, scan_decode_temp


def _resolve_params(model: LM, params: Any, packed: bool):
    """Accept a raw params tree, a ``PruneResult``, or a ``PrunedArtifact``
    and return ``(bound params, bind_report)`` — the report records any
    corrupt packed leaves ``bind`` degraded to dense serving (None for raw
    trees, which have nothing to degrade)."""
    from repro.core.pruner import PruneResult
    from repro.sparse import PrunedArtifact

    if isinstance(params, PruneResult):
        params = params.to_artifact()
    if isinstance(params, PrunedArtifact):
        bound = params.bind(model, packed=packed)
        return bound, params.bind_report
    if packed:
        raise TypeError(
            "packed=True needs a PrunedArtifact (or PruneResult); got a "
            "raw params tree — build one via PruneResult.to_artifact()"
        )
    return params, None


class ServeEngine:
    def __init__(
        self,
        model: LM,
        params: Any,
        *,
        batch_size: int,
        max_seq_len: int,
        sampler: Callable = greedy_sample,
        packed: bool = False,
        flash: Optional[bool] = None,
        bake_weights: Optional[bool] = None,
        seed: int = 0,
        speculative: Optional[Any] = None,
        draft_k: int = 4,
        draft_model: Optional[LM] = None,
        telemetry: Optional[Telemetry] = None,
        straggler: Optional[Any] = None,
    ):
        """``params`` may be a raw params tree, a ``PruneResult``, or a
        ``sparse.PrunedArtifact``. With ``packed=True`` (artifact/result
        only) the engine serves the compressed representation through the
        scheme→kernel registry. ``sampler`` must be jit-compatible
        (``logits (B, 1, V) -> (B, 1) int32``) — it runs on device inside
        the decode scan. Requests that set ``temperature`` override it:
        their chunk routes through the vectorized ``temperature_sample``
        with a per-slot temperature array (requests without one sample
        greedily there), keyed from ``seed``. ``flash`` forwards to
        ``LM.prefill``: None = auto (Pallas flash attention on real TPU
        backends, XLA blockwise otherwise/for unsupported shapes),
        True/False = force.

        ``bake_weights`` — close the bound params over the jitted PREFILL
        closure as COMPILE-TIME constants instead of per-call arguments:
        the weights of a serving engine never change, and specializing the
        program for them is the paper's compiler-level deployment (static
        lane/index tables lower to far better gather code than dynamic
        ones; constants fold). Costs one baked copy of the weights PER
        COMPILED PROMPT LENGTH — each distinct padded chunk length S
        compiles its own prefill executable, so serving highly diverse
        prompt lengths with a large model grows memory with the number of
        distinct lengths (pass bake_weights=False there). Decode keeps
        argument-passed params — its gathers are batch-sized and the
        scan's in-place cache update matters more than constant folding.
        None = auto: on for CPU backends (where the XLA gather lowering
        gains the most and weights are host-resident anyway), off on
        TPU.

        ``speculative`` — a drafter (``PrunedArtifact``/``PruneResult``,
        bound packed, or a raw params tree for ``draft_model``): route
        ``generate`` through a ``serve.SpeculativeEngine`` that drafts
        ``draft_k`` tokens per round with it and verifies them against
        THIS engine's params in one chunked dispatch. Greedy output stays
        bit-identical to this engine's own; ``engine.speculative.stats``
        has the acceptance numbers.

        ``telemetry`` — optional ``runtime.telemetry.Telemetry``: the
        engine records batch-level spans (``prefill``, ``decode_chunk``)
        and per-request ``retire`` events into its tracer, and latency
        histograms / status counters (labelled ``engine="chunked"``)
        into its registry. None = metrics into a private throwaway
        registry, no tracing — the hot path is unchanged either way
        (telemetry observes at host sync points; tokens are
        bit-identical with it on or off). Note the chunked engine has a
        SINGLE host sync per batch (the one token-block transfer), so
        its lifecycle timings are batch-granular: TTFT is measured from
        batch start to that sync.

        ``straggler`` — optional ``runtime.StragglerMonitor``: the engine
        records each batch's decode wall into it and, when a batch is
        flagged, emits a ``straggler`` tracer event (when tracing).
        Forwarded to the ``SpeculativeEngine`` when ``speculative`` is
        set, so speculative dispatch walls are monitored too."""
        self.model = model
        self.params, self.bind_report = _resolve_params(model, params,
                                                        packed)
        self.batch_size = batch_size
        self.max_seq_len = max_seq_len
        self.sampler = sampler
        self.telemetry = telemetry
        self.straggler = straggler
        self._batches = 0
        self._nbytes: Dict[Any, int] = {}   # profiler bytes, keyed by shape
        self._key = jax.random.PRNGKey(seed)
        self.speculative = None
        if speculative is not None:
            from repro.serve.speculative import SpeculativeEngine

            self.speculative = SpeculativeEngine(
                model, self.params, speculative, batch_size=batch_size,
                max_seq_len=max_seq_len, draft_k=draft_k,
                draft_model=draft_model, flash=flash, seed=seed,
                telemetry=telemetry, straggler=straggler,
            )
        backend = jax.default_backend()
        bake = (backend == "cpu") if bake_weights is None else bool(
            bake_weights)

        scan_decode, scan_decode_temp = _scan_decode_fns(model, sampler)

        if bake:
            # weight-specialized prefill: keeps the (p, x) call signature
            # but the bound tree is a compile-time constant inside the
            # jitted program — guard against serving rebound params from
            # the stale baked copy
            bp = self.params
            _jprefill = jax.jit(
                lambda x: model.prefill(bp, x, max_seq_len, flash=flash))

            def _prefill(p, x):
                if p is not bp:
                    raise ValueError(
                        "this engine was built with bake_weights: the "
                        "params are compiled into the prefill executable "
                        "and cannot be swapped — construct a new "
                        "ServeEngine to serve different weights"
                    )
                return _jprefill(x)

            self._prefill = _prefill
        else:
            self._prefill = jax.jit(
                lambda p, x: model.prefill(p, x, max_seq_len, flash=flash)
            )
        self._decode = jax.jit(model.decode_step)
        # donate the prefill cache into the scan: on TPU the decode loop
        # mutates the KV buffers in place (CPU has no donation — skip the
        # warning noise)
        donate = (1,) if backend == "tpu" else ()
        self._decode_many = jax.jit(
            scan_decode, static_argnums=(4,), donate_argnums=donate
        )
        self._decode_many_temp = jax.jit(
            scan_decode_temp, static_argnums=(6,), donate_argnums=donate
        )

    def generate(self, requests: List[Request]) -> List[Result]:
        """Serve a list of requests in fixed-size batches.

        Requests are BUCKETED by prompt length before chunking (stable
        sort, so same-length requests keep their arrival order within a
        bucket): every chunk prefills at its own longest prompt instead of
        one long prompt padding the whole chunk — the prefill cost of a
        chunk is max-in-chunk, and mixing lengths maximizes that max.
        Note prefill has no pad mask: shorter prompts in a chunk are
        left-padded with zero tokens the model attends to, so tokens
        depend on chunk composition; bucketing MINIMIZES that padding
        (equal-length chunks are pad-free and match solo serving) but a
        mixed-length tail chunk still pads — ``ContinuousEngine`` removes
        the distortion entirely via per-slot solo prefill. Each request's
        emitted tokens honor ITS stop conditions: trimmed to its own
        ``max_new_tokens`` and (when ``eos_id`` is set) at the first eos,
        eos included — the same contract the continuous engine enforces
        at retirement, so both engines agree. Results are returned in the
        ORIGINAL request order regardless of the serving order.
        """
        if self.speculative is not None:
            return self.speculative.generate(requests)
        return _bucketed_generate(requests, self.batch_size,
                                  self._generate_batch)

    def _generate_batch(self, requests: List[Request]) -> List[Result]:
        tel = self.telemetry
        straggler = self.straggler
        clock = tel.metrics.clock if tel is not None else time.perf_counter
        timed = tel is not None or straggler is not None
        t_b0 = clock() if timed else 0.0
        B = self.batch_size
        n = len(requests)
        prompts, slot_mask = _pad_prompts(requests, B)
        prof = get_profiler()
        if prof.active:
            from repro.sparse.tune import m_bucket

            if "params" not in self._nbytes:   # shape-fixed per engine
                self._nbytes["params"] = _tree_nbytes(self.params)
            # engine-level wall: the whole jitted prefill, keyed by its
            # GEMM row-count bucket B·S (the profiler never alters values)
            cache, logits = prof.wall(
                "prefill", self._prefill, (self.params, prompts),
                scheme="engine:chunked",
                bucket=m_bucket(B * int(prompts.shape[1])),
                nbytes=self._nbytes["params"])
        else:
            cache, logits = self._prefill(self.params, prompts)
        # scan length is trimmed per chunk: this chunk's longest request,
        # not a global engine-wide maximum
        max_new = max(r.max_new_tokens for r in requests)
        use_temp = any(r.temperature is not None for r in requests)
        if use_temp:
            # per-request key streams: token i of row b draws from
            # fold_in(row_key_b, i) — a seeded request reproduces across
            # engines and (same-shape) chunks
            temps, row_keys, self._key = _stochastic_rows(requests, B,
                                                          self._key)
            step_keys = fold_key_grid(row_keys, jnp.zeros((B,), jnp.int32),
                                      max_new)
            tok0 = temperature_sample(logits, step_keys[0], temps) \
                * slot_mask[:, None]
            if max_new > 1:
                dargs = (self.params, cache, tok0, slot_mask, temps,
                         step_keys[1:], max_new - 1)
                if prof.active:
                    ck = ("cache", B, int(prompts.shape[1]))
                    if ck not in self._nbytes:
                        self._nbytes[ck] = _tree_nbytes(cache)
                    _, rest = prof.wall(
                        "decode_many", self._decode_many_temp, dargs,
                        scheme="engine:chunked", bucket=m_bucket(B),
                        nbytes=self._nbytes[ck] * (max_new - 1))
                else:
                    _, rest = self._decode_many_temp(*dargs)
                toks = jnp.concatenate([tok0, rest], axis=1)
            else:
                toks = tok0
        else:
            tok0 = self.sampler(logits) * slot_mask[:, None]
            if max_new > 1:
                dargs = (self.params, cache, tok0, slot_mask, max_new - 1)
                if prof.active:
                    # KV bytes touched per chunk: the scan streams the
                    # whole cache every step
                    ck = ("cache", B, int(prompts.shape[1]))
                    if ck not in self._nbytes:
                        self._nbytes[ck] = _tree_nbytes(cache)
                    _, rest = prof.wall(
                        "decode_many", self._decode_many, dargs,
                        scheme="engine:chunked", bucket=m_bucket(B),
                        nbytes=self._nbytes[ck] * (max_new - 1))
                else:
                    _, rest = self._decode_many(*dargs)
                toks = jnp.concatenate([tok0, rest], axis=1)  # (B, max_new)
            else:
                toks = tok0
        # ONE device→host transfer for the whole token block (a per-token
        # int() loop on a device array would issue B·T blocking syncs)
        toks_np = np.asarray(jax.device_get(toks))
        results = [
            Result(uid=r.uid,
                   tokens=trim_at_eos(
                       [int(t) for t in toks_np[j, : r.max_new_tokens]],
                       r.eos_id))
            for j, r in enumerate(requests)
        ]
        if straggler is not None:
            # batch decode wall into the straggler window; a flagged
            # batch becomes a tracer event, not just a counter
            self._batches += 1
            ev = straggler.record(self._batches, max(clock() - t_b0, 0.0))
            if ev is not None and tel is not None and tel.tracer is not None:
                tel.tracer.event(
                    "straggler", ts=clock(), engine="chunked", step=ev.step,
                    seconds=ev.seconds, median=ev.median,
                    deviation=ev.deviation)
        if tel is not None:
            # batch-granular lifecycle: the transfer above is the single
            # sync, so first-token time == batch-done time for every
            # request in the chunk (see __init__ docstring)
            t_sync = clock()
            dur = max(t_sync - t_b0, 0.0)
            reg = tel.metrics
            reg.histogram("serve.chunk_seconds", engine="chunked") \
                .observe(dur)
            reg.counter("serve.chunks_total", engine="chunked").inc()
            h_ttft = reg.histogram("serve.ttft_seconds", engine="chunked")
            h_tpot = reg.histogram("serve.tpot_seconds", engine="chunked")
            c_ok = reg.counter("serve.requests_total", engine="chunked",
                               status="ok")
            tpot = dur / max_new
            for res in results:
                h_ttft.observe(dur)
                h_tpot.observe(tpot)
                c_ok.inc()
            if tel.tracer is not None:
                tel.tracer.span_record(
                    "decode_chunk", ts=t_b0, dur=dur, engine="chunked",
                    steps=max_new, active=n, batch=B)
                for res in results:
                    tel.tracer.event("retire", ts=t_sync, engine="chunked",
                                     uid=res.uid, status=res.status,
                                     tokens=len(res.tokens))
        return results


class ContinuousEngine:
    """Continuous-batching engine: slot-managed KV cache, in-flight
    admission, streaming results.

    The decode loop is the same device-resident scan as ``ServeEngine``
    (one dispatch + one host transfer per micro-chunk of ``chunk_steps``
    steps); between chunks the host-side ``Scheduler`` retires finished
    slots and admits queued requests into them via
    ``LM.prefill_into_slot`` — a solo (1, S) prefill whose KV lands in
    one row of the LIVE cache. Per-slot geometry (each row's own ``pos``,
    its own valid-length ``slot_pos`` mask, its own rope offsets) makes
    every slot independent: tokens are bit-identical to serving each
    request ALONE, for any admission order and any chunk-mates — the
    chunked engine's mixed-length padding distortion cannot happen here.

    Sampling is per-request: ``Request.temperature`` (None or <= 0 →
    greedy). A chunk with any stochastic slot routes through the
    vectorized ``temperature_sample`` whose per-slot temperature array is
    a traced argument — admissions never retrace the decode program.

    One compiled slot-prefill program per distinct prompt length (like
    the chunked engine's per-chunk-shape prefill); decode compiles at
    most ``chunk_steps`` scan lengths (the tail trims to the longest
    remaining budget). ``family="ssm"`` recurrent caches are not
    supported (no KV rows to manage); use ``ServeEngine``.
    """

    def __init__(
        self,
        model: LM,
        params: Any,
        *,
        batch_size: int,
        max_seq_len: int,
        chunk_steps: int = 8,
        packed: bool = False,
        flash: Optional[bool] = None,
        seed: int = 0,
        max_queue: Optional[int] = None,
        strict: bool = True,
        straggler: Optional[Any] = None,
        fault_hook: Optional[Callable[..., Any]] = None,
        telemetry: Optional[Telemetry] = None,
    ):
        """Reliability knobs (see ``serve.__init__`` for the contract):

        ``max_queue`` — bounded admission queue: submissions beyond this
        depth come back ``status="shed"`` instead of queueing without
        limit. None = unbounded (the pre-reliability behavior).

        ``strict`` — oversized requests (prompt + budget > cache
        capacity): True raises ``ValueError`` up front (library misuse —
        the historical contract); False sheds them typed
        (``status="shed"``) and serves the rest — the service posture,
        where one bad request must not kill the batch.

        ``straggler`` — optional ``runtime.straggler.StragglerMonitor``;
        every micro-chunk's wall time is recorded against it, so slow
        chunks (contended host, faulted device) surface as events in
        ``stats["straggler_events"]`` rather than silent latency.

        ``fault_hook`` — ``(cache, scheduler) -> cache | None``, called
        once per chunk edge BEFORE dispatch. This is the chaos-injection
        seam (``repro.testing.chaos``): token prompts are int32, so a
        NaN-poisoning fault can only enter through the cache, exactly
        like a real XLA/memory fault would. Production leaves it None.

        ``telemetry`` — optional ``runtime.telemetry.Telemetry``. The run
        loop records the full request lifecycle into its tracer (enqueue
        → admit/prefill → first_token → per-chunk decode → one terminal
        ``retire`` event per request carrying the ``Result.status``) and
        TTFT / TPOT / queue-wait / chunk-time histograms plus status
        counters (labelled ``engine="continuous"``) into its registry.
        Trace timestamps are on the ENGINE clock (the same one
        ``arrivals``/``deadline`` use — the tracer's clock is rebound
        for the run), so every latency in the registry is recomputable
        offline from the trace alone. Every phase of a loop iteration
        runs inside one top-level span (``reap``, ``admit``,
        ``arrival_wait``, ``fault_hook``, ``decode_chunk``, ``absorb``,
        ``emit``; see ``runtime/telemetry.py``), each also a profiler
        annotation, so a device profile names what the host did in every
        idle gap. None = metrics land in a private per-run registry (they
        still back ``stats``) and nothing is traced; spans only read the
        clock and write records on the host, so emitted tokens are
        bit-identical with telemetry on or off.
        """
        if model.config.family == "ssm":
            raise NotImplementedError(
                "ContinuousEngine manages KV-cache slots; xLSTM "
                "recurrent-state admission is not implemented — use "
                "ServeEngine"
            )
        if chunk_steps < 1:
            raise ValueError("chunk_steps must be >= 1")
        self.model = model
        self.params, self.bind_report = _resolve_params(model, params,
                                                        packed)
        self.batch_size = batch_size
        self.max_seq_len = max_seq_len
        self.chunk_steps = chunk_steps
        self.max_queue = max_queue
        self.strict = strict
        self.straggler = straggler
        self.fault_hook = fault_hook
        self.telemetry = telemetry
        self._key = jax.random.PRNGKey(seed)
        # per-slot request key streams (seeded requests reproduce exactly:
        # slot logits are batch-independent, and token i always draws from
        # fold_in(row_key, i) no matter the admission timing)
        self._slot_keys = np.zeros((batch_size, 2), np.uint32)
        spec = model.cache_spec(max_seq_len)
        self._capacity, self._ring = spec.capacity, spec.ring
        self.stats: Dict[str, Any] = {}

        def admitted(cache, logits, tok, slot, sample):
            with jax.named_scope("sample"):
                first = sample(logits)                         # (1, 1)
                tok = jax.lax.dynamic_update_slice(
                    tok, first, (jnp.asarray(slot, jnp.int32),
                                 jnp.int32(0)))
            with jax.named_scope("health"):
                ok = jnp.isfinite(logits).all()
            return cache, tok, first, ok

        def admit_greedy(p, cache, tok, prompt, slot):
            cache, logits = model.prefill_into_slot(p, cache, prompt, slot,
                                                    flash=flash)
            return admitted(cache, logits, tok, slot, greedy_sample)

        def admit_temp(p, cache, tok, prompt, slot, key, temp):
            cache, logits = model.prefill_into_slot(p, cache, prompt, slot,
                                                    flash=flash)
            return admitted(cache, logits, tok, slot,
                            lambda lg: temperature_sample(lg, key, temp))

        # decode chunks carry per-slot per-step finite-logit flags: the
        # NaN guard the scheduler quarantines on (observation only —
        # tokens stay bit-identical to the unflagged program)
        chunk_greedy, chunk_temp = _scan_decode_fns(model, greedy_sample,
                                                    with_flags=True)

        donate = (1,) if jax.default_backend() == "tpu" else ()
        # slot admission recompiles per prompt length S only (slot index,
        # temperature, and key are traced)
        self._admit_greedy = jax.jit(admit_greedy, donate_argnums=donate)
        self._admit_temp = jax.jit(admit_temp, donate_argnums=donate)
        self._chunk_greedy = jax.jit(
            chunk_greedy, static_argnums=(4,), donate_argnums=donate)
        self._chunk_temp = jax.jit(
            chunk_temp, static_argnums=(6,), donate_argnums=donate)

    # ---- public API --------------------------------------------------------

    def generate(self, requests: List[Request], *,
                 arrivals: Optional[Sequence[float]] = None,
                 clock: Optional[Callable[[], float]] = None,
                 ) -> List[Result]:
        """Serve to completion; results in the ORIGINAL request order."""
        results: List[Optional[Result]] = [None] * len(requests)
        for order, res in self._run(requests, arrivals=arrivals,
                                    clock=clock):
            results[order] = res
        return results  # type: ignore[return-value]

    def stream(self, requests: List[Request], *,
               arrivals: Optional[Sequence[float]] = None,
               clock: Optional[Callable[[], float]] = None,
               ) -> Iterator[Result]:
        """Yield each request's ``Result`` the moment it finishes
        (COMPLETION order — short requests overtake long chunk-mates).

        ``arrivals``: optional per-request arrival offsets (seconds);
        a request is only admitted once the clock passes its arrival.
        ``clock``: elapsed-seconds callable (default: wall clock anchored
        at the first call); an injected clock must advance on its own.
        """
        for _, res in self._run(requests, arrivals=arrivals, clock=clock):
            yield res

    # ---- the serve loop ----------------------------------------------------

    def _run(self, requests: List[Request],
             arrivals: Optional[Sequence[float]],
             clock: Optional[Callable[[], float]],
             ) -> Iterator[Tuple[int, Result]]:
        n = len(requests)
        arr = [0.0] * n if arrivals is None else [float(a) for a in arrivals]
        if len(arr) != n:
            raise ValueError("arrivals must match requests")

        ENG = "continuous"
        tel = self.telemetry
        tracer = tel.tracer if tel is not None else None
        # metrics always flow through a registry — a private per-run one
        # when no Telemetry is attached — so ``self.stats`` is a view
        # over the registry in every mode (deltas from the run-start
        # values, so a shared long-lived registry still yields per-run
        # stats while its counters accumulate monotonically)
        reg = tel.metrics if tel is not None else MetricsRegistry()
        statuses = ("ok", "shed", "timeout", "cancelled", "failed")
        c_status = {s: reg.counter("serve.requests_total", engine=ENG,
                                   status=s) for s in statuses}
        c_chunks = reg.counter("serve.chunks_total", engine=ENG)
        c_busy = reg.counter("serve.busy_slot_steps_total", engine=ENG)
        c_total = reg.counter("serve.total_slot_steps_total", engine=ENG)
        c_quar = reg.counter("serve.quarantined_slots_total", engine=ENG)
        h_ttft = reg.histogram("serve.ttft_seconds", engine=ENG)
        h_tpot = reg.histogram("serve.tpot_seconds", engine=ENG)
        h_qwait = reg.histogram("serve.queue_wait_seconds", engine=ENG)
        h_chunk = reg.histogram("serve.chunk_seconds", engine=ENG)
        base = {"chunks": c_chunks.value, "busy": c_busy.value,
                "total": c_total.value,
                **{s: c_status[s].value for s in statuses}}
        # order → first-token time on the engine clock, for TPOT at retire
        t_firsts: Dict[int, float] = {}

        def finish(order: int, uid: int, tokens: List[int], status: str,
                   t: Optional[float] = None):
            c_status[status].inc()
            t_first = t_firsts.get(order)
            if t is not None and t_first is not None and len(tokens) > 1:
                h_tpot.observe((t - t_first) / (len(tokens) - 1))
            if tracer is not None:
                # the ONE terminal event per request — name is always
                # "retire", the disposition rides in ``status`` (the
                # completeness invariant serve.__init__ documents)
                tracer.event("retire", engine=ENG, uid=uid, order=order,
                             status=status, tokens=len(tokens),
                             ts=t if t is not None else arr[order],
                             t_first=t_first, arrival=arr[order])
            return order, Result(uid=uid, tokens=tokens, status=status)

        oversized = set()
        for i, r in enumerate(requests):
            S = int(r.prompt.shape[0])
            if not self._ring and S + r.max_new_tokens - 1 > self._capacity:
                if self.strict:
                    raise ValueError(
                        f"request uid={r.uid}: prompt {S} + max_new_tokens "
                        f"{r.max_new_tokens} exceeds cache capacity "
                        f"{self._capacity} — raise max_seq_len"
                    )
                oversized.add(i)

        sched = Scheduler(self.batch_size, self.chunk_steps,
                          max_queue=self.max_queue)
        for i in sorted(range(n), key=lambda i: arr[i]):   # FIFO by arrival
            if i in oversized or not sched.submit(i, requests[i], arr[i]):
                # typed load-shedding: a full bounded queue (or, in
                # non-strict mode, an unservable request) rejects at the
                # door instead of queueing work that cannot complete
                yield finish(i, requests[i].uid, [], "shed")
            elif tracer is not None:
                tracer.event("enqueue", engine=ENG, uid=requests[i].uid,
                             order=i, ts=arr[i])

        cache = self.model.init_cache(self.batch_size, self.max_seq_len)
        tok = jnp.zeros((self.batch_size, 1), jnp.int32)
        t0 = time.perf_counter()
        now = clock if clock is not None \
            else (lambda: time.perf_counter() - t0)
        if tracer is not None:
            # trace timestamps share the engine clock — the one arrivals
            # and deadlines are on — so offline readers can reconstruct
            # every latency the registry's histograms observed
            tracer.clock = now
        if tel is None:
            reg.clock = now

        # every phase of an iteration runs inside one top-level span,
        # consecutive spans sharing their boundary reading (a no-op
        # without a tracer: no span, no extra clock reading)
        tl = Timeline(tracer)

        def emit(items):
            # the consumer's time: from each yield until the loop resumes
            for item in items:
                tl.to("emit", uid=item[1].uid)
                yield item

        try:
            while not sched.done:
                # ---- reap dead requests before they cost anything ---------
                t = now()
                tl.to("reap", t)
                yield from emit([finish(order, r.uid, [], status, t=t)
                                 for order, r, status
                                 in sched.reap_queue(t)])
                # ---- admit arrived requests into free slots ---------------
                for st in sched.ready_admissions(t):
                    r = st.request
                    t_adm = now()
                    tl.to("admit", t_adm, engine=ENG, uid=r.uid,
                          order=st.order, slot=st.slot,
                          arrival=arr[st.order])
                    tl.sub("admit.dispatch", t_adm, uid=r.uid)
                    prompt = r.prompt[None, ...]
                    if r.temperature is not None and r.temperature > 0:
                        row_key, self._key = request_key(r.seed, self._key)
                        self._slot_keys[st.slot] = np.asarray(row_key)
                        k = jax.random.fold_in(row_key, 0)  # token index 0
                        cache, tok, first, ok = self._admit_temp(
                            self.params, cache, tok, prompt, st.slot, k,
                            float(r.temperature))
                    else:
                        cache, tok, first, ok = self._admit_greedy(
                            self.params, cache, tok, prompt, st.slot)
                    # the admission's host sync: its health flag and its
                    # first token (needed for the eos/max_new check before
                    # the next chunk)
                    tl.sub("admit.sync", uid=r.uid)
                    if not bool(np.asarray(ok)):
                        # poisoned from the first logits: the slot's KV
                        # rows already hold NaN — quarantine the lane. The
                        # record is ``admit.failed``, so ``admit`` keeps
                        # to admissions that produced a first token
                        t_fail = now()
                        tl.rename("admit.failed")
                        tl.to("absorb", t_fail, uid=r.uid)
                        sched.table.quarantine(st.slot)
                        yield from emit([finish(st.order, r.uid, [],
                                                "failed", t=t_fail)])
                        continue
                    first_tok = int(np.asarray(first)[0, 0])
                    t_first = now()
                    tl.to("absorb", t_first, uid=r.uid)
                    t_firsts[st.order] = t_first
                    # queue wait ends when the admit dispatch began; TTFT
                    # ends at the first-token host sync just above — both
                    # measured from the request's scripted/real arrival
                    h_qwait.observe(t_adm - arr[st.order])
                    h_ttft.observe(t_first - arr[st.order])
                    if tracer is not None:
                        tracer.event("first_token", engine=ENG, uid=r.uid,
                                     order=st.order, ts=t_first,
                                     arrival=arr[st.order])
                    if st.push([first_tok]):
                        sched.table.retire(st.slot)
                        yield from emit([finish(st.order, r.uid,
                                                st.emitted, "ok",
                                                t=t_first)])
                # ---- reap live slots whose deadline/cancel fired ----------
                t_reap = now()
                tl.to("reap", t_reap)
                yield from emit([finish(st.order, st.request.uid, st.emitted,
                                        st.status, t=t_reap)
                                 for st in sched.reap_active(t_reap)])

                if not sched.table.active:
                    if sched.table.num_free == 0 and sched.pending:
                        # every lane is quarantined and requests still
                        # queue: nothing can ever admit — fail the backlog
                        # typed instead of spinning forever
                        t_fail = now()
                        tl.to("reap", t_fail)
                        yield from emit([finish(order, r.uid, [], status,
                                                t=t_fail)
                                         for order, r, status
                                         in sched.fail_pending()])
                        break
                    nxt = sched.next_arrival()
                    if nxt is None:
                        break
                    t = now()
                    if nxt > t:
                        # sleep toward the next arrival, one span a stretch,
                        # reaping the queue between sleeps. Real clock:
                        # steps of at most 50 ms; injected clock: yield
                        # briefly instead of busy-spinning (the clock
                        # advances on its own)
                        tl.to("arrival_wait", t)
                        dead: List[Tuple[int, Any, str]] = []
                        while not dead and t < nxt:
                            time.sleep(min(nxt - t, 0.05) if clock is None
                                       else 1e-4)
                            t = now()
                            dead = sched.reap_queue(t)
                        yield from emit([finish(order, r.uid, [], status,
                                                t=t)
                                         for order, r, status in dead])
                    continue

                # ---- chaos seam: deterministic cache-level fault injection
                if self.fault_hook is not None:
                    tl.to("fault_hook")
                    injected = self.fault_hook(cache, sched)
                    if injected is not None:
                        cache = injected

                # ---- one decode micro-chunk -------------------------------
                t_chunk = now()
                tl.to("decode_chunk", t_chunk, engine=ENG,
                      chunk=sched.chunks)
                tl.sub("decode_chunk.prep", t_chunk)
                K = sched.chunk_len()
                n_active = len(sched.table.active)
                mask = jnp.asarray(sched.table.active_mask())
                if sched.table.any_stochastic():
                    temps = jnp.asarray(sched.table.temperatures())
                    # step s of slot b draws from fold_in(row_key_b, e_b + s)
                    # where e_b is the slot's own emitted count — the stream
                    # follows the REQUEST, not the engine's chunk clock
                    offsets = np.zeros((self.batch_size,), np.int32)
                    for slot, st in sched.table.active.items():
                        offsets[slot] = len(st.emitted)
                    keys = fold_key_grid(jnp.asarray(self._slot_keys),
                                         jnp.asarray(offsets), K)
                    run, args = self._chunk_temp, (temps, keys, K)
                else:
                    run, args = self._chunk_greedy, (K,)
                tl.sub("decode_chunk.dispatch")
                cache, toks, flags = run(self.params, cache, tok, mask,
                                         *args)
                tok = toks[:, -1:]
                # ONE device→host transfer per chunk (tokens + health flags
                # ride the same sync)
                tl.sub("decode_chunk.sync")
                toks_np, flags_np = jax.device_get((toks, flags))
                toks_np = np.asarray(toks_np)
                t_end = now()
                # the chunk's record waits for the busy count absorb makes
                tl.to("absorb", t_end, hold=True)
                dt_chunk = max(t_end - t_chunk, 0.0)
                if self.straggler is not None:
                    # per-chunk watchdog: the transfer above synced the
                    # chunk, so the delta is real device+host time
                    ev = self.straggler.record(sched.chunks, dt_chunk)
                    if ev is not None and tracer is not None:
                        # flagged chunks land in the trace too — the
                        # analyzer correlates them with the stalls
                        tracer.event(
                            "straggler", ts=t_end, engine=ENG, step=ev.step,
                            seconds=ev.seconds, median=ev.median,
                            deviation=ev.deviation)
                busy0 = sched.busy_slot_steps
                finished = sched.absorb_chunk(toks_np, K,
                                              ok=np.asarray(flags_np))
                busy_d = sched.busy_slot_steps - busy0
                c_chunks.inc()
                c_busy.inc(busy_d)
                c_total.inc(self.batch_size * K)
                h_chunk.observe(dt_chunk)
                # busy/steps/batch make per-chunk (and run-aggregate)
                # occupancy recomputable from the trace alone
                tl.release(steps=K, active=n_active, busy=busy_d,
                           batch=self.batch_size)
                yield from emit([finish(st.order, st.request.uid,
                                        st.emitted, st.status, t=t_end)
                                 for st in finished])
        finally:
            tl.close()

        c_quar.inc(len(sched.table.quarantined))
        busy = c_busy.value - base["busy"]
        total = c_total.value - base["total"]
        # ``stats`` is the legacy surface, now a compat VIEW over the
        # registry: every numeric field below reads back out of the
        # counters recorded above (per-run deltas against the run-start
        # snapshot), so the dict and a registry export can never drift
        self.stats = {
            "chunks": int(c_chunks.value - base["chunks"]),
            "occupancy": (busy / total) if total else 0.0,
            "busy_slot_steps": int(busy),
            "total_slot_steps": int(total),
            "statuses": {s: int(c_status[s].value - base[s])
                         for s in statuses},
            "quarantined_slots": list(sched.table.quarantined),
            "straggler_events": (len(self.straggler.events)
                                 if self.straggler is not None else 0),
            "bind_fallbacks": (dict(self.bind_report["fallbacks"])
                               if self.bind_report else {}),
        }
        if tracer is not None:
            tracer.flush()
