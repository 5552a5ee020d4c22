"""Serving: chunked (``ServeEngine``) and continuous (``ContinuousEngine``)
engines over the same device-resident decode scan.

Per-slot geometry contract (the continuous engine's correctness rests on
it; the pieces live in the model, not the engine):

  * ``cache["pos"]`` is ``(B,)`` — each batch slot decodes at ITS OWN
    position: rope tables, the causal horizon, and the cache write
    pointer all follow ``pos[slot]`` independently per row
    (``LM.decode_step`` builds per-row rope from ``pos[:, None]``).
  * ``cache["slot_pos"]`` is ``(B, C)`` — each row's per-cache-slot valid
    positions; ``-1`` marks an empty slot and ``decode_attention`` masks
    it, so a slot's visible context is exactly its own written history.
    Ring caches (sliding window) reuse the same field with
    ``slot = pos % C``.
  * ``LM.prefill_into_slot(params, cache, prompt (1, S), slot)`` admits a
    prompt into ONE row of a live cache: a solo forward (positions
    0..S-1, no batch-mates, no padding — hidden states bit-identical to
    serving the request alone), the row's k/v written in place, the
    row's ``slot_pos`` RESET (fresh positions where written, -1
    elsewhere — the retired occupant's stale KV is masked out, never
    cleared), the row's ``pos`` set to S. All other rows pass through
    untouched. One compiled program per prompt length; the slot index is
    traced.

Consequence: batch rows are fully independent — continuous-batching
tokens are bit-identical to solo serving for ANY admission order, any
chunk-mates, any retirement pattern. The chunked engine's mixed-length
prefill padding (zero tokens the model attends to) is the one distortion
this geometry removes.

Dual-cache + rollback contract (the speculative engine's correctness
rests on it; ``serve/speculative.py``):

  * ``LM.verify_chunk(params, cache, tokens (B, K))`` decodes K tokens
    per row in ONE dispatch: each row at its own ``pos[b] .. pos[b]+K-1``
    (per-row rope, per-row causal horizon), the chunk's k/v inserted
    into the cache first so ``slot_pos <= q_pos`` masking covers
    intra-chunk causality. Returns per-position logits; ``pos`` advances
    by K.
  * ``LM.cache_snapshot(cache, K)`` saves the rows the next K inserts
    will overwrite; ``LM.cache_rollback(cache, snap, keep (B,))`` rewinds
    row ``b`` to ``snap pos + keep[b]`` accepted inserts, restoring the
    rejected rows' k/v bytes AND ``slot_pos`` from the snapshot. The
    restore is what makes rollback exact on RING caches too: a rejected
    insert that wrapped has overwritten live window history, which
    masking alone cannot bring back. After rollback the cache is
    bit-identical to one that only ever saw the accepted tokens.
  * The speculative engine keeps the drafter and target caches in
    LOCKSTEP: the drafter's K draft steps insert positions
    ``pending, d_1 .. d_{K-1}`` and the target's verify chunk inserts
    exactly the same K, and both roll back to the same per-row
    ``keep = min(accepted + 1, K)`` — so
    ``draft_cache["pos"] == target_cache["pos"]`` between rounds, always.

Host-side slot bookkeeping is ``serve/slots.py`` (free list, per-request
emission, retire conditions); admission policy and micro-chunk sizing is
``serve/scheduler.py``; samplers (vectorized per-slot temperature,
``temperature <= 0`` → exact greedy, per-request key streams via
``Request.seed``) are ``serve/sampler.py``.

Reliability contract (PR 7):

``Result.status`` state machine — every submitted request terminates in
exactly one of five typed states; nothing queues forever and nothing
crashes the batch:

                 submit
                   │
         queue full / unservable ──────────────▶ shed      (tokens: [])
                   │
                 queued ── deadline passed ────▶ timeout   (tokens: [])
                   │          or cancel()                  (never prefilled)
                 admitted
                   │
          ┌────────┼──────────────┬──────────────┐
      ran to its   │  deadline/cancel()      non-finite
      own stop     │  between chunks         logits in slot
          │        │      │                      │
          ▼        ▼      ▼                      ▼
         ok            timeout/cancelled       failed
                       (partial tokens)        (tokens up to the last
                                                healthy step; the slot is
                                                QUARANTINED — never
                                                readmitted, its KV holds
                                                NaN)

State is checked only BETWEEN micro-chunks/dispatches: a dispatched chunk
always completes, so cancellation/expiry costs at most one chunk of
decode. Quarantine isolates exactly the poisoned slot — batch-mates'
tokens stay bit-identical to solo serving (rows are independent through
every batched op, and the flags that detect the poison observe logits
without touching token math).

Degradation ladder — each rung trades speed for survival, never
correctness, and every demotion is recorded in the engine's ``.stats``:

  speculative ──▶ continuous/plain ──▶ dense
    drafter acceptance collapses         corrupt PackedTensor leaf
    (< demote_below after               (``validate_packed`` fails at
    demote_after drafted tokens)         bind): that leaf serves from
    or drafter artifact fails            the bound dense params
    verification → plain decoding        (``bind_report``/
    from the same target cache           ``stats["bind_fallbacks"]``)
    (``stats["demotions"]``)

Artifact integrity backs the bottom rung: every saved buffer carries a
CRC32 in a versioned manifest (``repro.checkpoint``), verified on load —
disk corruption surfaces as ``checkpoint.ArtifactError`` (with path +
field) before weights ever reach an engine; ``repro.testing.chaos``
injects all of the above deterministically and ``tests/test_chaos.py``
holds the guarantees.

Lifecycle-event contract (PR 9, ``runtime/telemetry.py``): an engine
given a ``Telemetry`` with a tracer records the request lifecycle as
schema-versioned JSONL, and the events are COMPLETE with respect to the
status state machine above:

  * every submitted request emits exactly ONE terminal event, named
    ``retire``, carrying ``status=<ok|shed|timeout|cancelled|failed>`` —
    the same string its ``Result.status`` reports. No request retires
    twice, none vanishes untraced; a missing retire is a bug of the
    same severity as an untyped Result.
  * every request that reaches a slot additionally has ``enqueue``
    (ts = arrival), an ``admit`` span (queue-dispatch → first-token
    sync) and a ``first_token`` event before its retire; shed requests
    have only the terminal event (they never cost a prefill, so there
    is nothing else to record).
  * ``decode_chunk`` spans carry ``busy``/``steps``/``batch`` per
    micro-chunk, so run occupancy is recomputable from the trace alone.
  * the continuous engine's loop is tiled by top-level phase spans
    (``reap``, ``admit``, ``arrival_wait``, ``fault_hook``,
    ``decode_chunk``, ``absorb``, ``emit``); ``admit`` and
    ``decode_chunk`` split into ``.dispatch`` / ``.sync`` children
    (``decode_chunk.prep`` first), so host sync and host work part.

Trace timestamps are on the ENGINE clock — the one ``arrivals`` and
``deadline`` use — so TTFT / TPOT / queue-wait recomputed offline from
the trace equal the registry's histograms exactly (the acceptance test
in ``tests/test_telemetry.py`` and the ``BENCH_telemetry`` gate hold
this). Telemetry only reads the clock and writes records on the host:
emitted tokens are bit-identical with it on or off, and the engines' legacy
``.stats`` dicts are compat views over the same registry counters.
"""

from repro.serve.engine import (
    CancelToken,
    ContinuousEngine,
    Request,
    Result,
    ServeEngine,
)
from repro.serve.sampler import greedy_sample, temperature_sample
from repro.serve.scheduler import Scheduler
from repro.serve.slots import SlotState, SlotTable, trim_at_eos
from repro.serve.speculative import SpeculativeEngine, shallow_drafter
