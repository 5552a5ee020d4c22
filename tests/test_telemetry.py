"""Unified telemetry (ISSUE-9): registry, tracer, engine lifecycle.

The layer's contract, in test form:

  * the registry's histograms are EXACT about bucket placement
    (upper-inclusive edges, Prometheus ``le`` semantics);
  * the tracer's JSONL round-trips through ``read_trace`` with ids,
    parents and (under a ``ScriptedClock``) deterministic timestamps;
  * both serve engines' legacy ``stats`` dicts are compat VIEWS over
    the registry (equal numbers, and per-run even when the registry is
    shared and accumulating);
  * telemetry never perturbs the decode math: emitted tokens are
    bit-identical with it on or off;
  * the acceptance bar — a traced ``ContinuousEngine`` run yields a
    trace from which TTFT / TPOT / queue-wait / occupancy are
    recomputable OFFLINE, matching the registry's histograms exactly
    (shared engine clock, floats preserved through JSON).
"""

import io
import json
import math
import os

import jax.numpy as jnp
import numpy as np
import pytest

from repro.runtime import telemetry_export
from repro.runtime.straggler import StragglerMonitor
from repro.runtime.telemetry import (
    MetricsRegistry,
    Telemetry,
    TRACE_SCHEMA_VERSION,
    Tracer,
    default_bucket_edges,
    get_registry,
    read_trace,
    registry_scope,
)
from repro.serve import ContinuousEngine, Request, ServeEngine
from repro.sparse.registry import dispatch_stats, dispatch_stats_scope
from repro.testing.chaos import ScriptedClock


# ---------------------------------------------------------------------------
# registry primitives
# ---------------------------------------------------------------------------


class TestHistogram:
    def test_bucket_edge_exactness(self):
        """An observation EQUAL to an edge lands in that edge's bucket
        (upper-inclusive, ``le`` semantics); anything above the last
        edge lands in the +Inf overflow cell."""
        reg = MetricsRegistry()
        h = reg.histogram("t", edges=(0.1, 1.0, 10.0))
        for v in (0.1, 1.0, 10.0):          # exactly on an edge
            h.observe(v)
        h.observe(0.0999999)                 # strictly below the first
        h.observe(10.0000001)                # strictly above the last
        assert h.counts == [2, 1, 1, 1]      # [<=0.1, <=1, <=10, +Inf]
        assert h.count == 5

    def test_same_value_same_bucket(self):
        reg = MetricsRegistry()
        h = reg.histogram("t2")
        for _ in range(3):
            h.observe(0.025)
        (idx,) = [i for i, c in enumerate(h.counts) if c]
        assert h.counts[idx] == 3

    def test_default_edges_log_spaced(self):
        edges = default_bucket_edges(lo=1e-4, hi=100.0, per_decade=4)
        assert edges[0] == pytest.approx(1e-4)
        assert edges[-1] == pytest.approx(100.0)
        ratios = [edges[i + 1] / edges[i] for i in range(len(edges) - 1)]
        assert all(r == pytest.approx(10 ** 0.25) for r in ratios)

    def test_sum_min_max_quantile(self):
        h = MetricsRegistry().histogram("t3", edges=(1.0, 2.0, 4.0))
        for v in (0.5, 1.5, 3.0, 3.5):
            h.observe(v)
        assert h.sum == pytest.approx(8.5)
        assert (h.min, h.max) == (0.5, 3.5)
        assert h.quantile(0.5) == 2.0        # bucket upper bound
        assert MetricsRegistry().histogram("e").quantile(0.5) == 0.0


class TestRegistry:
    def test_get_or_create_and_labels(self):
        reg = MetricsRegistry()
        a = reg.counter("x", mode="on")
        b = reg.counter("x", mode="on")
        c = reg.counter("x", mode="off")
        assert a is b and a is not c
        a.inc(2)
        assert reg.value("x", mode="on") == 2
        assert reg.sum_counter("x") == 2
        c.inc(3)
        assert reg.sum_counter("x") == 5
        assert len(reg.counter_family("x")) == 2

    def test_timer_uses_injected_clock(self):
        reg = MetricsRegistry(clock=ScriptedClock([1.0, 3.5]))
        with reg.timer("dur", stage="s"):
            pass
        h = reg.histogram("dur", stage="s")
        assert h.count == 1 and h.sum == pytest.approx(2.5)

    def test_registry_scope_isolates(self):
        outer = get_registry()
        outer_v = outer.sum_counter("scoped")
        with registry_scope() as reg:
            assert get_registry() is reg and reg is not outer
            reg.counter("scoped").inc()
            assert reg.sum_counter("scoped") == 1
        assert get_registry() is outer
        assert outer.sum_counter("scoped") == outer_v


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------


class TestTracer:
    def test_span_nesting_and_ordering_scripted(self):
        """Nested spans under a ScriptedClock: child closes first (JSONL
        is emit-on-close), parent ids link the tree, and every
        timestamp is exactly the scripted one."""
        buf = io.StringIO()
        tr = Tracer(buf, clock=ScriptedClock([1.0, 2.0, 3.0, 4.0, 5.0]))
        with tr.span("outer", run=7) as outer:
            tr.event("mark")                      # ts=2.0, parent=outer
            with tr.span("inner"):                # start 3.0, end 4.0
                pass
        recs = [json.loads(l) for l in buf.getvalue().splitlines()]
        assert [r["name"] for r in recs] == ["mark", "inner", "outer"]
        mark, inner, outerr = recs
        assert mark["parent"] == outer.span_id
        assert inner["parent"] == outer.span_id
        assert outerr["parent"] is None
        assert (mark["ts"], inner["ts"], inner["dur"]) == (2.0, 3.0, 1.0)
        assert (outerr["ts"], outerr["dur"]) == (1.0, 4.0)
        assert outerr["run"] == 7

    def test_jsonl_schema_round_trip(self, tmp_path):
        path = str(tmp_path / "trace.jsonl")
        tr = Tracer(path, clock=ScriptedClock([0.5]))
        tr.event("ping", uid=3, status="ok")
        tr.span_record("work", ts=1.25, dur=0.75, uid=3)
        tr.close()
        with open(path, "a") as f:                 # corrupt tail line
            f.write('{"half-written')
        recs = read_trace(path)
        assert len(recs) == 2                      # tail skipped, no raise
        ev, sp = recs
        assert ev == {"schema": TRACE_SCHEMA_VERSION, "kind": "event",
                      "name": "ping", "parent": None, "ts": 0.5,
                      "uid": 3, "status": "ok"}
        assert sp["kind"] == "span" and sp["ts"] == 1.25
        assert sp["dur"] == 0.75 and isinstance(sp["span"], int)

    def test_float_ts_survives_json_exactly(self, tmp_path):
        """The offline-recompute guarantee rests on JSON round-tripping
        floats bit-exactly."""
        path = str(tmp_path / "t.jsonl")
        t = 0.1 + 0.2 + 1e-9                       # not representable tidily
        tr = Tracer(path)
        tr.event("e", ts=t, arrival=t / 3.0)
        tr.close()
        (rec,) = read_trace(path)
        assert rec["ts"] == t and rec["arrival"] == t / 3.0


# ---------------------------------------------------------------------------
# engines: compat view, bit-identity, offline recompute
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def lm():
    from repro.configs.base import ModelConfig
    from repro.models import build_model

    cfg = ModelConfig(name="tiny", family="dense", num_layers=2, d_model=128,
                      num_heads=4, num_kv_heads=2, head_dim=32, d_ff=256,
                      vocab_size=512, param_dtype="float32")
    model = build_model(cfg)
    params = model.init(__import__("jax").random.PRNGKey(0))
    return cfg, model, params


def _running_sum(values):
    """Left-to-right float sum, the way ``Histogram.observe`` accumulates.

    Python 3.12's built-in ``sum`` compensates rounding (Neumaier), so it
    can differ from a running ``+=`` in the last bit."""
    total = 0.0
    for v in values:
        total += v
    return total


def _reqs(cfg, n=5):
    return [Request(uid=i, prompt=(jnp.arange(4 + 2 * i) + i) % cfg.vocab_size,
                    max_new_tokens=3 + i) for i in range(n)]


class TestEngineTelemetry:
    def test_continuous_tokens_bit_identical_on_off(self, lm, tmp_path):
        cfg, model, params = lm
        reqs = _reqs(cfg)
        off = ContinuousEngine(model, params, batch_size=2, max_seq_len=64,
                               chunk_steps=3)
        tel = Telemetry(trace_path=str(tmp_path / "t.jsonl"))
        on = ContinuousEngine(model, params, batch_size=2, max_seq_len=64,
                              chunk_steps=3, telemetry=tel)
        toks_off = [r.tokens for r in off.generate(reqs)]
        toks_on = [r.tokens for r in on.generate(reqs)]
        tel.close()
        assert toks_on == toks_off
        assert on.stats == off.stats

    def test_continuous_stats_is_registry_view(self, lm):
        """stats == registry deltas, and stays PER-RUN against a shared
        registry whose counters accumulate across runs."""
        cfg, model, params = lm
        reqs = _reqs(cfg)
        reg = MetricsRegistry()
        eng = ContinuousEngine(model, params, batch_size=2, max_seq_len=64,
                               chunk_steps=3, telemetry=Telemetry(metrics=reg))
        first = None
        for run in range(2):
            eng.generate(reqs)
            if first is None:
                first = dict(eng.stats)
        assert eng.stats["chunks"] == first["chunks"]        # per-run
        E = {"engine": "continuous"}
        assert reg.value("serve.chunks_total", **E) == 2 * first["chunks"]
        assert reg.value("serve.requests_total", status="ok", **E) \
            == 2 * first["statuses"]["ok"]
        assert reg.value("serve.busy_slot_steps_total", **E) \
            == 2 * first["busy_slot_steps"]
        h = reg.histogram("serve.ttft_seconds", **E)
        assert h.count == 2 * len(reqs)

    def test_chunked_engine_records(self, lm, tmp_path):
        cfg, model, params = lm
        reqs = _reqs(cfg, n=4)
        path = str(tmp_path / "chunked.jsonl")
        tel = Telemetry(trace_path=path)
        eng = ServeEngine(model, params, batch_size=2, max_seq_len=64,
                          telemetry=tel)
        base = ServeEngine(model, params, batch_size=2, max_seq_len=64)
        assert ([r.tokens for r in eng.generate(reqs)]
                == [r.tokens for r in base.generate(reqs)])
        tel.close()
        E = {"engine": "chunked"}
        assert tel.metrics.value("serve.requests_total", status="ok",
                                 **E) == len(reqs)
        retires = [r for r in read_trace(path) if r["name"] == "retire"]
        assert sorted(r["uid"] for r in retires) == [0, 1, 2, 3]
        assert all(r["status"] == "ok" for r in retires)

    def test_speculative_stats_is_registry_view(self, lm):
        from repro.serve.speculative import SpeculativeEngine

        cfg, model, params = lm
        reqs = _reqs(cfg, n=3)
        reg = MetricsRegistry()
        spec = SpeculativeEngine(model, params, params, batch_size=2,
                                 max_seq_len=64, draft_k=3,
                                 telemetry=Telemetry(metrics=reg))
        plain = SpeculativeEngine(model, params, params, batch_size=2,
                                  max_seq_len=64, draft_k=3)
        assert ([r.tokens for r in spec.generate(reqs)]
                == [r.tokens for r in plain.generate(reqs)])
        E = {"engine": "speculative"}
        for k in ("rounds", "dispatches", "drafted", "accepted"):
            assert spec.stats[k] == reg.value(f"spec.{k}_total", **E)
            assert spec.stats[k] == plain.stats[k]
        assert reg.value("spec.acceptance_rate", **E) \
            == pytest.approx(spec.stats["acceptance_rate"])
        assert reg.value("serve.requests_total", status="ok", **E) \
            == len(reqs)

    def test_terminal_statuses_have_matching_retire_events(self, lm,
                                                           tmp_path):
        """The lifecycle completeness invariant: shed, timeout and ok
        requests each end in exactly one ``retire`` event carrying
        their ``Result.status``."""
        cfg, model, params = lm
        reqs = [Request(uid=0, prompt=jnp.arange(4), max_new_tokens=4),
                Request(uid=1, prompt=jnp.arange(4), max_new_tokens=4,
                        deadline=0.0),                   # dead on arrival
                Request(uid=2, prompt=jnp.arange(4), max_new_tokens=4),
                Request(uid=3, prompt=jnp.arange(4), max_new_tokens=4)]
        path = str(tmp_path / "mix.jsonl")
        tel = Telemetry(trace_path=path)
        eng = ContinuousEngine(model, params, batch_size=2, max_seq_len=64,
                               chunk_steps=2, max_queue=3, strict=False,
                               telemetry=tel)
        results = eng.generate(reqs)
        tel.close()
        statuses = {r.uid: r.status for r in results}
        assert statuses[1] == "timeout"
        assert "shed" in statuses.values()                # queue bound hit
        retires = {r["uid"]: r["status"] for r in read_trace(path)
                   if r["name"] == "retire"}
        assert retires == statuses

    def test_offline_recompute_matches_registry(self, lm, tmp_path):
        """ACCEPTANCE: TTFT, TPOT, queue wait and occupancy recomputed
        from the trace alone equal the registry's histograms exactly —
        same engine clock, floats preserved through JSON."""
        cfg, model, params = lm
        reqs = _reqs(cfg)
        arrivals = [0.0, 0.001, 0.002, 0.01, 0.02]
        path = str(tmp_path / "run.jsonl")
        reg = MetricsRegistry()
        tel = Telemetry(metrics=reg, trace_path=path)
        eng = ContinuousEngine(model, params, batch_size=2, max_seq_len=64,
                               chunk_steps=3, telemetry=tel)
        eng.generate(reqs, arrivals=arrivals)
        tel.close()
        ev = read_trace(path)
        by = {}
        for e in ev:
            by.setdefault(e["name"], []).append(e)
        E = {"engine": "continuous"}

        firsts = by["first_token"]
        assert len(firsts) == len(reqs)
        h_ttft = reg.histogram("serve.ttft_seconds", **E)
        assert h_ttft.count == len(firsts)
        assert _running_sum(e["ts"] - e["arrival"] for e in firsts) \
            == h_ttft.sum

        admits = by["admit"]
        h_q = reg.histogram("serve.queue_wait_seconds", **E)
        assert h_q.count == len(admits)
        assert _running_sum(e["ts"] - e["arrival"] for e in admits) \
            == h_q.sum

        t_first = {e["uid"]: e["ts"] for e in firsts}
        off_tpot = sum((e["ts"] - t_first[e["uid"]]) / (e["tokens"] - 1)
                       for e in by["retire"] if e["tokens"] > 1)
        h_tpot = reg.histogram("serve.tpot_seconds", **E)
        assert off_tpot == pytest.approx(h_tpot.sum, abs=1e-12)

        chunks = by["decode_chunk"]
        assert len(chunks) == eng.stats["chunks"]
        busy = sum(e["busy"] for e in chunks)
        total = sum(e["batch"] * e["steps"] for e in chunks)
        assert busy / total == eng.stats["occupancy"]
        # chunk durations feed the chunk-seconds histogram verbatim
        h_c = reg.histogram("serve.chunk_seconds", **E)
        assert sum(e["dur"] for e in chunks) == pytest.approx(h_c.sum)


# ---------------------------------------------------------------------------
# ambient instrumentation: dispatch scope, straggler
# ---------------------------------------------------------------------------


class TestAmbient:
    def test_dispatch_stats_scope_isolates_and_restores(self, lm):
        from repro.core import DEFAULT_EXCLUDE, PruneConfig, greedy_prune

        cfg, model, params = lm
        pcfg = PruneConfig(
            scheme="tile_pattern", exclude=tuple(DEFAULT_EXCLUDE),
            overrides={".*": {"tile_block_p": 64, "tile_group_q": 8,
                              "tile_keep": 4}},
        )
        artifact = greedy_prune(params, pcfg).to_artifact(arch="tiny").pack()
        reqs = _reqs(cfg, n=2)
        # dispatch counts are TRACE-time: each fresh engine's jit
        # closures retrace on first use, so counts land per engine build
        ServeEngine(model, artifact, batch_size=2, max_seq_len=64,
                    packed=True).generate(reqs)
        before = dict(dispatch_stats())
        assert before                           # packed serving dispatched
        with dispatch_stats_scope() as scoped:
            assert not dispatch_stats()         # empty inside the scope
            ServeEngine(model, artifact, batch_size=2, max_seq_len=64,
                        packed=True).generate(reqs)
            inside = dict(dispatch_stats())
            assert inside and dict(scoped) == inside
        after = dict(dispatch_stats())
        # outer counts restored PLUS what the scope recorded
        assert all(after[k] >= v for k, v in before.items())
        assert sum(after.values()) \
            == sum(before.values()) + sum(inside.values())

    def test_straggler_window_excludes_flagged(self):
        """A sustained slowdown must keep reading as straggling: flagged
        samples stay out of the median window, so the baseline cannot
        drift up to the degraded speed."""
        mon = StragglerMonitor(window=50, threshold=3.0)
        for i in range(20):
            mon.record(i, 0.010)
        flagged = sum(mon.record(20 + i, 0.100) is not None
                      for i in range(30))
        assert flagged == 30                    # every slow step flags
        assert max(mon.window) == pytest.approx(0.010)
        snap = mon.snapshot()
        assert snap["samples"] == 50 and snap["events"] == 30
        assert snap["median"] == pytest.approx(0.010)
        assert snap["last_event"]["seconds"] == pytest.approx(0.100)

    def test_straggler_feeds_registry(self):
        with registry_scope() as reg:
            mon = StragglerMonitor(window=10, threshold=3.0)
            for i in range(10):
                mon.record(i, 0.01)
            mon.record(10, 1.0)
            assert reg.value("straggler.events_total") == 1
            assert reg.histogram("straggler.step_seconds").count == 11


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------


class TestExport:
    def _reg(self):
        reg = MetricsRegistry()
        reg.counter("serve.requests_total", engine="x", status="ok").inc(3)
        reg.gauge("spec.acceptance_rate").set(0.75)
        h = reg.histogram("serve.ttft_seconds", edges=(0.1, 1.0))
        h.observe(0.05)
        h.observe(5.0)
        return reg

    def test_prometheus_rendering(self):
        text = telemetry_export.to_prometheus(self._reg())
        assert 'serve_requests_total{engine="x",status="ok"} 3' in text
        assert "# TYPE serve_requests_total counter" in text
        assert "spec_acceptance_rate 0.75" in text
        # cumulative buckets + +Inf, Prometheus histogram convention
        assert 'serve_ttft_seconds_bucket{le="0.1"} 1' in text
        assert 'serve_ttft_seconds_bucket{le="+Inf"} 2' in text
        assert "serve_ttft_seconds_count 2" in text

    def test_json_snapshot_round_trip(self, tmp_path):
        path = str(tmp_path / "m.json")
        telemetry_export.write_json(path, self._reg(), arch="tiny")
        with open(path) as f:
            snap = json.load(f)
        assert snap["schema"] == TRACE_SCHEMA_VERSION
        assert snap["arch"] == "tiny" and "written_at" in snap
        (ctr,) = snap["metrics"]["counters"]
        assert ctr["name"] == "serve.requests_total"
        assert ctr["labels"] == {"engine": "x", "status": "ok"}
        assert ctr["value"] == 3
        (hist,) = snap["metrics"]["histograms"]
        assert hist["counts"] == [1, 0, 1] and hist["count"] == 2
        # a persisted snapshot re-renders through the same exporter
        text = telemetry_export.to_prometheus(snap["metrics"])
        assert 'serve_requests_total{engine="x",status="ok"} 3' in text

    def test_empty_histogram_min_max_null(self):
        reg = MetricsRegistry()
        reg.histogram("never.observed")
        snap = reg.snapshot()
        (h,) = snap["histograms"]
        assert h["min"] is None and h["max"] is None and h["count"] == 0
