"""The continuous engine's serve loop as spans, and its programs' scopes.

  * every phase of ``ContinuousEngine._run`` runs inside one top-level
    span; the top-level spans are disjoint and cover the run's wall;
  * children tile their parent and carry its request's ``uid``;
  * ``admit`` and ``decode_chunk`` keep their fields and their equality
    with the registry's histograms;
  * tokens are identical with the tracer on and off (greedy and
    sampled requests);
  * while a profile is recorded, every span is a host event of the same
    name in the xplane, on the same clock as the JSONL after one offset;
  * the admission and decode-chunk programs carry the model's named
    scopes and the packed plans' ``packed/<scheme>/<impl>`` in their
    ``op_name`` metadata.
"""

import glob
import io
import json
import re
import time

import jax
import jax.numpy as jnp
import pytest

from repro.runtime.telemetry import MetricsRegistry, Telemetry, Tracer
from repro.serve import ContinuousEngine, Request

TOP = {"reap", "admit", "arrival_wait", "fault_hook", "decode_chunk",
       "absorb", "emit"}
CHILDREN = {"admit": ["admit.dispatch", "admit.sync"],
            "decode_chunk": ["decode_chunk.prep", "decode_chunk.dispatch",
                             "decode_chunk.sync"]}
EPS = 1e-9


@pytest.fixture(scope="module")
def lm():
    from repro.configs.base import ModelConfig
    from repro.models import build_model

    cfg = ModelConfig(name="tiny", family="dense", num_layers=2, d_model=64,
                      num_heads=4, num_kv_heads=2, head_dim=16, d_ff=128,
                      vocab_size=256, param_dtype="float32", qkv_bias=True)
    model = build_model(cfg)
    return cfg, model, model.init(jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def engine(lm):
    cfg, model, params = lm
    eng = ContinuousEngine(model, params, batch_size=2, max_seq_len=64,
                           chunk_steps=4, fault_hook=lambda cache, s: None)
    # compile every program the runs below use
    eng.generate(_reqs(cfg))
    eng.generate(_reqs(cfg)[-1:])
    return eng


def _reqs(cfg, temperature=None):
    return [Request(uid=10 + i, prompt=(jnp.arange(6) + i) % cfg.vocab_size,
                    max_new_tokens=4 + 3 * i, temperature=temperature,
                    seed=i) for i in range(4)]


ARRIVALS = [0.0, 0.0, 0.0, 1.0]     # the last one after the batch drains


class Ticks:
    """An injected clock: each reading advances it by ``step`` seconds, so
    the run's timeline (the late arrival comes after the batch drains)
    does not depend on how busy the machine is."""

    def __init__(self, step=1e-3):
        self.t, self.step = 0.0, step

    def __call__(self):
        self.t += self.step
        return self.t


def _traced(engine, reqs, arrivals=None):
    """Run ``reqs`` with a tracer on an injected clock; returns the
    records, the registry, the tokens and the wall on that clock."""
    clock = Ticks()
    sink = io.StringIO()
    engine.telemetry = tel = Telemetry(metrics=MetricsRegistry(),
                                       tracer=Tracer(sink), clock=clock)
    try:
        a = clock()
        out = engine.generate(reqs, arrivals=arrivals, clock=clock)
        wall = clock() - a
    finally:
        engine.telemetry = None
    recs = [json.loads(line) for line in sink.getvalue().splitlines()]
    return recs, tel.metrics, [r.tokens for r in out], wall


@pytest.fixture(scope="module")
def run(lm, engine):
    cfg = lm[0]
    return _traced(engine, _reqs(cfg), ARRIVALS)


def _spans(recs):
    return [r for r in recs if r["kind"] == "span"]


def test_top_level_spans_tile_the_run(run):
    recs, _, _, wall = run
    top = sorted((r for r in _spans(recs) if r["parent"] is None),
                 key=lambda r: r["ts"])
    assert {r["name"] for r in top} == TOP
    for a, b in zip(top, top[1:]):
        assert a["ts"] + a["dur"] <= b["ts"] + EPS          # disjoint
    assert sum(r["dur"] for r in top) >= 0.99 * wall
    # one span a stretch of sleeping toward the late arrival, not one per
    # poll of the queue
    (wait,) = [r for r in top if r["name"] == "arrival_wait"]
    assert wait["dur"] > 0.1 and wait["ts"] + wait["dur"] >= ARRIVALS[-1]


def test_children_tile_their_parent_and_carry_its_uid(run):
    recs = _spans(run[0])
    by_parent = {}
    for r in recs:
        by_parent.setdefault(r["parent"], []).append(r)
    for r in recs:
        kids = sorted(by_parent.get(r["span"], []), key=lambda k: k["ts"])
        assert [k["name"] for k in kids] == CHILDREN.get(r["name"], [])
        if not kids:
            continue
        assert kids[0]["ts"] == r["ts"]
        for a, b in zip(kids, kids[1:]):
            assert a["ts"] + a["dur"] == pytest.approx(b["ts"], abs=EPS)
        end = kids[-1]["ts"] + kids[-1]["dur"]
        assert end == pytest.approx(r["ts"] + r["dur"], abs=EPS)
        if r["name"] == "admit":
            assert all(k["uid"] == r["uid"] for k in kids)


def test_spans_of_a_request_carry_its_uid(run):
    recs = run[0]
    uids = {r["uid"] for r in recs if r["name"] == "retire"}
    assert uids == {10, 11, 12, 13}
    emits = [r for r in recs if r["name"] == "emit"]
    assert sorted(r["uid"] for r in emits) == sorted(uids)
    admits = {r["span"]: r["uid"] for r in recs if r["name"] == "admit"}
    assert sorted(admits.values()) == sorted(uids)
    # the bookkeeping after an admission is that request's
    top = sorted((r for r in _spans(recs) if r["parent"] is None),
                 key=lambda r: r["ts"])
    for a, b in zip(top, top[1:]):
        if a["name"] == "admit":
            assert b["name"] == "absorb" and b["uid"] == a["uid"]


def test_admit_and_decode_chunk_keep_fields_and_histograms(run):
    recs, reg, _, _ = run
    E = {"engine": "continuous"}
    admits = [r for r in recs if r["name"] == "admit"]
    firsts = {r["uid"]: r for r in recs if r["name"] == "first_token"}
    for r in admits:
        assert {"engine", "uid", "order", "slot", "arrival"} <= set(r)
        # the span ends at the first-token host sync, exactly
        assert r["ts"] + r["dur"] == pytest.approx(firsts[r["uid"]]["ts"],
                                                   abs=EPS)
    h_q = reg.histogram("serve.queue_wait_seconds", **E)
    assert h_q.count == len(admits)
    total = 0.0
    for r in admits:
        total += r["ts"] - r["arrival"]
    assert total == h_q.sum
    chunks = [r for r in recs if r["name"] == "decode_chunk"]
    for r in chunks:
        assert {"engine", "chunk", "steps", "active", "busy",
                "batch"} <= set(r)
    h_c = reg.histogram("serve.chunk_seconds", **E)
    assert h_c.count == len(chunks)
    assert sum(r["dur"] for r in chunks) == pytest.approx(h_c.sum, abs=1e-9)
    assert [r["chunk"] for r in chunks] == list(range(len(chunks)))


def test_failed_admission_is_not_an_admit(lm):
    """An admission whose first logits are not finite is recorded as
    ``admit.failed``: ``admit`` holds only admissions that produced a
    first token, so its readers need no filter, and the trace still
    recomputes the registry."""
    from repro.runtime.trace_analysis import TraceAnalysis
    from repro.testing.chaos import nan_poison_leaf

    cfg, model, params = lm
    bad = nan_poison_leaf(params, seed=11, path_contains="blocks")
    eng = ContinuousEngine(model, bad, batch_size=2, max_seq_len=64,
                           chunk_steps=4)
    recs, reg, tokens, _ = _traced(eng, _reqs(cfg))
    assert tokens == [[]] * 4
    spans = _spans(recs)
    assert not [r for r in spans if r["name"] == "admit"]
    failed = [r for r in spans if r["name"] == "admit.failed"]
    assert sorted(r["uid"] for r in failed) == [10, 11]   # both lanes
    for r in failed:
        kids = sorted((k for k in spans if k["parent"] == r["span"]),
                      key=lambda k: k["ts"])
        assert [k["name"] for k in kids] == CHILDREN["admit"]
    assert TraceAnalysis(recs).crosscheck(reg)["matches"]


@pytest.mark.parametrize("temperature", [None, 0.8])
def test_tokens_identical_with_tracer_on_and_off(lm, engine, temperature):
    cfg = lm[0]
    off = [r.tokens for r in engine.generate(_reqs(cfg, temperature))]
    _, _, on, _ = _traced(engine, _reqs(cfg, temperature))
    assert on == off


def test_profile_holds_every_span_on_the_same_clock(lm, engine, tmp_path):
    """A CPU-profiled run: each JSONL span is a host event of the same name
    in the xplane, and after the marker's offset the two agree within
    1 ms at both ends."""
    cfg = lm[0]
    t0 = time.perf_counter()
    jax.profiler.start_trace(str(tmp_path))
    try:
        t_on = time.perf_counter() - t0
        with jax.profiler.TraceAnnotation("spans_test_mark"):
            pass
        sink = io.StringIO()
        engine.telemetry = Telemetry(
            tracer=Tracer(sink), clock=lambda: time.perf_counter() - t0)
        engine.generate(_reqs(cfg), arrivals=[0.0, 0.0, 0.0, 0.05],
                        clock=lambda: time.perf_counter() - t0)
    finally:
        engine.telemetry = None
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    host = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    host.setdefault(e.name, []).append(
                        (e.start_ns * 1e-9, (e.start_ns + e.duration_ns)
                         * 1e-9))
    (mark,) = host["spans_test_mark"]
    offset = mark[0] - t_on
    spans = _spans([json.loads(x) for x in sink.getvalue().splitlines()])
    assert {r["name"] for r in spans} >= TOP - {"arrival_wait"}
    for name in {r["name"] for r in spans}:
        mine = [r for r in spans if r["name"] == name]
        assert len(host.get(name, [])) == len(mine), name
        for r in mine:
            a, b = r["ts"] + offset, r["ts"] + r["dur"] + offset
            s, e = min(host[name], key=lambda ev: abs(ev[0] - a))
            assert abs(s - a) < 1e-3 and abs(e - b) < 1e-3, (name, r)


SCOPES = {"layer_scan", "qkv", "rope", "kv_write", "attention", "o_proj",
          "mlp", "head", "sample", "health"}


def test_programs_carry_named_scopes(lm):
    """The lowered admission and decode-chunk programs name each part of
    the step, and each packed plan dispatch, in their op_name metadata."""
    from repro.core import DEFAULT_EXCLUDE, PruneConfig, greedy_prune

    cfg, model, params = lm
    pcfg = PruneConfig(
        scheme="tile_pattern", exclude=tuple(DEFAULT_EXCLUDE),
        overrides={".*": {"tile_block_p": 32, "tile_group_q": 8,
                          "tile_keep": 4}})
    art = greedy_prune(params, pcfg).to_artifact(arch="tiny").pack()
    eng = ContinuousEngine(model, art, batch_size=2, max_seq_len=64,
                           chunk_steps=4, packed=True)
    cache = model.init_cache(2, 64)
    tok = jnp.zeros((2, 1), jnp.int32)
    prompt = jnp.arange(8, dtype=jnp.int32)[None]
    programs = {
        "admit": eng._admit_greedy.lower(eng.params, cache, tok, prompt, 0),
        "decode_chunk": eng._chunk_greedy.lower(
            eng.params, cache, tok, jnp.ones((2,), bool), 4),
    }
    for what, lowered in programs.items():
        names = set(re.findall(r'op_name="([^"]*)"',
                               lowered.compile().as_text()))
        segments = {seg for n in names for seg in n.split("/")}
        assert SCOPES <= segments, (what, SCOPES - segments)
        assert any("packed/tile_pattern/" in n for n in names), what
