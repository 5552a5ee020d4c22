"""The engine-span metrics on hand-built traces where the answer is known,
and the scope reader on the trace recorded on a TPU v5e."""

import os

import pytest

from bench import engine_spans as es
from bench import readers, scopes, spec
from bench import trace as tr

DATA = os.path.join(os.path.dirname(os.path.dirname(__file__)), "data",
                    "small_trace")


def ev(name, a, b):
    return tr.Event(name, a, b)


def facts(ops, modules=(), host=(), lo=0.0, hi=10.0):
    red = tr.Reduced(ops={0: list(ops)}, modules={0: list(modules)},
                     host=sorted(host, key=lambda e: e.start))
    return readers.Facts(shapes=None, arch=None, peaks=None, window=None,
                         reduced=red, lo=lo, hi=hi)


def metric(name, f):
    return spec.load_metric(name).compute(f)


# Two decode chunks of an engine, window [0, 10]:
#   chunk 0: host span [0, 3], program on the chip [0.5, 2.5], sync [1, 3]
#   host bookkeeping (absorb, reap, fault_hook) [3, 4]
#   chunk 1: host span [4, 7], program [4.2, 6.0], sync [5, 7]
#   waiting for an arrival [7, 9.5], then a reap [9.5, 10]
PROGRAMS = [ev("jit_scan_decode(1)", 0.5, 2.5),
            ev("jit_scan_decode(2)", 4.2, 6.0)]
OPS = [ev("fusion.1", 0.5, 2.5), ev("fusion.2", 4.2, 6.0)]
HOST = [ev("decode_chunk", 0.0, 3.0), ev("decode_chunk.prep", 0.0, 0.2),
        ev("decode_chunk.dispatch", 0.2, 1.0),
        ev("decode_chunk.sync", 1.0, 3.0),
        ev("absorb", 3.0, 3.5), ev("reap", 3.5, 3.8),
        ev("fault_hook", 3.8, 4.0),
        ev("decode_chunk", 4.0, 7.0), ev("decode_chunk.prep", 4.0, 4.1),
        ev("decode_chunk.dispatch", 4.1, 5.0),
        ev("decode_chunk.sync", 5.0, 7.0),
        ev("arrival_wait", 7.0, 9.5), ev("reap", 9.5, 10.0),
        ev("PjitFunction(scan_decode)", 4.1, 4.15)]


def test_host_stall_share_leaves_arrival_slack_out():
    # idle: [0, 0.5] + [2.5, 4.2] + [6, 10] = 6.2 s, of which 2.5 s inside
    # arrival_wait: 3.7 s of a 10 s window
    f = facts(OPS, PROGRAMS, HOST)
    assert metric("host_stall_share.long_prompt", f) == pytest.approx(37.0)
    # the same gap with no engine spans to tell it apart: nothing to read
    assert metric("host_stall_share.long_prompt", facts(OPS)) is None


def test_host_stall_share_counts_a_gap_only_outside_the_wait():
    # one gap [2, 8] half inside a wait [5, 9]: 3 s of 10 stall
    f = facts([ev("a", 0.0, 2.0), ev("b", 8.0, 10.0)],
              host=[ev("reap", 0.0, 5.0), ev("arrival_wait", 5.0, 9.0)])
    assert metric("host_stall_share.long_prompt", f) == pytest.approx(30.0)


def test_sync_lag_is_the_median_from_program_end_to_sync_end():
    # chunk 0: 3.0 - 2.5; chunk 1: 7.0 - 6.0; a third chunk's lag 0.2
    host = HOST + [ev("decode_chunk", 10.5, 12.0),
                   ev("decode_chunk.sync", 11.0, 12.0)]
    programs = PROGRAMS + [ev("jit_scan_decode(3)", 10.6, 11.8)]
    f = facts(OPS, programs, host, hi=13.0)
    assert metric("sync_lag_ms.chat_batch", f) == pytest.approx(500.0)
    f = facts(OPS, PROGRAMS, HOST)
    assert metric("sync_lag_ms.chat_batch", f) == pytest.approx(750.0)
    assert metric("sync_lag_ms.chat_batch", facts(OPS, PROGRAMS)) is None


def test_host_work_is_idle_time_outside_syncs_and_waits_per_chunk():
    # idle 6.2 s less [2.5, 3] and [6, 7] (syncs) and [7, 9.5] (wait):
    # 6.2 - 0.5 - 1.0 - 2.5 = 2.2 s over 2 chunks
    f = facts(OPS, PROGRAMS, HOST)
    assert metric("host_work_ms.chat_batch", f) == pytest.approx(1100.0)
    assert metric("host_work_ms.chat_batch", facts(OPS, PROGRAMS)) is None


def test_spans_partly_outside_the_window():
    # a chunk that started before the trace is not counted; a wait that
    # runs past its end still covers the gap it holds
    f = facts(OPS, PROGRAMS, HOST, lo=1.0, hi=8.0)
    assert [e.start for e in es.events(f, es.CHUNK)] == [4.0]
    assert es.cover(f, lambda n: n == es.WAIT) == [(7.0, 8.0)]
    # idle [2.5, 4.2] + [6, 8]: [2.5, 3] and [6, 7] in syncs, [7, 8] in
    # the wait, 1.2 s left for the one chunk
    assert metric("host_work_ms.chat_batch", f) == pytest.approx(1200.0)


def test_overlap_of_interval_lists():
    assert es.overlap([(0, 2), (3, 5)], [(1, 4)]) == pytest.approx(2.0)
    assert es.overlap([(0, 1)], [(1, 2)]) == 0.0
    assert es.overlap([], [(0, 1)]) == 0.0


def test_scope_paths():
    path = ("jit(scan_decode)/closed_call/layer_scan/closed_call/qkv/"
            "jit(fn)/packed/tile_pattern/gather/jit(_take)/gather")
    assert scopes.scope_of(path) == "layer_scan/qkv/packed/tile_pattern/gather"
    assert scopes.scope_of("jit(f)/layer_scan/while/body/kv_write/"
                           "dynamic_update_slice") == "layer_scan/kv_write"
    assert scopes.scope_of("jit(f)/dot_general") == "-"
    # a scope the model adds later is kept under its own name; calls,
    # control flow, cond branches and einsum specs are JAX's, not scopes
    assert scopes.scope_of("jit(f)/while/body/closed_call/layer_scan/"
                           "cond/branch_1_fun/new_part/vmap(g)/"
                           "bqd,bkd->bqk/dot_general") == "layer_scan/new_part"
    assert scopes.scope_of("") == "-"
    ops = [scopes.Op("%fusion.1 = x", 0.0, 2.0, "jit(f)/layer_scan/mlp/dot"),
           scopes.Op("%while.2 = x", 0.0, 5.0, "jit(f)/layer_scan"),
           scopes.Op("%copy.3 = x", 2.0, 3.0, "jit(f)/layer_scan/while")]
    assert scopes.time_by_scope(ops) == {"layer_scan/mlp": 2.0,
                                         "layer_scan": 1.0}


def test_scopes_read_the_recorded_tpu_trace():
    """The op_name paths of the chip's operations, read from the raw
    xplane, hold the flash kernel's ``pallas_call`` path; times match
    the profiler's own reader."""
    ops = scopes.read(DATA)
    flash = [o for o in ops if o.name.startswith("%flash_attention")]
    assert flash and all(
        o.tf_op == "jit(<lambda>)/jit(flash_attention)/flash_attention/"
                   "pallas_call" for o in flash)
    red = tr.load(DATA)
    assert [t for o in ops for t in (o.start, o.end)] == pytest.approx(
        [t for e in red.ops[0] for t in (e.start, e.end)])


def test_scopes_main_sums_by_scope_and_fails_without_scopes(
        monkeypatch, capsys):
    assert scopes.main([DATA]) == 0
    assert "flash_attention" in capsys.readouterr().out
    bare = [scopes.Op("%fusion.1 = x", 0.0, 1.0, "jit(f)/dot_general"),
            scopes.Op("%copy.2 = x", 1.0, 2.0, "")]
    monkeypatch.setattr(scopes, "read", lambda path: bare)
    assert scopes.main([DATA]) == 1
    assert ("jax_compilation_cache_include_metadata_in_key"
            in capsys.readouterr().err)
