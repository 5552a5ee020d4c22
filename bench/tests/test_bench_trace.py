"""The trace reduction, on hand-built events where the answer is known and
on a small trace recorded on a TPU v5e (``bench/data/small_trace``)."""

import os

import pytest

from bench import readers
from bench import trace as tr

DATA = os.path.join(os.path.dirname(os.path.dirname(__file__)), "data",
                    "small_trace")


def ev(name, a, b):
    return tr.Event(name, a, b)


def test_union_busy_and_gaps_hand_built():
    ops = [ev("fusion.1", 0.0, 1.0), ev("fusion.2", 0.5, 2.0),
           ev("pattern_gemm.3", 3.0, 4.0), ev("copy", 3.5, 3.75),
           ev("x", 6.0, 7.0)]
    assert tr.union(ops) == [(0.0, 2.0), (3.0, 4.0), (6.0, 7.0)]
    # window [1, 6.5]: busy 1 + 1 + 0.5, idle gaps 1 (2-3) and 2 (4-6)
    assert tr.busy_seconds(ops, 1.0, 6.5) == pytest.approx(2.5)
    assert tr.idle_gaps(ops, 1.0, 6.5) == [(4.0, 6.0), (2.0, 3.0)]
    assert tr.idle_gaps(ops, -1.0, 8.0)[:2] == [(4.0, 6.0), (-1.0, 0.0)]
    by = tr.time_by_name(ops + [ev("while.4", 0.0, 4.0)])
    assert by["fusion"] == pytest.approx(2.5) and by["pattern_gemm"] == 1.0
    assert "while" not in by


def test_names_and_index():
    assert tr.base_name("pattern_gemm.12") == "pattern_gemm"
    assert tr.base_name("fusion.3.1") == "fusion"
    assert tr.base_name("%pattern_gemm.172 = bf16[1024,8960]{1,0} "
                        "custom-call(s32[70,1,768] %a, f32[1536,1024] "
                        "%b.1)") == "pattern_gemm"
    idx = tr.Index([ev("b", 2.0, 3.0), ev("a", 0.0, 1.0),
                    ev("c", 2.5, 4.5)])
    assert [e.name for e in idx.inside(0.0, 3.0)] == ["a", "b"]
    assert [e.name for e in idx.inside(2.0, 5.0)] == ["b", "c"]


def _window(spans, outcomes):
    from bench.serving import Outcome, Window

    return Window(outcomes=outcomes, seconds=1.0, t_end=1.0, spans=spans,
                  busy_slot_steps=0, total_slot_steps=0, compiles=0)


def test_programs_matched_to_engine_spans():
    """Admissions and decode chunks found from the engine's spans on the
    benchmark's clock, shifted onto the trace's clock."""
    from bench.serving import Outcome

    spans = [
        {"kind": "span", "name": "admit", "ts": 0.10, "dur": 0.20,
         "uid": 7, "arrival": 0.05},
        {"kind": "span", "name": "decode_chunk", "ts": 0.40, "dur": 0.10,
         "chunk": 0, "steps": 4},
    ]
    outs = [Outcome(uid=7, prompt_len=32, max_new=3, arrival=0.05,
                    status="ok", tokens=[1, 2, 3])]
    modules = [ev("jit_admit_greedy(1)", 10.12, 10.28),
               ev("jit_slice(2)", 10.29, 10.295),
               ev("jit_scan_decode(3)", 10.41, 10.49)]
    red = tr.Reduced(ops={0: []}, modules={0: modules}, host=[])
    f = readers.Facts(shapes=None, arch=None, peaks=None,
                      window=_window(spans, outs), reduced=red, offset=10.0,
                      lo=10.0, hi=11.0)
    adm = readers.admissions(f)
    assert [(m.name, S) for m, S in adm] == [("jit_admit_greedy(1)", 32)]
    dec = readers.decode_chunks(f)
    # one live row: 2 more tokens, attending over 33 then 34 positions
    assert [(m.name, s) for m, s in dec] == [
        ("jit_scan_decode(3)", [[33], [34], [], []])]


def test_recorded_tpu_trace():
    red = tr.load(DATA)
    assert red.chips == [0]
    ops, mods = red.ops[0], red.modules[0]
    assert ops and mods
    lo = tr.marker(red.host, "bench_clock")
    hi = tr.marker(red.host, "bench_clock_end")
    assert lo is not None and hi is not None and hi.start > lo.start
    busy = tr.busy_seconds(ops, lo.start, hi.start)
    assert 0 < busy < hi.start - lo.start
    names = tr.time_by_name(ops)
    assert names.get("flash_attention", 0) > 0
    # every operation runs inside some program
    idx = tr.Index(ops)
    inner = sum(len(idx.inside(m.start, m.end)) for m in mods)
    assert inner >= 0.9 * len(ops)


def test_kernel_roofline_and_step_mfu_from_a_built_trace():
    """The readers divide the work required, as the architecture module
    counts it, by the kernel events found inside each matched admission;
    admissions with a wrong count of kernel calls are left out."""
    from bench import peaks, work
    from bench.serving import Outcome
    from bench.tests.test_bench_work import QWEN2, small_shapes

    s = small_shapes()
    p = peaks.peaks_of("TPU v5 lite")
    spans = [{"kind": "span", "name": "admit", "ts": 0.0, "dur": 1.0,
              "uid": 1, "arrival": 0.0},
             {"kind": "span", "name": "admit", "ts": 2.0, "dur": 1.0,
              "uid": 2, "arrival": 0.0}]
    outs = [Outcome(uid=1, prompt_len=32, max_new=1, arrival=0.0,
                    status="ok", tokens=[5]),
            Outcome(uid=2, prompt_len=64, max_new=1, arrival=0.0,
                    status="ok", tokens=[5])]
    mods = [ev("jit_admit_greedy(1)", 0.1, 0.9),
            ev("jit_admit_greedy(2)", 2.1, 2.9)]
    # first admission: 7 GEMMs x 2 layers of 10 ms; second: one call short
    ops = [ev("%pattern_gemm.1 = bf16[32,64] custom-call()",
              0.1 + 0.01 * i, 0.1 + 0.01 * (i + 1)) for i in range(14)]
    ops += [ev("%pattern_gemm.2 = x", 2.1 + 0.01 * i, 2.1 + 0.01 * (i + 1))
            for i in range(13)]
    ops += [ev("%flash_attention.3 = x", 0.5, 0.6),
            ev("%flash_attention.3 = x", 0.6, 0.7)]
    f = readers.Facts(shapes=s, arch=QWEN2, peaks=p,
                      window=_window(spans, outs),
                      reduced=tr.Reduced(ops={0: ops}, modules={0: mods},
                                         host=[]),
                      offset=0.0, lo=0.0, hi=3.0)
    names = QWEN2.BLOCK_GEMMS

    def need(S):
        return sum((QWEN2.gemm(s, n, S) for n in names), work.Work())

    assert QWEN2.prefill_kernels(s, 32)["pattern_gemm"] == (7, need(32))
    got = readers.kernel_roofline(f, "pattern_gemm")
    assert got == pytest.approx(100 * 2 * need(32).seconds(p) / 0.14)
    flash = readers.kernel_roofline(f, "flash_attention")
    assert flash == pytest.approx(
        100 * 2 * work.flash_prefill(32, 4, 2, 16).seconds(p) / 0.2)
    assert readers.kernel_roofline(f, "column_gemm") is None
    mfu = readers.step_mfu(f)
    busy = 0.14 + 0.2 + 0.13           # op time inside both admissions
    flops = QWEN2.prefill(s, 32).flops + QWEN2.prefill(s, 64).flops
    assert mfu == pytest.approx(100 * flops / (busy * p.bf16_flops))
