"""What the per-layer metrics of serving cells read, put on one clock.

The engine's tracer records ``admit`` and ``decode_chunk`` spans on the
benchmark's clock; the benchmark's ``TraceAnnotation`` mark puts that clock
beside the profiler's. A device program that lies wholly inside a host
span is the one the span dispatched and waited for: the longest such
``XLA Modules`` event is taken as it.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

from bench import trace as tr

SLACK = 1e-3             # seconds a program may stand outside its span


@dataclasses.dataclass
class Facts:
    """Everything one traced run hands to the metric readers."""

    shapes: Any                     # the architecture module's ``Shapes``
    arch: Any                       # the configuration's architecture module
    peaks: Any
    window: Any                     # serving.Window
    reduced: Optional[tr.Reduced] = None
    offset: float = 0.0             # profiler clock minus benchmark clock
    lo: float = 0.0                 # traced window, profiler clock
    hi: float = 0.0

    def __post_init__(self):
        self._index: Dict[str, tr.Index] = {}

    @property
    def chip(self) -> int:
        return self.reduced.chips[0]

    def ops(self) -> List[tr.Event]:
        return self.reduced.ops.get(self.chip, [])

    def modules(self) -> List[tr.Event]:
        return self.reduced.modules.get(self.chip, [])

    def index(self, what: str) -> tr.Index:
        """Sorted index of this chip's ``ops``, ``modules``, or of the
        operations of one kernel (``kernel:<name>``)."""
        if what not in self._index:
            if what == "ops":
                ev = self.ops()
            elif what == "modules":
                ev = self.modules()
            else:
                name = what.split(":", 1)[1]
                ev = [e for e in self.ops() if tr.base_name(e.name) == name]
            self._index[what] = tr.Index(ev)
        return self._index[what]


def spans(f: Facts, name: str) -> List[Tuple[float, float, Dict[str, Any]]]:
    """The engine's ``name`` spans inside the traced window, on the
    profiler's clock."""
    out = []
    for rec in f.window.spans:
        if rec.get("kind") != "span" or rec["name"] != name:
            continue
        a = rec["ts"] + f.offset
        b = a + rec["dur"]
        if a >= f.lo and b <= f.hi:
            out.append((a, b, rec))
    return out


def program_in(f: Facts, a: float, b: float) -> Optional[tr.Event]:
    """The longest device program wholly inside [a, b]."""
    inner = f.index("modules").inside(a - SLACK, b + SLACK)
    return max(inner, key=lambda m: m.dur, default=None)


def admissions(f: Facts) -> List[Tuple[tr.Event, int]]:
    """(device program, prompt tokens) of every admission in the trace."""
    if f.reduced is None:
        return []
    lens = {o.uid: o.prompt_len for o in f.window.outcomes}
    out = []
    for a, b, rec in spans(f, "admit"):
        m = program_in(f, a, b)
        if m is not None:
            out.append((m, lens[rec["uid"]]))
    return out


def kernel_events(f: Facts, programs: List[tr.Event], kernel: str
                  ) -> List[List[tr.Event]]:
    """Per program, the ``kernel`` operations that ran inside it."""
    idx = f.index("kernel:" + kernel)
    return [idx.inside(p.start, p.end) for p in programs]


def decode_contexts(f: Facts) -> Dict[int, List[List[int]]]:
    """Per decode chunk (by index), per step, the context each live row
    attends over: rebuilt from the admissions, each request's served
    tokens and the chunk spans, as the scheduler advances them."""
    w = f.window
    by_uid = {o.uid: o for o in w.outcomes}
    admits = sorted((rec["ts"] + rec["dur"], rec["uid"]) for rec in w.spans
                    if rec.get("kind") == "span" and rec["name"] == "admit")
    chunks = sorted((rec["ts"], rec) for rec in w.spans
                    if rec.get("kind") == "span"
                    and rec["name"] == "decode_chunk")
    live: Dict[int, int] = {}                    # uid -> tokens emitted
    out: Dict[int, List[List[int]]] = {}
    i = 0
    for ts, rec in chunks:
        while i < len(admits) and admits[i][0] <= ts:
            uid = admits[i][1]
            if len(by_uid[uid].tokens) > 1:
                live[uid] = 1
            i += 1
        K = rec["steps"]
        steps: List[List[int]] = [[] for _ in range(K)]
        for uid in list(live):
            o = by_uid[uid]
            e = live[uid]
            n = min(K, len(o.tokens) - e)
            for j in range(n):
                steps[j].append(o.prompt_len + e + j)
            live[uid] = e + n
            if live[uid] >= len(o.tokens):
                del live[uid]
        out[rec["chunk"]] = steps
    return out


def decode_chunks(f: Facts) -> List[Tuple[tr.Event, List[List[int]]]]:
    """(device program, contexts per step) of every decode chunk in the
    trace."""
    if f.reduced is None:
        return []
    ctx = decode_contexts(f)
    out = []
    for a, b, rec in spans(f, "decode_chunk"):
        m = program_in(f, a, b)
        if m is not None:
            out.append((m, ctx[rec["chunk"]]))
    return out


def busy_of(f: Facts, programs: List[tr.Event]) -> float:
    """Device seconds in which an operation of ``programs`` ran."""
    idx = f.index("ops")
    inner = [e for p in programs for e in idx.inside(p.start, p.end)]
    return sum(b - a for a, b in tr.union(inner))


def kernel_roofline(f: Facts, kernel: str) -> Optional[float]:
    """Percent of its roofline a prefill kernel reached over the traced
    admissions: the least time the chip could take for the work the
    kernel was asked (``arch.prefill_kernels``: calls and work per block),
    over the device time of the kernel's events. Admissions whose kernel
    count is not the architecture's calls per block times the blocks are
    left out; an architecture whose prefill does not run the kernel
    gives nothing to read."""
    adm = admissions(f)
    per = kernel_events(f, [m for m, _ in adm], kernel)
    least, took = 0.0, 0.0
    for (m, S), evs in zip(adm, per):
        need = f.arch.prefill_kernels(f.shapes, S).get(kernel)
        if need is None or len(evs) != need[0] * f.shapes.layers:
            continue
        least += f.shapes.layers * need[1].seconds(f.peaks)
        took += sum(e.dur for e in evs)
    return 100.0 * least / took if took > 0 else None


def step_mfu(f: Facts) -> Optional[float]:
    """Percent of the chip's bf16 peak: the operations every traced
    admission and decode chunk required, over the device time in which
    their operations ran."""
    adm = admissions(f)
    dec = decode_chunks(f)
    flops = sum(f.arch.prefill(f.shapes, S).flops for _, S in adm)
    flops += sum(f.arch.decode_token_flops(f.shapes, c)
                 for _, steps in dec for step in steps for c in step)
    busy = busy_of(f, [m for m, _ in adm] + [m for m, _ in dec])
    if busy <= 0:
        return None
    return 100.0 * flops / (busy * f.peaks.bf16_flops)
