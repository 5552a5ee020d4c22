"""Reduction of a profiler trace (``.xplane.pb``) to what the metrics read.

``jax.profiler.ProfileData`` reads the file with nothing but JAX. On a TPU
each chip is a plane named ``/device:TPU:<n>``; its ``XLA Ops`` line holds
one event per operation run on the chip (a Pallas kernel's event carries
the kernel's ``name``), its ``XLA Modules`` line one event per program run.
Host threads are the ``/host:...`` planes; the benchmark's own
``TraceAnnotation`` marks land there and put the host's clock beside the
device's.

All times here are seconds on the trace's clock.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_SUFFIX = re.compile(r"(\.\d+)+$")


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float
    end: float

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Reduced:
    """Device operations and programs per chip, and host events."""

    ops: Dict[int, List[Event]]
    modules: Dict[int, List[Event]]
    host: List[Event]

    @property
    def chips(self) -> List[int]:
        return sorted(self.ops)


def base_name(name: str) -> str:
    """An operation's name without XLA's ``.N`` uniquifying suffixes.

    The TPU trace names an operation by its whole HLO instruction,
    ``%pattern_gemm.172 = bf16[1024,8960]{...} custom-call(...)``; the name
    is the part between ``%`` and `` = ``.
    """
    if name.startswith("%"):
        name = name[1:].split(" = ", 1)[0]
    return _SUFFIX.sub("", name)


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def _events(line) -> List[Event]:
    return [Event(e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns)
                  * 1e-9) for e in line.events]


def load(path: str) -> Reduced:
    """Reduce the trace at ``path`` (a file, or a directory holding one)."""
    import jax

    if os.path.isdir(path):
        path = find_xplane(path)
    data = jax.profiler.ProfileData.from_file(path)
    ops: Dict[int, List[Event]] = {}
    modules: Dict[int, List[Event]] = {}
    host: List[Event] = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            chip = int(m.group(1))
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.setdefault(chip, []).extend(_events(line))
                elif line.name == MODULES_LINE:
                    modules.setdefault(chip, []).extend(_events(line))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(e for e in _events(line) if e.dur >= 0)
    for d in (ops, modules):
        for chip in d:
            d[chip].sort(key=lambda e: e.start)
    host.sort(key=lambda e: e.start)
    return Reduced(ops=ops, modules=modules, host=host)


def union(events: Iterable[Event], lo: float = float("-inf"),
          hi: float = float("inf")) -> List[Tuple[float, float]]:
    """Merged busy intervals of ``events``, clipped to [lo, hi]."""
    spans = sorted((max(e.start, lo), min(e.end, hi)) for e in events
                   if e.end > lo and e.start < hi)
    out: List[Tuple[float, float]] = []
    for a, b in spans:
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def busy_seconds(events: Iterable[Event], lo: float, hi: float) -> float:
    return sum(b - a for a, b in union(events, lo, hi))


def idle_gaps(events: Iterable[Event], lo: float, hi: float
              ) -> List[Tuple[float, float]]:
    """Intervals of [lo, hi] in which no event runs, longest first."""
    gaps, t = [], lo
    for a, b in union(events, lo, hi):
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    return sorted(gaps, key=lambda g: g[0] - g[1])


# operations whose event spans the operations of their body
CONTAINERS = frozenset({"while", "conditional", "call"})


def time_by_name(events: Iterable[Event]) -> Dict[str, float]:
    """Device seconds per operation name (suffixes dropped), leaving out
    the loops and calls whose events hold other operations' events."""
    out: Dict[str, float] = {}
    for e in events:
        k = base_name(e.name)
        if k not in CONTAINERS:
            out[k] = out.get(k, 0.0) + e.dur
    return out


class Index:
    """Events sorted by start, for finding those inside an interval."""

    def __init__(self, events: Iterable[Event]):
        self.events = sorted(events, key=lambda e: e.start)
        self.starts = [e.start for e in self.events]

    def inside(self, a: float, b: float) -> List[Event]:
        """The events that lie wholly inside [a, b]."""
        i = bisect.bisect_left(self.starts, a)
        j = bisect.bisect_right(self.starts, b)
        return [e for e in self.events[i:j] if e.end <= b]


def marker(host: Sequence[Event], name: str) -> Optional[Event]:
    """The first host event called ``name`` (a ``TraceAnnotation``)."""
    for e in host:
        if e.name == name:
            return e
    return None
