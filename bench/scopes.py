"""Device time by named scope, from the ``tf_op`` of each operation.

The program names the parts of its steps with ``jax.named_scope``
(``layer_scan``, ``qkv``, ``rope``, ``kv_write``, ``attention``,
``o_proj``, ``mlp``, ``head``, ``sample``, ``health``) and each packed
plan dispatch ``packed/<scheme>/<impl>``. XLA keeps the scope path in
each instruction's ``op_name``; the TPU profiler writes it into the
``tf_op`` stat of the operation's event metadata. ``jax.profiler``'s
``ProfileData`` does not hand event metadata out, so this module reads
the ``.xplane.pb`` wire format itself, with nothing beyond the Python
standard library and JAX's own file layout.

A program loaded from JAX's persistent compilation cache keeps the
op_names it was compiled with: the cache key leaves metadata out unless
``jax_compilation_cache_include_metadata_in_key`` is set, so a cache
filled by a build without the scopes serves programs without them.

    python3 bench/scopes.py <trace dir or .xplane.pb>

prints device seconds per scope path over every operation of the
trace's one chip. It exits 1 where no operation carries a scope.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
import struct
import sys
from typing import Dict, Iterable, Iterator, List, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
# segments of an op_name that JAX's own tracing puts there, not a scope:
# calls (``jit(f)``, ``vmap(f)``, ``transpose(jvp(f))``), control flow,
# cond branches and einsum specs
_STRUCTURE = {"closed_call", "while", "body", "cond", "scan", "pjit",
              "remat", "checkpoint", "custom_jvp_call", "custom_vjp_call",
              "pallas_call", "shard_map"}
_NOT_SCOPE = re.compile(r"^(\S+\(.*\)|branch_\d+_fun|.*->.*)$")


@dataclasses.dataclass(frozen=True)
class Op:
    """One device event: its name, seconds on the trace's clock, and the
    HLO ``op_name`` path (``tf_op`` less its ``:<type>`` suffix; empty
    where the event has none)."""

    name: str
    start: float
    end: float
    tf_op: str

    @property
    def dur(self) -> float:
        return self.end - self.start


# --- protobuf wire format ---------------------------------------------------

def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    out, shift = 0, 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes) -> Iterator[Tuple[int, object]]:
    """(field number, value) of a message: ints for varints and fixed
    widths, bytes for length-delimited fields."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 1:
            v = struct.unpack_from("<q", buf, i)[0]
            i += 8
        elif wire == 2:
            ln, i = _varint(buf, i)
            v = buf[i:i + ln]
            i += ln
        elif wire == 5:
            v = struct.unpack_from("<i", buf, i)[0]
            i += 4
        else:
            raise ValueError(f"wire type {wire} at byte {i}")
        yield num, v


def _map(entries: Iterable[bytes]) -> Dict[int, bytes]:
    out = {}
    for e in entries:
        d = dict(_fields(e))
        out[d.get(1, 0)] = d.get(2, b"")
    return out


# XSpace.planes = 1; XPlane: name = 2, lines = 3, event_metadata = 4,
# stat_metadata = 5; XLine: name = 2, timestamp_ns = 3, events = 4;
# XEvent: metadata_id = 1, offset_ps = 2, duration_ps = 3;
# XEventMetadata: stats = 5; XStatMetadata: name = 2;
# XStat: metadata_id = 1, str_value = 5, ref_value = 7.

def _plane_ops(plane: bytes) -> List[Op]:
    lines, ev_meta, st_meta = [], [], []
    for num, v in _fields(plane):
        if num == 3:
            lines.append(v)
        elif num == 4:
            ev_meta.append(v)
        elif num == 5:
            st_meta.append(v)
    stat_names = {k: dict(_fields(v)).get(2, b"").decode()
                  for k, v in _map(st_meta).items()}
    names: Dict[int, str] = {}
    tf_ops: Dict[int, str] = {}
    for k, v in _map(ev_meta).items():
        for num, f in _fields(v):
            if num == 2:
                names[k] = f.decode(errors="replace")
            elif num == 5:
                st = dict(_fields(f))
                if stat_names.get(st.get(1)) != "tf_op":
                    continue
                if 5 in st:
                    path = st[5].decode(errors="replace")
                else:
                    path = stat_names.get(st.get(7), "")
                tf_ops[k] = path.rsplit(":", 1)[0] if ":" in path else path
    ops = []
    for line in lines:
        d = {}
        events = []
        for num, v in _fields(line):
            if num == 4:
                events.append(v)
            else:
                d[num] = v
        if d.get(2, b"").decode() != "XLA Ops":
            continue
        t0 = d.get(3, 0) * 1e-9
        for e in events:
            ev = dict(_fields(e))
            mid = ev.get(1, 0)
            a = t0 + ev.get(2, 0) * 1e-12
            ops.append(Op(names.get(mid, ""), a, a + ev.get(3, 0) * 1e-12,
                          tf_ops.get(mid, "")))
    ops.sort(key=lambda o: o.start)
    return ops


def find_xplane(path: str) -> str:
    if not os.path.isdir(path):
        return path
    found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return found[-1]


def read(path: str) -> List[Op]:
    """The ``XLA Ops`` events, with their ``tf_op``, of the trace's one
    chip."""
    with open(find_xplane(path), "rb") as fh:
        space = fh.read()
    planes = []
    for num, plane in _fields(space):
        if num != 1:
            continue
        name = next((v for n, v in _fields(plane) if n == 2), b"").decode()
        if DEVICE_PLANE.match(name):
            planes.append(plane)
    if len(planes) != 1:
        raise ValueError(f"{path}: {len(planes)} TPU planes, expected one")
    return _plane_ops(planes[0])


# --- attribution -------------------------------------------------------------

def scope_of(tf_op: str) -> str:
    """The named scopes of an ``op_name`` path, outermost first, joined
    by ``/``: every segment but the last (the primitive) that JAX's
    tracing did not put there itself. ``-`` where the path holds none."""
    parts = [seg for seg in tf_op.split("/")[:-1]
             if seg and seg not in _STRUCTURE and not _NOT_SCOPE.match(seg)]
    return "/".join(parts) or "-"


def time_by_scope(ops: Iterable[Op]) -> Dict[str, float]:
    """Device seconds per scope path, leaving out the loops and calls
    whose events hold other operations' events."""
    out: Dict[str, float] = {}
    for o in ops:
        base = re.sub(r"(\.\d+)+$", "", o.name.lstrip("%").split(" = ")[0])
        if base in ("while", "conditional", "call"):
            continue
        k = scope_of(o.tf_op)
        out[k] = out.get(k, 0.0) + o.dur
    return out


def main(argv: List[str]) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("trace")
    args = ap.parse_args(argv)
    by = time_by_scope(read(args.trace))
    if set(by) <= {"-"}:
        print("no operation carries a named scope: the programs were "
              "compiled without them, or came from a compile cache filled "
              "by such a build (its key leaves op_name metadata out unless "
              "jax_compilation_cache_include_metadata_in_key is set)",
              file=sys.stderr)
        return 1
    total = sum(by.values())
    for k, v in sorted(by.items(), key=lambda kv: -kv[1]):
        print(f"{v:12.6f} s  {100 * v / total:6.2f}%  {k}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
