"""From a profiler trace to numbers.  `extract` reads the `.xplane.pb` the JAX
profiler wrote into plain lists (the form the recorded trace in the tests is
kept in); everything else works on those lists.

  {"devices": {"/device:TPU:0": [[start_ns, dur_ns, name], ...]},   device ops
   "host": [[start_ns, dur_ns, name], ...]}        the benchmark's annotations

Device ops are the events of each device plane's "XLA Ops" line: one event
for each operation the core ran, so their union is the time the device was
busy.  Host events are the `TraceAnnotation`s the benchmark's wrappers write
(`admit`, `decode_step`, `train_step`, `input`), on the same clock.
"""

from __future__ import annotations

import re
from typing import Any, Dict, Iterable, List, Optional, Tuple

OPS_LINE = "XLA Ops"
ANNOTATIONS = ("admit", "decode_step", "train_step", "input")
COLLECTIVE = re.compile(
    r"all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute", re.I
)
# the opcode of an HLO line, "%name = <result's type> opcode(operands), ...":
# the first word before a "(" that a space precedes (a type's own brackets,
# "f32[8]{0:T(8,128)S(1)}", follow a colon, a bracket or a digit); and the
# computation a fusion runs, "..., kind=kCustom, calls=%all-reduce-scatter.85"
_OPCODE = re.compile(r" ([a-z][\w-]*)\(")
_CALLS = re.compile(r"\bcalls=%?([\w.-]+)")


def is_collective(name: str) -> bool:
    """Whether the operation a trace names (by its whole HLO line on this
    runtime, or a name alone) IS a collective: its own name, before " = ", its
    opcode, or the computation it calls says so (the TPU compiler runs a
    reduce-scatter as a custom fusion that calls `%all-reduce-scatter.N`), an
    asynchronous pair's `-start` and `-done` included.  Its operands do not: a
    fusion that multiplies what `%all-gather.3` brought is compute."""
    own, _, rest = name.partition(" = ")
    opcode, called = _OPCODE.search(" " + rest), _CALLS.search(rest)
    parts = (own, opcode.group(1) if opcode else "", called.group(1) if called else "")
    return any(COLLECTIVE.search(part) for part in parts)


def extract(xplane_path: str) -> Dict[str, Any]:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(xplane_path)
    devices: Dict[str, List[list]] = {}
    host: List[list] = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices.setdefault(plane.name, []).extend(
                        [float(e.start_ns), float(e.duration_ns), e.name] for e in line.events
                    )
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(
                    [float(e.start_ns), float(e.duration_ns), e.name]
                    for e in line.events if e.name in ANNOTATIONS
                )
    for evs in devices.values():
        evs.sort()
    host.sort()
    return {"devices": devices, "host": host}


def _union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return [(a, b) for a, b in out]


def span(events: Dict[str, Any]) -> Optional[Tuple[float, float]]:
    """The traced window: first start to last end over the device ops."""
    evs = [e for d in events["devices"].values() for e in d]
    if not evs:
        return None
    return min(e[0] for e in evs), max(e[0] + e[1] for e in evs)


def busy(events: Dict[str, Any]) -> Optional[Dict[str, float]]:
    """Seconds in which an operation ran on the device, averaged over the
    devices traced, and the length of the traced window."""
    w = span(events)
    if w is None:
        return None
    per_device = [
        sum(b - a for a, b in _union((e[0], e[0] + e[1]) for e in evs))
        for evs in events["devices"].values()
    ]
    return {"busy_s": sum(per_device) / len(per_device) / 1e9, "window_s": (w[1] - w[0]) / 1e9}


def idle_percent(events) -> Optional[float]:
    b = busy(events)
    return None if b is None else 100.0 * (1.0 - b["busy_s"] / b["window_s"])


def _first_device(events) -> List[list]:
    return events["devices"][sorted(events["devices"])[0]] if events["devices"] else []


def collective_percent(events) -> Optional[float]:
    """Share of the traced window that the first device's core spent in
    collective operations.  The ops line is the core's serial timeline, so a
    collective that shows there is one the core waited in (an asynchronous
    one shows its `-start` and `-done`, not the transfer between them): time
    not hidden behind compute."""
    w, evs = span(events), _first_device(events)
    if w is None:
        return None
    t = sum(b - a for a, b in _union((e[0], e[0] + e[1]) for e in evs if is_collective(e[2])))
    return 100.0 * t / (w[1] - w[0])


def top_ops(events, n: int = 10) -> List[list]:
    """[name, seconds] of the first device's operations that took most time,
    instances of one operation summed."""
    total: Dict[str, float] = {}
    for _, dur, name in _first_device(events):
        name = name[:96]  # the trace names an operation by its whole HLO line
        total[name] = total.get(name, 0.0) + dur / 1e9
    return [[k, v] for k, v in sorted(total.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(events, n: int = 10) -> List[list]:
    """[what the host was doing, seconds] for the first device's idle time,
    each gap between operations given to the annotation that covers its
    middle ("host:other" where none does), summed by annotation."""
    ivs = _union((e[0], e[0] + e[1]) for e in _first_device(events))
    total: Dict[str, float] = {}
    host = events["host"]
    for (_, a_end), (b_start, _) in zip(ivs, ivs[1:]):
        mid = (a_end + b_start) / 2
        # innermost (latest started) annotation covering the gap's middle
        name = "host:other"
        for s, d, nm in host:
            if s > mid:
                break
            if s + d >= mid:
                name = nm
        total[name] = total.get(name, 0.0) + (b_start - a_end) / 1e9
    return [[k, v] for k, v in sorted(total.items(), key=lambda kv: -kv[1])[:n]]


def breakdown(events) -> Dict[str, List[list]]:
    return {"device_ops": top_ops(events), "idle_gaps": idle_gaps(events)}


def head(events: Dict[str, Any], seconds: float, name_chars: int = 80) -> Dict[str, Any]:
    """The first `seconds` of a trace with names cut short: small enough to
    keep beside the tests as a recorded trace."""
    w = span(events)
    if w is None:
        return {"devices": {}, "host": []}
    end = w[0] + seconds * 1e9
    cut = lambda evs: [[s, d, n[:name_chars]] for s, d, n in evs if s + d <= end]
    return {"devices": {k: cut(v) for k, v in events["devices"].items()}, "host": cut(events["host"])}
