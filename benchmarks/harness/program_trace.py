"""From a profiler trace to the program's own spans and the device's time by
scope.  `extract` reads the same `.xplane.pb` as trace_reduce.extract into
plain lists (the form the recorded trace in the tests is kept in); everything
else works on those lists.

  {"spans": [[thread, start_ns, dur_ns, name, {argument: value}], ...],
   "ops": {"/device:TPU:0": [[start_ns, dur_ns, name, scope], ...]}}

Spans are the program's `tracing.span()` blocks (util/tracing.py enters a
`TraceAnnotation` for each while a profiler session runs): the host events
whose name starts `llm.`, with the host line (one line a thread) they were
written on and the annotation's arguments.  Ops are the events of each device
plane's "XLA Ops" line with the scope the program ran them under: the
innermost `jax.named_scope` in the operation's `op_name` that is among the
common names below or those of the cell's architecture
(`references/<name>.py` SCOPES; `known_names`), "" where it names none.
This runtime (jax 0.9, TPU v5 lite) keeps the `op_name` in the `tf_op`
statistic of the event's metadata, the plane's table of operations, which
`jax.profiler.ProfileData` does not hand out: `op_names` reads that table
from the file itself.  A program without the spans or the scopes (an older
commit) gives empty lists and scopes, and every reader then finds nothing to
read.
"""

from __future__ import annotations

import functools
import json
import os
import re
import sys
from typing import Any, Dict, Iterable, Iterator, List, Optional, Tuple

from . import trace_reduce
from .stats import percentile

SPAN_PREFIX = "llm."
# the names every architecture's programs write (models/generate.py,
# models/transformer.py, ops/attention.py); an architecture's own come from its
# file under references/
SCOPES = (
    "embed", "norm", "attn.qkv", "attn.rope", "attn.cache", "attn.core", "attn.out",
    "ffn", "head", "sample", "loss", "optimizer",
)
KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")
OP_NAME_STAT = "tf_op"  # the statistic of an operation's metadata that holds its op_name

Interval = Tuple[float, float]


def known_names(ctx: Dict[str, Any]) -> Tuple[Tuple[str, ...], Tuple[str, ...]]:
    """(scopes, kernels) a run's readers know an operation by: the common ones
    and those of the cell's reference; the common ones alone where `ctx` names
    no cell."""
    if "cell" not in ctx:
        return SCOPES, KERNELS
    from . import manifest

    ref = manifest.reference_of(ctx["cell"])
    return SCOPES + tuple(ref.SCOPES), KERNELS + tuple(ref.KERNELS)


@functools.lru_cache(maxsize=None)
def _scope_re(scopes: Tuple[str, ...]):
    """A scope as a whole component of the op_name path, bare or wrapped by a
    transformation: "jit(f)/while/body/attn.core/dot_general",
    "transpose(jvp(attn.core))/mul", "checkpoint/rematted_computation/ffn/..." """
    names = "|".join(re.escape(s) for s in sorted(scopes, key=len, reverse=True))
    return re.compile(r"(?<![\w.])(" + names + r")(?![\w.])")


def scope_of(op_name: str, scopes: Tuple[str, ...] = SCOPES) -> str:
    """The innermost of `scopes` in an op_name path, "" if it names none."""
    found = _scope_re(tuple(scopes)).findall(op_name or "")
    return found[-1] if found else ""


# -- the file itself: the table of operations ProfileData leaves out ----------
# An .xplane.pb is one protobuf message (tsl/profiler/protobuf/xplane.proto):
# XSpace{1: planes}, XPlane{2: name, 4: event_metadata map, 5: stat_metadata
# map}, a map entry {1: key, 2: value}, XEventMetadata{2: name, 5: stats},
# XStatMetadata{2: name}, XStat{1: metadata_id, 5: str_value, 7: ref_value}.


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf: bytes) -> Iterator[Tuple[int, Any]]:
    """(field number, value) of one message: an int for a varint, the bytes
    for a length-delimited or fixed-width field."""
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        else:
            if kind == 2:
                size, i = _varint(buf, i)
            elif kind in (1, 5):
                size = 8 if kind == 1 else 4
            else:
                raise ValueError(f"wire type {kind} in an xplane file")
            value, i = buf[i:i + size], i + size
        yield key >> 3, value


def _map_values(plane: bytes, field: int) -> Iterator[Dict[int, list]]:
    """The values of one of a plane's maps, each as {field number: [values]}."""
    for number, entry in _fields(plane):
        if number == field:
            value: Dict[int, list] = {}
            for n, v in _fields(dict(_fields(entry)).get(2, b"")):
                value.setdefault(n, []).append(v)
            yield value


def op_names(xplane_path: str) -> Dict[str, Dict[str, str]]:
    """{device plane: {operation's name as the trace gives it: its op_name}}
    for the operations whose metadata carries one."""
    with open(xplane_path, "rb") as f:
        space = f.read()
    out: Dict[str, Dict[str, str]] = {}
    for number, plane in _fields(space):
        if number != 1:
            continue
        name = next((v for n, v in _fields(plane) if n == 2), b"").decode()
        if not name.startswith("/device:"):
            continue
        stat_names = {
            m[1][0]: m[2][0].decode() for m in _map_values(plane, 5) if 1 in m and 2 in m
        }
        table = out.setdefault(name, {})
        for meta in _map_values(plane, 4):
            for stat in meta.get(5, []):
                st = dict(_fields(stat))
                if stat_names.get(st.get(1)) != OP_NAME_STAT:
                    continue
                text = st[5].decode() if 5 in st else stat_names.get(st.get(7), "")
                if text and 2 in meta:
                    table[meta[2][0].decode()] = text
    return out


def extract(xplane_path: str, scopes: Tuple[str, ...] = SCOPES) -> Dict[str, Any]:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(xplane_path)
    try:
        names = op_names(xplane_path)
    except (ValueError, IndexError, UnicodeDecodeError) as e:  # not the layout above
        print(f"[bench] no operation metadata read from {xplane_path}: {e!r}", file=sys.stderr)
        names = {}
    spans: List[list] = []
    ops: Dict[str, List[list]] = {}
    thread = 0
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                thread += 1
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append(
                            [thread, float(e.start_ns), float(e.duration_ns), e.name, dict(e.stats)]
                        )
        elif plane.name.startswith("/device:"):
            by_name = {name: scope_of(op, scopes) for name, op in names.get(plane.name, {}).items()}
            for line in plane.lines:
                if line.name != trace_reduce.OPS_LINE:
                    continue
                evs = ops.setdefault(plane.name, [])
                for e in line.events:
                    evs.append([float(e.start_ns), float(e.duration_ns), e.name,
                                by_name.get(e.name, "")])
    spans.sort(key=lambda s: (s[1], -s[2]))
    for evs in ops.values():
        evs.sort(key=lambda e: (e[0], -e[1]))
    return {"spans": spans, "ops": ops}


def load(ctx: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """The run's trace in the form above, read once for all the readers of a
    run; None where the run was not traced.  Leaves a summary of what it found
    in `bench_out/<cell>.program_trace.json` for the builder to read."""
    if "program_trace" not in ctx:
        path = ctx.get("trace_path")
        ctx["program_trace"] = extract(path, known_names(ctx)[0]) if path else None
        if path:
            from . import cluster

            with open(os.path.join(cluster.out_dir(), f"{ctx['cell']['name']}.program_trace.json"), "w") as f:
                json.dump(dict(summary(ctx["program_trace"], known_names(ctx)[1]),
                               trace_stop_s=ctx.get("trace_stop_s"), trace_bytes=ctx.get("trace_bytes")), f)
    return ctx["program_trace"]


# -- spans --------------------------------------------------------------------


def spans_named(events: Dict[str, Any], name: str, thread: Optional[int] = None) -> List[list]:
    return [s for s in events["spans"] if s[3] == name and (thread is None or s[0] == thread)]


def _covered(events, family: str, thread: int) -> List[Interval]:
    """The time the thread spent inside a span of that name or under it
    (`llm.admit` and `llm.admit.*`).  The children count because the profiler
    keeps only spans that closed before it stopped: of a step or an admit cut
    by the slice's end, the parts that closed are all there is."""
    return trace_reduce._union(
        (s[1], s[1] + s[2]) for s in events["spans"]
        if s[0] == thread and (s[3] == family or s[3].startswith(family + "."))
    )


def pump_thread(events: Dict[str, Any]) -> Optional[int]:
    """The thread that drives the batcher: the one `llm.step` is written on."""
    steps = spans_named(events, "llm.step")
    return steps[0][0] if steps else None


def span_ms(events, name: str, q: Optional[float] = None) -> Optional[float]:
    """Duration of the spans of that name, ms: at percentile `q`, or their
    mean where `q` is None.  0 where the program writes spans and the slice
    holds none of that name (it is 4 s long, and two or three requests arrive
    in it: `summary` has the counts); None where the program writes none."""
    ms = [s[2] / 1e6 for s in spans_named(events, name)]
    if not ms:
        return None if pump_thread(events) is None else 0.0
    return sum(ms) / len(ms) if q is None else percentile(ms, q)


def span_arg_mean(events, name: str, arg: str) -> Optional[float]:
    """Mean of one argument over the spans of that name; 0 and None as span_ms."""
    values = [float(s[4][arg]) for s in spans_named(events, name) if arg in s[4]]
    if not values:
        return None if pump_thread(events) is None else 0.0
    return sum(values) / len(values)


def between_steps_ms(events, q: float) -> Optional[float]:
    """Time from the end of one `llm.step` to the start of the next on the
    pump's thread at percentile `q`, ms: token delivery, the lock handed to a
    caller, the metrics sync.  A gap is left out where the step after it
    admits a request: a caller's submit ran in that gap."""
    thread = pump_thread(events)
    if thread is None:
        return None
    steps = spans_named(events, "llm.step", thread)
    admits = [a[1] for a in spans_named(events, "llm.admit", thread)]
    gaps = [
        (b[1] - (a[1] + a[2])) / 1e6 for a, b in zip(steps, steps[1:])
        if not any(b[1] <= t < b[1] + b[2] for t in admits)
    ]
    return percentile(gaps, q) if gaps else None


# -- intervals ----------------------------------------------------------------


def _overlap(a: List[Interval], b: List[Interval]) -> float:
    """Total length in both of two sorted lists of disjoint intervals."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def _first_device(events) -> List[list]:
    return events["ops"][sorted(events["ops"])[0]] if events["ops"] else []


def _once(events: Dict[str, Any], key: str, make):
    """`make()` worked out once a trace and kept with it under `key`.  A
    cell's readers ask for the same walks of the device's operations a dozen
    times over, each a few seconds a million operations: worked out anew every
    time, they were most of a traced run's time after its window (PERF.md
    section 6, PR 58).  What is kept is marked with the lists it was made
    from, so a trace that is copied and given other spans or operations (the
    tests do) is walked again."""
    evs, spans = _first_device(events), events["spans"]
    mark = (id(evs), len(evs), id(spans), len(spans))
    kept = events.get(key)
    if kept is None or kept[0] != mark:
        kept = events[key] = (mark, make())
    return kept[1]


def idle_by_span(events) -> Optional[Dict[str, float]]:
    """Percent of the traced slice (first device operation to last) in which
    the first device ran nothing, each idle instant given to what the pump's
    thread was inside at that instant: `admit` (anything under `llm.admit`),
    `readback` (`llm.step.readback`), `step_host` (the rest of `llm.step`:
    upload, dispatch, scatter, its own time), `between_steps` (no `llm.step`
    open: `llm.pump.*` or nothing).  The four add up to the slice's idle
    share, the number trace_reduce.idle_percent gives.  None without the
    program's spans."""
    return _once(events, "_idle_by_span", lambda: _idle_by_span(events))


def _idle_by_span(events) -> Optional[Dict[str, float]]:
    thread, evs = pump_thread(events), _first_device(events)
    if thread is None or not evs:
        return None
    lo, hi = min(e[0] for e in evs), max(e[0] + e[1] for e in evs)
    busy = trace_reduce._union((e[0], e[0] + e[1]) for e in evs)
    edges = [lo] + [t for iv in busy for t in iv] + [hi]
    idle = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
    in_admit = _overlap(idle, _covered(events, "llm.admit", thread))
    in_readback = _overlap(idle, _covered(events, "llm.step.readback", thread))
    # an admit runs inside its step, which may not have closed
    in_step = _overlap(idle, trace_reduce._union(
        _covered(events, "llm.step", thread) + _covered(events, "llm.admit", thread)
    ))
    total = sum(b - a for a, b in idle)
    pct = lambda t: 100.0 * t / (hi - lo)
    return {
        "admit": pct(in_admit), "readback": pct(in_readback),
        "step_host": pct(in_step - in_admit - in_readback),
        "between_steps": pct(total - in_step),
    }


# -- device time by scope -----------------------------------------------------


def self_times(evs: List[list]) -> List[Tuple[float, str, str]]:
    """(self ns, name, scope) for each operation of one device's ops line: its
    duration less the time of the operations inside it (a `while` holds its
    body's), so the self times add up to the time the device was busy and an
    operation that contains others is not counted beside them."""
    out: List[list] = []
    stack: List[Tuple[float, int]] = []  # (end, index into out) of the operations still open
    for start, dur, name, scope in sorted(evs, key=lambda e: (e[0], -e[1])):
        while stack and stack[-1][0] <= start:
            stack.pop()
        if stack:
            out[stack[-1][1]][0] -= min(dur, stack[-1][0] - start)
        out.append([dur, name, scope])
        stack.append((start + dur, len(out) - 1))
    return [(max(t, 0.0), name, scope) for t, name, scope in out]


def device_self_times(events) -> List[Tuple[float, str, str]]:
    """`self_times` of the first device's operations, once a trace."""
    return _once(events, "_self_times", lambda: self_times(_first_device(events)))


def time_by_scope(events) -> Optional[Dict[str, float]]:
    """Self time of the first device's operations by scope, ns: "" for those
    under none, "collective" for the collectives whatever scope asked for
    them (trace_reduce counts those).  None where no operation names a scope
    (an older program, or a runtime that does not write them): `extract` gave
    an operation a scope only where its op_name holds a known one."""
    return _once(events, "_time_by_scope", lambda: _time_by_scope(events))


def _time_by_scope(events) -> Optional[Dict[str, float]]:
    total: Dict[str, float] = {}
    for t, name, scope in device_self_times(events):
        key = "collective" if trace_reduce.is_collective(name) else scope
        total[key] = total.get(key, 0.0) + t
    return total if any(k not in ("", "collective") for k in total) else None


def scope_percent(events, scopes: Iterable[str]) -> Optional[float]:
    """Share of the first device's busy time spent under any of `scopes`; a
    scope ending in "." takes every scope that starts with it."""
    by_scope = time_by_scope(events)
    if by_scope is None:
        return None
    wanted = tuple(scopes)
    hit = sum(
        t for k, t in by_scope.items()
        if k in wanted or any(w.endswith(".") and k.startswith(w) for w in wanted)
    )
    return 100.0 * hit / sum(by_scope.values())


def kernel_of(name: str, kernels: Tuple[str, ...] = KERNELS) -> str:
    """The Pallas kernel among `kernels` an operation is, by its own name in
    the trace ("%flash_fwd.5 = (...) custom-call(...)": the kernel's `name=`
    is the instruction's), "" for any other operation."""
    own = name.split(" = ", 1)[0].lstrip("%").split(".", 1)[0]
    return own if own in kernels else ""


def kernel_percent(events, kernels: Tuple[str, ...] = KERNELS) -> Optional[float]:
    """Share of the first device's busy time inside the named Pallas kernels;
    None where the trace holds none of them."""
    times = device_self_times(events)
    hit = [t for t, name, _ in times if kernel_of(name, kernels)]
    return 100.0 * sum(hit) / sum(t for t, _, _ in times) if hit else None


# -- what a builder reads, and the recorded trace -----------------------------


def summary(events: Dict[str, Any], kernels: Tuple[str, ...] = KERNELS) -> Dict[str, Any]:
    """Counts and times of everything above in one object (ms)."""
    names = sorted({s[3] for s in events["spans"]})
    by_scope = time_by_scope(events) or {}
    return {
        "spans": {
            n: {"count": len(spans_named(events, n)), "mean_ms": span_ms(events, n),
                "p50_ms": span_ms(events, n, 50)}
            for n in names
        },
        "pump_between_ms_p50": between_steps_ms(events, 50),
        "idle_by_span_percent": idle_by_span(events),
        "device_ms_by_scope": {k or "(none)": t / 1e6 for k, t in sorted(by_scope.items())},
        "kernels_percent": kernel_percent(events, kernels),
    }


def head(events: Dict[str, Any], seconds: float, name_chars: int = 80) -> Dict[str, Any]:
    """The first `seconds` of a trace with names cut short (trace_reduce.head's
    method): small enough to keep beside the tests as a recorded trace."""
    starts = [e[0] for evs in events["ops"].values() for e in evs]
    if not starts:
        return {"spans": [], "ops": {}}
    end = min(starts) + seconds * 1e9
    return {
        "spans": [s for s in events["spans"] if s[1] + s[2] <= end],
        "ops": {k: [[s, d, n[:name_chars], sc] for s, d, n, sc in v if s + d <= end]
                for k, v in events["ops"].items()},
    }
