#!/usr/bin/env python3
"""What `keye-longdoc-closed4`'s readers make of a traced run's `.xplane.pb`,
read by hand (since PR 58 the harness stops the profiler's session itself,
waits 300 s for it and the stop takes 25-36 s: PERF.md section 6, PR 58), and
where the file's bytes lie.

    python3 scripts/read_profile.py 'bench_out/trace/*/plugins/profile/*/*.xplane.pb' [cell]

Two JSON lines: the family `dsa`'s readings, the slice's steps, admits and
device operations, the device's time by program and scope; then every line of
every plane over 100 KB with its events and their bytes.  Run it where the
profile lies (a chip call's machine: the file is 40-75 MB)."""

import glob
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.harness import manifest, program_trace  # noqa: E402


def readings(path: str, cell: str) -> dict:
    ctx = {"cell": manifest.load_cell(cell), "trace_path": path, "device": {"kind": "TPU v5 lite"}}
    events = program_trace.load(ctx)
    ops = program_trace._first_device(events)
    steps = [s[4] for s in program_trace.spans_named(events, "llm.step")
             if float(s[4].get("live", 0)) > 0 and "context_rows" in s[4]]
    times = program_trace.self_times(ops)
    dsa = manifest.load_reader("dsa")
    out = {"xplane": path, "bytes": os.path.getsize(path), "device_operations": len(ops), "steps": len(steps),
           "admits": len(program_trace.spans_named(events, "llm.admit")), "busy_ms": sum(t for t, _, _ in times) / 1e6,
           "by_program_ms": {kind: {scope or "all": round(t / 1e6, 3) for scope, t in by.items()}
                             for kind, by in dsa.__globals__["_by_program"](times).items()}}
    for what in ("sparse_core_hbm", "indexer_hbm", "step_hbm", "prefill_roofline"):
        out[what] = dsa(ctx, what=what)
    for scope in ("attn.indexer", "attn.select", "attn.sparse_core"):
        out["share " + scope] = program_trace.scope_percent(events, (scope,))
    if steps:
        out["selected_rows_share"] = 100.0 * sum(float(a["cache_rows_read"]) for a in steps) / sum(
            float(a["context_rows"]) for a in steps)
    return out


def lines(path: str) -> list:
    """[plane, line, events, their bytes] (xplane.proto: XSpace{1: planes}, XPlane{2: name, 3: lines,
    4: event_metadata}, XLine{2: name, 4: events})."""
    fields, out = program_trace._fields, []
    for field, plane in fields(open(path, "rb").read()):
        if field != 1:
            continue
        name, metadata = "", 0
        for key, value in fields(plane):
            if key == 2:
                name = value.decode()
            elif key == 4:
                metadata += len(value)
            elif key == 3:
                line, n, size = "", 0, 0
                for lkey, lvalue in fields(value):
                    if lkey == 2:
                        line = lvalue.decode()
                    elif lkey == 4:
                        n, size = n + 1, size + len(lvalue)
                out.append([name, line, n, size])
        out.append([name, "(event metadata)", 0, metadata])
    return sorted((l for l in out if l[3] > 100_000), key=lambda l: -l[3])


if __name__ == "__main__":
    found = sorted(glob.glob(sys.argv[1]))[-1]
    print(json.dumps(readings(found, sys.argv[2] if len(sys.argv) > 2 else "keye-longdoc-closed4")))
    print(json.dumps({"lines": lines(found)}))
