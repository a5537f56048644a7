"""The decode kernel on the one stack of keys and values that several layers
share (the full layer that writes it and the cross layers that read it), on
the first device over the traced slice, as a share of its roofline.

What the slice's decode steps fetched of that stack: each `llm.step` span that
carries `shared_rows_read` through `shared_cache_step_bytes` of the
configuration's reference (the stack's readers x the key blocks' slots the live
rows' contexts take x a token's keys and values in one layer); over the time in
operations under the scopes `attn.core.full` and `attn.core.cross` times the
chip's peak HBM bytes/s (harness/peaks.json).  A decode step's attention is
bound by that read, so 100% is the least time the chip could take.  The time
also holds what the bytes leave out: the queries and results, a grid step's
fixed cost, an admit's one query against its prompt's keys (a prefill's tail
attends under the same scopes) and a step that the slice's end cut.  So the
share reads low by that much, never high.

Nothing where the trace holds no operation under those scopes (an older
program, another architecture), where no step carries the count, or where the
reference counts no shared stack."""
from benchmarks.harness import manifest, program_trace, stats

SCOPES = ("attn.core.full", "attn.core.cross")


def read(ctx):
    events = program_trace.load(ctx)
    if not events or "cell" not in ctx:
        return None
    ref = manifest.reference_of(ctx["cell"])
    if not hasattr(ref, "shared_cache_step_bytes"):
        return None
    times = program_trace.device_self_times(events)
    core_ns = sum(t for t, _, scope in times if scope in SCOPES)
    read_rows = [float(s[4]["shared_rows_read"]) for s in program_trace.spans_named(events, "llm.step")
                 if "shared_rows_read" in s[4]]
    if not core_ns or not read_rows:
        return None
    config = ctx["cell"]["config_file"]["config"]
    moved = sum(ref.shared_cache_step_bytes(config, rows) for rows in read_rows)
    return 100.0 * moved / (core_ns * 1e-9 * stats.peaks(ctx["device"]["kind"])["hbm_bytes_per_s"])
