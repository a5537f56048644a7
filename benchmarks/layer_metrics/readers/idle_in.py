"""Share of the traced slice in which the device ran nothing while the pump's
thread was in one `part` of its loop (program_trace.idle_by_span: admit,
readback, step_host, between_steps).  The four parts add up to the slice's
idle share."""
from benchmarks.harness import program_trace


def read(ctx, part):
    events = program_trace.load(ctx)
    parts = program_trace.idle_by_span(events) if events else None
    return parts[part] if parts else None
