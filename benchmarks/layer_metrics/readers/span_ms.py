"""Duration of the program's spans of one name (`tracing.span`, written into
the profiler's trace) over the traced slice, ms: at percentile `q`, or their
mean without one.  Nothing where the program has no such span."""
from benchmarks.harness import program_trace


def read(ctx, span, q=None):
    events = program_trace.load(ctx)
    return program_trace.span_ms(events, span, q) if events else None
