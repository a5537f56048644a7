"""Mean of one argument of the program's spans of one name over the traced
slice (an `llm.admit` carries its request's `queue_wait_ms`)."""
from benchmarks.harness import program_trace


def read(ctx, span, arg):
    events = program_trace.load(ctx)
    return program_trace.span_arg_mean(events, span, arg) if events else None
