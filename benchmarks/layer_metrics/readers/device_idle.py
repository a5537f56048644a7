"""Share of the traced slice in which no operation ran on the device."""
from benchmarks.harness import trace_reduce


def read(ctx):
    return trace_reduce.idle_percent(ctx["trace"]) if ctx.get("trace") else None
