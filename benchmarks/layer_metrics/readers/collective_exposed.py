"""Share of the traced slice the first device's core spent inside collective
operations (not hidden behind compute)."""
from benchmarks.harness import trace_reduce


def read(ctx):
    return trace_reduce.collective_percent(ctx["trace"]) if ctx.get("trace") else None
