"""Share of the first device's busy time in operations the program ran under
the given `jax.named_scope`s (a name ending in "." takes every scope that
starts with it), forward, recomputation and backward together; collectives are
counted apart (collective_exposed).  Nothing where no operation names a scope."""
from benchmarks.harness import program_trace


def read(ctx, scopes):
    events = program_trace.load(ctx)
    return program_trace.scope_percent(events, scopes) if events else None
