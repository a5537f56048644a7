"""Time between two decode steps on the pump's thread at a percentile, ms:
end of one `llm.step` to the start of the next (token delivery, the lock
handed over, the metrics sync), gaps in which a caller submitted left out."""
from benchmarks.harness import program_trace


def read(ctx, q):
    events = program_trace.load(ctx)
    return program_trace.between_steps_ms(events, q) if events else None
