"""One argument of the program's spans of one name summed over the traced
slice, over the sum of another (`scale` times it): row-passes a token handed
out, from an `llm.step`'s `live` and `tokens_out`.  Nothing where no span
carries both, or the second sums to nothing."""
from benchmarks.harness import program_trace


def read(ctx, span, over, under, scale=1.0):
    events = program_trace.load(ctx)
    if not events:
        return None
    both = [s[4] for s in program_trace.spans_named(events, span) if over in s[4] and under in s[4]]
    below = sum(float(a[under]) for a in both)
    return scale * sum(float(a[over]) for a in both) / below if below else None
