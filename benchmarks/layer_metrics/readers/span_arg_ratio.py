"""One argument of the program's spans of one name summed over the traced
slice, over the sum of another (`scale` times it): row-passes a token handed
out, from an `llm.step`'s `live` and `tokens_out`.  `per_config` names a key of
the cell's configuration that `scale` is taken over first (100 over the experts
a token chooses: a share of a token's assignments, in a cell of any top-k).
Nothing where no span carries both, or the second sums to nothing."""
from benchmarks.harness import program_trace


def read(ctx, span, over, under, scale=1.0, per_config=None):
    events = program_trace.load(ctx)
    if not events:
        return None
    if per_config is not None:
        scale = scale / ctx["cell"]["config_file"]["config"][per_config]
    both = [s[4] for s in program_trace.spans_named(events, span) if over in s[4] and under in s[4]]
    below = sum(float(a[under]) for a in both)
    return scale * sum(float(a[over]) for a in both) / below if below else None
