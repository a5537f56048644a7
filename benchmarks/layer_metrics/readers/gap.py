"""Gap between successive tokens of one stream at a percentile, over every gap
that ended inside the window, s."""
from benchmarks.harness.serve_driver import token_gaps
from benchmarks.harness.stats import percentile


def read(ctx, q):
    gaps = token_gaps(ctx)
    return percentile(gaps, q) if gaps else None
