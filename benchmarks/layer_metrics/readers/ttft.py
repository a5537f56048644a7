"""Client-side time to first token at a percentile, s."""
from benchmarks.harness.serve_driver import ttfts
from benchmarks.harness.stats import percentile


def read(ctx, q):
    values = ttfts(ctx)
    return percentile(values, q) if values else None
