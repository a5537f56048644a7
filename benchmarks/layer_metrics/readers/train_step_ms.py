"""Host-clock time of one optimizer step inside the window, closed by reading
the loss back, ms."""
from benchmarks.harness.stats import percentile


def read(ctx, q):
    ms = [1e3 * s[1] for s in ctx["steps"]]
    return percentile(ms, q) if ms else None
