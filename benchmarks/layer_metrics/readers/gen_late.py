"""How late the load generator sent requests: send time less due time, ms."""
from benchmarks.harness.serve_driver import window_records
from benchmarks.harness.stats import percentile


def read(ctx, q):
    late = [1e3 * (r["send"] - r["due"]) for r in window_records(ctx) if "send" in r]
    return percentile(late, q) if late else None
