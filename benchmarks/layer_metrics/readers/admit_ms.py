"""Wall time inside `ContinuousBatcher._admit` for each request it admitted
inside the window, ms: prefill or prefix-cache copy, suffix steps, install,
first sample.  Every live stream waits this long."""
from benchmarks.harness.serve_driver import in_window


def read(ctx):
    admits = [a for a in ctx["replica"]["admits"] if in_window(ctx, a[0])]
    n = sum(a[2] for a in admits)
    return 1e3 * sum(a[1] for a in admits) / n if n else None
