"""Programs compiled (or fetched from the persistent cache) by the process
that holds the chip while the window was open.  Has to be 0."""
from benchmarks.harness.serve_driver import in_window


def read(ctx):
    return sum(in_window(ctx, t) for t, _ in ctx["replica"]["compiles"])
