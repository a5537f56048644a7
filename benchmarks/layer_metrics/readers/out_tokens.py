"""Output tokens delivered to clients inside the window over its length.  In
an open loop below the knee this is the offered load; in a closed loop it is
what the system completes."""
from benchmarks.harness.serve_driver import tokens_in_window


def read(ctx):
    n = tokens_in_window(ctx)
    return n / ctx["seconds"] if n else None
