"""Tokens a decode step yields on average: tokens the batcher's steps handed
out inside the window, less the one each admit samples itself, over the decode
steps counted by `ContinuousBatcher.stats`."""
from benchmarks.harness.serve_driver import in_window


def read(ctx):
    steps = ctx["replica"]["steps"]
    tokens = decodes = 0
    for prev, cur in zip(steps, steps[1:]):
        if in_window(ctx, cur[0]):
            tokens += cur[3] - (cur[4] - prev[4])
            decodes += cur[5] - prev[5]
    return tokens / decodes if decodes else None
