"""Wall time of one `ContinuousBatcher.step` less the admits inside it, for
steps that decoded, ms.  The step ends in `np.asarray`, so it is closed: it
holds the device's decode program and the host work around it."""
from benchmarks.harness.serve_driver import in_window
from benchmarks.harness.stats import percentile


def read(ctx, q):
    steps = ctx["replica"]["steps"]
    ms = [
        1e3 * (cur[1] - cur[2]) for prev, cur in zip(steps, steps[1:])
        if in_window(ctx, cur[0]) and cur[5] > prev[5]
    ]
    return percentile(ms, q) if ms else None
