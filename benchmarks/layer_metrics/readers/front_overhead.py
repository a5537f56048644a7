"""What proxy, router and transport add to a request's first token: the
client's send-to-first-token time less the replica's own submit-to-first-token
time for the same request (matched by the `bench_id` the body carries), ms."""
from benchmarks.harness.serve_driver import window_records
from benchmarks.harness.stats import percentile


def read(ctx, q):
    first = ctx["replica"]["first"]
    over = []
    for r in window_records(ctx):
        stamp = first.get(r["bench_id"])
        if stamp and r["token_times"]:
            over.append(1e3 * ((r["token_times"][0] - r["send"]) - (stamp[1] - stamp[0])))
    return percentile(over, q) if over else None
