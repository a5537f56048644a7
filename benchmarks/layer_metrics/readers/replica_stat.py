"""One of the batcher's own counts (`cb.stats`, as `bench_collect` hands them
over after the window): a constant of the deployment, such as the bytes a token
takes in the cache (`cache_bytes_per_token`, from the cache's own shapes), or a
total.  Nothing where the program keeps no such count (an older program)."""


def read(ctx, stat):
    value = ctx.get("replica", {}).get("stats", {}).get(stat)
    return None if value is None else float(value)
