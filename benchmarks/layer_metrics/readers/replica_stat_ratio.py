"""One of the batcher's own totals over another (`cb.stats`, as `bench_collect`
hands them over after the window: every admit and step of the replica's life,
the warm-up's and the check's among them), `scale` times it.  A count that the
traced slice's few seconds may not hold one event of (an admit of a closed
loop of long answers) is read here and not from the slice's spans.  Nothing
where the program keeps no such totals (an older program) or the second is 0."""


def read(ctx, over, under, scale=1.0):
    stats = ctx.get("replica", {}).get("stats", {})
    if stats.get(over) is None or not stats.get(under):
        return None
    return scale * float(stats[over]) / float(stats[under])
