"""Seconds from the benchmark command's first line to the entry of the
replica's constructor: the cluster, the controller and the proxy, the worker's
process and what it imports.  The replica stamps `replica_init_mono`
(`time.monotonic()`, one clock for every process of the host) among its counts;
the command's first line is the window's opening less `setup_s`.  Nothing where
the program keeps no such stamp (an older program)."""


def read(ctx):
    entered = ctx.get("replica", {}).get("stats", {}).get("replica_init_mono")
    if entered is None:
        return None
    return float(entered) - (ctx["t_open"] - ctx["setup_s"])
