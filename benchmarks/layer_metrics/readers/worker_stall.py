"""The longest the TPU worker's threads or its IO loop (which sends its
heartbeats) went unserved in the attempt that ran to its end, any phase
(`harness/stallwatch.py`).  Past the head's heartbeat limit a stall costs the
job its worker group."""


def read(ctx):
    stalls = ctx.get("stalls")
    return None if stalls is None else stalls["max_s"]
