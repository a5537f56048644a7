"""Share of the first device's busy time inside the program's named Pallas
kernels (ops/attention.py: flash_fwd, flash_bwd_dq, flash_bwd_dkv)."""
from benchmarks.harness import program_trace


def read(ctx):
    events = program_trace.load(ctx)
    return program_trace.kernel_percent(events) if events else None
