"""A block-generating model's passes against the chip's memory: the bytes the
traced slice's passes had to read (each `llm.step` that carries `block_rows` is
one pass of its live slots' blocks: `decode_step_bytes` of the configuration's
reference at the deployment's slots and cache length, the experts those the
step itself counted, `moe_experts_touched`) over the first device's busy time times the chip's
peak HBM bytes/s (harness/peaks.json).  A pass at a decode batch is bound by
reading weights and cache, so 100% is the least time the chip could take.

The busy time holds what the bytes leave out: the prefills of the slice's
admits, the installs, and a step that the slice's end cut.  So the share reads
low by that much, never high.

Nothing where no step carries `block_rows` (an older program, a model of one
causal token a step)."""
from benchmarks.harness import manifest, program_trace, stats


def read(ctx):
    events = program_trace.load(ctx)
    if not events or "cell" not in ctx:
        return None
    steps = [s[4] for s in program_trace.spans_named(events, "llm.step") if "block_rows" in s[4]]
    busy_ns = sum(t for t, _, _ in program_trace.device_self_times(events))
    if not steps or not busy_ns:
        return None
    cell, ref = ctx["cell"], manifest.reference_of(ctx["cell"])
    dep = cell["traffic_file"]["deployment"]
    t_max = dep["max_prompt_len"] + dep["max_new_tokens"]
    read_bytes = sum(ref.decode_step_bytes(cell["config_file"]["config"], dep["slots"], t_max,
                                           touched=float(a.get("moe_experts_touched", 0.0))) for a in steps)
    peak = stats.peaks(ctx["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * read_bytes / (busy_ns * 1e-9 * peak)
