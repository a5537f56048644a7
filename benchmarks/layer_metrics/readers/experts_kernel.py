"""The first device's time in a mixture's routed experts over the traced
slice: the grouped-matmul kernels, known by their instruction's name (the
`KERNELS` of the configuration's reference: the TPU compiler's own
`ragged-dot-none`, which carries no op_name and therefore no scope), and the
operations under scope `moe.experts` (the activation between them).

  share_of="busy"          that time as a share of the device's busy time.
  share_of="hbm_roofline"  the bytes of the experts the slice's decode steps
      touched (argument `moe_experts_touched` of each `llm.step`, the mean over
      the layers, times the layers, times `expert_bytes` of the reference) over
      that time times the chip's peak HBM bytes/s (harness/peaks.json).  The
      experts' matmuls at a decode batch are bound by reading their weights, so
      100% is the least time the chip could take.  The prefills of the slice's
      admits run the same kernels and their bytes are not counted, nor is a
      step that the slice's end cut: the share reads low by that much, never
      high.

Nothing where the trace holds no such kernel (an older program, a dense model),
and for the roofline nothing where no step carries the count."""
from benchmarks.harness import manifest, program_trace, stats


def read(ctx, share_of):
    events = program_trace.load(ctx)
    if not events or "cell" not in ctx:
        return None
    ref = manifest.reference_of(ctx["cell"])
    times = program_trace.device_self_times(events)
    kernels = sum(t for t, name, _ in times if program_trace.kernel_of(name, tuple(ref.KERNELS)))
    if not kernels:
        return None
    experts_ns = kernels + sum(t for t, _, scope in times if scope == "moe.experts")
    if share_of == "busy":
        return 100.0 * experts_ns / sum(t for t, _, _ in times)
    if share_of != "hbm_roofline":
        raise ValueError(f"share_of is 'busy' or 'hbm_roofline', not {share_of!r}")
    touched = [float(s[4]["moe_experts_touched"]) for s in program_trace.spans_named(events, "llm.step")
               if "moe_experts_touched" in s[4]]
    if not touched:
        return None
    config = ctx["cell"]["config_file"]["config"]
    read_bytes = sum(touched) * config["num_hidden_layers"] * ref.expert_bytes(config)
    peak = stats.peaks(ctx["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * read_bytes / (experts_ns * 1e-9 * peak)
