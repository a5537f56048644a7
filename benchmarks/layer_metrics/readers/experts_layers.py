"""The first device's time in a mixture's routed experts over the traced slice,
in a stack where only some layers hold experts: the operations under scope
`moe.experts` (a held share's loop over the experts that were given a row, one
product a matrix and turn) and the grouped-matmul kernels of the larger prefill
buckets, known by their instruction's name (the `KERNELS` of the
configuration's reference; they carry no scope).

  share_of="busy"          that time as a share of the device's busy time.
  share_of="hbm_roofline"  the bytes of the experts the slice's decode steps
      touched (argument `moe_experts_touched` of each `llm.step`, the mean over
      the EXPERT layers of the held experts given a row, times
      `expert_layers(config)` of the reference, the layers that hold experts,
      times its `expert_bytes`) over that time times the chip's peak HBM
      bytes/s (harness/peaks.json).  The experts' matmuls at a decode batch are
      bound by reading their weights, so 100% is the least time the chip could
      take.  The prefills of the slice's admits read experts too and their
      bytes are not counted, nor is a step that the slice's end cut, nor the
      columns of zeros an expert's first matrix is stored with: the share reads
      low by that much, never high.

`experts_kernel` counts `num_hidden_layers` layers, which is every layer of
the stacks it was written for (here that would count the bytes 52 / 23 times),
and reads nothing where the trace holds no grouped matmul.

Nothing where the trace holds no such operation (an older program, a dense
model), and for the roofline nothing where no step carries the count or the
reference does not say how many layers hold experts."""
from benchmarks.harness import manifest, program_trace, stats


def read(ctx, share_of="hbm_roofline"):
    events = program_trace.load(ctx)
    if not events or "cell" not in ctx:
        return None
    ref = manifest.reference_of(ctx["cell"])
    times = program_trace.device_self_times(events)
    experts_ns = sum(t for t, name, scope in times
                     if scope == "moe.experts" or program_trace.kernel_of(name, tuple(ref.KERNELS)))
    if not experts_ns:
        return None
    if share_of == "busy":
        return 100.0 * experts_ns / sum(t for t, _, _ in times)
    if share_of != "hbm_roofline":
        raise ValueError(f"share_of is 'busy' or 'hbm_roofline', not {share_of!r}")
    touched = [float(s[4]["moe_experts_touched"]) for s in program_trace.spans_named(events, "llm.step")
               if "moe_experts_touched" in s[4]]
    if not touched or not hasattr(ref, "expert_layers"):
        return None
    config = ctx["cell"]["config_file"]["config"]
    read_bytes = sum(touched) * ref.expert_layers(config) * ref.expert_bytes(config)
    return 100.0 * read_bytes / (experts_ns * 1e-9 * stats.peaks(ctx["device"]["kind"])["hbm_bytes_per_s"])
