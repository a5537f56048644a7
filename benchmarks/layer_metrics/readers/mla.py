"""Latent attention's two paths on the first device over the traced slice.

  what="expand_share"   a prefill's way: the operations under scope
      `attn.mla.expand` (every head's keys and values made of the prompt's
      latents) and the flash kernel that attends over them (`flash_fwd`, known
      by its instruction's name), as a share of the device's busy time.
  what="core_roofline"  a decode step's way: what the slice's steps had to
      move and compute in the latent core (each `llm.step` that read a step
      with live rows: `mla_core_bytes` and `mla_core_flops` of the
      configuration's reference at the deployment's slots and cache length)
      against the time in operations under `attn.core` and `attn.cache`, the
      flash kernel left out: the larger of bytes over the chip's peak HBM
      bytes/s and operations over its peak bf16 FLOP/s (harness/peaks.json),
      over that time.  The core reads every slot's latent rows whatever the
      rows' depths, so at a decode batch it is bound by that read, and 100% is
      the least time the chip could take.  The time also holds what the counts
      leave out: a prefill's own writes under `attn.cache`, the transposes
      around its flash kernel, an admit's install, a step that the slice's end
      cut.  So the share reads low by that much, never high.

Nothing where the trace holds no operation under `attn.mla.expand` or
`attn.mla.absorb` (an older program, another architecture), where the
reference counts no latent core, or, for the roofline, where no step read any
live row."""
from benchmarks.harness import manifest, program_trace, stats

FLASH = ("flash_fwd",)


def read(ctx, what):
    events = program_trace.load(ctx)
    if not events or "cell" not in ctx:
        return None
    ref = manifest.reference_of(ctx["cell"])
    if not hasattr(ref, "mla_core_bytes"):
        return None
    times = program_trace.device_self_times(events)
    if not any(scope in ("attn.mla.expand", "attn.mla.absorb") for _, _, scope in times):
        return None
    if what == "expand_share":
        expand_ns = sum(t for t, name, scope in times
                        if scope == "attn.mla.expand" or program_trace.kernel_of(name, FLASH))
        return 100.0 * expand_ns / sum(t for t, _, _ in times)
    if what != "core_roofline":
        raise ValueError(f"what is 'expand_share' or 'core_roofline', not {what!r}")
    core_ns = sum(t for t, name, scope in times
                  if scope in ("attn.core", "attn.cache") and not program_trace.kernel_of(name, FLASH))
    steps = sum(1 for s in program_trace.spans_named(events, "llm.step") if float(s[4].get("live", 0)) > 0)
    if not core_ns or not steps:
        return None
    cell = ctx["cell"]
    dep, config = cell["traffic_file"]["deployment"], cell["config_file"]["config"]
    t_max = dep["max_prompt_len"] + dep["max_new_tokens"]
    peaks = stats.peaks(ctx["device"]["kind"])
    least_s = steps * max(ref.mla_core_bytes(config, dep["slots"], t_max) / peaks["hbm_bytes_per_s"],
                          ref.mla_core_flops(config, dep["slots"], t_max) / peaks["bf16_flops"])
    return 100.0 * least_s / (core_ns * 1e-9)
