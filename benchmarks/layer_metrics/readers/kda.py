"""Kimi Delta Attention's two forms on the first device over the traced slice, and
what its state weighs beside the latent cache.

  what="state_hbm"         the decode update's share of its roofline: the bytes the
      slice's steps had to move through the delta rule (each `llm.step`'s `live`
      rows, each row's matrix state read once and written once over the KDA
      layers: `kda_update_bytes` of the configuration's reference) over the time
      in operations under `kda.step` (the update) and `ssm.state` (the state's way
      through the layer loop: a layer's slice of the stacked state and its write
      back), times the chip's peak HBM bytes/s (harness/peaks.json).  The update is
      a few operations an element of the state, so it is bound by moving the
      state, and 100% is the least time the chip could take.  The time also holds
      what the bytes leave out: the state of the slots that hold no request, which
      the program moves with the others', an admit's install, a step that the
      slice's end cut.  So the share reads low by that much, never high.
  what="prefill_roofline"  the chunked form's share of the matrix peak: the
      operations of the prompts the slice's admits prefilled (`llm.admit`'s
      `prompt_len`; `kda_prefill_flops` of the reference, which counts the
      prompt's own positions and not its bucket's padding) over the time under
      `kda.chunk` times the chip's peak bf16 FLOP/s.  The products are float32 at
      `Precision.HIGHEST` (six bf16 passes each), the decayed Gram matrices'
      diagonal sub-blocks are elementwise, and the chunks' state is handed on in
      sequence: the share says how far from the matrix unit's peak that leaves the
      form.  0 where the slice holds decode steps and no prefill (a closed loop of
      long answers admits a request every two seconds or so, and a slice is four).
  what="state_vs_cache"    one slot's recurrent state (`state_bytes_per_slot` of the
      batcher's counts, from the cache's own shapes) over what the latent cache
      holds of a request at the window's mean context (`cache_bytes_per_token`
      times the context a served token saw on average: its prompt and half its
      answer, over the window's requests weighted by their answers' lengths).

Nothing where the reference counts no KDA layer (another architecture), where the
trace holds no operation under `kda.step` (an older program), or, for the ratio,
where the program keeps no such counts."""
from benchmarks.harness import manifest, program_trace, stats


def read(ctx, what):
    if "cell" not in ctx:
        return None
    ref = manifest.reference_of(ctx["cell"])
    if not hasattr(ref, "kda_update_bytes"):
        return None
    config = ctx["cell"]["config_file"]["config"]
    if what == "state_vs_cache":
        counts = ctx.get("replica", {}).get("stats", {})
        done = [r for r in ctx.get("records", []) if r.get("error") is None and r.get("n_out")]
        if not counts.get("state_bytes_per_slot") or not counts.get("cache_bytes_per_token") or not done:
            return None
        context = sum(r["n_out"] * (r["n_prompt"] + r["n_out"] / 2) for r in done) / sum(r["n_out"] for r in done)
        return float(counts["state_bytes_per_slot"]) / (float(counts["cache_bytes_per_token"]) * context)
    events = program_trace.load(ctx)
    if not events:
        return None
    times = program_trace.device_self_times(events)
    under = lambda scopes: sum(t for t, _, scope in times if scope in scopes)
    step_ns = under(("kda.step", "ssm.state"))
    if not under(("kda.step",)):
        return None
    peaks = stats.peaks(ctx["device"]["kind"])
    if what == "state_hbm":
        live = sum(float(s[4].get("live", 0)) for s in program_trace.spans_named(events, "llm.step"))
        return 100.0 * ref.kda_update_bytes(config, 1) * live / (step_ns * 1e-9 * peaks["hbm_bytes_per_s"]) if live else None
    if what != "prefill_roofline":
        raise ValueError(f"what is 'state_hbm', 'prefill_roofline' or 'state_vs_cache', not {what!r}")
    chunk_ns = under(("kda.chunk",))
    lengths = [int(s[4]["prompt_len"]) for s in program_trace.spans_named(events, "llm.admit") if "prompt_len" in s[4]]
    if not chunk_ns or not lengths:
        return 0.0
    return 100.0 * sum(ref.kda_prefill_flops(config, n) for n in lengths) / (chunk_ns * 1e-9 * peaks["bf16_flops"])
