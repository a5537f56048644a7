"""Learned sparse attention on the first device over the traced slice, each
number the work the mathematics requires, at the slice's own live rows and
contexts and prompts, over the time the program took for it times the chip's
peak (harness/peaks.json).  The counts are the reference's
(references/keye_dsa.py) and never of `slots x t_max` nor of what the program
happens to read: a core that read the whole context under a mask reads low
here, and a kernel that reads exactly the selection cannot read over 100%.

Every program writes the same three scopes (`attn.indexer`, `attn.select`,
`attn.sparse_core`); which work an operation is, a decode step's or an admit's,
is told by the PROGRAM it ran in: the device's operations from one `embed`
operation (a program's first) to the next are one program, an admit's where one
of its kernels (`dsa_index`, `dsa_select`, `dsa_flash`) lies among them, a
decode step's where an operation under `attn.sparse_core` does and no such
kernel.  Each `llm.step` span that read a step says what that step held:
`live`, `context_rows` (its live rows' contexts, summed), `cache_rows_read`
(their selected positions: min(context, topk) a row), `moe_experts_touched`.

  what="sparse_core_hbm"   the selected positions' keys and values, 2 x KV x D x
      2 B each and layer, over the decode steps' time under `attn.sparse_core` x
      peak HBM bytes/s.
  what="indexer_hbm"       the contexts' indexer keys, DI x 2 B a position and
      layer, and a layer's indexer weights once a step, over the decode steps'
      time under `attn.indexer` and `attn.select` x peak HBM bytes/s.
  what="step_hbm"          the whole step: `decode_step_bytes` at the step's own
      live rows, contexts and experts touched, over the time of the slice's
      decode steps' programs x peak HBM bytes/s.  A step's rows are given their
      mean context, all alike, and the step's own count of selected positions.
  what="prefill_roofline"  for each `llm.admit` span its OWN prompt length (not
      its bucket's) through `dsa_prefill_flops`: 2 x HI x DI a scored causal
      pair, 4 x H x D a selected pair; over the admits' time under the three
      scopes x peak bf16 FLOP/s.  The kernels compute whole tiles (those of
      pads alone are left out), and every causal pair's attention scores before
      the mask drops the unselected, so it reads low, never high.

Each reads low by what its time holds beside its count (a step the slice's end
cut, an admit without its span).  Nothing where the trace holds no operation
under these scopes (an older program, another architecture) or the slice no
step or admit to count."""

from benchmarks.harness import manifest, program_trace, stats

SCOPES = ("attn.indexer", "attn.select", "attn.sparse_core")
ADMIT_KERNELS = ("dsa_index", "dsa_select", "dsa_flash")


def _by_program(times):
    """{"step" | "admit": {scope: ns}} of `program_trace.self_times` (in the order the device ran them): the time by
    scope ("" for a program's whole) of the decode steps' programs and of the admits' (module docstring); a program
    that is neither (an install, a first token alone) is left out."""
    total = {"step": {}, "admit": {}}
    segment, core, admit = {}, False, False

    def close():
        kind = "admit" if admit else "step" if core else None
        for scope, t in segment.items() if kind else ():
            total[kind][scope] = total[kind].get(scope, 0.0) + t

    for t, name, scope in times:
        if scope == "embed" and segment:
            close()
            segment, core, admit = {}, False, False
        segment[""] = segment.get("", 0.0) + t
        if scope in SCOPES:
            segment[scope] = segment.get(scope, 0.0) + t
        core = core or scope == "attn.sparse_core"
        admit = admit or bool(program_trace.kernel_of(name, ADMIT_KERNELS))
    close()
    return total


def read(ctx, what):
    events = program_trace.load(ctx)
    if not events or "cell" not in ctx:
        return None
    ref = manifest.reference_of(ctx["cell"])
    if not hasattr(ref, "dsa_prefill_flops"):
        return None
    by = _by_program(program_trace.device_self_times(events))
    under = lambda kind, scopes: sum(by[kind].get(scope, 0.0) for scope in scopes)
    config = ctx["cell"]["config_file"]["config"]
    layers = config["num_hidden_layers"]
    peaks = stats.peaks(ctx["device"]["kind"])
    steps = [s[4] for s in program_trace.spans_named(events, "llm.step")
             if float(s[4].get("live", 0)) > 0 and "context_rows" in s[4]]
    if what == "prefill_roofline":
        lengths = [int(s[4]["prompt_len"]) for s in program_trace.spans_named(events, "llm.admit") if "prompt_len" in s[4]]
        ns = under("admit", SCOPES)
        if not lengths or not ns:
            return None
        return 100.0 * sum(ref.dsa_prefill_flops(config, n) for n in lengths) / (ns * 1e-9 * peaks["bf16_flops"])
    if not steps:
        return None
    if what == "sparse_core_hbm":
        ns = under("step", ("attn.sparse_core",))
        least = layers * ref.selected_row_bytes(config) * sum(float(a["cache_rows_read"]) for a in steps)
    elif what == "indexer_hbm":
        ns = under("step", ("attn.indexer", "attn.select"))
        least = layers * (ref.index_key_bytes(config) * sum(float(a["context_rows"]) for a in steps)
                          + 2 * ref.indexer_params(config) * len(steps))
    elif what == "step_hbm":
        ns = under("step", ("",))
        dep = ctx["cell"]["traffic_file"]["deployment"]
        least = sum(
            ref.decode_step_bytes(config, dep["slots"], dep["max_prompt_len"] + dep["max_new_tokens"],
                                  contexts=[float(a["context_rows"]) / float(a["live"])] * int(float(a["live"])),
                                  selected=float(a["cache_rows_read"]),
                                  touched=float(a["moe_experts_touched"]) if "moe_experts_touched" in a else None)
            for a in steps)
    else:
        raise ValueError(f"what is 'sparse_core_hbm', 'indexer_hbm', 'step_hbm' or 'prefill_roofline', not {what!r}")
    return 100.0 * least / (ns * 1e-9 * peaks["hbm_bytes_per_s"]) if ns else None
