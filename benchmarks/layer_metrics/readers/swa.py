"""The banded prefill kernel of a window layer on the first device over the
traced slice, as a share of its roofline.

What the slice's admits had to do in it: for each `llm.admit` span of the slice
its OWN prompt length (argument `prompt_len`, not its bucket's) through
`swa_flash_flops` of the configuration's reference, the (query, key) pairs the
band holds over the window layers, 2 operations a multiply-add for the scores
and for the weighted values; over the time in the kernel (known by its
instruction's name: the reference's `WINDOW_KERNELS`) times the chip's peak
bf16 FLOP/s (harness/peaks.json).  The band's pairs are few and each key block
is fetched once a head, so the kernel is bound by its arithmetic, and 100% is
the least time the chip could take.  The kernel multiplies in float32 and
computes whole key blocks, those a bucket's pads lie in too, so it reads low;
an admit that the slice's edge cut has its kernel's time in the slice and no
span, which reads lower still, never higher.

Nothing where the trace holds no such kernel (an older program, another
architecture), where the reference counts no band, or where the slice holds no
admit."""
from benchmarks.harness import manifest, program_trace, stats


def read(ctx, what):
    if what != "flash_roofline":
        raise ValueError(f"what is 'flash_roofline', not {what!r}")
    events = program_trace.load(ctx)
    if not events or "cell" not in ctx:
        return None
    ref = manifest.reference_of(ctx["cell"])
    if not hasattr(ref, "swa_flash_flops"):
        return None
    times = program_trace.device_self_times(events)
    kernel_ns = sum(t for t, name, _ in times if program_trace.kernel_of(name, tuple(ref.WINDOW_KERNELS)))
    lengths = [int(s[4]["prompt_len"]) for s in program_trace.spans_named(events, "llm.admit") if "prompt_len" in s[4]]
    if not kernel_ns or not lengths:
        return None
    config = ctx["cell"]["config_file"]["config"]
    flops = sum(ref.swa_flash_flops(config, n) for n in lengths)
    return 100.0 * flops / (kernel_ns * 1e-9 * stats.peaks(ctx["device"]["kind"])["bf16_flops"])
