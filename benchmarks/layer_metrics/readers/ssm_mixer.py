"""The state-space mixers' share of their roofline over the traced slice: the
bytes the slice's decode steps had to move through them (each `llm.step` that
carries `ssm_state_bytes`, times `mixer_step_bytes` of the configuration's
reference: the mixers' weights once and every slot's recurrent state read and
written) over the first device's time in operations under the `ssm.*` scopes
(the reference's `SCOPES`) times the chip's peak HBM bytes/s
(harness/peaks.json).  A decode step's mixers are bound by reading their
weights and moving the state, so 100% is the least time the chip could take.

The time holds everything the bytes count: the projections (`ssm.in`,
`ssm.out`), the convolution and the recurrence (`ssm.conv`, `ssm.scan`) and the
state's way through the layer loop (`ssm.state`: a run's slice of the stacked
state, each layer's write back, the runs' joining, an admit's install).  It
also holds what the bytes leave out: the state's copies around the loop, the
prefills of the slice's admits, and a step that the slice's end cut.  So the
share reads low by that much, never high.

Nothing where the trace holds no operation under such a scope (an older
program, another architecture), where no step carries the count, or where the
reference counts no mixer."""
from benchmarks.harness import manifest, program_trace, stats


def read(ctx):
    events = program_trace.load(ctx)
    if not events or "cell" not in ctx:
        return None
    ref = manifest.reference_of(ctx["cell"])
    if not hasattr(ref, "mixer_step_bytes"):
        return None
    times = program_trace.device_self_times(events)
    mixer_ns = sum(t for t, _, scope in times if scope.startswith("ssm."))
    steps = [s for s in program_trace.spans_named(events, "llm.step") if "ssm_state_bytes" in s[4]]
    if not mixer_ns or not steps:
        return None
    cell = ctx["cell"]
    slots = cell["traffic_file"]["deployment"]["slots"]
    moved = len(steps) * ref.mixer_step_bytes(cell["config_file"]["config"], slots)
    peak = stats.peaks(ctx["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * moved / (mixer_ns * 1e-9 * peak)
