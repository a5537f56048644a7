"""The readers of the program's own spans and scopes (harness/program_trace.py)
on a trace recorded from a chip run, on one written by hand whose answers are
worked out here, and on files the profiler itself writes.  No cluster, no chip."""

import copy
import glob
import json
import os

import pytest

from benchmarks.harness import cluster, manifest, program_trace, trace_reduce

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
DOC = manifest.load_manifest()
TRACE_READERS = ("span_ms", "span_arg", "pump_between", "idle_in", "scope_share", "kernel_share")
NEW = [  # the per-layer metrics that read what program_trace gives
    m for cell in ("chat-steady", "train-fsdp4") for m in manifest.layer_metrics_for(cell)
    if m["reader"] in TRACE_READERS
]
# the same readers under the names of the cells that came later: a cell's own (`cells`)
# and, since PR 38, a family's, which several cells read and which counts once
LATER = list({
    m["name"]: m for w in DOC["workloads"] if w["name"] not in ("chat-steady", "train-fsdp4")
    for m in manifest.layer_metrics_for(w["name"]) if m["reader"] in TRACE_READERS and ("cells" in m or "family" in m)
}.values())


def recorded():
    """The first quarter second of PR 24's traced chat-steady run on the chip
    (program_trace.head of its extraction, names cut at 32 characters)."""
    with open(os.path.join(DATA, "trace_program_small.json")) as f:
        return json.load(f)


def as_trace_reduce(events):
    return {"devices": {k: [e[:3] for e in v] for k, v in events["ops"].items()}, "host": []}


def by_hand():
    """1,000 ns of one device and two threads.  Busy [0,100] [200,650]
    [800,1000]: 750; idle [100,200] [650,800]: 250.  The `while` holds two
    operations and 100 ns of its own.  Thread 1 is the pump: a step with its
    parts, then a step that admits, then a third; thread 2 is a caller
    waiting for the lock through all of it."""
    op = lambda start, dur, name, scope: [float(start), float(dur), name, scope]
    span = lambda thread, start, dur, name, **args: [thread, float(start), float(dur), name, args]
    return {
        "ops": {"/device:TPU:0": [
            op(0, 100, "%fusion.1 = bf16[8] fusion(...)", "ffn"),
            op(200, 400, "%while.5 = (...) while(...)", ""),
            op(200, 100, "%fusion.2 = f32[8] fusion(...)", "attn.core"),
            op(300, 200, "%broadcast.9 = f32[8] broadcast(...)", "attn.core"),
            op(600, 50, "%flash_fwd.3 = (bf16[8]) custom-call(%fusion.2)", "attn.core"),
            op(800, 200, "%all-gather.1 = f32[8] all-gather(%flash_fwd.3)", "attn.qkv"),
        ]},
        "spans": [
            span(2, 0, 1000, "llm.submit"), span(2, 0, 995, "llm.submit.lock_wait"),
            span(1, 50, 650, "llm.step", live=3),
            span(1, 50, 100, "llm.step.upload"), span(1, 150, 30, "llm.step.dispatch"),
            span(1, 180, 500, "llm.step.readback"),
            span(1, 720, 270, "llm.step", live=4),
            span(1, 730, 60, "llm.admit", rid=7, queue_wait_ms=2.5),
            span(1, 740, 40, "llm.admit.prefill"),
            span(1, 1000, 100, "llm.step", live=4),
        ],
    }


# -- the trace written by hand ------------------------------------------------


def test_idle_goes_to_what_the_pump_was_inside_and_adds_up():
    events = by_hand()
    parts = program_trace.idle_by_span(events)
    # [100,150] upload, [150,180] dispatch, [680,700] the step's own time,
    # [720,730] and [790,800] the second step's: 120; [180,200] and [650,680]
    # readback: 50; [730,790] admit: 60; [700,720] no step open: 20
    assert parts == pytest.approx({"step_host": 12.0, "readback": 5.0, "admit": 6.0, "between_steps": 2.0})
    assert sum(parts.values()) == pytest.approx(trace_reduce.idle_percent(as_trace_reduce(events)))
    # the caller's thread was inside llm.submit.lock_wait through every idle
    # instant and claims none of it: without the pump's spans nothing is read
    events["spans"] = [s for s in events["spans"] if s[0] == 2]
    assert program_trace.idle_by_span(events) is None


def test_a_step_cut_by_the_slices_end_keeps_the_parts_that_closed():
    events = by_hand()
    # the profiler stopped inside the second step: the step never closed, its admit had
    events["spans"] = [s for s in events["spans"] if not (s[3] == "llm.step" and s[1] >= 720)]
    parts = program_trace.idle_by_span(events)
    assert parts["admit"] == pytest.approx(6.0) and parts["readback"] == pytest.approx(5.0)
    assert parts["step_host"] == pytest.approx(10.0) and parts["between_steps"] == pytest.approx(4.0)


def test_a_parent_operation_is_not_counted_beside_its_body():
    events = by_hand()
    by_scope = program_trace.time_by_scope(events)
    assert by_scope == {"ffn": 100.0, "": 100.0, "attn.core": 350.0, "collective": 200.0}
    assert sum(by_scope.values()) == 750.0  # the device's busy time, the while's 400 once
    assert program_trace.scope_percent(events, ["attn.core"]) == pytest.approx(100 * 350 / 750)
    # "attn." takes every attention scope; the all-gather asked for by attn.qkv is a collective
    assert program_trace.scope_percent(events, ["attn."]) == pytest.approx(100 * 350 / 750)
    assert program_trace.scope_percent(events, ["ffn", "loss"]) == pytest.approx(100 * 100 / 750)
    assert program_trace.kernel_percent(events) == pytest.approx(100 * 50 / 750)


def test_the_walks_of_a_trace_are_made_once_and_read_what_they_read(monkeypatch):
    """A cell's readers ask for the device's self times, the time by scope and
    the idle share by span a dozen times over: each is worked out once a trace,
    kept beside it, and is what working it out anew gives."""
    for events in (by_hand(), recorded()):
        plain = program_trace.self_times(program_trace._first_device(events))
        by_scope, idle = program_trace._time_by_scope(copy.deepcopy(events)), program_trace._idle_by_span(events)
        walks, inner = [], program_trace.self_times
        monkeypatch.setattr(program_trace, "self_times", lambda evs: walks.append(len(evs)) or inner(evs))
        for _ in range(3):
            assert program_trace.device_self_times(events) == plain
            assert program_trace.time_by_scope(events) == by_scope and program_trace.idle_by_span(events) == idle
            assert program_trace.kernel_percent(events) == (
                100.0 * sum(t for t, n, _ in plain if program_trace.kernel_of(n)) / sum(t for t, _, _ in plain)
                if any(program_trace.kernel_of(n) for _, n, _ in plain) else None)
        assert walks == [len(plain)]
        monkeypatch.setattr(program_trace, "self_times", inner)
        # the recorded form of a trace leaves what was kept behind, and a copy given other operations is walked again
        assert set(program_trace.head(events, 0.1)) == {"spans", "ops"}
        other = copy.deepcopy(events)
        other["ops"] = {k: v[:1] for k, v in other["ops"].items()}
        assert program_trace.device_self_times(other) == inner(program_trace._first_device(other)) != plain


def test_spans_by_name_argument_and_gap():
    events = by_hand()
    assert program_trace.span_ms(events, "llm.step", 50) == pytest.approx(270e-6)
    assert program_trace.span_ms(events, "llm.step") == pytest.approx((650 + 270 + 100) / 3 * 1e-6)
    assert program_trace.span_arg_mean(events, "llm.admit", "queue_wait_ms") == 2.5
    # the gap before the step that admits held a submit and is left out: 990 -> 1000 is the one
    assert program_trace.between_steps_ms(events, 50) == pytest.approx(10e-6)
    # a slice that holds no admit reads 0, with the count beside it for the builder
    assert program_trace.span_ms(events, "llm.admit.suffix") == 0.0
    assert program_trace.summary(events)["spans"]["llm.admit"]["count"] == 1


@pytest.mark.parametrize("op_name, scope", [
    ("jit(_decode_step_rowpos)/while/body/closed_call/attn.core/bhqk,bkhd->bqhd/dot_general:", "attn.core"),
    ("jit(scan)/while/body/closed_call/attn.core/flash_fwd/pallas_call:", "attn.core"),
    ("jit(train_step)/jit(main)/transpose(jvp(attn.qkv))/dot_general", "attn.qkv"),
    ("jit(train_step)/jvp(checkpoint)/rematted_computation/ffn/mul", "ffn"),
    ("jit(train_step)/optimizer/adamw/sqrt", "optimizer"),
    ("jit(train_step)/jvp(loss)/reduce_sum", "loss"),
    ("jit(_decode_step_rowpos)/while/body/dynamic_update_slice:", ""),
    ("jit(f)/norm_all/normalize/headroom", ""),
    ("", ""),
])
def test_scope_is_the_innermost_named_scope_of_the_op_name(op_name, scope):
    assert program_trace.scope_of(op_name) == scope


@pytest.mark.parametrize("name, kernel", [
    ("%flash_fwd.5 = (bf16[32,256,128]{2,1,0}, f32[32,1,256]) custom-call(...)", "flash_fwd"),
    ("%flash_bwd_dkv = (bf16[8]) custom-call(...)", "flash_bwd_dkv"),
    ("%get-tuple-element.7 = bf16[8] get-tuple-element(%flash_fwd.5), index=0", ""),
    ("%flash_fwd_like.1 = f32[8] fusion(...)", ""),
])
def test_a_kernel_is_known_by_the_instructions_own_name(name, kernel):
    assert program_trace.kernel_of(name) == kernel


# -- the recorded trace -------------------------------------------------------


def test_recorded_trace_gives_the_chip_runs_numbers():
    events = recorded()
    parts = program_trace.idle_by_span(events)
    idle = trace_reduce.idle_percent(as_trace_reduce(events))
    assert set(parts) == {"admit", "readback", "step_host", "between_steps"}
    assert abs(sum(parts.values()) - idle) < 0.5 and 20 < idle < 45
    assert parts["step_host"] > parts["readback"] > parts["admit"] == 0.0  # no admit in this quarter second
    # the step as the run had it: 20 ms of uploads, 55 ms waiting for the device
    assert 15 < program_trace.span_ms(events, "llm.step.upload", 50) < 25
    assert 50 < program_trace.span_ms(events, "llm.step.readback", 50) < 60
    assert program_trace.span_ms(events, "llm.step.scatter", 50) < 0.1
    assert program_trace.between_steps_ms(events, 50) < 1.0
    by_scope = program_trace.time_by_scope(events)
    busy = trace_reduce.busy(as_trace_reduce(events))["busy_s"] * 1e9
    assert sum(by_scope.values()) == pytest.approx(busy, rel=1e-6)
    assert 50 < program_trace.scope_percent(events, ["attn.core"]) < 60
    assert program_trace.scope_percent(events, ["attn.cache"]) < 1.0
    shares = [program_trace.scope_percent(events, [s]) for s in program_trace.SCOPES]
    assert sum(shares) < 100.0 and by_scope[""] > 0


@pytest.mark.parametrize("metric", NEW + LATER, ids=lambda m: m["name"])
def test_each_new_metric_reads_the_trace_and_nothing_from_an_older_program(metric):
    read = manifest.load_reader(metric["reader"])
    args = metric.get("args", {})
    events = by_hand() if metric.get("family") == "train" else recorded()
    value = read({"program_trace": events}, **args)
    assert isinstance(value, float) and 0.0 <= value < 1e4
    if metric["unit"] == "%":
        assert value <= 100.0
    # the parent commit: no `llm.` span, no operation under a scope, no named kernel
    older = copy.deepcopy(events)
    older["spans"] = []
    older["ops"] = {k: [[s, d, n.replace("flash_", "custom-call_"), ""] for s, d, n, _ in v]
                    for k, v in older["ops"].items()}
    assert read({"program_trace": older}, **args) is None
    assert read({"program_trace": None}, **args) is None  # a run that was not traced


def test_new_metrics_are_the_issues_twenty_and_keep_the_layers_names():
    assert len(NEW) == 20
    layers = {m["layer"] for m in DOC["per_layer"]}
    assert {m["layer"] for m in NEW} <= layers
    train = {m["name"] for m in NEW if m.get("family") == "train"}
    assert train == {"attn_share.train", "ffn_share.train", "head_loss_share.train",
                     "optimizer_share.train", "flash_share.train"}


# -- the profiler's own files -------------------------------------------------


def _varint(n):
    out = b""
    while True:
        out += bytes([(n & 0x7F) | (0x80 if n > 0x7F else 0)])
        n >>= 7
        if not n:
            return out


def _field(number, value):
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    return _varint(number << 3 | 2) + _varint(len(value)) + value


def test_op_names_reads_the_table_of_operations_from_the_file(tmp_path):
    """An XSpace written field by field: one device plane whose table gives
    one operation its `tf_op` as a string, one by reference, one none."""
    entry = lambda key, value: _field(1, key) + _field(2, value)
    stat_meta = lambda i, name: _field(5, entry(i, _field(1, i) + _field(2, name)))
    event_meta = lambda i, name, *stats: _field(4, entry(i, _field(1, i) + _field(2, name) + b"".join(
        _field(5, s) for s in stats)))
    plane = (
        _field(1, 7) + _field(2, b"/device:TPU:0")
        + stat_meta(26, b"tf_op") + stat_meta(30, b"flops") + stat_meta(31, b"jit(f)/ffn/mul:")
        + event_meta(1, b"%fusion.1 = bf16[8] fusion()", _field(1, 30) + _field(3, 12345),
                     _field(1, 26) + _field(5, b"jit(f)/while/body/attn.core/dot_general:"))
        + event_meta(2, b"%fusion.2 = bf16[8] fusion()", _field(1, 26) + _field(7, 31))
        + event_meta(3, b"%copy.3 = bf16[8] copy()", _field(1, 30) + _field(2, b"\0" * 8))
    )
    host = _field(2, b"/host:CPU") + event_meta(1, b"llm.step")
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_field(1, plane) + _field(1, host))
    assert program_trace.op_names(str(path)) == {"/device:TPU:0": {
        "%fusion.1 = bf16[8] fusion()": "jit(f)/while/body/attn.core/dot_general:",
        "%fusion.2 = bf16[8] fusion()": "jit(f)/ffn/mul:",
    }}


def _profiled(tmp_path, start, stop):
    """A few jitted calls under the benchmark's annotation and the program's
    span, between `start(dir)` and `stop(dir, what start gave)`."""
    import jax
    import jax.numpy as jnp

    from cluster_anywhere_tpu.util import tracing

    step = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((32, 32))
    step(x).block_until_ready()  # compiled before either profile
    os.makedirs(str(tmp_path), exist_ok=True)
    session = start(str(tmp_path))
    try:
        for live in (3, 2, 1):
            sp = tracing.span("llm.step")
            with jax.profiler.TraceAnnotation("decode_step"), sp:
                sp.set(live=live)
                step(x).block_until_ready()
    finally:  # a process holds one profile at a time: a test that fails leaves none open
        path = stop(str(tmp_path), session)
    return path


def _what_the_readers_see(path):
    """Planes, the operations' names (the CPU client writes an operation as an
    event of its own threads' lines), and both readers' extractions less their
    clock."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops = {e.name for p in data.planes for ln in p.lines if ln.name.startswith("tf_XLA") for e in ln.events}
    reduced, program = trace_reduce.extract(path), program_trace.extract(path)
    return {"planes": [p.name for p in data.planes], "ops": ops,
            "host": [e[2] for e in reduced["host"]], "devices": reduced["devices"],
            "spans": [s[3:] for s in program["spans"]], "program_ops": program["ops"]}


def _jax_profile(tmp_path):
    """The same calls under `jax.profiler`'s own start and stop, with the harness's options."""
    import jax

    from benchmarks.harness import replica

    _profiled(tmp_path, lambda trace_dir: jax.profiler.start_trace(trace_dir, profiler_options=replica.trace_options()),
              lambda trace_dir, _: jax.profiler.stop_trace())
    (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    return path


def test_the_harness_stops_its_own_session_and_leaves_the_profile_alone(tmp_path, monkeypatch, capfd):
    """`replica.start_trace` / `stop_trace`: one `.xplane.pb` where the profiler
    puts it and no `.trace.json.gz` beside it, and both readers make of it what
    they make of one `jax.profiler.stop_trace()` wrote from the same calls."""
    from benchmarks.harness import replica

    path = _profiled(tmp_path / "own", replica.start_trace, replica.stop_trace)
    assert glob.glob(os.path.join(str(tmp_path / "own"), "plugins", "profile", "*", "*.xplane.pb")) == [path]
    assert os.listdir(os.path.dirname(path)) == [os.path.basename(path)] and os.path.getsize(path) > 0
    theirs = _jax_profile(tmp_path / "jax")
    assert sorted(f.split(".", 1)[1] for f in os.listdir(os.path.dirname(theirs))) == ["trace.json.gz", "xplane.pb"]
    assert os.path.basename(theirs) == os.path.basename(path)  # <host>.xplane.pb
    mine, jaxs = _what_the_readers_see(path), _what_the_readers_see(theirs)
    assert mine == jaxs
    assert mine["host"] == ["decode_step"] * 3 and mine["spans"] == [["llm.step", {"live": n}] for n in (3, 2, 1)]
    assert "/host:CPU" in mine["planes"] and any(n.startswith("dot") for n in mine["ops"])
    assert "ProfilerSession" not in capfd.readouterr().err  # the fallback was not taken
    # what the stop cost goes with the summary the builder reads, which no reader reads back
    monkeypatch.setattr(cluster, "out_dir", lambda: str(tmp_path))
    ctx = {"cell": manifest.load_cell("chat-closed6"), "trace_path": path, "trace_stop_s": 0.25,
           "trace_bytes": os.path.getsize(path)}
    assert [s[3] for s in program_trace.load(ctx)["spans"]] == ["llm.step"] * 3
    with open(tmp_path / "chat-closed6.program_trace.json") as f:
        left = json.load(f)
    assert (left["trace_stop_s"], left["trace_bytes"]) == (0.25, ctx["trace_bytes"]) and left["spans"]["llm.step"]["count"] == 3


def test_a_jax_without_the_sessions_stop_takes_jax_profilers_own(tmp_path, monkeypatch, capfd):
    """The session class as an older JAX has it (no `stop()`): the harness says
    so and the trace is `jax.profiler`'s, export and all; the path it returns
    is the profile's all the same."""
    from jax._src.lib import _profiler

    from benchmarks.harness import replica

    real = _profiler.ProfilerSession

    class Older:
        def __init__(self, *args):
            self._session = real(*args)

        def stop_and_export(self, log_dir):
            self._session.stop_and_export(log_dir)

    monkeypatch.setattr(_profiler, "ProfilerSession", Older)
    assert replica._session_class() is None
    path = _profiled(tmp_path, replica.start_trace, replica.stop_trace)
    assert "no ProfilerSession.stop()" in capfd.readouterr().err
    assert sorted(f.split(".", 1)[1] for f in os.listdir(os.path.dirname(path))) == ["trace.json.gz", "xplane.pb"]
    assert _what_the_readers_see(path)["host"] == ["decode_step"] * 3
    # and where the module has no such class at all
    monkeypatch.delattr(_profiler, "ProfilerSession")
    assert replica._session_class() is None


def test_extract_finds_the_programs_spans_in_a_trace_the_profiler_wrote(tmp_path):
    """`tracing.span` in a profiled process: the span is in the trace with its
    arguments, on its thread's line, and the file reads without a device."""
    import jax
    import jax.numpy as jnp

    from cluster_anywhere_tpu.util import tracing

    jax.profiler.start_trace(str(tmp_path))
    try:
        sp = tracing.span("llm.step")
        with sp:
            sp.set(live=3)
            with tracing.span("llm.step.upload"):
                jnp.arange(4).block_until_ready()
        with tracing.span("not.the.programs"):
            pass
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    events = program_trace.extract(path)
    assert events["ops"] == {} and [s[3] for s in events["spans"]] == ["llm.step", "llm.step.upload"]
    step, upload = events["spans"]
    assert step[4] == {"live": 3} and step[0] == upload[0] == program_trace.pump_thread(events)
    assert step[1] <= upload[1] and upload[1] + upload[2] <= step[1] + step[2]
    assert program_trace.idle_by_span(events) is None and program_trace.time_by_scope(events) is None
