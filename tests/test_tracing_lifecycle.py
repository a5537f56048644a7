"""End-to-end distributed tracing: trace-context propagation through the
batched RPC envelope, task lifecycle events, Chrome-trace export, and the
metrics satellites that ride with it (prometheus escaping, flush re-staging,
server-side list limits)."""

import json
import time

import pytest

import cluster_anywhere_tpu as ca
from cluster_anywhere_tpu.core.protocol import reset_rpc_chaos
from cluster_anywhere_tpu.core.worker import global_worker
from cluster_anywhere_tpu.util import metrics, state, tracing

LIFECYCLE = ("SUBMITTED", "QUEUED", "SCHEDULED", "RUNNING", "FINISHED", "FAILED")


@pytest.fixture(scope="module", autouse=True)
def traced_cluster():
    if ca.is_initialized():
        ca.shutdown()
    tracing.enable()
    ca.init(num_cpus=4)
    yield
    ca.shutdown()
    tracing.disable()
    reset_rpc_chaos("")


def _task_hex(ref):
    return ref.id.task_id().binary().hex()


def _lifecycle(task_hex, want_states, timeout=15.0):
    """Poll the head's ring until `want_states` all appear for the task."""
    deadline = time.monotonic() + timeout
    evs = []
    while time.monotonic() < deadline:
        evs = state.task_lifecycle(task_hex)
        if set(want_states) <= {e.get("state") for e in evs}:
            return evs
        time.sleep(0.2)
    raise AssertionError(
        f"lifecycle states {want_states} never arrived; got "
        f"{[(e.get('state'), e.get('worker_id')) for e in evs]}"
    )


def test_single_trace_spans_all_phases_across_processes():
    """Acceptance: one trace ID submitted on the driver is observable across
    SUBMITTED→FINISHED, with the submit phases attributed to the driver
    process and RUNNING/FINISHED to a worker process."""

    @ca.remote
    def traced_add(x):
        return x + 1

    ref = traced_add.remote(1)
    assert ca.get(ref) == 2
    evs = _lifecycle(_task_hex(ref), {"SUBMITTED", "SCHEDULED", "RUNNING", "FINISHED"})
    by_state = {}
    for e in evs:
        by_state.setdefault(e["state"], e)
    trace_ids = {e["trace"]["tid"] for e in evs if e.get("trace")}
    assert len(trace_ids) == 1, f"trace id fragmented: {trace_ids}"
    driver_id = global_worker().client_id
    assert by_state["SUBMITTED"]["worker_id"] == driver_id
    assert by_state["SCHEDULED"]["worker_id"] == driver_id
    # execution side: a different process, attributed
    for st in ("RUNNING", "FINISHED"):
        assert by_state[st]["worker_id"], f"{st} has no worker attribution"
        assert by_state[st]["worker_id"] != driver_id
    assert by_state["FINISHED"]["name"] == "traced_add"


def test_trace_propagates_on_argless_fast_path():
    """Argless known-function submissions normally ride the pre-encoded
    template; traced ones must still carry the context end to end."""

    @ca.remote
    def traced_noop():
        return 1

    # once to export the function, again to hit the warm fast path
    ca.get(traced_noop.remote())
    ref = traced_noop.remote()
    ca.get(ref)
    evs = _lifecycle(_task_hex(ref), {"SUBMITTED", "RUNNING", "FINISHED"})
    tids = {e["trace"]["tid"] for e in evs if e.get("trace")}
    assert len(tids) == 1


def test_actor_call_lifecycle_and_trace():
    @ca.remote
    class T:
        def bump(self, x):
            return x + 1

    a = T.remote()
    ref = a.bump.remote(41)
    assert ca.get(ref) == 42
    evs = _lifecycle(_task_hex(ref), {"SUBMITTED", "SCHEDULED", "RUNNING", "FINISHED"})
    kinds = {e.get("type") for e in evs if e.get("state") == "FINISHED"}
    assert kinds == {"actor_task"}
    assert len({e["trace"]["tid"] for e in evs if e.get("trace")}) == 1
    ca.kill(a)


def test_nested_task_joins_parent_trace():
    """A remote() submitted from inside a task chains into the caller's
    trace (the ambient execution context is installed on the worker)."""

    @ca.remote
    def inner():
        return "inner-done"

    @ca.remote
    def outer():
        return ca.get(inner.remote())

    ref = outer.remote()
    assert ca.get(ref) == "inner-done"
    outer_evs = _lifecycle(_task_hex(ref), {"SUBMITTED", "FINISHED"})
    outer_tid = next(e["trace"]["tid"] for e in outer_evs if e.get("trace"))

    # the inner task's SUBMITTED event was recorded on the worker process
    # under the same trace id
    deadline = time.monotonic() + 15
    while time.monotonic() < deadline:
        evs = global_worker().head_call("list_task_events", limit=50_000)["events"]
        inner_sub = [
            e for e in evs
            if e.get("name") == "inner" and e.get("state") == "SUBMITTED"
        ]
        if inner_sub:
            break
        time.sleep(0.2)
    assert inner_sub, "nested task's SUBMITTED event never arrived"
    assert any((e.get("trace") or {}).get("tid") == outer_tid for e in inner_sub)
    driver_id = global_worker().client_id
    assert all(e["worker_id"] != driver_id for e in inner_sub)


def test_trace_across_batch_envelope_under_chaos(tmp_path):
    """Satellite: one trace ID spans submit→head→worker with the control
    plane under CA_TESTING_RPC_FAILURE chaos, and the Chrome-trace export is
    valid JSON whose duration events are all self-contained X (or matched
    B/E) events."""

    @ca.remote
    def chaotic(x):
        return x * 2

    # fail the first pushes/leases: submissions retry through fresh leases,
    # and the burst below rides batch envelopes either way
    reset_rpc_chaos("push_task=2,request_lease=1")
    try:
        refs = [chaotic.remote(i) for i in range(40)]
        assert ca.get(refs, timeout=60) == [i * 2 for i in range(40)]
    finally:
        reset_rpc_chaos("")
    ref = refs[-1]
    evs = _lifecycle(_task_hex(ref), {"SUBMITTED", "RUNNING", "FINISHED"})
    assert len({e["trace"]["tid"] for e in evs if e.get("trace")}) == 1

    # all 40 terminal events flushed (per-process buffers drain every 0.25s)
    deadline = time.monotonic() + 15
    while time.monotonic() < deadline:
        done = [t for t in state.list_tasks() if t["name"] == "chaotic"]
        if len(done) >= 40:
            break
        time.sleep(0.2)
    assert len(done) >= 40

    out = str(tmp_path / "chaos_trace.json")
    events = state.timeline(out)
    loaded = json.load(open(out))
    assert loaded and len(loaded) == len(events)
    assert all(e.get("ph") in ("X", "M", "s", "f", "B", "E", "i") for e in loaded)
    opens = sum(1 for e in loaded if e.get("ph") == "B")
    closes = sum(1 for e in loaded if e.get("ph") == "E")
    assert opens == closes  # every B matched (we emit self-contained X)
    mine = [e for e in loaded if e.get("name") == "chaotic" and e.get("ph") == "X"]
    assert len(mine) >= 40
    assert all(e["dur"] > 0 for e in mine)


def test_timeline_has_flow_arrows_and_process_metadata(tmp_path):
    @ca.remote
    def flowy():
        time.sleep(0.005)
        return 1

    ref = flowy.remote()
    ca.get(ref)
    _lifecycle(_task_hex(ref), {"SUBMITTED", "SCHEDULED", "FINISHED"})
    out = str(tmp_path / "flow.json")
    events = state.timeline(out)
    task_hex = _task_hex(ref)
    starts = [e for e in events if e.get("ph") == "s" and e.get("id") == task_hex]
    finishes = [e for e in events if e.get("ph") == "f" and e.get("id") == task_hex]
    assert starts and finishes, "no causal flow arrow for the traced task"
    # the arrow crosses processes: submit side and execute side differ
    assert starts[0]["pid"] != finishes[0]["pid"]
    # trace id is visible in the exported args
    assert starts[0]["args"]["trace_id"]
    metas = [e for e in events if e.get("ph") == "M" and e.get("name") == "process_name"]
    assert len(metas) >= 2  # driver + at least one worker
    # lifecycle phase slices on the driver row
    assert any(e.get("cat") == "lifecycle" for e in events)


def test_app_spans_nest_and_export():
    with tracing.span("outer_block") as outer:
        with tracing.span("inner_block") as inner:
            time.sleep(0.002)
    assert inner["tid"] == outer["tid"]
    assert inner["psid"] == outer["sid"]
    deadline = time.monotonic() + 15
    names = set()
    while time.monotonic() < deadline:
        evs = global_worker().head_call("list_task_events", limit=50_000)["events"]
        names = {e.get("name") for e in evs if e.get("state") == "SPAN"}
        if {"outer_block", "inner_block"} <= names:
            break
        time.sleep(0.2)
    assert {"outer_block", "inner_block"} <= names
    events = state.timeline()
    span_slices = [e for e in events if e.get("name") == "inner_block"]
    assert span_slices and all(e["ph"] == "X" for e in span_slices)


def test_disabled_path_keeps_template_fast_path():
    """With tracing disabled the argless fast path still renders pre-encoded
    templates (no per-call spec encode, no trace field)."""
    from cluster_anywhere_tpu.core import worker as worker_mod
    from cluster_anywhere_tpu.core.protocol import WIRE_STATS

    tracing.disable()
    try:
        assert worker_mod.TRACE_HOOK is None

        @ca.remote
        def plain():
            return 0

        ca.get(plain.remote())  # export
        before = WIRE_STATS["template_renders"]
        ca.get([plain.remote() for _ in range(50)], timeout=60)
        assert WIRE_STATS["template_renders"] > before
    finally:
        tracing.enable()


def test_disabled_span_installs_no_context():
    """A span block with tracing off must not make nested spans/submissions
    look traced (no ambient context, no events, no wire field)."""
    tracing.disable()
    try:
        with tracing.span("dead_outer") as outer:
            assert outer is None
            assert tracing.current() is None
            with tracing.span("dead_inner") as inner:
                assert inner is None
    finally:
        tracing.enable()


# ------------------------------------------------------- metrics satellites


def test_prometheus_escapes_label_values():
    snap = {
        "esc_metric": {
            "type": "gauge",
            "desc": "line one\nline two",
            "data": {json.dumps([["path", 'a"b\\c\nd']]): 1.0},
        }
    }
    text = metrics.render_prometheus(snap)
    line = next(l for l in text.splitlines() if l.startswith("esc_metric{"))
    assert '\\"' in line and "\\\\" in line and "\\n" in line
    assert "\n" not in line  # the raw newline never splits the sample line
    help_line = next(l for l in text.splitlines() if l.startswith("# HELP"))
    assert "\\n" in help_line
    # regression: the exposition stays one sample per line
    assert line == 'esc_metric{path="a\\"b\\\\c\\nd"} 1.0'


def test_flush_once_restages_on_send_failure():
    """Satellite: deltas drained from the metric objects must survive the
    head becoming unreachable between drain and send."""
    w = global_worker()
    c = metrics.Counter("test_restage_total", "restage check")
    c.inc(3)
    orig_notify = w.head.notify
    w.head.notify = lambda *a, **k: (_ for _ in ()).throw(
        ConnectionError("injected: head gone between drain and send")
    )
    try:
        metrics.flush_once()
        time.sleep(0.3)  # the failing send runs on the IO loop
    finally:
        w.head.notify = orig_notify
    assert c._pending == {} or sum(c._pending.values()) == 0  # really drained
    deadline = time.monotonic() + 10
    total = 0.0
    while time.monotonic() < deadline:
        snap = metrics.get_metrics_snapshot()
        total = sum(snap.get("test_restage_total", {}).get("data", {}).values())
        if total >= 3:
            break
        time.sleep(0.2)
    assert total >= 3, "re-staged deltas were lost"


def test_histogram_observe_hoisted_bisect():
    h = metrics.Histogram("test_hoist_seconds", "x", boundaries=[0.1, 1.0])
    # the hot path must not import per observation nor re-walk the bounds
    assert "bisect" not in h.observe.__code__.co_names
    h.observe(0.05)
    h.observe(0.5)
    h.observe(5.0)
    [pending] = h._pending.values()
    assert pending["buckets"] == [1, 1, 1]
    assert pending["count"] == 3


def test_list_actors_workers_limit_server_side():
    @ca.remote
    class L:
        def ping(self):
            return 1

    actors = [L.remote() for _ in range(2)]
    ca.get([a.ping.remote() for a in actors])
    w = global_worker()
    # the head itself honors the limit (not a client-side slice)
    assert len(w.head_call("list_actors", limit=1)["actors"]) == 1
    assert len(w.head_call("list_workers", limit=1)["workers"]) == 1
    assert len(state.list_actors(limit=1)) == 1
    assert len(state.list_workers(limit=1)) == 1
    assert len(state.list_actors()) >= 2
    for a in actors:
        ca.kill(a)
