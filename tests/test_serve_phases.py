"""A request's way from the proxy's socket to the token's write, measured where
it happens (ISSUE 36): the phase spans of proxy, router, replica and stream
forwarder as one trace, the whole-window histogram `ca_serve_phase_seconds`
beside them, the proxy's gauges, the clock beacon, and the operator's reading
(`state.serve_requests`).  CPU only; every case has a time limit of its own."""

import asyncio
import concurrent.futures
import contextlib
import http.client
import json
import signal
import socket
import threading
import time

import pytest

import cluster_anywhere_tpu as ca
from cluster_anywhere_tpu import serve
from cluster_anywhere_tpu.core.worker import global_worker
from cluster_anywhere_tpu.llm.serve_llm import StreamingLLMIngress
from cluster_anywhere_tpu.util import state, tracing

import sys  # noqa: E402

import cloudpickle  # noqa: E402

# the deployments below run in worker processes that cannot import this file
cloudpickle.register_pickle_by_value(sys.modules[__name__])

HOST = "127.0.0.1"
EXT_SID = "c0ffee11aa55bb77"


@contextlib.contextmanager
def time_limit(seconds: int):
    """A case's own limit: SIGALRM raises in the test's thread."""

    def on_alarm(signum, frame):
        raise TimeoutError(f"case ran over its {seconds} s")

    old = signal.signal(signal.SIGALRM, on_alarm)
    signal.alarm(seconds)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old)


def limited(seconds: int):
    def wrap(fn):
        import functools

        @functools.wraps(fn)
        def run(*a, **kw):
            with time_limit(seconds):
                return fn(*a, **kw)

        return run

    return wrap


# -- in the processes under test ----------------------------------------------
_constructed = [0]


def _count_spans(_instance=None, action="read"):
    """Runs inside the proxy's or a replica's process: counts every
    `tracing.span` (and so every `phase`) of a request's way constructed from
    `start` on.  The pump's own (`llm.step`, `llm.pump.*`, `llm.admit*`: one a
    step, as before this file) are not a request's."""
    from cluster_anywhere_tpu.util import tracing as t

    if action == "start" and not hasattr(t.span, "_counted_init"):
        inner = t.span.__init__

        def counted(self, name, *a, **kw):
            _constructed[0] += name.startswith(("serve.", "llm.stream", "llm.submit"))
            inner(self, name, *a, **kw)

        t.span._counted_init = inner
        t.span.__init__ = counted
    return _constructed[0]


class RpcOnlyIngress(StreamingLLMIngress):
    """The same replica without the compiled-DAG handshake: the proxy falls
    back to the streaming-RPC transport (`stream`'s generator)."""

    dag_stream = None

    def count_spans(self, action):
        return _count_spans(self, action)


class DagIngress(StreamingLLMIngress):
    def count_spans(self, action):
        return _count_spans(self, action)


def _free_port():
    with socket.socket() as s:
        s.bind((HOST, 0))
        return s.getsockname()[1]


def _post(port, path, body, headers=None):
    conn = http.client.HTTPConnection(HOST, port, timeout=60)
    try:
        conn.request("POST", path, body=json.dumps(body),
                     headers={"Content-Type": "application/json", **(headers or {})})
        r = conn.getresponse()
        return r.status, r.read()
    finally:
        conn.close()


SSE = {"accept": "text/event-stream"}
PATHS = {"dag": "/dag", "rpc": "/rpc"}


@pytest.fixture(scope="module")
def front():
    """Proxy, router and two tiny continuous-batching replicas (one a
    transport), warmed so that every program is compiled and the proxy knows
    which deployment has no `dag_stream`.  Torn down whatever happens."""
    from cluster_anywhere_tpu.llm import ModelSpec, ProcessorConfig

    if ca.is_initialized():
        ca.shutdown()
    assert not tracing.is_enabled()
    port = _free_port()
    try:
        with time_limit(240):
            ca.init(num_cpus=8)
            serve.start(host=HOST, port=port)
            cfg = ProcessorConfig(model=ModelSpec(preset="tiny"), max_prompt_len=16,
                                  max_new_tokens=48, prefix_cache_entries=0)
            for name, cls in (("dag", DagIngress), ("rpc", RpcOnlyIngress)):
                app = serve.deployment(cls, name=name, max_ongoing_requests=4).bind(cfg, 2)
                serve.run(app, name=name, route_prefix=PATHS[name], wait_timeout_s=180)
            time.sleep(1.0)  # the proxy's route poller
            for path in PATHS.values():
                for hdrs in (SSE, {}):
                    st, body = _post(port, path, {"prompt": "warm", "max_new_tokens": 3}, hdrs)
                    assert st == 200, body
        yield port
    finally:
        ca.shutdown()


def _ring():
    return global_worker().head_call("list_task_events", limit=100_000)["events"]


def _trace_events(tid, want_names, timeout=20.0):
    """The ring's events of one trace, once it holds every name in `want_names`."""
    deadline = time.monotonic() + timeout
    while True:
        evs = [e for e in _ring() if (e.get("trace") or {}).get("tid") == tid]
        names = {e.get("name") for e in evs if e.get("state") == "SPAN"}
        if want_names <= names or time.monotonic() > deadline:
            return evs
        time.sleep(0.25)


def _parents(evs):
    """{span name (with `what` / `method` where a name repeats): its parent's
    name} from the raw events: a span names its parent's id, a task's own
    spans name the execution's id that its RUNNING event carries."""
    alias = {e["exec_sid"]: e["trace"]["sid"] for e in evs if e.get("exec_sid")}
    by_sid = {}
    for e in evs:
        if e.get("state") == "SPAN":
            by_sid[e["trace"]["sid"]] = e["name"]
        elif e.get("task_id"):
            by_sid.setdefault(e["trace"]["sid"], f"task:{e.get('name')}")
    task_parent = {
        f"task:{e.get('name')}": e["trace"].get("psid")
        for e in evs if e.get("task_id") and e["trace"].get("psid")
    }
    out = {}
    for e in evs:
        if e.get("state") != "SPAN":
            continue
        label = e["name"] + "".join(f"[{e[k]}]" for k in ("what", "method") if e.get(k))
        psid = e["trace"].get("psid")
        out[label] = by_sid.get(alias.get(psid, psid))
    for name, psid in task_parent.items():
        out.setdefault(name, by_sid.get(alias.get(psid, psid)))
    return out


FRONT_OF_A_CALL = {
    "serve.proxy.route": "serve:POST {path}",
    "serve.proxy.admit": "serve:POST {path}",
    "serve.router.dispatch_wait": "{caller}",
    "serve.router.acquire": "{caller}",
    "serve.router.submit": "{caller}",
    "llm.submit": "serve.replica.handle",
    "llm.submit.lock_wait": "llm.submit",
    "llm.admit.prefill": "llm.admit",
}
FRONT_OF_A_STREAM = {
    **FRONT_OF_A_CALL,
    "serve.proxy.open_stream": "serve:POST {path}",
    "serve.proxy.executor_wait[{handshake}]": "serve.proxy.open_stream",
    "serve.proxy.executor_wait[pump]": "serve:POST {path}",
    "serve.proxy.executor_wait[first_get]": "serve:POST {path}",
    "serve.proxy.first_token": "serve:POST {path}",
    "llm.stream": "serve.replica.handle",
    "llm.stream.first_token": "llm.stream",
}
SHAPES = {
    # transport and shape -> (path, headers, the table's spans with their parents)
    "dag": ("/dag", SSE, dict(
        FRONT_OF_A_STREAM, **{"serve.replica.handle[{method}]": "task:handle_request"}),
        dict(caller="serve.proxy.open_stream", method="dag_stream", handshake="dag_stream")),
    "rpc": ("/rpc", SSE, dict(
        FRONT_OF_A_STREAM, **{"serve.replica.handle[{method}]": "task:handle_request_streaming"}),
        dict(caller="serve.proxy.open_stream", method="__call__", handshake="rpc_stream")),
    "unary": ("/dag", {}, dict(
        FRONT_OF_A_CALL, **{"serve.proxy.executor_wait[call]": "serve:POST {path}",
                            "serve.replica.handle[{method}]": "task:handle_request"}),
        dict(caller="serve:POST {path}", method="__call__")),
}


@pytest.mark.parametrize("shape", list(SHAPES))
@limited(90)
def test_one_traced_request_is_one_trace_of_the_tables_spans(front, shape):
    """One request with a `traceparent` through proxy, router and a
    continuous-batching replica: the ring holds one trace whose spans are the
    table's for that path, each a child of the right parent, each with
    `mono`; the request event says what the client got."""
    path, headers, table, names = SHAPES[shape]
    names = dict(names, path=path)
    names["caller"] = names["caller"].format(**names)
    want = {k.format(**names): v.format(**names) for k, v in table.items()}
    tid = f"{abs(hash(shape)) % 16 ** 8:08x}" * 4
    n_new = 6
    t_before = time.monotonic()
    st, body = _post(front, path, {"prompt": "hello", "max_new_tokens": n_new},
                     {**headers, "traceparent": f"00-{tid}-{EXT_SID}-01"})
    t_after = time.monotonic()
    assert st == 200, body
    evs = _trace_events(tid, {k.split("[")[0] for k in want} | {f"serve:POST {path}", "llm.admit"})
    spans = [e for e in evs if e.get("state") == "SPAN"]
    parents = _parents(evs)
    assert {k: parents.get(k) for k in want} == want
    # one request event, the client's span as its parent; every span on the
    # host's monotonic clock, inside the client's own stamps
    (request,) = [e for e in spans if e["name"] == f"serve:POST {path}"]
    assert request["trace"]["psid"] == EXT_SID
    assert all(t_before <= e["mono"] <= t_after for e in spans), [
        (e["name"], e.get("mono")) for e in spans]
    assert request["status"] == 200 and request["streamed"] == bool(headers)
    by_name = {e["name"]: e for e in spans}
    rid = by_name["llm.admit"]["rid"]
    assert by_name["llm.submit"]["rid"] == rid and by_name["llm.submit"]["prompt_len"] > 0
    handle = [e for e in spans if e["name"] == "serve.replica.handle"][-1]
    assert handle["ongoing"] == 0 and handle["method"] == names["method"]
    if not headers:
        assert request["tokens"] == 0 and "llm.stream" not in by_name
        return
    assert request["tokens"] == n_new and 0.0 < request["ttfb_ms"] <= 1e3 * (t_after - t_before)
    assert request["executor_wait_ms"] > 0.0 and request["write_wait_ms"] >= 0.0
    opened = by_name["serve.proxy.open_stream"]
    assert opened["transport"] == shape and opened["fallback"] is False
    stream = by_name["llm.stream"]
    assert stream["rid"] == rid == by_name["llm.stream.first_token"]["rid"]
    assert stream["transport"] == shape and stream["tokens"] == n_new
    assert stream["cancelled"] is False and stream["write_wait_max_ms"] <= stream["write_wait_ms"]
    admit_ms = 1e3 * (by_name["llm.admit"]["end"] - by_name["llm.admit"]["start"])
    assert stream["first_token_ms"] >= admit_ms
    # no span a token: the whole trace is the table's, whatever the length
    assert len([e for e in spans if e["name"].startswith(("serve.", "llm.stream"))]) <= 16
    # the operator's reading finds the same request, its phases under it
    mine = [r for r in state.serve_requests(limit=0)["requests"] if r["trace"] == tid]
    assert len(mine) == 1 and mine[0]["tokens"] == n_new
    depth = {p["name"]: p["depth"] for p in mine[0]["phases"]}
    assert depth["llm.stream.first_token"] == depth["llm.stream"] + 1 > depth["serve.proxy.open_stream"]
    assert depth["llm.admit"] == depth["llm.stream"] + 1  # joined by its `rid`


def _phase_counts():
    from cluster_anywhere_tpu.util.metrics import get_metrics_snapshot

    rec = get_metrics_snapshot().get("ca_serve_phase_seconds") or {}
    out = {}
    for key, cell in rec.get("data", {}).items():
        tags = dict(json.loads(key))
        if tags["deployment"] == "dag/dag":
            out[tags["phase"]] = out.get(tags["phase"], 0) + cell["count"]
    return out


def _proxy_exec(fn, *args):
    from cluster_anywhere_tpu.core.actor import get_actor

    proxy = get_actor(serve.PROXY_NAME)
    # a local function goes by value (a plain pickle would name this file)
    by_value = lambda inst, *a: fn(inst, *a)  # noqa: E731
    return ca.get(
        proxy._submit("__ca_exec__", (by_value, *args), {}, {"num_returns": 1}), timeout=30)


@limited(90)
def test_untraced_request_writes_no_span_and_counts_each_phase_once(front):
    """The same stream without the header: no SPAN event reaches the ring,
    nothing constructs a span a token (a stream eight times as long constructs
    as many, in the proxy and in the replica), and every phase of the table
    has one more observation (`executor_wait` one a named wait)."""
    handle = serve.get_deployment_handle("dag", "dag")
    for start in (lambda: _proxy_exec(_count_spans, "start"),
                  lambda: handle.count_spans.remote("start").result(timeout_s=30)):
        start()
    time.sleep(2.5)  # what the warm-up and the cases before left on its way
    spans_before = sum(e.get("state") == "SPAN" for e in _ring())
    counts_before = _phase_counts()

    def constructed_by(n_new):
        before = (_proxy_exec(_count_spans), handle.count_spans.remote("read").result(timeout_s=30))
        st, body = _post(front, "/dag", {"prompt": "quiet", "max_new_tokens": n_new}, SSE)
        assert st == 200 and body.count(b"data:") == n_new, body
        time.sleep(0.3)  # the forwarder's thread ends after the last frame
        after = (_proxy_exec(_count_spans), handle.count_spans.remote("read").result(timeout_s=30))
        return after[0] - before[0], after[1] - before[1]

    short, long_ = constructed_by(5), constructed_by(40)
    # `count_spans` itself is a request of the replica's (its handle's phases);
    # what matters is that 35 more tokens construct nothing more
    assert short == long_, (short, long_)
    want = {
        "serve.proxy.request": 2, "serve.proxy.route": 2, "serve.proxy.admit": 2,
        "serve.proxy.open_stream": 2, "serve.proxy.executor_wait": 6,
        "serve.proxy.first_token": 2, "llm.submit": 2, "llm.submit.lock_wait": 2,
        "llm.admit.queue_wait": 2, "llm.admit": 2, "llm.stream": 2, "llm.stream.first_token": 2,
    }
    deadline = time.monotonic() + 15
    while True:
        now = _phase_counts()
        delta = {k: now.get(k, 0) - counts_before.get(k, 0) for k in want}
        if delta == want or time.monotonic() > deadline:
            break
        time.sleep(0.5)
    assert delta == want
    # the handle's own calls (count_spans) go through the router and the
    # replica too: those phases rose by at least the two streams
    for k in ("serve.router.dispatch_wait", "serve.router.acquire", "serve.router.submit",
              "serve.replica.handle"):
        assert now.get(k, 0) - counts_before.get(k, 0) >= 2, (k, now, counts_before)
    assert sum(e.get("state") == "SPAN" for e in _ring()) == spans_before
    plane = state.serve_plane()
    assert plane["gauges"]["proxy_streams_open"] == 0 == plane["gauges"]["proxy_executor_pending"]
    assert plane["gauges"]["proxy_executor_threads"] >= 5  # min(32, cores + 4)
    q = plane["quantiles"]
    for k in want:
        assert q[f"{k}_count"] >= 2 and 0.0 < q[f"{k}_p50_s"] <= q[f"{k}_p99_s"], (k, q)


@limited(30)
def test_executor_wait_reads_the_hold_and_the_pending_gauge_rises_and_falls():
    """A one-thread pool held busy: the wait for a pool thread reads the hold
    time, and the gauge of work not yet started rises and falls."""
    from cluster_anywhere_tpu.serve import proxy as proxy_mod

    hold_s = 0.25
    gauge = proxy_mod._shed_metrics()["executor_pending"]
    seen = []

    def pending():
        return next(iter(gauge._values.values()), 0.0)

    async def main():
        loop = asyncio.get_running_loop()
        loop.set_default_executor(concurrent.futures.ThreadPoolExecutor(max_workers=1))
        rt = proxy_mod._RequestTrace(None, time.monotonic())
        release = threading.Event()
        blocker = rt.in_pool(loop, lambda: release.wait(5), None)
        await asyncio.sleep(0.05)  # the blocker has the pool's one thread
        seen.append(pending())
        waiting = rt.in_pool(loop, lambda: "ran", "held")
        seen.append(pending())
        await asyncio.sleep(hold_s)
        release.set()
        assert await waiting == "ran" and await blocker is True
        seen.append(pending())
        return rt

    rt = asyncio.run(main())
    assert seen == [0.0, 1.0, 0.0]
    assert hold_s <= rt.executor_wait_s < hold_s + 0.2


class _FakeAnnotation:
    """Stands in for `jax.profiler.TraceAnnotation`: the profiler's sink."""

    seen = []

    def __init__(self, name, **attrs):
        self.seen.append((name, attrs, time.time_ns(), time.monotonic_ns()))

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set_metadata(self, **attrs):
        pass


@limited(120)
def test_replica_side_spans_share_the_requests_rid_and_the_beacon_carries_both_clocks(monkeypatch):
    """A CPU batcher behind `ContinuousLLMServer`, a stream under a trace
    context: `llm.stream`, `llm.stream.first_token` and `llm.submit` carry the
    `rid` of `llm.admit`, the first token took at least the admit, no span is
    made a token; and the pump's `llm.pump.sync` carries the wall clock and the
    monotonic at one instant, in the profiler's sink."""
    from cluster_anywhere_tpu.llm import ContinuousLLMServer, ModelSpec, ProcessorConfig

    drain = tracing.drain_events
    monkeypatch.setattr(tracing, "drain_events", lambda: [])  # the housekeeping's
    drain()
    assert not tracing.is_enabled()
    _FakeAnnotation.seen = []
    monkeypatch.setattr(tracing, "_annotation_cls", _FakeAnnotation)
    made = []
    inner = tracing.span.__init__
    monkeypatch.setattr(tracing.span, "__init__",
                        lambda self, *a, **kw: (made.append(a[0]), inner(self, *a, **kw))[1])
    srv = ContinuousLLMServer(
        ProcessorConfig(model=ModelSpec(preset="tiny"), max_prompt_len=16, max_new_tokens=32,
                        prefix_cache_entries=0), slots=2)
    try:
        list(srv.stream({"prompt": "warm", "max_new_tokens": 2}))
        assert [e for e in drain() if e["state"] == "SPAN"] == []

        def made_by_stream(n_new):
            del made[:]
            assert len(list(srv.stream({"prompt": "count", "max_new_tokens": n_new}))) == n_new
            return sorted(n for n in made if n.startswith(("llm.stream", "llm.submit")))

        assert made_by_stream(4) == made_by_stream(30) == [
            "llm.stream.first_token", "llm.submit", "llm.submit.lock_wait"]
        token = tracing.push_execution({"tid": "feedfacefeedface", "sid": "0badf00d"})
        try:
            frames = list(srv.stream({"prompt": "traced", "max_new_tokens": 5}))
        finally:
            tracing.pop_execution(token)
        time.sleep(1.2)  # a beacon a second
        events = [e for e in drain() if e["state"] == "SPAN"]
    finally:
        srv.close()
    assert len(frames) == 5
    by_name = {e["name"]: e for e in events}
    rid = by_name["llm.admit"]["rid"]
    for name in ("llm.submit", "llm.stream", "llm.stream.first_token"):
        assert by_name[name]["rid"] == rid and by_name[name]["mono"] > 0.0, name
    stream, admit = by_name["llm.stream"], by_name["llm.admit"]
    assert stream["transport"] == "rpc" and stream["tokens"] == 5 and stream["cancelled"] is False
    assert stream["first_token_ms"] >= 1e3 * (admit["end"] - admit["start"]) > 0.0
    assert by_name["llm.stream.first_token"]["trace"]["psid"] == stream["trace"]["sid"]
    assert [e["name"] for e in events].count("llm.stream") == 1
    beacons = [s for s in _FakeAnnotation.seen if s[0] == "llm.pump.sync"]
    assert beacons, {s[0] for s in _FakeAnnotation.seen}
    for _, attrs, wall_ns, mono_ns in beacons:
        assert abs(attrs["wall_ns"] - wall_ns) < 1e6 and abs(attrs["mono_ns"] - mono_ns) < 1e6


def _span(name, sid, psid, start, end, **attrs):
    return {"task_id": "", "name": name, "type": "span", "state": "SPAN", "ts": start,
            "trace": {"tid": "t1", "sid": sid, **({"psid": psid} if psid else {})},
            "start": start, "end": end, "mono": start - 1000.0, **attrs}


@limited(10)
def test_serve_requests_gives_each_phase_its_self_time_on_a_hand_made_ring():
    """Self times add up to the request's duration; a child that outlives its
    parent is clipped to it; a task's spans hang under the task through its
    `exec_sid`; an admit that names an id nobody wrote hangs under the stream
    of its `rid`."""
    ring = [
        _span("serve:POST /llm", "r", "client", 10.0, 11.0, status=200, tokens=3),
        _span("serve.proxy.route", "a", "r", 10.0, 10.1),
        _span("serve.proxy.open_stream", "b", "r", 10.1, 10.5, transport="dag"),
        _span("serve.router.submit", "c", "b", 10.2, 10.3),
        # the task the router submitted: its events name "c", its spans "x1"
        {"task_id": "aa", "name": "handle_request", "type": "actor_task", "state": "SUBMITTED",
         "ts": 10.25, "trace": {"tid": "t1", "sid": "k", "psid": "c"}},
        {"task_id": "aa", "name": "handle_request", "type": "actor_task", "state": "RUNNING",
         "ts": 10.3, "trace": {"tid": "t1", "sid": "k"}, "exec_sid": "x1"},
        {"task_id": "aa", "name": "handle_request", "type": "actor_task", "state": "FINISHED",
         "ts": 10.5, "trace": {"tid": "t1", "sid": "k"}, "start": 10.3, "end": 10.5},
        _span("serve.replica.handle", "h", "x1", 10.35, 10.45, method="dag_stream"),
        # the stream outlives the handle that started it: clipped to it
        _span("llm.stream", "s", "h", 10.4, 10.9, rid=7, tokens=3),
        _span("llm.admit", "m", "nobody", 10.5, 10.7, rid=7),
        _span("serve.proxy.first_token", "f", "r", 10.5, 10.8),
        # another trace, no request of the proxy's in it: not a request
        {**_span("llm.step", "z", None, 10.0, 10.1), "trace": {"tid": "t2", "sid": "z"}},
    ]
    out = state.serve_requests(events=ring)
    (req,) = out["requests"]
    assert req["trace"] == "t1" and req["tokens"] == 3 and req["dur_ms"] == pytest.approx(1000.0)
    ph = {p["name"]: p for p in req["phases"]}
    assert [p["name"] for p in req["phases"]] == [
        "serve:POST /llm", "serve.proxy.route", "serve.proxy.open_stream", "serve.router.submit",
        "task:handle_request", "serve.replica.handle", "llm.stream", "llm.admit",
        "serve.proxy.first_token"]
    assert [ph[n]["depth"] for n in ("serve:POST /llm", "serve.proxy.open_stream",
                                     "serve.router.submit", "task:handle_request",
                                     "serve.replica.handle", "llm.stream", "llm.admit")] == list(range(7))
    # the request's own children cover 0.1 + 0.4 + 0.3 of its second
    assert ph["serve:POST /llm"]["self_ms"] == pytest.approx(200.0)
    assert ph["serve.proxy.open_stream"]["self_ms"] == pytest.approx(300.0)
    # the task began after the submit span ended: clipped to nothing
    assert ph["serve.router.submit"]["self_ms"] == pytest.approx(100.0)
    assert ph["task:handle_request"]["self_ms"] == pytest.approx(100.0)
    # the stream covers the handle's last 0.05 s only, whatever its own length
    assert ph["serve.replica.handle"]["self_ms"] == pytest.approx(50.0)
    assert ph["llm.stream"]["dur_ms"] == pytest.approx(500.0)
    assert ph["llm.stream"]["self_ms"] == pytest.approx(300.0) and "detached" not in ph["llm.admit"]
    # along the request's top level nothing is counted twice
    top = [p for p in req["phases"] if p["depth"] == 1]
    assert ph["serve:POST /llm"]["self_ms"] + sum(p["dur_ms"] for p in top) == pytest.approx(req["dur_ms"])
    assert out["phases"]["llm.stream"] == {
        "count": 1, "p50_ms": pytest.approx(500.0), "p99_ms": pytest.approx(500.0),
        "self_p50_ms": pytest.approx(300.0), "self_p99_ms": pytest.approx(300.0)}
    assert state.serve_requests(events=[e for e in ring if e["name"] != "serve:POST /llm"]) == {
        "requests": [], "phases": {}}


# -- set-up: before the first request (ISSUE 52) ------------------------------
SETUP_TREE = {
    "worker.boot": "actor.create",
    "actor.init": "actor.create",
    "serve.replica.start": "actor.init",
    "llm.replica.init": "serve.replica.start",
    "llm.replica.init.params": "llm.replica.init",
    "llm.replica.init.batcher": "llm.replica.init",
}


@limited(180)
def test_a_deploy_under_tracing_is_one_trace_from_the_workers_first_line_to_the_weights(front):
    """A deploy while the driver traces: the replica's creation (`actor.create`,
    in the controller), its worker's boot, the constructor's run on the worker
    (`actor.init` > `serve.replica.start` > `llm.replica.init` and its two
    children) are one trace of the deploy call's, every span with `mono`; the
    fixture's own two deploys, untraced, left none of them; and the operator's
    reading has the replica's set-up seconds by part and what jax's programs cost."""
    from cluster_anywhere_tpu.llm import ModelSpec, ProcessorConfig

    names = set(SETUP_TREE) | {"actor.create"}
    assert not [e for e in _ring() if e.get("state") == "SPAN" and e["name"] in names]
    cfg = ProcessorConfig(model=ModelSpec(preset="tiny"), max_prompt_len=16, max_new_tokens=8,
                          prefix_cache_entries=0)
    tracing.enable()
    try:
        app = serve.deployment(DagIngress, name="late", max_ongoing_requests=2).bind(cfg, 2)
        serve.run(app, name="late", route_prefix="/late", wait_timeout_s=120)
    finally:
        tracing.disable()
    deadline = time.monotonic() + 20.0
    while True:
        spans = [e for e in _ring() if e.get("state") == "SPAN" and e["name"] in names]
        starts = [e for e in spans if e["name"] == "serve.replica.start"]
        if (starts and {e["name"] for e in spans if e["trace"]["tid"] == starts[0]["trace"]["tid"]} == names) \
                or time.monotonic() > deadline:
            break
        time.sleep(0.25)
    (start,) = starts
    assert start["deployment"] == "late/late"
    mine = [e for e in spans if e["trace"]["tid"] == start["trace"]["tid"]]
    by_name = {e["name"]: e for e in mine}
    assert set(by_name) == names and len(mine) == len(names)
    assert all("mono" in e and e["end"] >= e["start"] for e in mine)
    by_sid = {e["trace"]["sid"]: e["name"] for e in mine}
    assert {n: by_sid.get(e["trace"].get("psid")) for n, e in by_name.items() if n != "actor.create"} == SETUP_TREE
    assert by_name["actor.create"]["cls"] == by_name["actor.init"]["cls"] == "Replica"
    assert by_name["worker.boot"]["pool"] == "cpu" and by_name["worker.boot"]["chips"] == 0
    # in the order they happened, each inside the one that holds it
    assert by_name["actor.create"]["mono"] <= by_name["worker.boot"]["mono"] <= by_name["actor.init"]["mono"]
    for child, parent in SETUP_TREE.items():
        if child != "worker.boot":
            assert by_name[parent]["mono"] <= by_name[child]["mono"]
            assert by_name[child]["end"] <= by_name[parent]["end"] + 0.05, child
    # the worker and the controller are other processes than this one, which enabled tracing
    assert by_name["llm.replica.init"]["worker_id"] != by_name["actor.create"]["worker_id"]
    # the operator's view, with no trace: the gauge by part, and jax's seconds by part of a build
    st, body = _post(front, "/late", {"prompt": "now", "max_new_tokens": 3})
    assert st == 200, body
    deadline = time.monotonic() + 20.0
    while True:
        plane = state.serve_plane()
        ready = {k: v for k, v in plane["gauges"].items() if k.startswith("replica_ready_seconds.")}
        if all(ready.get(f"replica_ready_seconds.{p}", 0.0) > 0.0 for p in ("init", "params", "build")) \
                or time.monotonic() > deadline:
            break
        time.sleep(0.5)
    assert sorted(ready) == [f"replica_ready_seconds.{p}" for p in ("build", "init", "params")]
    assert all(v > 0.0 for v in ready.values())
    # three replicas' constructors (the gauge sums them); a replica's weights are inside its constructor
    assert ready["replica_ready_seconds.params"] < ready["replica_ready_seconds.init"]
    jax_cost = plane["jax"]
    assert jax_cost["compiles"] > 0 and jax_cost["backend_s"] > 0.0 and jax_cost["trace_s"] > 0.0
    assert set(jax_cost) <= {"compiles", "cache_hits", "cache_misses", "trace_s", "lower_s", "backend_s",
                             "cache_fetch_s"}
