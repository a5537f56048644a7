"""Cluster-wide distributed tracing (analogue of the reference's
python/ray/util/tracing/tracing_helper.py, which propagates OpenTelemetry
context through every task/actor submission, plus the per-task state machine
GcsTaskManager exports as a Chrome timeline).

Three planes, one buffer:

* **Trace context.**  `enable()` turns on trace generation: every `remote()`
  submission mints a span under the ambient trace context (a fresh trace id
  at the driver, the executing task's context inside a worker) and the
  context rides the RPC as a small optional ``tr`` field on the logical
  message (`core/protocol.TRACE_FIELD`) — batch-envelope splicing carries
  whole message bodies, so the field survives corking untouched.  Workers
  install the received context as ambient for the executing thread/coroutine,
  so nested submissions and `span()` blocks chain into one trace.

* **Task lifecycle events.**  Submission-side (SUBMITTED / QUEUED /
  SCHEDULED, recorded by `core/worker.py`) and execution-side (RUNNING /
  FINISHED / FAILED, recorded by `core/workerproc.py`) phases land in this
  module's per-process buffer via `record_task_event()` and ship to the
  head's 50k `task_events` ring on the existing ``task_events`` notify path
  (drained by every Worker's housekeeping loop).  Terminal events always
  flow (tracing off or on); the richer phases and the ``tr`` wire field are
  gated on `enable()` so the disabled submit fast path pays one branch.

* **Export.**  `util/state.timeline()` / `ca timeline` assemble the ring
  into Chrome-trace/Perfetto JSON with causal flow arrows between the
  submit and execute spans; `span("name", **attrs)` records nested app
  spans into the same buffer (and a `ca_trace_span_seconds` histogram).

One span API, two sinks.  The event buffer above is on the wall clock and
takes a span while tracing is enabled or the block runs under a trace
context.  Where jax is loaded in the process, `span()` also enters a
`jax.profiler.TraceAnnotation` of the same name and attributes: whenever
anybody profiles the process, the profiler writes the span into its own
trace beside the device's operations, on one clock (a flag test while no
profiler session runs).

Spans from stamps.  A `with span(...)` block belongs to one thread and one
unbroken stretch of it: it installs its context in a contextvar and, where
jax is loaded, enters a `TraceAnnotation` on the thread's own stack.  **A span
is never held across an `await`** (nor across a generator's `yield`): the
loop runs other coroutines on the same thread meanwhile, and both would nest
wrongly.  A coroutine, and a wait that has no block to wrap (the wait for an
executor's thread), stamps `time.monotonic()` where the span starts and ends
and calls `emit(name, t0, t1, ctx=..., **attrs)`: a finished span of the
event sink, a child of `ctx` or of the ambient context, a no-op unless
tracing is enabled or a context is there.  Where the span's children have to
name it while it is still open, `child_context()` mints its context first
(`under(ctx)` installs it on the thread that does the work) and `emit(...,
own=ctx)` writes the span under that id.

One clock besides the wall's.  Every SPAN event, from `span` and from
`emit`, carries **`mono`**: `time.monotonic()` at its start, beside the
wall-clock `start`.  On Linux that is one clock for every process of a host,
and the clock of a load generator's own stamps, so a reader puts the ring's
spans and a client's token times on one axis without trusting two wall
clocks to agree (`util/state.serve_requests`).

JAX hooks: `enable_jax_profiling()` (called by `enable()` when jax is
already imported, by the LLM engine and by the train backend once they
have imported it) keeps what building programs costs, by the thread that
built them (`jax_build_totals`; `on_jax_build` registers a thread's sink) and
in `ca_jax_compiles_total`, `ca_jax_cache_hits_total`,
`ca_jax_cache_misses_total` and the histogram `ca_jax_compile_seconds`, and
samples per-device memory into `ca_device_memory_bytes` gauges at each
metrics flush.
"""

from __future__ import annotations

import contextvars
import os
import sys
import threading
import time
from typing import Any, Callable, Dict, List, Optional

from . import metrics

_enabled = False
_patched = False
_patch_lock = threading.Lock()
_submit_hist: Optional[metrics.Histogram] = None
_span_hist: Optional[metrics.Histogram] = None

# ambient trace context for the current thread/coroutine:
# {"tid": trace id, "sid": span id[, "psid": parent span id]}
_ctx: "contextvars.ContextVar[Optional[Dict[str, str]]]" = contextvars.ContextVar(
    "ca_trace_ctx", default=None
)

# ------------------------------------------------------------- event buffer
# Per-process lifecycle/span event buffer, drained by Worker._housekeeping
# onto the head's `task_events` ring.  Appends come from user threads,
# executor threads and the IO loop alike; a plain lock keeps it simple (the
# hot disabled path never reaches here).
_events_lock = threading.Lock()
_events: List[dict] = []
_EVENTS_CAP = 100_000  # headless processes (no flusher) must not grow forever

# lazily bound core.worker.try_global_worker (a top-level import would be
# circular: util.state imports core.worker at import time)
_try_global_worker = None


def _current_worker():
    global _try_global_worker
    if _try_global_worker is None:
        from ..core.worker import try_global_worker

        _try_global_worker = try_global_worker
    return _try_global_worker()


def record_task_event(
    task_id: str,
    name: Optional[str],
    kind: str,
    state: str,
    *,
    trace: Optional[Dict[str, str]] = None,
    worker_id: Optional[str] = None,
    node_id: Optional[str] = None,
    ts: Optional[float] = None,
    **extra: Any,
) -> None:
    """Buffer one lifecycle event (thread-safe).  Terminal events pass
    start=/end= through `extra` and keep the legacy schema the state API
    reads; phase events carry only `ts`."""
    ev: Dict[str, Any] = {
        "task_id": task_id,
        "name": name,
        "type": kind,
        "state": state,
        "ts": time.time() if ts is None else ts,
        "worker_id": worker_id,
        "node_id": node_id,
    }
    if trace:
        ev["trace"] = trace
    if extra:
        ev.update(extra)
    with _events_lock:
        _events.append(ev)
        if len(_events) > _EVENTS_CAP:
            del _events[: _EVENTS_CAP // 2]


def drain_events() -> List[dict]:
    """Take the buffered events (called by the housekeeping flusher)."""
    global _events
    if not _events:
        return []
    with _events_lock:
        out, _events = _events, []
    return out


def restage_events(evs: List[dict]) -> None:
    """Put drained events back (head unreachable at send time)."""
    if not evs:
        return
    with _events_lock:
        _events[:0] = evs


# ------------------------------------------------------------ trace context
def is_enabled() -> bool:
    return _enabled


def new_trace_id() -> str:
    return os.urandom(8).hex()


def new_span_id() -> str:
    return os.urandom(4).hex()


def current() -> Optional[Dict[str, str]]:
    """The ambient trace context of this thread/coroutine (None = no trace)."""
    return _ctx.get()


def begin_task_trace(
    task_id: str, name: str, kind: str, worker_id: str, node_id: str
) -> Optional[Dict[str, str]]:
    """Mint the submit span for a task submission under the ambient trace
    (a fresh trace at the root) and record its SUBMITTED event.  Returns the
    wire context: {"tid", "sid"} — the executing side parents on "sid".

    Returns None when there is nothing to trace: a worker process armed only
    by an incoming traced task (hook set, tracing not locally enabled) must
    not mint fresh root traces for unrelated submissions."""
    parent = _ctx.get()
    if parent is None:
        if not _enabled:
            return None
        ctx = {"tid": new_trace_id(), "sid": new_span_id()}
    else:
        ctx = {"tid": parent["tid"], "sid": new_span_id(), "psid": parent["sid"]}
    record_task_event(
        task_id, name, kind, "SUBMITTED",
        trace=ctx, worker_id=worker_id, node_id=node_id,
    )
    return {"tid": ctx["tid"], "sid": ctx["sid"]}


def _ensure_hook() -> None:
    """Arm the submission-side hook in this process.  Workers never call
    enable(); receiving a traced task is the signal that this process's
    nested submissions must propagate context."""
    from ..core import worker as worker_mod

    if worker_mod.TRACE_HOOK is None:
        worker_mod.TRACE_HOOK = sys.modules[__name__]


def push_execution(tr: Dict[str, str]):
    """Install a received wire context as the ambient context of the
    executing thread/coroutine (the execute span parents on the submit
    span).  Returns a token for `pop_execution`."""
    _ensure_hook()
    ctx = {"tid": tr["tid"], "sid": new_span_id(), "psid": tr["sid"]}
    return _ctx.set(ctx)


def pop_execution(token) -> None:
    _ctx.reset(token)


# --------------------------------------------------------- W3C traceparent
# Serve HTTP requests carry trace context as a standard `traceparent`
# header (https://www.w3.org/TR/trace-context/): 00-<32hex>-<16hex>-<flags>.
# Internal ids are shorter (16-hex trace, 8-hex span) so formatting
# zero-pads; parsing keeps the incoming ids verbatim — ids are opaque
# strings everywhere in this codebase, so an externally-minted 32-hex trace
# id flows through tasks, spans and the flight recorder unchanged.
def format_traceparent(tr: Dict[str, str]) -> str:
    tid = (tr.get("tid") or "")[:32].ljust(32, "0")
    sid = (tr.get("sid") or "")[:16].ljust(16, "0")
    return f"00-{tid}-{sid}-01"


def parse_traceparent(header: Optional[str]) -> Optional[Dict[str, str]]:
    """Parse an incoming traceparent into a wire context {"tid", "sid"}
    (the receiving side parents on "sid", exactly like a task's tr field).
    Returns None on anything malformed — a bad header is not an error,
    just an untraced request."""
    if not header:
        return None
    parts = header.strip().lower().split("-")
    if len(parts) < 4 or len(parts[1]) != 32 or len(parts[2]) != 16:
        return None
    tid, sid = parts[1], parts[2]
    try:
        int(tid, 16), int(sid, 16)
    except ValueError:
        return None
    if int(tid, 16) == 0 or int(sid, 16) == 0:
        return None
    # strip the zero-padding format_traceparent added so internally-minted
    # ids round-trip to their native width
    if tid.endswith("0" * 16) and int(tid[:16], 16):
        tid = tid[:16]
    if sid.endswith("0" * 8) and int(sid[:8], 16):
        sid = sid[:8]
    return {"tid": tid, "sid": sid}


# ------------------------------------------------------------------ enable
def enable():
    """Idempotently enable tracing: trace-context generation + propagation,
    lifecycle phase events, submit-latency/span histograms, and (when jax is
    already loaded) the JAX profiling hooks."""
    global _enabled, _patched, _submit_hist, _span_hist
    with _patch_lock:
        already_patched, _patched = _patched, True
        if _enabled:
            return
        _enabled = True
        _submit_hist = metrics.Histogram(
            "ca_trace_submit_latency_seconds",
            "client-side remote() submission latency",
            tag_keys=("kind", "name"),
        )
        _span_hist = metrics.Histogram(
            "ca_trace_span_seconds", "custom app spans", tag_keys=("name",)
        )

    # submission-side trace hook: core/worker.py checks this module ref with
    # one attribute load + branch per submission (no call, no allocation on
    # the disabled path)
    from ..core import worker as worker_mod

    worker_mod.TRACE_HOOK = sys.modules[__name__]

    if "jax" in sys.modules:
        enable_jax_profiling()

    if already_patched:
        return

    from ..core import actor as actor_mod
    from ..core import remote_function as rf_mod

    orig_task = rf_mod.RemoteFunction._remote

    def traced_task(self, args, kwargs, opts):
        if not _enabled:
            return orig_task(self, args, kwargs, opts)
        t0 = time.perf_counter()
        try:
            return orig_task(self, args, kwargs, opts)
        finally:
            _submit_hist.observe(
                time.perf_counter() - t0,
                {"kind": "task", "name": getattr(self._function, "__name__", "?")},
            )

    rf_mod.RemoteFunction._remote = traced_task

    orig_actor = actor_mod.ActorHandle._submit

    def traced_actor(self, method, args, kwargs, opts):
        if not _enabled:
            return orig_actor(self, method, args, kwargs, opts)
        t0 = time.perf_counter()
        try:
            return orig_actor(self, method, args, kwargs, opts)
        finally:
            _submit_hist.observe(
                time.perf_counter() - t0, {"kind": "actor", "name": method}
            )

    actor_mod.ActorHandle._submit = traced_actor


def disable():
    """Turn tracing back off (the monkeypatches stay installed but inert)."""
    global _enabled
    _enabled = False
    from ..core import worker as worker_mod

    worker_mod.TRACE_HOOK = None


# -------------------------------------------------------------------- spans
_annotation_cls = None  # jax.profiler.TraceAnnotation, once jax is loaded


def _trace_annotation():
    """`jax.profiler.TraceAnnotation` if jax is loaded in this process (never
    imported from here: a process without jax has no profiler to write to)."""
    global _annotation_cls
    if _annotation_cls is None:
        jax = sys.modules.get("jax")
        # a half-imported jax has no profiler yet: look again next time
        _annotation_cls = getattr(getattr(jax, "profiler", None), "TraceAnnotation", None)
    return _annotation_cls


class span:
    """Record a custom application span: `with span("name", key=scalar):`.

    Event sink: attaches to the ambient trace context (the executing task's
    trace inside a worker; spans nest), lands in the lifecycle event buffer
    for `timeline()` assembly with `attrs` as extra keys, and observes the
    ca_trace_span_seconds histogram.  Active when tracing is locally enabled
    OR the span runs inside a traced execution (worker processes never call
    enable(); the ambient context is the signal there).  An inactive span
    installs NO context — otherwise a disabled-tracing span block would make
    every nested span/remote() look traced and leak events onto the wire —
    and takes no lock and touches no histogram or buffer.

    Profiler sink: where jax is loaded, the block is also a
    `jax.profiler.TraceAnnotation(name, **attrs)`, which the profiler writes
    beside the device's operations while a profiler session runs and which
    costs a flag test otherwise.

    `attrs` are small scalars whose names are no field of a SPAN event
    (name, type, state, ts, trace, start, end, mono, worker_id, node_id);
    `set(**attrs)` adds what is known only inside the block.  `with ... as
    ctx` gives the span's trace context, None while the event sink is off."""

    __slots__ = ("name", "attrs", "ctx", "_token", "_annotation", "_t0", "_p0")

    def __init__(self, name: str, **attrs: Any):
        self.name = name
        self.attrs = attrs
        self.ctx = self._token = self._annotation = None

    def set(self, **attrs: Any) -> None:
        if self._annotation is not None:
            self._annotation.set_metadata(**attrs)
        if self.ctx is not None:
            self.attrs.update(attrs)

    def __enter__(self) -> Optional[Dict[str, str]]:
        parent = _ctx.get()
        if _enabled or parent is not None:
            if parent is None:
                self.ctx = {"tid": new_trace_id(), "sid": new_span_id()}
            else:
                self.ctx = {"tid": parent["tid"], "sid": new_span_id(), "psid": parent["sid"]}
            self._token = _ctx.set(self.ctx)
            self._t0 = time.time()
            self._p0 = time.monotonic()
        annotation = _trace_annotation()
        if annotation is not None:
            self._annotation = annotation(self.name, **self.attrs)
            self._annotation.__enter__()
        return self.ctx

    def __exit__(self, *exc) -> bool:
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
        if self.ctx is None:
            return False
        dur = time.monotonic() - self._p0
        _ctx.reset(self._token)
        _record_span(self.name, self.ctx, self._t0, dur, self._p0, self.attrs)
        return False


def _record_span(name: str, ctx: Dict[str, str], start: float, dur: float,
                 mono: float, attrs: Dict[str, Any]) -> None:
    """One finished span into the event buffer (and the span histogram):
    `start` on the wall clock, `mono` the same instant on the monotonic."""
    # after disable() the histogram must stop mutating too, not just the
    # event stream: _span_hist exists only once enable() has run
    if _span_hist is not None:
        _span_hist.observe(dur, {"name": name})
    w = _current_worker()
    record_task_event(
        "", name, "span", "SPAN",
        trace=ctx,
        worker_id=w.client_id if w is not None else None,
        node_id=w.node_id if w is not None else None,
        **{**attrs, "start": start, "end": start + dur, "mono": mono},
    )


def child_context(parent: Optional[Dict[str, str]] = None) -> Optional[Dict[str, str]]:
    """Mint the context of a span under `parent` (default: the ambient
    context; a fresh trace where there is none and tracing is enabled), for
    a span that `emit(..., own=ctx)` writes once it has ended.  None when
    there is nothing to trace."""
    if parent is None:
        parent = _ctx.get()
    if parent is None:
        if not _enabled:
            return None
        return {"tid": new_trace_id(), "sid": new_span_id()}
    return {"tid": parent["tid"], "sid": new_span_id(), "psid": parent["sid"]}


def emit(name: str, t0: float, t1: float, ctx: Optional[Dict[str, str]] = None,
         *, own: Optional[Dict[str, str]] = None, **attrs: Any) -> Optional[Dict[str, str]]:
    """Write a finished span from two `time.monotonic()` stamps: what a
    coroutine, or a wait with no block to wrap, uses in place of `with
    span(...)`.  The span is a child of `ctx`, or of the ambient context; or
    it is `own`, a context `child_context()` minted when the span began.
    Event sink only (the profiler takes no event after the fact).  Returns the
    span's context, None when nothing was written: tracing is not enabled
    and no context is there."""
    if own is None:
        own = child_context(ctx)
        if own is None:
            return None
    now_mono, now_wall = time.monotonic(), time.time()
    _record_span(name, own, now_wall - (now_mono - t0), max(0.0, t1 - t0), t0, attrs)
    return own


class under:
    """`with under(ctx):` installs a context as the ambient one of this
    thread for the block: the own context of a span that is still open
    (`child_context`), so that what the block does becomes its child.  An
    executor's thread does not inherit its submitter's contextvars; this is
    how a request's trace reaches it.  `under(None)` does nothing."""

    __slots__ = ("ctx", "_token")

    def __init__(self, ctx: Optional[Dict[str, str]]):
        self.ctx = ctx
        self._token = None

    def __enter__(self) -> Optional[Dict[str, str]]:
        if self.ctx is not None:
            _ensure_hook()
            self._token = _ctx.set(self.ctx)
        return self.ctx

    def __exit__(self, *exc) -> bool:
        if self._token is not None:
            try:
                _ctx.reset(self._token)
            except ValueError:
                pass  # a generator finalised on another thread than it ran on
            self._token = None
        return False


# ---------------------------------------------------------------- JAX hooks
_jax_hooked = False

# jax.monitoring's events (jax 0.9) by their exact names, each with the total it
# adds to.  A program new to the process is traced to a jaxpr, lowered to a
# module and handed to the backend, which compiles it or fetches it from the
# persistent cache (`backend_s` holds either; `cache_fetch_s` is the fetch alone
# and lies inside it).  `/jax/compilation_cache/compile_time_saved_sec` is no time
# spent and is left out.
_JAX_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_JAX_DURATIONS = {
    _JAX_TRACE: "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    "/jax/core/compile/backend_compile_duration": "backend_s",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_fetch_s",
}
_JAX_COUNTS = {
    "/jax/compilation_cache/cache_hits": "cache_hits",
    # a program compiled anew and written to the persistent cache (jax writes
    # none that compiled in under its `persistent_cache_min_compile_time_secs`)
    "/jax/compilation_cache/cache_misses": "cache_misses",
}
JAX_BUILD_KINDS = (*_JAX_DURATIONS.values(), "builds", *_JAX_COUNTS.values())
# JAX builds a program on the thread that calls it, and reports there: the totals
# are kept a thread (`threading.get_ident()`), each written by its own thread alone
_build_totals: Dict[int, Dict[str, float]] = {}
_build_sinks: Dict[int, Callable[[str, float], None]] = {}
_trace_depth: Dict[int, int] = {}  # jaxpr traces open on a thread: a jit inside a jit


def jax_build_totals(thread: Optional[int] = None) -> Dict[str, float]:
    """What the programs built on one thread (default: the caller's) have cost
    since `enable_jax_profiling()`: seconds tracing (`trace_s`; a jit traced
    inside another counts once, in the outer one), lowering (`lower_s`) and in
    the backend (`backend_s`: a compilation, or a fetch from the persistent
    cache, whose own seconds are `cache_fetch_s`), and counts: `builds`
    (programs handed to the backend), `cache_hits`, `cache_misses`.  A call of
    a shape the process has seen adds nothing."""
    totals = _build_totals.get(threading.get_ident() if thread is None else thread, {})
    return {kind: totals.get(kind, 0.0) for kind in JAX_BUILD_KINDS}


def on_jax_build(thread: int, fn: Optional[Callable[[str, float], None]]) -> None:
    """Register `fn(kind, amount)` for the programs built on one thread: it
    runs on that thread, at the event, once for each total of
    `jax_build_totals` that the event adds to (`amount`: seconds, or 1 for a
    count).  One sink a thread; None takes it away.  Whoever owns a thread that
    builds programs keeps its own counts current this way, with no statement on
    the path that calls them.  Registering starts the thread's totals at zero:
    an ident may have been a thread's that has ended."""
    if fn is None:
        _build_sinks.pop(thread, None)
    else:
        _build_totals.pop(thread, None)
        _build_sinks[thread] = fn


def _count_build(kind: str, amount: float) -> None:
    ident = threading.get_ident()
    totals = _build_totals.setdefault(ident, {})
    totals[kind] = totals.get(kind, 0.0) + amount
    sink = _build_sinks.get(ident)
    if sink is not None:
        sink(kind, amount)


def enable_jax_profiling() -> bool:
    """Surface device-side cost in the same pipeline, from jax.monitoring's
    events: what building programs costs, by thread (`jax_build_totals`,
    `on_jax_build`) and for the cluster's metrics: `ca_jax_compiles_total` (a
    program that was not in this process yet: compiled, or fetched from the
    persistent cache), `ca_jax_cache_hits_total`, `ca_jax_cache_misses_total`,
    and the histogram `ca_jax_compile_seconds{event}` (`trace`, `lower`,
    `backend`, `cache_fetch`), whose sums `util/state.serve_plane()["jax"]` and
    `ca status` print; and `ca_device_memory_bytes` gauges sampled at each
    metrics flush.  The engine and the train backend call it once they have
    imported jax; a profiler session has the compilations themselves on its
    own clock.  Returns False when jax (or its monitoring API) is unavailable —
    callers treat that as "nothing to profile", never an error."""
    global _jax_hooked
    if _jax_hooked:
        return True
    try:
        import jax
        from jax import monitoring
    except Exception:
        return False

    compile_hist = metrics.Histogram(
        "ca_jax_compile_seconds",
        "seconds building a program new to the process, by part: trace, lower, backend "
        "(compiled, or fetched from the persistent cache), cache_fetch (the fetch alone)",
        tag_keys=("event",),
    )
    counters = {
        "builds": metrics.Counter(
            "ca_jax_compiles_total",
            "programs this process compiled or fetched from the persistent cache",
        ),
        "cache_hits": metrics.Counter(
            "ca_jax_cache_hits_total", "programs fetched from jax's persistent compilation cache",
        ),
        "cache_misses": metrics.Counter(
            "ca_jax_cache_misses_total",
            "programs compiled anew and written to jax's persistent compilation cache",
        ),
    }
    hist_tags = {kind: {"event": kind[: -len("_s")]} for kind in _JAX_DURATIONS.values()}

    def _on_trace_start(event: str, value, **kw):
        if event == _JAX_TRACE:
            ident = threading.get_ident()
            _trace_depth[ident] = _trace_depth.get(ident, 0) + 1

    def _on_duration(event: str, duration: float, **kw):
        kind = _JAX_DURATIONS.get(event)
        if kind is None:
            return
        try:
            if event == _JAX_TRACE:
                # a trace that began before the hook was armed ends at depth 0
                ident = threading.get_ident()
                depth = _trace_depth[ident] = max(_trace_depth.get(ident, 0) - 1, 0)
                if depth:
                    return  # inside another trace, which holds these seconds
            compile_hist.observe(duration, hist_tags[kind])
            _count_build(kind, duration)
            if kind == "backend_s":
                counters["builds"].inc()
                _count_build("builds", 1)
        except Exception:
            return

    def _on_event(event: str, **kw):
        kind = _JAX_COUNTS.get(event)
        if kind is None:
            return
        try:
            counters[kind].inc()
            _count_build(kind, 1)
        except Exception:
            return

    try:
        monitoring.register_scalar_listener(_on_trace_start)
        monitoring.register_event_duration_secs_listener(_on_duration)
        monitoring.register_event_listener(_on_event)
    except Exception:
        return False

    mem_gauge = metrics.Gauge(
        "ca_device_memory_bytes",
        "per-device memory stats from the jax backend",
        tag_keys=("device", "kind"),
    )

    def _sample_device_memory():
        try:
            devices = jax.local_devices()
        except Exception:
            return
        for d in devices:
            try:
                stats = d.memory_stats() or {}
            except Exception:
                continue
            for key in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit"):
                if key in stats:
                    mem_gauge.set(float(stats[key]), {"device": str(d), "kind": key})

    metrics.register_flush_hook(_sample_device_memory)
    _jax_hooked = True
    return True
