"""Replica actor: hosts one copy of a deployment's user callable (analogue of
python/ray/serve/_private/replica.py Replica + UserCallableWrapper).
"""

from __future__ import annotations

import asyncio
import contextvars
import inspect
import threading
import time
from typing import Any, Callable, Dict, Optional

from ..util import tracing

_request_context: contextvars.ContextVar = contextvars.ContextVar(
    "ca_serve_request_context", default=None
)


class RequestContext:
    def __init__(self, request_id: str = "", multiplexed_model_id: str = "",
                 deployment: str = ""):
        self.request_id = request_id
        self.multiplexed_model_id = multiplexed_model_id
        # "<app>/<deployment>", the tag of this replica's phase observations;
        # set while the deployment's own constructor runs too
        self.deployment = deployment


def get_request_context() -> RequestContext:
    ctx = _request_context.get()
    return ctx if ctx is not None else RequestContext()


_metrics_cache = {}


def _serve_metrics():
    """Per-request Prometheus series (reference serve metrics:
    ray_serve_deployment_request_counter / _processing_latency_ms — here
    ca_serve_requests_total / ca_serve_request_latency_seconds /
    ca_serve_request_errors_total, tagged by deployment).  Lazy: replicas
    that never serve a request register nothing."""
    if not _metrics_cache:
        from ..util import metrics as m

        _metrics_cache["requests"] = m.Counter(
            "ca_serve_requests_total", "serve requests handled",
            tag_keys=("deployment",),
        )
        _metrics_cache["errors"] = m.Counter(
            "ca_serve_request_errors_total", "serve requests errored",
            tag_keys=("deployment",),
        )
        _metrics_cache["latency"] = m.Histogram(
            "ca_serve_request_latency_seconds", "serve request latency",
            boundaries=[0.001, 0.005, 0.02, 0.05, 0.1, 0.25, 1.0, 5.0],
            tag_keys=("deployment",),
        )
    return _metrics_cache


# ------------------------------------------------------------ request phases
# One histogram for every boundary a request crosses on its way from the
# proxy's socket to the token's write: ca_serve_phase_seconds{deployment,
# phase}, observed once where the phase's span is written, whether or not the
# request is traced.  `phase` is the span's name (ARCHITECTURE.md lists them).
PHASE_BOUNDARIES = [
    m * 10.0 ** e for e in range(-4, 1) for m in (1.0, 1.5, 2.0, 3.0, 5.0, 7.0)
] + [10.0, 15.0, 20.0, 30.0]  # 0.1 ms to 30 s, six steps a decade
_phase_tags: Dict[tuple, Dict[str, str]] = {}


def observe_phase(deployment: str, phase: str, seconds: float) -> None:
    hist = _metrics_cache.get("phase")
    if hist is None:
        from ..util import metrics as m

        hist = _metrics_cache["phase"] = m.Histogram(
            "ca_serve_phase_seconds",
            "time a serve request spent in one phase of its way (the span of the same name)",
            boundaries=PHASE_BOUNDARIES, tag_keys=("deployment", "phase"),
        )
    tags = _phase_tags.get((deployment, phase))
    if tags is None:
        tags = _phase_tags[(deployment, phase)] = {"deployment": deployment, "phase": phase}
    hist.observe(seconds, tags)


def emit_phase(deployment: str, name: str, t0: float, t1: float, ctx=None, *,
               own=None, **attrs):
    """A phase from two `time.monotonic()` stamps: one observation always, a
    span (`tracing.emit`) where the request is traced."""
    observe_phase(deployment, name, t1 - t0)
    return tracing.emit(name, t0, t1, ctx, own=own, **attrs)


class phase(tracing.span):
    """A phase that is one unbroken block of one thread: `tracing.span`
    (both sinks) and one observation of the histogram."""

    __slots__ = ("deployment", "_m0")

    def __init__(self, name: str, deployment: str, **attrs: Any):
        super().__init__(name, **attrs)
        self.deployment = deployment

    def __enter__(self):
        self._m0 = time.monotonic()
        return super().__enter__()

    def __exit__(self, *exc) -> bool:
        observe_phase(self.deployment, self.name, time.monotonic() - self._m0)
        return super().__exit__(*exc)


_pool_lock = threading.Lock()
_pool_pending = 0


def run_in_pool(loop, fn: Callable[[], Any], *, on_wait: Callable[[float, float], None],
                ctx=None, pending_gauge=None):
    """`loop.run_in_executor(None, fn)` with the wait for a pool thread
    measured where it happens: a stamp at the submission, one as the
    function's first line on the pool thread, `on_wait(t_submit, t_start)`
    called there with both.  `pending_gauge` is kept at the work handed to the
    executor and not yet started; `ctx` is installed as the thread's ambient
    trace context (an executor's thread inherits none)."""
    global _pool_pending
    t_submit = time.monotonic()
    if pending_gauge is not None:
        with _pool_lock:
            _pool_pending += 1
            pending_gauge.set(_pool_pending)

    def run():
        global _pool_pending
        t_start = time.monotonic()
        if pending_gauge is not None:
            with _pool_lock:
                _pool_pending -= 1
                pending_gauge.set(_pool_pending)
        with tracing.under(ctx):
            on_wait(t_submit, t_start)
            return fn()

    return loop.run_in_executor(None, run)


class Replica:
    """One replica process. Methods are async so many requests interleave on
    the actor's event loop up to max_ongoing_requests."""

    def __init__(
        self,
        deployment_def,
        init_args: tuple,
        init_kwargs: Dict[str, Any],
        user_config: Optional[Dict[str, Any]],
        replica_id: str,
        handle_specs: Optional[Dict[str, Any]] = None,
        deployment_name: Optional[str] = None,
    ):
        t_start = time.monotonic()  # where span `serve.replica.start` starts
        # late-bind nested DeploymentHandles (model composition): bound
        # sub-deployments arrive as specs and materialize into handles here
        from .router import DeploymentHandle

        def resolve(v):
            if isinstance(v, dict) and v.get("__ca_serve_handle__"):
                return DeploymentHandle(v["app"], v["deployment"])
            if isinstance(v, list):
                return [resolve(x) for x in v]
            if isinstance(v, tuple):
                return tuple(resolve(x) for x in v)
            if isinstance(v, dict):
                return {k: resolve(x) for k, x in v.items()}
            return v

        init_args = tuple(resolve(a) for a in init_args)
        init_kwargs = {k: resolve(v) for k, v in init_kwargs.items()}
        self.replica_id = replica_id
        self._metric_tags = {"deployment": deployment_name or replica_id}
        # the phase histogram's tag, spelt as the proxy and the router spell it
        self._phase_dep = (deployment_name or replica_id).replace(":", "/", 1)
        self._is_function = not inspect.isclass(deployment_def)
        if self._is_function:
            self.instance = deployment_def
        else:
            # the constructor can ask which deployment it is being built for
            token = _request_context.set(RequestContext(deployment=self._phase_dep))
            # span `serve.replica.start`: this actor's creation to its user class
            # constructed, whose own spans are its children (nothing where the
            # creation is not traced)
            own = tracing.child_context()
            try:
                with tracing.under(own):
                    self.instance = deployment_def(*init_args, **init_kwargs)
            finally:
                _request_context.reset(token)
            if own is not None:
                tracing.emit("serve.replica.start", t_start, time.monotonic(), own=own,
                             deployment=self._phase_dep)
        self.num_ongoing = 0
        self.total_requests = 0
        if user_config is not None:
            self._apply_user_config(user_config)

    def _apply_user_config(self, cfg: Dict[str, Any]):
        fn = getattr(self.instance, "reconfigure", None)
        if fn is not None:
            fn(cfg)

    # ----------------------------------------------------------- control API
    def reconfigure(self, user_config: Dict[str, Any]):
        self._apply_user_config(user_config)
        return "ok"

    def check_health(self) -> str:
        fn = getattr(self.instance, "check_health", None)
        if fn is not None:
            fn()
        return "ok"

    def get_queue_len(self) -> int:
        return self.num_ongoing

    def telemetry(self) -> Dict[str, Any]:
        """One RPC for the controller's reconcile pass: liveness (raises if
        the user's check_health hook does), ongoing-request count (router
        P2C signal, drain retirement gate, autoscale input), and the hosting
        node (drain detection)."""
        fn = getattr(self.instance, "check_health", None)
        if fn is not None:
            fn()
        try:
            from ..core.worker import global_worker

            node_id = global_worker().node_id
        except Exception:
            node_id = None
        return {
            "queue_len": self.num_ongoing,
            "node_id": node_id,
            "total": self.total_requests,
        }

    def stats(self) -> Dict[str, Any]:
        return {
            "replica_id": self.replica_id,
            "num_ongoing": self.num_ongoing,
            "total": self.total_requests,
        }

    def prepare_shutdown(self) -> str:
        """Run user cleanup before the controller hard-kills the process —
        GC finalizers never fire on kill()."""
        fn = getattr(self.instance, "__del__", None)
        if fn is not None:
            try:
                fn()
            except Exception:
                pass
        return "ok"

    # ----------------------------------------------------------- request path
    def _enter_request(self, meta: Dict[str, Any]):
        """What both request paths do first.  Returns what `_leave_request`
        takes: the entry stamp, `ongoing` at entry, the context token and
        `serve.replica.handle`'s own trace context, minted now so that the
        user method's spans name it as their parent (None when untraced)."""
        ongoing = self.num_ongoing
        self.num_ongoing += 1
        self.total_requests += 1
        _serve_metrics()["requests"].inc(1, tags=self._metric_tags)
        token = _request_context.set(
            RequestContext(
                request_id=meta.get("request_id", ""),
                multiplexed_model_id=meta.get("multiplexed_model_id", ""),
                deployment=self._phase_dep,
            )
        )
        return time.monotonic(), ongoing, token, tracing.child_context()

    def _leave_request(self, entered, method: str, executor_wait_s: float = 0.0):
        t0, ongoing, token, own = entered
        t1 = time.monotonic()
        _serve_metrics()["latency"].observe(t1 - t0, tags=self._metric_tags)
        emit_phase(
            self._phase_dep, "serve.replica.handle", t0, t1, own=own,
            method=method, ongoing=ongoing, executor_wait_ms=1e3 * executor_wait_s,
        )
        _request_context.reset(token)
        self.num_ongoing -= 1

    async def handle_request(self, meta: Dict[str, Any], *args, **kwargs):
        entered = self._enter_request(meta)
        own = entered[-1]
        method_name = meta.get("method", "__call__")
        waited = [0.0]
        try:
            fn = self.instance if self._is_function else getattr(self.instance, method_name)
            # a coroutine holds no span across its awaits: the ambient context
            # is handle's own for the user method, the span is written on leaving
            with tracing.under(own):
                if inspect.iscoroutinefunction(fn):
                    return await fn(*args, **kwargs)
                # sync user code must not block the replica's event loop
                ctx = contextvars.copy_context()

            def on_wait(t_submit: float, t_start: float) -> None:
                waited[0] = t_start - t_submit

            return await run_in_pool(
                asyncio.get_running_loop(), lambda: ctx.run(fn, *args, **kwargs),
                on_wait=on_wait,
            )
        except Exception:
            # Exception only: client cancellation (CancelledError /
            # GeneratorExit are BaseException) is not a deployment error and
            # must not feed the errors series alerts watch
            _serve_metrics()["errors"].inc(1, tags=self._metric_tags)
            raise
        finally:
            self._leave_request(entered, method_name, waited[0])

    def handle_request_streaming(self, meta: Dict[str, Any], *args, **kwargs):
        """Generator twin of handle_request: iterates the user method's
        generator so items stream back as ObjectRefGenerator frames
        (reference replica.py streaming path)."""
        entered = self._enter_request(meta)
        method_name = meta.get("method", "__call__")
        try:
            fn = self.instance if self._is_function else getattr(self.instance, method_name)
            # the worker runs this generator on one thread of its own, and a
            # generator shares its caller's context: handle's own stays
            # ambient between the items too
            with tracing.under(entered[-1]):
                out = fn(*args, **kwargs)
                if not hasattr(out, "__iter__") or isinstance(out, (str, bytes, dict)):
                    yield out  # non-generator result: one-item stream
                    return
                yield from out
        except Exception as e:
            # Exception only: client cancellation (CancelledError /
            # GeneratorExit are BaseException) is not a deployment error and
            # must not feed the errors series alerts watch.  TaskCancelledError
            # is the consumer abandoning the stream (proxy SSE disconnect) —
            # same story, different spelling.
            from ..core.errors import TaskCancelledError

            if not isinstance(e, TaskCancelledError):
                _serve_metrics()["errors"].inc(1, tags=self._metric_tags)
            raise
        finally:
            # latency covers the full stream (first byte to exhaustion)
            self._leave_request(entered, method_name)
