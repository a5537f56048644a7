"""serve.llm: online LLM serving deployment (analogue of the reference's
python/ray/serve/llm.py build_openai_app — compact: one deployment class with
request batching over the compiled generate path).
"""

from __future__ import annotations

import contextvars
import functools
import time
from typing import Any, Dict, Iterator, Optional

from ..util import tracing
from .processor import (
    ByteTokenizer,
    ModelSpec,
    ProcessorConfig,
    _InferenceWorker,
    engine_placement,
    model_params,
)


def _parse_body(request) -> Dict[str, Any]:
    """Accept a serve HTTP Request, a dict, or a bare prompt string — the
    one body parser every LLM deployment method shares."""
    from ..serve import Request

    if isinstance(request, Request):
        return request.json() if request.method == "POST" else dict(request.query_params)
    return request if isinstance(request, dict) else {"prompt": str(request)}


class LLMServer:
    """Serve deployment hosting one model; understands dict and HTTP requests:
       {"prompt": "...", "max_new_tokens": 16} -> {"generated_text": "..."}"""

    def __init__(self, config: ProcessorConfig):
        import numpy as np

        self.config = config
        self.worker = _InferenceWorker(config)
        self.np = np

    def reconfigure(self, cfg: Dict[str, Any]):
        if "max_new_tokens" in cfg:
            self.config.max_new_tokens = int(cfg["max_new_tokens"])
        if "temperature" in cfg:
            self.config.temperature = float(cfg["temperature"])

    def __call__(self, request) -> Dict[str, Any]:
        body = _parse_body(request)
        prompt = body.get("prompt", "")
        batch = {"prompt": self.np.asarray([prompt], dtype=object)}
        overrides = {}
        if "max_new_tokens" in body:
            overrides["max_new_tokens"] = int(body["max_new_tokens"])
        if "temperature" in body:
            overrides["temperature"] = float(body["temperature"])
        if "top_k" in body:
            overrides["top_k"] = int(body["top_k"])
        if "top_p" in body:
            overrides["top_p"] = float(body["top_p"])
        out = self.worker(batch, **overrides)
        return {
            "prompt": prompt,
            "generated_text": str(out["generated_text"][0]),
            "num_generated_tokens": int(len(out["generated_tokens"][0])),
        }

    def stream(self, request):
        """Token-streaming twin of __call__: yields one
        {"token_id", "text"} dict per sampled token.  Reaches HTTP clients
        as SSE via the proxy's text/event-stream path (serve streaming
        handles end-to-end: replica generator -> streaming actor frames ->
        one SSE event per token)."""
        body = _parse_body(request)
        kwargs = {}
        if "max_new_tokens" in body:
            kwargs["max_new_tokens"] = int(body["max_new_tokens"])
        if "temperature" in body:
            kwargs["temperature"] = float(body["temperature"])
        if "top_k" in body:
            kwargs["top_k"] = int(body["top_k"])
        if "top_p" in body:
            kwargs["top_p"] = float(body["top_p"])
        yield from self.worker.stream(body.get("prompt", ""), **kwargs)


def build_llm_deployment(
    config: Optional[ProcessorConfig] = None,
    *,
    num_replicas: int = 1,
    num_tpus: float = 0.0,
    name: str = "LLMServer",
):
    """Returns a bound serve Application for `serve.run`.  num_tpus is each
    replica's chip request; with the default 0 a replica runs in the CPU
    worker pool, and refuses to start on a host that has TPU chips."""
    from .. import serve

    config = config or ProcessorConfig()
    dep = serve.deployment(
        LLMServer,
        name=name,
        num_replicas=num_replicas,
        num_tpus=num_tpus,
        max_ongoing_requests=4,
    )
    return dep.bind(config)


class _StreamMeter:
    """What one token stream sums on its way out of the replica: no span a
    token, two stamps (before the write or the `yield`, after it)."""

    __slots__ = ("rid", "transport", "t0", "tokens", "first_token_s", "write_wait_s",
                 "write_wait_max_s", "cancelled")

    def __init__(self, rid: int, transport: str):
        self.rid, self.transport = rid, transport
        self.t0 = time.monotonic()  # submit's return
        self.tokens = 0
        self.first_token_s = self.write_wait_s = self.write_wait_max_s = 0.0
        self.cancelled = True  # until the stream's end is reached

    def wrote(self, t_before: float) -> None:
        """The consumer took a token: `ch.write` or the `yield` returned."""
        waited = time.monotonic() - t_before
        self.tokens += 1
        self.write_wait_s += waited
        if waited > self.write_wait_max_s:
            self.write_wait_max_s = waited

    def attrs(self) -> Dict[str, Any]:
        return {
            "rid": self.rid, "transport": self.transport, "tokens": self.tokens,
            "first_token_ms": 1e3 * self.first_token_s,
            "write_wait_ms": 1e3 * self.write_wait_s,
            "write_wait_max_ms": 1e3 * self.write_wait_max_s,
            "cancelled": self.cancelled,
        }


class ContinuousLLMServer:
    """LLM deployment with ITERATION-LEVEL scheduling (the vLLM-engine role
    of the reference's serve.llm): concurrent requests share the decode loop
    through one ContinuousBatcher — a request admits the moment a slot
    frees, instead of waiting for the current static batch to drain.

    One background pump thread drives decode steps; caller threads (the
    replica runs methods concurrently up to max_ongoing_requests) submit and
    wait on per-request events, or consume a token queue when streaming."""

    def __init__(self, config: ProcessorConfig, slots: int = 8):
        import queue
        import threading

        import jax

        from .continuous import ContinuousBatcher, prefill_buckets_for, tree_bytes

        from ..serve.replica import get_request_context, observe_phase, phase

        # the clock of every SPAN event's `mono` and of a load generator's stamps
        t_init = time.monotonic()
        # jax is loaded from here on: compilations and device memory reach
        # the cluster's metrics from the process that holds the chip
        tracing.enable_jax_profiling()
        built = tracing.jax_build_totals()  # this thread's, before the constructor's own
        # "<app>/<deployment>" where a replica builds this ("" in-process):
        # the tag its requests' phases are counted under
        self._deployment = get_request_context().deployment
        self._phase = functools.partial(phase, deployment=self._deployment)
        self.config = config
        self.tok = config.tokenizer or ByteTokenizer()
        tcfg = config.model.transformer_config(self.tok.vocab_size)
        t_max = config.max_prompt_len + config.max_new_tokens
        sp = tracing.span("llm.replica.init", slots=slots, t_max=t_max)
        with sp:
            # the first call that needs the backend starts it (seconds, on a TPU):
            # here, so that the weights' span below holds the weights alone
            jax.devices()
            t_backend = time.monotonic()
            params = model_params(config.model, tcfg)
            t_params = time.monotonic()
            self.engine_device = engine_placement(params)
            print(
                "[llm] engine on {platform} ({device_kind}) x{count}".format(
                    **self.engine_device
                ),
                flush=True,
            )
            # the batcher's own bucket ladder, up to the longest prompt this
            # deployment admits: a short prompt prefills a short program
            with tracing.span("llm.replica.init.batcher"):
                self.cb = ContinuousBatcher(
                    params, tcfg, slots=slots, t_max=t_max,
                    prefill_buckets=prefill_buckets_for(config.max_prompt_len), top_k=config.top_k,
                    prefix_cache_entries=getattr(config, "prefix_cache_entries", 8),
                    prefix_block=getattr(config, "prefix_block", 16),
                )
                jax.block_until_ready(self.cb.cache)
            # what the constructor's own programs cost (the weights made from a seed
            # are eager operations, each a small program the first time)
            init_build_s = sum(
                now - built[kind] for kind, now in tracing.jax_build_totals().items()
                if kind in ("trace_s", "lower_s", "backend_s"))
            sp.set(
                backend_ms=1e3 * (t_backend - t_init), build_ms=1e3 * init_build_s,
                param_bytes=tree_bytes(params), cache_bytes=tree_bytes(self.cb.cache),
                buckets=len(self.cb.prefill_buckets),
            )
        self.cb.observe_phase = functools.partial(observe_phase, self._deployment)
        self._metrics_synced: dict = {}
        self._lock = threading.Lock()  # batcher is single-threaded inside
        # beside the batcher's own counts: seconds callers waited for that lock
        # in _submit (updated under the lock), and what set-up cost before the
        # first request, written once: the instant the constructor was entered,
        # its seconds, and of them the backend's start, the weights, and the
        # building of its own programs (`program_build_s` and its kin, the
        # programs built on the pump's thread since, are the batcher's own)
        self.cb.stats.update(
            lock_wait_s=0.0, replica_init_mono=t_init, replica_init_s=time.monotonic() - t_init,
            backend_init_s=t_backend - t_init, params_init_s=t_params - t_backend,
            init_build_s=init_build_s,
        )
        self._queues: dict = {}  # request_id -> queue of token ids (+ None EOF)
        self._reqs: dict = {}  # request_id -> Request (done detection)
        self._queue_cls = queue.Queue
        self._stop = False
        self._engine_error: Optional[BaseException] = None
        self._pump = threading.Thread(target=self._pump_loop, daemon=True, name="llm-pump")
        self._pump.start()
        # every program of the batcher's is built on the pump's thread, at its
        # first call with a new shape: what that costs reaches the batcher's
        # counts from jax's own events, and the pump's loop gains no statement
        tracing.on_jax_build(self._pump.ident, self.cb.count_build)

    def check_health(self):
        """Serve controller hook: a dead pump means every request on this
        replica would hang to queue timeout — report it so the controller
        replaces the replica instead."""
        if self._engine_error is not None:
            raise RuntimeError(f"LLM engine pump died: {self._engine_error!r}")

    def close(self):
        """Stop the pump thread (dropping a replica without close() would
        leave it spinning and pinning params + the KV cache forever)."""
        self._stop = True
        if self._pump.is_alive():
            self._pump.join(timeout=5)
        tracing.on_jax_build(self._pump.ident, None)

    def __del__(self):  # best-effort; serve teardown also kills the process
        try:
            self.close()
        except Exception:
            pass

    _llm_metrics: dict = {}  # class-level: one registry entry per process
    _llm_gauges: dict = {}
    # the gauge's part -> the count it is set from (a set-up phase outlasts the
    # 30 s that ca_serve_phase_seconds' buckets end at)
    _READY_PARTS = (("init", "replica_init_s"), ("params", "params_init_s"), ("build", "program_build_s"))

    def _sync_engine_metrics(self):
        """Ship the batcher's counters (prefix-cache hits/misses/tokens
        reused, decode steps; requests submitted, tokens handed out, seconds
        queued, in admit and waiting for the replica's lock) as ca_serve_*
        cluster metrics — the series behind the envelope's "hits skip
        prefill" claim; mean queue wait and mean admit are each two rates.
        What set-up cost goes as one gauge, ca_serve_replica_ready_seconds
        {deployment, part}: the constructor (`init`), of it the weights
        (`params`), and every program built since (`build`)."""
        if not self._llm_metrics:
            from ..util import metrics as m

            for key, name, desc in (
                ("prefix_hits", "ca_serve_prefix_hits_total",
                 "LLM admits that reused cached prefix KV rows"),
                ("prefix_misses", "ca_serve_prefix_misses_total",
                 "LLM admits that prefilled (and cached) their prefix"),
                ("prefix_tokens_reused", "ca_serve_prefix_tokens_reused_total",
                 "prompt tokens whose prefill was skipped via the prefix cache"),
                ("decode_steps", "ca_serve_decode_steps_total",
                 "continuous-batcher decode iterations"),
                ("submitted", "ca_serve_submitted_total",
                 "requests queued on the continuous batcher"),
                ("tokens_out", "ca_serve_tokens_out_total",
                 "tokens the continuous batcher handed out"),
                ("queue_wait_s", "ca_serve_queue_wait_seconds_total",
                 "seconds requests spent queued before their admit began"),
                ("admit_s", "ca_serve_admit_seconds_total",
                 "seconds the decode pump spent admitting requests"),
                ("lock_wait_s", "ca_serve_lock_wait_seconds_total",
                 "seconds submitting callers waited for the replica's lock"),
                ("moe_assignments", "ca_serve_moe_assignments_total",
                 "(token, expert) pairs a layer's routed experts were given"),
                ("prefill_traces", "ca_serve_prefill_traces_total",
                 "LLM admits that traced and compiled a prefill program on the pump's thread"),
                ("ssm_state_bytes", "ca_serve_ssm_state_bytes_total",
                 "bytes of recurrent state the decode steps read and wrote and the admits installed"),
                ("block_passes", "ca_serve_block_passes_total",
                 "passes of one slot's block by a model that generates by blocks (a step is one a live slot)"),
                ("block_tokens_fixed", "ca_serve_block_tokens_fixed_total",
                 "positions those passes fixed, served or past an answer's end"),
                ("sort_steps", "ca_serve_sort_steps_total",
                 "decode steps that sorted the vocabulary: a live request sampled with top-k or top-p"),
                ("steps_ahead", "ca_serve_steps_ahead_total",
                 "decode steps dispatched while the step before was unread: the device had its next program queued"),
                ("late_rows", "ca_serve_late_rows_total",
                 "rows a decode step computed for a request that had ended while the step was in flight: dropped"),
                ("cache_rows_read", "ca_serve_cache_rows_read_total",
                 "slots of a layer's keys the decode steps' attention fetched: the live rows' own, in whole key blocks"),
                ("cache_rows", "ca_serve_cache_rows_total",
                 "slots of a layer's keys the cache held over those steps: what cache_rows_read is a share of"),
                ("window_rows_read", "ca_serve_window_rows_read_total",
                 "of the slots fetched, those in window layers' rings"),
                ("shared_rows_read", "ca_serve_shared_rows_read_total",
                 "of the slots fetched, those of the one stack that a full layer writes and the cross layers read"),
                ("prefill_positions_total", "ca_serve_prefill_positions_total",
                 "positions the admits' prefills computed in the first layer: the prompts' buckets"),
                ("prefill_tail_positions_total", "ca_serve_prefill_tail_positions_total",
                 "positions they computed in the last layer: 1 a prompt where the stack's second half runs at "
                 "a prompt's last position alone"),
            ):
                self._llm_metrics[key] = m.Counter(name, desc)
            # the cache's bytes by the extent of its rows: constants of the deployment
            for key, name, desc in (
                ("cache_full_bytes", "ca_serve_cache_full_bytes",
                 "bytes of the cache's stacks as long as a context: keys and values, latent rows"),
                ("cache_window_bytes", "ca_serve_cache_window_bytes",
                 "bytes of the window layers' rings of keys and values"),
            ):
                m.Gauge(name, desc).set(self.cb.stats[key])
            m.Gauge(
                "ca_serve_engine_devices",
                "devices holding this replica's model parameters",
                tag_keys=("platform", "device_kind"),
            ).set(
                self.engine_device["count"],
                tags={k: self.engine_device[k] for k in ("platform", "device_kind")},
            )
            self._llm_gauges["ready"] = m.Gauge(
                "ca_serve_replica_ready_seconds",
                "seconds of a replica's set-up: its constructor (init), of it the weights (params), "
                "and its programs traced, lowered, compiled or fetched since (build)",
                tag_keys=("deployment", "part"),
            )
        ready = self._llm_gauges["ready"]
        for part, key in self._READY_PARTS:
            cur = self.cb.stats.get(key, 0.0)
            if cur != self._metrics_synced.get(key, 0.0):
                ready.set(cur, tags={"deployment": self._deployment, "part": part})
                self._metrics_synced[key] = cur
        for key, counter in self._llm_metrics.items():
            cur = self.cb.stats.get(key, 0)
            delta = cur - self._metrics_synced.get(key, 0)
            if delta:
                counter.inc(delta)
                self._metrics_synced[key] = cur

    def _pump_loop(self):
        last_sync = 0.0
        while not self._stop:
            now = time.monotonic()
            if now - last_sync > 1.0:
                last_sync = now
                try:
                    # the clock beacon: once a second, in both sinks, the
                    # wall clock and the monotonic at one instant, so a reader
                    # maps a profile's own nanoseconds to the ring's wall clock
                    # and to a load generator's monotonic stamps
                    with tracing.span(
                        "llm.pump.sync", wall_ns=time.time_ns(), mono_ns=time.monotonic_ns()
                    ):
                        self._sync_engine_metrics()
                except Exception:
                    pass  # metrics must never kill the decode pump
            try:
                # a span only where the pump really waits: an idle replica
                # takes the free lock 200 times a second
                if not self._lock.acquire(blocking=False):
                    with tracing.span("llm.pump.lock_wait"):
                        self._lock.acquire()
                try:
                    work = self.cb.has_work
                    out = self.cb.step() if work else {}
                    if out:
                        self._deliver(out)
                finally:
                    # held on both paths above; the linter's flow analysis
                    # does not follow a non-blocking acquire's result:
                    # ca-lint: ignore[res-double-release]
                    self._lock.release()
            except BaseException as e:
                # engine failure (device OOM, shape bug): without this the
                # pump dies silently and every request blocks to the queue
                # timeout.  Fail fast: error every in-flight queue, mark the
                # replica unhealthy, stop pumping.
                with self._lock:
                    self._engine_error = e
                    for q in self._queues.values():
                        q.put(e)
                    self._queues.clear()
                    self._reqs.clear()
                return
            if not work:
                time.sleep(0.005)

    def _deliver(self, out: Dict[int, list]) -> None:
        """Put a step's tokens on their requests' queues (under the lock)."""
        with tracing.span("llm.pump.deliver", tokens=sum(map(len, out.values()))):
            delivered = []
            for rid, toks in out.items():
                q = self._queues.get(rid)
                req = self._reqs.get(rid)
                if q is not None:
                    for t in toks:
                        q.put(t)
                    if req is not None and req.done:
                        q.put(None)
                        delivered.append(rid)
            for rid in delivered:
                self._reqs.pop(rid, None)

    def _submit(self, body) -> tuple:
        sp = self._phase("llm.submit")
        with sp:
            prompt = body.get("prompt", "")
            ids = self.tok.encode(prompt)[: self.config.max_prompt_len]
            mnt = int(body.get("max_new_tokens", self.config.max_new_tokens))
            temp = float(body.get("temperature", self.config.temperature))
            top_k = body.get("top_k")
            top_p = float(body.get("top_p", 1.0))
            q = self._queue_cls()
            t0 = time.monotonic()
            with self._phase("llm.submit.lock_wait"):
                self._lock.acquire()
            try:
                self.cb.stats["lock_wait_s"] += time.monotonic() - t0
                if self._engine_error is not None:
                    raise RuntimeError(
                        f"LLM engine pump died: {self._engine_error!r}"
                    ) from self._engine_error
                # queue registered under the same lock as submit: the pump's
                # next step (admit + decode) finds it before any token flows
                req = self.cb.submit(
                    ids, max_new_tokens=mnt, temperature=temp,
                    top_k=None if top_k is None else int(top_k),
                    top_p=top_p,
                )
                self._queues[req.request_id] = q
                self._reqs[req.request_id] = req
            finally:
                self._lock.release()
            sp.set(rid=req.request_id, prompt_len=len(ids))
        return prompt, req, q

    def _forget(self, req):
        with self._lock:
            self._queues.pop(req.request_id, None)
            self._reqs.pop(req.request_id, None)
            if not req.done:
                # consumer abandoned mid-decode (SSE client disconnect):
                # free the slot NOW instead of decoding tokens nobody reads
                self.cb.cancel(req.request_id)

    def __call__(self, request) -> Dict[str, Any]:
        prompt, req, q = self._submit(_parse_body(request))
        toks = []
        try:
            while True:
                t = q.get(timeout=120)
                if t is None:
                    break
                if isinstance(t, BaseException):
                    raise RuntimeError(f"LLM engine pump died: {t!r}") from t
                toks.append(t)
        finally:
            self._forget(req)
        import numpy as np

        return {
            "prompt": prompt,
            "generated_text": self.tok.decode(np.asarray(toks, np.int32)),
            "num_generated_tokens": len(toks),
        }

    def _frames(self, q, meter: _StreamMeter, own=None) -> Iterator[dict]:
        """A stream's {"token_id", "text"} frames off the request's queue, to
        its end.  The wait for the first is the phase
        `llm.stream.first_token` (queue wait + admit + deliver), a child of
        `own`, the stream's span, where that is not the ambient one."""
        import numpy as np

        while True:
            if not meter.first_token_s:
                with tracing.under(own), self._phase("llm.stream.first_token", rid=meter.rid):
                    t = q.get(timeout=120)
                meter.first_token_s = time.monotonic() - meter.t0
            else:
                t = q.get(timeout=120)
            if t is None:
                meter.cancelled = False
                return
            if isinstance(t, BaseException):
                raise RuntimeError(f"LLM engine pump died: {t!r}") from t
            yield {"token_id": int(t), "text": self.tok.decode(np.asarray([t], np.int32))}

    def stream(self, request):
        """Per-token streaming while other requests decode in the same loop.
        `llm.stream` is written from stamps when the generator ends: a
        generator holds no span across its `yield`s."""
        from ..serve.replica import emit_phase

        prompt, req, q = self._submit(_parse_body(request))
        meter = _StreamMeter(req.request_id, "rpc")
        own = tracing.child_context()
        try:
            for frame in self._frames(q, meter, own):
                t_yield = time.monotonic()
                yield frame
                meter.wrote(t_yield)
        finally:
            self._forget(req)
            emit_phase(
                self._deployment, "llm.stream", meter.t0, time.monotonic(), own=own,
                **meter.attrs(),
            )

    def dag_stream(self, request) -> dict:
        """Compiled-DAG streaming: decode-step -> detokenize -> stream-out
        without a per-token RPC.  Submits the prompt, pre-opens a shm
        channel, and returns its spec; a forwarder thread pushes
        {"token_id","text"} frames into the channel and the proxy-side
        DagStreamReader futex-waits on them.  The only RPC left on the hot
        path is this handshake.  The forwarder's whole life is the span
        `llm.stream`, under the request's trace."""
        import threading

        from ..channel.shm_channel import BufferedShmChannel, ChannelClosedError
        from ..core.config import get_config
        from ..serve.dag_stream import DAG_EOF, DAG_ERR

        cfg = get_config()
        prompt, req, q = self._submit(_parse_body(request))
        meter = _StreamMeter(req.request_id, "dag")
        ch = BufferedShmChannel(
            num_readers=1, num_buffers=max(2, cfg.serve_dag_stream_buffers)
        )
        spec = ch.spec()

        def forward():
            sp = self._phase("llm.stream", rid=meter.rid, transport="dag")
            try:
                with sp:
                    try:
                        for frame in self._frames(q, meter):
                            # 120s matches the RPC path's queue timeout: a consumer
                            # stalled longer than that loses the stream either way
                            t_write = time.monotonic()
                            ch.write(frame, timeout=120)
                            meter.wrote(t_write)
                        ch.write(DAG_EOF, timeout=30)
                    except RuntimeError as e:  # the pump died: say so, then end
                        ch.write({DAG_ERR: str(e)}, timeout=30)
                    finally:
                        sp.set(**meter.attrs())
                    # drain barrier: release() unlinks the segment, so
                    # wait until the proxy acked the last frame first
                    ch.wait_consumed(30.0)
            except (ChannelClosedError, TimeoutError):
                pass  # proxy abandoned the stream; free the decode slot below
            except Exception:
                pass
            finally:
                self._forget(req)
                ch.release()

        # the thread starts with the caller's contextvars: the request's trace
        threading.Thread(
            target=contextvars.copy_context().run, args=(forward,),
            daemon=True, name="ca-dag-stream",
        ).start()
        return spec


class StreamingLLMIngress(ContinuousLLMServer):
    """ContinuousLLMServer whose __call__ STREAMS when the HTTP client asks
    for SSE (Accept: text/event-stream) and answers one JSON body otherwise
    — the proxy's SSE path invokes the ingress's __call__, so token
    streaming over plain `curl -H 'Accept: text/event-stream'` needs the
    branch here."""

    def __call__(self, request):
        from ..serve import Request

        if isinstance(request, Request) and "text/event-stream" in request.headers.get(
            "accept", ""
        ):
            return self.stream(request)  # generator -> one SSE event per token
        return ContinuousLLMServer.__call__(self, request)


def build_continuous_llm_deployment(
    config: Optional[ProcessorConfig] = None,
    *,
    slots: int = 8,
    num_replicas: int = 1,
    num_tpus: float = 0.0,
    name: str = "ContinuousLLMServer",
    admission=None,
    autoscaling_config=None,
    sse_ingress: bool = False,
):
    """Continuous-batching twin of build_llm_deployment: up to `slots`
    requests share every decode iteration on each replica.  `admission`
    (AdmissionPolicy/dict) arms the proxy's load-shedding gate;
    `sse_ingress=True` serves token-streaming SSE from __call__.  `num_tpus`
    is each replica's chip request (see build_llm_deployment)."""
    from .. import serve

    config = config or ProcessorConfig()
    dep = serve.deployment(
        StreamingLLMIngress if sse_ingress else ContinuousLLMServer,
        name=name,
        num_replicas=num_replicas,
        num_tpus=num_tpus,
        max_ongoing_requests=slots,  # callers block in __call__; pump is a thread
        admission=admission,
        autoscaling_config=autoscaling_config,
    )
    return dep.bind(config, slots)
