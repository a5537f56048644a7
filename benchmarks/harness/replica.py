"""What the benchmark puts into the process that holds the chip.

`BenchIngress` is the program's `StreamingLLMIngress` with methods the
benchmark reaches through the deployment handle.  It changes nothing the
program does for a request: it wraps `_admit` and `step` of its own batcher
and its own `_submit` with timers, counters and `TraceAnnotation`s (the
program has none on these paths yet: PERF.md lists them for the tracing
issue), and keeps what they record in memory until `bench_collect`.

All stamps are `time.monotonic()`, which on Linux is one clock for every
process of the host, so the driver lines them up with its own.
"""

from __future__ import annotations

import glob
import os
import socket
import sys
import threading
import time
from typing import Any, Dict, List, Optional

from cluster_anywhere_tpu.llm.serve_llm import StreamingLLMIngress


class IdTokenizer:
    """A prompt is space-separated decimal token ids, so a request carries an
    exact token count over the configuration's whole vocabulary.  Has what
    `ProcessorConfig.tokenizer` asks for: encode, decode, vocab_size."""

    pad_id = 0

    def __init__(self, vocab_size: int):
        self.vocab_size = int(vocab_size)

    def encode(self, text: str, add_bos: bool = True) -> List[int]:
        ids = [int(t) for t in text.split()]
        if any(i < 0 or i >= self.vocab_size for i in ids):
            raise ValueError(f"token id outside the vocabulary of {self.vocab_size}")
        return ids

    def decode(self, ids) -> str:
        return " ".join(str(int(i)) for i in ids)


def device_report() -> Dict[str, Any]:
    """The device as the process that holds it sees it."""
    import jax

    devs = jax.devices()
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devs]
    return {
        "platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs),
        "memory_peak_bytes": int(max(peaks)),
        "cache_dir": jax.config.jax_compilation_cache_dir,
    }


class CompileCounter:
    """Counts what `jax.monitoring` reports as a backend compilation (a
    program that was not in this process yet: compiled, or fetched from the
    persistent cache).  Inside the measured window there should be none."""

    def __init__(self):
        from jax import monitoring

        self.events: List[tuple] = []
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if "backend_compile" in event:
            self.events.append((time.monotonic(), float(duration)))


def _session_class():
    """The profiler session class whose `stop()` hands back the profile, or
    None where the installed JAX has none."""
    try:
        from jax._src.lib import _profiler

        return _profiler.ProfilerSession if hasattr(_profiler.ProfilerSession, "stop") else None
    except (ImportError, AttributeError):
        return None


def trace_options():
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # the Python tracer alone slows the host severalfold
    opts.host_tracer_level = 2
    return opts


def start_trace(trace_dir: str):
    """What `jax.profiler.start_trace` does, short of the module's state: the
    session is the caller's to hand to `stop_trace`, which can then end it
    without the export.  None where this JAX has no such session: the trace is
    then `jax.profiler`'s own, started here."""
    import jax

    opts = trace_options()
    cls = _session_class()
    if cls is None:
        print("[bench] this JAX has no ProfilerSession.stop(): jax.profiler's own start and stop, "
              "which export a .trace.json.gz too", file=sys.stderr, flush=True)
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        return None
    # the backend before the session: on a TPU the tracer otherwise starts before
    # libtpu does, and the profile holds no device operation
    jax.devices()
    return cls(opts)


def stop_trace(trace_dir: str, session) -> str:
    """Ends the trace `start_trace` began and returns the path of its profile,
    `<trace_dir>/plugins/profile/<stamp>/<host>.xplane.pb`: where
    `jax.profiler.stop_trace()` puts it, and nothing beside it.  The session's
    `stop()` (checked on JAX 0.9.0) returns the serialized profile and writes
    nothing, where `stop_trace()` always exports a `<host>.trace.json.gz` as
    well, at about 2 s a MB of profile: a viewer's file that no reader here
    opens, and that whoever wants a viewer can make from the `.xplane.pb` off
    line.  Without a session (a JAX that has none) this is
    `jax.profiler.stop_trace()`."""
    if session is None:
        import jax

        jax.profiler.stop_trace()
        found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
        if not found:
            raise RuntimeError(f"the profiler wrote no trace under {trace_dir}")
        return found[-1]
    profile = session.stop()
    if not profile:
        raise RuntimeError("the profiler's session gave an empty profile")
    run_dir = os.path.join(trace_dir, "plugins", "profile", time.strftime("%Y_%m_%d_%H_%M_%S"))
    os.makedirs(run_dir, exist_ok=True)
    path = os.path.join(run_dir, socket.gethostname() + ".xplane.pb")
    with open(path, "wb") as f:
        f.write(profile)
    return path


class BenchIngress(StreamingLLMIngress):
    def __init__(self, config, slots: int = 8):
        self._compiles = CompileCounter()
        super().__init__(config, slots)
        self._wrap_batcher()

    def _wrap_batcher(self) -> None:
        """Timers and counters around `self.cb`'s `_admit`, `step` and `submit`."""
        import jax

        # (t_end, wall_s, admit_s, tokens_out, admitted_total, decode_steps_total, requests_out)
        self._steps: List[tuple] = []
        self._admits: List[tuple] = []  # (t_end, wall_s, requests, prompt_tokens, reused_tokens)
        self._submitted: Dict[int, tuple] = {}  # request_id -> (bench_id, t_submit)
        self._first: Dict[str, tuple] = {}  # bench_id -> (t_submit, t_first_token)
        # bench_id -> request_id of the check streams, which `bench_check` hands to the
        # reference; None once it has run, so the window's requests leave nothing here
        self._check_ids: Optional[Dict[str, int]] = {}
        self._trace_session = None  # the profiler's, between bench_trace's "start" and "stop"
        cb = self.cb
        admit_inner, step_inner = cb._admit, cb.step
        annotate = jax.profiler.TraceAnnotation

        def admit(out=None):
            if not (cb.queue and None in cb._by_slot):
                return admit_inner(out)
            before = (cb.stats["admitted"], cb.stats["prefix_tokens_reused"])
            queued = list(cb.queue)
            t0 = time.monotonic()
            with annotate("admit"):
                admit_inner(out)
            t1 = time.monotonic()
            n = cb.stats["admitted"] - before[0]
            prompt_tokens = sum(len(r.prompt_ids) for r in queued[:n])
            self._admits.append(
                (t1, t1 - t0, n, prompt_tokens, cb.stats["prefix_tokens_reused"] - before[1])
            )
            self._admit_s += t1 - t0

        def step():
            self._admit_s = 0.0
            t0 = time.monotonic()
            with annotate("decode_step"):
                out = step_inner()
            t1 = time.monotonic()
            for rid in out:
                sub = self._submitted.pop(rid, None)
                if sub is not None:
                    self._first[sub[0]] = (sub[1], t1)
            self._steps.append(
                (t1, t1 - t0, self._admit_s, sum(len(v) for v in out.values()),
                 cb.stats["admitted"], cb.stats["decode_steps"], len(out))
            )
            return out

        submit_inner = cb.submit

        def submit(ids, **kw):
            # called by _submit under the replica's lock, so the pump cannot
            # hand out this request's first token before it is on record
            req = submit_inner(ids, **kw)
            if getattr(self._tls, "bench_id", None) is not None:
                self._submitted[req.request_id] = (self._tls.bench_id, self._tls.t_submit)
                if self._check_ids is not None:
                    self._check_ids[self._tls.bench_id] = req.request_id
            return req

        self._admit_s = 0.0
        self._tls = threading.local()
        cb._admit, cb.step, cb.submit = admit, step, submit

    def _submit(self, body):
        self._tls.bench_id, self._tls.t_submit = body.get("bench_id"), time.monotonic()
        return super()._submit(body)

    # -- reached through the deployment handle -------------------------------
    def bench_check(self, streams: List[Dict[str, Any]], t_begin: float,
                    reference_name: str) -> Dict[str, Any]:
        """The check streams against the configuration's reference
        (references/<reference_name>.py through reference.check_serving).
        Each stream comes with the `bench_id` it was sent under and goes on
        with the batcher's own `request_id`, by which a reference can ask the
        batcher what it recorded of that request; a stream that was never
        submitted here is an error.  The report says at what batch the
        streams were served, over the steps since `t_begin` that handed out
        any token: the tokens a step handed out (`decode_batch_mean`) and the
        requests it handed them to (`decode_requests_mean`).  Several streams
        have to have overlapped: a step may hand one request several tokens,
        so it is the requests that say so."""
        from . import manifest, reference

        with self._lock:
            ids, self._check_ids = self._check_ids or {}, None
            steps = [s for s in self._steps if s[0] >= t_begin and s[3] > 0]
        missing = [s["bench_id"] for s in streams if s["bench_id"] not in ids]
        if missing:
            raise RuntimeError(f"check streams {missing} were never submitted to this replica")
        streams = [dict(s, request_id=ids[s["bench_id"]]) for s in streams]
        report = reference.check_serving(self.cb, streams, manifest.load_reference(reference_name))
        report["decode_batch_mean"] = sum(s[3] for s in steps) / len(steps) if steps else 0.0
        report["decode_requests_mean"] = sum(s[6] for s in steps) / len(steps) if steps else 0.0
        if len(streams) > 1 and not report["decode_requests_mean"] > 1.0:
            report["ok"] = False  # the streams did not overlap: the batch programs were not checked
        return report

    def bench_trace(self, action: str, trace_dir: str) -> str:
        if action == "start":
            self._trace_session = start_trace(trace_dir)
            return ""
        return stop_trace(trace_dir, self._trace_session)

    def bench_collect(self) -> Dict[str, Any]:
        """Everything recorded so far, and the device's memory peak."""
        with self._lock:
            return {
                "steps": list(self._steps), "admits": list(self._admits),
                "first": dict(self._first), "compiles": list(self._compiles.events),
                "stats": dict(self.cb.stats), "device": device_report(),
            }
