"""Set-up measured where it happens (ISSUE 52): what building programs costs,
kept by the thread that built them from jax's own events; the replica's
constructor as a span with two children and as counts that are always kept; the
batcher's `program_*` counts, which a step never touches."""

import threading

import pytest

from _llm_tiny import llm_spans  # noqa: F401 (a fixture)

BUILD_KINDS = ("trace_s", "lower_s", "backend_s", "cache_fetch_s", "builds", "cache_hits", "cache_misses")


def _on_thread(fn, name="builder"):
    """Run `fn` on a thread of its own; returns (its result, the thread's ident)."""
    box = {}
    t = threading.Thread(target=lambda: box.update(out=fn(), ident=threading.get_ident()), name=name)
    t.start()
    t.join()
    return box["out"], box["ident"]


def test_build_totals_are_kept_by_the_thread_that_built():
    """A program built on a named thread lands in that thread's totals and in
    its sink, on that thread; one built elsewhere does not; a second call of the
    same shape adds nothing; a jit traced inside another counts once."""
    import jax
    import jax.numpy as jnp

    from cluster_anywhere_tpu.util import tracing

    assert tracing.enable_jax_profiling()
    inner = jax.jit(lambda x: jnp.sort(x) * 2)
    outer = jax.jit(lambda x: inner(x) + jnp.where(x > 0, x, 0))  # three jits inside one
    x = jnp.arange(11.0)  # made here: its own program is this thread's
    seen = []

    def build():
        tracing.on_jax_build(threading.get_ident(), lambda kind, amount: seen.append(
            (kind, amount, threading.get_ident())))
        try:
            before = tracing.jax_build_totals()
            outer(x).block_until_ready()
            once = tracing.jax_build_totals()
            outer(x).block_until_ready()
            return before, once, tracing.jax_build_totals()
        finally:
            tracing.on_jax_build(threading.get_ident(), None)

    mine = tracing.jax_build_totals()
    (before, once, twice), ident = _on_thread(build)
    assert set(once) == set(BUILD_KINDS) == set(tracing.JAX_BUILD_KINDS)
    assert once["builds"] - before["builds"] == 1 and once == twice
    assert all(once[k] > before[k] for k in ("trace_s", "lower_s", "backend_s"))
    # the sink ran on the builder's thread, once a total: one outermost trace, one lowering, one build
    assert sorted(k for k, _, _ in seen) == ["backend_s", "builds", "lower_s", "trace_s"]
    assert {i for _, _, i in seen} == {ident} and ident != threading.get_ident()
    assert dict((k, a) for k, a, _ in seen)["trace_s"] == pytest.approx(once["trace_s"] - before["trace_s"])
    assert tracing.jax_build_totals(ident) == twice
    # a program built here is this thread's, not the builder's, and reaches no sink
    jax.jit(lambda x: x * 5 - 1)(x).block_until_ready()
    assert tracing.jax_build_totals()["builds"] == mine["builds"] + 1
    assert tracing.jax_build_totals(ident) == twice and len(seen) == 4


@pytest.mark.parametrize("event, kind, amount", [
    ("/jax/compilation_cache/cache_hits", "cache_hits", 1),
    ("/jax/compilation_cache/cache_misses", "cache_misses", 1),
    ("/jax/compilation_cache/cache_retrieval_time_sec", "cache_fetch_s", 0.25),
    # a saving is no time spent; a name that only contains "compile" is no build
    ("/jax/compilation_cache/compile_time_saved_sec", None, 5.0),
    ("/jax/compilation_cache/compile_requests_use_cache", None, 1),
])
def test_jax_events_are_sorted_by_their_exact_names(event, kind, amount, monkeypatch):
    """The persistent cache's own events as jax 0.9 names them: each adds to
    its one total and to nothing else, and a sink that raises is swallowed."""
    from jax import monitoring

    from cluster_anywhere_tpu.util import metrics, tracing

    assert tracing.enable_jax_profiling()
    observed = []
    monkeypatch.setattr(metrics._by_name["ca_jax_compile_seconds"], "observe",
                        lambda v, tags=None: observed.append((v, tags)))

    def fire():
        def sink(kind, amount):
            raise RuntimeError("a sink's fault is not jax's")

        tracing.on_jax_build(threading.get_ident(), sink)
        try:
            before = tracing.jax_build_totals()
            if isinstance(amount, float):
                monitoring.record_event_duration_secs(event, amount)
            else:
                monitoring.record_event(event)
            return before, tracing.jax_build_totals()
        finally:
            tracing.on_jax_build(threading.get_ident(), None)

    (before, after), _ = _on_thread(fire)
    moved = {k: after[k] - before[k] for k in BUILD_KINDS if after[k] != before[k]}
    assert moved == ({kind: amount} if kind else {})
    assert observed == ([(0.25, {"event": "cache_fetch"})] if kind == "cache_fetch_s" else [])


REPLICA_INIT_STATS = {
    "replica_init_mono", "replica_init_s", "backend_init_s", "params_init_s", "init_build_s", "lock_wait_s",
    "program_build_s", "program_trace_s", "program_builds", "program_cache_misses",
}


def _server(cls_name="StreamingLLMIngress"):
    from cluster_anywhere_tpu.llm import ModelSpec, ProcessorConfig, serve_llm

    return getattr(serve_llm, cls_name)(
        ProcessorConfig(model=ModelSpec(preset="tiny"), max_prompt_len=16, max_new_tokens=4,
                        prefix_cache_entries=0),
        slots=2,
    )


@pytest.mark.parametrize("enabled", [True, False])
def test_replica_init_is_a_span_with_two_children_and_counts_that_are_always_kept(llm_spans, enabled):
    import time

    from cluster_anywhere_tpu.util import tracing

    t0 = time.monotonic()
    if enabled:
        tracing.enable()
    try:
        srv = _server()
    finally:
        tracing.disable()
    t1 = time.monotonic()
    try:
        events = [e for e in llm_spans() if e["name"].startswith("llm.replica.")]
        stats = dict(srv.cb.stats)
        assert srv._pump.name == "llm-pump" and srv._pump.ident in tracing._build_sinks
    finally:
        srv.close()
    assert srv._pump.ident not in tracing._build_sinks
    # the counts are there whether anybody traces or not
    assert REPLICA_INIT_STATS <= set(stats)
    assert t0 <= stats["replica_init_mono"] <= t1 and 0.0 < stats["replica_init_s"] <= t1 - t0
    assert 0.0 < stats["params_init_s"] < stats["replica_init_s"]
    assert 0.0 <= stats["backend_init_s"] < stats["replica_init_s"]
    assert 0.0 <= stats["init_build_s"] < stats["replica_init_s"]
    assert stats["program_builds"] == 0 and stats["program_build_s"] == 0.0  # no request yet
    if not enabled:
        assert events == []
        return
    by_name = {e["name"]: e for e in events}
    assert sorted(by_name) == ["llm.replica.init", "llm.replica.init.batcher", "llm.replica.init.params"]
    init = by_name["llm.replica.init"]
    children = [e for e in events if e["trace"].get("psid") == init["trace"]["sid"]]
    assert sorted(e["name"] for e in children) == ["llm.replica.init.batcher", "llm.replica.init.params"]
    assert all(t0 <= e["mono"] <= t1 for e in events) and init["mono"] >= stats["replica_init_mono"]
    assert by_name["llm.replica.init.params"]["source"] == "seed"
    assert (init["slots"], init["t_max"], init["buckets"]) == (2, 20, 1)
    assert init["param_bytes"] > init["cache_bytes"] > 0
    assert init["backend_ms"] == pytest.approx(1e3 * stats["backend_init_s"])
    assert init["build_ms"] == pytest.approx(1e3 * stats["init_build_s"])
    for child in children:
        assert init["start"] <= child["start"] and child["end"] <= init["end"]


def test_program_builds_are_counted_on_the_pumps_thread_and_an_admit_says_what_it_built(llm_spans):
    """One request builds the prefill of its bucket, the decode step and the
    small programs beside them, all on the pump's thread: the counts rise, the
    first admit of the bucket says `build_ms`; a second request of the same
    bucket builds nothing.  A program built on another thread of the process
    (the reference's check programs compile on a handler's) stays out."""
    import jax
    import jax.numpy as jnp

    from _llm_tiny import TRACE
    from cluster_anywhere_tpu.util import tracing

    keys = ("program_build_s", "program_trace_s", "program_builds", "program_cache_misses")
    srv = _server("ContinuousLLMServer")
    try:
        token = tracing.push_execution(TRACE)
        try:
            srv({"prompt": "first"})
            first = {k: srv.cb.stats[k] for k in keys}
            srv({"prompt": "again"})
            again = {k: srv.cb.stats[k] for k in keys}
        finally:
            tracing.pop_execution(token)
        admits = [e for e in llm_spans() if e["name"] == "llm.admit"]
        x = jnp.arange(13.0)  # a program of its own
        here = tracing.jax_build_totals()
        jax.jit(lambda x: x * 7 + 3)(x).block_until_ready()  # a handler's thread stands here
        assert tracing.jax_build_totals()["builds"] == here["builds"] + 1
        assert {k: srv.cb.stats[k] for k in keys} == again
        pump = tracing.jax_build_totals(srv._pump.ident)
    finally:
        srv.close()
    assert first["program_builds"] >= 2 and first["program_build_s"] > first["program_trace_s"] > 0.0
    assert again == first
    assert first["program_build_s"] == pytest.approx(pump["trace_s"] + pump["lower_s"] + pump["backend_s"])
    assert first["program_builds"] == pump["builds"]
    assert [a["traced"] for a in admits] == [1, 0]
    assert 0.0 < admits[0]["build_ms"] <= 1e3 * first["program_build_s"] and "build_ms" not in admits[1]


def test_a_bare_batcher_keeps_the_keys_and_counts_nothing(llm_spans):
    """Nobody registered a sink for the thread that steps it: the keys are
    there, at 0, and an admit says no `build_ms`."""
    from _llm_tiny import TRACE, _tiny_batcher
    from cluster_anywhere_tpu.util import tracing

    cb = _tiny_batcher()
    token = tracing.push_execution(TRACE)
    try:
        cb.submit([1, 2, 3], max_new_tokens=2)
        cb.pump()
    finally:
        tracing.pop_execution(token)
    admit = next(e for e in llm_spans() if e["name"] == "llm.admit")
    assert "build_ms" not in admit and "traced" in admit
    assert [cb.stats[k] for k in ("program_build_s", "program_trace_s", "program_builds",
                                  "program_cache_misses")] == [0.0, 0.0, 0, 0]
    # with the sink registered for this thread, the same batcher's next new shape is counted
    tracing.on_jax_build(threading.get_ident(), cb.count_build)
    try:
        cb.submit(list(range(1, 21)), max_new_tokens=2)  # the second bucket
        cb.pump()
    finally:
        tracing.on_jax_build(threading.get_ident(), None)
    assert cb.stats["program_builds"] >= 1 and cb.stats["program_build_s"] > 0.0


def test_one_helper_makes_the_weights_for_both_engines(llm_spans, tmp_path):
    """`model_params`: from the seed or from a path, under span
    `llm.replica.init.params` with its `source`, for the continuous server and
    the batch worker alike."""
    import jax
    import numpy as np

    from _llm_tiny import TRACE
    from cluster_anywhere_tpu.llm import ModelSpec, _params_io
    from cluster_anywhere_tpu.llm.processor import ByteTokenizer, ProcessorConfig, _InferenceWorker, model_params
    from cluster_anywhere_tpu.util import tracing

    spec = ModelSpec(preset="tiny", seed=3)
    tcfg = spec.transformer_config(ByteTokenizer.vocab_size)
    token = tracing.push_execution(TRACE)
    try:
        seeded = model_params(spec, tcfg)
        _params_io.save_params(seeded, str(tmp_path))
        loaded = model_params(ModelSpec(preset="tiny", params_path=str(tmp_path)), tcfg)
        worker = _InferenceWorker(ProcessorConfig(model=spec))
    finally:
        tracing.pop_execution(token)
    assert [e["source"] for e in llm_spans() if e["name"] == "llm.replica.init.params"] == ["seed", "path", "seed"]
    leaves = jax.tree_util.tree_leaves
    assert all(np.array_equal(a, b) for a, b in zip(leaves(seeded), leaves(loaded)))
    assert all(np.array_equal(a, b) for a, b in zip(leaves(seeded), leaves(worker.params)))
