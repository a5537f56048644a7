"""The batcher's own spans and counters (util/tracing.py: one span API), and
that what names a program's parts is metadata only."""

import contextlib

import pytest

from _llm_tiny import (  # noqa: F401 (llm_spans is a fixture)
    TRACE,
    _decode_step_program,
    _tiny_batcher,
    llm_spans,
)


def _children(events, parent):
    return sorted(e["name"] for e in events if e["trace"].get("psid") == parent["trace"]["sid"])


@pytest.mark.parametrize("prefix_cache_entries", [0, 2])
def test_batcher_spans_form_the_tree_under_the_requests_trace(llm_spans, prefix_cache_entries):
    """Two requests through a ContinuousBatcher under a trace context: every
    span of the table in ARCHITECTURE.md, each under its parent, all in the
    one trace; an admit carries its request's id, sizes and queue wait."""
    from cluster_anywhere_tpu.util import tracing

    cb = _tiny_batcher(prefix_cache_entries=prefix_cache_entries, prefix_block=4)
    prompt = list(range(1, 20))
    token = tracing.push_execution(TRACE)
    try:
        reqs = [cb.submit(prompt, max_new_tokens=3), cb.submit(prompt, max_new_tokens=3)]
        while cb.has_work:
            cb.step()
    finally:
        tracing.pop_execution(token)
    events = llm_spans()
    assert events and {e["trace"]["tid"] for e in events} == {TRACE["tid"]}
    steps = [e for e in events if e["name"] == "llm.step"]
    admits = [e for e in events if e["name"] == "llm.admit"]
    # both admits ran inside the first call, which dispatched the first step of both slots
    # and had none to read; the second dispatched the second and read the first; the third
    # read the second, which brought both requests to their length: no step for nothing
    assert [e["live"] for e in steps] == [0, 2, 2] and [e["ahead"] for e in steps] == [0, 1, 0]
    inner = ["llm.step.dispatch", "llm.step.readback", "llm.step.scatter", "llm.step.upload"]
    parts = [[n for n in _children(events, e) if n.startswith("llm.step.")] for e in steps]
    assert parts == [inner[:1] + inner[3:], inner, inner[1:3]]
    assert (cb.stats["decode_steps"], cb.stats["steps_ahead"], cb.stats["late_rows"]) == (2, 1, 0)
    assert [a["rid"] for a in admits] == [r.request_id for r in reqs]
    for i, a in enumerate(admits):
        assert a["prompt_len"] == 19 and a["queue_wait_ms"] >= 0.0
        assert steps[0]["start"] <= a["start"] and a["end"] <= steps[0]["end"]
        hit = bool(prefix_cache_entries) and i == 1
        assert a["prefix_hit"] == int(hit) and a["bucket"] == 32
        want = ["llm.admit.install", "llm.admit.sample"]
        if not hit:
            want.insert(1, "llm.admit.prefill")
        if prefix_cache_entries:
            want.append("llm.admit.suffix")
        assert _children(events, a) == sorted(want)
    assert cb.stats["submitted"] == 2 and cb.stats["tokens_out"] == 6
    assert cb.stats["admit_s"] > 0.0 and cb.stats["queue_wait_s"] >= 0.0


def test_server_spans_tie_a_traced_request_to_its_admit_on_the_pump_thread(llm_spans):
    """One request submitted to an in-process ContinuousLLMServer under a
    trace context: `llm.submit` and its lock wait on the caller's thread and
    that request's `llm.admit` with its children on the pump's, one trace id;
    the pump's own steps belong to no request and leave no event."""
    from cluster_anywhere_tpu.llm import ContinuousLLMServer, ModelSpec, ProcessorConfig
    from cluster_anywhere_tpu.util import tracing

    srv = ContinuousLLMServer(
        ProcessorConfig(model=ModelSpec(preset="tiny"), max_prompt_len=16, max_new_tokens=4,
                        prefix_cache_entries=0),
        slots=2,
    )
    try:
        srv({"prompt": "untraced"})
        assert llm_spans() == []
        token = tracing.push_execution(TRACE)
        try:
            ambient = tracing.current()
            srv({"prompt": "traced"})
        finally:
            tracing.pop_execution(token)
        events = llm_spans()
        stats = dict(srv.cb.stats)
    finally:
        srv.close()
    by_name = {e["name"]: e for e in events}
    assert sorted(by_name) == [
        "llm.admit", "llm.admit.install", "llm.admit.prefill", "llm.admit.sample",
        "llm.submit", "llm.submit.lock_wait",
    ] and len(events) == 6
    assert {e["trace"]["tid"] for e in events} == {TRACE["tid"]}
    assert by_name["llm.submit"]["trace"]["psid"] == ambient["sid"]
    assert _children(events, by_name["llm.submit"]) == ["llm.submit.lock_wait"]
    admit = by_name["llm.admit"]
    assert admit["rid"] == 2 and admit["prefix_hit"] == 0 and admit["queue_wait_ms"] >= 0.0
    assert _children(events, admit) == ["llm.admit.install", "llm.admit.prefill", "llm.admit.sample"]
    assert by_name["llm.submit"]["end"] <= admit["end"]
    assert stats["submitted"] == stats["admitted"] == 2 and stats["tokens_out"] == 8
    assert stats["lock_wait_s"] >= 0.0 and stats["admit_s"] > 0.0


def test_untraced_batcher_leaves_no_event_and_counts_right(llm_spans):
    cb = _tiny_batcher()
    a = cb.submit([1, 2, 3], max_new_tokens=4)
    b = cb.submit([4, 5], max_new_tokens=2, eos_id=None)
    cb.pump()
    assert llm_spans() == []
    assert a.done and b.done and a.trace is None and a.t_submit > 0.0
    want = dict(submitted=2, admitted=2, finished=2, tokens_out=6, decode_steps=3, cancelled=0)
    assert {k: cb.stats[k] for k in want} == want
    assert cb.stats["queue_wait_s"] >= 0.0 and cb.stats["admit_s"] > 0.0


def test_inactive_spans_cost_next_to_nothing(llm_spans):
    """100,000 spans with tracing off, no trace context and no profiler
    session (jax is loaded, so each enters its TraceAnnotation): under 1 s,
    and nothing reaches the event buffer."""
    import time

    from cluster_anywhere_tpu.util import tracing

    t0 = time.perf_counter()
    for i in range(100_000):
        with tracing.span("llm.step", live=i) as ctx:
            pass
    took = time.perf_counter() - t0
    assert ctx is None and llm_spans() == []
    assert took < 1.0, took


def test_jax_hook_counts_backend_compilations(monkeypatch):
    """The engine arms `enable_jax_profiling()`; a program new to the process
    is one more in `ca_jax_compiles_total`, and no SPAN event is made up."""
    import jax
    import jax.numpy as jnp

    from cluster_anywhere_tpu.util import metrics, tracing

    assert tracing.enable_jax_profiling()
    x = jnp.arange(7.0)  # a program of its own
    counted = []
    monkeypatch.setattr(
        metrics._by_name["ca_jax_compiles_total"], "inc", lambda *a, **k: counted.append(1)
    )
    before = len(tracing._events)
    jax.jit(lambda x: x * 3 + 1)(x).block_until_ready()
    assert len(counted) == 1 and len(tracing._events) == before


def test_a_traced_train_step_says_once_what_remat_keeps(llm_spans):
    """`train.remat` (models/transformer.py `_remat_keeps`): one span as the
    step is traced, with the decision layer by layer and what it was made from;
    a step that runs the traced program again says nothing more."""
    import jax
    import jax.numpy as jnp
    import optax

    from cluster_anywhere_tpu.models import transformer
    from cluster_anywhere_tpu.util import tracing

    cfg = transformer.TransformerConfig(
        vocab_size=128, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2, d_head=16, d_ff=128, max_seq_len=64, remat=True)
    step, init_state = transformer.make_train_step(cfg, None, optimizer=optax.sgd(1e-2))
    params, opt_state = init_state(jax.random.key(0))
    batch = {"ids": jnp.zeros((2, 33), jnp.int32)}
    jstep = jax.jit(step)
    token = tracing.push_execution(TRACE)
    try:
        params, opt_state, _ = jstep(params, opt_state, batch)
        jstep(params, opt_state, batch)
    finally:
        tracing.pop_execution(token)
    (event,) = llm_spans("train.remat")
    rows = 2 * 32
    a_layer, an_ffn = rows * (2 * (64 + 2 * 32 + 64 + 64) + 4 * 4), rows * 2 * 128 * 2
    weights = sum(x.size * x.dtype.itemsize for x in jax.tree_util.tree_leaves(params))
    want = {"kept": True, "kept_layers": 2, "kept_bytes": 2 * a_layer, "budget_bytes": -1,
            # the dense FFN's two up products beside them, in every layer where no limit is reported; the allowance
            # derived from the shapes (two layers' inputs, the weights in bfloat16, a layer's halves and their
            # gradients, which are more than the head and loss hold whole); the head and loss not by chunks
            "kept_ffn_layers": 2, "kept_ffn_bytes": 2 * an_ffn,
            "temp_bytes": 2 * rows * 64 * 2 + weights // 2 + 2 * (a_layer + an_ffn), "loss_chunk": 0}
    assert 2 * (a_layer + an_ffn) > 128 * (rows * 10 + 2 * 64 * 2)
    assert {k: event[k] for k in want} == want and event["trace"]["tid"] == TRACE["tid"]


def _instruction_count(compiled) -> int:
    import re

    return sum(
        1 for line in compiled.as_text().splitlines()
        if re.match(r"\s+(ROOT )?%?[\w.\-]+ = ", line)
    )


def _compile_program(which):
    import jax
    import jax.numpy as jnp
    import optax

    from cluster_anywhere_tpu.models import generate, transformer

    cfg = transformer.TransformerConfig(
        vocab_size=128, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2, d_head=16,
        d_ff=128, max_seq_len=64, remat=True,
    )
    slots, t_max = 4, 32
    if which == "decode_step":
        fn, args = _decode_step_program(cfg, slots, t_max)
        return jax.jit(fn).lower(*args).compile()
    params = jax.eval_shape(lambda k: transformer.init_params(k, cfg), jax.random.key(0))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    if which == "prefill":
        # the function under the jit: a jit keeps what it traced, scopes and all
        fn = jax.jit(lambda p, ids, pad: generate.prefill_counted.__wrapped__(p, ids, cfg, t_max, pad))
        return fn.lower(params, i32(1, 16), i32(1)).compile()
    step, _ = transformer.make_train_step(cfg, None)
    opt = jax.eval_shape(lambda p: optax.adamw(3e-4, weight_decay=0.01).init(p), params)
    return jax.jit(step).lower(params, opt, {"ids": i32(2, 33)}).compile()


@pytest.mark.parametrize("which", ["decode_step", "prefill", "train_step"])
def test_named_scopes_are_metadata_only(which, monkeypatch):
    """The scope names reach the operations' metadata and change nothing
    else: the optimized CPU HLO has as many instructions with them as with
    `jax.named_scope` made a no-op."""
    import jax

    with_scopes = _compile_program(which)
    text = with_scopes.as_text()
    wanted = ["embed", "norm", "attn.qkv", "attn.rope", "attn.core", "attn.out", "ffn", "head"]
    wanted += {"train_step": ["loss", "optimizer"], "decode_step": ["attn.cache", "sample"],
               "prefill": ["attn.cache"]}[which]
    assert [s for s in wanted if f"/{s}/" not in text and f"({s})/" not in text] == []
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    without = _compile_program(which)
    assert "attn.core" not in without.as_text()
    assert _instruction_count(with_scopes) == _instruction_count(without) > 100
