"""LLM library tests (batch processor over Data, generation correctness,
serve deployment)."""

import contextlib

import numpy as np
import pytest

import cluster_anywhere_tpu as ca
import cluster_anywhere_tpu.data as cad
from cluster_anywhere_tpu import llm


@pytest.fixture(scope="module", autouse=True)
def cluster():
    if ca.is_initialized():
        ca.shutdown()
    ca.init(num_cpus=4)
    yield
    ca.shutdown()


def test_byte_tokenizer_roundtrip():
    tok = llm.ByteTokenizer()
    ids = tok.encode("hello world")
    assert ids[0] == tok.bos_id
    assert tok.decode(ids) == "hello world"
    assert tok.decode(tok.encode("émojis 🎉")) == "émojis 🎉"


def test_generate_determinism_greedy():
    import jax
    import jax.numpy as jnp

    from cluster_anywhere_tpu.models.generate import generate
    from cluster_anywhere_tpu.models.transformer import TransformerConfig, init_params

    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2, d_head=8, d_ff=64
    )
    params = init_params(jax.random.key(0), cfg)
    prompt = jnp.array([[1, 5, 9]], jnp.int32)
    a = generate(params, prompt, jax.random.key(1), cfg=cfg, max_new_tokens=6)
    b = generate(params, prompt, jax.random.key(2), cfg=cfg, max_new_tokens=6)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))  # greedy: rng-free


def test_generate_left_padding_invariance():
    """Left-padding a prompt (with prompt_lens) must not change greedy output:
    pads are masked out of attention and RoPE counts real tokens only
    (ADVICE r1 medium finding)."""
    import jax
    import jax.numpy as jnp

    from cluster_anywhere_tpu.models.generate import generate
    from cluster_anywhere_tpu.models.transformer import TransformerConfig, init_params

    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2, d_head=8, d_ff=64
    )
    params = init_params(jax.random.key(0), cfg)
    real = [7, 3, 11, 2, 9]
    unpadded = jnp.array([real], jnp.int32)
    a = generate(params, unpadded, jax.random.key(1), cfg=cfg, max_new_tokens=6)

    pad_to = 12
    padded = jnp.array([[0] * (pad_to - len(real)) + real, list(range(1, pad_to + 1))], jnp.int32)
    lens = jnp.array([len(real), pad_to], jnp.int32)
    b = generate(
        params, padded, jax.random.key(2), cfg=cfg, max_new_tokens=6, prompt_lens=lens
    )
    np.testing.assert_array_equal(np.asarray(a)[0], np.asarray(b)[0])


def test_batch_processor_pipeline():
    cfg = llm.ProcessorConfig(
        model=llm.ModelSpec(preset="tiny", seed=7),
        batch_size=4,
        max_new_tokens=4,
    )
    processor = llm.build_llm_processor(
        cfg,
        preprocess=lambda row: {"prompt": f"say {row['word']}", "word": row["word"]},
        postprocess=lambda row: {
            "word": row["word"],
            "generated_text": row["generated_text"],
            "n": len(row["generated_tokens"]),
        },
    )
    ds = cad.from_items([{"word": w} for w in ["alpha", "beta", "gamma", "delta", "eps"]])
    rows = processor(ds).take_all()
    assert len(rows) == 5
    assert all(r["n"] == 4 for r in rows)
    assert {r["word"] for r in rows} == {"alpha", "beta", "gamma", "delta", "eps"}


def test_chat_template_stage():
    cfg = llm.ProcessorConfig(
        model=llm.ModelSpec(preset="tiny"),
        apply_chat_template=True,
        system_prompt="be brief",
        max_new_tokens=2,
    )
    processor = llm.build_llm_processor(cfg)
    ds = cad.from_items([{"prompt": "hi"}])
    row = processor(ds).take(1)[0]
    assert "<|user|>hi<|assistant|>" in row["prompt"]
    assert "<|system|>be brief" in row["prompt"]


def test_params_io_roundtrip(tmp_path):
    import jax

    from cluster_anywhere_tpu.llm import _params_io
    from cluster_anywhere_tpu.models.transformer import TransformerConfig, init_params

    cfg = TransformerConfig(vocab_size=32, d_model=16, n_layers=2, n_heads=2, n_kv_heads=2, d_head=8, d_ff=32)
    params = init_params(jax.random.key(0), cfg)
    _params_io.save_params(params, str(tmp_path / "ckpt"))
    loaded = _params_io.load_params(str(tmp_path / "ckpt"))
    flat1 = _params_io._flatten(params)
    flat2 = _params_io._flatten(loaded)
    assert set(flat1) == set(flat2)
    for k in flat1:
        np.testing.assert_array_equal(flat1[k], flat2[k])


def test_llm_serve_deployment():
    from cluster_anywhere_tpu import serve

    app = llm.build_llm_deployment(
        llm.ProcessorConfig(model=llm.ModelSpec(preset="tiny"), max_new_tokens=3)
    )
    handle = serve.run(app, name="llm_test")
    out = handle.remote({"prompt": "hello"}).result(timeout_s=120)
    assert out["prompt"] == "hello"
    assert out["num_generated_tokens"] == 3
    assert isinstance(out["generated_text"], str)
    # token streaming through the serve streaming-handle path
    toks = list(
        handle.options(method_name="stream", stream=True).remote(
            {"prompt": "hi", "max_new_tokens": 4}
        )
    )
    assert len(toks) == 4
    assert all("token_id" in t and "text" in t for t in toks)
    serve.delete("llm_test")
    serve.shutdown()


def test_continuous_batching_matches_sequential_greedy():
    """The gold contract of the iteration-level scheduler: a request decoded
    CONCURRENTLY with others (shared cache pool, per-row positions, slot
    churn) produces exactly the tokens it would get alone through the
    static generate() path (greedy)."""
    import jax
    import jax.numpy as jnp

    from cluster_anywhere_tpu.llm import ContinuousBatcher
    from cluster_anywhere_tpu.models.generate import generate
    from cluster_anywhere_tpu.models.transformer import TransformerConfig, init_params

    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2, d_head=8, d_ff=64
    )
    params = init_params(jax.random.key(0), cfg)
    prompts = [[1, 5, 9], [2, 3], [7, 8, 9, 10, 11]]
    want = [
        np.asarray(
            generate(
                params, jnp.asarray([p], jnp.int32), jax.random.key(9),
                cfg=cfg, max_new_tokens=6,
            )
        )[0].tolist()
        for p in prompts
    ]
    # slots=2 forces the third request to WAIT for a slot, exercising
    # admission mid-flight next to live decodes
    cb = ContinuousBatcher(params, cfg, slots=2, t_max=64, prefill_buckets=(8, 16))
    reqs = [cb.submit(p, max_new_tokens=6) for p in prompts]
    done = cb.pump()
    assert len(done) == 3 and all(r.done for r in reqs)
    for r, w in zip(reqs, want):
        assert r.out_tokens == w, (r.request_id, r.out_tokens, w)
    assert cb.stats["admitted"] == 3
    # concurrency actually happened: the three 6-token requests cannot have
    # taken 3 x 5 decode iterations (the first two share every step)
    assert cb.stats["decode_steps"] < 15, cb.stats


def test_continuous_batching_slot_churn_and_streaming():
    """Slots free the moment a request finishes and are re-admitted next
    step; step() yields per-request tokens incrementally (token streaming
    while other requests keep decoding)."""
    import jax
    import jax.numpy as jnp

    from cluster_anywhere_tpu.llm import ContinuousBatcher
    from cluster_anywhere_tpu.models.generate import generate
    from cluster_anywhere_tpu.models.transformer import TransformerConfig, init_params

    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2, d_head=8, d_ff=64
    )
    params = init_params(jax.random.key(0), cfg)
    cb = ContinuousBatcher(params, cfg, slots=2, t_max=64, prefill_buckets=(8,))
    short = cb.submit([1, 2], max_new_tokens=2)
    long = cb.submit([3, 4], max_new_tokens=10)
    late = cb.submit([5, 6], max_new_tokens=3)  # waits for short's slot
    seen: dict = {}
    step_members: list = []
    while cb.has_work:
        out = cb.step()
        step_members.append(set(out))
        for rid, toks in out.items():
            seen.setdefault(rid, []).append(list(toks))
    assert short.done and long.done and late.done
    # streaming: the long request produced tokens over many separate steps
    assert len(seen[long.request_id]) >= 8
    # churn: late genuinely ran WHILE long was still decoding (both ids
    # appear in at least one step's output)
    assert any(
        {late.request_id, long.request_id} <= members for members in step_members
    ), step_members
    # every token reaches step()'s output exactly once, incl. the prefill one
    assert sum(len(t) for t in seen[long.request_id]) == 10


def test_continuous_llm_server_concurrent_requests():
    """ContinuousLLMServer: concurrent callers share decode iterations (the
    serve-facing wrapper over ContinuousBatcher) and each gets exactly the
    text the plain static path would produce (greedy)."""
    import threading

    from cluster_anywhere_tpu.llm import ContinuousLLMServer, ModelSpec, ProcessorConfig

    cfg = ProcessorConfig(
        model=ModelSpec(preset="tiny"), max_prompt_len=16, max_new_tokens=8,
        temperature=0.0,
    )
    srv = ContinuousLLMServer(cfg, slots=4)
    prompts = ["hi", "hello there", "abc"]
    results = {}

    def call(p):
        results[p] = srv({"prompt": p})

    threads = [threading.Thread(target=call, args=(p,)) for p in prompts]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert set(results) == set(prompts)
    for p in prompts:
        assert results[p]["num_generated_tokens"] == 8, results[p]
    # the batcher really interleaved: 3 requests x 8 tokens but far fewer
    # decode iterations than 3 x 7 (they share steps)
    assert srv.cb.stats["admitted"] == 3
    assert srv.cb.stats["decode_steps"] < 21, srv.cb.stats
    # equivalence with the static path for one of them
    from cluster_anywhere_tpu.llm.processor import _InferenceWorker
    import numpy as np

    w = _InferenceWorker(cfg)
    static = w({"prompt": np.asarray(["hello there"], dtype=object)})
    assert results["hello there"]["generated_text"] == str(static["generated_text"][0])
    srv.close()  # replica lifecycle: the pump thread must stop


def test_moe_generate_and_continuous_batching():
    """MoE checkpoints serve: prefill/decode route each token through its
    top-1 expert (the dropless routed path of parallel/moe.py — no 'ep' axis
    at inference), greedy generation is deterministic, and the continuous
    batcher works over an MoE model unchanged, in the bfloat16 that is served.
    The prompt is one whose best two logits lie 0.18 or more apart at every
    step: after [1, 5, 9] tokens 7 and 56 tie at 2.359375 in bfloat16, and the
    jitted `generate` and the batcher's eager prefill break a tie differently."""
    import jax
    import jax.numpy as jnp

    from cluster_anywhere_tpu.llm import ContinuousBatcher
    from cluster_anywhere_tpu.models.generate import generate
    from cluster_anywhere_tpu.models.transformer import TransformerConfig, init_params

    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
        d_head=8, d_ff=64, n_experts=4,
    )
    params = init_params(jax.random.key(0), cfg)
    prompt = jnp.array([[7, 3, 2]], jnp.int32)
    a = generate(params, prompt, jax.random.key(1), cfg=cfg, max_new_tokens=6)
    b = generate(params, prompt, jax.random.key(2), cfg=cfg, max_new_tokens=6)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    cb = ContinuousBatcher(params, cfg, slots=2, t_max=32, prefill_buckets=(8,))
    req = cb.submit([7, 3, 2], max_new_tokens=6)
    cb.pump()
    assert req.done and req.out_tokens == np.asarray(a)[0].tolist()


def test_continuous_llm_server_pump_death_fails_fast():
    """An engine failure inside the pump loop (device OOM, shape bug) must
    not strand callers until the 120s queue timeout: in-flight requests get
    the error immediately, check_health reports the replica dead (so the
    serve controller replaces it), and new submits are refused."""
    import threading

    import pytest

    from cluster_anywhere_tpu.llm import ContinuousLLMServer, ModelSpec, ProcessorConfig

    cfg = ProcessorConfig(
        model=ModelSpec(preset="tiny"), max_prompt_len=16, max_new_tokens=8,
        temperature=0.0,
    )
    srv = ContinuousLLMServer(cfg, slots=4)
    try:
        boom = RuntimeError("simulated device OOM")
        orig_step = srv.cb.step
        calls = {"n": 0}

        def dying_step():
            calls["n"] += 1
            if calls["n"] >= 2:
                raise boom
            return orig_step()

        srv.cb.step = dying_step
        errs = {}

        def call():
            try:
                srv({"prompt": "hello"})
                errs["v"] = None
            except RuntimeError as e:
                errs["v"] = e

        t = threading.Thread(target=call)
        t.start()
        t.join(timeout=30)  # far below the 120s queue timeout
        assert not t.is_alive(), "caller stranded after pump death"
        assert errs["v"] is not None and "pump died" in str(errs["v"])
        with pytest.raises(RuntimeError, match="pump died"):
            srv.check_health()
        with pytest.raises(RuntimeError, match="pump died"):
            srv({"prompt": "after death"})
    finally:
        srv.close()


# -- the engine's own spans and counts (util/tracing.py: one span API) --------

TRACE = {"tid": "feedfacefeedface", "sid": "0badf00d"}


@pytest.fixture
def llm_spans(monkeypatch):
    """Reads the `llm.*` SPAN events out of tracing's event buffer.  This
    process is a cluster driver whose housekeeping ships that buffer to the
    head every second: it is held back while the test reads."""
    from cluster_anywhere_tpu.util import tracing

    drain = tracing.drain_events
    monkeypatch.setattr(tracing, "drain_events", lambda: [])
    drain()  # what earlier tests left
    assert not tracing.is_enabled()
    return lambda: [
        e for e in drain() if e["state"] == "SPAN" and e["name"].startswith("llm.")
    ]


_TINY = dict(vocab_size=64, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2, d_head=8, d_ff=64)
_TINY_MIXTURE = dict(_TINY, n_experts=4, n_experts_per_tok=2, moe_gated=True)
# a layer pattern: state-space, state-space, attention, twice over; one cached
# head, no rotary, a tied head: runs of length two and one of each kind
_TINY_HYBRID = dict(_TINY, n_layers=6, n_kv_heads=1, attn_layer_period=3, attn_layer_offset=2,
                    ssm_d_state=8, ssm_dt_rank=8, rotary=False, tie_embeddings=True)


def _tiny_batcher(**kw):
    import jax

    from cluster_anywhere_tpu.llm import ContinuousBatcher
    from cluster_anywhere_tpu.models.transformer import TransformerConfig, init_params

    cfg = TransformerConfig(**_TINY)
    return ContinuousBatcher(
        init_params(jax.random.key(0), cfg), cfg, slots=2, t_max=64, prefill_buckets=(8, 32), **kw
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


def _instruction_count(compiled) -> int:
    import re

    return sum(
        1 for line in compiled.as_text().splitlines()
        if re.match(r"\s+(ROOT )?%?[\w.\-]+ = ", line)
    )


def _decode_step_program(cfg, slots, t_max):
    """`_decode_step_rowpos` unjitted, and the shapes of its arguments."""
    import jax
    import jax.numpy as jnp

    from cluster_anywhere_tpu.llm import continuous
    from cluster_anywhere_tpu.models import generate, transformer

    params = jax.eval_shape(lambda k: transformer.init_params(k, cfg), jax.random.key(0))
    cache = jax.eval_shape(lambda: generate.init_cache(cfg, slots, t_max))
    key = jax.eval_shape(lambda: jax.random.key(0))
    ints = jax.ShapeDtypeStruct((6, slots), jnp.int32)
    floats = jax.ShapeDtypeStruct((2, slots), jnp.float32)
    prev = jax.ShapeDtypeStruct((slots,), jnp.int32)
    # a fresh function each time: jit keeps what it traced for one it has seen
    fn = lambda *a: continuous._decode_step_rowpos.__wrapped__(*a, cfg=cfg)
    return fn, (params, cache, ints, floats, prev, key)


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


def _attention_reference(q, k_cache, v_cache, valid_len, pad, n_heads):
    """Plain f32 attention over a repeated cache: the mathematics
    `_masked_attention` must keep, written the long way."""
    import jax.numpy as jnp

    q, k, v = (x.astype(jnp.float32) for x in (q, k_cache, v_cache))
    rep = n_heads // k.shape[2]
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)  # [B, T, H, D]
    b, t = k.shape[:2]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * q.shape[-1] ** -0.5
    slots = jnp.arange(t)[None, :]
    keep = slots < jnp.broadcast_to(jnp.asarray(valid_len), (b,))[:, None]
    if pad is not None:
        keep &= slots >= pad[:, None]
    scores = jnp.where(keep[:, None, None, :], scores, -jnp.inf)
    scores = scores - scores.max(axis=-1, keepdims=True)
    probs = jnp.exp(scores)
    probs = probs / probs.sum(axis=-1, keepdims=True)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


@pytest.mark.parametrize("rows", ["scalar_len", "per_row_len_and_pad"])
@pytest.mark.parametrize("n_heads,n_kv_heads", [(4, 4), (4, 2), (8, 1)])
def test_masked_attention_matches_plain_reference(n_heads, n_kv_heads, rows):
    """The decode attention takes the cache as stored ([B, T, KV, D], bf16)
    and gives what f32 attention over the cache repeated to every query head
    gives, to bf16's precision; whatever sits in the masked slots (past
    `valid_len`, before `pad`) changes nothing."""
    import jax
    import jax.numpy as jnp

    from cluster_anywhere_tpu.models import generate, transformer

    b, t, d = 3, 24, 16
    cfg = transformer.TransformerConfig(
        vocab_size=32, d_model=n_heads * d, n_layers=1, n_heads=n_heads, n_kv_heads=n_kv_heads,
        d_head=d, d_ff=32, max_seq_len=t,
    )
    kq, kk, kv, kg = jax.random.split(jax.random.key(n_heads * 10 + n_kv_heads), 4)
    q = jax.random.normal(kq, (b, 1, n_heads, d), jnp.float32).astype(jnp.bfloat16)
    k_cache = jax.random.normal(kk, (b, t, n_kv_heads, d), jnp.float32).astype(jnp.bfloat16)
    v_cache = jax.random.normal(kv, (b, t, n_kv_heads, d), jnp.float32).astype(jnp.bfloat16)
    if rows == "scalar_len":
        valid_len, pad = 9, None
        lens, pads = np.full(b, 9), np.zeros(b, int)
    else:
        lens, pads = np.array([t, 7, 13]), np.array([0, 3, 12])  # the last row sees one slot
        valid_len, pad = jnp.asarray(lens), jnp.asarray(pads)
    slots = np.arange(t)[None, :, None, None]
    masked = (slots >= lens[:, None, None, None]) | (slots < pads[:, None, None, None])
    garbage = (1e4 * jax.random.normal(kg, k_cache.shape, jnp.float32)).astype(jnp.bfloat16)

    out = generate._masked_attention(q, k_cache, v_cache, valid_len, cfg, pad)
    assert out.shape == q.shape and out.dtype == q.dtype
    want = _attention_reference(q, k_cache, v_cache, valid_len, pad, n_heads)
    # the probabilities are rounded to bf16 (8 bits) before they meet V, as is the output
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(want), atol=3e-2, rtol=2e-2)
    dirty = generate._masked_attention(
        q, jnp.where(masked, garbage, k_cache), jnp.where(masked, -garbage, v_cache),
        valid_len, cfg, pad,
    )
    assert bool(jnp.isfinite(dirty.astype(jnp.float32)).all())
    np.testing.assert_array_equal(np.asarray(dirty, np.float32), np.asarray(out, np.float32))


def _jaxpr_intermediates(jaxpr):
    """Every value a jaxpr computes, those of its nested jaxprs (the layer
    scan's body, a closed call) included."""
    import jax

    for eqn in jaxpr.eqns:
        yield from eqn.outvars
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _jaxpr_intermediates(sub)


@pytest.mark.parametrize("which", ["decode_step", "decode_one"])
def test_decode_never_widens_the_cache(which):
    """A decode program reads each layer's cache once, as stored: nothing it
    computes is as large as that cache repeated to every query head
    (S x T_max x n_heads x d_head), and nothing in f32 is as large as the cache
    itself (S x T_max x n_kv_heads x d_head).  A `jnp.repeat` of K or V, or an
    `.astype(float32)` of them, fails here on the CPU before a chip sees it."""
    import jax
    import jax.numpy as jnp

    from cluster_anywhere_tpu.models import generate, transformer

    # one layer, so the stacked cache is one layer's; a cache larger than any weight
    cfg = transformer.TransformerConfig(
        vocab_size=64, d_model=64, n_layers=1, n_heads=4, n_kv_heads=2, d_head=16,
        d_ff=128, max_seq_len=64,
    )
    slots, t_max = 4, 64
    fn, args = _decode_step_program(cfg, slots, t_max)
    if which == "decode_one":
        fn = lambda p, c, tok, pos, pad: generate.decode_one(p, c, tok, pos, cfg, pad)
        row = jax.ShapeDtypeStruct((slots,), jnp.int32)
        args = (*args[:2], row, jax.ShapeDtypeStruct((), jnp.int32), row)
    jaxpr = jax.make_jaxpr(fn)(*args)
    layer_cache = slots * t_max * cfg.n_kv_heads * cfg.d_head
    repeated = slots * t_max * cfg.n_heads * cfg.d_head
    values = [v.aval for v in _jaxpr_intermediates(jaxpr.jaxpr) if hasattr(v.aval, "shape")]
    assert len(values) > 100 and any(a.size == layer_cache for a in values)
    too_wide = [a for a in values if a.size >= repeated]
    f32_cache = [a for a in values if a.dtype == jnp.float32 and a.size >= layer_cache]
    assert too_wide == [] and f32_cache == [], (too_wide, f32_cache)


@contextlib.contextmanager
def _eager_dispatches():
    """The names of the primitives bound and the arrays put outside any
    compiled program while the block runs (a warm jitted call is neither)."""
    import jax
    from jax.extend.core import Primitive

    eager = []
    bind, put = Primitive.bind, jax._src.api.device_put
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(Primitive, "bind", lambda self, *a, **k: eager.append(self.name) or bind(self, *a, **k))
        patch.setattr(jax._src.api, "device_put", lambda *a, **k: eager.append("device_put") or put(*a, **k))
        yield eager


@pytest.mark.parametrize("model", [_TINY, _TINY_MIXTURE], ids=["dense", "mixture"])
def test_decode_step_inputs_reach_the_device_in_one_dispatch(model):
    """What `step` hands the device is one jitted call's arguments.  The traced
    decode program takes ONE key and splits it itself (S + 1 ways: the key the
    batcher carries on and one a row), and a warm `step()` on a live batcher
    binds no primitive and puts no array eagerly: the split unpacked into keys,
    six `jnp.asarray` and a `jnp.stack` were about forty dispatches a step,
    20 ms on the chip with the device idle."""
    import jax

    from cluster_anywhere_tpu.llm import ContinuousBatcher
    from cluster_anywhere_tpu.models.transformer import TransformerConfig, init_params

    cfg = TransformerConfig(**model)
    slots = 4
    fn, args = _decode_step_program(cfg, slots, 32)
    jaxpr = jax.make_jaxpr(fn)(*args).jaxpr
    is_key = lambda v: jax.dtypes.issubdtype(v.aval.dtype, jax.dtypes.prng_key)
    assert [v.aval.shape for v in jaxpr.invars if is_key(v)] == [()]
    splits = [e for e in jaxpr.eqns if e.primitive.name == "random_split"]
    assert [e.outvars[0].aval.shape for e in splits] == [(slots + 1,)]

    cb = ContinuousBatcher(init_params(jax.random.key(0), cfg), cfg, slots=slots, t_max=32,
                           prefill_buckets=(8,))
    reqs = [cb.submit([3, 1, 4], max_new_tokens=8, temperature=0.7, top_k=5), cb.submit([1, 5], max_new_tokens=8)]
    cb.step()
    cb.step()  # warm: the decode program is compiled, both requests are live
    with _eager_dispatches() as eager:
        out = cb.step()
    assert sorted(out) == [r.request_id for r in reqs] and all(len(t) == 1 for t in out.values())
    assert eager == []


def _watch_admit(cb):
    """Runs `cb._admit()` and says what it cost the host: (what
    `jax.monitoring` reported, as (event, function): a `jaxpr_trace`, a
    `jaxpr_to_mlir_module`, a `backend_compile`; the primitives bound and
    arrays put eagerly until `_install_slot` returned; those after it, the
    first token's sample)."""
    from jax import monitoring

    from cluster_anywhere_tpu.llm import continuous

    events, installed = [], []
    on_event = lambda event, duration, **kw: events.append(
        (event.rsplit("/", 1)[-1].removesuffix("_duration"), kw.get("fun_name")))
    install = continuous._install_slot

    def counted_install(*a):
        out = install(*a)
        installed.append(len(eager))
        return out

    with _eager_dispatches() as eager, pytest.MonkeyPatch.context() as patch:
        patch.setattr(continuous, "_install_slot", counted_install)
        monitoring.register_event_duration_secs_listener(on_event)
        try:
            cb._admit()
        finally:
            monitoring.unregister_event_duration_listener(on_event)
    (at,) = installed  # one admit
    return events, eager[:at], eager[at:]


@pytest.mark.parametrize("model", [_TINY, _TINY_MIXTURE], ids=["dense", "mixture"])
def test_a_warm_admit_runs_its_buckets_one_compiled_prefill(model, llm_spans):
    """`generate.prefill` is a compiled program a bucket: a bucket's first
    admit traces it (`prefill_traces`, the span's `traced`), and a further
    admit in that bucket traces, lowers and compiles nothing, the first
    token's sampler neither (`_sample_first`, one program a vocabulary), and
    dispatches the prefill, the install and the sample, each one compiled
    call, and eagerly nothing.  The eager `lax.scan` was traced and lowered
    again at every admit: 200-330 ms on the pump's thread for 14 ms of device
    work; the eager sample was a key split, a softmax, a sort and a cumulative
    sum op by op with the device idle."""
    import jax

    from cluster_anywhere_tpu.llm import ContinuousBatcher
    from cluster_anywhere_tpu.models import generate
    from cluster_anywhere_tpu.models.transformer import TransformerConfig, init_params
    from cluster_anywhere_tpu.util import tracing

    # a width and a cache length of this test's own: the programs are the
    # process's, and another test's batcher would have warmed its buckets
    cfg = TransformerConfig(**dict(model, d_ff=48))
    cb = ContinuousBatcher(init_params(jax.random.key(0), cfg), cfg, slots=6, t_max=40,
                           prefill_buckets=(8, 16))
    programs = generate.prefill_counted._cache_size()
    token = tracing.push_execution(TRACE)
    try:
        for n in (3, 12):  # cold: one program a bucket
            cb.submit(list(range(1, n + 1)), max_new_tokens=4)
            events, _, _ = _watch_admit(cb)
            assert {("jaxpr_trace", "prefill_counted"), ("jaxpr_to_mlir_module", "jit(prefill_counted)"),
                    ("backend_compile", "jit(prefill_counted)")} <= set(events)
        assert generate.prefill_counted._cache_size() == programs + 2 and cb.stats["prefill_traces"] == 2
        # warm, the last with no padding; a request's knobs are the sampler's operands
        for n, knobs in ((5, {}), (9, dict(temperature=0.7, top_k=3, top_p=0.9)), (8, dict(temperature=1.2))):
            cb.submit(list(range(2, n + 2)), max_new_tokens=4, **knobs)
            events, before_sample, sample = _watch_admit(cb)
            assert events == [] and before_sample == [] and sample == [], (n, events, sample)
    finally:
        tracing.pop_execution(token)
    assert generate.prefill_counted._cache_size() == programs + 2 and cb.stats["prefill_traces"] == 2
    admits = [e for e in llm_spans() if e["name"] == "llm.admit"]
    assert [(a["bucket"], a["traced"]) for a in admits] == [(8, 1), (16, 1), (8, 0), (16, 0), (8, 0)]


def test_the_plain_prefill_finds_the_program_an_admit_traced():
    """`generate.prefill` is `prefill_counted`'s program without its count,
    and the benchmark's check calls it for the admit's own program (device
    arrays, the pad by keyword: `benchmarks/harness/reference.py`): after an
    admit of the bucket it traces, lowers and compiles nothing.  Handing the
    pad on by position was a second signature, and a second trace, lowering
    and load of every bucket the check touches: 4 s each on the chip."""
    import jax
    import jax.numpy as jnp
    from jax import monitoring

    from cluster_anywhere_tpu.llm import ContinuousBatcher
    from cluster_anywhere_tpu.models import generate
    from cluster_anywhere_tpu.models.transformer import TransformerConfig, init_params

    cfg = TransformerConfig(**dict(_TINY_MIXTURE, d_ff=40, experts_held=(2, 2)))
    cb = ContinuousBatcher(init_params(jax.random.key(0), cfg), cfg, slots=2, t_max=40, prefill_buckets=(16,))
    cb.submit(list(range(1, 12)), max_new_tokens=4)
    cb._admit()
    padded = np.zeros((1, 16), np.int32)
    padded[0, 5:] = np.arange(1, 12)
    ids, pad, events = jnp.asarray(padded), jnp.asarray([5], np.int32), []
    on_event = lambda event, duration, **kw: events.append(
        (event.rsplit("/", 1)[-1].removesuffix("_duration"), kw.get("fun_name")))
    monitoring.register_event_duration_secs_listener(on_event)
    try:
        logits, rows = generate.prefill(cb.params, ids, cfg, cb.t_max, pad=pad)
    finally:
        monitoring.unregister_event_duration_listener(on_event)
    assert logits.shape == (1, 64) and set(rows) == {"k", "v"}
    assert not {"jaxpr_to_mlir_module", "backend_compile"} & {event for event, _ in events}, events


def test_the_prefix_cached_admit_prefills_through_the_same_program(llm_spans):
    """The prefix of a cache miss goes through `_prefill_padded` too: its
    bucket's program is traced once, a second miss of that length and a hit
    trace nothing, and the rows stay a batch of one from the prefill through
    the suffix steps to `_install_slot` (no eager slice or `[:, None]`)."""
    import jax

    from cluster_anywhere_tpu.llm import ContinuousBatcher
    from cluster_anywhere_tpu.models.transformer import TransformerConfig, init_params
    from cluster_anywhere_tpu.util import tracing

    cfg = TransformerConfig(**dict(_TINY, d_ff=80))
    cb = ContinuousBatcher(init_params(jax.random.key(0), cfg), cfg, slots=4, t_max=56,
                           prefill_buckets=(8, 32), prefix_cache_entries=4, prefix_block=4)
    token = tracing.push_execution(TRACE)
    try:
        seen = []
        for first in (1, 2, 1):  # a miss, a miss of the same split, a hit
            cb.submit(list(range(first, first + 19)), max_new_tokens=3)
            events, before_sample, _ = _watch_admit(cb)
            seen.append((cb.stats["prefill_traces"], ("jaxpr_trace", "prefill_counted") in events))
            if len(seen) > 1:
                assert set(events) <= {("jaxpr_trace", "convert_element_type")}, events
                # the snapshot's copy and the suffix's scalar uploads, no slice of the rows
                assert not {"slice", "squeeze", "gather", "broadcast_in_dim", "reshape", "scan"} & set(before_sample)
    finally:
        tracing.pop_execution(token)
    assert seen == [(1, True), (1, False), (1, False)]
    assert (cb.stats["prefix_misses"], cb.stats["prefix_hits"]) == (2, 1)
    admits = [e for e in llm_spans() if e["name"] == "llm.admit"]
    assert [(a["prefix_hit"], a["traced"]) for a in admits] == [(0, 1), (0, 0), (1, 0)]
    outs = [r.out_tokens for r in sorted(cb.pump(), key=lambda r: r.request_id)]
    assert outs[0] == outs[2]  # hit against miss, bit for bit


@pytest.mark.parametrize("program", ["forward", "prefill", "decode_one", "decode_step"])
@pytest.mark.parametrize("model", [_TINY, _TINY_MIXTURE, _TINY_HYBRID], ids=["dense", "mixture", "hybrid"])
def test_every_program_traces_the_one_block(model, program, monkeypatch):
    """A decoder block is written once: training's `forward`, `prefill`,
    `decode_one` and the batcher's `_decode_step_rowpos` all trace through
    `transformer._attention_half` or `transformer._ssm_half`, then
    `transformer._ffn_half`, in the one layer loop `transformer._scan_layers`,
    and end in `transformer._head`; outside them nothing projects q, k, v or
    norms.  A queued change to the block (window attention, a shared expert, a
    new cache layout) then has one site to edit."""
    import jax
    import jax.numpy as jnp

    from cluster_anywhere_tpu.models import generate, transformer

    assert generate._attention_half is transformer._attention_half
    assert generate._ffn_half is transformer._ffn_half
    assert generate._ssm_half is transformer._ssm_half and generate._ssm_mix is transformer._ssm_mix
    assert generate._scan_layers is transformer._scan_layers and generate._head is transformer._head
    calls = {"_attention_half": 0, "_ssm_half": 0, "_ffn_half": 0, "_head": 0, "_scan_layers": 0,
             "_rms_norm": 0, "_project_qkv": 0, "_ssm_mix": 0}
    inside = []

    def counted(name, is_half):
        inner = getattr(transformer, name)

        def wrapper(*a, **k):
            if not is_half:  # a norm or a projection: counted where no half is running
                calls[name] += not inside
                return inner(*a, **k)
            calls[name] += 1
            inside.append(name)
            try:
                return inner(*a, **k)
            finally:
                inside.pop()

        for module in (transformer, generate):
            if hasattr(module, name):
                monkeypatch.setattr(module, name, wrapper)

    for name in calls:
        counted(name, is_half=name.endswith("_half") or name == "_head")
    cfg = transformer.TransformerConfig(**model)
    slots, t_max = 4, 32
    fn, args = _decode_step_program(cfg, slots, t_max)
    params, cache = args[:2]
    ids, row = jax.ShapeDtypeStruct((slots, 8), jnp.int32), jax.ShapeDtypeStruct((slots,), jnp.int32)
    if program == "forward":
        fn, args = lambda p, i: transformer.forward(p, i, cfg), (params, ids)
    elif program == "prefill":
        fn, args = lambda p, i, pad: generate.prefill_counted.__wrapped__(p, i, cfg, t_max, pad), (params, ids, row)
    elif program == "decode_one":
        fn = lambda p, c, tok, pos: generate.decode_one(p, c, tok, pos, cfg)
        args = (params, cache, row, jax.ShapeDtypeStruct((), jnp.int32))
    jax.eval_shape(fn, *args)
    # the layer loop traces its body once a run of one kind (a model of one kind is
    # one run), and no norm, projection or mixer runs outside a half or the head
    runs = transformer._layer_runs(cfg.layer_kinds)
    attn, ssm = (sum(kind == k for kind, _, _ in runs) for k in ("attn", "ssm"))
    assert (attn, ssm) == ((2, 2) if model is _TINY_HYBRID else (1, 0))
    assert calls == {"_attention_half": attn, "_ssm_half": ssm, "_ffn_half": attn + ssm, "_head": 1,
                     "_scan_layers": 1, "_rms_norm": 0, "_project_qkv": 0, "_ssm_mix": 0}


def test_the_batcher_holds_no_model_mathematics():
    """`llm/continuous.py` is the scheduler, the sampler and the jitted
    wrapper: of `models/` it takes `prefill_counted`, the decode program's body and the
    nucleus mask its sampler shares, and it names no block, norm or layer loop."""
    import ast
    import inspect

    from cluster_anywhere_tpu.llm import continuous

    source = inspect.getsource(continuous)
    nodes = list(ast.walk(ast.parse(source)))
    imported = {
        alias.name
        for node in nodes
        if isinstance(node, ast.ImportFrom) and (node.module or "").startswith("models")
        for alias in node.names
    }
    # and the cache's layout, which is `generate.py`'s: what a slot holds, how one
    # request's rows are written over it, how much of it is recurrent state
    assert imported == {"prefill_counted", "decode_rows", "_nucleus_mask", "TransformerConfig",
                        "init_cache", "install_rows", "recurrent_state_bytes", "cache_bytes_per_token",
                        "key_slots", "cache_kind_bytes"}
    for name in ("_rms_norm", "_scan_blocks", "_scan_layers", "_block_", "_half", "_ssm_mix", "_project_qkv",
                 "_rope", "lax.scan", '"k"', '"v"', '"h"', "n_kv_heads", "d_inner"):
        assert name not in source, name
    called = [n.func.id for n in nodes if isinstance(n, ast.Call) and isinstance(n.func, ast.Name)]
    assert called.count("prefill_counted") == 1  # one pad-and-prefill for both admits


def _rows_of_the_next_step(cb):
    """[(slot, request)] that the batcher's next dispatch holds, counted by hand:
    the slots whose request is short of its length even once the step in flight,
    if it holds the request, has landed."""
    flying = [r for _, r in cb._flight.rows] if cb._flight is not None else []
    return [(s, r) for s, r in enumerate(cb._by_slot)
            if r is not None and len(r.out_tokens) + sum(r is f for f in flying) < r.max_new_tokens]


def test_sampled_streams_are_the_eager_split_and_sample():
    """Sampled streams keep their bits: with temperature, top-k and top-p set
    and requests admitted at different steps (so the admit's `split(rng)`
    interleaves with the step's), every decode token is what the eager formula
    gives: `rng, *keys = split(rng, S + 1)`, element 0 carried on, elements
    1..S the rows' keys, `_sample_rowwise` over the step's logits, a row's
    input the step before's own token unless the slot was admitted since.  A
    call hands out the step the call before dispatched."""
    import jax
    import jax.numpy as jnp

    from cluster_anywhere_tpu.llm import ContinuousBatcher, continuous
    from cluster_anywhere_tpu.models import generate
    from cluster_anywhere_tpu.models.transformer import TransformerConfig, init_params

    cfg = TransformerConfig(**_TINY, dtype=jnp.float32)
    params = init_params(jax.random.key(3), cfg)
    cb = ContinuousBatcher(params, cfg, slots=3, t_max=48, prefill_buckets=(8,))

    def eager_step(rng):
        """The next token of every slot and the carried key, op by op."""
        tokens = jnp.where(jnp.asarray(cb._fresh) != 0, jnp.asarray(cb._tokens), cb._prev)
        pos, pads = jnp.asarray(cb._pos), jnp.asarray(cb._pads)
        x = params["embed"].astype(cfg.dtype)[tokens][:, None, :]
        attn = lambda x, bp, experts, cache, layer: (
            generate._block_decode_rowpos(bp, x, cache, layer, pos, cfg, pads, None, experts)[0], cache, None)
        x, _, _ = generate._scan_blocks({"attn": attn}, x, params, cfg, cb.cache)
        logits = generate._head(params, x, cfg, row=0)
        rng, *keys = jax.random.split(rng, cb.slots + 1)
        nxt = continuous._sample_rowwise(
            logits, jnp.stack(keys), jnp.asarray(cb._temps), jnp.asarray(cb._topks), jnp.asarray(cb._topps))
        return rng, np.asarray(nxt)

    arrivals = {
        0: dict(prompt_ids=[3, 1, 4, 1, 5], max_new_tokens=9, temperature=0.8, top_k=8, top_p=0.9),
        2: dict(prompt_ids=[2, 7], max_new_tokens=6, temperature=1.3, top_p=0.7),
        3: dict(prompt_ids=[9, 9, 8], max_new_tokens=5),  # greedy, beside the sampled rows
        5: dict(prompt_ids=[6, 2, 6], max_new_tokens=7, temperature=1.0, top_k=3),  # waits for a slot
    }
    rng, reqs, compared, flying = cb._rng, [], 0, {}
    for i in range(16):
        if i in arrivals:
            reqs.append(cb.submit(arrivals[i].pop("prompt_ids"), **arrivals[i]))
        admitted = cb.stats["admitted"]
        cb._admit()  # as `step` begins; its own admit then finds the queue as this leaves it
        for _ in range(cb.stats["admitted"] - admitted):
            rng, _ = jax.random.split(rng)
        rows = {r.request_id: s for s, r in _rows_of_the_next_step(cb)}
        if rows:
            rng, want = eager_step(rng)  # before the step: it donates the cache
        out = cb.step()
        # the call dispatched `rows` and handed out the step in flight before it
        assert sorted(out) == sorted(flying)
        for rid, (slot, token) in flying.items():
            assert out[rid] == [token], (i, rid)
            compared += 1
        flying = {rid: (slot, want[slot]) for rid, slot in rows.items()}
    assert all(r.done for r in reqs) and compared == sum(r.max_new_tokens - 1 for r in reqs)
    assert not cb.has_work and cb.stats["late_rows"] == 0
    sampled = [r for r in reqs if r.temperature > 0]
    assert any(len(set(r.out_tokens)) > 2 for r in sampled)
    np.testing.assert_array_equal(jax.random.key_data(cb._rng), jax.random.key_data(rng))


def test_the_step_sorts_only_while_a_truncating_request_lives(llm_spans):
    """The sampler's sorts follow the knobs of the slots that hold a request:
    `llm.step` says of the step it read how many rows it held, and how many of
    the held slots sampled and how many of those truncated as it was dispatched;
    `stats["sort_steps"]` counts the steps in which one did, and a slot that
    frees, by its request's end or its cancel, asks nothing from then on
    (temperature 0, top-k 0, top-p 1.0): the sampler reads every row's knobs,
    live or not, so a finished top-p request's knobs left in its slot would
    keep every later step sorting.  A request whose last token is in flight
    holds its slot until that step is read, so its knobs are in one step more
    than its rows are.  A stale top-k or top-p beside temperature 0 never counts."""
    import jax

    from cluster_anywhere_tpu.llm import ContinuousBatcher
    from cluster_anywhere_tpu.models.transformer import TransformerConfig, init_params
    from cluster_anywhere_tpu.util import tracing

    cfg = TransformerConfig(**_TINY)
    cb = ContinuousBatcher(init_params(jax.random.key(0), cfg), cfg, slots=4, t_max=64, prefill_buckets=(8,))
    arrivals = {
        0: [dict(max_new_tokens=16), dict(max_new_tokens=14, top_k=5, top_p=0.5)],  # greedy, one with stale knobs
        2: [dict(max_new_tokens=9, temperature=0.9)],  # samples, sorts nothing
        4: [dict(max_new_tokens=3, temperature=0.8, top_p=0.9)],  # truncates for three tokens
        9: [dict(max_new_tokens=12, temperature=1.1, top_k=4)],  # truncates until it is cancelled
        13: [dict(max_new_tokens=1, temperature=0.7, top_p=0.3)],  # finishes inside its admit
    }
    truncates = lambda r: r.temperature > 0 and (r.top_k > 0 or 0 < r.top_p < 1)
    free = lambda s: (cb._temps[s], cb._topks[s], cb._topps[s]) == (0.0, 0, 1.0)
    want, reqs = [], {}
    token = tracing.push_execution(TRACE)
    try:
        for i in range(18):
            for knobs in arrivals.get(i, ()):
                reqs[i] = cb.submit([3, 1, 4, 1, 5], **knobs)  # the step's last: 9 and 13 are asked for below
            if i == 12:
                assert cb.cancel(reqs[9].request_id)  # while the step of call 11 holds its row
            cb._admit()  # as `step` begins; its own admit then finds the queue empty
            holding = [r for r in cb._by_slot if r is not None]
            rows = _rows_of_the_next_step(cb)
            if rows:
                want.append((len(rows), sum(r.temperature > 0 for r in holding), sum(map(truncates, holding))))
            cb.step()
            read = want[:len(want) - (cb._flight is not None)]
            assert cb.stats["decode_steps"] == len(read) and cb.stats["sort_steps"] == sum(w[2] > 0 for w in read), i
            assert all(free(s) for s, r in enumerate(cb._by_slot) if r is None), i
    finally:
        tracing.pop_execution(token)
    assert not cb.has_work and all(free(s) for s in range(4))
    assert (cb._sample_rows, cb._truncate_rows) == (0, 0)
    steps = [e for e in llm_spans() if e["name"] == "llm.step" and e["live"]]
    assert [(e["live"], e["sample_rows"], e["truncate_rows"]) for e in steps] == want
    # the two truncating requests' lives and nothing else.  The first: the steps of calls 4
    # and 5 hold its row (the first of three tokens is the admit's) and the step of call 6
    # its knobs, while its last token is in flight.  The second: the steps of calls 9, 10, 11;
    # the cancel before call 12 resets the knobs at once, and the row of step 11 is dropped
    at = [i for i, w in enumerate(want) if w[2]]
    assert at == [4, 5, 6, 9, 10, 11] and cb.stats["sort_steps"] == 6 and max(w[2] for w in want) == 1
    assert [w[0] for w in want[4:7]] == [4, 4, 3] and cb.stats["late_rows"] == 1
    assert max(w[1] for w in want) == 2 and reqs[13].done and len(reqs[13].out_tokens) == 1
    assert len(reqs[9].out_tokens) == 3 and cb.stats["tokens_out"] == 16 + 14 + 9 + 3 + 3 + 1
    import inspect

    from cluster_anywhere_tpu.llm import serve_llm

    shipped = inspect.getsource(serve_llm.ContinuousLLMServer._sync_engine_metrics)
    assert '"sort_steps", "ca_serve_sort_steps_total"' in shipped


@pytest.mark.parametrize("model", ["causal", "blocks"])
def test_a_step_says_how_much_of_the_cache_its_live_rows_could_reach(model, llm_spans):
    """`cache_rows_read` on `llm.step` and in `cb.stats`: of a layer's keys, the
    slots the step's attention kernel fetches, the live rows' own [pads, pos +
    the step's tokens) in whole key blocks, by the kernel's own helper on the
    host's vectors as the step was dispatched; `cache_rows`, the slots x t_max
    it is a share of.  After an admit, a request's end and a cancel, a freed
    slot's stale pos and pads count for nothing."""
    import importlib

    import jax
    import jax.numpy as jnp

    from cluster_anywhere_tpu.llm import ContinuousBatcher
    from cluster_anywhere_tpu.models.transformer import TransformerConfig, init_params
    from cluster_anywhere_tpu.util import tracing

    attention = importlib.import_module("cluster_anywhere_tpu.ops.attention")
    blocks = model == "blocks"
    cfg = TransformerConfig(**dict(_TINY, **(dict(
        vocab_size=251, n_experts=8, n_experts_per_tok=2, moe_gated=True, block_length=4, mask_token_id=250,
        denoise_steps=4, dtype=jnp.float32, param_dtype=jnp.float32) if blocks else {})))
    slots, t_max, tokens = 4, 64, 4 if blocks else 1
    assert attention.decode_key_block(t_max, cfg.n_kv_heads) == t_max  # one key block a row here ...
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(attention, "DECODE_BLOCK_K", 8)  # ... so rows of eight slots
        patch.setattr(attention, "DECODE_BLOCK_ROWS", 8)
        cb = ContinuousBatcher(init_params(jax.random.key(0), cfg), cfg, slots=slots, t_max=t_max,
                               prefill_buckets=(8, 32))
        want, reqs = [], []
        token = tracing.push_execution(TRACE)
        try:
            for i in range(40):
                if i in (0, 3, 5):
                    reqs.append(cb.submit(list(range(1, 4 + 5 * len(reqs))), max_new_tokens=(6, 30, 14)[len(reqs)]))
                if i == 12:
                    assert cb.cancel(reqs[1].request_id)
                cb._admit()
                held = [s for s, _ in _rows_of_the_next_step(cb)] if not blocks else \
                    [s for s, r in enumerate(cb._by_slot) if r is not None]
                if held:
                    first, last = cb._pads[held], cb._pos[held] + tokens
                    want.append(int(sum((-(-l // 8) - f // 8) * 8 for f, l in zip(first, last))))
                cb.step()
        finally:
            tracing.pop_execution(token)
    assert not cb.has_work and reqs[1].done and len(reqs[1].out_tokens) < 30 and cb.stats["cancelled"] == 1
    steps = [e for e in llm_spans() if e["name"] == "llm.step" and e["live"]]
    assert [e["cache_rows_read"] for e in steps] == want and len(want) == cb.stats["decode_steps"] > 12
    assert {e["cache_rows"] for e in steps} == {slots * t_max}
    assert cb.stats["cache_rows_read"] == sum(want) and cb.stats["cache_rows"] == len(want) * slots * t_max
    # a row's share grows with its depth, one live row reads less than three, and never the cache
    assert min(want) >= 8 and max(want) < slots * t_max / 2 and len(set(want)) > 3
    import inspect

    from cluster_anywhere_tpu.llm import serve_llm

    shipped = inspect.getsource(serve_llm.ContinuousLLMServer._sync_engine_metrics)
    assert '"cache_rows_read", "ca_serve_cache_rows_read_total"' in shipped
    assert '"cache_rows", "ca_serve_cache_rows_total"' in shipped


# -- the cache is the layer loop's carry: one row a slot written in place ---------


def _float32_model(model, seed=0):
    """(cfg, params) in float32: `_TINY_HYBRID` as `_hybrid` makes it."""
    import jax
    import jax.numpy as jnp

    from cluster_anywhere_tpu.models.transformer import TransformerConfig, init_params

    if model is _TINY_HYBRID:
        return _hybrid(seed=seed)
    cfg = TransformerConfig(**model, dtype=jnp.float32, param_dtype=jnp.float32)
    return cfg, init_params(jax.random.key(seed), cfg)


def _plain_decode_rows(params, cache, tokens, pos, pads, cfg, live=None):
    """`generate.decode_rows` layer by layer in Python: each layer's state taken
    out of the stacks, row b's k and v written at slot pos[b] of the layer, the
    layer put back.  Returns (logits [B, V], the cache after)."""
    import jax
    import jax.numpy as jnp

    from cluster_anywhere_tpu.models import generate, transformer

    cache, rows = dict(cache), jnp.arange(tokens.shape[0])
    stacks, seen = transformer.layer_stacks(params), {"attn": 0, "ssm": 0}
    x = params["embed"].astype(cfg.dtype)[tokens][:, None, :]
    for kind in cfg.layer_kinds:
        i = seen[kind]
        seen[kind] += 1
        bp = jax.tree_util.tree_map(lambda w: w[i], stacks[kind])
        if kind == "attn":

            def core(q, k, v):
                for name, new in (("k", k), ("v", v)):
                    cache[name] = cache[name].at[i].set(cache[name][i].at[rows, pos].set(new[:, 0]))
                return generate._masked_attention(q, cache["k"][i], cache["v"][i], pos + 1, cfg, pads), None

            x, _ = transformer._attention_half(bp, x, cfg, (pos - pads)[:, None], core)
        else:

            def core(xs):
                y, (window, h) = transformer._ssm_mix(bp, xs, (cache["conv"][i], cache["h"][i]), cfg)
                cache.update(conv=cache["conv"].at[i].set(window), h=cache["h"].at[i].set(h))
                return y, None

            x, _ = transformer._ssm_half(bp, x, cfg, core)
        x = transformer._ffn_half(bp, x, cfg, None if live is None else live[:, None])[0]
    return transformer._head(params, x, cfg, row=0), cache


def _slots_at_different_depths(cfg, seed=1):
    """(cache, tokens, pos, pads) of four slots, every array of the cache filled
    with noise (what a slot holds past its depth is masked, never read)."""
    import jax
    import jax.numpy as jnp

    from cluster_anywhere_tpu.models import generate

    cache = generate.init_cache(cfg, 4, 16)
    keys = jax.random.split(jax.random.key(seed), len(cache))
    cache = {n: 0.5 * jax.random.normal(k, c.shape, c.dtype) for k, (n, c) in zip(keys, cache.items())}
    as_ints = lambda v: jnp.asarray(v, jnp.int32)
    return cache, as_ints([3, 9, 5, 1]), as_ints([3, 12, 0, 7]), as_ints([0, 4, 0, 2])


def _assert_one_row_a_slot_changed(before, after, pos):
    """k and v differ in row pos[b] of slot b, in every layer, and nowhere
    else, bit for bit; every slot's recurrent state moved, in every layer."""
    before, after = ({n: np.asarray(a) for n, a in c.items()} for c in (before, after))
    pos = np.asarray(pos)
    for name in ("k", "v"):
        changed = np.any(before[name] != after[name], axis=(-2, -1))  # [L, B, T]
        want = np.zeros_like(changed)
        want[:, np.arange(len(pos)), pos] = True
        np.testing.assert_array_equal(changed, want)
    for name in set(before) - {"k", "v"}:
        moved = np.any(before[name] != after[name], axis=tuple(range(2, before[name].ndim)))  # [L, B]
        assert moved.all(), name


@pytest.mark.parametrize("program", ["decode_rows", "suffix_step", "generate"])
@pytest.mark.parametrize("model", [_TINY, _TINY_MIXTURE, _TINY_HYBRID], ids=["dense", "mixture", "hybrid"])
def test_decode_writes_one_row_a_slot_and_is_the_plain_layer_loop(model, program):
    """The cache travels through the layer loop as its carry, each layer
    reading and writing the stacks at its own number within its kind (runs of
    two kinds, a mixture's held experts, a stack that is one run): over slots
    at different depths `decode_rows` gives the logits and the cache of a plain
    per-layer loop, and the cache after differs from the cache before in
    exactly row pos[b] of each slot's k and v and in every recurrent state.
    The same through `_suffix_step`'s cache of batch one (jitted, its rows
    donated) and through `generate()`'s scan of decode steps.  This is what
    holds `_decode_step_rowpos`'s "rewrites it in place" on the CPU; the
    chip's program is held by tests/test_chip_compile.py."""
    import jax
    import jax.numpy as jnp

    from cluster_anywhere_tpu.llm import continuous
    from cluster_anywhere_tpu.models import generate

    cfg, params = _float32_model(model)
    cache, tokens, pos, pads = _slots_at_different_depths(cfg)
    close = lambda got, want: np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)
    with jax.default_matmul_precision("highest"):
        if program == "decode_rows":
            live = jnp.asarray([True, True, False, True])  # an empty slot's row takes no expert
            logits, after, _ = generate.decode_rows(params, cache, tokens, pos, pads, cfg, live)
            want, want_after = _plain_decode_rows(params, cache, tokens, pos, pads, cfg, live)
            close(logits, want)
            _assert_one_row_a_slot_changed(cache, after, pos)
            for name in cache:
                close(after[name], want_after[name])
        elif program == "suffix_step":
            want, want_after = _plain_decode_rows(params, cache, tokens, pos, pads, cfg)
            for b in range(tokens.shape[0]):
                one = slice(b, b + 1)
                rows = {n: c[:, one] for n, c in cache.items()}
                before = {n: np.array(c) for n, c in rows.items()}  # the rows are donated
                logits, after = continuous._suffix_step(params, rows, tokens[one], pos[one], pads[one], cfg=cfg)
                close(logits, want[one])
                _assert_one_row_a_slot_changed(before, after, pos[one])
                for name in cache:
                    close(after[name], want_after[name][:, one])
        else:
            prompt = jnp.asarray(np.random.default_rng(2).integers(1, cfg.vocab_size, (3, 5)), jnp.int32)
            lens = jnp.asarray([5, 3, 4], jnp.int32)  # left-padded rows: pads of 0, 2, 1
            n = 6
            got = generate.generate(params, prompt, jax.random.key(0), cfg=cfg, max_new_tokens=n, prompt_lens=lens)
            pads = 5 - lens
            logits, cache = generate.prefill(params, prompt, cfg, 5 + n, pads)
            want = [jnp.argmax(logits, axis=-1)]
            for i in range(n - 1):
                pos = jnp.full((3,), 5 + i, jnp.int32)
                logits, cache = _plain_decode_rows(params, cache, want[-1], pos, pads, cfg)
                want.append(jnp.argmax(logits, axis=-1))
            np.testing.assert_array_equal(np.asarray(got), np.stack(want, axis=1))


# -- a layer pattern: state-space layers beside attention layers ---------------


def _hybrid(dtype=None, seed=0):
    """(cfg, params) of `_TINY_HYBRID` in float32 with the three inner norms'
    weights moved off 1 and a convolution bias off 0, so that a norm or a bias
    that is left out shows."""
    import jax
    import jax.numpy as jnp

    from cluster_anywhere_tpu.models.transformer import TransformerConfig, init_params

    dtype = dtype or jnp.float32
    cfg = TransformerConfig(**_TINY_HYBRID, dtype=dtype, param_dtype=dtype)
    params = init_params(jax.random.key(seed), cfg)
    ssm = params["ssm_blocks"]
    for name, lo, hi in (("dt_norm", 0.6, 1.4), ("b_norm", 1.5, 0.7), ("c_norm", 0.8, 1.3)):
        ssm[name] = ssm[name] * jnp.linspace(lo, hi, ssm[name].shape[-1]).astype(dtype)
    ssm["conv_b"] = ssm["conv_b"] + jnp.linspace(-0.3, 0.3, ssm["conv_b"].shape[-1]).astype(dtype)
    return cfg, params


def test_state_space_init_is_mambas_and_each_kind_holds_its_own_layers():
    import jax
    import jax.numpy as jnp

    from cluster_anywhere_tpu.models import generate
    from cluster_anywhere_tpu.models.transformer import TransformerConfig, init_params

    cfg = TransformerConfig(**_TINY_HYBRID)
    assert cfg.layer_kinds == ("ssm", "ssm", "attn", "ssm", "ssm", "attn") and cfg.d_inner == 64
    params = init_params(jax.random.key(0), cfg)
    assert "lm_head" not in params  # tied: the head is the embedding
    assert {v.shape[0] for v in params["blocks"].values()} == {2}
    ssm = params["ssm_blocks"]
    assert {v.shape[0] for v in ssm.values()} == {4} and not {"wq", "wk", "wv", "wo"} & set(ssm)
    assert not {"ssm_in", "a_log"} & set(params["blocks"])
    np.testing.assert_allclose(np.exp(np.asarray(ssm["a_log"][0, 0])), np.arange(1, 9), rtol=1e-6)
    step = np.asarray(jax.nn.softplus(ssm["dt_bias"]))
    assert step.min() >= 1e-3 * 0.999 and step.max() <= 1e-1 * 1.001 and np.all(np.asarray(ssm["ssm_d"]) == 1)
    cache = generate.init_cache(cfg, 3, 16)
    assert {k: (v.shape, v.dtype) for k, v in cache.items()} == {
        "k": ((2, 3, 16, 1, 8), jnp.bfloat16), "v": ((2, 3, 16, 1, 8), jnp.bfloat16),
        "conv": ((4, 3, 3, 64), jnp.bfloat16), "h": ((4, 3, 64, 8), jnp.float32)}
    assert generate.recurrent_state_bytes(cache) == 4 * 3 * (3 * 64 * 2 + 64 * 8 * 4)
    assert generate.recurrent_state_bytes(generate.init_cache(TransformerConfig(**_TINY), 3, 16)) == 0
    # a mesh of more than one device is refused by name, as top-k experts over 'ep' are
    from cluster_anywhere_tpu.models.transformer import param_specs

    with pytest.raises(NotImplementedError, match="ssm"):
        param_specs(cfg)


@pytest.mark.parametrize("t", [1, 5, 16, 37])
def test_the_chunked_scan_is_the_recurrence_step_by_step(t):
    """`_selective_scan` at one token, under a chunk, at a chunk and over
    several with a ragged tail, from a state that is not zero, against the
    recurrence written out position by position."""
    import jax
    import jax.numpy as jnp

    from cluster_anywhere_tpu.models.transformer import _selective_scan

    ks = jax.random.split(jax.random.key(t), 6)
    bsz, ch, n = 2, 6, 4
    dt = jax.nn.softplus(jax.random.normal(ks[0], (bsz, t, ch)))
    dt = dt.at[0, : t // 3].set(0.0)  # left pads: steps that leave the state as it is
    a = -jnp.exp(jax.random.normal(ks[1], (ch, n)))
    b, c = jax.random.normal(ks[2], (bsz, t, n)), jax.random.normal(ks[3], (bsz, t, n))
    xc, h0 = jax.random.normal(ks[4], (bsz, t, ch)), jax.random.normal(ks[5], (bsz, ch, n))
    y, h_t = _selective_scan(dt, a, b, c, xc, h0)
    h, want = np.asarray(h0, np.float64), []
    for i in range(t):
        step = np.asarray(dt[:, i], np.float64)
        h = np.exp(step[..., None] * np.asarray(a)) * h + (
            step * np.asarray(xc[:, i]))[..., None] * np.asarray(b[:, i])[:, None, :]
        want.append(np.einsum("bcn,bn->bc", h, np.asarray(c[:, i])))
    np.testing.assert_allclose(np.asarray(y), np.stack(want, axis=1), atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(h_t), h, atol=2e-5, rtol=2e-5)


def test_left_pads_do_not_touch_the_recurrent_state():
    """One prompt in two buckets and unpadded gives the same logits and the
    same state: a pad's input and step size are zeroed, so the convolution sees
    what an unpadded prompt sees before its start and h passes the pads."""
    import jax
    import jax.numpy as jnp

    from cluster_anywhere_tpu.models import generate

    cfg, params = _hybrid()
    prompt = np.random.default_rng(0).integers(1, cfg.vocab_size, 11)
    with jax.default_matmul_precision("highest"):
        want, rows = generate.prefill(params, jnp.asarray(prompt[None]), cfg, 48)
        for bucket in (16, 32):
            padded = np.zeros((1, bucket), np.int32)
            padded[0, bucket - 11:] = prompt
            got, padded_rows = generate.prefill(
                params, jnp.asarray(padded), cfg, 48, pad=jnp.asarray([bucket - 11], jnp.int32))
            np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
            for name in ("conv", "h"):
                np.testing.assert_allclose(np.asarray(padded_rows[name]), np.asarray(rows[name]), atol=2e-5)
        # and the pads would have mattered: with the convolution's bias the state they
        # leave behind is not zero when nothing masks them
        unmasked, _ = generate.prefill(params, jnp.asarray(padded), cfg, 48)
    assert float(np.max(np.abs(np.asarray(unmasked) - np.asarray(want)))) > 1e-3


def test_the_recurrence_is_seen_to_matter():
    """A token further back than the convolution reaches (and that no attention
    layer could carry alone) changes the last logits, through h; and a program
    that keeps h in bfloat16, or runs the recurrence in it, is further from
    the float32 recurrence than the float32 program is from itself in another
    order of summation."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from cluster_anywhere_tpu.models import generate, transformer

    cfg, params = _hybrid()
    # the state-space layers alone: no attention layer carries a far token
    ssm_only = dataclasses.replace(cfg, n_layers=2)
    assert ssm_only.layer_kinds == ("ssm", "ssm")
    ssm_params = {"embed": params["embed"], "ln_f": params["ln_f"], "blocks": params["blocks"],
                  "ssm_blocks": jax.tree_util.tree_map(lambda w: w[:2], params["ssm_blocks"])}
    ids = np.random.default_rng(1).integers(1, cfg.vocab_size, (1, 24))
    far = ids.copy()
    far[0, 24 - 1 - 3 * cfg.ssm_d_conv] += 1  # twelve positions back: out of both layers' windows
    with jax.default_matmul_precision("highest"):
        a = transformer.forward(ssm_params, jnp.asarray(ids), ssm_only)[0, -1]
        b = transformer.forward(ssm_params, jnp.asarray(far), ssm_only)[0, -1]
    assert float(jnp.max(jnp.abs(a - b))) > 1e-2


@pytest.mark.parametrize("lowered", ["state", "recurrence"])
def test_a_state_or_a_recurrence_in_bfloat16_is_told_from_float32(lowered, monkeypatch):
    """The decode through the cache, teacher-forced over 40 tokens in float32
    weights: with h kept in bfloat16 between two tokens, or the recurrence run
    in bfloat16, the last logits differ from the float32 program's by far more
    than the float32 program differs from the plain forward pass."""
    import jax
    import jax.numpy as jnp

    from cluster_anywhere_tpu.models import generate, transformer

    cfg, params = _hybrid()
    ids = np.random.default_rng(2).integers(1, cfg.vocab_size, (1, 48))

    def through_the_cache():
        logits, rows = generate.prefill(params, jnp.asarray(ids[:, :8]), cfg, 64)
        for i in range(8, 48):
            logits, rows = generate.decode_one(params, rows, jnp.asarray(ids[:, i]), jnp.int32(i), cfg)
        return np.asarray(logits[0]), rows

    with jax.default_matmul_precision("highest"):
        want = np.asarray(transformer.forward(params, jnp.asarray(ids), cfg)[0, -1])
        exact, rows = through_the_cache()
        assert rows["h"].dtype == jnp.float32
        if lowered == "state":  # h handed on in bfloat16, the recurrence itself in float32
            zero_state = transformer._ssm_zero_state
            in_bf16 = lambda cfg, b: tuple(s.astype(jnp.bfloat16) for s in zero_state(cfg, b))
            monkeypatch.setattr(transformer, "_ssm_zero_state", in_bf16)
        else:
            monkeypatch.setattr(transformer, "SSM_STATE_DTYPE", jnp.bfloat16)
        jax.clear_caches()
        lower, rows = through_the_cache()
    jax.clear_caches()
    assert rows["h"].dtype == jnp.bfloat16
    own, low = float(np.max(np.abs(exact - want))), float(np.max(np.abs(lower - want)))
    assert own < 1e-4 and low > 20 * max(own, 1e-5), (own, low)


def _hybrid_batcher(params, cfg, **kw):
    from cluster_anywhere_tpu.llm import ContinuousBatcher

    return ContinuousBatcher(params, cfg, slots=2, t_max=64, prefill_buckets=(8, 32), **kw)


def _alone(params, cfg, prompt, n_new):
    """What a fresh batcher answers to one request sent alone."""
    cb = _hybrid_batcher(params, cfg)
    req = cb.submit(prompt, max_new_tokens=n_new)
    cb.pump()
    return list(req.out_tokens)


@pytest.mark.parametrize("freed_by", ["a_longer_request", "cancel"])
def test_a_reused_slot_answers_as_a_fresh_batcher_does(freed_by):
    """An admit overwrites a slot's recurrent state whole.  A stale key/value
    row is masked by position; a stale h is masked by nothing, and after a
    `cancel()` the freed lane's state keeps moving with every step until the
    next admit: the request that takes the slot answers as if it were alone."""
    cfg, params = _hybrid()
    rng = np.random.default_rng(3)
    first, other, second = (rng.integers(1, cfg.vocab_size, n) for n in (30, 9, 6))
    want = _alone(params, cfg, second, 8)
    cb = _hybrid_batcher(params, cfg)
    a = cb.submit(first, max_new_tokens=12)
    b = cb.submit(other, max_new_tokens=40)  # keeps the batcher stepping beside the freed lane
    if freed_by == "cancel":
        for _ in range(3):
            cb.step()
        assert cb.cancel(a.request_id) and cb._by_slot[a.slot] is None
        h = np.asarray(cb.cache["h"][:, a.slot])
        for _ in range(3):
            cb.step()
        assert not np.array_equal(np.asarray(cb.cache["h"][:, a.slot]), h)  # not frozen
    else:
        while not a.done:
            cb.step()
    c = cb.submit(second, max_new_tokens=8)
    while not c.done:
        cb.step()
    assert c.slot == a.slot and list(c.out_tokens) == want
    cb.pump()
    assert b.done and cb.stats["ssm_state_bytes"] > 0


def test_prefix_cache_hit_and_miss_are_bit_identical_with_a_recurrent_state(llm_spans):
    """The prefix's rows are a snapshot of every kind of state (keys, values,
    the convolution's window, h after the prefix's last token); the suffix is
    teacher-forced through the decode body on hit and miss alike.  So a hit
    answers bit for bit as the miss did, and both as a batcher without the
    cache answers up to the order of summation (it prefills the whole prompt)."""
    from cluster_anywhere_tpu.util import tracing

    cfg, params = _hybrid()
    rng = np.random.default_rng(4)
    shared = rng.integers(1, cfg.vocab_size, 16)
    prompts = [np.concatenate([shared, rng.integers(1, cfg.vocab_size, n)]) for n in (3, 5)]
    cb = _hybrid_batcher(params, cfg, prefix_cache_entries=2, prefix_block=16)
    miss = cb.submit(prompts[0], max_new_tokens=6)
    cb.pump()
    assert cb.stats["prefix_misses"] == 1 and cb.stats["prefix_hits"] == 0
    entry = next(iter(cb.prefix_cache._d.values()))
    assert set(entry["rows"]) == {"k", "v", "conv", "h"} and entry["rows"]["h"].shape == (4, 1, 64, 8)
    assert cb.prefix_cache.memory_bytes() == sum(
        a.size * a.dtype.itemsize for a in entry["rows"].values())
    llm_spans()
    token = tracing.push_execution(TRACE)
    try:
        hit = cb.submit(prompts[0], max_new_tokens=6)
        other = cb.submit(prompts[1], max_new_tokens=6)
        cb.pump()
    finally:
        tracing.pop_execution(token)
    assert cb.stats["prefix_hits"] == 2 and list(hit.out_tokens) == list(miss.out_tokens)
    events = llm_spans()
    admits = [e for e in events if e["name"] == "llm.admit"]
    assert [e["prefix_hit"] for e in admits] == [1, 1]
    # an admit installs one slot's recurrent state; a step reads and writes both slots'
    slot_bytes = 4 * (3 * 64 * 4 + 64 * 8 * 4)
    assert {e["ssm_state_bytes"] for e in admits} == {slot_bytes}
    # (the call that read it says so: the first call of the two admits dispatched one and read none)
    steps = [e for e in events if e["name"] == "llm.step"]
    assert [e["live"] for e in steps] == [0] + [2] * 5 and "ssm_state_bytes" not in steps[0]
    assert {e["ssm_state_bytes"] for e in steps[1:]} == {2 * 2 * slot_bytes}
    for prompt, req in ((prompts[0], hit), (prompts[1], other)):
        assert list(req.out_tokens) == _alone(params, cfg, prompt, 6)


# -- the causal step reads one step behind ------------------------------------------


def _watch_dispatches(monkeypatch):
    """Records what every `_decode_step_rowpos` call was handed: [(ints, a copy
    of it as it was, floats, a copy)]."""
    from cluster_anywhere_tpu.llm import continuous

    handed, real = [], continuous._decode_step_rowpos

    def spy(params, cache, ints, floats, prev, rng, *, cfg):
        handed.append((ints, ints.copy(), floats, floats.copy()))
        return real(params, cache, ints, floats, prev, rng, cfg=cfg)

    monkeypatch.setattr(continuous, "_decode_step_rowpos", spy)
    return handed


@pytest.mark.parametrize("model", [_TINY, _TINY_MIXTURE, _TINY_HYBRID], ids=["dense", "mixture", "hybrid"])
def test_a_batcher_that_reads_one_step_behind_answers_as_generate_does(model, monkeypatch):
    """Requests of unlike lengths through three slots, each step dispatched
    before the one before is read: one ends by eos in mid-stream (the step in
    flight holds its row once more: computed late, dropped), a waiting request
    takes its slot at the very next call, while that late step still runs; one
    is cancelled while a step holds its row, and its slot is taken likewise; one
    fills its cache rows to the last (`bucket + max_new_tokens == t_max`).
    Every greedy token is `generate()`'s, one by one; nothing is handed out past
    an eos, a length or a cancel; `tokens_out` is what was handed out; and no
    step, late ones included, was given a position outside the cache."""
    import jax
    import jax.numpy as jnp

    from cluster_anywhere_tpu.llm import ContinuousBatcher
    from cluster_anywhere_tpu.models.generate import generate

    cfg, params = _float32_model(model)
    t_max = 32
    rng = np.random.default_rng(7)
    answer = lambda prompt, n: np.asarray(generate(
        params, jnp.asarray([prompt], jnp.int32), jax.random.key(0), cfg=cfg, max_new_tokens=n))[0].tolist()
    prompt = lambda n: rng.integers(1, cfg.vocab_size, n).tolist()
    # a prompt whose greedy answer brings a token it has not held before as its 3rd to 6th:
    # that token as the request's eos ends it there, in mid-stream
    for _ in range(20):
        stopped = prompt(4)
        full = answer(stopped, 10)
        at = next((j for j in range(2, 6) if full[j] not in full[:j]), None)
        if at is not None:
            break
    assert at is not None
    handed = _watch_dispatches(monkeypatch)
    cb = ContinuousBatcher(params, cfg, slots=3, t_max=t_max, prefill_buckets=(8,))
    sent = {}  # name -> (request, the tokens it is to be handed)

    def submit(name, ids, n, keep=None, **kw):
        sent[name] = (cb.submit(ids, max_new_tokens=n, **kw), answer(ids, n)[:keep])
        return sent[name][0]

    edge = submit("edge", prompt(5), 24)  # admitted in bucket 8: 8 + 24 is the cache's length
    assert cb._bucket(5, 24) + 24 == t_max
    stops = submit("stops", stopped, 10, keep=at + 1, eos_id=full[at])
    submit("short", prompt(6), 4)
    submit("waits", prompt(3), 7)  # these two take the first two slots that free
    gone = submit("gone", prompt(7), 12, keep=3)
    streams, calls, ahead, took_over = {}, 0, 0, {}
    while cb.has_work:
        landing, slots_before = cb._flight, list(cb._by_slot)
        out = cb.step()
        calls += 1
        ahead += landing is not None and cb._flight is not None
        for rid, toks in out.items():
            streams.setdefault(rid, []).extend(toks)
        for name, late in (("stops", stops), ("gone", gone)):
            # the call after its end: its slot was free as the call began, the step then in
            # flight still held its row, and the call's admit put the next request into the slot
            if (late.done and slots_before[late.slot] is None and landing is not None
                    and any(r is late for _, r in landing.rows)):
                took_over.setdefault(name, cb._by_slot[late.slot])
        if len(gone.out_tokens) == 3 and not gone.done:
            assert any(r is gone for _, r in cb._flight.rows)  # a step holds its row: computed for nothing
            assert cb.cancel(gone.request_id)
            submit("last", prompt(2), 5)
    assert calls < 60 and all(r.done for r, _ in sent.values())
    for name, (req, want) in sent.items():
        assert req.out_tokens == want and streams[req.request_id] == want, name
    assert stops.out_tokens[-1] == full[at] and len(stops.out_tokens) < 10
    assert cb.stats["tokens_out"] == sum(len(t) for t in streams.values())
    # each of the two was in one step more than it was handed tokens of, and its slot was
    # given away while that step ran
    assert cb.stats["late_rows"] == 2 and cb.stats["cancelled"] == 1
    assert took_over["stops"] is not None and took_over["gone"] is sent["last"][0]
    # every call but the first read a step, and every call but the last dispatched one before it read
    assert cb.stats["decode_steps"] == len(handed) == calls - 1 and cb.stats["steps_ahead"] == ahead == calls - 2
    # every position any step was given lies in the cache; the request that fills its rows
    # was last dispatched at the last but one, and its idle row rests on the last
    assert all(0 <= was[1].min() and was[1].max() < t_max for _, was, _, _ in handed)
    assert max(was[1][edge.slot] for _, was, _, _ in handed) == t_max - 2 and cb._pos[edge.slot] == t_max - 1


def test_a_step_is_dispatched_before_the_step_before_it_is_read(llm_spans, monkeypatch):
    """The order is held: in a call that has a step in flight and dispatches
    another (`ahead=1`), `llm.step.dispatch` closes before `llm.step.readback`
    opens; the arrays a step was handed are its own, unchanged when the
    scheduler has written its vectors again; and `steps_ahead`, `late_rows` and
    the two series they are shipped as count what the calls below come to."""
    from cluster_anywhere_tpu.llm import serve_llm
    from cluster_anywhere_tpu.util import metrics, tracing

    handed = _watch_dispatches(monkeypatch)
    cb = _tiny_batcher()
    token = tracing.push_execution(TRACE)
    try:
        a, b = cb.submit([1, 2, 3], max_new_tokens=5), cb.submit([4, 5], max_new_tokens=3)
        outs = [cb.step() for _ in range(3)]
        # call 0 admitted both and dispatched step 0; call 1 dispatched step 1 and read step 0;
        # call 2 dispatched step 2 for `a` alone (`b` reaches its length with step 1) and read step 1
        assert [sorted(map(len, o.values())) for o in outs] == [[1, 1], [1, 1], [1, 1]]
        assert b.done and not a.done and cb._flight.rows == [(a.slot, a)]
        assert cb.cancel(a.request_id)  # while step 2 holds its row
        assert cb.has_work and cb.step() == {} and not cb.has_work  # call 3 read step 2 and dropped the row
        assert cb.step() == {}  # nothing in flight, nothing live: no step
    finally:
        tracing.pop_execution(token)
    assert (len(a.out_tokens), len(b.out_tokens)) == (3, 3)
    counted = dict(decode_steps=3, steps_ahead=2, late_rows=1, tokens_out=6, finished=1, cancelled=1)
    assert {k: cb.stats[k] for k in counted} == counted
    events = llm_spans()
    steps = [e for e in events if e["name"] == "llm.step"]
    assert [(e["live"], e["ahead"]) for e in steps] == [(0, 0), (2, 1), (2, 1), (1, 0), (0, 0)]
    part = lambda step, name: [e for e in events if e["name"] == name and e["trace"].get("psid") == step["trace"]["sid"]]
    for step, dispatched, read in zip(steps, (1, 1, 1, 0, 0), (0, 1, 1, 1, 0)):
        dispatch, readback = part(step, "llm.step.dispatch"), part(step, "llm.step.readback")
        assert (len(dispatch), len(readback)) == (dispatched, read)
        if step["ahead"]:
            closes = dispatch[0]["mono"] + (dispatch[0]["end"] - dispatch[0]["start"])
            assert closes <= readback[0]["mono"]
    # three dispatches, each handed arrays of its own: the scheduler moved its positions on and
    # took the fresh marks back right after each, and what the step was handed still reads as it did
    assert len(handed) == 3
    for ints, was, floats, floats_was in handed:
        assert not np.shares_memory(ints, cb._ints) and not np.shares_memory(floats, cb._floats)
        assert np.array_equal(ints, was) and np.array_equal(floats, floats_was)
    fresh, pos = [h[1][4].tolist() for h in handed], [h[1][1].tolist() for h in handed]
    assert fresh == [[1, 1], [0, 0], [0, 0]] and cb._fresh.tolist() == [0, 0]
    assert pos[1] == [p + 1 for p in pos[0]] and pos[2][a.slot] == pos[0][a.slot] + 2
    # shipped beside the batcher's other counters, as deltas of `cb.stats`
    shipped = []
    monkeypatch.setattr(metrics.Counter, "inc", lambda self, value=1.0, tags=None: shipped.append((self.name, value)))
    server = object.__new__(serve_llm.ContinuousLLMServer)  # the method's own needs, no pump's thread
    server.cb, server._metrics_synced = cb, {}
    server.engine_device = {"count": 1, "platform": "cpu", "device_kind": "cpu"}
    server._sync_engine_metrics()
    shipped = dict(shipped)
    assert shipped["ca_serve_steps_ahead_total"] == 2 and shipped["ca_serve_late_rows_total"] == 1
    assert shipped["ca_serve_decode_steps_total"] == 3
