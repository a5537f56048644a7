"""LLM library tests (batch processor over Data, generation correctness,
serve deployment)."""

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
