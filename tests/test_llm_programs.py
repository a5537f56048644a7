"""The shape of the engine's programs: one block, the step's inputs in one
dispatch, one compiled prefill a bucket, no widened cache, the sampler."""

import contextlib

import numpy as np
import pytest

from _llm_tiny import (  # noqa: F401 (llm_spans is a fixture)
    TRACE,
    _TINY,
    _TINY_HYBRID,
    _TINY_MIXTURE,
    _decode_step_program,
    _float32_model,
    llm_spans,
)


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
                        "cache_context_bytes_per_token",
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
    host's vectors as the step was dispatched (a pass of blocks: as it is read,
    where the host's mirror is its input, and [pads, pos) once more for a row
    that stores the block before); `cache_rows`, the slots x t_max
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
                # a causal step is reckoned as it is dispatched; a pass of blocks as it is read, on
                # the host's mirror, which is the pass's input only then (a row that the cancel
                # made late among them: the device ran it)
                held = [s for s, _ in _rows_of_the_next_step(cb)] if not blocks else \
                    [s for s, _ in cb._flight.rows] if cb._flight is not None else []
                if held:
                    first, last = cb._pads[held], cb._pos[held] + tokens
                    if blocks:  # a row that stores the block before fetches [pads, pos) for it once more
                        storing = [s for s in held if cb._blk_pending[s]]
                        first, last = np.append(first, cb._pads[storing]), np.append(last, cb._pos[storing])
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
