"""A layer pattern: state-space layers beside attention layers, in one stack."""

import numpy as np
import pytest

from _llm_tiny import (  # noqa: F401 (llm_spans is a fixture)
    TRACE,
    _TINY,
    _TINY_HYBRID,
    _hybrid,
    llm_spans,
)


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
        # one program a variant, traced while the variant's patch is in place: the
        # 40 steps are one scan of `decode_one`, not 40 eager calls of it
        @jax.jit
        def run(params, ids):
            def step(carry, at):
                token, i = at
                return generate.decode_one(params, carry[1], token, i, cfg), None

            first = generate.prefill(params, ids[:, :8], cfg, 64)
            (logits, rows), _ = jax.lax.scan(step, first, (ids[:, 8:].T, jnp.arange(8, 48, dtype=jnp.int32)))
            return logits, rows

        logits, rows = run(params, jnp.asarray(ids))
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
