"""Kimi Delta Attention in the program (models/transformer.py `_kda_mixer`, `_kda_chunked`, `_kda_step`; its
state in the slots: models/generate.py, llm/continuous.py) beside latent attention with a direct query
projection and no rotation, at a test's widths on the CPU in float32: the chunked form against the recurrence,
prefill and decode through the slots against the plain reference's full forward pass, what padding and a
reused slot leave behind, and each refusal by name."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import manifest
from cluster_anywhere_tpu.llm.continuous import ContinuousBatcher
from cluster_anywhere_tpu.models import generate, transformer
from cluster_anywhere_tpu.models.transformer import TransformerConfig, init_params

reference = manifest.load_reference("kimi_linear")

# the published pattern's shape at a test's widths: a dense first layer, runs of one, two and three kda layers
# between latent layers, the last layer latent
MIXERS = ("kda", "kda", "attn", "kda", "kda", "kda", "attn", "kda", "attn")
TINY = dict(
    vocab_size=128, d_model=64, n_layers=len(MIXERS), n_heads=4, n_kv_heads=4, d_head=24, d_ff=128,
    dtype=jnp.float32, param_dtype=jnp.float32, layer_mixers=MIXERS, rotary=False,
    kv_lora_rank=32, q_lora_rank=0, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    n_dense_layers=1, d_expert=32, n_shared_experts=1, n_experts=16, n_experts_per_tok=4, moe_gated=True,
    moe_renormalize=True, moe_scoring="sigmoid", moe_routed_scale=2.446, experts_held=(4, 4),
    kda_n_heads=4, kda_head_dim=16, norm_eps=1e-5)


@pytest.fixture(scope="module")
def model():
    cfg = TransformerConfig(**TINY)
    params = jax.jit(init_params, static_argnums=1)(jax.random.key(1), cfg)
    # the norms' weights off 1, so one that is left out or misplaced shows
    for stack in ("kda_blocks", "kda_dense_blocks", "blocks"):
        for name in ("ln1", "ln2", "kda_norm", "kv_a_norm"):
            if name in params[stack]:
                w = params[stack][name]
                params[stack][name] = w * jnp.linspace(0.6, 1.4, w.shape[-1])
    return cfg, params


@pytest.fixture(autouse=True)
def exact_products():
    with jax.default_matmul_precision("highest"):
        yield


def _rule_inputs(t, seed=0, b=2, h=3, d=16, strength=2.0):
    ks = jax.random.split(jax.random.key(seed), 6)
    q, k, v = (jax.random.normal(ks[i], (b, t, h, d)) for i in range(3))
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)
    g = -jnp.exp(jax.random.normal(ks[3], (b, t, h, d)) * strength)  # from hardly any decay to exp(-50) a step
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, t, h)))
    return unit(q) * d ** -0.5, unit(k), v, g, beta, jax.random.normal(ks[5], (b, h, d, d))


@jax.jit
def _stepped(q, k, v, g, beta, s):
    def one(s, now):
        o, s = transformer._kda_step(*now, s)
        return s, o

    s, o = jax.lax.scan(one, s, tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), s


def test_a_step_is_the_delta_rule_as_written():
    q, k, v, g, beta, s0 = _rule_inputs(1)
    eye = jnp.eye(q.shape[-1])
    forget = eye - beta[:, 0, :, None, None] * k[:, 0, :, :, None] * k[:, 0, :, None, :]
    want = (jnp.einsum("bhij,bhjv->bhiv", forget, jnp.exp(g[:, 0])[..., None] * s0)
            + beta[:, 0, :, None, None] * k[:, 0, :, :, None] * v[:, 0, :, None, :])
    o, s = transformer._kda_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], s0)
    np.testing.assert_allclose(s, want, atol=2e-6)
    np.testing.assert_allclose(o, jnp.einsum("bhk,bhkv->bhv", q[:, 0], want), atol=2e-6)


@pytest.mark.parametrize("t", [17, 77])
def test_the_chunked_form_is_the_recurrence_at_any_length(t):
    """Lengths under a sub-block, under a chunk, a chunk, and no multiple of either; decays from none to exp(-50)
    a step, whose inverse no float32 holds."""
    q, k, v, g, beta, s0 = _rule_inputs(t, seed=t)
    want_o, want_s = _stepped(q, k, v, g, beta, s0)
    o, s = transformer._kda_chunked(q, k, v, g, beta, s0, 32)
    assert bool(jnp.all(jnp.isfinite(o))) and bool(jnp.all(jnp.isfinite(s)))
    np.testing.assert_allclose(o, want_o, atol=5e-5)
    np.testing.assert_allclose(s, want_s, atol=5e-5)


@pytest.mark.parametrize("t", [3, 70])
def test_the_mixer_over_a_prompt_is_the_mixer_a_token_at_a_time(model, t):
    """A prompt shorter than the convolution (3 < 4), and one past a chunk and no multiple of it: one call from the zero
    state against one token a call from the state the call before left, convolution tail and all."""
    cfg, params = model
    bp = jax.tree_util.tree_map(lambda w: w[1], params["kda_blocks"])
    x = jax.random.normal(jax.random.key(t), (2, t, cfg.d_model))
    out, (tail, s) = transformer._kda_mixer(bp, x, cfg, transformer._kda_zero_state(cfg, 2))
    state, outs = transformer._kda_zero_state(cfg, 2), []
    one = jax.jit(lambda x_i, state: transformer._kda_mixer(bp, x_i, cfg, state))
    for i in range(t):
        f, state = one(x[:, i:i + 1], state)
        outs.append(f)
    np.testing.assert_allclose(out, jnp.concatenate(outs, axis=1), atol=2e-5)
    np.testing.assert_allclose(s, state[1], atol=2e-5)
    np.testing.assert_allclose(tail, state[0], atol=0)
    assert tail.shape == (2, 3, 3 * 4 * 16) and s.shape == (2, 4, 16, 16) and s.dtype == jnp.float32


def _through_the_slots(cfg, params, prompts, new, slots=4, t_max=96, bucket=48):
    """Each prompt prefilled left-padded to `bucket`, installed in a slot of a cache of `slots`, then decoded with
    the others a token a step, each row at its own depth, teacher-forced with `new` [n, steps].  Returns the logits
    [n, 1 + steps, V]: the prefill's, then each step's."""
    cache = generate.init_cache(cfg, slots, t_max)
    logits, pads = [], []
    for slot, p in enumerate(prompts):
        pad = bucket - len(p)
        first, rows = generate.prefill(params, jnp.asarray(np.pad(p, (pad, 0)))[None], cfg, t_max, pad=jnp.asarray([pad]))
        cache = generate.install_rows(cache, rows, slot)
        logits.append([first[0]])
        pads.append(pad)
    n = len(prompts)
    pads = np.asarray(pads + [0] * (slots - n), np.int32)
    live = jnp.arange(slots) < n
    pos = np.full(slots, bucket, np.int32)
    decode = jax.jit(generate.decode_rows, static_argnames=("cfg",))
    for step in range(new.shape[1]):
        tokens = np.zeros(slots, np.int32)
        tokens[:n] = new[:, step]
        out, cache, _ = decode(params, cache, jnp.asarray(tokens), jnp.asarray(pos), jnp.asarray(pads), cfg=cfg, live=live)
        for i in range(n):
            logits[i].append(out[i])
        pos = pos + 1
    return jnp.stack([jnp.stack(row) for row in logits]), cache


def test_prefill_then_decode_through_the_slots_is_the_references_full_forward(model):
    cfg, params = model
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab_size, n) for n in (5, 21, 40)]
    new = rng.integers(0, cfg.vocab_size, (3, 7))
    got, _ = _through_the_slots(cfg, params, prompts, new)
    for i, p in enumerate(prompts):
        want = np.asarray(reference.forward(params, np.concatenate([p, new[i]]), cfg))[len(p) - 1:]
        np.testing.assert_allclose(got[i], want, atol=2e-4)
    # and the program's own forward pass over whole sequences is the same model
    ids = np.concatenate([prompts[2], new[2]])
    np.testing.assert_allclose(transformer.forward(params, jnp.asarray(ids)[None], cfg)[0],
                               reference.forward(params, ids, cfg), atol=2e-4)


@pytest.mark.parametrize("bucket", [24])
def test_bucket_padding_leaves_no_trace_in_state_tail_or_cache(model, bucket):
    cfg, params = model
    prompt = np.random.default_rng(bucket).integers(0, cfg.vocab_size, 21)
    want_logits, want = generate.prefill(params, jnp.asarray(prompt)[None], cfg, 96)
    pad = bucket - len(prompt)
    logits, rows = generate.prefill(params, jnp.asarray(np.pad(prompt, (pad, 0)))[None], cfg, 96, pad=jnp.asarray([pad]))
    np.testing.assert_allclose(logits, want_logits, atol=2e-5)
    np.testing.assert_allclose(rows["h"], want["h"], atol=2e-5)
    np.testing.assert_allclose(rows["conv"], want["conv"], atol=2e-5)
    for name in ("ckv", "kr"):  # the prompt's latent rows lie behind the pads
        np.testing.assert_allclose(rows[name][:, :, pad:pad + 21], want[name][:, :, :21], atol=2e-5)
    assert rows["h"].shape == (6, 1, 4, 16, 16) and rows["conv"].shape == (6, 1, 3, 192)
    assert rows["ckv"].shape == (3, 1, 96, 32) and rows["kr"].shape == (3, 1, 96, 128)
    assert not np.asarray(rows["kr"][..., 8:]).any()  # the shared key's 8 dimensions, carried as they are, then zeros


def test_a_reused_slot_carries_nothing_of_the_request_before(model):
    """One slot: the second request is admitted into the slot the first one left, whose state nothing reset; it
    is served the tokens it is served alone."""
    cfg, params = model
    rng = np.random.default_rng(11)
    first, second = rng.integers(0, cfg.vocab_size, 30), rng.integers(0, cfg.vocab_size, 9)

    def serve(prompts):
        cb = ContinuousBatcher(params, cfg, slots=1, t_max=64, prefill_buckets=(16, 32))
        reqs = [cb.submit(p, max_new_tokens=8) for p in prompts]
        cb.pump()
        return cb, [list(r.out_tokens) for r in reqs]

    cb, both = serve([first, second])
    assert cb.stats["admitted"] == 2 and cb.stats["state_bytes_per_slot"] == 6 * (4 * 16 * 16 * 4 + 3 * 192 * 4)
    assert cb.stats["ssm_state_bytes"] > 0
    assert both[1] == serve([second])[1][0]


def test_the_shares_parts_with_the_shared_expert_once_add_up_to_the_uncut_layer(model):
    """Model-configs guide, section 4: the parts of a kda block's mixture that the four shares of four experts
    give, with what every chip computes alike (the shared expert) counted once, are the layer that holds all 16."""
    cfg, params = model
    bp = jax.tree_util.tree_map(lambda w: w[2], params["kda_blocks"])
    y = jax.random.normal(jax.random.key(5), (2, 9, cfg.d_model))
    whole_cfg = dataclasses.replace(cfg, experts_held=None)
    draw = jax.random.split(jax.random.key(6), 3)
    experts = {"w_gate": jax.random.normal(draw[0], (16, 64, 32)) / 8, "w_up": jax.random.normal(draw[1], (16, 64, 32)) / 8,
               "w_down": jax.random.normal(draw[2], (16, 32, 64)) / 6}
    whole = transformer._ffn({**bp, **experts}, y, whole_cfg)[0]
    dt = y.dtype
    shared = (jax.nn.silu(y @ bp["shared_gate"].astype(dt)) * (y @ bp["shared_up"].astype(dt))) @ bp["shared_down"].astype(dt)
    parts = 0.0
    for first in range(0, 16, 4):
        held = {name: w[first:first + 4] for name, w in experts.items()}
        out, _, touched = transformer._ffn({**bp, **held}, y, dataclasses.replace(cfg, experts_held=(first, 4)))
        assert touched.shape == (3,)
        parts = parts + out - shared
    np.testing.assert_allclose(parts + shared, whole, atol=2e-5)
    # and the reference's held part is the program's
    lp = {**bp, **{name: w[4:8] for name, w in experts.items()}}
    mine = transformer._ffn(lp, y[:1], cfg)[0][0] - shared[0]
    np.testing.assert_allclose(mine, reference._mla._routed(y[0], lp, 4, True, 2.446, 4)[0], atol=2e-5)


def test_the_latent_layers_turn_nothing_and_project_the_query_straight(model):
    cfg, params = model
    assert "wq" in params["blocks"] and "wq_a" not in params["blocks"] and params["blocks"]["wq"].shape == (3, 64, 4 * 24)
    assert not cfg.rotates("attn") and cfg.attn_scale == pytest.approx(24 ** -0.5)
    bp = jax.tree_util.tree_map(lambda w: w[0], params["blocks"])
    y = jax.random.normal(jax.random.key(2), (1, 6, cfg.d_model))
    q, k_r, c_kv = transformer._project_latent(bp, y, cfg, jnp.arange(6), rotate=False)
    np.testing.assert_allclose(k_r, (y @ bp["wkv_a"])[..., 32:], atol=1e-6)
    np.testing.assert_allclose(q.reshape(1, 6, -1), y @ bp["wq"], atol=1e-6)
    turned = transformer._project_latent(bp, y, cfg, jnp.arange(6), rotate=True)
    assert float(jnp.max(jnp.abs(turned[1][:, 1:] - k_r[:, 1:]))) > 0.1  # a rotation would have shown
    np.testing.assert_allclose(turned[2], c_kv, atol=0)


def test_the_decode_kernel_moves_on_the_rows_that_hold_a_request_and_no_other():
    """ops/kda.py's kernel, interpreted: the live rows' state of the one layer is `_kda_step`'s, their read-out too;
    a row that holds no request keeps its state and returns zeros; the other layers of the stack are as they were."""
    from cluster_anywhere_tpu.ops import kda

    b, h, d = 5, 16, 128
    q, k, v, g, beta, _ = _rule_inputs(1, seed=9, b=b, h=h, d=d, strength=1.0)
    q, k, v, g, beta = q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0]
    stack = jax.random.normal(jax.random.key(4), (3, b, h, d, d))
    live = jnp.asarray([True, False, True, True, False])
    rows = kda.live_rows(live, b)
    assert rows.tolist() == [0, 2, 3, 1, 4, 3] and kda.live_rows(None, 3).tolist() == [0, 1, 2, 3]
    o, after = kda.kda_decode_update(q, k, v, g, beta, stack, jnp.int32(1), rows, interpret=True)
    want_o, want_s = transformer._kda_step(q, k, v, g, beta, stack[1])
    np.testing.assert_allclose(o[live], want_o[live], atol=1e-6)
    np.testing.assert_allclose(after[1][live], want_s[live], atol=2e-6)
    assert not np.asarray(o[~live]).any()
    np.testing.assert_array_equal(after[1][~live], stack[1][~live])
    np.testing.assert_array_equal(after[0], stack[0])
    np.testing.assert_array_equal(after[2], stack[2])
    # a state kept in another type is read and written in it
    low, _ = kda.kda_decode_update(q, k, v, g, beta, stack.astype(jnp.bfloat16), jnp.int32(1), rows, interpret=True)
    np.testing.assert_allclose(low[live], want_o[live], atol=0.05)
    with pytest.raises(NotImplementedError, match="heads of 128 in blocks of 8"):
        kda.kda_decode_update(q[..., :64], k[..., :64], v[..., :64], g[..., :64], beta, stack[..., :64, :64], jnp.int32(0), rows)


def test_a_decode_step_through_the_kernel_is_the_step_without_it(monkeypatch):
    """`decode_rows` as the chip runs it (the state's stack handed to the kernel, interpreted here) against the
    same step in `jax.numpy`: the live rows' logits and state alike, a dead row's state untouched."""
    import functools

    from cluster_anywhere_tpu.ops import kda

    cfg = TransformerConfig(**{**TINY, "n_layers": 3, "layer_mixers": ("kda", "kda", "attn"), "kda_n_heads": 8,
                               "kda_head_dim": 128})
    params = init_params(jax.random.key(2), cfg)
    cache = generate.init_cache(cfg, 4, 32)
    cache = {**cache, "h": jax.random.normal(jax.random.key(3), cache["h"].shape) * 0.1}
    tokens, pos, pads = jnp.asarray([5, 6, 7, 8]), jnp.asarray([3, 9, 4, 0]), jnp.zeros(4, jnp.int32)
    live = jnp.asarray([True, False, True, False])
    assert not generate.kda_on_kernel(cache, cfg)
    want, want_cache, _ = generate.decode_rows(params, cache, tokens, pos, pads, cfg, live)
    monkeypatch.setattr(generate, "decode_on_kernel", lambda: True)
    monkeypatch.setattr(generate, "kda_decode_update", functools.partial(kda.kda_decode_update, interpret=True))
    assert generate.kda_on_kernel(cache, cfg) and not generate.kda_on_kernel({"h": cache["h"][:, :1]}, cfg)
    got, got_cache, _ = generate.decode_rows(params, cache, tokens, pos, pads, cfg, live)
    np.testing.assert_allclose(got[live], want[live], atol=2e-5)
    np.testing.assert_allclose(got_cache["h"][:, live], want_cache["h"][:, live], atol=2e-6)
    np.testing.assert_array_equal(got_cache["h"][:, ~live], cache["h"][:, ~live])
    np.testing.assert_allclose(got_cache["conv"][:, live], want_cache["conv"][:, live], atol=1e-6)


REFUSED = {
    "blocks": (dict(block_length=4, mask_token_id=1, denoise_steps=2), NotImplementedError, "pass over blocks"),
    "latent_blocks": (dict(layer_mixers=None, kda_n_heads=0, n_dense_layers=0, block_length=4, denoise_steps=2),
                      NotImplementedError, "a pass over blocks of positions through a latent cache"),
    "window_layer": (dict(layer_mixers=MIXERS[:-1] + ("attn_win",), attn_window=8), NotImplementedError, "kda layers stand beside"),
    "mamba_beside": (dict(layer_mixers=MIXERS[:-1] + ("ssm",)), NotImplementedError, "other\n".strip() + " "),
    "no_heads": (dict(kda_n_heads=0), ValueError, "a kda layer takes kda_n_heads=0"),
    "pipeline": (dict(pp=3), NotImplementedError, "pipeline stages"),
    "latent_widths": (dict(v_head_dim=0), ValueError, "q_lora_rank 0: the query is projected straight"),
}


@pytest.mark.parametrize("what", list(REFUSED))
def test_a_configuration_that_cannot_run_is_refused_by_name(what):
    over, error, says = REFUSED[what]
    with pytest.raises(error, match=says.strip()):
        TransformerConfig(**{**TINY, **over})


def test_a_mesh_a_train_step_and_a_prefix_cache_are_refused_by_name(model):
    cfg, params = model
    with pytest.raises(NotImplementedError, match=r"layers of kind \['kda', 'kda_dense', 'latent attention'\]"):
        transformer.param_specs(cfg)
    with pytest.raises(NotImplementedError, match="a training step through kda layers"):
        transformer.make_train_step(cfg, None)
    with pytest.raises(ValueError, match="a replica of kda layers keeps no prefix cache"):
        ContinuousBatcher(params, cfg, slots=2, t_max=64, prefill_buckets=(16,), prefix_cache_entries=4)
    # the same replica without one is built, and its cache holds the three kinds of rows
    cb = ContinuousBatcher(params, cfg, slots=2, t_max=64, prefill_buckets=(16,))
    assert sorted(cb.cache) == ["ckv", "conv", "h", "kr"] and cb.cache["h"].shape == (6, 2, 4, 16, 16)


def test_the_layer_loop_scans_the_published_pattern_run_by_run():
    kinds = TransformerConfig(**TINY).layer_kinds
    assert kinds == ("kda_dense", "kda", "attn", "kda", "kda", "kda", "attn", "kda", "attn")
    assert transformer._layer_runs(kinds) == [("kda_dense", 0, 1), ("kda", 0, 1), ("attn", 0, 1), ("kda", 1, 3),
                                              ("attn", 1, 1), ("kda", 4, 1), ("attn", 2, 1)]
    assert generate._state_index(TransformerConfig(**TINY)) == {
        "kda_dense": [0], "kda": [1, 2, 3, 4, 5], "attn": [0, 1, 2]}
