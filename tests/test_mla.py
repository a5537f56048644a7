"""Multi-head latent attention, a held share of a mixture's experts, shared
experts and leading dense layers (models/transformer.py, models/generate.py,
parallel/moe.py, llm/continuous.py) at a test's widths on the CPU, float32
weights from a seed.  The plain reference is the benchmark's own
(benchmarks/references/mla_moe.py), loaded as the harness loads it."""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import manifest
from cluster_anywhere_tpu.llm.continuous import ContinuousBatcher, prefill_buckets_for
from cluster_anywhere_tpu.models import generate, transformer
from cluster_anywhere_tpu.models.transformer import TransformerConfig, init_params
from cluster_anywhere_tpu.ops.attention import reference_attention
from cluster_anywhere_tpu.parallel.moe import EXPERT_MATRICES, routed_ffn, takes_loop

reference = manifest.load_reference("mla_moe")

LATENT = dict(kv_lora_rank=32, q_lora_rank=48, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=12)
YARN = dict(rope_factor=32.0, rope_original_max_len=64, rope_mscale=1.0, rope_mscale_all_dim=1.0)
SHARE = dict(n_dense_layers=1, d_expert=24, n_experts=32, n_experts_per_tok=4, moe_gated=True,
             moe_renormalize=True, moe_scoring="sigmoid", moe_routed_scale=2.5, n_shared_experts=1,
             experts_held=(6, 2))
TINY = dict(vocab_size=97, d_model=64, n_layers=4, n_heads=4, d_ff=160, **LATENT, **YARN, **SHARE)


def _model(seed=1, **over):
    cfg = TransformerConfig(**{**TINY, **over}, dtype=jnp.float32, param_dtype=jnp.float32)
    return cfg, init_params(jax.random.key(seed), cfg)


@pytest.fixture(scope="module")
def model():
    return _model()


def test_prefill_then_forty_decoded_tokens_through_the_latent_cache_match_the_reference(model):
    """Logits, not tokens: a prompt prefills (the expanded path, left-padded in
    one row), 40 tokens go one at a time through the latent cache (the absorbed
    path), and every step's logits are the plain reference's full forward."""
    cfg, params = model
    ids = np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 60))
    want = np.stack([np.asarray(reference.forward(params, row, cfg)) for row in ids])
    pad = jnp.asarray([0, 5], jnp.int32)
    prompt = ids[:, :20].copy()
    prompt[1] = np.concatenate([np.zeros(5, np.int64), ids[1, :15]])  # row 1: 15 tokens behind 5 pads
    logits, cache = generate.prefill(params, jnp.asarray(prompt), cfg, 64, pad)
    assert set(cache) == {"ckv", "kr"} and cache["ckv"].shape == (4, 2, 64, 32) and cache["kr"].shape == (4, 2, 64, 128)
    np.testing.assert_allclose(logits[0], want[0, 19], atol=2e-4)
    np.testing.assert_allclose(logits[1], want[1, 14], atol=2e-4)
    step = jax.jit(lambda c, tok, pos: generate.decode_rows(params, c, tok, pos, pad, cfg)[:2])
    for i in range(40):
        tok = jnp.asarray([ids[0, 20 + i], ids[1, 15 + i]])
        logits, cache = step(cache, tok, jnp.asarray([20 + i, 20 + i]))
        np.testing.assert_allclose(logits[0], want[0, 20 + i], atol=3e-4)
        np.testing.assert_allclose(logits[1], want[1, 15 + i], atol=3e-4)


def test_absorbed_decode_is_the_expanded_attention(model):
    """The decode core attends in the latent space (q W_UK^T against the latent
    rows, the weighted latents through W_UV); the prefill and training expand
    every head's keys and values.  The same numbers, by associativity."""
    cfg, params = model
    bp = jax.tree_util.tree_map(lambda w: w[1], params["blocks"])
    rng = np.random.default_rng(3)
    t, b = 23, 3
    y = jnp.asarray(rng.normal(size=(b, t, cfg.d_model)), jnp.float32)
    q, k_rope, c_kv = transformer._project_latent(bp, y, cfg, jnp.arange(t))
    k, v = transformer._latent_expand(bp, k_rope, c_kv, cfg)
    assert q.shape == (b, t, 4, 24) and k.shape == (b, t, 4, 24) and v.shape == (b, t, 4, 12)
    want = reference_attention(q, k, v, causal=True, scale=cfg.attn_scale)[:, -1:]
    # the cache holds the rows before the last; the core writes the last one itself
    # the rotated key's 8 values lie in the first of the 128 lanes its cache gives it
    cache = {"ckv": jnp.zeros((2, b, 32, 32)).at[1, :, :t - 1].set(c_kv[:, :-1]),
             "kr": jnp.zeros((2, b, 32, 128)).at[1, :, :t - 1, :8].set(k_rope[:, :-1])}
    pos = jnp.full((b,), t - 1)
    got, after = generate._latent_decode_core(bp, cache, 1, pos, jnp.zeros_like(pos), cfg,
                                              q[:, -1:], k_rope[:, -1:], c_kv[:, -1:])
    np.testing.assert_allclose(got, want, atol=2e-5)
    np.testing.assert_array_equal(after["ckv"][1, :, t - 1], c_kv[:, -1])
    np.testing.assert_array_equal(after["kr"][1, :, t - 1, :8], k_rope[:, -1])
    assert not np.any(np.asarray(after["kr"][..., 8:]))
    assert not np.any(np.asarray(after["ckv"][0])) and not np.any(np.asarray(after["ckv"][1, :, t:]))


def test_yarn_frequencies_and_the_softmax_scale_by_hand():
    """A.X-K1's own sizes: rope 64 of a head of 192, theta 10,000, factor 32 over
    4,096 positions, beta 32 and 1, mscale and mscale_all_dim 1."""
    cfg = TransformerConfig(
        n_heads=64, kv_lora_rank=512, q_lora_rank=1536, qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        rope_theta=10000.0, rope_factor=32.0, rope_original_max_len=4096, rope_beta_fast=32.0, rope_beta_slow=1.0,
        rope_mscale=1.0, rope_mscale_all_dim=1.0)
    # m = 0.1 ln 32 + 1 = 1.346574; a = 192^-0.5 m^2 = 0.0721688 x 1.813260 = 0.130861
    assert cfg.attn_scale == pytest.approx(0.130861, rel=1e-5)
    assert cfg.attn_scale == pytest.approx(192 ** -0.5 * (0.1 * math.log(32) + 1) ** 2, rel=1e-12)
    freqs, magnitude = transformer._rope_freqs(cfg)
    freqs = np.asarray(freqs, np.float64)
    assert magnitude == 1.0 and freqs.shape == (32,)
    # the correction dimensions: 64 ln(4096 / (32 x 2 pi)) / (2 ln 10000) = 10.47 -> 10,
    # 64 ln(4096 / (2 pi)) / (2 ln 10000) = 22.51 -> 23
    unscaled = 10000.0 ** (-np.arange(32) / 32)
    np.testing.assert_allclose(freqs[:11], unscaled[:11], rtol=1e-6)  # fast dimensions: as they were
    np.testing.assert_allclose(freqs[23:], unscaled[23:] / 32, rtol=1e-6)  # slow ones: interpolated
    # between them a linear ramp: dimension 16 is 6/13 of the way
    assert freqs[16] == pytest.approx(unscaled[16] * ((1 - 6 / 13) + (6 / 13) / 32), rel=1e-6)
    assert unscaled[16] == pytest.approx(0.01, rel=1e-9) and freqs[16] == pytest.approx(0.00552885, rel=1e-5)
    # the reference writes the same frequencies by itself
    ref_freqs, ref_magnitude, ref_scale = reference.yarn(cfg)
    np.testing.assert_allclose(ref_freqs, freqs, rtol=1e-6)
    assert (ref_magnitude, ref_scale) == (1.0, pytest.approx(cfg.attn_scale, rel=1e-12))
    # and a model without YaRN rotates as before
    plain = TransformerConfig(d_head=64)
    np.testing.assert_allclose(transformer._rope_freqs(plain)[0], 10000.0 ** (-np.arange(32) / 32), rtol=1e-6)
    assert plain.attn_scale == 64 ** -0.5


# the window-and-full mixture of tests/test_swa.py: the same expert layer under a norm of each half's OUTPUT
SWA = dict(vocab_size=97, d_model=64, n_layers=4, n_heads=4, n_kv_heads=2, d_head=16, d_ff=160,
           layer_mixers=("attn_win", "attn_win", "attn_win", "attn"), attn_window=8, attn_ring=16, rotary_full=False,
           norm_output=True, qk_norm=True, qk_norm_per_head=True, **SHARE)


@pytest.mark.parametrize("t", [17, 256], ids=["34-rows", "a-prefills-512-rows"])
@pytest.mark.parametrize("arch", ["mla_moe", "swa_moe"])
def test_the_sixteen_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer(arch, t):
    """What ties the share to the model (model-configs guide, section 4): the
    routed parts that the 16 shares give, each from the program's own expert
    layer told which 2 of 32 experts it holds, with the shared expert counted
    once, are the uncut reference layer's FFN; and the program's second half
    over the uncut layer is the reference's, the norm before the FFN
    (references/mla_moe.py) or after it (references/swa_moe.py).  At 34 rows as
    at a prefill's 512 every share loops over the experts it was given a row
    for (parallel/moe.py FEW_ROWS; past them it computes its part from the compact
    buffer: tests/test_moe_routed.py)."""
    ref = manifest.load_reference(arch)
    cfg = _model()[0] if arch == "mla_moe" else TransformerConfig(**SWA, dtype=jnp.float32, param_dtype=jnp.float32)
    whole = dataclasses.replace(cfg, experts_held=None)
    bp = jax.tree_util.tree_map(lambda w: w[0], init_params(jax.random.key(9), whole)["blocks"])
    bp["ln2"] = bp["ln2"] * jnp.linspace(0.7, 1.3, 64)
    assert bp["w_gate"].shape == (32, 64, 24) and bp["router"].shape == (64, 32)
    x = jnp.asarray(np.random.default_rng(2).normal(size=(2, t, 64)), jnp.float32)
    y = x if cfg.norm_output else ref._rms_norm(x, bp["ln2"])
    with jax.default_matmul_precision("highest"):
        routed, weight = reference._routed(y.reshape(-1, 64), bp, 4, True, 2.5, 0)
        shared = (jax.nn.silu(y @ bp["shared_gate"]) * (y @ bp["shared_up"])) @ bp["shared_down"]
        ffn = routed.reshape(x.shape) + shared
        want = x + (ref._rms_norm(ffn, bp["ln2"]) if cfg.norm_output else ffn)
    assert np.all(np.sum(np.asarray(weight) > 0, axis=-1) == 4)
    np.testing.assert_allclose(np.sum(np.asarray(weight), axis=-1), 2.5, rtol=1e-5)  # renormalised, scaled
    total, assignments, compact = None, 0, 0
    for share in range(16):
        held = dataclasses.replace(cfg, experts_held=(2 * share, 2))
        mine = {k: (v[2 * share:2 * share + 2] if k in EXPERT_MATRICES else v) for k, v in bp.items()}
        if share == 0:  # the whole FFN once: this share's part and the shared expert
            part, _, counts = transformer._ffn(mine, y, held)
        else:  # the other shares' routed parts alone
            part, _, counts = transformer._moe(mine, y, held)
        total = part if total is None else total + part
        assignments += int(counts[1])
        compact += int(counts[2])
    np.testing.assert_allclose(total, ffn, atol=2e-5)
    assert assignments == 2 * t * 4  # every (token, expert) pair fell on exactly one share
    assert takes_loop(2 * t, (0, 2)) and compact == 0
    np.testing.assert_allclose(transformer._ffn_half(bp, x, whole)[0], want, atol=2e-5)


@pytest.mark.parametrize("program", ["train", "prefill", "decode"])
def test_a_stack_of_dense_then_expert_layers_scans_by_runs(program, model):
    """`_scan_layers` scans runs that differ by FFN kind as it scans runs that
    differ by mixer: one leading dense layer (its own stack, `dense_blocks`),
    then three expert layers, in training, prefill and decode; the cache's
    latent rows are one stack over all four in the layers' order."""
    cfg, params = model
    assert cfg.layer_kinds == ("attn_dense", "attn", "attn", "attn")
    assert transformer._layer_runs(cfg.layer_kinds) == [("attn_dense", 0, 1), ("attn", 0, 3)]
    assert params["dense_blocks"]["w_gate"].shape == (1, 64, 160) and "router" not in params["dense_blocks"]
    assert params["blocks"]["w_gate"].shape == (3, 2, 64, 24) and params["blocks"]["router"].shape == (3, 64, 32)
    ids = np.random.default_rng(4).integers(0, cfg.vocab_size, (1, 12))
    want = np.asarray(reference.forward(params, ids[0], cfg))
    if program == "train":
        logits, aux = transformer.forward(params, jnp.asarray(ids), cfg, return_aux=True)
        np.testing.assert_allclose(logits[0], want, atol=2e-4)
        assert float(aux) > 0
        loss, grads = jax.value_and_grad(transformer.make_loss_fn(cfg))(params, {"ids": jnp.asarray(ids)})
        assert float(loss) == pytest.approx(reference.loss(params, ids[0], cfg) + cfg.moe_aux_weight * float(
            transformer.forward(params, jnp.asarray(ids[:, :-1]), cfg, return_aux=True)[1]), abs=1e-4)
        for stack, name in (("dense_blocks", "w_up"), ("dense_blocks", "wkv_b"), ("blocks", "shared_up"),
                            ("blocks", "wq_a"), ("blocks", "router")):
            assert float(jnp.linalg.norm(grads[stack][name])) > 0, (stack, name)
        # a layer's experts that no token of the batch chose on this share get no gradient; some do
        assert float(jnp.linalg.norm(grads["blocks"]["w_down"])) > 0
    elif program == "prefill":
        logits, cache = generate.prefill(params, jnp.asarray(ids), cfg, 16)
        np.testing.assert_allclose(logits[0], want[-1], atol=2e-4)
        # layer 0's rows are the dense layer's: the reference's own latents of the embedding
        lp = jax.tree_util.tree_map(lambda w: w[0], params["dense_blocks"])
        x0 = params["embed"][ids[0]]
        kv = reference._rms_norm(x0, lp["ln1"]) @ lp["wkv_a"]
        np.testing.assert_allclose(cache["ckv"][0, 0, :12], reference._rms_norm(kv[:, :32], lp["kv_a_norm"]), atol=2e-5)
    else:
        _, cache = generate.prefill(params, jnp.asarray(ids[:, :5]), cfg, 16)
        before = jax.tree_util.tree_map(np.asarray, cache)
        logits, after, touched = generate.decode_rows(
            params, cache, jnp.asarray(ids[:, 5]), jnp.asarray([5]), jnp.asarray([0]), cfg, jnp.asarray([True]))
        np.testing.assert_allclose(logits[0], want[5], atol=2e-4)
        # the step wrote one row a layer, at every one of the four layers, and nothing else
        for name in ("ckv", "kr"):
            changed = np.any(np.asarray(after[name]) != before[name], axis=-1)
            assert changed.shape == (4, 1, 16) and np.array_equal(np.nonzero(changed)[2], [5, 5, 5, 5])
        # a share's step says what fell on it: the held experts given a row and the assignments, layer means,
        # and the share of the layers whose rows went through the compact buffer
        assert touched.shape == (3,) and 0 <= float(touched[0]) <= 2 and float(touched[0]) <= float(touched[1]) <= 4
        assert 0 <= float(touched[2]) <= 1


def test_the_batcher_installs_latent_rows_and_snapshots_a_prefix_of_them(model):
    """`install_rows` and the prefix cache treat a request's rows as a pytree:
    a latent cache goes through both.  A prefix hit answers as the miss did and
    as a batcher without a prefix cache does, and an install touches its slot
    alone."""
    cfg, params = model
    rng = np.random.default_rng(6)
    shared, tails = rng.integers(0, cfg.vocab_size, 40), [rng.integers(0, cfg.vocab_size, n) for n in (5, 9)]
    prompts = [np.concatenate([shared, t]) for t in tails]

    def serve(entries):
        cb = ContinuousBatcher(params, cfg, slots=3, t_max=96, prefill_buckets=(32, 64), prefix_cache_entries=entries,
                               prefix_block=8)
        out = []
        for p in prompts + prompts[:1]:  # one at a time: the third finds the first's prefix
            req = cb.submit(p, max_new_tokens=6)
            cb.pump()
            out.append(list(req.out_tokens))
        return cb, out

    plain, want = serve(0)
    cached, got = serve(4)
    assert cached.stats["prefix_hits"] >= 1 and cached.stats["prefix_misses"] >= 1
    assert got[2] == got[0] and plain.stats["prefix_hits"] == 0
    for g, prompt in zip(got, prompts + prompts[:1]):
        # the cached path teacher-forces its suffix through the absorbed decode; the reference agrees with both
        seq = np.concatenate([prompt, g[:-1]])
        best = np.asarray(reference.forward(params, seq, cfg))[len(prompt) - 1:].argmax(axis=-1)
        assert list(best) == g
    assert want == got
    # four layers x (latent + the rotated key's 8 in the 128 lanes its cache gives it) x float32
    assert plain.stats["cache_bytes_per_token"] == 4 * (32 + 128) * 4
    entry = next(iter(cached.prefix_cache._d.values()))
    assert set(entry["rows"]) == {"ckv", "kr"} and entry["rows"]["ckv"].shape[:2] == (4, 1)
    cache = generate.init_cache(cfg, 3, 96)
    rows = jax.tree_util.tree_map(lambda a: jnp.ones_like(a[:, :1]), cache)
    after = generate.install_rows(cache, rows, 1)
    for name in ("ckv", "kr"):
        assert np.all(np.asarray(after[name][:, 1]) == 1) and not np.any(np.asarray(after[name][:, [0, 2]]))
    assert generate.cache_bytes_per_token(generate.init_cache(TransformerConfig(n_layers=2, n_kv_heads=2, d_head=16), 2, 8)) \
        == 2 * 2 * 2 * 16 * 2
    assert generate.recurrent_state_bytes(cache) == 0


@pytest.mark.parametrize("longest, ladder", [
    (512, (64, 128, 256, 512)), (4096, (64, 128, 256, 512, 1024, 2048, 4096)), (96, (64, 96)), (3000, (64, 128, 256, 512, 1024, 2048, 3000)),
], ids=["chat-512", "rag-4096", "short-96", "odd-3000"])
def test_the_bucket_ladder_continues_by_powers_of_two(longest, ladder):
    """A deployment of 512 keeps the four programs it has; one of 4,096 prefills
    a 700-token prompt in 1,024 positions, not 4,096."""
    assert prefill_buckets_for(longest) == ladder
    cfg, params = _model(n_layers=2)
    cb = ContinuousBatcher(params, cfg, slots=2, t_max=longest + 256, prefill_buckets=prefill_buckets_for(longest))
    assert cb._bucket(longest, 256) == longest and cb._bucket(60, 256) == 64
    if longest == 4096:
        assert [cb._bucket(n, 256) for n in (512, 700, 1024, 1025, 2100, 3900)] == [512, 1024, 1024, 2048, 4096, 4096]


def test_the_deployment_builds_its_ladder_from_max_prompt_len(monkeypatch):
    """`ContinuousLLMServer` hands its batcher the ladder of its own
    `max_prompt_len`: the serving cells' 512 gives the four buckets they had."""
    from cluster_anywhere_tpu.llm import serve_llm
    from cluster_anywhere_tpu.llm.processor import ModelSpec, ProcessorConfig

    for longest, want in ((512, (64, 128, 256, 512)), (2048, (64, 128, 256, 512, 1024, 2048))):
        server = serve_llm.ContinuousLLMServer(
            ProcessorConfig(model=ModelSpec(preset="tiny"), max_prompt_len=longest, max_new_tokens=8), slots=2)
        try:
            assert server.cb.prefill_buckets == want and server.cb.t_max == longest + 8
        finally:
            server.close()


@pytest.mark.parametrize("scoring", ["softmax", "sigmoid"])
def test_a_held_share_computes_its_experts_part_and_nothing_else(scoring):
    """`routed_ffn` told which experts it holds against the uncut layer with
    every other expert's second matrix zeroed: the same result, the same held
    groups, and the assignments it counts are those that fell on the share."""
    e, f, x_routed, k, n = 32, 16, 12, 3, 40
    ks = jax.random.split(jax.random.key(0), 5)
    router = jax.random.normal(ks[0], (e, x_routed)) * 0.5
    full = {"w_gate": jax.random.normal(ks[1], (1, x_routed, e, f)) * e ** -0.5,
            "w_up": jax.random.normal(ks[2], (1, x_routed, e, f)) * e ** -0.5,
            "w_down": jax.random.normal(ks[3], (1, x_routed, f, e)) * f ** -0.5}
    x = jax.random.normal(ks[4], (n, e))
    live = jnp.arange(n) % 7 != 0
    first, count = 5, 4
    kw = dict(k=k, renormalize=True, live=live, scoring=scoring, scale=2.5 if scoring == "sigmoid" else 1.0)
    mine = {name: w[:, first:first + count] for name, w in full.items()}
    got = routed_ffn(x, router, mine, 0, held=(first, count), **kw)
    mask = ((jnp.arange(x_routed) >= first) & (jnp.arange(x_routed) < first + count))[None, :, None, None]
    want = routed_ffn(x, router, {**full, "w_down": jnp.where(mask, full["w_down"], 0.0)}, 0, **kw)
    np.testing.assert_allclose(got.out, want.out, atol=1e-5)
    assert not np.any(np.asarray(got.out)[~np.asarray(live)])
    # the share's count by hand: the live rows' k largest scores that lie in [first, first + count)
    scores = np.asarray(x @ router)
    top = np.argsort(-scores, axis=-1)[:, :k]  # softmax and sigmoid are monotone: the same k
    on_share = ((top >= first) & (top < first + count)) & np.asarray(live)[:, None]
    assert int(got.assignments) == int(on_share.sum()) and int(want.assignments) == int(live.sum()) * k
    assert int(got.experts_touched) == len(set(top[on_share]))
    if scoring == "sigmoid":
        # the weights by hand for one live row: 2.5 s_e / sum of its k
        row = 1
        s = 1 / (1 + np.exp(-scores[row]))
        w = 2.5 * s[top[row]] / s[top[row]].sum()
        one = sum(w_e * np.asarray((jax.nn.silu(x[row] @ full["w_gate"][0, e_]) * (x[row] @ full["w_up"][0, e_]))
                                   @ full["w_down"][0, e_])
                  for w_e, e_ in zip(w, top[row]) if first <= e_ < first + count)
        np.testing.assert_allclose(got.out[row], one, atol=1e-5)


def test_configurations_that_are_not_built_are_refused_by_name():
    with pytest.raises(ValueError, match="latent attention takes qk_nope_head_dim, qk_rope_head_dim and v_head_dim"):
        TransformerConfig(kv_lora_rank=32)
    with pytest.raises(NotImplementedError, match="blocks of positions through a latent cache"):
        TransformerConfig(**LATENT, block_length=4)
    with pytest.raises(NotImplementedError, match="leading dense layers"):
        TransformerConfig(n_dense_layers=1)
    with pytest.raises(ValueError, match="experts_held"):
        TransformerConfig(n_experts=8, experts_held=(6, 4))
    cfg = TransformerConfig(**LATENT)
    with pytest.raises(NotImplementedError, match="latent attention"):
        transformer.param_specs(cfg)
    assert TransformerConfig(n_experts=8, experts_held=[2, 4]).experts_held == (2, 4)  # hashable however it came

