"""Window and full attention layers in one stack (models/transformer.py,
models/generate.py, ops/attention.py, llm/continuous.py) at a test's widths on
the CPU, float32 weights from a seed: a window of 8 in a ring of 16, the
pattern `LLLG LLLG` with a dense first layer, each half's output normed, rotary
on the window layers only.  The plain reference is the benchmark's own
(benchmarks/references/swa_moe.py), loaded as the harness loads it."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import manifest
from cluster_anywhere_tpu.llm.continuous import ContinuousBatcher
from cluster_anywhere_tpu.models import generate, transformer
from cluster_anywhere_tpu.models.transformer import TransformerConfig, init_params
from cluster_anywhere_tpu.ops.attention import decode_attention, decode_span, flash_attention, reference_attention

reference = manifest.load_reference("swa_moe")

LLLG = ("attn_win", "attn_win", "attn_win", "attn") * 2
TINY = dict(vocab_size=97, d_model=64, n_layers=8, n_heads=4, n_kv_heads=2, d_head=16, d_ff=160,
            layer_mixers=LLLG, attn_window=8, attn_ring=16, rotary_full=False, norm_output=True,
            qk_norm=True, qk_norm_per_head=True, rope_theta=1e6,
            n_dense_layers=1, d_expert=24, n_experts=32, n_experts_per_tok=4, moe_gated=True,
            moe_renormalize=True, moe_scoring="sigmoid", moe_routed_scale=2.5, n_shared_experts=1,
            experts_held=(6, 2))
T_MAX = 80


def _model(seed=1, **over):
    cfg = TransformerConfig(**{**TINY, **over}, dtype=jnp.float32, param_dtype=jnp.float32)
    params = init_params(jax.random.key(seed), cfg)
    # the norms' weights off 1, so a norm that is left out or misplaced shows
    for stack in ("blocks", "win_blocks", "win_dense_blocks"):
        b = params[stack]
        for name, (lo, hi) in {"ln1": (0.6, 1.4), "ln2": (1.3, 0.7), "q_norm": (0.5, 1.5), "k_norm": (1.5, 0.5)}.items():
            b[name] = b[name] * jnp.linspace(lo, hi, b[name].shape[-1])
    return cfg, params


@pytest.fixture(scope="module")
def model():
    return _model()


def test_the_kinds_of_layer_their_stacks_and_their_runs(model):
    """`layer_kinds` from a tuple of mixers and the leading dense layers: a
    window layer that keeps the dense FFN is a kind of its own, each kind's
    weights a stack of its own, the layer loop one scan a run, and each state's
    rows one stack in the model's order whatever kinds share it."""
    cfg, params = model
    assert cfg.layer_kinds == ("attn_win_dense", "attn_win", "attn_win", "attn", "attn_win", "attn_win", "attn_win", "attn")
    assert transformer._layer_runs(cfg.layer_kinds) == [
        ("attn_win_dense", 0, 1), ("attn_win", 0, 2), ("attn", 0, 1), ("attn_win", 2, 3), ("attn", 1, 1)]
    shapes = {name: params[name]["wq"].shape[0] for name in ("win_dense_blocks", "win_blocks", "blocks")}
    assert shapes == {"win_dense_blocks": 1, "win_blocks": 5, "blocks": 2}
    assert "router" not in params["win_dense_blocks"] and params["win_blocks"]["w_gate"].shape == (5, 2, 64, 24)
    assert generate._state_index(cfg) == {"attn_win_dense": [0], "attn_win": [1, 2, 3, 4, 5], "attn": [0, 1]}
    assert [cfg.rotates(k) for k in ("attn", "attn_win", "attn_win_dense")] == [False, True, True]
    cache = generate.init_cache(cfg, 3, T_MAX)
    assert {n: a.shape for n, a in cache.items()} == {
        "k": (2, 3, T_MAX, 2, 16), "v": (2, 3, T_MAX, 2, 16), "kw": (6, 3, 16, 2, 16), "vw": (6, 3, 16, 2, 16)}
    # without a ring of its own the extent is the decode kernel's key block that holds the window
    wide = dataclasses.replace(cfg, attn_ring=0, n_kv_heads=4, attn_window=128)
    assert generate.window_extent(wide, 8448) == 512 and generate.window_extent(wide, 300) == 300
    assert generate.window_extent(dataclasses.replace(wide, n_kv_heads=8, n_heads=8), 8448) == 256
    assert generate.cache_bytes_per_token(cache) == 8 * 2 * 2 * 16 * 4
    # what one more token of context adds: the two full layers' keys and values (a ring is a slot's)
    assert generate.cache_context_bytes_per_token(cache) == 2 * 2 * 2 * 16 * 4
    assert generate.cache_kind_bytes(cache) == {"full": 2 * 2 * 3 * T_MAX * 2 * 16 * 4, "window": 2 * 6 * 3 * 16 * 2 * 16 * 4}
    for bad in (dict(layer_mixers=LLLG[:3]), dict(attn_window=0), dict(attn_ring=4), dict(attn_layer_period=2),
                dict(layer_mixers=("ssm",) + LLLG[1:])):
        with pytest.raises((ValueError, NotImplementedError)):
            _model(**bad)
    with pytest.raises(NotImplementedError, match="one device only"):
        transformer.param_specs(cfg)


# window 8 is the model's; the others are the program served under another mask than the reference's
@pytest.mark.parametrize("served_window", [8, 7, 9, 0], ids=["window-8", "window-7", "window-9", "full-mask"])
def test_prefill_then_forty_decoded_tokens_through_the_two_extent_cache_match_the_reference(model, served_window):
    """Logits, not tokens: three prompts prefill in one batch (one shorter than
    the window, one longer than the ring, one left-padded in its row), 40
    tokens go one at a time through the full layers' cache and the window
    layers' ring, which goes round more than twice, and every step's logits are
    the plain reference's full forward under the window of 8.  The same program
    with a window of 7 or 9, or with the window layers' mask left full, fails
    the same bound."""
    cfg, params = model
    n, lens = 24, (5, 24, 15)
    ids = np.random.default_rng(0).integers(0, cfg.vocab_size, (3, n + 40))
    want = [np.asarray(reference.forward(params, row[:m + 40], cfg)) for row, m in zip(ids, lens)]
    served = dataclasses.replace(cfg, attn_window=served_window or T_MAX, attn_ring=16 if served_window else T_MAX)
    prompt = np.zeros((3, n), np.int64)
    for b, m in enumerate(lens):
        prompt[b, n - m:] = ids[b, :m]
    pad = jnp.asarray([n - m for m in lens], jnp.int32)
    worst = 0.0
    logits, cache = generate.prefill(params, jnp.asarray(prompt), served, T_MAX, pad)
    assert cache["kw"].shape == (6, 3, 16 if served_window else T_MAX, 2, 16) and cache["k"].shape == (2, 3, T_MAX, 2, 16)
    step = jax.jit(lambda c, tok, pos: generate.decode_rows(params, c, tok, pos, pad, served)[:2])
    for i in range(41):
        worst = max(worst, *(float(np.max(np.abs(logits[b] - want[b][m - 1 + i]))) for b, m in enumerate(lens)))
        if i < 40:
            tok = jnp.asarray([ids[b, m + i] for b, m in enumerate(lens)])
            logits, cache = step(cache, tok, jnp.full(3, n + i))
    if served_window == 8:
        assert worst < 3e-4, worst
    else:
        assert worst > 3e-2, worst


def test_training_forward_sees_the_same_windows(model):
    cfg, params = model
    ids = np.random.default_rng(4).integers(0, cfg.vocab_size, (1, 30))
    want = np.asarray(reference.forward(params, ids[0], cfg))
    logits, aux = transformer.forward(params, jnp.asarray(ids), cfg, return_aux=True)
    np.testing.assert_allclose(logits[0], want, atol=3e-4)
    loss, grads = jax.value_and_grad(transformer.make_loss_fn(cfg))(params, {"ids": jnp.asarray(ids)})
    assert float(loss) == pytest.approx(reference.loss(params, ids[0], cfg) + cfg.moe_aux_weight * float(
        transformer.forward(params, jnp.asarray(ids[:, :-1]), cfg, return_aux=True)[1]), abs=1e-4)
    for stack in ("win_dense_blocks", "win_blocks", "blocks"):
        assert float(jnp.linalg.norm(grads[stack]["wq"])) > 0 and float(jnp.linalg.norm(grads[stack]["q_norm"])) > 0


@pytest.mark.parametrize("t, window, pads", [(256, 128, None), (384, 128, (0, 77)), (256, 40, (130, 3)), (128, 64, (5, 0))])
def test_the_banded_flash_kernel_is_the_banded_reference(t, window, pads):
    """The Pallas kernel interpreted: a band of `window` under the causal mask,
    with and without left pads, key blocks before the band skipped."""
    ks = jax.random.split(jax.random.key(t + window), 3)
    q, k, v = (jax.random.normal(kk, (2, t, 2, 32), jnp.float32) for kk in ks)
    pad = None if pads is None else jnp.asarray(pads, jnp.int32)
    want = reference_attention(q, k, v, pad=pad, window=window)
    got = flash_attention(q, k, v, pad=pad, window=window, interpret=True)
    real = np.ones((2, t), bool) if pads is None else np.arange(t)[None, :] >= np.asarray(pads)[:, None]
    np.testing.assert_allclose(np.asarray(got)[real], np.asarray(want)[real], atol=2e-5)
    full = reference_attention(q, k, v, pad=pad)
    assert float(jnp.max(jnp.abs(full - want)[:, -1])) > 1e-2  # the window is narrower than the sequence
    with pytest.raises(ValueError, match="window"):
        flash_attention(q, k, v, window=window, causal=False, interpret=True)


@pytest.mark.parametrize("kv, heads", [(8, 16), (2, 4)])
def test_the_decode_kernel_reads_a_ring(kv, heads):
    """`decode_attention` over a window layer's stack, interpreted: rows at
    unequal depths, one below the window, one whose ring went round twice, one
    empty; against the dense contraction over the same ring."""
    extent, window, d = 256, 128, 32
    rng = np.random.default_rng(kv)
    k = jnp.asarray(rng.normal(size=(2, 4, extent, kv, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, 4, extent, kv, d)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(4, 1, heads, d)), jnp.float32)
    last = jnp.asarray([40, 300, 777, 9], jnp.int32)  # positions written so far, the newest last - 1
    pads = jnp.asarray([3, 0, 100, 0], jnp.int32)
    live = jnp.asarray([True, True, True, False])
    first = jnp.maximum(pads, last - window)
    span = decode_span(first, last, live, extent, kv, ring=True)
    assert int(span[4, 0]) == 3  # one key block a live row
    got = decode_attention(q, k, v, 1, span, ring=True, interpret=True)
    cfg = TransformerConfig(n_heads=heads, n_kv_heads=kv, d_head=d)
    want = generate._masked_attention(q, k[1], v[1], last, cfg, pads, seen=generate._ring_seen(first, last, extent))
    np.testing.assert_allclose(got[:3], want[:3], atol=2e-5)
    assert not np.any(np.asarray(got[3]))
    seen = np.asarray(generate._ring_seen(first, last, extent))
    assert seen.sum(axis=1).tolist() == [37, 128, 128, 9]
    assert seen[1, 299 % extent] and seen[1, 172 % extent] and not seen[1, 171 % extent]
    with pytest.raises(ValueError, match="one key block"):
        decode_attention(q, jnp.zeros((2, 4, 4096, kv, d)), jnp.zeros((2, 4, 4096, kv, d)), 1, span, ring=True,
                             interpret=True)


def test_the_batcher_serves_through_two_extents_and_counts_both(model):
    """`install_rows`, the prefix cache and the suffix step over rows with two
    time axes; `cache_rows_read`, `cache_rows` as means over the attention
    layers, `window_rows_read` the rings' part."""
    cfg, params = model
    rng = np.random.default_rng(7)
    shared = rng.integers(0, cfg.vocab_size, 32)
    prompts = [np.concatenate([shared, rng.integers(0, cfg.vocab_size, n)]) for n in (3, 9)] + [
        rng.integers(0, cfg.vocab_size, 6)]
    plain = ContinuousBatcher(params, cfg, slots=4, t_max=T_MAX, prefill_buckets=(8, 32, 64))
    cached = ContinuousBatcher(params, cfg, slots=4, t_max=T_MAX, prefill_buckets=(8, 32, 64),
                               prefix_cache_entries=4, prefix_block=16)
    out = {}
    for name, cb in (("plain", plain), ("cached", cached)):
        reqs = [cb.submit(p, max_new_tokens=12) for p in prompts]
        cb.pump()
        out[name] = [r.out_tokens for r in reqs]
    for p, toks in zip(prompts, out["plain"]):
        seq = np.concatenate([p, toks])
        ref = np.asarray(reference.forward(params, seq[:-1], cfg))
        assert toks == [int(np.argmax(ref[len(p) - 1 + i])) for i in range(12)]
    assert out["cached"] == out["plain"]
    assert cached.stats["prefix_hits"] == 1 and cached.stats["prefix_misses"] == 1
    entry = next(iter(cached.prefix_cache._d.values()))["rows"]
    assert entry["kw"].shape == (6, 1, 16, 2, 16) and entry["k"].shape == (2, 1, T_MAX, 2, 16)
    stats = plain.stats
    # 11 steps of three live rows: a full layer's slots are the cache's, a window layer's the ring's
    assert stats["decode_steps"] == 11 and stats["cache_rows"] == 11 * (2 * 4 * T_MAX + 6 * 4 * 16) // 8
    assert stats["window_rows_read"] == 11 * (6 * 3 * 16) // 8  # every live row's whole ring a window layer
    assert stats["cache_rows_read"] == 11 * (2 * 3 * T_MAX + 6 * 3 * 16) // 8  # T_MAX is one key block here
    assert stats["cache_window_bytes"] == 2 * 6 * 4 * 16 * 2 * 16 * 4 and stats["cache_full_bytes"] == 2 * 2 * 4 * T_MAX * 2 * 16 * 4
    assert stats["cache_window_share"] == pytest.approx(100 * 6 * 16 / (6 * 16 + 2 * T_MAX))
    # one extent reads as ever
    dense_cfg = TransformerConfig(vocab_size=97, d_model=32, n_layers=2, n_heads=2, n_kv_heads=2, d_head=16, d_ff=64,
                                  dtype=jnp.float32)
    cache = generate.init_cache(dense_cfg, 4, 64)
    assert generate.key_slots(cache) == (4 * 64, 0, 0)
    assert generate.key_slots(cache, np.asarray([0, 2]), np.asarray([10, 40])) == (2 * 64, 0, 0)


def test_installing_rows_overwrites_a_slots_ring_whole(model):
    cfg, params = model
    cache = jax.tree_util.tree_map(lambda a: a + 7.0, generate.init_cache(cfg, 3, T_MAX))
    ids = np.random.default_rng(2).integers(0, cfg.vocab_size, (1, 20))
    _, rows = generate.prefill(params, jnp.asarray(ids), cfg, T_MAX)
    after = generate.install_rows(cache, rows, 1)
    for name in ("k", "v", "kw", "vw"):
        np.testing.assert_array_equal(after[name][:, 1], rows[name][:, 0])
        assert np.all(np.asarray(after[name][:, 0]) == 7.0) and np.all(np.asarray(after[name][:, 2]) == 7.0)
    # column j of the prompt lies at ring slot j mod 16: the last 16 of 20, gone round once
    lp = jax.tree_util.tree_map(lambda w: w[0], params["win_dense_blocks"])
    x0 = params["embed"][ids[0]].astype(jnp.float32)
    _, k, _ = reference._qkv(x0, lp, reference._dims(cfg), cfg.attn_window)
    np.testing.assert_allclose(rows["kw"][0, 0, np.arange(4, 20) % 16], k[4:20], atol=2e-5)
