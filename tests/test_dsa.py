"""Learned sparse attention (ops/sparse_attention.py; `cfg.index_topk`) at a tiny
size on the CPU: the three kernels interpreted against the plain functions, ties
to the lower position, a context of at most topk the dense path to the bit, left
padding, a decode step's selection against the prefill's of the same prefix, the
third cache stack in the cache's bytes and in an installed slot, the batcher's
counters, the 8 shares against the uncut layer, and what is refused by name."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cluster_anywhere_tpu.llm.continuous import ContinuousBatcher
from cluster_anywhere_tpu.models import generate, transformer
from cluster_anywhere_tpu.models.transformer import TransformerConfig, init_params
from cluster_anywhere_tpu.ops import sparse_attention as sparse
from cluster_anywhere_tpu.parallel.moe import EXPERT_MATRICES

TOPK = 16
# the published block at a test's widths: 4 query heads on 2 cached heads of 16, an indexer of 2 heads x 8 on one
# key head, 16 of a context selected, 8 gated experts of which 0-3 are held, 4 layers
TINY = dict(vocab_size=97, d_model=64, n_layers=4, n_heads=4, n_kv_heads=2, d_head=16, d_ff=32, n_experts=8,
            n_experts_per_tok=2, moe_gated=True, moe_renormalize=True, d_expert=32, qk_norm=True, qk_norm_per_head=True,
            rope_theta=1e7, index_topk=TOPK, index_n_heads=2, index_head_dim=8, experts_held=(0, 4))


def model(dtype=jnp.float32, **over):
    cfg = TransformerConfig(**{**TINY, **over}, dtype=dtype, param_dtype=dtype)
    params = init_params(jax.random.key(0), cfg)
    b = params["blocks"]
    for name, (lo, hi) in {"ln1": (0.6, 1.4), "q_norm": (0.5, 1.5), "k_norm": (1.5, 0.5), "k_idx_norm": (0.7, 1.3)}.items():
        b[name] = b[name] * jnp.linspace(lo, hi, b[name].shape[-1]).astype(dtype)
    return cfg, params


def ids_of(n, seed=1):
    return np.asarray(jax.random.randint(jax.random.key(seed), (n,), 0, TINY["vocab_size"]))


# -- the three kernels, interpreted, against the plain functions -----------------------


def test_the_index_kernel_is_the_contraction_where_a_query_reads(monkeypatch):
    monkeypatch.setattr(sparse, "INDEX_BLOCK_Q", 128)
    monkeypatch.setattr(sparse, "INDEX_BLOCK_K", 128)
    rng = np.random.default_rng(0)
    qi = jnp.asarray(rng.normal(size=(2, 256, 4, 64)), jnp.bfloat16)
    ki = jnp.asarray(rng.normal(size=(2, 256, 64)), jnp.bfloat16)
    w = jnp.asarray(rng.normal(size=(2, 256, 4)), jnp.float32)
    want = sparse.index_scores_reference(qi, ki, w)
    got = sparse.index_scores_kernel(qi, ki, w, interpret=True)
    causal = np.tril(np.ones((256, 256), bool))
    np.testing.assert_allclose(np.where(causal, got, 0), np.where(causal, want, 0), atol=2e-5)
    # behind left pads (none, and 150 of 256: a whole tile of queries and of keys): what a real query reads of real keys
    padded = sparse.index_scores_kernel(qi, ki, w, first=jnp.asarray([0, 150]), interpret=True)
    read = causal[None] & (np.arange(256) >= np.array([0, 150])[:, None])[:, :, None] & (
        np.arange(256) >= np.array([0, 150])[:, None])[:, None, :]
    assert read[1].sum() == 106 * 107 // 2
    np.testing.assert_allclose(np.where(read, padded, 0), np.where(read, want, 0), atol=2e-5)
    # by hand: a weight a head times the relu of that head's product
    t, s = 200, 77
    by_hand = sum(float(w[1, t, h]) * max(float(jnp.dot(qi[1, t, h].astype(jnp.float32), ki[1, s].astype(jnp.float32))), 0.0)
                  for h in range(4))
    assert float(want[1, t, s]) == pytest.approx(by_hand, rel=1e-5)


@pytest.mark.parametrize("topk", [1, 16, 100, 300])
def test_the_select_kernel_finds_the_topk_exactly_and_ties_go_to_the_lower_position(topk):
    rng = np.random.default_rng(topk)
    # scores on a grid of halves: many equal, some -0.0 beside 0.0, and whatever lies outside a row's span
    scores = np.round(rng.normal(size=(64, 256)) * 2) / 2
    scores[:, ::7] *= -1.0
    scores = jnp.asarray(scores, jnp.float32)
    first = jnp.asarray(rng.integers(0, 20, size=64), jnp.int32)
    last = jnp.asarray(rng.integers(20, 257, size=64), jnp.int32)
    want = np.asarray(sparse.select_mask_reference(scores, first, last, topk))
    got = np.asarray(sparse.select_mask_kernel(scores, first, last, topk, interpret=True)) != 0
    assert np.array_equal(got, want)
    assert np.array_equal(want.sum(-1), np.minimum(np.asarray(last - first), topk))
    # by hand, a row: the sort by (score descending, position ascending)
    for row in (0, 17, 63):
        lo, hi = int(first[row]), int(last[row])
        order = sorted(range(lo, hi), key=lambda s: (-float(scores[row, s]), s))[:topk]
        assert sorted(np.nonzero(want[row])[0].tolist()) == sorted(order)
    # a decode step's list is the same set, and what it holds beyond its count is outside the span
    at, chosen = sparse.select_rows(scores, first, last, topk)
    for row in range(64):
        assert sorted(np.asarray(at[row, :int(chosen[row])]).tolist()) == np.nonzero(want[row])[0].tolist()


def test_a_block_of_rows_that_see_nothing_chooses_nothing():
    """A left pad's queries (last <= first), a whole block of the kernel's rows and part of the next."""
    rng = np.random.default_rng(9)
    scores = jnp.asarray(rng.normal(size=(192, 256)), jnp.float32)
    rows = np.arange(192)
    first, last = jnp.full((192,), 100, jnp.int32), jnp.asarray(rows + 1, jnp.int32)
    want = np.asarray(sparse.select_mask_reference(scores, first, last, 16))
    got = np.asarray(sparse.select_mask_kernel(scores, first, last, 16, interpret=True)) != 0
    assert np.array_equal(got, want) and not want[:100].any()
    assert np.array_equal(want.sum(-1), np.clip(rows + 1 - 100, 0, 16))


def test_equal_scores_go_to_the_lower_position():
    scores = jnp.zeros((8, 128), jnp.float32).at[:, 100].set(1.0)
    first, last = jnp.zeros((8,), jnp.int32), jnp.full((8,), 128, jnp.int32)
    want = np.zeros((8, 128), bool)
    want[:, :4], want[:, 100] = True, True
    assert np.array_equal(np.asarray(sparse.select_mask_reference(scores, first, last, 5)), want)
    assert np.array_equal(np.asarray(sparse.select_mask_kernel(scores, first, last, 5, interpret=True)) != 0, want)
    at, chosen = sparse.select_rows(scores, first, last, 5)
    assert np.asarray(at[0]).tolist() == [100, 0, 1, 2, 3] and int(chosen[0]) == 5


def test_the_flash_kernel_attends_under_the_mask_eight_heads_a_cached_head(monkeypatch):
    monkeypatch.setattr(sparse, "FLASH_BLOCK_Q", 128)
    monkeypatch.setattr(sparse, "FLASH_BLOCK_K", 128)
    rng = np.random.default_rng(3)
    t, pad = 256, 37
    q = jnp.asarray(rng.normal(size=(1, t, 8, 128)), jnp.bfloat16)
    k, v = (jnp.asarray(rng.normal(size=(1, t, 2, 128)), jnp.bfloat16) for _ in range(2))
    scores = jnp.asarray(rng.normal(size=(1, t, t)), jnp.float32)
    first = jnp.full((1, t), pad, jnp.int32)
    mask = sparse.select_mask_reference(scores, first, jnp.arange(1, t + 1)[None], 32).astype(jnp.int8)
    want = sparse.masked_attention_reference(q, k, v, mask, 128 ** -0.5)
    got = sparse.masked_flash_kernel(q, k, v, mask, 128 ** -0.5, first=jnp.asarray([pad]), interpret=True)
    real = np.arange(t) >= pad
    np.testing.assert_allclose(np.asarray(got, np.float32)[0, real], np.asarray(want, np.float32)[0, real], atol=0.03)
    assert not np.any(np.asarray(got, np.float32)[0, ~real])  # a pad's row sees nothing and is zeros


# -- the model ----------------------------------------------------------------------


def test_a_block_holds_the_indexers_weights_and_the_cache_its_keys():
    cfg, params = model()
    b = params["blocks"]
    assert b["wq_idx"].shape == (4, 64, 16) and b["wk_idx"].shape == (4, 64, 8) and b["w_idx"].shape == (4, 64, 2)
    assert b["k_idx_norm"].shape == b["k_idx_norm_b"].shape == (4, 8)
    cache = generate.init_cache(cfg, 3, 40)
    assert cache["kv"].shape == (4, 3, 40, 4, 16) and cache["ki"].shape == (4, 3, 8, 40) and "k" not in cache
    # a token: keys and values of 2 heads x 16 and the indexer's key of 8, over 4 layers
    assert generate.cache_bytes_per_token(cache, cfg) == 4 * (2 * 2 * 16 + 8) * 4
    assert generate.cache_context_bytes_per_token(cache, cfg) == generate.cache_bytes_per_token(cache, cfg)
    assert generate.cache_kind_bytes(cache) == {"full": 3 * 40 * 4 * (2 * 2 * 16 + 8) * 4, "window": 0}
    # what a step fetches of a layer's keys: a row's selected slots, min(its context, topk); every slot without rows
    assert generate.key_slots(cache, cfg=cfg) == (120, 0, 0)
    assert generate.key_slots(cache, np.asarray([0, 3]), np.asarray([10, 33]), cfg=cfg) == (10 + 16, 0, 0)
    plain = dataclasses.replace(cfg, index_topk=0)
    assert "ki" not in generate.init_cache(plain, 3, 40)
    # an installed slot takes the indexer's keys with it
    rows = {n: jnp.ones_like(a[:, :1]) for n, a in cache.items()}
    after = generate.install_rows(cache, rows, 1)
    assert all(float(after[n][:, 1].min()) == 1.0 and float(after[n][:, 0].max()) == 0.0 for n in ("kv", "ki"))


def test_a_context_of_at_most_topk_is_the_dense_path_to_the_bit():
    cfg, params = model()
    plain = dataclasses.replace(cfg, index_topk=0)  # the same weights, the indexer's unread
    ids = ids_of(TOPK)[None]
    assert np.array_equal(np.asarray(transformer.forward(params, ids, cfg)), np.asarray(transformer.forward(params, ids, plain)))
    sparse_logits, sparse_cache = generate.prefill(params, ids, cfg, TOPK)
    dense_logits, dense_cache = generate.prefill(params, ids, plain, TOPK)
    assert np.array_equal(np.asarray(sparse_logits), np.asarray(dense_logits))
    # keys and values lie side by side in the one stack
    assert np.array_equal(np.asarray(sparse_cache["kv"][:, :, :, :2]), np.asarray(dense_cache["k"]))
    assert np.array_equal(np.asarray(sparse_cache["kv"][:, :, :, 2:]), np.asarray(dense_cache["v"]))
    assert float(jnp.abs(sparse_cache["ki"]).min()) > 0  # written all the same
    # a cache of at most topk slots decodes through the dense core as well
    tok = jnp.asarray([5])
    a, _ = generate.decode_one(params, generate.prefill(params, ids[:, :9], cfg, TOPK)[1], tok, jnp.asarray(9), cfg)
    b, _ = generate.decode_one(params, generate.prefill(params, ids[:, :9], plain, TOPK)[1], tok, jnp.asarray(9), plain)
    assert np.array_equal(np.asarray(a), np.asarray(b))
    # and one position more is no longer the dense result
    ids = ids_of(TOPK + 8)[None]
    assert float(jnp.abs(transformer.forward(params, ids, cfg) - transformer.forward(params, ids, plain))[0, -1].max()) > 1e-4


def test_prefill_then_decode_through_the_cache_is_the_forward_and_left_padding_changes_nothing():
    cfg, params = model()
    ids = ids_of(44)
    with jax.default_matmul_precision("highest"):
        full = transformer.forward(params, ids[None], cfg)[0]
        n, t_max = 40, 64
        logits, cache = generate.prefill(params, ids[None, :n], cfg, t_max)
        np.testing.assert_allclose(logits[0], full[n - 1], atol=2e-5)
        padded = np.concatenate([np.zeros(8, ids.dtype), ids[:n]])[None]
        logits_p, cache_p = generate.prefill(params, padded, cfg, t_max, pad=jnp.asarray([8]))
        np.testing.assert_allclose(logits_p[0], logits[0], atol=2e-5)
        np.testing.assert_allclose(cache_p["ki"][:, 0, :, 8:48], cache["ki"][:, 0, :, :40], atol=2e-6)
        for i in range(n, 44):
            logits, cache = generate.decode_one(params, cache, jnp.asarray(ids[i:i + 1]), jnp.asarray(i), cfg)
            logits_p, cache_p = generate.decode_one(params, cache_p, jnp.asarray(ids[i:i + 1]), jnp.asarray(i + 8), cfg,
                                                    pad=jnp.asarray([8]))
            np.testing.assert_allclose(logits[0], full[i], atol=2e-5)
            np.testing.assert_allclose(logits_p[0], full[i], atol=2e-5)


def _first_layer_parts(cfg, params, ids, pad=0):
    """What the first layer's core is given for ids [T] behind `pad` pads: q, k, v and the indexer's part."""
    bp = jax.tree_util.tree_map(lambda w: w[0], params["blocks"])
    x = params["embed"][jnp.asarray(np.concatenate([np.zeros(pad, ids.dtype), ids]))][None]
    y = transformer._norm(x, bp, "ln1", cfg)
    positions = jnp.maximum(jnp.arange(x.shape[1]) - pad, 0)[None]
    q, k, v = transformer._project_qkv(bp, y, cfg)
    q, k = transformer._rope(q, k, positions, cfg)
    return q, k, v, transformer._project_index(bp, y, cfg, positions)


@pytest.mark.parametrize("pad", [0, 5])
def test_a_decode_steps_selection_is_the_prefills_of_the_same_prefix(pad):
    cfg, params = model()
    ids = ids_of(40, seed=4)
    q, k, v, index = _first_layer_parts(cfg, params, ids, pad)
    _, mask = transformer._sparse_attention(q, k, v, index, cfg, jnp.asarray([pad]), chosen=True)
    mask = np.asarray(mask[0]) != 0
    assert np.array_equal(mask.sum(-1)[pad:], np.minimum(np.arange(1, 41), TOPK)) and not mask[:pad].any()
    one = dataclasses.replace(cfg, n_layers=1)
    t_max = 64
    for t in (TOPK - 1, TOPK, 25, 39):
        cache = generate.init_cache(one, 1, t_max)
        put = lambda a: jnp.pad(a[0, :pad + t], ((0, t_max - pad - t),) + ((0, 0),) * (a.ndim - 2))
        cache = {"kv": cache["kv"].at[0, 0].set(put(jnp.concatenate([k, v], axis=2))),
                 "ki": cache["ki"].at[0, 0].set(put(index[1]).T)}
        row = lambda a: a[:, pad + t:pad + t + 1]
        pos, pads = jnp.asarray([pad + t]), jnp.asarray([pad])
        attn, after, (at, chosen) = generate._sparse_decode_core(cache, 0, pos, pads, one, row(q), row(k), row(v),
                                                                 tuple(map(row, index)), listed=True)
        assert np.array_equal(np.asarray(after["kv"][0, 0, pad + t]), np.asarray(jnp.concatenate([k, v], axis=2)[0, pad + t]))
        assert int(chosen[0]) == min(t + 1, TOPK)
        assert sorted(np.asarray(at[0, :int(chosen[0])]).tolist()) == np.nonzero(mask[pad + t])[0].tolist()
        want = sparse.masked_attention_reference(row(q), k[:, :pad + t + 1], v[:, :pad + t + 1],
                                                 jnp.asarray(mask[None, pad + t:pad + t + 1, :pad + t + 1]), cfg.attn_scale)
        np.testing.assert_allclose(attn, want, atol=2e-6)


def test_the_batcher_counts_the_selected_rows_the_contexts_and_the_scan():
    cfg, params = model()
    cb = ContinuousBatcher(params, cfg, slots=3, t_max=64, prefill_buckets=(16, 32, 48))
    lens = (10, 30, 40)
    reqs = [cb.submit(ids_of(n, seed=n), max_new_tokens=6) for n in lens]
    cb.pump()
    assert all(len(r.out_tokens) == 6 for r in reqs)
    # step j (of 5) reads, a live row, min(its context, topk) selected slots of its bucket + j positions
    buckets = [cb._bucket(n, 6) for n in lens]
    contexts = [[n + j + 1 for n in lens] for j in range(5)]
    assert buckets == [16, 32, 48]
    assert cb.stats["cache_rows_read"] == sum(min(c, TOPK) for step in contexts for c in step)
    assert cb.stats["context_rows"] == sum(map(sum, contexts))
    assert cb.stats["index_rows_read"] == 5 * 3 * 64 and cb.stats["cache_rows"] == 5 * 3 * 64
    # the served tokens are a lone generate's
    for r, n in zip(reqs, lens):
        alone = generate.generate(params, jnp.asarray(r.prompt_ids)[None], jax.random.key(0), cfg=cfg, max_new_tokens=6)
        assert np.asarray(alone)[0].tolist() == list(r.out_tokens)


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """What ties the share to the model: the routed parts that the 8 shares give, each from the program's own
    expert layer told which 2 of 16 experts it holds, are the uncut layer's mixture."""
    whole = TransformerConfig(**{**TINY, "n_experts": 16, "experts_held": None}, dtype=jnp.float32, param_dtype=jnp.float32)
    bp = jax.tree_util.tree_map(lambda w: w[0], init_params(jax.random.key(9), whole)["blocks"])
    y = jnp.asarray(np.random.default_rng(2).normal(size=(2, 17, 64)), jnp.float32)
    want, _, _ = transformer._moe(bp, y, whole)
    total, assignments = 0.0, 0
    for share in range(8):
        held = dataclasses.replace(whole, experts_held=(2 * share, 2))
        mine = {k: (v[2 * share:2 * share + 2] if k in EXPERT_MATRICES else v) for k, v in bp.items()}
        part, _, counts = transformer._moe(mine, y, held)
        total, assignments = total + part, assignments + int(counts[1])
    np.testing.assert_allclose(total, want, atol=3e-5)
    assert assignments == 2 * 17 * 2  # every (token, expert) pair fell on exactly one share


def test_what_is_not_built_is_refused_by_name():
    base = {**TINY, "experts_held": None}
    for over, match in [
        (dict(index_n_heads=0), "index_n_heads"),
        (dict(index_head_dim=7), "index_head_dim"),
        (dict(block_length=4, denoise_steps=4), "learned sparse attention"),
        (dict(layer_mixers=("attn", "attn_win", "attn", "attn"), attn_window=8), "learned sparse attention"),
        (dict(kv_lora_rank=8, q_lora_rank=8, qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8), "learned sparse attention"),
        (dict(sp=2), "learned sparse attention"),
    ]:
        with pytest.raises((ValueError, NotImplementedError), match=match):
            TransformerConfig(**{**base, **over})
    # served on one device: no mesh shards it
    with pytest.raises(NotImplementedError, match="learned sparse attention"):
        transformer.param_specs(TransformerConfig(**base))
