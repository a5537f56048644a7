"""The decode step's attention kernel (`ops/attention.py decode_attention`) in
interpret mode: against the dense contraction it stands in for on a TPU
(`models/generate.py _masked_attention`), what it may read of a cache, and a
batcher forced through it beside one on the CPU's path."""

import contextlib
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cluster_anywhere_tpu.llm import ContinuousBatcher
from cluster_anywhere_tpu.models import generate
from cluster_anywhere_tpu.models.transformer import TransformerConfig, init_params

attention = importlib.import_module("cluster_anywhere_tpu.ops.attention")

T_MAX, BLOCK_K = 64, 16
# (first, last, live): a prompt from slot 0; a left pad and two block edges crossed; a row that
# ends with the cache; a row of one slot; two released slots, one with the numbers of a row that
# had filled its cache and was moved on once more; a live row behind them
ROWS = [(0, 20, 1), (5, 41, 1), (13, T_MAX, 1), (33, 34, 1), (7, 30, 0), (2, T_MAX + 1, 0), (16, 32, 1)]
SHAPES = [(r, kv, tq) for r, kv in ((1, 4), (4, 2), (8, 2), (20, 1)) for tq in (1, 4)]
IDS = [f"r{r}-kv{kv}-tq{tq}" for r, kv, tq in SHAPES]


@pytest.fixture(autouse=True)
def small_key_blocks(monkeypatch):
    monkeypatch.setattr(attention, "DECODE_BLOCK_K", BLOCK_K)
    monkeypatch.setattr(attention, "DECODE_BLOCK_ROWS", BLOCK_K)


def test_the_key_block_by_the_serving_cells_shapes():
    """768 slots: 256 a grid step at 16 and 8 cached heads, 384 at 4, the row at 1."""
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(attention, "DECODE_BLOCK_K", 256)
        patch.setattr(attention, "DECODE_BLOCK_ROWS", 2048)
        assert [attention.decode_key_block(768, kv) for kv in (16, 8, 4, 1)] == [256, 256, 384, 768]
        assert attention.decode_key_block(4352, 8) == 256 and attention.decode_key_block(100, 8) == 100


def _problem(r, kv, tq, dtype=jnp.float32, d=16, layers=3):
    """(q, the stacks k and v, first, last, live, a configuration of the
    shape) with `ROWS` as the batch."""
    h = r * kv
    keys = jax.random.split(jax.random.key(100 * r + tq), 3)
    q = jax.random.normal(keys[0], (len(ROWS), tq, h, d), dtype)
    k, v = (jax.random.normal(key, (layers, len(ROWS), T_MAX, kv, d), dtype) for key in keys[1:])
    first, last, live = (jnp.asarray(c, jnp.int32) for c in zip(*ROWS))
    cfg = TransformerConfig(vocab_size=8, n_layers=1, d_model=h * d, n_heads=h, n_kv_heads=kv, d_head=d, d_ff=8)
    return q, k, v, first, last, live != 0, cfg


def _kernel(q, k, v, layer, first, last, live):
    span = attention.decode_span(first, last, live, *k.shape[2:4])
    return attention.decode_attention(q, k, v, jnp.int32(layer), span, interpret=True)


@pytest.mark.parametrize("r,kv,tq", SHAPES, ids=IDS)
def test_the_kernel_is_the_dense_contraction_over_the_live_rows(r, kv, tq):
    """Query heads a cached head of 1, 4, 8 and 20, one query position a row
    and a block of four: the live rows' outputs are `_masked_attention`'s on
    the layer's slice, a row that holds no request returns zeros."""
    q, k, v, first, last, live, cfg = _problem(r, kv, tq)
    got = _kernel(q, k, v, 1, first, last, live)
    want = generate._masked_attention(q, k[1], v[1], last, cfg, first)
    assert got.shape == want.shape == q.shape
    np.testing.assert_allclose(got[live], want[live], atol=2e-6)
    assert not np.asarray(got[~live]).any()
    # the layer is the one asked for
    assert float(jnp.max(jnp.abs(_kernel(q, k, v, 2, first, last, live) - got)[live])) > 1e-2
    # and without the flag every row is read to its own length
    every = _kernel(q, k, v, 1, first, jnp.minimum(last, T_MAX), None)
    np.testing.assert_allclose(every, generate._masked_attention(q, k[1], v[1], last, cfg, first), atol=2e-6)


@pytest.mark.parametrize("r,kv,tq", SHAPES, ids=IDS)
def test_the_kernel_reads_nothing_outside_the_live_rows_own_slots(r, kv, tq):
    """A cache that holds no number outside every live row's [first, last), in
    the other layers, the dead rows and the live rows' own pads and tails,
    leaves the live rows' outputs what they were: finite, and equal."""
    q, k, v, first, last, live, _ = _problem(r, kv, tq)
    clean = _kernel(q, k, v, 1, first, last, live)
    slots = jnp.arange(T_MAX)
    mine = live[:, None] & (slots >= first[:, None]) & (slots < last[:, None])  # [B, T_max]
    keep = (jnp.arange(k.shape[0]) == 1)[:, None, None] & mine[None]
    poisoned = [jnp.where(keep[..., None, None], a, jnp.nan) for a in (k, v)]
    got = _kernel(q, *poisoned, 1, first, last, live)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_array_equal(got, clean)


def test_the_kernel_in_the_caches_own_precision():
    """bfloat16 as the serving cells keep it: f32 scores and accumulator, the
    probabilities cast for the second contraction, as `_masked_attention`."""
    q, k, v, first, last, live, cfg = _problem(4, 2, 1, jnp.bfloat16)
    got = _kernel(q, k, v, 0, first, last, live).astype(jnp.float32)
    want = generate._masked_attention(q, k[0], v[0], last, cfg, first).astype(jnp.float32)
    np.testing.assert_allclose(got[live], want[live], atol=3e-2)


def test_the_rows_read_are_whole_key_blocks_of_the_rows_own_slots():
    """`decode_rows_read`, the count the batcher reports, by the index maps'
    own helper: [5, 41) lies in blocks 0 to 2 of 16 slots."""
    first, last = np.asarray([0, 5, 13, 33, 16, 2]), np.asarray([20, 41, T_MAX, 34, 32, T_MAX + 1])
    assert attention.decode_key_block(T_MAX, 2) == BLOCK_K and attention.decode_key_block(50, 2) == 50
    assert list(attention.decode_rows_read(first, last, T_MAX, 2)) == [32, 48, 64, 16, 16, 64]
    lo, hi = attention.decode_block_span(first, last, BLOCK_K, T_MAX)
    assert list(lo) == [0, 0, 0, 2, 1, 0] and list(hi) == [1, 2, 3, 2, 1, 3]  # inside the cache whatever a row says


_TINY = dict(vocab_size=64, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2, d_head=8, d_ff=64,
             dtype=jnp.float32, param_dtype=jnp.float32)
MODELS = {
    "dense": _TINY,
    "mixture": dict(_TINY, n_experts=4, n_experts_per_tok=2, moe_gated=True),
    "hybrid": dict(_TINY, n_layers=6, n_kv_heads=1, attn_layer_period=3, attn_layer_offset=2, ssm_d_state=8,
                   ssm_dt_rank=8, rotary=False, tie_embeddings=True),
    "blocks": dict(_TINY, vocab_size=251, n_experts=8, n_experts_per_tok=2, moe_gated=True, block_length=4,
                   mask_token_id=250, denoise_steps=4),
}


@contextlib.contextmanager
def through_the_kernel(seen):
    """The decode programs traced while this holds attend through the
    interpreted kernel (each call's query shape goes to `seen`); the programs
    traced before and after do not."""
    def kernel(q, *a, **k):
        seen.append(q.shape)
        return attention.decode_attention(q, *a, **k, interpret=True)

    jax.clear_caches()
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(generate, "decode_on_kernel", lambda: True)
        patch.setattr(generate, "decode_attention", kernel)
        yield
    jax.clear_caches()


def _serve(cfg, params, **kw):
    """A batcher's answers to five requests of unlike lengths over three slots
    (so slots are released and given out again), one cancelled on its way, and
    its counts."""
    cb = ContinuousBatcher(params, cfg, slots=3, t_max=T_MAX, prefill_buckets=(8, 32), **kw)
    rng = np.random.default_rng(3)
    reqs = [cb.submit(rng.integers(1, 60, n), max_new_tokens=m) for n, m in ((5, 9), (11, 4), (3, 14), (20, 6), (9, 8))]
    for _ in range(4):
        cb.step()
    cb.cancel(reqs[2].request_id)
    cb.pump()
    return [r.out_tokens for r in reqs], cb.stats


@pytest.mark.parametrize("model", list(MODELS), ids=list(MODELS))
def test_a_batcher_through_the_kernel_answers_token_for_token(model):
    cfg = TransformerConfig(**MODELS[model])
    params = init_params(jax.random.key(2), cfg)
    # with a prefix cache an admit's suffix steps run beside the batch's: a cache of one row, which
    # keeps the dense contraction on every backend (`generate._on_kernel`)
    kw = dict(prefix_cache_entries=2, prefix_block=4) if model == "dense" else {}
    want, stats = _serve(cfg, params, **kw)
    seen = []
    with through_the_kernel(seen):
        got, stats_kernel = _serve(cfg, params, **kw)
    assert seen and {shape[0] for shape in seen} == {3} and got == want and sum(map(len, want)) > 20
    assert stats_kernel["cache_rows_read"] == stats["cache_rows_read"] > 0  # the host's count: whatever path runs
