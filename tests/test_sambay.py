"""A decoder-hybrid-decoder stack with differential attention
(models/transformer.py, models/generate.py, ops/attention.py,
llm/continuous.py) at a test's widths on the CPU, float32 weights from a seed:
the map of Phi-4-mini-flash-reasoning at 8 layers (0 ssm, 1 window, 2 ssm, 3
window, 4 ssm -> the memory, 5 full -> the shared keys and values, 6 a gated
memory unit, 7 cross), a window of 8 in a ring of 16, LayerNorm, biases on the
attention projections, no rotary, a tied head.  The plain reference is the
benchmark's own (benchmarks/references/sambay.py), loaded as the harness loads
it."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import manifest
from cluster_anywhere_tpu.llm.continuous import ContinuousBatcher
from cluster_anywhere_tpu.models import generate, transformer
from cluster_anywhere_tpu.models.transformer import TransformerConfig, init_params
from cluster_anywhere_tpu.ops.attention import (
    decode_attention, decode_key_block, decode_span, flash_attention, reference_attention,
)

reference = manifest.load_reference("sambay")

MAP = ("ssm", "attn_win", "ssm", "attn_win", "ssm", "attn", "gmu", "attn_cross")
TINY = dict(vocab_size=97, d_model=64, n_layers=8, n_heads=8, n_kv_heads=4, d_head=8, d_ff=160,
            layer_mixers=MAP, attn_window=8, attn_ring=16, rotary=False, tie_embeddings=True,
            layer_norm=True, norm_eps=1e-5, attn_bias=True, diff_attn=True,
            ssm_d_state=4, ssm_dt_rank=4, ssm_inner_norms=False)
T_MAX = 80


def _model(seed=1, **over):
    cfg = TransformerConfig(**{**TINY, **over}, dtype=jnp.float32, param_dtype=jnp.float32)
    params = init_params(jax.random.key(seed), cfg)
    # the norms' weights off 1, so a norm that is left out or misplaced shows
    for stack in ("ssm_blocks", "win_blocks", "blocks", "gmu_blocks", "cross_blocks"):
        b = params[stack]
        for name, (lo, hi) in {"ln1": (0.6, 1.4), "ln2": (1.3, 0.7), "subln": (0.5, 1.5)}.items():
            if name in b:
                b[name] = b[name] * jnp.linspace(lo, hi, b[name].shape[-1])
    return cfg, params


@pytest.fixture(scope="module")
def model():
    return _model()


def test_the_kinds_of_layer_their_stacks_their_runs_and_what_keeps_no_rows(model):
    """A period of alternating kinds is one run of the layer loop; each kind's
    weights are a stack of its own; a cross layer holds no wk or wv and a gated
    memory unit no mixer state; the cache holds one full stack for the full
    layer and the cross layers together, flat (a slot's cached pairs as rows)."""
    cfg, params = model
    assert cfg.layer_kinds == MAP and cfg.carries and cfg.shared_readers == 2
    assert transformer._layer_runs(cfg.layer_kinds) == [
        (("ssm", "attn_win"), (0, 0), 2), ("ssm", 2, 1), ("attn", 0, 1), ("gmu", 0, 1), ("attn_cross", 0, 1)]
    published = ("ssm", "attn_win") * 8 + ("ssm", "attn") + ("gmu", "attn_cross") * 7
    assert transformer._layer_runs(published) == [
        (("ssm", "attn_win"), (0, 0), 8), ("ssm", 8, 1), ("attn", 0, 1), (("gmu", "attn_cross"), (0, 0), 7)]
    # runs of one kind stay what they were: Jamba's pattern, a leading dense layer
    assert transformer._layer_runs(("ssm",) * 7 + ("attn",) + ("ssm",) * 13 + ("attn",) + ("ssm",) * 6) == [
        ("ssm", 0, 7), ("attn", 0, 1), ("ssm", 7, 13), ("attn", 1, 1), ("ssm", 20, 6)]
    assert {name: params[name]["ln1"].shape[0] for name in ("ssm_blocks", "win_blocks", "blocks", "gmu_blocks", "cross_blocks")} == {
        "ssm_blocks": 3, "win_blocks": 2, "blocks": 1, "gmu_blocks": 1, "cross_blocks": 1}
    assert not {"wk", "wv", "bk", "bv"} & set(params["cross_blocks"]) and {"wq", "wo", "bq", "bo", "lq1", "subln"} <= set(params["cross_blocks"])
    assert set(params["gmu_blocks"]) == {"ln1", "ln1_b", "ln2", "ln2_b", "gmu_in", "gmu_out", "w_gate", "w_up", "w_down"}
    assert not {"dt_norm", "b_norm", "c_norm"} & set(params["ssm_blocks"]) and "ln_f_b" in params
    assert generate._state_index(cfg) == {"ssm": [0, 1, 2], "attn_win": [0, 1], "attn": [0], "gmu": [0], "attn_cross": [0]}
    assert generate.shared_layer(cfg) == 0 and (cfg.cached_heads, cfg.cached_width, cfg.flat_heads) == (2, 16, 2)
    cache = generate.init_cache(cfg, 3, T_MAX)
    assert {n: a.shape for n, a in cache.items()} == {
        "k": (1, 3, T_MAX * 2, 16), "v": (1, 3, T_MAX * 2, 16), "kw": (2, 3, 16 * 2, 16), "vw": (2, 3, 16 * 2, 16),
        "conv": (3, 3, 3, 128), "h": (3, 3, 128, 4)}
    # a token in the layers that keep keys and values (two rings, one full stack: a cross layer keeps none), and
    # one more token of context: the one full layer's keys and values, whatever reads them
    assert generate.cache_bytes_per_token(cache, cfg) == 3 * 2 * 4 * 8 * 4
    assert generate.cache_context_bytes_per_token(cache, cfg) == 2 * 4 * 8 * 4
    assert generate.cache_kind_bytes(cache) == {"full": 2 * 3 * T_MAX * 32 * 4, "window": 2 * 2 * 3 * 16 * 32 * 4}
    # the layers that READ a stack: two rings, and the full stack twice (its writer and the cross layer)
    assert generate.key_slots(cache, cfg=cfg) == ((2 * 3 * T_MAX + 2 * 3 * 16) // 4, 2 * 3 * 16 // 4, 2 * 3 * T_MAX // 4)
    first, last = np.asarray([0, 2]), np.asarray([10, 40])
    assert generate.key_slots(cache, first, last, 8, cfg) == ((2 * 2 * T_MAX + 2 * 2 * 16) // 4, 2 * 2 * 16 // 4, 2 * 2 * T_MAX // 4)
    for bad in (dict(layer_mixers=MAP[:4] + ("attn", "ssm", "gmu", "attn_cross")), dict(layer_mixers=MAP[:5] + ("attn_win", "gmu", "attn_cross")),
                dict(layer_mixers=MAP[:6] + ("ssm", "attn_cross")), dict(layer_mixers=("attn",) * 6 + ("gmu", "attn_cross")),
                dict(rotary=True), dict(n_kv_heads=1, n_heads=8)):
        with pytest.raises((ValueError, NotImplementedError)):
            _model(**bad)
    with pytest.raises(NotImplementedError, match="one device only"):
        transformer.param_specs(cfg)


def test_the_one_norm_is_what_its_weights_say():
    """`_norm` norms by what it is handed: an RMSNorm for a weight, a LayerNorm
    for a weight and a bias, nothing for neither; the epsilon is the
    configuration's.  A state-space layer made without the inner norms mixes
    without them, and one made with them is Jamba's as ever."""
    cfg = TransformerConfig(norm_eps=1e-3)
    x = jax.random.normal(jax.random.key(0), (3, 5, 16)) * 2 + 0.5
    w, b = jnp.linspace(0.5, 1.5, 16), jnp.linspace(-0.1, 0.1, 16)
    rms = x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-3) * w
    mean, var = jnp.mean(x, -1, keepdims=True), jnp.var(x, -1, keepdims=True)
    np.testing.assert_allclose(transformer._norm(x, {"n": w}, "n", cfg), rms, atol=1e-6)
    np.testing.assert_allclose(transformer._norm(x, {"n": w, "n_b": b}, "n", cfg), (x - mean) / jnp.sqrt(var + 1e-3) * w + b, atol=1e-6)
    assert transformer._norm(x, {}, "n", cfg) is x
    np.testing.assert_allclose(transformer._norm(x, {"n": w}, "n", TransformerConfig()), transformer._rms_norm(x, w), atol=0)
    jamba = TransformerConfig(d_model=32, n_layers=2, attn_layer_period=2, attn_layer_offset=1)
    plain = dataclasses.replace(jamba, ssm_inner_norms=False)
    with_norms = init_params(jax.random.key(0), jamba)["ssm_blocks"]
    without = init_params(jax.random.key(0), plain)["ssm_blocks"]
    assert set(with_norms) - set(without) == {"dt_norm", "b_norm", "c_norm"}
    one = lambda blocks: jax.tree_util.tree_map(lambda a: a[0], blocks)
    xs = jax.random.normal(jax.random.key(1), (2, 6, jamba.d_inner), jamba.dtype)
    state = transformer._ssm_zero_state(jamba, 2)
    normed, _ = transformer._ssm_mix(one(with_norms), xs, state, jamba)
    bare, _ = transformer._ssm_mix(one(without), xs, state, plain)
    same, _ = transformer._ssm_mix({k: v for k, v in one(with_norms).items() if not k.endswith("_norm")}, xs, state, jamba)
    assert float(jnp.max(jnp.abs(normed.astype(jnp.float32) - bare.astype(jnp.float32)))) > 1e-3
    np.testing.assert_array_equal(np.asarray(bare, np.float32), np.asarray(same, np.float32))


def test_training_forward_and_loss_are_the_references(model):
    cfg, params = model
    ids = np.random.default_rng(4).integers(0, cfg.vocab_size, (2, 30))
    logits = transformer.forward(params, jnp.asarray(ids), cfg)
    for row, got in zip(ids, logits):
        np.testing.assert_allclose(got, np.asarray(reference.forward(params, row, cfg)), atol=3e-4)
    loss, grads = jax.value_and_grad(transformer.make_loss_fn(cfg))(params, {"ids": jnp.asarray(ids[:1])})
    assert abs(float(loss) - reference.loss(params, ids[0], cfg)) < reference.LOSS_TOL / 100
    # every kind's weights take a gradient: the memory and the shared keys and values carry it down the stack
    for stack, name in (("ssm_blocks", "ssm_x"), ("win_blocks", "lq1"), ("blocks", "wk"), ("blocks", "bv"),
                        ("gmu_blocks", "gmu_in"), ("cross_blocks", "subln"), ("cross_blocks", "bq")):
        assert float(jnp.linalg.norm(grads[stack][name])) > 0, (stack, name)


def _padded(ids, lens, n):
    prompt = np.zeros((len(lens), n), np.int64)
    for b, m in enumerate(lens):
        prompt[b, n - m:] = ids[b, :m]
    return jnp.asarray(prompt), jnp.asarray([n - m for m in lens], jnp.int32)


# window 8 is the model's; the others are the program served under another mask than the reference's
@pytest.mark.parametrize("served_window", [8, 7, 0], ids=["window-8", "window-7", "full-mask"])
def test_prefill_then_forty_decoded_tokens_through_ring_stack_and_state_match_the_reference(model, served_window):
    """Logits, not tokens: three prompts prefill in one batch (one shorter than
    the window, one longer than the ring, one left-padded in its row), 40
    tokens go one at a time through the rings (which go round more than twice,
    past the window), the one full stack that two layers read and the
    recurrent state, and every step's logits are the plain reference's full
    forward.  Served under another window the same program fails the bound."""
    cfg, params = model
    n, lens = 24, (5, 24, 15)
    ids = np.random.default_rng(0).integers(0, cfg.vocab_size, (3, n + 40))
    want = [np.asarray(reference.forward(params, row[:m + 40], cfg)) for row, m in zip(ids, lens)]
    served = dataclasses.replace(cfg, attn_window=served_window or T_MAX, attn_ring=16 if served_window else T_MAX)
    prompt, pad = _padded(ids, lens, n)
    logits, cache = generate.prefill(params, prompt, served, T_MAX, pad)
    assert cache["kw"].shape == (2, 3, (16 if served_window else T_MAX) * 2, 16) and cache["k"].shape == (1, 3, T_MAX * 2, 16)
    step = jax.jit(lambda c, tok, pos: generate.decode_rows(params, c, tok, pos, pad, served)[:2])
    worst = 0.0
    for i in range(41):
        worst = max(worst, *(float(np.max(np.abs(logits[b] - want[b][m - 1 + i]))) for b, m in enumerate(lens)))
        if i < 40:
            tok = jnp.asarray([ids[b, m + i] for b, m in enumerate(lens)])
            logits, cache = step(cache, tok, jnp.full(3, n + i))
    if served_window == 8:
        assert worst < 3e-4, worst
    else:
        assert worst > 1e-2, worst


def test_the_early_exit_prefill_gives_the_full_forwards_logits_and_a_decodes_rows(model):
    """A prompt's prefill computes the second half of the stack at its last
    position alone: its logits there are the full forward's, and the rows it
    installs (rings, the full stack, recurrent state) are those that decoding
    the prompt position by position from an empty cache leaves."""
    cfg, params = model
    n, lens = 24, (21, 24, 9)
    ids = np.random.default_rng(3).integers(0, cfg.vocab_size, (3, n))
    prompt, pad = _padded(ids, lens, n)
    logits, rows = generate.prefill(params, prompt, cfg, T_MAX, pad)
    for b, m in enumerate(lens):
        np.testing.assert_allclose(logits[b], np.asarray(reference.forward(params, ids[b, :m], cfg))[m - 1], atol=3e-4)
    # the same columns one at a time: a pad's column moves no state on, as the prefill's mask has it
    cache = generate.init_cache(cfg, 3, T_MAX)
    step = jax.jit(lambda c, tok, pos: generate.decode_rows(params, c, tok, pos, pad, cfg)[:2])
    for j in range(n):
        before = cache
        last, cache = step(cache, prompt[:, j], jnp.full(3, j))
        is_pad = j < np.asarray(pad)
        cache = {name: jnp.where(is_pad.reshape(1, 3, *[1] * (a.ndim - 2)), before[name], a) if name in ("conv", "h") else a
                 for name, a in cache.items()}
    np.testing.assert_allclose(last, logits, atol=3e-4)
    extent = {"k": T_MAX, "v": T_MAX, "kw": 16, "vw": 16}
    for name in rows:
        got, want = np.asarray(rows[name]), np.asarray(cache[name])
        if name in extent:  # a pad's slot holds what no query sees
            slot = np.arange(extent[name])
            held = (((n - 1 - slot) % 16 < n - np.asarray(pad)[:, None]) if name in ("kw", "vw")
                    else (slot >= np.asarray(pad)[:, None]) & (slot < n))
            mask = np.repeat(held, cfg.flat_heads, axis=1)[None, :, :, None]
            got, want = got * mask, want * mask
        np.testing.assert_allclose(got, want, atol=3e-4, err_msg=name)
    assert float(np.max(np.abs(np.asarray(rows["kw"])))) > 0.1 and float(np.max(np.abs(np.asarray(rows["h"])))) > 1e-3


def _two_softmaxes(q, k, v, lam, first, last, window=0):
    """Differential attention the long way for one row of queries at
    positions [last - tq, last): q [Tq, H, D], k, v [T, KV, D]; pair p's two maps
    are softmaxes of their own over keys [first, last) (and the band), and the
    result is (A1 - lam A2) V over the pair's two value heads side by side.
    Returns [Tq, H / 2, 2 D]."""
    tq, h, d = q.shape
    kv = k.shape[1]
    per = (h // 2) // (kv // 2)
    pos = last - tq + np.arange(tq)
    slot = np.arange(k.shape[0])
    seen = (slot[None, :] <= pos[:, None]) & (slot[None, :] >= first)
    if window:
        seen &= pos[:, None] - slot[None, :] < window
    out = np.zeros((tq, h // 2, 2 * d))
    for p in range(h // 2):
        g = p // per
        maps = []
        for j in (0, 1):
            s = np.where(seen, q[:, 2 * p + j] @ k[:, 2 * g + j].T * d ** -0.5, -1e30)  # a pad's own row sees nothing
            e = np.exp(s - s.max(-1, keepdims=True))
            maps.append(e / e.sum(-1, keepdims=True))
        out[:, p] = (maps[0] - lam * maps[1]) @ np.concatenate([v[:, 2 * g], v[:, 2 * g + 1]], axis=-1)
    return out


@pytest.mark.parametrize("core", ["decode_kernel", "decode_ring_kernel", "decode_dense", "prefill_full", "prefill_banded",
                                  "prefill_banded_kernel", "prefill_full_kernel"])
def test_differential_attention_through_every_core_is_the_two_softmaxes(core):
    """`_diff_heads` lays a pair's two cached heads side by side as one head of
    twice the width and pads each query over the other head's half, so the
    cores that know nothing of pairs give both maps of every pair over the
    whole V: the decode kernel (on a full stack and on a ring), the dense
    decode contraction, the prefill's reference core and the flash kernels
    (interpreted), at R = 2 query pairs a cached pair, on left-padded rows."""
    h, kv, d, t = 8, 4, 16, 128
    rng = np.random.default_rng(len(core))
    q, k, v = (rng.normal(size=(2, t, heads, d)).astype(np.float32) for heads in (h, kv, kv))
    pads = np.asarray([0, 37])
    lam = 0.6
    qd, kd, vd = transformer._diff_heads(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
    assert qd.shape == (2, t, h, 2 * d) and kd.shape == vd.shape == (2, t, kv // 2, 2 * d)
    combine = lambda a: np.asarray(a, np.float64).reshape(*a.shape[:2], h // 2, 2, 2 * d)
    diff = lambda a: combine(a)[:, :, :, 0] - lam * combine(a)[:, :, :, 1]
    if core.startswith("decode"):
        ring = core == "decode_ring_kernel"
        window, extent = (64, 64) if ring else (0, t)
        last = np.asarray([100, 128])
        first = np.maximum(pads, last - window) if ring else pads
        rows = lambda a: jnp.stack([a[b, last[b] - 1] for b in range(2)])[:, None]  # the newest position's
        stored = kd, vd
        if ring:  # the last `extent` positions round the ring, position p at slot p mod extent
            stored = [jnp.stack([jnp.roll(a[b, last[b] - extent:last[b]], (last[b] - extent) % extent, axis=0) for b in range(2)])
                      for a in stored]
        if core == "decode_dense":
            cfg = TransformerConfig(n_heads=h, n_kv_heads=kv, d_head=d)
            got = generate._masked_attention(rows(qd), *stored, jnp.asarray(last), cfg, jnp.asarray(pads), out_dtype=jnp.float32)
        else:
            flat = [a.reshape(1, 2, -1, 2 * d) for a in stored]  # the stacks as the cache keeps them: [n, B, T * KV, D]
            span = decode_span(jnp.asarray(first), jnp.asarray(last), None, extent, kv // 2, ring=ring)
            got = decode_attention(rows(qd), *flat, 0, span, scale=d ** -0.5, ring=ring, kv=kv // 2,
                                   out_dtype=jnp.float32, interpret=True)
        assert got.dtype == jnp.float32
        for b in range(2):
            want = _two_softmaxes(q[b, last[b] - 1:last[b]], k[b, :last[b]], v[b, :last[b]], lam, first[b], last[b])
            np.testing.assert_allclose(diff(got)[b], want, atol=2e-5)
        return
    window = 40 if "banded" in core else 0
    kr, vr = (jnp.repeat(a, h // (kv // 2), axis=2) for a in (kd, vd))
    attend = functools.partial(flash_attention, interpret=True) if core.endswith("kernel") else reference_attention
    got = attend(qd, kr, vr, causal=True, scale=d ** -0.5, pad=jnp.asarray(pads), window=window, out_dtype=jnp.float32)
    assert got.dtype == jnp.float32
    for b in range(2):
        want = _two_softmaxes(q[b], k[b], v[b], lam, pads[b], t, window)
        np.testing.assert_allclose(diff(got)[b, pads[b]:], want[pads[b]:], atol=2e-5)


def test_a_ring_is_one_key_block_up_to_twice_the_full_stacks():
    """A window of 512 at 10 cached pairs: the ring of 512 slots is one step
    and one fetch of the decode kernel a live row; a ring past twice the full
    stacks' key block is refused."""
    assert decode_key_block(4096, 10) == 256 and decode_key_block(512, 10, ring=True) == 512
    cfg = TransformerConfig(n_heads=40, n_kv_heads=20, d_head=64, n_layers=2, layer_mixers=("attn_win", "attn"),
                            attn_window=512, diff_attn=True, rotary=False)
    assert generate.window_extent(cfg, 4096) == 512
    with pytest.raises(ValueError, match="one key block"):
        decode_key_block(1024, 10, ring=True)


def test_a_gated_memory_units_memory_is_the_last_mamba_layers_read_out_of_the_same_step(model):
    """In a decode step the memory is the step's own: move layer 4's recurrent
    state (the last Mamba layer's) and the gated memory unit's memory and the
    logits move; move what a layer above it reads (the full layer's stack) and
    the logits move but the memory does not.  The unit keeps nothing: the
    cache after the step holds no array of its own for it."""
    cfg, params = model
    n = 12
    ids = np.random.default_rng(5).integers(0, cfg.vocab_size, (2, n + 1))
    _, cache = generate.prefill(params, jnp.asarray(ids[:, :n]), cfg, T_MAX)
    seen = {}

    def spy(bp, x, m, cfg_):  # inside the layer loop's scan: the value leaves by a callback
        jax.debug.callback(lambda value: seen.__setitem__("m", np.asarray(value)), m)
        return inner(bp, x, m, cfg_)

    inner = transformer._gmu_half
    def step(c):
        out = generate.decode_rows(params, c, jnp.asarray(ids[:, n]), jnp.full(2, n), jnp.zeros(2, jnp.int32), cfg)
        jax.effects_barrier()
        return out

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(transformer, "_gmu_half", spy)
        logits, after, _ = step(cache)
        m = seen["m"]
        assert m.shape == (2, 1, cfg.d_inner)
        moved_state = dict(cache, h=cache["h"].at[2].add(0.5))  # layer 4 is the third Mamba layer
        logits_h, _, _ = step(moved_state)
        m_h = seen["m"]
        later = dict(cache, k=cache["k"] + 0.5)  # the full layer's stack: read above the memory's layer
        logits_k, _, _ = step(later)
        m_k = seen["m"]
    assert float(np.max(np.abs(m_h - m))) > 1e-2 and float(jnp.max(jnp.abs(logits_h - logits))) > 1e-4
    np.testing.assert_array_equal(m_k, m)
    assert float(jnp.max(jnp.abs(logits_k - logits))) > 1e-4
    # the memory is layer 4's read-out of this step: its mixer, from the state the cache held
    assert set(after) == set(cache) == {"k", "v", "kw", "vw", "conv", "h"}


def test_a_cross_layer_reads_the_full_layers_stack_and_writes_nothing(model):
    """The decode core of a cross layer is given no keys or values: it attends
    to the full layer's stack as the step's full layer left it, and hands the
    cache on as it was."""
    cfg, _ = model
    rng = np.random.default_rng(9)
    cache = {name: jnp.asarray(rng.normal(size=a.shape), a.dtype) for name, a in generate.init_cache(cfg, 2, T_MAX).items()}
    q = transformer._diff_heads(jnp.asarray(rng.normal(size=(2, 1, 8, 8)), jnp.float32), None, None)[0]
    pos, pads = jnp.asarray([30, 11]), jnp.asarray([2, 0])
    got, after = generate._kv_decode_core(cache, 0, pos, pads, cfg, q, None, None, kind="attn_cross")
    assert all(after[name] is cache[name] for name in cache)
    layer = lambda a: a[0].reshape(2, T_MAX, cfg.cached_heads, cfg.cached_width)
    want = generate._masked_attention(q, layer(cache["k"]), layer(cache["v"]), pos + 1, cfg, pads, out_dtype=jnp.float32)
    np.testing.assert_allclose(got, want, atol=1e-6)
    moved, _ = generate._kv_decode_core(dict(cache, kw=cache["kw"] + 1.0, vw=cache["vw"] * 2.0), 0, pos, pads, cfg, q, None, None,
                                        kind="attn_cross")
    np.testing.assert_array_equal(np.asarray(moved), np.asarray(got))


def _chosen(params, cfg, prompt, served):
    full = np.asarray(list(prompt) + list(served[:-1]), np.int32)
    return np.asarray(reference.forward(params, full, cfg))[len(prompt) - 1:]


@pytest.mark.parametrize("prefix_cache_entries", [0, 4], ids=["plain", "prefix-cache"])
def test_the_batcher_serves_staggered_admits_beside_a_dead_slot(model, prefix_cache_entries):
    """Through `ContinuousBatcher`: requests admitted at different steps, one
    slot never used, one reused; every served token is the reference's own
    choice from the logits of the full forward.  With a prefix cache the rows
    of a cached prefix (ring, full stack, state: a pytree) come back and the
    suffix is stepped token by token: the same tokens."""
    cfg, params = model
    rng = np.random.default_rng(7)
    shared = rng.integers(0, cfg.vocab_size, 32)
    prompts = [np.concatenate([shared, rng.integers(0, cfg.vocab_size, k)]) for k in (3, 9)] + [
        rng.integers(0, cfg.vocab_size, 6), rng.integers(0, cfg.vocab_size, 19)]
    cb = ContinuousBatcher(params, cfg, slots=4, t_max=T_MAX, prefill_buckets=(8, 32, 64),
                           prefix_cache_entries=prefix_cache_entries, prefix_block=16)
    reqs = [cb.submit(prompts[0], max_new_tokens=14)]
    cb.step(), cb.step()
    reqs.append(cb.submit(prompts[1], max_new_tokens=9))
    reqs.append(cb.submit(prompts[2], max_new_tokens=22))  # runs past the window and the ring's wrap
    for _ in range(12):
        cb.step()
    reqs.append(cb.submit(prompts[3], max_new_tokens=7))  # into the slot the second request left
    cb.pump()
    assert reqs[3].slot in (0, 1) and cb.stats["admitted"] == 4 and cb._by_slot == [None] * 4
    for prompt, req in zip(prompts, reqs):
        want = _chosen(params, cfg, prompt, req.out_tokens)
        regret = want.max(-1) - want[np.arange(len(req.out_tokens)), req.out_tokens]
        assert float(regret.max()) < 1e-4, regret
    stats = cb.stats
    if prefix_cache_entries:
        assert stats["prefix_hits"] == 1 and stats["prefix_misses"] >= 1
        entry = next(iter(cb.prefix_cache._d.values()))["rows"]
        assert entry["kw"].shape == (2, 1, 32, 16) and entry["k"].shape == (1, 1, T_MAX * 2, 16) and entry["h"].shape == (3, 1, 128, 4)
    else:
        # four admits: buckets 64, 64, 8, 32 computed in the first layer, one position each in the last
        assert (stats["prefill_positions_total"], stats["prefill_tail_positions_total"]) == (168, 4)
        assert stats["prefill_tail_share"] == pytest.approx(100 * 4 / 168)
    assert (stats["cache_bytes_per_token"], stats["cache_context_bytes_per_token"]) == (3 * 2 * 4 * 8 * 4, 2 * 4 * 8 * 4)
    assert stats["ssm_state_bytes"] > 0
    assert stats["shared_rows_read"] > 0 and stats["window_rows_read"] > 0
    assert stats["cache_rows_read"] == stats["shared_rows_read"] + stats["window_rows_read"]
    # a model whose layers hand on x alone computes every position in every layer
    dense = TransformerConfig(vocab_size=97, d_model=32, n_layers=2, n_heads=2, n_kv_heads=2, d_head=16, d_ff=64, dtype=jnp.float32)
    plain = ContinuousBatcher(init_params(jax.random.key(0), dense), dense, slots=2, t_max=48, prefill_buckets=(8,))
    plain.submit([1, 2, 3], max_new_tokens=2)
    plain.pump()
    assert plain.stats["prefill_tail_share"] == 100.0 and plain.stats["shared_rows_read"] == 0
