"""A model that generates by blocks (SDAR's kind: `TransformerConfig.block_length`
4) through the one continuous batcher, at a test's widths in float32 on the CPU:
prefill and passes through the cache against the plain reference's whole
forward, the batcher's streams against a plain loop over that reference, the
rule for what a pass fixes where confidences pass the threshold, fixedness as a
flag, the flash prefill under the block mask, q/k norm a head, and what such a
replica refuses."""

import dataclasses
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import manifest
from cluster_anywhere_tpu.llm import continuous
from cluster_anywhere_tpu.llm.continuous import ContinuousBatcher
from cluster_anywhere_tpu.models import generate, transformer
from cluster_anywhere_tpu.models.transformer import TransformerConfig, init_params

attention_ops = importlib.import_module("cluster_anywhere_tpu.ops.attention")  # the package exports the function
reference = manifest.load_reference("sdar")
MASK = 250
TINY = dict(
    vocab_size=251, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2, d_head=16, d_ff=48,
    n_experts=8, n_experts_per_tok=2, moe_gated=True, moe_renormalize=True, qk_norm=True,
    qk_norm_per_head=True, rope_theta=1e6, dtype=jnp.float32, param_dtype=jnp.float32,
    block_length=4, mask_token_id=MASK, denoise_steps=4,
)


def program(sharpen=0.0, **over):
    """(cfg, params) with the norms' weights off 1.  sharpen: the head scaled up,
    so that some confidences pass the threshold."""
    cfg = TransformerConfig(**{**TINY, **over})
    params = init_params(jax.random.key(4), cfg)
    blocks = params["blocks"]
    blocks["q_norm"] = blocks["q_norm"] * jnp.linspace(0.5, 1.5, blocks["q_norm"].shape[-1])
    blocks["k_norm"] = blocks["k_norm"] * jnp.linspace(1.4, 0.6, blocks["k_norm"].shape[-1])
    if sharpen:
        params["lm_head"] = params["lm_head"] * sharpen
    return cfg, params


def plain_generate(params, cfg, prompt, max_new, eos=None):
    """The published loop over the plain reference's whole forward, a sequence
    at a time, nothing kept between two passes; the answer ends at max_new
    tokens or with the token eos.  Returns (the answer, the pass of its block
    that fixed each of its tokens, the tokens a pass fixed, what the last block
    held fixed past the answer's end: [(position in the block, pass, token)])."""
    b, n = cfg.block_length, len(prompt)
    m, thr = b // cfg.denoise_steps, np.log(cfg.confidence_threshold)
    seq, out, at, sizes = list(prompt), [], [], []
    ended = lambda: len(out) >= max_new or (eos is not None and out[-1:] == [eos])
    while not ended():
        start = len(seq) - len(seq) % b
        block = seq[start:] + [cfg.mask_token_id] * (b - len(seq) + start)
        masked = [i >= len(seq) - start for i in range(b)]
        when, p, gave = [-1] * b, 0, len(seq) - start
        while not ended() and gave < b:
            logp = np.asarray(jax.nn.log_softmax(reference.forward(params, seq[:start] + block, cfg)[start:], axis=-1))
            tok, conf = logp.argmax(-1), np.where(masked, logp.max(-1), -np.inf)
            high = conf > thr
            fix = high if high.sum() >= m else np.isin(np.arange(b), np.argsort(-conf, kind="stable")[:m]) & masked
            sizes.append(int(fix.sum()))
            for i in np.nonzero(fix)[0]:
                block[i], masked[i], when[i] = int(tok[i]), False, p
            p += 1
            while gave < b and not masked[gave] and not ended():
                out.append(block[gave])
                at.append(when[gave])
                gave += 1
        seq = seq[:start] + block
    return out, at, sizes, [(i, when[i], block[i]) for i in range(gave, b) if not masked[i]]


def test_prefill_then_passes_through_the_cache_give_the_references_logits():
    """Logits, not tokens: a prompt's whole blocks through `prefill`, then three
    blocks through `_pass_logits` against the rows they left, each block
    seeing the ones before it and itself; the reference runs the whole sequence
    at once under the block mask."""
    cfg, params = program()
    ids = np.random.default_rng(0).integers(0, MASK, 28)
    ids[[17, 22, 23]] = MASK  # masks inside the blocks that pass
    want = np.asarray(reference.forward(params, ids, cfg))
    t_max, bucket, whole = 48, 32, 16
    padded = np.zeros((1, bucket), np.int32)
    padded[0, bucket - whole:] = ids[:whole]
    pad = np.asarray([bucket - whole], np.int32)
    none, rows = generate.prefill(params, padded, cfg, t_max, pad=pad)
    assert none is None  # the last position's logits are its own token's, which is known
    for start in (16, 20, 24):
        logits, rows = continuous._pass_logits(
            params, rows, ids[None, start:start + 4].astype(np.int32),
            np.asarray([bucket - whole + start], np.int32), pad, cfg=cfg)
        assert np.max(np.abs(np.asarray(logits[0]) - want[start:start + 4])) < 2e-4
    # under the block mask a position sees the rest of its own block and no later one
    later = ids.copy()
    later[21] = 7
    moved = np.asarray(reference.forward(params, later, cfg))
    assert np.array_equal(moved[:20], want[:20]) and not np.allclose(moved[20], want[20])
    with jax.default_matmul_precision("highest"):
        whole_forward = np.asarray(transformer.forward(params, jnp.asarray(ids[None]), cfg)[0])
    assert np.max(np.abs(whole_forward - want)) < 2e-4
    causal = np.asarray(reference.forward(params, ids, dataclasses.replace(cfg, block_length=0)))
    assert np.max(np.abs(causal - want)) > 1e-2


@pytest.mark.parametrize("sharpen", [0.0, 40.0], ids=["no-confidence-passes", "sharpened-head"])
def test_the_batchers_streams_are_a_plain_loop_over_the_reference(sharpen):
    """Prompts with n mod 4 of 0, 1, 2, 3 (and one shorter than a block),
    answers that end inside a block, more requests than slots, so that one step
    holds rows in different passes of different blocks: every stream and its
    record of passes are the plain loop's.  With the sharpened head some
    confidences pass 0.9: a pass fixes up to 4 positions, a step hands a row 0
    to 4 tokens, always in position order."""
    cfg, params = program(sharpen)
    cb = ContinuousBatcher(params, cfg, slots=3, t_max=64, prefill_buckets=(16, 32), prefix_cache_entries=0)
    rng = np.random.default_rng(2)
    asked = [(12, 9), (9, 10), (18, 7), (31, 6), (3, 11), (20, 8)]
    reqs = [cb.submit(rng.integers(0, MASK, n), max_new_tokens=new) for n, new in asked]
    handed, per_step = {r.request_id: [] for r in reqs}, []
    while cb.has_work:
        out = cb.step()
        per_step.extend(len(v) for v in out.values())
        assert all(out.values())  # a request that got nothing is not in the step's result
        for rid, toks in out.items():
            handed[rid].extend(toks)
    sizes = []
    for r in reqs:
        want, at, fixed, tail = plain_generate(params, cfg, r.prompt_ids.tolist(), r.max_new_tokens)
        assert r.out_tokens == want == handed[r.request_id] and len(want) == r.max_new_tokens
        assert cb.fixed_at(r.request_id) == at and cb.block_tail(r.request_id) == tail
        sizes += fixed
    assert cb.stats["tokens_out"] == sum(new for _, new in asked) == sum(per_step)
    assert cb.stats["block_tokens_fixed"] >= cb.stats["tokens_out"]
    if sharpen:
        assert cb.stats["block_passes"] < cb.stats["tokens_out"]  # under one pass a token
        assert {2, 3, 4} & set(sizes) and {1, 4} <= set(per_step) and max(sizes) <= 4
    else:
        # one position a pass and no pass that only stores: a whole block is four passes, and a
        # request is in as many as fixed a position of it and the one in flight when it ended
        assert cb.stats["block_passes"] == cb.stats["block_tokens_fixed"] + cb.stats["late_rows"]
        # every block of an answer but its last is stored, by the first pass of the block after it
        assert cb.stats["late_rows"] == len(reqs)
        assert cb.stats["block_stores_fused"] == sum(-(-(n % 4 + new) // 4) - 1 for n, new in asked) == 12
        assert set(sizes) == {1} and cb.fixed_at(reqs[0].request_id)[:8] != [0, 1, 2, 3] * 2
        assert sorted(cb.fixed_at(reqs[0].request_id)[:4]) == [0, 1, 2, 3]
    with pytest.raises(KeyError):
        cb.fixed_at(10_000)


@pytest.mark.parametrize("pos, pad, pending, tail", [
    (12, 4, False, 0), (12, 4, True, 0), (0, 0, False, 3), (16, 8, False, 1), (28, 8, True, 0),
], ids=["nothing-pending", "pending", "prompt-shorter-than-a-block", "prompt-tail", "pending-block-ends-at-t_max-less-B"])
def test_a_pass_that_stores_the_block_before_is_the_two_passes_it_replaces(pos, pad, pending, tail):
    """`decode_rows` over [S, 2B] tokens with a pending mask against a storing
    pass of the block before followed by a pass of the block (`_pass_logits`, a
    slot at a time): the cache rows [pos - B, pos + B) of every layer and the
    block's logits.  The slot under test lies between one that has a block
    pending at another depth and one that holds no request and says it has one
    pending: a row with nothing pending, live or not, leaves [pos - B, pos) as it
    was bit for bit, and no row writes anywhere else (a position before the
    cache's start does not come round to its end)."""
    cfg, params = program()
    b, t_max, slots = cfg.block_length, 32, 3
    rng = np.random.default_rng(pos + tail)
    cache = {name: jnp.asarray(rng.standard_normal(a.shape), a.dtype) for name, a in generate.init_cache(cfg, slots, t_max).items()}
    poss, pads = np.asarray([8, pos, 20], np.int32), np.asarray([0, pad, 4], np.int32)
    flags, live = np.asarray([True, pending, True]), np.asarray([True, True, False])
    before = rng.integers(0, MASK, (slots, b)).astype(np.int32)
    block = np.full((slots, b), MASK, np.int32)
    block[1, :tail] = rng.integers(0, MASK, tail)
    block[0, 2] = 17  # a position fixed by an earlier pass of its block
    step = jax.jit(lambda c, pending: generate.decode_rows(
        params, c, jnp.asarray(np.concatenate([before, block], axis=1)), jnp.asarray(poss), jnp.asarray(pads), cfg,
        jnp.asarray(live), pending))
    logits, after, touched = step(cache, jnp.asarray(flags))
    assert logits.shape == (slots, b, cfg.vocab_size) and logits.dtype == jnp.float32 and 2.0 <= float(touched) <= 8.0
    for s in range(slots):
        at, mine = int(poss[s]), lambda c: {name: np.asarray(a[:, s]) for name, a in c.items()}
        was, now = mine(cache), mine(after)
        written = np.zeros(t_max, bool)
        written[at:at + b] = True  # a slot without a request writes its block's rows too, into its own slot
        if flags[s] and live[s]:
            written[at - b:at] = True
        for name in ("k", "v"):
            assert np.array_equal(now[name][:, ~written], was[name][:, ~written]), (s, name)
        if not live[s]:
            continue
        rows, pad1 = {name: a[:, s:s + 1] for name, a in cache.items()}, pads[s:s + 1]
        if flags[s]:
            _, rows = continuous._pass_logits(params, rows, before[s:s + 1], poss[s:s + 1] - b, pad1, cfg=cfg)
        want, rows = continuous._pass_logits(params, rows, block[s:s + 1], poss[s:s + 1], pad1, cfg=cfg)
        assert np.max(np.abs(np.asarray(logits[s]) - np.asarray(want[0]))) < 2e-5, s
        for name in ("k", "v"):
            assert np.max(np.abs(now[name] - np.asarray(rows[name][:, 0]))[:, max(at - b, 0):at + b]) < 2e-5, (s, name)
    # the storing half is there: without it the block's logits are those of another cache
    if pending:
        apart, _, _ = step(cache, jnp.asarray([True, False, True]))
        assert np.max(np.abs(np.asarray(apart[1]) - np.asarray(logits[1]))) > 1e-3


def test_answers_that_end_where_the_cache_ends_are_the_plain_loops():
    """An answer whose last block ends at the cache's last slot: the pass in
    flight when the host learns of the end runs the row a block further on,
    past the cache (its block's rows are written nowhere and what it makes is
    dropped), and stores the last block, which nobody reads."""
    cfg, params = program()
    cb = ContinuousBatcher(params, cfg, slots=2, t_max=32, prefill_buckets=(8, 16), prefix_cache_entries=0)
    rng = np.random.default_rng(5)
    reqs = [cb.submit(rng.integers(0, MASK, n), max_new_tokens=new) for n, new in ((24, 8), (21, 11), (16, 16))]
    cb.pump()
    for r in reqs:
        want, at, _, tail = plain_generate(params, cfg, r.prompt_ids.tolist(), r.max_new_tokens)
        assert r.out_tokens == want and cb.fixed_at(r.request_id) == at and cb.block_tail(r.request_id) == tail
    assert cb.stats["late_rows"] == 3 and cb.stats["block_stores_fused"] == 1 + 2 + 3


def test_a_prompt_token_equal_to_the_mask_id_stays_fixed():
    """Fixedness is the flag: the mask id in the prompt's tail is a token of the
    first block like any other, is never chosen over, and is handed out to
    nobody; the answer is the plain loop's."""
    cfg, params = program()
    prompt = np.asarray([5, 9, 3, 4, 8, MASK, 11], np.int32)  # tail: 8, MASK, 11 and one masked position
    cb = ContinuousBatcher(params, cfg, slots=2, t_max=32, prefill_buckets=(8, 16), prefix_cache_entries=0)
    req = cb.submit(prompt, max_new_tokens=6)
    cb.step()  # the admit, and the first pass dispatched
    assert cb._blk_fixed[:, req.slot].tolist() == [1, 1, 1, 0] and not cb._blk_pending[req.slot]
    # the pass fixes the one masked position, so it leaves the block pending and the slot at an empty block 4 on:
    # what the next pass stores is the prompt's tail as it was given and the token this one fixed
    left = np.asarray(cb._prev)[:, req.slot].tolist()
    assert left[:10] == [8 + 4] + [0] * 8 + [1] and left[10:13] == [8, MASK, 11]
    cb.step()  # the first pass read: the mirror moved as the device's state did
    assert cb._blk_pending[req.slot] and cb._pos[req.slot] == 8 + 4 and not cb._blk_fixed[:, req.slot].any()
    assert req.out_tokens == left[13:] and req.fixed_at == [0]
    cb.pump()
    want, at, _, _ = plain_generate(params, cfg, prompt.tolist(), 6)
    assert req.out_tokens == want and req.fixed_at == at and at[0] == 0
    # the same prompt with another token there is another answer: the position was read as given
    other = prompt.copy()
    other[5] = 17
    cb2 = ContinuousBatcher(params, cfg, slots=2, t_max=32, prefill_buckets=(8, 16), prefix_cache_entries=0)
    assert cb2.submit(other, max_new_tokens=6) and cb2.pump()[0].out_tokens != want


def test_the_rule_for_what_a_pass_fixes():
    """`_choose_block` on logits made by hand, B = 4: confidences over the
    threshold are all fixed; where none passes, the most confident masked
    position alone, ties to the lower position; fixed positions and rows that
    are not live propose nothing; two positions a pass with 2 denoising steps."""
    cfg = TransformerConfig(**TINY)
    v = 16

    def logits_of(conf):  # a row of 16 logits whose softmax puts `conf` on token 3
        rest = np.log((1.0 - conf) / (v - 1))
        row = np.full(v, rest)
        row[3] = np.log(conf)
        return row

    conf = np.asarray([[0.95, 0.5, 0.93, 0.2], [0.3, 0.6, 0.6, 0.1], [0.99, 0.99, 0.99, 0.99], [0.3, 0.2, 0.25, 0.1]])
    logits = jnp.asarray(np.vectorize(logits_of, signature="()->(n)")(conf), jnp.float32)
    fixed = jnp.asarray([[0, 0, 0, 0], [0, 0, 0, 0], [0, 0, 0, 0], [1, 0, 0, 0]], bool)
    live = jnp.asarray([True, True, False, True])
    choose = lambda cfg, temps=jnp.zeros(4): continuous._choose_block(logits, fixed, live, temps, jax.random.key(0), cfg)
    tok, fix = choose(cfg)
    assert (np.asarray(tok) == 3).all()
    assert np.asarray(fix).tolist() == [[1, 0, 1, 0], [0, 1, 0, 0], [0, 0, 0, 0], [0, 0, 1, 0]]
    _, two = choose(dataclasses.replace(cfg, denoise_steps=2))
    assert np.asarray(two).tolist() == [[1, 0, 1, 0], [0, 1, 1, 0], [0, 0, 0, 0], [0, 1, 1, 0]]
    # a temperature samples without a sort; the confidence is the sampled token's own
    tok, fix = choose(cfg, jnp.asarray([0.0, 5.0, 0.0, 0.0]))
    assert (np.asarray(tok)[[0, 2, 3]] == 3).all() and np.asarray(fix).sum(axis=1).tolist() == [2, 1, 0, 1]
    with pytest.raises(ValueError, match="multiple of denoise_steps"):
        dataclasses.replace(cfg, denoise_steps=3)


def test_flash_prefill_under_the_block_mask_against_the_reference_attention():
    """The kernel in interpret mode: block 4 over 256 positions with left pads
    of whole blocks, against `reference_attention` with the same mask, and not
    the causal mask's result."""
    ks = jax.random.split(jax.random.key(1), 3)
    q, k, v = (jax.random.normal(kk, (2, 256, 2, 32), jnp.float32) for kk in ks)
    pad = jnp.asarray([0, 36], jnp.int32)
    got = attention_ops.flash_attention(q, k, v, pad=pad, block=4, block_q=128, block_k=128, interpret=True)
    want = attention_ops.reference_attention(q, k, v, pad=pad, block=4)
    real = np.arange(256)[None, :] >= np.asarray(pad)[:, None]
    err = np.abs(np.asarray(got) - np.asarray(want))[real]
    assert err.max() < 2e-5
    causal = attention_ops.reference_attention(q, k, v, pad=pad)
    assert np.abs(np.asarray(causal) - np.asarray(want))[real].max() > 1e-2
    # a query block has to end on a mask block's edge, and there is no backward
    with pytest.raises(ValueError, match="block mask"):
        attention_ops.flash_attention(q, k, v, block=3, block_q=128, block_k=128, interpret=True)
    with pytest.raises(ValueError, match="block mask"):
        attention_ops.flash_attention(q, k, v, block=4, return_lse=True, interpret=True)


def test_qk_norm_a_head_against_the_plain_formula():
    cfg, params = program()
    bp = jax.tree_util.tree_map(lambda w: w[0], params["blocks"])
    assert bp["q_norm"].shape == bp["k_norm"].shape == (cfg.d_head,)
    y = jax.random.normal(jax.random.key(2), (2, 5, cfg.d_model), jnp.float32)
    q, k, v = transformer._project_qkv(bp, y, cfg)

    def plain(w, norm, heads):
        x = np.asarray(y @ bp[w]).reshape(2, 5, heads, cfg.d_head)
        return x / np.sqrt((x * x).mean(-1, keepdims=True) + 1e-6) * np.asarray(bp[norm])

    assert np.max(np.abs(np.asarray(q) - plain("wq", "q_norm", cfg.n_heads))) < 1e-5
    assert np.max(np.abs(np.asarray(k) - plain("wk", "k_norm", cfg.n_kv_heads))) < 1e-5
    assert np.array_equal(np.asarray(v), np.asarray(y @ bp["wv"]).reshape(2, 5, cfg.n_kv_heads, cfg.d_head))
    # over the whole vector (OLMoE's) it is another function and other weights
    whole = dataclasses.replace(cfg, qk_norm_per_head=False)
    assert init_params(jax.random.key(0), whole)["blocks"]["q_norm"].shape == (2, cfg.n_heads * cfg.d_head)


def test_what_a_block_generating_replica_refuses():
    cfg, params = program()
    make = lambda **kw: ContinuousBatcher(params, cfg, slots=2, t_max=32, prefill_buckets=(8, 16),
                                          **{"prefix_cache_entries": 0, **kw})
    with pytest.raises(ValueError, match="keeps no prefix cache"):
        make(prefix_cache_entries=4)
    with pytest.raises(ValueError, match="top-k and top-p are not"):
        make(top_k=5)
    with pytest.raises(ValueError, match="whole number of blocks"):
        ContinuousBatcher(params, cfg, slots=2, t_max=30, prefix_cache_entries=0)
    cb = make()
    with pytest.raises(ValueError, match="top-k and top-p are not"):
        cb.submit([1, 2, 3], max_new_tokens=4, top_k=3)
    with pytest.raises(ValueError, match="top-k and top-p are not"):
        cb.submit([1, 2, 3], max_new_tokens=4, top_p=0.9)
    assert not cb.queue and cb.stats["submitted"] == 0
    # temperature alone is served, and the paths that yield one token a step say what does
    req = cb.submit([1, 2, 3, 4, 5], max_new_tokens=5, temperature=0.8, top_p=1.0)
    assert cb.pump() == [req] and len(req.out_tokens) == 5
    with pytest.raises(NotImplementedError, match="ContinuousBatcher"):
        generate.generate(params, jnp.zeros((1, 8), jnp.int32), jax.random.key(0), cfg=cfg, max_new_tokens=4)
    with pytest.raises(NotImplementedError, match="ContinuousBatcher"):
        next(generate.stream_generate(params, jnp.zeros((1, 8), jnp.int32), jax.random.key(0), cfg=cfg))


def test_the_step_and_the_admit_say_what_the_passes_did(monkeypatch):
    """`llm.step` carries block_rows, tokens_fixed, tokens_out, store_rows,
    fused_store_rows beside the expert path's moe_rows (positions) and moe_experts_touched; `llm.admit`
    carries block_tail and the experts' assignments of what it prefilled; the
    batcher's counts are their sums, and serve_llm ships them as counters."""
    cfg, params = program()
    cb = ContinuousBatcher(params, cfg, slots=3, t_max=32, prefill_buckets=(8, 16), prefix_cache_entries=0)
    seen = []
    real = continuous.tracing.span

    class Span(real):
        def set(self, **attrs):
            seen.append((self.name, attrs))
            super().set(**attrs)

    monkeypatch.setattr(continuous.tracing, "span", Span)
    reqs = [cb.submit(list(range(1, n + 1)), max_new_tokens=new) for n, new in ((6, 7), (9, 4))]
    cb.pump()
    admits = [a for name, a in seen if name == "llm.admit" and "block_tail" in a]
    assert [(a["block_tail"], a["bucket"]) for a in admits] == [(2, 8), (1, 8)]
    assert [a["moe_assignments"] for name, a in seen if name == "llm.admit" and "moe_assignments" in a] == [4 * 2, 8 * 2]
    assert not [name for name, _ in seen if name == "llm.admit.sample"]
    steps = [a for name, a in seen if name == "llm.step" and "block_rows" in a]
    assert all(s["block_rows"] == s["moe_rows"] and s["block_rows"] % 4 == 0 and 2.0 <= s["moe_experts_touched"] <= 8.0
               for s in steps)
    assert sum(s["tokens_out"] for s in steps) == 11 == cb.stats["tokens_out"]
    assert sum(s["tokens_fixed"] for s in steps) == cb.stats["block_tokens_fixed"] >= 11
    assert sum(s["block_rows"] for s in steps) == 4 * cb.stats["block_passes"]
    # the first request's first block (2 of the prompt, 2 masked) and its second are stored, each by
    # the first pass of the block after it; its third ends the answer; the second request's first
    # block is stored, its second ends it.  No pass only stores: the passes are those that fix a
    # position, 2 + 4 + 3 (the answer's last token is fixed third in its block) and 3 + 2, and the
    # one in flight when each answer ended; the steps are the longer request's (12 with two
    # storing passes)
    assert sum(s["fused_store_rows"] for s in steps) == 3 == cb.stats["block_stores_fused"]
    assert sum(s["store_rows"] for s in steps) == 0 and cb.stats["decode_steps"] == len(steps) == 9 + 1
    assert cb.stats["block_passes"] == (9 + 1) + (5 + 1) and cb.stats["late_rows"] == 2
    assert cb.stats["moe_assignments"] == (4 + 8) * 2 + cb.stats["block_passes"] * 4 * 2
    assert [len(r.out_tokens) for r in reqs] == [7, 4]
    from cluster_anywhere_tpu.llm import serve_llm
    import inspect

    shipped = inspect.getsource(serve_llm.ContinuousLLMServer._sync_engine_metrics)
    assert '"block_passes", "ca_serve_block_passes_total"' in shipped
    assert '"block_tokens_fixed", "ca_serve_block_tokens_fixed_total"' in shipped
