"""A stack of half layers (models/transformer.py, models/generate.py,
parallel/moe.py, llm/continuous.py) at a test's widths on the CPU, weights from
a seed: Nemotron-H's three kinds of layer, each ONE half, a Mamba-2 mixer
(`mamba2`), a mixture of relu^2 experts with an ungated shared expert (`ffn`) or
attention without a positional embedding (`attn_alone`), in a pattern that puts
two mixers in a row and an FFN between two mixers.  The plain reference is the
benchmark's own (benchmarks/references/nemotronh.py), loaded as the harness
loads it: float32, the recurrence one position a step, a loop over the experts."""

import dataclasses
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import manifest
from cluster_anywhere_tpu.llm.continuous import ContinuousBatcher
from cluster_anywhere_tpu.models import generate, transformer
from cluster_anywhere_tpu.models.transformer import TransformerConfig, init_params
from cluster_anywhere_tpu.parallel.moe import EXPERT_MATRICES, init_moe_params

reference = manifest.load_reference("nemotronh")

PUBLISHED = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
PATTERN = "MEM*EMEM*E"  # the published pattern's start and end: two mixers in a row, an FFN between two mixers
KINDS = reference.KINDS
TINY = dict(vocab_size=97, d_model=32, n_layers=len(PATTERN), n_heads=4, n_kv_heads=2, d_head=8, d_ff=48,
            layer_mixers=tuple(KINDS[m] for m in PATTERN), rotary=False, norm_eps=1e-5,
            ssm_n_heads=4, ssm_head_dim=8, ssm_n_groups=2, ssm_d_state=16, ssm_chunk=8,
            n_experts=16, n_experts_per_tok=3, moe_scoring="sigmoid", moe_renormalize=True, moe_routed_scale=2.5,
            moe_act="relu2", n_shared_experts=1, d_expert=24, d_shared=40, experts_held=(0, 4))
T_MAX = 64


def _model(seed=1, dtype=jnp.float32, **over):
    cfg = TransformerConfig(**{**TINY, **over}, dtype=dtype, param_dtype=dtype)
    params = init_params(jax.random.key(seed), cfg)
    # the norms' weights and the mixer's per-head vectors off their initial 1, so one that is left out shows
    for stack, names in (("mamba2_blocks", ("ln1", "ssm_norm", "ssm_d")), ("alone_blocks", ("ln1",)), ("ffn_blocks", ("ln2",))):
        for name in names:
            w = params[stack][name]
            params[stack][name] = (w * jnp.linspace(0.6, 1.4, w.shape[-1])).astype(w.dtype)
    return cfg, params


@pytest.fixture(scope="module")
def model():
    return _model()


def test_a_layer_is_one_half_its_kinds_stacks_runs_and_cache_rows(model):
    """Each kind's weights are a stack of its own, a mixer alone holds no FFN and
    an FFN alone no mixer; the published pattern's 52 layers are 15 runs of the
    layer loop ([M, E] x n, [E, M] x n and single layers), not 52; the cache holds
    the Mamba-2 layers' windows over x, B and C together and their [heads,
    head_dim, N] states, and the attention layers' two cached heads flat; an FFN
    keeps no rows."""
    cfg, params = model
    assert cfg.layer_kinds == tuple(KINDS[m] for m in PATTERN) and cfg.half_layers and not cfg.carries
    assert (cfg.d_inner, cfg.conv_width, cfg.flat_heads) == (32, 32 + 2 * 2 * 16, 2)
    runs = transformer._layer_runs(tuple(KINDS[m] for m in PUBLISHED))
    assert runs == [
        (("mamba2", "ffn"), (0, 0), 2), ("mamba2", 2, 1), ("attn_alone", 0, 1),
        (("ffn", "mamba2"), (2, 3), 3), ("attn_alone", 1, 1), (("ffn", "mamba2"), (5, 6), 3), ("attn_alone", 2, 1),
        (("ffn", "mamba2"), (8, 9), 3), ("attn_alone", 3, 1), (("ffn", "mamba2"), (11, 12), 3), ("attn_alone", 4, 1),
        (("ffn", "mamba2"), (14, 15), 4), ("attn_alone", 5, 1), (("ffn", "mamba2"), (18, 19), 4), ("ffn", 22, 1)]
    assert sum(n * (1 if isinstance(kind, str) else len(kind)) for kind, _, n in runs) == 52
    assert transformer._layer_runs(cfg.layer_kinds) == [
        ("mamba2", 0, 1), ("ffn", 0, 1), ("mamba2", 1, 1), ("attn_alone", 0, 1), (("ffn", "mamba2"), (1, 2), 2),
        ("attn_alone", 1, 1), ("ffn", 3, 1)]
    assert {name: set(params[name]) for name in ("mamba2_blocks", "alone_blocks", "ffn_blocks")} == {
        "mamba2_blocks": {"ln1", "ssm_in", "conv_w", "conv_b", "dt_bias", "a_log", "ssm_d", "ssm_norm", "ssm_out"},
        "alone_blocks": {"ln1", "wq", "wk", "wv", "wo"},
        "ffn_blocks": {"ln2", "router", "w_in", "w_out", "shared_in", "shared_out"}}
    assert params["mamba2_blocks"]["ssm_in"].shape == (4, 32, 256)  # [z | x B C | dt] 32 + 96 + 4, stored in whole tiles of lanes
    assert not np.asarray(params["mamba2_blocks"]["ssm_in"][..., 132:]).any()
    assert params["ffn_blocks"]["w_in"].shape == (4, 4, 32, 24) and params["ffn_blocks"]["router"].shape == (4, 32, 16)
    assert params["ffn_blocks"]["shared_in"].shape == (4, 32, 40)
    assert generate._state_index(cfg) == {"mamba2": [0, 1, 2, 3], "attn_alone": [0, 1], "ffn": [0, 1, 2, 3]}
    cache = generate.init_cache(cfg, 3, T_MAX)
    assert {n: (a.shape, a.dtype) for n, a in cache.items()} == {
        "k": ((2, 3, T_MAX * 2, 8), jnp.float32), "v": ((2, 3, T_MAX * 2, 8), jnp.float32),
        "conv": ((4, 3, 3, 96), jnp.float32), "h": ((4, 3, 4, 8, 16), jnp.float32)}
    assert generate.recurrent_state_bytes(cache) == 4 * 3 * (3 * 96 + 4 * 8 * 16) * 4
    assert generate.cache_bytes_per_token(cache, cfg) == 2 * 2 * 2 * 8 * 4


@pytest.mark.parametrize("dtype, tol", [(jnp.float32, 3e-5), (jnp.bfloat16, 0.25)], ids=["float32", "bfloat16"])
def test_training_forward_and_prefill_then_decode_through_the_cache_are_the_references(dtype, tol):
    """The program's forward over whole sequences, and a left-padded prefill then
    21 tokens one at a time through the state rows and the flat stacks (the
    prompts three chunks of the scan), give the reference's full forward's logits."""
    cfg, params = _model(dtype=dtype)
    ids = np.asarray(jax.random.randint(jax.random.key(2), (2, 45), 0, cfg.vocab_size))
    want = np.stack([np.asarray(reference.forward(params, row, cfg)) for row in ids])

    def close(got, want):
        """float32: every logit.  bf16: all but the rows a router's tie sent to other experts (a twentieth)."""
        far = np.abs(np.asarray(got, np.float32) - want) > tol
        return not far.any() if dtype == jnp.float32 else far.mean() < 0.05

    assert close(jax.jit(lambda p, i: transformer.forward(p, i, cfg))(params, jnp.asarray(ids)), want)
    step = jax.jit(lambda p, cache, token, pos, pads: generate.decode_one(p, cache, token, pos, cfg, pad=pads))
    pads = np.asarray([3, 7])  # row b's prompt is its first 24 - pad tokens, behind its own pad
    padded = np.zeros((2, 24), np.int32)
    for b, pad in enumerate(pads):
        padded[b, pad:] = ids[b, :24 - pad]
    logits, cache = generate.prefill(params, jnp.asarray(padded), cfg, T_MAX, pad=jnp.asarray(pads))
    rows = []
    for i in range(21):  # both rows decode on from slot 24, each at its own depth
        rows += [close(logits[b], want[b, 24 - pad - 1 + i]) for b, pad in enumerate(pads)]
        token = jnp.asarray([ids[b, 24 - pad + i] for b, pad in enumerate(pads)])
        logits, cache = step(params, cache, token, jnp.int32(24 + i), jnp.asarray(pads))
    # float32: every row.  bf16: a tie that sends one token to another expert than the float32 reference's stays in
    # the state for the tokens after it (seeds 1-6 of this model: 1 to 7 of the 42 rows, whichever path the
    # experts take); a cache that is read or written wrongly puts every row after the first off
    assert all(rows) if dtype == jnp.float32 else sum(rows) >= 0.8 * len(rows), rows


@pytest.mark.parametrize("pad", [0, 3])
@pytest.mark.parametrize("t", [1, 5, 8, 16, 21, 128, 131])
def test_the_chunked_prefill_is_the_recurrence_token_by_token(t, pad):
    """`_mamba2_mixer` over t positions in chunks of 8 (and of the published 128),
    at lengths that are and are not whole chunks, behind left pads: its result
    and the state it leaves are the reference's recurrence one position a step,
    and the program's own stepped one token at a time."""
    chunk = 128 if t >= 128 else 8
    cfg, params = _model(ssm_chunk=chunk)
    bp = jax.tree_util.tree_map(lambda w: w[1], params["mamba2_blocks"])
    x = jnp.asarray(np.random.default_rng(t).normal(size=(1, t, cfg.d_model)), jnp.float32)
    keep = (jnp.arange(pad + t) >= pad)[None]
    zero = transformer._mamba2_zero_state(cfg, 1)
    out, (window, h) = transformer._mamba2_mixer(bp, jnp.pad(x, ((0, 0), (pad, 0), (0, 0))), cfg, zero, keep if pad else None)
    dims = reference._dims(cfg)
    with jax.default_matmul_precision("highest"):
        u = reference._rms_norm(x[0], bp["ln1"], dims["eps"])
        want, (xbc, want_h, _) = reference._mamba2(u, bp, dims["ssm"], dims["eps"])
    np.testing.assert_allclose(out[0, pad:], want, atol=2e-5)
    np.testing.assert_allclose(h[0], want_h, atol=2e-5)
    np.testing.assert_allclose(window[0], jnp.pad(xbc, ((3, 0), (0, 0)))[-3:], atol=2e-5)
    state, outs = zero, []
    for i in range(t):
        o, state = transformer._mamba2_mixer(bp, x[:, i:i + 1], cfg, state)
        outs.append(o[0, 0])
    np.testing.assert_allclose(jnp.stack(outs), want, atol=2e-5)
    np.testing.assert_allclose(state[1][0], want_h, atol=2e-5)


def test_the_recurrence_the_gated_norm_and_the_groups_are_seen_to_matter(model):
    """What the comparison would miss if it could: the mixer without its state
    (h = 0 at every step), with one group's B and C for every head, or with the
    norm before the gate is far from the reference."""
    cfg, params = model
    bp = jax.tree_util.tree_map(lambda w: w[0], params["mamba2_blocks"])
    x = jnp.asarray(np.random.default_rng(0).normal(size=(1, 24, cfg.d_model)), jnp.float32)
    dims = reference._dims(cfg)
    with jax.default_matmul_precision("highest"):
        want = reference._mamba2(reference._rms_norm(x[0], bp["ln1"], dims["eps"]), bp, dims["ssm"], dims["eps"])[0]
    zero = transformer._mamba2_zero_state(cfg, 1)
    far = lambda got: float(jnp.max(jnp.abs(got - want))) > 0.05
    stateless = jnp.concatenate([transformer._mamba2_mixer(bp, x[:, i:i + 1], cfg, zero)[0] for i in range(24)], axis=1)[0]
    assert far(stateless)
    one_group = dataclasses.replace(cfg, ssm_n_groups=1)
    bp1 = {**bp, "ssm_in": bp["ssm_in"][:, :32 + 32 + 32 + 4], "conv_w": bp["conv_w"][:, :64], "conv_b": bp["conv_b"][:64]}
    assert far(transformer._mamba2_mixer(bp1, x, one_group, transformer._mamba2_zero_state(one_group, 1))[0][0])
    assert not far(transformer._mamba2_mixer(bp, x, cfg, zero)[0][0])


def test_a_state_handed_on_in_bfloat16_is_told_from_float32(model):
    """The check's own measure (references/nemotronh.py `ssm_state_step_err`):
    the state stepped token by token against one call over the whole sequence
    agrees to float32's rounding, and a state rounded to bf16 between two tokens
    does not."""
    cfg, params = model
    bp = jax.tree_util.tree_map(lambda w: w[2], params["mamba2_blocks"])
    x = jnp.asarray(np.random.default_rng(5).normal(size=(1, 40, cfg.d_model)), jnp.float32)
    whole = transformer._mamba2_mixer(bp, x, cfg, transformer._mamba2_zero_state(cfg, 1))[1][1]

    def stepped(round_state):
        state = transformer._mamba2_zero_state(cfg, 1)
        for i in range(40):
            _, (window, h) = transformer._mamba2_mixer(bp, x[:, i:i + 1], cfg, state)
            state = (window, h.astype(jnp.bfloat16).astype(jnp.float32) if round_state else h)
        return state[1]

    rel = lambda h: float(jnp.linalg.norm(h - whole) / jnp.linalg.norm(whole))
    assert rel(stepped(False)) < 1e-5 < 1e-3 < rel(stepped(True))


@pytest.mark.parametrize("t", [17, 256], ids=["34-rows", "a-prefills-512-rows"])
def test_the_eight_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer(t):
    """What ties the share to the model (model-configs guide, section 4): the
    routed parts that the 8 shares give, each from the program's own expert
    layer told which 2 of 16 experts it holds, with the ungated shared expert
    counted once, are the uncut reference layer's mixture; and the program's
    FFN half over the uncut layer is the reference's layer."""
    cfg = _model()[0]
    whole = dataclasses.replace(cfg, experts_held=None)
    bp = jax.tree_util.tree_map(lambda w: w[0], init_params(jax.random.key(9), whole)["ffn_blocks"])
    bp["ln2"] = bp["ln2"] * jnp.linspace(0.7, 1.3, 32)
    assert bp["w_in"].shape == (16, 32, 24) and bp["router"].shape == (32, 16) and "shared_gate" not in bp
    x = jnp.asarray(np.random.default_rng(2).normal(size=(2, t, 32)), jnp.float32)
    moe = (3, True, 2.5, 0)
    with jax.default_matmul_precision("highest"):
        y = reference._rms_norm(x, bp["ln2"], cfg.norm_eps)
        routed, weight = reference._routed(y.reshape(-1, 32), bp, *moe)
        mixture = reference._mixture(y.reshape(-1, 32), bp, moe).reshape(x.shape)
    assert np.all(np.sum(np.asarray(weight) > 0, axis=-1) == 3)
    np.testing.assert_allclose(np.sum(np.asarray(weight), axis=-1), 2.5, rtol=1e-5)  # renormalised, scaled
    total, assignments = None, 0
    for share in range(8):
        held = dataclasses.replace(cfg, experts_held=(2 * share, 2))
        mine = {k: (v[2 * share:2 * share + 2] if k in EXPERT_MATRICES else v) for k, v in bp.items()}
        if share == 0:  # the whole FFN once: this share's part and the shared expert
            part, _, counts = transformer._ffn(mine, y, held)
        else:  # the other shares' routed parts alone
            part, _, counts = transformer._moe(mine, y, held)
        total = part if total is None else total + part
        assignments += int(counts[1])
    np.testing.assert_allclose(total, mixture, atol=3e-5)
    assert assignments == 2 * t * 3  # every (token, expert) pair fell on exactly one share
    np.testing.assert_allclose(transformer._ffn_half(bp, x, whole)[0], x + mixture, atol=3e-5)
    # the reference's whole layer is that too
    got, made = reference._layer(x[0], bp, kind="ffn", **reference._dims(whole))
    np.testing.assert_allclose(got, (x + mixture)[0], atol=3e-5)
    np.testing.assert_allclose(made, y[0], atol=1e-6)


@pytest.mark.parametrize("act, gated", [("relu2", False), ("silu", False), ("relu2", True)])
def test_an_experts_activation_is_the_configurations(act, gated):
    """`routed_ffn` through relu(x)^2 and silu, ungated and gated, against the
    sum over each token's experts written out."""
    cfg = TransformerConfig(**{**TINY, "moe_act": act, "moe_gated": gated, "experts_held": None, "n_layers": 1,
                               "layer_mixers": ("ffn",)}, dtype=jnp.float32, param_dtype=jnp.float32)
    bp = jax.tree_util.tree_map(lambda w: w[0], init_params(jax.random.key(3), cfg)["ffn_blocks"])
    y = jnp.asarray(np.random.default_rng(1).normal(size=(1, 9, 32)), jnp.float32)
    f = {"relu2": lambda v: jnp.square(jnp.maximum(v, 0)), "silu": jax.nn.silu}[act]
    scores = jax.nn.sigmoid(y[0] @ bp["router"])
    top, idx = jax.lax.top_k(scores, 3)
    top = 2.5 * top / jnp.sum(top, axis=-1, keepdims=True)
    if gated:
        expert = lambda v, e: (f(v @ bp["w_gate"][e]) * (v @ bp["w_up"][e])) @ bp["w_down"][e]
        shared = (jax.nn.silu(y[0] @ bp["shared_gate"]) * (y[0] @ bp["shared_up"])) @ bp["shared_down"]
    else:
        expert = lambda v, e: f(v @ bp["w_in"][e]) @ bp["w_out"][e]
        shared = f(y[0] @ bp["shared_in"]) @ bp["shared_out"]
    want = jnp.stack([sum(top[i, j] * expert(y[0, i], idx[i, j]) for j in range(3)) for i in range(9)]) + shared
    np.testing.assert_allclose(transformer._ffn(bp, y, cfg)[0][0], want, atol=2e-5)


def test_an_expert_width_of_broken_lane_tiles_is_stored_in_whole_tiles():
    """An ungated expert 200 wide (over one tile of 128 lanes, no multiple of it)
    is made with its first matrix 256 wide, the columns past 200 zeros, and is 200
    wide all the same: its result is the narrow matrices' own.  A width of whole
    tiles, or under one, is stored as it is."""
    made = init_moe_params(jax.random.key(0), 32, 200, 4)
    assert made["w_in"].shape == (4, 32, 256) and made["w_out"].shape == (4, 200, 32)
    assert not np.any(np.asarray(made["w_in"][:, :, 200:])) and np.all(np.any(np.asarray(made["w_in"][:, :, :200]), axis=1))
    assert init_moe_params(jax.random.key(0), 32, 256, 4)["w_in"].shape == (4, 32, 256)
    assert init_moe_params(jax.random.key(0), 32, 24, 4)["w_in"].shape == (4, 32, 24)
    from cluster_anywhere_tpu.parallel.moe import routed_ffn
    x = jnp.asarray(np.random.default_rng(0).normal(size=(6, 32)), jnp.float32)
    through = lambda w_in: routed_ffn(x, made["router"], {"w_in": w_in[None], "w_out": made["w_out"][None]}, k=2,
                                      act="relu2", scoring="sigmoid").out
    wide = through(made["w_in"])
    np.testing.assert_allclose(wide, through(made["w_in"][:, :, :200]), atol=1e-6)
    # and the reference reads the expert at its own width
    with jax.default_matmul_precision("highest"):
        want, _ = reference._routed(x, made, 2, False, 1.0, 0)
    np.testing.assert_allclose(wide, want, atol=2e-5)


def _chosen(params, cfg, prompt, served):
    full = np.asarray(list(prompt) + list(served[:-1]), np.int32)
    return np.asarray(reference.forward(params, full, cfg))[len(prompt) - 1:]


@pytest.mark.parametrize("prefix_cache_entries", [0, 4], ids=["plain", "prefix-cache"])
def test_the_batcher_serves_staggered_admits_beside_a_dead_slot(model, prefix_cache_entries):
    """Through `ContinuousBatcher`: requests admitted at different steps, one slot
    never used, one reused; every served token is the reference's own choice from
    the logits of the full forward.  With a prefix cache the rows of a cached
    prefix (the flat stacks, the windows and h after the prefix's last token: a
    pytree) come back and the suffix is stepped token by token: the same tokens."""
    cfg, params = model
    rng = np.random.default_rng(7)
    shared = rng.integers(0, cfg.vocab_size, 32)
    prompts = [np.concatenate([shared, rng.integers(0, cfg.vocab_size, k)]) for k in (3, 9)] + [
        rng.integers(0, cfg.vocab_size, 6), rng.integers(0, cfg.vocab_size, 19)]
    cb = ContinuousBatcher(params, cfg, slots=4, t_max=T_MAX, prefill_buckets=(8, 32),
                           prefix_cache_entries=prefix_cache_entries, prefix_block=16)
    reqs = [cb.submit(prompts[0], max_new_tokens=14)]
    cb.step(), cb.step()
    reqs.append(cb.submit(prompts[1], max_new_tokens=9))
    reqs.append(cb.submit(prompts[2], max_new_tokens=18))
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
        assert entry["k"].shape == (2, 1, T_MAX * 2, 8) and entry["h"].shape == (4, 1, 4, 8, 16) and entry["conv"].shape == (4, 1, 3, 96)
    slot_bytes = 4 * (3 * 96 + 4 * 8 * 16) * 4
    assert stats["ssm_state_bytes"] > 0 and stats["ssm_state_bytes"] % slot_bytes == 0
    assert stats["cache_bytes_per_token"] == 2 * 2 * 2 * 8 * 4 and stats["moe_assignments"] > 0
    # the steps' held experts given a row, summed: at most the 4 held a step
    assert 0 < stats["moe_experts_touched"] <= 4 * stats["decode_steps"]


def test_a_slots_state_is_installed_preempted_and_resumed(model):
    """A request is cancelled in mid-answer (its lane's state keeps moving until
    an admit overwrites it), another takes its slot, and the first comes back as
    its prompt and the tokens it was given: it goes on with the tokens the
    uninterrupted answer has."""
    cfg, params = model
    rng = np.random.default_rng(11)
    prompt, other = rng.integers(0, cfg.vocab_size, 21), rng.integers(0, cfg.vocab_size, 9)
    alone = ContinuousBatcher(params, cfg, slots=2, t_max=T_MAX, prefill_buckets=(8, 32))
    whole = alone.submit(prompt, max_new_tokens=16)
    alone.pump()
    cb = ContinuousBatcher(params, cfg, slots=2, t_max=T_MAX, prefill_buckets=(8, 32))
    first = cb.submit(prompt, max_new_tokens=16)
    beside = cb.submit(other, max_new_tokens=30)  # keeps the batcher stepping beside the freed lane
    while len(first.out_tokens) < 6:
        cb.step()
    given = list(first.out_tokens)
    assert cb.cancel(first.request_id) and cb._by_slot[first.slot] is None
    h = np.asarray(cb.cache["h"][:, first.slot])
    cb.step(), cb.step()
    assert not np.array_equal(np.asarray(cb.cache["h"][:, first.slot]), h)  # not frozen
    resumed = cb.submit(np.concatenate([prompt, given]), max_new_tokens=16 - len(given))
    cb.pump()
    assert resumed.slot == first.slot and beside.done
    want = _chosen(params, cfg, prompt, list(whole.out_tokens))
    served = given + list(resumed.out_tokens)
    regret = want.max(-1) - want[np.arange(16), served]
    assert float(regret.max()) < 1e-4 and served == list(whole.out_tokens)


def test_an_admits_span_says_the_prefills_chunks_and_the_held_layers(model):
    cfg, params = model
    seen = []
    cb = ContinuousBatcher(params, cfg, slots=2, t_max=T_MAX, prefill_buckets=(8, 32))
    sp = types.SimpleNamespace(set=lambda **kw: seen.append(kw))  # the admit's span, as `_prefill_padded` uses it
    cb._prefill_padded(np.arange(19, dtype=np.int32), 32, sp)
    said = {k: v for kw in seen for k, v in kw.items()}
    assert said["ssm_chunks"] == 4 and said["prefill_positions"] == 32


def test_configurations_that_are_not_built_are_refused_by_name():
    with pytest.raises(NotImplementedError, match="half layers"):
        TransformerConfig(**{**TINY, "layer_mixers": ("mamba2", "attn") + TINY["layer_mixers"][2:]})
    with pytest.raises(ValueError, match="mamba2 layer takes"):
        TransformerConfig(**{**TINY, "ssm_n_groups": 3})
    with pytest.raises(NotImplementedError, match="run on one device only"):
        transformer.param_specs(TransformerConfig(**TINY))


@pytest.mark.parametrize("act", ["silu", "relu2"])
def test_experts_over_a_mesh_take_the_activation_and_a_first_matrix_stored_in_whole_tiles(act):
    """The 'ep' path (parallel/moe.py moe_ffn) computes the configuration's
    activation, and an ungated expert 200 wide, whose first matrix is stored 256
    wide, multiplies its 200 columns by the second matrix's 200 rows: with a
    capacity that drops nothing the mesh's logits are one device's."""
    from cluster_anywhere_tpu.parallel import MeshSpec, make_mesh

    cfg = TransformerConfig(vocab_size=64, d_model=32, n_layers=2, n_heads=4, n_kv_heads=4, d_head=8, d_ff=200,
                            n_experts=4, moe_act=act, capacity_factor=4.0, attn_impl="dense",
                            dtype=jnp.float32, param_dtype=jnp.float32)
    params = init_params(jax.random.key(3), cfg)
    assert params["blocks"]["w_in"].shape[-1] == 256 and params["blocks"]["w_out"].shape[-2] == 200
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 64, (8, 12)), jnp.int32)
    alone = transformer.forward(params, ids, cfg)
    mesh = make_mesh(MeshSpec(dp=4, ep=2))
    on_mesh = jax.jit(lambda p, i: transformer.forward(p, i, dataclasses.replace(cfg, ep=2), mesh))(params, ids)
    assert np.abs(np.asarray(on_mesh) - np.asarray(alone)).max() < 1e-4 * np.abs(np.asarray(alone)).max()
    other = dataclasses.replace(cfg, moe_act="silu" if act == "relu2" else "relu2")
    assert np.abs(np.asarray(transformer.forward(params, ids, other)) - np.asarray(alone)).max() > 1e-2


def test_a_sequence_of_runs_that_repeats_is_one_loop_of_loops(monkeypatch):
    """`_run_groups`: the published pattern's fifteen runs are five loops that
    hold ten layer bodies; and a stack whose (*, [E, M] x 2) comes twice, run as
    one loop of that sequence, gives the logits, the cache's rows and the
    counts that its runs give one after the other, and the reference's logits."""
    groups = transformer._run_groups(transformer._layer_runs(tuple(KINDS[m] for m in PUBLISHED)))
    assert groups == [
        (((("mamba2", "ffn"), (0, 0), 2),), 1), ((("mamba2", 2, 1),), 1),
        ((("attn_alone", 0, 1), (("ffn", "mamba2"), (2, 3), 3)), 4),
        ((("attn_alone", 4, 1), (("ffn", "mamba2"), (14, 15), 4)), 2), ((("ffn", 22, 1),), 1)]
    bodies = lambda runs: sum(1 if isinstance(kind, str) else len(kind) for kind, _, _ in runs)
    assert sum(bodies(runs) for runs, _ in groups) == 10 and sum(bodies(runs) * reps for runs, reps in groups) == 22
    pattern = "M*EMEM*EMEM*E"
    cfg, params = _model(layer_mixers=tuple(KINDS[m] for m in pattern), n_layers=len(pattern))
    assert transformer._run_groups(transformer._layer_runs(cfg.layer_kinds)) == [
        ((("mamba2", 0, 1),), 1), ((("attn_alone", 0, 1), (("ffn", "mamba2"), (0, 1), 2)), 2),
        ((("attn_alone", 2, 1),), 1), ((("ffn", 4, 1),), 1)]
    ids = np.asarray(jax.random.randint(jax.random.key(9), (2, 24), 0, cfg.vocab_size))
    pads = jnp.asarray([0, 5])

    def served():
        logits, cache, held = jax.jit(lambda p, i: generate.prefill_counted.__wrapped__(p, i, cfg, T_MAX, pads))(params, jnp.asarray(ids))
        out = [logits, held]
        for i in range(3):
            logits, cache, touched = jax.jit(lambda p, c, t: generate.decode_rows(p, c, t, jnp.full((2,), 24 + i), pads, cfg))(
                params, cache, jnp.argmax(logits, axis=-1))
            out += [logits, touched]
        return out + [cache], jax.jit(lambda p, i: transformer.forward(p, i, cfg))(params, jnp.asarray(ids))

    grouped, forward = served()
    monkeypatch.setattr(transformer, "_run_groups", lambda runs: [((run,), 1) for run in runs])
    one_by_one, forward_one = served()
    for a, b in zip(jax.tree_util.tree_leaves(grouped), jax.tree_util.tree_leaves(one_by_one)):
        assert a.shape == b.shape and np.abs(np.asarray(a, np.float32) - np.asarray(b, np.float32)).max() < 1e-5
    assert np.abs(np.asarray(forward) - np.asarray(forward_one)).max() < 1e-5
    want = np.stack([np.asarray(reference.forward(params, row, cfg)) for row in ids])
    assert np.abs(np.asarray(forward) - want).max() < 3e-5
