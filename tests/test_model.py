"""Flagship transformer tests: forward shapes, loss decreases under training,
parallel configs (tp/fsdp, sp ring, pp pipeline) agree with the single-device
model."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from cluster_anywhere_tpu.models import (
    TransformerConfig,
    forward,
    init_params,
    make_train_step,
    shard_params,
)
from cluster_anywhere_tpu.parallel import MeshSpec, make_mesh

TINY = dict(
    vocab_size=128,
    d_model=32,
    n_layers=4,
    n_heads=4,
    n_kv_heads=2,
    d_head=8,
    d_ff=64,
    max_seq_len=64,
    dtype=jnp.float32,
)


def _batch(key, b, t, vocab):
    return {"ids": jax.random.randint(key, (b, t + 1), 0, vocab)}


def test_forward_shapes():
    cfg = TransformerConfig(**TINY)
    params = init_params(jax.random.PRNGKey(0), cfg)
    ids = jnp.zeros((2, 16), dtype=jnp.int32)
    logits = forward(params, ids, cfg)
    assert logits.shape == (2, 16, cfg.vocab_size)
    assert np.isfinite(np.asarray(logits)).all()


def test_loss_decreases_single_device():
    cfg = TransformerConfig(**TINY)
    mesh = make_mesh(MeshSpec(dp=8))
    step, init_state = make_train_step(cfg, mesh, learning_rate=1e-2)
    params, opt_state = init_state(jax.random.PRNGKey(0))
    batch = _batch(jax.random.PRNGKey(1), 8, 16, cfg.vocab_size)
    jstep = jax.jit(step)
    losses = []
    for _ in range(8):
        params, opt_state, loss = jstep(params, opt_state, batch)
        losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.8, losses


def _logits_close(a, b, tol=2e-3):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=tol, atol=tol)


def test_tp_fsdp_matches_single():
    cfg = TransformerConfig(**TINY)
    params = init_params(jax.random.PRNGKey(0), cfg)
    ids = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, cfg.vocab_size)
    expect = forward(params, ids, cfg)

    mesh = make_mesh(MeshSpec(dp=2, fsdp=2, tp=2))
    sharded = shard_params(params, cfg, mesh)
    got = jax.jit(lambda p, i: forward(p, i, cfg, mesh))(sharded, ids)
    _logits_close(got, expect)


def test_sp_ring_matches_single():
    cfg = TransformerConfig(**TINY)
    params = init_params(jax.random.PRNGKey(0), cfg)
    ids = jax.random.randint(jax.random.PRNGKey(2), (2, 32), 0, cfg.vocab_size)
    expect = forward(params, ids, cfg)

    cfg_sp = TransformerConfig(**{**TINY, "sp": 4, "attn_impl": "ring"})
    mesh = make_mesh(MeshSpec(dp=2, sp=4))
    sharded = shard_params(params, cfg_sp, mesh)
    got = jax.jit(lambda p, i: forward(p, i, cfg_sp, mesh))(sharded, ids)
    _logits_close(got, expect)


def test_pp_matches_single():
    cfg = TransformerConfig(**TINY)
    params = init_params(jax.random.PRNGKey(0), cfg)
    ids = jax.random.randint(jax.random.PRNGKey(3), (4, 16), 0, cfg.vocab_size)
    expect = forward(params, ids, cfg)

    cfg_pp = TransformerConfig(**{**TINY, "pp": 2, "num_microbatches": 2})
    params_pp = init_params(jax.random.PRNGKey(0), cfg_pp)  # same key -> same weights
    mesh = make_mesh(MeshSpec(dp=2, pp=2, tp=2))
    sharded = shard_params(params_pp, cfg_pp, mesh)
    got = jax.jit(lambda p, i: forward(p, i, cfg_pp, mesh))(sharded, ids)
    _logits_close(got, expect)


def test_full_4d_train_step():
    """dp x pp x tp x sp all active in one train step."""
    cfg = TransformerConfig(
        **{**TINY, "pp": 2, "sp": 2, "num_microbatches": 2, "attn_impl": "ring"}
    )
    mesh = make_mesh(MeshSpec(dp=1, fsdp=1, pp=2, tp=2, sp=2))
    step, init_state = make_train_step(cfg, mesh, learning_rate=1e-2)
    params, opt_state = init_state(jax.random.PRNGKey(0))
    batch = _batch(jax.random.PRNGKey(4), 4, 32, cfg.vocab_size)
    jstep = jax.jit(step)
    l0 = None
    for _ in range(4):
        params, opt_state, loss = jstep(params, opt_state, batch)
        if l0 is None:
            l0 = float(loss)
    assert np.isfinite(float(loss))
    assert float(loss) < l0


def test_moe_transformer_trains_on_ep_mesh():
    """MoE flagship variant: every layer's FFN becomes n_experts switch
    experts sharded over 'ep' (parallel/moe.py all-to-all routing inside the
    shard_map manual region).  Loss decreases and the router receives
    gradients — i.e. the load-balance aux term and the expert path both
    differentiate through the token exchange."""
    import jax
    import jax.numpy as jnp

    from cluster_anywhere_tpu.models import TransformerConfig, make_train_step
    from cluster_anywhere_tpu.parallel import MeshSpec, make_mesh

    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_layers=2, n_heads=4, n_kv_heads=4,
        d_head=8, d_ff=64, max_seq_len=64, dtype=jnp.float32,
        n_experts=4, ep=2, attn_impl="dense",
    )
    mesh = make_mesh(MeshSpec(dp=4, ep=2))
    step, init_state = make_train_step(cfg, mesh)
    params, opt = init_state(jax.random.PRNGKey(0))
    batch = {
        "ids": jnp.asarray(
            np.random.default_rng(0).integers(0, 64, (8, 33)), jnp.int32
        )
    }
    jstep = jax.jit(step, donate_argnums=(0, 1))
    router_before = np.asarray(jax.device_get(params["blocks"]["router"]))
    losses = []
    for _ in range(8):
        params, opt, loss = jstep(params, opt, batch)
        losses.append(float(loss))
    assert losses[-1] < losses[0], losses
    router_after = np.asarray(jax.device_get(params["blocks"]["router"]))
    assert not np.allclose(router_before, router_after), "router got no gradient"


def test_nucleus_sampling_masks_tail():
    """top-p (nucleus) truncation: with p smaller than the top token's
    probability only the argmax can be sampled; p>=1 leaves the
    distribution untouched; the top token is always kept."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from cluster_anywhere_tpu.models.generate import _nucleus_mask, _sample

    logits = jnp.log(jnp.asarray([[0.5, 0.3, 0.15, 0.05]]))
    # p=0.4 < P(top): nucleus = {argmax} only
    masked = _nucleus_mask(logits, jnp.float32(0.4))
    assert np.asarray(masked[0, 0]) > -1e29
    assert (np.asarray(masked[0, 1:]) < -1e29).all()
    # p=0.85: keeps 0.5+0.3 (=0.8 exclusive-cum at third token is 0.8 < 0.85
    # -> third kept too); fourth excluded
    masked = _nucleus_mask(logits, jnp.float32(0.85))
    assert (np.asarray(masked[0, :3]) > -1e29).all()
    assert np.asarray(masked[0, 3]) < -1e29
    # p>=1: no-op
    masked = _nucleus_mask(logits, jnp.float32(1.0))
    assert (np.asarray(masked) > -1e29).all()
    # sampling respects the mask
    keys = jax.random.split(jax.random.key(0), 64)
    toks = [int(_sample(logits, k, jnp.float32(1.0), 0, jnp.float32(0.4))[0]) for k in keys[:16]]
    assert set(toks) == {0}


def test_rowwise_nucleus_sampling_per_request():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from cluster_anywhere_tpu.llm.continuous import _sample_rowwise

    logits = jnp.log(jnp.asarray([[0.5, 0.3, 0.15, 0.05]] * 2))
    rngs = jax.random.split(jax.random.key(1), 2)
    temps = jnp.asarray([1.0, 1.0])
    top_ks = jnp.asarray([0, 0])
    # row 0 nucleus-collapsed to argmax; row 1 unrestricted
    top_ps = jnp.asarray([0.4, 1.0])
    seen_row1 = set()
    for i in range(24):
        ks = jax.random.split(jax.random.key(100 + i), 2)
        out = np.asarray(_sample_rowwise(logits, ks, temps, top_ks, top_ps))
        assert out[0] == 0
        seen_row1.add(int(out[1]))
    assert len(seen_row1) > 1  # row 1 still samples the tail


def _two_sort_sample_rowwise(logits, rngs, temps, top_ks, top_ps):
    """`_sample_rowwise` as it stood before its work followed what the rows ask
    (PR 34), kept here letter for letter with its nucleus mask: both sorts of
    the vocabulary and a draw for every row, thrown away where `temps <= 0`."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    t = jnp.maximum(temps, 1e-6)[:, None]
    scaled = logits / t
    v = logits.shape[-1]
    sorted_desc = -jnp.sort(-scaled, axis=-1)
    kth_idx = jnp.clip(top_ks - 1, 0, v - 1)[:, None]
    kth = jnp.take_along_axis(sorted_desc, kth_idx, axis=-1)
    scaled = jnp.where((top_ks[:, None] > 0) & (scaled < kth), -1e30, scaled)
    top_p = top_ps[:, None]
    probs = jax.nn.softmax(scaled, axis=-1)
    sorted_p = -jnp.sort(-probs, axis=-1)
    cum_excl = jnp.cumsum(sorted_p, axis=-1) - sorted_p
    thresh = jnp.min(jnp.where(cum_excl < top_p, sorted_p, jnp.inf), axis=-1, keepdims=True)
    scaled = jnp.where((top_p > 0.0) & (top_p < 1.0) & (probs < thresh), -1e30, scaled)
    sampled = jax.vmap(lambda rng, row: jax.random.categorical(rng, row))(rngs, scaled).astype(jnp.int32)
    return jnp.where(temps <= 0.0, greedy, sampled)


# (temps, top_ks, top_ps) of six rows, and the branch of the sampler they ask for:
# 0 the largest logit alone, 1 a draw without a sort, 2 the two sorts
_ROW_KNOBS = {
    "all_greedy": ([0.0] * 6, [0] * 6, [1.0] * 6, 0),
    "greedy_with_stale_truncation": ([0.0] * 6, [5, 0, 3, 0, 0, 40], [0.9, 1.0, 0.5, 0.2, 1.0, 1.0], 0),
    "temperature_alone": ([0.7, 1.0, 1.3, 0.2, 2.0, 1e-3], [0] * 6, [1.0, 1.0, 0.0, 1.5, -1.0, 1.0], 1),
    "top_k": ([0.7, 1.0, 1.3, 0.2, 2.0, 1.0], [5, 1, 40, 200, 3, 64], [1.0] * 6, 2),
    "top_p": ([0.7, 1.0, 1.3, 0.2, 2.0, 1.0], [0] * 6, [0.9, 0.5, 0.1, 0.99, 0.7, 0.3], 2),
    "top_k_and_top_p": ([0.7, 1.0, 1.3, 0.2, 2.0, 1.0], [5, 0, 40, 0, 3, 64], [0.9, 0.5, 1.0, 1.0, 0.7, 0.3], 2),
    "greedy_and_sampled_mixed": ([0.0, 1.0, 0.0, 0.8, 0.0, 1.5], [0, 7, 0, 0, 0, 0], [1.0, 1.0, 1.0, 0.6, 1.0, 1.0], 2),
    "sampled_beside_a_stale_top_k": ([0.9, 0.0, 1.4, 0.0, 0.0, 0.0], [0, 12, 0, 0, 3, 0], [1.0, 1.0, 1.0, 0.4, 1.0, 1.0], 1),
}


@pytest.mark.parametrize("case", list(_ROW_KNOBS))
def test_rowwise_sampler_draws_the_two_sort_formulas_tokens(case):
    """The sampler does what its rows ask and no more (the largest logit
    alone; a draw without a sort; the two sorts only for a sampling row that
    truncates), and whichever it does, every row's token is the two-sort
    formula's for the same key and knobs, bit for bit, compiled as the decode
    step compiles it: a greedy row is an argmax in each, the masks are no-ops
    on a row that does not truncate, and rows are independent.  A stale top-k
    or top-p on a row with temperature 0 asks nothing."""
    from cluster_anywhere_tpu.llm.continuous import _sample_rowwise

    temps, top_ks, top_ps, branch = _ROW_KNOBS[case]
    knobs = (jnp.asarray(temps, jnp.float32), jnp.asarray(top_ks, jnp.int32), jnp.asarray(top_ps, jnp.float32))
    gated, frozen = jax.jit(_sample_rowwise), jax.jit(_two_sort_sample_rowwise)
    drawn = []
    for seed in range(8):
        k_logits, k_rows = jax.random.split(jax.random.key(seed))
        logits = 3.0 * jax.random.normal(k_logits, (6, 257), jnp.float32)
        logits = logits.at[:, 100].set(logits[:, 7])  # a tie, at the top of row 2
        logits = logits.at[2, 7].add(20.0).at[2, 100].add(20.0)
        rngs = jax.random.split(k_rows, 6)
        want = np.asarray(frozen(logits, rngs, *knobs))
        np.testing.assert_array_equal(np.asarray(gated(logits, rngs, *knobs)), want)
        drawn.append(want)
    # which branch runs is the switch's index in the traced program, and the sorts are the third's
    traced = jax.make_jaxpr(_sample_rowwise)(logits, rngs, *knobs)
    (switch,) = [e for e in traced.jaxpr.eqns if e.primitive.name == "cond"]
    assert ["sort" in str(b) for b in switch.params["branches"]] == [False, False, True]
    index = jax.core.eval_jaxpr(traced.jaxpr.replace(outvars=[switch.invars[0]]), traced.consts, logits, rngs, *knobs)
    assert int(index[0]) == branch
    greedy_rows = np.asarray(temps) <= 0
    if branch:  # the case does sample: its rows' draws move with the key
        assert len({tuple(d[~greedy_rows]) for d in drawn}) > 1
