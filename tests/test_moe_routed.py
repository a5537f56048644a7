"""The dropless routed expert path (parallel/moe.py routed_ffn) against a
per-token loop in float32, a held share's compact buffer against all N x k
rows, and the places the program calls it from: the one-device forward and
loss, prefill, and the decode step with its live mask."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import manifest
from cluster_anywhere_tpu.llm import continuous
from cluster_anywhere_tpu.models import generate, transformer
from cluster_anywhere_tpu.models.transformer import TransformerConfig, init_params
from cluster_anywhere_tpu.parallel import moe
from cluster_anywhere_tpu.parallel.moe import EXPERT_MATRICES, init_moe_params, routed_ffn

E, F, X = 16, 24, 8


def routed(x, bp, **kw):
    """`routed_ffn` over one layer's weights, handed over as a stack of one."""
    return routed_ffn(x, bp["router"], {k: bp[k][None] for k in EXPERT_MATRICES if k in bp}, **kw)


def layer(gated, seed=0):
    return init_moe_params(jax.random.key(seed), E, F, X, jnp.float32, gated=gated)


def tokens(n, seed=1):
    return jax.random.normal(jax.random.key(seed), (n, E), jnp.float32)


def expert(bp, e, x, gated):
    if gated:
        return (jax.nn.silu(x @ bp["w_gate"][e]) * (x @ bp["w_up"][e])) @ bp["w_down"][e]
    return jax.nn.silu(x @ bp["w_in"][e]) @ bp["w_out"][e]


def loop(x, bp, k, gated, renormalize, live=None):
    """One token at a time, one expert at a time."""
    probs = jax.nn.softmax(x @ bp["router"], axis=-1)
    rows = []
    for n in range(x.shape[0]):
        if live is not None and not bool(live[n]):
            rows.append(jnp.zeros_like(x[n]))
            continue
        best = jnp.argsort(-probs[n])[:k]
        weights = probs[n][best]
        if renormalize:
            weights = weights / jnp.sum(weights)
        rows.append(sum(weights[j] * expert(bp, best[j], x[n], gated) for j in range(k)))
    return jnp.stack(rows)


def all_experts_top1(x, bp):
    """What `_moe_infer` gave: every expert for every token, masked by the
    top-1 route and scaled by its probability."""
    probs = jax.nn.softmax(x @ bp["router"], axis=-1)
    idx = jnp.argmax(probs, axis=-1)
    every = jnp.einsum("nxf,xfe->nxe", jax.nn.silu(jnp.einsum("ne,xef->nxf", x, bp["w_in"])), bp["w_out"])
    pick = jax.nn.one_hot(idx, X) * jnp.max(probs, axis=-1, keepdims=True)
    return jnp.einsum("nxe,nx->ne", every, pick)


@pytest.mark.parametrize("k, gated, renormalize", [
    (1, False, False), (2, False, True), (8, True, False), (8, True, True), (3, True, False),
], ids=["top1-ungated", "top2-ungated-renorm", "top8-gated", "top8-gated-renorm", "top3-gated"])
def test_routed_equals_the_per_token_loop(k, gated, renormalize):
    bp, x = layer(gated), tokens(13)
    with jax.default_matmul_precision("highest"):
        got = routed(x, bp, k=k, renormalize=renormalize)
        want = loop(x, bp, k, gated, renormalize)
        assert np.max(np.abs(np.asarray(got.out - want))) < 1e-5
        if k == 1 and not gated:
            assert np.max(np.abs(np.asarray(got.out - all_experts_top1(x, bp)))) < 1e-5
    assert 1 <= int(got.experts_touched) <= min(X, 13 * k)
    if k == X:
        assert int(got.experts_touched) == X
    # jitted and eager are one computation
    jitted = jax.jit(lambda x, bp: routed(x, bp, k=k, renormalize=renormalize).out)
    assert np.max(np.abs(np.asarray(jitted(x, bp) - got.out))) < 1e-5


@pytest.mark.parametrize("k", [1, 2])
def test_every_token_on_one_expert_drops_nothing(k):
    """A capacity would drop all but a few of these rows; here each of the 40
    tokens gets its experts' whole result."""
    bp, x = layer(True), tokens(40)
    bp["router"] = jnp.zeros((E, X), jnp.float32)
    # a bias through a constant feature: every token prefers expert 5, then 2
    x = x.at[:, 0].set(1.0)
    bp["router"] = bp["router"].at[0, 5].set(60.0).at[0, 2].set(30.0)
    with jax.default_matmul_precision("highest"):
        got = routed(x, bp, k=k)
        want = loop(x, bp, k, True, False)
    assert int(got.experts_touched) == k
    assert np.max(np.abs(np.asarray(got.out - want))) < 1e-5
    only5 = expert(bp, 5, x, True)
    assert np.all(np.abs(np.asarray(want)).sum(axis=1) > 0)  # no row came back empty
    if k == 1:
        assert np.max(np.abs(np.asarray(got.out - only5))) < 1e-4


@pytest.mark.parametrize("gated", [False, True], ids=["ungated", "gated"])
def test_rows_that_are_not_live_take_no_expert(gated):
    bp, x = layer(gated), tokens(12)
    live = jnp.asarray([1, 0, 0, 1, 1, 0, 0, 0, 1, 0, 0, 0], bool)
    with jax.default_matmul_precision("highest"):
        every = routed(x, bp, k=2)
        some = routed(x, bp, k=2, live=live)
        # whatever the other rows hold, they reach no expert and no live row
        junk = jnp.where(live[:, None], x, 1e4)
        same = routed(junk, bp, k=2, live=live)
    alive = np.asarray(live)
    assert np.array_equal(np.asarray(some.out)[alive], np.asarray(every.out)[alive])
    assert np.array_equal(np.asarray(same.out), np.asarray(some.out))
    assert not np.asarray(some.out)[~alive].any()
    # four live rows x 2 experts: at most 8 experts were read, and exactly those the rows chose
    probs = jax.nn.softmax(x @ bp["router"], axis=-1)
    chosen = {int(e) for n in np.flatnonzero(alive) for e in np.argsort(-np.asarray(probs[n]))[:2]}
    assert int(some.experts_touched) == len(chosen) <= 8
    # no live row at all: nothing is read, nothing comes back
    none = routed(x, bp, k=2, live=jnp.zeros(12, bool))
    assert int(none.experts_touched) == 0 and not np.asarray(none.out).any()


@pytest.mark.parametrize("gated", [False, True], ids=["ungated", "gated"])
def test_a_layer_of_the_stack_is_that_layer_alone(gated):
    """Inside a scan the experts come as every layer's with the layer's index:
    the other layers' groups are empty, and the result is the layer's own."""
    layers = [layer(gated, seed) for seed in (0, 1, 2)]
    stack = {k: jnp.stack([bp[k] for bp in layers]) for k in layers[0] if k != "router"}
    x, live = tokens(9), jnp.arange(9) != 4
    with jax.default_matmul_precision("highest"):
        alone = [routed(x, bp, k=2, live=live) for bp in layers]
        _, scanned = jax.lax.scan(
            lambda _, inputs: (None, routed_ffn(x, inputs[0], stack, inputs[1], k=2, live=live)),
            None, (jnp.stack([bp["router"] for bp in layers]), jnp.arange(3)))
    for i, want in enumerate(alone):
        got = routed_ffn(x, layers[i]["router"], stack, i, k=2, live=live)
        assert np.array_equal(np.asarray(got.out), np.asarray(want.out))
        assert np.max(np.abs(np.asarray(scanned.out[i] - want.out))) < 1e-6
        assert int(got.experts_touched) == int(scanned.experts_touched[i]) == int(want.experts_touched)


# -- a held share: the compact buffer and all N x k rows ------------------------

X32, PREFILL = 32, 512  # 16 shares of 2 experts; a prefill's rows


def routed_share(x, bp, share, **kw):
    """`routed_ffn` told that it holds the share's 2 of the layer's 32 experts, a stack of one."""
    mine = {k: bp[k][None, 2 * share:2 * share + 2] for k in EXPERT_MATRICES if k in bp}
    return routed_ffn(x, bp["router"], mine, held=(2 * share, 2), **kw)


def every_row(monkeypatch):
    """`routed_ffn` as it was before the compact buffer: none is under half the rows."""
    monkeypatch.setattr(moe, "COMPACT_SHARE", 10 ** 6)


@pytest.mark.parametrize("with_live", [False, True], ids=["all-live", "padded"])
@pytest.mark.parametrize("scoring", ["softmax", "sigmoid"])
@pytest.mark.parametrize("gated", [False, True], ids=["ungated", "gated"])
def test_a_held_shares_compact_buffer_gives_what_every_row_gives(gated, scoring, with_live, monkeypatch):
    """A share of 2 of 32 experts at a prefill's 512 rows x 4: the first 512
    sorted rows (4 x the even share of 128) go through the experts and each
    token's places are looked up among them; the result is the one all 2,048
    rows give, to a float32 sum's rounding, and `RoutedOutput.compact` says
    which ran."""
    monkeypatch.setattr(moe, "FEW_ROWS", 0)  # a held share at so few rows takes the loop below; more rows take this
    bp = init_moe_params(jax.random.key(3), E, F, X32, jnp.float32, gated=gated)
    bp["router"] = bp["router"] * 40  # scores that differ
    x = tokens(PREFILL, seed=4)
    live = jnp.arange(PREFILL) >= 37 if with_live else None
    assert moe.compact_buffer_rows(PREFILL, 4, 2, X32) == 512
    kw = dict(k=4, renormalize=True, scoring=scoring, scale=2.5, live=live)
    with jax.default_matmul_precision("highest"):
        got = [routed_share(x, bp, s, **kw) for s in (0, 7, 15)]
        every_row(monkeypatch)
        want = [routed_share(x, bp, s, **kw) for s in (0, 7, 15)]
    for g, w in zip(got, want):
        assert int(g.compact) == 1 and int(w.compact) == 0
        assert 0 < int(g.assignments) == int(w.assignments) <= 512 and int(g.experts_touched) == int(w.experts_touched)
        assert np.abs(np.asarray(w.out)).max() > 1e-3
        np.testing.assert_allclose(g.out, w.out, atol=1e-6 * float(np.abs(np.asarray(w.out)).max()) + 1e-7)
        assert float(g.aux_loss) == pytest.approx(float(w.aux_loss), rel=1e-6)
        if with_live:
            assert not np.asarray(g.out)[:37].any()


@pytest.mark.parametrize("with_live", [False, True], ids=["all-live", "padded"])
@pytest.mark.parametrize("scoring", ["softmax", "sigmoid"])
@pytest.mark.parametrize("gated", [False, True], ids=["ungated", "gated"])
@pytest.mark.parametrize("rows", [8, PREFILL])
def test_a_held_share_given_few_rows_loops_over_the_experts_touched(rows, gated, scoring, with_live, monkeypatch):
    """At most FEW_ROWS rows (a decode step's, a prefill's smaller buckets): a
    turn of a loop for each held expert that was given a row, every row through
    it at the weight the row gives it.  The result, the counts, the loss and the
    gradient asked through it are the ones all N x k sorted rows give through
    the grouped matmul; gated and ungated, relu^2 as well as silu; an ungated
    expert stored wider than it is (LANES) stays as wide as it is."""
    assert rows <= moe.FEW_ROWS and moe.takes_loop(rows, (0, 2)) and not moe.takes_loop(rows, None)
    bp = init_moe_params(jax.random.key(3), E, 200, X32, jnp.float32, gated=gated)
    if not gated:
        assert bp["w_in"].shape[-1] == 256 and bp["w_out"].shape[-2] == 200
    bp["router"] = bp["router"] * 40
    x = tokens(rows, seed=4)
    live = jnp.arange(rows) >= rows // 8 if with_live else None
    kw = dict(k=4, renormalize=True, scoring=scoring, scale=2.5, live=live, act="relu2" if scoring == "sigmoid" else "silu")
    seen = tokens(rows, seed=5)

    def pulled(x, bp):  # a share's result against a cotangent, and its load-balance loss
        r = routed_share(x, bp, 7, **kw)
        return jnp.sum(r.out * seen) + r.aux_loss

    with jax.default_matmul_precision("highest"):
        got = [routed_share(x, bp, s, **kw) for s in (0, 7, 15)]
        assert " while[" in str(jax.make_jaxpr(lambda x: routed_share(x, bp, 7, **kw).out)(x))
        grads = jax.grad(pulled, argnums=(0, 1))(x, bp)
        monkeypatch.setattr(moe, "FEW_ROWS", 0)
        every_row(monkeypatch)
        want = [routed_share(x, bp, s, **kw) for s in (0, 7, 15)]
        assert " while[" not in str(jax.make_jaxpr(lambda x: routed_share(x, bp, 7, **kw).out)(x))
        want_grads = jax.grad(pulled, argnums=(0, 1))(x, bp)
    assert sum(int(w.assignments) for w in want) > 0
    for g, w in zip(got, want):
        assert int(g.compact) == 0 == int(w.compact)
        assert int(g.assignments) == int(w.assignments) and int(g.experts_touched) == int(w.experts_touched)
        np.testing.assert_allclose(g.out, w.out, atol=1e-6 * float(np.abs(np.asarray(w.out)).max()) + 1e-7)
        assert float(g.aux_loss) == pytest.approx(float(w.aux_loss), rel=1e-6)
        if with_live:
            assert not np.asarray(g.out)[:rows // 8].any()
    for g, w in zip(jax.tree_util.tree_leaves(grads), jax.tree_util.tree_leaves(want_grads)):
        assert np.abs(np.asarray(w)).max() > 0
        np.testing.assert_allclose(g, w, atol=1e-5 * float(np.abs(np.asarray(w)).max()))


@pytest.mark.parametrize("gated", [False, True], ids=["ungated", "gated"])
def test_rows_just_past_the_limit_keep_the_compact_grouped_path(gated):
    """A bucket the loop would slow (every row goes through every touched
    expert) is the compact buffer's behind its conditional, as before."""
    rows = moe.FEW_ROWS + 8
    bp = init_moe_params(jax.random.key(3), E, F, X32, jnp.float32, gated=gated)
    x = tokens(rows, seed=4)
    assert not moe.takes_loop(rows, (14, 2)) and moe.compact_buffer_rows(rows, 4, 2, X32) == rows
    traced = str(jax.make_jaxpr(lambda x: routed_share(x, bp, 7, k=4).out)(x))
    assert traced.count(" cond[") == 1 and " while[" not in traced
    assert int(routed_share(x, bp, 7, k=4).compact) == 1


@pytest.mark.parametrize("crowded", [False, True], ids=["even", "crowded"])
def test_the_sixteen_shares_add_up_whichever_branch_each_took(crowded, monkeypatch):
    """Dropless behind the conditional (the path of rows past the few): with a router that sends every token's
    first two choices to experts 0 and 1, share 0 is given 1,024 rows, twice its
    buffer, and takes all N x k rows; the other fifteen stay compact; the
    sixteen parts are still the uncut layer, row by row.  With an even router
    all sixteen are compact."""
    monkeypatch.setattr(moe, "FEW_ROWS", 0)
    bp = init_moe_params(jax.random.key(5), E, F, X32, jnp.float32, gated=True)
    bp["router"] = bp["router"] * 40
    x = tokens(PREFILL, seed=6)
    if crowded:
        x = x.at[:, 0].set(1.0)
        bp["router"] = bp["router"].at[0, 0].set(90.0).at[0, 1].set(80.0)
    kw = dict(k=4, renormalize=True, scoring="sigmoid", scale=2.5)
    with jax.default_matmul_precision("highest"):
        whole = routed_ffn(x, bp["router"], {k: bp[k][None] for k in EXPERT_MATRICES if k in bp}, **kw)
        parts = [routed_share(x, bp, s, **kw) for s in range(16)]
    assert int(whole.compact) == 0 and int(whole.assignments) == PREFILL * 4
    assert [int(p.compact) for p in parts] == [0 if crowded else 1] + [1] * 15
    assert int(parts[0].assignments) == (1024 if crowded else int(parts[0].assignments)) and sum(
        int(p.assignments) for p in parts) == PREFILL * 4  # every (token, expert) pair fell on exactly one share
    np.testing.assert_allclose(sum(p.out for p in parts), whole.out, atol=2e-5)


HELD = dict(vocab_size=64, d_model=32, n_layers=2, n_heads=4, n_kv_heads=4, d_head=8, d_ff=48,
            n_experts=32, n_experts_per_tok=2, moe_gated=True, experts_held=(4, 2),
            dtype=jnp.float32, param_dtype=jnp.float32)


@pytest.mark.parametrize("length, compact", [(512, 1), (256, 0)], ids=["compact-buffer", "loop"])
def test_one_device_loss_with_a_held_share_has_one_gradient_through_either_branch(length, compact, monkeypatch):
    """The one-device loss differentiates through `routed_ffn`: at 2 x 512 rows
    a held share's layers take the compact buffer (the spy reads each layer's
    flag as the loss runs), at 2 x 256 the loop over the experts touched, and
    loss and gradient are those of all N x k rows."""
    cfg = TransformerConfig(**HELD, moe_aux_weight=0.01)
    params = init_params(jax.random.key(7), cfg)
    assert params["blocks"]["w_gate"].shape == (2, 2, 32, 48) and params["blocks"]["router"].shape == (2, 32, 32)
    batch = {"ids": jnp.asarray(np.random.default_rng(1).integers(0, 64, (2, length + 1)), jnp.int32)}
    assert moe.takes_loop(2 * length, cfg.experts_held) == (not compact)
    took, inner = [], moe.routed_ffn

    def spy(*args, **kw):
        r = inner(*args, **kw)
        jax.debug.callback(lambda c: took.append(int(c)), r.compact)
        return r

    monkeypatch.setattr(moe, "routed_ffn", spy)
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.value_and_grad(transformer.make_loss_fn(cfg))(params, batch)
        jax.effects_barrier()
        assert took and set(took) == {compact}
        every_row(monkeypatch)
        monkeypatch.setattr(moe, "FEW_ROWS", 0)
        del took[:]
        want, want_grads = jax.value_and_grad(transformer.make_loss_fn(cfg))(params, batch)
        jax.effects_barrier()
        assert took and set(took) == {0}
    assert float(loss) == pytest.approx(float(want), abs=1e-6)
    for name in ("router", "w_gate", "w_up", "w_down", "wq", "wo"):
        g, w = np.asarray(grads["blocks"][name]), np.asarray(want_grads["blocks"][name])
        assert np.abs(w).max() > 0 and np.max(np.abs(g - w)) < 1e-5 * max(1.0, np.abs(w).max()), name


@pytest.mark.parametrize("rows, held", [(PREFILL, None), (1, (0, 2)), (PREFILL, (0, 16)), (2 * PREFILL, (0, 16)), (32, (0, 2))],
                         ids=["every-expert-held", "a-suffix-steps-one-row", "half-the-experts-few-rows", "half-the-experts",
                              "a-held-share-at-a-steps-rows"])
def test_no_conditional_is_traced_where_no_buffer_would_gain(rows, held, monkeypatch):
    """Every expert held (OLMoE, SDAR, the one-device train step), a share so
    large that the buffer is over half of N x k: the program is the one before
    the compact buffer, with no conditional.  Nor has a held share's program
    one at a decode step's rows or any other few: it loops over the experts
    touched, and no grouped matmul is in it."""
    bp = init_moe_params(jax.random.key(0), E, F, X32, jnp.float32, gated=True)
    stack = {k: bp[k][None, :X32 if held is None else held[1]] for k in EXPERT_MATRICES if k in bp}
    fn = lambda x: routed_ffn(x, bp["router"], stack, k=4, held=held)
    traced = str(jax.make_jaxpr(fn)(tokens(rows)))
    assert " cond[" not in traced
    assert (" while[" in traced, "ragged_dot" in traced) == ((True, False) if moe.takes_loop(rows, held) else (False, True))
    assert int(fn(tokens(rows)).compact) == 0
    if held is None:  # a device that holds every expert runs one program whatever the few are (OLMoE's, SDAR's: unmoved)
        monkeypatch.setattr(moe, "FEW_ROWS", 0)
        assert str(jax.make_jaxpr(fn)(tokens(rows))) == traced
        monkeypatch.undo()
    # where the buffer does gain there is exactly one: a prefill's rows past the few
    small = {k: v[:, :2] for k, v in stack.items()}
    n = 2 * PREFILL
    assert moe.compact_buffer_rows(n, 4, 2, X32) == n  # 4 x the even share of n * 4 * 2 / 32: a quarter of the rows
    traced = str(jax.make_jaxpr(lambda x: routed_ffn(x, bp["router"], small, k=4, held=(0, 2)))(tokens(n)))
    assert traced.count(" cond[") == 1

# -- where the program calls it from ------------------------------------------

SMALL = dict(vocab_size=64, d_model=32, n_layers=2, n_heads=4, n_kv_heads=4, d_head=8, d_ff=48,
             n_experts=8, n_experts_per_tok=2, moe_gated=True, qk_norm=True,
             dtype=jnp.float32, param_dtype=jnp.float32)


def test_defaults_are_the_top1_ungated_model():
    cfg = TransformerConfig(n_experts=4)
    assert (cfg.n_experts_per_tok, cfg.moe_gated, cfg.moe_renormalize, cfg.qk_norm) == (1, False, False, False)
    blocks = jax.eval_shape(lambda k: init_params(k, cfg), jax.random.key(0))["blocks"]
    assert {"router", "w_in", "w_out"} <= set(blocks) and not {"w_gate", "q_norm", "k_norm"} & set(blocks)
    cfg = TransformerConfig(**SMALL)
    blocks = jax.eval_shape(lambda k: init_params(k, cfg), jax.random.key(0))["blocks"]
    assert blocks["w_gate"].shape == blocks["w_up"].shape == (2, 8, 32, 48)
    assert blocks["w_down"].shape == (2, 8, 48, 32) and blocks["q_norm"].shape == (2, 32)
    assert set(transformer.param_specs(cfg)["blocks"]) == set(blocks)


def test_one_device_loss_and_its_gradient_match_the_loops(monkeypatch):
    cfg = TransformerConfig(**SMALL, moe_aux_weight=0.0)
    params = init_params(jax.random.key(2), cfg)
    # the norms' weights off 1, so a norm that is left out shows
    params["blocks"]["q_norm"] = params["blocks"]["q_norm"] * 1.3
    params["blocks"]["k_norm"] = params["blocks"]["k_norm"] * 0.7
    batch = {"ids": jnp.asarray(np.random.default_rng(0).integers(0, 64, (2, 9)), jnp.int32)}
    loss_fn = transformer.make_loss_fn(cfg)  # no mesh: one device
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)

        def looped(bp, y, cfg, live=None, experts=None):
            b, t, e = y.shape
            out = loop(y.reshape(b * t, e), bp, cfg.n_experts_per_tok, cfg.moe_gated, cfg.moe_renormalize)
            return out.reshape(b, t, e), jnp.zeros((), jnp.float32), jnp.zeros((), jnp.int32)

        monkeypatch.setattr(transformer, "_moe", looped)
        want, want_grads = jax.value_and_grad(transformer.make_loss_fn(cfg))(params, batch)
    assert float(loss) == pytest.approx(float(want), abs=1e-5)
    for name in ("router", "w_gate", "w_up", "w_down", "q_norm", "k_norm", "wq"):
        g, w = np.asarray(grads["blocks"][name]), np.asarray(want_grads["blocks"][name])
        assert np.abs(w).max() > 0 and np.max(np.abs(g - w)) < 1e-5 * max(1.0, np.abs(w).max()), name
    # the load-balance term reaches the router too
    with_aux = jax.grad(transformer.make_loss_fn(TransformerConfig(**SMALL, moe_aux_weight=0.5)))
    monkeypatch.undo()
    more = with_aux(params, batch)["blocks"]["router"]
    assert np.abs(np.asarray(more - grads["blocks"]["router"])).max() > 0


def test_more_than_one_expert_a_token_over_ep_is_refused():
    from cluster_anywhere_tpu.parallel import MeshSpec, make_mesh

    cfg = TransformerConfig(**dict(SMALL, qk_norm=False), ep=2)
    mesh = make_mesh(MeshSpec(dp=4, ep=2))
    with pytest.raises(NotImplementedError, match="top-1 and ungated"):
        transformer.forward(init_params(jax.random.key(0), cfg), jnp.zeros((8, 4), jnp.int32), cfg, mesh)


def test_decode_step_is_told_which_slots_are_live():
    """Empty slots take no expert: with one live row of four the step touches
    exactly that row's two experts a layer, whatever the other rows hold."""
    cfg = TransformerConfig(**SMALL)
    params = init_params(jax.random.key(4), cfg)
    slots, t_max = 4, 16
    cache = generate.init_cache(cfg, slots, t_max)
    floats = jnp.asarray([(0.0,) * 4, (1.0,) * 4], jnp.float32)  # temps, top_ps

    def step(tokens, live, params=params, cfg=cfg):
        # the int32 input's rows: tokens, pos, pads, top_ks, fresh (every slot feeds the
        # host's token, not the step before's), live
        rows = [tokens, (2, 0, 0, 0), (0,) * 4, (0,) * 4, (1,) * 4, live]
        nxt, _, _, touched = continuous._decode_step_rowpos.__wrapped__(
            params, cache, jnp.asarray(rows, jnp.int32), floats, jnp.zeros(4, jnp.int32),
            jax.random.key(0), cfg=cfg)
        return nxt, touched

    nxt, touched = step((5, 9, 11, 3), (1, 0, 0, 0))
    assert float(touched) == 2.0
    nxt2, touched2 = step((5, 1, 2, 60), (1, 0, 0, 0))
    assert int(nxt[0]) == int(nxt2[0]) and float(touched2) == 2.0
    _, all_live = step((5, 9, 11, 3), (1,) * 4)
    assert 2.0 <= float(all_live) <= 8.0
    # a dense model's step is told too (its attention reads the live rows' slots alone)
    # and says nothing of experts
    dense = TransformerConfig(**dict(SMALL, n_experts=0, moe_gated=False))
    assert step((5, 9, 11, 3), (1, 0, 0, 0), init_params(jax.random.key(4), dense), dense)[1] is None


@pytest.fixture
def seen(monkeypatch):
    """(span name, attributes) of every `set` on a span the batcher opens."""
    seen = []

    class Span(continuous.tracing.span):
        def set(self, **attrs):
            seen.append((self.name, attrs))
            super().set(**attrs)

    monkeypatch.setattr(continuous.tracing, "span", Span)
    return seen


def test_batcher_reports_rows_experts_and_assignments(seen):
    cfg = TransformerConfig(**SMALL)
    cb = continuous.ContinuousBatcher(init_params(jax.random.key(5), cfg), cfg, slots=4, t_max=32,
                                      prefill_buckets=(8, 16))
    reqs = [cb.submit(list(range(1, n + 1)), max_new_tokens=4) for n in (5, 11)]
    cb.pump()
    assert all(r.done and len(r.out_tokens) == 4 for r in reqs)
    admits = [a["moe_assignments"] for name, a in seen if name == "llm.admit" and "moe_assignments" in a]
    assert admits == [5 * 2, 11 * 2]
    steps = [a for name, a in seen if name == "llm.step" and "moe_rows" in a]
    assert len(steps) == 3 and all(s["moe_rows"] == 2 and 2.0 <= s["moe_experts_touched"] <= 4.0 for s in steps)
    assert cb.stats["moe_assignments"] == (5 + 11) * 2 + 3 * 2 * 2
    # the padded prefill gives what the unpadded one gives: the padding takes no expert
    ids = jnp.asarray([[3, 1, 4, 1, 5]], jnp.int32)
    bare, _ = generate.prefill(cb.params, ids, cfg, 32)
    padded, _ = generate.prefill(cb.params, jnp.pad(ids, ((0, 0), (3, 0))), cfg, 32, pad=jnp.asarray([3]))
    assert np.max(np.abs(np.asarray(bare - padded))) < 1e-5


def test_an_admit_of_a_held_share_reports_its_held_and_compact_layers(seen):
    """A replica that holds a share of the experts reads, with an admit's first
    token, how many of the prefill's expert layers there were and how many took
    the compact buffer: all in a bucket of 1,024 rows, none in one of 8, whose
    layers loop over the experts touched."""
    cfg = TransformerConfig(**HELD)
    cb = continuous.ContinuousBatcher(init_params(jax.random.key(5), cfg), cfg, slots=2, t_max=1032,
                                      prefill_buckets=(8, 1024))
    rng = np.random.default_rng(3)
    reqs = [cb.submit(rng.integers(1, 64, n).tolist(), max_new_tokens=3) for n in (5, 600)]
    cb.pump()
    assert all(r.done and len(r.out_tokens) == 3 for r in reqs)
    admits = [a for name, a in seen if name == "llm.admit" and "moe_held_layers" in a]
    assert [(a["moe_held_layers"], a["moe_compact_layers"]) for a in admits] == [(2, 0), (2, 2)]
    # a replica that holds every expert says nothing of it
    del seen[:]
    cfg = TransformerConfig(**SMALL)
    cb = continuous.ContinuousBatcher(init_params(jax.random.key(5), cfg), cfg, slots=2, t_max=32, prefill_buckets=(8,))
    cb.submit([1, 2, 3], max_new_tokens=2)
    cb.pump()
    assert [a for name, a in seen if name == "llm.admit"] and not [a for _, a in seen if "moe_held_layers" in a]


@pytest.mark.parametrize("few_rows, loop_layers", [(None, 2), (1, 0)], ids=["as-it-is", "a-step-past-the-few"])
def test_a_decode_step_of_a_held_share_reports_its_layers_that_loop(few_rows, loop_layers, seen, monkeypatch):
    """Which path a step's expert layers take is decided as its program is
    traced (`moe.takes_loop`), so the batcher counts it on the host: every step
    adds the held expert layers and those that loop over the experts touched,
    and says the latter on its span.  Over a model that holds every expert
    neither moves."""
    if few_rows is not None:
        monkeypatch.setattr(moe, "FEW_ROWS", few_rows)
    cfg = TransformerConfig(**HELD)
    cb = continuous.ContinuousBatcher(init_params(jax.random.key(5), cfg), cfg, slots=2, t_max=32, prefill_buckets=(8,))
    cb.submit([1, 2, 3], max_new_tokens=4)
    cb.pump()
    steps = [a for name, a in seen if name == "llm.step" and "moe_rows" in a]
    assert len(steps) == 3 == cb.stats["decode_steps"] and all(a["moe_loop_layers"] == loop_layers for a in steps)
    assert (cb.stats["moe_step_held_layers"], cb.stats["moe_step_loop_layers"]) == (3 * 2, 3 * loop_layers)
    # the benchmark's reader of one total over another (`held_loop_share.moe`, for a `benchmark` PR to add) reads them
    share = manifest.load_reader("replica_stat_ratio")
    args = dict(over="moe_step_loop_layers", under="moe_step_held_layers", scale=100.0)
    assert share({"replica": {"stats": dict(cb.stats)}}, **args) == 50.0 * loop_layers
    traced = str(jax.make_jaxpr(lambda *a: continuous._decode_step_rowpos.__wrapped__(*a, cfg=cfg))(
        cb.params, cb.cache, jnp.zeros((6, 2), jnp.int32), jnp.zeros((2, 2), jnp.float32), jnp.zeros(2, jnp.int32),
        jax.random.key(0)))
    assert ("ragged_dot" in traced) == (not loop_layers)  # what the batcher counted is what the step's program holds
    del seen[:]
    cfg = TransformerConfig(**SMALL)
    cb = continuous.ContinuousBatcher(init_params(jax.random.key(5), cfg), cfg, slots=2, t_max=32, prefill_buckets=(8,))
    cb.submit([1, 2, 3], max_new_tokens=3)
    cb.pump()
    assert [a for name, a in seen if name == "llm.step" and "moe_rows" in a] and not [a for _, a in seen if "moe_loop_layers" in a]
    assert (cb.stats["moe_step_held_layers"], cb.stats["moe_step_loop_layers"]) == (0, 0)
    assert share({"replica": {"stats": dict(cb.stats)}}, **args) is None  # nothing to divide by: the metric stays out of the line
