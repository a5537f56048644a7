"""What `cfg.remat` keeps (models/transformer.py `_remat_keeps`, KEPT_NAMES,
FFN_NAMES): a checkpointed block's attention half where the device has room, by
the names of a `jax.checkpoint` policy, the dense FFN's two up products beside
it in as many layers as the rest of the room holds, and the layer's input alone
where it has none.  The same values either way, made once or twice: the loss and
every gradient are the bare checkpoint's and `remat=False`'s.  And the head and
loss by chunks of positions (`_chunked_loss`): the whole computation's numbers."""

import dataclasses
import importlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from _llm_tiny import TRACE, llm_spans  # noqa: F401 (llm_spans is a fixture)
from cluster_anywhere_tpu.models import transformer
from cluster_anywhere_tpu.models.transformer import TransformerConfig
from cluster_anywhere_tpu.parallel import MeshSpec, make_mesh
from cluster_anywhere_tpu.util import tracing

attention = importlib.import_module("cluster_anywhere_tpu.ops.attention")

TINY = dict(vocab_size=96, d_model=64, n_layers=3, n_heads=4, n_kv_heads=4, d_head=16, d_ff=128, max_seq_len=128)
PRESETS = {
    "plain": TINY,
    "grouped_query": dict(TINY, n_kv_heads=2),
    "window": dict(TINY, n_kv_heads=2, layer_mixers=("attn_win", "attn_win", "attn"), attn_window=8, attn_ring=16,
                   rotary_full=False),
    "mixture": dict(TINY, n_kv_heads=2, n_experts=4, n_experts_per_tok=2, moe_gated=True, n_shared_experts=1, d_expert=32),
    "rolled": dict(TINY, unroll_layers=False),  # a loop that is not unrolled reads a run's layers where they lie, by their indices
}
# Mistral-7B's widths as `train-fsdp4` trains them, 12 layers deep, and the rows one of its four chips sees
CELL = dict(vocab_size=32768, d_model=4096, n_layers=12, n_heads=32, n_kv_heads=8, d_head=128, d_ff=14336, max_seq_len=4096)
CELL_ROWS = (2, 4096)
V5E_LIMIT = 16909336576  # what a v5e chip reports as `bytes_limit`: 15.75 GiB

# an optimizer whose state is the gradients it was given: the step hands them back to the bit
GRADIENTS = optax.GradientTransformation(lambda params: jax.tree_util.tree_map(jnp.zeros_like, params),
                                         lambda grads, state, params=None: (grads, grads))


@pytest.fixture
def limit(monkeypatch):
    """Sets what the devices report as their memory's limit (None: none, as the CPU)."""
    return lambda value: monkeypatch.setattr(transformer, "_memory_limit", lambda mesh: value)


@pytest.fixture
def remat_events(llm_spans):
    """The `train.remat` spans written since the call before, under a trace context."""
    token = tracing.push_execution(TRACE)
    yield lambda: llm_spans("train.remat")
    tracing.pop_execution(token)


def _loss_and_gradients(cfg, batch):
    step, _ = transformer.make_train_step(cfg, None, optimizer=GRADIENTS)
    params = transformer.init_params(jax.random.key(0), cfg)
    # every rounding to cfg.dtype where the program states one: left to itself the CPU's compiler keeps
    # a fused bf16 intermediate in float32, and it fuses the three programs differently
    jstep = jax.jit(step, compiler_options={"xla_allow_excess_precision": False})
    _, grads, loss = jstep(params, GRADIENTS.init(params), batch)
    return loss, grads


@pytest.fixture
def ffn_layers(monkeypatch):
    """Sets how many layers keep FFN_NAMES, whatever the rule says of the room."""
    rule = transformer._remat_keeps
    return lambda n: monkeypatch.setattr(transformer, "_remat_keeps", lambda *a, **k: rule(*a, **k)._replace(ffn_layers=n))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("preset", list(PRESETS) + ["plain_split", "window_split", "rolled_split"])
def test_loss_and_gradients_are_the_bare_checkpoints_and_no_checkpoints(preset, dtype, limit, ffn_layers):
    """(a) The names kept, the bare checkpoint and no checkpoint make the same
    values, in float32 and in bfloat16: the loss and every gradient, bit for bit.
    `_split`: the loop in two runs, the last layer alone keeps the FFN's names
    (without a limit every dense FFN does; a mixture's experts have none)."""
    split = preset.endswith("_split")
    cfg = TransformerConfig(**PRESETS[preset.removesuffix("_split")], dtype=dtype, remat=True)
    batch = {"ids": jax.random.randint(jax.random.key(1), (2, 65), 0, cfg.vocab_size)}
    limit(None)
    if split:
        ffn_layers(1)
    loss, grads = _loss_and_gradients(cfg, batch)
    limit(1)
    bare = _loss_and_gradients(cfg, batch)
    plain = _loss_and_gradients(dataclasses.replace(cfg, remat=False), batch)
    assert all(np.any(np.asarray(g) != 0) for g in jax.tree_util.tree_leaves(grads))
    for want_loss, want in (bare, plain):
        assert float(loss) == float(want_loss)
        jax.tree_util.tree_map(np.testing.assert_array_equal, grads, want)


def _gradient_jaxpr(cfg, shape=(2, 129), mesh=None):
    """The jaxpr of the train step over abstract weights, the dispatcher on the kernels as on the chip."""
    step, _ = transformer.make_train_step(cfg, mesh)
    params = jax.eval_shape(lambda k: transformer.init_params(k, cfg), jax.random.key(0))
    opt = jax.eval_shape(optax.adamw(3e-4).init, params)
    return str(jax.make_jaxpr(step)(params, opt, {"ids": jax.ShapeDtypeStruct(shape, jnp.int32)}))


@pytest.fixture
def on_tpu(monkeypatch):
    monkeypatch.setattr(attention, "_platform", lambda: "tpu")


def test_the_gradient_runs_the_attention_core_forward_once_a_layer(on_tpu, limit):
    """(b) The layer loop's one body holds the flash kernel's forward once with
    the names kept, and twice, forward and recomputation, with the bare
    checkpoint; its two backward kernels once either way."""
    cfg = TransformerConfig(**PRESETS["grouped_query"], remat=True)
    limit(None)
    kept = _gradient_jaxpr(cfg)
    limit(1)
    bare = _gradient_jaxpr(cfg)
    assert (kept.count("name=flash_fwd"), bare.count("name=flash_fwd")) == (1, 2)
    assert kept.count("name=flash_bwd_dq") == bare.count("name=flash_bwd_dq") == 1
    assert all(f"name={name}]" in kept for name in transformer.KEPT_NAMES + transformer.FFN_NAMES)
    assert "name=attn.q]" not in bare and "policy=None" in bare and "policy=None" not in kept


def test_the_kept_bytes_are_what_the_names_hold(on_tpu, limit, remat_events):
    """`_kept_bytes`' and `_kept_ffn_bytes`' arithmetic against the gradient's own jaxpr: the bytes of every named value."""
    sizes = {"bf16": 2, "f32": 4}
    for preset in ("plain", "grouped_query"):
        cfg = TransformerConfig(**PRESETS[preset], remat=True)
        limit(None)
        jaxpr = _gradient_jaxpr(cfg)
        (event,) = remat_events()
        named = re.findall(r":(bf16|f32)\[([\d,]+)\] = name\[name=(\S+)\]", jaxpr)
        assert sorted(name for _, _, name in named) == sorted(transformer.KEPT_NAMES + transformer.FFN_NAMES)
        held = {of: sum(sizes[dtype] * np.prod([int(n) for n in shape.split(",")]) for dtype, shape, name in named
                        if (name in transformer.FFN_NAMES) == of) for of in (False, True)}
        assert event["kept_bytes"] == cfg.n_layers * held[False] == cfg.n_layers * transformer._kept_bytes(cfg, 2 * 128)
        assert event["kept_ffn_bytes"] == cfg.n_layers * held[True] == cfg.n_layers * transformer._kept_ffn_bytes(cfg, 2 * 128)
        assert event["kept_ffn_layers"] == cfg.n_layers


@pytest.mark.parametrize("fsdp", [4, 1], ids=["fsdp4", "one_chip"])
def test_the_fit_rule_at_the_cells_shapes(fsdp, limit, remat_events):
    """(c) `train-fsdp4`'s step, traced over shapes alone.  Under a v5e's limit
    a chip of four keeps 236 MB a layer, 2.83 GB, beside its 8.66 GB of weights
    and moments, and the FFN's two up products, 470 MB a layer, in the last
    three layers; one chip that holds all 34.6 GB of them gives the bare
    checkpoint and says so.  A limit one FFN layer short says one fewer, one
    that has room for the attention names alone says none, and one byte under
    their need says the bare checkpoint."""
    cfg = TransformerConfig(**CELL, remat=True)
    mesh = make_mesh(MeshSpec(fsdp=fsdp), devices=jax.devices()[:fsdp]) if fsdp > 1 else None
    shape = (CELL_ROWS[0] * fsdp, CELL_ROWS[1] + 1)
    a_layer = 2 * 4096 * (2 * (4096 + 2 * 1024 + 4096 + 4096) + 4 * 32)
    an_ffn = 2 * 4096 * 2 * 14336 * 2
    assert (a_layer, an_ffn) == (235_929_600, 469_762_048)
    weights = sum(x.size * 4 for x in jax.tree_util.tree_leaves(
        jax.eval_shape(lambda k: transformer.init_params(k, cfg), jax.random.key(0))))
    # the allowance, derived: every layer's input; the weights a chip holds, in bfloat16; the larger of the head
    # and loss by chunks of 512 positions (1,024 rows of logits in bfloat16 and twice in float32, every row's
    # gradient in bfloat16, the head's matrix and its gradient whole) and of a layer's two halves with their
    # gradients; 1/64 of the limit
    inputs, loss, layer = 12 * 2 * 4096 * 4096 * 2, 32768 * (1024 * 10 + 2 * 4096 * 2 + 2 * 4096 * 2), 2 * (a_layer + an_ffn)
    assert (loss, layer) == (1_409_286_144, 1_411_383_296)
    temp = lambda value: inputs + weights // fsdp // 2 + max(loss, layer) + value // 64
    # Adam's two moments beside the weights, a float32 each; the norms' weights, 1.2 MB, are whole on every chip
    room = lambda value: value - 3 * weights // fsdp - temp(value)

    def traced(value):
        limit(value)
        jaxpr = _gradient_jaxpr(cfg, shape, mesh)
        (event,) = remat_events()
        assert event["kept_bytes"] == 12 * a_layer and event["kept_layers"] == 12 * event["kept"]
        assert event["kept_ffn_bytes"] == event["kept_ffn_layers"] * an_ffn and event["loss_chunk"] == 512
        assert ("name=attn.h]" in jaxpr, "policy=None" in jaxpr) == (event["kept"], not event["kept"])
        # the loop is two runs where some layers keep the FFN's names and some do not: a policy each
        assert jaxpr.count("name=ffn.gate]") == (0 < event["kept_ffn_layers"])
        assert len(set(re.findall(r"policy=(None|<function \S+ at \w+>)", jaxpr))) == 1 + (0 < event["kept_ffn_layers"] < 12)
        assert event["temp_bytes"] == pytest.approx(temp(value), abs=2 ** 20)
        assert event["budget_bytes"] == pytest.approx(room(value), abs=2 ** 21)
        return event

    event = traced(V5E_LIMIT)
    assert event["kept"] == (fsdp == 4) == (12 * a_layer <= room(V5E_LIMIT))
    if event["kept"]:
        spare = event["budget_bytes"] - 12 * a_layer
        assert event["kept_ffn_layers"] == 3 == spare // an_ffn
        # what a byte less of the limit takes from the budget: itself, and a 64th now and then
        short_of = lambda budget: next(value for value in range(V5E_LIMIT - (event["budget_bytes"] - budget) * 64 // 63 - 64, V5E_LIMIT)
                                       if value - value // 64 == V5E_LIMIT - V5E_LIMIT // 64 - (event["budget_bytes"] - budget))
        assert traced(short_of(12 * a_layer + 3 * an_ffn - 1))["kept_ffn_layers"] == 2
        assert traced(short_of(12 * a_layer + an_ffn - 1))["kept_ffn_layers"] == 0
        bare = traced(short_of(12 * a_layer - 1))
        assert (bare["kept"], bare["budget_bytes"], bare["kept_ffn_layers"]) == (False, 12 * a_layer - 1, 0)


def test_without_remat_nothing_is_checkpointed_or_named(limit, remat_events):
    """(d) `remat=False` traces no checkpoint, no name and no decision."""
    limit(None)
    jaxpr = _gradient_jaxpr(TransformerConfig(**PRESETS["grouped_query"]))
    assert "checkpoint" not in jaxpr and "name[" not in jaxpr and remat_events() == []


def test_a_loss_alone_decides_beside_the_weights(limit, remat_events):
    """`make_loss_fn` has no optimizer: its gradient counts the weights' bytes
    alone, a third of what the step holds with Adam's moments."""
    cfg = TransformerConfig(**PRESETS["plain"], remat=True)
    params = jax.eval_shape(lambda k: transformer.init_params(k, cfg), jax.random.key(0))
    weights = sum(x.size * x.dtype.itemsize for x in jax.tree_util.tree_leaves(params))
    batch = {"ids": jax.ShapeDtypeStruct((2, 65), jnp.int32)}
    limit(V5E_LIMIT)
    jax.eval_shape(jax.grad(transformer.make_loss_fn(cfg)), params, batch)
    (alone,) = remat_events()
    step, _ = transformer.make_train_step(cfg, None)
    jax.eval_shape(step, params, jax.eval_shape(optax.adamw(3e-4).init, params), batch)
    (with_state,) = remat_events()
    # the derived allowance is the same beside either: 3 layers' inputs, the weights in bfloat16, the larger of the
    # head and loss whole (128 rows of 96 logits, 10 bytes each, the head and its gradient) and a layer's halves, 1/64
    rows, a_layer = 2 * 64, transformer._kept_bytes(cfg, 2 * 64) + transformer._kept_ffn_bytes(cfg, 2 * 64)
    temp = 3 * rows * 64 * 2 + weights // 2 + max(96 * (rows * 10 + 2 * 64 * 2), 2 * a_layer) + V5E_LIMIT // 64
    assert alone["temp_bytes"] == with_state["temp_bytes"] == temp and alone["loss_chunk"] == 0
    assert alone["budget_bytes"] == V5E_LIMIT - weights - temp
    assert alone["budget_bytes"] - with_state["budget_bytes"] == pytest.approx(2 * weights, abs=64)


@pytest.fixture
def loss_chunk(monkeypatch):
    """Sets LOSS_CHUNK, the logits a device makes at a time."""
    return lambda logits: monkeypatch.setattr(transformer, "LOSS_CHUNK", logits)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("preset,positions,chunk", [("plain", 77, 20), ("tied", 77, 20), ("tied", 60, 20), ("plain", 16, 0)],
                         ids=["plain_ragged", "tied_ragged", "tied_even", "plain_under_a_chunk"])
def test_the_head_and_loss_by_chunks_are_the_whole_computations(preset, positions, chunk, dtype, loss_chunk):
    """(e) The head and loss by chunks of positions (`_chunked_loss`): the loss
    and every gradient are `cross_entropy_loss(forward(...))`'s to 1e-6 of the
    largest, in float32 and in bfloat16 (the logits and their gradient are
    rounded where they were and every reduction is over float32: what is left
    is a sum's order), with a last chunk
    that is part pad, with chunks that divide the positions, and with a tied
    head; a batch under one chunk goes through whole."""
    cfg = TransformerConfig(**dict(TINY, tie_embeddings=preset == "tied"), dtype=dtype)
    params = transformer.init_params(jax.random.key(0), cfg)
    batch = {"ids": jax.random.randint(jax.random.key(1), (2, positions + 1), 0, cfg.vocab_size)}
    whole = lambda p, b: transformer.cross_entropy_loss(transformer.forward(p, b["ids"][:, :-1], cfg), b["ids"][:, 1:])
    options = {"xla_allow_excess_precision": False}  # `_loss_and_gradients` says why
    want_loss, want = jax.jit(jax.value_and_grad(whole), compiler_options=options)(params, batch)
    loss_chunk(2 * 20 * cfg.vocab_size)
    assert transformer._loss_chunk(cfg, None, (2, positions)) == chunk
    by_chunks = transformer.make_loss_fn(cfg)
    jaxpr = str(jax.make_jaxpr(jax.grad(by_chunks))(params, batch))
    assert (f"f32[2,{positions},{cfg.vocab_size}]" in jaxpr) == (not chunk)  # no value of rows x vocabulary in float32
    loss, grads = jax.jit(jax.value_and_grad(by_chunks), compiler_options=options)(params, batch)
    assert float(loss) == pytest.approx(float(want_loss), abs=1e-6)
    alone = jax.jit(by_chunks, compiler_options=options)(params, batch)  # with no gradient asked
    assert float(alone) == pytest.approx(float(want_loss), abs=1e-6)
    for got, wanted in zip(jax.tree_util.tree_leaves(grads), jax.tree_util.tree_leaves(want)):
        assert np.any(np.asarray(wanted) != 0)
        np.testing.assert_allclose(got, wanted, rtol=0, atol=1e-6 * float(jnp.max(jnp.abs(wanted))))
