"""Ahead-of-time compiles for a described (not attached) TPU v5e: what the
chip's compiler refuses, it refuses here, at no chip time.  Interpret mode
cannot see these failures (a block the lowering rejects, a kernel GSPMD may
not partition).  Nothing runs, so these say nothing about results or times.

The dispatcher asks `jax.default_backend()`, which is the CPU here, so the
tests that go through it steer `_platform` themselves.
"""

import functools
import importlib
import math
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or the compiler logs under /tmp

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import topologies
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from cluster_anywhere_tpu.models import generate, transformer
from cluster_anywhere_tpu.parallel.mesh import AXES, MeshSpec

attention = importlib.import_module("cluster_anywhere_tpu.ops.attention")

FLAGSHIP = dict(d_model=1024, n_heads=8, n_kv_heads=4, d_head=128, d_ff=4096)
# OLMoE's widths (64 experts of 2048 x 1024, 8 a token), three layers deep
OLMOE3 = dict(
    vocab_size=512, n_layers=3, d_model=2048, n_heads=16, n_kv_heads=16, d_head=128, d_ff=1024,
    n_experts=64, n_experts_per_tok=8, moe_gated=True, qk_norm=True, param_dtype=jnp.bfloat16,
)


@pytest.fixture(scope="module")
def v5e():
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"cannot describe a v5e topology: {e!r}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep it off around these
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo.devices
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def on_tpu(monkeypatch):
    monkeypatch.setattr(attention, "_platform", lambda: "tpu")


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


def _flash_variant(variant):
    flash = attention.flash_attention
    if variant == "fwd":
        return lambda q, k, v, pad: flash(q, k, v)
    if variant == "fwd_bwd":
        return jax.grad(lambda q, k, v, pad: flash(q, k, v).astype(jnp.float32).sum(), (0, 1, 2))
    if variant == "padded_fwd":
        return lambda q, k, v, pad: flash(q, k, v, pad=pad)
    if variant == "padded_fwd_bwd":
        return jax.grad(
            lambda q, k, v, pad: flash(q, k, v, pad=pad).astype(jnp.float32).sum(), (0, 1, 2)
        )
    assert variant == "return_lse"
    return lambda q, k, v, pad: flash(q, k, v, causal=False, return_lse=True)


@pytest.mark.parametrize("t", [128, 256, 1024])
@pytest.mark.parametrize(
    "variant", ["fwd", "fwd_bwd", "padded_fwd", "padded_fwd_bwd", "return_lse"]
)
def test_flash_kernel_compiles(v5e, variant, t):
    one = SingleDeviceSharding(v5e[0])
    q = jax.ShapeDtypeStruct((2, t, 8, 128), jnp.bfloat16, sharding=one)
    pad = jax.ShapeDtypeStruct((2,), jnp.int32, sharding=one)
    compiled = jax.jit(_flash_variant(variant)).lower(q, q, q, pad).compile()
    assert _has_kernel(compiled)


@pytest.mark.parametrize("t", [96, 64])
def test_dispatcher_pads_to_the_kernel_tile(v5e, on_tpu, t):
    """A length that does not tile (the serve path's 64 bucket, an exact-split
    prefix) still runs the kernel, never the reference."""
    one = SingleDeviceSharding(v5e[0])
    q = jax.ShapeDtypeStruct((1, t, 8, 128), jnp.bfloat16, sharding=one)
    pad = jax.ShapeDtypeStruct((1,), jnp.int32, sharding=one)
    fn = lambda q, k, v, pad: attention.attention(q, k, v, pad=pad)
    assert jax.eval_shape(fn, q, q, q, pad).shape == q.shape
    assert _has_kernel(jax.jit(fn).lower(q, q, q, pad).compile())


def _compiled_admit_prefill(cfg, bucket, t_max, device):
    """`generate.prefill_counted`, the jitted function itself, compiled as an admit
    calls it: a batch-1 left-padded prompt of one bucket's length."""
    one = SingleDeviceSharding(device)
    on_chip = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one)
    params = jax.tree_util.tree_map(
        on_chip, jax.eval_shape(lambda k: transformer.init_params(k, cfg), jax.random.key(0))
    )
    ids = jax.ShapeDtypeStruct((1, bucket), jnp.int32, sharding=one)
    pad = jax.ShapeDtypeStruct((1,), jnp.int32, sharding=one)
    return generate.prefill_counted.lower(params, ids, cfg, t_max, pad=pad).compile()


@pytest.mark.parametrize("bucket", [128, 256])
def test_padded_prefill_compiles_at_flagship_width(v5e, on_tpu, bucket):
    """The continuous batcher's admit: the program it runs, with the kernel."""
    cfg = transformer.TransformerConfig(vocab_size=259, n_layers=8, **FLAGSHIP)
    assert _has_kernel(_compiled_admit_prefill(cfg, bucket, bucket + 32, v5e[0]))


def _buffers(compiled, width=100):
    """(dtype, elements, instruction) of every value the optimized program
    keeps in memory: each instruction outside a fusion's body, its text cut to
    `width` characters."""
    text = compiled.as_text()
    # a fusion that writes an operand in place names the aliasing before its body (the sharded train step has
    # them; the decode steps of the six serving widths have none and list what they listed without this)
    fused = set(re.findall(r"kind=k\w+, (?:output_to_operand_aliasing=\{.*?\}, )?calls=%([\w.\-]+)", text))
    out, inside = [], None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.\-]+) \(", line)
        if head:
            inside = head.group(1)
        elif inside not in fused:
            m = re.match(r"\s+(?:ROOT )?%[\w.\-]+ = (\w+)\[([\d,]+)\]", line)
            if m:
                elements = math.prod(int(d) for d in m.group(2).split(","))
                out.append((m.group(1), elements, line.strip()[:width]))
    return out


def test_decode_block_reads_the_cache_as_stored(v5e):
    """One decode block at the serving benchmark's widths (Mistral-7B: 32 Q /
    8 KV heads x 128, 32 slots, t_max 768) as the chip's compiler leaves it:
    no buffer as large as the layer's cache repeated to 32 heads, and none in
    f32 as large as the cache.  The jaxpr guard in test_llm_programs.py cannot
    see a broadcast that XLA materialises in front of a dot; this does."""
    cfg = transformer.TransformerConfig(
        vocab_size=259, n_layers=1, d_model=4096, n_heads=32, n_kv_heads=8, d_head=128,
        d_ff=14336, param_dtype=jnp.bfloat16,
    )
    slots, t_max = 32, 768
    one = SingleDeviceSharding(v5e[0])
    on_chip = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one)
    blocks = jax.eval_shape(lambda k: transformer.init_params(k, cfg), jax.random.key(0))["blocks"]
    bp = jax.tree_util.tree_map(lambda x: on_chip(x.shape[1:], x.dtype), blocks)
    x = on_chip((slots, 1, cfg.d_model), cfg.dtype)
    layer_cache = on_chip((1, slots, t_max, cfg.n_kv_heads, cfg.d_head), cfg.dtype)  # a stack of one
    rows = on_chip((slots,), jnp.int32)
    fn = lambda bp, x, k, v, pos, pads: generate._block_decode_rowpos(bp, x, {"k": k, "v": v}, 0, pos, cfg, pads)
    compiled = jax.jit(fn, donate_argnums=(2, 3)).lower(bp, x, layer_cache, layer_cache, rows, rows).compile()
    buffers = _buffers(compiled)
    cache = slots * t_max * cfg.n_kv_heads * cfg.d_head
    assert sum(1 for dt, n, _ in buffers if dt == "bf16" and n == cache) >= 2  # K and V are seen
    widened = [b for b in buffers if b[1] >= cache * cfg.n_heads // cfg.n_kv_heads
               or (b[0] == "f32" and b[1] >= cache)]
    assert widened == []


def _reads_the_experts_where_they_are(compiled) -> bool:
    """A program of `OLMOE3`: the grouped matmul is a kernel, the three stacked
    [L, X, E, F] matrices are seen whole, and no buffer of one layer's experts
    (X * E * F elements) exists."""
    layer = OLMOE3["n_experts"] * OLMOE3["d_model"] * OLMOE3["d_ff"]
    buffers = _buffers(compiled)
    stacks = sum(1 for _, n, _ in buffers if n == OLMOE3["n_layers"] * layer)
    return _has_kernel(compiled) and stacks >= 3 and not [b for b in buffers if b[1] == layer]


@functools.lru_cache(maxsize=None)  # a model's step compiles once for the tests that read it
def _compiled_decode_step(cfg, device, slots=32, t_max=768, on_kernel=False):
    """The continuous batcher's decode step as the serving cells run it (32
    slots, t_max 768, the cache donated), compiled for `device`.  on_kernel:
    with the dispatchers' answer of a TPU, so that the step attends through
    the decode kernel as it does on the chip (without, it takes the CPU's dense
    contraction, compiled for the chip: what the chip ran before the kernel).
    Returns (compiled, the shapes of its parameters, of its cache)."""
    from cluster_anywhere_tpu.llm import continuous

    one = SingleDeviceSharding(device)
    on_chip = lambda tree: jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one), tree)
    params = on_chip(jax.eval_shape(lambda k: transformer.init_params(k, cfg), jax.random.key(0)))
    cache = on_chip(jax.eval_shape(lambda: generate.init_cache(cfg, slots, t_max)))
    key = on_chip(jax.eval_shape(lambda: jax.random.key(0)))
    # the causal step takes the step before's tokens from the device beside the host's rows
    # (six: the last says which slots the step holds); a model that generates
    # by blocks of B takes a slot's position, its block's B tokens and B fixed flags, from the
    # pass before on the device (with whether the block before is to be stored, and its B tokens)
    # and from the host (four rows more), and its own step
    b = cfg.block_length if cfg.generates_blocks else 0
    step = continuous._pass_step_rowpos if b else continuous._decode_step_rowpos
    ints = on_chip(jax.ShapeDtypeStruct((4 + 2 * b if b else 6, slots), jnp.int32))
    floats = on_chip(jax.ShapeDtypeStruct((2, slots), jnp.float32))
    prev = on_chip(jax.ShapeDtypeStruct((2 + 3 * b, slots) if b else (slots,), jnp.int32))
    fn = lambda *a: step.__wrapped__(*a, cfg=cfg)
    with pytest.MonkeyPatch.context() as patch:
        if on_kernel:
            patch.setattr(attention, "_platform", lambda: "tpu")
        compiled = jax.jit(fn, donate_argnums=(1,)).lower(params, cache, ints, floats, prev, key).compile()
    return compiled, params, cache


def test_decode_step_reads_the_experts_where_they_are(v5e):
    """The decode step of a mixture of experts at OLMoE's widths (32 slots):
    the grouped matmul is a kernel of the compiler's, and the layer scan hands
    it the stacked [L, X, E, F] matrices whole.  Sliced out of the stack by the
    scan, a layer's experts were a copy of all 64 at every step, 0.7 ms a
    matrix and layer on the chip."""
    compiled, _, _ = _compiled_decode_step(transformer.TransformerConfig(**OLMOE3), v5e[0])
    assert _reads_the_experts_where_they_are(compiled)


@pytest.mark.parametrize("bucket", [64, 512])
def test_padded_prefill_reads_the_experts_where_they_are(v5e, on_tpu, bucket):
    """The admit's prefill of a mixture of experts at OLMoE's widths, at the
    shortest and the longest bucket of the serving cells: the module an admit
    runs scans its layers as the decode step does."""
    compiled = _compiled_admit_prefill(transformer.TransformerConfig(**OLMOE3), bucket, 768, v5e[0])
    assert _reads_the_experts_where_they_are(compiled)


# Jamba2-3B's widths (inner width 5120, state 16, rank 160, one cached head, no
# rotary, a tied head), eight layers deep: s a s s a s s a, so the state-space
# layers lie in runs of one and two and no run is its kind's whole stack
JAMBA8 = dict(
    vocab_size=512, n_layers=8, d_model=2560, n_heads=20, n_kv_heads=1, d_head=128, d_ff=8192,
    attn_layer_period=3, attn_layer_offset=1, ssm_d_state=16, ssm_d_conv=4, ssm_expand=2,
    ssm_dt_rank=160, rotary=False, tie_embeddings=True, param_dtype=jnp.bfloat16,
)


def test_decode_step_reads_a_state_space_layers_weights_where_they_are(v5e):
    """The decode step of a layer pattern at Jamba2-3B's widths (32 slots): a
    run of state-space layers that is not the whole stack reads each layer's
    matrices out of the stack where they lie.  A run's slice of the stack
    handed to the layer loop would be a copy of those layers at every step
    (104 MB a layer, 2.7 GB a step over the published 26)."""
    cfg = transformer.TransformerConfig(**JAMBA8)
    assert cfg.layer_kinds == ("ssm", "attn", "ssm", "ssm", "attn", "ssm", "ssm", "attn")
    compiled, params, cache = _compiled_decode_step(cfg, v5e[0])
    assert cache["h"].shape == (5, 32, 5120, 16) and cache["k"].shape == (3, 32, 768, 1, 128)
    # every value the program keeps of a state-space layer's matrices, by its last two
    # sizes: the whole stack of five (a parameter, or a loop's view of it), never one
    # layer's or a run's
    matrices = {(2560, 10240), (5120, 2560), (5120, 192), (160, 5120), (2560, 8192), (8192, 2560)}
    assert matrices <= {tuple(v.shape[1:]) for v in params["ssm_blocks"].values()}
    seen, copies = set(), []
    for dtype, _, line in _buffers(compiled):
        dims = tuple(int(d) for d in re.search(r"= \w+\[([\d,]+)\]", line).group(1).split(","))
        if dtype == "bf16" and dims[-2:] in matrices and len(dims) <= 3:
            if dims[:-2] == (5,):
                seen.add(dims[-2:])
            elif dims[:-2] != (3,):  # the three attention layers' MLPs are a stack of their own
                copies.append(line)
    assert seen == matrices and copies == []


def _assert_the_stacks_are_written_in_place(compiled, cache):
    """A value as large as one of the cache's stacks is that stack written in
    place or moved between memory spaces, never a copy, and nothing writes a
    whole layer's keys or values.  Returns the buffers of a layer's keys' size
    in the chip's memory."""
    stacks = {c.size for c in cache.values()}
    a_layers_keys = cache["k"].size // cache["k"].shape[0]
    seen, a_layers = set(), []
    for _, n, line in _buffers(compiled, width=None):
        shape, op = re.match(r"(?:ROOT )?%[\w.\-]+ = (\S+) ([\w\-]+)\(", line).groups()
        # in the chip's memory: a small stack that the compiler stages in fast memory
        # (space S(1): JAMBA8's 5 and 52 MB of state; a deployment's 272 MB do not fit) is its to move
        if n in stacks and "S(1)" not in shape and op not in ("parameter", "get-tuple-element", "bitcast"):
            in_place = op == "fusion" and '"aliasing_operands":{"lists":[{' in line
            assert in_place or op == "copy-done", line[:200]  # copy-done: back from fast memory
            seen.add(n)
        if n == a_layers_keys:
            assert "dynamic-update-slice" not in line and "scatter" not in line, line[:200]
            if "S(1)" not in shape:  # a small stack on its way to fast memory goes a layer at a time
                a_layers.append(line[:200])
    assert cache["k"].size in seen  # the keys' write was read for what it is
    return a_layers


# Mistral-7B's widths (32 Q / 8 KV heads x 128), four layers deep
MISTRAL4 = dict(
    vocab_size=512, n_layers=4, d_model=4096, n_heads=32, n_kv_heads=8, d_head=128, d_ff=14336,
    param_dtype=jnp.bfloat16,
)


@pytest.mark.parametrize("model", [MISTRAL4, OLMOE3, JAMBA8], ids=["mistral4", "olmoe3", "jamba8"])
def test_decode_step_writes_the_cache_in_place(v5e, model):
    """The decode step of each serving configuration's widths (32 slots, t_max
    768, the cache donated) as the chip's compiler leaves it: the cache is the
    layer loop's carry, one buffer from the argument to the result.  Its
    temporaries hold nothing of a stack's size (given to the loop as a scan's
    `xs` and taken back as its `ys` they held one more whole cache: 0.403 GB of
    0.403 at Mistral's widths, 1.009 of 0.604 at OLMoE's); a value as large as
    a stack of k, v, conv or h is that stack written in place or moved between
    memory spaces, never a copy; and nothing writes a whole layer's keys or
    values [S, T_max, KV, D], of which a step changes one row a slot."""
    cfg = transformer.TransformerConfig(**model)
    compiled, _, cache = _compiled_decode_step(cfg, v5e[0])
    cache_bytes = sum(c.size * c.dtype.itemsize for c in cache.values())
    # a layer's new recurrent state is a value before it is written over the old one
    a_state = generate.recurrent_state_bytes(cache) // max(cfg.layer_kinds.count("ssm"), 1)
    assert compiled.memory_analysis().temp_size_in_bytes < cache_bytes / 10 + a_state
    _assert_the_stacks_are_written_in_place(compiled, cache)


# A.X-K1's widths as one chip of 16 holds a layer (latent attention 64 heads of 128 + 64 over a
# latent of 512, YaRN, a leading dense layer of 18,432, 12 held of 192 sigmoid-routed experts of
# 2,048 and a shared one), three layers deep: benchmarks/configs/a.x-k1-ep16-serve1.json
AXK13 = dict(
    vocab_size=512, n_layers=3, d_model=7168, n_heads=64, d_ff=18432, kv_lora_rank=512, q_lora_rank=1536,
    qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128, rope_factor=32.0, rope_original_max_len=4096,
    rope_mscale_all_dim=1.0, n_dense_layers=1, d_expert=2048, n_experts=192, n_experts_per_tok=8,
    experts_held=(0, 12), moe_gated=True, moe_renormalize=True, moe_scoring="sigmoid", moe_routed_scale=2.5,
    n_shared_experts=1, param_dtype=jnp.bfloat16,
)


@pytest.mark.parametrize("variant", ["fwd", "padded_fwd", "fwd_bwd"])
def test_flash_kernel_compiles_with_a_value_width_of_its_own(v5e, variant):
    """Latent attention's expanded heads: queries and keys 192 wide (128 + the
    rotary 64, not a multiple of the lane width), values 128."""
    one = SingleDeviceSharding(v5e[0])
    q = jax.ShapeDtypeStruct((1, 1024, 8, 192), jnp.bfloat16, sharding=one)
    v = jax.ShapeDtypeStruct((1, 1024, 8, 128), jnp.bfloat16, sharding=one)
    pad = jax.ShapeDtypeStruct((1,), jnp.int32, sharding=one)
    flash = attention.flash_attention
    fn = {"fwd": lambda q, k, v, pad: flash(q, k, v, scale=0.13),
          "padded_fwd": lambda q, k, v, pad: flash(q, k, v, pad=pad, scale=0.13),
          "fwd_bwd": jax.grad(lambda q, k, v, pad: flash(q, k, v).astype(jnp.float32).sum(), (0, 1, 2))}[variant]
    out = jax.eval_shape(fn, q, q, v, pad)
    assert (out.shape if variant != "fwd_bwd" else out[2].shape) == v.shape
    assert _has_kernel(jax.jit(fn).lower(q, q, v, pad).compile())


def test_latent_decode_step_reads_and_writes_the_cache_where_it_lies(v5e):
    """The decode step at A.X-K1's widths and the cell's cache (32 slots x
    4,352): the latent rows and the rotated keys are the layer loop's carry,
    written a row a slot and layer in place and read by the absorbed core as
    stored.  Its temporaries hold nothing of a stack's size: with the rotated
    keys 64 wide (or latent and key in one row of 576) the chip's compiler gave
    the stack a layout of its own and the step copied it in and out, 0.25 GB
    (2.2 GB) a step; padded to the 128 lanes it is read as it lies
    (models/generate.py LATENT_LANES).  The held experts' stacks are seen whole."""
    cfg = transformer.TransformerConfig(**AXK13)
    compiled, _, cache = _compiled_decode_step(cfg, v5e[0], 32, 4352)
    assert set(cache) == {"ckv", "kr"} and cache["kr"].shape == (3, 32, 4352, 128)
    # less than the smaller of the two stacks: no copy of either (58 MB whatever the depth)
    assert compiled.memory_analysis().temp_size_in_bytes < min(c.size * c.dtype.itemsize for c in cache.values())
    stacks = {c.size for c in cache.values()}
    for _, n, line in _buffers(compiled, width=None):
        shape, op = re.match(r"(?:ROOT )?%[\w.\-]+ = (\S+) ([\w\-]+)\(", line).groups()
        if n in stacks and op not in ("parameter", "get-tuple-element", "bitcast"):
            assert op == "fusion" and '"aliasing_operands":{"lists":[{' in line, line[:200]
    held = 2 * 12 * 7168 * 2048
    assert sum(1 for _, n, _ in _buffers(compiled) if n == held) >= 3
    _loops_over_the_held_experts(compiled, 7168 * 2048, 12)
    assert not _has_kernel(compiled)  # the grouped matmul was the one this step had


def test_latent_prefill_expands_through_the_flash_kernel(v5e, on_tpu):
    """An admit's prefill at A.X-K1's widths in the 1,024 bucket: every head's
    keys and values are made of the latents and go through the flash kernel."""
    assert _has_kernel(_compiled_admit_prefill(transformer.TransformerConfig(**AXK13), 1024, 4352, v5e[0]))


# K-EXAONE's widths as one chip of 16 holds a layer (64 query heads on 8 cached heads of 128, a
# window of 128 in three layers of four, a leading dense layer of 18,432, 8 held of 128
# sigmoid-routed experts of 2,048 and a shared one), one period: benchmarks/configs/k-exaone-236b-a23b-ep16-serve1.json
KEXAONE4 = dict(
    vocab_size=512, n_layers=4, d_model=6144, n_heads=64, n_kv_heads=8, d_head=128, d_ff=18432,
    layer_mixers=("attn_win", "attn_win", "attn_win", "attn"), attn_window=128, rotary_full=False, norm_output=True,
    qk_norm=True, qk_norm_per_head=True, rope_theta=1e6, n_dense_layers=1, d_expert=2048, n_experts=128,
    n_experts_per_tok=8, experts_held=(0, 8), moe_gated=True, moe_renormalize=True, moe_scoring="sigmoid",
    moe_routed_scale=2.5, n_shared_experts=1, param_dtype=jnp.bfloat16,
)


def test_window_decode_step_reads_and_writes_both_stacks_where_they_lie(v5e):
    """The decode step at K-EXAONE's widths and the cell's cache (32 slots x
    8,448 in the full layer, x 256 in the three window layers) as the chip runs
    it: both pairs of stacks are the layer loop's carry, written a row a slot
    and layer in place (the ring at pos mod 256) and read by the decode kernel
    as stored, each through its own span.  Its temporaries, 5.5 MB at this
    depth, hold nothing of the cache and nothing of a weight: with the reshape
    to heads folded into the projections they were 106 MB, a copy in another
    layout of the query projection (100 MB a layer) of each stack of one layer
    here (`win_dense_blocks`, `blocks`), and 864 MB at the cell's eight layers
    (`transformer._project_heads`); no buffer has a cache stack's size but the
    donated one, written in place."""
    cfg = transformer.TransformerConfig(**KEXAONE4)
    compiled, _, cache = _compiled_decode_step(cfg, v5e[0], 32, 8448, on_kernel=True)
    assert {n: c.shape for n, c in cache.items()} == {
        "k": (1, 32, 8448, 8, 128), "v": (1, 32, 8448, 8, 128), "kw": (3, 32, 256, 8, 128), "vw": (3, 32, 256, 8, 128)}
    kernels = re.findall(r"%(decode_attn[\w.]*) = \S+ custom-call\(", compiled.as_text())
    assert len(kernels) >= 2 and _has_kernel(compiled)  # a window layers' run and the full layer's
    assert compiled.memory_analysis().temp_size_in_bytes < 8e6  # a tenth of one layer's wq
    stacks = {c.size for c in cache.values()}
    seen = set()
    for _, n, line in _buffers(compiled, width=None):
        shape, op = re.match(r"(?:ROOT )?%[\w.\-]+ = (\S+) ([\w\-]+)\(", line).groups()
        if n in stacks and "S(1)" not in shape and op not in ("parameter", "get-tuple-element", "bitcast"):
            assert op == "fusion" and '"aliasing_operands":{"lists":[{' in line, line[:200]
            seen.add(n)
    assert seen == stacks
    held = 2 * 8 * 6144 * 2048
    assert sum(1 for _, n, _ in _buffers(compiled) if n == held) >= 3  # the held experts' stacks are seen whole
    _loops_over_the_held_experts(compiled, 6144 * 2048, 8)


@pytest.mark.parametrize("bucket", [1024, 8192])
def test_window_prefill_goes_through_the_banded_kernel(v5e, on_tpu, bucket):
    """An admit's prefill at K-EXAONE's widths: the window layers attend
    through the banded kernel under its own name, the full layer through
    `flash_fwd`; the ring keeps the bucket's last 256 columns."""
    compiled = _compiled_admit_prefill(transformer.TransformerConfig(**KEXAONE4), bucket, 8448, v5e[0])
    text = compiled.as_text()
    assert re.search(r"%swa_flash[\w.]* = .* custom-call\(", text) and re.search(r"%flash_fwd[\w.]* = .* custom-call\(", text)
    print(bucket, "temporaries", compiled.memory_analysis().temp_size_in_bytes)


# Phi-4-mini-flash-reasoning's widths and its whole map of 32 layers (a smaller vocabulary: the head is no part of this)
PHI4FLASH = dict(
    vocab_size=512, n_layers=32, d_model=2560, n_heads=40, n_kv_heads=20, d_head=64, d_ff=10240,
    layer_mixers=("ssm", "attn_win") * 8 + ("ssm", "attn") + ("gmu", "attn_cross") * 7, attn_window=512,
    rotary=False, tie_embeddings=True, layer_norm=True, norm_eps=1e-5, attn_bias=True, diff_attn=True,
    ssm_d_state=16, ssm_d_conv=4, ssm_expand=2, ssm_dt_rank=160, ssm_inner_norms=False, param_dtype=jnp.bfloat16,
)


def test_shared_stack_decode_step_reads_the_one_stack_where_it_lies(v5e):
    """The decode step at Phi-4-mini-flash-reasoning's widths and the cell's
    cache (32 slots x 4,096) as the chip runs it: eight layers (the full layer
    and the seven cross layers) attend to ONE stack of keys and values, the
    cross layers keep none of their own, and the program holds no second
    [32, 4096, 20 x 64] anywhere: the stack and the eight rings are the layer
    loop's carry, written a row a slot in place and read by the decode kernel
    as stored, a slot's ten cached pairs as rows.  Kept as [T, 10, 128] the
    stacks were copied whole into the kernel's rows at every layer (2.4 GB of
    temporaries: the chip tiles 10 rows up to 16); flat, the temporaries hold
    nothing of the cache.  The 32 layers are four loops, not 32."""
    cfg = transformer.TransformerConfig(**PHI4FLASH)
    assert len(transformer._layer_runs(cfg.layer_kinds)) == 4
    compiled, _, cache = _compiled_decode_step(cfg, v5e[0], 32, 4096, on_kernel=True)
    assert {n: c.shape for n, c in cache.items()} == {
        "k": (1, 32, 40960, 128), "v": (1, 32, 40960, 128), "kw": (8, 32, 5120, 128), "vw": (8, 32, 5120, 128),
        "conv": (9, 32, 3, 5120), "h": (9, 32, 5120, 16)}
    text = compiled.as_text()
    kernels = re.findall(r"%(decode_attn[\w.]*) = \S+ custom-call\(", text)
    assert len(kernels) == 3 and _has_kernel(compiled)  # the rings' run, the full layer's, the cross layers' run
    assert len(re.findall(r" while\(", text)) <= 6  # four runs of layers (and the sampler's), not one a layer
    assert compiled.memory_analysis().temp_size_in_bytes < 16e6  # a sixth of one layer's w_gate; no logits of 200,064 here
    stack = 32 * 4096 * 20 * 64  # one layer's keys of every slot: the size no temporary may have
    assert cache["k"].size == cache["kw"].size == stack
    for _, n, line in _buffers(compiled, width=None):
        shape, op = re.match(r"(?:ROOT )?%[\w.\-]+ = (\S+) ([\w\-]+)\(", line).groups()
        if n >= stack and "S(1)" not in shape and op not in ("parameter", "get-tuple-element", "bitcast"):
            # the donated stacks written in place; a layer's FFN matrices are as large and are parameters
            assert op == "fusion" and '"aliasing_operands":{"lists":[{' in line, line[:200]


# Nemotron-3-Nano-30B-A3B's widths as one chip of 8 holds a layer (52 layers that are each ONE of a Mamba-2 mixer of
# 64 heads x 64 over a state of 128 in 8 groups, 16 held of 128 sigmoid-routed relu^2 experts of 1,856 with an ungated
# shared expert of 3,712, or attention 32 / 2 x 128 without a positional embedding), the published pattern's first 21
# layers (its (*, [E, M] x 3) twice, so one loop of loops: transformer._run_groups):
# benchmarks/configs/nemotron-3-nano-30b-a3b-ep8-serve1.json
NEMOTRONH21 = dict(
    vocab_size=512, n_layers=21, d_model=2688, n_heads=32, n_kv_heads=2, d_head=128, d_ff=1856, rotary=False,
    norm_eps=1e-5, layer_mixers=tuple({"M": "mamba2", "E": "ffn", "*": "attn_alone"}[m] for m in "MEMEM*EMEMEM*EMEMEM*E"),
    ssm_n_heads=64, ssm_head_dim=64, ssm_n_groups=8, ssm_d_state=128, ssm_chunk=128, n_experts=128, n_experts_per_tok=6,
    experts_held=(0, 16), moe_act="relu2", moe_renormalize=True, moe_scoring="sigmoid", moe_routed_scale=2.5,
    d_expert=1856, n_shared_experts=1, d_shared=3712, param_dtype=jnp.bfloat16,
)


def test_half_layer_decode_step_updates_the_state_and_reads_two_flat_heads_where_they_lie(v5e):
    """The decode step at Nemotron-3-Nano's widths and the cell's cache (32 slots
    x 4,096) as the chip runs it.  The Mamba-2 layers' state, [6, 32, 64, 64, 128]
    float32 (0.6 GB here, 1.5 GB over the published 23), is the layer loop's
    carry, read and written a layer at a time in place, through a loop of loops
    too: the program holds no second copy of it, nor of a layer's.  The two
    cached heads' stacks are flat ([T x 2, 128] a slot: 2 is no multiple of the
    chip's 8 sublanes, and as [T, 2, 128] every layer's stack was copied into
    the kernel's rows) and are written a row a slot in place.  The held experts
    are read an expert a turn of the loop over those that were given a row
    (parallel/moe.py FEW_ROWS), each matrix sliced out of the stack inside the
    product that reads it: no grouped matmul, and no buffer of a matrix's size.
    The mixers' in-projection, 10,304 wide, is stored 10,368 wide: at 10,304
    the chip laid the stack out with the model's width innermost and the loop
    of loops took it with its last axis innermost, a copy of every layer's
    matrix, 1.27 GB over the published 23, at every step."""
    cfg = transformer.TransformerConfig(**NEMOTRONH21)
    runs = transformer._layer_runs(cfg.layer_kinds)
    assert len(runs) == 8  # [M, E] x 2, M, *, [E, M] x 3, *, [E, M] x 3, *, E
    assert [reps for _, reps in transformer._run_groups(runs)] == [1, 1, 2, 1, 1]
    compiled, params, cache = _compiled_decode_step(cfg, v5e[0], 32, 4096, on_kernel=True)
    assert {n: c.shape for n, c in cache.items()} == {
        "k": (3, 32, 8192, 128), "v": (3, 32, 8192, 128), "conv": (9, 32, 3, 6144), "h": (9, 32, 64, 64, 128)}
    assert params["ffn_blocks"]["w_in"].shape == (9, 16, 2688, 1920) and params["ffn_blocks"]["w_out"].shape == (9, 16, 1856, 2688)
    assert params["mamba2_blocks"]["ssm_in"].shape == (9, 2688, 10368)
    text = compiled.as_text()
    assert len(re.findall(r"%(decode_attn[\w.]*) = \S+ custom-call\(", text)) == 2 and _has_kernel(compiled)
    assert "ragged-dot" not in text
    # a sixth of one layer's in_proj: nothing of the state's, a stack's or an expert's size
    assert compiled.memory_analysis().temp_size_in_bytes < 16e6
    a_state, a_stack, an_expert = 32 * 64 * 64 * 128, 32 * 8192 * 128, 2688 * 1856
    for dtype, n, line in _buffers(compiled, width=None):
        shape, op = re.match(r"(?:ROOT )?%[\w.\-]+ = (\S+) ([\w\-]+)\(", line).groups()
        if n >= min(a_state, a_stack) and "S(1)" not in shape and op not in ("parameter", "get-tuple-element", "bitcast"):
            # the donated state and stacks written in place, or a view of a weight's stack inside the loop
            in_place = op == "fusion" and '"aliasing_operands":{"lists":[{' in line
            assert in_place or op in ("while", "tuple", "copy-done", "copy-start"), line[:200]
        if dtype == "bf16" and n >= an_expert:  # no copy of an expert's matrix, nor of a layer's experts, in any layout
            assert op in ("parameter", "get-tuple-element", "bitcast", "while", "tuple") or in_place_stack(line), line[:200]


def in_place_stack(line) -> bool:
    """A fusion that writes one of the cache's stacks where it lies."""
    return " fusion(" in line and '"aliasing_operands":{"lists":[{' in line


def _loops_over_the_held_experts(compiled, matrix: int, held: int) -> None:
    """A held share's decode step loops over the experts that were given a row (parallel/moe.py FEW_ROWS): it
    holds no grouped matmul, and each of a gated expert's three matrices (`matrix` elements) is sliced out of
    the stack inside the product that reads it: nothing of a matrix's size or of a layer's `held` is made in HBM
    (a view of a stack may have that size; a layer's shared expert's matrix is fetched ahead into fast memory)."""
    assert "ragged-dot" not in compiled.as_text()
    for dtype, n, line in _buffers(compiled, width=None):
        shape, op = re.match(r"(?:ROOT )?%[\w.\-]+ = (\S+) ([\w\-]+)\(", line).groups()
        if dtype == "bf16" and n in (matrix, held * matrix) and "S(1)" not in shape:
            assert op in ("parameter", "get-tuple-element", "bitcast", "while", "tuple"), line[:200]


def _computations(text):
    """({name: its instructions' lines}, the entry's name) of an optimized program's text."""
    comps, entry, inside = {}, None, None
    for line in text.splitlines():
        head = re.match(r"(ENTRY )?%([\w.\-]+) \(.*\{$", line)
        if head:
            inside = head.group(2)
            comps[inside] = []
            entry = inside if head.group(1) else entry
        elif inside is not None and line.startswith("  "):
            comps[inside].append(line)
    return comps, entry


@pytest.mark.parametrize("model", [MISTRAL4, OLMOE3, JAMBA8], ids=["mistral4", "olmoe3", "jamba8"])
def test_decode_step_sorts_the_vocabulary_only_under_a_conditional(v5e, model):
    """The decode step of each serving configuration's widths as the chip's
    compiler leaves it: the sampler's two sorts of [32, vocabulary] (top-k's
    k-th largest, the nucleus's cumulative mass) are in the computation that
    one `conditional` calls as its third branch, for a step in which a sampling
    row asks top-k or top-p; none is in the entry computation, the layer loop
    or anything else a step always runs, nor in the branches of a greedy step
    and of a draw without truncation.  They were 1.7 ms of Mistral's 16.5 ms
    step on the chip, 3.3 of OLMoE's 18.4 and 3.9 of Jamba's 15.2, at
    temperature 0.  (A mixture's own sorts, the router's k largest of 64 and
    the 256 assignments by expert, are the layer loop's and stay.)"""
    cfg = transformer.TransformerConfig(**model)
    compiled, _, _ = _compiled_decode_step(cfg, v5e[0])
    comps, entry = _computations(compiled.as_text())

    def reached(roots):
        """The computations `roots` call, through anything but a conditional's choice."""
        seen, todo = set(), list(roots)
        while todo:
            name = todo.pop()
            if name not in seen:
                seen.add(name)
                todo += [n for line in comps[name] if " conditional(" not in line
                         for n in re.findall(r"%([\w.\-]+)", line.split(" = ", 1)[-1]) if n in comps]
        return seen

    def wide_sorts(names):
        return [line.strip()[:120] for name in names for line in comps[name]
                if re.search(rf"= \(?f32\[32,{cfg.vocab_size}\][^=]*? sort\(", line)]

    always = reached([entry])
    assert len(always) > 10 and wide_sorts(always) == []
    (choice,) = [line for name in always for line in comps[name] if " conditional(" in line]
    assert 'op_name="jit(<lambda>)/sample/cond"' in choice
    branches = re.search(r"branch_computations=\{([^}]*)\}", choice).group(1).replace("%", "").split(", ")
    assert [len(wide_sorts(reached([b]))) for b in branches] == [0, 0, 2]


@pytest.mark.parametrize("model", ["kexaone", "axk1"])
def test_a_held_shares_prefill_keeps_every_assignments_row_under_a_conditional(v5e, on_tpu, model):
    """An admit's prefill in the 4,096 bucket at the widths of the two
    configurations that hold a share of their experts, as the chip's compiler
    leaves it: the expert layer works on a compact buffer of 4 x the even share
    of the sorted rows (8,192 of 32,768), and an array of all N x k rows of the
    model's width ([32768, E] in any type: the gathered rows, the experts'
    result, its float32 copies in the combine, 0.4 to 1.6 GB each at 8,192) is
    only in the computation that one `conditional` calls where more rows fell
    on the share than the buffer holds, never in what every admit runs."""
    cfg = transformer.TransformerConfig(**(KEXAONE4 if model == "kexaone" else AXK13))
    bucket, k, e = 4096, cfg.n_experts_per_tok, cfg.d_model
    compiled = _compiled_admit_prefill(cfg, bucket, bucket + 256, v5e[0])
    comps, entry = _computations(compiled.as_text())
    fused = set(re.findall(r"kind=k\w+, calls=%([\w.\-]+)", compiled.as_text()))

    def reached(roots):
        """The computations `roots` call, through anything but a conditional's choice."""
        seen, todo = set(), list(roots)
        while todo:
            name = todo.pop()
            if name not in seen:
                seen.add(name)
                todo += [n for line in comps[name] if " conditional(" not in line
                         for n in re.findall(r"%([\w.\-]+)", line.split(" = ", 1)[-1]) if n in comps]
        return seen

    def every_rows(names):
        """Values of [N x k, E] that a computation keeps in memory (not a fusion's inside)."""
        return [line.strip()[:100] for name in names - fused for line in comps[name]
                if re.search(rf"= \(?\w+\[{bucket * k},{e}\]", line)]

    always = reached([entry])
    assert len(always) > 10 and every_rows(always) == []
    # one conditional a run of expert layers (its layer loop's body): branch 0, `false`, keeps all
    # N x k rows (gathered, through the experts, twice in float32), branch 1 the compact buffer
    choices = [line for name in always for line in comps[name] if " conditional(" in line]
    assert len(choices) == (2 if model == "kexaone" else 1)
    for choice in choices:
        branches = re.search(r"branch_computations=\{([^}]*)\}", choice).group(1).replace("%", "").split(", ")
        kept = [len(every_rows(reached([b]))) for b in branches]
        assert kept[0] >= 3 and kept[1] == 0, kept
    compact = bucket * k * cfg.experts_held[1] * 4 // cfg.n_experts
    assert any(re.search(rf"= \(?bf16\[{compact},{e}\]", line) for b in reached([branches[1]]) - fused for line in comps[b])


# SDAR-30B-A3B's widths (128 experts of 2048 x 768, 8 a token, 32 Q / 4 KV heads
# x 128 normalised a head, the whole vocabulary of 151,936), three layers deep:
# an answer is made a block of 4 positions at a time
SDAR3 = dict(
    vocab_size=151936, n_layers=3, d_model=2048, n_heads=32, n_kv_heads=4, d_head=128, d_ff=768,
    n_experts=128, n_experts_per_tok=8, moe_gated=True, moe_renormalize=True, qk_norm=True,
    qk_norm_per_head=True, rope_theta=1e6, param_dtype=jnp.bfloat16,
    block_length=4, mask_token_id=151669, denoise_steps=4,
)


def test_block_step_writes_its_rows_in_place_and_sorts_no_vocabulary(v5e):
    """The step of a model that generates by blocks (one pass of every slot's
    block of 4 and, in it, the block before's final tokens: 8 positions a slot,
    32 slots, t_max 768, the cache donated) as the chip's compiler leaves it:
    the cache is the layer loop's carry as in the causal step, the 8 rows a slot
    and layer are written into the whole stacks in place (the 4 of a slot with
    nothing pending dropped by the scatter, not written back as they were),
    nothing is a copy of a stack or writes a whole layer's keys, and the choice of what
    a pass fixes (a maximum and a log-sum-exp a position) sorts nothing of the
    vocabulary's size: the sorts are the expert path's, the router's k largest of
    128 probabilities a row and the 32 x 8 x 8 assignments by expert.  The head
    runs over the block alone: the float32 logits are [32, 4, V], 78 MB, and no
    value of the program holds logits of 8 positions a slot."""
    cfg = transformer.TransformerConfig(**SDAR3)
    slots, b = 32, cfg.block_length
    compiled, _, cache = _compiled_decode_step(cfg, v5e[0], slots=slots)
    assert _has_kernel(compiled)
    cache_bytes = sum(c.size * c.dtype.itemsize for c in cache.values())
    logits_bytes = slots * b * cfg.vocab_size * 4
    # the logits of every position in float32, and the noise of a sampled pass beside them
    assert compiled.memory_analysis().temp_size_in_bytes < cache_bytes / 10 + 3 * logits_bytes
    _assert_the_stacks_are_written_in_place(compiled, cache)
    sorts = [int(math.prod(int(d) for d in m.split(",")))
             for m in re.findall(r"= \(?\w+\[([\d,]+)\][^=]*? sort\(", compiled.as_text())]
    assert sorts and max(sorts) <= slots * 2 * b * cfg.n_experts < cfg.vocab_size, sorts
    held = _buffers(compiled, width=None)
    assert [line[:200] for _, n, line in held if n == slots * 2 * b * cfg.vocab_size] == []
    assert [line for dtype, n, line in held if dtype == "f32" and n == slots * b * cfg.vocab_size]


# Jamba2-3B's widths at the depth and in the order `jamba-closed6` runs them: 28 layers, attention
# at 7 and 21.  The kernel's step is read at this depth, not at JAMBA8's: there the three attention
# layers' keys are a stack of 19 MB, which the compiler may stage whole in fast memory around a
# row's write (it does once no layer's wq is copied there), and that buffer counts as a temporary
JAMBA28 = dict(JAMBA8, n_layers=28, attn_layer_period=14, attn_layer_offset=7)


@pytest.mark.parametrize("model", ["MISTRAL4", "OLMOE3", "SDAR3", "JAMBA28"])
def test_decode_step_attends_through_the_kernel_over_the_stacks_as_stored(v5e, model):
    """The decode step of each serving configuration's widths as the chip runs
    it (32 slots x 768; SDAR's is a pass of blocks of 4): the attention core is
    the decode kernel (`ops/attention.py decode_attention`), which takes the
    whole stacks of keys and values and the layer's index.  No buffer of a
    layer's K or V [S, T_max, KV, D] exists (read out of the stack for the
    dense contraction it was 50-200 MB a layer and step), and none of a stack's
    size but the donated one, written in place: the kernel's view of a slot's
    cached heads as rows one after the other, [T_max * KV, D], is the stack as
    it lies, whatever tiling the compiler gave KV of 16, 8, 4 and 1."""
    cfg = transformer.TransformerConfig(**globals()[model])
    compiled, _, cache = _compiled_decode_step(cfg, v5e[0], on_kernel=True)
    kernels = re.findall(r"%(decode_attn[\w.]*) = \S+ custom-call\(", compiled.as_text())
    assert kernels and _has_kernel(compiled)
    assert _assert_the_stacks_are_written_in_place(compiled, cache) == []
    cache_bytes = sum(c.size * c.dtype.itemsize for c in cache.values())
    logits = 32 * cfg.block_length * cfg.vocab_size * 4 if cfg.generates_blocks else 0
    a_state = generate.recurrent_state_bytes(cache) // max(cfg.layer_kinds.count("ssm"), 1)
    assert compiled.memory_analysis().temp_size_in_bytes < cache_bytes / 10 + a_state + 3 * logits
    # the dense contraction's program, which the other tests of the step read, has no such kernel
    dense, _, _ = _compiled_decode_step(cfg, v5e[0])
    assert "decode_attn" not in dense.as_text()


# the widths -> the slots and extent of the cell's cache, where they are not `_compiled_decode_step`'s own
# (said as the other tests of a step say them: one compile a model)
_PROJECTED = {"MISTRAL4": (), "OLMOE3": (), "SDAR3": (), "JAMBA28": (), "KEXAONE4": (32, 8448), "AXK13": (32, 4352)}


@pytest.mark.parametrize("model", list(_PROJECTED))
def test_decode_step_multiplies_by_the_projections_where_they_are_stored(v5e, model):
    """The decode step at each serving configuration's widths (32 slots; SDAR's
    is `_pass_step_rowpos`, a pass over blocks of 4; Jamba's at its cell's 28
    layers, two of them attention) as the chip's compiler leaves it: of the size of a layer's wq, wk or wv (under latent attention
    wq_b) it keeps the stacks themselves, seen by the layer loop or moved whole
    between memory spaces, and nothing else: each projection is one fusion that
    takes the stack and the layer's index.  With the reshape to heads folded
    into the product (`transformer._project_heads`) every layer's matrix was
    sliced out of its stack into fast memory by one fusion and transposed by a
    copy before its product read it: `constant_dynamic-slice_fusion`
    bf16[1,4096,4096] and `copy` bf16[1,4096,4096]{1,2,0} at Mistral's widths,
    2.2 ms of an 11.3 ms step; a stack of one layer was copied in HBM, 100 MB
    at K-EXAONE's.  (Latent attention's wkv_b, which the absorbed core
    multiplies a head at a time, is still copied a layer: ROADMAP S18.)"""
    cfg = transformer.TransformerConfig(**globals()[model])
    compiled, params, _ = _compiled_decode_step(cfg, v5e[0], *_PROJECTED[model], on_kernel=True)
    names = ("wq_b",) if cfg.latent else ("wq", "wk", "wv")
    every = transformer.layer_stacks(params).values()
    stacks = {stack[n].shape for stack in every for n in names if n in stack}
    whole = {a.shape for stack in every for a in stack.values()}  # wo's has wq's sizes the other way round
    # a value is a layer's matrix by its last two sizes, either way round (the copies were the transpose)
    matrices = {shape[1:] for shape in stacks} | {shape[:0:-1] for shape in stacks}
    seen, made = set(), []
    for dtype, _, line in _buffers(compiled, width=None):
        shape, op = re.match(r"(?:ROOT )?%[\w.\-]+ = (\S+) ([\w\-]+)\(", line).groups()
        dims = tuple(int(d) for d in re.match(r"\w+\[([\d,]+)\]", shape).group(1).split(","))
        if dtype != "bf16" or len(dims) not in (2, 3) or dims[-2:] not in matrices:
            continue
        if op in ("parameter", "get-tuple-element", "bitcast"):
            seen.add(dims)
        elif not ((op in ("copy-done", "slice-done") or 'custom_call_target="ConcatBitcast"' in line)
                  and dims in whole and "{2,1,0:" in shape):
            # a whole stack on its way to fast memory as it is stored is the compiler's to move, in one piece or (K-EXAONE's
            # window layers' wk since the experts' conditional left the step) in two that a bitcast joins there
            made.append(line[:200])
    assert stacks <= seen and made == []


def _under_rope(compiled):
    """(dtype, elements, operation, instruction) of every value the optimized
    program keeps in memory under the scope `attn.rope`."""
    return [(dtype, n, re.match(r"(?:ROOT )?%[\w.\-]+ = \S+ ([\w\-]+)\(", line).group(1), line[:200])
            for dtype, n, line in _buffers(compiled, width=None) if "attn.rope" in line]


@pytest.mark.parametrize("model", ["MISTRAL4", "KEXAONE4", "SDAR3", "AXK13"])
def test_decode_step_turns_the_pairs_where_they_lie(v5e, model):
    """The decode step at the rotating serving configurations' widths as the
    chip's compiler leaves it: under `attn.rope` q and k are each one fusion,
    the product with the pair-swap matrix and the turn its epilogue
    (`transformer._turn`), and nothing there is as large as q in float32.  On
    the strided halves Mistral's step held two float32 copies of q and of k,
    a pad and two more copies in bf16 a layer (`copy` f32[32,32,128]{1,0,2},
    `pad_maximum_fusion` bf16[32,1,32,64,2], `copy` bf16[32,1,32,64,2]).  Where
    a row is one position and a head 128 wide, no `copy` at all; a pass over
    blocks of 4 positions still copies its 4 cached heads' k (128 kB) into the
    cache's tiling, and the latent model slices its rotary 64 out of 192."""
    cfg = transformer.TransformerConfig(**globals()[model])
    compiled, _, _ = _compiled_decode_step(cfg, v5e[0], *_PROJECTED[model], on_kernel=True)
    held = _under_rope(compiled)
    # a pass of blocks turns the block before's positions with its own: 2 x 4 a slot
    q = 32 * max(2 * cfg.block_length, 1) * cfg.n_heads * cfg.rope_dim
    assert any(op == "fusion" and dtype == "bf16" and n == q for dtype, n, op, _ in held)
    assert [line for dtype, n, _, line in held if dtype == "f32" and n >= q] == []
    assert [line for _, _, op, line in held if op in ("pad", "gather", "scatter")] == []
    if model in ("MISTRAL4", "KEXAONE4"):
        assert [line for _, _, op, line in held if op == "copy"] == []


def test_a_cache_of_one_row_keeps_the_dense_contraction(v5e, on_tpu):
    """An admit's suffix step (one request's rows, a cache of batch one) at
    Mistral's widths: no decode kernel, and nothing of a stack's size beside
    the donated rows.  Through the kernel the chip's compiler gave the carried
    stacks of batch one a layout of its own and copied both to the kernel's at
    every layer (0.8 GB of temporaries at 16 layers)."""
    from cluster_anywhere_tpu.llm import continuous

    cfg = transformer.TransformerConfig(**MISTRAL4)
    one = SingleDeviceSharding(v5e[0])
    on_chip = lambda tree: jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one), tree)
    params = on_chip(jax.eval_shape(lambda k: transformer.init_params(k, cfg), jax.random.key(0)))
    rows = on_chip(jax.eval_shape(lambda: generate.init_cache(cfg, 1, 768)))
    row = on_chip(jax.ShapeDtypeStruct((1,), jnp.int32))
    fn = lambda *a: continuous._suffix_step.__wrapped__(*a, cfg=cfg)
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(params, rows, row, row, row).compile()
    assert "decode_attn" not in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < rows["k"].size * 2 / 2


@pytest.mark.parametrize("bucket", [64, 512])
def test_block_masked_prefill_compiles(v5e, on_tpu, bucket):
    """The admit's prefill of a model that generates by blocks, at the shortest
    and the longest bucket of the serving cell: the flash kernel under the block
    mask (at 64 through the dispatcher's left pad to a tile) and the experts
    read where they are."""
    compiled = _compiled_admit_prefill(transformer.TransformerConfig(**SDAR3), bucket, 768, v5e[0])
    assert _has_kernel(compiled)


def _compiled_train_step(cfg, mesh, batch, seq):
    """(the train step of `make_train_step` over AdamW compiled for the mesh's described devices, its parameters'
    shapes as sharded there): the weights and moments under `param_specs`, `batch` sequences of `seq` + 1 ids."""
    import optax

    step, _ = transformer.make_train_step(cfg, mesh)
    sharded = lambda tree: jax.tree_util.tree_map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=NamedSharding(mesh, s)),
        tree, transformer.param_specs(cfg), is_leaf=lambda x: isinstance(x, P),
    )
    params = sharded(
        jax.eval_shape(lambda k: transformer.init_params(k, cfg), jax.random.key(0))
    )
    adam = jax.eval_shape(optax.adamw(3e-4, weight_decay=0.01).init, params)
    count = jax.ShapeDtypeStruct((), jnp.int32, sharding=NamedSharding(mesh, P()))
    opt_state = tuple(
        s._replace(mu=sharded(s.mu), nu=sharded(s.nu), count=count) if hasattr(s, "mu") else s
        for s in adam
    )
    ids = jax.ShapeDtypeStruct((batch, seq + 1), jnp.int32, sharding=transformer.make_batch_sharding(cfg, mesh))
    return jax.jit(step, donate_argnums=(0, 1)).lower(params, opt_state, {"ids": ids}).compile(), params


@pytest.mark.parametrize(
    "spec", [MeshSpec(dp=4), MeshSpec(fsdp=2, tp=2)], ids=["dp4", "fsdp2_tp2"]
)
def test_train_step_compiles_on_a_mesh(v5e, on_tpu, spec):
    """GSPMD cannot partition a Mosaic kernel: the step compiles on a
    multi-device mesh only with the kernel under shard_map."""
    cfg = transformer.TransformerConfig(
        vocab_size=32000, n_layers=2, max_seq_len=1024, unroll_layers=False, **FLAGSHIP
    )
    mesh = Mesh(np.asarray(v5e).reshape(spec.axis_sizes()), AXES)
    compiled, params = _compiled_train_step(cfg, mesh, 8, 1024)
    assert _has_kernel(compiled)
    # forward, recomputation and backward turn q and k where they lie (`transformer._turn`): nothing under
    # `attn.rope` is as large as a device's q in float32 (the strided halves' copies, their gradient's pad)
    held = _under_rope(compiled)
    q = 8 // (spec.dp * spec.fsdp) * 1024 * cfg.n_heads // spec.tp * cfg.d_head
    assert held  # the scope is seen
    assert [line for dtype, n, op, line in held if (dtype == "f32" and n >= q) or op in ("pad", "gather", "scatter")] == []
    # params + Adam moments really are spread: a quarter each under fsdp x tp
    if spec.fsdp * spec.tp == 4:
        whole = 3 * sum(x.size * 4 for x in jax.tree_util.tree_leaves(params))
        assert compiled.memory_analysis().argument_size_in_bytes < 0.3 * whole


V5E_BYTES_LIMIT = 16909336064  # `memory_stats()["bytes_limit"]` of a v5e chip (PERF.md section 6, PR 53)


def test_the_train_cells_step_keeps_the_attention_half_and_fits(v5e, on_tpu, monkeypatch):
    """`train-fsdp4`'s step as one of its four chips runs it: Mistral-7B's
    widths 12 layers deep, fsdp=4, 8 x 4,096 tokens, `remat`.  Under a v5e's
    limit the checkpointed blocks keep their attention halves, and the last
    three the FFN's two up products beside them (`transformer._remat_keeps`; a
    described device reports no limit, so the test states it): the compiled
    step calls `flash_fwd` once a layer and each backward kernel once; of an
    `attn.*` scope the recomputation holds only `_gqa_repeat`'s k and v and the
    rotary tables, nothing under `attn.qkv` or `attn.out` and nothing of q's
    size [2, 4096, 32, 128]; it makes the FFN's up products again in the nine
    layers that did not keep them, twice nine and no more (the compiler, short
    of room, would make more on its own); the head and loss hold no value of
    rows x vocabulary in float32; and the state and the temporaries together
    stay under the limit."""
    monkeypatch.setattr(transformer, "_memory_limit", lambda mesh: V5E_BYTES_LIMIT)
    rule, said = transformer._remat_keeps, []
    monkeypatch.setattr(transformer, "_remat_keeps", lambda *a, **k: said.append(rule(*a, **k)) or said[-1])
    cfg = transformer.TransformerConfig(**dict(MISTRAL4, vocab_size=32768, n_layers=12, max_seq_len=4096, rope_theta=1e6,
                                               param_dtype=jnp.float32, remat=True))
    mesh = Mesh(np.asarray(v5e).reshape(MeshSpec(fsdp=4).axis_sizes()), AXES)
    compiled, _ = _compiled_train_step(cfg, mesh, 8, 4096)
    (keeps,) = said
    assert keeps.names and keeps.ffn_layers == 3
    text = compiled.as_text()
    calls = {name: len(re.findall(rf"custom-call.*{name}", text)) for name in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")}
    assert calls == dict.fromkeys(calls, cfg.n_layers)
    again = re.findall(r"= (\w+)\[([\d,]*)\][^\n]*rematted_computation/(attn\.[\w.]+)/", text)
    assert {scope for _, _, scope in again} == {"attn.core", "attn.rope"}
    q = 2 * 4096 * 32 * 128
    sizes = lambda scope: {(dtype, shape) for dtype, shape, s in again if s == scope and math.prod(map(int, shape.split(","))) * 4 >= q}
    assert sizes("attn.rope") == set() and sizes("attn.core") == {("bf16", "2,4096,8,4,128")}
    up_products = re.findall(r"= bf16\[2,4096,14336\]\S* (?:convolution|fusion)\([^\n]*rematted_computation/ffn/dot_general", text)
    assert len(up_products) == 2 * (cfg.n_layers - keeps.ffn_layers)
    assert "f32[2,4096,32768]" not in text and re.search(r"bf16\[8,2,512,32768\]", text)  # the loss's gradient, by chunks
    memory = compiled.memory_analysis()
    held = memory.argument_size_in_bytes + memory.output_size_in_bytes - memory.alias_size_in_bytes + memory.temp_size_in_bytes
    assert 3 * 2.88e9 < memory.argument_size_in_bytes and held < V5E_BYTES_LIMIT
    # the names' 2.83 + 1.41 GB are in the temporaries
    kept = 12 * transformer._kept_bytes(cfg, 2 * 4096) + keeps.ffn_layers * transformer._kept_ffn_bytes(cfg, 2 * 4096)
    assert 0.9 * kept + 3.5e9 < memory.temp_size_in_bytes


# Keye-VL-2.0's language model as `keye-longdoc-closed4` serves it: all 48 layers, 16 of 128 experts held, an eighth
# of the vocabulary, an indexer of 16 heads x 64 and a top-2,048 selection (learned sparse attention)
KEYE = dict(
    vocab_size=18992, n_layers=48, d_model=2048, n_heads=32, n_kv_heads=4, d_head=128, d_ff=6144, d_expert=768,
    n_experts=128, n_experts_per_tok=8, moe_gated=True, moe_renormalize=True, experts_held=(0, 16), qk_norm=True,
    qk_norm_per_head=True, rope_theta=1e7, index_topk=2048, index_n_heads=16, index_head_dim=64,
    param_dtype=jnp.bfloat16,
)
KEYE_SLOTS, KEYE_T_MAX = 4, 8704


@pytest.fixture
def sparse_on_tpu(monkeypatch):
    monkeypatch.setattr(attention, "_platform", lambda: "tpu")  # ops/sparse_attention.py asks the same function


def test_the_sparse_decode_step_gathers_the_selected_rows_and_fits(v5e):
    """The decode step of 4 slots x 8,704: the two stacks of the cache (keys and values in one, the indexer's keys)
    are written in place, the step holds ONE gather of [4, 2048, 8, 128] a layer (a row's selected keys and values
    together) and a top-k over [4, 8704], copies nothing of a stack's size, and weights and cache fit the chip."""
    cfg = transformer.TransformerConfig(**KEYE)
    compiled, _, cache = _compiled_decode_step(cfg, v5e[0], slots=KEYE_SLOTS, t_max=KEYE_T_MAX, on_kernel=True)
    assert set(cache) == {"kv", "ki"} and cache["kv"].shape == (48, 4, 8704, 8, 128) and cache["ki"].shape == (48, 4, 64, 8704)
    text = compiled.as_text()
    assert len(re.findall(r"= bf16\[4,2048,8,128\]\S* gather\(", text)) == 1
    assert re.search(r"sort\([^\n]*attn\.select", text) and "decode_attn" not in text
    _loops_over_the_held_experts(compiled, 2048 * 768, 16)
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= sum(math.prod(a.shape) * 2 for a in cache.values())
    assert memory.temp_size_in_bytes < 64e6
    assert 13.0e9 < memory.argument_size_in_bytes + memory.temp_size_in_bytes < V5E_BYTES_LIMIT


@pytest.mark.parametrize("bucket", [4096, 8192])
def test_the_sparse_prefill_goes_through_its_three_kernels_and_fits(v5e, sparse_on_tpu, bucket):
    """An admit's prefill of the cell's two buckets: `dsa_index`, `dsa_select` and `dsa_flash` once a layer loop, and
    with the weights, the rows it hands back, its temporaries and the replica's cache beside it under the chip's limit."""
    cfg = transformer.TransformerConfig(**KEYE)
    compiled = _compiled_admit_prefill(cfg, bucket, KEYE_T_MAX, v5e[0])
    text = compiled.as_text()
    assert all(len(re.findall(rf"custom-call.*{name}", text)) == 1 for name in ("dsa_index", "dsa_select", "dsa_flash"))
    assert "flash_fwd" not in text
    memory = compiled.memory_analysis()
    cache = jax.eval_shape(lambda: generate.init_cache(cfg, KEYE_SLOTS, KEYE_T_MAX))
    beside = sum(math.prod(a.shape) * 2 for a in cache.values())
    assert beside == KEYE_SLOTS * KEYE_T_MAX * 104_448
    held = memory.argument_size_in_bytes + memory.output_size_in_bytes + memory.temp_size_in_bytes + beside
    assert 9.4e9 < memory.argument_size_in_bytes and held < V5E_BYTES_LIMIT


# Kimi-Linear-48B-A3B's widths (KDA 32 x 128 with a convolution of 4, latent attention 512 + 64 with a direct query
# and no rotation, 16 of 256 sigmoid experts of 1024 held, a dense first FFN of 9216), five layers deep: the dense
# first layer, a run of two KDA layers, a latent layer, a KDA layer
KIMI5 = dict(
    vocab_size=512, n_layers=5, d_model=2304, n_heads=32, n_kv_heads=32, d_head=192, d_ff=9216,
    layer_mixers=("kda", "kda", "kda", "attn", "kda"), rotary=False, norm_eps=1e-5,
    kv_lora_rank=512, q_lora_rank=0, qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
    kda_n_heads=32, kda_head_dim=128, ssm_d_conv=4,
    n_dense_layers=1, d_expert=1024, n_shared_experts=1, n_experts=256, n_experts_per_tok=8, moe_gated=True,
    moe_renormalize=True, moe_scoring="sigmoid", moe_routed_scale=2.446, experts_held=(0, 16), param_dtype=jnp.bfloat16,
)


def test_kda_decode_step_moves_the_matrix_state_on_through_the_kernel_where_it_lies(v5e):
    """The decode step at Kimi Linear's widths and the cell's cache (32 slots x 4,096) as the chip runs it.  The KDA
    layers' matrix state, [4, 32, 32, 128, 128] float32 (0.27 GB here, 1.34 GB over the published 20), is handed to
    ops/kda.py's kernel as the stack it is, once a run of layers' loop body, and is the kernel's result in place: the
    program holds no copy of it, nor of a layer's (67 MB: in plain XLA a layer's state was taken out of the stack,
    read twice and written back, 6.3 ms of a 17.7 ms step).  The latent layer's cache is written a row a slot in
    place, and nothing of its size is copied."""
    cfg = transformer.TransformerConfig(**KIMI5)
    assert [r[2] for r in transformer._layer_runs(cfg.layer_kinds)] == [1, 2, 1, 1]
    compiled, params, cache = _compiled_decode_step(cfg, v5e[0], 32, 4096, on_kernel=True)
    assert {n: c.shape for n, c in cache.items()} == {
        "ckv": (1, 32, 4096, 512), "kr": (1, 32, 4096, 128), "conv": (4, 32, 3, 12288), "h": (4, 32, 32, 128, 128)}
    text = compiled.as_text()
    assert len(re.findall(r"%kda_update[\w.]* = .* custom-call\(", text)) == 3 and _has_kernel(compiled)
    a_state = 32 * 32 * 128 * 128
    for dtype, n, line in _buffers(compiled, width=None):
        shape, op = re.match(r"(?:ROOT )?%[\w.\-]+ = (\S+) ([\w\-]+)\(", line).groups()
        if dtype == "f32" and n >= a_state:
            assert op in ("parameter", "get-tuple-element", "bitcast", "while", "tuple", "custom-call"), line[:200]
    _loops_over_the_held_experts(compiled, 2304 * 1024, 16)
    assert compiled.memory_analysis().temp_size_in_bytes < 64e6
