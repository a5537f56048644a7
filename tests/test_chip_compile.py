"""Ahead-of-time compiles for a described (not attached) TPU v5e: what the
chip's compiler refuses, it refuses here, at no chip time.  Interpret mode
cannot see these failures (a block the lowering rejects, a kernel GSPMD may
not partition).  Nothing runs, so these say nothing about results or times.

The dispatcher asks `jax.default_backend()`, which is the CPU here, so the
tests that go through it steer `_platform` themselves.
"""

import importlib
import os

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


@pytest.mark.parametrize("bucket", [128, 256])
def test_padded_prefill_compiles_at_flagship_width(v5e, on_tpu, bucket):
    """The continuous batcher's admit: a batch-1 left-padded prompt."""
    cfg = transformer.TransformerConfig(vocab_size=259, n_layers=8, **FLAGSHIP)
    one = SingleDeviceSharding(v5e[0])
    on_chip = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one)
    params = jax.tree_util.tree_map(
        on_chip, jax.eval_shape(lambda k: transformer.init_params(k, cfg), jax.random.key(0))
    )
    ids = jax.ShapeDtypeStruct((1, bucket), jnp.int32, sharding=one)
    pad = jax.ShapeDtypeStruct((1,), jnp.int32, sharding=one)
    fn = lambda p, i, pad: generate.prefill(p, i, cfg, bucket + 32, pad=pad)
    assert _has_kernel(jax.jit(fn).lower(params, ids, pad).compile())


@pytest.mark.parametrize(
    "spec", [MeshSpec(dp=4), MeshSpec(fsdp=2, tp=2)], ids=["dp4", "fsdp2_tp2"]
)
def test_train_step_compiles_on_a_mesh(v5e, on_tpu, spec):
    """GSPMD cannot partition a Mosaic kernel: the step compiles on a
    multi-device mesh only with the kernel under shard_map."""
    import optax

    cfg = transformer.TransformerConfig(
        vocab_size=32000, n_layers=2, max_seq_len=1024, unroll_layers=False, **FLAGSHIP
    )
    mesh = Mesh(np.asarray(v5e).reshape(spec.axis_sizes()), AXES)
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
    batch = {
        "ids": jax.ShapeDtypeStruct(
            (8, 1025), jnp.int32, sharding=transformer.make_batch_sharding(cfg, mesh)
        )
    }
    compiled = jax.jit(step, donate_argnums=(0, 1)).lower(params, opt_state, batch).compile()
    assert _has_kernel(compiled)
    # params + Adam moments really are spread: a quarter each under fsdp x tp
    if spec.fsdp * spec.tp == 4:
        whole = 3 * sum(x.size * 4 for x in jax.tree_util.tree_leaves(params))
        assert compiled.memory_analysis().argument_size_in_bytes < 0.3 * whole
