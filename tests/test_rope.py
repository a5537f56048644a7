"""The rotary embedding (`models/transformer.py _rope`): the adjacent-pair
rotation, stated here the plain way on the strided halves, and the shape of
the program it lowers to."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cluster_anywhere_tpu.models import transformer


def _config(d, yarn):
    scaled = dict(rope_factor=8.0, rope_original_max_len=64, rope_mscale=1.0, rope_mscale_all_dim=0.5) if yarn else {}
    return transformer.TransformerConfig(
        vocab_size=32, n_layers=1, d_model=4 * d, n_heads=4, n_kv_heads=2, d_head=d, d_ff=32, rope_theta=1e4, **scaled)


def _adjacent_pairs(q, k, positions, cfg):
    """The definition: every pair (x[2i], x[2i + 1]) turned by positions x
    frequency i, in float32 (the program's own up to PR 48)."""
    freqs, magnitude = transformer._rope_freqs(cfg)
    angles = positions[..., None].astype(jnp.float32) * freqs
    if angles.ndim == 2:
        angles = angles[None]
    cos = jnp.cos(angles)[:, :, None, :] * magnitude
    sin = jnp.sin(angles)[:, :, None, :] * magnitude

    def rot(x):
        x1, x2 = x[..., 0::2], x[..., 1::2]
        return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).reshape(x.shape)

    return rot(q.astype(jnp.float32)).astype(q.dtype), rot(k.astype(jnp.float32)).astype(k.dtype)


def _inputs(d, dtype, seed):
    """q [3, 9, 4, d], k [3, 9, 2, d] and a float32 weight for each."""
    kq, kk, kw = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(kq, (3, 9, 4, d), jnp.float32).astype(dtype)
    k = jax.random.normal(kk, (3, 9, 2, d), jnp.float32).astype(dtype)
    return q, k, [jax.random.normal(key, x.shape, jnp.float32) for key, x in zip(jax.random.split(kw), (q, k))]


def _weighted_gradient(rope, q, k, positions, cfg, weights):
    """d/dq, d/dk of the weighted sum of what `rope` returns, as float32 arrays."""
    loss = lambda q, k: sum((out.astype(jnp.float32) * w).sum() for out, w in zip(rope(q, k, positions, cfg), weights))
    return [np.asarray(g, np.float32) for g in jax.grad(loss, argnums=(0, 1))(q, k)]


@pytest.mark.parametrize("yarn", [False, True], ids=["plain", "yarn"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
@pytest.mark.parametrize("rows", ["shared", "per_row"])
@pytest.mark.parametrize("d", [128, 64])
def test_rope_is_the_adjacent_pair_rotation(d, rows, dtype, yarn):
    """`_rope` gives what the definition gives, to float32 rounding (one last
    place of the result's own type), and so does the gradient of a weighted sum
    through it: for the head width of the dense models and the latent model's
    rotary part, positions [T] and [B, T] (left-padded rows), both activation
    types, YaRN's frequencies and magnitude on and off."""
    cfg = _config(d, yarn)
    assert (transformer._rope_freqs(cfg)[1] != 1.0) == yarn
    q, k, weights = _inputs(d, dtype, seed=d + yarn)
    pads = jnp.array([0, 4, 7])
    positions = jnp.arange(9) + 50 if rows == "shared" else jnp.maximum(jnp.arange(9)[None] - pads[:, None], 0)
    last_place = dict(rtol=2.0 ** -7, atol=1e-6) if dtype == jnp.bfloat16 else dict(rtol=1e-6, atol=1e-6)
    f32 = lambda tree: [np.asarray(x, np.float32) for x in tree]

    got, want = transformer._rope(q, k, positions, cfg), _adjacent_pairs(q, k, positions, cfg)
    assert [x.dtype for x in got] == [dtype, dtype] and [x.shape for x in got] == [q.shape, k.shape]
    for g, w in zip(f32(got), f32(want)):
        np.testing.assert_allclose(g, w, **last_place)
    assert not np.allclose(f32(got)[0], np.asarray(q, np.float32), atol=1e-2)  # and it turned something

    gradient = lambda rope: _weighted_gradient(rope, q, k, positions, cfg, weights)
    for g, w in zip(gradient(transformer._rope), gradient(_adjacent_pairs)):
        np.testing.assert_allclose(g, w, **last_place)


@pytest.mark.parametrize("d", [128, 64])
def test_stated_gradient_is_the_definitions_to_the_bit(d):
    """Why `_turn` states its gradient: in bf16 the stated one is the
    definition's to the bit (one rounding of the same float32 sum), while the
    one derived from `_turn`'s own body rounds the product's cotangent, the
    x * cos branch's and their sum, and differs in over a tenth of the
    elements."""
    cfg = _config(d, False)
    q, k, weights = _inputs(d, jnp.bfloat16, seed=d)
    gradient = lambda rope: _weighted_gradient(rope, q, k, jnp.arange(9) + 50, cfg, weights)

    def derived(q, k, positions, cfg):
        with pytest.MonkeyPatch.context() as patch:
            patch.setattr(transformer, "_turn", transformer._turn.fun)  # the body, differentiated as it stands
            return transformer._rope(q, k, positions, cfg)

    want = gradient(_adjacent_pairs)
    for got, w in zip(gradient(transformer._rope), want):
        np.testing.assert_array_equal(got, w)
    for got, w in zip(gradient(derived), want):
        np.testing.assert_allclose(got, w, rtol=2.0 ** -5, atol=2.0 ** -5)  # the same gradient, rounded more often
        assert (got != w).mean() > 0.1


# (B, T, query heads, key heads, positions a row of its own): Mistral-7B's decode step at 32 slots, and a chip's
# share of the train step
SHAPES = {"decode": (32, 1, 32, 8, True), "train": (2, 4096, 32, 8, False)}


@pytest.mark.parametrize("gradient", [False, True], ids=["forward", "gradient"])
@pytest.mark.parametrize("shape", list(SHAPES))
def test_rope_lowers_to_no_strided_slice_and_no_gather(shape, gradient):
    """The lowered program of `_rope`, and of its gradient, keeps every lane
    where it lies: no slice with a stride on the minor axis (the halves), no
    gather or scatter, no pad (the halves' gradient), no concatenate (the
    stack), and one product a tensor with the pair-swap matrix."""
    b, t, h, kv, own = SHAPES[shape]
    cfg = transformer.TransformerConfig(vocab_size=32, n_layers=1, d_model=h * 128, n_heads=h, n_kv_heads=kv, d_head=128,
                                        d_ff=32)
    q, k = jax.ShapeDtypeStruct((b, t, h, 128), jnp.bfloat16), jax.ShapeDtypeStruct((b, t, kv, 128), jnp.bfloat16)
    positions = jax.ShapeDtypeStruct((b, t) if own else (t,), jnp.int32)
    rope = lambda q, k, positions: transformer._rope(q, k, positions, cfg)
    if gradient:
        fn = lambda q, k, positions: jax.grad(
            lambda q, k: sum(jnp.square(x.astype(jnp.float32)).sum() for x in rope(q, k, positions)), (0, 1))(q, k)
    else:
        fn = rope
    text = jax.jit(fn).lower(q, k, positions).as_text()
    strides = [m for m in re.findall(r"stablehlo\.slice[^\n]*?\[([^\]]*)\]", text) if re.search(r":\s*\d+\s*:\s*[2-9]", m)]
    assert strides == []
    for op in ("gather", "scatter", "stablehlo.pad", "stablehlo.concatenate"):
        assert op not in text, op
    assert text.count("stablehlo.dot_general") == (4 if gradient else 2)
