"""Pallas kernel layer tests (interpret mode on the CPU test mesh).

Oracle = the dense jnp reference; the kernels must match it in both values
and gradients (fwd: flash streaming softmax; bwd: flash-attention-2
recomputation from saved lse).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from cluster_anywhere_tpu.ops.attention import (
    flash_attention,
    merge_attention,
    reference_attention,
)

B, T, H, D = 2, 256, 3, 64


def _inputs(seed=0, t=T, dtype=jnp.float32):
    ks = jax.random.split(jax.random.key(seed), 3)
    shape = (B, t, H, D)
    return tuple(jax.random.normal(k, shape, dtype) * 0.5 for k in ks)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_forward_matches_reference(causal):
    q, k, v = _inputs()
    out = flash_attention(q, k, v, causal=causal, interpret=True, block_q=64, block_k=64)
    ref = reference_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_backward_matches_reference(causal):
    q, k, v = _inputs(seed=1)

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, causal=causal, interpret=True, block_q=64, block_k=64)
        return jnp.sum(jnp.sin(o))

    def loss_ref(q, k, v):
        return jnp.sum(jnp.sin(reference_attention(q, k, v, causal=causal)))

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-4, rtol=3e-4)


def test_flash_lse_and_merge():
    """Splitting keys in half and merging the flash partials must equal full
    attention — the combine ring attention is built on."""
    q, k, v = _inputs(seed=2)
    half = T // 2
    o1, lse1 = flash_attention(
        q, k[:, :half], v[:, :half], causal=False, interpret=True,
        block_q=64, block_k=64, return_lse=True,
    )
    o2, lse2 = flash_attention(
        q, k[:, half:], v[:, half:], causal=False, interpret=True,
        block_q=64, block_k=64, return_lse=True,
    )
    merged, _ = merge_attention(o1, lse1, o2, lse2)
    ref = reference_attention(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(merged), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_flash_merge_gradients():
    """Gradients must flow through the (out, lse) pair and the merge."""
    q, k, v = _inputs(seed=3, t=128)
    half = 64

    def loss_merged(q, k, v):
        o1, l1 = flash_attention(
            q, k[:, :half], v[:, :half], causal=False, interpret=True,
            block_q=64, block_k=64, return_lse=True,
        )
        o2, l2 = flash_attention(
            q, k[:, half:], v[:, half:], causal=False, interpret=True,
            block_q=64, block_k=64, return_lse=True,
        )
        merged, _ = merge_attention(o1, l1, o2, l2)
        return jnp.sum(jnp.sin(merged))

    def loss_ref(q, k, v):
        return jnp.sum(jnp.sin(reference_attention(q, k, v, causal=False)))

    gm = jax.grad(loss_merged, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gm, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-4, rtol=3e-4)


def test_flash_bf16_inputs():
    q, k, v = _inputs(seed=4, dtype=jnp.bfloat16)
    out = flash_attention(q, k, v, causal=True, interpret=True, block_q=64, block_k=64)
    ref = reference_attention(q, k, v, causal=True)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=3e-2, rtol=3e-2
    )


def test_flash_pad_mask_matches_reference():
    """Pad-masked flash kernel (interpret mode) vs the dense masked oracle:
    forward and gradients, left-padded rows."""
    import jax
    import jax.numpy as jnp

    from cluster_anywhere_tpu.ops.attention import flash_attention, reference_attention

    b, t, h, d = 2, 32, 2, 8
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (b, t, h, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, t, h, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, t, h, d), jnp.float32)
    pad = jnp.asarray([5, 0], jnp.int32)  # row 0 left-padded by 5

    got = flash_attention(q, k, v, causal=True, pad=pad, block_q=8, block_k=8, interpret=True)
    want = reference_attention(q, k, v, causal=True, pad=pad)
    # pad-query rows (positions < pad) are undefined garbage in both paths;
    # compare real rows only
    import numpy as np

    for row, p in enumerate([5, 0]):
        np.testing.assert_allclose(
            np.asarray(got[row, p:]), np.asarray(want[row, p:]), atol=2e-5, rtol=2e-5
        )

    def loss_flash(q, k, v):
        out = flash_attention(q, k, v, causal=True, pad=pad, block_q=8, block_k=8, interpret=True)
        return (out[0, 5:].astype(jnp.float32) ** 2).sum() + (
            out[1].astype(jnp.float32) ** 2
        ).sum()

    def loss_ref(q, k, v):
        out = reference_attention(q, k, v, causal=True, pad=pad)
        return (out[0, 5:].astype(jnp.float32) ** 2).sum() + (
            out[1].astype(jnp.float32) ** 2
        ).sum()

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=5e-4, rtol=5e-4)


def test_prefill_uses_pad_dispatcher():
    """LLM prefill produces identical logits whether prompts are left-padded
    or not (the pad mask flows through the attention dispatcher)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from cluster_anywhere_tpu.models.generate import prefill
    from cluster_anywhere_tpu.models.transformer import TransformerConfig, init_params

    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_layers=2, n_heads=2, n_kv_heads=2, d_head=16,
        d_ff=64, max_seq_len=32, dtype=jnp.float32,
    )
    params = init_params(jax.random.PRNGKey(0), cfg)
    toks = np.array([3, 9, 27, 11, 5], np.int32)
    # unpadded: [1, 5]; padded: [1, 8] with 3 left pads
    logits_a, _ = prefill(params, jnp.asarray(toks[None]), cfg, 16, None)
    padded = np.concatenate([np.zeros(3, np.int32), toks])[None]
    logits_b, _ = prefill(
        params, jnp.asarray(padded), cfg, 16, jnp.asarray([3], jnp.int32)
    )
    np.testing.assert_allclose(
        np.asarray(logits_a[0]), np.asarray(logits_b[0]), atol=1e-4, rtol=1e-4
    )


@pytest.mark.parametrize(
    "t, pad, grads", [(40, None, False), (96, [3, 0], True), (130, [0, 17], False)]
)
def test_left_pad_to_tile_matches_reference(t, pad, grads):
    """What the dispatcher does on a TPU with a length that is not a multiple
    of the kernel's tile: left-pad, mask the new columns, drop their rows."""
    import jax
    import jax.numpy as jnp

    from cluster_anywhere_tpu.ops.attention import (
        _TILE,
        _left_pad_to_tile,
        flash_attention,
        reference_attention,
    )

    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q, k, v = (jax.random.normal(kk, (2, t, 2, 8), jnp.float32) for kk in ks)
    pad_arr = None if pad is None else jnp.asarray(pad, jnp.int32)
    want = reference_attention(q, k, v, causal=True, pad=pad_arr)

    def padded(q, k, v):
        q2, k2, v2, pad2, extra = _left_pad_to_tile(q, k, v, pad_arr)
        assert q2.shape[1] % _TILE == 0 and q2.shape[1] - t == extra < _TILE
        out = flash_attention(q2, k2, v2, causal=True, pad=pad2, interpret=True)
        return out[:, extra:]

    got = padded(q, k, v)
    assert got.shape == q.shape
    real = np.arange(t)[None, :] >= np.asarray(pad or [0, 0])[:, None]
    np.testing.assert_allclose(
        np.asarray(got)[real], np.asarray(want)[real], atol=2e-5, rtol=2e-5
    )
    if not grads:
        return
    # gradients flow through the pad and the slice
    keep = jnp.asarray(real)[:, :, None, None]
    g = jax.grad(lambda q: jnp.where(keep, padded(q, k, v), 0.0).sum())(q)
    g_ref = jax.grad(
        lambda q: jnp.where(keep, reference_attention(q, k, v, causal=True, pad=pad_arr), 0.0).sum()
    )(q)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref), atol=2e-4, rtol=2e-4)
