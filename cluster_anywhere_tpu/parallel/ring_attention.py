"""Ring attention: exact attention over sequence shards with O(T/sp) memory
per device and compute/communication overlap.

The reference provides no sequence parallelism (SURVEY.md §5: "SP/CP not
implemented in-tree"); this module is part of closing that gap TPU-natively.
Each device holds a sequence shard of Q, K, V.  K/V blocks rotate around the
'sp' mesh axis via `lax.ppermute` while every device accumulates its Q-shard's
attention with streaming (flash-style) softmax, so the full [T, T] score
matrix never materializes.

On TPU each arriving block is processed by the Pallas flash kernel
(ops.attention.flash_attention) — full attention for blocks from earlier
shards, causal for the diagonal block, skipped for future shards — and the
per-block (out, lse) partials are combined with ops.attention.merge_attention.
On the CPU backend (test meshes) the same schedule runs as a pure jnp
streaming-softmax loop; both paths are differentiable.

Usage inside shard_map (manual over 'sp'; see tests/test_parallel.py):
    out = ring_attention(q, k, v, axis_name="sp", causal=True)
with q, k, v shaped [batch, seq_shard, heads, head_dim].
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..ops.attention import NEG_INF, _platform, flash_attention, merge_attention


def _block_attention(q, k, v, scale, mask, m_prev, l_prev, o_prev):
    """One streaming-softmax accumulation step.

    q: [B, Tq, H, D]; k, v: [B, Tk, H, D]; mask: [Tq, Tk] bool (True=keep)
    m, l: [B, H, Tq]; o: [B, Tq, H, D]
    """
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if mask is not None:
        s = jnp.where(mask[None, None, :, :], s, NEG_INF)
    m_blk = jnp.max(s, axis=-1)  # [B, H, Tq]
    m_new = jnp.maximum(m_prev, m_blk)
    # guard fully-masked rows: keep exp() finite
    p = jnp.exp(s - m_new[..., None])
    if mask is not None:
        p = jnp.where(mask[None, None, :, :], p, 0.0)
    alpha = jnp.exp(m_prev - m_new)  # [B, H, Tq]
    l_new = l_prev * alpha + jnp.sum(p, axis=-1)
    pv = jnp.einsum("bhqk,bkhd->bqhd", p, v)
    o_new = o_prev * alpha.transpose(0, 2, 1)[..., None] + pv
    return m_new, l_new, o_new


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str = "sp",
    causal: bool = True,
    scale: Optional[float] = None,
    use_flash: Optional[bool] = None,
    interpret: bool = False,
) -> jax.Array:
    """Exact attention over a ring of sequence shards (call inside shard_map).

    Shapes (per device): q, k, v: [B, T_local, H, D] -> out [B, T_local, H, D].
    For GQA repeat K/V heads to H before calling.  use_flash defaults to the
    backend (kernel on a TPU, jnp loop elsewhere); tests force the kernel
    schedule on the CPU with use_flash=True, interpret=True.
    """
    n = lax.psum(1, axis_name)
    my_idx = lax.axis_index(axis_name)
    b, t_local, h, d = q.shape
    if scale is None:
        scale = d ** -0.5
    if use_flash is None:
        use_flash = _platform() == "tpu"
    if use_flash:
        return _ring_flash(q, k, v, axis_name, causal, scale, n, my_idx, interpret)

    m0 = jnp.full((b, h, t_local), NEG_INF, dtype=jnp.float32)
    l0 = jnp.zeros((b, h, t_local), dtype=jnp.float32)
    o0 = jnp.zeros((b, t_local, h, d), dtype=jnp.float32)

    q32 = q.astype(jnp.float32)
    perm = [(i, (i + 1) % n) for i in range(n)]
    local_pos = jnp.arange(t_local)

    def step(carry, step_idx):
        k_blk, v_blk, m, l, o = carry
        # the block arriving at step s originated at device (my_idx - s) mod n
        src = (my_idx - step_idx) % n
        if causal:
            q_pos = my_idx * t_local + local_pos  # global query positions
            k_pos = src * t_local + local_pos
            mask = q_pos[:, None] >= k_pos[None, :]
        else:
            mask = None
        m, l, o = _block_attention(
            q32, k_blk.astype(jnp.float32), v_blk.astype(jnp.float32),
            scale, mask, m, l, o,
        )
        # rotate k/v to the next device; skip the final (wasted) rotation
        k_nxt = lax.ppermute(k_blk, axis_name, perm)
        v_nxt = lax.ppermute(v_blk, axis_name, perm)
        return (k_nxt, v_nxt, m, l, o), None

    (_, _, m, l, o), _ = lax.scan(step, (k, v, m0, l0, o0), jnp.arange(n))
    # final normalization; fully-masked rows (l==0) return 0
    l_safe = jnp.where(l == 0.0, 1.0, l)
    out = o / l_safe.transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def _ring_flash(q, k, v, axis_name, causal, scale, n, my_idx, interpret):
    """Flash-kernel ring schedule: per arriving K/V block run the Pallas
    kernel in the right causality mode and merge the (out, lse) partials.
    Blocks from later shards contribute nothing under causal masking and are
    skipped via lax.switch (the branch still participates in the merge with
    lse=-inf, i.e. zero weight)."""
    b, t_local, h, d = q.shape
    perm = [(i, (i + 1) % n) for i in range(n)]

    def _full(q, kb, vb):
        o, lse = flash_attention(
            q, kb, vb, causal=False, scale=scale, return_lse=True, interpret=interpret
        )
        return o.astype(jnp.float32), lse

    def _causal(q, kb, vb):
        o, lse = flash_attention(
            q, kb, vb, causal=True, scale=scale, return_lse=True, interpret=interpret
        )
        return o.astype(jnp.float32), lse

    def _skip(q, kb, vb):
        return (
            jnp.zeros((b, t_local, h, d), jnp.float32),
            jnp.full((b, h, t_local), NEG_INF, jnp.float32),
        )

    o0 = jnp.zeros((b, t_local, h, d), jnp.float32)
    lse0 = jnp.full((b, h, t_local), NEG_INF, jnp.float32)

    def step(carry, step_idx):
        k_blk, v_blk, o, lse = carry
        src = (my_idx - step_idx) % n
        if causal:
            # 0: future shard (skip), 1: diagonal (causal), 2: past (full)
            mode = jnp.where(src == my_idx, 1, jnp.where(src < my_idx, 2, 0))
        else:
            mode = 2
        ob, lb = lax.switch(mode, [_skip, _causal, _full], q, k_blk, v_blk)
        o, lse = merge_attention(o, lse, ob, lb)
        k_nxt = lax.ppermute(k_blk, axis_name, perm)
        v_nxt = lax.ppermute(v_blk, axis_name, perm)
        return (k_nxt, v_nxt, o, lse), None

    (_, _, o, _), _ = lax.scan(step, (k, v, o0, lse0), jnp.arange(n))
    return o.astype(q.dtype)


def ring_attention_sharded(
    q, k, v, mesh, axis_name="sp", causal=True, use_flash=None, interpret=False
):
    """Convenience wrapper: shard_map over the sp axis of `mesh` with
    [batch, seq, heads, dim] inputs sharded on seq."""
    from jax.sharding import PartitionSpec as P

    spec = P(None, axis_name, None, None)
    fn = functools.partial(
        ring_attention, axis_name=axis_name, causal=causal, use_flash=use_flash,
        interpret=interpret,
    )
    return jax.shard_map(
        fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec, check_vma=False
    )(q, k, v)


from ..ops.attention import reference_attention  # noqa: E402  (re-export; test oracle)
