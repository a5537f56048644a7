"""Ulysses-style sequence parallelism: all-to-all head/sequence re-sharding.

Alternative to ring attention for models where heads >= sp: re-shard
[B, T/sp, H, D] -> [B, T, H/sp, D] with one all-to-all, run *full-sequence*
attention on the local head subset, then all-to-all back.  Two collectives
per attention call instead of sp ppermutes; wins when T is moderate and H
is divisible by the sp axis.
"""

from __future__ import annotations

import functools

import jax
from jax import lax

from ..ops.attention import attention as _dispatch_attention


def ulysses_attention(q, k, v, axis_name: str = "sp", causal: bool = True, attn_fn=None):
    """Call inside shard_map. q,k,v: [B, T_local, H, D] (heads complete,
    sequence sharded). Requires H % sp == 0.

    Default attention over the gathered full sequence goes through the
    dispatcher: the Pallas flash kernel on a TPU, the jnp reference on the
    CPU backend."""
    n = lax.psum(1, axis_name)
    if q.shape[2] % n != 0:
        raise ValueError(f"heads {q.shape[2]} not divisible by {axis_name}={n}")
    if attn_fn is None:
        attn_fn = functools.partial(_dispatch_attention, causal=causal)

    def scatter_heads(x):
        # [B, T/sp, H, D] -> [B, T, H/sp, D]
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1, tiled=True)

    def gather_heads(x):
        # [B, T, H/sp, D] -> [B, T/sp, H, D]
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2, tiled=True)

    qh, kh, vh = scatter_heads(q), scatter_heads(k), scatter_heads(v)
    out = attn_fn(qh, kh, vh)
    return gather_heads(out)


def ulysses_attention_sharded(q, k, v, mesh, axis_name="sp", causal=True):
    from jax.sharding import PartitionSpec as P

    spec = P(None, axis_name, None, None)
    fn = functools.partial(ulysses_attention, axis_name=axis_name, causal=causal)
    return jax.shard_map(
        fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec, check_vma=False
    )(q, k, v)
