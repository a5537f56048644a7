"""Pipeline parallelism: GPipe-style microbatch schedule over the 'pp' mesh
axis, expressed as a shard_map + lax.scan + ppermute program.

The reference reaches pipeline parallelism only through its compiled-graph
scheduler pushing per-actor operation lists (SURVEY.md §2.3 aDAG); here the
schedule is a compiled XLA program: every device runs its stage every step,
activations hop stage->stage+1 over ICI via ppermute, and the M+n-1 step loop
(bubble included) is a single lax.scan that XLA pipelines.  Differentiable by
construction — the backward pass is the transposed schedule.
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax


def pipeline_apply(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    stage_params: Any,
    x: jax.Array,
    *,
    axis_name: str = "pp",
    num_microbatches: int,
    with_aux: bool = False,
):
    """Run a stage-partitioned function over microbatches (call inside
    shard_map, manual over `axis_name`).

    stage_fn(params_of_my_stage, activ) -> activ, same shape/dtype (uniform
    stages).  x: [B, ...] (replicated across pp); returns [B, ...] with every
    stage holding the final output (psum broadcast).

    with_aux=True: stage_fn returns (activ, aux_scalar) — an auxiliary loss
    per microbatch per stage (MoE load balance).  Bubble steps (a stage fed
    zeros before/after its real work) are masked out; the result is the
    per-microbatch mean, summed over stages, so it matches what the
    unpipelined stack would have computed over the full batch.  Returns
    (out, aux)."""
    n = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    m = num_microbatches
    batch = x.shape[0]
    if batch % m != 0:
        raise ValueError(f"batch {batch} not divisible by num_microbatches {m}")
    micro = x.reshape(m, batch // m, *x.shape[1:])

    total_steps = m + n - 1
    buf0 = jnp.zeros_like(micro[0])
    outs0 = jnp.zeros_like(micro)
    # stage i -> i+1; stage 0 receives zeros (no wraparound source)
    fwd_perm = [(i, i + 1) for i in range(n - 1)]

    def step(carry, t):
        prev, outs, aux_acc = carry
        incoming = lax.ppermute(prev, axis_name, fwd_perm)
        mb = lax.dynamic_index_in_dim(micro, jnp.clip(t, 0, m - 1), 0, keepdims=False)
        x_t = jnp.where(idx == 0, mb, incoming)
        if with_aux:
            y, aux = stage_fn(stage_params, x_t)
            # stage idx holds microbatch (t - idx) at step t; real work only
            # for 0 <= t - idx < m — everything else is pipeline bubble
            valid = jnp.logical_and(t >= idx, t - idx < m)
            aux_acc = aux_acc + jnp.where(valid, aux.astype(jnp.float32), 0.0)
        else:
            y = stage_fn(stage_params, x_t)
        out_idx = jnp.clip(t - (n - 1), 0, m - 1)
        updated = lax.dynamic_update_slice(
            outs, y[None].astype(outs.dtype), (out_idx,) + (0,) * y.ndim
        )
        write = jnp.logical_and(idx == n - 1, t >= n - 1)
        outs = jnp.where(write, updated, outs)
        return (y, outs, aux_acc), None

    (_, outs, aux_acc), _ = lax.scan(
        step, (buf0, outs0, jnp.zeros((), jnp.float32)), jnp.arange(total_steps)
    )
    # only the last stage holds real outputs; broadcast to every stage so the
    # loss (computed replicated over pp) sees them
    outs = lax.psum(jnp.where(idx == n - 1, outs, jnp.zeros_like(outs)), axis_name)
    out = outs.reshape(batch, *x.shape[1:])
    if with_aux:
        # sum over stages (layers are partitioned over pp), mean over
        # microbatches — the unpipelined equivalent computes one aux over
        # the whole batch, which the per-microbatch mean estimates exactly
        # for batch-linear aux terms
        return out, lax.psum(aux_acc, axis_name) / m
    return out


def pipeline_sharded(stage_fn, mesh, *, axis_name="pp", num_microbatches):
    """Wrap pipeline_apply in shard_map: stage_params must be stacked with a
    leading pp axis (params[i] = stage i); x replicated."""
    from jax.sharding import PartitionSpec as P

    def inner(stacked_params, x):
        my_params = jax.tree_util.tree_map(lambda p: p[0], stacked_params)
        return pipeline_apply(
            stage_fn, my_params, x, axis_name=axis_name, num_microbatches=num_microbatches
        )

    def apply(stacked_params, x):
        in_param_specs = jax.tree_util.tree_map(lambda _: P(axis_name), stacked_params)
        return jax.shard_map(
            inner,
            mesh=mesh,
            in_specs=(in_param_specs, P()),
            out_specs=P(),
            check_vma=False,
        )(stacked_params, x)

    return apply


def num_pipeline_stages(mesh, axis_name: str = "pp") -> int:
    return mesh.shape[axis_name]
