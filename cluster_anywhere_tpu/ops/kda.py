"""Kimi Delta Attention's decode update as a Pallas kernel over the state where it
lies (models/transformer.py `_kda_step` is the same update in `jax.numpy`, and what
runs anywhere but on a TPU).

A decode step moves a KDA layer's matrix state S [H, D, D] float32 of every row on by
one position: S' = Diag(exp(g)) S, u = beta (v - S'^T k), S = S' + k u^T, o = S^T q.
That is a few operations an element of a state of 2 MB a row and layer (42 MB a slot
over Kimi Linear's 20 layers), so the update is bound by moving the state, and what a
step has to move is the state of the rows that hold a request, read once and written
once.  Plain XLA takes the layer's state out of the stacks (a copy of every slot's,
live or not), reads it for the sums, and reads and writes it again for the update:
6.3 ms of a 17.7 ms step at 32 slots with 8 live (PERF.md section 6, PR 60).  The
kernel is handed the WHOLE stack [n, B, H, D, D] and the layer's index, as
`ops/attention.py decode_attention` is handed the cache's, and the stack is its
result too (aliased: written where it lies): a grid step is one live row's block of
KDA_HEADS heads, fetched, updated and written back; a row that holds no request is
neither fetched nor a step, and keeps the state it had (what it holds is overwritten
whole when the slot is given out).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# heads a grid step: 8 x 128 x 128 float32 = 512 KB in and as much out, each double-buffered
KDA_HEADS = 8
_LANES = 128


def live_rows(live, rows: int):
    """What `kda_decode_update` is told of a step's rows, made once a step (every layer's call reads the same):
    int32 [rows + 1], the rows that hold a request first, in their order, and behind them how many they are.
    live: [rows] bool, None: every row."""
    if live is None:
        return jnp.arange(rows + 1, dtype=jnp.int32)
    order = jnp.argsort(jnp.logical_not(live), stable=True).astype(jnp.int32)
    return jnp.concatenate([order, jnp.sum(live, dtype=jnp.int32)[None]])


def _columns(block):
    """block [heads, D] -> [D, 128]: column h is head h's vector, down the sublanes (a transpose of a whole
    [128, 128] tile: the heads' rows first, zeros below)."""
    heads, d = block.shape
    return jnp.concatenate([block, jnp.zeros((_LANES - heads, d), block.dtype)], axis=0).T


def _update_kernel(layer_ref, rows_ref, q_ref, k_ref, v_ref, g_ref, beta_ref, s_ref, zeros_ref, o_ref, s_out_ref):
    del layer_ref, rows_ref, zeros_ref
    f = jnp.float32
    q_cols, k_cols, a_cols = _columns(q_ref[...].astype(f)), _columns(k_ref[...].astype(f)), _columns(jnp.exp(g_ref[...].astype(f)))
    v, beta = v_ref[...].astype(f), beta_ref[...].astype(f)
    for h in range(s_ref.shape[0]):
        column = lambda cols: cols[:, h:h + 1]  # [D, 1]: along the keys
        q, k = column(q_cols), column(k_cols)
        s = column(a_cols) * s_ref[h].astype(f)  # [Dk, Dv]
        u = beta[h:h + 1] * (v[h:h + 1] - jnp.sum(k * s, axis=0, keepdims=True))  # [1, Dv]
        o_ref[h:h + 1, :] = (jnp.sum(q * s, axis=0, keepdims=True) + jnp.sum(q * k, axis=0, keepdims=True) * u).astype(o_ref.dtype)
        s_out_ref[h] = (s + k * u).astype(s_out_ref.dtype)


def kda_decode_update(q, k, v, g, beta, state, layer, rows, *, interpret: bool = False):
    """One position of the delta rule for the rows that hold a request.  q, k, v, g: [B, H, D] float32 (q scaled,
    q and k unit vectors a head, g the log-decay a key channel); beta: [B, H]; state: the WHOLE stack [n, B, H, D,
    D] as `models.generate.init_cache` makes it, and `layer`, the index of the one to update; rows: `live_rows`'.
    Returns (o [B, H, D] float32, zeros for a row that holds no request; the stack, the live rows' state of
    `layer` moved on and everything else as it was)."""
    b, hh, d = q.shape
    if d != _LANES or hh % KDA_HEADS or state.shape[2:] != (hh, d, d):
        raise NotImplementedError(f"the KDA decode kernel takes heads of {_LANES} in blocks of {KDA_HEADS}: {state.shape}")

    def vector_map(i, j, layer_ref, rows_ref):
        return rows_ref[i], j, 0

    def state_map(i, j, layer_ref, rows_ref):
        return layer_ref[0], rows_ref[i], j, 0, 0

    vector = pl.BlockSpec((None, KDA_HEADS, d), vector_map)
    stack = pl.BlockSpec((None, None, KDA_HEADS, d, d), state_map)
    o, state = pl.pallas_call(
        _update_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(rows[b], hh // KDA_HEADS),
            in_specs=[vector, vector, vector, vector, vector, stack, pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[vector, stack],
        ),
        out_shape=[jax.ShapeDtypeStruct((b, hh, d), jnp.float32), jax.ShapeDtypeStruct(state.shape, state.dtype)],
        input_output_aliases={8: 0, 7: 1},  # the zeros: what a row without a request returns; the stack, in place
        interpret=interpret,
        name="kda_update",  # the kernel's name in a device trace
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), rows, q, k, v, g, jnp.broadcast_to(beta[..., None], q.shape), state,
      jnp.zeros((b, hh, d), jnp.float32))
    return o, state
