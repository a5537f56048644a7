"""Learned sparse attention: an indexer scores every earlier position, a query
attends to its `topk` best (DeepSeek Sparse Attention's lightning indexer, as
Keye-VL-2.0's `sa_config` sizes it).

    I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])      float32, s <= t
    S_t     = the min(topk, t + 1) positions of largest I[t, s], equal scores to the lower position
    o_t     = softmax over S_t of q_t . k_s * scale, times v_s

Three steps, each under a scope of its own in every program (`attn.indexer`,
`attn.select`, `attn.sparse_core`: models/transformer.py, models/generate.py)
and each a function here with the dispatch of ops/attention.py: a Pallas
kernel on a TPU, plain `jax.numpy` anywhere else.

  index_scores   I for whole sequences (a prefill, a forward): kernel
                 `dsa_index`, a tile of scores at a time, every head's product
                 folded into the tile where it lies, so [T, heads, S] never
                 stands.  A decode step's scores, one query a row against the
                 cached indexer keys, are a contraction of their caller's.
  select_mask    S_t as a mask [rows, S] int8: kernel `dsa_select` finds each
                 row's topk-th largest score EXACTLY by bisection over the
                 score's 32 bits (a float's bits, made monotone, order as the
                 floats do) and then, among the scores equal to it, the
                 position up to which they are taken, by bisection over the
                 position's bits: 31 + log2(S) counting passes over a block of
                 rows that stays in fast memory, no sort.  Elsewhere: the ranks
                 of a stable argsort.
  select_rows    S_t as a list of positions [rows, topk], for a decode step
                 that gathers the selected keys and values: `lax.top_k`, whose
                 equal elements come lower index first.
  masked_flash   flash attention under the mask, grouped-query heads as they
                 are cached: kernel `dsa_flash`, one program a cached head and
                 block of queries, its R query heads' rows stacked so that a
                 key block and a mask tile are fetched once for all of them.

A selection leaves nothing out while a context is at most `topk`: the mask is
then the causal one and the result plain causal attention.
"""

from __future__ import annotations

import functools
import importlib
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_attention = importlib.import_module(__package__ + ".attention")  # the package's `attention` is the function

NEG_INF = _attention.NEG_INF
INT_MIN = -2 ** 31

# the kernels' names in a device trace
INDEX_KERNEL, SELECT_KERNEL, FLASH_KERNEL = "dsa_index", "dsa_select", "dsa_flash"
# the tile every whole-sequence kernel's blocks divide: a sequence is left-padded to a multiple of it
TILE = 512
INDEX_BLOCK_Q, INDEX_BLOCK_K = 256, 512
SELECT_ROWS = 64
FLASH_BLOCK_Q, FLASH_BLOCK_K = 128, 512
VMEM_LIMIT = 64 * 2 ** 20


def on_kernel() -> bool:
    """Whether the three steps run their kernels (on a TPU): `attention()`'s rule."""
    return _attention._platform() == "tpu"


# --------------------------------------------------------------------------
# the indexer's scores
# --------------------------------------------------------------------------


def index_scores_reference(qi, ki, w, keys_last: bool = False):
    """qi [B, T, HI, DI], ki [B, S, DI], w [B, T, HI] float32 -> I [B, T, S]
    float32: every head's product with every key, the relu, the weights.  The
    products take qi and ki in their own type with a float32 accumulator.
    keys_last: ki comes [B, DI, S], as the cache keeps it (no transposed copy
    of a layer's keys is made for the product)."""
    s = jnp.einsum("bthd,bds->bths" if keys_last else "bthd,bsd->bths", qi, ki, preferred_element_type=jnp.float32,
                   precision=lax.Precision.HIGHEST if qi.dtype == jnp.float32 else None)
    return jnp.sum(w[..., None] * jnp.maximum(s, 0.0), axis=2)


def _index_kernel(lo_ref, q_ref, w_ref, k_ref, o_ref, *, heads, block_q, block_k):
    qi, ki = pl.program_id(1), pl.program_id(2)
    pad = lo_ref[pl.program_id(0)]

    # a tile wholly above the diagonal, or of a left pad's queries or keys alone, is no query's to read: it is left
    # as it lies
    @pl.when((ki * block_k < (qi + 1) * block_q) & ((qi + 1) * block_q > pad) & ((ki + 1) * block_k > pad))
    def _():
        k, w = k_ref[...], w_ref[...]
        acc = jnp.zeros((block_q, block_k), jnp.float32)
        for h in range(heads):
            s = lax.dot_general(q_ref[h], k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
            acc = acc + w[:, h:h + 1] * jnp.maximum(s, 0.0)
        o_ref[...] = acc


def index_scores_kernel(qi, ki, w, first=None, interpret: bool = False):
    """`index_scores_reference` of a whole sequence against itself (S = T, a
    multiple of TILE) as the kernel `dsa_index`: only the tiles that hold a key
    at or before one of their queries, neither among the row's first[b] left
    pads (None: 0), are computed; the others hold whatever lay there, and no
    query's selection reads them (`select_mask`'s `first`, `last`)."""
    b, t, h, d = qi.shape
    s = ki.shape[1]
    bq, bk = min(INDEX_BLOCK_Q, t), min(INDEX_BLOCK_K, s)
    lo = jnp.zeros((b,), jnp.int32) if first is None else first.astype(jnp.int32)
    return pl.pallas_call(
        functools.partial(_index_kernel, heads=h, block_q=bq, block_k=bk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, t // bq, s // bk),
            in_specs=[
                pl.BlockSpec((None, h, bq, d), lambda bi, i, j, lo: (bi, 0, i, 0)),
                pl.BlockSpec((None, bq, h), lambda bi, i, j, lo: (bi, i, 0)),
                pl.BlockSpec((None, bk, d), lambda bi, i, j, lo: (bi, j, 0)),
            ],
            out_specs=pl.BlockSpec((None, bq, bk), lambda bi, i, j, lo: (bi, i, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((b, t, s), jnp.float32),
        interpret=interpret,
        name=INDEX_KERNEL,
    )(lo, qi.transpose(0, 2, 1, 3), w, ki)


def index_scores(qi, ki, w, first=None):
    """I [B, T, S] float32 of whole sequences (module docstring): the kernel on
    a TPU where the sequence tiles, the contraction anywhere else.  first [B]:
    a row's left pads, whose scores no selection reads (the kernel leaves their
    tiles out)."""
    t, s = qi.shape[1], ki.shape[1]
    if on_kernel() and t == s and t % TILE == 0:
        return index_scores_kernel(qi, ki, w, first)
    return index_scores_reference(qi, ki, w)


# --------------------------------------------------------------------------
# the selection
# --------------------------------------------------------------------------


def _masked_scores(scores, first, last):
    """scores [..., S] with the positions outside a row's [first, last) at -inf
    and a -0 at +0 (the two are one score): what a selection orders."""
    pos = jnp.arange(scores.shape[-1])
    valid = (pos >= first[..., None]) & (pos < last[..., None])
    return jnp.where(valid, jnp.where(scores == 0.0, 0.0, scores), -jnp.inf), valid


def select_mask_reference(scores, first, last, topk: int):
    """scores [..., S] float32; first, last [...] int: a row's queries see the
    positions [first, last).  -> bool [..., S]: the min(topk, last - first) of
    them with the largest scores, equal scores to the lower position: the
    ranks of a stable descending sort."""
    masked, valid = _masked_scores(scores, first, last)
    order = jnp.argsort(-masked, axis=-1, stable=True)
    rank = jnp.argsort(order, axis=-1)
    return valid & (rank < topk)


def _choose(s, first, last, key_ref, topk: int, bits: int):
    """Inside a kernel: the chosen of s [rows, n] float32, bool [rows, n]; first, last [rows, 1]; key_ref: a
    scratch of s's shape, int32."""
    rows, n = s.shape
    pos = lambda: lax.broadcasted_iota(jnp.int32, (rows, n), 1)
    valid = lambda: (pos() >= first) & (pos() < last)
    # a float's bits as an integer that orders as the float does: the negatives' magnitudes turned round
    raw = lax.bitcast_convert_type(jnp.where(s == 0.0, 0.0, s), jnp.int32)
    key_ref[...] = jnp.where(valid(), jnp.where(raw < 0, raw ^ 0x7FFFFFFF, raw), INT_MIN)
    want = jnp.clip(last - first, 1, topk).astype(jnp.float32)
    # counts as float32 sums: exact up to 2^24, and the reduction the chip's compiler knows best.  One pass over the
    # whole block a count: in chunks of 512 positions, only those under the diagonal, a pass took 1.4 times as long
    # (5.20 against 3.82 ms a layer at 8,192: PERF.md section 6, PR 56)
    count = lambda pred: jnp.sum(jnp.where(pred, 1.0, 0.0), axis=1, keepdims=True)

    # the largest value v with count(key >= v) >= want, its sign first and then bit by bit
    low = jnp.where(count(key_ref[...] >= 0) >= want, 0, INT_MIN).astype(jnp.int32)

    def value_bit(i, low):
        cand = low + jnp.left_shift(jnp.int32(1), 30 - i)
        return jnp.where(count(key_ref[...] >= cand) >= want, cand, low)

    thr = lax.fori_loop(0, 31, value_bit, low)
    # of the keys equal to it, the first `need` by position: the largest p with fewer than `need` of them before it
    need = want - count(key_ref[...] > thr)

    def position_bit(i, p):
        cand = p + jnp.left_shift(jnp.int32(1), bits - 1 - i)
        return jnp.where(count((key_ref[...] == thr) & (pos() < cand)) < need, cand, p)

    # where no row holds more equal keys than it needs (no two scores tie at the threshold: nearly always), every one
    # of them is taken and the second bisection is not run; a row that sees nothing has none
    ties = jnp.max(jnp.where(last > first, count(key_ref[...] == thr) - need, 0.0)) > 0.0
    upto = lax.cond(ties, lambda: lax.fori_loop(0, bits, position_bit, jnp.zeros_like(low)),
                    lambda: jnp.full_like(low, n))
    key = key_ref[...]
    return valid() & ((key > thr) | ((key == thr) & (pos() <= upto)))


def _select_kernel(s_ref, first_ref, last_ref, o_ref, key_ref, *, topk, bits):
    # a block whose rows all see nothing (a left pad's queries) chooses nothing, in no pass
    sees = jnp.max(last_ref[...] - first_ref[...]) > 0

    @pl.when(sees)
    def _():
        o_ref[...] = _choose(s_ref[...], first_ref[...], last_ref[...], key_ref, topk, bits).astype(o_ref.dtype)

    @pl.when(jnp.logical_not(sees))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)


def select_mask_kernel(scores, first, last, topk: int, interpret: bool = False):
    """`select_mask_reference` as the kernel `dsa_select`.  scores [R, S]
    float32, R a multiple of 8; first, last [R] -> int8 [R, S], 1 at the chosen."""
    r, n = scores.shape
    rows = math.gcd(r, SELECT_ROWS)
    col = lambda a: a.astype(jnp.int32).reshape(r, 1)
    return pl.pallas_call(
        functools.partial(_select_kernel, topk=topk, bits=max((n - 1).bit_length(), 1)),
        grid=(r // rows,),
        in_specs=[
            pl.BlockSpec((rows, n), lambda i: (i, 0)),
            pl.BlockSpec((rows, 1), lambda i: (i, 0)),
            pl.BlockSpec((rows, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((rows, n), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r, n), jnp.int8),
        scratch_shapes=[pltpu.VMEM((rows, n), jnp.int32)],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name=SELECT_KERNEL,
    )(scores, col(first), col(last))


def select_mask(scores, first, last, topk: int):
    """int8 [B, T, S]: 1 where query t of row b attends to position s (module
    docstring).  scores [B, T, S] float32; first, last [B, T]."""
    b, t, n = scores.shape
    if on_kernel() and (b * t) % 32 == 0 and n % 128 == 0:
        return select_mask_kernel(scores.reshape(b * t, n), first.reshape(-1), last.reshape(-1),
                                  topk).reshape(b, t, n)
    return select_mask_reference(scores, first, last, topk).astype(jnp.int8)


def select_rows(scores, first, last, topk: int):
    """A decode step's selection as a list: scores [B, S] float32 of each row's
    one query; first, last [B].  -> (positions int32 [B, min(topk, S)], chosen
    first, largest score first; how many of them are chosen [B]): `lax.top_k`
    over the scores with everything outside [first, last) at -inf, whose equal
    elements come lower index first, so the chosen stand before the rest."""
    masked, _ = _masked_scores(scores, first, last)
    _, at = lax.top_k(masked, min(topk, scores.shape[-1]))
    return at.astype(jnp.int32), jnp.clip(last - first, 0, topk).astype(jnp.int32)


# --------------------------------------------------------------------------
# attention under the selection
# --------------------------------------------------------------------------


def masked_attention_reference(q, k, v, mask, scale: float, out_dtype=None):
    """q [B, T, H, D]; k, v [B, S, KV, D(v)]; mask [B, T, S] (non-zero: seen)
    -> [B, T, H, Dv]: the dense contraction under the mask, each cached head
    with its H / KV query heads, probabilities in float32."""
    b, t, h, d = q.shape
    kv = k.shape[2]
    qg = q.reshape(b, t, kv, h // kv, d)
    high = lax.Precision.HIGHEST if q.dtype == jnp.float32 else None
    s = jnp.einsum("bqgrd,bkgd->bgrqk", qg, k, preferred_element_type=jnp.float32, precision=high) * scale
    seen = (mask != 0)[:, None, None]
    p = jax.nn.softmax(jnp.where(seen, s, NEG_INF), axis=-1)
    p = jnp.where(seen, p, 0.0)  # a row that sees nothing (a left pad's) gives zeros, not a mean
    out = jnp.einsum("bgrqk,bkgd->bqgrd", p.astype(v.dtype), v, preferred_element_type=jnp.float32, precision=high)
    return out.astype(out_dtype or q.dtype).reshape(b, t, h, v.shape[-1])


def _flash_kernel(lo_ref, q_ref, k_ref, v_ref, m_ref, o_ref, *, scale, block_q, block_k, group):
    qi = pl.program_id(1)
    rows = group * block_q
    q = q_ref[...].reshape(rows, q_ref.shape[-1])
    # the key blocks that may hold a seen key: from the row's first real position to the diagonal
    first_k = lo_ref[pl.program_id(0)] // block_k
    num_k = lax.div((qi + 1) * block_q + block_k - 1, block_k)

    def body(j, carry):
        m, l, acc = carry
        k_blk, v_blk = k_ref[pl.ds(j * block_k, block_k), :], v_ref[pl.ds(j * block_k, block_k), :]
        s = lax.dot_general(q, k_blk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32) * scale
        seen = m_ref[:, pl.ds(j * block_k, block_k)].astype(jnp.int32) != 0  # [block_q, block_k]: every head's
        seen = jnp.concatenate([seen] * group, axis=0)
        s = jnp.where(seen, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(seen, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * alpha + lax.dot_general(p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
                                                preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    m0 = jnp.full((rows, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((rows, 1), jnp.float32)
    acc0 = jnp.zeros((rows, v_ref.shape[-1]), jnp.float32)
    _, l, acc = lax.fori_loop(first_k, num_k, body, (m0, l0, acc0))
    out = acc / jnp.where(l == 0.0, 1.0, l)
    o_ref[...] = out.reshape(group, block_q, v_ref.shape[-1]).astype(o_ref.dtype)


def masked_flash_kernel(q, k, v, mask, scale: float, first=None, interpret: bool = False, out_dtype=None):
    """`masked_attention_reference` as the kernel `dsa_flash` for a causal
    mask (nothing above the diagonal is seen).  T = S, a multiple of TILE.
    first [B]: no key before it is seen (a left pad's count); None: 0."""
    b, t, h, d = q.shape
    kv, dv = k.shape[2], v.shape[-1]
    group = h // kv
    bq, bk = min(FLASH_BLOCK_Q, t), min(FLASH_BLOCK_K, t)
    # [B * KV, R, T, D]: a cached head's R query heads one after the other, each its T rows
    qf = q.reshape(b, t, kv, group, d).transpose(0, 2, 3, 1, 4).reshape(b * kv, group, t, d)
    heads_first = lambda a: a.transpose(0, 2, 1, 3).reshape(b * kv, t, a.shape[-1])
    lo = jnp.repeat(jnp.zeros((b,), jnp.int32) if first is None else first.astype(jnp.int32), kv)
    out = pl.pallas_call(
        functools.partial(_flash_kernel, scale=scale, block_q=bq, block_k=bk, group=group),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b * kv, t // bq),
            in_specs=[
                pl.BlockSpec((None, group, bq, d), lambda bi, i, lo: (bi, 0, i, 0)),
                pl.BlockSpec((None, t, d), lambda bi, i, lo: (bi, 0, 0)),
                pl.BlockSpec((None, t, dv), lambda bi, i, lo: (bi, 0, 0)),
                pl.BlockSpec((None, bq, t), lambda bi, i, lo: (bi // kv, i, 0)),
            ],
            out_specs=pl.BlockSpec((None, group, bq, dv), lambda bi, i, lo: (bi, 0, i, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((b * kv, group, t, dv), out_dtype or q.dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name=FLASH_KERNEL,
    )(lo, qf, heads_first(k), heads_first(v), mask)
    return out.reshape(b, kv, group, t, dv).transpose(0, 3, 1, 2, 4).reshape(b, t, h, dv)


def masked_flash(q, k, v, mask, scale: float, first=None, out_dtype=None):
    """Attention of whole sequences under a causal selection (module
    docstring): the kernel on a TPU where the sequence tiles, the dense
    contraction anywhere else."""
    t, s = q.shape[1], k.shape[1]
    if on_kernel() and t == s and t % TILE == 0:
        return masked_flash_kernel(q, k, v, mask, scale, first, out_dtype=out_dtype)
    return masked_attention_reference(q, k, v, mask, scale, out_dtype)


def causal_spans(pad, n: int):
    """(first, last) [B, n] of a causal selection over n positions behind pad [B] left pads: query i of a row sees
    the positions [pad, i]."""
    b = pad.shape[0]
    return jnp.broadcast_to(pad[:, None], (b, n)), jnp.broadcast_to(jnp.arange(1, n + 1)[None, :], (b, n))


def left_pad_to_tile(arrays, pad):
    """Whole sequences [B, T, ...] left-padded with zeros to the next multiple
    of TILE where the kernels run, the new columns counted as pad tokens.
    Returns (arrays, pad [B], extra): the caller drops the first `extra` rows
    of what it makes."""
    b, t = arrays[0].shape[:2]
    extra = -t % TILE if on_kernel() else 0
    pad = jnp.zeros((b,), jnp.int32) if pad is None else pad.astype(jnp.int32)
    if not extra:
        return arrays, pad, 0
    widen = lambda a: jnp.pad(a, ((0, 0), (extra, 0)) + ((0, 0),) * (a.ndim - 2))
    return [widen(a) for a in arrays], pad + extra, extra
