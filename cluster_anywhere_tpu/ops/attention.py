"""Flash attention as a Pallas TPU kernel (forward + backward).

Replaces the O(T^2)-memory dense softmax attention with the streaming-softmax
tiling that keeps the MXU busy from VMEM: per query block, K/V are consumed in
blocks with a running (max, normalizer, accumulator) — the [T, T] score matrix
never hits HBM.  Backward recomputes scores blockwise from the saved
log-sum-exp (no O(T^2) residuals), the standard flash-attention-2 scheme.

The reference framework has no attention kernels at all (it delegates model
math to torch; SURVEY.md §5 notes SP/CP absent in-tree) — this kernel is the
compute core of the TPU-native model stack: the dense transformer path calls
`attention()`, and ring attention merges per-block flash results with
`merge_attention` (parallel/ring_attention.py).

Layout contract: [B, T, H, D] inputs (time-major per head), fp32 accumulation
regardless of input dtype.  GQA callers repeat K/V heads first.  The values may
be of another width than the queries and keys ([B, T_kv, H, Dv]: latent
attention's heads are 192 wide against values of 128); the output is as wide
as the values.

`attention()` runs the kernel on a TPU and the fused-jnp reference on the CPU
backend (tests, virtual meshes); it never changes algorithm by shape.  Tests
run the kernels themselves in interpret mode by passing
`flash_attention(..., interpret=True)` (tests/test_ops.py); nothing else
selects it.

A window layer's band (`window=`: query i sees keys i - W + 1 .. i) is the same
forward kernel under the name `swa_flash`, with the key blocks before a query
block's band skipped as those above the diagonal are; forward only.

A decode step has a kernel of its own, `decode_attention`: a few query
positions a row against the row's own slots of a key/value cache, which it
reads where it lies (the whole stacks and a layer's index) and only as far as
rows hold a request and their contexts reach; told `ring=True` it reads a
window layer's stack, written round, one key block a live row.  `decode_on_kernel()` is its
dispatch, by the same rule; its dense counterpart is the caller's
(models/generate.py `_masked_attention`), and tests/test_decode_attention.py
runs it interpreted.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _platform() -> str:
    """The backend every dispatch decision asks (here and in parallel/): one
    place for a compile-only test to steer, since under an ahead-of-time
    compile for a described chip the default backend is still the CPU."""
    return jax.default_backend()


# --------------------------------------------------------------------------
# forward kernel
# --------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, *refs, scale, causal, block_k, has_pad, mask_block=0, window=0):
    if has_pad:
        pad_ref, o_ref, lse_ref = refs
        pad_val = pad_ref[pl.program_id(0)]
    else:
        (o_ref, lse_ref) = refs
        pad_val = None
    block_q, d = q_ref.shape
    t_kv = k_ref.shape[0]
    qi = pl.program_id(1)
    q = q_ref[...].astype(jnp.float32)

    m0 = jnp.full((block_q,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q,), jnp.float32)
    acc0 = jnp.zeros((block_q, v_ref.shape[-1]), jnp.float32)

    first_k = 0
    if causal:
        # skip key blocks fully above the diagonal
        num_k = lax.div((qi + 1) * block_q + block_k - 1, block_k)
        if window:
            # and those wholly before the band: the block's first query sees no key before its window's start
            first_k = lax.div(jnp.maximum(qi * block_q - (window - 1), 0), block_k)
    else:
        num_k = t_kv // block_k

    def body(ki, carry):
        m, l, acc = carry
        k_blk = k_ref[pl.ds(ki * block_k, block_k), :].astype(
            jnp.float32
        )
        v_blk = v_ref[pl.ds(ki * block_k, block_k), :].astype(
            jnp.float32
        )
        s = (
            lax.dot_general(
                q, k_blk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            )
            * scale
        )
        if causal or has_pad:
            k_pos = ki * block_k + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            ok = None
            if causal:
                q_pos = qi * block_q + lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 0
                )
                if mask_block > 1:
                    # the block mask: a query sees its own block to the end
                    q_pos = q_pos // mask_block * mask_block + (mask_block - 1)
                ok = q_pos >= k_pos
                if window:
                    ok = ok & (q_pos - k_pos < window)  # itself and the window - 1 before it
            if has_pad:
                # left-padded rows: keys before pad_val are pad tokens
                k_ok = k_pos >= pad_val
                ok = k_ok if ok is None else (ok & k_ok)
            s = jnp.where(ok, s, NEG_INF)
        m_blk = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m, m_blk)
        p = jnp.exp(s - m_new[:, None])
        if causal or has_pad:
            p = jnp.where(s <= NEG_INF, 0.0, p)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[:, None] + lax.dot_general(
            p, v_blk, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        return m_new, l_new, acc_new

    m, l, acc = lax.fori_loop(first_k, num_k, body, (m0, l0, acc0))
    l_safe = jnp.where(l == 0.0, 1.0, l)
    o_ref[...] = (acc / l_safe[:, None]).astype(o_ref.dtype)
    # lse_ref is the full (1, t) row; each grid step writes its q-block slice
    block_q_ = q_ref.shape[0]
    lse_ref[0, pl.ds(qi * block_q_, block_q_)] = m + jnp.log(l_safe)


# --------------------------------------------------------------------------
# backward kernels (flash-attention-2: recompute p from lse, no O(T^2) saves)
# --------------------------------------------------------------------------


def _bwd_dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *refs, scale, causal, block_k, has_pad
):
    if has_pad:
        pad_ref, dq_ref = refs
        pad_val = pad_ref[pl.program_id(0)]
    else:
        (dq_ref,) = refs
        pad_val = None
    block_q, d = q_ref.shape
    t_kv = k_ref.shape[0]
    qi = pl.program_id(1)
    q = q_ref[...].astype(jnp.float32)
    do = do_ref[...].astype(jnp.float32)
    lse = lse_ref[0, pl.ds(qi * block_q, block_q)].astype(jnp.float32)
    delta = delta_ref[0, pl.ds(qi * block_q, block_q)].astype(jnp.float32)

    if causal:
        num_k = lax.div((qi + 1) * block_q + block_k - 1, block_k)
    else:
        num_k = t_kv // block_k

    def body(ki, dq):
        k_blk = k_ref[pl.ds(ki * block_k, block_k), :].astype(
            jnp.float32
        )
        v_blk = v_ref[pl.ds(ki * block_k, block_k), :].astype(
            jnp.float32
        )
        s = (
            lax.dot_general(
                q, k_blk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            )
            * scale
        )
        if causal or has_pad:
            k_pos = ki * block_k + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            ok = None
            if causal:
                q_pos = qi * block_q + lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 0
                )
                ok = q_pos >= k_pos
            if has_pad:
                k_ok = k_pos >= pad_val
                ok = k_ok if ok is None else (ok & k_ok)
            s = jnp.where(ok, s, NEG_INF)
        p = jnp.exp(s - lse[:, None])
        if causal or has_pad:
            p = jnp.where(s <= NEG_INF, 0.0, p)
        dp = lax.dot_general(
            do, v_blk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta[:, None])
        return dq + scale * lax.dot_general(
            ds, k_blk, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    dq = lax.fori_loop(0, num_k, body, jnp.zeros((block_q, d), jnp.float32))
    dq_ref[...] = dq.astype(dq_ref.dtype)


def _bwd_dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *refs, scale, causal, block_q, has_pad
):
    if has_pad:
        pad_ref, dk_ref, dv_ref = refs
        pad_val = pad_ref[pl.program_id(0)]
    else:
        dk_ref, dv_ref = refs
        pad_val = None
    block_k, d = k_ref.shape
    t_q = q_ref.shape[0]
    ki = pl.program_id(1)
    k = k_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    nq = t_q // block_q
    # causal: query blocks strictly before this key block contribute nothing
    lo = lax.div(ki * block_k, block_q) if causal else 0

    def body(qi, carry):
        dk, dv = carry
        q_blk = q_ref[pl.ds(qi * block_q, block_q), :].astype(
            jnp.float32
        )
        do_blk = do_ref[pl.ds(qi * block_q, block_q), :].astype(
            jnp.float32
        )
        lse_blk = lse_ref[0, pl.ds(qi * block_q, block_q)].astype(jnp.float32)
        delta_blk = delta_ref[0, pl.ds(qi * block_q, block_q)].astype(jnp.float32)
        s = (
            lax.dot_general(
                q_blk, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            )
            * scale
        )
        if causal or has_pad:
            k_pos = ki * block_k + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            ok = None
            if causal:
                q_pos = qi * block_q + lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 0
                )
                ok = q_pos >= k_pos
            if has_pad:
                k_ok = k_pos >= pad_val
                ok = k_ok if ok is None else (ok & k_ok)
            s = jnp.where(ok, s, NEG_INF)
        p = jnp.exp(s - lse_blk[:, None])  # [bq, bk]
        if causal or has_pad:
            p = jnp.where(s <= NEG_INF, 0.0, p)
        dv_new = dv + lax.dot_general(
            p, do_blk, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        dp = lax.dot_general(
            do_blk, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta_blk[:, None])
        dk_new = dk + scale * lax.dot_general(
            ds, q_blk, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        return dk_new, dv_new

    dk0 = jnp.zeros((block_k, d), jnp.float32)
    dv0 = jnp.zeros((block_k, v_ref.shape[-1]), jnp.float32)
    dk, dv = lax.fori_loop(lo, nq, body, (dk0, dv0))
    dk_ref[...] = dk.astype(dk_ref.dtype)
    dv_ref[...] = dv.astype(dv_ref.dtype)


# --------------------------------------------------------------------------
# host-side wrappers
# --------------------------------------------------------------------------


def _to_bhtd(x):
    """[B, T, H, D] -> [B*H, T, D]."""
    b, t, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, t, d)


def _from_bhtd(x, b, h):
    bh, t, d = x.shape
    return x.reshape(b, h, t, d).transpose(0, 2, 1, 3)


def _pad_bh(pad, h):
    """[B] per-row left-pad counts -> [B*H] int32, one scalar per row of the
    B*H-flattened kernel grid.  The kernels read it whole from SMEM
    (_PAD_SPEC) indexed by program_id(0): a (1,)-wide VMEM block of it is
    refused by the TPU lowering."""
    return jnp.repeat(pad.astype(jnp.int32), h)


_PAD_SPEC = pl.BlockSpec(memory_space=pltpu.SMEM)


# the banded forward kernel's name in a device trace: a window layer's prefill, beside `flash_fwd`
WINDOW_KERNEL = "swa_flash"
# the banded kernel's query and key blocks: the lane width, the least the chip's tiling allows,
# so that a query block's band of W + 127 keys lies in two key blocks at W = 128
WINDOW_BLOCK = 128


def _fwd_impl(q, k, v, pad, causal, scale, block_q, block_k, interpret, mask_block=0, window=0, out_dtype=None):
    b, t, h, d = q.shape
    t_kv, dv = k.shape[1], v.shape[-1]
    qf, kf, vf = _to_bhtd(q), _to_bhtd(k), _to_bhtd(v)
    bh = b * h
    nq = t // block_q
    grid = (bh, nq)
    has_pad = pad is not None
    in_specs = [
        pl.BlockSpec((None, block_q, d), lambda bi, qi: (bi, qi, 0)),
        pl.BlockSpec((None, t_kv, d), lambda bi, qi: (bi, 0, 0)),
        pl.BlockSpec((None, t_kv, dv), lambda bi, qi: (bi, 0, 0)),
    ]
    args = [qf, kf, vf]
    if has_pad:
        in_specs.append(_PAD_SPEC)
        args.append(_pad_bh(pad, h))
    out, lse = pl.pallas_call(
        functools.partial(
            _fwd_kernel, scale=scale, causal=causal, block_k=block_k, has_pad=has_pad,
            mask_block=mask_block, window=window,
        ),
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((None, block_q, dv), lambda bi, qi: (bi, qi, 0)),
            # (1, t) full-row blocks: TPU lowering requires the last two block
            # dims divisible by (8, 128) OR equal to the array dims
            pl.BlockSpec((None, 1, t), lambda bi, qi: (bi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t, dv), out_dtype or q.dtype),
            jax.ShapeDtypeStruct((bh, 1, t), jnp.float32),
        ],
        interpret=interpret,
        name=WINDOW_KERNEL if window else "flash_fwd",  # the kernel's name in a device trace
    )(*args)
    return _from_bhtd(out, b, h), lse.reshape(b, h, t)


def _bwd_impl(q, k, v, o, lse, do, pad, causal, scale, block_q, block_k, interpret, dlse=None):
    b, t, h, d = q.shape
    t_kv, dv = k.shape[1], v.shape[-1]
    qf, kf, vf = _to_bhtd(q), _to_bhtd(k), _to_bhtd(v)
    dof, of = _to_bhtd(do), _to_bhtd(o)
    bh = b * h
    lsef = lse.reshape(bh, 1, t)
    # delta_i = rowsum(dO_i * O_i) — cheap elementwise, leave to XLA.  An lse
    # cotangent folds in with opposite sign: ds = p * (dp - (delta - dlse))
    delta = jnp.sum(dof.astype(jnp.float32) * of.astype(jnp.float32), axis=-1)
    if dlse is not None:
        delta = delta - dlse.reshape(bh, t).astype(jnp.float32)
    delta = delta.reshape(bh, 1, t)
    has_pad = pad is not None
    pad_arg = [_pad_bh(pad, h)] if has_pad else []
    pad_spec = [_PAD_SPEC] if has_pad else []

    dq = pl.pallas_call(
        functools.partial(
            _bwd_dq_kernel, scale=scale, causal=causal, block_k=block_k, has_pad=has_pad
        ),
        grid=(bh, t // block_q),
        in_specs=[
            pl.BlockSpec((None, block_q, d), lambda bi, qi: (bi, qi, 0)),
            pl.BlockSpec((None, t_kv, d), lambda bi, qi: (bi, 0, 0)),
            pl.BlockSpec((None, t_kv, dv), lambda bi, qi: (bi, 0, 0)),
            pl.BlockSpec((None, block_q, dv), lambda bi, qi: (bi, qi, 0)),
            pl.BlockSpec((None, 1, t), lambda bi, qi: (bi, 0, 0)),
            pl.BlockSpec((None, 1, t), lambda bi, qi: (bi, 0, 0)),
        ] + pad_spec,
        out_specs=pl.BlockSpec((None, block_q, d), lambda bi, qi: (bi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, t, d), q.dtype),
        interpret=interpret,
        name="flash_bwd_dq",
    )(qf, kf, vf, dof, lsef, delta, *pad_arg)

    dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_dkv_kernel, scale=scale, causal=causal, block_q=block_q, has_pad=has_pad
        ),
        grid=(bh, t_kv // block_k),
        in_specs=[
            pl.BlockSpec((None, t, d), lambda bi, ki: (bi, 0, 0)),
            pl.BlockSpec((None, block_k, d), lambda bi, ki: (bi, ki, 0)),
            pl.BlockSpec((None, block_k, dv), lambda bi, ki: (bi, ki, 0)),
            pl.BlockSpec((None, t, dv), lambda bi, ki: (bi, 0, 0)),
            pl.BlockSpec((None, 1, t), lambda bi, ki: (bi, 0, 0)),
            pl.BlockSpec((None, 1, t), lambda bi, ki: (bi, 0, 0)),
        ] + pad_spec,
        out_specs=[
            pl.BlockSpec((None, block_k, d), lambda bi, ki: (bi, ki, 0)),
            pl.BlockSpec((None, block_k, dv), lambda bi, ki: (bi, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t_kv, d), k.dtype),
            jax.ShapeDtypeStruct((bh, t_kv, dv), v.dtype),
        ],
        interpret=interpret,
        name="flash_bwd_dkv",
    )(qf, kf, vf, dof, lsef, delta, *pad_arg)
    return _from_bhtd(dq, b, h), _from_bhtd(dk, b, h), _from_bhtd(dv, b, h)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _flash(q, k, v, pad, causal, scale, block_q, block_k, interpret):
    out, _ = _fwd_impl(q, k, v, pad, causal, scale, block_q, block_k, interpret)
    return out


# The names under which `_flash`'s forward rule hands its two own residuals to a `jax.checkpoint`
# policy by names (models/transformer.py KEPT_NAMES): kept, a checkpointed block's backward pass
# reads them and runs no second `flash_fwd`.  The rule is traced only where a gradient is taken.
FLASH_OUT, FLASH_LSE = "flash.out", "flash.lse"


def _flash_fwd(q, k, v, pad, causal, scale, block_q, block_k, interpret):
    out, lse = _fwd_impl(q, k, v, pad, causal, scale, block_q, block_k, interpret)
    out, lse = checkpoint_name(out, FLASH_OUT), checkpoint_name(lse, FLASH_LSE)
    return out, (q, k, v, pad, out, lse)


def _flash_bwd(causal, scale, block_q, block_k, interpret, res, do):
    q, k, v, pad, out, lse = res
    dq, dk, dv = _bwd_impl(
        q, k, v, out, lse, do, pad, causal, scale, block_q, block_k, interpret
    )
    return dq, dk, dv, None  # pad is integer-valued: no cotangent


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _flash_with_lse(q, k, v, pad, causal, scale, block_q, block_k, interpret):
    return _fwd_impl(q, k, v, pad, causal, scale, block_q, block_k, interpret)


def _flash_with_lse_fwd(q, k, v, pad, causal, scale, block_q, block_k, interpret):
    out, lse = _fwd_impl(q, k, v, pad, causal, scale, block_q, block_k, interpret)
    return (out, lse), (q, k, v, pad, out, lse)


def _flash_with_lse_bwd(causal, scale, block_q, block_k, interpret, res, cts):
    """Cotangent of lse folds into the delta term: d(lse)/ds = p per row, so
    ds = p*(dp - delta + dlse) — pass (delta - dlse) where the kernels expect
    delta (the ring merge differentiates through lse)."""
    q, k, v, pad, out, lse = res
    do, dlse = cts
    dq, dk, dv = _bwd_impl(
        q, k, v, out, lse, do, pad, causal, scale, block_q, block_k, interpret,
        dlse=dlse,
    )
    return dq, dk, dv, None


_flash_with_lse.defvjp(_flash_with_lse_fwd, _flash_with_lse_bwd)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    scale: Optional[float] = None,
    pad: Optional[jax.Array] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: bool = False,
    return_lse: bool = False,
    block: int = 0,
    window: int = 0,
    out_dtype=None,
):
    """Pallas flash attention.  q: [B, T, H, D]; k: [B, T_kv, H, D]; v:
    [B, T_kv, H, Dv], of the keys' width or another; returns [B, T, H, Dv].

    window: W > 0 narrows the causal mask to a band: query i sees key j where
    0 <= i - j < W, itself and the W - 1 before it (a left pad masks on top of
    it: indices and positions differ by the same shift on both sides).  The key
    blocks wholly before a query block's band are skipped like those above the
    diagonal, not masked after the fact, so the kernel's work follows T x W, not
    T x T; the blocks default to WINDOW_BLOCK so that little outside the band
    is computed.  The kernel runs under a name of its own (WINDOW_KERNEL).
    Forward only, as the block mask.

    block: B > 1 widens the causal mask to the block mask of a model that
    generates by blocks: query i sees key j where j // B <= i // B, every
    earlier block and the whole of its own.  Indices, not positions: a left pad
    has to be a multiple of B, so that block edges fall on index edges.
    Forward only (no custom_vjp: a gradient through it is refused by JAX).

    pad: optional [B] int32 per-row LEFT-pad counts — keys at positions
    < pad[b] are masked out (the left-padded-prompt mask the LLM prefill
    needs; models/generate.py _prefill_block).

    block_q/block_k default to the largest power-of-two divisor of T / T_kv
    capped at 256 / 512 — measured best for fwd+bwd on v5e at d_head=64
    (vs 128/128: bigger K tiles amortize the half-empty 64-lane contraction
    and cut grid-step overhead; Q tiles above 256 pay more bwd recompute
    than they save).  Requires T % block_q == 0 and T_kv % block_k == 0, and
    on the chip blocks that are multiples of 128 (the lse row is stored by
    lane-aligned q-blocks); the dispatcher `attention()` pads to that.
    With return_lse=True also returns the per-row log-sum-exp [B, H, T] —
    the carry ring attention needs to merge per-block results
    (merge_attention).

    out_dtype: the result's type where it is not the queries' (the kernel
    accumulates in float32 whatever it is given: differential attention
    subtracts two results before anything is rounded).  Forward only.
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    if block_q is None:
        block_q = _auto_block(q.shape[1], WINDOW_BLOCK if window else 256)
    if block_k is None:
        block_k = _auto_block(k.shape[1], WINDOW_BLOCK if window else 512)
    block_q = min(block_q, q.shape[1])
    block_k = min(block_k, k.shape[1])
    if window:
        if not causal or return_lse or block > 1:
            raise ValueError(f"a window ({window}) is the causal mask narrowed to a band: causal=True, no lse, no block mask")
        return _fwd_impl(q, k, v, pad, True, scale, block_q, block_k, interpret, window=window, out_dtype=out_dtype)[0]
    if block > 1:
        # forward only, and the kernel's skip of the key blocks above the
        # diagonal takes a query block to end on a mask block's edge
        if not causal or return_lse or block_q % block:
            raise ValueError(
                f"the block mask (block={block}) is the causal mask widened to a block's end: "
                f"causal=True, no lse, block_q ({block_q}) a multiple of block"
            )
        return _fwd_impl(q, k, v, pad, True, scale, block_q, block_k, interpret, block, out_dtype=out_dtype)[0]
    if out_dtype is not None:
        if return_lse:
            raise ValueError("out_dtype is forward only: no lse, no gradient")
        return _fwd_impl(q, k, v, pad, causal, scale, block_q, block_k, interpret, out_dtype=out_dtype)[0]
    if return_lse:
        return _flash_with_lse(q, k, v, pad, causal, scale, block_q, block_k, interpret)
    return _flash(q, k, v, pad, causal, scale, block_q, block_k, interpret)


def _auto_block(t: int, cap: int) -> int:
    """Largest power-of-two divisor of t, capped.  When t has no power-of-two
    divisor >= 8, fall back to t itself (one full block — always valid:
    a block equal to the array dim satisfies the TPU tiling rule, whereas
    returning a non-divisor would leave grid-uncovered rows unwritten)."""
    b = cap
    while b > 8 and t % b != 0:
        b //= 2
    return b if t % b == 0 else t


def merge_attention(o1, lse1, o2, lse2):
    """Merge two normalized attention partials over disjoint key sets.

    o: [B, T, H, D]; lse: [B, H, T].  Returns (o, lse) of the union — the
    streaming-softmax combine that lets ring attention run flash per block.
    """
    m = jnp.maximum(lse1, lse2)
    # exp(-inf - -inf) guard: where both lse are -inf the row saw no keys
    w1 = jnp.where(lse1 == NEG_INF, 0.0, jnp.exp(lse1 - m))
    w2 = jnp.where(lse2 == NEG_INF, 0.0, jnp.exp(lse2 - m))
    tot = w1 + w2
    tot_safe = jnp.where(tot == 0.0, 1.0, tot)
    w1t = (w1 / tot_safe).transpose(0, 2, 1)[..., None].astype(o1.dtype)
    w2t = (w2 / tot_safe).transpose(0, 2, 1)[..., None].astype(o2.dtype)
    o = o1 * w1t + o2 * w2t
    lse = m + jnp.log(tot_safe)
    return o, jnp.where(tot == 0.0, NEG_INF, lse)


def reference_attention(q, k, v, causal=True, scale=None, pad=None, block=0, window=0, out_dtype=None):
    """Dense jnp attention (fallback + test oracle): [B,T,H,D] -> [B,T,H,D].
    pad: optional [B] left-pad counts (keys < pad[b] masked).  block: B > 1
    widens the causal mask to the block mask, window: W > 0 narrows it to the
    band 0 <= i - j < W, out_dtype: the result's type where it is not the
    queries' (`flash_attention`)."""
    d = q.shape[-1]
    if scale is None:
        scale = d ** -0.5
    s = (
        jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32))
        * scale
    )
    t_q, t_k = s.shape[-2], s.shape[-1]
    mask = None
    if causal and block > 1:
        mask = (jnp.arange(t_q)[:, None] // block >= jnp.arange(t_k)[None, :] // block)[None, None]
    elif causal:
        mask = jnp.tril(jnp.ones((t_q, t_k), dtype=bool))[None, None]
        if window:
            mask = mask & (jnp.arange(t_q)[:, None] - jnp.arange(t_k)[None, :] < window)
    if pad is not None:
        key_ok = (jnp.arange(t_k)[None, :] >= pad[:, None])[:, None, None, :]
        mask = key_ok if mask is None else (mask & key_ok)
    if mask is not None:
        s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32)).astype(out_dtype or q.dtype)


_TILE = 128  # the compiled kernel's q/k blocks are multiples of the lane width


def _left_pad_to_tile(q, k, v, pad):
    """Left-pad a [B, T, H, D] self-attention problem to the next multiple of
    the kernel's tile, counting the new columns as pad tokens.  Returns
    (q, k, v, pad, extra); the caller drops the first `extra` output rows."""
    b, t = q.shape[:2]
    extra = (-t) % _TILE
    widen = lambda x: jnp.pad(x, ((0, 0), (extra, 0), (0, 0), (0, 0)))
    pad = extra + (jnp.zeros((b,), jnp.int32) if pad is None else pad.astype(jnp.int32))
    return widen(q), widen(k), widen(v), pad, extra


def attention(q, k, v, causal: bool = True, scale: Optional[float] = None, pad=None, block: int = 0,
              window: int = 0, out_dtype=None):
    """Dispatcher: the Pallas flash kernel on a TPU, the jnp reference on any
    other backend.  A sequence that is not a multiple of the kernel's tile is
    left-padded up to one and the new columns masked as pad tokens (their
    query rows are dropped), so the algorithm never changes with the shape.
    block: the block mask (`flash_attention`); the columns added on the left
    then have to be whole blocks, which they are for a sequence of whole blocks.
    window: the band, out_dtype: the result's type (`flash_attention`)."""
    if _platform() != "tpu":
        return reference_attention(q, k, v, causal=causal, scale=scale, pad=pad, block=block, window=window,
                                   out_dtype=out_dtype)
    t, t_kv = q.shape[1], k.shape[1]
    if t % _TILE == 0 and t_kv % _TILE == 0:
        return flash_attention(q, k, v, causal=causal, scale=scale, pad=pad, block=block, window=window,
                               out_dtype=out_dtype)
    if t != t_kv:
        raise ValueError(
            f"flash kernel needs T and T_kv to be multiples of {_TILE} when "
            f"they differ, got T={t}, T_kv={t_kv}"
        )
    q, k, v, pad, extra = _left_pad_to_tile(q, k, v, pad)
    if block > 1 and extra % block:
        raise ValueError(f"a sequence of {t} under the block mask of {block}: {extra} columns on the left shift its blocks")
    return flash_attention(q, k, v, causal=causal, scale=scale, pad=pad, block=block, window=window,
                           out_dtype=out_dtype)[:, extra:]


# --------------------------------------------------------------------------
# decode: a few query positions a row against the row's own part of a cache
# --------------------------------------------------------------------------

# A grid step of the decode kernel fetches DECODE_BLOCK_K cache slots, or as many as hold
# DECODE_BLOCK_ROWS cached heads where that is more: a step costs about 0.35 us whatever it
# fetches, so few cached heads a slot take more slots a step (one cached head: the whole row)
DECODE_BLOCK_K = 256
DECODE_BLOCK_ROWS = 2048
# a window layer's ring is one key block whatever its extent, up to so many of the blocks above
# (a window of 512 at 10 cached heads of 128: 1.3 MB each of keys and values in fast memory)
DECODE_RING_BLOCKS = 2


def decode_key_block(t_max: int, kv: int, ring: bool = False) -> int:
    """The decode kernel's key block over a cache of `t_max` slots of `kv`
    cached heads: the largest divisor of t_max that is a multiple of 8 and
    within the cap above, t_max itself where there is none; and a window
    layer's ring whole (`ring`): a live row is one step and one fetch, so a
    ring may be as long as DECODE_RING_BLOCKS times that cap and no longer."""
    cap = max(DECODE_BLOCK_K, DECODE_BLOCK_ROWS // kv)
    if ring:
        if t_max > DECODE_RING_BLOCKS * cap:
            raise ValueError(f"a ring of {t_max} slots of {kv} cached heads is not one key block "
                             f"(at most {DECODE_RING_BLOCKS * cap} slots)")
        return t_max
    return max((b for b in range(8, min(cap, t_max) + 1, 8) if t_max % b == 0), default=t_max)


def decode_block_span(first, last, block_k: int, t_max: int):
    """(lo, hi): the key blocks that hold slots [first, last) of a row, both
    inclusive and inside the cache whatever a stale row says.  Arrays or scalars
    of numpy's (on the host) or of jax's (in a program): the kernel's index
    maps, its body and the batcher's count of the rows a step reads share it."""
    top = t_max // block_k - 1
    lo = (first // block_k).clip(0, top)
    return lo, ((last - 1) // block_k).clip(lo, top)


def decode_rows_read(first, last, t_max: int, kv: int, ring: bool = False):
    """The cache slots the decode kernel fetches, a layer's K (and as many of
    its V), for rows that attend to [first, last): whole key blocks (`ring`:
    the row's ring)."""
    block_k = decode_key_block(t_max, kv, ring)
    lo, hi = decode_block_span(first, last, block_k, t_max)
    return (hi - lo + 1) * block_k


def decode_on_kernel() -> bool:
    """Whether a decode step's attention runs `decode_attention` (on a TPU) or
    the caller's dense contraction (anywhere else): `attention()`'s rule."""
    return _platform() == "tpu"


def decode_span(first, last, live, t_max: int, kv: int, ring: bool = False):
    """What `decode_attention` is told of a step's rows, made once a step (every
    layer's call reads the same): int32 [5, B * key blocks a row].  Rows 0 and 1:
    first, last (row b's queries see slots [first[b], last[b]) of its own cache
    of `t_max` slots of `kv` cached heads).  Rows 2 and 3: the kernel's work, one
    entry a grid step: the (row, key block) pairs that hold a slot of a row's
    [first, last), the rows that hold a request only (live: [B], None: every
    row) and in their order, a row's blocks ascending; behind them the last
    entry again (where the index maps look one step ahead).  [4, 0]: how many.
    ring: the cache is a window layer's ring, first and last positions: its
    `t_max` slots are one key block (`decode_key_block`)."""
    b = first.shape[0]
    block_k = decode_key_block(t_max, kv, ring)
    first, last = first.astype(jnp.int32), last.astype(jnp.int32)
    lo, hi = decode_block_span(first, last, block_k, t_max)
    blocks = hi - lo + 1 if live is None else jnp.where(live, hi - lo + 1, 0)
    ends = jnp.cumsum(blocks)
    step = jnp.arange(b * (t_max // block_k), dtype=jnp.int32)
    step = jnp.minimum(step, jnp.maximum(ends[-1] - 1, 0))
    row = jnp.minimum(jnp.sum(ends[None, :] <= step[:, None], axis=1), b - 1)
    block = (lo + blocks - ends)[row] + step  # the row's first block, and the steps into the row
    wide = lambda a: jnp.zeros_like(step).at[:a.shape[0]].set(a)
    return jnp.stack([wide(first), wide(last), row, block, wide(ends[-1:])]).astype(jnp.int32)


def _decode_kernel(layer_ref, span_ref, q_ref, k_ref, v_ref, zeros_ref, o_ref, m_ref, l_ref, acc_ref, *,
                   scale, block_k, t_max, kv, heads, ring=False):
    """One (row, key block) of the work.  q_ref [Tq * H, D]; k_ref, v_ref
    [block_k * KV, D]: the block's slots as stored, a slot's KV cached heads one
    after the other.  Every query head meets every cached head of the block in
    one contraction and the mask keeps its own: the stationary operand of both
    contractions is the block itself whatever the grouping, and no head is
    taken out of a tile.  zeros_ref is the output before the kernel (aliased):
    the rows the work does not name.  ring: the row's `t_max` slots (one key
    block) are written round, position p at slot p mod t_max: a slot is seen
    where the position it holds, the newest that falls on it, lies in
    [first, last)."""
    del zeros_ref
    i = pl.program_id(0)
    b, j = span_ref[2, i], span_ref[3, i]
    first, last = span_ref[0, b], span_ref[1, b]
    lo, hi = decode_block_span(first, last, block_k, t_max)

    @pl.when(j == lo)
    def _():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def block(whole: bool):
        """The block's part of the online softmax.  whole: every slot of the
        block lies in [first, last); else the slots outside are masked out of
        the scores and their values zeroed (a probability of 0 times whatever
        lies there is not 0 if it is no number)."""
        q, k, v = q_ref[...], k_ref[...], v_ref[...]
        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32) * scale
        col = lax.broadcasted_iota(jnp.int32, (1, s.shape[1]), 1)
        row = lax.broadcasted_iota(jnp.int32, (s.shape[0], 1), 0)
        ok = col % kv == row % heads // (heads // kv)  # a query head's own cached head
        if not whole:
            slot = j * block_k + col // kv
            at = j * block_k + lax.broadcasted_iota(jnp.int32, (v.shape[0], 1), 0) // kv
            if ring:
                # how far behind the newest position, last - 1, the position a slot holds lies
                seen = lambda a: (last - 1 - a + t_max) % t_max < last - first
            else:
                seen = lambda a: (a >= first) & (a < last)
            ok = ok & seen(slot)
            v = jnp.where(seen(at), v, jnp.zeros_like(v))
        s = jnp.where(ok, s, NEG_INF)
        m = m_ref[...]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        # the block holds a slot of [first, last), so m_new is a score's, and a masked one's p is 0
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    if ring:
        block(False)
    else:
        whole = (j * block_k >= first) & ((j + 1) * block_k <= last)
        pl.when(whole)(functools.partial(block, True))
        pl.when(~whole)(functools.partial(block, False))

    @pl.when(j == hi)
    def _():
        o_ref[...] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def decode_attention(q, k, v, layer, span, *, scale: Optional[float] = None, interpret: bool = False,
                     ring: bool = False, out_dtype=None, kv: int = 0):
    """Attention of a decode step, as a Pallas kernel over the caches where they
    lie.  q: [B, Tq, H, D]; k, v: the WHOLE stacks [n_attn, B, T_max, KV, D(v)]
    as `models.generate.init_cache` makes them, and `layer`, the index of the
    one to read (a layer's slice of a stack handed to a kernel would be a copy
    of the layer).  span: `decode_span`'s, of this cache: row b's Tq queries all
    see slots [first[b], last[b]) of its own cache, in both directions, and a
    row that holds no request returns zeros.  Returns [B, Tq, H, Dv].

    ring: the stacks are a window layer's, T_max slots a row written round
    (position p at slot p mod T_max), and T_max is one key block: a live row is
    one step and one fetch, `span` is `decode_span(..., ring=True)` over (first,
    last) as positions with this T_max, and a slot is seen where the newest
    position that falls on it lies in [first, last) (at most T_max of them).
    out_dtype: the result's type where it is not the queries' (float32 for
    differential attention, which subtracts two results before it rounds).
    kv: the cached heads a slot, where the stacks come flat, [n_attn, B, T_max *
    KV, D(v)]: the rows as the kernel reads them.  Keys are
    stored turned, so their order in the ring does not matter to the softmax.

    The grid is the work `span` lists and no longer: a step a key block
    (`decode_key_block(T_max, KV)` slots, all their cached heads) that holds a
    slot of a live row's [first, last), about 0.35 us a step and the block's
    read.  A block outside, and every block of a row that holds no request, is
    neither fetched nor computed nor a step.  Online softmax in f32 over a
    row's blocks; the probabilities go into the second contraction in the
    cache's dtype."""
    b, tq, h, d = q.shape
    if kv:
        n, _, rows, dv = v.shape
        t_max = rows // kv
    else:
        n, _, t_max, kv, dv = v.shape
    block_k = decode_key_block(t_max, kv, ring)
    out_dtype = out_dtype or q.dtype

    def kv_map(i, layer_ref, span_ref):
        return layer_ref[0], span_ref[2, i], span_ref[3, i], 0

    def q_map(i, layer_ref, span_ref):
        return span_ref[2, i], 0, 0

    # a slot's cached heads lie one after the other: [T_max, KV, D] is [T_max * KV, D] as stored
    flat = lambda a: a.reshape(n, b, t_max * kv, a.shape[-1])
    m = tq * h
    out = pl.pallas_call(
        functools.partial(_decode_kernel, scale=d ** -0.5 if scale is None else scale, block_k=block_k,
                          t_max=t_max, kv=kv, heads=h, ring=ring),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(span[4, 0],),
            in_specs=[
                pl.BlockSpec((None, m, d), q_map),
                pl.BlockSpec((None, None, block_k * kv, d), kv_map),
                pl.BlockSpec((None, None, block_k * kv, dv), kv_map),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((None, m, dv), q_map),
            scratch_shapes=[pltpu.VMEM((m, 1), jnp.float32), pltpu.VMEM((m, 1), jnp.float32),
                            pltpu.VMEM((m, dv), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((b, m, dv), out_dtype),
        input_output_aliases={5: 0},  # the zeros: what a row without work returns
        interpret=interpret,
        name="decode_attn",  # the kernel's name in a device trace
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), span, q.reshape(b, m, d), flat(k), flat(v),
      jnp.zeros((b, m, dv), out_dtype))
    return out.reshape(b, tq, h, dv)


def flash_numerics_errors() -> dict:
    """Compiled (never interpret-mode) flash-vs-reference check on the device
    this process holds, so a wrong kernel cannot ship a fast number: max abs
    error of the causal kernel, of its left-padded variant, and of the
    dispatcher on a length it has to pad, at the flagship head shape
    (d_head 128).  bf16 inputs; callers hold the result to a bf16 tolerance
    (0.05)."""
    t = 2 * _TILE
    ks = jax.random.split(jax.random.key(7), 3)
    q, k, v = (jax.random.normal(kk, (2, t, 4, 128), jnp.bfloat16) for kk in ks)
    pad = jnp.asarray([0, t // 4 + 3], jnp.int32)

    def err(got, want, keep=None):
        d = jnp.abs(got.astype(jnp.float32) - want.astype(jnp.float32))
        if keep is not None:
            d = jnp.where(keep[:, :, None, None], d, 0.0)
        return float(jnp.max(d))

    ref = jax.jit(reference_attention, static_argnames=("causal",))
    real = jnp.arange(t)[None, :] >= pad[:, None]  # pad-query rows are garbage
    odd = t - _TILE // 2
    return {
        "causal": err(jax.jit(flash_attention)(q, k, v), ref(q, k, v)),
        "padded": err(
            jax.jit(lambda q, k, v, p: flash_attention(q, k, v, pad=p))(q, k, v, pad),
            ref(q, k, v, pad=pad), real,
        ),
        "dispatch_unaligned": err(
            jax.jit(attention)(q[:, :odd], k[:, :odd], v[:, :odd]),
            ref(q[:, :odd], k[:, :odd], v[:, :odd]),
        ),
    }
