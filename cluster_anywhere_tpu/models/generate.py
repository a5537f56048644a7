"""Autoregressive generation with a KV cache for the flagship transformer
(the decode path the reference delegates to vLLM; here TPU-native:
static-shape cache + `lax.scan` decode loop so the whole generate compiles
into one XLA program).

This module owns the cache's layout (`init_cache`, `LAYER_STATE`): every kind
of state the configuration's layers keep, each stacked over the layers of its
kind alone,
    k, v: [n_attn, B, T_max, H_kv, D]
    kv: [n_attn, B, T_max, 2 H_kv, D], ki: [n_attn, B, DI, T_max]  (learned sparse
        attention: a token's keys and values side by side in ONE stack, and the
        indexer's one key head of each token with the positions innermost:
        `init_cache` says why)
    conv: [n_ssm, B, K-1, C], h: [n_ssm, B, C, N] (float32)
        (a Mamba-2 layer's: [n, B, K-1, C + 2 G N] and [n, B, H, P, N]; a kda layer's:
        [n, B, K-1, 3 H D] and the matrix state [n, B, H, D, D])
    ckv: [n_attn, B, T_max, R], kr: [n_attn, B, T_max, rope up to 128s]  (latent
        attention: a token is one latent row and the one rotated key every
        head shares, no heads axis; `LATENT_LANES` says why the key is padded)
    kw, vw: [n_win, B, W, H_kv, D]  (window layers: a ring of W = `window_extent`
        slots a row whatever the context's length, position p at slot p mod W)
the rows of one request written over a slot (`install_rows`), and the cores
that write it.  An attention block is
transformer.py's two halves (`_attention_half`, `_ffn_half`) around one of
them: the prefill core writes a prompt's k, v into zeroed rows and attends
with the pad-masked flash kernel (`_prefill_block`); the decode core writes
row b's new k, v at [layer, b, pos[b]] of the stacks and attends to the row's
own slots [pads[b], pos[b]] (`_block_decode_rowpos`: static shapes, no
recompilation per step).  On a TPU, over a cache of more than one row
(`_on_kernel`), it attends through a kernel (ops/attention.py
`decode_attention`) that is handed the stacks as they lie
and the layer's index, and fetches, of the layer's k and v, the key blocks
that hold those slots of the rows that hold a request and no others: what a
step reads of the cache follows what is live, not the cache's size.  Anywhere
else (and as the kernel's reference) the layer is taken out of the stacks and
the query [B, 1, H, D], grouped to [B, 1, H_kv, H // H_kv, D], is contracted
with all of k, v [B, T_max, H_kv, D] in the cache's dtype with an f32
accumulator under a position mask (`_masked_attention`).  Nothing of the
cache's size is repeated to H heads or copied to f32 on either path; only the
prefill repeats its own k, v for the flash kernel.  A
state-space block is `_ssm_half`, `_ffn_half` around `_ssm_mix` from the zero
state over a whole prompt (`_ssm_prefill_block`, which is training's block
with the pads masked: they leave the state untouched) or from a slot's own
state for one more token (`_ssm_block_decode`, which reads the layer's state
out of the stacks and writes the new one in its place).

A window layer (`transformer.is_window(kind)`: its queries see the last
`cfg.attn_window` positions) has the same two blocks over stacks of its own:
the prefill attends under the banded mask (ops/attention.py `window=`: key
blocks before the band are skipped) and keeps the last W columns of its bucket
round the ring; the decode core (`_kv_decode_core`, one for both kinds of
stack) writes row b's k, v at slot pos[b] mod W and attends to the slots whose
position, the newest that falls on them, lies in [max(pads[b], pos[b] + 1 -
window), pos[b]]: through the same kernel told `ring=True` (a live row is one
key block: one fetch), or `_masked_attention` under `_ring_seen`.  Keys are
stored turned, so their order in the ring does not matter to the softmax.
Which layers' rows share a stack, and where a layer's lie in it, is
`_state_index`: the model's order among the layers that keep that kind of
state, whatever their kind's FFN.

A kda block (Kimi Delta Attention: transformer.py `_kda_mixer`) keeps a MATRIX state a
head, [n, B, H, D, D] float32, and its convolution's last inputs in the "ssm"
stacks at its own shapes.  A prefill runs the delta rule in chunks from the zero state
(pads masked: they leave the state as it is) and hands the state on as its rows; a
decode step moves it on one position (`_kda_decode_mixer`): on a TPU through
ops/kda.py's kernel, which is handed the stack as it lies and the layer's index and
fetches, updates and writes back the rows that hold a request and no others; anywhere
else the layer's state is read out of the stack and the new one written in its place,
as a state-space layer's is.

Latent attention (`cfg.latent`) has the same two blocks with cores of its own (its
queries through a low rank or, `cfg.q_lora_rank` 0, straight to their heads; the shared
key turned, or under `cfg.rotary` False carried as it is projected).
The prefill core EXPANDS: every head's key and value are made of the prompt's
latents (`transformer._latent_expand`) and go through the same flash kernel, at
a q/k width of nope + rope and a value width of v; what it stores is the
latents.  The decode core ABSORBS: the query's nope part is carried into the
latent space (q W_UK^T, a head at a time), the scores are taken against the
cached rows as they lie (`_latent_attention`, which is `_masked_attention`
with all H query heads on the one cached "head": q_lat . c_kv + q_rope .
k_rope), the probabilities weigh the latent rows, and W_UV brings each head's
weighted latent out.  The same numbers as the expanded form by associativity;
nothing of the cache's size is ever expanded to heads.

A program that is given a cache carries it through the layer loop
(`_scan_blocks`): the stacks are one buffer from the program's argument to its
result, a decode step writes one row a slot and layer of k and v in place, and
a donated cache is never copied.  A prefill makes its own rows, as the loop's
stacked outputs.

There is one decode block, with per-row positions, and one decode program
body, `decode_rows`: `decode_one` (what `generate()` and `stream_generate`
scan) is it with every row at the same position, and the continuous batcher's
jitted step (llm/continuous.py) is it between unpacking its slot vectors and
sampling.

Compiled entry points: `prefill_counted` (one program a prompt shape: the
continuous batcher's admit calls it as it is, a bucket a program; `prefill` is
the same program without its count of held expert layers), `generate` (prefill
and the scanned decode loop as one program) and `_stream_fns`' pair.
`decode_rows`, `decode_one` and `_sample` are bodies: plain functions that run
inside their caller's program (the batcher's `_decode_step_rowpos` and
`_suffix_step`, `generate`'s scan), or eagerly where a caller has none.

Every stage runs under a `jax.named_scope` with the same name in every layer
and every program (`embed`, `norm`, `attn.qkv`, `attn.rope`, `attn.cache`,
`attn.core` (`attn.core.window` in a window layer), `attn.out`, `ffn`, `head`, `sample`; a mixture of experts adds
`moe.router`, `moe.dispatch`, `moe.experts`, `moe.combine` under `ffn`,
parallel/moe.py, and `moe.shared` for its shared experts; a state-space layer
writes `ssm.in`, `ssm.conv`, `ssm.scan`, `ssm.state`, `ssm.out` in place of the
`attn.*`; latent attention writes `attn.mla.q`, `attn.mla.kv` in place of
`attn.qkv`, `attn.mla.expand` in a prefill and `attn.mla.absorb` in a decode
step): the names reach each
operation's metadata, so a device trace sums a kind of work over the depth
whatever the compiler numbers its operations.  Metadata only: the programs
compile to the same instructions with or without them.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..ops import sparse_attention as sparse
from ..ops.attention import (
    DECODE_BLOCK_K, DECODE_BLOCK_ROWS, attention, decode_attention, decode_on_kernel, decode_rows_read, decode_span,
)
from ..ops.kda import KDA_HEADS, kda_decode_update, live_rows
from ..parallel.moe import EXPERT_MATRICES
from .transformer import (
    _INIT_KIND, SSM_STATE_DTYPE, TransformerConfig, _attention_half, _ffn_half, _gmu_block, _gqa_repeat, _hand_on,
    _head, _kda_half, _kda_mixer, _kda_zero_state, _latent_expand, _latent_up, _mamba2_half, _mamba2_zero_state, _scan_layers,
    _sparse_attention,
    _ssm_block_forward, _ssm_half, _ssm_mix, _x, carried, core_scope, is_window, layer_stacks,
)

# what a layer of each kind of state keeps of a sequence between two tokens, as
# the cache's keys: one stacked array each over the layers that keep that kind.
# "attn_win": a window layer's keys and values, a ring of `window_extent` slots a row
# Under learned sparse attention (`cfg.index_topk`) an attention layer keeps "attn_kv", a token's keys AND values
# in ONE stack (`init_cache` says why), and beside it "index", the indexer's one key head of each token
LAYER_STATE = {"attn": ("k", "v"), "ssm": ("conv", "h"), "latent": ("ckv", "kr"), "attn_win": ("kw", "vw"),
               "attn_kv": ("kv",), "index": ("ki",)}
# the scope a kind's state is read, written and installed under
STATE_SCOPE = {"attn": "attn.cache", "ssm": "ssm.state", "latent": "attn.cache", "attn_win": "attn.cache",
               "attn_kv": "attn.cache", "index": "attn.cache"}
# the kinds of layer that keep no rows of their own: a gated memory unit keeps nothing
# between two tokens (its memory is the step's own), a cross layer reads the stack of keys
# and values that the one full layer before it writes, an FFN alone mixes no positions
NO_ROWS = ("gmu", "attn_cross", "ffn")


# The rotated key's last axis in the cache is padded with zeros to a multiple
# of the chip's 128 lanes.  An array whose last axis is 64 wide (or 576: latent
# and key in one row) is laid out by the chip's compiler with T_max as its minor
# axis, and a decode step then copies the whole stack in and out of the layout
# its dots want: 0.25 GB a step for the keys alone at 7 x 32 x 4352 x 64, 2.2 GB
# for one array of 576 (what the compiler made of each is in PERF.md section 4,
# Model 5).  A row of 128 is read as it lies; the upper lanes meet zeros of the query.
LATENT_LANES = 128


def _lanes(a):
    """`a` with its last axis padded with zeros to a multiple of LATENT_LANES."""
    return jnp.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, -a.shape[-1] % LATENT_LANES)])


def _state_kind(kind: str, cfg: TransformerConfig) -> Optional[str]:
    """The kind of state (LAYER_STATE's key) a layer of `kind` keeps, or of a
    cross layer (which keeps none: NO_ROWS) reads; None for a layer that
    touches none."""
    if kind in ("gmu", "ffn"):
        return None
    if kind in ("ssm", "mamba2", "kda", "kda_dense"):  # a Mamba-2 or kda layer's window and state are the "ssm" rows, at its own shapes
        return "ssm"
    if is_window(kind):
        return "attn_win"
    if cfg.index_topk:
        return "attn_kv"
    return "latent" if cfg.latent else "attn"


def window_extent(cfg: TransformerConfig, t_max: int) -> int:
    """The slots a window layer's cache keeps of a row: `cfg.attn_ring`, or
    the least the decode kernel's key block allows that holds the window (256
    at 8 cached heads: ops/attention.py decode_key_block); the whole context
    where that is no longer.  Position p lies at slot p mod the extent."""
    block = max(DECODE_BLOCK_K, DECODE_BLOCK_ROWS // cfg.cached_heads)
    return min(cfg.attn_ring or -(-cfg.attn_window // block) * block, t_max)


def _state_index(cfg: TransformerConfig):
    """{kind: for each of the kind's layers, in order, its index in the stacks
    of the state it keeps}: kinds that keep one kind of state (a mixture's
    leading dense layers and its expert layers) share those stacks in the
    model's order.  A kind that keeps no rows (NO_ROWS) has no place in any
    stack: its layers are numbered among themselves."""
    seen, out = {}, {}
    for kind in cfg.layer_kinds:
        state = kind if kind in NO_ROWS else _state_kind(kind, cfg)
        out.setdefault(kind, []).append(seen.get(state, 0))
        seen[state] = seen.get(state, 0) + 1
    return out


def shared_layer(cfg: TransformerConfig) -> int:
    """Where, in the full layers' stacks k and v, the keys and values lie that
    the cross layers read: those of the full layer before them."""
    return _state_index(cfg)["attn"][-1]


def _scan_blocks(bodies, x, params, cfg: TransformerConfig, cache=None):
    """The layer loop (`transformer._scan_layers`) of the programs that keep a
    cache: `bodies[kind](x, bp, experts, cache, layer) -> (x, cache, ys)`; x is
    what a layer hands the next, `transformer.Carried` where that is more than
    the residual stream (`carried`).
    `cache` is the loop's carry beside x: every kind's whole stacks, which a
    decode body reads and writes at `layer`, the layer's number within its
    kind (LAYER_STATE says which arrays are a kind's).  None for a prefill,
    which makes its own rows as `ys` (and is given no `layer` by a model that
    has no other use for one).  A mixture of experts' matrices are not
    scanned: the grouped matmul that reads them is a kernel, and a layer's
    slice of the stack handed to a kernel is a copy of every expert at every
    step.  `experts` is the whole stack and the layer's index among the expert
    layers, which `routed_ffn` reads in place; None for a dense model or layer.  Returns (x, the
    cache after, {kind: ys over that kind's layers})."""

    index = _state_index(cfg)

    def body(kind, carry, bp, held, layer):
        x, cache = carry
        # where the layer's state lies in its stacks: a mixture's leading dense
        # layers come first among the attention layers' state, so an expert
        # layer's lies that many further on
        at = layer
        ahead = {a - i for i, a in enumerate(index[kind])}
        if layer is not None and ahead != {0}:
            at = layer + ahead.pop() if len(ahead) == 1 else jnp.asarray(index[kind], jnp.int32)[layer]
        x, cache, ys = bodies[kind](x, bp, (held, layer) if held else None, cache, at)
        return (x, cache), ys

    stacks = layer_stacks(params)
    (x, cache), outs = _scan_layers(body, (x, cache), stacks, cfg,
                                    unsliced={kind: EXPERT_MATRICES for kind, b in stacks.items() if "router" in b},
                                    indexed=cache is not None or cfg.diff_attn)
    return x, cache, outs


def _masked_attention(q, k_cache, v_cache, valid_len, cfg: TransformerConfig, pad=None, scale=None,
                      also=None, seen=None, out_dtype=None):
    """q: [B, Tq, H, D]; caches: [B, T_max, KV, D] as stored, never repeated to
    H heads and never copied to f32.  The query is viewed as [B, Tq, KV, R, D]
    (R = H // KV query heads share one cached head; R == 1 is multi-head
    attention) and both contractions take the cache in its own dtype with an
    f32 accumulator.  Cache slots >= valid_len (a scalar, or a per-row [B]) are
    masked out, as are slots < pad[b] (left-padding of the prompt; pad is a
    per-row [B] count of pad tokens, None = no padding).  For decode Tq == 1.
    scale: what the scores are multiplied by, d_head^-0.5 unless given.  also:
    (q2 [B, Tq, H, D2], k2 [B, T_max, KV, D2]), a second part of every query and
    key kept in a cache of its own, whose products add to the scores.  seen:
    [B, T_max] bool, the slots a row's queries see, in place of valid_len and
    pad (a window layer's ring: `_ring_seen`).  out_dtype: the result's type
    where it is not the queries' (`_core_dtype`).
    Returns [B, Tq, H, Dv], Dv the cached values' width."""
    b, tq, h, d = q.shape
    t_max, kv = k_cache.shape[1:3]
    qg = q.reshape(b, tq, kv, h // kv, d)
    logits = jnp.einsum("bqgrd,bkgd->bgrqk", qg, k_cache, preferred_element_type=jnp.float32)
    if also is not None:
        q2, k2 = also
        logits = logits + jnp.einsum("bqgrd,bkgd->bgrqk", q2.reshape(b, tq, kv, h // kv, -1), k2,
                                     preferred_element_type=jnp.float32)
    logits = logits * (cfg.d_head ** -0.5 if scale is None else scale)
    slots = jnp.arange(t_max)
    if seen is not None:
        mask = seen[:, None, None, None, :]
    else:
        mask = slots < jnp.reshape(valid_len, (-1, 1, 1, 1, 1))
        if pad is not None:
            mask = mask & (slots >= pad[:, None, None, None, None])
    logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bgrqk,bkgd->bqgrd", probs, v_cache, preferred_element_type=jnp.float32)
    return out.astype(out_dtype or q.dtype).reshape(b, tq, h, v_cache.shape[-1])


def _core_dtype(cfg: TransformerConfig):
    """The type an attention core's result is asked in where it is not the
    queries': float32 under differential attention, whose two maps' results
    are subtracted before anything is rounded (`transformer._diff_combine`)."""
    return jnp.float32 if cfg.diff_attn else None


def _latent_attention(q_lat, q_rope, ckv, kr, valid_len, cfg: TransformerConfig, pad=None):
    """Attention in the latent space: `_masked_attention` with every query
    head on the one cached "head" that a latent cache has.  q_lat: [B, Tq, H, R],
    the queries' nope parts carried through W_UK; q_rope: [B, Tq, H, rope];
    ckv [B, T_max, R] and kr [B, T_max, rope up to LATENT_LANES] as stored.  A
    head's score against a slot is q_lat . ckv + q_rope . kr and the values
    are the latent rows themselves.  Returns [B, Tq, H, R]: each head's
    weighted latent, which W_UV brings out."""
    one = lambda a: a[:, :, None, :]  # [B, T_max, 1, .]: the one cached "head"
    return _masked_attention(q_lat, one(ckv), one(ckv), valid_len, cfg, pad, scale=cfg.attn_scale,
                             also=(_lanes(q_rope), one(kr)))


def _ring_seen(first, last, extent: int):
    """[B, extent] bool: the slots of a ring of `extent`, position p at slot
    p mod extent, whose position (the newest that falls on the slot, up to
    last - 1) lies in [first, last).  first, last: [B]."""
    behind = (last[:, None] - 1 - jnp.arange(extent)) % extent  # how far behind the newest a slot's position lies
    return behind < (last - first)[:, None]


def init_cache(cfg: TransformerConfig, batch: int, t_max: int):
    """Every kind of state the configuration's layers keep, each stacked over
    the layers that keep it alone: k, v [n_attn, B, t_max, KV, D] (under learned
    sparse attention kv [n_attn, B, t_max, 2 KV, D], keys and values in one stack,
    and the indexer's keys ki [n_attn, B, DI, t_max]), or under
    latent attention ckv [n_attn, B, t_max, R] and kr [n_attn, B, t_max, rope up
    to LATENT_LANES] (a token's latent and its rotated key);
    the window layers' kw, vw [n_win, B, W, KV, D], W = `window_extent`: an
    extent of their own, whatever the context's length;
    a state-space layer's convolution window [n_ssm, B, K-1, C] and its h
    [n_ssm, B, C, N] in SSM_STATE_DTYPE, whatever the context's length (a
    Mamba-2 layer's: [n, B, K-1, C + 2 G N] and [n, B, H, P, N]; a kda layer's:
    [n, B, K-1, 3 H D] and [n, B, H, D, D]).  A layer
    that keeps no rows (NO_ROWS) adds nothing; KV and D are the heads as they
    are cached (`cfg.cached_heads`, `cfg.cached_width`), and under
    `cfg.flat_heads` the four stacks of keys and values are [n, B, T * KV, D]."""
    kinds = [kind for kind in cfg.layer_kinds if kind not in NO_ROWS]
    n_ssm = sum(_state_kind(kind, cfg) == "ssm" for kind in kinds)
    n_win = sum(map(is_window, kinds))
    n_attn = len(kinds) - n_ssm - n_win
    cache = {}
    # [T, KV, D], or flat: a slot's cached heads as rows of their own (`cfg.flat_heads`)
    slots = lambda t: (t * cfg.flat_heads,) if cfg.flat_heads else (t, cfg.cached_heads)
    if n_win:
        shape = (n_win, batch, *slots(window_extent(cfg, t_max)), cfg.cached_width)
        cache.update(kw=jnp.zeros(shape, cfg.dtype), vw=jnp.zeros(shape, cfg.dtype))
    if n_attn and cfg.latent:
        rope = -(-cfg.qk_rope_head_dim // LATENT_LANES) * LATENT_LANES
        cache.update(ckv=jnp.zeros((n_attn, batch, t_max, cfg.kv_lora_rank), cfg.dtype),
                     kr=jnp.zeros((n_attn, batch, t_max, rope), cfg.dtype))
    elif n_attn and cfg.index_topk:
        # learned sparse attention: a decode step GATHERS a row's selected slots, and a gather costs by the row it
        # fetches, not by the row's bytes: 8,192 rows of 1 KB (4 cached heads of 128) took 183 us and 8,192 rows
        # of 2 KB 172 us on a v5e (PERF.md section 6, PR 56), so a token's keys and values lie side by side in ONE
        # stack, heads 0 .. KV-1 the keys and KV .. 2 KV-1 the values, and one gather fetches both
        cache.update(kv=jnp.zeros((n_attn, batch, t_max, 2 * cfg.cached_heads, cfg.cached_width), cfg.dtype))
        # the indexer's keys, one head of index_head_dim a token, with the positions innermost: a last axis 64 wide
        # is no whole tile of lanes (LATENT_LANES, above; a kernel handed such a stack is handed a copy of all of
        # it, 0.43 GB a call at 48 x 4 x 8,704), and [DI, T_max] is the second operand of the scores' product as the
        # matrix unit takes it (a step's scan of a layer: 63 us so against 75, PERF.md section 6, PR 56)
        cache.update(ki=jnp.zeros((n_attn, batch, cfg.index_head_dim, t_max), cfg.dtype))
    elif n_attn:
        shape = (n_attn, batch, *slots(t_max), cfg.cached_width)
        cache.update(k=jnp.zeros(shape, cfg.dtype), v=jnp.zeros(shape, cfg.dtype))
    if n_ssm:
        # Mamba-2: [heads, head_dim, N] a slot, and a window over x, B and C together; kda: a matrix [D, D] a head,
        # and a window over q, k and v together
        state = ((cfg.kda_n_heads, cfg.kda_head_dim, cfg.kda_head_dim) if "kda" in kinds else
                 (cfg.ssm_n_heads, cfg.ssm_head_dim, cfg.ssm_d_state) if "mamba2" in kinds
                 else (cfg.d_inner, cfg.ssm_d_state))
        cache.update(
            conv=jnp.zeros((n_ssm, batch, cfg.ssm_d_conv - 1, cfg.conv_width), cfg.dtype),
            h=jnp.zeros((n_ssm, batch, *state), SSM_STATE_DTYPE),
        )
    return cache


def install_rows(cache, rows, slot):
    """One request's rows (a cache of batch one, as `prefill` and a suffix's
    `decode_rows` return it) written whole over slot `slot` of `cache`: the
    batch axis comes off here.  A reused slot's stale key/value rows are masked
    by position; its recurrent state is masked by nothing, so every array of
    the slot is overwritten."""
    out = {}
    for kind, names in LAYER_STATE.items():
        with jax.named_scope(STATE_SCOPE[kind]):
            out.update({n: cache[n].at[:, slot].set(rows[n][:, 0]) for n in names if n in cache})
    return out


def recurrent_state_bytes(cache) -> int:
    """The bytes of a cache's recurrent state (every slot's convolution window
    and h over the state-space layers): what a decode step reads and writes
    again whatever the rows' depths.  0 for a cache of keys and values alone."""
    return sum(int(cache[n].size) * cache[n].dtype.itemsize for n in LAYER_STATE["ssm"] if n in cache)


def _token_bytes(cache, kinds, cfg: Optional[TransformerConfig]) -> int:
    """The bytes of one slot of the stacks of `kinds`, summed over their layers
    (a flat stack holds a slot as `cfg.flat_heads` rows)."""
    names = [n for kind in kinds for n in LAYER_STATE[kind] if n in cache]
    heads = max(cfg.flat_heads, 1) if cfg is not None else 1
    return sum(int(cache[n].size) * cache[n].dtype.itemsize // (cache[n].shape[1] * cache[n].shape[2])
               for n in names) * heads


def _index_token_bytes(cache) -> int:
    """The bytes of one slot's indexer keys over their layers ([n, B, DI, T]: the positions are the last axis)."""
    ki = cache.get("ki")
    return 0 if ki is None else int(ki.size) * ki.dtype.itemsize // (ki.shape[1] * ki.shape[3])


def cache_bytes_per_token(cache, cfg: Optional[TransformerConfig] = None) -> int:
    """The bytes one token of one sequence takes in a cache over all the layers
    that attend (its keys and values and, where there is an indexer, its indexer key, or its latent row and rotated key; in a
    window layer too, while the layer holds it: each array by its own extent).
    A layer that reads another layer's stack keeps nothing and adds nothing.
    0 for a cache of recurrent state alone.  cfg: the cache's configuration,
    where its stacks may be flat."""
    return _token_bytes(cache, ("attn", "attn_kv", "latent", "attn_win"), cfg) + _index_token_bytes(cache)


def cache_context_bytes_per_token(cache, cfg: Optional[TransformerConfig] = None) -> int:
    """The bytes one MORE token of a sequence's context adds to a cache: the
    part of `cache_bytes_per_token` that lies in stacks as long as the context.
    A window layer's ring and a recurrent state are a slot's whatever its
    context holds (`cache_kind_bytes`, `recurrent_state_bytes`).  0 for a cache
    of rings or recurrent state alone."""
    return _token_bytes(cache, ("attn", "attn_kv", "latent"), cfg) + _index_token_bytes(cache)


def cache_kind_bytes(cache) -> Dict[str, int]:
    """{"full": the bytes of the stacks whose extent is the context's (keys and
    values and the indexer's keys, latent rows), "window": those of the window layers' rings}."""
    size = lambda kinds: sum(int(cache[n].size) * cache[n].dtype.itemsize
                             for kind in kinds for n in LAYER_STATE[kind] if n in cache)
    return {"full": size(("attn", "attn_kv", "latent", "index")), "window": size(("attn_win",))}


def key_slots(cache, first=None, last=None, window: int = 0, cfg: Optional[TransformerConfig] = None):
    """(the slots of one layer's keys (and as many of its values, or its latent
    rows) in a cache that attends, the window layers' part of that number, the
    part of the one stack that `readers` layers share).
    Where the layers' extents differ (window layers keep a ring beside the full
    layers' T_max) it is the sum over the layers that READ a stack divided by
    their number, so one extent reads as ever; cfg: the cache's configuration,
    where `cfg.shared_readers` layers read the full layers' last stack, which
    then counts so many times, or the stacks are flat (`cfg.flat_heads` rows a
    slot).  Without first and last: every row's
    extent, what a decode step's attention may read of a layer.  Given first
    and last (numpy [rows], the host's: the rows attend to [first, last) of
    their own, a window layer to the last `window` of those), the slots the
    step fetches: whole key blocks of those rows under the decode kernel
    (ops/attention.py decode_rows_read; a window layer's row is its ring),
    every slot of every row under latent attention, whose core contracts with
    the layer whole, and a row's selected slots alone, min(its context,
    `cfg.index_topk`), under learned sparse attention.  (0, 0, 0) for a cache of recurrent state alone."""
    readers, flat_heads = (cfg.shared_readers, cfg.flat_heads) if cfg is not None else (0, 0)
    per_layer = {}  # the stack's name -> (the layers that read it, the slots of one)
    for name in ("k", "kv", "ckv", "kw"):
        if name not in cache:
            continue
        n, rows, t_max, *heads = cache[name].shape
        if flat_heads and name != "ckv":
            t_max, heads = t_max // flat_heads, [flat_heads]
        if name == "k" and readers:
            n += readers - 1  # the layer that writes the shared stack is one of its readers
        if first is None or name == "ckv":
            per_layer[name] = n, rows * t_max
        elif cfg is not None and cfg.index_topk and t_max > cfg.index_topk:
            # learned sparse attention gathers a row's selected slots and no other
            per_layer[name] = n, int(np.minimum(last - first, cfg.index_topk).sum())
        else:
            lo = np.maximum(first, last - window) if name == "kw" else first
            per_layer[name] = n, int(decode_rows_read(lo, last, t_max, heads[0], ring=name == "kw").sum())
    layers = sum(n for n, _ in per_layer.values())
    if not layers:
        return 0, 0, 0
    ring = per_layer.pop("kw", (0, 0))
    shared = readers * per_layer["k"][1] if readers else 0
    return ((sum(n * slots for n, slots in per_layer.values()) + ring[0] * ring[1]) // layers,
            ring[0] * ring[1] // layers, shared // layers)


def _on_kernel(cache) -> bool:
    """Whether a decode step over `cache` attends through the decode kernel: on
    a TPU, keys and values, more than one row.  A cache of one row (an admit's
    suffix step, a check's pass over one request) keeps the dense contraction:
    there is no dead row to spare it, and with a batch axis of one the chip's
    compiler gives the carried stacks a layout of its own and copies them to
    the kernel's at every layer (16 x 2 x 25 MB a token at Mistral's widths;
    from two rows on the stacks are read where they lie)."""
    name = next((n for n in ("k", "kw") if n in cache), None)
    return decode_on_kernel() and name is not None and cache[name].shape[1] > 1


def _span(cache, valid_len, pads, live, cfg: TransformerConfig):
    """{kind of state: `decode_span` of a decode step's rows over that kind's
    keys and values}: the full layers' [pads, valid_len) of T_max, the window
    layers' last `cfg.attn_window` of those, as positions, over their ring."""
    spans = {}
    extent_heads = lambda a: (a.shape[2] // cfg.flat_heads, cfg.flat_heads) if cfg.flat_heads else a.shape[2:4]
    if "k" in cache:
        t_max, kv = extent_heads(cache["k"])
        spans["attn"] = decode_span(pads, valid_len, live, t_max, kv)
    if "kw" in cache:
        extent, kv = extent_heads(cache["kw"])
        spans["attn_win"] = decode_span(jnp.maximum(pads, valid_len - cfg.attn_window), valid_len, live, extent, kv,
                                        ring=True)
    return spans


def _spans(cache, pos, t: int, pads, live, cfg: TransformerConfig, pending=None):
    """(`_span` of a decode step whose rows hold t positions from pos on,); where
    the first half of them is the block before (`pending`: `_kv_decode_core`),
    the two halves': the first for the rows that have a block pending alone, up
    to pos; the second from pos on, as a pass without it."""
    if pending is None:
        return (_span(cache, pos + t, pads, live, cfg),)
    return _span(cache, pos, pads, pending, cfg), _span(cache, pos + t // 2, pads, live, cfg)


def _latent_decode_core(bp, cache, layer, pos, pads, cfg: TransformerConfig, q, k_rope, c_kv):
    """The decode block's core under latent attention, one token a row: row b's
    latent c_kv [B, 1, R] and rotated key k_rope [B, 1, rope] are written at
    [layer, b, pos[b]] of the stacks ckv and kr, the query's nope part is
    absorbed into the latent space (q_lat = q_nope W_UK^T), attention runs over
    the layer's latent rows as stored (`_latent_attention`) and W_UV brings each
    head's weighted latent out.  q: [B, 1, H, nope + rope].  Returns
    (attn [B, 1, H, v], the cache after)."""
    dn = cfg.qk_nope_head_dim
    with jax.named_scope(STATE_SCOPE["latent"]):
        at = (layer, jnp.arange(q.shape[0]), pos)
        ckv_all = cache["ckv"].at[at].set(c_kv[:, 0])
        kr_all = cache["kr"].at[at].set(_lanes(k_rope[:, 0]))
        ckv, kr = (lax.dynamic_index_in_dim(a, layer, keepdims=False) for a in (ckv_all, kr_all))
    with jax.named_scope("attn.mla.absorb"):
        w_uk, w_uv = _latent_up(bp, cfg, q.dtype)
        q_lat = jnp.einsum("bthn,rhn->bthr", q[..., :dn], w_uk)
    with jax.named_scope("attn.core"):
        o_lat = _latent_attention(q_lat, q[..., dn:], ckv, kr, pos + 1, cfg, pads)
    with jax.named_scope("attn.mla.absorb"):
        attn = jnp.einsum("bthr,rhv->bthv", o_lat, w_uv)
    return attn, {**cache, "ckv": ckv_all, "kr": kr_all}


def _attend_selected(q, kv_all, layer, at, chosen, cfg: TransformerConfig):
    """q [B, 1, H, D] against the listed slots of each row and NO other: of the
    stack kv_all [n, B, T_max, 2 KV, D] the rows [layer, b, at[b]] are gathered
    in one gather, [B, topk, 2 KV, D], keys and values side by side, and the
    first chosen[b] of them attended to (`_masked_attention` over the gathered
    rows)."""
    kv = cfg.cached_heads
    listed = kv_all[layer, jnp.arange(q.shape[0])[:, None], at]
    return _masked_attention(q, listed[:, :, :kv], listed[:, :, kv:], chosen, cfg)


def _sparse_decode_core(cache, layer, pos, pads, cfg: TransformerConfig, q, k, v, index, listed: bool = False):
    """A decode step's core under learned sparse attention, one token a row.
    Row b's keys and values k, v [B, 1, KV, D] are written side by side at
    [layer, b, pos[b]] of the stack `kv` and its indexer key at [layer, b, :,
    pos[b]] of `ki`.  Over a cache longer than `cfg.index_topk` the row's
    indexer queries score its whole context [pads[b], pos[b]] against the
    layer's indexer keys (`attn.indexer`), the `cfg.index_topk` best are listed
    (`attn.select`; fewer while the context is shorter: then all of it), and of
    `kv` the listed slots of the row and NO other are gathered and attended to
    (`attn.sparse_core`): the scopes an admit's prefill writes, each one level
    deep.  A shorter cache's selection leaves nothing out: the layer is taken
    out of the stack and attended to whole, as ever.
    index: `transformer._project_index`'s of the step's tokens, or the way to
    it.  Returns (attn [B, 1, H, D], the cache after), and with `listed` the
    list and its count behind them."""
    if q.shape[1] != 1 or k is None:
        raise NotImplementedError("learned sparse attention decodes one token a row over its own stacks")
    rows, kv, extent = jnp.arange(q.shape[0]), cfg.cached_heads, cache["kv"].shape[2]
    with jax.named_scope(STATE_SCOPE["attn_kv"]):
        kv_all = cache["kv"].at[layer, rows, pos].set(jnp.concatenate([k[:, 0], v[:, 0]], axis=1))
    with jax.named_scope("attn.indexer"):
        qi, ki, w = index() if callable(index) else index
    with jax.named_scope(STATE_SCOPE["index"]):
        ki_all = cache["ki"].at[layer, rows, :, pos].set(ki[:, 0])
    after = {**cache, "kv": kv_all, "ki": ki_all}
    if extent <= cfg.index_topk:
        with jax.named_scope(STATE_SCOPE["attn_kv"]):
            kv_layer = lax.dynamic_index_in_dim(kv_all, layer, keepdims=False)
        with jax.named_scope("attn.sparse_core"):
            attn = _masked_attention(q, kv_layer[:, :, :kv], kv_layer[:, :, kv:], pos + 1, cfg, pads)
        return (attn, after, None) if listed else (attn, after)
    with jax.named_scope("attn.indexer"):
        ki_layer = lax.dynamic_index_in_dim(ki_all, layer, keepdims=False)  # [B, DI, T_max], as it lies
        scores = sparse.index_scores_reference(qi, ki_layer, w, keys_last=True)[:, 0]  # [B, T_max]
    with jax.named_scope("attn.select"):
        at, chosen = sparse.select_rows(scores, pads, pos + 1, cfg.index_topk)
    with jax.named_scope("attn.sparse_core"):
        attn = _attend_selected(q, kv_all, layer, at, chosen, cfg)
    return (attn, after, (at, chosen)) if listed else (attn, after)


def _kv_decode_core(cache, layer, pos, pads, cfg: TransformerConfig, q, k, v, live=None, span=None, kind: str = "attn",
                    pending=None, index=None):
    """The decode block's core over cached keys and values.  q: [B, T, H, D],
    k, v: [B, T, KV, D] of the step's own positions (T = 1, or a block's): they
    are written at [layer, b, pos[b] ...] of the stacks of the layer's state
    (`_state_kind`: k, v, or a window layer's ring kw, vw, at pos[b] mod its
    extent), and row b attends to the slots its kind sees, through the decode
    kernel over the stacks as they lie (`_on_kernel`) or the dense contraction
    over the layer taken out of them.  k = v = None: a cross layer's, which
    writes nothing and attends to the stack another layer wrote, where it lies
    (`shared_layer`).  live, span, kind, pending: `_block_decode_rowpos`'s.
    pending [B] bool: T is two blocks, the first the block before, from
    pos[b] - T / 2 on: its keys and values are written there for the rows that
    have one pending and for no other row (a scatter that drops the others'
    rows: nothing of the stack is read), and its queries see the slots before
    pos[b]; the second half as a pass without it.
    index: the way to the indexer's part of the step's tokens under learned
    sparse attention, whose core is `_sparse_decode_core` over stacks of its own.
    Returns (attn [B, T, H, Dv], the cache after)."""
    if index is not None:
        return _sparse_decode_core(cache, layer, pos, pads, cfg, q, k, v, index)
    t = q.shape[1]
    state = _state_kind(kind, cfg)
    ring = state == "attn_win"
    if ring and t > 1:
        raise NotImplementedError("a pass over a block of positions through a window layer's ring")
    k_name, v_name = LAYER_STATE[state]
    heads = cfg.flat_heads  # the stacks are [n, B, T * KV, D]: a slot's heads are rows pos KV .. pos KV + KV - 1
    extent = cache[k_name].shape[2] // max(heads, 1)
    half = t // 2
    if k is None:
        layer, k_all, v_all = shared_layer(cfg), cache[k_name], cache[v_name]
    else:
        with jax.named_scope(STATE_SCOPE[state]):
            rows = jnp.arange(q.shape[0])
            if t == 1 and heads:
                slot = pos % extent if ring else pos
                at, new = (layer, rows[:, None], slot[:, None] * heads + jnp.arange(heads)), lambda a: a[:, 0]
            elif t == 1:
                at, new = (layer, rows, pos % extent if ring else pos), lambda a: a[:, 0]
            elif pending is None:
                at, new = (layer, rows[:, None], pos[:, None] + jnp.arange(t)), lambda a: a
            else:
                # a row with no block pending writes its first half past the stack's end: nowhere
                mine = pending[:, None] | (jnp.arange(t) >= half)
                at, new = (layer, rows[:, None], jnp.where(mine, pos[:, None] + jnp.arange(-half, half), extent)), lambda a: a
            k_all = cache[k_name].at[at].set(new(k), mode="drop")
            v_all = cache[v_name].at[at].set(new(v), mode="drop")
    # the queries that share a span: all of them, or a half each
    parts = [q] if pending is None else [q[:, :half], q[:, half:]]
    if _on_kernel(cache):
        if span is None:
            span = _spans(cache, pos, t, pads, live, cfg, pending)
        # the stacks and the layer's index: a layer's slice handed to a kernel is a copy of it
        with jax.named_scope(core_scope(kind, cfg)):
            attn = [decode_attention(part, k_all, v_all, layer, sp[state], scale=cfg.d_head ** -0.5, ring=ring,
                                     out_dtype=_core_dtype(cfg), kv=heads) for part, sp in zip(parts, span)]
    else:
        with jax.named_scope(STATE_SCOPE[state]):
            k_layer, v_layer = (lax.dynamic_index_in_dim(a, layer, keepdims=False) for a in (k_all, v_all))
            if heads:
                k_layer, v_layer = (a.reshape(a.shape[0], extent, heads, a.shape[-1]) for a in (k_layer, v_layer))
        with jax.named_scope(core_scope(kind, cfg)):
            seen = _ring_seen(jnp.maximum(pads, pos + 1 - cfg.attn_window), pos + 1,
                              k_layer.shape[1]) if ring else None
            ends = [pos + t] if pending is None else [pos, pos + half]  # per-row lengths
            attn = [_masked_attention(part, k_layer, v_layer, end, cfg, pads, seen=seen, out_dtype=_core_dtype(cfg))
                    for part, end in zip(parts, ends)]
    attn = attn[0] if len(attn) == 1 else jnp.concatenate(attn, axis=1)
    return attn, {**cache, k_name: k_all, v_name: v_all}


def _block_decode_rowpos(bp, x, cache, layer, pos, cfg: TransformerConfig, pads, live=None,
                         experts=None, span=None, kind: str = "attn", pending=None):
    """One block, one token, PER-ROW cache positions (continuous batching:
    every slot decodes at its own depth).  x: [B, 1, E]; pos/pads: [B];
    cache: the attention layers' stacks k, v [n_attn, B, Tmax, KV, D] (among
    whatever else it holds; ckv, kr under latent attention, whose core absorbs
    the up-projections: module docstring) and layer: this one's number among them.  Row b
    writes its k/v at [layer, b, pos[b]], one scatter of [B, KV, D] an array
    and nothing else of the stacks, takes RoPE position pos[b] - pads[b], and
    attends to slots [pads[b], pos[b]] of the layer, read where it lies.
    live: [B] bool, the rows that hold a request: an empty slot's row reads
    nothing of the cache on a TPU (the kernel returns it zeros) and takes no
    expert (None: every row does both).  span: `_spans` of the rows, made
    once a step by `decode_rows`; None: made here.  Returns (x, the cache
    after, experts touched or None: `_ffn_half`).

    kind: the layer's.  A window layer (`is_window`) keeps the stacks kw, vw
    [n_win, B, W, KV, D], a ring: row b writes at [layer, b, pos[b] mod W] and
    attends to the slots that hold positions [max(pads[b], pos[b] + 1 -
    cfg.attn_window), pos[b]], under scope `attn.core.window`.

    x: [B, T, E] with T > 1 is one pass of a model that generates by blocks
    (`cfg.block_length` = T): row b's T positions lie at slots pos[b] ..
    pos[b] + T - 1, their k/v are written there (again at every pass of the
    block: those of the block's final tokens are written by the next block's
    first pass, below), and each attends to slots [pads[b], pos[b] + T): every
    earlier block and the whole of its own, in both directions.

    pending: [B] bool, of the live rows, with x: [B, 2T, E]: the pass also
    stores the block before.  Row b's first T positions are that block's final
    tokens, at slots pos[b] - T .. pos[b] - 1: where pending[b], their k/v are
    written there and each attends to [pads[b], pos[b]), as a pass over that
    block alone would; where not, the half is dead: it writes nothing, reads
    nothing and takes no expert.  The second T positions are the pass above,
    and see the first half's k/v of this layer where they were just written."""
    t = x.shape[1]
    positions = (pos - pads)[:, None]
    if cfg.latent:
        core = functools.partial(_latent_decode_core, bp, cache, layer, pos, pads, cfg)
    else:
        core = functools.partial(_kv_decode_core, cache, layer, pos, pads, cfg, live=live, span=span, kind=kind,
                                 pending=pending)
    if t > 1:
        positions = positions + (jnp.arange(t) if pending is None else jnp.arange(-(t // 2), t // 2))
    x, cache = _attention_half(bp, x, cfg, positions, core, kind, layer)
    if pending is not None:
        live = jnp.repeat(jnp.stack([pending, live], axis=1), t // 2, axis=1)  # [B, 2T]: a half each
    elif live is not None:
        live = live[:, None] if t == 1 else jnp.broadcast_to(live[:, None], x.shape[:2])
    x, _, touched = _ffn_half(bp, x, cfg, live, experts, kind=kind)
    return x, cache, touched


def _prefill_tail(cfg: TransformerConfig, kind: str) -> bool:
    """Whether a prefill computes a layer of `kind` at the prompt's last
    position alone.  Where the stack ends in layers that read another layer's
    keys and values or its memory (`cfg.carries`), nothing above the full layer
    that writes those keys and values mixes positions: the logits at the last
    position need that layer's keys and values of every position, and its
    queries and everything above at the last alone.  The loop goes on carrying
    x whole (a scan's carry keeps its shape) and such a layer reads and writes
    its last row."""
    return cfg.carries and (kind in NO_ROWS or (kind == "attn" and cfg.shared_readers > 0))


def _prefill_block(bp, s, pad, cfg: TransformerConfig, t_max: int, experts=None, kind: str = "attn", layer=None):
    """One block over the whole prompt (s: x [B, T, E], or `transformer.Carried`
    with it; `_prefill_tail` layers compute the last position alone, a cross
    layer against the keys and values the loop carries, and the full layer
    below them hands its own on); returns padded caches [B,Tmax,KV,D]
    (under latent attention the latents [B,Tmax,R] and rotated keys [B,Tmax,rope up to 128s];
    of a window layer, `kind`, the ring [B,W,KV,D] of its last W = `window_extent`
    columns, column j at slot j mod W, attended under the banded mask).
    pad: [B] per-row left-pad counts or None. Real tokens sit at columns
    [pad[b], T); they get RoPE positions starting at 0 and never attend to
    pad-token keys (ADVICE r1: unmasked pads skewed generation).  layer: the
    layer's number among its kind, where `_attention_half` asks for it."""
    x = _x(s)
    b, t, _ = x.shape
    tail = _prefill_tail(cfg, kind)

    def latent_core(q, k_rope, c_kv):
        with jax.named_scope("attn.cache"):
            stored = tuple(
                lax.dynamic_update_slice(jnp.zeros((b, t_max, a.shape[-1]), x.dtype), a, (0, 0, 0))
                for a in (c_kv, _lanes(k_rope)))
        k, v = _latent_expand(bp, k_rope, c_kv, cfg)
        with jax.named_scope("attn.core"):
            attn = attention(q, k, v, causal=True, pad=pad, scale=cfg.attn_scale)
            return attn.astype(x.dtype), stored

    window = is_window(kind)
    extent = window_extent(cfg, t_max) if window else t_max

    def stored(a):
        """a [B, T, KV, D] as the cache keeps it: from slot 0 of T_max on, or
        the last `extent` columns round a window layer's ring (and flat under
        `cfg.flat_heads`: [B, extent * KV, D])."""
        if t >= extent:  # only a ring is shorter than a prompt
            kept = jnp.roll(a[:, t - extent:], (t - extent) % extent, axis=1)
        else:
            kept = lax.dynamic_update_slice(jnp.zeros((b, extent, *a.shape[2:]), x.dtype), a, (0, 0, 0, 0))
        return kept.reshape(b, -1, a.shape[-1]) if cfg.flat_heads else kept

    def core(q, k, v, index=None):
        if index is not None:
            # learned sparse attention: keys and values are kept side by side in one stack, and the
            # indexer's keys, one head a token, beside it with the positions innermost
            with jax.named_scope("attn.indexer"):
                index = index()
            with jax.named_scope("attn.cache"):
                kv_cache = stored(jnp.concatenate([k, v], axis=2))
                ki_cache = jnp.swapaxes(stored(index[1][:, :, None, :])[:, :, 0], 1, 2)  # [B, DI, T_max]
            return _sparse_attention(q, k, v, index, cfg, pad).astype(x.dtype), (kv_cache, ki_cache)
        with jax.named_scope("attn.cache"):
            k_cache, v_cache = stored(k), stored(v)
        # causal attention within the prompt (q already has full heads; only
        # k/v need the GQA repeat).  On a TPU the dispatcher runs the pad-masked
        # Pallas flash kernel at every prompt length (ops/attention.py), so
        # prefill never materializes the [T, T] score matrix.
        with jax.named_scope(core_scope(kind, cfg)):
            k, v = _gqa_repeat(k, cfg), _gqa_repeat(v, cfg)
            # a model that generates by blocks prefills under its block mask:
            # the prompt, and with it the pad, is then a multiple of the block
            attn = attention(q, k, v, causal=True, pad=pad, block=cfg.block_length,
                             window=cfg.attn_window * window, scale=cfg.d_head ** -0.5, out_dtype=_core_dtype(cfg))
            return attn.astype(_core_dtype(cfg) or x.dtype), (k_cache, v_cache)

    def last_core(q, k, v):
        """The last position's queries against every position's keys and
        values: the layer's own, which it stores and hands on, or (k = v =
        None: a cross layer) those the loop carries."""
        made = None
        if k is not None:
            with jax.named_scope("attn.cache"):
                made = (stored(k), stored(v)), (k, v)
        else:
            k, v = s.k, s.v
        with jax.named_scope(core_scope(kind, cfg)):
            return _masked_attention(q, k, v, t, cfg, pad, out_dtype=_core_dtype(cfg)), made

    positions = jnp.arange(t)
    if pad is not None:
        positions = jnp.maximum(positions[None, :] - pad[:, None], 0)  # [B, T]
    if tail:
        # the last column is a prompt's last token whatever its pad
        x_last, made = _attention_half(bp, x[:, -1:], cfg, positions[..., -1:], last_core, kind, layer,
                                       kv_of=x if "wk" in bp else None)
        x_last, _, touched = _ffn_half(bp, x_last, cfg, None, experts, kind=kind)
        layer_cache, handed = made or (None, (s.k, s.v))
        x = lax.dynamic_update_slice(x, x_last, (0, t - 1, 0))
        return s._replace(x=x, k=handed[0], v=handed[1]), layer_cache, touched
    x, layer_cache = _attention_half(bp, x, cfg, positions, latent_core if cfg.latent else core, kind, layer)
    # the left padding takes no expert
    live = None if pad is None else jnp.arange(t)[None, :] >= pad[:, None]
    x, _, touched = _ffn_half(bp, x, cfg, live, experts, kind=kind)
    return _hand_on(s, x), layer_cache, touched


def _ssm_block_decode(bp, x, cache, layer, cfg: TransformerConfig, live=None, experts=None):
    """One state-space block, one token a row, from each row's own state.
    x: [B, 1, E]; cache: the state-space layers' stacks conv [n_ssm, B, K-1, C]
    and h [n_ssm, B, C, N] (among whatever else it holds) and layer: this one's
    number among them.  The layer's state is read out of the stacks and the
    new one written in its place: a recurrent state has to move whole, once.
    A row's position and pads do not enter: the state is all a recurrence
    knows of what came before.  An empty slot's row moves its state on like
    any other (what it holds is overwritten whole when the slot is given out:
    `install_rows`).  Returns (x, the cache after, experts touched or None, the
    mixer's read-out y [B, 1, C] before its gate: the step's memory)."""

    names = LAYER_STATE["ssm"]

    def core(xs):
        with jax.named_scope(STATE_SCOPE["ssm"]):
            state = tuple(lax.dynamic_index_in_dim(cache[n], layer, keepdims=False) for n in names)
        y, state = _ssm_mix(bp, xs, state, cfg)
        with jax.named_scope(STATE_SCOPE["ssm"]):
            after = {n: lax.dynamic_update_index_in_dim(cache[n], new, layer, 0) for n, new in zip(names, state)}
        return y, ({**cache, **after}, y)

    x, (cache, y) = _ssm_half(bp, x, cfg, core)
    x, _, touched = _ffn_half(bp, x, cfg, None if live is None else live[:, None], experts)
    return x, cache, touched, y


def _mamba2_block_decode(bp, x, cache, layer, cfg: TransformerConfig):
    """One Mamba-2 layer, one token a row, from each row's own state.  x:
    [B, 1, E]; cache: the state-space stacks conv [n, B, K-1, C + 2 G N] and h
    [n, B, H, P, N] (among whatever else it holds) and layer: this one's number
    among them.  As `_ssm_block_decode`: the layer's state is read out of the
    stacks and the new one written in its place, whole, once; a donated cache's
    1.5 GB of state at Nemotron-H's widths are updated where they lie
    (tests/test_chip_compile.py).  Returns (x, the cache after)."""
    names = LAYER_STATE["ssm"]
    with jax.named_scope(STATE_SCOPE["ssm"]):
        state = tuple(lax.dynamic_index_in_dim(cache[n], layer, keepdims=False) for n in names)
    x, state = _mamba2_half(bp, x, cfg, state)
    with jax.named_scope(STATE_SCOPE["ssm"]):
        after = {n: lax.dynamic_update_index_in_dim(cache[n], new, layer, 0) for n, new in zip(names, state)}
    return x, {**cache, **after}


def kda_on_kernel(cache, cfg: TransformerConfig) -> bool:
    """Whether a decode step over `cache` moves the KDA layers' state on through ops/kda.py's kernel: on a TPU, more
    than one row (`_on_kernel`'s rule and reason), heads as the kernel blocks them."""
    return (decode_on_kernel() and cfg.kda_n_heads > 0 and cache["h"].shape[1] > 1
            and cfg.kda_head_dim == 128 and cfg.kda_n_heads % KDA_HEADS == 0)


def _kda_decode_mixer(bp, x, cache, layer, cfg: TransformerConfig, rows=None):
    """A KDA layer's mixer, one token a row, from each row's own state.  x: [B, 1, E]; cache: the recurrent stacks
    conv [n, B, K-1, 3 H D] and h [n, B, H, D, D] (among whatever else it holds) and layer: this one's number among
    them.  rows: `ops.kda.live_rows` of the step (made once a step by `decode_rows`): the matrix state is then moved
    on by the kernel over the stack as it lies, the rows that hold a request alone, and only the convolution's
    window is read out of its stack and written back; None: as `_mamba2_block_decode`, the layer's state read out
    of the stacks whole and the new one written in its place.  Returns (the mixer's result [B, 1, E], the cache
    after)."""
    conv, h = LAYER_STATE["ssm"]
    at = lambda name: lax.dynamic_index_in_dim(cache[name], layer, keepdims=False)
    put = lambda name, new: lax.dynamic_update_index_in_dim(cache[name], new, layer, 0)
    moved = {}

    def update(q, k, v, g, beta):
        o, moved[h] = kda_decode_update(q, k, v, g, beta, cache[h], layer, rows)
        return o

    with jax.named_scope(STATE_SCOPE["ssm"]):
        state = at(conv), None if rows is not None else at(h)
    f, (window, s) = _kda_mixer(bp, x, cfg, state, update=None if rows is None else update)
    with jax.named_scope(STATE_SCOPE["ssm"]):
        return f, {**cache, conv: put(conv, window), h: put(h, s) if rows is None else moved[h]}


def _pad_keep(pad, t: int):
    """[B, T] bool, False at a left pad; None where there are none."""
    return None if pad is None else jnp.arange(t)[None, :] >= pad[:, None]


def _ssm_prefill_block(bp, x, pad, cfg: TransformerConfig, experts=None):
    """One state-space block over the whole prompt from the zero state;
    returns the state after the last token, (window [B, K-1, C], h [B, C, N]),
    the experts touched or None, and the mixer's read-out y [B, T, C].
    pad: [B] left-pad counts or None: a pad's input and step size are zeroed, so
    the state, and the logits, are the unpadded prompt's in any bucket."""
    x, _, layer_state, touched, y = _ssm_block_forward(bp, x, cfg, _pad_keep(pad, x.shape[1]), experts)
    return x, layer_state, touched, y


@functools.partial(jax.jit, static_argnames=("cfg", "t_max"))
def prefill_counted(params, ids, cfg: TransformerConfig, t_max: int, pad=None):
    """ids: [B, T_prompt] -> (last-token logits [B, V], cache, held layers).
    pad: optional [B] left-pad counts (see _prefill_block).  Compiled: one
    program for each shape of `ids` (with or without `pad`), `cfg` and
    `t_max`, traced at its first call and called thereafter; under another
    jit (`generate`, `_stream_fns`) it is a nested call of that program.
    A model that generates by blocks is given whole blocks of the prompt (pad
    and T_prompt multiples of `cfg.block_length`) and no logits come back: its
    last position's logits are that position's own token, which is known.
    Held layers: where the device holds a share of the experts
    (`cfg.experts_held`), int32 [2]: the expert layers, and those of them whose
    rows went through the compact buffer (parallel/moe.py); else None."""
    with jax.named_scope("embed"):
        x = params["embed"].astype(cfg.dtype)[ids]

    def attn(kind, s, bp, experts, _cache, layer):
        s, kv, touched = _prefill_block(bp, s, pad, cfg, t_max, experts, kind, layer)
        return s, None, (kv, touched)

    def ssm(s, bp, experts, _cache, _layer):
        x, state, touched, y = _ssm_prefill_block(bp, _x(s), pad, cfg, experts)
        return _hand_on(s, x, m=y[:, -1:]), None, (state, touched)

    def gmu(s, bp, _experts, _cache, _layer):
        # of the last position alone (`_prefill_tail`): the memory the loop carries is that position's
        last = _gmu_block(bp, s._replace(x=s.x[:, -1:]), cfg)
        return s._replace(x=lax.dynamic_update_slice(s.x, last.x, (0, s.x.shape[1] - 1, 0))), None, (None, None)

    t = ids.shape[1]
    keep = _pad_keep(pad, t)  # the left padding keeps its state and takes no expert

    def mamba2(x, bp, _experts, _cache, _layer):
        x, state = _mamba2_half(bp, x, cfg, _mamba2_zero_state(cfg, x.shape[0]), keep)
        return x, None, (state, None)

    def ffn(x, bp, experts, _cache, _layer):
        x, _, touched = _ffn_half(bp, x, cfg, keep, experts)
        return x, None, (None, touched)

    def kda(x, bp, experts, _cache, _layer):
        x, state = _kda_half(bp, x, cfg, _kda_zero_state(cfg, x.shape[0]), keep)
        x, _, touched = _ffn_half(bp, x, cfg, keep, experts)
        return x, None, (state, touched)

    x, _, outs = _scan_blocks(_bodies(attn, ssm, gmu, mamba2, ffn, kda), carried(x, cfg, 1, t), params, cfg)
    x = _x(x)
    rows = {kind: made for kind, (made, _) in outs.items() if kind not in NO_ROWS}
    # each kind's rows into the stacks of the state it keeps, at its layers' places there
    index = _state_index(cfg)
    cache: Dict[str, Any] = {}
    for state, names in LAYER_STATE.items():
        # the indexer's keys are made by the layers that keep keys and values in one stack, behind it
        of, skip = ("attn_kv", 1) if state == "index" else (state, 0)
        kinds = [kind for kind in rows if _state_kind(kind, cfg) == of and len(rows[kind]) > skip]
        order = np.argsort(np.concatenate([index[kind] for kind in kinds])) if kinds else None
        for i, name in enumerate(names if kinds else (), skip):
            joined = rows[kinds[0]][i] if len(kinds) == 1 else jnp.concatenate([rows[kind][i] for kind in kinds])
            cache[name] = joined if np.array_equal(order, np.arange(len(order))) else joined[order]
    held = None
    if cfg.experts_held is not None:
        # a held layer's touched is [layers, 3]: the last says which branch its rows took
        compact = jnp.concatenate([touched[:, 2] for _, touched in outs.values() if touched is not None])
        held = jnp.stack([jnp.int32(compact.shape[0]), jnp.sum(compact)])
    return None if cfg.generates_blocks else _head(params, x, cfg, row=-1), cache, held


def prefill(params, ids, cfg: TransformerConfig, t_max: int, pad=None):
    """`prefill_counted`'s program without its count: (last-token logits
    [B, V], cache).  `pad` goes by keyword, as the batcher's admit passes it:
    a call's signature is part of what a traced program is found by, and the
    benchmark's check calls this for the admit's own program, not a second
    trace of it."""
    return prefill_counted(params, ids, cfg, t_max, pad=pad)[:2]


def _bodies(attn, ssm, gmu=None, mamba2=None, ffn=None, kda=None):
    """`_scan_blocks`' bodies: every attention kind's is `attn(kind, ...)`, a kda block's `kda` whatever its FFN."""
    own = {"ssm": ssm, "gmu": gmu, "mamba2": mamba2, "ffn": ffn, "kda": kda, "kda_dense": kda}
    return {kind: own[kind] if kind in own else functools.partial(attn, kind) for kind in _INIT_KIND}


def decode_rows(params, cache, tokens, pos, pads, cfg: TransformerConfig, live=None, pending=None):
    """The decode program's body: one token for every row of the cache, each
    at its own depth.  tokens, pos, pads: [B] (`_block_decode_rowpos` says what
    each row does with its own); live: [B] bool, the rows that hold a request
    (what an empty slot's row is spared: `_block_decode_rowpos`), or None, every
    row (`generate`, `decode_one`, a suffix step).  Returns (logits
    [B, V], updated cache, experts touched: the mean over the expert layers of
    the experts that were given a row, None for a dense model; where this
    device holds a share of the experts, [3]: that of the held, the mean of
    the assignments that fell on them, and the share of the layers that took the
    compact buffer).

    tokens [B, T]: one pass of each row's own block of T positions, the first
    of them at pos[b] (a model that generates by blocks).  Returns the logits
    of every position [B, T, V], each its own position's token.

    pending [B] bool, with tokens [B, 2T]: the pass stores the block before
    while it runs the block at pos[b].  The first T tokens are that block's
    final ones, run at pos[b] - T for the rows that have it pending (for the
    others the half is dead: `_block_decode_rowpos`); the logits are the second
    half's alone, [B, T, V]: the head is not run over tokens that are fixed."""
    blocks = tokens.ndim == 2
    if blocks and (not cfg.generates_blocks or set(cfg.layer_kinds) != {"attn"}):
        raise NotImplementedError("a pass over blocks of positions: attention layers, cfg.block_length > 1")
    if pending is not None:
        live = jnp.ones_like(pending) if live is None else live
        pending = pending & live
    with jax.named_scope("embed"):
        x = params["embed"].astype(cfg.dtype)[tokens]
        if not blocks:
            x = x[:, None, :]  # [B,1,E]

    # what the attention kernel is told of the rows: made once, every layer reads the same
    span = _spans(cache, pos, tokens.shape[1] if blocks else 1, pads, live, cfg, pending) if _on_kernel(cache) else None

    def attn(kind, s, bp, experts, cache, layer):
        x, cache, touched = _block_decode_rowpos(bp, _x(s), cache, layer, pos, cfg, pads, live, experts, span, kind,
                                                 pending)
        return _hand_on(s, x), cache, touched

    def ssm(s, bp, experts, cache, layer):
        x, cache, touched, y = _ssm_block_decode(bp, _x(s), cache, layer, cfg, live, experts)
        return _hand_on(s, x, m=y), cache, touched

    def gmu(s, bp, _experts, cache, _layer):
        return _gmu_block(bp, s, cfg), cache, None

    def mamba2(x, bp, _experts, cache, layer):
        return (*_mamba2_block_decode(bp, x, cache, layer, cfg), None)

    def ffn(x, bp, experts, cache, _layer):
        x, _, touched = _ffn_half(bp, x, cfg, None if live is None else live[:, None], experts)
        return x, cache, touched

    kda_rows = live_rows(live, tokens.shape[0]) if kda_on_kernel(cache, cfg) else None

    def kda(x, bp, experts, cache, layer):
        f, cache = _kda_decode_mixer(bp, x, cache, layer, cfg, kda_rows)
        return ffn(x + f, bp, experts, cache, layer)

    x, cache, touched = _scan_blocks(_bodies(attn, ssm, gmu, mamba2, ffn, kda), carried(x, cfg, 1), params, cfg, cache)
    x = _x(x)
    touched = [t for t in touched.values() if t is not None]
    touched = jnp.mean(jnp.concatenate(touched).astype(jnp.float32), axis=0) if touched else None
    if pending is not None:
        x = x[:, x.shape[1] // 2:]
    logits = _head(params, x, cfg).astype(jnp.float32) if blocks else _head(params, x, cfg, row=0)
    return logits, cache, touched


def decode_one(params, cache, token, pos, cfg: TransformerConfig, pad=None):
    """token: [B], every row at cache slot `pos` -> (logits [B, V], updated
    cache).  pad: [B] left-pad counts, None for none."""
    pos = jnp.broadcast_to(pos, token.shape)
    pads = jnp.zeros_like(pos) if pad is None else pad
    return decode_rows(params, cache, token, pos, pads, cfg)[:2]


def _one_token_a_step(cfg: TransformerConfig, what: str) -> None:
    """`generate` and `stream_generate` yield one causal token a step; a model
    that generates by blocks is served by llm/continuous.py's batcher alone."""
    if cfg.generates_blocks:
        raise NotImplementedError(
            f"{what}: block_length={cfg.block_length} generates by passes over blocks, "
            "which ContinuousBatcher runs (llm/continuous.py); this path yields one token a step"
        )


def _nucleus_mask(scaled, top_p):
    """Mask logits outside the smallest probability-mass prefix >= top_p
    (nucleus sampling).  top_p is TRACED; <= 0 or >= 1 disables.  The
    highest-probability token is always kept (its exclusive cumsum is 0)."""
    probs = jax.nn.softmax(scaled, axis=-1)
    sorted_p = -jnp.sort(-probs, axis=-1)
    cum_excl = jnp.cumsum(sorted_p, axis=-1) - sorted_p
    included = cum_excl < top_p
    thresh = jnp.min(
        jnp.where(included, sorted_p, jnp.inf), axis=-1, keepdims=True
    )
    apply = (top_p > 0.0) & (top_p < 1.0)
    return jnp.where(apply & (probs < thresh), -1e30, scaled)


def _sample(logits, rng, temperature, top_k: int, top_p=1.0):
    """temperature/top_p are traced (no recompile per request value); top_k
    stays static (lax.top_k needs a static k). temperature <= 0 means
    greedy; top_p in (0, 1) applies nucleus truncation."""
    with jax.named_scope("sample"):
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        t = jnp.maximum(temperature, 1e-6)
        scaled = logits / t
        if top_k > 0:
            top = lax.top_k(scaled, top_k)[0][..., -1:]
            scaled = jnp.where(scaled < top, -1e30, scaled)
        # statically skip a guaranteed no-op mask (python-float defaults): the
        # nucleus pass costs a full-vocab softmax+sort per step.  Traced top_p
        # (streaming/continuous paths) always runs it — the mask itself gates
        # on (0, 1) membership.
        if not (isinstance(top_p, (int, float)) and not (0.0 < float(top_p) < 1.0)):
            scaled = _nucleus_mask(scaled, top_p)
        sampled = jax.random.categorical(rng, scaled).astype(jnp.int32)
        return jnp.where(temperature <= 0.0, greedy, sampled)


@functools.partial(jax.jit, static_argnames=("cfg", "max_new_tokens", "top_k", "top_p"))
def generate(
    params,
    prompt_ids,
    rng,
    *,
    cfg: TransformerConfig,
    max_new_tokens: int = 32,
    temperature: float = 0.0,
    top_k: int = 0,
    top_p: float = 1.0,
    prompt_lens: Optional[jax.Array] = None,
) -> jax.Array:
    """prompt_ids: [B, T_prompt] int32 -> generated ids [B, max_new_tokens].
    One compiled program: prefill + a lax.scan of decode steps.
    prompt_lens: optional [B] int32 count of real (rightmost) tokens per row
    when prompts are left-padded to a fixed T_prompt; pads are masked out of
    attention and RoPE positions count real tokens only."""
    _one_token_a_step(cfg, "generate")
    b, t_prompt = prompt_ids.shape
    t_max = t_prompt + max_new_tokens
    pad = None if prompt_lens is None else (t_prompt - prompt_lens).astype(jnp.int32)
    logits, cache = prefill(params, prompt_ids, cfg, t_max, pad)
    rngs = jax.random.split(rng, max_new_tokens)
    first = _sample(logits, rngs[0], temperature, top_k, top_p)

    def step(carry, rng_i):
        token, cache, pos = carry
        logits, cache = decode_one(params, cache, token, pos, cfg, pad)
        nxt = _sample(logits, rng_i, temperature, top_k, top_p)
        return (nxt, cache, pos + 1), nxt

    (_, _, _), tokens = lax.scan(
        step, (first, cache, jnp.int32(t_prompt)), rngs[1:]
    )
    # tokens: the N-1 follow-on samples; prepend the prefill sample
    out = jnp.concatenate([first[None], tokens], axis=0)
    return out.T  # [B, N]


@functools.lru_cache(maxsize=8)
def _stream_fns(cfg: TransformerConfig, t_prompt: int, t_max: int, top_k: int):
    """Jitted prefill+sample and single-decode-step closures for streaming
    decoding (compiled once per shape/config/top_k; temperature and top_p
    are TRACED operands, so per-request values never recompile)."""
    _one_token_a_step(cfg, "stream_generate")

    def _prefill(params, ids, pad, rng, temperature, top_p):
        logits, cache = prefill(params, ids, cfg, t_max, pad)
        return _sample(logits, rng, temperature, top_k, top_p), cache

    def _step(params, cache, token, pos, pad, rng, temperature, top_p):
        logits, cache = decode_one(params, cache, token, pos, cfg, pad)
        return _sample(logits, rng, temperature, top_k, top_p), cache

    return jax.jit(_prefill), jax.jit(_step)


def stream_generate(
    params,
    prompt_ids,
    rng,
    *,
    cfg: TransformerConfig,
    max_new_tokens: int = 32,
    temperature: float = 0.0,
    top_k: int = 0,
    top_p: float = 1.0,
    prompt_lens: Optional[jax.Array] = None,
):
    """Python generator yielding one [B] int32 token array per decode step.

    The interactive/streaming counterpart of generate(): a host loop over a
    jitted single decode step, so each token is observable as soon as it is
    sampled (wired to num_returns="streaming" actor methods by the LLM
    layer).  generate()'s scanned loop remains the throughput path."""
    import numpy as np

    b, t_prompt = prompt_ids.shape
    t_max = t_prompt + max_new_tokens
    pad = None if prompt_lens is None else (t_prompt - prompt_lens).astype(jnp.int32)
    pre, step = _stream_fns(cfg, t_prompt, t_max, int(top_k))
    temp_op = jnp.float32(temperature)
    top_p_op = jnp.float32(top_p)
    rngs = jax.random.split(rng, max_new_tokens)
    token, cache = pre(params, prompt_ids, pad, rngs[0], temp_op, top_p_op)
    yield np.asarray(token)
    pos = t_prompt
    for i in range(1, max_new_tokens):
        token, cache = step(params, cache, token, jnp.int32(pos), pad, rngs[i], temp_op, top_p_op)
        pos += 1
        yield np.asarray(token)
