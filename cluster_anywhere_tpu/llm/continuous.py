"""Continuous batching for LLM decoding (the iteration-level scheduler the
reference gets from its vLLM-backed `serve.llm` deployments —
python/ray/llm's engine does exactly this; redesigned here for the XLA
compilation model instead of paged CUDA kernels).

The scheduler owns a fixed pool of decode SLOTS over one shared cache (keys
and values [L, S, T_max, KV, D], a window layer's ring [L, S, W, KV, D], latent
rows, and a state-space layer's recurrent state: models/generate.py
init_cache; rows are a pytree here, and no array's time axis is assumed).  Each slot runs one request; requests at different
depths decode together in ONE jitted step whose shapes never change — slot
count and cache length are static, per-row positions are traced — so
admitting or finishing requests never recompiles anything:

- admit: a queued request prefills (a compiled batch-1 program, the prompt
  padded to a bucket length so that there is one program a bucket) and its
  cache rows scatter into its slot between decode steps.
- decode: every live slot advances one token per step.  Per-row cache
  positions/pads drive RoPE and masking, and the step is told which slots it
  holds: on a TPU its attention reads the live rows' own slots of the cache
  and nothing else (`cache_rows_read` of `cache_rows` on `llm.step` and in
  `stats`).  Finished or empty slots still compute the rest (their lanes are
  garbage) but write only to their own cache rows
  (a key/value row past its position; a recurrent state, which is not frozen:
  it moves on with every step), which the next admit overwrites whole.
- a step is read one step behind: a row's next input is the token the step
  before it made, which stays on the device (`prev`; an admitted slot's first
  token is the host's, marked `fresh`), so step() dispatches step N+1 and only
  then reads step N: the device has its next program queued while the host
  reads, scatters and delivers.  A call returns the tokens of the step it
  READ (and its own admits' first tokens) and counts that step.  A request
  that reaches max_new_tokens with the step in flight is not dispatched again
  (the host knows the count at dispatch) and keeps its slot until that step is
  read; an eos is in the token, so the step in flight holds the row once more:
  that row is computed late, dropped at its read (`late_rows`), never handed out.
- finish: a slot frees the moment its request's last token (max_new_tokens or
  eos) is read, or at its cancel; the next step() can admit into it
  immediately, even while a step that still holds the old row runs (the
  install is ordered after it on the device and overwrites the slot whole) —
  no head-of-line batching barrier, which is the whole point vs static
  generate() batching.

A model that generates by blocks (`cfg.block_length` B > 1: an answer is made
B positions at a time, by passes that fix the most confident masked positions)
goes through the same scheduler, cache and admit; what differs is the step:
- a step is one PASS of every live slot's own block, slots in different passes
  of different blocks in one program (`_pass_step_rowpos`).  The slot vectors
  carry a block's B tokens and fixed flags; the program runs the B positions
  against the cache and themselves, writes the block's keys and values in
  place, and chooses on the device what to fix (`_choose_block`).
- a pass that leaves every position of a block fixed leaves the block pending:
  its keys and values in the cache are those of the pass's input, the last
  positions still masked.  The slot moves on to its next block, and that
  block's first pass runs the pending block's final tokens beside its own B
  positions (2B a row; a row with nothing pending has the first half dead) and
  so stores their keys and values, which its own positions see.  So a block of
  B costs its denoising passes and no more; the head and the choice run over
  the block's own B positions alone.
- a step hands a request 0 to B tokens, in position order: a token goes out
  once every position before it is fixed.  An admit prefills the prompt's
  whole blocks and hands out nothing; the prompt's tail is the fixed part of
  the first block.
- a pass is read one pass behind, as a causal step is: what a slot feeds next
  is the pass's own output, or, where that is a block with every position
  fixed, an empty block B slots on with that block pending, and the program
  decides which from what the pass fixed; it stays on the device (`prev`; an
  admitted slot starts from the host's rows, marked `fresh`, with nothing
  pending).  So step() dispatches pass N+1 and only then reads pass N.
  The host keeps a mirror of every slot's block and position, one pass behind
  the device's, and decides alone what the device is never told: which
  positions go out, where an answer ends, `fixed_at`, `block_tail`.  An answer's
  end is in what a pass fixes, so the pass in flight holds a request that ends
  once more: every request costs one row computed late and dropped
  (`late_rows`), whose slot the next call may have given away by then.
- `fixed_at(request_id)` is the record of the pass of its block at which each
  served token was fixed, which the tokens do not say.

This module is the scheduler, the per-row sampler and the jitted wrapper.
The model's mathematics is models/generate.py's: `prefill_counted`, and `decode_rows`,
the decode program's body, which also owns the cache's layout.  serve_llm.py
is the deployment that drives it.
"""

from __future__ import annotations

import functools
import itertools
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..models.generate import (
    _nucleus_mask, cache_bytes_per_token, cache_context_bytes_per_token, cache_kind_bytes, decode_rows, init_cache, install_rows, key_slots, prefill_counted,
    recurrent_state_bytes,
)
from ..models.transformer import TransformerConfig
from ..parallel.moe import takes_loop
from ..util import tracing


PREFILL_BUCKETS = (64, 128, 256)  # padded prompt lengths: one prefill program each


def prefill_buckets_for(max_prompt_len: int) -> tuple:
    """The bucket ladder of a deployment that admits prompts up to
    `max_prompt_len`: PREFILL_BUCKETS, continued by powers of two (512, 1024,
    2048, ...) below that length, and the length itself.  A 700-token prompt of
    a 4,096-token deployment prefills 1,024 positions, not 4,096; a deployment
    of 512 has the four programs 64, 128, 256, 512."""
    ladder = list(PREFILL_BUCKETS)
    while 2 * ladder[-1] < max_prompt_len:
        ladder.append(2 * ladder[-1])
    return tuple(b for b in ladder if b < max_prompt_len) + (max_prompt_len,)


# a total of `tracing.jax_build_totals` -> the counts of `stats` it adds to
_BUILD_STATS = {
    "trace_s": ("program_build_s", "program_trace_s"), "lower_s": ("program_build_s", "program_trace_s"),
    "backend_s": ("program_build_s",), "builds": ("program_builds",), "cache_misses": ("program_cache_misses",),
}
_NO_TRUNCATION = (
    "{what}: a replica that generates by blocks of {b} chooses each position's token and its "
    "confidence without sorting the vocabulary; temperature alone is served, top-k and top-p are not"
)


@dataclass
class Request:
    request_id: int
    prompt_ids: np.ndarray
    max_new_tokens: int
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    eos_id: Optional[int] = None
    # stamped by submit(): the submitter's trace context (its admit, on the
    # pump's thread, runs under it) and time.monotonic() (queue wait)
    trace: Optional[Dict[str, str]] = None
    t_submit: float = 0.0
    # filled as the request runs
    out_tokens: List[int] = field(default_factory=list)
    slot: int = -1
    done: bool = False
    # a model that generates by blocks: the pass of its block at which each of
    # out_tokens was fixed; of the current block, the passes made, the pass
    # that fixed each position (-1: a prompt token, or masked yet) and the
    # positions handed out or of the prompt; and what was fixed past the
    # answer's end when the request finished: [(position in the block, pass, token)]
    fixed_at: List[int] = field(default_factory=list)
    block_pass: int = 0
    pass_of: List[int] = field(default_factory=list)
    block_out: int = 0
    block_tail: List[tuple] = field(default_factory=list)


@dataclass
class _StepInFlight:
    """A decode step (a pass of blocks) that was dispatched and is not read yet."""
    # on the device: every slot's next token [S] int32, or every slot's block after
    # the pass [2B, S] (tokens, then fixed flags)
    made: Any
    touched: Any  # experts touched, on the device; None for a dense model
    # (slot, request) of the rows the step holds, as the slots were at dispatch:
    # a row's token goes to THAT request, or nowhere if it has ended since
    rows: List[tuple]
    # of the slots that held a request then (a request whose last token was in flight
    # among them), as the sampler saw their knobs: how many sample, how many truncate
    sample_rows: int
    truncate_rows: int
    # `ContinuousBatcher._rows_read` of those slots, as their rows stood then; None for a
    # pass of blocks, reckoned at its read (the host's mirror is its input only then)
    cache_rows_read: Optional[tuple]


def _sample_rowwise(logits, rngs, temps, top_ks, top_ps):
    """Per-row sampling with TRACED temperature, top-k, and top-p (requests
    in one decode batch carry their own knobs; a static top_k would force
    one value per compiled program).  top_k <= 0 means no truncation;
    top_p outside (0, 1) means no nucleus mask; temp <= 0 means greedy.

    It does the work its rows ask for and no more, chosen on the device from
    the three vectors: the largest logit alone where no row samples; one draw a
    row from `logits / temp` where some row samples and no sampling row
    truncates; the two sorts of the vocabulary (top-k's k-th largest, the
    nucleus's cumulative mass) only where a sampling row asks top-k or top-p.
    A row's token is the same in every branch that may serve it: the masks are
    no-ops for a row that does not truncate, and rows are independent."""
    with jax.named_scope("sample"):
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        samples = temps > 0.0
        truncates = samples & ((top_ks > 0) | ((top_ps > 0.0) & (top_ps < 1.0)))

        def draw(scaled):
            sampled = jax.vmap(lambda rng, row: jax.random.categorical(rng, row))(rngs, scaled)
            return jnp.where(samples, sampled.astype(jnp.int32), greedy)

        def scale():
            return logits / jnp.maximum(temps, 1e-6)[:, None]

        def truncated():
            scaled = scale()
            v = logits.shape[-1]
            # traced top-k: k-th largest per row via a descending sort
            sorted_desc = -jnp.sort(-scaled, axis=-1)
            kth_idx = jnp.clip(top_ks - 1, 0, v - 1)[:, None]
            kth = jnp.take_along_axis(sorted_desc, kth_idx, axis=-1)
            scaled = jnp.where((top_ks[:, None] > 0) & (scaled < kth), -1e30, scaled)
            # per-row nucleus mask: [S,1] top_p broadcasts through the shared helper
            return draw(_nucleus_mask(scaled, top_ps[:, None]))

        asked = jnp.any(samples).astype(jnp.int32) + jnp.any(truncates)
        return lax.switch(asked, (lambda: greedy, lambda: draw(scale()), truncated))


@jax.jit
def _sample_first(logits, rng, temp, top_k, top_p):
    """An admit's first token from its prefill's logits [1, V], by the step's
    own sampler in one program (op by op the nucleus mask alone was twenty
    dispatches on the pump's thread).  rng: the batcher's key, split once an
    admit into the key it carries on and the draw's; temp, top_k, top_p: [1],
    the request's.  Returns (the token, the carried key)."""
    rng, key = jax.random.split(rng)
    return _sample_rowwise(logits, key[None], temp, top_k, top_p)[0], rng


@functools.partial(jax.jit, static_argnames=("cfg",), donate_argnums=(1,))
def _decode_step_rowpos(params, cache, ints, floats, prev, rng, *, cfg):
    """One token for every slot with PER-ROW cache positions.
    ints: [6, S] int32, the rows tokens, pos, pads, top_ks, fresh, live: the
    last 1 for the slots this step holds a request in.  Every model is told: the
    attention core reads the live rows' own slots of the cache and no others
    (a released slot keeps its last pos and pads), and a mixture of experts
    gives the other rows no expert.
    floats: [2, S] float32, the rows temps, top_ps.  prev: [S] int32, the step
    before's own result, still on the device: a slot feeds its token of that
    step, which the host may not have read yet, except where fresh is set (a
    slot admitted since: its first token is the admit's, the host's row).  rng:
    the batcher's one key, split here into the key it carries on and one key a
    row.  Returns (next_tokens [S], cache, the carried key, experts touched):
    the last is the mean over the layers of the experts that were given a row,
    None for a dense model.  The cache is donated and is the layer loop's carry
    (models/generate.py), so the step writes one row a slot and layer of
    [L,S,Tmax,KV,D] x2 in place and copies nothing of that size:
    tests/test_chip_compile.py holds the chip's program to it
    (`test_decode_step_writes_the_cache_in_place`), tests/test_llm_programs.py
    the rows it may change."""
    host_tokens, pos, pads, top_ks, fresh, live = ints
    temps, top_ps = floats
    tokens = jnp.where(fresh != 0, host_tokens, prev)
    keys = jax.random.split(rng, ints.shape[1] + 1)
    logits, cache, touched = decode_rows(params, cache, tokens, pos, pads, cfg, live != 0)
    nxt = _sample_rowwise(logits, keys[1:], temps, top_ks, top_ps)
    return nxt, cache, keys[0], touched


def _choose_block(logits, fixed, live, temps, rng, cfg: TransformerConfig):
    """What one pass fixes, on the device, without a sort of the vocabulary.
    logits: [S, B, V] float32, each position's own; fixed: [S, B] bool; live:
    [S] bool; temps: [S].  Every masked position (not fixed, in a live row)
    proposes a token, the largest logit (temperature 0) or a sample of
    softmax(logits / temperature) (a Gumbel maximum), with its confidence, the
    token's probability under that distribution: a maximum and a log-sum-exp.
    Fixed are the masked positions whose confidence passes
    cfg.confidence_threshold, or, where fewer than m = B / denoise_steps do,
    the m most confident (ties to the lower position), found by B x B
    comparisons.  Returns (tokens [S, B] int32, newly fixed [S, B] bool)."""
    with jax.named_scope("block.choose"):
        b = logits.shape[1]

        def greedy(_):
            return jnp.argmax(logits, axis=-1), jnp.max(logits, axis=-1) - jax.nn.logsumexp(logits, axis=-1)

        def sampled(rng):
            t = temps[:, None, None]
            scaled = jnp.where(t > 0.0, logits / jnp.maximum(t, 1e-6), logits)
            noise = jnp.where(t > 0.0, jax.random.gumbel(rng, logits.shape, logits.dtype), 0.0)
            tok = jnp.argmax(scaled + noise, axis=-1)
            at_tok = jnp.take_along_axis(scaled, tok[..., None], axis=-1)[..., 0]
            return tok, at_tok - jax.nn.logsumexp(scaled, axis=-1)

        # the noise is as large as the logits: made only where a row asks for it
        tok, log_conf = lax.cond(jnp.any((temps > 0.0) & live), sampled, greedy, rng)
        masked = ~fixed & live[:, None]
        conf = jnp.where(masked, log_conf, -jnp.inf)
        high = conf > jnp.log(jnp.float32(cfg.confidence_threshold))
        m = b // cfg.denoise_steps
        ahead = (conf[:, None, :] > conf[:, :, None]) | (
            (conf[:, None, :] == conf[:, :, None]) & (jnp.arange(b)[None, :] < jnp.arange(b)[:, None]))
        most = masked & (jnp.sum(ahead, axis=-1) < m)
        fix = jnp.where(jnp.sum(high, axis=-1, keepdims=True) >= m, high, most)
        return tok.astype(jnp.int32), fix


@functools.partial(jax.jit, static_argnames=("cfg",), donate_argnums=(1,))
def _pass_step_rowpos(params, cache, ints, floats, prev, rng, *, cfg):
    """One pass of every slot's own block (a model that generates by blocks of
    B = cfg.block_length), which also stores the block before where that is
    pending.  ints: [4 + 2B, S] int32, the rows pads, live, fresh, then a
    slot's state as the host has it: pos (the cache slot of the block's first
    position), the block's B tokens and its B fixed flags; floats: [2, S], the
    rows temps, top_ps (the second unused: a request that asks top-p is
    refused).  prev: [2 + 3B, S] int32, the state the pass before left every
    slot for this one, still on the device: the host's rows, then whether the
    block before is pending and its B final tokens.  A live slot takes it, which
    the host may not have read yet, except where fresh is set (a slot admitted
    since: the admit's position, the prompt's tail and its flags are the
    host's rows, and nothing is pending).  A position that is not fixed goes in
    as cfg.mask_token_id whatever its token says, and fixedness is the flag
    alone.  Returns (the blocks after the pass [2B, S]: tokens, then flags; the
    state for the next pass [2 + 3B, S]; cache; the carried key; experts
    touched).

    A row runs 2B positions, pos - B .. pos + B - 1 (`decode_rows`, `pending`):
    the pending block's final tokens, whose keys and values are thereby stored
    as those of its tokens, and the block itself, which sees them; for a row
    with nothing pending the first half is dead.  The logits and the choice are
    the block's alone.  A pass that leaves every position of its block fixed
    leaves it pending: the next state is pos + B, an empty block, and the
    block's tokens to store, so a block of B costs its denoising passes and no
    more.  The rows of keys and values a slot and layer are written in place
    at [layer, b, pos - B : pos + B] (models/generate.py, the decode block), as
    the causal step writes its one: tests/test_chip_compile.py holds the chip's
    program to that, to no sort of the vocabulary and to logits of B positions
    a slot."""
    b = cfg.block_length
    pads, live, fresh = ints[0], ints[1] != 0, ints[2] != 0
    # a slot that holds no request rests on the host's rows, as it always did; they hold nothing pending
    state = jnp.where(fresh | ~live, jnp.pad(ints[3:], ((0, 1 + b), (0, 0))), prev)
    pos, tokens, fixed = state[0], state[1:1 + b].T, state[1 + b:1 + 2 * b].T != 0
    pending, before = state[1 + 2 * b] != 0, state[2 + 2 * b:].T
    key, sub = jax.random.split(rng)
    ids = jnp.concatenate([before, jnp.where(fixed, tokens, cfg.mask_token_id)], axis=1)
    logits, cache, touched = decode_rows(params, cache, ids, pos, pads, cfg, live, pending)
    chosen, fix = _choose_block(logits, fixed, live, floats[0], sub, cfg)
    after = jnp.concatenate([jnp.where(fix, chosen, tokens).T, (fixed | fix).T.astype(jnp.int32)])
    whole = jnp.all(fixed | fix, axis=1)  # the block is left pending
    nxt = jnp.concatenate([jnp.where(whole, pos + b, pos)[None], jnp.where(whole, 0, after),
                           whole[None].astype(jnp.int32), jnp.where(whole, after[:b], 0)])
    return after, nxt, cache, key, touched


@functools.partial(jax.jit, static_argnames=("cfg",), donate_argnums=(1,))
def _pass_logits(params, rows, ids, pos, pad, *, cfg):
    """One pass over a SINGLE request's block against its own cache rows (a
    cache of batch one, donated): ids [1, B] as the step would feed them, pos
    and pad [1].  Returns (every position's logits [1, B, V] float32, the
    rows).  What the step's program computes before it chooses, for a check
    that wants the logits themselves (the step hands out tokens)."""
    return decode_rows(params, rows, ids, pos, pad, cfg)[:2]


@functools.partial(jax.jit, donate_argnums=(0,))
def _install_slot(cache, rows, slot):
    """Scatter one request's prefilled rows (a cache of batch one, as `prefill`
    and `_suffix_step` return them) into its slot, on the device, every array
    of the slot overwritten (models/generate.py install_rows)."""
    return install_rows(cache, rows, slot)


@functools.partial(jax.jit, static_argnames=("cfg",), donate_argnums=(1,))
def _suffix_step(params, rows, token, pos, pad, *, cfg):
    """One teacher-forced token over a SINGLE request's cache rows
    (a cache of batch one, donated — updated in place) during chunked admit:
    feeds a known prompt token at cache slot `pos` ([1], as token and pad
    are), returns the next-token logits [1, V] and the updated rows.  The
    prefix-cache admit path runs the un-cached tail of the prompt through this
    instead of prefill, so a warm hit and a cold miss compute the suffix
    IDENTICALLY (bit-equal outputs is the cache's correctness contract)."""
    return decode_rows(params, rows, token, pos, pad, cfg)[:2]


def tree_bytes(tree) -> int:
    """The bytes of a pytree's arrays (weights, a cache, a slot's rows)."""
    return sum(int(a.size) * a.dtype.itemsize for a in jax.tree_util.tree_leaves(tree))


class PrefixCache:
    """Bounded LRU of prefilled prompt-prefix rows (a cache of batch one: the
    prefix's keys and values, and a recurrence's state after its last token),
    keyed by the prefix token content (+ bucket shape).  A hit hands the admit
    path device-ready rows — the shared system prompt's prefill is skipped
    entirely and only the request's unique tail is computed."""

    def __init__(self, entries: int):
        from collections import OrderedDict

        self.entries = entries
        self._d: "OrderedDict[str, dict]" = OrderedDict()
        self.evictions = 0

    @staticmethod
    def key(prefix_ids: np.ndarray, bucket: int) -> str:
        import hashlib

        h = hashlib.sha1(np.ascontiguousarray(prefix_ids, np.int32).tobytes())
        return f"{h.hexdigest()}:{len(prefix_ids)}:{bucket}"

    def get(self, key: str):
        e = self._d.get(key)
        if e is not None:
            self._d.move_to_end(key)
        return e

    def put(self, key: str, rows: dict, pad: int) -> None:
        self._d[key] = {"rows": rows, "pad": pad}
        while len(self._d) > self.entries:
            self._d.popitem(last=False)
            self.evictions += 1

    def __len__(self) -> int:
        return len(self._d)

    def memory_bytes(self) -> int:
        return sum(tree_bytes(e["rows"]) for e in self._d.values())


class ContinuousBatcher:
    """Iteration-level scheduler over a fixed slot pool (see module doc).

    Drive it with submit() + step() (one decode iteration), or pump() until
    every request has finished.  step() returns per-request newly produced
    tokens (of the step it read: one behind the step it dispatched), enabling
    token streaming per request while others keep decoding."""

    def __init__(
        self,
        params,
        cfg: TransformerConfig,
        *,
        slots: int = 8,
        t_max: int = 512,
        prefill_buckets: (tuple) = PREFILL_BUCKETS,
        top_k: int = 0,
        prefix_cache_entries: int = 0,
        prefix_block: int = 16,
    ):
        self.params = params
        self.cfg = cfg
        self.slots = slots
        self.t_max = t_max
        self.top_k = top_k
        # B where the model generates by blocks of B positions, else 0
        self._block = cfg.block_length if cfg.generates_blocks else 0
        if self._block:
            if prefix_cache_entries > 0:
                raise ValueError(
                    f"prefix_cache_entries={prefix_cache_entries}: a replica that generates by blocks "
                    f"of {self._block} keeps no prefix cache (its split would have to fall on a block's "
                    "edge and its suffix run as passes: not built); pass 0"
                )
            if top_k:
                raise ValueError(_NO_TRUNCATION.format(what=f"a default top_k of {top_k}", b=self._block))
            if t_max % self._block:
                raise ValueError(f"a cache of {t_max} slots is no whole number of blocks of {self._block}")
        if prefix_cache_entries > 0 and "kda" in (cfg.layer_mixers or ()):
            raise ValueError(
                f"prefix_cache_entries={prefix_cache_entries}: a replica of kda layers keeps no prefix cache (every "
                f"entry would hold a snapshot of the matrix state at its split, {cfg.kda_n_heads} x {cfg.kda_head_dim} x "
                f"{cfg.kda_head_dim} float32 a layer, and a hit would install it: not built); pass 0"
            )
        self.prefill_buckets = tuple(sorted(prefill_buckets))
        # prefix/KV reuse (0 entries = off, the pre-cache admit path
        # verbatim).  When on, admit splits the prompt at the largest
        # prefix_block multiple: the prefix prefills once and its KV rows
        # are cached; the suffix is teacher-forced through _suffix_step on
        # BOTH hit and miss so outputs are bit-identical either way.
        self.prefix_cache = (
            PrefixCache(prefix_cache_entries) if prefix_cache_entries > 0 else None
        )
        self.prefix_block = max(1, int(prefix_block))
        # split granularity: the prefix goes through the same compiled
        # `prefill` as a whole prompt, one program a DISTINCT padded length
        # (the configured buckets rarely leave decode room for bucket +
        # suffix + max_new, so `_bucket`'s exact-length fall-back is the
        # common case).  Quantizing splits to max(block, longest bucket/8)
        # bounds that family at ~8 programs for any prompt length: a
        # compile stalls the shared pump thread, so an unbounded shape
        # family would freeze live streams on long-tail traffic.
        longest = self.prefill_buckets[-1] if self.prefill_buckets else t_max
        q = max(self.prefix_block, longest // 8)
        self._split_quantum = -(-q // self.prefix_block) * self.prefix_block
        self.cache = init_cache(cfg, slots, t_max)
        # the recurrent state a decode step reads and writes again (every slot's,
        # live or not), and what an admit installs: 0 for attention alone
        self._ssm_slot_bytes = recurrent_state_bytes(self.cache) // slots
        self._ssm_step_bytes = 2 * slots * self._ssm_slot_bytes
        # a replica that holds a share of the experts: a decode step's expert layers, and those of them that
        # loop over the experts touched (parallel/moe.py takes_loop: the step's rows decide as the program is
        # traced, so the host knows without a readback)
        held = [] if cfg.experts_held is None else [b for b in params.values() if isinstance(b, dict) and "router" in b]
        self._moe_step_held_layers = sum(b["router"].shape[0] for b in held)
        loops = takes_loop(slots * (2 * self._block or 1), cfg.experts_held)
        self._moe_step_loop_layers = self._moe_step_held_layers if loops else 0
        # the slots of a layer's keys that a step could read (as many of its values, or its
        # latent rows; over layers of two extents, their mean): what `cache_rows_read` is a
        # share of; 0 for recurrent state alone
        self._cache_rows = key_slots(self.cache, cfg=cfg)[0]
        self._cache_bytes = cache_kind_bytes(self.cache)
        # the decode step's per-slot inputs as its program takes them: two host
        # arrays, the scheduler's vectors their rows, written between steps (an
        # admit, a cancel) and as a step is dispatched or read.  A step is handed
        # a copy of each (one dispatch, no eager upload), since it may still be
        # reading them when the scheduler writes next (on the CPU backend a host
        # array may be aliased, not copied).  _fresh marks the slots admitted
        # since the last dispatch, which start from the host's rows; every other
        # live slot feeds what the step before left it on the device, `_prev`;
        # _live: the slots the step holds, written as it is dispatched
        self._floats = np.zeros((2, slots), np.float32)
        if self._block:
            # the host's mirror of a slot's state, which a pass's read moves on
            # (one pass behind the device's): _pos, the cache slot of the block's
            # first position; _blk_tokens, _blk_fixed: [B, S], the block's tokens
            # and which of them are fixed.  The program reads it of a fresh slot
            self._ints = np.zeros((4 + 2 * self._block, slots), np.int32)
            self._pads, self._live, self._fresh, self._pos = self._ints[:4]
            self._blk_tokens, self._blk_fixed = self._ints[4:4 + self._block], self._ints[4 + self._block:]
            self._topks = np.zeros(slots, np.int32)  # top-k is refused: the row stays 0 and is not uploaded
            # whether the pass the mirror stands before stores the slot's block before: the
            # host's alone (the program keeps its own flag, and that block's tokens, in `_prev`)
            self._blk_pending = np.zeros(slots, bool)
            state = (2 + 3 * self._block, slots)
        else:
            self._ints = np.zeros((6, slots), np.int32)
            # _tokens: an admit's first token, the row of a fresh slot's first
            # step; _pos: cache slot of the NEXT write, moved on as a step is
            # dispatched
            self._tokens, self._pos, self._pads, self._topks, self._fresh, self._live = self._ints
            state = (slots,)
        # what the last dispatched step left for the next, on the device: every
        # slot's token, or its position, its block and the block it has yet to store
        # (`_pass_step_rowpos`)
        self._prev = jnp.zeros(state, jnp.int32)
        # a step while it is dispatched and unread: each step() dispatches one
        # and reads the one before
        self._flight: Optional[_StepInFlight] = None
        self._temps, self._topps = self._floats
        self._topps[:] = 1.0
        # of the live slots, those that sample (temperature > 0) and those of them
        # that truncate (top-k or top-p), as the step's sampler reads the three
        # vectors: kept at admit and at release.  A free slot asks nothing (0, 0,
        # 1.0), so what the sampler sees in all its rows is what the live ones ask
        self._sample_rows = self._truncate_rows = 0
        self._by_slot: List[Optional[Request]] = [None] * slots
        self.queue: deque[Request] = deque()
        # bounded: pump() drains it; step()-driven servers track their own
        # Requests (an unbounded list would grow for the replica's lifetime)
        self._completed: deque[Request] = deque(maxlen=4096)
        self._ids = itertools.count(1)
        self._rng = jax.random.key(0)
        # a server's hook, `(phase, seconds)`: each admit's queue wait and its
        # own time, counted over the whole window (ca_serve_phase_seconds)
        self.observe_phase = None
        self.stats = {
            "admitted": 0, "finished": 0, "decode_steps": 0, "cancelled": 0,
            "prefix_hits": 0, "prefix_misses": 0, "prefix_tokens_reused": 0,
            # counted where the spans are: requests queued, tokens handed
            # out, and cumulative seconds queued and in admit
            "submitted": 0, "tokens_out": 0, "queue_wait_s": 0.0, "admit_s": 0.0,
            # (token, expert) pairs a layer's routed experts were given, in
            # admits and steps; stays 0 for a dense model
            "moe_assignments": 0,
            # the steps' `moe_experts_touched` summed (each the mean over the expert layers
            # of the experts given a row): over `decode_steps`, what a step's experts read
            "moe_experts_touched": 0.0,
            # a replica that holds a share of the experts: its admits' expert layers, and
            # those of them that took the compact buffer (parallel/moe.py)
            "moe_held_layers": 0, "moe_compact_layers": 0,
            # the same replica's decode steps: their expert layers, and those of them that looped
            # over the experts touched in place of the grouped matmul
            "moe_step_held_layers": 0, "moe_step_loop_layers": 0,
            # admits that found no compiled prefill for their padded length
            # and traced one; stays where it is once every bucket is warm
            "prefill_traces": 0,
            # what building this batcher's programs cost, from jax's own events on the
            # thread that steps it (`count_build`; they stay 0 where nobody registered
            # it): seconds tracing, lowering and in the backend (a compilation, or a
            # fetch from the persistent cache), of them the first two alone (which no
            # cache saves), the programs handed to the backend, and those of them
            # compiled anew and written to the persistent cache
            "program_build_s": 0.0, "program_trace_s": 0.0, "program_builds": 0, "program_cache_misses": 0,
            # recurrent state read and written by the steps and installed by the
            # admits; stays 0 for a model of attention layers alone
            "ssm_state_bytes": 0,
            # one slot's recurrent state over the layers that keep one (a kda layer's matrix state and its
            # convolution's last inputs among them), by the cache's own shapes: a constant of the deployment
            "state_bytes_per_slot": self._ssm_slot_bytes,
            # a model that generates by blocks: passes of one slot's block (a
            # step is one for every live slot) and the positions they fixed;
            # stay 0 for one causal token a step
            "block_passes": 0, "block_tokens_fixed": 0,
            # of block_passes, those that stored the block before while they ran their own
            "block_stores_fused": 0,
            # decode steps that sorted the vocabulary: a live row sampled with
            # top-k or top-p; stays 0 under greedy or temperature-only traffic
            "sort_steps": 0,
            # steps dispatched while the one before was unread (the device had its
            # next program queued), and rows such a step computed for a request
            # that had ended meanwhile (by eos or a cancel; a pass of blocks: by
            # any end, one a request): dropped
            "steps_ahead": 0, "late_rows": 0,
            # of a layer's keys, the slots the steps' attention fetches (the live rows' own
            # [pads, pos + its tokens), in whole key blocks: ops/attention.py
            # decode_attention; a latent core reads every slot) and the slots the cache
            # holds, a step; both stay 0 for a cache of recurrent state alone
            "cache_rows_read": 0, "cache_rows": 0,
            # of cache_rows_read, the part read in window layers' rings (a live row's whole
            # ring a layer); stays 0 without window layers
            "window_rows_read": 0,
            # of cache_rows_read, the part read of the one stack that several layers share (the
            # full layer that writes it and the cross layers above it); stays 0 where none share
            "shared_rows_read": 0,
            # under learned sparse attention: the live rows' contexts over the steps (what
            # cache_rows_read, the selected slots, is a share of) and the indexer keys scanned
            "context_rows": 0, "index_rows_read": 0,
            # the positions the admits' prefills computed in the first layer (the prompts'
            # buckets) and in the last (the same, or 1 a prompt where the stack's second half
            # is computed at a prompt's last position alone: models/generate.py _prefill_tail),
            # and the second as a share of the first, percent
            "prefill_positions_total": 0, "prefill_tail_positions_total": 0, "prefill_tail_share": 100.0,
            # the cache's bytes by the extent of its rows: the stacks as long as a context
            # (keys and values, latent rows), and the window layers' rings
            "cache_full_bytes": self._cache_bytes["full"], "cache_window_bytes": self._cache_bytes["window"],
            # the rings' share of the two, percent
            "cache_window_share": 100.0 * self._cache_bytes["window"] / max(sum(self._cache_bytes.values()), 1),
            # what a token takes in the cache over all the layers that keep keys and values
            # (a window layer's ring too, while it holds the token), and the part of it that one
            # more token of context adds (the stacks as long as a context alone), by the
            # cache's own shapes: constants of the deployment
            "cache_bytes_per_token": cache_bytes_per_token(self.cache, cfg),
            "cache_context_bytes_per_token": cache_context_bytes_per_token(self.cache, cfg),
        }

    # ------------------------------------------------------------- interface
    def submit(
        self,
        prompt_ids,
        *,
        max_new_tokens: int = 32,
        temperature: float = 0.0,
        top_k: Optional[int] = None,
        top_p: float = 1.0,
        eos_id: Optional[int] = None,
    ) -> Request:
        prompt = np.asarray(prompt_ids, np.int32).reshape(-1)
        if len(prompt) + max_new_tokens > self.t_max:
            raise ValueError(
                f"prompt ({len(prompt)}) + max_new_tokens ({max_new_tokens}) "
                f"exceeds the cache length {self.t_max}"
            )
        top_k = self.top_k if top_k is None else int(top_k)
        if self._block and (top_k > 0 or 0.0 < float(top_p) < 1.0):
            raise ValueError(_NO_TRUNCATION.format(what=f"top_k={top_k}, top_p={top_p}", b=self._block))
        req = Request(
            next(self._ids), prompt, int(max_new_tokens), float(temperature),
            top_k, float(top_p), eos_id, tracing.current(), time.monotonic(),
        )
        self.queue.append(req)
        self.stats["submitted"] += 1
        return req

    def cancel(self, request_id: int) -> bool:
        """Abort one request: drop it from the queue, or free its slot so
        the next admit reuses it immediately (abandoned-stream path — the
        consumer is gone, decoding its remaining tokens is pure waste).
        Returns False when the request already finished (no-op)."""
        for i, r in enumerate(self.queue):
            if r.request_id == request_id:
                del self.queue[i]
                r.done = True
                self.stats["cancelled"] += 1
                return True
        for s, r in enumerate(self._by_slot):
            if r is not None and r.request_id == request_id:
                r.done = True
                self._release(s)  # lane decodes garbage until an admit overwrites its rows
                self.stats["cancelled"] += 1
                return True
        return False

    def count_build(self, kind: str, amount: float) -> None:
        """A sink of `tracing.on_jax_build` for the thread that steps this
        batcher (a server registers it for its pump): every program here is built
        on that thread at its first call with a new shape (the prefill a bucket,
        the decode step or the pass, the suffix, install and sampling programs),
        and what jax says that cost goes into `stats`."""
        for key in _BUILD_STATS.get(kind, ()):
            self.stats[key] += amount

    @property
    def has_work(self) -> bool:
        return bool(self.queue) or any(r is not None for r in self._by_slot) or self._flight is not None

    def step(self) -> Dict[int, List[int]]:
        """Admit into free slots, dispatch the next decode step of every live
        slot, then read the step the call before dispatched.  Returns
        {request_id: [new tokens]}: the tokens of the step that was read and
        the prefill-sampled first token of requests admitted in this call, so
        streaming consumers see every token exactly once.  A model that
        generates by blocks goes the same way, a pass its step: an admit hands
        out nothing, and a read pass hands a request 0 to B tokens."""
        sp = tracing.span("llm.step")
        with sp:
            out: Dict[int, List[int]] = {}
            self._admit(out)
            landing = self._flight
            self._flight = self._dispatch(landing)
            ahead = landing is not None and self._flight is not None
            sp.set(live=len(landing.rows) if landing is not None else 0, ahead=int(ahead))
            self.stats["steps_ahead"] += ahead
            if landing is not None:
                self._land(landing, out, sp)
            return out

    def _dispatch(self, landing: Optional[_StepInFlight]) -> Optional[_StepInFlight]:
        """Dispatch one step more for every slot whose request has not reached
        its length once `landing` (the step in flight, unread) is read; None
        where there is no such slot.  A row's input is what the step before
        left it on the device, so the host has nothing to wait for: a request
        that ends by eos in `landing` is in this step too, one row computed
        late.  Where a pass of blocks is in flight, the host cannot tell which
        requests it ends (the count of an answer is in what the pass fixes):
        every slot that holds a request is in the next pass."""
        rows = [(s, r) for s, r in enumerate(self._by_slot) if r is not None]
        if not self._block:
            flying = {r.request_id for _, r in landing.rows} if landing is not None else ()
            rows = [(s, r) for s, r in rows if len(r.out_tokens) + (r.request_id in flying) < r.max_new_tokens]
        if not rows:
            return None
        slots = [s for s, _ in rows]
        with tracing.span("llm.step.upload"):
            self._live[:] = 0
            self._live[slots] = 1
            ints, floats = self._ints.copy(), self._floats.copy()
            self._fresh[:] = 0
            rows_read = None  # of a pass of blocks: reckoned at its read, where its position moves too
            if not self._block:
                rows_read = self._rows_read(slots, 1)
                self._pos[slots] += 1
        with tracing.span("llm.step.dispatch"):
            step = _pass_step_rowpos if self._block else _decode_step_rowpos
            # first what the host reads, last what the next step feeds: a causal step's
            # tokens are both, a pass returns its blocks and the state it leaves
            *made, self.cache, self._rng, touched = step(
                self.params, self.cache, ints, floats, self._prev, self._rng, cfg=self.cfg,
            )
            made, self._prev = made[0], made[-1]
        return _StepInFlight(made, touched, rows, self._sample_rows, self._truncate_rows, rows_read)

    def _rows_read(self, slots: List[int], tokens: int) -> tuple:
        """Of a layer's keys, the cache slots the attention fetches in a step
        that gives each of `slots` `tokens` positions from its `_pos` on: the
        rows' own [pads, pos + tokens) in whole key blocks, by the kernel's own
        helper, and the window layers' part of them (models/generate.py
        key_slots: the mean over the attention layers where their extents
        differ).  The host's arithmetic on its own vectors.  Under learned
        sparse attention (`cfg.index_topk`) the fetched slots are a row's
        selected ones, min(its context, topk), and two numbers follow: the
        rows' contexts, summed, and the indexer keys the step scans, every
        slot's whole extent (the scores are one contraction over the layer)."""
        first, last = self._pads[slots], self._pos[slots] + tokens
        read = key_slots(self.cache, first, last, self.cfg.attn_window, self.cfg)
        if "ki" in self.cache:
            read += (int((last - first).sum()), self.slots * self.t_max)
        return read

    def _count_rows_read(self, rows_read: tuple, sp: tracing.span) -> None:
        if self._cache_rows:
            read, window, shared, *sparse = rows_read
            sp.set(cache_rows_read=read, cache_rows=self._cache_rows)
            if sparse:
                sp.set(context_rows=sparse[0], index_rows_read=sparse[1])
                self.stats["context_rows"] += sparse[0]
                self.stats["index_rows_read"] += sparse[1]
            self.stats["cache_rows_read"] += read
            self.stats["cache_rows"] += self._cache_rows
            if self._cache_bytes["window"]:
                sp.set(window_rows_read=window)
                self.stats["window_rows_read"] += window
            if self.cfg.shared_readers:
                sp.set(shared_rows_read=shared, shared_readers=self.cfg.shared_readers)
                self.stats["shared_rows_read"] += shared

    def _land(self, step: _StepInFlight, out: Dict[int, List[int]], sp: tracing.span) -> None:
        """Read a dispatched step and hand what each row made to the request
        that held the slot at dispatch, unless that request has ended since:
        such a row is dropped, and its slot, which may hold another request by
        now, is left alone."""
        with tracing.span("llm.step.readback"):
            made, touched = jax.device_get((step.made, step.touched))
        positions = len(step.rows) * (self._block or 1)  # late rows among them: the device ran them
        said: Dict[str, Any] = dict(sample_rows=step.sample_rows, truncate_rows=step.truncate_rows)
        self.stats["sort_steps"] += step.truncate_rows > 0
        if touched is not None:
            # a replica that holds a share of the experts reads the held experts given a
            # row, then the assignments that fell on them (layer means; the third, the share
            # of the layers that took the compact buffer, is an admit's to report)
            touched, *held = np.ravel(touched)
            said.update(moe_rows=positions, moe_experts_touched=float(touched))
            if held:
                said.update(moe_held_assignments=float(held[0]), moe_loop_layers=self._moe_step_loop_layers)
                self.stats["moe_step_held_layers"] += self._moe_step_held_layers
                self.stats["moe_step_loop_layers"] += self._moe_step_loop_layers
            self.stats["moe_assignments"] += positions * self.cfg.n_experts_per_tok
            self.stats["moe_experts_touched"] += float(touched)
        if self._ssm_step_bytes:
            said.update(ssm_state_bytes=self._ssm_step_bytes)
            self.stats["ssm_state_bytes"] += self._ssm_step_bytes
        rows_read = step.cache_rows_read
        if self._block:
            # the mirror is this pass's input until the scatter below moves it on (a late
            # row's slot that was given away since stands at its new request's); a row that
            # stores the block before fetches the slots before its own block once more
            slots = [s for s, _ in step.rows]
            storing = [s for s in slots if self._blk_pending[s]]
            rows_read = tuple(map(sum, zip(self._rows_read(slots, self._block), self._rows_read(storing, 0))))
        self._count_rows_read(rows_read, sp)
        self.stats["decode_steps"] += 1
        rows = [(s, req) for s, req in step.rows if not req.done]
        self.stats["late_rows"] += len(step.rows) - len(rows)
        with tracing.span("llm.step.scatter"):
            if self._block:
                said.update(self._scatter_blocks(rows, made, out), block_rows=positions)
                self.stats["block_passes"] += len(step.rows)
            else:
                self._scatter_tokens(rows, made, out)
        sp.set(**said)

    def _scatter_tokens(self, rows: List[tuple], nxt: np.ndarray, out: Dict[int, List[int]]) -> None:
        """A causal step's tokens to their requests; a request's last frees its slot."""
        for s, req in rows:
            tok = int(nxt[s])
            req.out_tokens.append(tok)
            out.setdefault(req.request_id, []).append(tok)
            self.stats["tokens_out"] += 1
            if len(req.out_tokens) >= req.max_new_tokens or (
                req.eos_id is not None and tok == req.eos_id
            ):
                self._finish(s, req)

    def _scatter_blocks(self, rows: List[tuple], after: np.ndarray, out: Dict[int, List[int]]) -> Dict[str, int]:
        """What a pass of blocks did to each of `rows`, from the blocks `after`
        it [2B, S] and the host's mirror, which is the pass's input: the tokens
        that every position before them is fixed for go out, and the mirror
        moves on as the program moved the device's state (`_pass_step_rowpos`):
        a block that the pass left with every position fixed is pending, and the
        slot stands at an empty block B slots on, whose first pass (pass 0 in
        `pass_of`) stores it.  Returns what `llm.step` says of the pass."""
        b = self._block
        fixed_now = handed = stored = fused = 0
        for s, req in rows:
            fused += int(self._blk_pending[s])
            newly = np.nonzero(after[b:, s] != self._blk_fixed[:, s])[0]
            for i in newly:
                req.pass_of[i] = req.block_pass
            fixed_now += len(newly)
            stored += not len(newly)  # nothing was masked: no admit and no pass leaves a slot so
            self._blk_tokens[:, s], self._blk_fixed[:, s] = after[:b, s], after[b:, s]
            req.block_pass += 1
            new = out.setdefault(req.request_id, [])
            while req.block_out < b and self._blk_fixed[req.block_out, s] and not req.done:
                tok = int(self._blk_tokens[req.block_out, s])
                req.out_tokens.append(tok)
                req.fixed_at.append(req.pass_of[req.block_out])
                new.append(tok)
                req.block_out += 1
                if len(req.out_tokens) >= req.max_new_tokens or (
                    req.eos_id is not None and tok == req.eos_id
                ):
                    req.block_tail = [
                        (i, req.pass_of[i], int(self._blk_tokens[i, s]))
                        for i in range(req.block_out, b) if self._blk_fixed[i, s]
                    ]
                    self._finish(s, req)
            handed += len(new)
            if not new:
                del out[req.request_id]
            # a block left whole: the slot's next pass stores it and runs the first pass of the next
            self._blk_pending[s] = not req.done and self._blk_fixed[:, s].all()
            if self._blk_pending[s]:
                self._pos[s] += b
                self._blk_tokens[:, s] = 0
                self._blk_fixed[:, s] = 0
                req.block_pass, req.block_out, req.pass_of = 0, 0, [-1] * b
        self.stats["tokens_out"] += handed
        self.stats["block_tokens_fixed"] += fixed_now
        self.stats["block_stores_fused"] += fused
        return dict(tokens_fixed=fixed_now, tokens_out=handed, store_rows=stored, fused_store_rows=fused)

    def fixed_at(self, request_id: int) -> List[int]:
        """The pass of its block (0: the block's first) at which each token
        handed out for this request so far was fixed, for a model that
        generates by blocks; of a request in a slot, or among the last that
        finished.  With `block_tail` all that the tokens leave open of how
        they came about."""
        return list(self._request(request_id).fixed_at)

    def block_tail(self, request_id: int) -> List[tuple]:
        """[(position in the block, pass, token)]: what a finished request's
        last block held fixed past the answer's end, which the passes that
        fixed the answer's last tokens saw and the answer does not hold."""
        return list(self._request(request_id).block_tail)

    def _request(self, request_id: int) -> Request:
        for r in itertools.chain(self._by_slot, reversed(self._completed)):
            if r is not None and r.request_id == request_id:
                return r
        raise KeyError(f"request {request_id} is in no slot and not among the last {self._completed.maxlen} finished")

    def pump(self) -> List[Request]:
        """Run until every submitted request finishes; returns them in
        completion order (test/batch convenience — servers call step())."""
        before = list(self._completed)
        while self.has_work:
            self.step()
        seen = {id(r) for r in before}
        return [r for r in self._completed if id(r) not in seen]

    # ------------------------------------------------------------- internals
    def _finish(self, slot: int, req: Request) -> None:
        req.done = True
        self._release(slot)
        self._completed.append(req)
        self.stats["finished"] += 1

    def _asks(self, slot: int):
        """(samples, truncates): what the slot's knobs ask of the step's
        sampler, by `_sample_rowwise`'s own predicate on the rows as uploaded."""
        samples = bool(self._temps[slot] > 0.0)
        return samples, samples and bool(self._topks[slot] > 0 or 0.0 < self._topps[slot] < 1.0)

    def _release(self, slot: int) -> None:
        """The slot frees for the next admit and asks nothing of the sampler
        until then: the sampler reads every row's knobs, live or not, and one
        finished top-p request would leave every later step sorting."""
        samples, truncates = self._asks(slot)
        self._sample_rows -= samples
        self._truncate_rows -= truncates
        self._temps[slot], self._topks[slot], self._topps[slot] = 0.0, 0, 1.0
        self._by_slot[slot] = None

    def _bucket(self, n: int, max_new: int) -> int:
        """Smallest bucket holding the prompt AND leaving room to decode;
        falls back to the exact prompt length when every bucket would
        overflow the cache.  The fall-back is one more prefill program for
        each DISTINCT length, compiled at that length's first admit on the
        pump's thread (`prefill_traces` counts them)."""
        for b in self.prefill_buckets:
            if n <= b and b + max_new <= self.t_max:
                return b
        return n

    def _prefix_split(self, prompt: np.ndarray) -> int:
        """Cacheable prefix length: the largest _split_quantum multiple that
        still leaves >= 1 suffix token (the last prompt token must be
        teacher-forced through _suffix_step to produce first-token logits).
        0 = no usable prefix (prompt too short)."""
        split = ((len(prompt) - 1) // self._split_quantum) * self._split_quantum
        return split if split >= self.prefix_block else 0

    def _prefill_padded(self, prompt: np.ndarray, bucket: int, sp: tracing.span):
        """Left-pad `prompt` to `bucket` and prefill it: one compiled batch-1
        program a bucket, traced at the bucket's first admit and one dispatch
        thereafter (the padded ids and the pad count go as the host arrays
        they are).  Returns (first-token logits [1, V], its cache rows as a
        batch of one, pad, the held expert layers and the compact ones on the
        device: `prefill_counted`'s, None where the replica holds every expert).
        `sp`, the request's `llm.admit` span, is told the positions computed in
        the first layer and in the last (`stats`)."""
        pre = tracing.span("llm.admit.prefill")
        with pre:
            padded = np.zeros((1, bucket), np.int32)
            pad = bucket - len(prompt)
            padded[0, pad:] = prompt  # LEFT pad: generate.py's prefill contract
            programs = prefill_counted._cache_size()
            logits, rows, held = prefill_counted(
                self.params, padded, self.cfg, self.t_max, pad=np.asarray([pad], np.int32)
            )
            self.stats["prefill_traces"] += prefill_counted._cache_size() - programs
            tail = 1 if self.cfg.carries else bucket
            sp.set(prefill_positions=bucket, tail_positions=tail)
            if self.cfg.index_topk and bucket > self.cfg.index_topk:
                # the bucket's queries each select among its keys (a shorter bucket attends densely)
                pre.set(select_queries=bucket, select_keys=bucket)
            if self.cfg.ssm_n_heads:  # a Mamba-2 prefill's scan runs in chunks (models/transformer.py _ssd_scan)
                sp.set(ssm_chunks=-(-bucket // self.cfg.ssm_chunk))
            self.stats["prefill_positions_total"] += bucket
            self.stats["prefill_tail_positions_total"] += tail
            self.stats["prefill_tail_share"] = (
                100.0 * self.stats["prefill_tail_positions_total"] / self.stats["prefill_positions_total"])
        return logits, rows, pad, held

    def block_plan(self, n: int, max_new: int):
        """How a prompt of n tokens enters a model that generates by blocks of
        B: (the tokens of its whole blocks, which prefill; the bucket they are
        padded to on the left; the pad).  The other n % B are the fixed part of
        the first answer block.  The bucket leaves room for the answer's last
        block to its end, which may lie past n + max_new."""
        b = self._block
        whole = n - n % b
        if not whole:
            return 0, 0, 0  # nothing to prefill: the first block starts the cache
        bucket = self._bucket(whole, -(-(n % b + max_new) // b) * b)
        return whole, bucket, bucket - whole

    def _admit_blocks(self, req: Request, slot: int, sp: tracing.span) -> int:
        """The admit of a model that generates by blocks: the prompt's whole
        blocks prefill (under the block mask) into the slot, its tail is the
        fixed part of the slot's first block, and no token is handed out.
        Returns the tokens prefilled."""
        b, prompt = self._block, req.prompt_ids
        whole, bucket, pad = self.block_plan(len(prompt), req.max_new_tokens)
        tail = len(prompt) - whole
        sp.set(bucket=bucket, prefix_hit=0, block_tail=tail)
        if whole:
            _, rows, _, _ = self._prefill_padded(prompt[:whole], bucket, sp)
            with tracing.span("llm.admit.install"):
                self.cache = _install_slot(self.cache, rows, slot)
        self._blk_tokens[:, slot] = 0
        self._blk_tokens[:tail, slot] = prompt[whole:]
        self._blk_fixed[:, slot] = np.arange(b) < tail
        req.block_pass, req.block_out, req.pass_of = 0, tail, [-1] * b
        self._blk_pending[slot] = False
        self._pos[slot] = bucket
        self._pads[slot] = pad
        self._fresh[slot] = 1
        return whole

    def _admit_full_prefill(self, req: Request, sp: tracing.span):
        """Cold admit: prefill the whole prompt.  Returns (first-token logits
        [1,V], its rows as a batch of one, pad, next_pos, the prefill's held
        expert layers: `_prefill_padded`).  `sp` is the request's `llm.admit`
        span."""
        bucket = self._bucket(len(req.prompt_ids), req.max_new_tokens)
        sp.set(bucket=bucket, prefix_hit=0)
        logits, rows, pad, held = self._prefill_padded(req.prompt_ids, bucket, sp)
        return logits, rows, pad, bucket, held

    def _admit_prefix_cached(self, req: Request, split: int, sp: tracing.span):
        """Chunked admit via the prefix cache: the block-aligned prefix
        comes from the cache (or prefills once, populating it); the suffix
        teacher-forces through _suffix_step token by token.  Hit and miss
        run the SAME suffix computation on the same prefix rows, so the
        produced tokens are bit-identical either way — a hit just skips the
        prefix prefill (the TTFT win on shared-system-prompt traffic).  Returns
        what `_admit_full_prefill` does; a hit ran no prefill and counts no
        held layers."""
        prompt = req.prompt_ids
        suffix = prompt[split:]
        # bucket must leave room for the stepped suffix AND decode
        bucket = self._bucket(split, req.max_new_tokens + len(suffix))
        key = PrefixCache.key(prompt[:split], bucket)
        entry = self.prefix_cache.get(key)
        sp.set(bucket=bucket, prefix_hit=int(entry is not None))
        held = None
        if entry is None:
            _, rows, pad, held = self._prefill_padded(prompt[:split], bucket, sp)
            # store a snapshot BEFORE stepping: _suffix_step donates its rows
            self.prefix_cache.put(key, jax.tree_util.tree_map(jnp.copy, rows), pad)
            self.stats["prefix_misses"] += 1
        else:
            pad = entry["pad"]
            rows = jax.tree_util.tree_map(jnp.copy, entry["rows"])
            self.stats["prefix_hits"] += 1
            self.stats["prefix_tokens_reused"] += split
        with tracing.span("llm.admit.suffix", tokens=len(suffix)):
            pad_arr = jnp.asarray([pad], np.int32)
            logits = None
            for i, tok in enumerate(suffix):
                logits, rows = _suffix_step(
                    self.params, rows,
                    jnp.asarray([int(tok)], np.int32),
                    jnp.asarray([bucket + i], np.int32),
                    pad_arr, cfg=self.cfg,
                )
        return logits, rows, pad, bucket + len(suffix), held

    def _admit(self, out: Optional[Dict[int, List[int]]] = None) -> None:
        while self.queue and None in self._by_slot:
            req = self.queue.popleft()
            # the admit runs on the pump's thread but belongs to the request:
            # under the submitter's trace context, as a worker runs a task
            token = tracing.push_execution(req.trace) if req.trace else None
            try:
                self._admit_one(req, out)
            finally:
                if token is not None:
                    tracing.pop_execution(token)

    def _admit_one(self, req: Request, out: Optional[Dict[int, List[int]]]) -> None:
        t0 = time.monotonic()
        queue_wait = t0 - req.t_submit
        sp = tracing.span(
            "llm.admit", rid=req.request_id, prompt_len=len(req.prompt_ids),
            queue_wait_ms=1e3 * queue_wait,
        )
        with sp:
            slot = self._by_slot.index(None)
            traces, built = self.stats["prefill_traces"], self.stats["program_build_s"]
            prefilled = len(req.prompt_ids)
            if self._block:
                prefilled = self._admit_blocks(req, slot, sp)
            else:
                first = self._admit_first_token(req, slot, sp)
                req.out_tokens.append(first)
                if out is not None:
                    out.setdefault(req.request_id, []).append(first)
                self.stats["tokens_out"] += 1
            # 1 where this admit traced its bucket's prefill program
            sp.set(traced=self.stats["prefill_traces"] - traces)
            if self.stats["program_build_s"] > built:  # and what the programs it built cost
                sp.set(build_ms=1e3 * (self.stats["program_build_s"] - built))
            req.slot = slot
            self._by_slot[slot] = req
            self._temps[slot] = req.temperature
            self._topps[slot] = req.top_p
            samples, truncates = self._asks(slot)
            self._sample_rows += samples
            self._truncate_rows += truncates
            self.stats["admitted"] += 1
            if self.cfg.n_experts:
                assignments = prefilled * self.cfg.n_experts_per_tok
                sp.set(moe_assignments=assignments)
                self.stats["moe_assignments"] += assignments
            if self._ssm_slot_bytes:
                sp.set(ssm_state_bytes=self._ssm_slot_bytes)
                self.stats["ssm_state_bytes"] += self._ssm_slot_bytes
            if not self._block and (len(req.out_tokens) >= req.max_new_tokens or (
                req.eos_id is not None and first == req.eos_id
            )):
                self._finish(slot, req)
        admit_s = time.monotonic() - t0
        self.stats["queue_wait_s"] += queue_wait
        self.stats["admit_s"] += admit_s
        if self.observe_phase is not None:
            self.observe_phase("llm.admit.queue_wait", queue_wait)
            self.observe_phase("llm.admit", admit_s)

    def _admit_first_token(self, req: Request, slot: int, sp: tracing.span) -> int:
        """The admit of a model that yields one causal token a step: the prompt
        prefills (through the prefix cache where there is one) into the slot,
        and the prefill's logits choose the request's first token, which the
        slot's next step feeds.  Returns that token."""
        split = (
            self._prefix_split(req.prompt_ids)
            if self.prefix_cache is not None
            else 0
        )
        if split:
            logits, rows, pad, next_pos, held = self._admit_prefix_cached(req, split, sp)
        else:
            logits, rows, pad, next_pos, held = self._admit_full_prefill(req, sp)
        with tracing.span("llm.admit.install"):
            self.cache = _install_slot(self.cache, rows, slot)
        with tracing.span("llm.admit.sample"):
            first, self._rng = _sample_first(
                logits, self._rng, np.float32([req.temperature]), np.int32([req.top_k]),
                np.float32([req.top_p]),
            )
            # a replica that holds a share of the experts reads, with the token, how many
            # of the prefill's expert layers took the compact buffer (parallel/moe.py)
            first, held = jax.device_get((first, held))
            first = int(first)
        if held is not None:
            sp.set(moe_held_layers=int(held[0]), moe_compact_layers=int(held[1]))
            self.stats["moe_held_layers"] += int(held[0])
            self.stats["moe_compact_layers"] += int(held[1])
        self._tokens[slot], self._fresh[slot] = first, 1
        self._pos[slot] = next_pos  # next write lands after the prompt
        self._pads[slot] = pad
        self._topks[slot] = req.top_k
        return first
