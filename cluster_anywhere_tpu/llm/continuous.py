"""Continuous batching for LLM decoding (the iteration-level scheduler the
reference gets from its vLLM-backed `serve.llm` deployments —
python/ray/llm's engine does exactly this; redesigned here for the XLA
compilation model instead of paged CUDA kernels).

The scheduler owns a fixed pool of decode SLOTS over one shared cache (keys
and values [L, S, T_max, KV, D], and a state-space layer's recurrent state:
models/generate.py init_cache).  Each slot runs one request; requests at different
depths decode together in ONE jitted step whose shapes never change — slot
count and cache length are static, per-row positions are traced — so
admitting or finishing requests never recompiles anything:

- admit: a queued request prefills (a compiled batch-1 program, the prompt
  padded to a bucket length so that there is one program a bucket) and its
  cache rows scatter into its slot between decode steps.
- decode: every live slot advances one token per step.  Per-row cache
  positions/pads drive RoPE and masking; finished or empty slots still
  compute (their lanes are garbage) but write only to their own cache rows
  (a key/value row past its position; a recurrent state, which is not frozen:
  it moves on with every step), which the next admit overwrites whole.
- finish: a slot frees the moment its request hits max_new_tokens or eos;
  the next step() can admit into it immediately — no head-of-line batching
  barrier, which is the whole point vs static generate() batching.

This module is the scheduler, the per-row sampler and the jitted wrapper.
The model's mathematics is models/generate.py's: `prefill`, and `decode_rows`,
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

from ..models.generate import (
    _nucleus_mask, _sample, decode_rows, init_cache, install_rows, prefill, recurrent_state_bytes,
)
from ..models.transformer import TransformerConfig
from ..util import tracing


PREFILL_BUCKETS = (64, 128, 256)  # padded prompt lengths: one prefill program each


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


def _sample_rowwise(logits, rngs, temps, top_ks, top_ps):
    """Per-row sampling with TRACED temperature, top-k, and top-p (requests
    in one decode batch carry their own knobs; a static top_k would force
    one value per compiled program).  top_k <= 0 means no truncation;
    top_p outside (0, 1) means no nucleus mask; temp <= 0 means greedy."""
    with jax.named_scope("sample"):
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        t = jnp.maximum(temps, 1e-6)[:, None]
        scaled = logits / t
        v = logits.shape[-1]
        # traced top-k: k-th largest per row via a descending sort
        sorted_desc = -jnp.sort(-scaled, axis=-1)
        kth_idx = jnp.clip(top_ks - 1, 0, v - 1)[:, None]
        kth = jnp.take_along_axis(sorted_desc, kth_idx, axis=-1)
        scaled = jnp.where((top_ks[:, None] > 0) & (scaled < kth), -1e30, scaled)
        # per-row nucleus mask: [S,1] top_p broadcasts through the shared helper
        scaled = _nucleus_mask(scaled, top_ps[:, None])
        sampled = jax.vmap(lambda rng, row: jax.random.categorical(rng, row))(
            rngs, scaled
        ).astype(jnp.int32)
        return jnp.where(temps <= 0.0, greedy, sampled)


@functools.partial(jax.jit, static_argnames=("cfg",), donate_argnums=(1,))
def _decode_step_rowpos(params, cache, ints, floats, rng, *, cfg):
    """One token for every slot with PER-ROW cache positions.
    ints: [4, S] int32, the rows tokens, pos, pads, top_ks, and for a mixture
    of experts a fifth, live: 1 for the slots that hold a request (the other
    rows are given no expert; a dense model is not told).  floats: [2, S]
    float32, the rows temps, top_ps.  rng: the batcher's one key, split here
    into the key it carries on and one key a row.  Returns (next_tokens [S],
    cache, the carried key, experts touched): the last is the mean over the
    layers of the experts that were given a row, None for a dense model.  The
    cache is donated and is the layer loop's carry (models/generate.py), so
    the step writes one row a slot and layer of [L,S,Tmax,KV,D] x2 in place
    and copies nothing of that size:
    tests/test_chip_compile.py holds the chip's program to it
    (`test_decode_step_writes_the_cache_in_place`), tests/test_llm.py the
    rows it may change."""
    tokens, pos, pads, top_ks, *live = ints
    live = live[0] != 0 if live else None
    temps, top_ps = floats
    keys = jax.random.split(rng, ints.shape[1] + 1)
    logits, cache, touched = decode_rows(params, cache, tokens, pos, pads, cfg, live)
    nxt = _sample_rowwise(logits, keys[1:], temps, top_ks, top_ps)
    return nxt, cache, keys[0], touched


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
        return sum(
            int(a.size) * a.dtype.itemsize
            for e in self._d.values()
            for a in jax.tree_util.tree_leaves(e["rows"])
        )


class ContinuousBatcher:
    """Iteration-level scheduler over a fixed slot pool (see module doc).

    Drive it with submit() + step() (one decode iteration), or pump() until
    a request finishes.  step() returns per-request newly produced tokens,
    enabling token streaming per request while others keep decoding."""

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
        # the decode step's per-slot inputs as its program takes them: two
        # host arrays that go to the jitted call as they are (one dispatch, no
        # eager upload), the scheduler's vectors their rows.  The host writes
        # them only in an admit and in llm.step.scatter, after the step's
        # tokens are read back: the program has consumed them by then (on the
        # CPU backend a host array may be aliased, not copied).
        self._ints = np.zeros((5 if cfg.n_experts else 4, slots), np.int32)
        self._floats = np.zeros((2, slots), np.float32)
        # _pos: cache slot of the NEXT write; a mixture's fifth row: live slots
        self._tokens, self._pos, self._pads, self._topks = self._ints[:4]
        self._temps, self._topps = self._floats
        self._topps[:] = 1.0
        self._by_slot: List[Optional[Request]] = [None] * slots
        self.queue: deque[Request] = deque()
        # bounded: pump() drains it; step()-driven servers track their own
        # Requests (an unbounded list would grow for the replica's lifetime)
        self._completed: deque[Request] = deque(maxlen=4096)
        self._ids = itertools.count(1)
        self._rng = jax.random.key(0)
        self.stats = {
            "admitted": 0, "finished": 0, "decode_steps": 0, "cancelled": 0,
            "prefix_hits": 0, "prefix_misses": 0, "prefix_tokens_reused": 0,
            # counted where the spans are: requests queued, tokens handed
            # out, and cumulative seconds queued and in admit
            "submitted": 0, "tokens_out": 0, "queue_wait_s": 0.0, "admit_s": 0.0,
            # (token, expert) pairs a layer's routed experts were given, in
            # admits and steps; stays 0 for a dense model
            "moe_assignments": 0,
            # admits that found no compiled prefill for their padded length
            # and traced one; stays where it is once every bucket is warm
            "prefill_traces": 0,
            # recurrent state read and written by the steps and installed by the
            # admits; stays 0 for a model of attention layers alone
            "ssm_state_bytes": 0,
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
        req = Request(
            next(self._ids), prompt, int(max_new_tokens), float(temperature),
            self.top_k if top_k is None else int(top_k), float(top_p), eos_id,
            tracing.current(), time.monotonic(),
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
                self._by_slot[s] = None  # lane decodes garbage until an admit overwrites its rows
                self.stats["cancelled"] += 1
                return True
        return False

    @property
    def has_work(self) -> bool:
        return bool(self.queue) or any(r is not None for r in self._by_slot)

    def step(self) -> Dict[int, List[int]]:
        """Admit into free slots, then decode one token on every live slot.
        Returns {request_id: [new tokens this step]} — including the
        prefill-sampled first token of requests admitted this step, so
        streaming consumers see every token exactly once."""
        sp = tracing.span("llm.step")
        with sp:
            out: Dict[int, List[int]] = {}
            self._admit(out)
            live = [s for s, r in enumerate(self._by_slot) if r is not None]
            sp.set(live=len(live))
            if not live:
                return out
            with tracing.span("llm.step.upload"):
                if self.cfg.n_experts:
                    self._ints[4] = [r is not None for r in self._by_slot]
            with tracing.span("llm.step.dispatch"):
                nxt, self.cache, self._rng, touched = _decode_step_rowpos(
                    self.params, self.cache, self._ints, self._floats, self._rng,
                    cfg=self.cfg,
                )
            with tracing.span("llm.step.readback"):
                nxt, touched = jax.device_get((nxt, touched))
            if touched is not None:
                sp.set(moe_rows=len(live), moe_experts_touched=float(touched))
                self.stats["moe_assignments"] += len(live) * self.cfg.n_experts_per_tok
            if self._ssm_step_bytes:
                sp.set(ssm_state_bytes=self._ssm_step_bytes)
                self.stats["ssm_state_bytes"] += self._ssm_step_bytes
            self.stats["decode_steps"] += 1
            self.stats["tokens_out"] += len(live)
            with tracing.span("llm.step.scatter"):
                for s in live:
                    req = self._by_slot[s]
                    tok = int(nxt[s])
                    req.out_tokens.append(tok)
                    out.setdefault(req.request_id, []).append(tok)
                    self._tokens[s] = tok
                    self._pos[s] += 1
                    if len(req.out_tokens) >= req.max_new_tokens or (
                        req.eos_id is not None and tok == req.eos_id
                    ):
                        self._finish(s, req)
            return out

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
        self._by_slot[slot] = None  # slot frees for the next admit
        self._completed.append(req)
        self.stats["finished"] += 1

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

    def _prefill_padded(self, prompt: np.ndarray, bucket: int):
        """Left-pad `prompt` to `bucket` and prefill it: one compiled batch-1
        program a bucket, traced at the bucket's first admit and one dispatch
        thereafter (the padded ids and the pad count go as the host arrays
        they are).  Returns (first-token logits [1, V], its cache rows as a
        batch of one, pad)."""
        with tracing.span("llm.admit.prefill"):
            padded = np.zeros((1, bucket), np.int32)
            pad = bucket - len(prompt)
            padded[0, pad:] = prompt  # LEFT pad: generate.py's prefill contract
            programs = prefill._cache_size()
            logits, rows = prefill(
                self.params, padded, self.cfg, self.t_max, pad=np.asarray([pad], np.int32)
            )
            self.stats["prefill_traces"] += prefill._cache_size() - programs
        return logits, rows, pad

    def _admit_full_prefill(self, req: Request, sp: tracing.span):
        """Cold admit: prefill the whole prompt.  Returns (first-token logits
        [1,V], its rows as a batch of one, pad, next_pos).  `sp` is the
        request's `llm.admit` span."""
        bucket = self._bucket(len(req.prompt_ids), req.max_new_tokens)
        sp.set(bucket=bucket, prefix_hit=0)
        return (*self._prefill_padded(req.prompt_ids, bucket), bucket)

    def _admit_prefix_cached(self, req: Request, split: int, sp: tracing.span):
        """Chunked admit via the prefix cache: the block-aligned prefix
        comes from the cache (or prefills once, populating it); the suffix
        teacher-forces through _suffix_step token by token.  Hit and miss
        run the SAME suffix computation on the same prefix rows, so the
        produced tokens are bit-identical either way — a hit just skips the
        prefix prefill (the TTFT win on shared-system-prompt traffic)."""
        prompt = req.prompt_ids
        suffix = prompt[split:]
        # bucket must leave room for the stepped suffix AND decode
        bucket = self._bucket(split, req.max_new_tokens + len(suffix))
        key = PrefixCache.key(prompt[:split], bucket)
        entry = self.prefix_cache.get(key)
        sp.set(bucket=bucket, prefix_hit=int(entry is not None))
        if entry is None:
            _, rows, pad = self._prefill_padded(prompt[:split], bucket)
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
        return logits, rows, pad, bucket + len(suffix)

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
            traces = self.stats["prefill_traces"]
            split = (
                self._prefix_split(req.prompt_ids)
                if self.prefix_cache is not None
                else 0
            )
            if split:
                logits, rows, pad, next_pos = self._admit_prefix_cached(req, split, sp)
            else:
                logits, rows, pad, next_pos = self._admit_full_prefill(req, sp)
            # 1 where this admit traced its bucket's prefill program
            sp.set(traced=self.stats["prefill_traces"] - traces)
            with tracing.span("llm.admit.install"):
                self.cache = _install_slot(self.cache, rows, slot)
            with tracing.span("llm.admit.sample"):
                self._rng, k = jax.random.split(self._rng)
                first = int(
                    np.asarray(
                        _sample(
                            logits, k, jnp.float32(req.temperature), req.top_k,
                            jnp.float32(req.top_p),
                        )
                    )[0]
                )
            req.out_tokens.append(first)
            if out is not None:
                out.setdefault(req.request_id, []).append(first)
            req.slot = slot
            self._by_slot[slot] = req
            self._tokens[slot] = first
            self._pos[slot] = next_pos  # next write lands after the prompt
            self._pads[slot] = pad
            self._temps[slot] = req.temperature
            self._topks[slot] = req.top_k
            self._topps[slot] = req.top_p
            self.stats["admitted"] += 1
            self.stats["tokens_out"] += 1
            if self.cfg.n_experts:
                assignments = len(req.prompt_ids) * self.cfg.n_experts_per_tok
                sp.set(moe_assignments=assignments)
                self.stats["moe_assignments"] += assignments
            if self._ssm_slot_bytes:
                sp.set(ssm_state_bytes=self._ssm_slot_bytes)
                self.stats["ssm_state_bytes"] += self._ssm_slot_bytes
            if len(req.out_tokens) >= req.max_new_tokens or (
                req.eos_id is not None and first == req.eos_id
            ):
                self._finish(slot, req)
        self.stats["queue_wait_s"] += queue_wait
        self.stats["admit_s"] += time.monotonic() - t0
