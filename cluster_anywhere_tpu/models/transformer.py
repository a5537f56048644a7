"""Flagship decoder-only transformer (LLaMA-style), TPU-first.

Design points (vs. the reference, which delegates all modeling to torch):
- pure-pytree params + functional forward: jit/grad/vmap compose freely
- layers stacked on a leading axis and iterated with `lax.scan` — one block
  gets compiled once regardless of depth (compile-time O(1) in layers)
- every parallelism axis is native: DP/FSDP/TP via GSPMD param/activation
  shardings (parallel.sharding), PP via the shard_map pipeline schedule
  (parallel.pipeline), SP via ring attention or Ulysses (parallel.ring_attention,
  parallel.ulysses) under a partial-manual shard_map over {'pp','sp'}
- bfloat16 activations, fp32 params/optimizer, RoPE, GQA, SwiGLU, RMSNorm

A block is two halves, each written once for training, prefill and decode
(`_attention_half` or `_ssm_half`, then `_ffn_half`): what differs between the
three is the rotary positions and the attention core, or the state a
state-space mixer starts from, so those are the first half's arguments.  This
module holds the training cores (`_attention`; `_ssm_mix` from the zero
state); those that keep a cache are in models/generate.py, which owns its
layout.  Which layer is which kind is the configuration's `layer_kinds`; every
program's layer loop is `_scan_layers`, one scan a run of one kind, and every
program's output head is `_head`.

Latent attention (`cfg.latent`) is the first half with a second way to q, k, v
(`_project_latent`: a low-rank query, one rotated key that every head shares
and a latent of which each head's keys and values are up-projections) and, in
training, every head's keys and values expanded for the same dense core
(`_latent_expand`); YaRN's frequencies are `_rope_freqs`.  A mixture may keep
leading dense layers (kind "attn_dense": a run of its own in the loop), shared
experts beside the routed ones (`_ffn_half`) and, on one chip of several that
divide a layer's experts, a held share of them (parallel/moe.py routed_ffn).

A layer may also be ONE half alone (`HALF_KINDS`: a Mamba-2 mixer
`_mamba2_half` over `_ssd_scan`, attention, an FFN), x + f(norm(x)) with the
one norm, in a stack whose mixers and FFNs stand in any order; `_ffn_half`
hands x back where a block holds no second half.

Which layer mixes how may also be a tuple (`cfg.layer_mixers`): window layers
("attn_win": the last `cfg.attn_window` positions, `_attention(window=)`)
beside full ones, each kind's weights a stack of its own (`_INIT_KIND`); which
kinds turn q and k is `cfg.rotates`, and `cfg.norm_output` norms each half's
output in place of its input.

The model is the `entry()` / `dryrun_multichip()` flagship in
__graft_entry__.py and what benchmarks/ trains and serves.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import math
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from ..ops.attention import FLASH_LSE, FLASH_OUT
from ..ops.attention import attention as dense_attention
from ..parallel.pipeline import pipeline_apply
from ..parallel.ring_attention import ring_attention
from ..parallel.ulysses import ulysses_attention
from ..util import tracing


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    d_model: int = 512
    n_layers: int = 4
    n_heads: int = 8
    n_kv_heads: int = 8
    d_head: int = 64
    d_ff: int = 1408
    max_seq_len: int = 2048
    rope_theta: float = 10000.0
    dtype: Any = jnp.bfloat16  # activation/compute dtype
    param_dtype: Any = jnp.float32
    attn_impl: str = "auto"  # dense | ring | ulysses | auto
    pp: int = 1
    sp: int = 1
    num_microbatches: int = 1
    # recompute what does not fit: every block is a `jax.checkpoint` that keeps its input and runs
    # forward again in the backward pass, all of it or, where the device's memory has room beside
    # the step's state (`_remat_keeps` decides layer by layer as the step is traced, from the shapes,
    # the mesh and the device's limit), only the two norms and the FFN half: the attention half's q,
    # k, v, the core's result and h are kept (KEPT_NAMES), and in as many of the last layers as the
    # rest of the room holds the dense FFN's two up products too (FFN_NAMES)
    remat: bool = False
    # unroll the layer scan: XLA overlaps each layer's weight streaming with
    # the previous layer's compute across iteration boundaries (a rolled
    # while-loop can't), worth ~12% a step on v5e; compile time grows with
    # depth, so deep stacks can turn it off
    unroll_layers: bool = True
    # mixture-of-experts FFN (parallel/moe.py): every layer's dense FFN
    # becomes n_experts experts behind a softmax router.  0 = dense.  On one
    # device (serving, and the forward and loss without a mesh) tokens go
    # through the dropless top-k path `routed_ffn`; on a multi-device mesh the
    # experts are sharded over the 'ep' axis and tokens travel by all_to_all
    # (`moe_ffn`: top-1 with a capacity, ungated experts only).
    n_experts: int = 0
    ep: int = 1
    capacity_factor: float = 1.25  # moe_ffn only: routed_ffn drops nothing
    moe_aux_weight: float = 0.01
    n_experts_per_tok: int = 1  # the k largest router probabilities a token
    # gated experts (silu(x w_gate) * (x w_up)) w_down, each [X, E, F] / [X, F, E];
    # False: silu(x w_in) w_out
    moe_gated: bool = False
    moe_renormalize: bool = False  # the k probabilities divided by their sum
    # RMSNorm with a learned weight over the whole projected q and k vectors
    # (before the split into heads and the rotary embedding): q_norm, k_norm
    qk_norm: bool = False
    # with qk_norm: the RMSNorm runs over each head's d_head instead, after the
    # split into heads, one weight [d_head] shared by the heads
    qk_norm_per_head: bool = False
    rotary: bool = True  # False: attention takes no positional embedding at all
    tie_embeddings: bool = False  # the head is the embedding transposed; no `lm_head`
    # a layer pattern: layer i mixes tokens by attention where
    # i % attn_layer_period == attn_layer_offset and by a selective state-space
    # recurrence (`_ssm_half`) otherwise.  Period 0: every layer attends.  Each
    # kind's parameters and cache rows are stacked on a leading axis of their own
    # (`blocks`, `ssm_blocks`), and the layer loop (`_scan_layers`) scans each
    # maximal run of one kind.  One device only: a larger mesh raises.
    attn_layer_period: int = 0
    attn_layer_offset: int = 0
    # the state-space mixer's sizes: inner width ssm_expand * d_model, state
    # ssm_d_state a channel, a causal depthwise convolution over ssm_d_conv
    # positions, the step size projected through ssm_dt_rank
    ssm_d_state: int = 16
    ssm_d_conv: int = 4
    ssm_expand: int = 2
    ssm_dt_rank: int = 16
    ssm_conv_bias: bool = True
    # how the model generates.  block_length 0 or 1: one causal token a step
    # from the logits at a row's last position.  block_length B > 1: an answer
    # is made B positions at a time, aligned to absolute positions; attention
    # sees every earlier block and the whole of a position's own block, in both
    # directions; the logits at a position give the token at that position; a
    # block starts as mask_token_id and each pass fixes the masked positions
    # whose confidence passes confidence_threshold, or the B / denoise_steps
    # most confident where fewer do (llm/continuous.py `_choose_block`)
    block_length: int = 0
    mask_token_id: int = 0
    denoise_steps: int = 1
    confidence_threshold: float = 0.9
    # multi-head latent attention (kv_lora_rank > 0; `_project_latent`): a token
    # is cached as one latent row of kv_lora_rank values and one rotated key of
    # qk_rope_head_dim shared by every head, and each head's key (nope part)
    # and value are up-projections of the latent (`wkv_b`); the query goes
    # through a low rank of q_lora_rank.  A head's q and k are qk_nope_head_dim
    # + qk_rope_head_dim wide, its value v_head_dim; d_head and n_kv_heads are
    # not read.  0: keys and values are projected and cached a head (`_project_qkv`).
    kv_lora_rank: int = 0
    q_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # YaRN (rope_factor > 1): the rotary frequencies are the unscaled ones where
    # a dimension turns more than rope_beta_fast times over
    # rope_original_max_len positions, those divided by rope_factor where it
    # turns fewer than rope_beta_slow times, a linear blend between; cos and
    # sin are scaled by mscale(rope_mscale) / mscale(rope_mscale_all_dim) and
    # the softmax scale by mscale(rope_mscale_all_dim)^2, mscale(m) = 0.1 m
    # ln(rope_factor) + 1 (`_rope_freqs`, `attn_scale`)
    rope_factor: float = 1.0
    rope_original_max_len: int = 0
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 0.0
    # the FFN by layer: the first n_dense_layers layers of a mixture keep a
    # dense gated MLP of d_ff (kind "attn_dense", stacked apart as
    # `dense_blocks`); the others' experts are d_expert wide (0: d_ff)
    n_dense_layers: int = 0
    d_expert: int = 0
    # a gated MLP of n_shared_experts * d_expert that every token takes beside
    # its routed experts (scope `moe.shared`)
    n_shared_experts: int = 0
    # "softmax": the k largest of softmax(logits); "sigmoid": the k largest of
    # sigmoid(logits), divided by their sum (+1e-20) under moe_renormalize.
    # Either way times moe_routed_scale.
    moe_scoring: str = "softmax"
    moe_routed_scale: float = 1.0
    # (first, count): of every layer's n_experts this device holds experts
    # first .. first + count - 1 (its share under expert parallelism).  The
    # router stays n_experts wide and a token takes its k of all of them; what
    # the experts held here add for the tokens routed to them is computed, the
    # rest is left out (parallel/moe.py routed_ffn).  None: all are held.
    experts_held: Optional[Tuple[int, int]] = None
    # each layer's mixer, in the model's order: "attn" (every earlier position),
    # "attn_win" (the last attn_window positions: itself and attn_window - 1
    # before it), "ssm", "gmu" or "attn_cross" (below), each a block of two
    # halves with its FFN; or, in a stack of half layers, what the ONE half is:
    # "mamba2", "attn_alone" or "ffn" (HALF_KINDS).  None: the period and offset
    # above.  `layer_kinds` says how a mixer and a leading dense FFN make one kind.
    layer_mixers: Optional[Tuple[str, ...]] = None
    attn_window: int = 0
    # the slots a window layer's cache keeps of a row, written round (position p
    # at slot p mod attn_ring); 0: the decode kernel's key block that holds the
    # window (models/generate.py window_extent)
    attn_ring: int = 0
    # False: among window layers the full-attention layers take no positional
    # embedding at all (the window layers turn under `rotary` as ever)
    rotary_full: bool = True
    # each half's OUTPUT is normed, not its input: x + norm(attn(x)), then
    # x + norm(ffn(x)); `ln1` and `ln2` are those norms' weights
    norm_output: bool = False
    # LayerNorm (the mean subtracted, a weight and a bias) where an RMSNorm stands: every
    # block's two norms and the final one are made with a bias beside their weight
    # (`ln1_b`), and `_norm` norms by what it is handed; norm_eps is every norm's epsilon
    layer_norm: bool = False
    norm_eps: float = 1e-6
    # a bias on the attention projections (`bq`, `bk`, `bv`, `bo` beside the matrices)
    attn_bias: bool = False
    # whether a state-space block is made with an RMSNorm on its step size, B and C
    # (Jamba's three; plain Mamba-1 has none): `_ssm_mix` norms what its layer holds
    ssm_inner_norms: bool = True
    # differential attention (`_diff_heads`, `_diff_combine`): adjacent query heads are
    # a pair, a pair's result is softmax(q1 k1^T) V - lambda softmax(q2 k2^T) V over the
    # value V of both its cached heads, normed over V's width and scaled by 1 -
    # lambda_init; lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init with four
    # learned vectors a layer, lambda_init = 0.8 - 0.6 exp(-0.3 depth).  A pair's two
    # cached heads are cached as one of twice the width (`cached_heads`).
    diff_attn: bool = False
    # a layer may be ONE half alone (`layer_mixers` "mamba2", "attn_alone", "ffn": HALF_KINDS), x + f(norm(x)) with
    # the one norm; such a stack's mixers and FFNs stand in any order, two mixers in a row among them.
    # Mamba-2 (mixer "mamba2": `_mamba2_half`): ssm_n_heads heads of ssm_head_dim channels (their product is
    # the inner width, whatever ssm_expand says), one scalar decay a head, B and C of ssm_d_state shared by the
    # heads of each of ssm_n_groups groups, a causal convolution over x, B and C together, a state of
    # [heads, head_dim, ssm_d_state] a layer, a gated RMSNorm over the groups before the output projection, and
    # a prefill in chunks of ssm_chunk positions whose inside is matrix products (`_ssd_scan`)
    ssm_n_heads: int = 0
    ssm_head_dim: int = 0
    ssm_n_groups: int = 1
    ssm_chunk: int = 128
    # an expert's activation (parallel/moe.py ACTIVATIONS): "silu", or "relu2", relu(x)^2; the shared
    # expert's too, which is gated where the routed ones are and (act(x w_in)) w_out where they are not
    moe_act: str = "silu"
    d_shared: int = 0  # the shared expert's width; 0: n_shared_experts * d_expert
    # learned sparse attention (index_topk > 0; ops/sparse_attention.py): every attention layer carries an
    # indexer, index_n_heads query heads of index_head_dim against ONE key head that is cached
    # beside a token's keys and values, I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s]) in float32 with w a
    # projection of the layer's normed input times index_n_heads^-1/2 index_head_dim^-1/2; a query attends to
    # the index_topk earlier positions of largest I (equal scores to the lower position), one set for all its
    # heads, and to everything while its context is no longer than that.  Served on one device
    # (`_project_index`, models/generate.py); no mesh shards it and no block, window or latent layer takes it.
    index_topk: int = 0
    index_n_heads: int = 0
    index_head_dim: int = 0
    # Kimi Delta Attention (mixer "kda": `_kda_mixer`), a linear-attention layer: kda_n_heads heads whose state between
    # two tokens is a MATRIX S [kda_head_dim, kda_head_dim] in float32, updated by a rank-one delta rule under a decay of
    # its own for every key channel, S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T, o_t = S_t^T q_t;
    # q, k, v go through a causal depthwise convolution over ssm_d_conv positions and silu, q and k are L2-normed a head;
    # the decay g and the output's gate are projections through a low rank of kda_head_dim.  A prompt runs in chunks of
    # KDA_CHUNK positions whose inside is matrix products (`_kda_chunked`), a decode step is the update once a row
    # (`_kda_step`).  The state and the convolution's last inputs are a slot's "ssm" rows at their own shapes
    # (models/generate.py).  One device; one token a step.
    kda_n_heads: int = 0
    kda_head_dim: int = 0

    def __post_init__(self):
        if self.index_topk and (self.index_n_heads <= 0 or self.index_head_dim <= 0 or self.index_head_dim % 2):
            raise ValueError(
                f"index_topk={self.index_topk}: an indexer takes index_n_heads={self.index_n_heads} query heads of an "
                f"even index_head_dim={self.index_head_dim} against one key head")
        if self.index_topk and (self.latent or self.generates_blocks or self.diff_attn or self.layer_mixers is not None
                                or self.attn_layer_period or self.pp > 1 or self.sp > 1 or self.ep > 1):
            raise NotImplementedError(
                f"index_topk={self.index_topk}: learned sparse attention is every layer's of a stack of plain "
                "grouped-query attention blocks that yields one token a step, on one device: no latent or "
                "differential attention, blocks of positions, layer pattern, pipeline stages, sequence or expert axis")
        if self.generates_blocks and self.block_length % self.denoise_steps:
            raise ValueError(
                f"block_length {self.block_length} is not a multiple of denoise_steps "
                f"{self.denoise_steps}: every pass fixes the same number of positions"
            )
        if self.latent and not (self.qk_nope_head_dim and self.qk_rope_head_dim and self.v_head_dim):
            raise ValueError(
                f"kv_lora_rank={self.kv_lora_rank}: latent attention takes qk_nope_head_dim, qk_rope_head_dim and "
                "v_head_dim as well (q_lora_rank 0: the query is projected straight to its heads)"
            )
        if self.latent and self.generates_blocks:
            raise NotImplementedError("a pass over blocks of positions through a latent cache")
        if self.layer_mixers is not None:
            object.__setattr__(self, "layer_mixers", tuple(self.layer_mixers))  # hashable whatever carried it
            unknown = sorted(set(self.layer_mixers) - {"attn", "attn_win", "ssm", "gmu", "attn_cross", "kda", *HALF_KINDS})
            if unknown or len(self.layer_mixers) != self.n_layers or self.attn_layer_period:
                raise ValueError(
                    f"layer_mixers={self.layer_mixers}: one of attn, attn_win, ssm, gmu, attn_cross, kda, "
                    f"{', '.join(HALF_KINDS)} for each of the {self.n_layers} layers, and no attn_layer_period beside it"
                )
            if "kda" in self.layer_mixers:
                if self.kda_n_heads <= 0 or self.kda_head_dim <= 0:
                    raise ValueError(
                        f"a kda layer takes kda_n_heads={self.kda_n_heads} heads of kda_head_dim={self.kda_head_dim}")
                if (self.generates_blocks or self.half_layers or self.diff_attn or self.index_topk or self.norm_output
                        or {"ssm", "gmu", "attn_cross", "attn_win"} & set(self.layer_mixers)
                        or self.pp > 1 or self.sp > 1 or self.ep > 1):
                    raise NotImplementedError(
                        f"layer_mixers={self.layer_mixers}: kda layers stand beside attention layers (latent or not) in "
                        "a stack that yields one causal token a step on one device: no pass over blocks of positions "
                        "(a block's positions would enter the state in both directions), no half layers, other "
                        "recurrences, window or cross layers, differential or learned sparse attention, normed "
                        "outputs, pipeline stages, sequence or expert axis")
            if self.half_layers:
                if (set(self.layer_mixers) - set(HALF_KINDS) or self.latent or self.generates_blocks or self.diff_attn
                        or self.n_dense_layers or self.norm_output or self.pp > 1 or self.sp > 1):
                    raise NotImplementedError(
                        f"layer_mixers={self.layer_mixers}: a stack of half layers ({', '.join(HALF_KINDS)}) holds no "
                        "paired block, and runs without latent or differential attention, blocks of positions, "
                        "leading dense layers, normed outputs, pipeline stages or a sequence axis")
                if "mamba2" in self.layer_mixers and (
                        self.ssm_n_heads <= 0 or self.ssm_head_dim <= 0 or self.ssm_n_heads % self.ssm_n_groups
                        or self.d_inner % self.ssm_n_groups or self.ssm_chunk <= 0):
                    raise ValueError(
                        f"a mamba2 layer takes ssm_n_heads={self.ssm_n_heads} heads of ssm_head_dim={self.ssm_head_dim} "
                        f"in ssm_n_groups={self.ssm_n_groups} equal groups, and chunks of ssm_chunk={self.ssm_chunk}")
            tail = [i for i, m in enumerate(self.layer_mixers) if m in ("gmu", "attn_cross")]
            if tail:
                # the second half of a decoder-hybrid-decoder: it reads what ONE full layer cached
                # and what the state-space layer before it read out, and mixes no positions itself
                head = self.layer_mixers[:tail[0]]
                if (self.layer_mixers[tail[0]:].count("attn") + self.layer_mixers[tail[0]:].count("attn_win")
                        + self.layer_mixers[tail[0]:].count("ssm") or tail != list(range(tail[0], self.n_layers))):
                    raise NotImplementedError(
                        f"layer_mixers={self.layer_mixers}: the layers that read another layer's keys and values "
                        "or memory (attn_cross, gmu) are the stack's last, with no layer of another kind among them")
                if "attn_cross" in self.layer_mixers and (head.count("attn") != 1 or head[-1] != "attn"):
                    raise NotImplementedError("attn_cross layers read the one attn layer, which stands right below "
                                              f"the first of them: {head.count('attn')} stand before it")
                if "gmu" in self.layer_mixers and "ssm" not in head:
                    raise ValueError("a gmu layer gates the read-out of a state-space layer before it: none stands there")
                if self.latent or self.generates_blocks or self.n_experts:
                    raise NotImplementedError("gmu and attn_cross layers under latent attention, generation by "
                                              "blocks or a mixture of experts")
            window = "attn_win" in self.layer_mixers
            if window and not 0 < self.attn_window <= (self.attn_ring or self.attn_window):
                raise ValueError(f"window layers see attn_window={self.attn_window} positions, "
                                 f"within attn_ring={self.attn_ring} slots")
            if window and (self.latent or self.generates_blocks):
                raise NotImplementedError("a window layer under latent attention, or in a model that generates by blocks")
        if self.diff_attn and (self.latent or self.generates_blocks or self.n_dense_layers or self.n_heads % 2
                               or self.n_kv_heads % 2 or self.n_heads % self.n_kv_heads or self.rotary
                               or self.qk_norm or self.pp > 1 or self.sp > 1):
            raise NotImplementedError(
                "differential attention pairs adjacent heads of plain grouped-query attention on one device: even "
                "head counts, no latent attention, no blocks, no leading dense layers, no rotary embedding, no q/k norm")
        if self.n_dense_layers and (not self.n_experts or self.attn_layer_period
                                    or "ssm" in (self.layer_mixers or ())[:self.n_dense_layers]):
            raise NotImplementedError(
                f"n_dense_layers={self.n_dense_layers}: leading dense layers stand before the expert "
                "layers of a mixture whose layers all attend"
            )
        if self.experts_held is not None:
            first, count = self.experts_held
            object.__setattr__(self, "experts_held", (int(first), int(count)))  # hashable whatever carried it
            if not (0 <= first and count > 0 and first + count <= self.n_experts):
                raise ValueError(f"experts_held={self.experts_held} of n_experts={self.n_experts}")

    @property
    def generates_blocks(self) -> bool:
        return self.block_length > 1

    @property
    def latent(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def rope_dim(self) -> int:
        """The width the rotary embedding turns: a head's, or its rotary part."""
        return self.qk_rope_head_dim if self.latent else self.d_head

    @property
    def attn_scale(self) -> float:
        """What the scores are multiplied by before the softmax."""
        d = self.qk_nope_head_dim + self.qk_rope_head_dim if self.latent else self.d_head
        m = _yarn_mscale(self.rope_factor, self.rope_mscale_all_dim)
        return d ** -0.5 * m * m

    @property
    def layer_kinds(self) -> Tuple[str, ...]:
        """Each layer's kind, in the model's order.  A kind is what a layer
        loop scans as one run and what is stacked on a leading axis of its
        own, parameters (`_INIT_KIND`) and cache rows (models/generate.py
        LAYER_STATE) alike.  It is the layer's mixer, "attn", "attn_win" (a
        window layer), "ssm", "gmu", "attn_cross" or a half layer's own
        ("mamba2", "attn_alone", "ffn": HALF_KINDS), from `layer_mixers` or,
        without one, from the period and offset; and a mixture's leading `n_dense_layers`, which keep
        a dense FFN, are a kind of their mixer's with "_dense" behind it:
        "attn_dense", "attn_win_dense".  The two properties are independent:
        the suffix decides the FFN's weights and nothing else, the mixer the
        core, the rotary embedding and the cache the layer writes (an
        "attn_win_dense" layer's rows lie in the window layers' stacks, before
        those of the "attn_win" layers that follow it)."""
        if self.layer_mixers is not None:
            mixers = self.layer_mixers
        elif self.attn_layer_period:
            mixers = tuple("attn" if i % self.attn_layer_period == self.attn_layer_offset else "ssm"
                           for i in range(self.n_layers))
        else:
            mixers = ("attn",) * self.n_layers
        return tuple(m + "_dense" if i < self.n_dense_layers else m for i, m in enumerate(mixers))

    @property
    def cached_heads(self) -> int:
        """The heads a token's keys (and values) are cached as, of `cached_width`
        each: under differential attention a pair's two heads lie side by side as
        one head of twice the width, which is the pair's value and meets a query
        padded with zeros where the other head's key lies (`_diff_heads`)."""
        return self.n_kv_heads // 2 if self.diff_attn else self.n_kv_heads

    @property
    def cached_width(self) -> int:
        return 2 * self.d_head if self.diff_attn else self.d_head

    @property
    def flat_heads(self) -> int:
        """The cached heads a slot of the key/value stacks holds as ROWS, where
        the stacks are kept flat, [n, B, T * KV, D] (models/generate.py
        init_cache); 0: they are [n, B, T, KV, D].  The decode kernel reads a
        stack as rows of D, a slot's heads one after the other; the chip tiles
        an array's last two axes by (8, 128), so [T, KV, D] is those rows as
        they lie only where KV is a multiple of 8, and anything else is copied
        whole into that shape at every layer of every step.  Differential
        attention caches 10 pairs at Phi-4-mini-flash's widths: its stacks are
        the rows themselves.  The switch is the configuration served with such
        a count, not the count: the other configurations' stacks keep the
        layout their programs were measured with (tests/test_program_text.py).
        A stack of half layers caches 2 heads at Nemotron-H's widths and is
        flat as well."""
        return self.cached_heads if self.diff_attn or self.half_layers else 0

    @property
    def carries(self) -> bool:
        """Whether a layer hands the next more than x (`Carried`): some layer
        reads another layer's keys and values, or its memory."""
        return any(m in ("gmu", "attn_cross") for m in self.layer_mixers or ())

    @property
    def shared_readers(self) -> int:
        """The layers that read the one stack of keys and values the cross
        layers share: they and the layer that writes it.  0: no layer shares."""
        cross = (self.layer_mixers or ()).count("attn_cross")
        return cross + 1 if cross else 0

    def lambda_inits(self, kind: str) -> Tuple[float, ...]:
        """Differential attention's lambda_init = 0.8 - 0.6 exp(-0.3 depth) of
        each layer of `kind`, in their order: depth is the layer's index in
        the model."""
        return tuple(0.8 - 0.6 * math.exp(-0.3 * i) for i, k in enumerate(self.layer_kinds) if k == kind)

    def rotates(self, kind: str) -> bool:
        """Whether a layer of `kind` turns its queries and keys."""
        return self.rotary and (self.rotary_full or is_window(kind))

    @property
    def half_layers(self) -> bool:
        """Whether the stack is made of half layers (HALF_KINDS)."""
        return any(m in HALF_KINDS for m in self.layer_mixers or ())

    @property
    def d_inner(self) -> int:
        """A state-space mixer's inner width: Mamba-2's heads x their width, Mamba-1's ssm_expand x d_model."""
        return self.ssm_n_heads * self.ssm_head_dim if self.ssm_n_heads else self.ssm_expand * self.d_model

    @property
    def conv_width(self) -> int:
        """The channels a recurrent mixer's convolution runs over, which its window in the cache keeps:
        Mamba-1's x; Mamba-2's x, B and C side by side; a kda layer's q, k and v side by side."""
        if self.kda_n_heads:
            return 3 * self.kda_n_heads * self.kda_head_dim
        return self.d_inner + (2 * self.ssm_n_groups * self.ssm_d_state if self.ssm_n_heads else 0)

    @property
    def layers_per_stage(self) -> int:
        if self.n_layers % self.pp != 0:
            raise ValueError(f"n_layers {self.n_layers} not divisible by pp {self.pp}")
        return self.n_layers // self.pp

    def resolved_attn(self) -> str:
        if self.attn_impl != "auto":
            return self.attn_impl
        return "ring" if self.sp > 1 else "dense"


# the kinds of layer that are one half alone, x + f(norm(x)) with one norm `ln1` (the FFN's: `ln2`, as `_ffn_half`
# reads it): a Mamba-2 mixer, attention, an FFN.  Each kind's weights are a stack of its own and the layer loop scans
# their periods ([mamba2, ffn] x n, [ffn, mamba2] x n) and single layers (`_layer_runs`), a sequence of them that
# repeats as one loop of loops (`_run_groups`).
HALF_KINDS = ("mamba2", "attn_alone", "ffn")
# the positions a chunk of a kda prefill (`_kda_chunked`: the family's kernels' chunk), and those of a sub-block of
# it: inside one the decays between two positions are taken pair by pair, between two of them through the later one's
# first position (`_kda_pairs`)
KDA_CHUNK = 64
KDA_SUB = 16


def is_window(kind: str) -> bool:
    """Whether a layer of `kind` attends to a window of the last positions."""
    return kind.startswith("attn_win")


def _yarn_mscale(factor: float, m: float) -> float:
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1.0 and m else 1.0


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def _init_norm(name: str, cfg: TransformerConfig, key=None):
    """A block's norm over d_model (or the final one): its weight, and under
    `cfg.layer_norm` a bias `<name>_b` drawn small from `key`."""
    out = {name: jnp.ones((cfg.d_model,), cfg.param_dtype)}
    if cfg.layer_norm:
        out[name + "_b"] = jax.random.normal(key, (cfg.d_model,), cfg.param_dtype) * 0.02
    return out


def _init_ffn(ks, cfg: TransformerConfig):
    """A block's second half from three keys: its norm and the dense SwiGLU
    matrices, or the experts behind their router."""
    e, f, pd = cfg.d_model, cfg.d_ff, cfg.param_dtype
    s = lambda fan_in: fan_in ** -0.5
    if cfg.n_experts:
        from ..parallel.moe import init_moe_params

        fx = cfg.d_expert or f
        out = {**_init_norm("ln2", cfg, jax.random.fold_in(ks[0], 1)),
               **init_moe_params(ks[0], e, fx, cfg.n_experts, pd, gated=cfg.moe_gated,
                                 held=cfg.experts_held and cfg.experts_held[1])}
        if cfg.n_shared_experts:
            fs, kg, ku, kd = cfg.d_shared or cfg.n_shared_experts * fx, *jax.random.split(ks[1], 3)
            if cfg.moe_gated:
                out.update(
                    shared_gate=jax.random.normal(kg, (e, fs), pd) * s(e),
                    shared_up=jax.random.normal(ku, (e, fs), pd) * s(e),
                    shared_down=jax.random.normal(kd, (fs, e), pd) * s(fs),
                )
            else:  # as the routed experts are: no gate
                out.update(shared_in=jax.random.normal(ku, (e, fs), pd) * s(e),
                           shared_out=jax.random.normal(kd, (fs, e), pd) * s(fs))
        return out
    return {
        **_init_norm("ln2", cfg, jax.random.fold_in(ks[0], 1)),
        "w_gate": jax.random.normal(ks[0], (e, f), pd) * s(e),
        "w_up": jax.random.normal(ks[1], (e, f), pd) * s(e),
        "w_down": jax.random.normal(ks[2], (f, e), pd) * s(f),
    }


def _init_block(key, cfg: TransformerConfig, keys_and_values: bool = True, ffn: bool = True):
    """An attention block; `keys_and_values` False: one that projects queries
    alone and reads another layer's keys and values (kind "attn_cross"); `ffn`
    False: the attention half alone (kind "attn_alone")."""
    e, h, kv, d = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    ks = jax.random.split(key, 7)
    s = lambda fan_in: fan_in ** -0.5
    pd = cfg.param_dtype
    if cfg.latent:
        rq, r, dn, dr, dv = (cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_nope_head_dim,
                             cfg.qk_rope_head_dim, cfg.v_head_dim)
        ka = jax.random.split(ks[0], 5)
        # the query through a low rank and its norm, or (q_lora_rank 0) straight to its heads
        query = {"wq_a": jax.random.normal(ka[0], (e, rq), pd) * s(e), "q_a_norm": jnp.ones((rq,), pd),
                 "wq_b": jax.random.normal(ka[1], (rq, h * (dn + dr)), pd) * s(rq)} if rq else {
                     "wq": jax.random.normal(ka[0], (e, h * (dn + dr)), pd) * s(e)}
        out = {
            **_init_norm("ln1", cfg, jax.random.fold_in(key, 1)),
            **query,
            "wkv_a": jax.random.normal(ka[2], (e, r + dr), pd) * s(e),
            "kv_a_norm": jnp.ones((r,), pd),
            "wkv_b": jax.random.normal(ka[3], (r, h * (dn + dv)), pd) * s(r),
            "wo": jax.random.normal(ka[4], (h * dv, e), pd) * s(h * dv),
        }
        out.update(_init_ffn(ks[4:], cfg))
        return out
    out = {
        **_init_norm("ln1", cfg, jax.random.fold_in(key, 1)),
        "wq": jax.random.normal(ks[0], (e, h * d), pd) * s(e),
        "wo": jax.random.normal(ks[3], (h * d, e), pd) * s(h * d),
    }
    if keys_and_values:
        out.update(wk=jax.random.normal(ks[1], (e, kv * d), pd) * s(e),
                   wv=jax.random.normal(ks[2], (e, kv * d), pd) * s(e))
    if cfg.attn_bias:
        widths = {"bq": h * d, "bk": kv * d, "bv": kv * d, "bo": e}
        out.update({b: jax.random.normal(jax.random.fold_in(key, 2 + i), (n,), pd) * 0.02
                    for i, (b, n) in enumerate(widths.items()) if "w" + b[1] in out})
    if cfg.diff_attn:
        # the four vectors lambda is made of, drawn N(0, 0.1), and the weight of the norm over a pair's result
        out.update({name: jax.random.normal(jax.random.fold_in(key, 6 + i), (d,), pd) * 0.1
                    for i, name in enumerate(("lq1", "lk1", "lq2", "lk2"))})
        out["subln"] = jnp.ones((2 * d,), pd)
    if cfg.qk_norm:
        per_head = cfg.qk_norm_per_head
        out.update({"q_norm": jnp.ones((d if per_head else h * d,), pd),
                    "k_norm": jnp.ones((d if per_head else kv * d,), pd)})
    if cfg.index_topk:
        # the indexer: its queries' and its one key's projections, the key's LayerNorm (weight and bias),
        # and the projection to a weight a head
        hi, di = cfg.index_n_heads, cfg.index_head_dim
        ki = [jax.random.fold_in(key, 16 + i) for i in range(4)]
        out.update(wq_idx=jax.random.normal(ki[0], (e, hi * di), pd) * s(e),
                   wk_idx=jax.random.normal(ki[1], (e, di), pd) * s(e),
                   k_idx_norm=jnp.ones((di,), pd), k_idx_norm_b=jax.random.normal(ki[2], (di,), pd) * 0.02,
                   w_idx=jax.random.normal(ki[3], (e, hi), pd) * s(e))
    if ffn:
        out.update(_init_ffn(ks[4:], cfg))
    return out


def _init_ssm_block(key, cfg: TransformerConfig):
    """A state-space block (Mamba-1, with Jamba's three inner norms under
    `cfg.ssm_inner_norms`), initialised
    as Mamba is: A = -(1..N) in every channel, the step size's bias the inverse
    softplus of a step log-uniform in [1e-3, 1e-1], D = 1."""
    e, c, n, r, kw = cfg.d_model, cfg.d_inner, cfg.ssm_d_state, cfg.ssm_dt_rank, cfg.ssm_d_conv
    ks = jax.random.split(key, 7)
    s = lambda fan_in: fan_in ** -0.5
    pd = cfg.param_dtype
    k_dt, k_bias = jax.random.split(ks[3])
    step = jnp.exp(jax.random.uniform(k_bias, (c,)) * (jnp.log(1e-1) - jnp.log(1e-3)) + jnp.log(1e-3))
    out = {
        **_init_norm("ln1", cfg, ks[6]),
        "ssm_in": jax.random.normal(ks[0], (e, 2 * c), pd) * s(e),
        "conv_w": jax.random.normal(ks[1], (kw, c), pd) * s(kw),
        "ssm_x": jax.random.normal(ks[2], (c, r + 2 * n), pd) * s(c),
        **({"dt_norm": jnp.ones((r,), pd), "b_norm": jnp.ones((n,), pd), "c_norm": jnp.ones((n,), pd)}
           if cfg.ssm_inner_norms else {}),
        "ssm_dt": jax.random.normal(k_dt, (r, c), pd) * s(r),
        "dt_bias": (step + jnp.log(-jnp.expm1(-step))).astype(pd),
        "a_log": jnp.broadcast_to(jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32)), (c, n)).astype(pd),
        "ssm_d": jnp.ones((c,), pd),
        "ssm_out": jax.random.normal(ks[4], (c, e), pd) * s(c),
    }
    if cfg.ssm_conv_bias:
        out["conv_b"] = jnp.zeros((c,), pd)
    out.update(_init_ffn(jax.random.split(ks[5], 3), cfg))
    return out


def _init_mamba2_block(key, cfg: TransformerConfig):
    """A Mamba-2 mixer alone (kind "mamba2"), initialised as Mamba-2 is: A = -(a
    number uniform in [1, 16]) a head, the step size's bias the inverse
    softplus of a step log-uniform in [1e-3, 1e-1], D = 1, the gated norm's
    weight 1.  `ssm_in` projects to [z (d_inner), x B C (conv_width), dt
    (heads)] side by side, then columns of zeros up to whole tiles of lanes
    (parallel/moe.py LANES: the chip lays [2688, 10304] out with 2688 innermost,
    a loop inside a loop takes the stack with its last axis innermost, and the
    compiled step then copied all 23 layers' matrices, 1.27 GB, at every call;
    at 10,368 the stored layout is the one every loop reads); the convolution's
    taps are stored [K, conv_width]."""
    from ..parallel.moe import LANES

    e, c, w, hh, kw = cfg.d_model, cfg.d_inner, cfg.conv_width, cfg.ssm_n_heads, cfg.ssm_d_conv
    ks = jax.random.split(key, 7)
    s = lambda fan_in: fan_in ** -0.5
    pd = cfg.param_dtype
    step = jnp.exp(jax.random.uniform(ks[3], (hh,)) * (jnp.log(1e-1) - jnp.log(1e-3)) + jnp.log(1e-3))
    out = {
        **_init_norm("ln1", cfg, ks[6]),
        "ssm_in": jnp.pad(jax.random.normal(ks[0], (e, c + w + hh), pd) * s(e), ((0, 0), (0, -(c + w + hh) % LANES))),
        "conv_w": jax.random.normal(ks[1], (kw, w), pd) * s(kw),
        "dt_bias": (step + jnp.log(-jnp.expm1(-step))).astype(pd),
        "a_log": jnp.log(jax.random.uniform(ks[2], (hh,), minval=1.0, maxval=16.0)).astype(pd),
        "ssm_d": jnp.ones((hh,), pd),
        "ssm_norm": jnp.ones((c,), pd),
        "ssm_out": jax.random.normal(ks[4], (c, e), pd) * s(c),
    }
    if cfg.ssm_conv_bias:
        out["conv_b"] = jax.random.normal(ks[5], (w,), pd) * 0.02
    return out


def _init_kda_block(key, cfg: TransformerConfig):
    """A Kimi Delta Attention block (kind "kda") and its FFN.  `kda_qkv` projects to [q | k | v], each kda_n_heads x
    kda_head_dim, the convolution's taps `kda_conv` are stored [K, 3 H D] over the three side by side (no bias); the
    decay's low-rank pair `kda_f1`, `kda_f2` with `dt_bias` a channel [H D] and `a_log` a head [H], drawn as Mamba-2's are
    (A uniform in [1, 16], the bias the inverse softplus of a step log-uniform in [1e-3, 1e-1]); `kda_b` to one beta a
    head; the output gate's pair `kda_g1`, `kda_g2`; `kda_norm` [D], one RMSNorm weight the heads share; `kda_out`."""
    e, hh, d, kw = cfg.d_model, cfg.kda_n_heads, cfg.kda_head_dim, cfg.ssm_d_conv
    c, r = hh * d, d  # the two low-rank projections' rank is the head's width
    ks = jax.random.split(key, 12)
    s = lambda fan_in: fan_in ** -0.5
    pd = cfg.param_dtype
    draw = lambda i, shape, fan_in: jax.random.normal(ks[i], shape, pd) * s(fan_in)
    step = jnp.exp(jax.random.uniform(ks[3], (c,)) * (jnp.log(1e-1) - jnp.log(1e-3)) + jnp.log(1e-3))
    return {
        **_init_norm("ln1", cfg, ks[0]),
        "kda_qkv": draw(1, (e, 3 * c), e),
        "kda_conv": draw(2, (kw, 3 * c), kw),
        "kda_f1": draw(4, (e, r), e), "kda_f2": draw(5, (r, c), r),
        "dt_bias": (step + jnp.log(-jnp.expm1(-step))).astype(pd),
        "a_log": jnp.log(jax.random.uniform(ks[6], (hh,), minval=1.0, maxval=16.0)).astype(pd),
        "kda_b": draw(7, (e, hh), e),
        "kda_g1": draw(8, (e, r), e), "kda_g2": draw(9, (r, c), r),
        "kda_norm": jnp.ones((d,), pd),
        "kda_out": draw(10, (c, e), c),
        **_init_ffn(jax.random.split(ks[11], 3), cfg),
    }


def _without_experts(init):
    """`init` of a mixture's leading layer, which keeps the dense FFN of d_ff."""
    return lambda key, cfg: init(key, dataclasses.replace(cfg, n_experts=0, n_dense_layers=0, experts_held=None))


def _init_ffn_block(key, cfg: TransformerConfig):
    """An FFN alone (kind "ffn"): `_init_ffn`'s norm `ln2` and its dense
    matrices or experts."""
    return _init_ffn(jax.random.split(key, 3), cfg)


def _init_gmu_block(key, cfg: TransformerConfig):
    """A gated memory unit's block: its norm, the gate's projection up to the
    memory's width and the projection back down."""
    e, c, pd = cfg.d_model, cfg.d_inner, cfg.param_dtype
    ks = jax.random.split(key, 4)
    return {
        **_init_norm("ln1", cfg, ks[3]),
        "gmu_in": jax.random.normal(ks[0], (e, c), pd) * e ** -0.5,
        "gmu_out": jax.random.normal(ks[1], (c, e), pd) * c ** -0.5,
        **_init_ffn(jax.random.split(ks[2], 3), cfg),
    }


_INIT_KIND = {"attn": ("blocks", _init_block), "ssm": ("ssm_blocks", _init_ssm_block),
              "attn_dense": ("dense_blocks", _without_experts(_init_block)),
              "attn_win": ("win_blocks", _init_block), "attn_win_dense": ("win_dense_blocks", _without_experts(_init_block)),
              "kda": ("kda_blocks", _init_kda_block), "kda_dense": ("kda_dense_blocks", _without_experts(_init_kda_block)),
              "gmu": ("gmu_blocks", _init_gmu_block),
              "attn_cross": ("cross_blocks", functools.partial(_init_block, keys_and_values=False)),
              "mamba2": ("mamba2_blocks", _init_mamba2_block),
              "attn_alone": ("alone_blocks", functools.partial(_init_block, ffn=False)),
              "ffn": ("ffn_blocks", _init_ffn_block)}


def init_params(key, cfg: TransformerConfig) -> Dict[str, Any]:
    """`blocks`: the attention layers' parameters stacked [n, ...]; `ssm_blocks`:
    the state-space layers', where the pattern has any; `dense_blocks`: a
    mixture's leading dense layers'; `win_blocks`, `win_dense_blocks`: the
    window layers' of either FFN; `gmu_blocks`, `cross_blocks`: the gated memory
    units' and the cross layers'; `kda_blocks`, `kda_dense_blocks`: the kda layers' of either FFN;
    `mamba2_blocks`, `alone_blocks`, `ffn_blocks`: the half layers' (`_INIT_KIND`: a stack a kind).  Layer i's
    key is the i-th of one split whatever its kind."""
    k_embed, k_blocks, k_head = jax.random.split(key, 3)
    block_keys = jax.random.split(k_blocks, cfg.n_layers)
    kinds = cfg.layer_kinds
    out = {
        "embed": jax.random.normal(k_embed, (cfg.vocab_size, cfg.d_model), cfg.param_dtype)
        * 0.02,
    }
    for kind in dict.fromkeys(kinds):
        name, init = _INIT_KIND[kind]
        keys = block_keys if len(set(kinds)) == 1 else block_keys[
            jnp.asarray([i for i, k in enumerate(kinds) if k == kind])]
        out[name] = jax.vmap(lambda k: init(k, cfg))(keys)
    if cfg.pp > 1:
        # restack [L, ...] -> [pp, L/pp, ...] for stage sharding
        out["blocks"] = jax.tree_util.tree_map(
            lambda x: x.reshape(cfg.pp, cfg.layers_per_stage, *x.shape[1:]), out["blocks"]
        )
    out.update(_init_norm("ln_f", cfg, jax.random.fold_in(k_head, 1)))
    if not cfg.tie_embeddings:
        out["lm_head"] = (
            jax.random.normal(k_head, (cfg.d_model, cfg.vocab_size), cfg.param_dtype)
            * cfg.d_model ** -0.5
        )
    return out


def param_specs(cfg: TransformerConfig) -> Dict[str, Any]:
    """PartitionSpecs: tp shards head/ff/vocab dims, fsdp shards the other
    matmul dim, pp shards the stage axis of stacked blocks."""
    _one_device_only(cfg, "a sharding of its parameters")
    lead = ("pp", None) if cfg.pp > 1 else (None,)

    def blk(*spec):
        return P(*lead, *spec)

    blocks: Dict[str, Any] = {
        "ln1": blk(None),
        "wq": blk("fsdp", "tp"),
        "wk": blk("fsdp", "tp"),
        "wv": blk("fsdp", "tp"),
        "wo": blk("tp", "fsdp"),
        "ln2": blk(None),
    }
    if cfg.qk_norm:
        blocks.update({"q_norm": blk(None), "k_norm": blk(None)})
    if cfg.n_experts:
        # experts sharded over 'ep'; each expert's matmuls tp-sharded
        into, out_of = blk("ep", "fsdp", "tp"), blk("ep", "tp", "fsdp")
        blocks["router"] = blk(None, None)
        if cfg.moe_gated:
            blocks.update({"w_gate": into, "w_up": into, "w_down": out_of})
        else:
            blocks.update({"w_in": into, "w_out": out_of})
    else:
        blocks.update(
            {
                "w_gate": blk("fsdp", "tp"),
                "w_up": blk("fsdp", "tp"),
                "w_down": blk("tp", "fsdp"),
            }
        )
    specs = {"embed": P("fsdp", "tp"), "blocks": blocks, "ln_f": P(None)}
    if not cfg.tie_embeddings:
        specs["lm_head"] = P("fsdp", "tp")
    return specs


def _one_device_only(cfg: TransformerConfig, what: str) -> None:
    """A layer pattern's training loop over a mesh is not written (ROADMAP M1):
    say which kind of layer stands in the way instead of sharding it wrongly."""
    kinds = (sorted(set(cfg.layer_kinds) - {"attn"}) + ["latent attention"] * cfg.latent
             + ["learned sparse attention (index_topk)"] * bool(cfg.index_topk))
    if kinds:
        raise NotImplementedError(
            f"{what}: layers of kind {kinds} (attn_layer_period={cfg.attn_layer_period}, "
            f"n_dense_layers={cfg.n_dense_layers}, layer_mixers={cfg.layer_mixers}) run on one device only; a mesh with more than "
            "one device shards attention blocks that cache keys and values alone"
        )


def shard_params(params, cfg: TransformerConfig, mesh):
    """Annotate params with their mesh shardings.  Skipped on a single-device
    mesh: NamedSharding-constrained inputs put XLA through the SPMD
    partitioner's layout constraints for zero benefit (10x slower per train
    step, 167 ms -> 1627 ms at the bench config, on a runtime that no longer
    exists; not re-measured)."""
    if mesh is None or mesh.size <= 1:
        return params
    specs = param_specs(cfg)
    return jax.tree_util.tree_map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), params, specs,
        is_leaf=lambda x: isinstance(x, P),
    )


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _rms_norm(x, w, eps=1e-6):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x * lax.rsqrt(var + eps).astype(x.dtype)) * w.astype(x.dtype)


def _norm(x, bp, name: str, cfg: TransformerConfig):
    """x normed over its last axis by the norm `name` of `bp`, whatever that
    is: an RMSNorm where `bp` holds a weight `name`, a LayerNorm in float32
    ((x - mean) / sqrt(var + eps) * w + b) where it holds a bias `<name>_b`
    beside it, and x itself where it holds neither (a state-space layer made
    without the inner norms).  The epsilon is the configuration's.  Every
    block's norms, the inner ones and the final one go through here, so no
    call site chooses."""
    if name not in bp:
        return x
    if name + "_b" not in bp:
        return _rms_norm(x, bp[name], cfg.norm_eps)
    xf = x.astype(jnp.float32)
    xf = xf - jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    out = xf * lax.rsqrt(var + cfg.norm_eps) * bp[name].astype(jnp.float32) + bp[name + "_b"].astype(jnp.float32)
    return out.astype(x.dtype)


def _rope_freqs(cfg: TransformerConfig, d: Optional[int] = None):
    """(the rotary frequencies [d / 2], d = `cfg.rope_dim` unless given; what cos and sin are scaled by).
    With YaRN (`cfg.rope_factor` > 1; the configuration says what each size
    is) dimension i keeps its frequency where it turns more than beta_fast
    times over the original length, is interpolated (/ factor) where it turns
    fewer than beta_slow times, and is blended linearly between the two
    correction dimensions."""
    d = d or cfg.rope_dim
    freqs = cfg.rope_theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    if cfg.rope_factor == 1.0:
        return freqs, 1.0
    # the dimension that turns `turns` times over the original length
    at = lambda turns: d * math.log(cfg.rope_original_max_len / (turns * 2 * math.pi)) / (
        2 * math.log(cfg.rope_theta))
    low, high = max(math.floor(at(cfg.rope_beta_fast)), 0), min(math.ceil(at(cfg.rope_beta_slow)), d - 1)
    ramp = jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - low) / max(high - low, 1e-3), 0.0, 1.0)
    freqs = freqs / cfg.rope_factor * ramp + freqs * (1.0 - ramp)
    return freqs, (_yarn_mscale(cfg.rope_factor, cfg.rope_mscale)
                   / _yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim))


def _pair_swap(d: int, dtype):
    """S [d, d] with (x @ S)[2i] = -x[2i + 1] and (x @ S)[2i + 1] = x[2i]: every
    column holds one +-1, so the product is exact in any float type."""
    i = np.arange(d)
    s = np.zeros((d, d), np.float32)
    s[i ^ 1, i] = np.where(i % 2 == 0, -1.0, 1.0)
    return jnp.asarray(s, dtype)


@jax.custom_vjp
def _turn(x, cos, sin):
    """x [B, T, H, D] with every adjacent pair (x[2i], x[2i + 1]) turned by its
    angle: x * cos + (x @ S) * sin in float32 (S: `_pair_swap`), cos and sin
    [B|1, T, 1, D] holding a pair's value on both its lanes.  The same two
    products and one sum an element as x1 cos - x2 sin, x2 cos + x1 sin on the
    strided halves, and the same bits; what differs is how a lane's partner
    reaches it.  The halves `x[..., 0::2]`, `x[..., 1::2]` and the stack that
    puts them back are copies between layouts on the chip, the pad and scatter
    of their gradient too: the product with S leaves every lane where it lies,
    on the matrix unit, and the compiler makes the rest its epilogue, one pass
    that reads x and writes the result.  S's entries are +-1 and the product
    accumulates in float32, so it is exact for bf16; a float32 x asks for the
    exact passes.

    The gradient is stated, the turn back (cos, -sin) of the cotangent in the
    same float32 and rounded once, as the strided halves' gradient was: it is
    that gradient to the bit for bf16 (tests/test_rope.py).  Derived, the
    transpose of the product hands back a bf16 cotangent of its own, the
    x * cos branch another, and their sum is rounded a third time: a quarter
    of a bf16 gradient's elements then differ from the definition's.  (On the
    chip the derived one also widens the cotangent and copies it into the
    forward's layout before it meets S; one reading, 219.7 against 217.6 ms
    below, is no more than a hint.)

    Which form, chosen by microbenchmarks on a v5e (PERF.md section 6, PR 49:
    the least of 10 calls, one seed, one device; what the change is worth is
    read in the cells' windows, there too).  The strided halves / jnp.roll by
    +1 and -1 with a select / this product with its gradient derived / as here
    / a Pallas kernel of pltpu.roll and a select.  Inside the programs:
    Mistral-7B's decode step of 8 layers at 32 slots 5.320 / 5.244 / 5.232 /
    5.234 / 5.227 ms; a prefill of 8,192 rows through 2 layers of 64 + 8 heads
    70.5 / 72.5 / 62.5 / 62.5 / 62.1 ms; the train step of 2 layers over 2 x
    4,096 rows, forward, recomputed and backward, 227.4 / 235.4 / 219.7 / 217.6
    / 217.9 ms.  Alone, q and k of the train step's shape, forward and with
    the gradient: 1.01 and 2.92 / 1.90 and 4.62 / 0.23 and 0.32 / 0.23 and 0.32
    / 0.49 and 0.72 ms (jnp.roll keeps four float32 copies of q).  One form
    serves every shape; the kernel gains 0.2 ms a layer at 8,192 rows and
    nothing elsewhere, and would be a second path wherever there is no TPU.
    cos and sin are made again at every layer of the decode step's loop: made
    once before it, 5.227 ms (0.9 us a layer), so the call stays where it is."""
    swap = jnp.matmul(x, _pair_swap(x.shape[-1], x.dtype), preferred_element_type=jnp.float32,
                      precision=lax.Precision.HIGHEST if x.dtype == jnp.float32 else None)
    return (x.astype(jnp.float32) * cos + swap * sin).astype(x.dtype)


_turn.defvjp(lambda x, cos, sin: (_turn(x, cos, sin), (cos, sin)),
             lambda tables, g: (_turn(g, tables[0], -tables[1]), None, None))


def _rope(q, k, positions, cfg: TransformerConfig, d: Optional[int] = None):
    """Rotary embeddings; q,k: [B, T, H, D]. positions: [T] global positions,
    or [B, T] per-row positions (left-padded prompts shift each row's real
    tokens to start at position 0).  d: the width turned where it is not
    `cfg.rope_dim` (the indexer's heads: `_project_index`)."""
    freqs, magnitude = _rope_freqs(cfg, d)
    angles = positions[..., None].astype(jnp.float32) * jnp.repeat(freqs, 2)  # [..., T, D]
    if angles.ndim == 2:
        angles = angles[None]  # broadcast over batch
    cos = jnp.cos(angles)[:, :, None, :]  # [B|1, T, 1, D]
    sin = jnp.sin(angles)[:, :, None, :]
    if magnitude != 1.0:
        cos, sin = cos * magnitude, sin * magnitude
    return _turn(q, cos, sin), _turn(k, cos, sin)


# The rows (batch x positions) up to which a projection that is reshaped to heads is kept apart
# from its reshape (`_project_heads`): the largest measured size at which that gains.  On a v5e,
# apart against folded (PERF.md section 6, PR 44): a prefill of 64 / 256 / 512 rows at Mistral-7B's
# widths 10.97 / 12.44 / 22.97 ms against 11.94 / 14.14 / 24.55, of 1,024 / 2,048 / 4,096 / 8,192
# rows at K-EXAONE's 50.9 / 93.6 / 185.3 / 395.7 against 54.9 / 99.9 / 190.4 / 396.3: what a
# layer's copied weights cost is fixed and the product grows with the rows, so at 8,192 nothing is
# gained (and with it that cell's peak of device memory read 0.58 GB higher), and the train step of
# `train-fsdp4` (32,768 rows, forward and backward) is 1.0% slower apart (22,964 tokens/s
# against 23,194).  Above the boundary a product keeps the program the compiler makes it.
STORED_PRODUCT_ROWS = 4096


def _project_heads(y, w):
    """y [B, T, E] @ w [E, F] -> [B, T, F], for a product whose result is viewed
    as heads [B, T, H, D].  Up to STORED_PRODUCT_ROWS rows the product is kept a
    value of its own, two-dimensional as the weight is stored, and the reshape
    works on the result.  Given the reshape to fold into the product, the chip's
    compiler makes the heads an output axis and wants the weight with E
    innermost, the stored [E, F] transposed: in a decode step a layer's matrix
    sliced out of its stack into fast memory, copied into the other layout and
    only then multiplied, three serial operations a projection at every layer of
    every step, 2.2 ms of an 11.3 ms step at Mistral-7B's widths.  Kept apart,
    the product is one fusion that takes the weight's stack and the layer's index
    and reads the matrix once, where it lies, as the FFN's and wo do.

    The `optimization_barrier` is a workaround for that fold in this compiler,
    not part of the arithmetic: the plain product on [B * T, E], an explicit
    einsum to heads and the transposed product all compile to the copies.  Its
    guard is tests/test_chip_compile.py
    test_decode_step_multiplies_by_the_projections_where_they_are_stored, which
    compiles the decode step for a v5e: if a later compiler no longer folds the
    reshape in, that test passes without the barrier and it can go.  The rows
    are all the code sees, so a forward or a train step of at most
    STORED_PRODUCT_ROWS rows takes the barrier too (its transpose is a barrier
    on the cotangent): meant so, a layer's weights cost such a pass what they
    cost a prefill of as many rows; not measured on the chip."""
    out = y @ w.astype(y.dtype)
    if y.shape[0] * y.shape[1] <= STORED_PRODUCT_ROWS:
        out = lax.optimization_barrier(out)
    return out


def _project_qkv(bp, y, cfg: TransformerConfig, y_kv=None):
    """q [B, T, H, D], k and v [B, T, KV, D] of one block from its normed
    input y [B, T, E]: the three projections (each with its bias where the
    block holds one) and, with `cfg.qk_norm`, the
    RMSNorm of q and k: over the whole projected vector, or over each head's
    own d_head (`cfg.qk_norm_per_head`).  The one place every forward, prefill
    and decode block projects.  The reshape to heads stays outside the product
    (`_project_heads`): folded into it, a decode step copies wq, wk and wv into
    another layout at every layer instead of reading them where they are stored.
    y_kv [B, T_kv, E]: the rows k and v are made of where they are not the
    queries' (a prefill that asks at its last position alone).  A block that
    holds no wk (a cross layer) gives k = v = None."""
    d = cfg.d_head

    def project(src, w, heads, norm=None):
        out = _project_heads(src, bp[w])
        if "b" + w[1] in bp:
            out = out + bp["b" + w[1]].astype(out.dtype)
        if norm is not None and cfg.qk_norm and not cfg.qk_norm_per_head:
            out = _rms_norm(out, bp[norm])
        out = out.reshape(*src.shape[:2], heads, d)
        if norm is not None and cfg.qk_norm and cfg.qk_norm_per_head:
            out = _rms_norm(out, bp[norm])
        return out

    q = project(y, "wq", cfg.n_heads, "q_norm")
    if "wk" not in bp:
        return q, None, None
    y_kv = y if y_kv is None else y_kv
    return q, project(y_kv, "wk", cfg.n_kv_heads, "k_norm"), project(y_kv, "wv", cfg.n_kv_heads)


def _project_index(bp, y, cfg: TransformerConfig, positions, y_kv=None):
    """The indexer's part of a block's normed input y [B, T, E] (`cfg.index_topk`):
    (qI [B, T, HI, DI], kI [B, T_kv, DI], w [B, T, HI] float32).  qI = y wq_idx
    by heads; kI = LayerNorm(y wk_idx), one head, what a token is cached as
    beside its keys and values; both turned over all DI dimensions at the
    model's theta; w = y w_idx times HI^-1/2 DI^-1/2.  The score of a query
    against a key is sum_j w[j] relu(qI[j] . kI) (ops/sparse_attention.py)."""
    b, t, _ = y.shape
    hi, di = cfg.index_n_heads, cfg.index_head_dim
    y_kv = y if y_kv is None else y_kv
    qi = _project_heads(y, bp["wq_idx"]).reshape(b, t, hi, di)
    ki = _norm(y_kv @ bp["wk_idx"].astype(y.dtype), bp, "k_idx_norm", cfg)
    if cfg.rotary:
        qi, ki = _rope(qi, ki[:, :, None, :], positions, cfg, di)
        ki = ki[:, :, 0]
    w = (y @ bp["w_idx"].astype(y.dtype)).astype(jnp.float32) * (hi ** -0.5 * di ** -0.5)
    return qi, ki, w


def _diff_heads(q, k, v):
    """Differential attention's q, k, v as a plain grouped-query core takes
    them.  A pair's two cached heads side by side are one cached head of twice
    the width, [B, T, KV / 2, 2 D] (a view of the rows as projected): as a value
    it is the pair's V, and as a key it gives q1 . k1 to a query padded with
    zeros over its second half and q2 . k2 to one padded over its first.  So
    q [B, T, H, D] becomes [B, T, H, 2 D], head h in half h mod 2, and the core's
    result at head h is softmax(q_h k_(h mod 2)^T) V: both maps of every pair,
    each over the whole V, from kernels that know nothing of pairs.  k, v None
    (a cross layer's) stay None."""
    b, t, h, d = q.shape
    half = jnp.arange(h)[:, None] % 2 == jnp.arange(2)[None, :]  # [H, 2]
    q = jnp.where(half[:, :, None], q[:, :, :, None, :], 0).reshape(b, t, h, 2 * d)
    pair = lambda a: None if a is None else a.reshape(*a.shape[:2], a.shape[2] // 2, 2 * d)
    return q, pair(k), pair(v)


def _diff_combine(bp, attn, lambda_init, cfg: TransformerConfig):
    """attn [B, T, H, 2 D] float32, head 2p the pair's first map over V and
    head 2p + 1 its second -> [B, T, H / 2, 2 D] in cfg.dtype: A1 V - lambda A2 V,
    an RMSNorm over the pair's 2 D (weight `subln`), times 1 - lambda_init.
    All of it in float32: the two results are close, and what is left of them
    is normed."""
    f = jnp.float32
    b, t, h, w = attn.shape
    dot = lambda a, c: jnp.sum(bp[a].astype(f) * bp[c].astype(f))
    lam = jnp.exp(dot("lq1", "lk1")) - jnp.exp(dot("lq2", "lk2")) + lambda_init
    maps = attn.astype(f).reshape(b, t, h // 2, 2, w)
    out = maps[:, :, :, 0] - lam * maps[:, :, :, 1]
    out = _norm(out, bp, "subln", cfg) * (1.0 - lambda_init)
    return out.astype(cfg.dtype)


def _project_latent(bp, y, cfg: TransformerConfig, positions, rotate: bool = True):
    """Latent attention's way to a block's q, k, v from its normed input y
    [B, T, E]: q [B, T, H, nope + rope] through the low rank c_q =
    norm(y wq_a), or straight through `wq` where the block holds no low rank
    (`cfg.q_lora_rank` 0), its rotary part rotated, or with `rotate` False carried
    as it is (the query's and the key's alike: a layer without a positional
    embedding, whose "rope" dimensions are 64 more that every head shares); and
    what a token is cached as, the
    rotated key k_rope [B, T, rope] that every head shares and the latent
    c_kv = norm((y wkv_a)[:kv_lora_rank]) [B, T, kv_lora_rank], of which each
    head's keys and values are up-projections (`_latent_expand`, or absorbed
    into the query and the output: models/generate.py).  Returns
    (q, k_rope, c_kv): what `_attention_half`'s core is given as q, k, v."""
    b, t, _ = y.shape
    dt = y.dtype
    h, dn, dr, r = cfg.n_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.kv_lora_rank
    with jax.named_scope("attn.mla.q"):
        if "wq" in bp:
            q = _project_heads(y, bp["wq"]).reshape(b, t, h, dn + dr)
        else:
            c_q = _rms_norm(y @ bp["wq_a"].astype(dt), bp["q_a_norm"], cfg.norm_eps)
            q = _project_heads(c_q, bp["wq_b"]).reshape(b, t, h, dn + dr)
    with jax.named_scope("attn.mla.kv"):
        kv = y @ bp["wkv_a"].astype(dt)
        c_kv = _rms_norm(kv[..., :r], bp["kv_a_norm"], cfg.norm_eps)
    if not rotate:
        return q, kv[..., r:], c_kv
    with jax.named_scope("attn.rope"):
        q_rope, k_rope = _rope(q[..., dn:], kv[:, :, None, r:], positions, cfg)
        q = jnp.concatenate([q[..., :dn], q_rope], axis=-1)
    return q, k_rope[:, :, 0], c_kv


def _latent_up(bp, cfg: TransformerConfig, dt):
    """`wkv_b` as (W_UK [R, H, nope], W_UV [R, H, v]): each head's key and value
    up-projections of the latent."""
    w = bp["wkv_b"].astype(dt).reshape(cfg.kv_lora_rank, cfg.n_heads, -1)
    return w[..., :cfg.qk_nope_head_dim], w[..., cfg.qk_nope_head_dim:]


def _latent_expand(bp, k_rope, c_kv, cfg: TransformerConfig):
    """Every head's own key and value of whole sequences, for a core that
    attends head by head (training, a prompt's prefill): k [B, T, H, nope +
    rope] = [c_kv W_UK | k_rope], v [B, T, H, v] = c_kv W_UV."""
    b, t, _ = c_kv.shape
    with jax.named_scope("attn.mla.expand"):
        w_uk, w_uv = _latent_up(bp, cfg, c_kv.dtype)
        k_nope = jnp.einsum("btr,rhn->bthn", c_kv, w_uk)
        k_rope = jnp.broadcast_to(k_rope[:, :, None, :], (b, t, cfg.n_heads, k_rope.shape[-1]))
        return jnp.concatenate([k_nope, k_rope], axis=-1), jnp.einsum("btr,rhv->bthv", c_kv, w_uv)


def _moe(bp, y, cfg: TransformerConfig, live=None, experts=None):
    """The dropless routed expert FFN (parallel/moe.py routed_ffn) of one
    block over y [B, T, E]; `live` [B, T] marks the rows that take experts.
    `experts`: (every layer's experts' matrices, unsliced; this layer's index)
    from a scan that keeps them out of its slices (models/generate.py
    _scan_blocks); None: `bp` holds this layer's own, a stack of one.  Returns
    (out [B, T, E], aux loss, experts touched: with `cfg.experts_held` [3], the
    held experts given a row, the assignments that fell on them, and 1 where
    they went through the compact buffer)."""
    from ..parallel.moe import EXPERT_MATRICES, routed_ffn

    b, t, e = y.shape
    stack, layer = experts or ({k: bp[k][None] for k in EXPERT_MATRICES if k in bp}, 0)
    r = routed_ffn(
        y.reshape(b * t, e), bp["router"], stack, layer, k=cfg.n_experts_per_tok,
        renormalize=cfg.moe_renormalize, live=None if live is None else live.reshape(b * t),
        scoring=cfg.moe_scoring, scale=cfg.moe_routed_scale, held=cfg.experts_held, act=cfg.moe_act,
    )
    touched = r.experts_touched
    if cfg.experts_held is not None:
        # beside the held experts given a row, the assignments that fell on them and which branch took them
        touched = jnp.stack([touched, r.assignments, r.compact])
    return r.out.reshape(b, t, e), r.aux_loss, touched


# [B, T, H, D] as the dense block leaves it: batch over the data axes, heads
# over tp (wq/wk/wv shard their head columns on tp)
_ATTN_SPEC = P(("dp", "fsdp"), None, "tp", None)


def _per_shard(attn_fn, mesh, manual_axes=frozenset()):
    """Run attn_fn(q, k, v) on each device's own batch rows and heads.  GSPMD
    cannot partition a Mosaic kernel ("wrap the call in a shard_map": any
    axis left to it, even of size 1, is refused), and attention needs no
    collective across batch or heads, so on a multi-device mesh the call is
    manual over every axis the caller (`manual_axes`: pp/sp/ep) is not
    already manual over.  A nested shard_map takes the mesh of its context."""
    if mesh is None or mesh.size == 1:
        return attn_fn
    return jax.shard_map(
        attn_fn,
        mesh=None if manual_axes else mesh,
        in_specs=(_ATTN_SPEC,) * 3,
        out_specs=_ATTN_SPEC,
        axis_names=frozenset(mesh.axis_names) - manual_axes,
        check_vma=False,
    )


def _attention(q, k, v, cfg: TransformerConfig, mesh, manual_axes, window: int = 0):
    """The training core: [B, T, H, D] each.  Under differential attention the
    result is float32 (`_diff_combine` subtracts before it rounds)."""
    impl = cfg.resolved_attn()
    over_sp = impl in ("ring", "ulysses") and "sp" in manual_axes
    if over_sp and (cfg.generates_blocks or window):
        raise NotImplementedError(
            f"the block mask (block_length={cfg.block_length}) or a window ({window}) with {impl} attention")
    if over_sp:
        fn = functools.partial(ring_attention if impl == "ring" else ulysses_attention, axis_name="sp", causal=True)
    else:  # dense: the dispatcher picks by backend
        fn = functools.partial(dense_attention, causal=True, block=cfg.block_length, scale=cfg.attn_scale,
                               window=window, out_dtype=jnp.float32 if cfg.diff_attn else None)
    return _per_shard(fn, mesh, manual_axes)(q, k, v)


def _sparse_attention(q, k, v, index, cfg: TransformerConfig, pad=None, chosen: bool = False):
    """Learned sparse attention over whole sequences (a forward, a prompt's
    prefill): q [B, T, H, D]; k, v [B, T, KV, D] as projected, not repeated;
    index: `_project_index`'s; pad [B]: left-pad counts or None.  The indexer's
    scores of every causal pair, each query's `cfg.index_topk` best as a mask,
    and attention under the mask, each under its own scope (`attn.indexer`,
    `attn.select`, `attn.sparse_core`; ops/sparse_attention.py).  A sequence no
    longer than `cfg.index_topk` leaves no position out: it goes the dense
    path, to the bit.  chosen: hand back (the result, the mask int8 [B, T, T],
    None on the dense path) for a test or a check to read the selection."""
    from ..ops import sparse_attention as sparse

    t = q.shape[1]
    if t <= cfg.index_topk:
        with jax.named_scope("attn.sparse_core"):
            out = dense_attention(q, _gqa_repeat(k, cfg), _gqa_repeat(v, cfg), causal=True, pad=pad,
                                  scale=cfg.attn_scale)
            return (out, None) if chosen else out
    (q, k, v, qi, ki, w), pad, extra = sparse.left_pad_to_tile([q, k, v, *index], pad)
    with jax.named_scope("attn.indexer"):
        scores = sparse.index_scores(qi, ki, w, first=pad)
    with jax.named_scope("attn.select"):
        mask = sparse.select_mask(scores, *sparse.causal_spans(pad, t + extra), cfg.index_topk)
    with jax.named_scope("attn.sparse_core"):
        out = sparse.masked_flash(q, k, v, mask, cfg.attn_scale, first=pad)[:, extra:]
    return (out, mask[:, extra:, extra:]) if chosen else out


def _gqa_repeat(x, cfg: TransformerConfig):
    """k or v [B, T, KV, D] repeated to the query's H heads, for a core that
    wants them alike (the flash kernel, ring, Ulysses)."""
    if x.shape[2] != cfg.n_heads:
        x = jnp.repeat(x, cfg.n_heads // x.shape[2], axis=2)
    return x


def _attention_half(bp, x, cfg: TransformerConfig, positions, core, kind: str = "attn", layer=None, kv_of=None):
    """A block's first half, for training, prefill and decode alike:
    x + wo(core(rope(qkv(norm(x))))), the rotary embedding where the
    configuration has one for a layer of `kind` (`cfg.rotates`); under
    `cfg.norm_output` x + norm(wo(core(rope(qkv(x))))).  x: [B, T, E]; positions: [T] or [B, T], what `_rope`
    takes; `core(q, k, v) -> (attn [B, T, H, D], extra)` attends
    under its own scopes (`attn.core`, and `attn.cache` where it keeps one) and
    hands back what its caller keeps of k and v.  Returns (x, extra).
    Latent attention (`cfg.latent`) makes them its own way: the core is given
    the query, the shared rotated key and the latent (`_project_latent`).
    Differential attention (`cfg.diff_attn`) gives the core `_diff_heads` of
    them and makes of its float32 result `_diff_combine` under scope
    `attn.diff`; `layer` is then the layer's number among its kind, by which
    its lambda_init is found.  kv_of [B, T_kv, E]: the rows whose keys and
    values the layer makes where they are more than x's (`_project_qkv`).  A
    cross layer's core is given k = v = None and brings another layer's.

    The scope names are the same in every layer and every program: a device
    trace sums a kind of work over the depth (forward, recomputation and
    backward carry the name; metadata only)."""
    b, t, _ = x.shape
    y, y_kv = x, kv_of
    if not cfg.norm_output:
        with jax.named_scope("norm"):
            y = _norm(x, bp, "ln1", cfg)
            if kv_of is not None:
                y_kv = _norm(kv_of, bp, "ln1", cfg)
    if cfg.latent:
        q, k, v = _project_latent(bp, y, cfg, positions, cfg.rotates(kind))
    else:
        with jax.named_scope("attn.qkv"):
            q, k, v = _project_qkv(bp, y, cfg, y_kv)
            if cfg.diff_attn:
                q, k, v = _diff_heads(q, k, v)
        if cfg.rotates(kind):
            with jax.named_scope("attn.rope"):
                q, k = _rope(q, k, positions, cfg)
    if cfg.index_topk:
        # the core scores, selects and attends under the selection: it is given the way to the indexer's part
        # beside q, k, v, and goes it under its own scope (`attn.indexer`)
        core = functools.partial(core, index=functools.partial(_project_index, bp, y, cfg, positions, y_kv))
    attn, extra = core(q, k, v)
    if cfg.diff_attn:
        with jax.named_scope("attn.diff"):
            attn = _diff_combine(bp, attn, jnp.asarray(cfg.lambda_inits(kind), jnp.float32)[layer], cfg)
    with jax.named_scope("attn.out"):
        out = attn.reshape(b, t, -1) @ bp["wo"].astype(x.dtype)
        if "bo" in bp:
            out = out + bp["bo"].astype(x.dtype)
    if cfg.norm_output:
        with jax.named_scope("norm"):
            out = _norm(out, bp, "ln1", cfg)
    return x + out, extra


# the precision of the recurrence: the step size, the decay exp(dt A), the state
# h between two tokens (in the cache too: models/generate.py init_cache) and the
# read-out.  Weights and every other activation are in cfg.dtype.
SSM_STATE_DTYPE = jnp.float32
SSM_CHUNK = 16  # positions a chunk of the prefill's scan: about sqrt(T) at chat lengths


def _selective_scan(dt, a, b, c, xc, h0):
    """h_t = exp(dt_t a) h_{t-1} + dt_t b_t xc_t from h0; y_t = sum_n h_t c_t.
    dt, xc: [B, T, C]; b, c: [B, T, N]; a: [C, N]; h0: [B, C, N], all of one
    float type.  Returns (y [B, T, C], h_T).

    One token is one step.  A sequence is cut into chunks of SSM_CHUNK
    positions: every chunk's recurrence runs from zero, all chunks side by
    side (SSM_CHUNK dependent steps over [B, T/Q, C, N], each step's read-out
    reduced over N at once); the chunks' first states follow from their last
    ones by T/Q dependent steps over [B, C, N]; and what a chunk's first state
    adds to its positions' read-outs is one fused product.  Nothing of size
    [T, C, N] is kept: the state of all chunks is read and written once a
    step, 2 T C N values in all.  A step with dt = 0 (a pad) leaves h as it is."""
    bsz, t, ch = dt.shape
    decay = lambda step: jnp.exp(step[..., None] * a)  # [..., C] -> [..., C, N]
    # elementwise and a sum over N, not a dot: 16 terms a channel fuse with what makes them
    read_out = lambda h, c_t: jnp.sum(h * c_t[..., None, :], axis=-1)  # [..., C, N], [..., N] -> [..., C]
    if t == 1:
        h = decay(dt[:, 0]) * h0 + (dt[:, 0] * xc[:, 0])[..., None] * b[:, 0, None, :]
        return read_out(h, c[:, 0])[:, None], h
    q = SSM_CHUNK
    g = -(-t // q)
    # [B, T, ...] -> [Q, B, G, ...], the tail of the last chunk steps of dt = 0
    chunked = lambda v: jnp.moveaxis(
        jnp.pad(v, ((0, 0), (0, g * q - t), (0, 0))).reshape(bsz, g, q, v.shape[-1]), 2, 0)
    dt_q, b_q, c_q, x_q = chunked(dt), chunked(b), chunked(c), chunked(xc)

    def within(s, step):
        dt_j, b_j, c_j, x_j = step
        s = decay(dt_j) * s + (dt_j * x_j)[..., None] * b_j[..., None, :]
        return s, read_out(s, c_j)

    last, y = lax.scan(within, jnp.zeros((bsz, g, ch, a.shape[-1]), dt.dtype), (dt_q, b_q, c_q, x_q))

    def across(h, chunk):
        whole, last_g = chunk
        return whole * h + last_g, h

    h_t, first = lax.scan(
        across, h0, (jnp.moveaxis(decay(jnp.sum(dt_q, axis=0)), 1, 0), jnp.moveaxis(last, 1, 0)))
    since = jnp.cumsum(dt_q, axis=0)  # [Q, B, G, C]: the steps from the chunk's start through each position
    y = y + read_out(decay(since) * jnp.moveaxis(first, 0, 1), c_q)
    return jnp.moveaxis(y, 0, 2).reshape(bsz, g * q, ch)[:, :t], h_t


def _ssm_mix(bp, xs, state, cfg: TransformerConfig, keep=None):
    """A state-space mixer between its two projections: the causal depthwise
    convolution, the step size and the input and output maps of each position,
    and the recurrence.  xs: [B, T, C]; state: (the convolution's window, the
    last ssm_d_conv - 1 inputs [B, K-1, C]; h [B, C, N]) as the tokens before
    left it, zeros before a sequence starts; keep: [B, T] bool, False at a
    left pad (whose xs the caller zeroed: `_ssm_half`), None for none.
    Returns (y [B, T, C], the state after the last position).

    At a pad dt = 0: exp(0) = 1 and the input term is 0, so h passes the pads
    unchanged and the state after the last token is the unpadded prompt's."""
    window, h = state
    n, r, f = cfg.ssm_d_state, cfg.ssm_dt_rank, SSM_STATE_DTYPE
    t = xs.shape[1]
    with jax.named_scope("ssm.conv"):
        padded = jnp.concatenate([window.astype(xs.dtype), xs], axis=1)  # [B, K-1+T, C]
        taps = bp["conv_w"].astype(xs.dtype)
        xc = sum(padded[:, j:j + t] * taps[j] for j in range(cfg.ssm_d_conv))
        if cfg.ssm_conv_bias:
            xc = xc + bp["conv_b"].astype(xs.dtype)
        xc = jax.nn.silu(xc)
    with jax.named_scope("ssm.state"):
        window = padded[:, t:].astype(window.dtype)
    with jax.named_scope("ssm.scan"):
        low = xc @ bp["ssm_x"].astype(xs.dtype)
        step, b, c = (
            _norm(part, bp, w, cfg) for part, w in zip(
                jnp.split(low, (r, r + n), axis=-1), ("dt_norm", "b_norm", "c_norm"))
        )
        dt = (step @ bp["ssm_dt"].astype(xs.dtype)).astype(f) + bp["dt_bias"].astype(f)
        dt = jax.nn.softplus(dt)
        if keep is not None:
            dt = jnp.where(keep[..., None], dt, 0.0)
        a = -jnp.exp(bp["a_log"].astype(f))
        y, h_new = _selective_scan(dt, a, b.astype(f), c.astype(f), xc.astype(f), h.astype(f))
        y = y + bp["ssm_d"].astype(f) * xc.astype(f)
    with jax.named_scope("ssm.state"):
        h_new = h_new.astype(h.dtype)
    return y.astype(xs.dtype), (window, h_new)


def _ssm_zero_state(cfg: TransformerConfig, batch: int):
    """The state before a sequence starts: (window, h) as `_ssm_mix` takes them."""
    with jax.named_scope("ssm.state"):
        return (jnp.zeros((batch, cfg.ssm_d_conv - 1, cfg.d_inner), cfg.dtype),
                jnp.zeros((batch, cfg.d_inner, cfg.ssm_d_state), SSM_STATE_DTYPE))


def _ssm_half(bp, x, cfg: TransformerConfig, core, keep=None):
    """A state-space block's first half, for training, prefill and decode
    alike: x + w_out(core(xs) * silu(z)), [xs, z] = w_in(norm(x)).  x: [B, T, E];
    `core(xs) -> (y [B, T, C], extra)` is `_ssm_mix` from the state its caller
    holds (zeros for a whole sequence, a slot's for one more token) and hands
    back what the caller keeps of the state after it; keep: [B, T] bool, False
    at a left pad, whose xs are zeroed here so that the convolution sees what
    an unpadded prompt sees before its start.  Returns (x, extra)."""
    with jax.named_scope("norm"):
        u = _norm(x, bp, "ln1", cfg)
    with jax.named_scope("ssm.in"):
        xs, z = jnp.split(u @ bp["ssm_in"].astype(x.dtype), 2, axis=-1)
        if keep is not None:
            xs = jnp.where(keep[..., None], xs, 0)
    y, extra = core(xs)
    with jax.named_scope("ssm.out"):
        x = x + (y * jax.nn.silu(z)) @ bp["ssm_out"].astype(x.dtype)
    return x, extra


def _ssd_scan(dt, a, b, c, xs, h0, chunk: int):
    """Mamba-2's recurrence, h_t[h] = exp(dt_t[h] a[h]) h_{t-1}[h] + dt_t[h] xs_t[h] (x) b_t[g], y_t[h] = h_t[h] c_t[g],
    head h in group g = h // (H / G), from h0.  dt: [B, T, H]; a: [H]; b, c: [B, T, G, N]; xs: [B, T, H, P]; h0:
    [B, H, P, N], all float32.  Returns (y [B, T, H, P], h_T).

    One token is one step, elementwise over the state.  A sequence runs in chunks of `chunk` positions as SSD
    does (arXiv:2405.21060, section 6), the tail of the last chunk steps of dt = 0.  Inside a chunk, under scope
    `ssm.scan.chunk`, matrix products: Y = ((C B^T) * L) (dt xs) with L[i, j] = exp(sum_{j<k<=i} dt_k a) for
    i >= j, and the chunk's own state sum_j exp(sum_{k>j} dt_k a) dt_j xs_j (x) B_j.  Between chunks, under
    `ssm.scan.carry`, T / chunk dependent steps hand the state on, and what a chunk's first state adds to its
    positions, C_i exp(sum_{k<=i} dt_k a) h_prev, is one more product.  Nothing of size [T, H, P, N] is kept:
    the chunks' first states are [T / chunk, H, P, N].  The operands are float32 and the products exact
    (`Precision.HIGHEST`: three per cent of a prefill's operations at Nemotron-H's widths), so a state the
    prefill hands the decode steps is the recurrence's to float32's rounding.  A step with dt = 0 (a pad)
    leaves h as it is."""
    bsz, t, hh = dt.shape
    g, n = b.shape[2:]
    r = hh // g  # heads a group
    p = xs.shape[-1]
    if t == 1:
        dt1 = dt[:, 0]
        per_head = lambda v: jnp.repeat(v[:, 0], r, axis=1)  # [B, G, N] -> [B, H, N]
        h = (jnp.exp(dt1 * a)[..., None, None] * h0
             + (dt1[..., None] * xs[:, 0])[..., None] * per_head(b)[:, :, None, :])
        return jnp.sum(h * per_head(c)[:, :, None, :], axis=-1)[:, None], h
    q = chunk
    nc = -(-t // q)
    exact = functools.partial(jnp.einsum, precision=lax.Precision.HIGHEST)
    # [B, T, ...] -> [B, chunks, Q, ...]: heads as [G, R], so that a group's B and C meet its heads without a copy
    chunked = lambda v, *tail: jnp.pad(v, ((0, 0), (0, nc * q - t)) + ((0, 0),) * (v.ndim - 2)).reshape(bsz, nc, q, *tail)
    dt_q, b_q, c_q, x_q = chunked(dt, g, r), chunked(b, g, n), chunked(c, g, n), chunked(xs, g, r, p)
    with jax.named_scope("ssm.scan.chunk"):
        since = jnp.cumsum(dt_q * a.reshape(g, r), axis=2)  # [B, C, Q, G, R]: log of the decay from the chunk's start
        dtx = dt_q[..., None] * x_q
        span = since[:, :, :, None] - since[:, :, None]  # [B, C, i, j, G, R]
        causal = (jnp.arange(q)[:, None] >= jnp.arange(q)[None, :])[:, :, None, None]
        decay = jnp.exp(jnp.where(causal, span, -jnp.inf))
        scores = exact("bcign,bcjgn->bcijg", c_q, b_q)
        y = exact("bcijgr,bcjgrp->bcigrp", scores[..., None] * decay, dtx)
        # the chunk's own state: every position's input decayed to the chunk's end
        to_end = jnp.exp(since[:, :, -1:] - since)
        own = exact("bcjgrp,bcjgn->bcgrpn", to_end[..., None] * dtx, b_q)
    with jax.named_scope("ssm.scan.carry"):
        whole = jnp.exp(since[:, :, -1])  # [B, C, G, R]: a chunk's decay from its start to its end

        def across(h, at):
            whole_c, own_c = at
            return whole_c[..., None, None] * h + own_c, h

        h_t, first = lax.scan(across, h0.reshape(bsz, g, r, p, n),
                              (jnp.moveaxis(whole, 1, 0), jnp.moveaxis(own, 1, 0)))
        first = jnp.moveaxis(first, 0, 1)  # [B, C, G, R, P, N]: each chunk's state before its first position
        y = y + jnp.exp(since)[..., None] * exact("bcign,bcgrpn->bcigrp", c_q, first)
    return y.reshape(bsz, nc * q, hh, p)[:, :t], h_t.reshape(bsz, hh, p, n)


def _mamba2_zero_state(cfg: TransformerConfig, batch: int):
    """The state before a sequence starts: (window, h) as `_mamba2_half` takes them."""
    with jax.named_scope("ssm.state"):
        return (jnp.zeros((batch, cfg.ssm_d_conv - 1, cfg.conv_width), cfg.dtype),
                jnp.zeros((batch, cfg.ssm_n_heads, cfg.ssm_head_dim, cfg.ssm_d_state), SSM_STATE_DTYPE))


def _mamba2_half(bp, x, cfg: TransformerConfig, state, keep=None):
    """A Mamba-2 layer, which is this half alone: x + `_mamba2_mixer` of it.
    Returns (x, the state after the last position)."""
    out, state = _mamba2_mixer(bp, x, cfg, state, keep)
    return x + out, state


def _mamba2_mixer(bp, x, cfg: TransformerConfig, state, keep=None):
    """A Mamba-2 mixer with its norm, for training, prefill and decode alike:
    w_out(norm_g(y * silu(z))), [z, xBC, dt] = w_in(norm(x)),
    xBC through the causal depthwise convolution and silu, split into xs
    [heads, head_dim], B and C [groups, d_state]; y the recurrence's read-out
    (`_ssd_scan`) + D xs; norm_g an RMSNorm over each group's d_inner / groups
    channels with one weight over all.  x: [B, T, E]; state: (the convolution's
    window, the last ssm_d_conv - 1 rows of xBC [B, K-1, conv_width]; h [B, H,
    P, N]) as the tokens before left it, zeros before a sequence starts
    (`_mamba2_zero_state`); keep: [B, T] bool, False at a left pad, None for
    none.  Returns (the mixer's result [B, T, E], the state after the last
    position).

    At a pad xBC = 0, so the convolution sees what an unpadded prompt sees
    before its start, and dt = 0: exp(0) = 1 and the input term is 0, so h
    passes the pads unchanged and the state after the last token is the
    unpadded prompt's.  dt, the decays, h (in the cache too) and y before the
    gate are float32 (SSM_STATE_DTYPE), the rest cfg.dtype."""
    window, h = state
    bsz, t, _ = x.shape
    c, w, hh, g, n, f = cfg.d_inner, cfg.conv_width, cfg.ssm_n_heads, cfg.ssm_n_groups, cfg.ssm_d_state, SSM_STATE_DTYPE
    with jax.named_scope("norm"):
        u = _norm(x, bp, "ln1", cfg)
    with jax.named_scope("ssm.in"):
        z, xbc, dt, _ = jnp.split(u @ bp["ssm_in"].astype(x.dtype), (c, c + w, c + w + hh), axis=-1)  # _: the columns of zeros
        if keep is not None:
            xbc = jnp.where(keep[..., None], xbc, 0)
    with jax.named_scope("ssm.conv"):
        padded = jnp.concatenate([window.astype(x.dtype), xbc], axis=1)  # [B, K-1+T, W]
        taps = bp["conv_w"].astype(x.dtype)
        xc = sum(padded[:, j:j + t] * taps[j] for j in range(cfg.ssm_d_conv))
        if "conv_b" in bp:
            xc = xc + bp["conv_b"].astype(x.dtype)
        xc = jax.nn.silu(xc)
    with jax.named_scope("ssm.state"):
        window = padded[:, t:].astype(window.dtype)
    with jax.named_scope("ssm.scan"):
        xs, b, cc = jnp.split(xc.astype(f), (c, c + g * n), axis=-1)
        xs = xs.reshape(bsz, t, hh, cfg.ssm_head_dim)
        dt = jax.nn.softplus(dt.astype(f) + bp["dt_bias"].astype(f))
        if keep is not None:
            dt = jnp.where(keep[..., None], dt, 0.0)
        y, h_new = _ssd_scan(dt, -jnp.exp(bp["a_log"].astype(f)), b.reshape(bsz, t, g, n), cc.reshape(bsz, t, g, n),
                             xs, h.astype(f), cfg.ssm_chunk)
        y = (y + bp["ssm_d"].astype(f)[:, None] * xs).reshape(bsz, t, c)
    with jax.named_scope("ssm.state"):
        h_new = h_new.astype(h.dtype)
    with jax.named_scope("ssm.norm"):
        gated = (y * jax.nn.silu(z.astype(f))).reshape(bsz, t, g, c // g)
        gated = gated * lax.rsqrt(jnp.mean(jnp.square(gated), axis=-1, keepdims=True) + cfg.norm_eps)
        gated = (gated.reshape(bsz, t, c) * bp["ssm_norm"].astype(f)).astype(x.dtype)
    with jax.named_scope("ssm.out"):
        return gated @ bp["ssm_out"].astype(x.dtype), (window, h_new)


def _kda_step(q, k, v, g, beta, s):
    """One position of the delta rule a row and head: S' = Diag(exp(g)) S, u = beta (v - S'^T k), S = S' + k u^T,
    o = S^T q.  q, k, v, g: [B, H, D]; beta: [B, H]; s: [B, H, Dk, Dv], all float32.  Returns (o [B, H, Dv], S).
    Elementwise and sums over the key axis, not dots: the state is read and written once where the sums fuse with
    what makes them, and o = S'^T q + (q . k) u needs no second pass over the new state."""
    s = jnp.exp(g)[..., None] * s
    u = beta[..., None] * (v - jnp.sum(k[..., None] * s, axis=-2))
    o = jnp.sum(q[..., None] * s, axis=-2) + jnp.sum(q * k, axis=-1, keepdims=True) * u
    return o, s + k[..., None] * u[..., None, :]


def _kda_pairs(a, k, since):
    """M[i, j] = sum_c a_i[c] k_j[c] exp(since_i[c] - since_j[c]) for the positions i >= j of a chunk, for each `a` of
    the list (the chunk's keys, its queries), 0 for i < j.  a, k, since: [..., C, D]; since, the log of the decay from
    the chunk's start through each position, falls with the position, so every exponent taken is <= 0: exp(-since_j)
    by itself overflows float32 where a channel forgets fast.  The chunk is KDA_SUB-position sub-blocks.  A row i of
    sub-block I meets a column j of an EARLIER sub-block through I's first position r, exp(since_i - since_r) on the
    row's side and exp(since_r - since_j) on the column's: a matrix product a sub-block row.  Inside a sub-block the
    exponents are taken pair by pair, [.., 16, 16, D] at once, and summed over the channels.  Returns [..., C, C] each."""
    *lead, c, d = k.shape
    nb, sb = c // KDA_SUB, KDA_SUB
    blocks = lambda x: x.reshape(*lead, nb, sb, d)
    exact = functools.partial(jnp.einsum, precision=lax.Precision.HIGHEST)
    since_b = blocks(since)
    first = since_b[..., 0, :]  # [..., nb, D]
    rows_decay = jnp.exp(since_b - first[..., None, :])
    earlier = (jnp.arange(c)[None, :] < (jnp.arange(nb) * sb)[:, None])[..., None]  # [nb, C, 1]: column j before sub-block I
    cols = k[..., None, :, :] * jnp.exp(jnp.where(earlier, first[..., None, :] - since[..., None, :, :], -jnp.inf))
    lower = (jnp.arange(sb)[:, None] >= jnp.arange(sb)[None, :])[..., None]
    inside = jnp.exp(jnp.where(lower, since_b[..., :, None, :] - since_b[..., None, :, :], -jnp.inf))  # [..., nb, i, j, D]
    inside = inside * blocks(k)[..., None, :, :]
    own = jnp.eye(nb, dtype=k.dtype)[:, None, :, None]  # a sub-block's own columns among the chunk's
    out = []
    for x in a:
        across = exact("...Iic,...Ijc->...Iij", blocks(x) * rows_decay, cols)  # [..., nb, sb, C]
        within = jnp.sum(blocks(x)[..., :, None, :] * inside, axis=-1)  # [..., nb, sb, sb]
        out.append((across.reshape(*lead, nb, sb, nb, sb) + within[..., :, :, None, :] * own).reshape(*lead, c, c))
    return out


def _kda_chunked(q, k, v, g, beta, s0, chunk: int):
    """The delta rule of `_kda_step` over whole sequences from s0, in chunks of `chunk` positions (Kimi Linear,
    arXiv:2510.26692, section 3; Gated DeltaNet's WY form with a decay a channel).  q, k, v, g: [B, T, H, D]; beta:
    [B, T, H]; s0: [B, H, Dk, Dv], all float32.  Returns (o [B, T, H, Dv], S after the last position).

    With since_i the log-decay from the chunk's start through position i and S_0 the state before the chunk, S_i =
    Diag(exp(since_i)) S_0 + sum_{j<=i} Diag(exp(since_i - since_j)) k_j u_j^T, where the u_j solve the unit lower
    triangular system (I + A) U = beta (V - (K exp(since)) S_0), A[i, j] = beta_i sum_c k_i k_j exp(since_i -
    since_j) for j < i.  All chunks side by side (`_kda_pairs`, one triangular solve of [K exp(since) | V] a chunk and
    head); then, chunk after chunk, T / chunk dependent steps of matrix products hand the state on: U = U' - W S, o =
    (Q exp(since)) S + A_qk U, S = Diag(exp(since_C)) S + (K exp(since_C - since))^T U.  Float32 operands, exact
    products (`Precision.HIGHEST`: the chunked form is about two per cent of a prefill's operations at Kimi Linear's
    widths), so the state a prefill hands the decode steps is the recurrence's to float32's rounding.  The tail of the
    last chunk, and a left pad, are positions of k = 0, beta = 0, g = 0: they leave S as it is."""
    bsz, t, hh, d = q.shape
    nc = -(-t // chunk)
    exact = functools.partial(jnp.einsum, precision=lax.Precision.HIGHEST)
    # [B, T, H, ...] -> [B, chunks, H, C, ...]
    chunked = lambda x: jnp.moveaxis(
        jnp.pad(x, ((0, 0), (0, nc * chunk - t)) + ((0, 0),) * (x.ndim - 2)).reshape(bsz, nc, chunk, *x.shape[2:]), 2, 3)
    q, k, v, g, beta = chunked(q), chunked(k), chunked(v), chunked(g), chunked(beta)
    since = jnp.cumsum(g, axis=-2)
    decay = jnp.exp(since)
    a_kk, a_qk = _kda_pairs([k, q], k, since)
    idx = jnp.arange(chunk)
    system = jnp.eye(chunk, dtype=k.dtype) + jnp.where(idx[:, None] > idx[None, :], a_kk * beta[..., None], 0.0)
    rhs = beta[..., None] * jnp.concatenate([k * decay, v], axis=-1)
    solved = jax.scipy.linalg.solve_triangular(system, rhs, lower=True, unit_diagonal=True)
    w, u_own = solved[..., :d], solved[..., d:]
    a_qk = jnp.where(idx[:, None] >= idx[None, :], a_qk, 0.0)
    to_end = k * jnp.exp(since[..., -1:, :] - since)

    def across(s, at):
        w_c, u_c, q_c, a_c, k_c, whole = at
        u = u_c - exact("bhck,bhkv->bhcv", w_c, s)
        o = exact("bhck,bhkv->bhcv", q_c, s) + exact("bhij,bhjv->bhiv", a_c, u)
        return whole[..., None] * s + exact("bhck,bhcv->bhkv", k_c, u), o

    per_chunk = lambda x: jnp.moveaxis(x, 1, 0)
    s, o = lax.scan(across, s0, tuple(map(per_chunk, (w, u_own, q * decay, a_qk, to_end, decay[..., -1, :]))))
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 2, 3)  # [chunks, B, H, C, D] -> [B, chunks, C, H, D]
    return o.reshape(bsz, nc * chunk, hh, d)[:, :t], s


def _causal_conv(padded, taps, t: int):
    """A causal depthwise convolution: padded [B, K-1+T, C], the K - 1 inputs before the sequence's first and then
    its own; taps [K, C].  Returns [B, T, C]: position i from the inputs i-K+1 .. i."""
    return sum(padded[:, j:j + t] * taps[j] for j in range(taps.shape[0]))


def _kda_zero_state(cfg: TransformerConfig, batch: int):
    """The state before a sequence starts: (window, S) as `_kda_mixer` takes them."""
    with jax.named_scope("ssm.state"):
        return (jnp.zeros((batch, cfg.ssm_d_conv - 1, cfg.conv_width), cfg.dtype),
                jnp.zeros((batch, cfg.kda_n_heads, cfg.kda_head_dim, cfg.kda_head_dim), SSM_STATE_DTYPE))


def _kda_gates(bp, u, cfg: TransformerConfig, keep=None):
    """What a KDA layer's normed input u [B, T, E] says of the delta rule beside q, k, v: (the log-decay g [B, T, H, D]
    = -exp(a_log) softplus(w_f2 w_f1 u + dt_bias), a key channel's own; beta [B, T, H] = sigmoid(w_b u); the
    output's gate sigmoid(w_g2 w_g1 u) [B, T, H D]), float32.  keep: [B, T] bool, False at a left pad, where g = 0 and
    beta = 0: the state passes a pad as it is."""
    f = SSM_STATE_DTYPE
    low = lambda first, second: (u @ bp[first].astype(u.dtype)) @ bp[second].astype(u.dtype)
    g = jax.nn.softplus(low("kda_f1", "kda_f2").astype(f) + bp["dt_bias"].astype(f))
    g = -jnp.exp(bp["a_log"].astype(f))[:, None] * g.reshape(*u.shape[:2], cfg.kda_n_heads, cfg.kda_head_dim)
    beta = jax.nn.sigmoid((u @ bp["kda_b"].astype(u.dtype)).astype(f))
    if keep is not None:
        g, beta = jnp.where(keep[..., None, None], g, 0.0), jnp.where(keep[..., None], beta, 0.0)
    return g, beta, jax.nn.sigmoid(low("kda_g1", "kda_g2").astype(f))


def _kda_mixer(bp, x, cfg: TransformerConfig, state, keep=None, update=None):
    """A Kimi Delta Attention mixer with its norm, for a whole sequence, a prompt's prefill and a decode step alike:
    w_out(norm_h(o) * sigmoid(w_g2 w_g1 u)), u = norm(x); [q | k | v] = silu(conv(w_qkv u)), q and k L2-normed a head
    and q scaled by D^-1/2; g = -exp(a_log) softplus(w_f2 w_f1 u + dt_bias) a channel, beta = sigmoid(w_b u) a head;
    o the delta rule's read-out (`_kda_chunked`; one position: `_kda_step`); norm_h an RMSNorm over a head's D with
    one weight the heads share.  x: [B, T, E]; state: (the convolution's window, the last ssm_d_conv - 1 rows of
    [q | k | v] as projected [B, K-1, 3 H D]; S [B, H, D, D]) as the tokens before left it, zeros before a sequence
    starts (`_kda_zero_state`); keep: [B, T] bool, False at a left pad, None for none.  Returns (the mixer's result
    [B, T, E], the state after the last position).  update: for T = 1, `update(q, k, v, g, beta) -> o [B, H, D]` moves
    the rows' states on where its caller keeps them and reads them out (a decode step over the cache's stacks through
    ops/kda.py's kernel: models/generate.py); S is then not this function's to hold, and comes and goes as None.

    At a pad the projection is zeroed, so the convolution sees what an unpadded prompt sees before its start and k = 0,
    and g = 0 and beta = 0: S passes the pads unchanged and the state after the last token is the unpadded prompt's.
    q, k, v after the convolution, g, beta, S (in the cache too) and o up to the gate are float32 (SSM_STATE_DTYPE),
    the rest cfg.dtype."""
    window, s = state
    bsz, t, _ = x.shape
    hh, d, f = cfg.kda_n_heads, cfg.kda_head_dim, SSM_STATE_DTYPE
    heads = lambda a: a.reshape(bsz, t, hh, d)
    with jax.named_scope("norm"):
        u = _norm(x, bp, "ln1", cfg)
    with jax.named_scope("kda.proj"):
        qkv = _project_heads(u, bp["kda_qkv"])
        if keep is not None:
            qkv = jnp.where(keep[..., None], qkv, 0)
    with jax.named_scope("kda.conv"):
        padded = jnp.concatenate([window.astype(x.dtype), qkv], axis=1)  # [B, K-1+T, 3 H D]
        xc = jax.nn.silu(_causal_conv(padded, bp["kda_conv"].astype(x.dtype), t)).astype(f)
        q, k, v = (heads(a) for a in jnp.split(xc, 3, axis=-1))
        unit = lambda a: a * lax.rsqrt(jnp.sum(jnp.square(a), axis=-1, keepdims=True) + 1e-6)
        q, k = unit(q) * d ** -0.5, unit(k)
    with jax.named_scope("ssm.state"):
        window = padded[:, t:].astype(window.dtype)
    with jax.named_scope("kda.gates"):
        g, beta, gate = _kda_gates(bp, u, cfg, keep)
    if update is not None:
        with jax.named_scope("kda.step"):
            o, s_new = update(q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0])[:, None], None
    elif t == 1:
        with jax.named_scope("kda.step"):
            o, s_new = _kda_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], s.astype(f))
            o = o[:, None]
    else:
        with jax.named_scope("kda.chunk"):
            o, s_new = _kda_chunked(q, k, v, g, beta, s.astype(f), KDA_CHUNK)
    if s_new is not None:
        with jax.named_scope("ssm.state"):
            s_new = s_new.astype(s.dtype)
    with jax.named_scope("kda.out"):
        o = o * lax.rsqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True) + cfg.norm_eps) * bp["kda_norm"].astype(f)
        gated = (o.reshape(bsz, t, hh * d) * gate).astype(x.dtype)
        return gated @ bp["kda_out"].astype(x.dtype), (window, s_new)


def _kda_half(bp, x, cfg: TransformerConfig, state, keep=None):
    """A kda block's first half: x + `_kda_mixer` of it.  Returns (x, the state after the last position)."""
    out, state = _kda_mixer(bp, x, cfg, state, keep)
    return x + out, state


def _ffn(bp, y, cfg: TransformerConfig, live=None, experts=None, manual_axes=frozenset(), keep: bool = False):
    """A block's FFN over what the block gives it, y [B, T, E]: dense SwiGLU or
    a mixture of experts (by what `bp` holds: a router or none), the mixture
    with the shared experts' MLP beside it where the configuration has any
    (gated, or the experts' own activation without a gate: by what `bp` holds).  On one device the mixture is the dropless routed path (`_moe`, which
    says what `live` and `experts` are); with 'ep' among the caller's manual
    axes its experts are sharded and tokens travel to them (parallel/moe.py
    moe_ffn).  keep: the block is a checkpoint that keeps FFN_NAMES, which the
    dense FFN's two up products are given here (a mixture's experts have none).
    Returns (out [B, T, E], aux loss, experts touched): None for a dense model,
    and no count from moe_ffn."""
    from ..parallel.moe import ACTIVATIONS

    b, t, e = y.shape
    dt = y.dtype
    with jax.named_scope("ffn"):
        if "router" not in bp:  # a dense model's block, or a mixture's leading dense layer
            named = lambda a, name: checkpoint_name(a, name) if keep else a
            gated = jax.nn.silu(named(y @ bp["w_gate"].astype(dt), "ffn.gate")) * named(y @ bp["w_up"].astype(dt), "ffn.up")
            return gated @ bp["w_down"].astype(dt), None, None
        if "ep" not in manual_axes:
            out, aux, touched = _moe(bp, y, cfg, live, experts)
            if cfg.n_shared_experts:
                with jax.named_scope("moe.shared"):
                    if "shared_gate" in bp:
                        shared = jax.nn.silu(y @ bp["shared_gate"].astype(dt)) * (y @ bp["shared_up"].astype(dt))
                        out = out + shared @ bp["shared_down"].astype(dt)
                    else:  # no gate, the experts' own activation
                        shared = ACTIVATIONS[cfg.moe_act](y @ bp["shared_in"].astype(dt))
                        out = out + shared @ bp["shared_out"].astype(dt)
            return out, aux, touched
        # tokens flatten, travel to their expert's device, come back (traced
        # under shard_map manual over 'ep': see forward())
        from ..parallel.moe import moe_ffn

        r = moe_ffn(
            y.reshape(b * t, e),
            bp["router"].astype(dt),
            bp["w_in"].astype(dt),
            bp["w_out"].astype(dt),
            axis_name="ep",
            capacity_factor=cfg.capacity_factor,
            act=cfg.moe_act,
        )
        return r.out.reshape(b, t, e), r.aux_loss.astype(jnp.float32), None


def _ffn_half(bp, x, cfg: TransformerConfig, live=None, experts=None, manual_axes=frozenset(), kind=None,
              keep: bool = False):
    """A block's second half: x + FFN(norm(x)), or under `cfg.norm_output`
    x + norm(FFN(x)); `_ffn` says what the FFN is and what the other arguments
    are.  kind: the layer's, where the caller serves more than one: attention
    alone ("attn_alone": HALF_KINDS) has no second half and x comes back as it
    is; keep: `_ffn`'s.  Returns (x, aux loss, experts touched)."""
    if kind == "attn_alone":
        return x, None, None
    if cfg.norm_output:
        out, aux, touched = _ffn(bp, x, cfg, live, experts, manual_axes, keep)
        with jax.named_scope("norm"):
            return x + _norm(out, bp, "ln2", cfg), aux, touched
    with jax.named_scope("norm"):
        y = _norm(x, bp, "ln2", cfg)
    out, aux, touched = _ffn(bp, y, cfg, live, experts, manual_axes, keep)
    return x + out, aux, touched


def core_scope(kind: str, cfg: Optional[TransformerConfig] = None) -> str:
    """The scope a layer of `kind` attends under: a window layer's beside the
    others'; and where layers share one stack of keys and values
    (`cfg.shared_readers`), by the stack a layer reads: the rings, the full
    layer that writes the stack, the cross layers that read it."""
    if is_window(kind):
        return "attn.core.window"
    if cfg is not None and cfg.shared_readers:
        return "attn.core.cross" if kind == "attn_cross" else "attn.core.full"
    return "attn.core"


class Carried(NamedTuple):
    """What a layer hands the next where that is more than x (`cfg.carries`):
    the layer loop's carry in x's place, every field of one shape from the
    first layer to the last (a scan's carry keeps its type), zeros until the
    layer that makes it.  A model whose layers hand on x alone carries x itself."""

    x: Any
    # the memory the gated memory units gate: the newest state-space layer's read-out y
    # before its own gate, [B, T, C] (a prefill and a decode step: its last position, [B, 1, C])
    m: Any = None
    # the keys and values the cross layers read, as `_diff_heads` gives them, in a program that
    # keeps no cache for them to lie in (training's forward; a prefill, over its bucket)
    k: Any = None
    v: Any = None


def carried(x, cfg: TransformerConfig, t_m: int, t_kv: int = 0):
    """x [B, T, E] as the layer loop carries it: itself, or under
    `cfg.carries` `Carried` with zeros for a memory of t_m positions and, where
    t_kv, for shared keys and values of t_kv positions."""
    if not cfg.carries:
        return x
    b = x.shape[0]
    kv = jnp.zeros((b, t_kv, cfg.cached_heads, cfg.cached_width), cfg.dtype) if t_kv else None
    return Carried(x, jnp.zeros((b, t_m, cfg.d_inner), cfg.dtype), kv, kv)


def _x(s):
    """The residual stream of what a layer is handed (`Carried`, or x itself)."""
    return s.x if isinstance(s, Carried) else s


def _hand_on(s, x, **made):
    """What a layer hands the next: x, and in a `Carried` what it `made` beside it."""
    return s._replace(x=x, **made) if isinstance(s, Carried) else x


def _gmu_half(bp, x, m, cfg: TransformerConfig):
    """A gated memory unit's first half: x + w_out(m * silu(w_in(norm(x)))), m
    [B, T, C] the memory of x's own positions (`Carried.m`).  It mixes no
    positions and keeps nothing between two tokens.  x: [B, T, E]."""
    with jax.named_scope("norm"):
        u = _norm(x, bp, "ln1", cfg)
    with jax.named_scope("gmu.in"):
        gate = u @ bp["gmu_in"].astype(x.dtype)
    with jax.named_scope("gmu.gate"):
        gated = m.astype(x.dtype) * jax.nn.silu(gate)
    with jax.named_scope("gmu.out"):
        return x + gated @ bp["gmu_out"].astype(x.dtype)


def _gmu_block(bp, s, cfg: TransformerConfig, live=None):
    """One gated-memory block of every program, over what the loop carries:
    x's positions are the memory's.  live: `_ffn_half`'s."""
    x = _gmu_half(bp, s.x, s.m, cfg)
    x, _, _ = _ffn_half(bp, x, cfg, live)
    return s._replace(x=x)


# What a checkpointed block (`cfg.remat`) keeps of its attention half where the device has room
# (`_remat_keeps`), by the names `jax.checkpoint`'s policy knows them under: q, k and v as
# `_block_forward`'s core is given them (turned, k and v at their own heads, before `_gqa_repeat`),
# the half's result h = x + wo(attn), from which the FFN half's recomputation starts, and the two
# residuals the flash kernel's forward rule makes (ops/attention.py).  Outside a checkpoint a name
# is the identity and lowers to nothing; the programs that serve (models/generate.py) trace none.
KEPT_NAMES = ("attn.q", "attn.k", "attn.v", "attn.h", FLASH_OUT, FLASH_LSE)
# What a block that has kept those keeps of a dense FFN beside them, in the layers where the room goes on
# (`_remat_keeps` counts them): the two up products `y @ w_gate` and `y @ w_up` as `_ffn` makes them,
# after which its backward pass makes again the two norms, `_gqa_repeat` and the gate alone.
FFN_NAMES = ("ffn.gate", "ffn.up")


def _block_forward(bp, s, cfg: TransformerConfig, mesh=None, manual_axes=frozenset(), kind: str = "attn",
                   layer=None, keep: bool = False, keep_ffn: bool = False):
    """One transformer block. s: x [B, T_local, E], or `Carried`.  manual_axes:
    the mesh axes the caller's shard_map is already manual over (pp/sp/ep
    subset); kind: the layer's (a window layer attends to its window: forward
    only on a TPU; a cross layer to the keys and values the loop carries, which
    the full layer before it left there); layer: its number among its kind;
    keep: the block is a checkpoint that keeps KEPT_NAMES, which are given here;
    keep_ffn: and FFN_NAMES (`_ffn`).
    Returns (what it hands on, the MoE load-balance loss: 0 dense)."""
    x = _x(s)
    t = x.shape[1]
    offset = lax.axis_index("sp") * t if "sp" in manual_axes and cfg.sp > 1 else 0

    def core(q, k, v, index=None):
        if keep:
            q, k, v = (a if a is None else checkpoint_name(a, "attn." + name) for a, name in zip((q, k, v), "qkv"))
        if index is not None:  # learned sparse attention: the way to the indexer's part beside q, k, v
            with jax.named_scope("attn.indexer"):
                index = index()
            return _sparse_attention(q, k, v, index, cfg), (k, v)
        with jax.named_scope(core_scope(kind, cfg)):
            if k is None:
                k, v = s.k, s.v
            made = (k, v)
            if cfg.latent:
                k, v = _latent_expand(bp, k, v, cfg)
            else:
                k, v = _gqa_repeat(k, cfg), _gqa_repeat(v, cfg)
            return _attention(q, k, v, cfg, mesh, manual_axes, cfg.attn_window * is_window(kind)), made

    x, (k, v) = _attention_half(bp, x, cfg, offset + jnp.arange(t), core, kind, layer)
    if keep:
        x = checkpoint_name(x, "attn.h")
    x, aux, _ = _ffn_half(bp, x, cfg, manual_axes=manual_axes, kind=kind, keep=keep_ffn)
    made = dict(k=k, v=v) if kind == "attn" else {}
    return _hand_on(s, x, **made), jnp.zeros((), jnp.float32) if aux is None else aux


def _ssm_block_forward(bp, x, cfg: TransformerConfig, keep=None, experts=None):
    """One state-space block over whole sequences from the zero state, for
    training and for a prompt's prefill.  keep: [B, T] bool, False at a left
    pad, None for none (`_ssm_half`); experts: `_ffn_half`'s.  Returns (x, the
    MoE load-balance loss: 0 dense, the state after the last position, experts
    touched or None, the mixer's read-out y [B, T, C] before its gate: what a
    gated memory unit further up gates)."""

    def core(xs):
        y, state = _ssm_mix(bp, xs, _ssm_zero_state(cfg, x.shape[0]), cfg, keep)
        return y, (state, y)

    x, (state, y) = _ssm_half(bp, x, cfg, core, keep)
    x, aux, touched = _ffn_half(bp, x, cfg, keep, experts)
    return x, jnp.zeros((), jnp.float32) if aux is None else aux, state, touched, y


def layer_stacks(params) -> Dict[str, Any]:
    """{kind: that kind's blocks, stacked on a leading axis of their own}."""
    stacks = {kind: params.get(name) for kind, (name, _) in _INIT_KIND.items()}
    return {kind: blocks for kind, blocks in stacks.items() if blocks is not None}


def _layer_runs(kinds, before=()):
    """[(kind, the run's first layer counted among its kind, its length)] for
    each maximal run of one kind: 7 ssm, attn, 13 ssm, attn, 6 ssm.  Where
    kinds alternate (a layer's kind is not the next one's) and a period of
    distinct kinds comes at least twice in a row, the run is the period:
    ((its kinds), (each one's first layer among its kind), the repetitions);
    [ssm, attn_win] x 8, ssm, attn, [gmu, attn_cross] x 7 is four runs, not 32.
    before: the kinds of the layers that stand before these, which a kind's
    layers are counted from."""
    runs, seen, i = [], dict(collections.Counter(before)), 0
    while i < len(kinds):
        period, n = (kinds[i],), 1
        while kinds[i + n:i + n + 1] == period:
            n += 1
        if n == 1:
            for p in range(2, len(set(kinds)) + 1):
                unit = tuple(kinds[i:i + p])
                if len(set(unit)) == p and tuple(kinds[i + p:i + 2 * p]) == unit:
                    period = unit
                    while tuple(kinds[i + n * p:i + (n + 1) * p]) == unit:
                        n += 1
                    break
        starts = tuple(seen.get(kind, 0) for kind in period)
        runs.append((period[0], starts[0], n) if len(period) == 1 else (period, starts, n))
        for kind in period:
            seen[kind] = seen.get(kind, 0) + n
        i += n * len(period)
    return runs


def _run_groups(runs):
    """`_layer_runs`' runs with each sequence of two or more that comes again at
    once, the same periods at the same lengths, made one group: [((the
    sequence's runs, their starts the first repetition's), the repetitions)],
    a run by itself a sequence of one that comes once.  Nemotron-H's 52 layers,
    MEMEM (*EMEMEM) x 4 (*EMEMEMEM) x 2 E, are [M, E] x 2, M, (*, [E, M] x 3) x 4,
    (*, [E, M] x 4) x 2, E: five loops that hold ten layer bodies, where their
    fifteen runs hold twenty-two.

    A sequence is made of single layers and periods.  One that holds a run of
    two or more layers of ONE kind stays as it is: inside a repetition such a
    run is a slice of its kind's stacks at an offset the program computes, and
    the chip's compiler copies every such slice into fast memory a repetition
    (tests/test_chip_compile.py: a state-space run of two between attention
    layers, three slices a repetition), where a run by itself reads each
    layer's matrices where they lie."""
    shape = lambda run: (run[0], run[2])
    sliced = lambda run: isinstance(run[0], str) and run[2] > 1
    groups, i = [], 0
    while i < len(runs):
        found = None
        for size in range(2, (len(runs) - i) // 2 + 1):
            unit = [shape(r) for r in runs[i:i + size]]
            reps = 1
            while [shape(r) for r in runs[i + reps * size:i + (reps + 1) * size]] == unit:
                reps += 1
            if reps > 1 and not any(map(sliced, runs[i:i + size])):
                found = (size, reps)
                break
        size, reps = found or (1, 1)
        groups.append((tuple(runs[i:i + size]), reps))
        i += size * reps
    return groups


def _scan_layers(body, carry, stacks, cfg: TransformerConfig, unsliced=None, unroll=1, indexed=False, span=None):
    """The layer loop of every program: each maximal run of one kind of layer
    is one `lax.scan` of `body(kind, carry, bp, held, layer) -> (carry, ys)`;
    a model of one kind is one run, and a period of alternating kinds that
    repeats is one scan of the period, the body once a kind (`_layer_runs`).
    A sequence of runs that repeats is one scan of the sequence, its runs the
    loops inside it (`_run_groups`): a program is compiled a loop body at a
    time, and its size and the time to compile it go by the bodies it holds.
    stacks: `layer_stacks`; span: (the first, one past the last) of the model's
    layers that this call runs, None for all (a loop whose body changes at a
    layer is two calls: one scan traces its body once).  Returns (carry, {kind:
    ys over that kind's layers}).

    What a program keeps from one call to the next (a cache) is part of
    `carry`, through every run of every kind, as whole stacks [n_kind, ...]
    that the body reads and writes at `layer`, the layer's number within its
    kind (`indexed` asks for it where the loop itself has no use for it).  It
    is never a scan's `xs` and `ys`: the stacked `ys` are a fresh buffer of the
    cache's size, which a donated cache cannot be, and every layer's slice is
    written back whole.  A carry is one buffer from the program's argument to
    its result.

    A run that is its kind's whole stack scans the stacks themselves.  A
    shorter run scans its layers' indices and reads each layer's parameters
    where they lie: a slice of a stack handed to a loop is a copy of those
    layers at every call (1.3 GB for thirteen state-space layers); a loop that
    is unrolled whole takes the slice all the same: there each layer's slice of
    it is a slice of the stack, and the gradients come stacked as the
    optimizer takes them, where the indices' come scattered into a stack of
    zeros a layer.  The names
    `unsliced` lists for a kind ({kind: names}: a mixture's experts, which a
    kernel reads) are never sliced by either: `held` is their stacks, to be
    read at `layer`, empty where there are none.  `layer` is None where nothing
    asked for it."""
    kinds = cfg.layer_kinds
    first, last = span or (0, len(kinds))
    outs: Dict[str, list] = {}

    def scan_run(carry, period, starts, n, ahead=None):
        """One run's scan.  ahead: the layers of each of the period's kinds that the
        repetitions of the run's group before this one hold (traced), or None
        outside a group.  Returns (carry, [(kind, its ys)])."""
        if isinstance(period, str):
            period, starts = (period,), (starts,)
        members, xs = [], {}  # a kind of the period: (kind, held, rest); its layers' parameters or indices
        for j, (kind, start) in enumerate(zip(period, starts)):
            blocks = stacks[kind]
            total = jax.tree_util.tree_leaves(blocks)[0].shape[0]
            if len(set(kinds)) == 1 and span is None:
                n = total  # one kind: the stack given is the run (a pipeline stage holds its share)
            held = {k: blocks[k] for k in (unsliced or {}).get(kind, ()) if k in blocks}
            rest = {k: v for k, v in blocks.items() if k not in held}
            members.append((kind, held, rest))
            if n == total:
                xs[f"bp{j}"] = rest
            elif unroll is True and ahead is None:
                xs[f"bp{j}"] = jax.tree_util.tree_map(lambda w: w[start:start + n], rest)
            if ahead is not None:
                xs[f"layer{j}"] = start + ahead[j] + jnp.arange(n)
            elif held or indexed or n != total:
                xs[f"layer{j}"] = jnp.arange(start, start + n)

        def step(carry, xs, members=members):
            """One layer, or one period: a layer of each of its kinds, in their order."""
            ys = []
            for j, (kind, held, rest) in enumerate(members):
                layer = xs.get(f"layer{j}")
                bp = xs[f"bp{j}"] if f"bp{j}" in xs else jax.tree_util.tree_map(lambda w: w[layer], rest)
                carry, y = body(kind, carry, bp, held, layer)
                ys.append(y)
            return carry, tuple(ys)

        carry, ys = lax.scan(step, carry, xs, unroll=unroll)
        return carry, list(zip(period, ys))

    join = lambda *parts: parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0)
    for runs, reps in _run_groups(_layer_runs(kinds[first:last], kinds[:first])):
        if reps == 1:
            (run,) = runs
            carry, made = scan_run(carry, *run)
        else:
            per_rep = {}  # kind -> its layers in one repetition of the sequence
            for period, _, n in runs:
                for kind in ((period,) if isinstance(period, str) else period):
                    per_rep[kind] = per_rep.get(kind, 0) + n

            def repetition(carry, rep, runs=runs, per_rep=per_rep):
                made: Dict[str, list] = {}
                for period, starts, n in runs:
                    period_kinds = (period,) if isinstance(period, str) else period
                    carry, ys = scan_run(carry, period, starts, n, ahead=[rep * per_rep[kind] for kind in period_kinds])
                    for kind, y in ys:
                        made.setdefault(kind, []).append(y)
                return carry, {kind: jax.tree_util.tree_map(join, *ys) for kind, ys in made.items()}

            carry, made = lax.scan(repetition, carry, jnp.arange(reps))
            # [repetitions, a repetition's layers of the kind, ...] -> the kind's layers in their order
            made = [(kind, jax.tree_util.tree_map(lambda y: y.reshape(-1, *y.shape[2:]), ys)) for kind, ys in made.items()]
        for kind, y in made:
            outs.setdefault(kind, []).append(y)
    return carry, {kind: jax.tree_util.tree_map(join, *runs) for kind, runs in outs.items()}


# The share of a device's memory that `_remat_keeps` leaves unspent: 1 / REMAT_MARGIN of its limit, for what
# the compiler holds that the shapes do not say (under fsdp the gathered weights of the layers in flight and
# their gradients before they are scattered, a fusion's own temporaries).  With less the chip's compiler, short
# of room, makes values again on its own, a layer's FFN products among them (PERF.md section 6, PRs 57 and 59).
REMAT_MARGIN = 64

# The logits one device makes at a time where the head and loss go by chunks of positions (`_loss_chunk`):
# 32 Mi of them, 64 MB in bfloat16 and 128 in float32.  A batch with fewer goes through whole.
LOSS_CHUNK = 2 ** 25

# the kinds of layer that attend to nothing: a checkpoint of theirs holds none of KEPT_NAMES
_NO_ATTENTION = ("ssm", "gmu", "mamba2", "ffn", "kda", "kda_dense")


def _memory_limit(mesh) -> Optional[int]:
    """The least `bytes_limit` that this process's devices of the mesh report
    (without a mesh: its first device), None where a device reports none (the
    CPU)."""
    devices = jax.local_devices()[:1] if mesh is None else [
        d for d in mesh.devices.flat if d.process_index == jax.process_index()]
    limits = [(d.memory_stats() or {}).get("bytes_limit") for d in devices]
    return min(limits) if limits and all(limits) else None


def _bytes_a_chip(params, cfg: TransformerConfig, mesh) -> int:
    """The bytes of `params` (arrays, tracers or shapes) that one device holds: all
    of them without a mesh, a leaf's share under `param_specs` on one."""
    if mesh is None or mesh.size == 1:
        return sum(x.size * x.dtype.itemsize for x in jax.tree_util.tree_leaves(params))
    shards = lambda spec: math.prod(mesh.shape[a] for part in spec if part for a in (part if isinstance(part, tuple) else (part,)))
    held = jax.tree_util.tree_map(lambda x, spec: x.size * x.dtype.itemsize // shards(spec), params, param_specs(cfg),
                                  is_leaf=lambda x: isinstance(x, P))
    return sum(jax.tree_util.tree_leaves(held))


def _rows_a_chip(cfg: TransformerConfig, mesh, shape) -> int:
    """The rows (batch x positions) of ids of `shape` [B, T] that one device
    sees: all of them without a mesh; on one the axes that divide the batch or,
    under a ring, the positions divide them (tp counts as dividing nothing)."""
    rows = shape[0] * shape[1]
    if mesh is None:
        return rows
    over = ["dp", "fsdp"] + ["sp"] * (cfg.resolved_attn() != "dense") + ["ep"] * bool(cfg.n_experts)
    return -(-rows // math.prod(mesh.shape[axis] for axis in over))


def _kept_bytes(cfg: TransformerConfig, rows: int) -> int:
    """The bytes KEPT_NAMES hold of one attention layer over `rows` (batch x
    positions): q, k, v as `_attention_half` hands them to its core, the core's
    result as wide as the values (float32 under differential attention), h,
    each in cfg.dtype, and the kernel's log-sum-exp, a float32 a head."""
    h = cfg.n_heads
    if cfg.latent:
        q, kv, out = h * (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim), cfg.qk_rope_head_dim + cfg.kv_lora_rank, h * cfg.v_head_dim
    else:
        q, kv, out = h * cfg.cached_width, 2 * cfg.cached_heads * cfg.cached_width, h * cfg.cached_width * (1 + cfg.diff_attn)
    return rows * ((q + kv + out + cfg.d_model) * jnp.dtype(cfg.dtype).itemsize + 4 * h)


def _kept_ffn_bytes(cfg: TransformerConfig, rows: int) -> int:
    """The bytes FFN_NAMES hold of one dense FFN over `rows`: the two up products in cfg.dtype."""
    return rows * 2 * cfg.d_ff * jnp.dtype(cfg.dtype).itemsize


def _loss_chunk(cfg: TransformerConfig, mesh, shape) -> int:
    """The positions the head and loss of a step over ids of `shape` [B, T]
    take at a time (`_chunked_loss`): as many as make LOSS_CHUNK logits on one
    device, a multiple of 128 where there are as many; 0 where all T make no
    more than that, and the head and loss go through whole."""
    chunk = LOSS_CHUNK * shape[1] // (_rows_a_chip(cfg, mesh, shape) * cfg.vocab_size)
    if chunk >= shape[1]:
        return 0
    return chunk - chunk % 128 if chunk >= 128 else max(chunk, 1)


def _loss_bytes(cfg: TransformerConfig, rows: int, positions: int, chunk: int) -> int:
    """The bytes the head and loss hold of `rows` a device over `positions`,
    `chunk` of them at a time (0: whole): the logits made at a time in cfg.dtype,
    in float32 and their gradient in float32; by chunks, every row's gradient in
    cfg.dtype, which is what goes from the forward pass to the backward pass
    (`_chunked_loss`); and the head's matrix and its gradient, whole, in
    cfg.dtype."""
    size = jnp.dtype(cfg.dtype).itemsize
    at_a_time = -(-rows * chunk // positions) if chunk else rows
    return cfg.vocab_size * (at_a_time * (size + 8) + bool(chunk) * rows * size + 2 * cfg.d_model * size)


def _dense_ffn_layers(cfg: TransformerConfig) -> Tuple[int, ...]:
    """The layers, by their place in the model, whose checkpoint can keep
    FFN_NAMES: those that attend and have a dense FFN behind the attention
    half; none of a mixture's or of a pipeline's stages."""
    if cfg.n_experts or cfg.pp > 1:
        return ()
    return tuple(i for i, kind in enumerate(cfg.layer_kinds) if kind not in _NO_ATTENTION + ("attn_alone",))


class Keeps(NamedTuple):
    """What the checkpointed blocks of a step keep (`_remat_keeps`)."""

    names: bool = False  # KEPT_NAMES, in every layer that attends
    ffn_layers: int = 0  # FFN_NAMES beside them, in the last so many layers that attend and have a dense FFN


def _remat_keeps(cfg: TransformerConfig, mesh, shape, weight_bytes: int, state: float = 1.0, loss_chunk: int = 0) -> Keeps:
    """What the checkpointed blocks of a step over ids of `shape` [B, T] keep
    (`_stage_forward`), layer by layer, of what the device's memory
    (`_memory_limit`) has left beside what the chip holds through the step,
    `state` times `weight_bytes` (the weights' bytes a chip; `_hidden` says
    what state is).  First KEPT_NAMES in every attention layer of a stage,
    `_kept_bytes` of the rows a chip sees (`_rows_a_chip`; under pp over the
    schedule's m + pp - 1 steps of B / m rows), all of the layers or none:
    where they do not fit nothing else is asked.  Then FFN_NAMES,
    `_kept_ffn_bytes`, in as many of those layers as the rest holds, counted
    from the last: the backward pass begins there, at the moment the step holds
    most, and a layer that kept its up products needs no room to make them
    again.  A mixture's layers and a pipeline's stages keep KEPT_NAMES alone.

    The budget is the limit less the state and `temp_bytes`, what the step
    holds beside both while every name is alive, from the loss to the last
    layer's backward pass: every layer's input (a checkpoint's own); the
    weights in cfg.dtype where they are kept in another (the compiler makes
    each layer's once and keeps it from the forward pass to the backward pass
    while it has room); the larger of what the head and loss hold
    (`_loss_bytes`; loss_chunk: `_loss_chunk`'s answer, 0 for logits made
    whole) and of one layer's halves made again with their gradients (twice
    the two names' bytes); and a margin, 1 / REMAT_MARGIN of the limit.  The
    gradients are not in it: a layer's are made as its names and input are let
    go (218 MB for 303 at `train-fsdp4`'s shapes), and all of them are there
    only when no name is: they are the bare step's need, which nothing decided
    here changes.

    A device that reports no limit (the CPU) keeps every name in every layer:
    nothing says they do not fit, and the tests off the chip then run the path
    the chip runs.  Decided as the step is traced and said once there, in a
    span `train.remat`: kept, kept_bytes, kept_layers (0 where not kept),
    budget_bytes (-1: no limit), kept_ffn_layers, kept_ffn_bytes (of the
    layers that keep them), temp_bytes, loss_chunk."""
    kinds = cfg.layer_kinds
    layers = sum(kind not in _NO_ATTENTION for kind in kinds) // cfg.pp
    dense = len(_dense_ffn_layers(cfg))
    seen = rows = _rows_a_chip(cfg, mesh, shape)
    if cfg.pp > 1:
        rows = -(-rows * (cfg.num_microbatches + cfg.pp - 1) // cfg.num_microbatches)
    a_layer, an_ffn = _kept_bytes(cfg, rows), _kept_ffn_bytes(cfg, rows)
    kept_bytes, limit = layers * a_layer, _memory_limit(mesh)
    size, kept_as = jnp.dtype(cfg.dtype).itemsize, jnp.dtype(cfg.param_dtype)
    inputs = len(kinds) // cfg.pp * rows * cfg.d_model * size
    cast = weight_bytes * size // kept_as.itemsize * (kept_as != jnp.dtype(cfg.dtype))
    temp = inputs + cast + max(_loss_bytes(cfg, seen, shape[1], loss_chunk), 2 * (a_layer + an_ffn))
    temp += 0 if limit is None else limit // REMAT_MARGIN
    budget = -1 if limit is None else limit - int(state * weight_bytes) - temp
    kept = layers > 0 and (limit is None or kept_bytes <= budget)
    ffn_layers = dense * kept if limit is None else min(dense, max(budget - kept_bytes, 0) // an_ffn) * kept
    with tracing.span("train.remat", kept=kept, kept_bytes=kept_bytes, kept_layers=layers * kept, budget_bytes=budget,
                      kept_ffn_layers=ffn_layers, kept_ffn_bytes=ffn_layers * an_ffn, temp_bytes=temp, loss_chunk=loss_chunk):
        pass
    return Keeps(kept, ffn_layers)


def _stage_forward(stacks, x, cfg: TransformerConfig, mesh=None, manual_axes=frozenset(), keeps: Keeps = Keeps()):
    """The layer loop over this stage's layers.  stacks: `layer_stacks`, leaves
    [L_stage, ...].  Returns (x, aux) — aux is the summed MoE load-balance loss
    (0 dense).

    Under `cfg.remat` every block is a `jax.checkpoint`: a layer's input is
    kept and the backward pass runs the block forward again.  keeps
    (`_remat_keeps`: what the device has room for) hands the checkpoint a
    policy by names.  Under `keeps.names` an attention block also keeps
    KEPT_NAMES and its backward pass runs again the two norms, `_gqa_repeat`
    and the FFN half alone; the last `keeps.ffn_layers` of them keep FFN_NAMES
    too and run again the norms, `_gqa_repeat` and the gate.  The policy
    changes at one layer, so the loop is two runs there (`_scan_layers`' span),
    each a scan with its own body.  A block of a kind that attends to nothing
    holds no such name and recomputes whole either way, as does a core that
    makes other residuals than the flash kernel's (the dense reference, a
    ring)."""

    def blocks_that_keep(ffn: bool):
        # an attention kind's block is the same whatever its FFN: that is what its weights hold
        blocks = {
            kind: functools.partial(_block_forward, cfg=cfg, mesh=mesh, manual_axes=manual_axes, kind=kind,
                                    keep=keeps.names, keep_ffn=ffn)
            for kind in _INIT_KIND if kind not in _NO_ATTENTION
        }

        def ssm(bp, s, layer=None):
            x, aux, _, _, y = _ssm_block_forward(bp, _x(s), cfg)
            return _hand_on(s, x, m=y), aux

        blocks["ssm"] = ssm
        blocks["gmu"] = lambda bp, s, layer=None: (_gmu_block(bp, s, cfg), jnp.zeros((), jnp.float32))
        blocks["mamba2"] = lambda bp, x, layer=None: (
            _mamba2_half(bp, x, cfg, _mamba2_zero_state(cfg, x.shape[0]))[0], jnp.zeros((), jnp.float32))

        def ffn_alone(bp, x, layer=None):
            x, aux, _ = _ffn_half(bp, x, cfg, manual_axes=manual_axes)
            return x, jnp.zeros((), jnp.float32) if aux is None else aux

        blocks["ffn"] = ffn_alone
        # a kda block: its mixer from the zero state, then the FFN its weights hold
        blocks["kda"] = blocks["kda_dense"] = lambda bp, x, layer=None: ffn_alone(
            bp, _kda_half(bp, x, cfg, _kda_zero_state(cfg, x.shape[0]))[0])
        if cfg.remat:
            names = KEPT_NAMES * keeps.names + FFN_NAMES * ffn
            policy = jax.checkpoint_policies.save_only_these_names(*names) if names else None
            blocks = {kind: jax.checkpoint(block, policy=policy) for kind, block in blocks.items()}
        return blocks

    def run(carry, span, ffn: bool):
        blocks = blocks_that_keep(ffn)

        def body(kind, carry, bp, _held, layer):
            x, aux = carry
            x, a = blocks[kind](bp, x, layer=layer)
            return (x, aux + a), None

        return _scan_layers(body, carry, stacks, cfg, unroll=True if cfg.unroll_layers else 1, indexed=cfg.diff_attn,
                            span=span)[0]

    t = x.shape[1]
    carry = (carried(x, cfg, t, t), jnp.zeros((), jnp.float32))
    # the layer from which on every dense FFN keeps its names: the `keeps.ffn_layers`-th such layer from the last
    cut = _dense_ffn_layers(cfg)[-keeps.ffn_layers] if keeps.ffn_layers else len(cfg.layer_kinds)
    if 0 < cut < len(cfg.layer_kinds):
        carry = run(run(carry, (0, cut), False), (cut, len(cfg.layer_kinds)), True)
    else:
        carry = run(carry, None, cut == 0)
    x, aux = carry
    return _x(x), aux


def _head(params, x, cfg: TransformerConfig, row=None):
    """The final norm and the output head of every program: x [B, T, E] ->
    logits [B, T, V] in cfg.dtype, or of position `row` alone [B, V] in float32,
    as a sampler takes them.  A tied head is the embedding, contracted over its
    own width."""
    with jax.named_scope("norm"):
        x = _norm(x, params, "ln_f", cfg)
    with jax.named_scope("head"):
        if row is not None:
            x = x[:, row]
        if cfg.tie_embeddings:
            logits = jnp.einsum("...e,ve->...v", x, params["embed"].astype(cfg.dtype))
        else:
            logits = x @ params["lm_head"].astype(cfg.dtype)
        return logits if row is None else logits.astype(jnp.float32)


def forward(params, ids, cfg: TransformerConfig, mesh=None, return_aux: bool = False):
    """ids: [B, T] int32 -> logits [B, T, V] (with the MoE load-balance aux
    loss when return_aux; 0 for dense configs).  Under `cfg.remat` a gradient
    through it keeps what fits beside the weights alone (`_remat_keeps`)."""
    x, aux = _hidden(params, ids, cfg, mesh)
    logits = _head(params, x, cfg)
    return (logits, aux) if return_aux else logits


def _hidden(params, ids, cfg: TransformerConfig, mesh, state: float = 1.0, loss_chunk: int = 0):
    """`forward` up to the last layer's result, before the final norm and the
    head: (x [B, T, E], aux), told what stays on a chip through the step it is
    part of, `state` times the weights' bytes (`make_train_step`: the
    optimizer's state beside them), and how the head and loss that follow go
    (`_loss_chunk`)."""
    with jax.named_scope("embed"):
        x = params["embed"].astype(cfg.dtype)[ids]  # [B, T, E]
    manual_axes = set()
    if cfg.pp > 1:
        manual_axes.add("pp")
    if cfg.sp > 1 and cfg.resolved_attn() in ("ring", "ulysses"):
        manual_axes.add("sp")
    # one device holds every expert and routes without a capacity; any larger
    # mesh shards the experts over its 'ep' axis
    if cfg.n_experts and mesh is not None and mesh.size > 1:
        manual_axes.add("ep")

    if manual_axes or (mesh is not None and mesh.size > 1):
        _one_device_only(cfg, f"forward on a mesh of {1 if mesh is None else mesh.size} devices")
    keeps = Keeps()
    if cfg.remat:
        keeps = _remat_keeps(cfg, mesh, ids.shape, _bytes_a_chip(params, cfg, mesh), state, loss_chunk)
    if manual_axes:
        if mesh is None:
            raise ValueError("mesh required for pp/sp execution")
        if cfg.n_experts:
            if (cfg.n_experts_per_tok > 1 or cfg.moe_gated or cfg.n_shared_experts
                    or cfg.moe_scoring != "softmax" or cfg.experts_held is not None):
                raise NotImplementedError(
                    "experts sharded over a mesh's 'ep' axis are top-1 and ungated "
                    f"(parallel/moe.py moe_ffn); n_experts_per_tok={cfg.n_experts_per_tok}, "
                    f"moe_gated={cfg.moe_gated} run on one device only, as do shared experts, "
                    "sigmoid scores and a held share of the experts"
                )
            mesh_ep = mesh.shape["ep"]
            if cfg.ep > 1 and cfg.ep != mesh_ep:
                raise ValueError(
                    f"cfg.ep={cfg.ep} disagrees with the mesh's ep axis ({mesh_ep})"
                )
            if cfg.n_experts % mesh_ep != 0:
                raise ValueError(
                    f"n_experts={cfg.n_experts} not divisible by the mesh's "
                    f"ep axis ({mesh_ep})"
                )
        return _apply_blocks_manual(params["blocks"], x, cfg, mesh, frozenset(manual_axes), keeps)
    if mesh is not None and mesh.size > 1:
        # state the residual stream's layout instead of leaving it to
        # propagation: between the embedding (columns on tp), wo/w_down
        # (columns on fsdp) and the attention region (batch on dp x fsdp)
        # the partitioner otherwise picks its own, and on dp2 x fsdp2 x
        # tp2 with one row per shard that gave wrong logits
        x = lax.with_sharding_constraint(
            x, NamedSharding(mesh, P(("dp", "fsdp"), None, None))
        )
    return _stage_forward(layer_stacks(params), x, cfg, mesh, keeps=keeps)


def _apply_blocks_manual(blocks, x, cfg: TransformerConfig, mesh, manual_axes, keeps: Keeps = Keeps()):
    """Run the block stack under shard_map, manual over {'pp','sp','ep'}
    (subset), GSPMD-auto over dp/fsdp/tp.  With 'ep' manual, the batch dim
    shards over experts' owner devices (tokens all_to_all inside moe_ffn).
    keeps: `_stage_forward`'s."""
    sp_manual = "sp" in manual_axes
    pp_manual = "pp" in manual_axes
    ep_manual = "ep" in manual_axes

    def inner(blocks_local, x_local):
        if pp_manual:
            my_blocks = jax.tree_util.tree_map(lambda p: p[0], blocks_local)
            stage = lambda bp, a: _stage_forward(
                {"attn": bp}, a, cfg=cfg, mesh=mesh, manual_axes=manual_axes, keeps=keeps
            )
            if cfg.n_experts:
                # MoE through the pipeline: each stage's MoE layers
                # all_to_all over 'ep' inside their pipeline step; the
                # load-balance aux threads through the schedule (bubble
                # steps masked) and comes back psum'd over stages
                x_out, aux = pipeline_apply(
                    stage,
                    my_blocks,
                    x_local,
                    axis_name="pp",
                    num_microbatches=cfg.num_microbatches,
                    with_aux=True,
                )
            else:
                x_out = pipeline_apply(
                    lambda bp, a: stage(bp, a)[0],
                    my_blocks,
                    x_local,
                    axis_name="pp",
                    num_microbatches=cfg.num_microbatches,
                )
                aux = jnp.zeros((), jnp.float32)
        else:
            x_out, aux = _stage_forward(
                {"attn": blocks_local}, x_local, cfg, mesh, manual_axes, keeps
            )
        # the P() out-spec claims aux is replicated across EVERY manual axis;
        # each shard computed it over its own tokens, so reduce over all
        # (pp already reduced inside pipeline_apply)
        if ep_manual:
            aux = lax.pmean(aux, "ep")
        if sp_manual:
            aux = lax.pmean(aux, "sp")
        return x_out, aux

    def leaf_spec(path, _leaf):
        # expert tensors carry their 'ep' shard inside the manual region;
        # the leading stacked-layer axis (and pp stage axis) comes first
        name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        lead = ("pp",) if pp_manual else ()
        if ep_manual and name in ("w_in", "w_out"):
            return P(*lead, None, "ep")  # [.., L, n_experts, ...]
        return P(*lead) if lead else P()

    block_specs = jax.tree_util.tree_map_with_path(leaf_spec, blocks)
    batch_axis = "ep" if ep_manual else None
    x_spec = P(batch_axis, "sp" if sp_manual else None, None)
    aux_spec = P()
    out, aux = jax.shard_map(
        inner,
        mesh=mesh,
        in_specs=(block_specs, x_spec),
        out_specs=(x_spec, aux_spec),
        axis_names=frozenset(manual_axes),
        check_vma=False,
    )(blocks, x)
    return out, aux


# ---------------------------------------------------------------------------
# loss / train step
# ---------------------------------------------------------------------------


def cross_entropy_loss(logits, targets, mask=None):
    logits = logits.astype(jnp.float32)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    nll = logz - gold
    if mask is not None:
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)


def _chunks(x, w, targets, chunk: int, with_gradient: bool):
    """`_chunked_loss`'s one pass: a scan over T's chunks (the last padded with
    rows that count for nothing) of the head's product, the log-sum-exp and,
    with_gradient, the loss's gradient by the chunk's logits, rounded to their
    dtype as the transpose of `cross_entropy_loss`'s cast rounds it.  Returns
    (the loss, x by chunks [n, B, chunk, E], that gradient [n, B, chunk, V] or
    None)."""
    b, t, e = x.shape
    n = -(-t // chunk)
    by_chunks = lambda a: jnp.pad(a, ((0, 0), (0, n * chunk - t)) + ((0, 0),) * (a.ndim - 2)).reshape(
        b, n, chunk, *a.shape[2:]).swapaxes(0, 1)
    xs = by_chunks(x)
    # what `jnp.mean` hands every row's loss in the backward pass, 0 where a row is the pad's
    weight = by_chunks(jnp.full((b, t), 1.0 / (b * t), jnp.float32))

    def one(_, chunk_of):
        xc, tc, ct = chunk_of
        with jax.named_scope("head"):
            logits = xc @ w
        with jax.named_scope("loss"):
            logits = logits.astype(jnp.float32)
            top = jnp.max(logits, axis=-1, keepdims=True)
            exps = jnp.exp(logits - top)
            total = jnp.sum(exps, axis=-1, keepdims=True)
            gold = jnp.take_along_axis(logits, tc[..., None], axis=-1)
            nll = (jnp.log(total) + top - gold)[..., 0]
            if not with_gradient:
                return None, (nll, None)
            ct = ct[..., None]
            by_logits = exps * (ct / total)
            at_gold = lax.broadcasted_iota(tc.dtype, logits.shape, logits.ndim - 1) == tc[..., None]
            return None, (nll, jnp.where(at_gold, by_logits - ct, by_logits).astype(x.dtype))

    _, (nll, by_logits) = lax.scan(one, None, (xs, by_chunks(targets), weight))
    with jax.named_scope("loss"):
        return jnp.mean(nll.swapaxes(0, 1).reshape(b, n * chunk)[:, :t]), xs, by_logits


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _chunked_loss(x, w, targets, chunk: int):
    """`cross_entropy_loss(x @ w, targets)`, the mean over every row, for x
    [B, T, E] (the final norm's result) and the head's matrix w [E, V], both
    in cfg.dtype, `chunk` positions at a time: no more than B x chunk x V
    logits exist at once, in their dtype or in float32.  The numbers are the
    whole computation's: the logits are made in x's dtype and cast up exactly,
    every reduction is over float32.  Under a gradient the one pass also
    leaves the loss's gradient by the logits, in their dtype (what the whole
    computation's backward pass hands the head's two products), in place of
    the logits: the backward pass is those two products, and the head's own
    product is made once."""
    return _chunks(x, w, targets, chunk, False)[0]


def _chunked_loss_forward(x, w, targets, chunk: int):
    loss, xs, by_logits = _chunks(x, w, targets, chunk, True)
    return loss, (xs, w, by_logits, targets)


def _chunked_loss_backward(chunk: int, kept, ct):
    xs, w, by_logits, targets = kept
    n, b, _, e = xs.shape
    ct = ct.astype(xs.dtype)
    with jax.named_scope("head"):
        by_x = jnp.einsum("nbcv,ev->nbce", by_logits, w).swapaxes(0, 1).reshape(b, n * chunk, e)[:, :targets.shape[1]]
        by_w = jnp.einsum("nbce,nbcv->ev", xs, by_logits)
    return by_x * ct, by_w * ct, None


_chunked_loss.defvjp(_chunked_loss_forward, _chunked_loss_backward)


def _loss(params, batch, cfg: TransformerConfig, mesh, state: float = 1.0):
    """The next-token loss of batch["ids"] [B, T+1]; state: `_hidden`'s.  Where
    the logits of all T positions are more than LOSS_CHUNK a device
    (`_loss_chunk`) the head and loss take the positions by chunks
    (`_chunked_loss`); a smaller batch makes its logits whole, in cfg.dtype and
    in float32."""
    ids = batch["ids"]
    chunk = _loss_chunk(cfg, mesh, ids[:, :-1].shape)
    x, aux = _hidden(params, ids[:, :-1], cfg, mesh, state, chunk)
    if chunk:
        with jax.named_scope("norm"):
            x = _norm(x, params, "ln_f", cfg)
        with jax.named_scope("head"):
            w = params["embed"].astype(cfg.dtype).T if cfg.tie_embeddings else params["lm_head"].astype(cfg.dtype)
            if mesh is not None and mesh.size > 1:
                # gathered once, before the chunks: left to itself the partitioner gathers the matrix inside the
                # loop, once a chunk (2 ms each of 8 at `train-fsdp4`'s shapes)
                w = lax.with_sharding_constraint(w, NamedSharding(mesh, P(None, "tp")))
        loss = _chunked_loss(x, w, ids[:, 1:], chunk)
    else:
        logits = _head(params, x, cfg)
    with jax.named_scope("loss"):
        if not chunk:
            loss = cross_entropy_loss(logits, ids[:, 1:])
        if cfg.n_experts:
            loss = loss + cfg.moe_aux_weight * aux
    return loss


def make_loss_fn(cfg: TransformerConfig, mesh=None):
    """loss_fn(params, batch) -> the loss.  Its gradient under `cfg.remat`
    keeps what fits beside the weights alone (`_remat_keeps`)."""
    return functools.partial(_loss, cfg=cfg, mesh=mesh)


def make_train_step(cfg: TransformerConfig, mesh, optimizer=None, learning_rate=3e-4):
    """Returns (train_step, init_state). train_step is jittable:
    (params, opt_state, batch) -> (params, opt_state, loss)."""
    import optax

    if "kda" in (cfg.layer_mixers or ()):
        raise NotImplementedError(
            "a training step through kda layers: the chunked delta rule's backward pass (`_kda_chunked`: the "
            "triangular solve's and the carried state's) is neither written nor measured; kda layers are served")
    if optimizer is None:
        optimizer = optax.adamw(learning_rate, weight_decay=0.01)

    def train_step(params, opt_state, batch):
        # what `cfg.remat` keeps is decided beside everything the step holds: an optimizer's state is
        # made of the parameters' shapes (`optimizer.init`) and inherits their shardings
        state = 1.0 + _bytes_a_chip(opt_state, cfg, None) / _bytes_a_chip(params, cfg, None)
        loss, grads = jax.value_and_grad(functools.partial(_loss, cfg=cfg, mesh=mesh, state=state))(params, batch)
        with jax.named_scope("optimizer"):
            updates, opt_state = optimizer.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    def init_state(key):
        params = init_params(key, cfg)
        params = shard_params(params, cfg, mesh)
        opt_state = optimizer.init(params)  # inherits param shardings
        return params, opt_state

    return train_step, init_state


def make_batch_sharding(cfg: TransformerConfig, mesh):
    """Input batch sharding: batch over (dp, fsdp), sequence over sp."""
    return NamedSharding(mesh, P(("dp", "fsdp"), "sp" if cfg.sp > 1 else None))
