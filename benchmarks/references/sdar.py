"""SDAR's decoder (JetLM/SDAR-30B-A3B-Chat, `model_type` `sdar_moe`): a
Qwen3-MoE block (RMSNorm, grouped-query attention with an RMSNorm on every
head of q and k, rotary embeddings, a mixture of gated experts behind a softmax
router whose k largest probabilities are renormalised, no shared expert, untied
head) that generates by diffusion over blocks.  The interface is the package's
(references/__init__.py), with the three hooks of a generation that is not one
causal token a step.

The equations, a layer (every layer is this one):

    h   = x + Attn(RMSNorm(x));   out = h + MoE(RMSNorm(h));   final RMSNorm; head
    q   = RMSNorm_q(x Wq as heads),  k = RMSNorm_k(x Wk as heads)   (each over
          the d_head of ONE head, one weight [d_head] shared by the heads),
          v = x Wv;  rotary on q and k at absolute positions
    Attn: position i sees position j where j // B <= i // B: every earlier block
          and the whole of its own, in both directions (B = block_length)
    p   = softmax(x Wr) over all experts, in float32;  S its k largest;
          g_e = p_e / sum_S p
    MoE = sum over S of  g_e * (silu(x Wg_e) * (x Wu_e)) Wd_e
    The logits at position i give the token at position i itself.

Generation (the family's published loop, `generate.py` of the SDAR repository,
`block_diffusion_generate`; the numbers are the configuration file's `assumed`):
a prompt's whole blocks are prefilled under the block mask.  Then block by
block, aligned to absolute positions: a block starts as the prompt's tail
(first block only) and masks; a pass runs the block against the blocks before
it and itself; each masked position proposes t_i = argmax logits_i with the
confidence c_i = softmax(logits_i)[t_i]; the masked positions with c_i over the
threshold are fixed to t_i, or, where fewer than m = B / steps are, the m most
confident; a block with no mask left takes one more pass, which stores its keys
and values, and the next block begins.  An answer that ends inside a block is
that block's leading positions: the block is denoised whole and what was fixed
past the answer's end is not served.

The plain reference is straightforward `jax.numpy` in float32 at `highest`
matmul precision: no kernel, no batching, no sorting of tokens, the experts one
at a time over every row (references/olmoe.py `_experts`, the same mixture; the
harness's own copy of that file is used, loaded as the harness loads it).  One
function is a layer, `_layer`: rows at given positions, the keys and values of
rows that came before (none, for a whole sequence), and who sees whom.  `forward`
is it over a whole sequence under the block mask.  The replay of a stream's
passes is it over the passes' blocks, each seeing the keys and values that the
whole sequence of the final tokens gives the blocks before it: under the block
mask an earlier block does not depend on a later one, so those are what the
program had stored when the pass ran.  It shares no code with
`cluster_anywhere_tpu/models/` or `parallel/`; it reads the same parameter tree.
(`program_logits` and `mechanism_checks` call the program as what they check.)

Departures from the published model, which the configuration file lists: the
rotary embedding rotates adjacent pairs (x[2i], x[2i+1]) as the program does
(Hugging Face rotates halves: a permutation of the columns of Wq, Wk and of the
norms' weights; on random weights the two are the same model).
"""

from __future__ import annotations

import dataclasses
import functools
import os
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmarks.harness import manifest

# the same mixture of gated experts: one reference of it (this directory's own file)
_moe_ref = manifest.load_reference("olmoe", os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

RMS_EPS = 1e-6
# what this architecture's programs write beyond the common names
# (program_trace.SCOPES): the four of the expert path under `ffn`
# (parallel/moe.py routed_ffn), and `block.choose` in place of `sample`: each
# masked position's token and confidence and the rule for what a pass fixes
SCOPES = ("moe.router", "moe.dispatch", "moe.experts", "moe.combine", "block.choose")
KERNELS = ("ragged-dot-none",)  # references/olmoe.py says why it is known by name

# what the program's configuration has to know for this architecture: a checkout
# that lacks one cannot run it, and says so before a replica is started
PROGRAM_FIELDS = ("block_length", "mask_token_id", "denoise_steps", "confidence_threshold", "qk_norm_per_head")


def program_config(config_file: Dict[str, Any], **extra) -> Dict[str, Any]:
    """The program's TransformerConfig fields from a configuration file's
    `config` (the published keys as run, and the generation's `assumed`)."""
    from cluster_anywhere_tpu.models.transformer import TransformerConfig

    lacks = sorted(set(PROGRAM_FIELDS) - {f.name for f in dataclasses.fields(TransformerConfig)})
    if lacks:
        raise NotImplementedError(
            f"this checkout's TransformerConfig has no {lacks}: the program cannot generate by blocks")
    c = config_file["config"]
    out = dict(
        d_model=c["hidden_size"], n_layers=c["num_hidden_layers"],
        n_heads=c["num_attention_heads"], n_kv_heads=c["num_key_value_heads"],
        d_head=c["head_dim"], d_ff=c["moe_intermediate_size"], rope_theta=float(c["rope_theta"]),
        max_seq_len=c["max_position_embeddings"],
        n_experts=c["num_experts"], n_experts_per_tok=c["num_experts_per_tok"],
        moe_renormalize=bool(c["norm_topk_prob"]), moe_gated=True, qk_norm=True, qk_norm_per_head=True,
        block_length=c["block_length"], mask_token_id=c["mask_token_id"],
        denoise_steps=c["denoising_steps"], confidence_threshold=float(c["confidence_threshold"]),
    )
    out.update(extra)
    return out


def _rms_norm(x, w):
    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + RMS_EPS)) * w


def _rope(x, pos, theta: float):
    """x: [N, H, D] at absolute positions pos [N]."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]  # [N, D/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1).reshape(x.shape)


@functools.partial(jax.jit, static_argnames=("dims",))
def _layer(x, pos, see, before, lp, *, dims):
    """One block over rows x [N, E] (float32) at absolute positions pos [N].
    before: (k, v) [M, KV, D] each, the keys and values of rows that came
    earlier, or None; see: [N, M + N] bool, which of those and of these rows
    each row sees.  lp: this layer's weights in whatever type they are stored
    in.  Returns (the block's output, these rows' (k, v), what its experts were
    given: the normed stream [N, E])."""
    h, kv, d, theta, k_top, renormalize = dims
    f32 = lambda name: lp[name].astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        n = x.shape[0]
        y = _rms_norm(x, f32("ln1"))
        q = _rms_norm((y @ f32("wq")).reshape(n, h, d), f32("q_norm"))
        k = _rms_norm((y @ f32("wk")).reshape(n, kv, d), f32("k_norm"))
        v = (y @ f32("wv")).reshape(n, kv, d)
        q, k = _rope(q, pos, theta), _rope(k, pos, theta)
        keys, values = (k, v) if before is None else (
            jnp.concatenate([before[0], k]), jnp.concatenate([before[1], v]))
        keys, values = jnp.repeat(keys, h // kv, axis=1), jnp.repeat(values, h // kv, axis=1)
        s = jnp.einsum("qhd,khd->hqk", q, keys) * d ** -0.5
        p = jax.nn.softmax(jnp.where(see[None], s, -jnp.inf), axis=-1)
        x = x + jnp.einsum("hqk,khd->qhd", p, values).reshape(n, h * d) @ f32("wo")
        y = _rms_norm(x, f32("ln2"))
        return x + _moe_ref._experts(y, lp, k_top, renormalize)[0], (k, v), y


def _dims_of(cfg):
    return (cfg.n_heads, cfg.n_kv_heads, cfg.d_head, float(cfg.rope_theta),
            cfg.n_experts_per_tok, bool(cfg.moe_renormalize))


def _blocks(params, ids, pos, see, cfg, before=None):
    """Rows `ids` [N] through the stack.  before: [layer] of (k, v), or None.
    Yields, a layer at a time, (the block's output [N, E], the rows' (k, v),
    what its experts were given [N, E])."""
    x = params["embed"][jnp.asarray(ids)].astype(jnp.float32)
    pos, see = jnp.asarray(pos), jnp.asarray(see)
    for i in range(params["blocks"]["wq"].shape[0]):
        x, kv, y = _layer(x, pos, see, None if before is None else before[i],
                          _moe_ref._layer_of(params, i), dims=_dims_of(cfg))
        yield x, kv, y


def _block_mask(t: int, block: int) -> np.ndarray:
    """[t, t]: position i sees j where j's block is not after i's (block 1:
    the causal mask)."""
    of = np.arange(t) // max(block, 1)
    return of[None, :] <= of[:, None]


def _head(params, x):
    with jax.default_matmul_precision("highest"):
        return _rms_norm(x, params["ln_f"].astype(jnp.float32)) @ params["lm_head"].astype(jnp.float32)


def forward(params: Dict[str, Any], ids, cfg):
    """ids: [T] -> logits [T, V], float32, under the block mask of
    cfg.block_length; row i is position i's own token.  `cfg`: the program's
    TransformerConfig, read for its sizes."""
    t = len(ids)
    for x, _, _ in _blocks(params, ids, np.arange(t), _block_mask(t, cfg.block_length), cfg):
        pass
    return _head(params, x)


def loss(params, ids, cfg) -> float:
    raise NotImplementedError(
        "no training cell runs a model that generates by blocks: its objective (the masked positions of "
        "a noised block given the clean blocks before it) is no next-token cross entropy; LOSS_TOL says so")


# -- what chose a served token ------------------------------------------------------
# A served token was chosen by the logits of one pass of its block, at its own
# position, with the positions fixed in earlier passes of that block holding
# their tokens and the others the mask.  Which pass that was the tokens do not
# say: the batcher keeps it by request id (`cb.fixed_at`), and what a finished
# request's last block held fixed past the answer's end (`cb.block_tail`), which
# the last passes saw.  The replay: the final tokens of the whole stream go
# through `_blocks` once under the block mask, which gives every layer's keys and
# values of every position (and what every layer's experts were given, which
# `mechanism_checks` reads); then every (block, pass) that fixed a served token
# is one group of B rows through `_blocks` again, all groups of a stream at once,
# a group seeing the first sequence's keys and values before its block and its
# own rows.  Sequences are padded on the right to a multiple of ROW_BLOCK (an
# earlier block does not see a later one) and the groups to a multiple of the
# batcher's slots (`_program_choice` lays them out as the step's rows), so that
# a few shapes compile.
ROW_BLOCK = 256
# request id -> what `chosen_logits` computed that `mechanism_checks` reads
_kept: Dict[int, Dict[str, Any]] = {}


def _stream_plan(cb, stream):
    """The stream as its passes saw it.  Returns (final: every position's last
    token through the end of the answer's last block, the mask where none was
    fixed; whole: the prompt's tokens in whole blocks; groups: [(the block's
    first position, the pass, the block's ids before that pass)]; fixed: per
    group [(position in the block, served index or None)] what the program
    fixed in that pass; masked: per group the positions that were masked)."""
    cfg = cb.cfg
    b, mask_id = cfg.block_length, cfg.mask_token_id
    prompt, served = list(stream["prompt_ids"]), [int(t) for t in stream["served"]]
    at = cb.fixed_at(stream["request_id"])
    if len(at) != len(served):
        raise ValueError(f"a record of {len(at)} passes for {len(served)} served tokens")
    n, total = len(prompt), len(prompt) + len(served)
    whole, end = n - n % b, -(-total // b) * b
    # position -> (pass, token): the answer's, then the last block's past its end
    when = {n + j: (p, tok) for j, (p, tok) in enumerate(zip(at, served))}
    when.update({end - b + i: (p, tok) for i, p, tok in cb.block_tail(stream["request_id"])})
    final = prompt + [when[i][1] if i in when else mask_id for i in range(n, end)]
    groups, fixed, masked = [], [], []
    for start in range(whole, end, b):
        inside = range(start, start + b)
        for p in sorted({when[i][0] for i in inside if n <= i < total}):
            groups.append((start, p, [
                final[i] if i < n or (i in when and when[i][0] < p) else mask_id for i in inside]))
            fixed.append([(i - start, i - n if i < total else None)
                          for i in inside if i in when and when[i][0] == p])
            masked.append([i - start for i in inside if i >= n and not (i in when and when[i][0] < p)])
    return final, whole, groups, fixed, masked


def _replay(cb, stream):
    """(every group's logits [groups, B, V], the plan) for one stream, and keeps
    what `mechanism_checks` reads of it."""
    params, cfg = cb.params, cb.cfg
    b = cfg.block_length
    final, whole, groups, fixed, masked = plan = _stream_plan(cb, stream)
    t = -(-len(final) // ROW_BLOCK) * ROW_BLOCK
    first = list(_blocks(params, np.pad(np.asarray(final, np.int32), (0, t - len(final))),
                         np.arange(t), _block_mask(t, b), cfg))
    g = -(-len(groups) // cb.slots) * cb.slots
    starts = np.asarray([s for s, _, _ in groups] + [0] * (g - len(groups)))
    ids = np.asarray([row for _, _, row in groups] + [[0] * b] * (g - len(groups)), np.int32).reshape(-1)
    group_of = np.repeat(np.arange(g), b)
    see = np.concatenate([np.arange(t)[None, :] < np.repeat(starts, b)[:, None],
                          group_of[:, None] == group_of[None, :]], axis=1)
    pos = np.repeat(starts, b) + np.tile(np.arange(b), g)
    for x, _, _ in _blocks(params, ids, pos, see, cfg, before=[kv for _, kv, _ in first]):
        pass
    logits = _head(params, x).reshape(g, b, -1)
    _kept[stream["request_id"]] = {
        "given": [y[:len(final)] for _, _, y in first], "whole": whole, "plan": plan,
        # each masked position's log-confidence: the largest log-probability of its row
        "log_conf": np.asarray(jnp.max(jax.nn.log_softmax(logits, axis=-1), axis=-1))[:len(groups)],
        "program_choice": _program_choice(cb, logits, masked),
    }
    return logits[:len(groups)], plan


@functools.partial(jax.jit, static_argnames=("cfg", "slots"))
def _choice(logits, fixed, live, *, cfg, slots):
    """`_choose_block` over [groups, B, V] as steps of [slots, B, V], temperature 0."""
    from cluster_anywhere_tpu.llm.continuous import _choose_block

    g, b, _ = logits.shape
    step = lambda a: _choose_block(*a, jnp.zeros((slots,), jnp.float32), jax.random.key(0), cfg)[1]
    return lax.map(step, (logits.reshape(g // slots, slots, b, -1), fixed.reshape(g // slots, slots, b),
                          live.reshape(g // slots, slots))).reshape(g, b)


def _program_choice(cb, logits, masked) -> List[List[int]]:
    """What the program's own rule (`llm/continuous.py` `_choose_block`, which
    the step's program runs on its logits) fixes when it is given this
    reference's logits of every replayed pass: [groups, B, V] laid out as steps
    of [slots, B, V], the groups past the last not live, temperature 0.  Returns
    the positions it fixed, a group at a time."""
    fixed = np.ones(logits.shape[:2], bool)
    for i, open_ in enumerate(masked):
        fixed[i, open_] = False
    fix = np.asarray(_choice(logits, fixed, np.arange(len(fixed)) < len(masked), cfg=cb.cfg, slots=cb.slots))
    return [np.nonzero(row)[0].tolist() for row in fix[:len(masked)]]


def chosen_logits(cb, stream) -> np.ndarray:
    """Row j: the logits of the pass that fixed served[j], at its position."""
    logits, (_, _, _, fixed, _) = _replay(cb, stream)
    pick = sorted((j, g, i) for g, row in enumerate(fixed) for i, j in row if j is not None)
    assert [j for j, _, _ in pick] == list(range(len(stream["served"]))), "a served token no pass fixed"
    return np.asarray(logits[np.asarray([g for _, g, _ in pick]), np.asarray([i for _, _, i in pick])])


def program_logits(cb, stream):
    """The program's own first pass over the first block of the answer (the
    prompt's whole blocks through the batcher's prefill program of their bucket,
    then one pass of the block against those rows, as the step's program computes
    it before it chooses), held to the rows of the tokens that pass fixed."""
    from cluster_anywhere_tpu.llm.continuous import _pass_logits
    from cluster_anywhere_tpu.models.generate import init_cache, prefill

    cfg, prompt = cb.cfg, np.asarray(stream["prompt_ids"], np.int32)
    b, served = cfg.block_length, stream["served"]
    whole, bucket, pad = cb.block_plan(len(prompt), len(served))
    if whole:
        padded = np.zeros((1, bucket), np.int32)
        padded[0, pad:] = prompt[:whole]
        _, rows = prefill(cb.params, padded, cfg, cb.t_max, pad=np.asarray([pad], np.int32))
    else:
        rows = init_cache(cfg, 1, cb.t_max)
    tail = len(prompt) - whole
    ids = np.full((1, b), cfg.mask_token_id, np.int32)
    ids[0, :tail] = prompt[whole:]
    logits, _ = _pass_logits(cb.params, rows, ids, np.asarray([bucket], np.int32),
                                   np.asarray([pad], np.int32), cfg=cfg)
    at = cb.fixed_at(stream["request_id"])
    first = [j for j in range(min(b - tail, len(served))) if at[j] == 0]
    return np.asarray(logits[0], np.float32)[[tail + j for j in first]], first


# -- counts from shapes ---------------------------------------------------------
# `c` is the `config` object of a configuration file (the published keys as run).


def _dims(c: Dict[str, Any]):
    return (c["hidden_size"], c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"],
            c["moe_intermediate_size"], c["num_hidden_layers"], c["vocab_size"], c["num_experts"],
            c["num_experts_per_tok"])


def _attention_params(c: Dict[str, Any]) -> int:
    """wq, wk, wv, wo and the two norms over a head of q and of k."""
    e, h, kv, d = _dims(c)[:4]
    return e * h * d + 2 * e * kv * d + h * d * e + 2 * d


def expert_params(c: Dict[str, Any]) -> int:
    """One expert's three matrices."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def expert_bytes(c: Dict[str, Any], bytes_per: int = 2) -> int:
    """What reading one expert of one layer costs."""
    return expert_params(c) * bytes_per


def param_count(c: Dict[str, Any]) -> int:
    e, _, _, _, _, L, V, X, _ = _dims(c)
    per_layer = _attention_params(c) + e * X + X * expert_params(c) + 2 * e
    return L * per_layer + 2 * V * e + e


def train_flops_per_step(c: Dict[str, Any], batch: int, seq: int) -> float:
    """Operations a forward and a backward pass over `batch` sequences of `seq`
    tokens require (2 per multiply-add over the weights a token meets: its k
    experts and the router; attention in full, the 4*t*t*d*h square; backward
    twice the forward), whatever objective the logits are held to."""
    e, h, kv, d, _, L, V, X, k = _dims(c)
    matmul_weights = e * h * d + 2 * e * kv * d + h * d * e + e * X + k * expert_params(c)
    fwd = batch * seq * 2 * matmul_weights * L + batch * 4 * seq * seq * d * h * L + batch * seq * 2 * e * V
    return 3.0 * fwd


def experts_touched(c: Dict[str, Any], rows: int) -> float:
    """The experts of one layer that `rows` positions read between them if
    each takes its k at random: X (1 - (1 - k/X)^rows)."""
    X, k = c["num_experts"], c["num_experts_per_tok"]
    return X * (1.0 - (1.0 - k / X) ** rows)


def decode_step_bytes(c: Dict[str, Any], slots: int, t_max: int, bytes_per: int = 2, touched=None) -> int:
    """Bytes one pass at [slots, B] has to read at the least: every weight
    outside the experts once (the embedding only its slots x B rows), of each
    layer's experts those that were given a row (`touched`, a layer's mean as a
    step counts it; where it is not given, what slots x B positions would touch
    if each took its k at random, which is an upper bound: a block's masked
    positions hold one token and differ by their rotary angle alone, so they
    choose much the same experts), and the whole key/value cache, which the
    program attends over in full whatever the rows' depths."""
    e, _, kv, d, _, L, V, X, _ = _dims(c)
    b = c["block_length"]
    outside = L * (_attention_params(c) + e * X + 2 * e) + V * e + e + slots * b * e
    experts = L * (experts_touched(c, slots * b) if touched is None else touched) * expert_params(c)
    cache = 2 * L * slots * t_max * kv * d
    return int((outside + experts + cache) * bytes_per)


# -- tolerances ------------------------------------------------------------------
# harness/reference.py says which program each of the three serving tolerances
# holds.  Each is set from two readings on the chip at the published widths, 7
# layers (PERF.md section 6, PR 34), both taken as the cell's check takes them:
# the four check streams of traffic/chat-closed-blocks.json served together, 256
# served tokens, their passes replayed through this reference in float32.  The
# first is the largest the program (bf16 weights and activations, float32 router
# softmax and confidences) gave over 50 checks on 50 seeds (28 through a batcher
# alone in one process, `_archive/precision34.py`; 22 in the cell).  The second
# is the nearest precision below bf16: every matrix rounded to float8 e4m3, the
# streams served from the rounded matrices by the program itself (its own passes,
# its own record) and held to this reference over the bf16 matrices, 12 to 28
# seeds.  It has to come out as not correct, and does in all 28, by the logits (25
# of 25) and by the mean regret (28 of 28).
#
# bf16 is louder here than in OLMoE's cell (logits 0.04-0.11 there): the router's
# eight probabilities are renormalised, so an expert enters the stream times 1/8
# and not times 0.02-0.05, a near-tie of the router at rank 8/9 (about one (row,
# layer) pair in a hundred between a bf16 stream and a float32 one) swaps an
# eighth of a layer's result, and the largest of 151,936 logits' errors is taken
# where OLMoE's vocabulary has 50,304.
#
# Logits, the program's first pass of the first answer block against the rows of
# the tokens that pass fixed: the program 0.152-0.581 in 49 checks and 0.645 in one
# (a largest error over 151,936 logits has a tail: a Gumbel fitted to the 50 puts
# one run in 70 over 0.72, the geometric mean of the two readings, which the
# driver's dozens of runs would meet); float8 0.891-1.338 (25 seeds).  The bound is
# 1.32 x over the program's largest and 1.05 x under float8's least: the room is
# given to the program, because the mean regret is what fails float8 with room.
LOGIT_TOL = 0.85
# The regret of the served tokens.  Its mean over the 256: the program
# 0.0021-0.0132 (50 checks), float8 0.040-0.180 in 28 seeds of 28: the bound is the geometric
# mean of 0.0132 and 0.040, 1.74 x from either.  This and the logits are what
# fail float8 in every seed.  The largest of 256 regrets is the weakest of the
# statistics, as in OLMoE's file and more so: a flip costs the distance between
# the best two logits, and noise of 0.4 flips pairs 0.4 apart.  The program read
# 0.103-0.347 in 47 checks and 0.366, 0.457 and 0.608 in three (seeds 3400000021,
# -22, -64: means 0.0078, 0.0067, 0.0111, nothing else of those runs apart);
# float8 0.470-1.124: the two overlap, and no bound passes the program and fails
# float8.  What this one is there for is a served token that is not among the
# reference's best few (a wrong row, a wrong position, a wrong pass): the best
# and a token drawn at random lie 4.5 apart at these logits, 3 and more in the
# CPU test that plants one (tests/benchmark/test_benchmark_sdar.py).  The bound
# is 1.64 x over the program's largest and 3 x under that.
REGRET_MAX_TOL = 1.0
REGRET_MEAN_TOL = 0.023
# No training cell runs this architecture (`loss` says why); the dense decoder's
# bound stands in the interface.
LOSS_TOL = 0.01


# -- what the logits cannot see ---------------------------------------------------
# The expert layer by itself, as references/olmoe.py holds OLMoE's (its comment
# says what the two numbers are and how the probe experts read the program's
# choice), in this program's shapes: each prompt's whole blocks alone
# [1, bucket, E], left-padded, the pads not live, and the answer's blocks as
# passes of [slots, 8, E], a stream a slot, the block a pass stores before the
# block it works on, the other slots and a first pass's first half not live
# (`program_shapes`; tests/benchmark/test_benchmark_sdar.py holds that a batcher
# serving the streams traces `transformer._moe` with exactly these shapes).  Rows:
# what every layer's experts are given at every position of the streams' final
# tokens, 7,112 (row, layer) pairs.  Readings (50 checks of the program; the
# controls planted in `routed_ffn` once the streams are served, 15 seeds each,
# `ok` false in all 15 with the three numbers on the logits the program's own):
#   the router: the program 0 of 7,112 in all 50; the softmax in bf16 2.74-3.68%.
#   The bound allows 35 pairs and lies 5.5 x under the control's least.
#   the experts: the program 0.00450-0.00541; the experts' matrices rounded to
#   float8 e4m3 0.0609-0.0721.  3.7 x over the program's largest, 3.0 x under
#   float8's least.  (The bf16 softmax reads 0.0101-0.0142 here.)
PROBE_WIDTH = _moe_ref.PROBE_WIDTH
MOE_ROUTER_SET_TOL = 0.005
MOE_EXPERTS_ERR_TOL = 0.02
# The order of the reveal: which masked positions a pass fixed, which the regrets
# of the tokens cannot see (each token is the best of the pass that fixed it
# whatever the order).  `_reveal_regrets` measures a choice in this reference's own
# log-confidences of the pass's masked positions.  Two numbers:
#   reveal_regret_max   of what the served program fixed, by the batcher's record:
#       the largest over the four streams of a stream's MEAN over its replayed
#       passes.  The largest of a single pass is bf16's noise and tells nothing:
#       four masked positions hold one token and differ by their rotary angle, their
#       confidences lie 0.4-0.5 apart in all, and the program's own logits move by
#       as much: it read 0.093-0.416 from the program (50 checks) against 0.375-0.636
#       from a step that fixes the LEAST confident position (25 seeds).  The mean
#       over a stream's 64 passes, its largest over the streams (13 seeds each, the
#       control serving the streams itself; the program on 20): the program
#       **0.0039-0.0154**; the least
#       confident first **0.096-0.156**; the bound 2.6 x over the one and 2.4 x under
#       the other.  (Float8 in every matrix reads 0.039-0.079 here, the log-sum-exp
#       in bf16 0.0038-0.0178: the program's own range.)
#   choose_regret_max   of what the program's rule (`llm/continuous.py`
#       `_choose_block`, the function the step's program runs on its logits) fixes
#       when it is given this reference's own logits of every replayed pass, laid
#       out as the step's [slots, 4, V] with the groups past the last not live:
#       the largest of a single pass.  The program **0 in all 48** (a maximum and a
#       log-sum-exp in float32 on the same logits order four positions as this
#       file's log_softmax does); the least confident first 0.394-0.644 (25 seeds).
#       The confidences' log-sum-exp in bf16 reads 0-0.0040 (0 in 7 seeds of 28, median
#       0.0009): the four log-sum-exps of a block agree to 0.01 and the order is the
#       largest logits', so bf16 there costs next to nothing and no bound here
#       tells it from float32 in every seed (PERF.md section 7).
REVEAL_REGRET_TOL = 0.04
CHOOSE_REGRET_TOL = 0.02


def program_shapes(cb, streams):
    """How the rows of the check streams (stream by stream, every position of a
    stream through the end of its answer's last block) lie in the calls of
    `_moe` that serving the streams together makes.  Returns (prefills: a
    (first row, rows, pads on the left) a stream whose prompt has a whole block;
    passes: [steps, slots, 2B], the row a slot's position holds at a step, or
    the number of rows where the position is not live: a pass runs the block
    before in its first half, which it stores, and the block itself in its
    second (`llm/continuous.py _pass_step_rowpos`), so a slot's first pass and a
    slot that is not live have the first half dead; read: [steps, slots, 2B],
    the one place of the two a row lies in whose result is read, where the pass
    stores it, and a stream's last block, which no pass stores, in its own; that
    number)."""
    assert len(streams) <= cb.slots, "the check streams are served together, a slot each"
    b = cb.cfg.block_length
    kept = [_kept[s["request_id"]] for s in streams]
    lengths = [len(k["given"][0]) for k in kept]
    first = np.cumsum([0] + lengths)
    prefills = [(int(off), k["whole"], cb.block_plan(len(s["prompt_ids"]), len(s["served"]))[2])
                for off, k, s in zip(first, kept, streams) if k["whole"]]
    steps = [(n - k["whole"]) // b for n, k in zip(lengths, kept)]
    passes = np.full((max(steps), cb.slots, 2 * b), first[-1], np.int32)
    read = np.zeros(passes.shape, bool)
    for slot, (off, k, t) in enumerate(zip(first, kept, steps)):
        blocks = (off + k["whole"] + np.arange(t * b)).reshape(t, b)
        passes[:t, slot, b:], passes[1:t, slot, :b] = blocks, blocks[:-1]
        read[1:t, slot, :b], read[t - 1, slot, b:] = True, True
    return prefills, passes, read, int(first[-1])


def _program(cfg, prefills, passes, read, n: int):
    """The compiled program of one layer's calls of `_moe`, laid out by
    `program_shapes`: (given: [stream] of [T, E] float32, every layer's routers,
    every layer's experts, the layer's index) -> (the n rows as a block hands
    them over, in the activations' type; what `_moe` makes of them in the
    prefill's and the pass's shapes; which experts it gave each to:
    references/olmoe.py says how the probe experts tell)."""
    from cluster_anywhere_tpu.models.transformer import _moe

    held = np.nonzero(read.reshape(-1))[0]  # the (step, slot, position) triples a row's result is read at
    at = passes.reshape(-1)[held]

    @jax.jit
    def program(given, routers, experts, layer):
        rows = jnp.concatenate(given).astype(cfg.dtype)
        bp = {"router": routers[layer]}
        probe = _moe_ref._probe_experts(cfg.d_model, cfg.n_experts, cfg.dtype)

        def both(y, live):
            return (_moe(bp, y, cfg, live, (experts, layer))[0],
                    _moe(bp, y, cfg, live, (probe, 0))[0][..., :cfg.n_experts] != 0)

        got, chosen = jnp.zeros_like(rows), jnp.zeros((n, cfg.n_experts), bool)
        for off, t, pad in prefills:
            out, marks = both(jnp.pad(rows[off:off + t], ((pad, 0), (0, 0)))[None],
                              jnp.asarray(np.arange(pad + t) >= pad)[None])
            got, chosen = got.at[off:off + t].set(out[0, pad:]), chosen.at[off:off + t].set(marks[0, pad:])
        steps = jnp.pad(rows, ((0, 1), (0, 0)))[passes]  # [steps, slots, 2B, E]
        out, marks = lax.map(lambda step: both(*step), (steps, jnp.asarray(passes < n)))
        got = got.at[at].set(out.reshape(-1, out.shape[-1])[held])
        return rows, got, chosen.at[at].set(marks.reshape(-1, cfg.n_experts)[held])

    return program


def _reveal_regrets(cfg, kept, fixed_by: str) -> np.ndarray:
    """For every replayed pass of one stream, how far a choice of what to fix
    lies from this reference's, in this reference's own log-confidences of that
    pass's masked positions.  fixed_by "record": what the served program fixed
    in that pass, by the batcher's record; "program_choice": what the program's
    rule fixes on this reference's own logits (`_program_choice`).  Where m = B
    / steps positions were fixed (the rule's second arm: no confidence, or just
    m, passed the threshold): the reference's m-th largest log-confidence less
    its smallest at a fixed position.  Where more were (the first arm): how far
    the smallest at a fixed position lies under the threshold, and the largest
    at a position left masked over it.  A pass that fixed nothing: infinity."""
    m, thr = cfg.block_length // cfg.denoise_steps, float(np.log(cfg.confidence_threshold))
    _, _, _, fixed, masked = kept["plan"]
    choices = [[i for i, _ in now] for now in fixed] if fixed_by == "record" else kept[fixed_by]
    out = []
    for conf, now, open_ in zip(kept["log_conf"], choices, masked):
        if not now:
            out.append(np.inf)
        elif len(now) <= m:
            out.append(np.sort(conf[open_])[::-1][min(m, len(open_)) - 1] - conf[now].min())
        else:
            left = [i for i in open_ if i not in now]
            out.append(max(0.0, thr - conf[now].min(), conf[left].max() - thr if left else 0.0))
    return np.asarray(out, np.float64)


def mechanism_checks(cb, streams):
    """The expert layer by itself, as references/olmoe.py checks it and in this
    program's shapes (`program_shapes`), over every layer and every position of
    the check streams; and the order of the reveal (`_reveal_regret`), of the
    served program and of its rule by itself, over every replayed pass."""
    from cluster_anywhere_tpu.parallel.moe import EXPERT_MATRICES

    params, cfg = cb.params, cb.cfg
    for s in streams:
        if s["request_id"] not in _kept:
            _replay(cb, s)
    prefills, passes, read, n = program_shapes(cb, streams)
    kept = [_kept.pop(s["request_id"]) for s in streams]
    program = _program(cfg, prefills, passes, read, n)
    experts = {name: params["blocks"][name] for name in EXPERT_MATRICES if name in params["blocks"]}
    numbers = []
    for layer in range(cfg.n_layers):
        if numbers:
            jax.block_until_ready(numbers[-1])  # one layer's copy out of the stack at a time
        rows, got, chosen = program([k["given"][layer] for k in kept], params["blocks"]["router"], experts, layer)
        numbers.append(_moe_ref._layer_errors(rows, _moe_ref._layer_of(params, layer), got, chosen,
                                              k=cfg.n_experts_per_tok, renormalize=bool(cfg.moe_renormalize)))
    other_sets, worst = (np.asarray(x) for x in zip(*numbers))
    pairs = n * len(numbers)
    served, by_rule = ([_reveal_regrets(cfg, k, by) for k in kept] for by in ("record", "program_choice"))
    return [
        {"name": "moe_router_other_set", "error": int(other_sets.sum()) / pairs, "tolerance": MOE_ROUTER_SET_TOL,
         "why": f"(row, layer) pairs of {pairs} in which the program's set of {cfg.n_experts_per_tok} is not "
                "the float32 reference's"},
        {"name": "moe_experts_rel_err", "error": float(worst.max()), "tolerance": MOE_EXPERTS_ERR_TOL,
         "why": "largest relative error of a row's expert-layer result, over the rows whose sets agree"},
        {"name": "reveal_regret_max", "error": max(float(r.mean()) for r in served),
         "tolerance": REVEAL_REGRET_TOL,
         "why": "largest over the streams of a stream's mean, over its replayed passes, of the distance in the "
                "reference's log-confidences between what the rule would have fixed and what the served "
                "program fixed (the largest of a single pass read "
                f"{max(float(r.max()) for r in served):.4f})"},
        {"name": "choose_regret_max", "error": max(float(r.max()) for r in by_rule),
         "tolerance": CHOOSE_REGRET_TOL,
         "why": "largest such distance of a single pass for what the program's rule fixes when given the "
                "reference's own logits of every replayed pass"},
    ]
