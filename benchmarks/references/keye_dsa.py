"""Keye-VL-2.0-30B-A3B's language model (Kwai-Keye; `model_type` KeyeVL2): a
Qwen3-MoE decoder whose attention carries DeepSeek Sparse Attention's indexer.
The interface is the package's (references/__init__.py).

A layer, with h = RMSNorm(x) (epsilon 1e-6):

    q  = W_q h as 32 heads x 128, k = W_k h, v = W_v h as 4 heads x 128; an
         RMSNorm with one weight [128] on every head of q and of k; rotary over
         all 128 dimensions at rope_theta (adjacent pairs)
    qI = W_qI h as 16 heads x 64;  kI = LayerNorm(W_kI h), one head of 64
         (weight and bias, epsilon 1e-6); rotary on qI and kI at the model's theta
    w  = W_w h (16 numbers) times 16^-1/2 * 64^-1/2
    I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])          s <= t, float32
    S_t = the min(topk, t + 1) positions s <= t of largest I[t, s]; equal scores
          go to the lower position; one set for all 32 heads
    o_t = sum_{s in S_t} softmax_{s in S_t}(q_t . k_s / sqrt(128)) v_s, eight
          query heads a cached head; then W_o;  x += that
    p   = softmax(RMSNorm(x) W_r) over all 128 experts, float32; the 8 largest,
          renormalised over the 8;  x += sum of p_e (silu(y Wg_e) * (y Wu_e)) Wd_e
          over the experts HELD here (`cfg.experts_held`: the others' part is left
          out, in the program and here alike)

The equations are DeepSeek-V3.2-Exp's (its report and `inference/model.py`, class
`Indexer`) on the sizes of this model's `sa_config`; what the published config
does not settle is listed in the configuration's file under `assumed`, and what
is left out (the Hadamard rotation and float8 of qI and kI, image positions)
under `departures`.

The plain reference is straightforward `jax.numpy` in float32 at `highest` matmul
precision: the selection is the ranks of a stable `jnp.argsort`, no kernel, no
cache, no batching, none of `cluster_anywhere_tpu/models/` or `ops/`.  Layers run
one at a time in a Python loop; inside a layer attention runs by blocks of
ATTN_BLOCK queries, so that [32, T, T] never stands, and the experts one at a
time.  (`mechanism_checks`, at the end, calls the program's own functions as what
it checks, not as a reference.)
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

RMS_EPS = 1e-6
LN_EPS = 1e-6
ATTN_BLOCK = 256  # query rows a block: bounds the [heads, block, T] scores (0.27 GB at 8,192 keys)
# what this architecture's programs write beyond the common names (program_trace.SCOPES): the mixture's four
# under `ffn` (parallel/moe.py routed_ffn); the indexer's three in every program, an admit's prefill and a decode
# step alike (the projections, the norm and the scores; the selection; attention under it): a trace tells a step's
# scan of the cached indexer keys, its top-k and its gather from an admit's kernels by the program they ran in
# (layer_metrics/readers/dsa.py)
SCOPES = ("moe.router", "moe.dispatch", "moe.experts", "moe.combine", "attn.indexer", "attn.select", "attn.sparse_core")
# the grouped matmul (`lax.ragged_dot`: olmoe.py says why it is known by name) and the three kernels of
# ops/sparse_attention.py
KERNELS = ("ragged-dot-none", "dsa_index", "dsa_select", "dsa_flash")

PROGRAM_FIELDS = ("index_topk", "index_n_heads", "index_head_dim", "experts_held")


def program_config(config_file: Dict[str, Any], **extra) -> Dict[str, Any]:
    """The program's TransformerConfig fields from a configuration file's keys.
    `num_experts` counts the experts HELD; the router's width is
    `num_experts_routed` and the share starts at `experts_held_first`.  A
    program that lacks the indexer's fields cannot run the configuration:
    refused here, by name, before anything is deployed."""
    from cluster_anywhere_tpu.models.transformer import TransformerConfig

    lacking = sorted(set(PROGRAM_FIELDS) - {f.name for f in dataclasses.fields(TransformerConfig)})
    if lacking:
        raise NotImplementedError(
            f"this program's TransformerConfig has no {lacking}: it serves no learned sparse attention (an indexer, "
            "a selection inside the cache's read), and this configuration cannot run on it")
    c = config_file["config"]
    sa = c["sa_config"]
    if sa["indexer_num_kv_heads"] != 1 or c["rope_scaling"]["rope_type"] != "default":
        raise ValueError("the program and this file write one indexer key head and no scaled rotary frequencies")
    out = dict(
        d_model=c["hidden_size"], n_layers=c["num_hidden_layers"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], d_head=c["head_dim"], d_ff=c["intermediate_size"],
        rope_theta=float(c["rope_theta"]), max_seq_len=c["max_position_embeddings"], norm_eps=float(c["rms_norm_eps"]),
        qk_norm=True, qk_norm_per_head=True, d_expert=c["moe_intermediate_size"],
        n_experts=c["num_experts_routed"], n_experts_per_tok=c["num_experts_per_tok"], moe_gated=True,
        moe_renormalize=bool(c["norm_topk_prob"]), experts_held=(c["experts_held_first"], c["num_experts"]),
        index_topk=sa["topk"], index_n_heads=sa["indexer_num_heads"], index_head_dim=sa["indexer_head_dim"],
    )
    out.update(extra)
    return out


def _rms_norm(x, w):
    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + RMS_EPS)) * w


def _layer_norm(x, w, b):
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + LN_EPS)) * w + b


def _rope(x, theta: float):
    """x: [T, H, D] at positions 0 .. T - 1: adjacent pairs turned over all D."""
    t, _, d = x.shape
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]  # [T, D/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1).reshape(x.shape)


def _experts(y, lp, k: int, renormalize: bool, held):
    """The mixture's part of the experts held here, for y [T, E]: the router's
    k largest probabilities of each token over ALL experts, then one held
    expert after the other over every token.  held: (first, count)."""
    probs = jax.nn.softmax(y @ lp["router"].astype(jnp.float32), axis=-1)  # [T, X]
    top, idx = lax.top_k(probs, k)
    if renormalize:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    weight = jnp.sum(jax.nn.one_hot(idx, probs.shape[-1], dtype=jnp.float32) * top[..., None], axis=1)
    weight = weight[:, held[0]:held[0] + held[1]]

    def one_expert(acc, e):
        wg, wu, wd, w_e = e
        wg, wu, wd = (w.astype(jnp.float32) for w in (wg, wu, wd))
        return acc + w_e[:, None] * ((jax.nn.silu(y @ wg) * (y @ wu)) @ wd), None

    out, _ = lax.scan(one_expert, jnp.zeros_like(y), (lp["w_gate"], lp["w_up"], lp["w_down"], weight.T))
    return out


def _qkv(y, lp, dims):
    """(q [T, H, D], k, v [T, KV, D]) of a layer's normed input y [T, E], q and k normed a head and turned."""
    h, kv, d, theta = dims[:4]
    f32 = lambda name: lp[name].astype(jnp.float32)
    t = y.shape[0]
    q = _rms_norm((y @ f32("wq")).reshape(t, h, d), f32("q_norm"))
    k = _rms_norm((y @ f32("wk")).reshape(t, kv, d), f32("k_norm"))
    return _rope(q, theta), _rope(k, theta), (y @ f32("wv")).reshape(t, kv, d)


def _index(y, lp, dims):
    """The indexer's (qI [T, HI, DI], kI [T, DI], w [T, HI]) of a layer's normed input y [T, E]."""
    theta, hi, di = dims[3], dims[4], dims[5]
    f32 = lambda name: lp[name].astype(jnp.float32)
    t = y.shape[0]
    qi = _rope((y @ f32("wq_idx")).reshape(t, hi, di), theta)
    ki = _rope(_layer_norm(y @ f32("wk_idx"), f32("k_idx_norm"), f32("k_idx_norm_b"))[:, None, :], theta)[:, 0]
    return qi, ki, (y @ f32("w_idx")) * (hi ** -0.5 * di ** -0.5)


def _scores(qi, ki, w, lo: int):
    """I[t, s] [rows, S] for the queries qi [rows, HI, DI], w [rows, HI] at positions lo .. against the keys ki
    [S, DI] at 0 ..: -inf where s > t."""
    scores = jnp.sum(w[:, :, None] * jnp.maximum(jnp.einsum("qhd,kd->qhk", qi, ki), 0.0), axis=1)
    causal = (lo + jnp.arange(qi.shape[0]))[:, None] >= jnp.arange(ki.shape[0])[None, :]
    return jnp.where(causal, jnp.where(scores == 0.0, 0.0, scores), -jnp.inf), causal


def _selected(scores, causal, topk: int):
    """S_t as a mask [rows, S]: the ranks of a stable descending argsort; ties to the lower position."""
    rank = jnp.argsort(jnp.argsort(-scores, axis=-1, stable=True), axis=-1)
    return causal & (rank < topk)


def _attend(q, k, v, seen):
    """q [rows, H, D] against k, v [S, KV, D] under seen [rows, S]: eight query heads a cached head."""
    h, kv = q.shape[1], k.shape[1]
    k, v = jnp.repeat(k, h // kv, axis=1), jnp.repeat(v, h // kv, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k) * q.shape[-1] ** -0.5
    p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
    return jnp.einsum("hqk,khd->qhd", p, v)


def _sparse_attention(y, lp, dims, topk: int):
    """A layer's attention result before W_o [T, H * D] from its normed input y [T, E], by blocks of queries."""
    t = y.shape[0]
    q, k, v = _qkv(y, lp, dims)
    qi, ki, w = _index(y, lp, dims)
    outs = []
    for lo in range(0, t, ATTN_BLOCK):
        hi = min(t, lo + ATTN_BLOCK)
        scores, causal = _scores(qi[lo:hi], ki[:hi], w[lo:hi], lo)
        outs.append(_attend(q[lo:hi], k[:hi], v[:hi], _selected(scores, causal, topk)))
    return jnp.concatenate(outs, axis=0).reshape(t, -1)


@functools.partial(jax.jit, static_argnames=("dims",))
def _layer(x, lp, *, dims):
    """One block over one sequence.  x: [T, E] float32; lp: this layer's weights in whatever type they are
    stored in.  Returns (the block's output, the attention half's normed input [T, E])."""
    topk, k, renormalize, held = dims[6:]
    f32 = lambda name: lp[name].astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        y = _rms_norm(x, f32("ln1"))
        x = x + _sparse_attention(y, lp, dims, topk) @ f32("wo")
        return x + _experts(_rms_norm(x, f32("ln2")), lp, k, renormalize, held), y


def _dims_of(cfg):
    return (cfg.n_heads, cfg.n_kv_heads, cfg.d_head, float(cfg.rope_theta), cfg.index_n_heads, cfg.index_head_dim,
            cfg.index_topk, cfg.n_experts_per_tok, bool(cfg.moe_renormalize),
            tuple(cfg.experts_held or (0, cfg.n_experts)))


def _layer_of(params, i: int):
    return jax.tree_util.tree_map(lambda w: w[i], params["blocks"])


def _blocks(params: Dict[str, Any], ids, cfg):
    """ids: [T] through the stack.  Yields, a layer at a time, (the block's output [T, E], the attention half's
    normed input [T, E])."""
    dims = _dims_of(cfg)
    x = params["embed"][jnp.asarray(ids)].astype(jnp.float32)
    for i in range(params["blocks"]["wq"].shape[0]):
        x, y = _layer(x, _layer_of(params, i), dims=dims)
        yield x, y


@jax.jit
def _head(params, x):
    with jax.default_matmul_precision("highest"):
        return _rms_norm(x, params["ln_f"].astype(jnp.float32)) @ params["lm_head"].astype(jnp.float32)


def forward(params: Dict[str, Any], ids, cfg):
    """ids: [T] -> logits [T, V], float32.  `cfg`: the program's TransformerConfig, read for sizes only."""
    for x, _ in _blocks(params, ids, cfg):
        pass
    return _head({k: params[k] for k in ("ln_f", "lm_head")}, x)


def selected_sets(params: Dict[str, Any], ids, cfg) -> np.ndarray:
    """bool [layers, T, T]: S_t of every layer as this reference's full pass selects (tests hold the program's
    masks to it at a tiny size)."""
    dims, out = _dims_of(cfg), []
    with jax.default_matmul_precision("highest"):
        for i, (_, y) in enumerate(_blocks(params, ids, cfg)):
            qi, ki, w = _index(y, _layer_of(params, i), dims)
            out.append(np.asarray(_selected(*_scores(qi, ki, w, 0), cfg.index_topk)))
    return np.stack(out)


def loss(params, ids, cfg) -> float:
    """Mean next-token cross entropy of one sequence ids[:-1] -> ids[1:]."""
    ids = jnp.asarray(ids)
    logits = forward(params, ids[:-1], cfg)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, ids[1:, None], axis=-1)[:, 0]
    return float(jnp.mean(logz - gold))


# -- what chose a served token ------------------------------------------------------
# One causal token a step from the last position's logits, the harness's default, by this file's own pass: the
# same pass says what the attention half of the checked layers was given, which `mechanism_checks` reads again.
# A stream is padded on the right to a multiple of ATTN_BLOCK (a causal model's earlier positions do not see
# what follows) and the head takes the rows that chose a token alone.
#
# The layers `mechanism_checks` looks into: every MECH_STRIDE-th and the last.  A layer's rows are 8,000 x 2,048
# a stream; all 48 layers' would be 6 GB on the host and 48 x 4 passes of two programs each.  The sparse path
# reads nothing by a layer's index but the cache's stacks, which the served tokens go through at every layer.
MECH_STRIDE = 8
# a stream's ids -> {layer: [T, E] in the activations' type, on the host}: kept by `chosen_logits` until
# `mechanism_checks` takes it
_given: Dict[bytes, Dict[int, np.ndarray]] = {}


def _stream_ids(stream) -> np.ndarray:
    return np.asarray(stream["prompt_ids"] + stream["served"][:-1], np.int32)


def mech_layers(n_layers: int) -> List[int]:
    return sorted(set(range(0, n_layers, MECH_STRIDE)) | {n_layers - 1})


def _given_of(params, ids, cfg):
    """(the last block's output [T, E], {checked layer: its attention half's normed input, rounded to the
    program's activation type, on the host})."""
    keep, given = mech_layers(cfg.n_layers), {}
    for i, (x, y) in enumerate(_blocks(params, np.pad(ids, (0, -len(ids) % ATTN_BLOCK)), cfg)):
        if i in keep:
            given[i] = np.asarray(y[:len(ids)].astype(cfg.dtype))
    return x[:len(ids)], given


def chosen_logits(cb, stream) -> np.ndarray:
    """Row i: the logits at position len(prompt) - 1 + i of prompt + served[:-1], which chose served[i]."""
    ids, n = _stream_ids(stream), len(stream["prompt_ids"])
    x, _given[ids.tobytes()] = _given_of(cb.params, ids, cb.cfg)
    return np.asarray(_head({k: cb.params[k] for k in ("ln_f", "lm_head")}, x[n - 1:]))


# -- counts from shapes ---------------------------------------------------------
# `c` is the `config` object of a configuration file: the published keys as run (`num_experts` the experts HELD).


def _attention_params(c: Dict[str, Any]) -> int:
    """wq, wk, wv, wo and the two norms a head."""
    e, h, kv, d = c["hidden_size"], c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    return e * h * d + 2 * e * kv * d + h * d * e + 2 * d


def indexer_params(c: Dict[str, Any]) -> int:
    """W_qI, W_kI, the LayerNorm's weight and bias, W_w."""
    e, sa = c["hidden_size"], c["sa_config"]
    hi, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    return e * hi * di + e * di + 2 * di + e * hi


def expert_params(c: Dict[str, Any]) -> int:
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def _routed(c: Dict[str, Any]) -> int:
    return c.get("num_experts_routed", c["num_experts"])


def param_count(c: Dict[str, Any]) -> int:
    """The parameters this chip holds: `num_experts` of them a layer (96,899,456 a layer and 4,729 M in all at the
    cell's cut; 625.4 M a layer and 30.6 B with all 128 and the whole vocabulary)."""
    e = c["hidden_size"]
    per_layer = _attention_params(c) + indexer_params(c) + e * _routed(c) + c["num_experts"] * expert_params(c) + 2 * e
    return c["num_hidden_layers"] * per_layer + 2 * c["vocab_size"] * e + e


def causal_pairs(n: int) -> int:
    return n * (n + 1) // 2


def selected_pairs(n: int, topk: int) -> int:
    """sum over t < n of min(t + 1, topk)."""
    m = min(n, topk)
    return m * (m + 1) // 2 + (n - m) * topk


def dsa_prefill_flops(c: Dict[str, Any], n: int) -> float:
    """The operations the mathematics asks of a prompt of n tokens between a layer's projections and W_o, over all
    layers: 2 x HI x DI a scored (query, key) pair (every causal pair is scored) and 4 x H x D a selected pair (the
    scores and the weighted values).  The selection itself is comparisons, not counted."""
    sa = c["sa_config"]
    scored = 2.0 * sa["indexer_num_heads"] * sa["indexer_head_dim"] * causal_pairs(n)
    attended = 4.0 * c["num_attention_heads"] * c["head_dim"] * selected_pairs(n, sa["topk"])
    return c["num_hidden_layers"] * (scored + attended)


def selected_row_bytes(c: Dict[str, Any], bytes_per: int = 2) -> int:
    """A selected position's keys and values in one layer."""
    return 2 * c["num_key_value_heads"] * c["head_dim"] * bytes_per


def index_key_bytes(c: Dict[str, Any], bytes_per: int = 2) -> int:
    """A cached position's indexer key in one layer."""
    return c["sa_config"]["indexer_head_dim"] * bytes_per


def train_flops_per_step(c: Dict[str, Any], batch: int, seq: int) -> float:
    """Forward and backward of `batch` sequences of `seq` tokens: 2 per multiply-add over the weights a token meets
    (its k experts, the router, the indexer), the indexer's scores of every causal pair and attention over the
    selected pairs, backward twice the forward.  No cell trains this architecture."""
    e, k = c["hidden_size"], c["num_experts_per_tok"]
    weights = (_attention_params(c) + indexer_params(c) + e * _routed(c) + k * expert_params(c))
    fwd = batch * seq * 2.0 * (weights * c["num_hidden_layers"] + e * c["vocab_size"]) + batch * dsa_prefill_flops(c, seq)
    return 3.0 * fwd


def experts_touched(c: Dict[str, Any], rows: int) -> float:
    """The HELD experts of one layer that `rows` tokens read between them if each takes its k of all at random."""
    return c["num_experts"] * (1.0 - (1.0 - c["num_experts_per_tok"] / _routed(c)) ** rows)


def decode_step_bytes(c: Dict[str, Any], slots: int, t_max: int, bytes_per: int = 2, contexts=None,
                      touched=None, selected=None) -> int:
    """Bytes one decode step has to read at the least.  Every weight outside the experts once (the embedding only
    its rows), of each layer's held experts those touched (`touched`: the step's own count, a layer's mean; None:
    the expected number for its rows), and of the cache, for each live row, its whole context of indexer keys and
    its SELECTED positions' keys and values: min(context, topk) of them (`selected`: the step's own count over its
    rows).  contexts: the live rows' contexts (None: `slots` rows at t_max)."""
    contexts = [t_max] * slots if contexts is None else list(contexts)
    e, L, topk = c["hidden_size"], c["num_hidden_layers"], c["sa_config"]["topk"]
    rows = len(contexts)
    outside = L * (_attention_params(c) + indexer_params(c) + e * _routed(c) + 2 * e) + c["vocab_size"] * e + e + rows * e
    experts = L * (experts_touched(c, rows) if touched is None else touched) * expert_params(c)
    selected = sum(min(n, topk) for n in contexts) if selected is None else selected
    cache = L * (selected * selected_row_bytes(c, 1) + sum(contexts) * index_key_bytes(c, 1))
    return int((outside + experts + cache) * bytes_per)


# -- tolerances ------------------------------------------------------------------
# harness/reference.py says which program each of the three serving tolerances holds.  Each is set from two
# readings on the chip at the cell's own size (all 48 layers; the four check streams of traffic/longdoc-closed.json
# served together, prompts 3,100 / 3,900 / 5,900 / 7,900, 4 x 64 positions, teacher-forced through this reference in
# float32; my chip runs, PR 56: PERF.md section 6 has every run).  The lower one is the largest the program gave
# (bf16 weights and activations; float32 router, softmax and indexer scores) over 31 runs on 30 seeds (29 of the
# cell, 2 of the controls' script); the upper one the
# least of the nearest precision below, every stored matrix rounded to float8 e4m3's 3 bits of mantissa and served
# so (`scripts/keye_controls.py float8-weights`), which has to come out as not correct.
#
# Logits at the prompt's last row: the program 0.049-0.143; float8 1.54 and 2.68 (two seeds).  The bound is 2.8 x the program's
# largest reading and 3.8 x under float8's least.  1,024 selected where the configuration says 2,048 reads 0.51.
LOGIT_TOL = 0.4
# The regrets of the served tokens, which alone hold the batch decode (the scan of the cached indexer keys, the
# top-k, the gather): the program's largest 0-0.070, its mean 0-0.0026 (of 256 tokens 200-256 the reference's own);
# float8 0.667-1.32 and 0.164-0.361.  Bounds near the geometric means: 2.9 x over the program's largest (the largest of 256
# regrets has a tail: 0.054 was the largest of the first 19 runs) and 3.3 x under float8's, 3.8 x over and 16 x under.
# Two of the planted faults are theirs to catch and are caught by both: dense attention served (the selection
# skipped) reads 0.35 and 0.071, the indexer's scores without the relu 0.26 and 0.0185.
REGRET_MAX_TOL = 0.2
REGRET_MEAN_TOL = 0.01
# no training cell runs this architecture: the dense decoder's bound, whose reason (bf16 rounding through the
# stack moves a mean over thousands of positions by 1e-3 at most) holds here as well
LOSS_TOL = 0.01


# -- the indexer, the selection and the core by themselves ---------------------------
# What the logits cannot see, each on this reference's own rows (the checked layers' normed inputs, rounded to the
# activations' type: `chosen_logits` keeps them from the pass the logits come from), through the program's own
# functions at the window's shapes: a prompt alone, [1, bucket, E], left-padded to the bucket the batcher admits
# it in, through `transformer._project_qkv`, `_project_index`, `_rope` and `_sparse_attention`'s three steps
# (ops/sparse_attention.py `index_scores`, `select_mask`, `masked_flash`: the admit's kernels); and a decode
# step's [slots, 1, E], every stream's row j at step j in slots 0, 1, ..., through `generate._sparse_decode_core`
# over a cache of the deployment's shape that the prefill's own rows were installed in.
#
#   dsa_select_other_set   over (position, checked layer) pairs, prompts and served tokens: the share whose
#       selected set is WRONGLY another than this reference's.  The program scores with bf16 products summed in
#       float32, this reference in float32 throughout, so a position whose score lies at the topk-th largest may
#       fall either way.  A difference is wrong where a position that one side chose and the other did not has a
#       reference score farther from the reference's topk-th largest than BAND of the score's own scale, sum_j
#       |w_j| |qI_j| |kI_s| (what the products are bounded by): bf16 keeps 8 bits, qI and kI are each rounded after
#       their projection, their norm and their turn, and 2^-6 is eight such roundings.  Anything else is a wrong
#       selection.
#   dsa_core_rel_err       the sparse core on THIS REFERENCE's selection (so a near-tie does not enter), prefill
#       (`masked_flash` under the reference's mask) and decode (`generate._attend_selected`, the one gather of keys
#       and values, on the reference's list), against float32: the largest |program - reference| / |reference| of a position's result (2-norms
#       over the heads' widths).
#   dsa_index_key_err      the cached indexer key (the stack `ki` after the prefill and the steps) against this
#       reference's kI, the same relative error a position.
#
# The tolerances, from the chip at the cell's own size (my chip runs, PR 56; 147,364 (position, layer) pairs a run
# over layers 0, 8, 16, 24, 32, 40, 47; `scripts/keye_controls.py` plants the faults, seed 3000000041).  Lower
# reading: the program's largest over the same 31 runs.  Upper: the least of the fault that is this number's to catch.
#   The selection: the program **0 of 147,364 in every run** (float32 sums of the same bf16 products choose the
# reference's 2,048 but for positions at the threshold); the scores without the relu 0.599; 1,024 chosen where the
# configuration says 2,048 0.355.  The bound allows 737 pairs and lies 120 x under the relu's reading.  The
# indexer's keys in float8 read 0 here (BAND is a bound on what the products can be off by, not what they
# typically are, and a float8 key moves a score by less): that fault is the third number's.
#   The core: the program 0.0083-0.0154 over 33 runs (0.0115 the largest of the first 24: a maximum over 147,364
# rows), prefill kernel and decode gather alike (bf16's 2^-9 through two products and the cast of the result;
# probabilities go into the second product in bf16).  The core's probabilities rounded to float8 e4m3's 3 bits before
# the second product, and nothing else (`scripts/keye_controls.py core-probs-float8`, seed 3000000043): **0.0323**,
# the selection's and the cached key's numbers unmoved (0, 0.0044), logits 0.078, regrets 0.039 / 0.0012: the
# fault is this number's alone to catch.  The bound is 1.56 x the program's largest and 1.35 x under the fault.
#   The cached indexer key: the program 0.0041-0.0048 (a bf16 key against a float32 one); rounded to float8 0.0389.
# The bound is the geometric mean: 2.7 x over, 3 x under.
BAND = 2.0 ** -6
DSA_SELECT_SET_TOL = 0.005
DSA_CORE_ERR_TOL = 0.024
DSA_INDEX_KEY_ERR_TOL = 0.013


def _rel_err(got, want):
    flat = lambda a: a.astype(jnp.float32).reshape(a.shape[0], -1)
    return jnp.linalg.norm(flat(got) - flat(want), axis=-1) / jnp.maximum(jnp.linalg.norm(flat(want), axis=-1), 1e-30)


MECH_ROWS = 128  # the queries a pass of `_reference_block` compares


@functools.partial(jax.jit, static_argnames=("dims",))
def _reference_parts(y, lp, *, dims):
    """(q, k, v, qI, kI, w) of this reference for the rows y [T, E] (float32 copies of the rounded rows)."""
    with jax.default_matmul_precision("highest"):
        return (*_qkv(y, lp, dims), *_index(y, lp, dims))


@functools.partial(jax.jit, static_argnames=("topk",))
def _reference_block(parts, chosen, lo, *, topk):
    """What this reference makes of queries lo .. lo + MECH_ROWS - 1 of a stream and how the program's selection
    `chosen` [T, T] of them stands to it: (S_t [ROWS, T]; the attention result under it [ROWS, H, D]; the rows
    whose program set is WRONGLY another [ROWS]; a position's distance from the topk-th score, less the band, where
    a difference at it would be wrong [ROWS, T] bool)."""
    q, k, v, qi, ki, w = parts
    rows = lambda a: lax.dynamic_slice_in_dim(a, lo, MECH_ROWS)
    with jax.default_matmul_precision("highest"):
        scores, causal = _scores(rows(qi), ki, rows(w), lo)
        want = _selected(scores, causal, topk)
        band = BAND * jnp.einsum("qh,k->qk", jnp.abs(rows(w)) * jnp.linalg.norm(rows(qi), axis=-1),
                                 jnp.linalg.norm(ki, axis=-1))
        kth = jnp.min(jnp.where(want, scores, jnp.inf), axis=-1, keepdims=True)  # the topk-th largest score
        far = causal & (jnp.abs(scores - kth) > band)
        return want, _attend(rows(q), k, v, want), jnp.any((want != rows(chosen)) & far, axis=-1), far


def _program_layer(cfg):
    """The program's way from a layer's normed rows to what a core is given, compiled once a shape:
    (y [B, T, E], layer's weights, positions [B, T]) -> (q, k, v, qI, kI, w)."""
    from cluster_anywhere_tpu.models import transformer

    def project(y, bp, positions):
        q, k, v = transformer._project_qkv(bp, y, cfg)
        q, k = transformer._rope(q, k, positions, cfg)
        return (q, k, v, *transformer._project_index(bp, y, cfg, positions))

    return project


def mechanism_checks(cb, streams):
    """The three numbers above (references/__init__.py says what the harness does with them)."""
    from cluster_anywhere_tpu.models import generate
    from cluster_anywhere_tpu.models.transformer import _sparse_attention as program_sparse_attention
    from cluster_anywhere_tpu.ops import sparse_attention as sparse

    params, cfg = cb.params, cb.cfg
    dims, topk = _dims_of(cfg), cfg.index_topk
    given = [_given.pop(ids.tobytes(), None) or _given_of(params, ids, cfg)[1] for ids in map(_stream_ids, streams)]
    project = _program_layer(cfg)
    one = dataclasses.replace(cfg, n_layers=1, n_experts=0, experts_held=None)
    prompts = [len(s["prompt_ids"]) for s in streams]
    steps = [len(s["served"]) - 1 for s in streams]
    assert len(streams) <= cb.slots, "the check streams are served together, a slot each"

    @jax.jit
    def prefill_parts(y, bp, pad):
        """A prompt alone in its bucket, as an admit runs it: the program's rows and its selection."""
        t = y.shape[1]
        q, k, v, qi, ki, w = project(y, bp, jnp.maximum(jnp.arange(t)[None, :] - pad[:, None], 0))
        _, mask = program_sparse_attention(q, k, v, (qi, ki, w), cfg, pad, chosen=True)
        return (q, k, v, ki), mask

    @jax.jit
    def core_under(q, k, v, mask, pad):
        """The admit's core under a given mask [1, bucket, bucket]."""
        (q, k, v, mask), pad, extra = sparse.left_pad_to_tile([q, k, v, mask], pad)  # the mask's rows
        mask = jnp.pad(mask, ((0, 0), (0, 0), (extra, 0)))  # and its columns
        return sparse.masked_flash(q, k, v, mask, cfg.attn_scale, first=pad)[:, extra:]

    @jax.jit
    def decode_step(cache, y, bp, pos, pads, want_at, want_n):
        """One step of the deployment's shape: every slot's row through the program's projections and
        `_sparse_decode_core`; and its gather and attention on a given list."""
        q, k, v, qi, ki, w = project(y, bp, (pos - pads)[:, None])
        _, cache, (at, chosen) = generate._sparse_decode_core(cache, 0, pos, pads, cfg, q, k, v, (qi, ki, w), listed=True)
        return cache, at, chosen, generate._attend_selected(q, cache["kv"], 0, want_at, want_n, cfg)

    if cb.t_max <= topk:
        raise ValueError(f"a cache of {cb.t_max} positions selects nothing: the decode core under check is not run")
    wrong, pairs, core_err, key_err = 0, 0, 0.0, 0.0
    for layer in sorted(given[0]):
        lp = _layer_of(params, layer)
        cache = generate.init_cache(one, cb.slots, cb.t_max)
        pads = np.zeros(cb.slots, np.int32)
        tails = []  # a stream's served rows: (S_t, the reference's result, where a difference is wrong, kI), each [served, ...]
        for slot, (g, n, served) in enumerate(zip(given, prompts, steps)):
            total = n + served
            y = jnp.pad(jnp.asarray(g[layer]), ((0, -total % MECH_ROWS), (0, 0)))  # the activations' type
            bucket = cb._bucket(n, served + 1)
            pad = pads[slot] = bucket - n
            at_pad = jnp.asarray([pad], jnp.int32)
            (q, k, v, ki), mask = prefill_parts(jnp.pad(y[:n], ((pad, 0), (0, 0)))[None], lp, at_pad)
            if mask is None:
                raise ValueError(f"a bucket of {bucket} selects nothing: the admit's kernels under check are not run")
            parts = _reference_parts(y.astype(jnp.float32), lp, dims=dims)
            # the program's selection of the prompt's rows, laid where the reference's rows lie
            chosen = jnp.zeros((y.shape[0],) * 2, bool).at[:n, :n].set(mask[0, pad:, pad:] != 0)
            blocks = [_reference_block(parts, chosen, lo, topk=topk) for lo in range(0, y.shape[0], MECH_ROWS)]
            want, ref_core, differs, far = (jnp.concatenate([b[i] for b in blocks]) for i in range(4))
            wrong += int(jnp.sum(differs[:n]))
            pairs += n
            under = jnp.zeros((1, bucket, bucket), jnp.int8).at[0, pad:, pad:].set(want[:n, :n].astype(jnp.int8))
            core_err = max(core_err, float(jnp.max(_rel_err(core_under(q, k, v, under, at_pad)[0, pad:], ref_core[:n]))))
            key_err = max(key_err, float(jnp.max(_rel_err(ki[0, pad:], parts[4][:n]))))
            tails.append(tuple(np.asarray(a[n:total]) for a in (want, ref_core, far, parts[4])))
            # the prompt's rows into the slot, as an admit installs them
            put = lambda a: jnp.pad(a[0], ((0, cb.t_max - bucket),) + ((0, 0),) * (a.ndim - 2))
            # keys and values side by side in the one stack, the indexer's keys [DI, T_max]
            cache = {"kv": cache["kv"].at[0, slot].set(put(jnp.concatenate([k, v], axis=2))),
                     "ki": cache["ki"].at[0, slot].set(put(ki).T)}
        # the served tokens, a step of all the slots at a time
        for j in range(max(steps)):
            live = [slot for slot, t in enumerate(steps) if j < t]
            y = np.zeros((cb.slots, 1, cfg.d_model), np.float32)
            pos = np.zeros(cb.slots, np.int32)
            want_at = np.zeros((cb.slots, topk), np.int32)
            want_n = np.zeros(cb.slots, np.int32)
            for slot in live:
                t = prompts[slot] + j
                y[slot, 0], pos[slot] = np.asarray(given[slot][layer][t], np.float32), pads[slot] + t
                at = np.nonzero(tails[slot][0][j])[0]
                want_at[slot, :len(at)], want_n[slot] = pads[slot] + at, len(at)
            cache, at, chosen, given_core = decode_step(
                cache, jnp.asarray(y, cfg.dtype), lp, jnp.asarray(pos), jnp.asarray(pads), jnp.asarray(want_at),
                jnp.asarray(want_n))
            at, chosen = np.asarray(at), np.asarray(chosen)
            for slot in live:
                want, ref_core, far, ref_ki = (a[j] for a in tails[slot])
                got = np.zeros_like(want)
                got[at[slot, :chosen[slot]] - pads[slot]] = True
                wrong += int(np.any((want != got) & far))
                pairs += 1
                core_err = max(core_err, float(_rel_err(given_core[slot], ref_core[None])[0]))
                key_err = max(key_err, float(_rel_err(cache["ki"][0, slot, :, pos[slot]][None], ref_ki[None])[0]))
    return [
        {"name": "dsa_select_other_set", "error": wrong / pairs, "tolerance": DSA_SELECT_SET_TOL,
         "why": f"(position, layer) pairs of {pairs} (layers {sorted(given[0])}) whose selected set differs from the "
                "float32 reference's by a position farther from the topk-th score than bf16's rounding"},
        {"name": "dsa_core_rel_err", "error": core_err, "tolerance": DSA_CORE_ERR_TOL,
         "why": "largest relative error of a position's attention result under the reference's own selection, "
                "prefill kernel and decode gather"},
        {"name": "dsa_index_key_err", "error": key_err, "tolerance": DSA_INDEX_KEY_ERR_TOL,
         "why": "largest relative error of a position's cached indexer key"},
    ]
