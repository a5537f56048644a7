"""A decoder with multi-head latent attention (MLA) over a mixture of experts
with shared experts and leading dense layers, as this chip's share of a
deployment that divides each layer's routed experts over several chips: the
key set of `skt/A.X-K1` (`model_type` `axk1`; configs/a.x-k1-ep16-serve1.json).
The interface is the package's (references/__init__.py).

The equations, one block; x a token's residual, H heads, RMSNorm with a learned
weight and epsilon 1e-6:

  attention on y = RMSNorm(x):
    c_q = RMSNorm(y W_qa)                         [q_lora_rank]
    [q_nope | q_rope] = c_q W_qb                  [H, nope + rope]
    [c_kv | k_r] = y W_kva;  c_kv = RMSNorm(c_kv) [kv_lora_rank], [rope]
    q_rope, k_rope = rotary(q_rope), rotary(k_r) at the token's position; k_rope
        is one head that all H share
    [k_nope | v] = c_kv W_kvb                     [H, nope + v]
    score_h(t, s) = a (q_nope_h(t) . k_nope_h(s) + q_rope_h(t) . k_rope(s)),
        causal softmax, o_h = sum_s p_h(t, s) v_h(s), output concat(o) W_o
    a = (nope + rope)^-0.5 m^2,  m = 0.1 mscale_all_dim ln(factor) + 1
  rotary: YaRN's frequencies over the rope dimensions.  f_i = theta^(-2i/rope);
    dimension i keeps f_i below the correction dimension of beta_fast, takes
    f_i / factor above that of beta_slow, a linear ramp between
    (corr(b) = rope ln(L_orig / (2 pi b)) / (2 ln theta), floor and ceiling);
    cos and sin times mscale(mscale) / mscale(mscale_all_dim), 1 here
  FFN on y = RMSNorm(x):
    the first `first_k_dense_replace` layers: (silu(y W_g) * (y W_u)) W_d
    the others: s = sigmoid(y W_r) over all the routed experts, float32; the k
        largest s; w_e = scale s_e / (sum of the k + 1e-20);
        sum over the chosen e THAT ARE HELD of w_e E_e(y), + Shared(y);
        E_e and Shared gated MLPs of `moe_intermediate_size`
  residual around each half; a final RMSNorm; an untied head.

What is cached a token a layer in the program: c_kv after its norm and k_rope
after rotation.  Its decode step attends in the latent space (q_nope W_UK^T
against c_kv, the weighted latents through W_UV: the same numbers as above by
associativity); this reference never does, it expands every head's keys and
values as written above.

The share (model-configs guide, section 4): `experts_held` = (first, count) of
the program's configuration says which of the router's experts this chip
holds; the router keeps its width and a token takes its k of all of them; what
the experts held elsewhere would add is left out, here as in the program, and
the partial result goes on to the next layer.  The vocabulary is the slice the
configuration holds.  tests/benchmark/test_benchmark_mla_moe.py holds that the
shares' parts, with the shared expert once, add up to the uncut layer.

The plain reference is straightforward `jax.numpy` in float32 at `highest`
matmul precision: no kernel, no cache, no batching, no sorting of tokens, no
absorbed projection.  Layers run one at a time in a Python loop; inside a layer
the attention scores are made a block of ATTN_BLOCK query rows at a time, the
dense MLP a slice of its width at a time and the held experts one at a time, so
that one expert's (or one slice's) weights are upcast at a time and 3,964
positions fit beside 9.7 GB of weights.  It shares no code with
`cluster_anywhere_tpu/models/` or `parallel/`; it reads the same parameter
tree.  (`mechanism_checks`, at the end, calls the program's own functions as
what it checks, not as a reference.)

Departures from the published model, which the configuration file lists:
`topk_method` "none" is read as plain top-k over all routed experts (no group
restriction, no correction bias); the rotary embedding rotates adjacent pairs
(x[2i], x[2i+1]) as the program does (the family rotates halves after its own
de-interleaving: a permutation of the rotary columns of W_qb and W_kva; on
random weights the two are the same model).  The training auxiliary loss is no
part of serving and no part of `loss`.
"""

from __future__ import annotations

import dataclasses
import functools
import math
import os
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmarks.harness import manifest

# the probe experts that read a router's choice out of the program are OLMoE's file's
_moe_ref = manifest.load_reference("olmoe", os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

RMS_EPS = 1e-6
ATTN_BLOCK = 256  # query rows per block: bounds the [heads, block, T] scores
DENSE_SLICES = 4  # the dense MLP's width is taken in this many slices
# what this architecture's programs write beyond the common names
# (program_trace.SCOPES): the mixture's five under `ffn` (parallel/moe.py
# routed_ffn, models/transformer.py _ffn_half), latent attention's four in
# place of `attn.qkv` (models/transformer.py _project_latent, _latent_expand;
# models/generate.py _latent_decode_core)
SCOPES = ("moe.router", "moe.dispatch", "moe.experts", "moe.combine", "moe.shared",
          "attn.mla.q", "attn.mla.kv", "attn.mla.expand", "attn.mla.absorb")
# the grouped matmul is `lax.ragged_dot`, the compiler's own Mosaic kernel,
# known by its instruction's name (references/olmoe.py says why)
KERNELS = ("ragged-dot-none",)


def program_config(config_file: Dict[str, Any], **extra) -> Dict[str, Any]:
    """The program's TransformerConfig fields from a configuration file's
    keys.  `n_routed_experts` counts the experts HELD; the router's width is
    `n_routed_experts_routed` and the share starts at `experts_held_first`.  A
    program that lacks one of the fields cannot run the configuration: refused
    here, by name, before anything is deployed."""
    from cluster_anywhere_tpu.models.transformer import TransformerConfig

    c = config_file["config"]
    rs = c["rope_scaling"]
    if rs["type"] != "yarn":
        raise ValueError(f"rope_scaling of type {rs['type']!r}: this file writes YaRN's frequencies")
    out = dict(
        d_model=c["hidden_size"], n_layers=c["num_hidden_layers"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], d_head=c["qk_nope_head_dim"] + c["qk_rope_head_dim"],
        d_ff=c["intermediate_size"], rope_theta=float(c["rope_theta"]),
        max_seq_len=c["max_position_embeddings"],
        kv_lora_rank=c["kv_lora_rank"], q_lora_rank=c["q_lora_rank"],
        qk_nope_head_dim=c["qk_nope_head_dim"], qk_rope_head_dim=c["qk_rope_head_dim"],
        v_head_dim=c["v_head_dim"],
        rope_factor=float(rs["factor"]), rope_original_max_len=rs["original_max_position_embeddings"],
        rope_beta_fast=float(rs["beta_fast"]), rope_beta_slow=float(rs["beta_slow"]),
        rope_mscale=float(rs["mscale"]), rope_mscale_all_dim=float(rs["mscale_all_dim"]),
        n_dense_layers=c["first_k_dense_replace"], d_expert=c["moe_intermediate_size"],
        n_shared_experts=c["n_shared_experts"],
        n_experts=c["n_routed_experts_routed"], n_experts_per_tok=c["num_experts_per_tok"],
        moe_gated=True, moe_renormalize=bool(c["norm_topk_prob"]), moe_scoring=c["scoring_func"],
        moe_routed_scale=float(c["routed_scaling_factor"]),
        experts_held=(c["experts_held_first"], c["n_routed_experts"]),
    )
    out.update(extra)
    lacking = sorted(set(out) - {f.name for f in dataclasses.fields(TransformerConfig)})
    if lacking:
        raise NotImplementedError(
            f"this program's TransformerConfig has no {lacking}: it serves no latent attention over a "
            "held share of a mixture's experts, and this configuration cannot run on it"
        )
    return out


# -- the mathematics ---------------------------------------------------------------


def _rms_norm(x, w):
    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + RMS_EPS)) * w


def _mscale(factor: float, m: float) -> float:
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1.0 and m else 1.0


def yarn(cfg):
    """(the rotary frequencies [rope / 2] as numpy float32, what cos and sin
    are multiplied by, what the scores are multiplied by) from the program's
    configuration object, read for its sizes only."""
    d, theta, factor = cfg.qk_rope_head_dim, float(cfg.rope_theta), float(cfg.rope_factor)
    base = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    if factor > 1.0:
        corr = lambda turns: d * math.log(cfg.rope_original_max_len / (turns * 2 * math.pi)) / (2 * math.log(theta))
        low = max(math.floor(corr(cfg.rope_beta_fast)), 0)
        high = min(math.ceil(corr(cfg.rope_beta_slow)), d - 1)
        ramp = np.clip((np.arange(d // 2) - low) / max(high - low, 1e-3), 0.0, 1.0)
        base = base / factor * ramp + base * (1.0 - ramp)
    m_all = _mscale(factor, cfg.rope_mscale_all_dim)
    scale = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5 * m_all * m_all
    return base.astype(np.float32), _mscale(factor, cfg.rope_mscale) / m_all, scale


def _rope(x, inv_freq, magnitude: float):
    """x: [T, H, rope] at positions 0..T-1, adjacent pairs rotated."""
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :] * magnitude, jnp.sin(ang)[:, None, :] * magnitude
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1).reshape(x.shape)


def _latent_qkv(x, lp, inv_freq, dims):
    """What attention makes of one sequence x [T, E] (float32) before it
    attends: (q [T, H, nope + rope] with its rotary part rotated, the rotated
    key k_rope [T, rope] that every head shares, the normed latent c_kv [T, R])."""
    h, dn, dr, _, r, magnitude, _ = dims
    f32 = lambda name: lp[name].astype(jnp.float32)
    t = x.shape[0]
    y = _rms_norm(x, f32("ln1"))
    q = (_rms_norm(y @ f32("wq_a"), f32("q_a_norm")) @ f32("wq_b")).reshape(t, h, dn + dr)
    kv = y @ f32("wkv_a")
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], inv_freq, magnitude)], axis=-1)
    return q, _rope(kv[:, None, r:], inv_freq, magnitude)[:, 0], _rms_norm(kv[:, :r], f32("kv_a_norm"))


def _expanded(q, k_rope, c_kv, lp, dims, rows=None):
    """Every head's EXPANDED attention over one sequence: each head's keys
    [c_kv W_UK | k_rope] and values c_kv W_UV made of the latents, a causal
    softmax a head.  Returns concat(o) [T, H v], or of the query rows `rows`
    (an index array) alone, each of which sees the positions up to its own."""
    h, dn, dr, dv, _, _, scale = dims
    t = q.shape[0]
    up = (c_kv @ lp["wkv_b"].astype(jnp.float32)).reshape(t, h, dn + dv)
    k = jnp.concatenate([up[..., :dn], jnp.broadcast_to(k_rope[:, None, :], (t, h, dr))], axis=-1)
    v = up[..., dn:]
    if rows is not None:
        s = jnp.einsum("qhd,khd->hqk", q[rows], k) * scale
        causal = rows[:, None] >= jnp.arange(t)[None, :]
        p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v).reshape(len(rows), h * dv)
    outs = []
    for lo in range(0, t, ATTN_BLOCK):
        hi = min(t, lo + ATTN_BLOCK)
        s = jnp.einsum("qhd,khd->hqk", q[lo:hi], k[:hi]) * scale
        causal = jnp.arange(lo, hi)[:, None] >= jnp.arange(hi)[None, :]
        p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("hqk,khd->qhd", p, v[:hi]))
    return jnp.concatenate(outs, axis=0).reshape(t, h * dv)


def _attend(x, lp, inv_freq, dims):
    return _expanded(*_latent_qkv(x, lp, inv_freq, dims), lp, dims)


def _dense_mlp(y, wg, wu, wd):
    """(silu(y W_g) * (y W_u)) W_d, the width in DENSE_SLICES slices so that one
    slice of the three matrices is upcast at a time."""
    f = wg.shape[-1]
    n = DENSE_SLICES if f % DENSE_SLICES == 0 else 1
    w = f // n

    def one_slice(acc, i):
        g, u = (lax.dynamic_slice_in_dim(m, i * w, w, axis=1).astype(jnp.float32) for m in (wg, wu))
        d = lax.dynamic_slice_in_dim(wd, i * w, w, axis=0).astype(jnp.float32)
        return acc + (jax.nn.silu(y @ g) * (y @ u)) @ d, None

    return lax.scan(one_slice, jnp.zeros_like(y), jnp.arange(n))[0]


def _routed(y, lp, k: int, renormalize: bool, scale: float, first: int):
    """The held experts' part of the routed result for y [T, E]: sigmoid
    scores over all the router's experts, the k largest, their weights; then
    the experts held here (`lp`'s, the router's experts first, first + 1, ...)
    one after the other over every token, each weighted by the token's weight
    for it, 0 where it is not among the token's k.  Returns (the part [T, E],
    the tokens' weights over ALL the router's experts [T, X])."""
    scores = jax.nn.sigmoid(y @ lp["router"].astype(jnp.float32))  # [T, X]
    top, idx = lax.top_k(scores, k)
    if renormalize:
        top = top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20)
    top = top * scale
    weight = jnp.sum(jax.nn.one_hot(idx, scores.shape[-1], dtype=jnp.float32) * top[..., None], axis=1)
    held = lp["w_down"].shape[0]

    def one_expert(acc, e):
        wg, wu, wd, w_e = e
        wg, wu, wd = (w.astype(jnp.float32) for w in (wg, wu, wd))
        return acc + w_e[:, None] * ((jax.nn.silu(y @ wg) * (y @ wu)) @ wd), None

    out, _ = lax.scan(one_expert, jnp.zeros_like(y),
                      (lp["w_gate"], lp["w_up"], lp["w_down"], weight[:, first:first + held].T))
    return out, weight


@functools.partial(jax.jit, static_argnames=("dims", "moe"))
def _layer(x, lp, inv_freq, *, dims, moe):
    """One block over one sequence.  x: [T, E] float32; lp: this layer's
    weights in whatever type they are stored in; moe: None for a dense layer,
    else (k, renormalize, scale, first held expert).  Returns (the block's
    output, what its FFN was given: the normed stream [T, E])."""
    f32 = lambda name: lp[name].astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        x = x + _attend(x, lp, inv_freq, dims) @ f32("wo")
        y = _rms_norm(x, f32("ln2"))
        if moe is None:
            return x + _dense_mlp(y, lp["w_gate"], lp["w_up"], lp["w_down"]), y
        shared = (jax.nn.silu(y @ f32("shared_gate")) * (y @ f32("shared_up"))) @ f32("shared_down")
        return x + _routed(y, lp, *moe)[0] + shared, y


def _dims(cfg):
    inv_freq, magnitude, scale = yarn(cfg)
    return jnp.asarray(inv_freq), (cfg.n_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim,
                                   cfg.kv_lora_rank, float(magnitude), float(scale))


def _moe_dims(cfg):
    first = cfg.experts_held[0] if cfg.experts_held is not None else 0
    return (cfg.n_experts_per_tok, bool(cfg.moe_renormalize), float(cfg.moe_routed_scale), first)


def _layers(params):
    """[(the stack a layer's weights lie in, its index there, whether it is an
    expert layer)] in the model's order: the leading dense layers, then the rest."""
    dense = params.get("dense_blocks")
    n_dense = 0 if dense is None else dense["ln1"].shape[0]
    return ([("dense_blocks", i, False) for i in range(n_dense)]
            + [("blocks", i, True) for i in range(params["blocks"]["ln1"].shape[0])])


def _layer_of(params, stack: str, i: int):
    return jax.tree_util.tree_map(lambda w: w[i], params[stack])


def _blocks(params: Dict[str, Any], ids, cfg):
    """ids: [T] through the stack.  Yields, a layer at a time, (the block's
    input [T, E], the block's output, what its FFN was given, whether it is an
    expert layer)."""
    inv_freq, dims = _dims(cfg)
    x = params["embed"][jnp.asarray(ids)].astype(jnp.float32)
    for stack, i, is_moe in _layers(params):
        x_in = x
        x, y = _layer(x, _layer_of(params, stack, i), inv_freq, dims=dims, moe=_moe_dims(cfg) if is_moe else None)
        yield x_in, x, y, is_moe


def _head(params, x):
    with jax.default_matmul_precision("highest"):
        return _rms_norm(x, params["ln_f"].astype(jnp.float32)) @ params["lm_head"].astype(jnp.float32)


def forward(params: Dict[str, Any], ids, cfg):
    """ids: [T] -> logits [T, V], float32.  `cfg`: the program's
    TransformerConfig, read for its sizes (heads, the latent attention's five,
    YaRN's, the experts a token takes, their scale, the share held)."""
    for _, x, _, _ in _blocks(params, ids, cfg):
        pass
    return _head(params, x)


def loss(params, ids, cfg) -> float:
    """Mean next-token cross entropy of one sequence ids[:-1] -> ids[1:]."""
    ids = jnp.asarray(ids)
    logits = forward(params, ids[:-1], cfg)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, ids[1:, None], axis=-1)[:, 0]
    return float(jnp.mean(logz - gold))


# -- what chose a served token ------------------------------------------------------
# One causal token a step from the last position's logits: the harness's default,
# by this file's own pass, which also says what every layer's attention and FFN
# were given; `mechanism_checks` reads those again.  A stream is padded on the
# right to a multiple of ROW_BLOCK (a causal model's earlier positions do not see
# what follows), and the head takes the rows that chose a token alone.
ROW_BLOCK = 128
# a stream's ids -> ([layer] of the block's input [T, E], [expert layer] of what its
# FFN was given [T, E]), on the host in the program's activation type: kept by
# `chosen_logits` until `mechanism_checks` takes it
_given: Dict[bytes, tuple] = {}


def _stream_ids(stream) -> np.ndarray:
    return np.asarray(stream["prompt_ids"] + stream["served"][:-1], np.int32)


def _given_of(params, ids, cfg):
    """ids [T] through the stack.  Returns (the last block's output [T, E],
    ([layer] of block inputs, [expert layer] of FFN inputs) as `_given` keeps them)."""
    n = len(ids)
    host = lambda a: np.asarray(a[:n].astype(cfg.dtype))
    inputs, ffn = [], []
    for x_in, x, y, is_moe in _blocks(params, np.pad(ids, (0, -n % ROW_BLOCK)), cfg):
        inputs.append(host(x_in))
        if is_moe:
            ffn.append(host(y))
    return x[:n], (inputs, ffn)


def chosen_logits(cb, stream) -> np.ndarray:
    """Row i: the logits at position len(prompt) - 1 + i of prompt +
    served[:-1], which chose served[i]."""
    ids, n = _stream_ids(stream), len(stream["prompt_ids"])
    x, _given[ids.tobytes()] = _given_of(cb.params, ids, cb.cfg)
    return np.asarray(_head(cb.params, x[n - 1:]))


# -- counts from shapes ---------------------------------------------------------
# `c` is the `config` object of a configuration file: the published keys, with
# `n_routed_experts` the experts HELD and `n_routed_experts_routed` the router's.


def attention_params(c: Dict[str, Any]) -> int:
    """W_qa, W_qb, W_kva, W_kvb, W_o and the two low-rank norms."""
    e, h = c["hidden_size"], c["num_attention_heads"]
    rq, r, dn, dr, dv = (c["q_lora_rank"], c["kv_lora_rank"], c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                         c["v_head_dim"])
    return e * rq + rq * h * (dn + dr) + e * (r + dr) + r * h * (dn + dv) + h * dv * e + rq + r


def expert_params(c: Dict[str, Any]) -> int:
    """One routed (or shared) expert's three matrices."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def expert_bytes(c: Dict[str, Any], bytes_per: int = 2) -> int:
    return expert_params(c) * bytes_per


def _layer_counts(c: Dict[str, Any]):
    dense = c["first_k_dense_replace"]
    return dense, c["num_hidden_layers"] - dense


def param_count(c: Dict[str, Any]) -> int:
    """What this chip holds: every layer's attention, the dense layers' MLP, of
    each expert layer the router (all its columns), the shared experts and the
    experts held; the sliced embedding and head."""
    e, V = c["hidden_size"], c["vocab_size"]
    dense, moe = _layer_counts(c)
    per_dense = attention_params(c) + 3 * e * c["intermediate_size"] + 2 * e
    per_moe = (attention_params(c) + e * c["n_routed_experts_routed"]
               + (c["n_shared_experts"] + c["n_routed_experts"]) * expert_params(c) + 2 * e)
    return dense * per_dense + moe * per_moe + 2 * V * e + e


def train_flops_per_step(c: Dict[str, Any], batch: int, seq: int) -> float:
    """Operations the forward and backward passes require on THIS chip for
    `batch` sequences of `seq` tokens: 2 per multiply-add over the weights a
    token meets here (attention expanded; of its k routed experts the share
    that falls on those held, k held / routed in expectation; the shared
    experts; the router), attention's square in full, backward twice the
    forward.  No training cell runs this architecture."""
    e, h, V = c["hidden_size"], c["num_attention_heads"], c["vocab_size"]
    dense, moe = _layer_counts(c)
    here = c["num_experts_per_tok"] * c["n_routed_experts"] / c["n_routed_experts_routed"]
    attn = attention_params(c)
    weights = (dense * (attn + 3 * e * c["intermediate_size"])
               + moe * (attn + e * c["n_routed_experts_routed"] + (c["n_shared_experts"] + here) * expert_params(c)))
    square = 2 * seq * seq * h * (c["qk_nope_head_dim"] + c["qk_rope_head_dim"] + c["v_head_dim"])
    fwd = batch * seq * 2 * weights + batch * square * (dense + moe) + batch * seq * 2 * e * V
    return 3.0 * fwd


def experts_touched(c: Dict[str, Any], rows: int) -> float:
    """The held experts of one layer that `rows` tokens read between them if
    each takes its k of all the routed at random: held (1 - (1 - k/X)^rows)."""
    X, k, held = c["n_routed_experts_routed"], c["num_experts_per_tok"], c["n_routed_experts"]
    return held * (1.0 - (1.0 - k / X) ** rows)


def mla_core_bytes(c: Dict[str, Any], slots: int, t_max: int, bytes_per: int = 2) -> int:
    """What a decode step's latent cores have to read at the least: every
    slot's latent rows and rotated keys over the whole cache length (the
    program attends over the full T_max whatever the rows' depths), once a
    layer."""
    return c["num_hidden_layers"] * slots * t_max * (c["kv_lora_rank"] + c["qk_rope_head_dim"]) * bytes_per


def mla_core_flops(c: Dict[str, Any], slots: int, t_max: int) -> float:
    """The operations of the same: every head's scores against a latent row
    and the rotated key, and the latent rows weighted by the probabilities."""
    h, r, dr = c["num_attention_heads"], c["kv_lora_rank"], c["qk_rope_head_dim"]
    return 2.0 * c["num_hidden_layers"] * slots * t_max * h * (2 * r + dr)


def decode_step_bytes(c: Dict[str, Any], slots: int, t_max: int, bytes_per: int = 2, touched=None) -> int:
    """Bytes one decode step has to read at the least: every weight outside
    the routed experts once (attention with W_kvb as the absorbed form reads
    it, the dense MLP, routers, shared experts; the embedding only its `slots`
    rows; the head), of each expert layer's held experts those a batch
    touches (`touched` a layer, or `experts_touched` at `slots` rows), and the
    whole latent cache (`mla_core_bytes`)."""
    e, V = c["hidden_size"], c["vocab_size"]
    dense, moe = _layer_counts(c)
    outside = ((dense + moe) * (attention_params(c) + 2 * e) + dense * 3 * e * c["intermediate_size"]
               + moe * (e * c["n_routed_experts_routed"] + c["n_shared_experts"] * expert_params(c))
               + V * e + e + slots * e)
    held = moe * (experts_touched(c, slots) if touched is None else touched) * expert_params(c)
    return int((outside + held) * bytes_per) + mla_core_bytes(c, slots, t_max, bytes_per)


# -- tolerances ------------------------------------------------------------------
# harness/reference.py says which program each of the three serving tolerances
# holds.  The readings are the chip's at the published widths, 1 + 6 layers, taken
# as the cell's check takes them (the four check streams of traffic/rag-closed.json
# served together: prompts 600, 1100, 2100, 3900, 64 tokens each, teacher-forced
# through this reference in float32): the program over 19 runs on 19 seeds (13
# through a batcher alone in one process, `_archive/controls41.py`; 6 runs of the
# cell), and the nearest precision below bf16 over 3 seeds: every matrix rounded to
# float8 e4m3's 3 bits of mantissa and the streams served from them ("float8-all").
# My chip runs, PR 41; PERF.md section 6 has every number.
#
# **What sets the program's numbers is the router, not rounding's size.**  A held
# expert enters the stream with a weight near 2.5 / 8 = 0.31 (OLMoE's unrenormalised
# probabilities are 0.02-0.05), and this chip adds the parts of the 12 held alone.
# The 8th and 9th of a token's 192 sigmoid scores lie about 0.1 apart in their
# logits; the program's bf16 stream moves a router logit by 0.01-0.02, so in about
# one (token, layer) pair in a hundred the program and this float32 pass take or
# leave another held expert, and a whole weighted expert's result differs from
# there on.  On the same rows the two route alike in every pair
# (`moe_router_other_set` 0 of 47,712 in all 18 runs of the check's final form).  With the routed experts
# switched off (scale 0; a width of 1,024 on the CPU) the same check reads logits
# 0.13-0.14 and regrets 0.05 / 0.0007-0.0019, as the other architectures do; with
# them on, 0.16-0.56 and 0.12-0.28 / 0.004-0.013.  So the largest logit error and the
# largest regret have the tail of a rare large event, and their float8 readings
# lie inside it.
#
# The mean regret is the number that tells the precisions apart: the program
# 0.0091-0.0297 (19 runs; the next largest 0.0204), float8-all 0.295, 0.317, 0.318.
# The bound is 3.0 x over the program's largest and 3.3 x under float8's least.
REGRET_MEAN_TOL = 0.09
# The logits at a prompt's last row (4 rows x 20,480 a run): the program 0.17-0.21
# in 9 runs and 0.49, 0.51, 0.52, 0.53, 0.68, 0.69, 1.17, 1.45, 1.53, 1.79 in the
# other 10 (a flip upstream of one of the four rows, or none); float8-all 2.01,
# 2.07, 2.19.  The largest regret of 256 tokens: the program 0.32-1.22 (median
# 0.63); float8-all 1.58, 1.98, 2.30.  No bound between the program's largest
# and float8's least has room on both sides for the seeds a check draws (19
# readings of a tail that one flip sets), so these two are set on the program's side
# alone, at about twice its largest, and catch what is not a matter of precision: a
# layer out of order, a norm left out, a cache row misplaced.  The lower precisions
# fail by the mean regret and by the mechanisms' three numbers below, which is what
# those are for.  PERF.md section 7 says what a reference that could hold these two
# tighter would need.
LOGIT_TOL = 4.0
REGRET_MAX_TOL = 3.0
# No training cell runs this architecture; the dense decoder's bound and reason.
LOSS_TOL = 0.01


# -- the mechanisms by themselves ---------------------------------------------------
# What the logits cannot see.  Three numbers, each the program's own code at the
# window's shapes against this file's plain mathematics ON THE SAME ROWS: what the
# reference's own float32 pass gave each layer (`_given`), rounded to the program's
# activation type, which is how a block hands them over, so neither the layers
# before nor the rounding of the input is the mechanism's error.
#
#   mla_absorb_rel_err      the decode step's attention, absorbed: for every layer
#       and every served position of the check streams, concat(o) [H v] as the
#       program's decode core gives it (`models/generate.py` `_latent_decode_core`,
#       one token a row at [slots, 1, .], the streams in slots 0, 1, ... at the
#       depths and pads the batcher gave them, the other slots empty, against a
#       latent cache of the deployment's [slots, T_max] that holds each stream as a
#       prefill stores it) against this file's EXPANDED attention in float32.  Both
#       start from the same queries, rotated keys and latents: this file's own, of
#       the rows as given, rounded to the program's activation type (the
#       down-projections' rounding moves a score by as much as the core's own
#       arithmetic does, and it is the logits' to see: with the program's own
#       projections on its side the program read 0.0169-0.0174 and bf16 scores
#       0.0197, PR 41's first chip call).  The largest |program - reference| /
#       |reference| of a row (2-norms over H v).  It holds the absorbed
#       up-projections, the scores' accumulation and the cache's precision, which
#       enter the stream through W_o under the noise of everything else.
#   moe_router_other_set    the share of (row, layer) pairs in which the program's
#       set of k (of ALL the router's experts: read by probe experts, as
#       references/olmoe.py reads it, through the same `_moe` without a held
#       share) is not this reference's.
#   moe_experts_rel_err     over the rows whose sets agree and that chose an expert
#       held here, the largest |program - reference| / |reference| of the held
#       experts' part of a row's result, the program's through `_moe` with the
#       share it holds, in the prefill's and the decode's shapes; a row that chose
#       none of them has to come back as zeros.
#
# The tolerances, from the chip at the cell's own size (my chip runs, PR 41: the 19
# runs above; each control planted once the streams are served, 3 seeds, so the
# numbers on the logits are the program's while `ok` comes out false by the
# control's own number: `_archive/controls41.py`, tests/benchmark/
# test_benchmark_mla_moe.py CONTROLS).  Lower reading: the program's largest.
# Upper: the control's least.
#   The absorbed core: the program **0.00381-0.00422** in 18 runs (1,764 (row,
# layer) pairs a run; a maximum with hardly a tail); the absorbed scores accumulated
# in bf16 **0.00891, 0.00944, 0.00989**; the latent cache rounded to float8's 3 bits
# **0.0565, 0.0595, 0.0606**.  The bound is 1.54 x over the program's largest and
# 1.37 x under the bf16 scores' least (9 x under float8's).
#   The router: the program **0 of 47,712 in all 18 runs**; the sigmoid and the
# top-8 in bf16 **18.07%, 18.14%, 18.27%** (192 scores in bf16's 8 bits tie by the
# dozen).  The bound allows 143 pairs of rows at which two float32 sums tie.
#   The held experts: the program **0.00463-0.00517** in 18 runs; no control of its own was
# asked for, the bound is OLMoE's (references/olmoe.py: float8 in the experts reads
# 0.06 there through the same kernels), 3.9 x over the program's largest.  The
# bf16 router reads 0.0082-0.0084 here, under it: it is the router's number that
# fails it.
MLA_ABSORB_ERR_TOL = 0.0065
MOE_ROUTER_SET_TOL = 0.003
MOE_EXPERTS_ERR_TOL = 0.02


def program_shapes(cb, streams):
    """How the rows of the check streams (stream by stream, a stream's prompt
    and then its served tokens but the last) lie in the calls that serving the
    streams together makes.  Returns (lay: a (first row, prompt rows, decode
    steps, pads on the left) a stream, stream i in slot i as the batcher fills
    its free slots in order; decode: [steps, slots], the row a slot holds at a
    step, or the number of rows where the slot is not live)."""
    assert len(streams) <= cb.slots, "the check streams are served together, a slot each"
    prompts = [len(s["prompt_ids"]) for s in streams]
    steps = [len(s["served"]) - 1 for s in streams]
    first = np.cumsum([0] + [n + t for n, t in zip(prompts, steps)])
    lay = tuple((int(off), n, t, cb._bucket(n, len(s["served"])) - n)
                for off, n, t, s in zip(first, prompts, steps, streams))
    decode = np.full((max(steps), cb.slots), first[-1], np.int32)
    for slot, (off, n, t, _) in enumerate(lay):
        decode[:t, slot] = off + n + np.arange(t)
    return lay, decode


@functools.partial(jax.jit, static_argnames=("dims", "lay", "dtype"))
def _latent_rows(rows, lp, inv_freq, *, dims, lay, dtype):
    """This file's own q, rotated key and latent of every row of every stream
    (each stream's positions count from 0), in float32 from the rows as given,
    rounded to the program's activation type: what both sides of
    `mla_absorb_rel_err` start from."""
    with jax.default_matmul_precision("highest"):
        parts = [_latent_qkv(rows[off:off + n + t].astype(jnp.float32), lp, inv_freq, dims) for off, n, t, _ in lay]
    return tuple(jnp.concatenate(p).astype(dtype) for p in zip(*parts))


def _attention_program(cb, lay, decode):
    """The compiled program of one layer's decode cores: (q, k_rope, c_kv of
    every row of every stream, in the activations' type; the layer's weights)
    -> concat(o) [steps * slots, H v] of every (step, slot), through the
    program's own core, `generate._latent_decode_core`, one token a row at
    [slots, 1, .] against a latent cache of the deployment's [slots, T_max]
    that holds each stream as a prefill stores it (left pads, then its rows)."""
    from cluster_anywhere_tpu.models import generate

    cfg = cb.cfg
    pads = np.zeros(cb.slots, np.int32)
    pos = np.zeros(decode.shape, np.int32)
    for slot, (_, n, _, pad) in enumerate(lay):
        pads[slot], pos[:, slot] = pad, pad + n + np.arange(len(decode))

    @jax.jit
    def program(q, k_rope, c_kv, bp):
        cache = generate.init_cache(dataclasses.replace(cfg, n_layers=1, n_dense_layers=0), cb.slots, cb.t_max)
        for slot, (off, n, t, pad) in enumerate(lay):
            cache = {"ckv": lax.dynamic_update_slice(cache["ckv"], c_kv[None, None, off:off + n + t], (0, slot, pad, 0)),
                     "kr": lax.dynamic_update_slice(cache["kr"], generate._lanes(k_rope[None, None, off:off + n + t]),
                                                    (0, slot, pad, 0))}
        at = lambda a, row: jnp.pad(a, [(0, 1)] + [(0, 0)] * (a.ndim - 1))[row][:, None]  # an empty slot's row: zeros

        def step(now):
            row, p = now
            o, _ = generate._latent_decode_core(bp, cache, 0, p, jnp.asarray(pads), cfg,
                                                at(q, row), at(k_rope, row), at(c_kv, row))
            return o.reshape(cb.slots, -1)

        out = lax.map(step, (jnp.asarray(decode), jnp.asarray(pos)))
        return out.reshape(-1, out.shape[-1])

    return program


@functools.partial(jax.jit, static_argnames=("dims", "lay", "slots"))
def _attention_errors(q, k_rope, c_kv, lp, got, *, dims, lay, slots):
    """The largest relative error of `got` [steps * slots, H v] against this
    file's expanded attention of each stream's decode rows from the same q,
    rotated keys and latents, a stream attended by itself."""
    f32 = lambda a, off, n, t: a[off:off + n + t].astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        worst = jnp.zeros((), jnp.float32)
        for slot, (off, n, t, _) in enumerate(lay):
            want = _expanded(f32(q, off, n, t), f32(k_rope, off, n, t), f32(c_kv, off, n, t), lp, dims,
                             rows=n + jnp.arange(t))
            mine = got[np.arange(t) * slots + slot].astype(jnp.float32)
            worst = jnp.maximum(worst, jnp.max(jnp.linalg.norm(mine - want, axis=-1) / jnp.linalg.norm(want, axis=-1)))
        return worst


@functools.partial(jax.jit, static_argnames=("moe",))
def _expert_errors(rows, lp, got, chosen, *, moe):
    """One layer's two numbers on the device: (the rows whose set of k,
    `chosen` [N, X], is not this reference's; the largest relative error of the
    held experts' part `got` among the others)."""
    first = moe[3]
    held = lp["w_down"].shape[0]
    with jax.default_matmul_precision("highest"):
        want, weight = _routed(rows.astype(jnp.float32), lp, *moe)
    same = jnp.all(chosen == (weight > 0), axis=-1)
    here = jnp.any(weight[:, first:first + held] > 0, axis=-1)
    got = got.astype(jnp.float32)
    err = jnp.linalg.norm(got - want, axis=-1) / jnp.where(here, jnp.linalg.norm(want, axis=-1), 1.0)
    # a row that chose no expert held here comes back as zeros: |got| / 1 is 0
    return jnp.sum(~same), jnp.max(jnp.where(same, err, 0.0))


def _experts_program(cb, lay, decode, n: int):
    """The compiled program of one expert layer's calls of `_moe`, laid out by
    `program_shapes`: (the n rows in the activations' type, every layer's
    routers, every layer's held experts, the layer's index) -> (what `_moe`
    makes of them with the share it holds, in the prefill's and the decode's
    shapes; which of ALL the router's experts it gave each to)."""
    from cluster_anywhere_tpu.models.transformer import _moe

    cfg = cb.cfg
    every = dataclasses.replace(cfg, experts_held=None)  # the same router, every choice visible
    held = np.nonzero(decode.reshape(-1) < n)[0]
    at = decode.reshape(-1)[held]

    @jax.jit
    def program(rows, routers, experts, layer):
        bp = {"router": routers[layer]}
        probe = _moe_ref._probe_experts(cfg.d_model, cfg.n_experts, cfg.dtype)

        def both(y, live):
            return (_moe(bp, y, cfg, live, (experts, layer))[0],
                    _moe(bp, y, every, live, (probe, 0))[0][..., :cfg.n_experts] != 0)

        got, chosen = jnp.zeros_like(rows), jnp.zeros((n, cfg.n_experts), bool)
        for off, t, _, pad in lay:
            out, marks = both(jnp.pad(rows[off:off + t], ((pad, 0), (0, 0)))[None],
                              jnp.asarray(np.arange(pad + t) >= pad)[None])
            got, chosen = got.at[off:off + t].set(out[0, pad:]), chosen.at[off:off + t].set(marks[0, pad:])
        steps = jnp.pad(rows, ((0, 1), (0, 0)))[decode][:, :, None, :]  # [steps, slots, 1, E]
        out, marks = lax.map(lambda step: both(*step), (steps, jnp.asarray(decode < n)[:, :, None]))
        got = got.at[at].set(out.reshape(-1, out.shape[-1])[held])
        return got, chosen.at[at].set(marks.reshape(-1, cfg.n_experts)[held])

    return program


def mechanism_checks(cb, streams):
    """The three numbers above, over every layer and every position of the
    check streams (references/__init__.py says what the harness does with them)."""
    from cluster_anywhere_tpu.parallel.moe import EXPERT_MATRICES

    params, cfg = cb.params, cb.cfg
    given = [_given.pop(ids.tobytes(), None) or _given_of(params, ids, cfg)[1] for ids in map(_stream_ids, streams)]
    n = sum(len(g[0][0]) for g in given)
    lay, decode = program_shapes(cb, streams)
    inv_freq, dims = _dims(cfg)

    # attention, absorbed against expanded: every layer
    program = _attention_program(cb, lay, decode)
    absorb = []
    for i, (stack, j, _) in enumerate(_layers(params)):
        if absorb:
            jax.block_until_ready(absorb[-1])  # one layer's float32 copies at a time
        lp = _layer_of(params, stack, j)
        rows = jnp.asarray(np.concatenate([g[0][i] for g in given]))
        latent = _latent_rows(rows, lp, inv_freq, dims=dims, lay=lay, dtype=jnp.dtype(cfg.dtype).name)
        absorb.append(_attention_errors(*latent, lp, program(*latent, lp), dims=dims, lay=lay, slots=cb.slots))

    # the expert layer: every expert layer
    program = _experts_program(cb, lay, decode, n)
    experts = {name: params["blocks"][name] for name in EXPERT_MATRICES if name in params["blocks"]}
    numbers = []
    for layer in range(len(given[0][1])):
        if numbers:
            jax.block_until_ready(numbers[-1])
        rows = jnp.asarray(np.concatenate([g[1][layer] for g in given]))
        got, chosen = program(rows, params["blocks"]["router"], experts, layer)
        numbers.append(_expert_errors(rows, _layer_of(params, "blocks", layer), got, chosen, moe=_moe_dims(cfg)))
    other_sets, worst = (np.asarray(x) for x in zip(*numbers))
    pairs = n * len(numbers)
    return [
        {"name": "mla_absorb_rel_err", "error": max(float(a) for a in absorb), "tolerance": MLA_ABSORB_ERR_TOL,
         "why": f"largest relative error of a decode row's absorbed attention (concat of {cfg.n_heads} heads' "
                f"results) against the expanded form in float32, over {sum(t for _, _, t, _ in lay) * len(absorb)} "
                "(row, layer) pairs"},
        {"name": "moe_router_other_set", "error": int(other_sets.sum()) / pairs, "tolerance": MOE_ROUTER_SET_TOL,
         "why": f"(row, layer) pairs of {pairs} in which the program's set of {cfg.n_experts_per_tok} of "
                f"{cfg.n_experts} is not the float32 reference's"},
        {"name": "moe_experts_rel_err", "error": float(worst.max()), "tolerance": MOE_EXPERTS_ERR_TOL,
         "why": "largest relative error of the held experts' part of a row's result, over the rows whose sets "
                "agree (zeros where a row chose none of them)"},
    ]
