"""A decoder whose attention layers are of two kinds in one stack, window
layers that see the last W positions and full layers that see all, over a
mixture of experts with a shared expert and a leading dense layer, as this
chip's share of a deployment that divides each layer's routed experts over
several chips: the key set of `LGAI-EXAONE/K-EXAONE-236B-A23B` (`model_type`
`exaone_moe`; configs/k-exaone-236b-a23b-ep16-serve1.json).  The interface is
the package's (references/__init__.py).

The equations, one block l; x a token's residual [E], H query heads on KV
cached heads of D, N an RMSNorm with a learned weight (epsilon 1e-6):

  attention on x itself (no norm before it):
    q = x W_q [H, D], k = x W_k, v = x W_v [KV, D], no bias
    q, k <- N_q(q), N_k(k): one weight [D] each, over each head's own D
    a window layer (`layer_types[l]` "sliding_attention"): q, k <- rotary(q, k)
        at the token's position, theta `rope_theta`, the whole head turned;
        a full layer ("full_attention"): no positional embedding at all
    score_h(i, j) = q_h(i) . k_g(j) / sqrt(D), g = h // (H / KV)
    position i sees j <= i, and in a window layer also i - j < W
        (`sliding_window`: itself and the W - 1 before it)
    a = concat_h(softmax_j(score_h) v_g) W_o
  x <- x + N_a(a);  x <- x + N_f(F(x)): each half's OUTPUT is normed
  F in the first `first_k_dense_replace` layers: (silu(x W_g) * (x W_u)) W_d
  F in the others: s = sigmoid(x W_r) over all the routed experts, float32;
      the k largest s; w_e = scale s_e / (sum of the k + 1e-20);
      sum over the chosen e THAT ARE HELD of w_e E_e(x), + Shared(x);
      E_e and Shared gated MLPs of `moe_intermediate_size`
  after the last layer N_out, then an untied head.

What the program caches a token a layer: k after its norm and rotation, and v;
in a full layer at the token's slot of T_max, in a window layer in a ring of
`window_extent` slots (position p at slot p mod the extent).  This reference
keeps no cache: a window is an explicit [T, T] mask, made a block of ATTN_BLOCK
query rows at a time.

The share (model-configs guide, section 4) is A.X-K1's: `experts_held` =
(first, count) of the program's configuration says which of the router's
experts this chip holds; the router keeps its width and a token takes its k of
all of them; what the experts held elsewhere would add is left out, here as in
the program.  The vocabulary is the slice the configuration holds.
tests/test_mla.py holds that the shares' parts, with the shared expert once,
add up to the uncut layer, for this file as for references/mla_moe.py.

The plain reference is straightforward `jax.numpy` in float32 at `highest`
matmul precision: no kernel, no cache, no ring, no batching, no sorting of
tokens.  Layers run one at a time in a Python loop; the dense MLP a slice of
its width at a time and the held experts one at a time (references/mla_moe.py's
own two functions, which are plain mathematics on the same parameter names).
It shares no code with `cluster_anywhere_tpu/models/` or `parallel/`; it reads
the same parameter tree.  (`mechanism_checks`, at the end, calls the program's
own functions as what it checks, not as a reference.)

Assumed, which the configuration file lists with where each was taken from
(the family's published `modeling_exaone4.py`; the row's `config` has no key
for them): the q/k norm a head; rotary on the window layers only; the norm of
each half's output.  Departures: no multi-token-prediction layer (the
published model decodes without it); the RMSNorm epsilon is the program's 1e-6
(published 1e-5); adjacent-pair rotation (the family rotates halves: a
permutation of W_q's and W_k's columns within a head, under a q/k norm whose
weight is permuted alike; on random weights the same model); no selection bias
on the router's scores.
"""

from __future__ import annotations

import dataclasses
import functools
import os
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmarks.harness import manifest

# the dense MLP in slices, the held experts one at a time, the expert layer's two checks and
# the rows' layout in the program's calls are A.X-K1's file's: plain mathematics and host
# arithmetic on the same parameter names
_mla = manifest.load_reference("mla_moe", os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

RMS_EPS = 1e-6
ATTN_BLOCK = 256  # query rows per block: bounds the [heads, block, T] scores
# what this architecture's programs write beyond the common names (program_trace.SCOPES):
# the mixture's five under `ffn`, and a window layer's attention beside `attn.core`
SCOPES = ("moe.router", "moe.dispatch", "moe.experts", "moe.combine", "moe.shared", "attn.core.window")
# the grouped matmul (references/olmoe.py says why it is known by name), and the banded
# prefill kernel of a window layer (ops/attention.py WINDOW_KERNEL)
KERNELS = ("ragged-dot-none", "swa_flash")
WINDOW_KERNELS = ("swa_flash",)

_MIXER = {"sliding_attention": "attn_win", "full_attention": "attn"}


def program_config(config_file: Dict[str, Any], **extra) -> Dict[str, Any]:
    """The program's TransformerConfig fields from a configuration file's
    keys.  `num_experts` counts the experts HELD; the router's width is
    `num_experts_routed` and the share starts at `experts_held_first`; the
    lists by layer are the published ones, read as far as `num_hidden_layers`.
    A program that lacks one of the fields cannot run the configuration:
    refused here, by name, before anything is deployed."""
    from cluster_anywhere_tpu.models.transformer import TransformerConfig

    c = config_file["config"]
    n = c["num_hidden_layers"]
    dense = c["first_k_dense_replace"]
    if c["mlp_layer_types"][:n] != ["dense"] * dense + ["sparse"] * (n - dense):
        raise ValueError("mlp_layer_types: the dense layers lead, as first_k_dense_replace counts them")
    windows = {w for w, kind in zip(c["sliding_windows"][:n], c["layer_types"][:n]) if kind == "sliding_attention"}
    if windows != {c["sliding_window"]}:
        raise ValueError(f"sliding_windows {sorted(windows)}: one window, sliding_window, in every window layer")
    if c["n_group"] != 1 or c["rope_parameters"]["rope_type"] != "default":
        raise ValueError("this file writes no expert groups and no scaled rotary frequencies")
    out = dict(
        d_model=c["hidden_size"], n_layers=n, n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], d_head=c["head_dim"], d_ff=c["intermediate_size"],
        rope_theta=float(c["rope_parameters"]["rope_theta"]), max_seq_len=c["max_position_embeddings"],
        layer_mixers=tuple(_MIXER[kind] for kind in c["layer_types"][:n]), attn_window=c["sliding_window"],
        rotary_full=False, norm_output=True, qk_norm=True, qk_norm_per_head=True,
        n_dense_layers=dense, d_expert=c["moe_intermediate_size"], n_shared_experts=c["num_shared_experts"],
        n_experts=c["num_experts_routed"], n_experts_per_tok=c["num_experts_per_tok"],
        moe_gated=True, moe_renormalize=bool(c["norm_topk_prob"]), moe_scoring=c["scoring_func"],
        moe_routed_scale=float(c["routed_scaling_factor"]),
        experts_held=(c["experts_held_first"], c["num_experts"]),
    )
    out.update(extra)
    lacking = sorted(set(out) - {f.name for f in dataclasses.fields(TransformerConfig)})
    if lacking:
        raise NotImplementedError(
            f"this program's TransformerConfig has no {lacking}: it serves no window layers beside full ones "
            "over caches of their own extents, and this configuration cannot run on it"
        )
    return out


# -- the mathematics ---------------------------------------------------------------


def _rms_norm(x, w):
    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + RMS_EPS)) * w


def _rope(x, theta: float):
    """x: [T, heads, D] at positions 0..T-1, adjacent pairs rotated, the whole head."""
    d = x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1).reshape(x.shape)


def _qkv(x, lp, dims, window: int):
    """What attention makes of one sequence x [T, E] (float32) before it
    attends: q [T, H, D], k, v [T, KV, D], q and k normed a head and, in a
    window layer, turned."""
    h, kv, d, theta = dims
    t = x.shape[0]
    f32 = lambda name: lp[name].astype(jnp.float32)
    q = _rms_norm((x @ f32("wq")).reshape(t, h, d), f32("q_norm"))
    k = _rms_norm((x @ f32("wk")).reshape(t, kv, d), f32("k_norm"))
    if window:
        q, k = _rope(q, theta), _rope(k, theta)
    return q, k, (x @ f32("wv")).reshape(t, kv, d)


def _attention(q, k, v, window: int, rows=None):
    """Every head's attention over one sequence under an explicit mask:
    position i sees j <= i, and with a window also i - j < window.  q [T, H, D];
    k, v [T, KV, D], each cached head serving H / KV query heads.  Returns
    concat(o) [T, H D], or of the query rows `rows` (an index array) alone."""
    t, h, d = q.shape
    kv = k.shape[1]

    def block(at):
        """The query rows `at` against every position up to the last of them."""
        hi = t if rows is not None else int(at[-1]) + 1
        s = jnp.einsum("qgrd,kgd->grqk", q[at].reshape(len(at), kv, h // kv, d), k[:hi]) * d ** -0.5
        ahead = at[:, None] - jnp.arange(hi)[None, :]
        mask = (ahead >= 0) & (ahead < window) if window else ahead >= 0
        p = jax.nn.softmax(jnp.where(mask[None, None], s, -jnp.inf), axis=-1)
        return jnp.einsum("grqk,kgd->qgrd", p, v[:hi]).reshape(len(at), h * d)

    if rows is not None:
        return block(rows)
    return jnp.concatenate([block(np.arange(lo, min(t, lo + ATTN_BLOCK))) for lo in range(0, t, ATTN_BLOCK)], axis=0)


@functools.partial(jax.jit, static_argnames=("dims", "window", "moe"))
def _layer(x, lp, *, dims, window, moe):
    """One block over one sequence.  x: [T, E] float32; lp: this layer's
    weights in whatever type they are stored in; window: 0 for a full layer;
    moe: None for a dense layer, else (k, renormalize, scale, first held
    expert).  Returns (the block's output, what its FFN was given [T, E])."""
    f32 = lambda name: lp[name].astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        x = x + _rms_norm(_attention(*_qkv(x, lp, dims, window), window) @ f32("wo"), f32("ln1"))
        if moe is None:
            out = _mla._dense_mlp(x, lp["w_gate"], lp["w_up"], lp["w_down"])
        else:
            out = (_mla._routed(x, lp, *moe)[0]
                   + (jax.nn.silu(x @ f32("shared_gate")) * (x @ f32("shared_up"))) @ f32("shared_down"))
        return x + _rms_norm(out, f32("ln2")), x


def _dims(cfg):
    return (cfg.n_heads, cfg.n_kv_heads, cfg.d_head, float(cfg.rope_theta))


# a kind of layer (the program's `layer_kinds`) -> the stack its weights lie in
_STACK = {"attn": "blocks", "attn_dense": "dense_blocks", "attn_win": "win_blocks",
          "attn_win_dense": "win_dense_blocks"}


def _layers(cfg):
    """[(the stack a layer's weights lie in, its index there, its window: 0 for
    a full layer, whether it is an expert layer)] in the model's order."""
    seen: Dict[str, int] = {}
    out = []
    for kind in cfg.layer_kinds:
        stack = _STACK[kind]
        out.append((stack, seen.get(stack, 0), cfg.attn_window * kind.startswith("attn_win"),
                    not kind.endswith("_dense")))
        seen[stack] = seen.get(stack, 0) + 1
    return out


def _layer_of(params, stack: str, i: int):
    return jax.tree_util.tree_map(lambda w: w[i], params[stack])


def _blocks(params: Dict[str, Any], ids, cfg):
    """ids: [T] through the stack.  Yields, a layer at a time, (the block's
    input [T, E], the block's output, what its FFN was given)."""
    x = params["embed"][jnp.asarray(ids)].astype(jnp.float32)
    for stack, i, window, is_moe in _layers(cfg):
        x_in = x
        x, y = _layer(x, _layer_of(params, stack, i), dims=_dims(cfg), window=window,
                      moe=_mla._moe_dims(cfg) if is_moe else None)
        yield x_in, x, y


def _head(params, x):
    with jax.default_matmul_precision("highest"):
        return _rms_norm(x, params["ln_f"].astype(jnp.float32)) @ params["lm_head"].astype(jnp.float32)


def forward(params: Dict[str, Any], ids, cfg):
    """ids: [T] -> logits [T, V], float32.  `cfg`: the program's
    TransformerConfig, read for its sizes (heads, the window, which layer is of
    which kind, the experts a token takes, their scale, the share held)."""
    for _, x, _ in _blocks(params, ids, cfg):
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
# One causal token a step from the last position's logits: the harness's default, by
# this file's own pass, which also says what every layer was given; `mechanism_checks`
# reads those again.  A stream is padded on the right to a multiple of ROW_BLOCK (a
# causal model's earlier positions do not see what follows), and the head takes the
# rows that chose a token alone.
ROW_BLOCK = 128
# a stream's ids -> ([layer] of the block's input [T, E], [layer] of what its FFN was
# given), on the host in the program's activation type
_given: Dict[bytes, tuple] = {}


def _stream_ids(stream) -> np.ndarray:
    return np.asarray(stream["prompt_ids"] + stream["served"][:-1], np.int32)


def _given_of(params, ids, cfg):
    """ids [T] through the stack.  Returns (the last block's output [T, E],
    ([layer] of block inputs, [layer] of FFN inputs) as `_given` keeps them)."""
    n = len(ids)
    host = lambda a: np.asarray(a[:n].astype(cfg.dtype))
    inputs, ffn = [], []
    for x_in, x, y in _blocks(params, np.pad(ids, (0, -n % ROW_BLOCK)), cfg):
        inputs.append(host(x_in))
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
# `num_experts` the experts HELD and `num_experts_routed` the router's.


def attention_params(c: Dict[str, Any]) -> int:
    """W_q, W_k, W_v, W_o and the two norms a head."""
    e, h, kv, d = c["hidden_size"], c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    return e * h * d + 2 * e * kv * d + h * d * e + 2 * d


def expert_params(c: Dict[str, Any]) -> int:
    """One routed (or shared) expert's three matrices."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def expert_bytes(c: Dict[str, Any], bytes_per: int = 2) -> int:
    return expert_params(c) * bytes_per


def layer_counts(c: Dict[str, Any]):
    """(dense layers, expert layers, window layers, full layers) of the layers held."""
    n, dense = c["num_hidden_layers"], c["first_k_dense_replace"]
    window = sum(kind == "sliding_attention" for kind in c["layer_types"][:n])
    return dense, n - dense, window, n - window


def param_count(c: Dict[str, Any]) -> int:
    """What this chip holds: every layer's attention and two norms, the dense
    layer's MLP, of each expert layer the router (all its columns), the shared
    expert and the experts held; the sliced embedding and head; the last norm."""
    e, V = c["hidden_size"], c["vocab_size"]
    dense, moe, _, _ = layer_counts(c)
    per_dense = attention_params(c) + 3 * e * c["intermediate_size"] + 2 * e
    per_moe = (attention_params(c) + e * c["num_experts_routed"]
               + (c["num_shared_experts"] + c["num_experts"]) * expert_params(c) + 2 * e)
    return dense * per_dense + moe * per_moe + 2 * V * e + e


def band_pairs(n: int, window: int) -> int:
    """The (query, key) pairs of one sequence of n tokens under the band: query
    i sees min(i + 1, window) keys."""
    full = min(n, window)
    return full * (full + 1) // 2 + (n - full) * window


def swa_flash_flops(c: Dict[str, Any], prompt_len: int) -> float:
    """The operations the banded prefill kernel has to do for ONE admit of
    `prompt_len` tokens over the window layers held: every query head's scores
    against the keys its band holds and the values weighted by them, 2 per
    multiply-add.  The admit's own length, not its bucket's: the pads' columns
    are the program's to spare."""
    _, _, window, _ = layer_counts(c)
    return 4.0 * window * c["num_attention_heads"] * c["head_dim"] * band_pairs(prompt_len, c["sliding_window"])


def train_flops_per_step(c: Dict[str, Any], batch: int, seq: int) -> float:
    """Operations the forward and backward passes require on THIS chip for
    `batch` sequences of `seq` tokens: 2 per multiply-add over the weights a
    token meets here (attention; of its k routed experts the share that falls
    on those held, k held / routed in expectation; the shared expert; the
    router), attention's pairs under each layer's own mask (the band in a
    window layer, the causal half in a full one), backward twice the forward.
    No training cell runs this architecture."""
    e, h, d, V = c["hidden_size"], c["num_attention_heads"], c["head_dim"], c["vocab_size"]
    dense, moe, window, full = layer_counts(c)
    here = c["num_experts_per_tok"] * c["num_experts"] / c["num_experts_routed"]
    attn = attention_params(c)
    weights = (dense * (attn + 3 * e * c["intermediate_size"])
               + moe * (attn + e * c["num_experts_routed"] + (c["num_shared_experts"] + here) * expert_params(c)))
    pairs = window * band_pairs(seq, c["sliding_window"]) + full * seq * (seq + 1) // 2
    fwd = batch * seq * 2 * weights + batch * 4 * h * d * pairs + batch * seq * 2 * e * V
    return 3.0 * fwd


def experts_touched(c: Dict[str, Any], rows: int) -> float:
    """The held experts of one layer that `rows` tokens read between them if
    each takes its k of all the routed at random: held (1 - (1 - k/X)^rows)."""
    X, k, held = c["num_experts_routed"], c["num_experts_per_tok"], c["num_experts"]
    return held * (1.0 - (1.0 - k / X) ** rows)


def cache_step_bytes(c: Dict[str, Any], lengths, bytes_per: int = 2) -> int:
    """The keys and values a decode step has to read for live rows whose
    contexts are `lengths` long: in a full layer a row's whole context, in a
    window layer its last `sliding_window` positions at most."""
    _, _, window, full = layer_counts(c)
    token = 2 * c["num_key_value_heads"] * c["head_dim"] * bytes_per
    return sum(token * (full * n + window * min(n, c["sliding_window"])) for n in lengths)


def decode_step_bytes(c: Dict[str, Any], slots: int, t_max: int, bytes_per: int = 2, touched=None,
                      lengths=None) -> int:
    """Bytes one decode step has to read at the least: every weight outside
    the routed experts once (attention, the dense MLP, routers, shared experts;
    the embedding only its `slots` rows; the head), of each expert layer's held
    experts those a batch touches (`touched` a layer, or `experts_touched` at
    `slots` rows), and the live rows' own keys and values (`cache_step_bytes`
    of `lengths`; without them `slots` rows of t_max, the most a step can read)."""
    e, V = c["hidden_size"], c["vocab_size"]
    dense, moe, _, _ = layer_counts(c)
    outside = ((dense + moe) * (attention_params(c) + 2 * e) + dense * 3 * e * c["intermediate_size"]
               + moe * (e * c["num_experts_routed"] + c["num_shared_experts"] * expert_params(c))
               + V * e + e + slots * e)
    held = moe * (experts_touched(c, slots) if touched is None else touched) * expert_params(c)
    return int((outside + held) * bytes_per) + cache_step_bytes(c, [t_max] * slots if lengths is None else lengths,
                                                                bytes_per)


# -- tolerances ------------------------------------------------------------------
# harness/reference.py says which program each of the three serving tolerances
# holds.  The readings are the chip's at the published widths, 8 layers (1 dense +
# 7 expert; 6 window + 2 full), taken as the cell's check takes them (the four
# check streams of traffic/longrag-closed.json served together, prompts 1100, 2100,
# 3900, 5900 and 64 tokens each, teacher-forced through this reference in float32):
# the program over 16 runs on 16 seeds of the tree as it stands (2 through a batcher
# alone in one process, `_archive/controls43.py`; 14 runs of the cell), and the
# nearest precision below bf16 over 2 seeds: every matrix rounded to float8 e4m3's
# 3 bits of mantissa and the streams served from them ("float8-all").  A draft of
# this PR (prompts 1100, 2100, 3000, 3900; a held share's prefill combined in bf16,
# which the review took out) gave 14 readings more on 12 seeds; they are named
# where they were the larger.  My chip runs, PR 43; PERF.md section 6 has every
# number and the seeds.
#
# The router still sets the program's tail (references/mla_moe.py says how: where
# the bf16 stream and this float32 pass take or leave another held expert, a whole
# weighted expert's result differs from there on), but the numbers are a tenth of
# A.X-K1's: here each half's output is normed before it joins the stream, so a
# flipped expert is rescaled with the rest of its layer's FFN.
#
# The logits at a prompt's last row (4 rows x 19,200 a run): the program
# 0.027-0.036 in 11 runs and 0.218-0.242 in 5, the seeds on which that row itself
# takes or leaves a held expert in one pass and not in the other
# (`_archive/flip_probe43.py`: PERF.md section 6 has the rows and layers); the
# draft's runs 0.026-0.038 in 12, 0.259 in 1, 0.499 in 1.  A largest of many has a
# tail, so the bound stands in the middle of the two readings and not near the
# lower.  float8-all 2.52, 2.56.  The bound is 4.5 x over the program's largest
# (2.2 x over the draft's) and 2.3 x under float8's least.
LOGIT_TOL = 1.1
# The largest regret of 256 tokens: the program 0.003-0.172; float8-all 2.14, 2.40.
# 3.2 x over, 3.9 x under.
REGRET_MAX_TOL = 0.55
# The mean regret: the program 0.00001-0.0019; float8-all 0.633, 0.674.  10 x over,
# 32 x under.
REGRET_MEAN_TOL = 0.02
# No training cell runs this architecture; the dense decoder's bound and reason.
LOSS_TOL = 0.01


# -- the mechanisms by themselves ---------------------------------------------------
# What the logits cannot see.  Each number is the program's own code at the window's
# shapes against this file's plain mathematics ON THE SAME ROWS: what the reference's
# own float32 pass gave each layer (`_given`), rounded to the program's activation
# type, which is how a block hands them over.
#
#   swa_decode_rel_err, full_decode_rel_err    a decode step's attention in one
#       window layer and one full layer (the first of each kind that is an expert
#       layer): for every served position of the check streams, concat(o) [H D] as
#       the program's decode core gives it (`models/generate.py` `_kv_decode_core`,
#       one token a row at [slots, 1, .], the streams in slots 0, 1, ... at the
#       depths and pads the batcher gave them, the other slots empty, over a cache
#       of the deployment's [slots, T_max] and [slots, ring] that holds each stream
#       as a prefill stores it, the steps one after the other so that the ring is
#       written round as serving writes it) against this file's masked attention
#       in float32.  Both start from the same q, k and v: this file's own, of the
#       rows as given, rounded to the program's activation type.  The largest
#       |program - reference| / |reference| of a row (2-norms over H D).
#   swa_full_mask_miss    the same window layer's program output against this
#       reference WITH THE MASK LEFT FULL, which has to miss: FULL_MASK_FACTOR x
#       the window's bound over that error, held under 1.  It says that the check
#       above can tell a window from none at these depths, whatever the seed.
#   moe_router_other_set, moe_experts_rel_err    A.X-K1's two numbers of the expert
#       layer, through the same entry (references/mla_moe.py says what they are),
#       over every expert layer, whichever stack it lies in.
#
# The tolerances, from the chip at the cell's own size (my chip runs, PR 43; each
# control planted once the streams are served, 2 seeds, so the numbers on the logits
# are the program's while `ok` comes out false by the control's own number:
# `_archive/controls43.py`).  Lower reading: the program's largest.  Upper: the
# control's least.
#   The window layer's core: the program 0.00174-0.00177 in 30 runs (a maximum over 252 rows with
#   hardly a tail); the stacks rounded to float8's 3 bits under the decode kernel
#   0.01123, 0.01175; a window of 127 in the program's mask 0.0433, 0.0492, of 129
#   0.0470, 0.0545; the whole ring of 256 seen 0.666, 0.668.  The bound is 2.5 x
#   over the program's largest and 2.5 x under float8's least.
#   The full layer's core: the program 0.00169-0.00173; its stacks rounded to float8
#   0.00366, 0.00372 (a row's thousands of values average the rounding out: a third
#   of the window's).  The bound is 1.45 x over the program's largest and 1.46 x
#   under float8's least; what room there is lies between readings that do not move
#   in their third digit from seed to seed.
#   The full mask: at depths of 1,100-5,900 this reference with the mask left full
#   differs from the program's window by 2.2-2.8 x its own norm (128 positions' mean
#   against thousands'), 500-620 x the bound; the
#   factor asks for 50.
#   The router and the held experts: the program 0 of 92,764 pairs in every run and
#   0.0044-0.0047 over the rows whose sets agree; the bounds and their reasons are references/mla_moe.py's (float8
#   in the experts reads 0.06 through the same kernels).
SWA_DECODE_ERR_TOL = 0.0045
FULL_DECODE_ERR_TOL = 0.0025
FULL_MASK_FACTOR = 50.0
MOE_ROUTER_SET_TOL = _mla.MOE_ROUTER_SET_TOL
MOE_EXPERTS_ERR_TOL = _mla.MOE_EXPERTS_ERR_TOL


@functools.partial(jax.jit, static_argnames=("dims", "lay", "window", "dtype"))
def _qkv_rows(rows, lp, *, dims, lay, window, dtype):
    """This file's own q, k, v of every row of every stream (each stream's
    positions count from 0), in float32 from the rows as given, rounded to the
    program's activation type: what both sides of the decode errors start from."""
    with jax.default_matmul_precision("highest"):
        parts = [_qkv(rows[off:off + n + t].astype(jnp.float32), lp, dims, window) for off, n, t, _ in lay]
    return tuple(jnp.concatenate(p).astype(dtype) for p in zip(*parts))


def _decode_program(cb, lay, decode, kind: str):
    """The compiled program of one layer's decode cores: (q, k, v of every row
    of every stream, in the activations' type) -> concat(o) [steps * slots, H D]
    of every (step, slot), through the program's own core,
    `generate._kv_decode_core`, one token a row at [slots, 1, .] over a cache of
    one layer of `kind` at the deployment's slots and extents that holds each
    stream's prompt as a prefill stores it (left pads, then its rows; in a ring
    the last columns of the bucket, column j at slot j mod the extent).  The
    steps run one after the other and the core writes each step's own k and v,
    as serving does."""
    from cluster_anywhere_tpu.models import generate

    one = dataclasses.replace(cb.cfg, n_layers=1, n_dense_layers=0, layer_mixers=(kind,))
    pads = np.zeros(cb.slots, np.int32)
    pos = np.zeros(decode.shape, np.int32)
    for slot, (_, n, _, pad) in enumerate(lay):
        pads[slot], pos[:, slot] = pad, pad + n + np.arange(len(decode))
    n_rows = sum(n + t for _, n, t, _ in lay)

    @jax.jit
    def program(q, k, v):
        cache = generate.init_cache(one, cb.slots, cb.t_max)
        names = generate.LAYER_STATE[kind]
        extent = cache[names[0]].shape[2]
        for slot, (off, n, _, pad) in enumerate(lay):
            for name, a in zip(names, (k, v)):
                cols = jnp.pad(a[off:off + n], ((pad, 0), (0, 0), (0, 0)))  # the bucket's columns
                first = max(pad + n - extent, 0)  # the first column the layer still holds
                kept = jnp.roll(jnp.pad(cols[first:], ((0, extent - (pad + n - first)), (0, 0), (0, 0))),
                                first % extent, axis=0)
                cache[name] = cache[name].at[0, slot].set(kept)
        at = lambda a, row: jnp.pad(a, [(0, 1)] + [(0, 0)] * (a.ndim - 1))[row][:, None]  # an empty slot's row: zeros

        def step(cache, now):
            row, p = now
            o, cache = generate._kv_decode_core(cache, 0, p, jnp.asarray(pads), one, at(q, row), at(k, row),
                                                at(v, row), live=row < n_rows, kind=kind)
            return cache, o.reshape(cb.slots, -1)

        _, out = lax.scan(step, cache, (jnp.asarray(decode), jnp.asarray(pos)))
        return out.reshape(-1, out.shape[-1])

    return program


@functools.partial(jax.jit, static_argnames=("lay", "slots", "window"))
def _decode_errors(q, k, v, got, *, lay, slots, window):
    """The largest relative error of `got` [steps * slots, H D] against this
    file's masked attention of each stream's decode rows from the same q, k, v,
    a stream attended by itself, under the band of `window` (0: none)."""
    f32 = lambda a, off, n, t: a[off:off + n + t].astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        worst = jnp.zeros((), jnp.float32)
        for slot, (off, n, t, _) in enumerate(lay):
            want = _attention(f32(q, off, n, t), f32(k, off, n, t), f32(v, off, n, t), window,
                              rows=n + jnp.arange(t))
            mine = got[np.arange(t) * slots + slot].astype(jnp.float32)
            worst = jnp.maximum(worst, jnp.max(jnp.linalg.norm(mine - want, axis=-1) / jnp.linalg.norm(want, axis=-1)))
        return worst


def _first_expert_layer(cfg, window: bool) -> int:
    """The first expert layer that is a window layer (or a full one)."""
    return next(i for i, (_, _, w, is_moe) in enumerate(_layers(cfg)) if is_moe and bool(w) == window)


def attention_checks(cb, streams, given):
    """(the window layer's decode error, the full layer's, the window layer's
    against the full mask): `mechanism_checks`' first three numbers before they
    are held to anything."""
    params, cfg = cb.params, cb.cfg
    lay, decode = _mla.program_shapes(cb, streams)
    errors = {}
    for window in (True, False):
        i = _first_expert_layer(cfg, window)
        stack, j, w, _ = _layers(cfg)[i]
        kind = "attn_win" if window else "attn"
        rows = jnp.asarray(np.concatenate([g[0][i] for g in given]))
        qkv = _qkv_rows(rows, _layer_of(params, stack, j), dims=_dims(cfg), lay=lay, window=w,
                        dtype=jnp.dtype(cfg.dtype).name)
        got = _decode_program(cb, lay, decode, kind)(*qkv)
        errors[kind] = float(_decode_errors(*qkv, got, lay=lay, slots=cb.slots, window=w))
        if window:
            errors["full_mask"] = float(_decode_errors(*qkv, got, lay=lay, slots=cb.slots, window=0))
    return errors["attn_win"], errors["attn"], errors["full_mask"], lay, decode


def mechanism_checks(cb, streams):
    """The five numbers above (references/__init__.py says what the harness
    does with them)."""
    from cluster_anywhere_tpu.parallel.moe import EXPERT_MATRICES

    params, cfg = cb.params, cb.cfg
    given = [_given.pop(ids.tobytes(), None) or _given_of(params, ids, cfg)[1] for ids in map(_stream_ids, streams)]
    n = sum(len(g[0][0]) for g in given)
    swa, full, full_mask, lay, decode = attention_checks(cb, streams, given)

    # the expert layer: every expert layer, in whichever stack its weights lie
    program = _mla._experts_program(cb, lay, decode, n)
    numbers = []
    for i, (stack, j, _, is_moe) in enumerate(_layers(cfg)):
        if not is_moe:
            continue
        if numbers:
            jax.block_until_ready(numbers[-1])  # one layer's float32 copies at a time
        experts = {name: params[stack][name] for name in EXPERT_MATRICES if name in params[stack]}
        rows = jnp.asarray(np.concatenate([g[1][i] for g in given]))
        got, chosen = program(rows, params[stack]["router"], experts, j)
        numbers.append(_mla._expert_errors(rows, _layer_of(params, stack, j), got, chosen, moe=_mla._moe_dims(cfg)))
    other_sets, worst = (np.asarray(x) for x in zip(*numbers))
    pairs = n * len(numbers)
    rows_checked = sum(t for _, _, t, _ in lay)
    return [
        {"name": "swa_decode_rel_err", "error": swa, "tolerance": SWA_DECODE_ERR_TOL,
         "why": f"largest relative error of a decode row's attention in a window layer (window {cfg.attn_window}, "
                f"read through its ring) against the masked attention in float32, over {rows_checked} rows"},
        {"name": "full_decode_rel_err", "error": full, "tolerance": FULL_DECODE_ERR_TOL,
         "why": f"the same in a full layer, over its cache of {cb.t_max} slots a row"},
        {"name": "swa_full_mask_miss", "error": FULL_MASK_FACTOR * SWA_DECODE_ERR_TOL / max(full_mask, 1e-30),
         "tolerance": 1.0,
         "why": f"{FULL_MASK_FACTOR:g} x the bound over the window layer's error against the reference with the "
                f"mask left full ({full_mask:.4g}): under 1 where the check tells a window from none"},
        {"name": "moe_router_other_set", "error": int(other_sets.sum()) / pairs, "tolerance": MOE_ROUTER_SET_TOL,
         "why": f"(row, layer) pairs of {pairs} in which the program's set of {cfg.n_experts_per_tok} of "
                f"{cfg.n_experts} is not the float32 reference's"},
        {"name": "moe_experts_rel_err", "error": float(worst.max()), "tolerance": MOE_EXPERTS_ERR_TOL,
         "why": "largest relative error of the held experts' part of a row's result, over the rows whose sets "
                "agree (zeros where a row chose none of them)"},
    ]
