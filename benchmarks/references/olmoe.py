"""OLMoE's decoder (allenai/OLMoE-1B-7B): RMSNorm, multi-head attention with
an RMSNorm on the whole projected q and k, rotary embeddings, a mixture of
gated experts behind a softmax router (the k largest of the probabilities, not
renormalised unless `norm_topk_prob`), no shared expert, untied output head.
The interface is the package's (references/__init__.py).

The equations are those of Hugging Face's `modeling_olmoe.py`:

    h   = x + Attn(RMSNorm(x));   out = h + MoE(RMSNorm(h));   final RMSNorm; head
    q   = RMSNorm_q(x Wq),  k = RMSNorm_k(x Wk)   (each over the whole projected
          vector, before the split into heads),  v = x Wv;  rotary on q and k;
          causal softmax;  Wo
    p   = softmax(x Wr) over all experts, in float32
    MoE = sum over the k largest p_e of  p_e * (silu(x Wg_e) * (x Wu_e)) Wd_e

The plain reference is straightforward `jax.numpy` in float32 at `highest`
matmul precision: no kernel, no cache, no batching, no sorting of tokens.
Layers run one at a time in a Python loop and, inside a layer, the experts one
at a time in a loop: every expert is computed for every token and weighted by
the token's router probability if the expert is among its k, by 0 otherwise, so
that only one expert's weights are upcast at a time and nothing of the size
[T, k, E, F] is gathered.  It shares no code with `cluster_anywhere_tpu/models/`
or `parallel/`; it reads the same parameter tree.  The training auxiliary and
z losses are no part of serving and no part of `loss`.

Departures from the published model, which the configuration file lists: the
RMSNorm epsilon is the program's 1e-6 (published: 1e-5), and the rotary
embedding rotates adjacent pairs (x[2i], x[2i+1]) as the program does (Hugging
Face rotates halves: a permutation of the columns of Wq, Wk and of the two
norms' weights; on random weights the two are the same model).
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax

RMS_EPS = 1e-6
ATTN_BLOCK = 512  # query rows per block: bounds the [heads, block, T] scores
# what this architecture's programs write beyond the common names
# (program_trace.SCOPES), all four under `ffn` (parallel/moe.py routed_ffn)
SCOPES = ("moe.router", "moe.dispatch", "moe.experts", "moe.combine")
# The grouped matmul is `lax.ragged_dot`, which the TPU's compiler turns into a
# Mosaic kernel of its own.  That kernel's instruction carries no op_name, so it
# is under no scope in a trace and is known by its name, `%ragged-dot-none[.n]`
# (the activation between the matmuls stays under `moe.experts`).
KERNELS = ("ragged-dot-none",)


def program_config(config_file: Dict[str, Any], **extra) -> Dict[str, Any]:
    """The program's TransformerConfig fields from a configuration file's
    published keys."""
    c = config_file["config"]
    out = dict(
        d_model=c["hidden_size"], n_layers=c["num_hidden_layers"],
        n_heads=c["num_attention_heads"], n_kv_heads=c["num_key_value_heads"],
        d_head=c["head_dim"], d_ff=c["intermediate_size"], rope_theta=float(c["rope_theta"]),
        max_seq_len=c["max_position_embeddings"],
        n_experts=c["num_experts"], n_experts_per_tok=c["num_experts_per_tok"],
        moe_renormalize=bool(c["norm_topk_prob"]), moe_gated=True, qk_norm=True,
    )
    out.update(extra)
    return out


def _rms_norm(x, w):
    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + RMS_EPS)) * w


def _rope(x, theta: float):
    """x: [T, H, D] at positions 0..T-1."""
    t, _, d = x.shape
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]  # [T, D/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1).reshape(x.shape)


def _experts(y, lp, k: int, renormalize: bool):
    """MoE(y) for y [T, E]: the router's k largest probabilities of each token,
    then one expert after the other over every token."""
    probs = jax.nn.softmax(y @ lp["router"].astype(jnp.float32), axis=-1)  # [T, X]
    top, idx = lax.top_k(probs, k)
    if renormalize:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    # [T, X]: a token's weight for an expert, 0 where it is not among its k
    weight = jnp.sum(jax.nn.one_hot(idx, probs.shape[-1], dtype=jnp.float32) * top[..., None], axis=1)

    def one_expert(acc, e):
        wg, wu, wd, w_e = e
        wg, wu, wd = (w.astype(jnp.float32) for w in (wg, wu, wd))
        return acc + w_e[:, None] * ((jax.nn.silu(y @ wg) * (y @ wu)) @ wd), None

    out, _ = lax.scan(one_expert, jnp.zeros_like(y), (lp["w_gate"], lp["w_up"], lp["w_down"], weight.T))
    return out


@functools.partial(jax.jit, static_argnames=("dims",))
def _layer(x, lp, *, dims):
    """One block over one sequence.  x: [T, E] float32; lp: this layer's
    weights in whatever type they are stored in."""
    h, kv, d, theta, k, renormalize = dims
    f32 = lambda name: lp[name].astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        t = x.shape[0]
        y = _rms_norm(x, f32("ln1"))
        q = _rms_norm(y @ f32("wq"), f32("q_norm")).reshape(t, h, d)
        kk = _rms_norm(y @ f32("wk"), f32("k_norm")).reshape(t, kv, d)
        v = (y @ f32("wv")).reshape(t, kv, d)
        q, kk = _rope(q, theta), _rope(kk, theta)
        kk, v = jnp.repeat(kk, h // kv, axis=1), jnp.repeat(v, h // kv, axis=1)
        outs = []
        for lo in range(0, t, ATTN_BLOCK):
            hi = min(t, lo + ATTN_BLOCK)
            s = jnp.einsum("qhd,khd->hqk", q[lo:hi], kk[:hi]) * d ** -0.5
            causal = jnp.arange(lo, hi)[:, None] >= jnp.arange(hi)[None, :]
            p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
            outs.append(jnp.einsum("hqk,khd->qhd", p, v[:hi]))
        x = x + jnp.concatenate(outs, axis=0).reshape(t, h * d) @ f32("wo")
        return x + _experts(_rms_norm(x, f32("ln2")), lp, k, renormalize)


def forward(params: Dict[str, Any], ids, cfg):
    """ids: [T] -> logits [T, V], float32.  `cfg`: the program's
    TransformerConfig, read for its head counts, head size, rope_theta, the
    experts a token takes and whether their probabilities are renormalised."""
    dims = (cfg.n_heads, cfg.n_kv_heads, cfg.d_head, float(cfg.rope_theta),
            cfg.n_experts_per_tok, bool(cfg.moe_renormalize))
    x = params["embed"][jnp.asarray(ids)].astype(jnp.float32)
    n_layers = params["blocks"]["wq"].shape[0]
    for i in range(n_layers):
        x = _layer(x, jax.tree_util.tree_map(lambda w: w[i], params["blocks"]), dims=dims)
    with jax.default_matmul_precision("highest"):
        x = _rms_norm(x, params["ln_f"].astype(jnp.float32))
        return x @ params["lm_head"].astype(jnp.float32)


def loss(params, ids, cfg) -> float:
    """Mean next-token cross entropy of one sequence ids[:-1] -> ids[1:]."""
    ids = jnp.asarray(ids)
    logits = forward(params, ids[:-1], cfg)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, ids[1:, None], axis=-1)[:, 0]
    return float(jnp.mean(logz - gold))


# -- counts from shapes ---------------------------------------------------------
# `c` is the `config` object of a configuration file (the published keys).


def _dims(c: Dict[str, Any]):
    return (c["hidden_size"], c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"],
            c["intermediate_size"], c["num_hidden_layers"], c["vocab_size"], c["num_experts"],
            c["num_experts_per_tok"])


def _attention_params(c: Dict[str, Any]) -> int:
    """wq, wk, wv, wo and the two norms over the projected q and k."""
    e, h, kv, d = _dims(c)[:4]
    return e * h * d + 2 * e * kv * d + h * d * e + h * d + kv * d


def expert_params(c: Dict[str, Any]) -> int:
    """One expert's three matrices."""
    return 3 * c["hidden_size"] * c["intermediate_size"]


def expert_bytes(c: Dict[str, Any], bytes_per: int = 2) -> int:
    """What reading one expert of one layer costs."""
    return expert_params(c) * bytes_per


def param_count(c: Dict[str, Any]) -> int:
    e, _, _, _, _, L, V, X, _ = _dims(c)
    per_layer = _attention_params(c) + e * X + X * expert_params(c) + 2 * e
    return L * per_layer + 2 * V * e + e


def train_flops_per_step(c: Dict[str, Any], batch: int, seq: int) -> float:
    """Operations the forward and backward passes require for `batch`
    sequences of `seq` tokens: 2 per multiply-add over the weights a token
    meets (its k experts, not all of them, and the router), attention counted
    in full (the 4*t*t*d*h square, no causal discount), backward twice the
    forward, recomputation not counted."""
    e, h, kv, d, _, L, V, X, k = _dims(c)
    matmul_weights = e * h * d + 2 * e * kv * d + h * d * e + e * X + k * expert_params(c)
    fwd = batch * seq * 2 * matmul_weights * L + batch * 4 * seq * seq * d * h * L + batch * seq * 2 * e * V
    return 3.0 * fwd


def experts_touched(c: Dict[str, Any], rows: int) -> float:
    """The experts of one layer that `rows` tokens read between them if each
    takes its k at random: X (1 - (1 - k/X)^rows)."""
    X, k = c["num_experts"], c["num_experts_per_tok"]
    return X * (1.0 - (1.0 - k / X) ** rows)


def decode_step_bytes(c: Dict[str, Any], slots: int, t_max: int, bytes_per: int = 2) -> int:
    """Bytes one decode step has to read at the least: every weight outside
    the experts once (the embedding only its `slots` rows), of each layer's
    experts the expected number that a batch of `slots` rows touches
    (`experts_touched`: 63 of 64 at 32 rows, 35 at 6), and the whole key/value
    cache, which the program attends over in full whatever the rows' depths."""
    e, _, kv, d, _, L, V, X, _ = _dims(c)
    outside = L * (_attention_params(c) + e * X + 2 * e) + V * e + e + slots * e
    experts = L * experts_touched(c, slots) * expert_params(c)
    cache = 2 * L * slots * t_max * kv * d
    return int((outside + experts + cache) * bytes_per)


# -- tolerances ------------------------------------------------------------------
# harness/reference.py says which program each of the three serving tolerances
# holds.  Each is set from two readings on the chip at the published widths, 10
# layers (PERF.md section 6, PR 27), both taken as the cell's check takes them:
# the four check streams of traffic/chat-closed.json served together, 256
# positions, teacher-forced through this reference in float32.  The first is the
# largest the program (bf16 weights and activations, float32 router softmax)
# gave over 47 runs on 46 seeds.  The second is the nearest precision below
# bf16, every matrix rounded to float8 e4m3, over 12 seeds and in two forms that
# agree: this reference in float32 over the rounded matrices, choosing its own
# token at each of the program's 256 positions, and the program itself serving
# the streams from the rounded matrices (a decode in float8).  It has to come out
# as not correct.
#
# Logits at the prompt's last row: the program 0.041-0.112 (the dense decoder's
# 33 runs ranged 0.031-0.144 under its 0.25); float8 0.465-0.593.  The bound is
# 1.8 x the program's largest reading and 2.3 x under float8's least.
#
# A router near-tie at rank 8/9: with its inputs rounded to bf16, as the program
# computes them, this reference picks another set of 8 in 44-59 of 4,640
# (token, layer) pairs (0.9-1.3%).  Each swaps two experts whose probabilities
# are equal to 3 digits, and all of them together move the prompt row's logits by
# 0.005-0.056 and flip no token: inside the program's own 0.04-0.11.  What no
# bound here can see, measured the same way: the router's softmax in bf16
# (106-154 swaps, logits 0.012-0.054) and float8 in the experts alone (logits
# 0.056-0.087, regrets 0.025-0.077 and 0.00025-0.0025 over 12 seeds, the
# program's own range; an expert's result enters the stream times a probability
# of 0.02-0.05).  Both sit inside bf16's own noise at this depth: they need a
# check of the expert layer's output by itself, which is the harness's to add
# (PERF.md section 7, ROADMAP R0b).
LOGIT_TOL = 0.2
# The regret of the served tokens, which alone holds the batch decode: the best
# two of 50,304 logits lie 0.06-0.33 apart at the median position, and bf16
# flips 1-10% of the tokens to a neighbour.  Over 64 runs on 63 seeds the
# program's largest regret read 0-0.068 in 63 and **0.113** in one (seed
# 4100000384: its mean 0.0013, logits 0.056, 6% of the tokens flipped, nothing
# else of that run apart), its mean 0-0.0014; float8 read 0.175-0.47 and
# 0.0056-0.066 in 11 seeds of 12, in both forms.  The largest of 256 regrets is
# the weakest of the three statistics: a flip costs the distance between the
# best two logits, and rounding that moves each by up to 0.1 can flip a pair
# 0.2 apart, so its tail runs towards float8's least reading.  Its bound is the
# geometric mean of the two readings, 0.113 and 0.175: 1.24 x over the
# program's largest, 1.25 x under float8's least.  It was 0.12 at first and 0.1
# after the review, set on 47 runs whose largest was 0.064, and the 62nd run
# read 0.113: not correct by a bound that its own program had not been sampled
# far enough to set.  The mean's bound stays: 1.5 x over the program's largest
# and 2.8 x under float8's least, and it fails every float8 seed that the
# largest fails.  In the twelfth seed the streams settle where the best two
# logits lie 0.33 apart, float8 reads 0.030-0.037 and 0.00024-0.0004, under the
# program's own largest, and no bound on a regret that the program passes can
# fail it: a decode in float8 fails 11 runs in 12, and the driver makes dozens.
# No bound between 0.1 and 0.17 changes a verdict on the 12 float8 seeds.  The
# dense decoder's 60 seeds, the same check at like noise, have one at 0.077 and
# means to 0.0014 under its 0.12 (PERF.md section 6 has the history).
REGRET_MAX_TOL = 0.14
REGRET_MEAN_TOL = 0.002
# A training step's first loss against `loss`: no training cell runs this
# architecture yet (ROADMAP R1's four-chip follow-up sets it from its own chip
# readings); until then the dense decoder's bound, whose reason (bf16 rounding
# through the stack moves a mean over thousands of positions by 1e-3 at most)
# holds here as well.
LOSS_TOL = 0.01
