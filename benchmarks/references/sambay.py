"""A decoder-hybrid-decoder (SambaY, arXiv:2507.06607) with differential
attention (arXiv:2410.05258): the key set of
`microsoft/Phi-4-mini-flash-reasoning` (`model_type` `phi4flash`;
configs/phi-4-mini-flash-reasoning-serve1.json).  The first half of the stack
alternates Mamba-1 mixers and window attention; one full-attention layer ends
it; the second half alternates gated memory units, which gate the read-out of
the first half's last Mamba layer, and cross attention, which reads the full
layer's keys and values and projects none of its own.  The interface is the
package's (references/__init__.py).

The equations, with L layers, i a layer's index from 0, u = LN1(x) a block's
normed input, every product without a bias unless one is named:

    every layer   x = x + Mixer_i(LN1(x));  x = x + MLP(LN2(x));  final LN;  logits = x E^T
                  LN(x) = (x - mean) / sqrt(var + eps) * w + b          (`layer_norm_eps`)
                  MLP(u) = (silu(u Wg) * (u Wu)) Wd
    mixer of i    even i <  L/2 + 1: Mamba-1          (`mb_per_layer` 2: i = 0, 2, ... L/2)
                  odd  i <  L/2:     differential attention over the last `sliding_window` positions
                  i = L/2 + 1:       differential attention over every earlier position
                  even i >  L/2 + 1: gated memory unit
                  odd  i >  L/2 + 1: differential cross attention on layer L/2 + 1's keys and values
    Mamba-1       [xs, z] = u W_in                                   (E -> 2 C, C = 2 E)
                  xc_t = silu(b_c + sum_j w_c[j] * xs_{t-K+1+j})     (K = 4, zeros before the start)
                  [r_t, B_t, C_t] = xc_t W_x                         (C -> R + N + N, R = ceil(E / 16), N = 16)
                  dt_t = softplus(r_t W_dt + b_dt);  A = -exp(A_log)   (no norm on r, B, C: plain Mamba-1)
                  h_t[c, n] = exp(dt_t[c] A[c, n]) h_{t-1}[c, n] + dt_t[c] B_t[n] xc_t[c],  h_{-1} = 0
                  y_t[c] = sum_n h_t[c, n] C_t[n] + D[c] xc_t[c]
                  Mixer(u)_t = (y_t * silu(z_t)) W_out;    layer L/2's y is the MEMORY m
    gated memory  Mixer(u)_t = (m_t * silu(u_t W_1)) W_2             (E -> C -> E; m of the SAME position)
    differential  q = u W_q + b_q [T, H, D];  k = u W_k + b_k, v = u W_v + b_v [T, KV, D]
    attention     query pair p (of H / 2): q1 = q[2p], q2 = q[2p + 1];  its cached pair g = p // (H / KV):
                  k1 = k[2g], k2 = k[2g + 1], V = concat(v[2g], v[2g + 1])   [T, 2 D]
                  A1 = softmax(q1 k1^T / sqrt(D) + mask), A2 = softmax(q2 k2^T / sqrt(D) + mask)
                  o_p = (A1 - lambda A2) V;   o_p = RMSNorm_2D(o_p; w_sub, eps) * (1 - lambda_init)
                  lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init,  lambda_init = 0.8 - 0.6 exp(-0.3 i)
                  Mixer(u) = concat_p(o_p) W_o + b_o;   the mask is causal, and banded in a window layer
    cross         the same with q, W_o, the lambdas and w_sub its own and k, v layer L/2 + 1's; no W_k, W_v
    no positional embedding anywhere

The published model prefills a prompt of N tokens through layers 0 .. L/2 and
layer L/2 + 1's keys and values over all N, and everything above at position
N - 1 alone, which gives the same logits at N - 1.  This reference does NOT: it
runs every layer over every position, keeps no cache and no recurrent state
between calls, and writes the two softmaxes as two softmaxes.

The plain reference is straightforward `jax.numpy` in float32 at `highest`
matmul precision: no kernel, no cache, no ring, no batching, no chunking, no
early exit.  Layers run one at a time in a Python loop with one layer's
weights upcast at a time; the recurrence is a sequential `lax.scan` over the
positions.  It shares no code with `cluster_anywhere_tpu/models/`; it reads
the same parameter tree (`ssm_blocks`, `win_blocks`, `blocks`, `gmu_blocks`,
`cross_blocks`: each kind's layers stacked in their order; `conv_w` is stored
[K, C]).  (`mechanism_checks`, at the end, calls the program's own functions as
what it checks, not as a reference.)

Assumed, which the configuration file lists with where each was taken from
(the family's published `modeling_phi4flash.py` / `configuration_phi4flash.py`
and the two papers; the row's `config` has no key for them): the layer map;
the Mamba sizes (the class's defaults, Mamba-1's own); differential attention
with its lambda and sub-norm; LayerNorm; the biases on W_qkv and W_o; no
positional embedding; head_dim 64.  Departures: temperature 0; the
convolution's weight stored [K, C] (Hugging Face: [C, 1, K]); the fused
W_qkv stored as three matrices; adjacent heads pair (2p, 2p + 1) where the
published code pairs head p with head p + H / 2 and halves of the value
likewise: a permutation of W_q's, W_k's and W_v's columns and W_o's rows, on
random weights the same model.
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

# how the check streams' rows lie in the calls that serve them (`program_shapes`) is A.X-K1's
# file's: host arithmetic on the batcher's own buckets
_mla = manifest.load_reference("mla_moe", os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

ATTN_BLOCK = 256  # query rows per block: bounds the [pairs, block, T] scores
# what this architecture's programs write beyond the common names (program_trace.SCOPES): a
# state-space layer's and a gated memory unit's in place of the `attn.*`, the attention
# core by the stack it reads, and what differential attention does outside the kernel
SCOPES = ("ssm.in", "ssm.conv", "ssm.scan", "ssm.state", "ssm.out", "gmu.in", "gmu.gate", "gmu.out",
          "attn.core.window", "attn.core.full", "attn.core.cross", "attn.diff")
# the banded prefill kernel of a window layer (ops/attention.py WINDOW_KERNEL) and the decode
# kernel: differential attention runs in them as they are (a pair's two cached heads are one
# head of twice the width), so it brings no kernel of its own
KERNELS = ("swa_flash", "decode_attn")

_MIXERS = ("ssm", "attn_win", "attn", "gmu", "attn_cross")


def layer_map(c: Dict[str, Any]):
    """Each layer's mixer, in the model's order (the table in this file's
    docstring), from `num_hidden_layers`, `mb_per_layer` and `sliding_window`."""
    n, every = c["num_hidden_layers"], c["mb_per_layer"]
    if every != 2 or n % 4 or not c["sliding_window"]:
        raise ValueError("this file writes the map of mb_per_layer 2 over a depth that is a multiple of 4, "
                         "with a sliding window")
    full = n // 2 + 1
    return tuple(
        ("ssm" if i % 2 == 0 else "attn_win") if i < full - 1 else
        "ssm" if i == full - 1 else "attn" if i == full else
        ("gmu" if i % 2 == 0 else "attn_cross")
        for i in range(n))


def program_config(config_file: Dict[str, Any], **extra) -> Dict[str, Any]:
    """The program's TransformerConfig fields from a configuration file's keys.
    A program that lacks one of the fields cannot run the configuration:
    refused here, by name, before anything is deployed."""
    from cluster_anywhere_tpu.models.transformer import TransformerConfig

    c = config_file["config"]
    if c["hidden_act"] != "silu" or c["mlp_bias"] or c["lm_head_bias"] or c["mamba_proj_bias"]:
        raise ValueError("this file writes a gated silu MLP, an unbiased head and unbiased Mamba projections")
    if c["hidden_size"] != c["num_attention_heads"] * c["head_dim"]:
        raise ValueError("head_dim is hidden_size / num_attention_heads")
    if list(c.get("layer_map") or layer_map(c)) != list(layer_map(c)):  # written out in the file, for a reader
        raise ValueError("layer_map is not what num_hidden_layers, mb_per_layer and sliding_window give")
    if not (c.get("differential_attention", True) and c.get("gmu", True) and c.get("no_positional_embedding", True)
            and c.get("norm", "layer_norm") == "layer_norm"):
        raise ValueError("this file writes differential attention, gated memory units, LayerNorm and no positional "
                         "embedding")
    out = dict(
        d_model=c["hidden_size"], n_layers=c["num_hidden_layers"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], d_head=c["head_dim"], d_ff=c["intermediate_size"],
        max_seq_len=c["max_position_embeddings"],
        layer_mixers=layer_map(c), attn_window=c["sliding_window"],
        rotary=False, tie_embeddings=bool(c["tie_word_embeddings"]),
        layer_norm=True, norm_eps=float(c["layer_norm_eps"]), attn_bias=bool(c.get("attention_bias", True)),
        diff_attn=True,
        ssm_d_state=c["mamba_d_state"], ssm_d_conv=c["mamba_d_conv"], ssm_expand=c["mamba_expand"],
        ssm_dt_rank=c["mamba_dt_rank"], ssm_conv_bias=bool(c["mamba_conv_bias"]),
        ssm_inner_norms=bool(c.get("mamba_inner_norms", False)),
    )
    out.update(extra)
    lacking = sorted(set(out) - {f.name for f in dataclasses.fields(TransformerConfig)})
    if lacking:
        raise NotImplementedError(
            f"this program's TransformerConfig has no {lacking}: it runs no layer that reads another layer's keys "
            "and values or its memory, and no differential attention; this configuration cannot run on it"
        )
    return out


# -- the mathematics ---------------------------------------------------------------


def _layer_norm(x, w, b, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * w + b


def _mlp(x, lp, eps):
    f32 = lambda name: lp[name].astype(jnp.float32)
    u = _layer_norm(x, f32("ln2"), f32("ln2_b"), eps)
    return x + (jax.nn.silu(u @ f32("w_gate")) * (u @ f32("w_up"))) @ f32("w_down")


@functools.partial(jax.jit, static_argnames=("dims",))
def _mamba_core(xs, lp, dims):
    """The mixer between its two projections, over one sequence's xs [T, C]
    from h = 0 and zeros before the start: the convolution, the step size, B
    and C of each position, and the recurrence.  Returns (y [T, C] before the
    gate, h [C, N] after the last position)."""
    n, r, kw = dims
    f32 = lambda name: lp[name].astype(jnp.float32)
    t = xs.shape[0]
    padded = jnp.concatenate([jnp.zeros((kw - 1, xs.shape[1]), xs.dtype), xs], axis=0)
    xc = sum(f32("conv_w")[j] * padded[j:j + t] for j in range(kw))
    if "conv_b" in lp:
        xc = xc + f32("conv_b")
    xc = jax.nn.silu(xc)
    low = xc @ f32("ssm_x")
    step, b, c = low[:, :r], low[:, r:r + n], low[:, r + n:]
    dt = jax.nn.softplus(step @ f32("ssm_dt") + f32("dt_bias"))  # [T, C]
    a = -jnp.exp(f32("a_log"))  # [C, N]

    def one_position(h, at):
        dt_t, b_t, c_t, xc_t = at
        h = jnp.exp(dt_t[:, None] * a) * h + (dt_t * xc_t)[:, None] * b_t[None, :]
        return h, h @ c_t

    h, y = lax.scan(one_position, jnp.zeros_like(a), (dt, b, c, xc))
    return y + f32("ssm_d") * xc, h


def _mamba(u, lp, dims):
    """A Mamba-1 mixer over one sequence's normed rows u [T, E] from h = 0.
    Returns (Mixer(u) [T, E], y [T, C] before the gate, h after the last
    position, xs [T, C])."""
    xs, z = jnp.split(u @ lp["ssm_in"].astype(jnp.float32), 2, axis=-1)  # [T, C] each
    y, h = _mamba_core(xs, lp, dims)
    return (y * jax.nn.silu(z)) @ lp["ssm_out"].astype(jnp.float32), y, h, xs


def _qkv(u, lp, dims):
    """q [T, H, D] of the normed rows u [T, E], and k, v [T, KV, D] where the
    layer projects them (a cross layer does not: None)."""
    h, kv, d = dims
    t = u.shape[0]
    f32 = lambda name: lp[name].astype(jnp.float32)
    q = (u @ f32("wq") + f32("bq")).reshape(t, h, d)
    if "wk" not in lp:
        return q, None, None
    return q, (u @ f32("wk") + f32("bk")).reshape(t, kv, d), (u @ f32("wv") + f32("bv")).reshape(t, kv, d)


def _lambda(lp, lambda_init):
    f32 = lambda name: lp[name].astype(jnp.float32)
    return jnp.exp(jnp.sum(f32("lq1") * f32("lk1"))) - jnp.exp(jnp.sum(f32("lq2") * f32("lk2"))) + lambda_init


def _diff_attention(q, k, v, lp, lambda_init, eps, window: int, rows=None):
    """Differential attention of one sequence, two softmaxes a pair written as
    two softmaxes.  q [T, H, D]; k, v [T_kv, KV, D] (the layer's own, or
    another's); position i sees j <= i, and with a window also i - j < window.
    Returns concat_p(o_p) [T, H D] (a pair's result is 2 D wide), or of the
    query rows `rows` (an index array into the keys' positions) alone, q then
    holding those rows only."""
    t, h, d = q.shape
    kv = k.shape[1]
    per = (h // 2) // (kv // 2)  # query pairs a cached pair
    lam = _lambda(lp, lambda_init)
    w_sub = lp["subln"].astype(jnp.float32)
    k1, k2 = k[:, 0::2], k[:, 1::2]  # [T, KV / 2, D]
    vv = jnp.concatenate([v[:, 0::2], v[:, 1::2]], axis=-1)  # [T, KV / 2, 2 D]

    def block(q_rows, at):
        """Query rows at positions `at` against every position up to the last of them."""
        hi = k.shape[0] if rows is not None else int(at[-1]) + 1
        q1 = q_rows[:, 0::2].reshape(len(at), kv // 2, per, d)
        q2 = q_rows[:, 1::2].reshape(len(at), kv // 2, per, d)
        ahead = at[:, None] - jnp.arange(hi)[None, :]
        mask = ((ahead >= 0) & (ahead < window) if window else ahead >= 0)[None, None]
        a1 = jax.nn.softmax(jnp.where(mask, jnp.einsum("qgrd,kgd->grqk", q1, k1[:hi]) * d ** -0.5, -jnp.inf), axis=-1)
        a2 = jax.nn.softmax(jnp.where(mask, jnp.einsum("qgrd,kgd->grqk", q2, k2[:hi]) * d ** -0.5, -jnp.inf), axis=-1)
        o = jnp.einsum("grqk,kgw->qgrw", a1 - lam * a2, vv[:hi])  # [q, KV / 2, per, 2 D]
        o = o / jnp.sqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps) * w_sub * (1.0 - lambda_init)
        return o.reshape(len(at), h * d)

    if rows is not None:
        return block(q, rows)
    return jnp.concatenate([block(q[lo:lo + ATTN_BLOCK], np.arange(lo, min(t, lo + ATTN_BLOCK)))
                            for lo in range(0, t, ATTN_BLOCK)], axis=0)


@functools.partial(jax.jit, static_argnames=("mixer", "dims", "ssm", "window", "eps"))
def _layer(x, lp, m, shared, lambda_init, *, mixer, dims, ssm, window, eps):
    """One block over one sequence.  x: [T, E] float32; lp: this layer's
    weights in whatever type they are stored in; m: the memory [T, C] (read by
    a gated memory unit), shared: layer L/2 + 1's (k, v) (read by a cross
    layer); either None before its layer; lambda_init: the layer's (an operand,
    so that one compilation serves every layer of a kind).  Returns (the block's output; what
    the layer made for later ones and for the checks: a Mamba layer's (y, h
    after the last position, xs), an attention layer's own (k, v))."""
    f32 = lambda name: lp[name].astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        u = _layer_norm(x, f32("ln1"), f32("ln1_b"), eps)
        made = None
        if mixer == "ssm":
            out, *made = _mamba(u, lp, ssm)
        elif mixer == "gmu":
            out = (m * jax.nn.silu(u @ f32("gmu_in"))) @ f32("gmu_out")
        else:
            q, k, v = _qkv(u, lp, dims)
            if k is None:
                k, v = shared
            else:
                made = (k, v)
            out = _diff_attention(q, k, v, lp, lambda_init, eps, window) @ f32("wo") + f32("bo")
        return _mlp(x + out, lp, eps), made


_STACK = {"ssm": "ssm_blocks", "attn_win": "win_blocks", "attn": "blocks", "gmu": "gmu_blocks",
          "attn_cross": "cross_blocks"}


def _layers(cfg):
    """[(mixer, the stack its weights lie in, its index there, lambda_init)] in
    the model's order."""
    seen: Dict[str, int] = {}
    out = []
    for i, mixer in enumerate(cfg.layer_mixers):
        out.append((mixer, _STACK[mixer], seen.get(mixer, 0), 0.8 - 0.6 * float(np.exp(-0.3 * i))))
        seen[mixer] = seen.get(mixer, 0) + 1
    return out


def _layer_of(params, stack: str, i: int):
    return jax.tree_util.tree_map(lambda w: w[i], params[stack])


def _dims(cfg):
    return dict(dims=(cfg.n_heads, cfg.n_kv_heads, cfg.d_head),
                ssm=(cfg.ssm_d_state, cfg.ssm_dt_rank, cfg.ssm_d_conv), eps=float(cfg.norm_eps))


def _blocks(params: Dict[str, Any], ids, cfg):
    """ids: [T] through the stack, every layer over every position.  Yields, a
    layer at a time, (the block's input [T, E], its output, what it made:
    `_layer`'s, the layer's weights)."""
    x = params["embed"][jnp.asarray(ids)].astype(jnp.float32)
    m = shared = None
    for mixer, stack, i, lambda_init in _layers(cfg):
        x_in = x
        lp = _layer_of(params, stack, i)
        x, made = _layer(x, lp, m, shared, jnp.float32(lambda_init), mixer=mixer,
                         window=cfg.attn_window * (mixer == "attn_win"), **_dims(cfg))
        if mixer == "ssm":
            m = made[0]  # the newest Mamba layer's read-out: layer L/2's by the time a gated memory unit reads it
        elif mixer == "attn":
            shared = made
        yield x_in, x, made, lp


def _head(params, x, cfg):
    with jax.default_matmul_precision("highest"):
        f32 = lambda name: params[name].astype(jnp.float32)
        return _layer_norm(x, f32("ln_f"), f32("ln_f_b"), float(cfg.norm_eps)) @ f32("embed").T


def forward(params: Dict[str, Any], ids, cfg):
    """ids: [T] -> logits [T, V], float32.  `cfg`: the program's
    TransformerConfig, read for its sizes (heads, the window, the layer map,
    the state-space sizes, the epsilon)."""
    for _, x, _, _ in _blocks(params, ids, cfg):
        pass
    return _head(params, x, cfg)


def loss(params, ids, cfg) -> float:
    """Mean next-token cross entropy of one sequence ids[:-1] -> ids[1:]."""
    ids = jnp.asarray(ids)
    logits = forward(params, ids[:-1], cfg)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, ids[1:, None], axis=-1)[:, 0]
    return float(jnp.mean(logz - gold))


# -- what chose a served token ------------------------------------------------------
# One causal token a step from the last position's logits: the harness's default, by
# this file's own pass, which also keeps what `mechanism_checks` reads again: the input
# rows of the layers it enters, and how far the rows that the program's prefill of the
# stream's prompt installs lie from this pass's own (`_prefill_rows_errors`: the pass
# makes every layer's k, v and xs once, and a prompt's are those of its positions).  Every
# stream is padded on the right to ONE length, the deployment's longest bucket and the
# served tokens in whole ROW_BLOCKs (a causal model's earlier positions do not see what
# follows; one length is one compilation a kind of layer, five in all), and the head
# takes the rows that chose a token alone.
ROW_BLOCK = 128
_given: Dict[bytes, tuple] = {}


def _stream_ids(stream) -> np.ndarray:
    return np.asarray(stream["prompt_ids"] + stream["served"][:-1], np.int32)


def _checked_layers(cfg):
    """{what a check reads: its layer's index}: the first window layer, the
    Mamba layer whose read-out is the memory, the full layer, the first cross
    layer."""
    mixers = cfg.layer_mixers
    first_gmu = mixers.index("gmu")
    return {"window": mixers.index("attn_win"), "memory": max(i for i in range(first_gmu) if mixers[i] == "ssm"),
            "full": mixers.index("attn"), "cross": mixers.index("attn_cross")}


def _given_of(cb, stream):
    """A stream's prompt + served[:-1] through the stack.  Returns (the last
    block's output [T, E], ({layer index: the block's input [T, E]} on the
    host, in float32, for the layers the checks enter; the largest relative
    error of the rows the program's prefill of the prompt installs))."""
    params, cfg = cb.params, cb.cfg
    ids, served = _stream_ids(stream), len(stream["served"])
    n = len(ids)
    length = -(-(max(cb.prefill_buckets) + served) // ROW_BLOCK) * ROW_BLOCK
    wanted = set(_checked_layers(cfg).values())
    rows = _PrefillRows(cb, stream)
    kept = {}
    for i, (x_in, x, made, lp) in enumerate(_blocks(params, np.pad(ids, (0, max(length, n) - n)), cfg)):
        if i in wanted:
            kept[i] = np.asarray(x_in[:n])
        rows.hold(cfg.layer_mixers[i], made, lp)
    return x[:n], (kept, rows.worst())


def chosen_logits(cb, stream) -> np.ndarray:
    """Row i: the logits at position len(prompt) - 1 + i of prompt +
    served[:-1], which chose served[i]."""
    ids, n = _stream_ids(stream), len(stream["prompt_ids"])
    x, _given[ids.tobytes()] = _given_of(cb, stream)
    return np.asarray(_head(cb.params, x[n - 1:], cb.cfg))


# -- counts from shapes ---------------------------------------------------------
# `c` is the `config` object of a configuration file: the published keys and the
# assumed ones (`head_dim`, the Mamba sizes).


def layer_counts(c: Dict[str, Any]) -> Dict[str, int]:
    """{mixer: its layers}."""
    kinds = layer_map(c)
    return {mixer: kinds.count(mixer) for mixer in _MIXERS}


def attention_params(c: Dict[str, Any], cross: bool = False) -> int:
    """W_q and W_o with their biases, W_k and W_v with theirs unless `cross`,
    the four lambda vectors and the sub-norm's weight."""
    e, h, kv, d = c["hidden_size"], c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    own = 0 if cross else 2 * (e * kv * d + kv * d)
    return e * h * d + h * d + own + h * d * e + e + 4 * d + 2 * d


def mixer_params(c: Dict[str, Any]) -> int:
    """One Mamba-1 mixer: W_in, the convolution (and its bias), W_x, W_dt and
    its bias, A_log, D, W_out."""
    e, n, r, kw = c["hidden_size"], c["mamba_d_state"], c["mamba_dt_rank"], c["mamba_d_conv"]
    ch = c["mamba_expand"] * e
    conv = ch * kw + (ch if c["mamba_conv_bias"] else 0)
    return e * 2 * ch + conv + ch * (r + 2 * n) + r * ch + ch + ch * n + ch + ch * e


def gmu_params(c: Dict[str, Any]) -> int:
    return 2 * c["hidden_size"] * c["mamba_expand"] * c["hidden_size"]


def _mlp_params(c: Dict[str, Any]) -> int:
    return 3 * c["hidden_size"] * c["intermediate_size"]


def param_count(c: Dict[str, Any]) -> int:
    """Every layer's mixer, MLP and two LayerNorms (weight and bias), the
    embedding (which is the head) and the final LayerNorm."""
    e, V = c["hidden_size"], c["vocab_size"]
    n = layer_counts(c)
    mixers = (n["ssm"] * mixer_params(c) + (n["attn_win"] + n["attn"]) * attention_params(c)
              + n["attn_cross"] * attention_params(c, cross=True) + n["gmu"] * gmu_params(c))
    head = 0 if c["tie_word_embeddings"] else V * e
    return mixers + c["num_hidden_layers"] * (_mlp_params(c) + 4 * e) + V * e + head + 2 * e


def slot_state_bytes(c: Dict[str, Any]) -> int:
    """One slot's recurrent state over the Mamba layers: h [C, N] in float32 and
    the convolution's window [K-1, C] in bf16."""
    ch = c["mamba_expand"] * c["hidden_size"]
    return layer_counts(c)["ssm"] * (ch * c["mamba_d_state"] * 4 + (c["mamba_d_conv"] - 1) * ch * 2)


def mixer_step_bytes(c: Dict[str, Any], slots: int, bytes_per: int = 2) -> int:
    """Bytes one decode step's Mamba mixers have to move at the least: their
    weights once, and every slot's recurrent state read and written again (a
    recurrence has no dead row: an empty slot's state moves on with the rest)."""
    return layer_counts(c)["ssm"] * mixer_params(c) * bytes_per + 2 * slots * slot_state_bytes(c)


def token_bytes(c: Dict[str, Any], bytes_per: int = 2) -> int:
    """One token's keys and values in one layer's cache."""
    return 2 * c["num_key_value_heads"] * c["head_dim"] * bytes_per


def shared_cache_step_bytes(c: Dict[str, Any], rows_read: float, bytes_per: int = 2) -> float:
    """The bytes a decode step fetched of the one stack of keys and values that
    the full layer writes and the cross layers read, from `llm.step`'s
    `shared_rows_read`: that argument is the slots fetched over the stack's
    readers (the full layer and the cross layers: every one fetches the live
    rows' own key blocks again) divided by all the layers that read a stack
    (the window layers too: `key_slots`' mean), so times their number it is
    readers x slots, and a slot is `token_bytes`."""
    n = layer_counts(c)
    return rows_read * (n["attn_win"] + n["attn"] + n["attn_cross"]) * token_bytes(c, bytes_per)


def train_flops_per_step(c: Dict[str, Any], batch: int, seq: int) -> float:
    """Operations the forward and backward passes require for `batch`
    sequences of `seq` tokens: 2 per multiply-add over the matrices a token
    meets, both maps of every pair under each layer's own mask (the band in a
    window layer, the causal half in the full and the cross layers) against a
    value of twice the head's width, the recurrence's elementwise work (about
    9 operations a channel and state a token), backward twice the forward.  No
    training cell runs this architecture."""
    e, h, d, V = c["hidden_size"], c["num_attention_heads"], c["head_dim"], c["vocab_size"]
    n, r, w = c["mamba_d_state"], c["mamba_dt_rank"], c["sliding_window"]
    ch = c["mamba_expand"] * e
    counts = layer_counts(c)
    mixer_matmul = e * 2 * ch + ch * (r + 2 * n) + r * ch + ch * e
    per_token = (counts["ssm"] * (mixer_matmul + c["mamba_d_conv"] * ch)
                 + (counts["attn_win"] + counts["attn"]) * attention_params(c)
                 + counts["attn_cross"] * attention_params(c, cross=True) + counts["gmu"] * gmu_params(c)
                 + c["num_hidden_layers"] * _mlp_params(c) + e * V)
    full = min(seq, w)
    band = full * (full + 1) // 2 + (seq - full) * w
    pairs = counts["attn_win"] * band + (counts["attn"] + counts["attn_cross"]) * seq * (seq + 1) // 2
    # a head's scores over D and its map over the pair's 2 D: 2 (D + 2 D) a (query head, key) pair
    fwd = batch * seq * 2 * per_token + batch * 6 * h * d * pairs + batch * seq * 9 * ch * n * counts["ssm"]
    return 3.0 * fwd


def decode_step_bytes(c: Dict[str, Any], slots: int, t_max: int, bytes_per: int = 2, lengths=None) -> int:
    """Bytes one decode step has to read at the least: every weight once (the
    embedding is the head, read whole), every slot's recurrent state read and
    written, and the live rows' keys and values: a row's whole context once
    for each of the layers that read the shared stack, its last
    `sliding_window` positions in each window layer (`lengths`: the live rows'
    contexts; without them `slots` rows of t_max, the most a step can read)."""
    n = layer_counts(c)
    weights = param_count(c) - n["ssm"] * mixer_params(c)
    lengths = [t_max] * slots if lengths is None else lengths
    readers = n["attn"] + n["attn_cross"]
    cache = sum(token_bytes(c, bytes_per) * (readers * t + n["attn_win"] * min(t, c["sliding_window"]))
                for t in lengths)
    return int(weights * bytes_per) + cache + mixer_step_bytes(c, slots, bytes_per)


# -- tolerances ------------------------------------------------------------------
# harness/reference.py says which program each of the three serving tolerances
# holds.  The readings are the chip's at the published widths, all 32 layers, taken
# as the cell's check takes them (the four check streams of traffic/reason-closed.json
# served together, prompts 100, 200, 480, 1000 and 64 tokens each, teacher-forced
# through this reference in float32).  PERF.md section 6 (PR 47) has every number
# and its seeds; the controls are `scripts/sambay_controls.py`'s.
#
# The first reading of each is the largest the program (bf16 weights and activations;
# float32 norms, softmaxes, subtraction, recurrence and state) gave over 22 runs on 22
# seeds (18 runs of the cell, 13 of them of the committed files alone with the bounds as
# they stand; 4 of `scripts/sambay_controls.py` through a batcher alone), and where a later
# 13 runs on 13 fresh seeds read further out the line says so; the second is the nearest precision below bf16 over 2 seeds:
# every stored matrix rounded to float8 e4m3's 3 bits of mantissa, served by the program
# and held to this reference over the unrounded parameters ("float8-weights").  It has to
# come out as not correct, and does by each of the three.
#
# The logits at a prompt's last row (4 rows x 200,064 a run): the program 0.209-0.285;
# float8-weights 2.86, 3.08.  The bound is 2.8 x over the program's largest and 3.6 x
# under float8's least.
LOGIT_TOL = 0.8
# The largest regret of 256 served tokens, which alone holds the batch decode: the
# program 0.065-0.181 (0.187 once in the later 13; 8-16% of the tokens flip to a neighbour:
# 200,064 logits lie close); float8-weights 2.47, 2.80.  A largest of 256 has a tail: 3.2 x over,
# 4.1 x under.
REGRET_MAX_TOL = 0.6
# The mean regret: the program 0.0026-0.0066; float8-weights 0.610, 0.630.  4.5 x over,
# 20 x under.
REGRET_MEAN_TOL = 0.03
# What none of the three can see, measured the same way: the recurrent state handed from
# token to token in bf16 AND SERVED so (seed 3000000211) reads 0.273 / 0.134 / 0.0044,
# inside the program's own range, as in Jamba's cell.  `ssm_state_step_err` and
# `ssm_state_rel_err` below see it.
# No training cell runs this architecture; the dense decoder's bound and reason.
LOSS_TOL = 0.01


# -- the mechanisms by themselves ---------------------------------------------------
# What the logits cannot see.  Each number is the program's own code at the window's
# shapes against this file's plain mathematics ON THE SAME ROWS: what the reference's
# own float32 pass gave the layer (`_given`), and of them this file's own q, k, v (or a
# Mamba layer's xs), rounded to the program's activation type, which is how a block
# hands them to its core.
#
#   window_decode_rel_err, full_decode_rel_err, cross_decode_rel_err    a decode step's
#       differential attention in the first window layer, in the full layer and in the
#       first cross layer: for every served position of the check streams, concat_p(o_p)
#       [H D] as the program makes it (`transformer._diff_heads`, the decode core
#       `generate._kv_decode_core` one token a row at [slots, 1, .], the streams in slots
#       0, 1, ... at the depths and pads the batcher gave them, the other slots empty,
#       over a stack of the deployment's [slots, T_max] or [slots, ring] that holds each
#       stream as a prefill stores it, the steps one after the other so that a ring is
#       written round as serving writes it; then `transformer._diff_combine`) against
#       `_diff_attention` here, two softmaxes a pair, in float32.  The cross layer's
#       queries are its own and the stack holds the full layer's keys and values, all of
#       a stream's, which it writes nowhere.  The largest |program - reference| /
#       |reference| of a row (2-norms over H D).
#   cross_shared_rows_miss    the cross layer's program output with the shared stack's
#       values replaced by their negatives against its output with them as they are, which
#       has to MOVE, and by twice its norm (what is left of the two maps is normed, so the
#       sign is all that changes): MISS_FACTOR x the cross layer's bound over that
#       difference, held under 1.
#   cross_ring_rows_moved    the same with a window layer's ring beside the stack in the
#       cache, filled with other numbers: the largest difference of any output, which has
#       to be 0: a cross layer reads no ring.
#   prefill_rows_rel_err    the rows an admit installs: for each stream the program's own
#       prefill of its bucket (`generate.prefill`, the admit's compiled program, left
#       pads and all), every array of its rows (each window layer's ring, the full
#       layer's stack, each Mamba layer's convolution window and h) against this file's
#       own pass over the stream (a prompt's rows are those of its positions): k and v of
#       every position where the cache holds them, the last K - 1 inputs, h after the
#       prompt's last token (the recurrence again over the prompt's xs alone).  The program computed the stack's
#       second half at the prompt's last position only; the rows must be the full
#       forward's.  The largest |program - reference| / |reference| of an array (2-norms
#       over the slots that hold a token), the reference's in float32.
#   ssm_memory_rel_err, ssm_state_rel_err    the Mamba layer whose read-out is the
#       memory, through the program's own `transformer._ssm_mix` as serving runs it: each
#       stream's prompt in one call from the zero state (left pads masked), then one
#       token a row at [slots, 1, C] from the slots' own states, the state handed on as
#       the cache keeps it; against `_mamba_core` here over the whole stream, from the
#       same xs.  The first is the read-out y of the decode rows (what the gated memory
#       units gate), the second h after the last step: the largest relative error of a
#       row, and of a stream's h.  Both carry what the mixer's own bf16 projections of
#       dt, B and C differ by from float32, so neither can see how h is handed on.
#   ssm_state_step_err    that is this number's to see: the same h after the last step,
#       handed from token to token as the cache keeps it, against the same `_ssm_mix` in
#       ONE call over the whole stream from the zero state, whose recurrence holds h in
#       float32 from the first position to the last and rounds it to the cache's type
#       once.  The two paths make the same dt, B and C of the same xs, so they differ by
#       what handing h on costs and nothing else: nothing but float32's own rounding
#       where the cache keeps h in float32.  The largest relative error of a stream's h.
#       Its answer is the program's own mixer, so it is the program against itself: it
#       shows that stepping rounds nothing, not that the mixer is right.  That is
#       `ssm_state_rel_err`'s, whose answer is this file's `_mamba_core`.
#
# The tolerances, from the chip at the cell's own size (my chip runs, PR 47: the program
# over the same 22 runs on 22 seeds; each control planted once the streams are served, 2
# or 3 seeds, so the numbers on the logits are the program's while `ok` comes out false
# by the control's own number: `scripts/sambay_controls.py`, whose docstring says what
# each control is).  Lower reading: the program's largest.  Upper: the control's least.
#   The three decode errors are maxima over 252 rows with hardly a tail (the program's
#   22 readings of the window's lie within 5% of their middle), so their bounds can stand nearer
#   their readings than a bound on the logits could.
#   window: the program 0.00222-0.00247 (0.00218-0.00240 in the later 13); "rounded-maps" (the two maps' results rounded
#   to bf16 before they are subtracted) 0.00347, 0.00348; "bf16-softmax" (scores, softmax
#   and weighted sum in bf16) 0.00458, 0.00472, 0.00479; a window of 511 in the program's
#   mask 0.0730, 0.0876, 0.0896 (of 513: the program's own number, since a ring of 512
#   slots holds no 513th position: the widest mask a served window layer can have).
#   The bound is 1.17 x over the program's largest and 1.20 x under rounded-maps' least.
#   full: the program 0.00226-0.00274; rounded-maps 0.00604, 0.00829; bf16-softmax
#   0.00581, 0.00790, 0.01387.  1.46 x over, 1.45 x under.
#   cross: the program 0.00209-0.00272; rounded-maps 0.00911, 0.01000; bf16-softmax
#   0.00494, 0.00914, 0.01111; a stack nothing wrote ("own-stack") 1.0.  1.32 x over,
#   1.37 x under.
#   So a subtraction after rounding fails all three and bf16 softmaxes fail all three.
#   cross_shared_rows_miss: the output moves by exactly twice its norm (0.036 at the
#   factor and bound below); own-stack reads 7e28.  cross_ring_rows_moved: 0 in every run.
#   prefill_rows: the program 0.0375-0.0624 (bf16 activations through 17 layers against
#   float32: the rows of the deepest layer); the rings handed over one slot on
#   ("ring-shifted") 1.408, 1.411.  3.2 x over, 7 x under.
#   ssm_memory: the program 0.00255-0.00312 (0.00349 once in the later 13).  It carries the mixer's own bf16 projections
#   and CANNOT see the state's precision (state-bf16 0.0029, 0.0043; the whole recurrence
#   in bf16 0.0030, 0.0048); what it holds is the mathematics: the mixer without its D
#   term ("no-d-term") reads 1.13.  1.7 x over the program's largest, 190 x under.
#   ssm_state: the program 0.00196-0.00333; state-bf16 0.0063, 0.0102; recurrence-bf16
#   0.0066, 0.0113.  1.35 x over, 1.4 x under.
#   ssm_state_step: the program 0.000144-0.000235 (the two paths' bf16 projections round
#   a few of their outputs apart; float32 alone would read 1e-6); state-bf16 0.0017,
#   0.0060, 0.0102; recurrence-bf16 0.0069, 0.0117.  2.3 x over, 3.1 x under.  This is the
#   number that closes, for this cell, what PERF.md section 7 says no bound could see in
#   `jamba-closed6`: a state kept in bf16.
DECODE_ERR_TOL = {"window": 0.0029, "full": 0.004, "cross": 0.0036}
MISS_FACTOR = 20.0
PREFILL_ROWS_ERR_TOL = 0.2
SSM_MEMORY_ERR_TOL = 0.006
SSM_STATE_ERR_TOL = 0.0045
SSM_STATE_STEP_ERR_TOL = 5.5e-4


@functools.partial(jax.jit, static_argnames=("dims", "lay", "dtype", "eps"))
def _qkv_rows(rows, lp, *, dims, lay, dtype, eps):
    """This file's own q, k, v of every row of every stream in float32 from the
    rows as given (a block's input: normed here), rounded to the program's
    activation type: what both sides of a decode error start from.  k = v =
    None for a cross layer."""
    f32 = lambda name: lp[name].astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        parts = [_qkv(_layer_norm(rows[off:off + n + t].astype(jnp.float32), f32("ln1"), f32("ln1_b"), eps), lp, dims)
                 for off, n, t, _ in lay]
    return tuple(None if p[0] is None else jnp.concatenate(p).astype(dtype) for p in zip(*parts))


def _stored(a, pad: int, n: int, extent: int):
    """A stream's rows a [rows, KV, D] as a prefill stores them in a stack of
    `extent` slots: behind `pad` left pads, the first n of them (the prompt);
    of a ring the last `extent` columns of the bucket, column j at slot j mod
    the extent."""
    cols = jnp.pad(a[:n], ((pad, 0), (0, 0), (0, 0)))  # the bucket's columns
    first = max(pad + n - extent, 0)  # the first column the layer still holds
    return jnp.roll(jnp.pad(cols[first:], ((0, extent - (pad + n - first)), (0, 0), (0, 0))), first % extent, axis=0)


def _decode_program(cb, lay, decode, kind: str, lp, lambda_init: float, beside=None, negated: bool = False):
    """The compiled program of one layer's decode attention: (q, k, v of every
    row of every stream, as `_qkv_rows` gives them) -> concat_p(o_p) [steps *
    slots, H D] of every (step, slot), through the program's own
    `transformer._diff_heads`, `generate._kv_decode_core` and
    `transformer._diff_combine`, one token a row at [slots, 1, .] over a cache
    of one layer of `kind` at the deployment's slots and extents.  A cross
    layer's stack holds all of each stream's rows (the full layer writes a
    step's before the cross layers read them) and the core writes nothing.
    beside: a ring to lie in the cache beside a cross layer's stack; negated:
    the stack's values replaced by their negatives (`cross_*`'s controls)."""
    from cluster_anywhere_tpu.models import generate, transformer

    cfg = cb.cfg
    state = "attn_win" if kind == "attn_win" else "attn"
    names = generate.LAYER_STATE[state]
    one = dataclasses.replace(cfg, n_layers=2, layer_mixers=("ssm", kind if kind != "attn_cross" else "attn"))
    pads = np.zeros(cb.slots, np.int32)
    pos = np.zeros(decode.shape, np.int32)
    for slot, (_, n, _, pad) in enumerate(lay):
        pads[slot], pos[:, slot] = pad, pad + n + np.arange(len(decode))
    n_rows = sum(n + t for _, n, t, _ in lay)

    @jax.jit
    def program(q, k, v):
        made = generate.init_cache(one, cb.slots, cb.t_max)
        cache = {name: made[name] for name in names}
        extent = cache[names[0]].shape[2] // cfg.flat_heads
        for slot, (off, n, t, pad) in enumerate(lay):
            for name, a in zip(names, (k, v)):
                # a pair's two cached heads side by side are one cached head of twice the width
                a = a[off:off + n + t].reshape(n + t, cfg.cached_heads, cfg.cached_width)
                kept = _stored(a, pad, n + t if kind == "attn_cross" else n, extent)
                kept = -kept if negated and name == names[1] else kept
                cache[name] = cache[name].at[0, slot].set(kept.reshape(-1, a.shape[-1]))
        if beside is not None:
            ring = generate.init_cache(dataclasses.replace(one, layer_mixers=("ssm", "attn_win")), cb.slots, cb.t_max)
            cache.update({name: jnp.full_like(ring[name], beside) for name in generate.LAYER_STATE["attn_win"]})
        at = lambda a, row: jnp.pad(a, [(0, 1)] + [(0, 0)] * (a.ndim - 1))[row][:, None]  # an empty slot's row: zeros

        def step(cache, now):
            row, p = now
            heads = transformer._diff_heads(at(q, row), *(None if kind == "attn_cross" else at(a, row) for a in (k, v)))
            o, cache = generate._kv_decode_core(cache, 0, p, jnp.asarray(pads), cfg, *heads, live=row < n_rows, kind=kind)
            return cache, transformer._diff_combine(lp, o, jnp.float32(lambda_init), cfg).reshape(cb.slots, -1)

        _, out = lax.scan(step, cache, (jnp.asarray(decode), jnp.asarray(pos)))
        return out.reshape(-1, out.shape[-1])

    return program


@functools.partial(jax.jit, static_argnames=("lay", "slots", "window", "eps"))
def _decode_errors(q, k, v, got, lp, lambda_init, *, lay, slots, window, eps):
    """The largest relative error of `got` [steps * slots, H D] against this
    file's differential attention of each stream's decode rows from the same
    q, k, v, a stream attended by itself, under the band of `window` (0: none)."""
    f32 = lambda a, off, n, t: a[off:off + n + t].astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        worst = jnp.zeros((), jnp.float32)
        for slot, (off, n, t, _) in enumerate(lay):
            want = _diff_attention(f32(q, off, n, t)[n:], f32(k, off, n, t), f32(v, off, n, t), lp, lambda_init, eps,
                                   window, rows=n + jnp.arange(t))
            mine = got[np.arange(t) * slots + slot].astype(jnp.float32)
            worst = jnp.maximum(worst, jnp.max(jnp.linalg.norm(mine - want, axis=-1) / jnp.linalg.norm(want, axis=-1)))
        return worst


def attention_checks(cb, streams, given):
    """{"window", "full", "cross": a decode error; "cross_moved": how far the
    cross layer's output moves with the shared rows replaced; "ring_moved":
    with a ring beside them}: `mechanism_checks`' first five numbers before
    they are held to anything."""
    params, cfg = cb.params, cb.cfg
    lay, decode = _mla.program_shapes(cb, streams)
    layers, at = _layers(cfg), _checked_layers(cfg)
    rows_of = lambda i: jnp.asarray(np.concatenate([g[i] for g in given]))
    kw = dict(dims=_dims(cfg)["dims"], lay=lay, dtype=jnp.dtype(cfg.dtype).name, eps=float(cfg.norm_eps))
    out = {}
    for what, kind in (("window", "attn_win"), ("full", "attn"), ("cross", "attn_cross")):
        mixer, stack, j, lambda_init = layers[at[what]]
        lp = _layer_of(params, stack, j)
        q, k, v = _qkv_rows(rows_of(at[what]), lp, **kw)
        if k is None:  # the full layer's, which the loop above made last
            k, v = shared
        shared = (k, v)
        program = functools.partial(_decode_program, cb, lay, decode, kind, lp, lambda_init)
        got = program()(q, k, v)
        out[what] = float(_decode_errors(q, k, v, got, lp, jnp.float32(lambda_init), lay=lay, slots=cb.slots,
                                         window=cfg.attn_window * (kind == "attn_win"), eps=float(cfg.norm_eps)))
        if what == "cross":
            live = np.concatenate([np.arange(t) * cb.slots + slot for slot, (_, _, t, _) in enumerate(lay)])
            norm = lambda a: float(jnp.max(jnp.linalg.norm(a[live].astype(jnp.float32), axis=-1)))
            out["cross_moved"] = norm(program(negated=True)(q, k, v) - got) / max(norm(got), 1e-30)
            out["ring_moved"] = float(jnp.max(jnp.abs(program(beside=3.0)(q, k, v) - got)))
    return out, lay, decode


class _PrefillRows:
    """The rows the program's own prefill of a stream's bucket hands the admit
    (`generate.prefill`: the admit's compiled program, left pads and all), held
    layer by layer to what this file's pass makes of the same positions."""

    def __init__(self, cb, stream):
        from cluster_anywhere_tpu.models.generate import prefill

        self.cfg = cfg = cb.cfg
        prompt = np.asarray(stream["prompt_ids"], np.int32)
        self.n = n = len(prompt)
        bucket = cb._bucket(n, len(stream["served"]))
        self.pad = bucket - n
        padded = np.zeros(bucket, np.int32)
        padded[self.pad:] = prompt
        _, self.rows = prefill(cb.params, jnp.asarray(padded[None]), cfg, cb.t_max, pad=jnp.asarray([self.pad], np.int32))
        self.seen = {"ssm": 0, "attn_win": 0, "attn": 0}
        self.errors = []

    def hold(self, mixer: str, made, lp) -> None:
        """One layer's part: `made` is `_layer`'s of the whole stream, of which
        the prompt's positions are the first n."""
        if mixer not in self.seen:
            return
        cfg, n, rows = self.cfg, self.n, self.rows
        j = self.seen[mixer]
        self.seen[mixer] += 1
        rel = lambda got, want: jnp.linalg.norm(got.astype(jnp.float32) - want) / jnp.linalg.norm(want)
        if mixer == "ssm":
            xs = made[2][:n]
            with jax.default_matmul_precision("highest"):
                h = _mamba_core(xs, lp, _dims(cfg)["ssm"])[1]  # after the prompt's last token
            window = jnp.pad(xs, ((cfg.ssm_d_conv - 1, 0), (0, 0)))[-(cfg.ssm_d_conv - 1):]
            self.errors += [rel(rows["h"][j, 0], h), rel(rows["conv"][j, 0], window)]
            return
        heads, width = cfg.cached_heads, cfg.cached_width
        names = ("kw", "vw") if mixer == "attn_win" else ("k", "v")
        extent = rows[names[0]].shape[2] // heads
        holds = _stored(jnp.ones((n, 1, 1)), self.pad, n, extent)  # a left pad's slot holds what no query sees
        for name, a in zip(names, made):
            # a pair's two cached heads side by side are one cached head of twice the width
            want = _stored(a[:n].reshape(n, heads, width), self.pad, n, extent)
            self.errors.append(rel(rows[name][j, 0].reshape(extent, heads, width) * holds, want))

    def worst(self) -> float:
        return float(jnp.max(jnp.stack(self.errors)))


def _ssm_program(cb, lay, decode, lp):
    """The compiled program of the memory layer's mixer as serving runs it:
    (xs of every row of every stream [rows, C]) -> (y [steps * slots, C] of
    every (step, slot), h [slots, C, N] after the last step, h [streams, C, N]
    of each stream through one call), through the
    program's own `transformer._ssm_mix`: a stream's prompt in one call from the
    zero state at its bucket's length, left pads masked, into its slot; then one
    token a row at [slots, 1, C]."""
    from cluster_anywhere_tpu.models import transformer

    cfg = cb.cfg

    @jax.jit
    def program(xs):
        window, h = transformer._ssm_zero_state(cfg, cb.slots)
        for slot, (off, n, _, pad) in enumerate(lay):
            keep = (jnp.arange(pad + n) >= pad)[None]
            _, (w1, h1) = transformer._ssm_mix(lp, jnp.pad(xs[off:off + n], ((pad, 0), (0, 0)))[None],
                                               transformer._ssm_zero_state(cfg, 1), cfg, keep)
            window, h = window.at[slot].set(w1[0]), h.at[slot].set(h1[0])
        at = lambda row: jnp.pad(xs, ((0, 1), (0, 0)))[row][:, None]  # an empty slot's row: zeros

        def step(state, row):
            y, state = transformer._ssm_mix(lp, at(row), state, cfg)
            return state, y[:, 0]

        (_, h), y = lax.scan(step, (window, h), jnp.asarray(decode))
        # and each stream in one call, prompt and decode rows together: h rounded to the cache's type once
        whole = [transformer._ssm_mix(lp, xs[off:off + n + t][None], transformer._ssm_zero_state(cfg, 1), cfg)[1][1][0]
                 for off, n, t, _ in lay]
        return y.reshape(-1, y.shape[-1]), h, jnp.stack(whole)

    return program


@functools.partial(jax.jit, static_argnames=("dims", "lay", "dtype", "eps"))
def _xs_rows(rows, lp, *, dims, lay, dtype, eps):
    """This file's own xs (the mixer's input after W_in) of every row of every
    stream, from the rows as given, rounded to the program's activation type."""
    f32 = lambda name: lp[name].astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        u = _layer_norm(rows.astype(jnp.float32), f32("ln1"), f32("ln1_b"), eps)
        return jnp.split(u @ f32("ssm_in"), 2, axis=-1)[0].astype(dtype)


@functools.partial(jax.jit, static_argnames=("dims", "lay", "slots"))
def _ssm_errors(xs, y, h, whole, lp, *, dims, lay, slots):
    """(the largest relative error of a decode row's read-out y, of a stream's
    h after its last step) against `_mamba_core` over each whole stream; and
    of the stepped h against the program's own one call (`whole`)."""
    with jax.default_matmul_precision("highest"):
        worst_y = worst_h = jnp.zeros((), jnp.float32)
        f32 = lambda a: a.astype(jnp.float32)
        step = jnp.max(jnp.stack([jnp.linalg.norm(f32(h[slot]) - f32(whole[slot])) / jnp.linalg.norm(f32(whole[slot]))
                                  for slot in range(len(lay))]))
        for slot, (off, n, t, _) in enumerate(lay):
            want_y, want_h = _mamba_core(xs[off:off + n + t].astype(jnp.float32), lp, dims)
            mine = y[np.arange(t) * slots + slot].astype(jnp.float32)
            worst_y = jnp.maximum(worst_y, jnp.max(jnp.linalg.norm(mine - want_y[n:], axis=-1)
                                                   / jnp.linalg.norm(want_y[n:], axis=-1)))
            worst_h = jnp.maximum(worst_h, jnp.linalg.norm(f32(h[slot]) - want_h) / jnp.linalg.norm(want_h))
        return worst_y, worst_h, step


def ssm_checks(cb, given, lay, decode):
    """(the memory's error, the state's, the stepped state's against one call):
    `mechanism_checks`' last three numbers."""
    cfg = cb.cfg
    i = _checked_layers(cfg)["memory"]
    _, stack, j, _ = _layers(cfg)[i]
    lp = _layer_of(cb.params, stack, j)
    dims = _dims(cfg)
    xs = _xs_rows(jnp.asarray(np.concatenate([g[i] for g in given])), lp, dims=dims["ssm"], lay=lay,
                  dtype=jnp.dtype(cfg.dtype).name, eps=dims["eps"])
    y, h, whole = _ssm_program(cb, lay, decode, lp)(xs)
    return tuple(float(e) for e in _ssm_errors(xs, y, h, whole, lp, dims=dims["ssm"], lay=lay, slots=cb.slots))


def mechanism_checks(cb, streams):
    """The nine numbers above (references/__init__.py says what the harness
    does with them)."""
    cfg = cb.cfg
    kept = [_given.pop(_stream_ids(s).tobytes(), None) or _given_of(cb, s)[1] for s in streams]
    given, prefill_rows = [k[0] for k in kept], max(k[1] for k in kept)
    attn, lay, decode = attention_checks(cb, streams, given)
    memory, state, stepped = ssm_checks(cb, given, lay, decode)
    rows_checked = sum(t for _, _, t, _ in lay)
    what = {"window": f"a window layer (window {cfg.attn_window}, read through its ring)",
            "full": f"the full layer, over its stack of {cb.t_max} slots a row",
            "cross": "a cross layer, its own queries on the full layer's stack"}
    out = [
        {"name": f"{name}_decode_rel_err", "error": attn[name], "tolerance": DECODE_ERR_TOL[name],
         "why": f"largest relative error of a decode row's differential attention in {what[name]}, against two "
                f"softmaxes a pair in float32, over {rows_checked} rows"}
        for name in ("window", "full", "cross")
    ]
    return out + [
        {"name": "cross_shared_rows_miss", "tolerance": 1.0,
         "error": MISS_FACTOR * DECODE_ERR_TOL["cross"] / max(attn["cross_moved"], 1e-30),
         "why": f"{MISS_FACTOR:g} x the cross layer's bound over how far its output moves with the shared stack's "
                f"values negated ({attn['cross_moved']:.4g} of its norm): under 1 where the layer reads that stack"},
        {"name": "cross_ring_rows_moved", "error": attn["ring_moved"], "tolerance": 0.0,
         "why": "largest difference of the cross layer's output with a ring of other numbers beside the stack: "
                "it reads no ring"},
        {"name": "prefill_rows_rel_err", "error": prefill_rows, "tolerance": PREFILL_ROWS_ERR_TOL,
         "why": f"largest relative error of an array of the rows the {len(streams)} streams' prefills install "
                "(rings, the full layer's stack, convolution windows, h) against the full forward's over the prompt"},
        {"name": "ssm_memory_rel_err", "error": memory, "tolerance": SSM_MEMORY_ERR_TOL,
         "why": f"largest relative error of a decode row's memory (the last Mamba layer's read-out), over {rows_checked} rows"},
        {"name": "ssm_state_rel_err", "error": state, "tolerance": SSM_STATE_ERR_TOL,
         "why": "largest relative error of a stream's state h after its last step, handed on as the cache keeps it"},
        {"name": "ssm_state_step_err", "error": stepped, "tolerance": SSM_STATE_STEP_ERR_TOL,
         "why": "largest relative error of that h against the same mixer's one call over the whole stream, which "
                "holds h in float32 throughout: what handing the state on from token to token costs"},
    ]
