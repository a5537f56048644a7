"""A decoder whose layers mix tokens by Kimi Delta Attention (KDA: a linear-attention
recurrence with a MATRIX state a head, updated by a rank-one delta rule under a decay of
its own for every key channel) three times out of four and by multi-head latent attention
WITHOUT a positional embedding the fourth, over a mixture of sigmoid-routed experts with a
shared expert and one leading dense layer, as this chip's share of a deployment that
divides each layer's routed experts over several chips: the key set of
`moonshotai/Kimi-Linear-48B-A3B-Instruct` (`model_type` `kimi_linear`;
configs/kimi-linear-48b-a3b-ep16-serve1.json).  The interface is the package's
(references/__init__.py).

The equations, one block; x a token's residual, RMSNorm with a learned weight and the
configuration's epsilon; every layer is x + mixer(RMSNorm(x)), then x + FFN(RMSNorm(x)).

  KDA on u = RMSNorm(x), H heads of D = 128 (Kimi Linear, arXiv:2510.26692, section 3):
    [q | k | v](t) = silu(conv4([W_q | W_k | W_v] u))(t): a causal depthwise convolution over the
        last 4 positions, zeros before the sequence's first; q and k L2-normed a head
        (a / sqrt(sum a^2 + 1e-6)), q times D^-1/2
    g(t) = -exp(A_log[h]) softplus(W_f2 W_f1 u + dt_bias), a vector of D a head; alpha = exp(g)
    beta(t) = sigmoid(W_b u), one scalar a head
    S(t) = (I - beta k k^T) Diag(alpha) S(t-1) + beta k v^T, S in R^{D x D} from S = 0;  o(t) = S(t)^T q(t)
    y(t) = W_o [ RMSNorm_head(o) * sigmoid(W_g2 W_g1 u) ], RMSNorm_head over a head's D, one weight
  latent attention on u, H heads, NO rotation (`mla_use_nope`):
    q = u W_q  [H, nope + rope];  [c_kv | k_r] = u W_kva;  c_kv = RMSNorm(c_kv) [512], k_r [64] as projected
    [k_nope | v] = c_kv W_kvb  [H, nope + v]
    score_h(t, s) = (nope + rope)^-1/2 (q_nope_h(t) . k_nope_h(s) + q_rope_h(t) . k_r(s)), causal softmax,
    o_h = sum_s p_h(t, s) v_h(s), output concat(o) W_o
  FFN on y = RMSNorm(x): layer 1 (silu(y W_g) * (y W_u)) W_d; the others s = sigmoid(y W_r) over ALL the routed
    experts, float32; the k largest; w_e = scale s_e / (sum of the k + 1e-20); sum over the chosen e THAT ARE HELD of
    w_e E_e(y), + Shared(y); E_e and Shared gated MLPs of `moe_intermediate_size`
  a final RMSNorm; an untied head.

This file writes the recurrence ONE POSITION A STEP, exactly as above (`_delta_rule`: no chunks, no
triangular solve), expands every head's keys and values of a latent layer, and keeps no cache; the
program runs a prompt in chunks of 64 whose inside is matrix products, a decode step as one update a
row from the slot's own state, and attends in the latent space.  Straightforward `jax.numpy` in
float32 at `highest` matmul precision; layers run one at a time in a Python loop.  The dense MLP, the
held experts one at a time, the expanded attention in blocks of query rows and how the check streams'
rows lie in the calls that serve them are references/mla_moe.py's own functions (the same mathematics:
A.X-K1's file), loaded by path; nothing here or there shares code with `cluster_anywhere_tpu/models/`
or `parallel/`.  (`mechanism_checks`, at the end, calls the program's own functions as what it
checks, not as a reference.)

The share (model-configs guide, section 4): `experts_held` = (first, count) of the program's
configuration says which of the router's experts this chip holds; the router keeps its width and a
token takes its k of all of them; what the experts held elsewhere would add is left out, here as in
the program.  The vocabulary is the slice the configuration holds.
tests/benchmark/test_benchmark_kimi_linear.py holds that the shares' parts, with the shared expert
once, add up to the uncut layer.

Assumed and departures: the configuration file lists each.  In short: the two low-rank projections
have rank 128; A_log is a head's, dt_bias a channel's; no bias on the output gate or the
convolutions; no selection bias on the router's scores; `head_dim` 72 and `rope_theta` are read by
nothing; the state is float32.
"""

from __future__ import annotations

import dataclasses
import functools
import os
import types
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmarks.harness import manifest

_mla = manifest.load_reference("mla_moe", os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# what this architecture's programs write beyond the common names (program_trace.SCOPES): a KDA layer's six
# (models/transformer.py `_kda_mixer`: `kda.chunk` in a prefill, `kda.step` in a decode step) and the scope its state
# is read, written and installed under; the mixture's five under `ffn`; latent attention's four in place of `attn.qkv`
# (its core runs under `attn.core`, as A.X-K1's does: no rotation is a step left out, not another core)
SCOPES = ("kda.proj", "kda.conv", "kda.gates", "kda.chunk", "kda.step", "kda.out", "ssm.state",
          "moe.router", "moe.dispatch", "moe.experts", "moe.combine", "moe.shared",
          "attn.mla.q", "attn.mla.kv", "attn.mla.expand", "attn.mla.absorb")
# the grouped matmul (references/olmoe.py says why it is known by name) and the decode step's state update
# (ops/kda.py, under scope `kda.step`).  The chunked prefill's products are plain JAX.
KERNELS = ("ragged-dot-none", "kda_update")

_STACK = {"kda_dense": "kda_dense_blocks", "kda": "kda_blocks", "attn_dense": "dense_blocks", "attn": "blocks"}
L2_EPS = 1e-6


def mixers(c: Dict[str, Any]):
    """Each layer's mixer, "kda" or "attn", in the model's order, from `linear_attn_config`'s two lists
    (which count the layers from 1)."""
    la = c["linear_attn_config"]
    kda, full = set(la["kda_layers"]), set(la["full_attn_layers"])
    n = c["num_hidden_layers"]
    if kda & full or (kda | full) != set(range(1, n + 1)):
        raise ValueError("linear_attn_config: every layer 1..num_hidden_layers is a kda layer or a full-attention layer")
    return tuple("kda" if i in kda else "attn" for i in range(1, n + 1))


def program_config(config_file: Dict[str, Any], **extra) -> Dict[str, Any]:
    """The program's TransformerConfig fields from a configuration file's keys.  `num_experts` counts the
    experts HELD; the router's width is `num_experts_routed` and the share starts at `experts_held_first`.
    A program that lacks one of the fields cannot run the configuration: refused here, by name, before
    anything is deployed."""
    from cluster_anywhere_tpu.models import transformer
    from cluster_anywhere_tpu.models.transformer import TransformerConfig

    c = config_file["config"]
    la = c["linear_attn_config"]
    if c["hidden_act"] != "silu" or c["moe_router_activation_func"] != "sigmoid" or c["moe_layer_freq"] != 1:
        raise ValueError("this file writes silu experts behind a sigmoid router in every layer past the dense ones")
    if c["num_expert_group"] != 1 or c["topk_group"] != 1 or c["num_nextn_predict_layers"]:
        raise ValueError("this file writes no expert groups and no next-token-prediction layers")
    if c["q_lora_rank"] is not None or not c["mla_use_nope"] or c["rope_scaling"] is not None:
        raise ValueError("this file writes latent attention with a direct query projection and no rotation")
    out = dict(
        d_model=c["hidden_size"], n_layers=c["num_hidden_layers"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], d_head=c["qk_nope_head_dim"] + c["qk_rope_head_dim"],
        d_ff=c["intermediate_size"], rope_theta=float(c["rope_theta"]), max_seq_len=c["model_max_length"],
        layer_mixers=mixers(c), rotary=False, tie_embeddings=bool(c["tie_word_embeddings"]),
        norm_eps=float(c["rms_norm_eps"]),
        kv_lora_rank=c["kv_lora_rank"], q_lora_rank=0, qk_nope_head_dim=c["qk_nope_head_dim"],
        qk_rope_head_dim=c["qk_rope_head_dim"], v_head_dim=c["v_head_dim"],
        kda_n_heads=la["num_heads"], kda_head_dim=la["head_dim"], ssm_d_conv=la["short_conv_kernel_size"],
        n_dense_layers=c["first_k_dense_replace"], d_expert=c["moe_intermediate_size"],
        n_shared_experts=c["num_shared_experts"],
        n_experts=c["num_experts_routed"], n_experts_per_tok=c["num_experts_per_token"],
        moe_gated=True, moe_renormalize=bool(c["moe_renormalize"]), moe_scoring="sigmoid",
        moe_routed_scale=float(c["routed_scaling_factor"]),
        experts_held=(c["experts_held_first"], c["num_experts"]),
    )
    out.update(extra)
    lacking = sorted(set(out) - {f.name for f in dataclasses.fields(TransformerConfig)})
    if lacking:
        raise NotImplementedError(
            f"this program's TransformerConfig has no {lacking}: it runs no linear-attention (kda) layer and no latent "
            "attention with a direct query projection; this configuration cannot run on it"
        )
    # two sizes the file assumes and the program does not take as fields: the low-rank projections' rank, which the
    # program makes the head's width, and the chunk of a prefill's delta rule
    if c["kda_gate_rank"] != la["head_dim"] or c["kda_chunk"] != transformer.KDA_CHUNK:
        raise ValueError(f"kda_gate_rank={c['kda_gate_rank']}, kda_chunk={c['kda_chunk']}: the program's low-rank "
                         f"projections have the head's width, {la['head_dim']}, and its chunks {transformer.KDA_CHUNK} positions")
    return out


# -- the mathematics ---------------------------------------------------------------


def _rms_norm(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _kda_inputs(u, lp, dims):
    """What a KDA mixer makes of one sequence's normed rows u [T, E] before its recurrence, zeros before the
    start: (q, k, v [T, H, D] after the convolution, silu and the L2 norms, q scaled; the log-decay g [T, H, D];
    beta [T, H]; [q | k | v] [T, 3 H D] as projected, before the convolution)."""
    hh, d, kw = dims
    f32 = lambda name: lp[name].astype(jnp.float32)
    t = u.shape[0]
    qkv = u @ f32("kda_qkv")
    padded = jnp.concatenate([jnp.zeros((kw - 1, qkv.shape[1]), qkv.dtype), qkv], axis=0)
    xc = jax.nn.silu(sum(f32("kda_conv")[j] * padded[j:j + t] for j in range(kw)))
    q, k, v = (a.reshape(t, hh, d) for a in jnp.split(xc, 3, axis=-1))
    unit = lambda a: a / jnp.sqrt(jnp.sum(a * a, axis=-1, keepdims=True) + L2_EPS)
    g = jax.nn.softplus((u @ f32("kda_f1")) @ f32("kda_f2") + f32("dt_bias")).reshape(t, hh, d)
    g = -jnp.exp(f32("a_log"))[:, None] * g
    return unit(q) * d ** -0.5, unit(k), v, g, jax.nn.sigmoid(u @ f32("kda_b")), qkv


def _delta_rule(q, k, v, g, beta, at=-1):
    """The recurrence, one position a step, from S = 0, as the module's docstring writes it: S = (I - beta k
    k^T) Diag(exp(g)) S + beta k v^T, o = S^T q.  q, k, v, g: [T, H, D]; beta [T, H].  Returns (o [T, H, D], S
    [H, D, D] after the last position, S after position `at`)."""
    d = q.shape[-1]
    eye = jnp.eye(d, dtype=jnp.float32)

    def one_position(carry, now):
        s, kept = carry
        i, q_t, k_t, v_t, g_t, b_t = now
        forget = eye - b_t[:, None, None] * k_t[:, :, None] * k_t[:, None, :]  # [H, D, D]
        s = jnp.einsum("hij,hjv->hiv", forget, jnp.exp(g_t)[:, :, None] * s) + b_t[:, None, None] * k_t[:, :, None] * v_t[:, None, :]
        return (s, jnp.where(i == at, s, kept)), jnp.einsum("hk,hkv->hv", q_t, s)

    zero = jnp.zeros((q.shape[1], d, d), jnp.float32)
    (s, kept), o = lax.scan(one_position, (zero, zero), (jnp.arange(q.shape[0]), q, k, v, g, beta))
    return o, s, kept


def _kda(u, lp, dims, eps, at=-1):
    """A KDA mixer over one sequence's normed rows u [T, E] from S = 0.  Returns (f [T, E], ([q | k | v] [T, 3 H
    D] as projected, S after the last position, S after position `at`))."""
    f32 = lambda name: lp[name].astype(jnp.float32)
    q, k, v, g, beta, qkv = _kda_inputs(u, lp, dims)
    o, s, kept = _delta_rule(q, k, v, g, beta, at)
    o = o / jnp.sqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps) * f32("kda_norm")
    gate = jax.nn.sigmoid((u @ f32("kda_g1")) @ f32("kda_g2"))
    return (o.reshape(u.shape[0], -1) * gate) @ f32("kda_out"), (qkv, s, kept)


def _latent_qkv(u, lp, dims, eps):
    """What a latent-attention layer makes of one sequence's normed rows u [T, E] before it attends: (q [T, H,
    nope + rope], the key k_r [T, rope] that every head shares, the normed latent c_kv [T, R]), nothing turned."""
    h, dn, dr, _, r, _, _ = dims
    f32 = lambda name: lp[name].astype(jnp.float32)
    kv = u @ f32("wkv_a")
    return (u @ f32("wq")).reshape(u.shape[0], h, dn + dr), kv[:, r:], _rms_norm(kv[:, :r], f32("kv_a_norm"), eps)


@functools.partial(jax.jit, static_argnames=("kind", "mla", "kda", "moe", "eps"))
def _layer(x, lp, at=-1, *, kind, mla, kda, moe, eps):
    """One layer over one sequence.  x: [T, E] float32; lp: this layer's weights in whatever type they are stored
    in; kind: the program's name for it ("kda", "attn", with "_dense" behind it where the FFN is dense).  Returns
    (the layer's output; what its mixer made, for the checks: a KDA layer's ([q | k | v] as projected, S after
    the last position, S after position `at`: an operand, so that one compilation serves every prompt), a latent
    layer's (k_r, c_kv); what its FFN was given, the normed stream [T, E])."""
    f32 = lambda name: lp[name].astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        u = _rms_norm(x, f32("ln1"), eps)
        if kind.startswith("kda"):
            out, made = _kda(u, lp, kda, eps, at)
        else:
            q, k_r, c_kv = _latent_qkv(u, lp, mla, eps)
            out, made = _mla._expanded(q, k_r, c_kv, lp, mla) @ f32("wo"), (k_r, c_kv)
        x = x + out
        y = _rms_norm(x, f32("ln2"), eps)
        if kind.endswith("_dense"):
            return x + _mla._dense_mlp(y, lp["w_gate"], lp["w_up"], lp["w_down"]), made, y
        shared = (jax.nn.silu(y @ f32("shared_gate")) * (y @ f32("shared_up"))) @ f32("shared_down")
        return x + _mla._routed(y, lp, *moe)[0] + shared, made, y


def _dims(cfg):
    return dict(mla=(cfg.n_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim, cfg.kv_lora_rank, 1.0,
                     float((cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5)),
                kda=(cfg.kda_n_heads, cfg.kda_head_dim, cfg.ssm_d_conv), moe=_mla._moe_dims(cfg), eps=float(cfg.norm_eps))


def _layers(cfg):
    """[(kind, the stack its weights lie in, its index there)] in the model's order."""
    seen: Dict[str, int] = {}
    out = []
    for kind in cfg.layer_kinds:
        out.append((kind, _STACK[kind], seen.get(kind, 0)))
        seen[kind] = seen.get(kind, 0) + 1
    return out


def _layer_of(params, stack: str, i: int):
    return jax.tree_util.tree_map(lambda w: w[i], params[stack])


def _blocks(params: Dict[str, Any], ids, cfg, at=-1):
    """ids: [T] through the stack.  Yields, a layer at a time, (its kind, its input [T, E], its output, what its
    mixer made and what its FFN was given: `_layer`'s)."""
    x = params["embed"][jnp.asarray(ids)].astype(jnp.float32)
    for kind, stack, i in _layers(cfg):
        x_in = x
        x, made, y = _layer(x, _layer_of(params, stack, i), at, kind=kind, **_dims(cfg))
        yield kind, x_in, x, made, y


def _head(params, x, cfg):
    with jax.default_matmul_precision("highest"):
        return _rms_norm(x, params["ln_f"].astype(jnp.float32), float(cfg.norm_eps)) @ params["lm_head"].astype(jnp.float32)


def forward(params: Dict[str, Any], ids, cfg):
    """ids: [T] -> logits [T, V], float32.  `cfg`: the program's TransformerConfig, read for its sizes (heads, the
    two mixers' widths, the experts a token takes, their scale, the share held, the layers' kinds, the epsilon)."""
    for _, _, x, _, _ in _blocks(params, ids, cfg):
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
# One causal token a step from the last position's logits: the harness's default, by this file's own pass, which
# also keeps what `mechanism_checks` reads again: the input rows of the layers it enters (the last KDA layer, every
# latent layer), what every mixture was given, and how far the rows that the program's prefill of the stream's
# prompt installs lie from this pass's own (`_PrefillRows`).  Every stream is padded on the right to ONE length,
# the deployment's longest bucket and the served tokens in whole ROW_BLOCKs (a causal model's earlier positions do
# not see what follows; one length is one compilation a kind of layer), and the head takes the rows that chose a
# token alone.
ROW_BLOCK = 128
_given: Dict[bytes, tuple] = {}


def _stream_ids(stream) -> np.ndarray:
    return np.asarray(stream["prompt_ids"] + stream["served"][:-1], np.int32)


def _checked_layers(cfg):
    """(the layer whose KDA mixer the checks enter: the last; the latent layers, all of them)."""
    kinds = cfg.layer_kinds
    return (max(i for i, k in enumerate(kinds) if k.startswith("kda")),
            tuple(i for i, k in enumerate(kinds) if k.startswith("attn")))


class _PrefillRows:
    """The rows the program's own prefill of a stream's bucket hands the admit (`generate.prefill`: the admit's
    compiled program, left pads, chunks and all), held layer by layer to what this file's pass makes of the same
    positions."""

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
        self.seen = {"kda": 0, "attn": 0}
        self.errors = {"state": [], "ckv": [], "kr": []}  # an array's relative error, by the kind of row
        self.tails = []  # (program's tail - reference's, reference's) of every KDA layer: held as ONE array

    def hold(self, kind: str, made) -> None:
        """One layer's part: `made` is `_layer`'s of the whole stream, of which the prompt's positions are the
        first n."""
        cfg, n, rows = self.cfg, self.n, self.rows
        mixer = kind.split("_")[0]
        j = self.seen[mixer]
        self.seen[mixer] += 1
        rel = lambda got, want: jnp.linalg.norm(got.astype(jnp.float32) - want) / jnp.linalg.norm(want)
        if mixer == "kda":
            qkv, _, s = made  # S after the prompt's last token
            tail = jnp.pad(qkv[:n], ((cfg.ssm_d_conv - 1, 0), (0, 0)))[-(cfg.ssm_d_conv - 1):]
            self.errors["state"].append(rel(rows["h"][j, 0], s))
            self.tails.append((rows["conv"][j, 0].astype(jnp.float32) - tail, tail))
            return
        k_r, c_kv = made
        for name, a in (("kr", k_r), ("ckv", c_kv)):
            got = rows[name][j, 0, self.pad:self.pad + n, :a.shape[-1]]  # behind the left pads, the prompt's rows
            self.errors[name].append(rel(got, a[:n]))

    def worst(self) -> Dict[str, float]:
        """{kind of row: the largest relative error of an array of it}; the convolution tails, K - 1 rows a layer,
        are one array over the layers (three rows alone are one token's router flip upstream away from any number)."""
        off, want = (jnp.stack(a) for a in zip(*self.tails))
        out = {kind: float(jnp.max(jnp.stack(e))) for kind, e in self.errors.items()}
        return {**out, "conv": float(jnp.linalg.norm(off) / jnp.linalg.norm(want))}


def _given_of(cb, stream):
    """A stream's prompt + served[:-1] through the stack.  Returns (the last layer's output [T, E], ({layer index:
    the layer's input [T, E]} for the layers the checks enter, [expert layer] of what its mixture was given, both on
    the host in the program's activation type; the largest relative error of the rows the program's prefill of the
    prompt installs, by the kind of row))."""
    params, cfg = cb.params, cb.cfg
    ids, served = _stream_ids(stream), len(stream["served"])
    n, n_prompt = len(ids), len(stream["prompt_ids"])
    length = -(-(max(cb.prefill_buckets) + served) // ROW_BLOCK) * ROW_BLOCK
    last_kda, latent = _checked_layers(cfg)
    wanted = {last_kda, *latent}
    host = lambda a: np.asarray(a[:n].astype(cfg.dtype))
    rows = _PrefillRows(cb, stream)
    kept, ffn = {}, []
    for i, (kind, x_in, x, made, y) in enumerate(_blocks(params, np.pad(ids, (0, max(length, n) - n)), cfg, at=n_prompt - 1)):
        if i in wanted:
            kept[i] = host(x_in)
        if not kind.endswith("_dense"):
            ffn.append(host(y))
        rows.hold(kind, made)
    return x[:n], (kept, ffn, rows.worst())


def chosen_logits(cb, stream) -> np.ndarray:
    """Row i: the logits at position len(prompt) - 1 + i of prompt + served[:-1], which chose served[i]."""
    ids, n = _stream_ids(stream), len(stream["prompt_ids"])
    x, _given[ids.tobytes()] = _given_of(cb, stream)
    return np.asarray(_head(cb.params, x[n - 1:], cb.cfg))


# -- counts from shapes ---------------------------------------------------------
# `c` is the `config` object of a configuration file: the published keys, with `num_experts` the experts HELD and
# `num_experts_routed` the router's.  They count the mathematics, not the implementation.


def layer_counts(c: Dict[str, Any]) -> Dict[str, int]:
    """{"kda": its layers, "attn": the latent layers, "dense": the leading dense FFNs, "moe": the mixture layers}."""
    m = mixers(c)
    dense = c["first_k_dense_replace"]
    return {"kda": m.count("kda"), "attn": m.count("attn"), "dense": dense, "moe": c["num_hidden_layers"] - dense}


def kda_width(c: Dict[str, Any]) -> int:
    """A KDA layer's inner width, heads x their width: q's, k's and v's each."""
    la = c["linear_attn_config"]
    return la["num_heads"] * la["head_dim"]


def kda_params(c: Dict[str, Any]) -> int:
    """One KDA mixer: W_q, W_k, W_v, the three convolutions, the decay's low-rank pair with dt_bias and A_log, W_b,
    the output gate's pair, the head norm's weight, W_o, the layer's norm."""
    e, w, la, r = c["hidden_size"], kda_width(c), c["linear_attn_config"], c["kda_gate_rank"]
    return (3 * e * w + 3 * w * la["short_conv_kernel_size"] + 2 * (e * r + r * w) + w + la["num_heads"]
            + e * la["num_heads"] + la["head_dim"] + w * e + e)


def attention_params(c: Dict[str, Any]) -> int:
    """One latent layer: W_q, W_kva, its norm, W_kvb, W_o, the layer's norm."""
    e, h = c["hidden_size"], c["num_attention_heads"]
    r, dn, dr, dv = c["kv_lora_rank"], c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    return e * h * (dn + dr) + e * (r + dr) + r + r * h * (dn + dv) + h * dv * e + e


def expert_params(c: Dict[str, Any]) -> int:
    """One routed (or shared) expert's three matrices."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def expert_bytes(c: Dict[str, Any], bytes_per: int = 2) -> int:
    return expert_params(c) * bytes_per


def mixture_params(c: Dict[str, Any], held=None) -> int:
    """One mixture FFN with `held` of its routed experts (None: those the configuration holds): the router at its
    published width, the shared expert, the experts, the FFN's norm."""
    e = c["hidden_size"]
    held = c["num_experts"] if held is None else held
    return e * c["num_experts_routed"] + (c["num_shared_experts"] + held) * expert_params(c) + e


def param_count(c: Dict[str, Any]) -> int:
    """Every layer, the embedding, the untied head and the final norm, as held here."""
    n, e = layer_counts(c), c["hidden_size"]
    return (n["kda"] * kda_params(c) + n["attn"] * attention_params(c) + n["dense"] * (3 * e * c["intermediate_size"] + e)
            + n["moe"] * mixture_params(c) + 2 * c["vocab_size"] * e + e)


def kda_state_bytes(c: Dict[str, Any]) -> int:
    """One slot's matrix state over the KDA layers: S [H, D, D] in float32 a layer."""
    la = c["linear_attn_config"]
    return layer_counts(c)["kda"] * la["num_heads"] * la["head_dim"] ** 2 * 4


def slot_state_bytes(c: Dict[str, Any]) -> int:
    """One slot's recurrent state: `kda_state_bytes` and the convolutions' last K - 1 inputs [K-1, 3 H D] in bf16."""
    la = c["linear_attn_config"]
    return kda_state_bytes(c) + layer_counts(c)["kda"] * (la["short_conv_kernel_size"] - 1) * 3 * kda_width(c) * 2


def token_bytes(c: Dict[str, Any], bytes_per: int = 2) -> int:
    """What one token takes in the latent layers' cache as the mathematics has it: a latent row and the shared key."""
    return layer_counts(c)["attn"] * (c["kv_lora_rank"] + c["qk_rope_head_dim"]) * bytes_per


def kda_update_bytes(c: Dict[str, Any], rows: int) -> int:
    """What the decode update has to move for `rows` live rows at the least: each row's state read once and written
    once, over the KDA layers."""
    return 2 * rows * kda_state_bytes(c)


def kda_step_flops(c: Dict[str, Any], rows: int) -> float:
    """The update's operations for `rows` rows: the decay of S, S^T k, the rank-one update and S^T q, 2 a multiply-add."""
    la = c["linear_attn_config"]
    return float(rows * layer_counts(c)["kda"] * la["num_heads"] * (1 + 3 * 2) * la["head_dim"] ** 2)


def kda_prefill_flops(c: Dict[str, Any], positions: int) -> float:
    """The chunked form's operations for `positions` positions of one sequence, over the KDA layers and heads, 2 a
    multiply-add, in chunks of C = `kda_chunk`: a chunk's two decayed Gram matrices (k k and q k, their causal
    halves: C (C + 1) / 2 pairs of D channels each), the unit triangular solve of [K | V] (C (C - 1) / 2 x 2 D), W S
    and Q S (C D D each), A_qk U (C (C + 1) / 2 x D) and the state's update K^T U (C D D)."""
    la = c["linear_attn_config"]
    ch, d = c["kda_chunk"], la["head_dim"]
    half, strict = ch * (ch + 1) // 2, ch * (ch - 1) // 2
    a_chunk = 2.0 * (2 * half * d + strict * 2 * d + 2 * ch * d * d + half * d + ch * d * d)
    return -(-positions // ch) * a_chunk * la["num_heads"] * layer_counts(c)["kda"]


def experts_touched(c: Dict[str, Any], rows: int) -> float:
    """The held experts of one layer that `rows` tokens read between them if each takes its k of all the routed at
    random: held (1 - (1 - k/X)^rows)."""
    X, k, held = c["num_experts_routed"], c["num_experts_per_token"], c["num_experts"]
    return held * (1.0 - (1.0 - k / X) ** rows)


def train_flops_per_step(c: Dict[str, Any], batch: int, seq: int) -> float:
    """Operations the forward and backward passes require on THIS chip for `batch` sequences of `seq` tokens: 2 per
    multiply-add over the weights a token meets here (of its k routed experts the share that falls on those held),
    the latent layers' square in full, the KDA layers' chunked form, backward twice the forward.  No training cell
    runs this architecture."""
    n, e, V = layer_counts(c), c["hidden_size"], c["vocab_size"]
    here = c["num_experts_per_token"] * c["num_experts"] / c["num_experts_routed"]
    weights = (n["kda"] * kda_params(c) + n["attn"] * attention_params(c) + n["dense"] * 3 * e * c["intermediate_size"]
               + n["moe"] * (e * c["num_experts_routed"] + (c["num_shared_experts"] + here) * expert_params(c)))
    square = 2 * seq * seq * c["num_attention_heads"] * (c["qk_nope_head_dim"] + c["qk_rope_head_dim"] + c["v_head_dim"])
    fwd = batch * (seq * 2 * weights + square * n["attn"] + kda_prefill_flops(c, seq) + seq * 2 * e * V)
    return 3.0 * fwd


def decode_step_bytes(c: Dict[str, Any], slots: int, t_max: int, bytes_per: int = 2, lengths=None, touched=None) -> int:
    """Bytes one decode step has to move at the least: every weight outside the routed experts once (the mixers,
    the dense MLP, routers, shared experts; the embedding only its `slots` rows; the head), of each mixture layer's
    held experts those a batch touches (`touched` a layer, or `experts_touched` at `slots` rows), each row's
    recurrent state read and written (`slot_state_bytes`), and the rows' latent cache up to their `lengths` (None:
    t_max each)."""
    n, e, V = layer_counts(c), c["hidden_size"], c["vocab_size"]
    outside = (n["kda"] * kda_params(c) + n["attn"] * attention_params(c) + n["dense"] * (3 * e * c["intermediate_size"] + e)
               + n["moe"] * (e * c["num_experts_routed"] + c["num_shared_experts"] * expert_params(c) + e)
               + V * e + e + slots * e)
    held = n["moe"] * (experts_touched(c, slots) if touched is None else touched) * expert_params(c)
    context = slots * t_max if lengths is None else int(sum(lengths))
    return int((outside + held) * bytes_per) + 2 * slots * slot_state_bytes(c) + context * token_bytes(c, bytes_per)


# -- tolerances ------------------------------------------------------------------
# harness/reference.py says which program each of the three serving tolerances holds.  The readings are the chip's
# at the published widths, all 27 layers, taken as the cell's check takes them (the four check streams of
# traffic/reason-closed.json served together: prompts 100, 200, 480, 1000, 64 tokens each, teacher-forced through
# this reference in float32): the program through a batcher alone (`scripts/kimi_controls.py`) and in the cell's own
# runs; every control planted before the streams are served.  My chip runs, PR 60; PERF.md section 6 has every number.
#
# The program: 23 runs on 23 draws of the prompts (eighteen of the cell in three sets of six, four more of it, two of
# them traced, one through a batcher alone): the first two sets and the four on one draw of the weights, the third set
# and the batcher's on a draw each.  The faults: one seed each (2600200001).
#
# As in references/mla_moe.py, what sets the program's numbers on the logits is the router, not rounding's size: a held
# expert enters the stream with a weight near 2.446 / 8 = 0.31, and where the program's bf16 stream and this float32
# pass take or leave another held expert a whole weighted expert's result differs from there on.  So the largest logit
# error and the largest regret have the tail of a rare large event.
#
# The mean regret: the program **0.0075-0.0231**; padding let into the state 0.139, beta ignored 1.15, the decay one
# scalar a head 1.46, dropped 1.92, the convolution skipped 2.01.  2.6 x over the program's largest, 2.3 x under the
# least of those.  A rotation applied in the latent layers reads 0.0254 and a state handed on in bf16 0.0146, inside
# the program's range (random weights' attention is near uniform whether the shared key is turned or not; the state's
# rounding is a thousandth of it): `latent_rows_rel_err` and `kda_state_step_err` are theirs to fail.
REGRET_MEAN_TOL = 0.06
# The logits at a prompt's last row (4 rows x 20,480 a run): the program **0.307-0.805** (median 0.40; the three over 0.7
# a flip upstream of one of the four rows); beta ignored 3.45, the decay one scalar 3.98, the convolution skipped 4.17,
# the decay dropped 4.42; padding let in 1.92.  The largest regret of 256 tokens: the program **0.119-0.832** (0.61 and under in 22 of 23); beta
# ignored 2.90, the decay one scalar 3.53, the convolution skipped 4.17, the decay dropped 4.77; padding let in 1.29.
# They are set at three times and at nearly twice the program's largest, for the tail of seeds a check draws, and under
# every fault of the rule (1.4 x and 1.9 x under beta ignored, the least); padding let in reads inside both and is held by the rows a
# prefill installs (`kda_prefill_state_rel_err` 0.672, `latent_rows_rel_err` 0.646).
LOGIT_TOL = 2.4
REGRET_MAX_TOL = 1.5
# No training cell runs this architecture; the dense decoder's bound and reason.
LOSS_TOL = 0.01


# -- the mechanisms by themselves ---------------------------------------------------
# What the logits cannot see.  Each number is the program's own code at the window's shapes against this file's plain
# mathematics, and but for the first three ON THE SAME ROWS: what the reference's own float32 pass gave the layer
# (`_given`), rounded to the program's activation type, which is how a layer is handed them.
#
#   kda_prefill_state_rel_err, kda_conv_tail_rel_err, latent_rows_rel_err    the rows an admit installs: for each
#       stream the program's own prefill of its bucket (`generate.prefill`, the admit's compiled program: left pads,
#       the chunked delta rule, the compact held experts and all), every array of its rows against this file's own
#       pass over the stream: each KDA layer's S after the prompt's last token (the largest relative error of a
#       layer's, 2-norms over [H, D, D]), the convolutions' last three inputs (the 20 layers' as one array), each
#       latent layer's rows c_kv and k_r behind the left pads (the larger of the two kinds' largest).  They carry bf16
#       activations and the router's flips through the depth, so they hold the mathematics, not a precision: a
#       decay dropped or made one scalar a head, beta ignored, the convolution skipped, padding let into the state
#       (every one an error of the size of the state itself), a rotation applied to k_r (which the logits of random
#       weights cannot see: their attention is near uniform either way).
#   kda_chunk_state_rel_err, kda_state_rel_err, kda_out_rel_err    the last KDA layer through the program's own
#       `transformer._kda_mixer` as serving runs it: each stream's prompt in one call from the zero state at its
#       bucket's length, left pads masked, in chunks (`_kda_chunked`), then one token a row at [slots, 1, E] from the
#       slots' own states as a decode step runs it (`generate._kda_decode_mixer` over the stacks: on the chip through
#       ops/kda.py's kernel, the slots that hold a stream alone), the state handed on as the cache keeps it; against `_kda` here, one position
#       a step over the whole stream, from the same input rows.  The first is S after the prompt (the chunked form's
#       final state), the second S after the last step, the third the mixer's result of the decode rows.  All three
#       carry what the mixer's own bf16 projection and convolution differ by from float32.
#   kda_state_step_err    the same S after the last step, handed from token to token as the cache keeps it, against the
#       same `_kda_mixer` in ONE call over the whole stream from the zero state, which holds S in float32 from the
#       first position to the last.  The two paths make the same q, k, v, g and beta of the same rows, so they differ
#       by what handing S on costs (and by chunks against steps) and nothing else: the program against itself.
#   mla_absorb_rel_err    references/mla_moe.py's: every latent layer's decode core, absorbed (`generate.
#       _latent_decode_core`, one token a row at [slots, 1, .] against a latent cache of the deployment's [slots,
#       T_max]) against the expanded form in float32, both from this file's own q, k_r and c_kv rounded to the
#       program's activation type.
#   moe_router_other_set, moe_experts_rel_err    references/mla_moe.py's two, over every mixture layer and position.
#
# The tolerances, from the chip at the cell's own size (my chip runs, PR 60: the 17 runs above; each fault planted
# before the streams are served, seed 2600200001, `scripts/kimi_controls.py`, which says what each is; PERF.md section 6
# has the table).  Lower reading: the program's largest.  Upper: the least of the faults the number is there to catch.
#   kda_prefill_state: the program 0.079-0.153 (20 layers of bf16 stream and router flips through the depth); padding
#   let into the state 0.672, the decay one scalar 0.966, beta ignored 1.28, the convolution skipped 1.69, the decay
#   dropped 3.35.  2.4 x over, 1.9 x under.  (A rotation in the latent layers 0.165: the stream behind them moves.)
#   kda_conv_tail: the program 0.050-0.156 (three rows a layer: the noisiest of the ten, one token's router flip
#   upstream moves it); beta ignored 0.713, the decay one scalar 0.814, the convolution skipped 1.02, the decay dropped
#   1.02.  2.9 x over, 1.6 x under.  Padding let in reads 0.335, INSIDE it: the two numbers beside it hold that fault
#   (1.9 x and 2.2 x under it).
#   latent_rows: the program 0.084-0.122 (c_kv the larger kind in every run); padding let in 0.646, a rotation applied
#   **1.24** (the shared key turned: the one number that sees it), the others 0.76-1.2.  2.5 x over, 2.2 x under.
#   kda_chunk_state: the program 0.00345-0.00388; beta ignored 0.475, the decay one scalar 0.780, the convolution
#   skipped 1.48, the decay dropped 2.10.  3.1 x over, 40 x under.  It cannot see how S is handed on (state-bf16 0.00405).
#   kda_state: the program 0.00336-0.00383; beta ignored 0.478 and more.  3.1 x over, 40 x under.  It holds the stepped
#   rule (through the kernel on the chip) to this file's own, not a precision: it carries the mixer's bf16 projection
#   and convolution, so a state handed on in bf16 (0.00993) reads INSIDE it and is the next number's alone.
#   kda_state_step: the program **3.54e-05-3.94e-05** (the two paths' bf16 projections round a few outputs apart, and
#   chunks against steps; float32 alone reads 2e-7 on the CPU); a state handed on in bf16 **0.00941**.  13 x over, 19 x
#   under: the nearest precision below the configuration's comes out as not correct by this number.
#   kda_out: the program 0.00597-0.00987 (0.0082 and under in 22 of 23), the largest over 252 rows of a row's own relative error (a widest gap: Mamba-2's
#   read twice the rest on one seed in 33, references/nemotronh.py); beta ignored 0.439, the others 0.86-1.45.  3.0 x
#   over, 15 x under.  A state in bf16 reads 0.012, inside it.
#   mla_absorb: the program 0.00244-0.00248 in all 23, a maximum with no tail; no control of its own was asked for: the
#   bound and its controls are references/mla_moe.py's (the absorbed scores in bf16 0.0089 there), 2.6 x over.
#   moe_router_other_set: 0 of 52,832 in all 23; moe_experts: the program 0.0050-0.0059; mla_moe.py's two bounds.
KDA_PREFILL_STATE_ERR_TOL = 0.35
KDA_CONV_TAIL_ERR_TOL = 0.45
LATENT_ROWS_ERR_TOL = 0.3
KDA_CHUNK_STATE_ERR_TOL = 0.012
KDA_STATE_ERR_TOL = 0.012
KDA_OUT_ERR_TOL = 0.03
KDA_STATE_STEP_ERR_TOL = 5e-4
MLA_ABSORB_ERR_TOL = _mla.MLA_ABSORB_ERR_TOL
MOE_ROUTER_SET_TOL = _mla.MOE_ROUTER_SET_TOL
MOE_EXPERTS_ERR_TOL = _mla.MOE_EXPERTS_ERR_TOL


def _kda_program(cb, lay, decode):
    """The compiled program of the checked KDA layer's mixer as serving runs it: (the layer's input rows of every
    stream [rows, E], the layer's weights: an operand, not a value the program closes over) -> (f [steps * slots,
    E] of every (step, slot), S [streams, H, D, D] after each stream's prompt, S [slots, H, D, D] after the last
    step, S [streams, H, D, D] of each stream through one call), through the program's own `transformer._kda_mixer`
    and, for the steps, `generate._kda_decode_mixer` over the state's stacks (ops/kda.py's kernel on a TPU)."""
    from cluster_anywhere_tpu.models import generate, transformer
    from cluster_anywhere_tpu.ops.kda import live_rows

    cfg = cb.cfg
    zero = lambda b: transformer._kda_zero_state(cfg, b)
    n_rows = sum(n + t for _, n, t, _ in lay)

    @jax.jit
    def program(rows, lp):
        window, s = zero(cb.slots)
        # the states between two tokens are what the cache keeps: its type (the batcher's own cache says which)
        window, s = window.astype(cb.cache["conv"].dtype), s.astype(cb.cache["h"].dtype)
        after_prompt = []
        for slot, (off, n, _, pad) in enumerate(lay):
            keep = (jnp.arange(pad + n) >= pad)[None]
            _, (w1, s1) = transformer._kda_mixer(lp, jnp.pad(rows[off:off + n], ((pad, 0), (0, 0)))[None], cfg, zero(1), keep)
            window, s = window.at[slot].set(w1[0].astype(window.dtype)), s.at[slot].set(s1[0].astype(s.dtype))
            after_prompt.append(s1[0])
        at = lambda row: jnp.pad(rows, ((0, 1), (0, 0)))[row][:, None]  # an empty slot's row: zeros

        def step(cache, row):
            # as `generate.decode_rows` runs a kda layer's mixer: over the stacks (of one layer here), through the
            # kernel where the chip's step goes through it, the slots that hold a stream alone
            live = live_rows(row < n_rows, cb.slots) if generate.kda_on_kernel(cache, cfg) else None
            f, cache = generate._kda_decode_mixer(lp, at(row), cache, 0, cfg, live)
            return cache, f[:, 0]

        cache, f = lax.scan(step, {"conv": window[None], "h": s[None]}, jnp.asarray(decode))
        # and each stream in one call, prompt and decode rows together: S held in float32 throughout
        whole = [transformer._kda_mixer(lp, rows[off:off + n + t][None], cfg, zero(1))[1][1][0] for off, n, t, _ in lay]
        return f.reshape(-1, f.shape[-1]), jnp.stack(after_prompt), cache["h"][0], jnp.stack(whole)

    return program


@functools.partial(jax.jit, static_argnames=("dims", "lay", "slots", "eps"))
def _kda_errors(rows, f, after_prompt, s, whole, lp, *, dims, lay, slots, eps):
    """(the largest relative error of a stream's S after its prompt, of its S after its last step, of a decode row's
    f) against `_kda` here over each whole stream; and of the stepped S against the program's own one call."""
    with jax.default_matmul_precision("highest"):
        f32 = lambda a: a.astype(jnp.float32)
        rel = lambda got, want: jnp.linalg.norm(f32(got) - want) / jnp.linalg.norm(want)
        worst_p = worst_s = worst_f = step = jnp.zeros((), jnp.float32)
        for slot, (off, n, t, _) in enumerate(lay):
            u = _rms_norm(f32(rows[off:off + n + t]), f32(lp["ln1"]), eps)
            want_f, (_, want_s, want_p) = _kda(u, lp, dims, eps, at=n - 1)
            mine = f32(f[np.arange(t) * slots + slot])
            worst_f = jnp.maximum(worst_f, jnp.max(jnp.linalg.norm(mine - want_f[n:], axis=-1)
                                                   / jnp.linalg.norm(want_f[n:], axis=-1)))
            worst_p = jnp.maximum(worst_p, rel(after_prompt[slot], want_p))
            worst_s = jnp.maximum(worst_s, rel(s[slot], want_s))
            step = jnp.maximum(step, rel(s[slot], f32(whole[slot])))
        return worst_p, worst_s, worst_f, step


def kda_checks(cb, given, lay, decode):
    """(the chunked prefill's state error, the stepped state's, the mixer's result's, the stepped state's against
    one call): four of `mechanism_checks`' numbers before they are held to anything."""
    cfg = cb.cfg
    i = _checked_layers(cfg)[0]
    _, stack, j = _layers(cfg)[i]
    lp = _layer_of(cb.params, stack, j)
    rows = jnp.asarray(np.concatenate([g[i] for g in given]))
    out = _kda_program(cb, lay, decode)(rows, lp)
    dims = _dims(cfg)
    return tuple(float(e) for e in _kda_errors(rows, *out, lp, dims=dims["kda"], lay=lay, slots=cb.slots, eps=dims["eps"]))


@functools.partial(jax.jit, static_argnames=("dims", "lay", "dtype", "eps"))
def _latent_rows(rows, lp, *, dims, lay, dtype, eps):
    """This file's own q, shared key and latent of every row of every stream, in float32 from the rows as given (a
    layer's input: normed here), rounded to the program's activation type: what both sides of `mla_absorb_rel_err`
    start from."""
    with jax.default_matmul_precision("highest"):
        parts = [_latent_qkv(_rms_norm(rows[off:off + n + t].astype(jnp.float32), lp["ln1"].astype(jnp.float32), eps),
                             lp, dims, eps) for off, n, t, _ in lay]
    return tuple(jnp.concatenate(p).astype(dtype) for p in zip(*parts))


def attention_check(cb, given, lay, decode) -> float:
    """The largest relative error of a decode row's absorbed attention over every latent layer.  The core's program
    is references/mla_moe.py's, told of a stack of latent layers alone with this configuration's five widths (the
    core is handed q, the shared key and the latent as they are: whether they were turned is not its to know)."""
    cfg = cb.cfg
    dims = _dims(cfg)
    latent_only = types.SimpleNamespace(
        cfg=dataclasses.replace(cfg, layer_mixers=None, kda_n_heads=0, n_dense_layers=0), slots=cb.slots, t_max=cb.t_max)
    program = _mla._attention_program(latent_only, lay, decode)
    worst = []
    for i in _checked_layers(cfg)[1]:
        if worst:
            jax.block_until_ready(worst[-1])  # one layer's float32 copies at a time
        _, stack, j = _layers(cfg)[i]
        lp = _layer_of(cb.params, stack, j)
        rows = jnp.asarray(np.concatenate([g[i] for g in given]))
        latent = _latent_rows(rows, lp, dims=dims["mla"], lay=lay, dtype=jnp.dtype(cfg.dtype).name, eps=dims["eps"])
        worst.append(_mla._attention_errors(*latent, lp, program(*latent, lp), dims=dims["mla"], lay=lay, slots=cb.slots))
    return max(float(w) for w in worst)


def expert_checks(cb, ffn_given, lay, decode):
    """(the share of (row, layer) pairs whose set is not the reference's, the held experts' largest error, the
    pairs), over every mixture layer: the KDA blocks' and the latent blocks', each kind's stack by itself."""
    from cluster_anywhere_tpu.parallel.moe import EXPERT_MATRICES

    params, cfg = cb.params, cb.cfg
    n = sum(len(g[0]) for g in ffn_given)
    program = _mla._experts_program(cb, lay, decode, n)
    moe_layers = [(stack, j) for kind, stack, j in _layers(cfg) if not kind.endswith("_dense")]
    numbers = []
    for layer, (stack, j) in enumerate(moe_layers):
        if numbers:
            jax.block_until_ready(numbers[-1])  # one layer's float32 copies at a time
        blocks = params[stack]
        experts = {name: blocks[name] for name in EXPERT_MATRICES if name in blocks}
        rows = jnp.asarray(np.concatenate([g[layer] for g in ffn_given]))
        got, chosen = program(rows, blocks["router"], experts, j)
        numbers.append(_mla._expert_errors(rows, _layer_of(params, stack, j), got, chosen, moe=_mla._moe_dims(cfg)))
    other_sets, worst = (np.asarray(x) for x in zip(*numbers))
    pairs = n * len(numbers)
    return int(other_sets.sum()) / pairs, float(worst.max()), pairs


def mechanism_checks(cb, streams):
    """The ten numbers above (references/__init__.py says what the harness does with them)."""
    from cluster_anywhere_tpu.models.transformer import KDA_CHUNK

    cfg = cb.cfg
    kept = [_given.pop(_stream_ids(s).tobytes(), None) or _given_of(cb, s)[1] for s in streams]
    given, ffn_given = [k[0] for k in kept], [k[1] for k in kept]
    by_kind = {kind: max(k[2][kind] for k in kept) for kind in kept[0][2]}
    lay, decode = _mla.program_shapes(cb, streams)
    after_prompt, state, out, stepped = kda_checks(cb, given, lay, decode)
    absorb = attention_check(cb, given, lay, decode)
    other_sets, experts, pairs = expert_checks(cb, ffn_given, lay, decode)
    rows_checked = sum(t for _, _, t, _ in lay)
    n_kda = sum(k.startswith("kda") for k in cfg.layer_kinds)
    return [
        {"name": "kda_prefill_state_rel_err", "error": by_kind["state"], "tolerance": KDA_PREFILL_STATE_ERR_TOL,
         "why": f"largest relative error of a KDA layer's state S after the prompt, of the rows the {len(streams)} "
                f"streams' prefills install over {n_kda} layers, against the full forward's recurrence"},
        {"name": "kda_conv_tail_rel_err", "error": by_kind["conv"], "tolerance": KDA_CONV_TAIL_ERR_TOL,
         "why": f"relative error of the convolutions' last {cfg.ssm_d_conv - 1} inputs those prefills install, the "
                f"{n_kda} layers' as one array"},
        {"name": "latent_rows_rel_err", "error": max(by_kind["ckv"], by_kind["kr"]), "tolerance": LATENT_ROWS_ERR_TOL,
         "why": f"largest relative error of a latent layer's cached rows those prefills install: c_kv {by_kind['ckv']:.4f}, "
                f"the shared key (not turned) {by_kind['kr']:.4f}"},
        {"name": "kda_chunk_state_rel_err", "error": after_prompt, "tolerance": KDA_CHUNK_STATE_ERR_TOL,
         "why": f"largest relative error of a stream's S after its prompt, from the prefill's delta rule in chunks of "
                f"{KDA_CHUNK}, against the recurrence one position a step in float32, on the same rows"},
        {"name": "kda_state_rel_err", "error": state, "tolerance": KDA_STATE_ERR_TOL,
         "why": "largest relative error of a stream's S after its last step, handed on as the cache keeps it"},
        {"name": "kda_out_rel_err", "error": out, "tolerance": KDA_OUT_ERR_TOL,
         "why": f"largest relative error of a decode row's KDA mixer result, head norm, gate and all, over {rows_checked} rows"},
        {"name": "kda_state_step_err", "error": stepped, "tolerance": KDA_STATE_STEP_ERR_TOL,
         "why": "largest relative error of that S against the same mixer's one call over the whole stream, which "
                "holds S in float32 throughout: what handing the state on from token to token costs"},
        {"name": "mla_absorb_rel_err", "error": absorb, "tolerance": MLA_ABSORB_ERR_TOL,
         "why": f"largest relative error of a decode row's absorbed attention (concat of {cfg.n_heads} heads' results, no "
                f"rotation) against the expanded form in float32, over {rows_checked} rows of every latent layer"},
        {"name": "moe_router_other_set", "error": other_sets, "tolerance": MOE_ROUTER_SET_TOL,
         "why": f"(row, layer) pairs of {pairs} in which the program's set of {cfg.n_experts_per_tok} of "
                f"{cfg.n_experts} is not the float32 reference's"},
        {"name": "moe_experts_rel_err", "error": experts, "tolerance": MOE_EXPERTS_ERR_TOL,
         "why": "largest relative error of the held experts' part of a row's result, over the rows whose sets "
                "agree (zeros where a row chose none of them)"},
    ]
