"""A stack whose layers are each ONE of a Mamba-2 mixer, a mixture of relu^2
experts with a shared expert, or attention, as this chip's share of a
deployment that divides each mixture layer's routed experts over several
chips: the key set of `nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16`
(`model_type` `nemotron_h`; Nemotron-H, arXiv:2504.03624; its mixer Mamba-2 /
SSD, arXiv:2405.21060; configs/nemotron-3-nano-30b-a3b-ep8-serve1.json).  The
interface is the package's (references/__init__.py).

The equations.  `hybrid_override_pattern` gives each layer's letter; every
layer is x = x + f(N(x)) with ONE RMSNorm N (a learned weight, `norm_eps`), no
bias anywhere but the convolution's; after the last layer N_out and an untied
head; no embedding scale.  u = N(x) [E]:

  M  Mamba-2   [z (C), xBC (C + 2 G N), dt (H)] = u W_in,  C = H P (`mamba_num_heads` x `mamba_head_dim`)
               xBC_t <- silu(b_c + sum_j w_c[j] * xBC_{t-K+1+j})    (K = `conv_kernel`, depthwise, zeros before the start)
               xs [H, P], B [G, N], C [G, N] = split(xBC);  head h reads group g = h // (H / G)
               dt = softplus(dt + dt_bias) [H];  A = -exp(A_log) [H]
               h_t[h] = exp(dt_t[h] A[h]) h_{t-1}[h] + dt_t[h] xs_t[h] (x) B_t[g]     ([P, N] a head, h_{-1} = 0)
               y_t[h] = h_t[h] C_t[g] + D[h] xs_t[h]
               y <- RMSNorm_groups(y * silu(z)): over each of the G groups' C / G channels, one weight [C], `norm_eps`
               f = y W_out
  E  mixture   s = sigmoid(u W_r) over all `n_routed_experts_routed`, float32; the `num_experts_per_tok` largest
               (`n_group` 1, `topk_group` 1: no groups); w_e = `routed_scaling_factor` s_e / (sum of the k + 1e-20)
               f = sum over the chosen e THAT ARE HELD of w_e relu(u W_up_e)^2 W_down_e  +  relu(u W_up_s)^2 W_down_s
               (experts `moe_intermediate_size` wide, the shared one `moe_shared_expert_intermediate_size`; no gate)
  *  attention q = u W_q [H_q, D], k = u W_k, v = u W_v [KV, D];  score(i, j) = q_h(i) . k_g(j) / sqrt(D), j <= i;
               f = concat_h(softmax_j(score_h) v_g) W_o;  NO positional embedding

The share (model-configs guide, section 4): `experts_held` = (first, count) of
the program's configuration says which of the router's experts this chip
holds; the router keeps its width and a token takes its k of all of them; what
the experts held elsewhere would add is left out, here as in the program, and
the partial result goes on to the next layer.  The vocabulary is the slice the
configuration holds.  tests/test_nemotronh.py holds that the eight shares'
parts, with the shared expert once, add up to the uncut layer.

The plain reference is straightforward `jax.numpy` in float32 at `highest`
matmul precision: no kernel, no cache, no batching, no sorting of tokens, NO
CHUNKS: the recurrence is a sequential `lax.scan`, one position a step, and the
held experts a loop, one expert's matrices upcast at a time.  Layers run one at
a time in a Python loop.  It shares no code with `cluster_anywhere_tpu/models/`
or `parallel/`; it reads the same parameter tree (`mamba2_blocks`, `ffn_blocks`,
`alone_blocks`: each kind's layers stacked in their order).
(`mechanism_checks`, at the end, calls the program's own functions as what it
checks, not as a reference.)

Assumed and departures: the configuration file lists each with where it was
taken from.  In short: no rotary embedding (the config carries `rope_theta` and
`partial_rotary_factor`; Nemotron-H's attention reads neither); the gated norm's
groups and its order (gate, then norm); no selection bias on the router's scores
(the published code's buffer of zeros); `time_step_*` are initialisation only;
the state is float32.  `mamba_num_heads x mamba_head_dim` is the inner width,
not `expand x hidden_size`; the convolution's weight is stored [K, C + 2 G N].
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

# how the check streams' rows lie in the calls that serve them (`program_shapes`) and the program
# of an expert layer's calls of `_moe` in those shapes (`_experts_program`) are A.X-K1's file's:
# host arithmetic on the batcher's own buckets and the program's own function
_mla = manifest.load_reference("mla_moe", os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

ATTN_BLOCK = 256  # query rows per block: bounds the [heads, block, T] scores
# what this architecture's programs write beyond the common names (program_trace.SCOPES): a Mamba-2
# layer's in place of the `attn.*` (the recurrence's two parts in a prefill, and the gated norm,
# beside Mamba-1's five), and the mixture's five under `ffn`
SCOPES = ("ssm.in", "ssm.conv", "ssm.scan", "ssm.scan.chunk", "ssm.scan.carry", "ssm.norm", "ssm.state", "ssm.out",
          "moe.router", "moe.dispatch", "moe.experts", "moe.combine", "moe.shared")
# the grouped matmul (references/olmoe.py says why it is known by name).  The chunked prefill's
# products and the decode step's state update are plain JAX: no kernel of this architecture's own.
KERNELS = ("ragged-dot-none",)

KINDS = {"M": "mamba2", "E": "ffn", "*": "attn_alone"}
_STACK = {"mamba2": "mamba2_blocks", "ffn": "ffn_blocks", "attn_alone": "alone_blocks"}


def pattern(c: Dict[str, Any]) -> str:
    """The letters of the layers that are run: the published pattern as far as `num_hidden_layers`."""
    return c["hybrid_override_pattern"][:c["num_hidden_layers"]]


def program_config(config_file: Dict[str, Any], **extra) -> Dict[str, Any]:
    """The program's TransformerConfig fields from a configuration file's
    keys.  `n_routed_experts` counts the experts HELD; the router's width is
    `n_routed_experts_routed` and the share starts at `experts_held_first`.
    A program that lacks one of the fields cannot run the configuration:
    refused here, by name, before anything is deployed."""
    from cluster_anywhere_tpu.models.transformer import TransformerConfig

    c = config_file["config"]
    if (c["mlp_hidden_act"] != "relu2" or c["mamba_hidden_act"] != "silu" or c["mlp_bias"] or c["attention_bias"]
            or c["mamba_proj_bias"] or c["use_bias"]):
        raise ValueError("this file writes relu^2 experts, a silu mixer and no bias but the convolution's")
    if c["n_group"] != 1 or c["topk_group"] != 1 or c["n_shared_experts"] != 1:
        raise ValueError("this file writes no expert groups and one shared expert")
    if set(pattern(c)) - set(KINDS) or len(pattern(c)) != c["num_hidden_layers"]:
        raise ValueError("hybrid_override_pattern: a letter of M, E, * for each of num_hidden_layers")
    if c["norm_eps"] != c["layer_norm_epsilon"]:
        raise ValueError("one epsilon for every norm")
    out = dict(
        d_model=c["hidden_size"], n_layers=c["num_hidden_layers"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], d_head=c["head_dim"], d_ff=c["intermediate_size"],
        max_seq_len=c["max_position_embeddings"], layer_mixers=tuple(KINDS[m] for m in pattern(c)),
        rotary=False, tie_embeddings=bool(c["tie_word_embeddings"]), norm_eps=float(c["norm_eps"]),
        ssm_n_heads=c["mamba_num_heads"], ssm_head_dim=c["mamba_head_dim"], ssm_n_groups=c["n_groups"],
        ssm_d_state=c["ssm_state_size"], ssm_d_conv=c["conv_kernel"], ssm_chunk=c["chunk_size"],
        ssm_conv_bias=bool(c["use_conv_bias"]),
        n_experts=c["n_routed_experts_routed"], n_experts_per_tok=c["num_experts_per_tok"], moe_gated=False,
        moe_act="relu2", moe_renormalize=bool(c["norm_topk_prob"]), moe_scoring="sigmoid",
        moe_routed_scale=float(c["routed_scaling_factor"]), d_expert=c["moe_intermediate_size"],
        n_shared_experts=c["n_shared_experts"], d_shared=c["moe_shared_expert_intermediate_size"],
        experts_held=(c["experts_held_first"], c["n_routed_experts"]),
    )
    out.update(extra)
    lacking = sorted(set(out) - {f.name for f in dataclasses.fields(TransformerConfig)})
    if lacking:
        raise NotImplementedError(
            f"this program's TransformerConfig has no {lacking}: it runs no layer that is one half alone, no Mamba-2 "
            "mixer and no relu^2 expert; this configuration cannot run on it"
        )
    return out


# -- the mathematics ---------------------------------------------------------------


def _rms_norm(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _mamba2_inputs(u, lp, dims):
    """What a Mamba-2 mixer makes of one sequence's normed rows u [T, E] before
    its recurrence, from zeros before the start: (z [T, C], xs [T, H, P], B, C
    [T, G, N], dt [T, H] after its softplus, A [H], xBC [T, C + 2 G N] as
    projected, before the convolution)."""
    hh, p, g, n, kw = dims
    c = hh * p
    f32 = lambda name: lp[name].astype(jnp.float32)
    t = u.shape[0]
    proj = u @ f32("ssm_in")
    w = c + 2 * g * n
    z, xbc, dt = proj[:, :c], proj[:, c:c + w], proj[:, c + w:c + w + hh]  # past them, the columns of zeros W_in is stored with
    padded = jnp.concatenate([jnp.zeros((kw - 1, xbc.shape[1]), xbc.dtype), xbc], axis=0)
    xc = sum(f32("conv_w")[j] * padded[j:j + t] for j in range(kw))
    if "conv_b" in lp:
        xc = xc + f32("conv_b")
    xc = jax.nn.silu(xc)
    xs, b, cc = xc[:, :c].reshape(t, hh, p), xc[:, c:c + g * n].reshape(t, g, n), xc[:, c + g * n:].reshape(t, g, n)
    return z, xs, b, cc, jax.nn.softplus(dt + f32("dt_bias")), -jnp.exp(f32("a_log")), xbc


def _recurrence(xs, b, cc, dt, a, at=-1):
    """The recurrence, one position a step, from h = 0: xs [T, H, P]; b, cc
    [T, G, N]; dt [T, H]; a [H].  Returns (y [T, H, P] without the D term, h
    [H, P, N] after the last position, h after position `at`)."""
    r = xs.shape[1] // b.shape[1]  # heads a group

    def one_position(carry, now):
        h, kept = carry
        i, x_t, b_t, c_t, dt_t = now
        b_h, c_h = jnp.repeat(b_t, r, axis=0), jnp.repeat(c_t, r, axis=0)  # [H, N]
        h = jnp.exp(dt_t * a)[:, None, None] * h + (dt_t[:, None] * x_t)[:, :, None] * b_h[:, None, :]
        return (h, jnp.where(i == at, h, kept)), jnp.sum(h * c_h[:, None, :], axis=-1)

    zero = jnp.zeros((*xs.shape[1:], b.shape[-1]), jnp.float32)
    (h, kept), y = lax.scan(one_position, (zero, zero), (jnp.arange(xs.shape[0]), xs, b, cc, dt))
    return y, h, kept


def _mamba2(u, lp, dims, eps, at=-1):
    """A Mamba-2 mixer over one sequence's normed rows u [T, E] from h = 0.
    Returns (f [T, E], (xBC [T, C + 2 G N] as projected, h after the last
    position, h after position `at`))."""
    g = dims[2]
    f32 = lambda name: lp[name].astype(jnp.float32)
    z, xs, b, cc, dt, a, xbc = _mamba2_inputs(u, lp, dims)
    y, h, kept = _recurrence(xs, b, cc, dt, a, at)
    t = u.shape[0]
    y = ((y + f32("ssm_d")[:, None] * xs).reshape(t, -1) * jax.nn.silu(z)).reshape(t, g, -1)
    y = (y / jnp.sqrt(jnp.mean(y * y, axis=-1, keepdims=True) + eps)).reshape(t, -1) * f32("ssm_norm")
    return y @ f32("ssm_out"), (xbc, h, kept)


def _qkv(u, lp, dims):
    h, kv, d = dims
    t = u.shape[0]
    f32 = lambda name: lp[name].astype(jnp.float32)
    return (u @ f32("wq")).reshape(t, h, d), (u @ f32("wk")).reshape(t, kv, d), (u @ f32("wv")).reshape(t, kv, d)


def _attention(q, k, v, rows=None):
    """Every head's causal attention over one sequence under an explicit mask.
    q [T, H, D]; k, v [T_kv, KV, D], each cached head serving H / KV query
    heads.  Returns concat(o) [T, H D], or of the query rows at positions `rows`
    (an index array into the keys' positions) alone, q then holding those rows only."""
    t, h, d = q.shape
    kv = k.shape[1]

    def block(q_rows, at):
        hi = k.shape[0] if rows is not None else int(at[-1]) + 1
        s = jnp.einsum("qgrd,kgd->grqk", q_rows.reshape(len(at), kv, h // kv, d), k[:hi]) * d ** -0.5
        mask = at[:, None] >= jnp.arange(hi)[None, :]
        p = jax.nn.softmax(jnp.where(mask[None, None], s, -jnp.inf), axis=-1)
        return jnp.einsum("grqk,kgd->qgrd", p, v[:hi]).reshape(len(at), h * d)

    if rows is not None:
        return block(q, rows)
    return jnp.concatenate([block(q[lo:lo + ATTN_BLOCK], np.arange(lo, min(t, lo + ATTN_BLOCK)))
                            for lo in range(0, t, ATTN_BLOCK)], axis=0)


def _relu2(x):
    return jnp.square(jnp.maximum(x, 0.0))


def _routed(u, lp, k: int, renormalize: bool, scale: float, first: int):
    """The held experts' part of the routed result for u [T, E]: sigmoid scores
    over all the router's experts, the k largest, their weights; then the
    experts held here (`lp`'s, the router's experts first, first + 1, ...) one
    after the other over every token, each weighted by the token's weight for
    it, 0 where it is not among the token's k.  Returns (the part [T, E], the
    tokens' weights over ALL the router's experts [T, X])."""
    scores = jax.nn.sigmoid(u @ lp["router"].astype(jnp.float32))  # [T, X]
    top, idx = lax.top_k(scores, k)
    if renormalize:
        top = top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20)
    top = top * scale
    weight = jnp.sum(jax.nn.one_hot(idx, scores.shape[-1], dtype=jnp.float32) * top[..., None], axis=1)
    held = lp["w_out"].shape[0]
    w_ups = lp["w_in"][:, :, :lp["w_out"].shape[1]]  # W_up [X, E, F]: the program stores it with columns of zeros behind F

    def one_expert(acc, e):
        w_up, w_down, w_e = e
        return acc + w_e[:, None] * (_relu2(u @ w_up.astype(jnp.float32)) @ w_down.astype(jnp.float32)), None

    out, _ = lax.scan(one_expert, jnp.zeros_like(u), (w_ups, lp["w_out"], weight[:, first:first + held].T))
    return out, weight


def _mixture(u, lp, moe):
    f32 = lambda name: lp[name].astype(jnp.float32)
    return _routed(u, lp, *moe)[0] + _relu2(u @ f32("shared_in")) @ f32("shared_out")


@functools.partial(jax.jit, static_argnames=("kind", "dims", "ssm", "moe", "eps"))
def _layer(x, lp, at=-1, *, kind, dims, ssm, moe, eps):
    """One layer over one sequence.  x: [T, E] float32; lp: this layer's weights
    in whatever type they are stored in.  Returns (the layer's output; what it
    made, for the checks: a Mamba-2 layer's (xBC, h after the last position, h
    after position `at`: an operand, so that one compilation serves every prompt),
    an attention layer's (k, v), a mixture's normed input u)."""
    with jax.default_matmul_precision("highest"):
        u = _rms_norm(x, lp["ln2" if kind == "ffn" else "ln1"].astype(jnp.float32), eps)
        if kind == "mamba2":
            out, made = _mamba2(u, lp, ssm, eps, at)
        elif kind == "ffn":
            out, made = _mixture(u, lp, moe), u
        else:
            q, k, v = _qkv(u, lp, dims)
            out, made = _attention(q, k, v) @ lp["wo"].astype(jnp.float32), (k, v)
        return x + out, made


def _layers(cfg):
    """[(kind, the stack its weights lie in, its index there)] in the model's order."""
    seen: Dict[str, int] = {}
    out = []
    for kind in cfg.layer_mixers:
        out.append((kind, _STACK[kind], seen.get(kind, 0)))
        seen[kind] = seen.get(kind, 0) + 1
    return out


def _layer_of(params, stack: str, i: int):
    return jax.tree_util.tree_map(lambda w: w[i], params[stack])


def _moe_dims(cfg):
    first = cfg.experts_held[0] if cfg.experts_held is not None else 0
    return (cfg.n_experts_per_tok, bool(cfg.moe_renormalize), float(cfg.moe_routed_scale), first)


def _dims(cfg):
    return dict(dims=(cfg.n_heads, cfg.n_kv_heads, cfg.d_head),
                ssm=(cfg.ssm_n_heads, cfg.ssm_head_dim, cfg.ssm_n_groups, cfg.ssm_d_state, cfg.ssm_d_conv),
                moe=_moe_dims(cfg), eps=float(cfg.norm_eps))


def _blocks(params: Dict[str, Any], ids, cfg, at=-1):
    """ids: [T] through the stack.  Yields, a layer at a time, (its kind, its
    input [T, E], its output, what it made: `_layer`'s, its weights)."""
    x = params["embed"][jnp.asarray(ids)].astype(jnp.float32)
    for kind, stack, i in _layers(cfg):
        x_in = x
        lp = _layer_of(params, stack, i)
        x, made = _layer(x, lp, at, kind=kind, **_dims(cfg))
        yield kind, x_in, x, made, lp


def _head(params, x, cfg):
    with jax.default_matmul_precision("highest"):
        return _rms_norm(x, params["ln_f"].astype(jnp.float32), float(cfg.norm_eps)) @ params["lm_head"].astype(jnp.float32)


def forward(params: Dict[str, Any], ids, cfg):
    """ids: [T] -> logits [T, V], float32.  `cfg`: the program's
    TransformerConfig, read for its sizes (heads, the mixer's five, the experts a
    token takes, their scale, the share held, the layers' kinds, the epsilon)."""
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
# One causal token a step from the last position's logits: the harness's default, by this
# file's own pass, which also keeps what `mechanism_checks` reads again: the input rows of the
# two layers it enters, what every mixture was given, and how far the rows that the program's
# prefill of the stream's prompt installs lie from this pass's own (`_PrefillRows`).  Every
# stream is padded on the right to ONE length, the deployment's longest bucket and the served
# tokens in whole ROW_BLOCKs (a causal model's earlier positions do not see what follows; one
# length is one compilation a kind of layer and prompt length), and the head takes the rows
# that chose a token alone.
ROW_BLOCK = 128
_given: Dict[bytes, tuple] = {}


def _stream_ids(stream) -> np.ndarray:
    return np.asarray(stream["prompt_ids"] + stream["served"][:-1], np.int32)


def _checked_layers(cfg):
    """{what a check enters: its layer's index}: the last Mamba-2 layer and the last attention layer."""
    last = lambda kind: max(i for i, m in enumerate(cfg.layer_mixers) if m == kind)
    return {"ssm": last("mamba2"), "attn": last("attn_alone")}


def _given_of(cb, stream):
    """A stream's prompt + served[:-1] through the stack.  Returns (the last
    layer's output [T, E], ({layer index: the layer's input [T, E]} for the
    layers the checks enter, [expert layer] of what its mixture was given, both
    on the host in the program's activation type; the largest relative error of
    the rows the program's prefill of the prompt installs, by the kind of row))."""
    params, cfg = cb.params, cb.cfg
    ids, served = _stream_ids(stream), len(stream["served"])
    n, n_prompt = len(ids), len(stream["prompt_ids"])
    length = -(-(max(cb.prefill_buckets) + served) // ROW_BLOCK) * ROW_BLOCK
    wanted = set(_checked_layers(cfg).values())
    host = lambda a: np.asarray(a[:n].astype(cfg.dtype))
    rows = _PrefillRows(cb, stream)
    kept, ffn = {}, []
    for i, (kind, x_in, x, made, _) in enumerate(_blocks(params, np.pad(ids, (0, max(length, n) - n)), cfg, at=n_prompt - 1)):
        if i in wanted:
            kept[i] = host(x_in)
        if kind == "ffn":
            ffn.append(host(made))
        else:
            rows.hold(kind, made)
    return x[:n], (kept, ffn, rows.worst())


def chosen_logits(cb, stream) -> np.ndarray:
    """Row i: the logits at position len(prompt) - 1 + i of prompt +
    served[:-1], which chose served[i]."""
    ids, n = _stream_ids(stream), len(stream["prompt_ids"])
    x, _given[ids.tobytes()] = _given_of(cb, stream)
    return np.asarray(_head(cb.params, x[n - 1:], cb.cfg))


# -- counts from shapes ---------------------------------------------------------
# `c` is the `config` object of a configuration file: the published keys, with
# `n_routed_experts` the experts HELD and `n_routed_experts_routed` the router's.


def layer_counts(c: Dict[str, Any]) -> Dict[str, int]:
    """{kind: its layers}, of the layers that are run."""
    return {kind: pattern(c).count(letter) for letter, kind in KINDS.items()}


def expert_layers(c: Dict[str, Any]) -> int:
    """The layers that hold experts: the pattern's E."""
    return layer_counts(c)["ffn"]


def conv_width(c: Dict[str, Any]) -> int:
    """The channels the convolution runs over: x, B and C side by side."""
    return c["mamba_num_heads"] * c["mamba_head_dim"] + 2 * c["n_groups"] * c["ssm_state_size"]


def mixer_params(c: Dict[str, Any]) -> int:
    """One Mamba-2 layer: W_in to [z, xBC, dt], the convolution and its bias,
    dt_bias, A_log and D a head, the gated norm's weight, W_out, the layer's norm."""
    e, hh = c["hidden_size"], c["mamba_num_heads"]
    inner, w = hh * c["mamba_head_dim"], conv_width(c)
    conv = w * c["conv_kernel"] + (w if c["use_conv_bias"] else 0)
    return e * (inner + w + hh) + conv + 3 * hh + inner + inner * e + e


def attention_params(c: Dict[str, Any]) -> int:
    e, h, kv, d = c["hidden_size"], c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    return e * h * d + 2 * e * kv * d + h * d * e + e


def expert_params(c: Dict[str, Any]) -> int:
    """One routed expert: W_up and W_down."""
    return 2 * c["hidden_size"] * c["moe_intermediate_size"]


def expert_bytes(c: Dict[str, Any], bytes_per: int = 2) -> int:
    return expert_params(c) * bytes_per


def mixture_params(c: Dict[str, Any], held=None) -> int:
    """One mixture layer with `held` of its routed experts (None: those the
    configuration holds): the router at its published width, the shared expert,
    the experts, the layer's norm."""
    e = c["hidden_size"]
    held = c["n_routed_experts"] if held is None else held
    return (e * c["n_routed_experts_routed"] + 2 * e * c["moe_shared_expert_intermediate_size"]
            + held * expert_params(c) + e)


def param_count(c: Dict[str, Any]) -> int:
    """Every layer, the embedding, the untied head and the final norm, as held here."""
    n = layer_counts(c)
    head = 0 if c["tie_word_embeddings"] else c["vocab_size"] * c["hidden_size"]
    return (n["mamba2"] * mixer_params(c) + n["attn_alone"] * attention_params(c) + n["ffn"] * mixture_params(c)
            + c["vocab_size"] * c["hidden_size"] + head + c["hidden_size"])


def slot_state_bytes(c: Dict[str, Any]) -> int:
    """One slot's recurrent state over the Mamba-2 layers: h [H, P, N] in float32
    and the convolution's window [K-1, C + 2 G N] in bf16."""
    h = c["mamba_num_heads"] * c["mamba_head_dim"] * c["ssm_state_size"] * 4
    return layer_counts(c)["mamba2"] * (h + (c["conv_kernel"] - 1) * conv_width(c) * 2)


def mixer_step_bytes(c: Dict[str, Any], slots: int, bytes_per: int = 2) -> int:
    """Bytes one decode step's Mamba-2 mixers have to move at the least: their
    weights once, and every slot's recurrent state read and written again (a
    recurrence has no dead row: an empty slot's state moves on with the rest)."""
    return layer_counts(c)["mamba2"] * mixer_params(c) * bytes_per + 2 * slots * slot_state_bytes(c)


def token_bytes(c: Dict[str, Any], bytes_per: int = 2) -> int:
    """One token's keys and values over the attention layers."""
    return layer_counts(c)["attn_alone"] * 2 * c["num_key_value_heads"] * c["head_dim"] * bytes_per


def experts_touched(c: Dict[str, Any], rows: int) -> float:
    """The held experts a layer's step is expected to touch when each of `rows`
    tokens takes its k of all the router's experts uniformly."""
    p = c["num_experts_per_tok"] / c["n_routed_experts_routed"]
    return c["n_routed_experts"] * (1.0 - (1.0 - p) ** rows)


def train_flops_per_step(c: Dict[str, Any], batch: int, seq: int) -> float:
    """Operations the forward and backward passes require for `batch`
    sequences of `seq` tokens: 2 per multiply-add over the matrices a token
    meets (its k experts of those HELD in expectation, the shared expert, the
    router), the causal half of the attention layers' scores and maps, the
    recurrence as SSD computes it in chunks (a chunk's C B^T, its map and its
    state, and the carried state's read-out), backward twice the forward.  No
    training cell runs this architecture."""
    e, h, d = c["hidden_size"], c["num_attention_heads"], c["head_dim"]
    hh, p, g, n, q = c["mamba_num_heads"], c["mamba_head_dim"], c["n_groups"], c["ssm_state_size"], c["chunk_size"]
    counts = layer_counts(c)
    here = c["num_experts_per_tok"] * c["n_routed_experts"] / c["n_routed_experts_routed"]
    mixer_matmul = mixer_params(c) - (conv_width(c) * (c["conv_kernel"] + 1) + 3 * hh + hh * p + e)
    mixture = (e * c["n_routed_experts_routed"] + 2 * e * c["moe_shared_expert_intermediate_size"]
               + here * expert_params(c))
    per_token = (counts["mamba2"] * mixer_matmul + counts["attn_alone"] * (attention_params(c) - e)
                 + counts["ffn"] * mixture + e * c["vocab_size"])
    ssd = counts["mamba2"] * (q * g * n + q * hh * p + 2 * hh * p * n)  # multiply-adds a token
    fwd = batch * seq * 2 * (per_token + ssd) + batch * counts["attn_alone"] * 4 * h * d * seq * (seq + 1) // 2
    return 3.0 * fwd


def decode_step_bytes(c: Dict[str, Any], slots: int, t_max: int, bytes_per: int = 2, lengths=None,
                      touched=None) -> int:
    """Bytes one decode step has to read at the least: the mixers' weights and
    every slot's state (`mixer_step_bytes`), the attention layers', routers' and
    shared experts' weights, the head (the embedding is a gather of the live
    rows), the held experts that are touched (`touched` a layer: None = all
    held), and the live rows' keys and values (`lengths`: their contexts;
    without them `slots` rows of t_max, the most a step can read)."""
    n = layer_counts(c)
    e = c["hidden_size"]
    touched = c["n_routed_experts"] if touched is None else touched
    weights = (n["attn_alone"] * attention_params(c) + n["ffn"] * mixture_params(c, held=0)
               + c["vocab_size"] * e + e)
    lengths = [t_max] * slots if lengths is None else lengths
    return int(weights * bytes_per + n["ffn"] * touched * expert_bytes(c, bytes_per)
               + token_bytes(c, bytes_per) * sum(lengths) + mixer_step_bytes(c, slots, bytes_per))


# -- tolerances ------------------------------------------------------------------
# harness/reference.py says which program each of the three serving tolerances holds.
# The readings are the chip's at the published widths, all 52 layers, 16 of 128 experts and
# 16,384 vocabulary rows held, taken as the cell's check takes them (the four check streams of
# traffic/reason-closed.json served together, prompts 100, 200, 480, 1000 and 64 tokens
# each, teacher-forced through this reference in float32).  PERF.md section 6 (PR 50) has
# every number and its seeds; the controls are `scripts/nemotronh_controls.py`'s.
#
# **What sets the program's numbers is the router, not rounding's size**, as in A.X-K1's and
# K-EXAONE's cells (references/mla_moe.py has the argument in full): a held expert enters the
# stream with a weight near 2.5 / 6 = 0.42; the 6th and 7th of a token's 128 sigmoid scores lie
# close, the program's bf16 stream moves a router logit by 0.01-0.02, so now and then the
# program and this float32 pass take or leave another held expert in one of 23 layers and a
# whole weighted expert's result differs from there on.  On the same rows the two route alike
# in every pair (`moe_router_other_set` 0 in every run).  So the largest logit error and the
# largest regret have the tail of a rare large event and the lower precision's readings lie
# inside it; the mean regret tells the precisions apart.
#
# The first reading of each is the program's (bf16 weights and activations; float32 norms,
# softmaxes, router, recurrence and state) over 16 runs on 16 seeds (7 runs of the cell, 9 of `scripts/nemotronh_controls.py` through a batcher alone; the runs after these are in PERF.md section 6); the second is the nearest precision
# below bf16 over 2 seeds: every stored matrix rounded to float8 e4m3's 3 bits of mantissa,
# served by the program and held to this reference over the unrounded parameters
# ("float8-weights").  It has to come out as not correct, and does by the mean regret.
#
# The mean regret of 256 served tokens: the program 0.0023-0.0258 (the next largest 0.0233, 0.0226; 0.0-0.0139 and once 0.0337 in the refusal round's 9 runs: 1.5 x under the bound); float8-weights 0.0782, 0.1187 (2 seeds: 1.9 x over the program's largest, 1.6 x under float8's least); the
# experts' activation silu in relu^2's place, served ("silu-experts"), 0.192.
REGRET_MEAN_TOL = 0.05
# The logits at a prompt's last row (4 rows x 16,384 a run): the program 0.05-1.15 (nine of 16 over 0.7: a flip upstream of one of the four rows, or none; 0.04-1.225 in the refusal round's 9); float8-weights
# 1.11, 1.24.  The largest regret: the program 0.18-0.84 (0.94 once in the six runs after); float8-weights 1.14, 1.34.  No bound
# between the program's largest and float8's least has room on both sides (readings of a tail
# that one flip sets), so these two are set on the program's side alone, at about twice its
# largest, and catch what is not a matter of precision: a layer out of order, a norm left out, a
# cache row misplaced.
LOGIT_TOL = 2.4
REGRET_MAX_TOL = 1.7
# What none of the three can see, measured the same way: the recurrent state handed from token
# to token in bf16 AND SERVED so reads 0.492 / 0.447 / 0.0171 (seed 3000000211), inside the
# program's own range, and so does a rotary embedding applied in the six attention layers AND
# SERVED (0.315 / 0.626 / 0.0258 and 0.078 / 0.665 / 0.0162: with random weights the attention
# layers' maps are near uniform with or without it).  `ssm_state_step_err` sees the first and
# `prefill_rows_rel_err` the second, below.
# No training cell runs this architecture; the dense decoder's bound and reason.
LOSS_TOL = 0.01


# -- the mechanisms by themselves ---------------------------------------------------
# What the logits cannot see.  Each number is the program's own code at the window's shapes
# against this file's plain mathematics ON THE SAME ROWS: what the reference's own float32
# pass gave the layer (`_given`), rounded to the program's activation type, which is how a
# layer is handed them.
#
#   prefill_rows_rel_err    the rows an admit installs: for each stream the program's own
#       prefill of its bucket (`generate.prefill`, the admit's compiled program: left pads,
#       the chunked scan, the compact held experts and all), every array of its rows (each
#       Mamba-2 layer's convolution window and h, each attention layer's flat stack of keys
#       and values) against this file's own pass over the stream (a prompt's rows are those
#       of its positions; h after the prompt's last token is the token-by-token recurrence's
#       there).  The largest |program - reference| / |reference| of an array (2-norms over
#       the slots that hold a token; a layer's h, its keys, its values; the windows, three
#       rows a layer, as one array over the layers).  It carries bf16 activations and the
#       router's flips through the depth, so it holds the mathematics (a rotary embedding
#       applied turns every key, which the logits of random weights cannot see: their
#       attention is near uniform either way), not a precision.
#   ssm_prefill_state_rel_err, ssm_state_rel_err, ssm_out_rel_err    the last Mamba-2 layer
#       through the program's own `transformer._mamba2_mixer` as serving runs it: each
#       stream's prompt in one call from the zero state at its bucket's length, left pads
#       masked, in chunks (`_ssd_scan`), then one token a row at [slots, 1, E] from the slots'
#       own states, the state handed on as the cache keeps it; against `_mamba2` here, one
#       position a step over the whole stream, from the same input rows.  The first is h after
#       the prompt (the chunked scan's final state), the second h after the last step, the
#       third the mixer's result f of the decode rows: the largest relative error of a
#       stream's h, and of a row.  All three carry what the mixer's own bf16 projection and
#       convolution differ by from float32, so none can see how h is handed on.
#   ssm_state_step_err    that is this number's to see: the same h after the last step, handed
#       from token to token as the cache keeps it, against the same `_mamba2_mixer` in ONE call
#       over the whole stream from the zero state, which holds h in float32 from the first
#       position to the last.  The two paths make the same dt, B, C and xs of the same rows, so
#       they differ by what handing h on costs (and by chunks against steps) and nothing else.
#       It is the program against itself: it shows that stepping rounds nothing, not that the
#       mixer is right; that is `ssm_state_rel_err`'s.
#   attn_decode_rel_err    a decode step's attention in the last attention layer: for every
#       served position of the check streams, concat(o) [H D] as the program's decode core
#       gives it (`generate._kv_decode_core`, one token a row at [slots, 1, .] over flat stacks
#       of 2 cached heads at the deployment's [slots, T_max x 2], each stream as a prefill
#       stores it, the steps one after the other) against `_attention` here in float32, both
#       from this file's own q, k, v rounded to the program's activation type.
#   moe_router_other_set, moe_experts_rel_err    references/mla_moe.py's two, over every
#       expert layer and every position: the share of (row, layer) pairs in which the
#       program's set of k of ALL the router's experts is not this reference's; and, over the
#       rows whose sets agree, the largest relative error of the held experts' part of a row's
#       result through `transformer._moe` with the share it holds, in the prefill's and the
#       decode's shapes (a row that chose none of them has to come back as zeros).
#
# The tolerances, from the chip at the cell's own size (my chip runs, PR 50: the program over 16 runs, and over the
# 10 of the review round where it says so, after the held experts' loop, the loop of loops and `ssm_in`'s columns;
# each control planted once the streams are served, 1 or 2 seeds, so the numbers on the logits are the program's
# while `ok` comes out false by the control's own number: `scripts/nemotronh_controls.py`, whose docstring says
# what each control is).  Lower reading: the program's largest.  Upper: the control's least.
#   prefill_rows: the program 0.147-0.360 in the 8 runs of this form (0.083-0.358 and once 0.494 in the refusal round's 9: 1.4 x under the bound; h the largest kind in each; 0.125-0.430 in
#   the 8 before, when each layer's window was an array of its own); a rotary embedding applied in the prefills
#   ("rotary-applied") 1.212, 1.228, by the keys.  1.9 x over, 1.7 x under.
#   ssm_prefill_state: the program 0.0019-0.0053; the recurrence in bf16 ("recurrence-bf16") 0.056.  2.3 x over,
#   4.7 x under.  It cannot see how h is handed on (state-bf16 0.0041).
#   ssm_state: the program 0.0019-0.0061 (0.0068 once in the six runs after; 0.0020-0.0055 in the review round's 10);
#   a decode step that hands back the state it was given ("state-kept") 0.19985.  2.9 x over, 10 x under.  It holds
#   the stepped recurrence to this file's own, not a precision: it carries the mixer's bf16 projection and
#   convolution, so a state handed on in bf16 (0.0139) and the recurrence in bf16 (0.0117) read INSIDE it.  The
#   first is held by the next number alone (25 x under), the second by `ssm_prefill_state` (4.7 x) and the next.
#   ssm_state_step: the program 0.00003-0.00009 (the two paths' bf16 projections round a few outputs apart; float32
#   alone reads 1e-7 on the CPU); state-bf16 0.0139, recurrence-bf16 0.0247.  6 x over, 25 x under.
#   ssm_out: the program 0.0042-0.0077 in those 26 runs and in six of PR 54's seven seeds (0.0044-0.0062), and
#   **0.01482 on seed 2540000104, twice** (my chip runs, PR 54: a traced and an untraced run of the cell, the same
#   to the last digit, every other number of the check inside its range): the number is the largest over 256 rows of
#   a row's own relative error, a widest gap, and one row of a seed's draw whose result is small reads twice the rest.
#   Lower reading 0.01482 (33 runs); the norm without its gate ("no-gate") 1.0, the upper one.  The bound stood at
#   0.012 until PR 54, 1.56 x over 26 runs' largest, and read a sound run as not correct; it is 0.05, 3.4 x over the
#   lower reading and 20 x under the upper.  The recurrence in bf16 reads 0.0132 here, under the lower reading, so
#   it is not this number's to catch: `ssm_prefill_state` holds it (0.056, 4.7 x its bound) and `ssm_state_step`
#   (0.0247, 45 x); a state handed on in bf16 reads inside it too and is `ssm_state_step`'s (0.0139, 25 x).
#   attn_decode: the program 0.00166-0.00178 in all 26, a maximum with no tail: it is the bf16 rounding of the
#   core's own result, which cannot pass 2^-9 = 0.00195 of a row's norm.  The scores, softmax and weighted sum in
#   bf16 ("bf16-softmax", at this cell's 2 flat heads) read 0.00216: the same fault reads 0.0058 and more in
#   references/sambay.py's and swa_moe.py's cells, but here a random model's attention is near uniform over a
#   thousand keys and the scores' rounding averages out.  The bound stands over what rounding the result can give
#   and under the control: 1.15 x over the program's largest, 1.05 x under the one control reading.
#   moe_router_other_set: 0 of 46,736 in all 16; the bound and its control are references/mla_moe.py's.
#   moe_experts: the program 0.0049-0.0057; the experts' activation silu ("silu-experts") 0.724; mla_moe.py's bound.
PREFILL_ROWS_ERR_TOL = 0.7
SSM_PREFILL_STATE_ERR_TOL = 0.012
SSM_STATE_ERR_TOL = 0.02
SSM_OUT_ERR_TOL = 0.05
SSM_STATE_STEP_ERR_TOL = 5.5e-4
ATTN_DECODE_ERR_TOL = 0.00205
MOE_ROUTER_SET_TOL = _mla.MOE_ROUTER_SET_TOL
MOE_EXPERTS_ERR_TOL = _mla.MOE_EXPERTS_ERR_TOL


def _stored(a, pad: int, n: int, extent: int):
    """A stream's rows a [rows, KV, D] as a prefill stores them in a stack of
    `extent` slots: behind `pad` left pads, the first n of them (the prompt)."""
    return jnp.pad(a[:n], ((pad, extent - pad - n), (0, 0), (0, 0)))


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
        self.seen = {"mamba2": 0, "attn_alone": 0}
        self.errors = {"h": [], "k": [], "v": []}  # an array's relative error, by the kind of row
        self.windows = []  # (program's window - reference's, reference's) of every Mamba-2 layer: held as ONE array

    def hold(self, kind: str, made) -> None:
        """One layer's part: `made` is `_layer`'s of the whole stream, of which
        the prompt's positions are the first n."""
        cfg, n, rows = self.cfg, self.n, self.rows
        j = self.seen[kind]
        self.seen[kind] += 1
        rel = lambda got, want: jnp.linalg.norm(got.astype(jnp.float32) - want) / jnp.linalg.norm(want)
        if kind == "mamba2":
            xbc, _, h = made  # h after the prompt's last token
            window = jnp.pad(xbc[:n], ((cfg.ssm_d_conv - 1, 0), (0, 0)))[-(cfg.ssm_d_conv - 1):]
            self.errors["h"].append(rel(rows["h"][j, 0], h))
            self.windows.append((rows["conv"][j, 0].astype(jnp.float32) - window, window))
            return
        heads = cfg.n_kv_heads
        extent = rows["k"].shape[2] // heads
        holds = _stored(jnp.ones((n, 1, 1)), self.pad, n, extent)  # a left pad's slot holds what no query sees
        for name, a in zip(("k", "v"), made):
            self.errors[name].append(rel(rows[name][j, 0].reshape(extent, heads, -1) * holds, _stored(a, self.pad, n, extent)))

    def worst(self) -> Dict[str, float]:
        """{kind of row: the largest relative error of an array of it}; the
        windows, K - 1 rows a layer, are one array over the layers (three
        rows alone are one token's router flip upstream away from any number)."""
        off, want = (jnp.stack(a) for a in zip(*self.windows))
        out = {kind: float(jnp.max(jnp.stack(e))) for kind, e in self.errors.items()}
        return {**out, "conv": float(jnp.linalg.norm(off) / jnp.linalg.norm(want))}


def _ssm_program(cb, lay, decode):
    """The compiled program of the checked Mamba-2 layer's mixer as serving runs
    it: (the layer's input rows of every stream [rows, E], the layer's weights: an
    operand, not a value the program closes over, which the compiler would keep
    inside the program, 187 MB of it written to the compile cache anew for every
    seed) -> (f [steps * slots,
    E] of every (step, slot), h [streams, H, P, N] after each stream's prompt, h
    [slots, H, P, N] after the last step, h [streams, H, P, N] of each stream
    through one call), through the program's own `transformer._mamba2_mixer`."""
    from cluster_anywhere_tpu.models import transformer

    cfg = cb.cfg
    zero = lambda b: transformer._mamba2_zero_state(cfg, b)

    @jax.jit
    def program(rows, lp):
        window, h = zero(cb.slots)
        after_prompt = []
        for slot, (off, n, _, pad) in enumerate(lay):
            keep = (jnp.arange(pad + n) >= pad)[None]
            _, (w1, h1) = transformer._mamba2_mixer(lp, jnp.pad(rows[off:off + n], ((pad, 0), (0, 0)))[None], cfg,
                                                    zero(1), keep)
            window, h = window.at[slot].set(w1[0]), h.at[slot].set(h1[0])
            after_prompt.append(h1[0])
        at = lambda row: jnp.pad(rows, ((0, 1), (0, 0)))[row][:, None]  # an empty slot's row: zeros

        def step(state, row):
            f, state = transformer._mamba2_mixer(lp, at(row), cfg, state)
            return state, f[:, 0]

        (_, h), f = lax.scan(step, (window, h), jnp.asarray(decode))
        # and each stream in one call, prompt and decode rows together: h held in float32 throughout
        whole = [transformer._mamba2_mixer(lp, rows[off:off + n + t][None], cfg, zero(1))[1][1][0] for off, n, t, _ in lay]
        return f.reshape(-1, f.shape[-1]), jnp.stack(after_prompt), h, jnp.stack(whole)

    return program


@functools.partial(jax.jit, static_argnames=("dims", "lay", "slots", "eps"))
def _ssm_errors(rows, f, after_prompt, h, whole, lp, *, dims, lay, slots, eps):
    """(the largest relative error of a stream's h after its prompt, of its h
    after its last step, of a decode row's f) against `_mamba2` here over each
    whole stream; and of the stepped h against the program's own one call."""
    with jax.default_matmul_precision("highest"):
        f32 = lambda a: a.astype(jnp.float32)
        rel = lambda got, want: jnp.linalg.norm(f32(got) - want) / jnp.linalg.norm(want)
        worst_p = worst_h = worst_f = step = jnp.zeros((), jnp.float32)
        for slot, (off, n, t, _) in enumerate(lay):
            u = _rms_norm(f32(rows[off:off + n + t]), f32(lp["ln1"]), eps)
            want_f, (_, want_h, want_p) = _mamba2(u, lp, dims, eps, at=n - 1)
            mine = f32(f[np.arange(t) * slots + slot])
            worst_f = jnp.maximum(worst_f, jnp.max(jnp.linalg.norm(mine - want_f[n:], axis=-1)
                                                   / jnp.linalg.norm(want_f[n:], axis=-1)))
            worst_p = jnp.maximum(worst_p, rel(after_prompt[slot], want_p))
            worst_h = jnp.maximum(worst_h, rel(h[slot], want_h))
            step = jnp.maximum(step, rel(h[slot], f32(whole[slot])))
        return worst_p, worst_h, worst_f, step


def ssm_checks(cb, given, lay, decode):
    """(the chunked prefill's state error, the stepped state's, the mixer's
    result's, the stepped state's against one call): four of `mechanism_checks`'
    numbers before they are held to anything."""
    cfg = cb.cfg
    i = _checked_layers(cfg)["ssm"]
    _, stack, j = _layers(cfg)[i]
    lp = _layer_of(cb.params, stack, j)
    rows = jnp.asarray(np.concatenate([g[i] for g in given]))
    out = _ssm_program(cb, lay, decode)(rows, lp)
    dims = _dims(cfg)
    return tuple(float(e) for e in _ssm_errors(rows, *out, lp, dims=dims["ssm"], lay=lay, slots=cb.slots, eps=dims["eps"]))


@functools.partial(jax.jit, static_argnames=("dims", "lay", "dtype", "eps"))
def _qkv_rows(rows, lp, *, dims, lay, dtype, eps):
    """This file's own q, k, v of every row of every stream in float32 from the
    rows as given (a layer's input: normed here), rounded to the program's
    activation type: what both sides of the decode error start from."""
    with jax.default_matmul_precision("highest"):
        parts = [_qkv(_rms_norm(rows[off:off + n + t].astype(jnp.float32), lp["ln1"].astype(jnp.float32), eps), lp, dims)
                 for off, n, t, _ in lay]
    return tuple(jnp.concatenate(p).astype(dtype) for p in zip(*parts))


def _decode_program(cb, lay, decode):
    """The compiled program of one attention layer's decode cores: (q, k, v of
    every row of every stream, as `_qkv_rows` gives them) -> concat(o) [steps *
    slots, H D] of every (step, slot), through the program's own core,
    `generate._kv_decode_core`, one token a row at [slots, 1, .] over a cache of
    one attention layer at the deployment's slots and extent (flat: 2 cached
    heads a slot as rows) that holds each stream's prompt as a prefill stores
    it.  The steps run one after the other and the core writes each step's own
    k and v, as serving does."""
    from cluster_anywhere_tpu.models import generate

    cfg = cb.cfg
    one = dataclasses.replace(cfg, n_layers=1, layer_mixers=("attn_alone",))
    pads = np.zeros(cb.slots, np.int32)
    pos = np.zeros(decode.shape, np.int32)
    for slot, (_, n, _, pad) in enumerate(lay):
        pads[slot], pos[:, slot] = pad, pad + n + np.arange(len(decode))
    n_rows = sum(n + t for _, n, t, _ in lay)

    @jax.jit
    def program(q, k, v):
        cache = generate.init_cache(one, cb.slots, cb.t_max)
        extent = cache["k"].shape[2] // one.flat_heads
        for slot, (off, n, _, pad) in enumerate(lay):
            for name, a in zip(("k", "v"), (k, v)):
                kept = _stored(a[off:off + n], pad, n, extent)
                cache[name] = cache[name].at[0, slot].set(kept.reshape(-1, a.shape[-1]))
        at = lambda a, row: jnp.pad(a, [(0, 1)] + [(0, 0)] * (a.ndim - 1))[row][:, None]  # an empty slot's row: zeros

        def step(cache, now):
            row, p = now
            o, cache = generate._kv_decode_core(cache, 0, p, jnp.asarray(pads), one, at(q, row), at(k, row), at(v, row),
                                                live=row < n_rows, kind="attn_alone")
            return cache, o.reshape(cb.slots, -1)

        _, out = lax.scan(step, cache, (jnp.asarray(decode), jnp.asarray(pos)))
        return out.reshape(-1, out.shape[-1])

    return program


@functools.partial(jax.jit, static_argnames=("lay", "slots"))
def _decode_errors(q, k, v, got, *, lay, slots):
    """The largest relative error of `got` [steps * slots, H D] against this
    file's attention of each stream's decode rows from the same q, k, v, a
    stream attended by itself."""
    f32 = lambda a, off, n, t: a[off:off + n + t].astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        worst = jnp.zeros((), jnp.float32)
        for slot, (off, n, t, _) in enumerate(lay):
            want = _attention(f32(q, off, n, t)[n:], f32(k, off, n, t), f32(v, off, n, t), rows=n + jnp.arange(t))
            mine = got[np.arange(t) * slots + slot].astype(jnp.float32)
            worst = jnp.maximum(worst, jnp.max(jnp.linalg.norm(mine - want, axis=-1) / jnp.linalg.norm(want, axis=-1)))
        return worst


def attention_check(cb, given, lay, decode) -> float:
    cfg = cb.cfg
    i = _checked_layers(cfg)["attn"]
    _, stack, j = _layers(cfg)[i]
    dims = _dims(cfg)
    qkv = _qkv_rows(jnp.asarray(np.concatenate([g[i] for g in given])), _layer_of(cb.params, stack, j),
                    dims=dims["dims"], lay=lay, dtype=jnp.dtype(cfg.dtype).name, eps=dims["eps"])
    return float(_decode_errors(*qkv, _decode_program(cb, lay, decode)(*qkv), lay=lay, slots=cb.slots))


@functools.partial(jax.jit, static_argnames=("moe",))
def _expert_errors(rows, lp, got, chosen, *, moe):
    """One expert layer's two numbers on the device: (the rows whose set of k,
    `chosen` [N, X], is not this reference's; the largest relative error of the
    held experts' part `got` among the others)."""
    first = moe[3]
    held = lp["w_out"].shape[0]
    with jax.default_matmul_precision("highest"):
        want, weight = _routed(rows.astype(jnp.float32), lp, *moe)
    same = jnp.all(chosen == (weight > 0), axis=-1)
    here = jnp.any(weight[:, first:first + held] > 0, axis=-1)
    got = got.astype(jnp.float32)
    err = jnp.linalg.norm(got - want, axis=-1) / jnp.where(here, jnp.linalg.norm(want, axis=-1), 1.0)
    # a row that chose no expert held here comes back as zeros: |got| / 1 is 0
    return jnp.sum(~same), jnp.max(jnp.where(same, err, 0.0))


def expert_checks(cb, ffn_given, lay, decode):
    """(the share of (row, layer) pairs whose set is not the reference's, the
    held experts' largest error, the pairs), over every expert layer."""
    from cluster_anywhere_tpu.parallel.moe import EXPERT_MATRICES

    params, cfg = cb.params, cb.cfg
    n = sum(len(g[0]) for g in ffn_given)
    program = _mla._experts_program(cb, lay, decode, n)
    blocks = params["ffn_blocks"]
    experts = {name: blocks[name] for name in EXPERT_MATRICES if name in blocks}
    numbers = []
    for layer in range(len(ffn_given[0])):
        if numbers:
            jax.block_until_ready(numbers[-1])  # one layer's float32 copies at a time
        rows = jnp.asarray(np.concatenate([g[layer] for g in ffn_given]))
        got, chosen = program(rows, blocks["router"], experts, layer)
        numbers.append(_expert_errors(rows, _layer_of(params, "ffn_blocks", layer), got, chosen, moe=_moe_dims(cfg)))
    other_sets, worst = (np.asarray(x) for x in zip(*numbers))
    pairs = n * len(numbers)
    return int(other_sets.sum()) / pairs, float(worst.max()), pairs


def mechanism_checks(cb, streams):
    """The eight numbers above (references/__init__.py says what the harness
    does with them)."""
    cfg = cb.cfg
    kept = [_given.pop(_stream_ids(s).tobytes(), None) or _given_of(cb, s)[1] for s in streams]
    given, ffn_given = [k[0] for k in kept], [k[1] for k in kept]
    by_kind = {kind: max(k[2][kind] for k in kept) for kind in kept[0][2]}
    prefill_rows = max(by_kind.values())
    lay, decode = _mla.program_shapes(cb, streams)
    after_prompt, state, out, stepped = ssm_checks(cb, given, lay, decode)
    attn = attention_check(cb, given, lay, decode)
    other_sets, experts, pairs = expert_checks(cb, ffn_given, lay, decode)
    rows_checked = sum(t for _, _, t, _ in lay)
    return [
        {"name": "prefill_rows_rel_err", "error": prefill_rows, "tolerance": PREFILL_ROWS_ERR_TOL,
         "why": f"largest relative error of an array of the rows the {len(streams)} streams' prefills install against "
                "the full forward's over the prompt: " + ", ".join(f"{kind} {e:.4f}" for kind, e in by_kind.items())
                + " (a layer's h, keys, values; the layers' convolution windows as one array)"},
        {"name": "ssm_prefill_state_rel_err", "error": after_prompt, "tolerance": SSM_PREFILL_STATE_ERR_TOL,
         "why": f"largest relative error of a stream's state h after its prompt, from the prefill's scan in chunks of "
                f"{cfg.ssm_chunk}, against the recurrence one position a step in float32"},
        {"name": "ssm_state_rel_err", "error": state, "tolerance": SSM_STATE_ERR_TOL,
         "why": "largest relative error of a stream's state h after its last step, handed on as the cache keeps it"},
        {"name": "ssm_out_rel_err", "error": out, "tolerance": SSM_OUT_ERR_TOL,
         "why": f"largest relative error of a decode row's Mamba-2 mixer result, gated norm and all, over {rows_checked} rows"},
        {"name": "ssm_state_step_err", "error": stepped, "tolerance": SSM_STATE_STEP_ERR_TOL,
         "why": "largest relative error of that h against the same mixer's one call over the whole stream, which "
                "holds h in float32 throughout: what handing the state on from token to token costs"},
        {"name": "attn_decode_rel_err", "error": attn, "tolerance": ATTN_DECODE_ERR_TOL,
         "why": f"largest relative error of a decode row's attention over flat stacks of {cfg.n_kv_heads} cached heads, "
                f"against a masked softmax in float32, over {rows_checked} rows"},
        {"name": "moe_router_other_set", "error": other_sets, "tolerance": MOE_ROUTER_SET_TOL,
         "why": f"(row, layer) pairs of {pairs} in which the program's set of {cfg.n_experts_per_tok} of "
                f"{cfg.n_experts} is not the float32 reference's"},
        {"name": "moe_experts_rel_err", "error": experts, "tolerance": MOE_EXPERTS_ERR_TOL,
         "why": "largest relative error of the held experts' part of a row's result, over the rows whose sets "
                "agree (zeros where a row chose none of them)"},
    ]
