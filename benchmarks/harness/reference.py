"""The plain reference of a dense decoder (Mistral-7B's kind): RMSNorm,
grouped-query attention with rotary embeddings, SwiGLU, untied output head.
Straightforward `jax.numpy` in float32 at `highest` matmul precision: no
kernel, no cache, no batching, layer by layer in a Python loop so that only
one layer's weights are upcast at a time.  It shares no code with
`cluster_anywhere_tpu/models/`; it reads the same parameter tree.

Departures from the published model, which the configuration files list:
the RMSNorm epsilon is the program's 1e-6 (published: 1e-5), and the rotary
embedding rotates adjacent pairs (x[2i], x[2i+1]) as the program and
Mistral's own reference code do (Hugging Face's port rotates halves, with the
weights permuted to match; on random weights the two are the same model).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

RMS_EPS = 1e-6
ATTN_BLOCK = 512  # query rows per block: bounds the [heads, block, T] scores


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


@functools.partial(jax.jit, static_argnames=("dims",))
def _layer(x, lp, *, dims):
    """One block over one sequence.  x: [T, E] float32; lp: this layer's
    weights in whatever type they are stored in."""
    h, kv, d, theta = dims
    with jax.default_matmul_precision("highest"):
        lp = jax.tree_util.tree_map(lambda w: w.astype(jnp.float32), lp)
        t = x.shape[0]
        y = _rms_norm(x, lp["ln1"])
        q = _rope((y @ lp["wq"]).reshape(t, h, d), theta)
        k = _rope((y @ lp["wk"]).reshape(t, kv, d), theta)
        v = (y @ lp["wv"]).reshape(t, kv, d)
        k, v = jnp.repeat(k, h // kv, axis=1), jnp.repeat(v, h // kv, axis=1)
        outs = []
        for lo in range(0, t, ATTN_BLOCK):
            hi = min(t, lo + ATTN_BLOCK)
            s = jnp.einsum("qhd,khd->hqk", q[lo:hi], k[:hi]) * d ** -0.5
            causal = jnp.arange(lo, hi)[:, None] >= jnp.arange(hi)[None, :]
            p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
            outs.append(jnp.einsum("hqk,khd->qhd", p, v[:hi]))
        x = x + jnp.concatenate(outs, axis=0).reshape(t, h * d) @ lp["wo"]
        y = _rms_norm(x, lp["ln2"])
        return x + (jax.nn.silu(y @ lp["w_gate"]) * (y @ lp["w_up"])) @ lp["w_down"]


def forward(params: Dict[str, Any], ids, n_heads: int, n_kv_heads: int, d_head: int,
            rope_theta: float):
    """ids: [T] -> logits [T, V], float32."""
    dims = (n_heads, n_kv_heads, d_head, float(rope_theta))
    x = params["embed"][jnp.asarray(ids)].astype(jnp.float32)
    n_layers = params["blocks"]["wq"].shape[0]
    for i in range(n_layers):
        x = _layer(x, jax.tree_util.tree_map(lambda w: w[i], params["blocks"]), dims=dims)
    with jax.default_matmul_precision("highest"):
        x = _rms_norm(x, params["ln_f"].astype(jnp.float32))
        return x @ params["lm_head"].astype(jnp.float32)


def loss(params, ids, **dims) -> float:
    """Mean next-token cross entropy of one sequence ids[:-1] -> ids[1:]."""
    ids = jnp.asarray(ids)
    logits = forward(params, ids[:-1], **dims)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, ids[1:, None], axis=-1)[:, 0]
    return float(jnp.mean(logz - gold))


def dims_of(cfg) -> Dict[str, Any]:
    """The reference's arguments from the program's TransformerConfig."""
    return dict(n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads, d_head=cfg.d_head,
                rope_theta=cfg.rope_theta)


# What `correct` rests on in a serving cell, and which program each part holds:
#
#   prefill (one program a bucket)   the logits that chose each check stream's
#       first token, against the reference's at the prompt's last position:
#       LOGIT_TOL, over streams x vocabulary logits.
#   the batch decode program         every later token of the check streams.
#       They were served through the normal path, several streams at once, so
#       the decode program ran at batch > 1 with unequal row positions and
#       pads.  It hands out tokens, not logits, so the reference is
#       teacher-forced over each stream's served tokens and says how far below
#       its own best logit each served token lies (its regret).  The largest
#       regret and the mean regret each have a bound of their own.
#
# Why 0.25 for the logits: the system computes in bfloat16 (relative step
# 2**-8) through two matmul groups a layer, the reference in float32.  On
# logits of unit scale (the head is scaled by d_model**-0.5 behind an RMSNorm)
# the largest difference measured on the chip at 16 layers was 0.031 to 0.144
# over 33 runs (PERF.md, PR 23).  The bound was first 0.35 (a guess), then 0.1
# (one seed then read 0.099), now 1.7 x the largest reading; a dropped layer or
# a wrong mask gives several times the bound.
LOGIT_TOL = 0.25
# Why these two for the regret: on random weights the best two of 32768 logits
# lie about 0.2 apart, so bfloat16 noise of 0.01-0.02 a logit flips about one
# token in ten to a neighbour the reference ranks 0.01-0.05 lower.  The largest
# regret grows slowly with the noise, the mean as its square: arithmetic with
# twice the noise (an int8 or fp8 cache or attention) quadruples the mean.
# Measured on the chip over 4 streams x 64 tokens, 5 seeds (PERF.md, PR 23):
# 3-9% of the tokens flipped, each 0.010-0.016 under the best on average; the
# largest regret 0.022-0.050, the mean 0.0003-0.0014.  The bounds are 2.4 x
# and 3.5 x the largest reading.
REGRET_MAX_TOL = 0.12
REGRET_MEAN_TOL = 0.005


def check_serving(cb, streams: List[Dict[str, List[int]]]) -> Dict[str, Any]:
    """`streams`: [{"prompt_ids", "served"}, ...], each answered through the
    serving path while the others were.  Reads the batcher's parameters and
    calls its `prefill` as `_admit_full_prefill` does; everything else is the
    reference.  Each stream's prompt + served tokens go through the reference
    once, padded on the right to one length (a causal model's earlier
    positions do not see what follows, and one length is one compilation)."""
    from cluster_anywhere_tpu.models.generate import prefill

    dims = dims_of(cb.cfg)
    fulls = [np.asarray(s["prompt_ids"] + s["served"][:-1], np.int32) for s in streams]
    length = max(len(f) for f in fulls)
    logit_err, regrets, agree = 0.0, [], 0
    for s, full in zip(streams, fulls):
        prompt, served = np.asarray(s["prompt_ids"], np.int32), [int(t) for t in s["served"]]
        n = len(prompt)
        ref = np.asarray(forward(cb.params, np.pad(full, (0, length - len(full))), **dims))
        ref = ref[n - 1: n - 1 + len(served)]  # row i: the logits that choose served[i]
        bucket = cb._bucket(n, len(served))
        padded = np.zeros(bucket, np.int32)
        padded[bucket - n:] = prompt
        logits, _ = prefill(cb.params, jnp.asarray(padded[None]), cb.cfg, cb.t_max,
                            pad=jnp.asarray([bucket - n], np.int32))
        logit_err = max(logit_err, float(np.max(np.abs(np.asarray(logits[0], np.float32) - ref[0]))))
        regret = ref.max(axis=-1) - ref[np.arange(len(served)), served]
        regrets.extend(float(r) for r in regret)
        agree += int(np.sum(regret == 0.0))
    report = {
        "logit_max_abs_err": logit_err, "logit_tolerance": LOGIT_TOL,
        "regret_max": max(regrets), "regret_max_tolerance": REGRET_MAX_TOL,
        "regret_mean": sum(regrets) / len(regrets), "regret_mean_tolerance": REGRET_MEAN_TOL,
        "agree_share": agree / len(regrets), "streams": len(streams), "positions": len(regrets),
    }
    report["ok"] = bool(
        logit_err <= LOGIT_TOL and report["regret_max"] <= REGRET_MAX_TOL
        and report["regret_mean"] <= REGRET_MEAN_TOL
    )
    return report
