#!/usr/bin/env python3
"""Learned sparse attention's pieces alone on a TPU, at `keye-longdoc-closed4`'s
widths (32 query on 4 cached heads x 128, an indexer of 16 x 64, topk 2,048):

    chiprun --timeout 900 -- python3 scripts/dsa_sweep.py <seed> [--tiny]

  decode   a step's core of one layer inside a scan over LAYERS layers' stacks
           (4 slots x 8,704, contexts 3k-8.7k): the indexer's scan of the cached
           keys, `lax.top_k`, the gather and attention over the list
           (`generate._attend_selected`: keys and values in one stack and one
           gather); beside them the dense read
           of the whole context through `decode_attention`, the selection as the
           kernel `dsa_select` finds it (a mask, not a list), and the gather alone:
           as it is, over a list in ascending order, told that its rows are distinct
           and inside the stack, and from the layer taken out of the stack first.
  prefill  an admit's three kernels of one layer at a bucket of 8,192 and 4,096
           (`dsa_index`, `dsa_select`, `dsa_flash`) beside the dense causal flash
           kernel over the same q, k, v.

Each number is the least of REPS calls in microseconds a layer; the results go
to chiprun_out/dsa_sweep.json.  `--tiny` rehearses the script on the CPU."""

import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from cluster_anywhere_tpu.models import generate, transformer  # noqa: E402
from cluster_anywhere_tpu.ops import attention as _attn_fn  # noqa: E402,F401
from cluster_anywhere_tpu.ops import sparse_attention as sparse  # noqa: E402
from cluster_anywhere_tpu.ops.attention import decode_attention, decode_span, flash_attention  # noqa: E402

REPS = 10


def least_us(fn, *args, per: int = 1) -> float:
    jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(REPS):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return 1e6 * best / per


def main() -> None:
    tiny = "--tiny" in sys.argv
    seed = int(next((a for a in sys.argv[1:] if a.isdigit()), "0"))
    layers, slots, t_max, topk = (2, 2, 256, 64) if tiny else (8, 4, 8704, 2048)
    cfg = transformer.TransformerConfig(
        vocab_size=512, d_model=256 if tiny else 2048, n_layers=layers, n_heads=32, n_kv_heads=4, d_head=128,
        qk_norm=True, qk_norm_per_head=True, index_topk=topk, index_n_heads=16, index_head_dim=64,
        dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    keys = (jax.random.fold_in(jax.random.key(seed), i) for i in range(10 ** 6))
    bf = lambda shape: jax.random.normal(next(keys), shape, jnp.bfloat16)
    cache = {"k": bf((layers, slots, t_max, 4, 128)), "v": bf((layers, slots, t_max, 4, 128)),
             "ki": bf((layers, slots, 64, t_max)), "kv": bf((layers, slots, t_max, 8, 128))}
    q, qi = bf((slots, 1, 32, 128)), bf((slots, 1, 16, 64))
    w = jax.random.normal(next(keys), (slots, 1, 16), jnp.float32) * 0.03
    pos = jnp.asarray(np.linspace(t_max * 0.4, t_max - 2, slots), jnp.int32)
    pads = jnp.asarray(np.linspace(0, t_max * 0.05, slots), jnp.int32)
    out = {"seed": seed, "device": str(jax.devices()[0].device_kind), "layers": layers, "slots": slots, "t_max": t_max,
           "contexts": [int(a) for a in np.asarray(pos + 1 - pads)]}

    def over_layers(body):
        """body(cache, layer) -> something [slots, ...] summed over the layers in a scan, as a step's loop runs it."""
        @jax.jit
        def run(cache):
            def step(acc, layer):
                return acc + jnp.sum(body(cache, layer).astype(jnp.float32)), None
            return lax.scan(step, jnp.zeros((), jnp.float32), jnp.arange(layers))[0]
        return run

    def scan_scores(cache, layer):
        ki = lax.dynamic_index_in_dim(cache["ki"], layer, keepdims=False)
        return sparse.index_scores_reference(qi, ki, w, keys_last=True)[:, 0]

    def gather_hinted(stack, layer, at):
        """The same rows told to be distinct and inside the stack."""
        return stack.at[layer, jnp.arange(slots)[:, None], at].get(unique_indices=True, mode="promise_in_bounds")

    def gather_sliced(stack, layer, at):
        """The layer taken out of the stack first, then its rows along the positions."""
        return jnp.take_along_axis(lax.dynamic_index_in_dim(stack, layer, keepdims=False), at[:, :, None, None], axis=1)

    on_tpu = jax.default_backend() == "tpu"
    scores = jax.jit(lambda c: scan_scores(c, 0))(cache)
    at, chosen = jax.jit(lambda s: sparse.select_rows(s, pads, pos + 1, topk))(scores)
    span = decode_span(pads, pos + 1, None, t_max, 4)
    rows8 = lambda a: jnp.pad(a, ((0, -a.shape[0] % 8),) + ((0, 0),) * (a.ndim - 1))
    at_sorted = jnp.sort(jnp.where(jnp.arange(at.shape[1])[None] < chosen[:, None], at, t_max - 1), axis=1)
    decode = {
        "scan": over_layers(scan_scores),
        "top_k": over_layers(lambda c, l: sparse.select_rows(scan_scores(c, l), pads, pos + 1, topk)[0]),
        "gather_attend": over_layers(lambda c, l: generate._attend_selected(q, c["kv"], l, at, chosen, cfg)),
        "gather_attend_ascending": over_layers(lambda c, l: generate._attend_selected(q, c["kv"], l, at_sorted, chosen, cfg)),
        "gather_alone": over_layers(lambda c, l: c["k"][l, jnp.arange(slots)[:, None], at]),
        "gather_alone_hinted": over_layers(lambda c, l: gather_hinted(c["k"], l, at)),
        # keys and values in ONE stack of 8 heads a slot, 2 KB a row: one gather where the core makes two
        "gather_both_in_one": over_layers(lambda c, l: c["kv"][l, jnp.arange(slots)[:, None], at]),
        "gather_alone_sliced": over_layers(lambda c, l: gather_sliced(c["k"], l, at)),
        "select_kernel": over_layers(lambda c, l: sparse.select_mask_kernel(
            rows8(scan_scores(c, l)), rows8(pads), rows8(pos + 1), topk, interpret=not on_tpu)),
        "dense_masked": over_layers(lambda c, l: generate._masked_attention(
            q, *(lax.dynamic_index_in_dim(c[n], l, keepdims=False) for n in ("k", "v")), pos + 1, cfg, pads)),
    }
    if on_tpu:
        decode["dense_kernel"] = over_layers(lambda c, l: decode_attention(q, c["k"], c["v"], l, span))
    out["decode_us_a_layer"] = {}
    for name, fn in decode.items():
        out["decode_us_a_layer"][name] = least_us(fn, cache, per=layers)
        print(name, out["decode_us_a_layer"][name], flush=True)
    out["decode_us_a_layer"]["top_k_alone"] = out["decode_us_a_layer"]["top_k"] - out["decode_us_a_layer"]["scan"]

    out["prefill_us_a_layer"] = {}
    for t in ((256,) if tiny else (8192, 4096)):
        tk = min(topk, t // 4)
        pq, pk, pv = bf((1, t, 32, 128)), bf((1, t, 4, 128)), bf((1, t, 4, 128))
        pqi, pki = bf((1, t, 16, 64)), bf((1, t, 64))
        pw = jax.random.normal(next(keys), (1, t, 16), jnp.float32) * 0.03
        pad = jnp.asarray([t // 10], jnp.int32)
        first, last = sparse.causal_spans(pad, t)
        if on_tpu:
            index = jax.jit(sparse.index_scores_kernel)
            select = jax.jit(lambda s: sparse.select_mask_kernel(s[0], first[0], last[0], tk)[None])
            flash = jax.jit(lambda m, q, k, v: sparse.masked_flash_kernel(q, k, v, m, 128 ** -0.5, pad))
            dense = jax.jit(lambda q, k, v: flash_attention(q, jnp.repeat(k, 8, 2), jnp.repeat(v, 8, 2), causal=True, pad=pad))
        else:
            index = jax.jit(sparse.index_scores_reference)
            select = jax.jit(lambda s: sparse.select_mask_reference(s, first, last, tk).astype(jnp.int8))
            flash = jax.jit(lambda m, q, k, v: sparse.masked_attention_reference(q, k, v, m, 128 ** -0.5))
            dense = jax.jit(lambda q, k, v: _attn_fn(q, jnp.repeat(k, 8, 2), jnp.repeat(v, 8, 2), causal=True, pad=pad))
        s = index(pqi, pki, pw)
        m = select(s)
        got = {"dsa_index": least_us(index, pqi, pki, pw), "dsa_select": least_us(select, s),
               "dsa_flash": least_us(flash, m, pq, pk, pv), "dense_flash": least_us(dense, pq, pk, pv)}
        # the kernels against the plain functions, on the rows that are no pad's
        if on_tpu and t <= 4096:
            want_s = sparse.index_scores_reference(pqi, pki, pw)
            causal = jnp.tril(jnp.ones((t, t), bool))[None]
            got["index_err"] = float(jnp.max(jnp.abs(jnp.where(causal, s - want_s, 0.0))))
            want_m = sparse.select_mask_reference(s, first, last, tk)
            got["select_differs"] = int(jnp.sum(want_m != (m != 0)))
            want_o = sparse.masked_attention_reference(pq, pk, pv, m, 128 ** -0.5)
            real = (jnp.arange(t) >= pad[0])[None, :, None, None]
            got["flash_err"] = float(jnp.max(jnp.abs(jnp.where(real, flash(m, pq, pk, pv).astype(jnp.float32) - want_o.astype(jnp.float32), 0.0))))
        out["prefill_us_a_layer"][str(t)] = got
        print(t, got, flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/dsa_sweep.json", "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
