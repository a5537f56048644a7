"""A layer's decode attention on the chip this process holds: the kernel
(`ops/attention.py decode_attention`, the stacks and a layer's index) beside
the dense path it replaced on a TPU (`models/generate.py _masked_attention` on
the layer's slice), in each serving configuration's shape at 32 slots x 768,
at 1, 6 and 32 live rows of chat lengths and at 32 rows of the whole cache.
Each time is of one call inside a loop over the layers in one program (what
the decode step's layer loop costs), the largest difference of the two
outputs over the live rows beside it.

    chiprun --timeout 1500 -- python3 scripts/decode_attn_sweep.py

writes chiprun_out/decode_attn_sweep.json and prints it.
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from cluster_anywhere_tpu.models.generate import _masked_attention
from cluster_anywhere_tpu.models.transformer import TransformerConfig
from cluster_anywhere_tpu.ops.attention import decode_attention, decode_rows_read, decode_span

SLOTS, T_MAX, D = 32, 768, 128
# name: (attention layers, cached heads, query heads, query positions a row)
SHAPES = {"mistral": (16, 8, 32, 1), "olmoe": (10, 16, 16, 1), "sdar": (7, 4, 32, 4), "jamba": (2, 1, 20, 1)}


def rows_of(live_rows, full, rng):
    """(first, last, live) [SLOTS]: `live_rows` rows spread over the slots, each
    a chat's (a left pad under 128, 100-350 slots deep) or the whole cache; the
    others keep a stale row's numbers."""
    live = np.zeros(SLOTS, bool)
    live[rng.permutation(SLOTS)[:live_rows]] = True
    first = rng.integers(0, 128, SLOTS)
    last = first + rng.integers(100, 350, SLOTS)
    if full:
        first[:], last[:] = 0, T_MAX
    return first.astype(np.int32), last.astype(np.int32), live


def timed(fn, *args, reps=20):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps, out


def main():
    rng = np.random.default_rng(int(sys.argv[1]) if len(sys.argv) > 1 else 42)
    report = {"device": str(jax.devices()[0]), "slots": SLOTS, "t_max": T_MAX, "cases": []}
    for name, (n, kv, h, tq) in SHAPES.items():
        cfg = TransformerConfig(vocab_size=8, n_layers=1, d_model=h * D, n_heads=h, n_kv_heads=kv, d_head=D, d_ff=8)
        ks = jax.random.split(jax.random.key(n), 3)
        q = jax.random.normal(ks[0], (SLOTS, tq, h, D), jnp.bfloat16)
        k = jax.random.normal(ks[1], (n, SLOTS, T_MAX, kv, D), jnp.bfloat16)
        v = jax.random.normal(ks[2], (n, SLOTS, T_MAX, kv, D), jnp.bfloat16)

        def layers(core):
            # every layer's output is kept (summed), so no call is dead code
            def run(q, k, v, first, last, live):
                span = decode_span(first, last, live, T_MAX, kv)
                body = lambda i, acc: acc + core(q, k, v, i, first, last, span).astype(jnp.float32)
                return lax.fori_loop(0, n, body, jnp.zeros(q.shape, jnp.float32))
            return jax.jit(run)

        kernel = layers(lambda q, k, v, i, first, last, span: decode_attention(q, k, v, i, span))
        dense = layers(lambda q, k, v, i, first, last, span: _masked_attention(
            q, lax.dynamic_index_in_dim(k, i, keepdims=False), lax.dynamic_index_in_dim(v, i, keepdims=False),
            last, cfg, first))
        for live_rows, full in ((1, False), (6, False), (32, False), (32, True)):
            first, last, live = rows_of(live_rows, full, rng)
            t_kernel, got = timed(kernel, q, k, v, first, last, live)
            t_dense, want = timed(dense, q, k, v, first, last, live)
            err = float(jnp.max(jnp.abs(got - want)[live])) / n
            dead = float(jnp.max(jnp.abs(got)[~live])) if not live.all() else 0.0
            report["cases"].append({
                "shape": name, "layers": n, "kv": kv, "heads": h, "tq": tq, "live_rows": live_rows, "full": full,
                "kernel_us_a_layer": 1e6 * t_kernel / n, "dense_us_a_layer": 1e6 * t_dense / n,
                "max_abs_diff_a_layer": err, "dead_rows_max_abs": dead,
                "rows_read_share": float(decode_rows_read(first[live], last[live], T_MAX, kv).sum()) / (SLOTS * T_MAX),
            })
            print(json.dumps(report["cases"][-1]), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/decode_attn_sweep.json", "w") as f:
        json.dump(report, f, indent=1)


if __name__ == "__main__":
    main()
