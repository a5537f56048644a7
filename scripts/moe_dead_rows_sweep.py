"""What rows that are not live cost a layer of gated experts, on the chip this
process holds: `routed_ffn` alone at `sdar-closed6`'s widths (an expert three
matrices of 2,048 x 768, 128 experts, eight a token, renormalised), inside a
scan over LAYERS layers' stacked experts as the decode step's layer loop has
them.  A pass of blocks hands the layer [slots, B] positions, 32 x 4 = 128 rows
of which the live slots' are given experts; a pass that also stores the block
before hands it [slots, 2B], 256 rows, with the same positions live and a few
more.  The same live rows (the same x, the same router, so the same experts
touched) at 128, 256 and 512 rows say what the rows that are not live cost;
24 to 64 live rows at each size say what a touched expert costs.

    chiprun --timeout 600 -- python3 scripts/moe_dead_rows_sweep.py <seed>

writes chiprun_out/moe_dead_rows_sweep.json (microseconds a layer) and prints
it as it goes; about 2 min.  `--tiny` runs the same code at toy widths on any
backend (a rehearsal, no timing worth reading)."""
import functools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax
import jax.numpy as jnp
from jax import lax

from cluster_anywhere_tpu.parallel import moe

TINY = "--tiny" in sys.argv
LAYERS, ROUTED, K = (2, 16, 2) if TINY else (7, 128, 8)
E, F = (64, 48) if TINY else (2048, 768)
OUT = {}


def say(key, value):
    OUT[key] = value
    print(key, json.dumps(value), flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "moe_dead_rows_sweep.json"), "w") as f:
        json.dump(OUT, f, indent=1)


@functools.lru_cache(maxsize=None)
def layers_program(n: int):
    """x [n, E], routers [LAYERS, E, X], experts, live [n] -> the sum of LAYERS
    layers' routed parts, each layer's experts read at the scan's index out of
    the stacks, and each layer's experts touched."""
    def run(x, routers, experts, live):
        def layer(total, i):
            r = moe.routed_ffn(x, routers[i], experts, i, k=K, renormalize=True, live=live)
            return total + r.out.astype(jnp.float32), r.experts_touched
        return lax.scan(layer, jnp.zeros(x.shape, jnp.float32), jnp.arange(LAYERS))

    return jax.jit(run)


def timed(program, args, reps=3 if TINY else 40):
    out = jax.block_until_ready(program(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = program(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps / LAYERS * 1e6, out


def main():
    seed = int(next((a for a in sys.argv[1:] if a.isdigit()), "1"))
    key = jax.random.key(seed)
    # every layer's experts made where they lie in the stack (8.5 GB at the cell's widths: no second copy fits)
    kg, ku, kd, kr = jax.random.split(key, 4)
    normal = lambda k, shape, scale: (jax.random.normal(k, shape, jnp.bfloat16) * scale).astype(jnp.bfloat16)
    experts = {"w_gate": normal(kg, (LAYERS, ROUTED, E, F), E ** -0.5), "w_up": normal(ku, (LAYERS, ROUTED, E, F), E ** -0.5),
               "w_down": normal(kd, (LAYERS, ROUTED, F, E), F ** -0.5)}
    routers = normal(kr, (LAYERS, E, ROUTED), 0.02)
    say("device", {"kind": jax.devices()[0].device_kind, "seed": seed, "layers": LAYERS,
                   "expert_bytes": int(sum(experts[m][0, 0].size * 2 for m in experts))})
    widest = 64 if TINY else 512
    x = jax.random.normal(jax.random.fold_in(key, 99), (widest, E), jnp.bfloat16)
    sizes = (16, 32, 64) if TINY else (128, 256, 512)
    for live_rows in ((4, 8) if TINY else (24, 30, 32, 48, 64)):
        first = None
        for n in sizes:
            # the live rows lie where a pass has them: a slot's positions together, the slots' halves apart
            live = jnp.arange(n) < live_rows
            us, (out, touched) = timed(layers_program(n), (x[:n], routers, experts, live))
            first = out[:live_rows] if first is None else first
            say(f"live{live_rows}.rows{n}", {
                "us_a_layer": us, "touched_mean": float(jnp.mean(touched.astype(jnp.float32))),
                "max_abs_diff_to_smallest": float(jnp.max(jnp.abs(out[:live_rows] - first)))})


if __name__ == "__main__":
    main()
