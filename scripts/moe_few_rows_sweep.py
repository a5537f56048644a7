"""A held share of ungated experts given few rows, on the chip this process
holds: what set `FEW_ROWS` of parallel/moe.py.  `routed_ffn` alone at
`nemotron3nano-reason-closed8`'s widths (an expert 2,688 x 1,856 stored 1,920
wide, 16 held of 128, six a token), inside a scan over LAYERS layers' stacked
experts as the decode step's layer loop has them, the grouped matmul
(FEW_ROWS = 0) beside the loop over the touched experts:

  touched  a decode step's 32 rows, 8 live, the router made so that all 8 send
           `t` of their six assignments to the same `t` held experts (a seeded
           model's routers have such favourites), t = 0 .. 6; and so that the
           8 rows' assignments on the share are spread over 8, 12 and 16.
  rows     32, 64, 128, 256 and 512 rows, all live, a random router: where
           the loop stops paying.

    chiprun --timeout 600 -- python3 scripts/moe_few_rows_sweep.py <seed>

writes chiprun_out/moe_few_rows_sweep.json (microseconds a layer, and the two
paths' largest difference) and prints it as it goes; about 3 min.  `--tiny`
runs the same code at toy widths on any backend (a rehearsal, no timing worth
reading)."""
import functools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from cluster_anywhere_tpu.parallel import moe

TINY = "--tiny" in sys.argv
LAYERS, HELD, ROUTED, K = (2, 4, 32, 6) if TINY else (8, 16, 128, 6)
E, F = (64, 200) if TINY else (2688, 1856)
OUT = {}


def say(key, value):
    OUT[key] = value
    print(key, json.dumps(value), flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "moe_few_rows_sweep.json"), "w") as f:
        json.dump(OUT, f, indent=1)


@functools.lru_cache(maxsize=None)
def layers_program(few_rows: int, n: int):
    """x, router, experts, live -> the sum of LAYERS layers' routed parts: each
    layer's experts read at the scan's index out of the stacks, as the decode
    step's loop reads them."""
    def run(x, router, experts, live):
        def layer(total, i):
            r = moe.routed_ffn(x, router, experts, i, k=K, renormalize=True, live=live, scoring="sigmoid",
                               scale=2.5, held=(0, HELD), act="relu2")
            return total + r.out.astype(jnp.float32), (r.experts_touched, r.assignments)
        return lax.scan(layer, jnp.zeros(x.shape, jnp.float32), jnp.arange(LAYERS))

    moe.FEW_ROWS = few_rows
    try:  # the constant is read as the program is traced
        return jax.jit(run).lower(*jax.tree_util.tree_map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), ARGS(n))).compile()
    finally:
        moe.FEW_ROWS = FEW_ROWS


def timed(program, args, reps=3 if TINY else 30):
    out = jax.block_until_ready(program(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = program(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps / LAYERS * 1e6, out


def both(n, x, router, live):
    experts = EXPERTS
    args = (x, router, experts, live)
    loop_us, (loop, (touched, given)) = timed(layers_program(max(n, 1), n), args)
    grouped_us, (grouped, _) = timed(layers_program(0, n), args)
    return {"loop_us": loop_us, "grouped_us": grouped_us, "touched": float(touched[0]), "assignments": float(given[0]),
            "max_abs_diff": float(jnp.max(jnp.abs(loop - grouped))), "max_abs": float(jnp.max(jnp.abs(grouped)))}


def routed_to(n, live_rows, held_of_row):
    """x [n, E] whose row j is the j-th unit vector (times 8) and a router whose
    row j gives row j's six largest scores to `held_of_row[j]` held experts and
    to experts past the share for the rest."""
    x = jnp.zeros((n, E), jnp.bfloat16).at[jnp.arange(n), jnp.arange(n)].set(8.0)
    router = np.full((E, ROUTED), -1.0, np.float32)
    for j in range(live_rows):
        mine = list(held_of_row[j])
        chosen = mine + [HELD + (j + i) % (ROUTED - HELD) for i in range(K - len(mine))]
        router[j, chosen] = 1.0 + 0.01 * np.arange(K)
    return x, jnp.asarray(router, jnp.bfloat16), jnp.arange(n) < live_rows


def main():
    global EXPERTS, ARGS, FEW_ROWS
    FEW_ROWS = moe.FEW_ROWS
    seed = int(next((a for a in sys.argv[1:] if a.isdigit()), "1"))
    key = jax.random.key(seed)
    stacks = [moe.init_moe_params(jax.random.fold_in(key, i), E, F, ROUTED, jnp.bfloat16, held=HELD) for i in range(LAYERS)]
    EXPERTS = {name: jnp.stack([s[name] for s in stacks]) for name in ("w_in", "w_out")}
    ARGS = lambda n: (jnp.zeros((n, E), jnp.bfloat16), jnp.zeros((E, ROUTED), jnp.bfloat16), EXPERTS, jnp.zeros((n,), bool))
    say("device", {"kind": jax.devices()[0].device_kind, "seed": seed, "layers": LAYERS,
                   "expert_bytes": int(sum(EXPERTS[m][0, 0].size * 2 for m in EXPERTS))})
    for t in range(0, K + 1):  # every live row's first t assignments on the same t held experts
        say(f"touched.same{t}", both(32, *routed_to(32, 8, [range(t)] * 8)))
    for spread in (8, 12, 16):  # the 8 rows' held assignments spread over `spread` experts, one or two a row
        per = [[(j * spread // 8 + i) % HELD for i in range(spread // 8 + (1 if j < spread % 8 else 0))] for j in range(8)]
        say(f"touched.spread{spread}", both(32, *routed_to(32, 8, per)))
    for n in ((8, 16) if TINY else (32, 64, 128, 256, 512)):
        kx, kr = jax.random.split(jax.random.fold_in(key, n))
        x = jax.random.normal(kx, (n, E), jnp.bfloat16)
        say(f"rows.{n}", both(n, x, (jax.random.normal(kr, (E, ROUTED)) * 0.02).astype(jnp.bfloat16), jnp.ones((n,), bool)))


if __name__ == "__main__":
    main()
