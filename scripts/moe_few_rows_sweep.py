"""A held share of experts given few rows, on the chip this process holds: what
set `FEW_ROWS` of parallel/moe.py.  `routed_ffn` alone at
the five held cells' widths (WIDTHS: Nemotron-3-Nano's ungated experts, and the
gated ones of Keye-VL, Kimi-Linear, K-EXAONE and A.X-K1), inside a scan of
STEPS turns over a few layers' stacked experts as the decode step's layer loop
has them, the grouped matmul (`takes_loop` answering no: the compact buffer
behind its conditional) beside the loop over the touched experts:

  touched  a decode step's rows (32, of which 8 or 6 live; Keye's 4), the
           router made so that every live row sends `t` of its assignments to
           the same `t` held experts (a seeded model's routers have such
           favourites), t = 0 .. k; at Nemotron's widths also spread over 8, 12
           and 16.
  rows     32 to 1,024 rows, all live, a random router: where the loop stops
           paying.

    chiprun --timeout 900 -- python3 scripts/moe_few_rows_sweep.py <seed> [width ...]

writes chiprun_out/moe_few_rows_sweep.json (microseconds a layer, and the two
paths' largest difference) and prints it as it goes; about 8 min.  `--tiny`
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
STEPS = 4 if TINY else 16
# name -> an expert's E x F, held of routed, k, gated, activation, a decode step's (rows, live), layers in the stack
WIDTHS = {
    "nemotron": (2688, 1856, 16, 128, 6, False, "relu2", (32, 8), 8),
    "keye": (2048, 768, 16, 128, 8, True, "silu", (4, 4), 8),
    "kimi": (2304, 1024, 16, 256, 8, True, "silu", (32, 8), 8),
    "kexaone": (6144, 2048, 8, 128, 8, True, "silu", (32, 6), 4),
    "axk1": (7168, 2048, 12, 192, 8, True, "silu", (32, 6), 4),
}
if TINY:
    WIDTHS = {name: (64, 200 if name == "nemotron" else 48, 4, 32, k, gated, act, step, 2)
              for name, (_, _, _, _, k, gated, act, step, _) in WIDTHS.items()}
OUT = {}


def say(key, value):
    OUT[key] = value
    print(key, json.dumps(value), flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "moe_few_rows_sweep.json"), "w") as f:
        json.dump(OUT, f, indent=1)


class Width:
    """One cell's experts, stacked over `layers`, and the two programs over them."""

    def __init__(self, name, key):
        self.e, self.f, self.held, self.routed, self.k, gated, self.act, self.step, self.layers = WIDTHS[name]
        made = jax.jit(jax.vmap(lambda k: moe.init_moe_params(k, self.e, self.f, self.routed, jnp.bfloat16, gated=gated,
                                                              held=self.held)))(jax.random.split(key, self.layers))
        self.experts = {m: made[m] for m in moe.EXPERT_MATRICES if m in made}
        self.program = functools.lru_cache(maxsize=None)(self._program)

    def _program(self, loop: bool, n: int):
        """x, router, experts, live -> the sum of STEPS layers' routed parts: each
        layer's experts read at the scan's index out of the stacks, as the decode
        step's loop reads them."""
        def run(x, router, experts, live):
            def layer(total, i):
                r = moe.routed_ffn(x, router, experts, i % self.layers, k=self.k, renormalize=True, live=live,
                                   scoring="sigmoid", scale=2.5, held=(0, self.held), act=self.act)
                return total + r.out.astype(jnp.float32), (r.experts_touched, r.assignments)
            return lax.scan(layer, jnp.zeros(x.shape, jnp.float32), jnp.arange(STEPS))

        args = (jnp.zeros((n, self.e), jnp.bfloat16), jnp.zeros((self.e, self.routed), jnp.bfloat16), self.experts,
                jnp.zeros((n,), bool))
        rule, moe.takes_loop = moe.takes_loop, lambda *_: loop
        try:  # the rule is asked as the program is traced
            return jax.jit(run).lower(*jax.tree_util.tree_map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), args)).compile()
        finally:
            moe.takes_loop = rule

    def both(self, n, x, router, live):
        args = (x, router, self.experts, live)
        loop_us, (loop, (touched, given)) = timed(self.program(True, n), args)
        grouped_us, (grouped, _) = timed(self.program(False, n), args)
        return {"loop_us": loop_us, "grouped_us": grouped_us, "touched": float(touched[0]), "assignments": float(given[0]),
                "rule": moe.takes_loop(n, (0, self.held)),
                "max_abs_diff": float(jnp.max(jnp.abs(loop - grouped))), "max_abs": float(jnp.max(jnp.abs(grouped)))}

    def routed_to(self, n, live_rows, held_of_row):
        """x [n, E] whose row j is the j-th unit vector (times 8) and a router whose
        row j gives row j's k largest scores to `held_of_row[j]` held experts and
        to experts past the share for the rest."""
        x = jnp.zeros((n, self.e), jnp.bfloat16).at[jnp.arange(n), jnp.arange(n)].set(8.0)
        router = np.full((self.e, self.routed), -1.0, np.float32)
        for j in range(live_rows):
            mine = list(held_of_row[j])
            chosen = mine + [self.held + (j + i) % (self.routed - self.held) for i in range(self.k - len(mine))]
            router[j, chosen] = 1.0 + 0.01 * np.arange(self.k)
        return x, jnp.asarray(router, jnp.bfloat16), jnp.arange(n) < live_rows


def timed(program, args, reps=3 if TINY else 20):
    out = jax.block_until_ready(program(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = program(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps / STEPS * 1e6, out


def main():
    words = [a for a in sys.argv[1:] if not a.startswith("--")]
    seed = int(next((a for a in words if a.isdigit()), "1"))
    key = jax.random.key(seed)
    say("device", {"kind": jax.devices()[0].device_kind, "seed": seed, "steps": STEPS})
    for name in [a for a in words if a in WIDTHS] or list(WIDTHS):
        w = Width(name, jax.random.fold_in(key, len(name)))
        say(f"{name}.widths", {"e": w.e, "f": w.f, "held": w.held, "routed": w.routed, "k": w.k, "layers": w.layers,
                               "gated": "w_gate" in w.experts,
                               "expert_bytes": int(sum(w.experts[m][0, 0].size * 2 for m in w.experts))})
        n, live = w.step
        for t in range(0, min(w.k, w.held) + 1):  # every live row's first t assignments on the same t held experts
            say(f"{name}.touched.same{t}", w.both(n, *w.routed_to(n, live, [range(t)] * live)))
        for spread in (8, 12, 16) if name == "nemotron" and not TINY else ():  # the live rows' held assignments over `spread` experts
            per = [[(j * spread // 8 + i) % w.held for i in range(spread // 8 + (1 if j < spread % 8 else 0))] for j in range(8)]
            say(f"{name}.touched.spread{spread}", w.both(n, *w.routed_to(n, live, per)))
        for n in ((8, 16) if TINY else (32, 64, 128, 256, 512, 1024)):
            kx, kr = jax.random.split(jax.random.fold_in(key, n))
            x = jax.random.normal(kx, (n, w.e), jnp.bfloat16)
            say(f"{name}.rows.{n}", w.both(n, x, (jax.random.normal(kr, (w.e, w.routed)) * 0.02).astype(jnp.bfloat16),
                                          jnp.ones((n,), bool)))
        del w
        jax.clear_caches()


if __name__ == "__main__":
    main()
