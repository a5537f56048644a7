"""A held share's expert layer on the chip this process holds: what set
`COMPACT_SHARE` and `COMPACT_LOOKUP_BYTES` of parallel/moe.py, and which
combine the compact path keeps.  Three parts, each for the two configurations that
hold a share of their experts (`kexaone-longrag-closed6`, `axk1-rag-closed6`):

  layer    `routed_ffn` alone at the cell's widths, a decode step's 32 rows (6
           and all of them live), the smallest prefill bucket and the two
           largest: all N x k rows (the path before PR 45) against the compact
           buffer at 4 times the even share; in the two largest at 2, 3 and 8
           times too, with the combine as one scatter-add (this file's
           `combine_scatter`) in place of the module's k gathers, and with the
           gathers' rows looked up whole and not by column blocks; each with
           its largest difference from the full path.
  held     the cell's own model from each seed as the benchmark makes it
           (`init_params(key(seed))`, prompts of the mix's `make_plan`): every
           expert layer's assignments on the held experts over the even share
           N x k x held / routed of the prompt's bucket, so the share of the
           (prompt, layer) pairs that a buffer of 1, 2, 3, 4, 6, 8 times the
           even share holds.
  prefill  an admit's whole prefill program in the two largest buckets, full
           against compact.

    chiprun --timeout 3000 -- python3 scripts/moe_compact_sweep.py layer,held,prefill 4500001,4500002

writes chiprun_out/moe_compact_sweep.json and prints it as it goes.  `--tiny`
runs the same code at toy widths on any backend (a rehearsal, no timing worth reading).
"""
import functools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(ROOT, ".jax_cache"))

import jax
import jax.numpy as jnp
import numpy as np

jax.config.update("jax_compilation_cache_dir", os.environ["JAX_COMPILATION_CACHE_DIR"])

from cluster_anywhere_tpu.models import generate
from cluster_anywhere_tpu.models.transformer import TransformerConfig, init_params
from cluster_anywhere_tpu.parallel import moe

TINY = "--tiny" in sys.argv
CELLS = ("kexaone-longrag-closed6", "axk1-rag-closed6")
OUT = {}


def say(key, value):
    OUT[key] = value
    print(key, json.dumps(value), flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "moe_compact_sweep.json"), "w") as f:
        json.dump(OUT, f, indent=1)


def timed(fn, *args, reps=5, rounds=3):
    """Best of `rounds` means over `reps` calls, ms."""
    jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(rounds):
        t0 = time.perf_counter()
        for _ in range(reps):
            out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / reps)
    return 1e3 * best


def combine_scatter(out, gate, back, in_groups, *, order, k):
    """The compact combine as one scatter-add: every computed row weighted in
    float32 and added to its token's row."""
    n = gate.shape[0]
    c = out.shape[0]
    with jax.named_scope("moe.combine"):
        head = order[:c]
        weight = jnp.where(jnp.arange(c) < in_groups, gate.reshape(n * k)[head], 0.0)
        rows = jnp.where((jnp.arange(c) < in_groups)[:, None], out.astype(jnp.float32), 0.0) * weight[:, None]
        return jnp.zeros((n, out.shape[-1]), jnp.float32).at[head // k].add(rows).astype(out.dtype)


def cell_config(name):
    from benchmarks.harness import manifest

    cell = manifest.load_cell(name)
    if TINY:
        sys.path.insert(0, os.path.join(ROOT, "tests", "benchmark"))
        mod = __import__("test_benchmark_swa_moe" if "kexaone" in name else "test_benchmark_mla_moe")
        cell = mod.tiny_config()
        cell["traffic_file"]["deployment"].update(slots=8, max_prompt_len=1024, max_new_tokens=32)
        cell["traffic_file"]["prompt_len"].update(median=600, min=512, max=1024)
    ref = manifest.reference_of(cell)
    cf, dep = cell["config_file"], cell["traffic_file"]["deployment"]
    cfg = TransformerConfig(vocab_size=cf["config"]["vocab_size"], **ref.program_config(cf, param_dtype=jnp.bfloat16))
    return cell, cfg, dep["max_prompt_len"] + dep["max_new_tokens"]


def buckets_of(cell):
    from cluster_anywhere_tpu.llm.continuous import prefill_buckets_for

    return [b for b in prefill_buckets_for(cell["traffic_file"]["deployment"]["max_prompt_len"]) if b >= 512]


FULL = 10 ** 6  # a COMPACT_SHARE under which no buffer is under half the rows: all N x k rows, as before PR 45


def layer_part(name):
    """`routed_ffn` alone, random weights and rows at the cell's widths."""
    cell, cfg, _ = cell_config(name)
    e, f, x_routed, k = cfg.d_model, cfg.d_expert or cfg.d_ff, cfg.n_experts, cfg.n_experts_per_tok
    first, held = cfg.experts_held
    keys = jax.random.split(jax.random.key(0), 6)
    layers = 3
    experts = {n_: jax.random.normal(kk, (layers, held, *shape), jnp.bfloat16) * shape[0] ** -0.5
               for n_, kk, shape in (("w_gate", keys[0], (e, f)), ("w_up", keys[1], (e, f)), ("w_down", keys[2], (f, e)))}
    router = jax.random.normal(keys[3], (e, x_routed), jnp.bfloat16) * e ** -0.5

    def layer_fn(x, router, experts, live):
        r = moe.routed_ffn(x, router, experts, 1, k=k, renormalize=cfg.moe_renormalize, scoring=cfg.moe_scoring,
                           scale=cfg.moe_routed_scale, held=(first, held), live=live)
        return r.out, r.compact

    kept = moe.COMPACT_SHARE, moe.COMPACT_LOOKUP_BYTES, moe._combine_compact
    moe.FEW_ROWS = 0  # since PR 61 a held share given few rows loops over its experts: here every size takes the grouped paths
    buckets = buckets_of(cell)
    # (rows, live rows, [(tag, COMPACT_SHARE, the combine, COMPACT_LOOKUP_BYTES)]): a decode step's 32 slots with 6
    # and with all of them live, the smallest bucket, the two largest
    some = [("x4", 4, "gather", kept[1])]
    more = [("x2", 2, "gather", kept[1]), ("x3", 3, "gather", kept[1]), ("x8", 8, "gather", kept[1]),
            ("x4_scatter", 4, "scatter", kept[1]), ("x4_one_block", 4, "gather", 2 ** 40)]
    # the whole list where the buffer is looked up by column blocks, the first two of it in the other large buckets
    blocked = lambda b: moe.compact_buffer_rows(b, k, held, x_routed) * e * 2 > kept[1]
    for n, live_rows, cases in [(32, 6, some), (32, 32, some), (buckets[0], None, some)] + [
            (b, None, some + (more if blocked(b) else more[:2])) for b in buckets[-2:]]:
        x = jax.random.normal(keys[4], (n, e), jnp.bfloat16)
        live = jnp.ones((n,), bool) if live_rows is None else jnp.arange(n) < live_rows
        args = (x, router, experts, live)
        moe.COMPACT_SHARE = FULL
        full = jax.jit(lambda *a: layer_fn(*a))  # a function of its own: the trace reads the module's constants
        row = {"full_ms": timed(full, *args)}
        want = np.asarray(full(*args)[0].astype(jnp.float32))
        for tag, share, how, lookup in cases:
            moe.COMPACT_SHARE, moe.COMPACT_LOOKUP_BYTES = share, lookup
            if how == "scatter":  # the scatter needs the sorted order: read it back from the places
                moe._combine_compact = lambda out, gate, back, in_groups: combine_scatter(
                    out, gate, back, in_groups, order=jnp.argsort(back), k=k)
            fn = jax.jit(lambda *a: layer_fn(*a))
            got, took = fn(*args)
            row[f"{tag}_ms"] = timed(fn, *args)
            row[tag] = {"rows": moe.compact_buffer_rows(n, k, held, x_routed), "compact": int(took),
                        "max_abs_diff": float(np.max(np.abs(np.asarray(got.astype(jnp.float32)) - want)))}
            moe.COMPACT_SHARE, moe.COMPACT_LOOKUP_BYTES, moe._combine_compact = kept
        row["rms"] = float(np.sqrt(np.mean(want * want)))
        say(f"layer.{name}.{n}" + (f".live{live_rows}" if live_rows else ""), row)


@functools.partial(jax.jit, static_argnames=("cfg", "t_max"))
def _layer_counts(params, ids, cfg, t_max, pad):
    """`generate.prefill_counted`'s layer loop, handing out every expert
    layer's [touched, assignments, compact] in place of the cache."""
    x = params["embed"].astype(cfg.dtype)[ids]

    def attn(kind, x, bp, experts, _cache, _layer):
        x, _, touched = generate._prefill_block(bp, x, pad, cfg, t_max, experts, kind)
        return x, None, touched

    _, _, outs = generate._scan_blocks(generate._bodies(attn, None), x, params, cfg)
    return jnp.concatenate([t for t in outs.values() if t is not None])


def held_part(name, seeds):
    from benchmarks.harness import loadgen

    cell, cfg, t_max = cell_config(name)
    k, (_, held), vocab = cfg.n_experts_per_tok, cfg.experts_held, cfg.vocab_size
    buckets = buckets_of(cell)
    ratios, by_seed = [], {}
    for seed in seeds:
        params = init_params(jax.random.key(seed % 2 ** 31), cfg)
        plan = loadgen.make_plan(cell, 51.0, seed, vocab)
        mine = []
        for caller in plan:
            for req in caller["requests"][:2 if TINY else 3]:
                prompt = np.asarray(req["prompt_ids"], np.int32)
                bucket = next(b for b in buckets if len(prompt) <= b)
                ids = np.zeros((1, bucket), np.int32)
                ids[0, bucket - len(prompt):] = prompt
                rows = np.asarray(_layer_counts(params, ids, cfg, t_max, np.asarray([bucket - len(prompt)], np.int32)))
                even = bucket * k * held / cfg.n_experts
                mine += [float(a) / even for a in rows[:, 1]]
        by_seed[seed] = {"pairs": len(mine), "max": max(mine), "mean": float(np.mean(mine)),
                         "sorted_top": sorted(mine)[-8:]}
        ratios += mine
        del params
    ratios = np.asarray(ratios)
    say(f"held.{name}", {"pairs": int(ratios.size), "by_seed": by_seed,
                         "quantiles": {q: float(np.quantile(ratios, q)) for q in (0.5, 0.9, 0.95, 0.99, 1.0)},
                         "share_held_by_a_buffer_of": {m: float(np.mean(ratios <= m)) for m in (1, 2, 3, 4, 6, 8)}})


def prefill_part(name, seed):
    cell, cfg, t_max = cell_config(name)
    params = init_params(jax.random.key(seed % 2 ** 31), cfg)
    rng = np.random.default_rng(seed)
    kept = moe.COMPACT_SHARE
    for bucket in buckets_of(cell)[-2:]:
        ids = np.zeros((1, bucket), np.int32)
        pad = bucket // 8
        ids[0, pad:] = rng.integers(0, cfg.vocab_size, bucket - pad)
        row, logits = {}, {}
        for how, share in (("full", FULL), ("compact", kept)):
            moe.COMPACT_SHARE = share
            jax.clear_caches()  # the trace reads the module's constants
            fn = lambda: generate.prefill_counted(params, ids, cfg, t_max, pad=np.asarray([pad], np.int32))
            row[f"{how}_ms"] = timed(fn, reps=3)
            out = fn()
            logits[how] = np.asarray(out[0].astype(jnp.float32))
            row[f"{how}_held_layers"] = np.asarray(out[2]).tolist()
        row["logits_max_abs_diff"] = float(np.max(np.abs(logits["full"] - logits["compact"])))
        say(f"prefill.{name}.{bucket}", row)
    moe.COMPACT_SHARE = kept
    jax.clear_caches()


def main():
    args = [a for a in sys.argv[1:] if not a.startswith("--")]
    parts = args[0].split(",") if args else ["layer", "held", "prefill"]
    seeds = [int(s) for s in args[1].split(",")] if len(args) > 1 else [4500001, 4500002]
    say("device", str(jax.devices()[0]))
    for name in CELLS:
        if "layer" in parts:
            layer_part(name)
        if "held" in parts:
            held_part(name, seeds)
        if "prefill" in parts:
            prefill_part(name, seeds[0])


if __name__ == "__main__":
    main()
