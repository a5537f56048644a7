#!/usr/bin/env python3
"""The readings `benchmarks/references/kimi_linear.py` sets its tolerances from, on
the chip at the published widths: the check streams of `kimilinear-reason-closed8`
served together through a `ContinuousBatcher` of the cell's deployment in one
process (no front), then `check_serving` on what was served, as the program is
and with each fault planted BEFORE the streams are served (every one of them
changes what is served; `scripts/sambay_controls.py` has the helpers).

    chiprun --timeout 3400 -- python3 scripts/kimi_controls.py [--only a,b] <seed> ...

    program           nothing planted
    state-bf16        the matrix state S kept in bfloat16 between two tokens (the cache's array and what a
                      step hands on): the nearest precision below the configuration's float32 state
    decay-dropped     alpha = 1: the delta rule without its decay (g = 0 in a prefill's chunks and in a step)
    decay-scalar      the decay made one scalar a head: every channel of a head forgets at the head's mean g
    beta-ignored      beta = 1: every position overwrites its key's direction whole
    conv-skipped      q, k, v without their short convolution: silu of the projection itself
    rotary-applied    a rotary embedding applied in the latent layers, which take none (`mla_use_nope`)
    padding-kept      a prefill's left padding let into the state, the convolution and the experts

Each fault is planted in the program's own functions, so the mechanism's checks,
which enter those functions, read it too; a report says beside its numbers how
many of the served tokens are not those the program served for the same seed
(`served_differ`).  One control a batcher, one batcher at a time (the parameters
and a cache are 11 GB of the chip), `jax.clear_caches()` between: what was traced
without the fault is not what runs under it.  Writes
`chiprun_out/kimi_controls.json`: {seed: {control: report}}.  `--tiny` rehearses
it on the CPU at a test's widths."""

import copy
import importlib.util
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmarks.harness import manifest  # noqa: E402
from benchmarks.harness.reference import check_serving  # noqa: E402
from cluster_anywhere_tpu.models import generate, transformer  # noqa: E402

_spec = importlib.util.spec_from_file_location("sambay_controls", os.path.join(ROOT, "scripts", "sambay_controls.py"))
common = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(common)  # planted, serve

CELL = "kimilinear-reason-closed8"
TINY = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=4, intermediate_size=160, kv_lora_rank=32,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, moe_intermediate_size=24, vocab_size=512,
            num_experts=4, num_experts_routed=32, experts_held_first=8, num_experts_per_token=4, num_hidden_layers=8,
            kda_gate_rank=16,
            linear_attn_config=dict(full_attn_layers=[4, 8], kda_layers=[1, 2, 3, 5, 6, 7], head_dim=16, num_heads=4,
                                    short_conv_kernel_size=4))


def _rule(change):
    """The delta rule's inputs with `change(g, beta) -> (g, beta)` between what makes them and every form of the rule
    (a prefill's chunks, a step's update in `jax.numpy` and through the kernel)."""
    gates = transformer._kda_gates

    def planted(bp, u, cfg, keep=None):
        g, beta, gate = gates(bp, u, cfg, keep)
        return (*change(g, beta), gate)

    return [(transformer, "_kda_gates", planted)]


def state_bf16():
    init = generate.init_cache

    def init_cache(cfg, batch, t_max):
        cache = init(cfg, batch, t_max)
        return {name: a.astype(jnp.bfloat16) if name == "h" else a for name, a in cache.items()}

    zero = transformer._kda_zero_state

    def zero_state(cfg, batch):
        window, s = zero(cfg, batch)
        return window, s.astype(jnp.bfloat16)

    # the batcher's cache, a prefill's rows (made from the zero state's type) and the mixer's `s.dtype` follow
    import cluster_anywhere_tpu.llm.continuous as continuous
    return [(generate, "init_cache", init_cache), (continuous, "init_cache", init_cache),
            (transformer, "_kda_zero_state", zero_state), (generate, "_kda_zero_state", zero_state)]


def decay_dropped():
    return _rule(lambda g, beta: (jnp.zeros_like(g), beta))


def decay_scalar():
    return _rule(lambda g, beta: (jnp.broadcast_to(jnp.mean(g, axis=-1, keepdims=True), g.shape), beta))


def beta_ignored():
    # a left pad's key is 0 either way, so beta = 1 there writes nothing
    return _rule(lambda g, beta: (g, jnp.ones_like(beta)))


def conv_skipped():
    return [(transformer, "_causal_conv", lambda padded, taps, t: padded[:, padded.shape[1] - t:])]


def rotary_applied():
    return [(transformer.TransformerConfig, "rotates", lambda self, kind: True)]


def padding_kept():
    return [(generate, "_pad_keep", lambda pad, t: None)]


CONTROLS = {"program": None, "state-bf16": state_bf16, "decay-dropped": decay_dropped, "decay-scalar": decay_scalar,
            "beta-ignored": beta_ignored, "conv-skipped": conv_skipped, "rotary-applied": rotary_applied,
            "padding-kept": padding_kept}


def main(argv):
    tiny = "--tiny" in argv
    cell = copy.deepcopy(manifest.load_cell(CELL))
    if tiny:
        cell["config_file"]["config"].update(TINY)
        cell["traffic_file"]["deployment"].update(slots=4, max_prompt_len=160, max_new_tokens=16)
        cell["traffic_file"]["check"].update(stream_prompt_lens=[20, 30, 70, 150], stream_new_tokens=8)
    reference = manifest.reference_of(cell)
    if tiny:
        reference.ROW_BLOCK = 16
        reference._mla.ATTN_BLOCK = 16
    config = cell["config_file"]["config"]
    cfg = transformer.TransformerConfig(vocab_size=config["vocab_size"],
                                        **reference.program_config(cell["config_file"], param_dtype=jnp.bfloat16))
    controls = dict(CONTROLS)
    if "--only" in argv:
        only = argv[argv.index("--only") + 1].split(",")
        controls = {name: c for name, c in controls.items() if name in only}
    tag = "." + "+".join(controls) if "--only" in argv else ""
    path = os.path.join(ROOT, "chiprun_out", f"kimi_controls{tag}.json")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    out = {}
    for seed in [int(a) for a in argv if a.isdigit()]:
        params = transformer.init_params(jax.random.key(seed % (2 ** 31)), cfg)
        out[seed] = {}
        for name, plant in controls.items():
            t0 = time.time()
            with common.planted(plant):
                jax.clear_caches()  # what was traced without the fault is not what runs under it
                cb, streams = common.serve(cell, cfg, params, seed, tiny)
                reference._given.clear()
                out[seed][name] = check_serving(cb, streams, reference)
            del cb
            jax.clear_caches()
            served = [t for stream in streams for t in stream["served"]]
            as_program = out[seed].setdefault("_served", served)
            out[seed][name].update(seconds=round(time.time() - t0, 1),
                                   served_differ=sum(a != b for a, b in zip(served, as_program)))
            print(seed, name, json.dumps(out[seed][name]), flush=True)
            with open(path, "w") as f:
                json.dump({s: {n: r for n, r in of.items() if n != "_served"} for s, of in out.items()}, f, indent=1)
        del params


if __name__ == "__main__":
    main(sys.argv[1:])
