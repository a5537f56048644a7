#!/usr/bin/env python3
"""The readings `benchmarks/references/sambay.py` sets its tolerances from, on
the chip at the published widths: the check streams of `phi4flash-reason-closed8`
served together through a `ContinuousBatcher` of the cell's deployment in one
process (no front), then `check_serving` on what was served, first as the
program is and then with each control planted once the streams are served.

    chiprun --timeout 3000 -- python3 scripts/sambay_controls.py [--served state-bf16 | --served float8-weights] [--only a,b] <seed> ...

A control is a fault that one number of the check is there to catch.  Those
that change what is SERVED are a process of their own (`--served`: two copies
of the parameters do not fit the chip, so the unrounded ones are read from the
host a layer at a time):

    float8-weights    every stored matrix rounded to float8 e4m3's 3 bits of
                      mantissa (the nearest precision below the configuration's
                      bf16), served by the program and held to the reference
                      over the unrounded parameters: the three numbers on the
                      logits have to come out as not correct
    state-bf16        the recurrent state h handed from one token to the next in
                      bf16, served: what the logits see of it, beside what
                      `ssm_state_rel_err` sees of the same fault planted after

Those planted after the streams are served (the logits' numbers are then the
program's; `ok` comes out false by the control's own number):

    rounded-maps      the two maps' results rounded to bf16 before they are subtracted
    bf16-softmax      the decode cores' scores, softmax and weighted sum in bf16
    window-511 / 513  the window layers' decode step under another window
    own-stack         a cross layer on a stack nothing wrote
    state-bf16        as above, in the mixer the check enters
    recurrence-bf16   the step size, the decay, h and the read-out all in bf16
    ring-shifted      the rows a prefill hands the admit with the rings a slot on
    no-d-term         the mixer without its skip term D xc: what the memory's number holds

A rounding is `lax.reduce_precision`: the chip's compiler takes a conversion to
bf16 and back out of a program where it may keep the excess precision.

Writes `chiprun_out/sambay_controls.json`: {seed: {control: report}}.  `--tiny`
rehearses it on the CPU at a test's widths."""

import copy
import dataclasses
import json
import os
import sys
import time
import types

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks.harness import manifest  # noqa: E402
from benchmarks.harness.reference import check_serving  # noqa: E402
from cluster_anywhere_tpu.llm.continuous import ContinuousBatcher, prefill_buckets_for  # noqa: E402
from cluster_anywhere_tpu.models import generate, transformer  # noqa: E402

CELL = "phi4flash-reason-closed8"
TINY = dict(hidden_size=64, num_attention_heads=8, num_key_value_heads=4, head_dim=8, intermediate_size=160,
            vocab_size=512, num_hidden_layers=8, sliding_window=8, mamba_dt_rank=4, mamba_d_state=4, layer_map=None)


def mantissa_bits(x, bits: int):
    """x rounded to `bits` bits of mantissa by arithmetic on its float32 form."""
    u = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    drop = 23 - bits
    u = (u + jnp.uint32(1 << (drop - 1))) & jnp.uint32(~((1 << drop) - 1) & 0xFFFFFFFF)
    return jax.lax.bitcast_convert_type(u, jnp.float32).astype(x.dtype)


def bf16(x):
    """x with bf16's 8 bits of mantissa, in x's own type."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def rounded_maps():
    inner = generate._kv_decode_core

    def core(*a, **kw):
        out, cache = inner(*a, **kw)
        return bf16(out), cache

    return [(generate, "_kv_decode_core", core)]


def bf16_softmax():
    """The decode cores attend through a dense contraction whose scores,
    softmax and weighted sum are bf16 (the kernel's are float32)."""

    def core(cache, layer, pos, pads, cfg, q, k, v, live=None, span=None, kind="attn"):
        state = generate._state_kind(kind, cfg)
        names = generate.LAYER_STATE[state]
        heads, ring = cfg.flat_heads, state == "attn_win"
        extent = cache[names[0]].shape[2] // heads
        if k is not None:
            slot = pos % extent if ring else pos
            at = (layer, jnp.arange(q.shape[0])[:, None], slot[:, None] * heads + jnp.arange(heads))
            cache = {**cache, names[0]: cache[names[0]].at[at].set(k[:, 0]), names[1]: cache[names[1]].at[at].set(v[:, 0])}
        else:
            layer = generate.shared_layer(cfg)
        kl, vl = (cache[n][layer].reshape(q.shape[0], extent, heads, -1) for n in names)
        s = jnp.einsum("bqgrd,bkgd->bgrqk", q.reshape(*q.shape[:2], heads, -1, q.shape[-1]).astype(jnp.bfloat16), kl)
        s = (s * cfg.d_head ** -0.5).astype(jnp.bfloat16)
        first = jnp.maximum(pads, pos + 1 - cfg.attn_window) if ring else pads
        seen = (generate._ring_seen(first, pos + 1, extent) if ring
                else (jnp.arange(extent)[None] >= pads[:, None]) & (jnp.arange(extent)[None] <= pos[:, None]))
        p = jax.nn.softmax(jnp.where(seen[:, None, None, None, :], s, -1e30), axis=-1).astype(jnp.bfloat16)
        out = jnp.einsum("bgrqk,bkgd->bqgrd", p, vl).astype(jnp.bfloat16)
        return out.reshape(q.shape[0], 1, q.shape[2], -1).astype(jnp.float32), cache

    return [(generate, "_kv_decode_core", core)]


def window_of(width):
    def plant():
        inner = generate._kv_decode_core
        return [(generate, "_kv_decode_core",
                 lambda cache, layer, pos, pads, cfg, *a, **kw: inner(
                     cache, layer, pos, pads, dataclasses.replace(cfg, attn_window=width), *a, **kw))]

    return plant


def own_stack():
    inner = generate._kv_decode_core

    def core(cache, layer, pos, pads, cfg, q, k, v, **kw):
        if k is None:
            cache = dict(cache, k=jnp.zeros_like(cache["k"]), v=jnp.zeros_like(cache["v"]))
        return inner(cache, layer, pos, pads, cfg, q, k, v, **kw)

    return [(generate, "_kv_decode_core", core)]


def state_bf16():
    inner = transformer._ssm_mix

    def mix(bp, xs, state, cfg, keep=None):
        y, (window, h) = inner(bp, xs, state, cfg, keep)
        return y, (window, bf16(h))

    return [(transformer, "_ssm_mix", mix), (generate, "_ssm_mix", mix)]


def recurrence_bf16():
    return [(transformer, "SSM_STATE_DTYPE", jnp.bfloat16), (generate, "SSM_STATE_DTYPE", jnp.bfloat16)]


def no_d_term():
    inner = transformer._ssm_mix
    return [(transformer, "_ssm_mix", lambda bp, *a, **kw: inner({**bp, "ssm_d": jnp.zeros_like(bp["ssm_d"])}, *a, **kw))]


def ring_shifted():
    inner = generate.prefill

    def prefill(params, ids, cfg, t_max, pad=None):
        logits, rows = inner(params, ids, cfg, t_max, pad=pad)
        return logits, dict(rows, kw=jnp.roll(rows["kw"], cfg.flat_heads, axis=2))

    return [(generate, "prefill", prefill)]


class planted:
    def __init__(self, plant):
        self.patches = plant() if plant else []

    def __enter__(self):
        self.was = [(m, n, getattr(m, n)) for m, n, _ in self.patches]
        for m, n, fn in self.patches:
            setattr(m, n, fn)

    def __exit__(self, *exc):
        for m, n, fn in self.was:
            setattr(m, n, fn)


def serve(cell, cfg, params, seed, tiny):
    """The check streams through a batcher of the cell's deployment, together."""
    dep, chk = cell["traffic_file"]["deployment"], cell["traffic_file"]["check"]
    cb = ContinuousBatcher(params, cfg, slots=dep["slots"], t_max=dep["max_prompt_len"] + dep["max_new_tokens"],
                           prefill_buckets=prefill_buckets_for(dep["max_prompt_len"]))
    rng = np.random.default_rng(seed + 1)
    reqs = [cb.submit(rng.integers(0, cfg.vocab_size, n), max_new_tokens=chk["stream_new_tokens"])
            for n in chk["stream_prompt_lens"]]
    cb.pump()
    return cb, [{"prompt_ids": r.prompt_ids.tolist(), "served": list(r.out_tokens), "request_id": r.request_id}
                for r in reqs]


def main(argv):
    tiny = "--tiny" in argv
    seeds = [int(a) for a in argv if a.lstrip("-").isdigit()]
    cell = copy.deepcopy(manifest.load_cell(CELL))
    if tiny:
        cell["config_file"]["config"].update(TINY)
        cell["traffic_file"]["deployment"].update(slots=4, max_prompt_len=160, max_new_tokens=16)
        cell["traffic_file"]["check"].update(stream_prompt_lens=[12, 30, 70, 150], stream_new_tokens=8)
    reference = manifest.reference_of(cell)
    config = cell["config_file"]["config"]
    window = config["sliding_window"]
    extra = dict(attn_ring=16) if tiny else {}
    cfg = transformer.TransformerConfig(vocab_size=config["vocab_size"],
                                        **reference.program_config(cell["config_file"], param_dtype=jnp.bfloat16, **extra))
    after = {"program": None, "rounded-maps": rounded_maps, "bf16-softmax": bf16_softmax,
             f"window-{window - 1}": window_of(window - 1), f"window-{window + 1}": window_of(window + 1),
             "own-stack": own_stack, "state-bf16": state_bf16, "recurrence-bf16": recurrence_bf16,
             "ring-shifted": ring_shifted, "no-d-term": no_d_term}
    if "--only" in argv:
        only = argv[argv.index("--only") + 1].split(",")
        after = {name: plant for name, plant in after.items() if name in only}
    served = argv[argv.index("--served") + 1] if "--served" in argv else None
    seeds = [a for a in seeds if str(a) != served]
    path = os.path.join(ROOT, "chiprun_out", f"sambay_controls{'.' + served if served else ''}.json")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    out = {}
    logits_only = types.SimpleNamespace(
        forward=reference.forward, **{n: getattr(reference, n) for n in ("LOGIT_TOL", "REGRET_MAX_TOL", "REGRET_MEAN_TOL")})
    for seed in seeds:
        t0 = time.time()
        params = transformer.init_params(jax.random.key(seed % (2 ** 31)), cfg)
        out[seed] = {}
        if served is None:
            cb, streams = serve(cell, cfg, params, seed, tiny)
            for name, plant in after.items():
                with planted(plant):
                    reference._given.clear()
                    out[seed][name] = check_serving(cb, streams, reference)
                print(seed, name, json.dumps(out[seed][name]), flush=True)
        elif served == "state-bf16":
            # served under the fault, held to the reference by the three numbers on the logits
            with planted(state_bf16):
                cb, streams = serve(cell, cfg, params, seed, tiny)
            out[seed][served] = check_serving(cb, streams, logits_only)
            print(seed, served, json.dumps(out[seed][served]), flush=True)
        elif served == "float8-weights":
            # the unrounded parameters go to the host, the rounded ones take their place on the chip;
            # the program serves and prefills from those, the reference reads the host's a layer at a time
            host = jax.device_get(params)
            is_matrix = lambda a: a.ndim >= 3 or a.shape[0] == cfg.vocab_size
            rounded = jax.jit(lambda p: jax.tree_util.tree_map(lambda a: mantissa_bits(a, 3) if is_matrix(a) else a, p),
                              donate_argnums=0)(params)
            del params
            cb, streams = serve(cell, cfg, rounded, seed, tiny)
            logits_only.forward = lambda _params, ids, cfg_: reference.forward(host, ids, cfg_)
            out[seed][served] = check_serving(cb, streams, logits_only)
            print(seed, served, json.dumps(out[seed][served]), flush=True)
        else:
            raise SystemExit(f"--served state-bf16 or float8-weights, not {served!r}")
        del cb
        print(seed, "seconds", round(time.time() - t0, 1), flush=True)
        with open(path, "w") as f:
            json.dump(out, f, indent=1)


if __name__ == "__main__":
    main(sys.argv[1:])
