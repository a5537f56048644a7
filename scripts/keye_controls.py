#!/usr/bin/env python3
"""The readings `benchmarks/references/keye_dsa.py` sets its tolerances from, on
the chip at the published widths: the check streams of `keye-longdoc-closed4`
served together through a `ContinuousBatcher` of the cell's deployment in one
process (no front), then `check_serving` on what was served, as the program is
and with each control planted BEFORE the streams are served (every one of them
changes what is served; `scripts/sambay_controls.py` has the helpers).

    chiprun --timeout 3400 -- python3 scripts/keye_controls.py [--only a,b] <seed> ...

    program             nothing planted
    selection-skipped   dense attention served: every query attends to its whole context
    topk-1024           a query attends to its 1,024 best where its configuration says 2,048
    no-relu             the indexer's scores without the relu: sum_j w_j (qI_j . kI)
    index-keys-float8   the indexer's keys rounded to float8 e4m3's 3 bits of mantissa, cached and scored so
    core-probs-float8   the sparse core's probabilities rounded likewise before they meet the values, in an
                        admit's attention under the mask and in a step's over the gathered rows: a fault of the
                        core ALONE (the scores, the selection and the cached keys are the program's), which
                        `dsa_core_rel_err` is there to catch and whose tolerance stands between this and `program`
    float8-weights      every stored matrix rounded likewise, the nearest precision below the configuration's
                        bf16, against the reference over the unrounded parameters

The first and the last are held to the reference by the three numbers on the
logits alone (the mechanism's checks enter the program's functions as they stand
and would read the configuration that was asked for); the others by the whole
check, the mechanism's numbers with the fault in the functions they enter.  A
report says beside its numbers how many of the served tokens are not those the
program served for the same seed (`served_differ`).  One
control a batcher, one batcher at a time: the parameters and a cache are 13 GB
of the chip.  Writes `chiprun_out/keye_controls.json`: {seed: {control: report}}.
`--tiny` rehearses it on the CPU at a test's widths."""

import copy
import dataclasses
import importlib.util
import json
import os
import sys
import time
import types

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmarks.harness import manifest  # noqa: E402
from benchmarks.harness.reference import check_serving  # noqa: E402
from cluster_anywhere_tpu.models import generate, transformer  # noqa: E402
from cluster_anywhere_tpu.ops import sparse_attention as sparse  # noqa: E402

_spec = importlib.util.spec_from_file_location("sambay_controls", os.path.join(ROOT, "scripts", "sambay_controls.py"))
common = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(common)  # mantissa_bits, planted, serve

CELL = "keye-longdoc-closed4"
TINY = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=2, head_dim=16, intermediate_size=160,
            moe_intermediate_size=24, vocab_size=512, num_experts=4, num_local_experts=4, num_experts_routed=32,
            experts_held_first=8, num_experts_per_tok=4, num_hidden_layers=4,
            sa_config=dict(indexer_head_dim=8, indexer_num_heads=2, indexer_num_kv_heads=1, kv_chunk_size=512,
                           q_chunk_size=512, topk=16))


def no_relu():
    def scores(qi, ki, w, keys_last=False):
        s = jnp.einsum("bthd,bds->bths" if keys_last else "bthd,bsd->bths", qi, ki, preferred_element_type=jnp.float32)
        return jnp.sum(w[..., None] * s, axis=2)

    # the admit's kernel holds the relu inside: the plain contraction takes its place, a block of queries at a time
    def by_blocks(qi, ki, w, first=None):
        block = min(256, qi.shape[1])
        parts = [scores(qi[:, lo:lo + block], ki, w[:, lo:lo + block]) for lo in range(0, qi.shape[1], block)]
        return jnp.concatenate(parts, axis=1)

    return [(sparse, "index_scores_reference", scores), (sparse, "index_scores", by_blocks)]


def topk_halved():
    """The program selects half of what its configuration says, in an admit's mask and in a step's list."""
    mask, rows = sparse.select_mask, sparse.select_rows
    return [(sparse, "select_mask", lambda scores, first, last, topk: mask(scores, first, last, topk // 2)),
            (sparse, "select_rows", lambda scores, first, last, topk: rows(scores, first, last, topk // 2))]


def index_keys_float8():
    project = transformer._project_index

    def rounded(*a, **kw):
        qi, ki, w = project(*a, **kw)
        return qi, common.mantissa_bits(ki, 3), w

    return [(transformer, "_project_index", rounded)]


def core_probs_float8():
    def attend(q, k, v, mask, scale, out_dtype=None):
        """`sparse.masked_attention_reference` with its probabilities rounded before the second product."""
        b, t, h, d = q.shape
        kv = k.shape[2]
        s = jnp.einsum("bqgrd,bkgd->bgrqk", q.reshape(b, t, kv, h // kv, d), k, preferred_element_type=jnp.float32) * scale
        seen = (mask != 0)[:, None, None]
        p = jnp.where(seen, jax.nn.softmax(jnp.where(seen, s, sparse.NEG_INF), axis=-1), 0.0)
        out = jnp.einsum("bgrqk,bkgd->bqgrd", common.mantissa_bits(p, 3).astype(v.dtype), v,
                         preferred_element_type=jnp.float32)
        return out.astype(out_dtype or q.dtype).reshape(b, t, h, v.shape[-1])

    def flash(q, k, v, mask, scale, first=None, out_dtype=None):
        b, t = q.shape[:2]
        block = 256
        if t % block:
            return attend(q, k, v, mask, scale, out_dtype)
        blocks = lambda a: jnp.moveaxis(a.reshape(b, t // block, block, *a.shape[2:]), 1, 0)
        out = jax.lax.map(lambda qm: attend(qm[0], k, v, qm[1], scale, out_dtype), (blocks(q), blocks(mask)))
        return jnp.moveaxis(out, 0, 1).reshape(b, t, *out.shape[3:])

    def selected(q, kv_all, layer, at, chosen, cfg):
        kv = cfg.cached_heads
        listed = kv_all[layer, jnp.arange(q.shape[0])[:, None], at]
        mask = (jnp.arange(at.shape[1])[None, :] < chosen[:, None])[:, None, :]
        return attend(q, listed[:, :, :kv], listed[:, :, kv:], mask, cfg.attn_scale)

    return [(sparse, "masked_flash", flash), (generate, "_attend_selected", selected)]


def main(argv):
    tiny = "--tiny" in argv
    cell = copy.deepcopy(manifest.load_cell(CELL))
    if tiny:
        cell["config_file"]["config"].update(TINY)
        cell["traffic_file"]["deployment"].update(slots=4, max_prompt_len=160, max_new_tokens=16)
        cell["traffic_file"]["check"].update(stream_prompt_lens=[20, 30, 70, 150], stream_new_tokens=8)
    reference = manifest.reference_of(cell)
    if tiny:
        reference.ATTN_BLOCK = reference.MECH_ROWS = 16
    config = cell["config_file"]["config"]
    cfg = transformer.TransformerConfig(vocab_size=config["vocab_size"],
                                        **reference.program_config(cell["config_file"], param_dtype=jnp.bfloat16))
    topk = cfg.index_topk
    logits_only = types.SimpleNamespace(
        forward=reference.forward, **{n: getattr(reference, n) for n in ("LOGIT_TOL", "REGRET_MAX_TOL", "REGRET_MEAN_TOL")})
    # control -> (what is planted, the configuration served, whether the mechanism's checks run)
    controls = {
        "program": (None, cfg, True),
        "selection-skipped": (None, dataclasses.replace(cfg, index_topk=10 ** 6), False),
        f"topk-{topk // 2}": (topk_halved, cfg, True),
        "no-relu": (no_relu, cfg, True),
        "index-keys-float8": (index_keys_float8, cfg, True),
        "core-probs-float8": (core_probs_float8, cfg, True),
        "float8-weights": (None, cfg, False),
    }
    if "--only" in argv:
        only = argv[argv.index("--only") + 1].split(",")
        controls = {name: c for name, c in controls.items() if name in only}
    tag = "." + "+".join(controls) if "--only" in argv else ""
    path = os.path.join(ROOT, "chiprun_out", f"keye_controls{tag}.json")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    out = {}
    for seed in [int(a) for a in argv if a.isdigit()]:
        params = transformer.init_params(jax.random.key(seed % (2 ** 31)), cfg)
        out[seed] = {}
        for name, (plant, served_cfg, mechanism) in controls.items():
            t0 = time.time()
            held_to, served_params = reference if mechanism else logits_only, params
            if name == "float8-weights":
                # the unrounded parameters go to the host, the rounded ones take their place on the chip; the
                # program serves from those, the reference reads the host's a layer at a time
                host = jax.device_get(params)
                is_matrix = lambda a: a.ndim >= 3 or a.shape[0] == cfg.vocab_size or a.shape[-1] == cfg.vocab_size
                served_params = jax.jit(lambda p: jax.tree_util.tree_map(
                    lambda a: common.mantissa_bits(a, 3) if is_matrix(a) else a, p), donate_argnums=0)(params)
                params = None
                held_to = types.SimpleNamespace(**vars(logits_only))
                held_to.forward = lambda _params, ids, cfg_: reference.forward(host, ids, cfg_)
            with common.planted(plant):
                jax.clear_caches()  # what was traced without the fault is not what runs under it
                cb, streams = common.serve(cell, served_cfg, served_params, seed, tiny)
                cb.cfg = cfg  # the check reads the configuration that was asked for
                reference._given.clear()
                out[seed][name] = check_serving(cb, streams, held_to)
            del cb
            jax.clear_caches()
            served = [t for stream in streams for t in stream["served"]]
            as_program = out[seed].setdefault("_served", served)
            out[seed][name].update(seconds=round(time.time() - t0, 1),
                                   served_differ=sum(a != b for a, b in zip(served, as_program)))
            print(seed, name, json.dumps(out[seed][name]), flush=True)
            with open(path, "w") as f:
                json.dump({s: {n: r for n, r in of.items() if n != "_served"} for s, of in out.items()}, f, indent=1)
        del params, served_params


if __name__ == "__main__":
    main(sys.argv[1:])
