#!/usr/bin/env python3
"""The readings `benchmarks/references/nemotronh.py` sets its tolerances from,
on the chip at the published widths: the check streams of
`nemotron3nano-reason-closed8` served together through a `ContinuousBatcher` of
the cell's deployment in one process (no front), then `check_serving` on what
was served, first as the program is and then with each control planted once the
streams are served.

    chiprun --timeout 3000 -- python3 scripts/nemotronh_controls.py [--served <control>] [--only a,b] <seed> ...

A control is a fault that one number of the check is there to catch
(`scripts/sambay_controls.py` says how they are planted; its helpers are used
here).  Those that change what is SERVED are a process of their own
(`--served`), held to the reference by the three numbers on the logits:

    float8-weights    every stored matrix rounded to float8 e4m3's 3 bits of
                      mantissa, the nearest precision below the configuration's
                      bf16, against the reference over the unrounded parameters
    state-bf16        the recurrent state h handed from token to token in bf16
    silu-experts      the experts' and the shared expert's activation silu, not relu(x)^2
    rotary-applied    the attention layers turn their queries and keys

Those planted after the streams are served (the logits' numbers are then the
program's; `ok` comes out false by the control's own number):

    state-bf16        as above, in the mixer the check enters
    state-kept        a decode step hands back the state it was given: what `ssm_state_rel_err` holds
    bf16-softmax      the decode core's scores, softmax and weighted sum in bf16 (the kernel's are float32)
    recurrence-bf16   the step size, the decay, h and the read-out all in bf16
    silu-experts      as above, in the expert layer's own check
    rotary-applied    as above, in the prefills whose rows and logits the check reads
    no-gate           the mixer's norm without its gate

Writes `chiprun_out/nemotronh_controls[.<served>].json`: {seed: {control: report}}.
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
from cluster_anywhere_tpu.parallel import moe  # noqa: E402

_spec = importlib.util.spec_from_file_location("sambay_controls", os.path.join(ROOT, "scripts", "sambay_controls.py"))
common = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(common)  # mantissa_bits, bf16, planted, serve

CELL = "nemotron3nano-reason-closed8"
TINY = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=2, head_dim=16, intermediate_size=24,
            hybrid_override_pattern="MEM*EMEM*E", num_hidden_layers=10, mamba_num_heads=4, mamba_head_dim=8, n_groups=2,
            ssm_state_size=16, chunk_size=8, moe_intermediate_size=24, moe_shared_expert_intermediate_size=40,
            n_routed_experts=4, n_routed_experts_routed=16, num_experts_per_tok=3, vocab_size=512)


def state_bf16():
    inner = transformer._mamba2_mixer

    def mixer(bp, x, cfg, state, keep=None):
        out, (window, h) = inner(bp, x, cfg, state, keep)
        return out, (window, common.bf16(h))

    return [(transformer, "_mamba2_mixer", mixer)]


def state_kept():
    inner = transformer._mamba2_mixer

    def mixer(bp, x, cfg, state, keep=None):
        out, (window, h) = inner(bp, x, cfg, state, keep)
        return out, (window, state[1] if x.shape[1] == 1 else h)

    return [(transformer, "_mamba2_mixer", mixer)]


def recurrence_bf16():
    return [(transformer, "SSM_STATE_DTYPE", jnp.bfloat16), (generate, "SSM_STATE_DTYPE", jnp.bfloat16)]


def silu_experts():
    return [(moe, "ACTIVATIONS", {**moe.ACTIVATIONS, "relu2": jax.nn.silu})]


def rotary_applied():
    """The prefills whose rows and logits the check reads turn the attention layers' queries and keys."""
    inner = generate.prefill
    return [(generate, "prefill",
             lambda params, ids, cfg, t_max, pad=None: inner(params, ids, dataclasses.replace(cfg, rotary=True), t_max, pad=pad))]


def no_gate():
    inner = transformer._mamba2_mixer

    def mixer(bp, x, cfg, state, keep=None):
        return inner({**bp, "ssm_in": bp["ssm_in"].at[:, :cfg.d_inner].set(0)}, x, cfg, state, keep)

    return [(transformer, "_mamba2_mixer", mixer)]


SERVED = {"state-bf16": state_bf16, "silu-experts": silu_experts}


def main(argv):
    tiny = "--tiny" in argv
    cell = copy.deepcopy(manifest.load_cell(CELL))
    if tiny:
        cell["config_file"]["config"].update(TINY)
        cell["traffic_file"]["deployment"].update(slots=4, max_prompt_len=160, max_new_tokens=16)
        cell["traffic_file"]["check"].update(stream_prompt_lens=[12, 30, 70, 150], stream_new_tokens=8)
    reference = manifest.reference_of(cell)
    config = cell["config_file"]["config"]
    cfg = transformer.TransformerConfig(vocab_size=config["vocab_size"],
                                        **reference.program_config(cell["config_file"], param_dtype=jnp.bfloat16))
    after = {"program": None, "state-bf16": state_bf16, "state-kept": state_kept, "bf16-softmax": common.bf16_softmax,
             "recurrence-bf16": recurrence_bf16, "silu-experts": silu_experts, "rotary-applied": rotary_applied,
             "no-gate": no_gate}
    if "--only" in argv:
        only = argv[argv.index("--only") + 1].split(",")
        after = {name: plant for name, plant in after.items() if name in only}
    served = argv[argv.index("--served") + 1] if "--served" in argv else None
    seeds = [int(a) for a in argv if a.isdigit()]
    path = os.path.join(ROOT, "chiprun_out", f"nemotronh_controls{'.' + served if served else ''}.json")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    out = {}
    logits_only = types.SimpleNamespace(
        forward=reference.forward, **{n: getattr(reference, n) for n in ("LOGIT_TOL", "REGRET_MAX_TOL", "REGRET_MEAN_TOL")})
    params = cb = None
    for seed in seeds:
        t0 = time.time()
        del params, cb  # the parameters are two thirds of the chip: the last seed's go before this seed's are made
        params = transformer.init_params(jax.random.key(seed % (2 ** 31)), cfg)
        out[seed] = {}
        if served is None:
            cb, streams = common.serve(cell, cfg, params, seed, tiny)
            for name, plant in after.items():
                with common.planted(plant):
                    reference._given.clear()
                    out[seed][name] = check_serving(cb, streams, reference)
                print(seed, name, json.dumps(out[seed][name]), flush=True)
        elif served in SERVED:
            # served under the fault, held to the reference by the three numbers on the logits
            with common.planted(SERVED[served]):
                cb, streams = common.serve(cell, cfg, params, seed, tiny)
            out[seed][served] = check_serving(cb, streams, logits_only)
            print(seed, served, json.dumps(out[seed][served]), flush=True)
        elif served == "rotary-applied":
            # a batcher whose attention layers turn their queries and keys; the reference applies none whatever it is told
            cb, streams = common.serve(cell, dataclasses.replace(cfg, rotary=True), params, seed, tiny)
            out[seed][served] = check_serving(cb, streams, logits_only)
            print(seed, served, json.dumps(out[seed][served]), flush=True)
        elif served == "float8-weights":
            # the unrounded parameters go to the host, the rounded ones take their place on the chip;
            # the program serves and prefills from those, the reference reads the host's a layer at a time
            host = jax.device_get(params)
            is_matrix = lambda a: a.ndim >= 3 or a.shape[0] == cfg.vocab_size or a.shape[-1] == cfg.vocab_size
            rounded = jax.jit(lambda p: jax.tree_util.tree_map(lambda a: common.mantissa_bits(a, 3) if is_matrix(a) else a, p),
                              donate_argnums=0)(params)
            params = None
            cb, streams = common.serve(cell, cfg, rounded, seed, tiny)
            logits_only.forward = lambda _params, ids, cfg_: reference.forward(host, ids, cfg_)
            out[seed][served] = check_serving(cb, streams, logits_only)
            print(seed, served, json.dumps(out[seed][served]), flush=True)
        else:
            raise SystemExit(f"--served float8-weights, rotary-applied or one of {sorted(SERVED)}, not {served!r}")
        print(seed, "seconds", round(time.time() - t0, 1), flush=True)
        with open(path, "w") as f:
            json.dump(out, f, indent=1)


if __name__ == "__main__":
    main(sys.argv[1:])
