"""What the compiled train step of `train-fsdp4` keeps and makes again, without a chip.

Compiles the cell's step (Mistral-7B's widths 12 layers deep, fsdp=4, 8 x 4,096
tokens, `remat`) for a described v5e 2x2 under a v5e's `bytes_limit`, once for
each candidate, and prints a JSON line a candidate: what the rule
(`transformer._remat_keeps`) answered, the compiler's own count of the step's
temporaries, the FFN's up products under `rematted_computation` (2 a layer that
keeps no FFN name is the policy's; more is the compiler's own, short of room),
what the compiler made again by itself (`.remat` in a name), and whether a value
of rows x vocabulary in float32 is left.  About a minute a candidate on the CPU.

    python3 scripts/remat_sweep.py [rule | <ffn layers>[/<margin>[/<log2 chunk>]] ...]

`rule` is the rule as it stands; `4/64/26` forces four layers under a margin of
1/64 of the limit with chunks of 2**26 logits (0: the head and loss whole).

    chiprun --chips 4 --timeout 1500 -- python3 scripts/remat_sweep.py --run <seed> rule 4 ...

runs each candidate's step on a host's four chips instead (weights from the
seed, a fresh batch a step, 8 steps after the one that compiles): ms a step, the
losses, what the chip's own compiler counts, into
`chiprun_out/remat_sweep.jsonl` too; about two minutes a candidate."""
import json
import os
import re
import sys
import time

RUN, TINY = "--run" in sys.argv, "--tiny" in sys.argv  # --tiny: a rehearsal of --run on four devices of the CPU
os.environ.setdefault("TPU_LOG_DIR", "disabled")
if TINY:
    sys.argv.remove("--tiny")
    os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=4"
if TINY or not RUN:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.experimental import topologies
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from cluster_anywhere_tpu.models import transformer
from cluster_anywhere_tpu.parallel.mesh import AXES, MeshSpec

V5E_BYTES_LIMIT = 16909336064
CELL = dict(vocab_size=32768, n_layers=12, d_model=4096, n_heads=32, n_kv_heads=8, d_head=128, d_ff=14336,
            max_seq_len=4096, rope_theta=1e6, param_dtype=jnp.float32, remat=True)


def compiled_step(cfg, mesh, batch, seq):
    step, _ = transformer.make_train_step(cfg, mesh)
    sharded = lambda tree: jax.tree_util.tree_map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=NamedSharding(mesh, s)),
        tree, transformer.param_specs(cfg), is_leaf=lambda x: isinstance(x, P))
    params = sharded(jax.eval_shape(lambda k: transformer.init_params(k, cfg), jax.random.key(0)))
    adam = jax.eval_shape(optax.adamw(3e-4, weight_decay=0.01).init, params)
    count = jax.ShapeDtypeStruct((), jnp.int32, sharding=NamedSharding(mesh, P()))
    opt_state = tuple(s._replace(mu=sharded(s.mu), nu=sharded(s.nu), count=count) if hasattr(s, "mu") else s for s in adam)
    ids = jax.ShapeDtypeStruct((batch, seq + 1), jnp.int32, sharding=transformer.make_batch_sharding(cfg, mesh))
    return jax.jit(step, donate_argnums=(0, 1)).lower(params, opt_state, {"ids": ids}).compile()


def run_steps(cfg, mesh, seed, steps=8):
    """The cell's loop (benchmarks/harness/train_driver.py) without the trainer: (seconds to the first
    step's end, [ms a step] after it, [loss a step])."""
    optimizer = optax.adamw(3e-4, weight_decay=0.01)
    step, _ = transformer.make_train_step(cfg, mesh, optimizer=optimizer)
    shardings = jax.tree_util.tree_map(lambda spec: NamedSharding(mesh, spec), transformer.param_specs(cfg),
                                       is_leaf=lambda x: isinstance(x, P))
    params = jax.jit(lambda k: transformer.init_params(k, cfg), out_shardings=shardings)(jax.random.key(seed % 2 ** 31))
    # the step counter where the step leaves it: a second compile for an argument that moved is a minute lost
    opt_state = jax.tree_util.tree_map(lambda x: jax.device_put(x, NamedSharding(mesh, P())) if x.ndim == 0 else x,
                                       optimizer.init(params))
    jstep = jax.jit(step, donate_argnums=(0, 1))
    sharding, rng = transformer.make_batch_sharding(cfg, mesh), np.random.default_rng(seed)
    batch = lambda: {"ids": jax.device_put(rng.integers(0, cfg.vocab_size, (8, cfg.max_seq_len + 1), dtype=np.int32), sharding)}
    t0 = time.monotonic()
    params, opt_state, loss = jstep(params, opt_state, batch())
    losses, took = [float(loss)], []
    first_s = time.monotonic() - t0
    for _ in range(steps):
        ids = batch()
        t1 = time.monotonic()
        params, opt_state, loss = jstep(params, opt_state, ids)
        losses.append(float(loss))
        took.append(round((time.monotonic() - t1) * 1e3, 2))
    del params, opt_state
    return round(first_s, 1), took, losses


def main(candidates):
    seed = None
    if RUN:
        seed, candidates = int(candidates[1]), candidates[2:]
        mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(MeshSpec(fsdp=4).axis_sizes()), AXES)
        os.makedirs("chiprun_out", exist_ok=True)
    else:
        importlib.import_module("cluster_anywhere_tpu.ops.attention")._platform = lambda: "tpu"
        jax.config.update("jax_enable_compilation_cache", False)
        devices = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices
        mesh = Mesh(np.asarray(devices).reshape(MeshSpec(fsdp=4).axis_sizes()), AXES)
        transformer._memory_limit = lambda mesh: V5E_BYTES_LIMIT
    cfg = transformer.TransformerConfig(**dict(CELL, vocab_size=512, d_model=64, n_heads=4, n_kv_heads=2, d_head=16, d_ff=128,
                                               max_seq_len=64) if TINY else CELL)
    rule, margin, chunk = transformer._remat_keeps, transformer.REMAT_MARGIN, transformer.LOSS_CHUNK
    for candidate in candidates:
        said, forced = {}, None
        transformer.REMAT_MARGIN, transformer.LOSS_CHUNK = margin, chunk
        if candidate != "rule":
            forced, *rest = (int(part) for part in candidate.split("/"))
            if rest:
                transformer.REMAT_MARGIN = rest[0]
            if rest[1:]:
                transformer.LOSS_CHUNK = 2 ** rest[1] if rest[1] else 2 ** 62

        def keeps(*args, **kwargs):
            answer = rule(*args, **kwargs)
            said.update(answer._asdict(), loss_chunk=transformer._loss_chunk(cfg, mesh, (8, cfg.max_seq_len)))
            return answer if forced is None else answer._replace(ffn_layers=forced)

        transformer._remat_keeps = keeps
        t0 = time.monotonic()
        line = {"candidate": candidate}
        if RUN:
            try:
                first_s, took, losses = run_steps(cfg, mesh, seed)
                line.update(rule=said, first_step_s=first_s, step_ms=took, step_ms_p50=float(np.median(took[1:])), losses=losses,
                            limit=transformer._memory_limit(mesh), device=jax.devices()[0].device_kind)
            except Exception as e:  # a step that does not fit is refused by the compiler or as it is given its memory
                line.update(rule=said, refused=str(e)[:400])
            print(json.dumps(line), flush=True)
            with open("chiprun_out/remat_sweep.jsonl", "a") as f:
                f.write(json.dumps(line) + "\n")
            jax.clear_caches()
            continue
        try:
            compiled = compiled_step(cfg, mesh, 8, 4096)
        except Exception as e:  # the compiler refuses a step that does not fit
            print(json.dumps(dict(line, rule=said, refused=str(e)[:300])), flush=True)
            continue
        text, memory = compiled.as_text(), compiled.memory_analysis()
        again = re.findall(r"= bf16\[2,4096,14336\]\S* (?:convolution|fusion)\([^\n]*rematted_computation/ffn/dot_general", text)
        own = re.findall(r"%([\w.\-]+\.remat\d*) = (\w+\[[\d,]*\])", text)
        big = [name for name, shape in own if np.prod([int(n) for n in re.findall(r"\d+", shape.split("[")[1])] or [1]) >= 2 ** 24]
        print(json.dumps(dict(
            line, rule=said, compile_s=round(time.monotonic() - t0, 1),
            temp_bytes=memory.temp_size_in_bytes, argument_bytes=memory.argument_size_in_bytes,
            held_bytes=memory.argument_size_in_bytes + memory.output_size_in_bytes - memory.alias_size_in_bytes
            + memory.temp_size_in_bytes, limit=V5E_BYTES_LIMIT,
            ffn_up_products_again=len(again), compilers_own_remat=len(own), compilers_own_remat_large=len(big),
            flash_fwd=len(re.findall(r"custom-call.*flash_fwd", text)),
            f32_rows_by_vocabulary=len(re.findall(r"f32\[2,4096,32768\]", text)),
            bf16_rows_by_vocabulary=len(re.findall(r"bf16\[(?:\d+,)?2,(?:4096|\d+),32768\]", text)))), flush=True)
        if os.environ.get("REMAT_SWEEP_TEXT"):
            with open(os.environ["REMAT_SWEEP_TEXT"] + "." + candidate.replace("/", "_") + ".hlo", "w") as f:
                f.write(text)


if __name__ == "__main__":
    main(sys.argv[1:] or ["rule"])  # --run <seed> first where the candidates run
