"""Which of the accepted cells' programs a change moves, without a chip.

    python3 scripts/program_text.py <checkout> [out.json]

prints (and writes) a hash of the StableHLO text, lowered for the TPU platform
with abstract parameters at the published widths, of every accepted serving
configuration's decode step and its first and last prefill bucket, of Mistral's
one-device train step, and of the attention kernels' jaxprs in the shapes the
cells call them in.  A kernel's serialized body embeds its source lines, so it
is masked in a program's text and the kernels are compared by their jaxprs.
Two checkouts whose hashes agree compile the same programs for those cells:
nothing of theirs can move on the chip.  `tests/data/program_text.json` holds
the hashes of the last commit that meant to change them and
`tests/test_program_text.py` holds the tree to it; a PR that means to change a
program writes the file anew (the second argument) and its diff says which.

About 20 s on the CPU: nothing is compiled and no weight is made."""
import hashlib
import importlib
import inspect
import json
import os
import re
import sys

CELLS = ("chat-closed6", "olmoe-closed6", "jamba-closed6", "sdar-closed6", "axk1-rag-closed6",
         "kexaone-longrag-closed6", "phi4flash-reason-closed8", "nemotron3nano-reason-closed8",
         "keye-longdoc-closed4", "kimilinear-reason-closed8")


def hashes(root: str) -> dict:
    sys.path.insert(0, root)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from benchmarks.harness import manifest
    from cluster_anywhere_tpu.llm import continuous
    from cluster_anywhere_tpu.models import generate, transformer

    attention = importlib.import_module("cluster_anywhere_tpu.ops.attention")
    assert os.path.abspath(transformer.__file__).startswith(os.path.abspath(root)), transformer.__file__
    attention._platform = lambda: "tpu"  # the dispatchers take the kernels, as on the chip
    benchmarks = os.path.join(root, "benchmarks")
    shape = jax.ShapeDtypeStruct

    def text(fn, *args, **kw):
        lowered = jax.jit(fn, **kw).trace(*args).lower(lowering_platforms=("tpu",)).as_text()
        return re.sub(r'\\22body\\22: \\22[A-Za-z0-9+/=]+\\22', "BODY", lowered)

    sha = lambda t: hashlib.sha256(t.encode()).hexdigest()[:16]
    out = {}
    for name in CELLS:
        if not os.path.exists(os.path.join(benchmarks, "cells", name + ".json")):
            continue  # a checkout from before the cell
        cell = manifest.load_cell(name, benchmarks)
        ref = manifest.load_reference(cell["config_file"]["reference"], benchmarks)
        try:
            cfg = transformer.TransformerConfig(vocab_size=cell["config_file"]["config"]["vocab_size"],
                                                **ref.program_config(cell["config_file"], param_dtype=jnp.bfloat16))
        except NotImplementedError:  # a checkout from before the configuration: it has no such program
            continue
        dep = cell["traffic_file"]["deployment"]
        slots, t_max = dep["slots"], dep["max_prompt_len"] + dep["max_new_tokens"]
        params = jax.eval_shape(lambda k: transformer.init_params(k, cfg), jax.random.key(0))
        cache = jax.eval_shape(lambda: generate.init_cache(cfg, slots, t_max))
        key = jax.eval_shape(lambda: jax.random.key(0))
        b = cfg.block_length if cfg.generates_blocks else 0
        step = continuous._pass_step_rowpos if b else continuous._decode_step_rowpos
        # a checkout whose pass of blocks is read where it is dispatched hands it the host's rows alone
        behind = "prev" in inspect.signature(step.__wrapped__).parameters
        ints, floats = shape((3 + behind + 2 * b if b else 6, slots), jnp.int32), shape((2, slots), jnp.float32)
        # a pass's state holds the block it has yet to store; a checkout from before that, position and block alone
        for state in ((2 + 3 * b, 1 + 2 * b) if b else (None,)):
            prev = (shape((state, slots) if b else (slots,), jnp.int32),) if behind else ()
            try:
                out[f"{name}.decode"] = sha(text(lambda *a: step.__wrapped__(*a, cfg=cfg), params, cache, ints, floats, *prev,
                                                 key, donate_argnums=(1,)))
                break
            except (TypeError, ValueError) as e:  # the program takes a state of another size
                refused = e
        else:
            raise refused
        buckets = continuous.prefill_buckets_for(dep["max_prompt_len"])
        for bucket in (buckets[0], buckets[-1]):
            ids, pad = shape((1, bucket), jnp.int32), shape((1,), jnp.int32)
            out[f"{name}.prefill{bucket}"] = sha(text(
                lambda p, i, pd: generate.prefill_counted.__wrapped__(p, i, cfg, t_max, pd), params, ids, pad))
    cell = manifest.load_cell("train-fsdp4", benchmarks)
    ref = manifest.load_reference(cell["config_file"]["reference"], benchmarks)
    job = cell["traffic_file"]["job"]
    cfg = transformer.TransformerConfig(
        **ref.program_config(cell["config_file"], vocab_size=cell["config_file"]["config"]["vocab_size"],
                             max_seq_len=job["seq"]), remat=job["remat"])
    train_step, init_state = transformer.make_train_step(cfg, None)
    state = jax.eval_shape(init_state, jax.random.key(0))
    out["train-fsdp4.step_one_device"] = sha(text(train_step, *state, {"ids": shape((1, 1025), jnp.int32)}))

    # the kernels: Mistral's decode shapes, a window layer's ring, the three flash forwards and the gradient
    jaxpr = lambda f, *a: sha(str(jax.make_jaxpr(f)(*a)))
    q, k, span = shape((32, 1, 32, 128), jnp.bfloat16), shape((16, 32, 768, 8, 128), jnp.bfloat16), shape((5, 96), jnp.int32)
    out["kernel.decode"] = jaxpr(lambda q, k, v, s: attention.decode_attention(q, k, v, 3, s), q, k, k, span)
    q, k, span = shape((32, 1, 64, 128), jnp.bfloat16), shape((6, 32, 256, 8, 128), jnp.bfloat16), shape((5, 32), jnp.int32)
    out["kernel.decode_ring"] = jaxpr(lambda q, k, v, s: attention.decode_attention(q, k, v, 2, s, ring=True), q, k, k, span)
    x, pad = shape((1, 512, 32, 128), jnp.bfloat16), shape((1,), jnp.int32)
    out["kernel.flash_pad"] = jaxpr(lambda q, k, v, p: attention.flash_attention(q, k, v, pad=p), x, x, x, pad)
    out["kernel.flash_window"] = jaxpr(lambda q, k, v, p: attention.flash_attention(q, k, v, pad=p, window=128), x, x, x, pad)
    out["kernel.flash_block"] = jaxpr(lambda q, k, v, p: attention.flash_attention(q, k, v, pad=p, block=4), x, x, x, pad)
    out["kernel.flash_grad"] = jaxpr(
        jax.grad(lambda q, k, v: attention.flash_attention(q, k, v).sum().astype(jnp.float32), argnums=(0, 1, 2)), x, x, x)
    try:
        sparse = importlib.import_module("cluster_anywhere_tpu.ops.sparse_attention")
    except ImportError:  # a checkout from before learned sparse attention
        return out
    try:  # the KDA decode update in `kimilinear-reason-closed8`'s shapes: 32 slots of 32 heads over 20 layers' state
        kda = importlib.import_module("cluster_anywhere_tpu.ops.kda")
        vec = shape((32, 32, 128), jnp.float32)
        out["kernel.kda_update"] = jaxpr(lambda q, k, v, g, b, s, r: kda.kda_decode_update(q, k, v, g, b, s, 7, r), vec, vec, vec,
                                         vec, shape((32, 32), jnp.float32), shape((20, 32, 32, 128, 128), jnp.float32),
                                         shape((33,), jnp.int32))
    except ImportError:  # a checkout from before the kernel
        pass
    # its three kernels in `keye-longdoc-closed4`'s shapes: an admit's bucket of 8,192
    t, f32, i32 = 8192, jnp.float32, jnp.int32
    out["kernel.dsa_index"] = jaxpr(sparse.index_scores_kernel, shape((1, t, 16, 64), jnp.bfloat16),
                                    shape((1, t, 64), jnp.bfloat16), shape((1, t, 16), f32))
    out["kernel.dsa_select"] = jaxpr(lambda s, a, b: sparse.select_mask_kernel(s, a, b, 2048), shape((t, t), f32),
                                     shape((t,), i32), shape((t,), i32))
    out["kernel.dsa_flash"] = jaxpr(lambda q, k, v, m, p: sparse.masked_flash_kernel(q, k, v, m, 128 ** -0.5, p),
                                    shape((1, t, 32, 128), jnp.bfloat16), shape((1, t, 4, 128), jnp.bfloat16),
                                    shape((1, t, 4, 128), jnp.bfloat16), shape((1, t, t), jnp.int8), shape((1,), i32))
    return out


if __name__ == "__main__":
    found = hashes(os.path.abspath(sys.argv[1]))
    print(json.dumps(found, indent=1))
    if len(sys.argv) > 2:
        with open(sys.argv[2], "w") as f:
            json.dump(found, f, indent=1)
            f.write("\n")
