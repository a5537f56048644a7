#!/usr/bin/env python3
"""Proof that the system's two main paths start on a TPU.

    python3 chip_smoke.py            one chip: detect, train, serve
    python3 chip_smoke.py --chips 4  four chips: the sharded train step only

Everything runs through the entry points a user calls — `ca.init()`,
`JaxTrainer.fit`, `serve.run` behind the proxy and router — at the width of
the flagship LLaMA-style block (d_model 1024, 8 layers, 8 Q / 4 KV heads x
128, d_ff 4096, bf16), weights random from a seed.  This process never
initialises a JAX backend: only workers the head spawned touch the chip, one
process at a time, and each phase waits for its TPU process to be gone before
the next starts.

Any phase that fails raises and the script exits non-zero.  No TPU found is a
failure, never a CPU run.  The last line of standard output is
`{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}` with
the device as the process that held the chip saw it.
"""

from __future__ import annotations

import argparse
import http.client
import json
import os
import socket
import tempfile
import time
from typing import Any, Dict, List, Optional

FLAGSHIP = dict(d_model=1024, n_layers=8, n_heads=8, n_kv_heads=4, d_head=128, d_ff=4096)
TRAIN = dict(
    widths=dict(FLAGSHIP, vocab_size=32000, max_seq_len=1024),
    batch=8, seq=1024, steps=6, seed=0, learning_rate=3e-4, meshes={"single": {}},
)
MESHES = {"single": {}, "fsdp2_tp2": {"fsdp": 2, "tp": 2}, "dp4": {"dp": 4}}  # --chips 4
SERVE = dict(widths=FLAGSHIP, max_prompt_len=256, max_new_tokens=32, slots=8, new_tokens=8)
FLASH_TOL = 0.05  # bf16 inputs (ops/attention.py flash_numerics_errors)
MESH_LOSS_TOL = 0.05  # bf16 activations: sharded reductions sum in another order


def say(phase: str, **fields) -> None:
    print(f"[{phase}] " + json.dumps(fields, default=str), flush=True)


# --------------------------------------------------------------------------
# worker side: these run in the TPU worker the head spawned
# --------------------------------------------------------------------------


def _device_report() -> Dict[str, Any]:
    import jax

    devs = jax.devices()
    return {
        "platform": devs[0].platform,
        "device_kind": devs[0].device_kind,
        "device_count": len(devs),
        "cache_dir": jax.config.jax_compilation_cache_dir,
        "visible_chips": os.environ.get("TPU_VISIBLE_CHIPS"),
    }


def _steps(jstep, state, batch, n: int):
    """Compile, then n steps on one batch.  Returns (compile_s, has_kernel,
    losses, step_s); each loss is read back, so a step's time includes its
    completion."""
    params, opt_state = state
    t0 = time.perf_counter()
    compiled = jstep.lower(params, opt_state, batch).compile()
    compile_s = time.perf_counter() - t0
    has_kernel = "tpu_custom_call" in compiled.as_text()
    losses, step_s = [], []
    for _ in range(n):
        t0 = time.perf_counter()
        params, opt_state, loss = compiled(params, opt_state, batch)
        losses.append(float(loss))
        step_s.append(time.perf_counter() - t0)
    return compile_s, has_kernel, losses, step_s


def train_loop(config: Dict[str, Any]) -> None:
    """Train steps of the flagship block on one fixed seeded batch, on each
    mesh of config["meshes"] in turn, in this one process (it owns all the
    chips of its request), with what the parameters' shardings say about
    where they live."""
    import jax

    from cluster_anywhere_tpu import train
    from cluster_anywhere_tpu.models import TransformerConfig, make_train_step
    from cluster_anywhere_tpu.models.transformer import make_batch_sharding
    from cluster_anywhere_tpu.ops.attention import flash_numerics_errors
    from cluster_anywhere_tpu.parallel import MeshSpec, make_mesh

    out = _device_report()
    if out["platform"] == "tpu":
        # the compiled kernel against the reference, before it trains anything
        out["flash_numerics"] = flash_numerics_errors()
    cfg = TransformerConfig(**config["widths"])
    ids = jax.random.randint(
        jax.random.key(config["seed"] + 1),
        (config["batch"], config["seq"] + 1), 0, cfg.vocab_size,
    )
    out["meshes"] = {}
    for name, axes in config["meshes"].items():
        spec = MeshSpec(**axes)
        mesh = make_mesh(spec, devices=jax.devices()[: spec.size])
        step, init_state = make_train_step(
            cfg, mesh, learning_rate=config["learning_rate"]
        )
        params, opt_state = init_state(jax.random.key(config["seed"]))
        batch = {"ids": ids}
        if mesh.size > 1:
            batch = jax.device_put(batch, make_batch_sharding(cfg, mesh))
        leaves = jax.tree_util.tree_leaves(params)
        rec = {
            "devices_holding_params": max(len(x.sharding.device_set) for x in leaves),
            "param_bytes": sum(x.nbytes for x in leaves),
            "param_bytes_on_device_0": sum(
                x.addressable_shards[0].data.nbytes for x in leaves
            ),
        }
        jstep = jax.jit(step, donate_argnums=(0, 1))
        rec["compile_s"], rec["kernel_in_program"], rec["losses"], rec["step_s"] = _steps(
            jstep, (params, opt_state), batch, config["steps"]
        )
        out["meshes"][name] = rec
    train.report(out)


# --------------------------------------------------------------------------
# driver side
# --------------------------------------------------------------------------


def _fit(loop, config: Dict[str, Any], chips: int) -> Dict[str, Any]:
    from cluster_anywhere_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as storage:
        result = JaxTrainer(
            loop,
            train_loop_config=config,
            scaling_config=ScalingConfig(
                num_workers=1, use_tpu=True, chips_per_worker=chips
            ),
            run_config=RunConfig(name="chip_smoke", storage_path=storage),
        ).fit()
    return result.metrics


def wait_tpu_workers_gone(timeout_s: float = 60.0) -> None:
    """A chip belongs to one process: the next phase's worker fails at start-up
    while the last one's still holds it."""
    from cluster_anywhere_tpu.core.worker import global_worker

    deadline = time.monotonic() + timeout_s
    while True:
        live = [
            w for w in global_worker().head_call("list_workers")["workers"]
            if w["pool"] != "cpu" and w["state"] != "dead"
        ]
        if not live:
            return
        if time.monotonic() > deadline:
            raise RuntimeError(f"TPU workers still alive after {timeout_s}s: {live}")
        time.sleep(0.2)


def phase_detect(chips: int) -> Dict[str, float]:
    """`ca.init()` finds the chips itself and advertises them."""
    import cluster_anywhere_tpu as ca
    from cluster_anywhere_tpu.core import accelerators
    from cluster_anywhere_tpu.native import build as native

    if "CA_NUM_TPUS" in os.environ:
        raise RuntimeError("unset CA_NUM_TPUS: detection has to find the chips")
    res = ca.init()["resources"]
    marker = accelerators.accelerator_type()
    say("detect", resources=res, accelerator_type=marker)
    if res.get("TPU", 0.0) != float(chips):
        raise RuntimeError(f"need {chips} TPU chip(s), ca.init() found {res.get('TPU', 0)}")
    if not marker or res.get(marker) != float(chips):
        raise RuntimeError(f"no accelerator-type marker resource in {res}")
    lib = native.load()
    say("detect", native_so_loaded=lib is not None, native_so=native._SO)
    if lib is None:
        raise RuntimeError(f"native/ca_native.cpp did not build or load:\n{native.last_error}")
    return res


def check_train(rep: Dict[str, Any]) -> None:
    import math

    losses = rep["losses"]
    if not all(math.isfinite(x) for x in losses):
        raise RuntimeError(f"loss not finite: {losses}")
    if not losses[-1] < losses[0]:
        raise RuntimeError(f"loss did not fall on a fixed batch: {losses}")


def require_tpu(rep: Dict[str, Any], chips: int) -> Dict[str, Any]:
    """The worker's own view of the device, as the last line reports it."""
    device = {
        "platform": rep["platform"], "kind": rep["device_kind"],
        "count": rep["device_count"],
    }
    if device["platform"] != "tpu" or device["count"] != chips:
        raise RuntimeError(f"worker ran on {device}, need {chips} tpu device(s)")
    return device


def phase_train(config: Dict[str, Any]) -> Dict[str, Any]:
    """`JaxTrainer.fit` twice, each in its own head-spawned TPU worker: the
    first compiles cold and trains, the second finds the step in the
    compilation cache."""
    cold = _fit(train_loop, config, chips=1)
    say("train", run="cold", **cold)
    wait_tpu_workers_gone()
    warm = _fit(train_loop, dict(config, steps=2), chips=1)
    say("train", run="warm", **warm)
    wait_tpu_workers_gone()
    (first,), (second,) = cold["meshes"].values(), warm["meshes"].values()
    check_train(first)
    check_train(second)
    say("train", cold_compile_s=first["compile_s"], warm_compile_s=second["compile_s"],
        cache_dir=cold["cache_dir"])
    if second["losses"] != first["losses"][:2]:
        raise RuntimeError(
            f"same seed, same steps, other losses: {first['losses'][:2]} vs {second['losses']}"
        )
    return cold


def require_kernel(rep: Dict[str, Any]) -> None:
    """The step program holds the Pallas kernel, and the kernel is right."""
    for name, rec in rep["meshes"].items():
        if not rec["kernel_in_program"]:
            raise RuntimeError(
                f"{name}: the train step holds no tpu_custom_call: it ran the reference"
            )
    bad = {k: v for k, v in rep["flash_numerics"].items() if not v < FLASH_TOL}
    if bad:
        raise RuntimeError(f"flash kernel disagrees with the reference: {bad}")


def _post(port: int, path: str, body: dict, sse: bool = False):
    """One HTTP request to the proxy.  Returns (status, tokens, ttft_s):
    tokens is the generated text for a JSON answer, the list of token ids for
    an event stream; ttft_s is the time to the first body byte."""
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=300)
    try:
        headers = {"Content-Type": "application/json"}
        if sse:
            headers["Accept"] = "text/event-stream"
        t0 = time.perf_counter()
        conn.request("POST", path, json.dumps(body), headers)
        resp = conn.getresponse()
        first = resp.read(1)
        ttft = time.perf_counter() - t0
        raw = first + resp.read()
    finally:
        conn.close()
    if resp.status != 200:
        return resp.status, raw.decode("utf-8", "replace"), ttft
    if sse:
        events = [
            json.loads(line[5:]) for line in raw.decode().splitlines()
            if line.startswith("data:")
        ]
        if any("error" in e for e in events):
            raise RuntimeError(f"stream carried an error: {events}")
        return resp.status, [e["token_id"] for e in events], ttft
    return resp.status, json.loads(raw), ttft


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _prompt(n_tokens: int, tag: str) -> str:
    """A prompt of exactly n_tokens under the byte tokenizer (1 bos + bytes)."""
    text = (tag + " the quick brown fox jumps over the lazy dog. ") * 8
    return text[: n_tokens - 1]


def phase_serve(config: Dict[str, Any]) -> Dict[str, Any]:
    """A continuous-batching deployment on the chip behind proxy and router:
    prompts in every prefill bucket, one repeated (prefix-cache hit), one
    streamed."""
    from cluster_anywhere_tpu import serve
    from cluster_anywhere_tpu.llm.processor import ModelSpec, ProcessorConfig
    from cluster_anywhere_tpu.llm.serve_llm import build_continuous_llm_deployment
    from cluster_anywhere_tpu.util.metrics import get_metrics_snapshot

    port = _free_port()
    serve.start(host="127.0.0.1", port=port)
    pcfg = ProcessorConfig(
        model=ModelSpec(preset="custom", seed=0, config_overrides=dict(config["widths"])),
        max_prompt_len=config["max_prompt_len"],
        max_new_tokens=config["max_new_tokens"],
    )
    app = build_continuous_llm_deployment(
        pcfg, slots=config["slots"], num_tpus=1, sse_ingress=True
    )
    t0 = time.perf_counter()
    serve.run(app, name="llm", route_prefix="/llm", wait_timeout_s=600)
    say("serve", replica_ready_s=time.perf_counter() - t0)
    n = config["new_tokens"]
    # prompt lengths by the prefill program they reach (llm/continuous.py):
    # 12 -> whole prompt in the 64 bucket; 100 -> a 96-token prefix in the
    # 128 bucket; 200 -> a 192-token prefix in the 256 bucket
    plan = [
        ("bucket64", _prompt(12, "a"), False),
        ("bucket128", _prompt(100, "b"), False),
        ("bucket256", _prompt(200, "c"), False),
        ("bucket256_again", _prompt(200, "c"), False),
        ("bucket128_stream", _prompt(100, "d"), True),
        ("bucket64_tail", _prompt(40, "e"), False),
    ]
    answers: Dict[str, Any] = {}
    for name, prompt, sse in plan:
        status, out, ttft = _post(
            port, "/llm", {"prompt": prompt, "max_new_tokens": n, "temperature": 0.0}, sse
        )
        say("serve", request=name, status=status, ttft_s=ttft, sse=sse,
            answer=out if status != 200 or sse else out["generated_text"])
        if status != 200:
            raise RuntimeError(f"request {name}: HTTP {status}: {out}")
        got = len(out) if sse else out["num_generated_tokens"]
        if got != n:
            raise RuntimeError(f"request {name}: {got} tokens, asked for {n}")
        answers[name] = out if sse else out["generated_text"]
    if answers["bucket256"] != answers["bucket256_again"]:
        raise RuntimeError(
            "the repeated prompt answered differently from the prefix cache: "
            f"{answers['bucket256']!r} vs {answers['bucket256_again']!r}"
        )
    # the replica's own account, through the engine metrics (synced ~1/s)
    deadline = time.monotonic() + 30
    while True:
        snap = get_metrics_snapshot()
        hits = sum(snap.get("ca_serve_prefix_hits_total", {}).get("data", {}).values())
        engine = snap.get("ca_serve_engine_devices", {}).get("data", {})
        if (hits and engine) or time.monotonic() > deadline:
            break
        time.sleep(0.5)
    say("serve", prefix_hits=hits, engine_devices=engine)
    if not hits:
        raise RuntimeError("no prefix-cache hit was counted for the repeated prompt")
    if len(engine) != 1:
        raise RuntimeError(f"expected one engine placement, metrics say {engine}")
    (tags, count), = engine.items()
    rep = dict(json.loads(tags), device_count=int(count))
    serve.shutdown()
    wait_tpu_workers_gone()
    return rep


def phase_mesh(config: Dict[str, Any]) -> Dict[str, Any]:
    """One worker that owns four chips: fsdp x tp and dp against one device."""
    rep = _fit(train_loop, config, chips=4)
    say("mesh", **rep)
    base = rep["meshes"]["single"]
    check_train(base)
    for name, rec in rep["meshes"].items():
        if name == "single":
            continue
        check_train(rec)
        if rec["devices_holding_params"] != 4:
            raise RuntimeError(f"{name}: parameters on {rec['devices_holding_params']} devices")
        worst = max(abs(a - b) for a, b in zip(rec["losses"], base["losses"]))
        say("mesh", mesh=name, max_loss_diff_vs_single=worst)
        if not worst < MESH_LOSS_TOL:
            raise RuntimeError(
                f"{name} losses {rec['losses']} differ from one device {base['losses']}"
            )
    share = rep["meshes"]["fsdp2_tp2"]["param_bytes_on_device_0"] / base["param_bytes"]
    say("mesh", fsdp2_tp2_param_share_on_device_0=share)
    if not 0.2 < share < 0.3:
        raise RuntimeError(f"fsdp2 x tp2 holds {share:.3f} of the parameters on device 0")
    wait_tpu_workers_gone()
    return rep


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    chips = ap.parse_args(argv).chips

    import cluster_anywhere_tpu as ca

    try:
        phase_detect(chips)
        if chips == 4:
            rep = phase_mesh(dict(TRAIN, steps=3, meshes=MESHES))
        else:
            rep = phase_train(TRAIN)
        device = require_tpu(rep, chips)
        require_kernel(rep)
        if chips == 1:
            served = phase_serve(SERVE)
            if require_tpu(served, 1) != device:
                raise RuntimeError(f"replica saw {served}, trainer saw {device}")
    finally:
        ca.shutdown()
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
