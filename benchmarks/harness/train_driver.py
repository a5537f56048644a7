"""Drives a training cell: `JaxTrainer.fit` with one worker that owns the
cell's chips, running the loop below on the configuration's `make_train_step`
over the mix's mesh.  The loop is the benchmark's (a user's loop would look
the same); the trainer, the worker group, the step program and the sharding
are the program's."""

from __future__ import annotations

import math
import os
import tempfile
import time
from typing import Any, Dict

from . import cluster, manifest

TRACE_STEPS = 2  # a traced run profiles this many steps after the window


def train_loop(config: Dict[str, Any]) -> None:
    """Runs in the TPU worker.  Weights and optimizer state come from the seed
    in one jitted call, sharded as they are made; a fresh seeded batch is
    drawn on the host and put on the devices every step; every step is closed
    by reading its loss back.  The first step, outside the window, takes one
    sequence in every row so that its loss can be held to the loss the
    configuration's reference (references/<config["reference"]>.py) gives."""
    from .stallwatch import StallWatch

    t_loop = time.monotonic()
    # from before JAX is imported: a worker lost at start-up is this watch's case
    watch = StallWatch(os.path.join(config["trail_dir"], f"{config['cell']}.stalls.{os.getpid()}.txt"))
    watch.mark("import")
    import jax
    import numpy as np
    import optax
    from jax.sharding import NamedSharding, PartitionSpec

    from cluster_anywhere_tpu import train
    from cluster_anywhere_tpu.models import TransformerConfig, make_train_step
    from cluster_anywhere_tpu.models.transformer import (
        init_params, make_batch_sharding, param_specs,
    )
    from cluster_anywhere_tpu.parallel import MeshSpec, make_mesh

    from .replica import CompileCounter, device_report, start_trace, stop_trace

    compiles = CompileCounter()
    job, seed, seconds = config["job"], config["seed"], config["seconds"]
    cfg = TransformerConfig(**config["widths"], remat=job["remat"])
    spec = MeshSpec(**job["mesh"])
    watch.mark("devices")
    mesh = make_mesh(spec, devices=jax.devices()[: spec.size])
    # what make_train_step's own init_state does, but the weights are made
    # sharded in one jitted call (init_state makes them whole on one device
    # first: 11.6 GB of float32 here); the moments inherit the shardings
    watch.mark("init_weights")
    optimizer = optax.adamw(job["learning_rate"], weight_decay=0.01)
    step, _ = make_train_step(cfg, mesh, optimizer=optimizer)
    shardings = jax.tree_util.tree_map(
        lambda spec: NamedSharding(mesh, spec), param_specs(cfg),
        is_leaf=lambda x: isinstance(x, PartitionSpec),
    )
    params = jax.jit(lambda k: init_params(k, cfg), out_shardings=shardings)(
        jax.random.key(seed % (2 ** 31))
    )
    opt_state = optimizer.init(params)
    jstep = jax.jit(step, donate_argnums=(0, 1))
    sharding = make_batch_sharding(cfg, mesh)
    rng = np.random.default_rng(seed)
    annotate = jax.profiler.TraceAnnotation

    def put(ids):
        return {"ids": jax.device_put(ids, sharding)}

    def next_batch():
        with annotate("input"):
            return put(rng.integers(0, cfg.vocab_size, (job["batch"], job["seq"] + 1), dtype=np.int32))

    def one_step(batch):
        nonlocal params, opt_state
        t0 = time.monotonic()
        with annotate("train_step"):
            params, opt_state, loss = jstep(params, opt_state, batch)
            loss = float(loss)
        t1 = time.monotonic()
        return (t1, t1 - t0, loss)

    # set-up: the reference's loss on the first batch's first sequence at the
    # initial weights, then the steps that compile and warm the program
    watch.mark("reference")
    first = rng.integers(0, cfg.vocab_size, job["seq"] + 1, dtype=np.int32)
    ref_loss = manifest.load_reference(config["reference"]).loss(params, first, cfg)
    watch.mark("first_step")
    warm = [one_step(put(np.tile(first, (job["batch"], 1))))]
    watch.mark("warmup")
    for _ in range(job["warmup_steps"]):
        warm.append(one_step(next_batch()))
    n_compiles = len(compiles.events)
    # the window: whole steps until `seconds` have passed; it closes with the
    # step that crosses the line, so it holds all the work and all its time
    watch.mark("window")
    t_open = time.monotonic()
    steps = []
    while time.monotonic() - t_open < seconds:
        steps.append(one_step(next_batch()))
    t_close = steps[-1][0]
    trace_path = trace_stop_s = None
    watch.mark("after")
    if config["trace_dir"]:
        session = start_trace(config["trace_dir"])
        for _ in range(TRACE_STEPS):
            one_step(next_batch())
        t_stop = time.monotonic()
        trace_path = stop_trace(config["trace_dir"], session)
        trace_stop_s = time.monotonic() - t_stop
    watch.stop()
    train.report({
        "stalls": watch.report(),
        "device": device_report(), "param_bytes_on_device_0": sum(
            x.addressable_shards[0].data.nbytes for x in jax.tree_util.tree_leaves((params, opt_state))
        ), "t_open": t_open, "t_close": t_close, "steps": steps,
        "warm": warm, "ref_loss": ref_loss, "trace_path": trace_path,
        "trace_stop_s": trace_stop_s,
        "compiles_in_window": len(compiles.events) - n_compiles,
        "loop_s": time.monotonic() - t_loop,
        "tokens_per_step": job["batch"] * job["seq"],
        # the controller numbers its attempts from 0: this one's number is how
        # many worker groups were lost and started again before it
        "restarts": train.get_context().attempt,
    })


def measure(cell: Dict[str, Any], seed: int, seconds: float, trace: bool,
            t_start: float) -> Dict[str, Any]:
    from cluster_anywhere_tpu.train import FailureConfig, JaxTrainer, RunConfig, ScalingConfig

    job, ref = cell["traffic_file"]["job"], manifest.reference_of(cell)
    config = {
        "job": job, "seed": seed, "seconds": float(seconds),
        "reference": cell["config_file"]["reference"],
        "widths": ref.program_config(
            cell["config_file"], vocab_size=cell["config_file"]["config"]["vocab_size"],
            max_seq_len=job["seq"],
        ),
        "trace_dir": cluster.trace_dir(cell["name"]) if trace else None,
        "cell": cell["name"], "trail_dir": cluster.out_dir(),
    }
    t_fit = time.monotonic()
    with tempfile.TemporaryDirectory(prefix="bench_train_") as storage:
        result = JaxTrainer(
            train_loop, train_loop_config=config,
            scaling_config=ScalingConfig(
                num_workers=1, use_tpu=True, chips_per_worker=cell["chips"]
            ),
            # what a team running a job for hours sets: a worker lost at
            # start-up (2 of 12 four-chip runs, PERF.md) costs a restart and
            # not the job.  A restart is never silent: the result line carries
            # `restarts`, the per-layer metric `fit_restarts` counts them, and
            # the time they took is in setup_s
            run_config=RunConfig(name="bench_train", storage_path=storage,
                                 failure_config=FailureConfig(max_failures=2)),
        ).fit()
    fit_s = time.monotonic() - t_fit
    if result.error is not None:
        raise result.error
    rep = result.metrics
    if rep["restarts"]:
        # why a worker group was lost: the cluster's own logs go with its
        # session, so keep their ends now
        cluster.save_session_logs(cell["name"])
    cluster.wait_tpu_workers_gone()
    window_s = rep["t_close"] - rep["t_open"]
    return {
        "cell": cell, "kind": "train_steps", "chips": cell["chips"], "device": rep["device"],
        "setup_s": rep["t_open"] - t_start, "fit_s": fit_s, "loop_s": rep["loop_s"],
        "steps": rep["steps"], "warm": rep["warm"], "ref_loss": rep["ref_loss"],
        "train_tok_s": len(rep["steps"]) * rep["tokens_per_step"] / window_s,
        "window_s": window_s, "trace_path": rep["trace_path"], "trace_stop_s": rep["trace_stop_s"],
        "compiles_in_window": rep["compiles_in_window"], "restarts": rep["restarts"],
        "stalls": rep["stalls"], "state_bytes_on_device_0": rep["param_bytes_on_device_0"],
        "check": {
            "first_loss": rep["warm"][0][2], "ref_loss": rep["ref_loss"], "tolerance": ref.LOSS_TOL,
        },
    }


def end_to_end(ctx: Dict[str, Any]) -> Dict[str, float]:
    return {"setup_s": ctx["setup_s"], "train_tok_s": ctx["train_tok_s"]}


def outcome(ctx: Dict[str, Any]) -> Dict[str, Any]:
    """attempted / failed count optimizer steps; a step fails when its loss is
    not finite.  Correct: the first step's loss agrees with the reference
    within the reference's own LOSS_TOL (`check.tolerance`), and the mean of
    the last five losses is below the first."""
    losses = [s[2] for s in ctx["warm"] + ctx["steps"]]
    failed = sum(not math.isfinite(x) for x in losses)
    agrees = abs(losses[0] - ctx["ref_loss"]) <= ctx["check"]["tolerance"]
    fell = sum(losses[-5:]) / len(losses[-5:]) < losses[0]
    ctx["check"].update(agrees=agrees, loss_fell=fell, last_loss=losses[-1])
    return {
        "attempted": len(losses), "failed": failed,
        "correct": bool(agrees and fell and not failed and ctx["steps"]),
    }
