"""The benchmark's two drivers off the chip: each cell kind run through a real
cluster at a tiny width on the CPU backend (a TPU resource that is only a
number), a window of a few seconds, and the result line refusing to exist
without a TPU.  These are the only two tests of the benchmark that start a
cluster; each tears it down in `finally`, under the time limit that
conftest.py gives every test of the benchmark."""

import copy
import json
import os
import subprocess
import sys
import time

import pytest

import cluster_anywhere_tpu as ca

from benchmarks import run as bench_run
from benchmarks.harness import cluster, manifest, serve_driver, train_driver

ROOT = manifest.ROOT
TINY = dict(hidden_size=64, num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, intermediate_size=128, vocab_size=512)


def tiny_cell(name: str) -> dict:
    cell = copy.deepcopy(manifest.load_cell(name))
    cell["config_file"]["config"].update(TINY)
    return cell


def fake_chips(n: int):
    if ca.is_initialized():
        ca.shutdown()
    ca.init(num_cpus=4, num_tpus=n)


@pytest.mark.parametrize("name", ["chat-steady", "chat-closed6"])
def test_serve_rehearsal(name):
    chat = tiny_cell(name)
    closed = chat["traffic_file"]["kind"] == "closed_loop"
    chat.update({"callers": 3} if closed else {"rate": 6.0})
    chat["traffic_file"].update(
        ramp_s=0.5, drain_s=60.0, warmup_prompt_lens=[20, 70],
        prompt_len=dict(dist="lognormal", median=24, sigma=0.5, min=8, max=80),
        output_len=dict(dist="lognormal", median=6, sigma=0.3, min=4, max=12),
        check=dict(stream_prompt_lens=[12, 30, 70], stream_new_tokens=8, repeat_prompt_len=40,
                   repeat_new_tokens=5),
        deployment=dict(slots=4, max_prompt_len=96, max_new_tokens=16, prefix_cache_entries=0),
    )
    fake_chips(1)  # each case has a cluster, and (conftest.py) a time limit, of its own
    try:
        ctx = serve_driver.measure(chat, seed=3_000_000_019, seconds=3.0, trace=False,
                                   t_start=time.monotonic())
    finally:
        ca.shutdown()
    out = serve_driver.outcome(ctx)
    assert out["failed"] == 0 and out["attempted"] >= 3, out
    # the check streams were one batch of the decode program, and the reference passed them
    check = ctx["check"]
    assert check["streams"] == 3 and check["positions"] == 24 and check["decode_batch_mean"] > 1.0, check
    # a step hands a request one token, two in the step that admits it
    assert 1.0 < check["decode_requests_mean"] <= min(3.0, check["decode_batch_mean"]), check
    assert check["ok"] and check["repeat_identical"] and out["correct"], check
    assert "mechanism" not in check  # the dense decoder's reference brings none
    e2e = serve_driver.end_to_end(ctx)
    assert all(v > 0 for v in e2e.values()), e2e
    assert {"setup_s", "gap_p50_s", "gap_mean_s", "ttft_p90_s", "serve_out_tok_s"} <= set(e2e)
    if closed:
        # three callers, each with one request in flight at a time and the next due at the
        # last token of the one before; all of it went through proxy, router and replica
        by_caller = {}
        for r in ctx["records"]:
            by_caller.setdefault(r["caller"], []).append(r)
        assert len(by_caller) == 3 and max(len(v) for v in by_caller.values()) >= 2, by_caller
        for recs in by_caller.values():
            assert all(b["due"] == a["token_times"][-1] for a, b in zip(recs, recs[1:]))
        assert max(r["due"] for r in ctx["records"]) < ctx["t_open"] + ctx["seconds"]
    layer = manifest.read_layer_metrics(name, ctx)
    # an admit runs its bucket's compiled prefill program (PERF.md, PR 30), and the
    # warm-up compiled every bucket the mix reaches: nothing compiles in the window
    assert closed or layer["compiles_in_window"]["value"] == 0
    suffix = ".closed" if closed else ""
    assert layer["decode_batch_mean" + suffix]["value"] >= 1.0
    # no trace: the reader returns nothing
    assert "device_idle.serve" not in layer and "device_idle.closed" not in layer
    assert {n + suffix for n in ("gen_late_p99_ms", "front_overhead_p50_ms", "admit_ms_mean",
                                 "decode_step_ms_p50", "gap_p99_s", "ttft_p50_s")} <= set(layer)
    assert closed or {"ttft_p90_s", "delivered_tok_s"} <= set(layer)
    # a run that landed on the CPU is a failure, never a result
    with pytest.raises(RuntimeError, match="need 1 tpu"):
        bench_run.result_line(ctx["cell"], serve_driver, ctx, trace=False)
    # what the result line would say on a chip
    ctx["device"].update(platform="tpu", kind="TPU v5 lite", count=1)
    line = bench_run.result_line(ctx["cell"], serve_driver, ctx, trace=False)
    assert set(line) >= {"correct", "attempted", "failed", "metrics", "device"}
    # every number compared beside its limit, as the line's last key
    assert list(line)[-1] == "check" and line["check"] is ctx["check"]
    assert set(line["metrics"]) == (
        {"setup_s", "serve_out_tok_s"} if closed else {"setup_s", "gap_p50_s", "gap_mean_s"})
    assert set(line["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    json.dumps(line)


def test_train_rehearsal():
    cell = tiny_cell("train-fsdp4")
    cell["traffic_file"]["job"].update(batch=8, seq=32, learning_rate=1e-2)
    fake_chips(4)
    try:
        ctx = train_driver.measure(cell, seed=3_000_000_019, seconds=2.0, trace=False,
                                   t_start=time.monotonic())
    finally:
        ca.shutdown()
    out = train_driver.outcome(ctx)
    assert out["failed"] == 0 and out["correct"], (out, ctx["check"])
    assert ctx["compiles_in_window"] == 0 and ctx["restarts"] == 0
    assert ctx["check"]["tolerance"] == manifest.reference_of(cell).LOSS_TOL == 0.01
    assert abs(ctx["check"]["first_loss"] - ctx["check"]["ref_loss"]) < ctx["check"]["tolerance"]
    e2e = train_driver.end_to_end(ctx)
    assert e2e["train_tok_s"] > 0 and e2e["setup_s"] > 0
    layer = manifest.read_layer_metrics("train-fsdp4", dict(ctx, device=dict(ctx["device"], kind="TPU v5 lite")))
    assert {"fit_overhead_s", "step_ms_p50", "mfu.train"} <= set(layer)
    assert layer["fit_restarts"]["value"] == 0
    # the worker's stall watch saw every phase of the loop and left its trail
    assert [m[1] for m in ctx["stalls"]["marks"]] == [
        "start", "import", "devices", "init_weights", "reference", "first_step", "warmup", "window", "after"]
    assert layer["worker_stall_max_s"]["value"] == ctx["stalls"]["max_s"] >= 0
    assert [f for f in os.listdir(cluster.out_dir()) if f.startswith("train-fsdp4.stalls.")]
    # the mix's cluster_env is the program's own tunable, and only that
    assert cell["traffic_file"]["cluster_env"] == {"CA_HEALTH_CHECK_FAILURE_THRESHOLD": "30"}
    with pytest.raises(ValueError, match="CA_ tunables"):
        cluster.init_cluster(4, {"JAX_PLATFORMS": "cpu"})
    with pytest.raises(RuntimeError, match="need 4 tpu"):
        bench_run.result_line(cell, train_driver, ctx, trace=False)
    ctx["device"].update(platform="tpu", kind="TPU v5 lite", count=4)
    line = bench_run.result_line(cell, train_driver, ctx, trace=False)
    assert line["restarts"] == 0 and line["stalls"]["max_s"] == ctx["stalls"]["max_s"]


def test_command_fails_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("CA_NUM_TPUS", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"), "--workload", "chat-steady",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=120, cwd=ROOT,
    )
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout
    assert "need 1 TPU chip(s)" in proc.stderr
