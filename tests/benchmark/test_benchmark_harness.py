"""The benchmark's pure parts: no cluster, no chip, no compile for the chip.
Every subprocess call has a timeout."""

import copy
import json
import os
import shutil
import statistics
import subprocess
import sys

import numpy as np
import pytest

from benchmarks.harness import loadgen, manifest, serve_driver, stats, trace_reduce, train_driver
from benchmarks.harness.replica import IdTokenizer

ROOT = manifest.ROOT
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
DOC = manifest.load_manifest()
CELLS = [w["name"] for w in DOC["workloads"]]


# -- loadgen ------------------------------------------------------------------


def _open(seed, seconds=40.0, rate=2.0):
    traffic = manifest.load_cell("chat-steady")["traffic_file"]
    return traffic, loadgen.open_poisson_schedule(traffic, rate, seconds, seed, vocab=32768)


def test_open_schedule_is_the_seeds_and_only_the_seeds():
    traffic, a = _open(3_000_000_019)
    _, b = _open(3_000_000_019)
    _, c = _open(5)
    key = lambda s: [(r["due"], r["max_new_tokens"], r["prompt_ids"].tolist()) for r in s]
    assert key(a) == key(b) and key(a) != key(c)
    # every seed carries the same sizes, in another order
    for field in ("max_new_tokens",):
        assert sorted(r[field] for r in a) == sorted(r[field] for r in c)
    assert sorted(len(r["prompt_ids"]) for r in a) == sorted(len(r["prompt_ids"]) for r in c)
    # the window holds the same requests whatever the seed, the ramp too
    inside = lambda s: sorted((len(r["prompt_ids"]), r["max_new_tokens"]) for r in s if r["due"] >= 0)
    assert inside(a) == inside(c) and len(inside(a)) == round(2.0 * 40.0)
    gaps = lambda s: sorted(np.round(np.diff([0.0] + [r["due"] for r in s if r["due"] >= 0]), 9))
    assert gaps(a) == gaps(c)
    assert len(a) == round(2.0 * 40.0) + round(2.0 * traffic["ramp_s"])
    assert [r["id"] for r in a] == list(range(len(a)))
    assert all(-traffic["ramp_s"] <= r["due"] < 40.0 for r in a)
    p, o = traffic["prompt_len"], traffic["output_len"]
    assert all(p["min"] <= len(r["prompt_ids"]) <= p["max"] for r in a)
    assert all(o["min"] <= r["max_new_tokens"] <= o["max"] for r in a)
    assert all(0 <= r["prompt_ids"].min() and r["prompt_ids"].max() < 32768 for r in a)


def test_tokenizer_carries_exact_token_counts():
    tok = IdTokenizer(32768)
    ids = [0, 7, 32767, 123]
    assert tok.encode(loadgen.prompt_text(ids)) == ids
    assert tok.decode(ids) == "0 7 32767 123" and tok.vocab_size == 32768
    with pytest.raises(ValueError):
        tok.encode("32768")


# -- arithmetic ---------------------------------------------------------------


@pytest.mark.parametrize("q", [0, 50, 90, 99, 100])
def test_percentile_matches_numpy(q):
    xs = [0.3, 1.5, 0.2, 9.0, 4.4, 4.4, 2.0]
    assert stats.percentile(xs, q) == pytest.approx(float(np.percentile(xs, q)))


def test_percentile_of_nothing_raises_and_spread_is_the_contracts():
    with pytest.raises(ValueError):
        stats.percentile([], 50)
    vals = [10.0, 10.2, 9.9, 10.4, 10.1, 9.7]
    q1, _, q3 = statistics.quantiles(vals, n=4)
    assert stats.quartile_spread(vals) == pytest.approx((q3 - q1) / statistics.median(vals))


def test_flops_and_bytes_against_hand_counts():
    c = dict(hidden_size=8, num_attention_heads=2, num_key_value_heads=1, head_dim=4,
             intermediate_size=16, num_hidden_layers=3, vocab_size=32)
    # per layer: wq 8*8, wk 8*4, wv 8*4, wo 8*8, three FFN matrices 8*16, two norms of 8
    per_layer = 64 + 32 + 32 + 64 + 3 * 128 + 16
    assert stats.param_count(c) == 3 * per_layer + 2 * 32 * 8 + 8
    # forward, one sequence of 5: matmuls 2 flops a weight a token; attention 4*t*t*d*h
    matmul_w = 64 + 32 + 32 + 64 + 3 * 128
    fwd = 5 * 2 * matmul_w * 3 + 4 * 5 * 5 * 4 * 2 * 3 + 5 * 2 * 8 * 32
    assert stats.train_flops_per_step(c, batch=1, seq=5) == 3 * fwd
    assert stats.train_flops_per_step(c, batch=4, seq=5) == 4 * 3 * fwd
    # decode: all weights but the embedding table, its 2 rows, and K and V for 2 slots of 10
    weights = stats.param_count(c) - 32 * 8 + 2 * 8
    assert stats.decode_step_bytes(c, slots=2, t_max=10) == 2 * (weights + 2 * 3 * 2 * 10 * 1 * 4)


def test_peaks_table_and_mfu():
    assert stats.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        stats.peaks("TPU v9 imaginary")
    assert stats.mfu_percent(1e9, 197e3, 1, "TPU v5 lite") == pytest.approx(100.0)
    c = manifest.load_cell("train-fsdp4")["config_file"]["config"]
    per_token = stats.train_flops_per_step(c, 8, 4096) / (8 * 4096)
    assert 17e9 < per_token < 21e9  # the issue's "about 18.9 GFLOP a token"


# -- trace reduction ----------------------------------------------------------


def test_trace_reduce_on_the_recorded_trace():
    with open(os.path.join(DATA, "trace_small.json")) as f:
        events = json.load(f)
    (name, ops), = events["devices"].items()
    lo = min(e[0] for e in ops)
    hi = max(e[0] + e[1] for e in ops)
    # busy time the slow way: every stretch between two boundaries that some op covers
    starts = np.array([e[0] for e in ops])
    ends = np.array([e[0] + e[1] for e in ops])
    cuts = np.unique(np.concatenate([starts, ends]))
    mids = (cuts[:-1] + cuts[1:]) / 2
    covered = ((starts[None, :] <= mids[:, None]) & (mids[:, None] < ends[None, :])).any(axis=1)
    slow = float(np.sum(np.diff(cuts)[covered]))
    b = trace_reduce.busy(events)
    assert b["window_s"] == pytest.approx((hi - lo) / 1e9)
    assert b["busy_s"] == pytest.approx(slow / 1e9, rel=1e-9)
    assert 0 < b["busy_s"] <= b["window_s"]
    assert trace_reduce.idle_percent(events) == pytest.approx(100 * (1 - b["busy_s"] / b["window_s"]))
    top = trace_reduce.top_ops(events)
    assert len(top) <= 10 and top == sorted(top, key=lambda kv: -kv[1])
    gaps = trace_reduce.idle_gaps(events)
    assert sum(s for _, s in gaps) == pytest.approx(b["window_s"] - b["busy_s"], rel=1e-6)
    assert {n for n, _ in gaps} <= set(trace_reduce.ANNOTATIONS) | {"host:other"}


def test_trace_reduce_by_hand():
    events = {
        "devices": {"/device:TPU:0": [[0, 100, "fusion.1"], [50, 100, "all-gather-done.2"],
                                      [300, 100, "fusion.1"], [900, 100, "all-reduce.7"]]},
        "host": [[140, 200, "train_step"], [380, 600, "input"]],
    }
    assert trace_reduce.busy(events) == {"busy_s": pytest.approx(350e-9), "window_s": pytest.approx(1000e-9)}
    assert trace_reduce.idle_percent(events) == pytest.approx(65.0)
    assert trace_reduce.collective_percent(events) == pytest.approx(20.0)
    assert trace_reduce.top_ops(events)[0] == ["fusion.1", pytest.approx(200e-9)]
    assert dict(map(tuple, trace_reduce.idle_gaps(events))) == {
        "train_step": pytest.approx(150e-9), "input": pytest.approx(500e-9)}
    assert trace_reduce.busy({"devices": {}, "host": []}) is None


# -- the reduction from records to metrics -----------------------------------


def _ctx():
    t0 = 100.0
    rec = lambda due, times, err=None, n=3: dict(
        id=0, bench_id="r0", due=t0 + due, send=t0 + due + 0.001, status=200, error=err,
        tokens=[1] * len(times), token_times=[t0 + t for t in times], n_prompt=5, n_out=n)
    return {
        "t_open": t0, "seconds": 10.0, "kind": "open_poisson", "setup_s": 50.0,
        "check": {"ok": True},
        "records": [
            rec(-1.0, [-0.5, 0.5, 1.0]),      # due in the ramp: its in-window tokens count
            rec(1.0, [1.5, 2.0, 4.0]),
            rec(2.0, [], err="timeout"),      # never answered: misses at the window length
            rec(9.0, [9.5, 10.5, 11.0]),      # ends after the window closed
        ],
    }


def test_serve_reduction_counts_what_the_window_holds():
    ctx = _ctx()
    assert serve_driver.ttfts(ctx) == pytest.approx([0.5, 10.0, 0.5])
    assert sorted(serve_driver.token_gaps(ctx)) == pytest.approx([0.5, 0.5, 1.0, 2.0])
    assert serve_driver.tokens_in_window(ctx) == 2 + 3 + 1
    e2e = serve_driver.end_to_end(ctx)
    assert e2e["serve_out_tok_s"] == pytest.approx(0.6) and e2e["setup_s"] == 50.0
    assert e2e["gap_p50_s"] == pytest.approx(0.75) and e2e["gap_mean_s"] == pytest.approx(4.0 / 4)
    # the share of the offered tokens that the window delivered, and the edges in it
    knee = serve_driver.knee_stats(ctx)
    assert (knee["requests"], knee["offered_tokens"]) == (3, 9)
    assert (knee["carry_in_tokens"], knee["carry_out_tokens"]) == (2, 2)
    assert knee["delivered_share"] == pytest.approx(6 / 9)
    out = serve_driver.outcome(ctx)
    assert (out["attempted"], out["failed"], out["correct"]) == (4, 1, False)
    ctx["records"].pop(2)
    assert serve_driver.outcome(ctx)["correct"] is True
    ctx["check"]["ok"] = False
    assert serve_driver.outcome(ctx)["correct"] is False


def test_layer_readers_on_a_replicas_records():
    ctx = _ctx()
    ctx["replica"] = {
        # (t_end, wall, admit_s, tokens_out, admitted_total, decode_steps_total)
        "steps": [(99.0, 0.1, 0.0, 2, 2, 10), (101.0, 0.5, 0.4, 4, 3, 11), (102.0, 0.1, 0.0, 3, 3, 12),
                  (111.0, 0.1, 0.0, 3, 3, 13)],
        # (t_end, wall, requests, prompt_tokens, reused_tokens)
        "admits": [(100.9, 0.4, 1, 1000, 250), (120.0, 9.0, 1, 10, 0)],
        "compiles": [(99.0, 1.0), (105.0, 0.2)],
        "first": {"r0": (101.0, 101.2)},
    }
    ctx["device"] = {"memory_peak_bytes": 12_000_000_000}
    read = lambda name, **kw: manifest.load_reader(name)(ctx, **kw)
    assert read("decode_batch") == pytest.approx((4 - 1 + 3) / 2)
    assert read("admit_ms") == pytest.approx(400.0)
    assert read("compiles") == 1
    assert read("decode_step_ms", q=50) == pytest.approx(100.0)
    assert read("hbm_peak") == pytest.approx(12.0)
    assert read("device_idle") is None  # nothing traced: nothing reported
    assert read("gen_late", q=99) == pytest.approx(1.0)
    ctx["steps"], ctx["fit_s"], ctx["loop_s"] = [(1.0, 2.0, 9.0), (3.0, 2.2, 8.9)], 70.0, 61.5
    assert read("train_step_ms", q=50) == pytest.approx(2100.0)
    assert read("fit_overhead") == pytest.approx(8.5)
    assert read("fit_restarts") is None  # a run that does not say: nothing reported
    ctx["restarts"] = 1
    assert read("fit_restarts") == 1


def test_train_outcome_rests_on_the_reference_and_a_falling_loss():
    base = dict(warm=[(0, 1, 10.90), (0, 1, 10.8)], steps=[(0, 1, 10.7)] * 5, ref_loss=10.899, check={})
    assert train_driver.outcome(copy.deepcopy(base))["correct"] is True
    off = dict(copy.deepcopy(base), ref_loss=10.9 + 2 * train_driver.LOSS_TOL)
    assert train_driver.outcome(off)["correct"] is False
    flat = dict(copy.deepcopy(base), steps=[(0, 1, 10.95)] * 5)
    assert train_driver.outcome(flat)["correct"] is False
    nan = dict(copy.deepcopy(base), steps=[(0, 1, float("nan"))] * 5)
    out = train_driver.outcome(nan)
    assert out["failed"] == 5 and out["correct"] is False


def test_stall_watch_sees_a_held_lock_and_names_its_phase(tmp_path):
    import ctypes
    import time

    from benchmarks.harness.stallwatch import StallWatch

    watch = StallWatch(str(tmp_path / "trail.txt"))
    watch.mark("quiet")
    time.sleep(0.6)  # sleeping gives the lock away: no stall
    watch.mark("held")
    held = ctypes.PyDLL(None).usleep  # a C call through PyDLL keeps the interpreter lock
    held(900_000)
    time.sleep(0.6)  # the watch thread gets to look
    watch.stop()
    rep = watch.report()
    assert [m[1] for m in rep["marks"]] == ["start", "quiet", "held"]
    assert rep["worst"]["quiet"]["threads_s"] < 0.3
    assert 0.5 < rep["worst"]["held"]["threads_s"] < 2.0 and rep["max_s"] == rep["worst"]["held"]["threads_s"]
    trail = (tmp_path / "trail.txt").read_text()
    assert "stall threads=" in trail and trail.rstrip().endswith("stop")


# -- the manifest and its files ----------------------------------------------


def test_manifest_keeps_the_contract():
    assert set(DOC) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert isinstance(DOC["run_seconds"], int) and 1 <= DOC["run_seconds"] <= 51
    names = [m["name"] for m in DOC["end_to_end"] + DOC["per_layer"]]
    assert len(names) == len(set(names)) and len(CELLS) == len(set(CELLS))
    for entry in DOC["end_to_end"] + DOC["per_layer"] + DOC["configs"] + DOC["workloads"]:
        assert manifest.NAME_RE.match(entry["name"]), entry["name"]
    for m in DOC["end_to_end"] + DOC["per_layer"]:
        assert manifest.UNIT_RE.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
        assert set(m.get("workloads", CELLS)) <= set(CELLS)
    e2e = {m["name"]: m for m in DOC["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for m in DOC["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert 0.01 <= m["bound"] <= 0.1 and m["source"] in ("host_clock", "device_trace")
    for m in DOC["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        moved = e2e[m["moves"]]
        assert set(m.get("workloads", CELLS)) <= set(moved.get("workloads", CELLS)), m["name"]
    for cell in CELLS:
        reported = [m for m in DOC["end_to_end"] if cell in m.get("workloads", CELLS)]
        assert len(reported) >= 2
        assert any(cell in m.get("workloads", CELLS) for m in DOC["per_layer"])
    four = [w for w in DOC["workloads"] if w["chips"] == 4]
    assert all(w["chips"] in (1, 4) for w in DOC["workloads"]) and len(four) <= max(1, len(CELLS) // 4)
    assert all(1 <= len(w["why"]) <= 200 and "\n" not in w["why"] for w in DOC["workloads"])
    for path in DOC["paths"]:
        assert os.path.isdir(os.path.join(ROOT, path))
    assert len(json.dumps(DOC)) < 64 * 1024
    width = ("hidden_size", "intermediate_size", "head_dim", "_dim", "_rank", "latent", "state_size", "per_tok")
    for c in DOC["configs"]:
        assert c["file"].startswith("benchmarks/") and os.path.isfile(os.path.join(ROOT, c["file"]))
        assert not [k for k in c["reduced"] if any(w in k for w in width)]


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_resolves_by_name(cell):
    c = manifest.load_cell(cell)
    entry = next(w for w in DOC["workloads"] if w["name"] == cell)
    assert (c["config"], c["traffic"], c["chips"], c["why"]) == (
        entry["config"], entry["traffic"], entry["chips"], entry["why"])
    cfg = c["config_file"]
    assert {"source", "config", "reduced", "assumed", "departures", "deployment"} <= set(cfg)
    published = dict(hidden_size=4096, intermediate_size=14336, num_attention_heads=32,
                     num_key_value_heads=8, head_dim=128, vocab_size=32768, rope_theta=1e6,
                     max_position_embeddings=32768)
    assert {k: cfg["config"][k] for k in published} == published
    listed = next(x for x in DOC["configs"] if x["name"] == c["config"])
    assert listed["source"] == cfg["source"] and set(listed["reduced"]) == set(cfg["reduced"])
    assert os.path.isfile(os.path.join(manifest.BENCH_DIR, "harness", c["traffic_file"]["driver"] + ".py"))
    metrics = manifest.layer_metrics_for(cell)
    in_doc = {m["name"]: m for m in DOC["per_layer"] if cell in m.get("workloads", CELLS)}
    assert {m["name"] for m in metrics} == set(in_doc)
    for m in metrics:
        d = in_doc[m["name"]]
        assert (m["unit"], m["layer"], m["moves"], m["source"]) == (d["unit"], d["layer"], d["moves"], d["source"])
        assert callable(manifest.load_reader(m["reader"]))


def test_a_cell_a_mix_and_a_metric_are_added_as_files_only(tmp_path):
    bench = tmp_path / "benchmarks"
    shutil.copytree(manifest.BENCH_DIR, bench, ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}
    mix = json.loads((bench / "traffic" / "chat-steady.json").read_text())
    mix["deployment"]["prefix_cache_entries"] = 8
    (bench / "traffic" / "chat-default-cache.json").write_text(json.dumps(mix))
    (bench / "cells" / "chat-default-cache.json").write_text(json.dumps(
        {"config": "mistral-7b-v0.3-serve1", "traffic": "chat-default-cache", "chips": 1,
         "rate": 0.6, "why": "the chat mix with the default prefix cache"}))
    (bench / "layer_metrics" / "admits_total.json").write_text(json.dumps(
        {"unit": "count", "layer": "batcher (llm/continuous.py)", "moves": "serve_out_tok_s",
         "source": "program_counter", "cells": ["chat-default-cache"], "reader": "admits_total"}))
    (bench / "layer_metrics" / "readers" / "admits_total.py").write_text(
        "def read(ctx):\n    return sum(a[2] for a in ctx['replica']['admits'])\n")
    cell = manifest.load_cell("chat-default-cache", str(bench))
    assert cell["traffic_file"]["deployment"]["prefix_cache_entries"] == 8
    schedule = loadgen.make_plan(cell["traffic_file"]["kind"], cell["traffic_file"], cell["rate"], 10.0, 1, 32768)
    assert len(schedule) == round(0.6 * 10.0) + round(0.6 * mix["ramp_s"])
    ctx = {"replica": {"admits": [(0, 0.1, 2, 10, 0), (1, 0.1, 1, 5, 0)]}, "device": {}}
    got = manifest.read_layer_metrics("chat-default-cache", ctx, str(bench))
    assert got["admits_total"] == {"value": 3.0, "unit": "count"}
    assert "admits_total" not in {m["name"] for m in manifest.layer_metrics_for("chat-steady", str(bench))}
    assert all(p.read_bytes() == data for p, data in before.items())  # no file was edited


def test_command_fails_where_only_the_benchmarks_files_are(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path / "BENCHMARK.json")
    for path in DOC["paths"]:
        shutil.copytree(os.path.join(ROOT, path), tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run(
        [sys.executable, *DOC["command"][1:], "--workload", CELLS[0], "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=60,
    )
    assert proc.returncode != 0 and '"correct"' not in proc.stdout
