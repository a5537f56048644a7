"""The benchmark's pure parts: no cluster, no chip, no compile for the chip.
Every subprocess call has a timeout."""

import asyncio
import copy
import json
import os
import re
import shutil
import statistics
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from benchmarks.harness import loadgen, manifest, program_trace, serve_driver, stats, trace_reduce, train_driver
from benchmarks.harness.reference import check_serving
from benchmarks.harness.replica import IdTokenizer
from cluster_anywhere_tpu.models.transformer import TransformerConfig

ROOT = manifest.ROOT
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
DOC = manifest.load_manifest()
CELLS = [w["name"] for w in DOC["workloads"]]
DENSE = manifest.load_reference("dense_gqa")
# what `reduced` may never name: a width, by the substrings the contract gives
WIDTH = ("hidden_size", "intermediate_size", "head_dim", "_dim", "_rank", "latent", "state_size", "per_tok")


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


# -- the closed loop, against a local stub with a fixed service time -----------

CLOSED = {"kind": "closed_loop", "shape_seed": 7, "caller_requests": 40, "ramp_s": 0.3,
          "drain_s": 5.0, "prompt_len": {"dist": "uniform", "min": 3, "max": 9},
          "output_len": {"dist": "uniform", "min": 2, "max": 5}}


def _closed_plan(seed, callers=3, **over):
    return loadgen.make_plan({"traffic_file": dict(CLOSED, **over), "callers": callers}, 1.0, seed, vocab=100)


class StubServer:
    """Answers a streamed POST as the proxy does: `max_new_tokens` events spread
    over `service_s`, or a 500 at once for a `bench_id` in `fail`, or one event
    and the end of the stream `service_s` later for one in `cut`.  Keeps how
    many requests were open at once and when each arrived."""

    def __init__(self, service_s: float, fail=(), cut=()):
        self.service_s, self.fail, self.cut = service_s, set(fail), set(cut)
        self.open = self.most_open = 0
        self.arrived = []
        self.loop = asyncio.new_event_loop()
        self.port = None

    async def _handle(self, reader, writer):
        head = await reader.readuntil(b"\r\n\r\n")
        size = int(re.search(rb"Content-Length: (\d+)", head).group(1))
        body = json.loads(await reader.readexactly(size))
        self.arrived.append((time.monotonic(), body["bench_id"]))
        self.open += 1
        self.most_open = max(self.most_open, self.open)
        try:
            if body["bench_id"] in self.fail:
                writer.write(b"HTTP/1.1 500 Internal Server Error\r\n\r\nno")
            else:
                writer.write(b"HTTP/1.1 200 OK\r\nContent-Type: text/event-stream\r\n\r\n")
                if body["bench_id"] in self.cut:
                    writer.write(b'data: {"token_id": 0}\n\n')
                    await writer.drain()
                    await asyncio.sleep(self.service_s)
                    return
                for i in range(body["max_new_tokens"]):
                    await asyncio.sleep(self.service_s / body["max_new_tokens"])
                    writer.write(b'data: {"token_id": %d}\n\n' % i)
                    await writer.drain()
        finally:
            self.open -= 1
            writer.close()

    def __enter__(self):
        started = threading.Event()

        def run():
            asyncio.set_event_loop(self.loop)
            server = self.loop.run_until_complete(asyncio.start_server(self._handle, "127.0.0.1", 0))
            self.port = server.sockets[0].getsockname()[1]
            started.set()
            self.loop.run_forever()
            server.close()
            self.loop.run_until_complete(server.wait_closed())
            self.loop.close()

        self.thread = threading.Thread(target=run, daemon=True)
        self.thread.start()
        assert started.wait(10)
        return self

    def __exit__(self, *exc):
        self.loop.call_soon_threadsafe(self.loop.stop)
        self.thread.join(10)
        assert not self.thread.is_alive()


def test_closed_plan_is_the_seeds_and_only_the_seeds():
    a, b, c = _closed_plan(3_000_000_019), _closed_plan(3_000_000_019), _closed_plan(5)
    key = lambda plan: [(e["caller"], e["start"], [(r["max_new_tokens"], r["prompt_ids"].tolist())
                                                    for r in e["requests"]]) for e in plan]
    assert key(a) == key(b) and key(a) != key(c)
    # callers start evenly over the ramp, whatever the seed, and each has its whole sequence
    assert [e["start"] for e in a] == [e["start"] for e in c] == pytest.approx([-0.3, -0.2, -0.1])
    assert all(len(e["requests"]) == 40 for e in a)
    # every seed gives a caller the same frozen sizes, in another order
    sizes = lambda e: [(len(r["prompt_ids"]), r["max_new_tokens"]) for r in e["requests"]]
    assert [sorted(sizes(e)) for e in a] == [sorted(sizes(e)) for e in c]
    assert all(sizes(x) != sizes(y) for x, y in zip(a, c))
    assert sorted(sizes(a[0])) != sorted(sizes(a[1]))  # the callers' draws differ
    assert all(3 <= len(r["prompt_ids"]) <= 9 and 2 <= r["max_new_tokens"] <= 5 and r["prompt_ids"].max() < 100
               for e in a for r in e["requests"])
    # another number of callers is another plan of the same kind; an unknown kind is refused
    assert len(_closed_plan(1, callers=6)) == 6
    with pytest.raises(ValueError, match="unknown traffic kind"):
        loadgen.make_plan({"traffic_file": dict(CLOSED, kind="bursty"), "callers": 3}, 1.0, 1, 100)


def _sizes(entry):
    return [(len(r["prompt_ids"]), r["max_new_tokens"]) for r in entry["requests"]]


@pytest.mark.parametrize("dist", ["uniform", "lognormal"])
def test_a_closed_mix_of_quantiles_goes_round_one_set_whatever_the_seed(dist):
    spec = {"uniform": ({"dist": "uniform", "min": 3, "max": 10}, {"dist": "uniform", "min": 2, "max": 9}),
            "lognormal": ({"dist": "lognormal", "median": 40, "sigma": 0.4, "min": 10, "max": 80},
                          {"dist": "lognormal", "median": 9, "sigma": 0.6, "min": 2, "max": 24})}[dist]
    over = dict(caller_requests=8, caller_sizes="quantiles", caller_rounds=3, prompt_len=spec[0], output_len=spec[1])
    a, b, c = (_closed_plan(s, **over) for s in (3_000_000_019, 3_000_000_019, 5))
    assert [_sizes(e) for e in a] == [_sizes(e) for e in b]
    assert all(len(e["requests"]) == 24 for e in a)
    for x, y in zip(a, c):
        # any 8 requests in a row are the caller's whole set, the same for every seed, in the seed's order
        whole = sorted(_sizes(x)[:8])
        assert all(sorted(_sizes(e)[k:k + 8]) == whole for e in (x, y) for k in range(17))
        assert _sizes(x)[:8] != _sizes(y)[:8] and _sizes(x)[:8] == _sizes(x)[8:16] == _sizes(x)[16:]
        # the lengths are the distribution's own quantiles: no draw, so every caller has the same
        # prompt lengths and the same output lengths, paired its own way
        assert sorted(p for p, _ in whole) == loadgen._quantile_lengths(8, spec[0]).tolist()
        assert sorted(o for _, o in whole) == loadgen._quantile_lengths(8, spec[1]).tolist()
        # a round's token ids are its own
        assert x["requests"][0]["prompt_ids"].tolist() != x["requests"][8]["prompt_ids"].tolist()
    assert sorted(_sizes(a[0])[:8]) != sorted(_sizes(a[1])[:8])  # the pairing is the caller's
    with pytest.raises(ValueError, match="unknown caller_sizes"):
        _closed_plan(1, caller_sizes="sorted")


def test_quantile_lengths_by_hand():
    assert loadgen._quantile_lengths(4, {"dist": "uniform", "min": 1, "max": 8}).tolist() == [2, 4, 6, 8]
    q = loadgen._quantile_lengths(5, {"dist": "lognormal", "median": 100, "sigma": 0.5, "min": 60, "max": 1000})
    z = [statistics.NormalDist().inv_cdf(p) for p in (0.1, 0.3, 0.5, 0.7, 0.9)]
    assert q.tolist() == [60] + [round(100 * np.exp(0.5 * v)) for v in z[1:]] and q[2] == 100
    with pytest.raises(ValueError, match="unknown length distribution"):
        loadgen._quantile_lengths(3, {"dist": "zipf"})


def test_the_long_documents_mix_gives_every_seed_the_same_work():
    """`longrag-closed`: a window reaches about 23 requests a caller, so a caller goes round
    24 quantiles; 24 in a row hold the same prompt and output tokens for every seed."""
    cell = manifest.load_cell("kexaone-longrag-closed6")
    mix = cell["traffic_file"]
    assert (mix["caller_sizes"], mix["caller_requests"], mix["caller_rounds"]) == ("quantiles", 24, 8)
    a, b = (loadgen.make_plan(cell, 51.0, s, 19200) for s in (2_540_000_901, 7))
    assert len(a) == cell["callers"] == 6 and all(len(e["requests"]) == 192 for e in a)
    work = lambda e, k: (sum(p for p, _ in _sizes(e)[k:k + 24]), sum(o for _, o in _sizes(e)[k:k + 24]))
    assert len({work(e, k) for e in a + b for k in (0, 5, 29, 168)}) == 1
    p, o = zip(*_sizes(a[0])[:24])
    assert (min(p), max(p), min(o), max(o)) == (1814, 8192, 28, 256)
    assert sum(n > 4096 for n in p) == 12 and sum(n <= 2048 for n in p) == 1  # the buckets' shares
    # the mixes that a window goes deep into keep their draw
    for other in ("chat-closed", "rag-closed", "reason-closed", "chat-closed-blocks"):
        assert "caller_sizes" not in json.load(open(os.path.join(manifest.BENCH_DIR, "traffic", other + ".json")))


@pytest.mark.parametrize("seed", [1, 7, 2_580_000_101, 2_999_999_999, 3_000_000_019])
def test_the_whole_documents_mix_holds_the_same_work_in_every_window(seed):
    """`longdoc-closed`: a request is 6 s, so a 51 s window reaches 8 requests a caller, and a
    caller goes round 8 quantiles: whatever the seed and wherever a window begins, any 8
    requests in a row of a caller are the same 8 (prompt, output) sizes."""
    cell = manifest.load_cell("keye-longdoc-closed4")
    mix = cell["traffic_file"]
    assert (mix["caller_sizes"], mix["caller_requests"], mix["caller_rounds"]) == ("quantiles", 8, 24)
    plan, other = (loadgen.make_plan(cell, 51.0, s, 19200) for s in (seed, seed + 1))
    assert len(plan) == cell["callers"] == 4 and all(len(e["requests"]) == 192 for e in plan)
    for mine, theirs in zip(plan, other):
        whole = sorted(_sizes(mine)[:8])
        assert all(sorted(_sizes(e)[k:k + 8]) == whole for e in (mine, theirs) for k in range(185))
        assert sorted(p for p, _ in whole) == [3072, 3753, 4315, 4846, 5410, 6075, 6984, 8192]
        assert sorted(o for _, o in whole) == [89, 123, 150, 177, 208, 245, 299, 413]
        assert _sizes(mine)[:8] != _sizes(theirs)[:8]  # the order is the seed's
    # the pairing of prompts and outputs is a caller's own, from `shape_seed`: the same for every seed
    assert len({tuple(sorted(_sizes(e)[:8])) for e in plan}) > 1
    assert [sorted(_sizes(e)[:8]) for e in plan] == [sorted(_sizes(e)[:8]) for e in other]


def test_a_configuration_may_name_one_draw_of_its_weights():
    assert serve_driver.weights_seed({}, 3_000_000_019) == 3_000_000_019 % 2 ** 31
    assert serve_driver.weights_seed({"weights": {"seed": 12}}, 3_000_000_019) == 12
    held = manifest.load_cell("kexaone-longrag-closed6")["config_file"]
    assert {serve_driver.weights_seed(held, s) for s in (1, 2_900_000_801)} == {2_540_000_222 % 2 ** 31}
    for name in sorted(os.listdir(os.path.join(manifest.BENCH_DIR, "configs"))):
        doc = json.load(open(os.path.join(manifest.BENCH_DIR, "configs", name)))
        # the two cells whose spread between seeds was the held experts' load by the draw (PR 54, PR 58)
        assert ("weights" in doc) == (name in ("k-exaone-236b-a23b-ep16-serve1.json",
                                               "nemotron-3-nano-30b-a3b-ep8-serve1.json"))
    held = manifest.load_cell("nemotron3nano-reason-closed8")["config_file"]
    assert {serve_driver.weights_seed(held, s) for s in (1, 2_900_000_801)} == {2_540_000_402 % 2 ** 31}


def test_closed_loop_keeps_each_caller_to_one_request_due_at_its_last_token():
    plan = _closed_plan(11)
    with StubServer(service_s=0.05) as stub:
        t_open = time.monotonic() + CLOSED["ramp_s"] + 0.05
        recs = loadgen.send({"traffic_file": CLOSED}, "127.0.0.1", stub.port, "/llm", plan, 1.0, t_open)
    t_close = t_open + 1.0
    assert stub.most_open == 3  # never more than `callers` in flight, and all of them at once
    assert all(r["error"] is None and r["status"] == 200 and len(r["tokens"]) == r["n_out"] for r in recs)
    by_caller = {c: [r for r in recs if r["caller"] == c] for c in range(3)}
    for c, mine in by_caller.items():
        assert mine[0]["due"] == pytest.approx(t_open + plan[c]["start"])
        # the next request is due the instant the last token of the one before arrived, and is that
        # caller's next of the plan
        assert all(b["due"] == a["token_times"][-1] for a, b in zip(mine, mine[1:]))
        assert [r["n_out"] for r in mine] == [q["max_new_tokens"] for q in plan[c]["requests"][:len(mine)]]
        assert [r["bench_id"] for r in mine] == [f"c{c}r{k}" for k in range(len(mine))]
        # about 1.2 s of 50 ms requests, each sent at once (the room is for a loaded test machine)
        assert 4 <= len(mine) <= 27 and all(0.0 <= r["send"] - r["due"] < 0.1 for r in mine)
        # nothing is sent once the window has closed; the request in flight then still counts
        assert all(r["due"] < t_close for r in mine) and mine[-1]["token_times"][-1] >= t_close
    assert max(t for t, _ in stub.arrived) < t_close + 0.1
    # and the reduction reads these records as an open schedule's
    ctx = {"records": recs, "t_open": t_open, "seconds": 1.0, "kind": "closed_loop", "setup_s": 1.0,
           "check": {"ok": True}}
    out = serve_driver.outcome(ctx)
    assert (out["attempted"], out["failed"], out["correct"]) == (len(recs), 0, True)
    e2e = serve_driver.end_to_end(ctx)
    assert e2e["serve_out_tok_s"] > 50 and 0.0 < manifest.load_reader("ttft")(ctx, q=50) < 0.2


def test_closed_loop_a_failed_request_frees_its_caller():
    plan = _closed_plan(12, callers=2)
    with StubServer(service_s=0.05, fail={"c0r1", "c1r0"}, cut={"c1r2"}) as stub:
        t_open = time.monotonic() + CLOSED["ramp_s"] + 0.05
        recs = loadgen.send({"traffic_file": CLOSED}, "127.0.0.1", stub.port, "/llm", plan, 0.5, t_open)
    failed = [r for r in recs if r["error"] is not None]
    assert sorted(r["bench_id"] for r in failed) == ["c0r1", "c1r0", "c1r2"]
    assert sorted(r["status"] for r in failed) == [200, 500, 500]
    for c in range(2):
        mine = [r for r in recs if r["caller"] == c]
        assert len(mine) >= 4  # it went on after the failure
        k = next(i for i, r in enumerate(mine) if r["error"] is not None)
        # the failed request had no last token: its caller was free when it returned
        assert mine[k]["send"] < mine[k + 1]["due"] < mine[k]["send"] + 0.25
    # a stream cut after its first token frees its caller when it ends, not at that token
    cut, after = [r for r in recs if r["caller"] == 1][2:4]
    assert cut["error"].startswith("1 tokens, asked for") and len(cut["token_times"]) == 1
    assert cut["token_times"][0] + 0.04 < after["due"] < cut["token_times"][0] + 0.3
    ctx = {"records": recs, "t_open": t_open, "seconds": 0.5, "kind": "closed_loop", "check": {"ok": True}}
    out = serve_driver.outcome(ctx)
    assert (out["failed"], out["correct"]) == (3, False) and out["attempted"] == len(recs)


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
    assert DENSE.param_count(c) == 3 * per_layer + 2 * 32 * 8 + 8
    # forward, one sequence of 5: matmuls 2 flops a weight a token; attention 4*t*t*d*h
    matmul_w = 64 + 32 + 32 + 64 + 3 * 128
    fwd = 5 * 2 * matmul_w * 3 + 4 * 5 * 5 * 4 * 2 * 3 + 5 * 2 * 8 * 32
    assert DENSE.train_flops_per_step(c, batch=1, seq=5) == 3 * fwd
    assert DENSE.train_flops_per_step(c, batch=4, seq=5) == 4 * 3 * fwd
    # decode: all weights but the embedding table, its 2 rows, and K and V for 2 slots of 10
    weights = DENSE.param_count(c) - 32 * 8 + 2 * 8
    assert DENSE.decode_step_bytes(c, slots=2, t_max=10) == 2 * (weights + 2 * 3 * 2 * 10 * 1 * 4)


def test_peaks_table_and_mfu():
    assert stats.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        stats.peaks("TPU v9 imaginary")
    assert stats.mfu_percent(1e9, 197e3, 1, "TPU v5 lite") == pytest.approx(100.0)
    cell = manifest.load_cell("train-fsdp4")
    per_token = manifest.reference_of(cell).train_flops_per_step(cell["config_file"]["config"], 8, 4096) / (8 * 4096)
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


LAYOUT = "{1,0:T(8,128)(2,1)S(1)}"
HLO_LINES = [  # (an operation as this runtime's trace names it: its whole HLO line; whether it IS a collective)
    (f"%fusion.102 = bf16[4096,32768]{LAYOUT} fusion(f32[2,4096]{LAYOUT} %get-tuple-element.7, "
     f"bf16[4096,14336]{LAYOUT} %all-gather.3), kind=kOutput, calls=%fused_computation.102", False),
    (f"%all-gather.3 = bf16[4096,14336]{LAYOUT} all-gather(bf16[1024,14336]{LAYOUT} %param.4), "
     "channel_id=7, replica_groups=[1,4]<=[4], dimensions={0}", True),
    (f"%all-reduce-start.1 = f32[4096]{{0:T(1024)}} all-reduce-start(f32[4096]{{0:T(1024)}} %fusion.9), "
     "channel_id=2, to_apply=%add", True),
    (f"%all-reduce-done.1 = f32[4096]{{0:T(1024)}} all-reduce-done(f32[4096]{{0:T(1024)}} %all-reduce-start.1)", True),
    (f"%reduce-scatter.12 = f32[1024,14336]{LAYOUT} reduce-scatter(f32[4096,14336]{LAYOUT} %fusion.77), "
     "dimensions={0}, to_apply=%add", True),
    (f"%all-to-all.2 = (bf16[8,64]{LAYOUT}, bf16[8,64]{LAYOUT}) all-to-all(bf16[8,64]{LAYOUT} %a, bf16[8,64]{LAYOUT} %b)", True),
    (f"%collective-permute.5 = bf16[2,4096]{LAYOUT} collective-permute(bf16[2,4096]{LAYOUT} %fusion.1), "
     "source_target_pairs={{0,1},{1,2}}", True),
    # a result that is a tuple, and a loop whose operand is a tuple that holds a collective's result
    (f"%multiply_add_fusion.2 = (f32[12,14336,1024]{LAYOUT}, f32[12,14336,1024]{LAYOUT}) fusion("
     f"f32[12,14336,1024]{LAYOUT} %reduce-scatter.12, f32[] %c), kind=kLoop", False),
    (f"%while.4 = (s32[], bf16[8,128]{LAYOUT}) while((s32[], bf16[8,128]{LAYOUT}) %tuple.all-gather.1), "
     "condition=%cond, body=%body", False),
    # the TPU compiler's reduce-scatter: a custom fusion that calls the collective (my chip run, PR 54)
    (f"%fusion.89 = bf16[1024,32768]{LAYOUT} fusion(bf16[4096,32768]{LAYOUT} %fusion.102), kind=kCustom, "
     "calls=%all-reduce-scatter.85", True),
    # a collective that another pass renamed is known by its opcode, one cut short by its name
    (f"%ag.7 = bf16[4096,14336]{LAYOUT} all-gather(bf16[1024,14336]{LAYOUT} %param.4), dimensions={{0}}", True),
    ("%all-gather-start.3 = (bf16[1024,14336]{1,0:T(8,128)(2,1)}, bf16[4096,143", True),
    ("%fusion.2993 = (bf16[4096]{0:T(1024)(128)(2,1)}, bf16[2,4096]{1,0:T(2,128)(2,1)S(1)}, bf16[2,409", False),
    # a name alone, as the recorded traces and the tests by hand give it
    ("all-gather-done.2", True), ("all-reduce.7", True), ("fusion.1", False),
    # what reads an asynchronous collective's result is compute as well
    ("%fusion.9 = f32[4096]{0:T(1024)} fusion(f32[4096]{0:T(1024)} %all-reduce-done.1), kind=kLoop, calls=%fused_computation.9", False),
]


@pytest.mark.parametrize("line, collective", HLO_LINES, ids=[ln.split(" = ")[0][:24] + f"-{i}" for i, (ln, _) in enumerate(HLO_LINES)])
def test_a_collective_is_known_by_what_the_operation_is_not_by_its_operands(line, collective):
    """PR 53's traced pair read `collective_exposed.train` 5.7 -> 18.3 on a change
    that added no collective: the recomputed FFN products took what an
    all-gather had brought as an operand, and the pattern was searched in the
    whole line.  The name before " = ", the opcode and, for a fusion, the
    computation it calls decide (PR 54)."""
    assert trace_reduce.is_collective(line) is collective
    assert bool(trace_reduce.COLLECTIVE.search(line)) or not collective  # the old search saw every one, and more


def test_the_collectives_share_and_the_scopes_go_through_the_one_matcher():
    product, gather = HLO_LINES[0][0], HLO_LINES[1][0]
    ops = [[0, 100, gather, "ffn"], [100, 300, product, "ffn"], [400, 100, "%fusion.5 = f32[8]{0} fusion()", "attn.core"]]
    events = {"devices": {"/device:TPU:0": [e[:3] for e in ops]}, "host": []}
    assert trace_reduce.collective_percent(events) == pytest.approx(100 * 100 / 500)
    by_scope = program_trace.time_by_scope({"spans": [], "ops": {"/device:TPU:0": ops}})
    assert by_scope == {"collective": 100.0, "ffn": 300.0, "attn.core": 100.0}
    # the parent's search gave the product to the collectives: 80% exposed, `ffn` nothing
    assert sum(e[1] for e in ops if trace_reduce.COLLECTIVE.search(e[2])) == 400


# -- the reduction from records to metrics -----------------------------------


def _ctx(kind="open_poisson"):
    t0 = 100.0
    rec = lambda due, times, err=None, n=3: dict(
        id=0, bench_id="r0", due=t0 + due, send=t0 + due + 0.001, status=200, error=err,
        tokens=[1] * len(times), token_times=[t0 + t for t in times], n_prompt=5, n_out=n)
    return {
        "t_open": t0, "seconds": 10.0, "kind": kind, "setup_s": 50.0,
        "check": {"ok": True},
        "records": [
            rec(-1.0, [-0.5, 0.5, 1.0]),      # due in the ramp: its in-window tokens count
            rec(1.0, [1.5, 2.0, 4.0]),
            rec(2.0, [], err="timeout"),      # never answered: misses at the window length
            rec(9.0, [9.5, 10.5, 11.0]),      # ends after the window closed
        ],
    }


@pytest.mark.parametrize("kind", ["open_poisson", "closed_loop"], ids=["open", "closed"])
def test_serve_reduction_counts_what_the_window_holds(kind):
    """A closed loop's `due` is the instant its caller was free, and everything
    reads it as it reads an open schedule's due time."""
    ctx = _ctx(kind)
    assert serve_driver.ttfts(ctx) == pytest.approx([0.5, 10.0, 0.5])
    assert sorted(serve_driver.token_gaps(ctx)) == pytest.approx([0.5, 0.5, 1.0, 2.0])
    assert serve_driver.tokens_in_window(ctx) == 2 + 3 + 1
    e2e = serve_driver.end_to_end(ctx)
    assert e2e["serve_out_tok_s"] == pytest.approx(0.6) and e2e["setup_s"] == 50.0
    assert e2e["gap_p50_s"] == pytest.approx(0.75) and e2e["gap_mean_s"] == pytest.approx(4.0 / 4)
    assert manifest.load_reader("ttft")(ctx, q=50) == pytest.approx(0.5)
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
    base = dict(warm=[(0, 1, 10.90), (0, 1, 10.8)], steps=[(0, 1, 10.7)] * 5, ref_loss=10.899,
                check={"tolerance": DENSE.LOSS_TOL})
    assert train_driver.outcome(copy.deepcopy(base))["correct"] is True
    off = dict(copy.deepcopy(base), ref_loss=10.9 + 2 * DENSE.LOSS_TOL)
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
    for c in DOC["configs"]:
        assert c["file"].startswith("benchmarks/") and os.path.isfile(os.path.join(ROOT, c["file"]))
        assert not [k for k in c["reduced"] if any(w in k for w in WIDTH)]


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_resolves_by_name(cell):
    c = manifest.load_cell(cell)
    entry = next(w for w in DOC["workloads"] if w["name"] == cell)
    assert (c["config"], c["traffic"], c["chips"], c["why"]) == (
        entry["config"], entry["traffic"], entry["chips"], entry["why"])
    # the configuration against its own source: `published` is the source's values
    # verbatim, `config` what runs here
    cfg = c["config_file"]
    assert {"source", "reference", "published", "config", "reduced", "assumed", "departures",
            "deployment"} <= set(cfg)
    config, published = cfg["config"], cfg["published"]
    assert set(published) <= set(config)
    assert {k for k in published if config[k] != published[k]} == set(cfg["reduced"])
    assert set(config) - set(published) == set(cfg["assumed"])
    assert not [k for k in cfg["reduced"] if any(w in k for w in WIDTH)]
    listed = next(x for x in DOC["configs"] if x["name"] == c["config"])
    assert listed["source"] == cfg["source"] and set(listed["reduced"]) == set(cfg["reduced"])
    # its architecture's file loads, has the whole interface, and maps to fields the program has
    ref = manifest.reference_of(c)
    assert all(hasattr(ref, name) for name in manifest.REFERENCE_INTERFACE)
    assert TransformerConfig(**ref.program_config(cfg)).d_model == config["hidden_size"]
    assert ref.param_count(config) > 0
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
    # ... and a closed loop of other lengths and another number of callers
    closed = json.loads((bench / "traffic" / "chat-closed.json").read_text())
    closed.update(output_len={"dist": "uniform", "min": 32, "max": 32}, caller_requests=12)
    (bench / "traffic" / "chat-closed-short.json").write_text(json.dumps(closed))
    (bench / "cells" / "chat-closed-short3.json").write_text(json.dumps(
        {"config": "mistral-7b-v0.3-serve1", "traffic": "chat-closed-short", "chips": 1,
         "callers": 3, "why": "three callers, 32 tokens an answer"}))
    cell = manifest.load_cell("chat-default-cache", str(bench))
    assert cell["traffic_file"]["deployment"]["prefix_cache_entries"] == 8
    schedule = loadgen.make_plan(cell, 10.0, 1, 32768)
    assert len(schedule) == round(0.6 * 10.0) + round(0.6 * mix["ramp_s"])
    plan = loadgen.make_plan(manifest.load_cell("chat-closed-short3", str(bench)), 10.0, 1, 32768)
    assert [len(c["requests"]) for c in plan] == [12, 12, 12]
    assert {r["max_new_tokens"] for c in plan for r in c["requests"]} == {32}
    ctx = {"replica": {"admits": [(0, 0.1, 2, 10, 0), (1, 0.1, 1, 5, 0)]}, "device": {}}
    got = manifest.read_layer_metrics("chat-default-cache", ctx, str(bench))
    assert got["admits_total"] == {"value": 3.0, "unit": "count"}
    assert "admits_total" not in {m["name"] for m in manifest.layer_metrics_for("chat-steady", str(bench))}
    assert all(p.read_bytes() == data for p, data in before.items())  # no file was edited


# -- families: one entry a metric, a cell joins from its own file (PR 38) -------

def _renames(pr):
    with open(os.path.join(DATA, f"per_layer_renames_pr{pr}.json")) as f:
        return json.load(f)


# PR 38 retired the copies a closed cell; PR 54 those a configuration (a `.swa`,
# `.sambay`, `.nemotronh` of what another cell's file read already)
RENAMES = {38: _renames(38), 54: _renames(54)}
SAME_KEYS = ("reader", "args", "unit", "layer", "moves", "source")
# the committed files' metrics by cell and name, read once for the cases below
METRICS_OF = {cell: {m["name"]: m for m in manifest.layer_metrics_for(cell)} for cell in CELLS}


@pytest.mark.parametrize("row", RENAMES[38] + RENAMES[54],
                         ids=[r["name"] for r in RENAMES[38]] + [f"{r['name']}@{r['cell']}" for r in RENAMES[54]])
def test_a_retired_name_is_read_under_its_new_one(row):
    """PR 38 retired 58 per-cell copies and PR 54 24 per-configuration ones.  In
    the cell each was read in, the name the table gives now reads the same
    quantity: the same reader, arguments, unit, layer, moved metric and source
    that the retired file had.  Two of PR 54's kept files took other arguments
    to serve every cell (`args_now`; the test after the next holds that they
    read the same values)."""
    assert set(row) <= {"name", "cell", "now", "args_now", *SAME_KEYS}
    by_name = METRICS_OF[row["cell"]]
    assert row["name"] not in by_name and row["name"] not in {m["name"] for m in DOC["per_layer"]}
    assert not os.path.exists(os.path.join(manifest.BENCH_DIR, "layer_metrics", row["name"] + ".json"))
    now = by_name[row["now"]]
    was = dict(row, args=row["args_now"]) if "args_now" in row else row
    assert {k: now.get(k) for k in SAME_KEYS} == {k: was.get(k) for k in SAME_KEYS}
    # no second file of the cell reads the same thing
    assert [m["name"] for m in by_name.values()
            if (m["reader"], m.get("args")) == (now["reader"], now.get("args"))] == [row["now"]]


def test_the_rename_tables_are_whole():
    assert len(RENAMES[38]) == 58 == len({r["name"] for r in RENAMES[38]})
    assert {r["cell"] for r in RENAMES[38]} == {"olmoe-closed6", "jamba-closed6", "sdar-closed6"}
    assert len({r["now"] for r in RENAMES[38]}) == 15 + 1 + 2 + 5
    # PR 54: 24 names, each read in one cell, to the 16 that stay; a kept name is one of its copies'
    assert len(RENAMES[54]) == 24 == len({r["name"] for r in RENAMES[54]}) and len({r["now"] for r in RENAMES[54]}) == 16
    assert {r["cell"] for r in RENAMES[54]} == {
        "axk1-rag-closed6", "kexaone-longrag-closed6", "phi4flash-reason-closed8", "nemotron3nano-reason-closed8"}
    assert {r["now"] for r in RENAMES[54] if "args_now" in r} == {"ssm_scan_share.ssm", "held_assignments_share.mla"}
    assert not {r["name"] for r in RENAMES[54]} & {r["now"] for r in RENAMES[38] + RENAMES[54]}
    # a metric is one entry and one file (74 of each when PR 38's copies went, 127 before PR 54's, 103 after,
    # 111 with PR 56's family): at least those that were there then, at most what the list may hold
    files = [f for f in os.listdir(os.path.join(manifest.BENCH_DIR, "layer_metrics")) if f.endswith(".json")]
    assert 74 <= len(DOC["per_layer"]) == len(files) <= 128


def _hand_ctx(cell):
    """A traced slice by hand in the cell's own context: operations under every
    scope of every served state-space kind (a cell's trace holds only the scopes
    its reference names: program_trace.known_names), and steps that count a held
    share's assignments."""
    c = manifest.load_cell(cell)
    known = program_trace.known_names({"cell": c})[0]
    scopes = ["ssm.in", "ssm.conv", "ssm.scan/ssm.scan.chunk", "ssm.scan/ssm.scan.carry", "ssm.scan/ssm.norm", "ssm.state",
              "ssm.out", "ffn", "attn.core"]
    ops = [[100.0 * i, 10.0 * (i + 1), "%fusion.1", program_trace.scope_of(f"jit(step)/while/body/{sc}/mul", known)]
           for i, sc in enumerate(scopes)]
    spans = [[1, 0.0, 8e6, "llm.step", {"live": 6, "moe_rows": 6, "moe_held_assignments": 5.0}],
             [1, 9e6, 8e6, "llm.step", {"live": 5, "moe_rows": 5, "moe_held_assignments": 3.0}]]
    return {"cell": c, "device": {}, "program_trace": {"spans": spans, "ops": {"/device:TPU:0": ops}}}


@pytest.mark.parametrize("name, cell, was", [
    (r["now"], r["cell"], r["args"]) for r in RENAMES[54] if "args_now" in r
] + [("ssm_scan_share.ssm", "jamba-closed6", {"scopes": ["ssm.conv", "ssm.scan", "ssm.state"]}),
     ("held_assignments_share.mla", "axk1-rag-closed6",
      {"span": "llm.step", "over": "moe_held_assignments", "under": "moe_rows", "scale": 12.5})],
    ids=lambda v: v if isinstance(v, str) else "")
def test_a_merged_metric_reads_what_each_cells_copy_read(name, cell, was):
    """The two files that serve every cell by other arguments than their copies
    had: the scopes of every state-space kind (a cell's operations carry its own
    reference's alone), and 100 over the configuration's experts a token (12.5
    at 8, 16.666666666666668 at 6: the copies' numbers to the last digit)."""
    now = METRICS_OF[cell][name]
    read = manifest.load_reader(now["reader"])
    ctx = _hand_ctx(cell)
    value = read(ctx, **now["args"])
    assert value == read(ctx, **was) and value > 0  # equal, not close
    if name == "ssm_scan_share.ssm":
        mamba2 = "ssm.norm" in manifest.reference_of(ctx["cell"]).SCOPES
        assert value == pytest.approx(100 * (20 + 30 + 40 + 50 + 60) / 450)  # all but `ssm.in`, `ssm.out`, `ffn`, `attn.core`
        by_scope = program_trace.time_by_scope(ctx["program_trace"])
        assert ("ssm.scan.chunk" in by_scope) == ("ssm.norm" in by_scope) == mamba2
    else:
        per_tok = ctx["cell"]["config_file"]["config"]["num_experts_per_tok"]
        assert value == (100.0 / per_tok) * 8.0 / 11.0 and was["scale"] == 100.0 / per_tok


@pytest.mark.parametrize("cell", CELLS)
def test_a_cell_reads_a_quantity_under_one_name(cell):
    """No two of a cell's files are one reader with one set of arguments: a copy
    under a second suffix is an entry of the 128 spent on nothing (PR 38, PR 54)."""
    seen = {}
    for name, m in METRICS_OF[cell].items():
        seen.setdefault(json.dumps([m["reader"], m.get("args")], sort_keys=True), []).append(name)
    assert not {k: v for k, v in seen.items() if len(v) > 1}


@pytest.mark.parametrize("entry", DOC["per_layer"], ids=[m["name"] for m in DOC["per_layer"]])
def test_an_entrys_workloads_are_what_the_manifest_resolves(entry):
    resolved = [c for c in CELLS if entry["name"] in METRICS_OF[c]]
    assert entry.get("workloads", CELLS) == resolved and resolved
    assert os.path.isfile(os.path.join(manifest.BENCH_DIR, "layer_metrics", entry["name"] + ".json"))


def _toy_bench(tmp_path, metrics, families=None):
    """A benchmarks/ of two cells and the given metric files."""
    bench = tmp_path / "benchmarks"
    for d in ("cells", "layer_metrics"):
        (bench / d).mkdir(parents=True)
    (bench / "cells" / "one.json").write_text(json.dumps(
        {"config": "c", "traffic": "t", "chips": 1, **({} if families is None else {"families": families})}))
    (bench / "cells" / "two.json").write_text(json.dumps({"config": "c", "traffic": "t", "chips": 1}))
    for name, keys in metrics.items():
        (bench / "layer_metrics" / (name + ".json")).write_text(json.dumps(dict(unit="ms", reader="r", **keys)))
    return str(bench)


@pytest.mark.parametrize("case", ["cells", "family", "neither", "both", "unknown_family", "bad_name"])
def test_where_a_metric_is_read(tmp_path, case):
    names = lambda cell, bench: [m["name"] for m in manifest.layer_metrics_for(cell, bench)]
    if case == "cells":
        bench = _toy_bench(tmp_path, {"b": {"cells": ["one"]}, "a": {"cells": ["one", "two"]}, "c": {"cells": ["two"]}})
        assert names("one", bench) == ["a", "b"] and names("two", bench) == ["a", "c"]
    elif case == "family":  # the cell's own file enrols it; a cell without the key joins none
        bench = _toy_bench(tmp_path, {"a.x": {"family": "x"}, "b.y": {"family": "y"}, "c.x": {"family": "x"}},
                           families=["x"])
        assert names("one", bench) == ["a.x", "c.x"] and names("two", bench) == []
    elif case == "neither":
        bench = _toy_bench(tmp_path, {"all": {}, "a.x": {"family": "x"}}, families=[])
        assert names("one", bench) == ["all"] == names("two", bench)
    elif case == "both":
        bench = _toy_bench(tmp_path, {"a.x": {"family": "x", "cells": ["one"]}}, families=["x"])
        with pytest.raises(ValueError, match=r"a\.x\.json has both `cells` and `family`"):
            manifest.layer_metrics_for("two", bench)
    elif case == "unknown_family":
        bench = _toy_bench(tmp_path, {"a.x": {"family": "x"}}, families=["x", "z"])
        with pytest.raises(ValueError, match=r"cells/one\.json names families no metric has: \['z'\]"):
            manifest.layer_metrics_for("one", bench)
        assert names("two", bench) == []
    else:
        bench = _toy_bench(tmp_path, {"a.x": {"family": "x"}}, families=["x", "no such/name"])
        with pytest.raises(ValueError, match="not a valid name: 'no such/name'"):
            manifest.layer_metrics_for("one", bench)
        bench = _toy_bench(tmp_path / "2", {"a": {"family": "x y"}})
        with pytest.raises(ValueError, match="not a valid name: 'x y'"):
            manifest.layer_metrics_for("two", bench)


def test_a_closed_cell_joins_the_families_from_its_own_file(tmp_path):
    """What the next `model_config` PR depends on: a fifth closed serving cell
    reads the shared sixteen by naming their families in its own new file, and
    brings one metric of its own; no file that was there changed."""
    bench = tmp_path / "benchmarks"
    shutil.copytree(manifest.BENCH_DIR, bench, ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}
    (bench / "cells" / "toy-closed6.json").write_text(json.dumps(
        {"config": "mistral-7b-v0.3-serve1", "traffic": "chat-closed", "chips": 1, "callers": 6,
         "families": ["closed", "causal"], "why": "a fifth closed cell"}))
    (bench / "layer_metrics" / "latent_share.toy.json").write_text(json.dumps(
        {"unit": "%", "layer": "decode/prefill programs (models/generate.py)", "moves": "serve_out_tok_s",
         "source": "device_trace", "cells": ["toy-closed6"], "reader": "scope_share",
         "args": {"scopes": ["attn.latent"]}}))
    got = {m["name"]: m for m in manifest.layer_metrics_for("toy-closed6", str(bench))}
    chat = {m["name"]: m for m in manifest.layer_metrics_for("chat-closed6", str(bench))}
    assert list(got) == sorted(got)
    shared = {n for n, m in got.items() if m.get("family") == "closed"}
    assert {n[: -len(".closed")] for n in shared} >= {
        "admit_ms_mean", "admit_prefill_ms_mean", "submit_lock_wait_ms_mean", "step_upload_ms_p50",
        "decode_step_ms_p50", "gap_p50_s", "gap_p99_s", "ttft_p50_s", "gen_late_p99_ms", "front_overhead_p50_ms",
        "device_idle", "idle_in.admit", "idle_in.readback", "idle_in.step_host", "idle_in.between_steps",
        "stream_first_token_ms_p50", "stream_write_wait_ms_mean"}
    assert "decode_batch_mean.closed" in got and got["decode_batch_mean.closed"]["family"] == "causal"
    assert {"latent_share.toy", "hbm_peak_gb"} <= set(got)
    # they are the files chat-closed6 reads, the same readers with the same arguments
    assert {n: m for n, m in got.items() if n != "latent_share.toy"} == chat
    assert "latent_share.toy" not in chat
    # the cells that were there read what they read
    for cell in CELLS:
        assert [m["name"] for m in manifest.layer_metrics_for(cell, str(bench))] == list(METRICS_OF[cell])
    assert all(p.read_bytes() == data for p, data in before.items())  # no file was edited


def test_a_training_cell_joins_the_family_train_from_its_own_file(tmp_path):
    """What the next training configuration depends on (PR 54): a second training
    cell reads the step's share of the peak, the scope shares, the kernels', the
    collectives' and the controller's numbers by naming `train` in its own new
    file, with a mix of its own mesh, and brings one metric of its own
    mechanism; no file that was there changed."""
    bench = tmp_path / "benchmarks"
    shutil.copytree(manifest.BENCH_DIR, bench, ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}
    mix = json.loads((bench / "traffic" / "train-steps.json").read_text())
    mix["job"].update(batch=4, seq=8192, mesh={"ep": 4})
    (bench / "traffic" / "toy-train-steps.json").write_text(json.dumps(mix))
    (bench / "cells" / "toy-train-ep4.json").write_text(json.dumps(
        {"config": "mistral-7b-v0.3-fsdp4", "traffic": "toy-train-steps", "chips": 4, "families": ["train"],
         "why": "a second training cell"}))
    bare = [m["name"] for m in manifest.layer_metrics_for("toy-train-ep4", str(bench))]
    assert bare == list(METRICS_OF["train-fsdp4"]) and len(bare) == 13
    (bench / "layer_metrics" / "exchange_share.toy.json").write_text(json.dumps(
        {"unit": "%", "layer": "expert path (parallel/moe.py)", "moves": "train_tok_s", "source": "device_trace",
         "cells": ["toy-train-ep4"], "reader": "scope_share", "args": {"scopes": ["moe.exchange"]}}))
    got = {m["name"]: m for m in manifest.layer_metrics_for("toy-train-ep4", str(bench))}
    fsdp = {m["name"]: m for m in manifest.layer_metrics_for("train-fsdp4", str(bench))}
    train = {n for n, m in got.items() if m.get("family") == "train"}
    assert train == {"mfu.train", "step_ms_p50", "attn_share.train", "ffn_share.train", "head_loss_share.train",
                     "optimizer_share.train", "flash_share.train", "collective_exposed.train", "device_idle.train",
                     "fit_overhead_s", "fit_restarts", "worker_stall_max_s"}
    assert set(got) == train | {"hbm_peak_gb", "exchange_share.toy"}
    # they are the files train-fsdp4 reads, the same readers with the same arguments
    assert {n: m for n, m in got.items() if n != "exchange_share.toy"} == fsdp and "exchange_share.toy" not in fsdp
    assert manifest.load_cell("train-fsdp4", str(bench))["families"] == ["train"]
    # the mix is the driver's: the mesh, the batch and the sequence are the file's own
    cell = manifest.load_cell("toy-train-ep4", str(bench))
    assert cell["traffic_file"]["driver"] == "train_driver" and cell["traffic_file"]["job"]["mesh"] == {"ep": 4}
    # no serving cell reads a training metric, and the cells that were there read what they read
    for name in CELLS:
        names = [m["name"] for m in manifest.layer_metrics_for(name, str(bench))]
        assert names == list(METRICS_OF[name]) and (name == "train-fsdp4" or not train & set(names))
    assert all(p.read_bytes() == data for p, data in before.items())  # no file was edited


def test_a_configuration_of_another_architecture_is_added_as_files_only(tmp_path):
    """What a `model_config` PR does: a reference, a configuration of other
    widths that names it, a cell and a metric over its own scope, as new files in
    a copy of benchmarks/; every part of the harness that knows an architecture
    then goes through the new file, and no file that was there changed."""
    import jax
    import jax.numpy as jnp

    from cluster_anywhere_tpu.llm.continuous import ContinuousBatcher
    from cluster_anywhere_tpu.models.transformer import init_params

    bench = tmp_path / "benchmarks"
    shutil.copytree(manifest.BENCH_DIR, bench, ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}
    shutil.copy(os.path.join(DATA, "toy_moe.py"), bench / "references" / "toy_moe.py")
    published = dict(hidden_size=48, intermediate_size=96, num_hidden_layers=4, num_attention_heads=4,
                     num_key_value_heads=2, num_experts=4, vocab_size=256, max_position_embeddings=256,
                     rope_theta=10000.0)
    (bench / "configs" / "toy-moe-serve1.json").write_text(json.dumps({
        "source": "https://example.org/toy-moe/config.json", "reference": "toy_moe", "published": published,
        "config": dict(published, num_hidden_layers=2, head_dim=12),
        "reduced": {"num_hidden_layers": {"published": 4, "here": 2, "why": "a test's size"}},
        "assumed": {"head_dim": "hidden_size / num_attention_heads"}, "departures": {}, "deployment": "a test"}))
    (bench / "cells" / "toy-chat.json").write_text(json.dumps(
        {"config": "toy-moe-serve1", "traffic": "chat-steady", "chips": 1, "rate": 0.6, "why": "a test"}))
    (bench / "layer_metrics" / "router_share.toy.json").write_text(json.dumps(
        {"unit": "%", "layer": "decode/prefill programs (models/generate.py)", "moves": "gap_p50_s",
         "source": "device_trace", "cells": ["toy-chat"], "reader": "scope_share",
         "args": {"scopes": ["moe.router"]}}))

    cell = manifest.load_cell("toy-chat", str(bench))
    toy = manifest.reference_of(cell)
    assert toy is manifest.load_reference("toy_moe", str(bench)) and toy is not DENSE
    assert toy.__file__ == str(bench / "references" / "toy_moe.py")
    # the mapping: a published key the dense mapping does not know reaches the program's field
    fields = toy.program_config(cell["config_file"], vocab_size=256)
    assert (fields["n_experts"], fields["d_model"], fields["n_layers"]) == (4, 48, 2)
    assert "n_experts" not in DENSE.program_config(manifest.load_cell("chat-steady")["config_file"])
    # the scope: an operation under the toy's own scope has it in the toy's cell, and not in a dense one
    router_op = "jit(_decode_step_rowpos)/while/body/closed_call/ffn/moe.router/dot_general:"
    scopes, kernels = program_trace.known_names({"cell": cell})
    assert "moe.router" in scopes and kernels == program_trace.KERNELS
    assert program_trace.scope_of(router_op, scopes) == "moe.router"
    assert program_trace.scope_of(router_op) == "ffn"
    ops = [[0, 30, "%fusion.1", program_trace.scope_of(router_op, scopes)],
           [40, 70, "%fusion.2", program_trace.scope_of("jit(f)/while/body/ffn/dot_general:", scopes)]]
    ctx = {"cell": cell, "device": {}, "program_trace": {"spans": [], "ops": {"/device:TPU:0": ops}}}
    got = manifest.read_layer_metrics("toy-chat", ctx, str(bench))
    assert got["router_share.toy"] == {"value": pytest.approx(30.0), "unit": "%"}
    assert "router_share.toy" not in {m["name"] for m in manifest.layer_metrics_for("chat-steady", str(bench))}
    # the check: a tiny batcher built from the configuration, its streams held to the toy's forward
    cfg = TransformerConfig(**fields, dtype=jnp.float32, param_dtype=jnp.float32)
    cb = ContinuousBatcher(init_params(jax.random.key(7), cfg), cfg, slots=4, t_max=96,
                           prefill_buckets=(32, 64))
    rng = np.random.default_rng(2)
    reqs = [cb.submit(rng.integers(0, 256, n), max_new_tokens=6) for n in (9, 21, 40)]
    cb.pump()
    streams = [{"prompt_ids": r.prompt_ids.tolist(), "served": list(r.out_tokens)} for r in reqs]
    rep = check_serving(cb, streams, toy)
    assert rep["ok"] and rep["logit_tolerance"] == toy.LOGIT_TOL and rep["positions"] == 18, rep
    with pytest.raises(KeyError):  # the dense reference knows no such block: nothing fell back to it
        check_serving(cb, streams, DENSE)
    # a token the toy ranks low fails the toy's own bound
    streams[0]["served"][-1] = int(np.argmin(np.asarray(toy.forward(
        cb.params, np.asarray(streams[0]["prompt_ids"] + streams[0]["served"][:-1]), cfg))[-1]))
    assert not check_serving(cb, streams, toy)["ok"]
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
