#!/usr/bin/env python3
"""The builder's client for the serve front's phases (ISSUE 36): what the
benchmark's `loadgen` cannot do, because it sends no `traceparent` and is not
this PR's to edit.

    chiprun --timeout 3000 -- python3 scripts/serve_phase_sweep.py --seed 3600000001

One cluster, one deployment of `chat-closed6`'s configuration through the
benchmark's own set-up (`benchmarks/harness/serve_driver.py`: weights from the
seed, warm-up, check against the reference), then window after window of the
benchmark's own plans (`loadgen.make_plan`), sent by this file's client, which
is `loadgen.stream_request` plus one header:

  overhead   `chat-closed6` and `chat-steady` at their cells' load, every
             request traced or none, three plan seeds each, in turn
  profile    one traced `chat-closed6` window with a 4 s profile of the
             replica at its end: the clock beacons (`llm.pump.sync`), and the
             token's way from `llm.step`'s end to this client's socket
  sweep      a closed loop of 6, 8, 10, 12, 16 and 24 callers, 40 s each,
             every request traced

After each window it reads what the program wrote: `state.serve_requests()`
(the ring's spans by request, with self times), the window's share of
`ca_serve_phase_seconds` (a difference of two snapshots), and the proxy's
three gauges sampled twice a second.  Everything goes to
`chiprun_out/pr36_phases.json`; the last line of standard output is a
summary.  This process never touches JAX's devices; no chip is exit code 1.
"""

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import copy  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.harness import cluster, loadgen, manifest, serve_driver  # noqa: E402
from benchmarks.harness.stats import percentile  # noqa: E402

HOST, ROUTE = serve_driver.HOST, serve_driver.ROUTE
GAUGES = ("ca_serve_proxy_streams_open", "ca_serve_proxy_executor_pending",
          "ca_serve_proxy_executor_threads")


def say(**fields) -> None:
    print("[phases] " + json.dumps(fields, default=str), file=sys.stderr, flush=True)


# -- the client ---------------------------------------------------------------


def make_client(traced: bool):
    """`loadgen.stream_request` to the letter, plus a `traceparent` of the
    request's own where `traced`; the trace id is kept in the record."""

    async def stream_request(host, port, path, body, rec, timeout_s):
        rec.update(tokens=[], token_times=[], status=None, error=None)
        payload = json.dumps(body).encode()
        header = ""
        if traced:
            rec["trace"] = os.urandom(16).hex()
            header = f"traceparent: 00-{rec['trace']}-{os.urandom(8).hex()}-01\r\n"
        head = (
            f"POST {path} HTTP/1.1\r\nHost: {host}:{port}\r\n"
            "Content-Type: application/json\r\nAccept: text/event-stream\r\n"
            f"{header}Content-Length: {len(payload)}\r\nConnection: close\r\n\r\n"
        ).encode()
        writer = None

        async def talk():
            nonlocal writer
            rec["send"] = time.monotonic()
            reader, writer = await asyncio.wait_for(
                asyncio.open_connection(host, port, limit=1 << 20), loadgen.CONNECT_TIMEOUT_S)
            writer.write(head + payload)
            # the whole of talk() runs under the wait_for below
            # ca-lint: ignore[async-unbounded-io]
            await writer.drain()
            # ca-lint: ignore[async-unbounded-io]
            rec["status"] = int((await reader.readline()).split()[1])
            # ca-lint: ignore[async-unbounded-io]
            while (await reader.readline()) not in (b"\r\n", b"\n", b""):
                pass
            if rec["status"] != 200:
                rec["error"] = (await reader.read()).decode("utf-8", "replace")[:500]
                return
            while True:
                # ca-lint: ignore[async-unbounded-io]
                line = await reader.readline()
                if not line:
                    return
                if line.startswith(b"data:"):
                    now = time.monotonic()
                    event = json.loads(line[5:])
                    if "error" in event:
                        rec["error"] = str(event["error"])[:500]
                        return
                    rec["tokens"].append(event["token_id"])
                    rec["token_times"].append(now)

        try:
            await asyncio.wait_for(talk(), timeout_s)
        except (asyncio.TimeoutError, OSError, ValueError, IndexError) as e:
            rec["error"] = f"{type(e).__name__}: {e}"
        finally:
            if writer is not None:
                writer.close()
        if rec["error"] is None and len(rec["tokens"]) != body["max_new_tokens"]:
            rec["error"] = f"{len(rec['tokens'])} tokens, asked for {body['max_new_tokens']}"
        return rec

    return stream_request


# -- what the program wrote ---------------------------------------------------


def phase_cells():
    """{phase: (bounds, buckets, count, sum)} of ca_serve_phase_seconds now."""
    from cluster_anywhere_tpu.util.metrics import get_metrics_snapshot, merged_histogram
    from cluster_anywhere_tpu.util.state import _cells_by_tag

    rec = get_metrics_snapshot().get("ca_serve_phase_seconds")
    out = {}
    for phase, cells in _cells_by_tag(rec, "phase").items():
        bounds, buckets, count = merged_histogram({"data": cells})
        out[phase] = (bounds, buckets, count, sum(c.get("sum", 0.0) for c in cells.values()))
    return out


def phase_window(before, after):
    """Whole-window p50 / p99 / mean of every phase between two snapshots, ms."""
    from cluster_anywhere_tpu.util.metrics import histogram_quantile

    out = {}
    for phase, (bounds, buckets, count, total) in sorted(after.items()):
        _, b0, c0, t0 = before.get(phase, (bounds, [0] * len(buckets), 0, 0.0))
        n = count - c0
        if n <= 0:
            continue
        diff = [b - (b0[i] if i < len(b0) else 0) for i, b in enumerate(buckets)]
        out[phase] = {
            "count": n, "mean_ms": 1e3 * (total - t0) / n,
            "p50_ms": 1e3 * histogram_quantile(bounds, diff, n, 0.50),
            "p99_ms": 1e3 * histogram_quantile(bounds, diff, n, 0.99),
        }
    return out


class GaugeSampler(threading.Thread):
    """The proxy's three gauges as the head has them, twice a second."""

    def __init__(self):
        super().__init__(daemon=True)
        self.samples, self._halt = [], threading.Event()

    def run(self):
        from cluster_anywhere_tpu.util.metrics import get_metrics_snapshot

        while not self._halt.wait(0.5):
            try:
                snap = get_metrics_snapshot()
            except Exception:
                continue
            self.samples.append([
                next(iter((snap.get(g) or {}).get("data", {}).values()), 0.0) for g in GAUGES])

    def summary(self):
        self._halt.set()
        self.join(5)
        cols = list(zip(*self.samples)) or [[0.0]] * len(GAUGES)
        return {g.replace("ca_serve_proxy_", ""): {"max": max(c), "mean": sum(c) / len(c)}
                for g, c in zip(GAUGES, cols)}


def summarise(requests):
    """p50 / p99 of duration and self time by span name over these requests, ms."""
    by_name = {}
    for r in requests:
        for ph in r["phases"]:
            by_name.setdefault(ph["name"], []).append(ph)
    return {
        name: {"count": len(phs),
               **{f"{key}_p{q}": percentile([p[f"{key}_ms"] for p in phs], q)
                  for key in ("dur", "self") for q in (50, 99)}}
        for name, phs in sorted(by_name.items())
    }


def request_attrs(requests):
    """The request events' own sums, and those of their `llm.stream`, by median and p99."""
    out = {}
    for key in ("dur_ms", "ttfb_ms", "executor_wait_ms", "write_wait_ms"):
        vals = [r[key] for r in requests if key in r]
        if vals:
            out[key] = {"p50": percentile(vals, 50), "p99": percentile(vals, 99)}
    streams = [p for r in requests for p in r["phases"] if p["name"] == "llm.stream"]
    for key in ("first_token_ms", "write_wait_ms", "write_wait_max_ms"):
        vals = [s[key] for s in streams if key in s]
        if vals:
            out["llm.stream." + key] = {"p50": percentile(vals, 50), "p99": percentile(vals, 99)}
    return out


def client_join(records, requests, ctx):
    """Client and ring on the one monotonic clock, by the trace id the client
    sent: send to accept, accept to first byte written (the request event's
    `ttfb_ms`), first byte written to the client's own first token; and
    `front`: the client's send-to-first-token less the replica's part
    (`llm.submit` + `llm.stream.first_token`), which is what the harness's
    `front_overhead_p50_ms` takes from outside."""
    by_trace = {r["trace"]: r for r in requests}
    cols = {"send_to_accept_ms": [], "accept_to_first_write_ms": [], "write_to_client_ms": [],
            "client_ttft_ms": [], "replica_part_ms": [], "front_ms": []}
    for rec in records:
        req = by_trace.get(rec.get("trace"))
        if req is None or not rec["token_times"] or req.get("mono") is None:
            continue
        if not serve_driver.in_window(ctx, rec["due"]):
            continue
        ph = {}
        for p in req["phases"]:
            ph.setdefault(p["name"], p)
        if "llm.submit" not in ph or "llm.stream.first_token" not in ph:
            continue
        ttft = 1e3 * (rec["token_times"][0] - rec["send"])
        replica = ph["llm.submit"]["dur_ms"] + ph["llm.stream.first_token"]["dur_ms"]
        cols["send_to_accept_ms"].append(1e3 * (req["mono"] - rec["send"]))
        cols["accept_to_first_write_ms"].append(req["ttfb_ms"])
        cols["write_to_client_ms"].append(
            1e3 * (rec["token_times"][0] - req["mono"]) - req["ttfb_ms"])
        cols["client_ttft_ms"].append(ttft)
        cols["replica_part_ms"].append(replica)
        cols["front_ms"].append(ttft - replica)
    return {k: {"n": len(v), "p50": percentile(v, 50), "p99": percentile(v, 99)}
            for k, v in cols.items() if v}


# -- one window ---------------------------------------------------------------


def run_window(cell, port, handle, vocab, *, label, seconds, plan_seed, traced, profile=False):
    from cluster_anywhere_tpu.util import state

    plan = loadgen.make_plan(cell, seconds, plan_seed, vocab)
    before = phase_cells()
    sampler = GaugeSampler()
    sampler.start()
    loadgen.stream_request = make_client(traced)  # this process's own copy of the module
    t_open = time.monotonic() + cell["traffic_file"]["ramp_s"] + 0.25
    trace_path = None

    def profiled_slice():
        nonlocal trace_path
        tdir = cluster.trace_dir("pr36-" + label)
        time.sleep(max(0.0, t_open + seconds - serve_driver.TRACE_SLICE_S - time.monotonic()))
        handle.bench_trace.remote("start", tdir).result(timeout_s=60)
        time.sleep(max(0.0, t_open + seconds - time.monotonic()))
        trace_path = handle.bench_trace.remote("stop", tdir).result(timeout_s=120)

    profiler = threading.Thread(target=profiled_slice, daemon=True) if profile else None
    if profiler:
        profiler.start()
    records = loadgen.send(cell, HOST, port, ROUTE, plan, seconds, t_open)
    if profiler:
        profiler.join(300)
    gauges = sampler.summary()
    ctx = {"records": records, "t_open": t_open, "seconds": float(seconds), "setup_s": 0.0}
    failed = [r for r in records if r["error"] is not None or r["status"] != 200]
    out = {
        "label": label, "traced": traced, "plan_seed": plan_seed, "seconds": seconds,
        "callers": cell.get("callers"), "rate": cell.get("rate"),
        "attempted": len(records), "failed": len(failed),
        "first_failure": failed[0]["error"] if failed else None,
        "in_window": len(serve_driver.window_records(ctx)),
        "end_to_end": serve_driver.end_to_end(ctx) if serve_driver.token_gaps(ctx) else {},
        "gauges": gauges,
    }
    ttft = [t for t in serve_driver.ttfts(ctx)]
    if ttft:
        out["ttft_p50_s"] = percentile(ttft, 50)
    time.sleep(2.5)  # the processes' event buffers and metrics reach the head once a second
    out["phase_seconds"] = phase_window(before, phase_cells())
    if traced:
        requests = [
            r for r in state.serve_requests(limit=0)["requests"]
            if r.get("mono") is not None
            and t_open - cell["traffic_file"]["ramp_s"] <= r["mono"] < t_open + seconds
        ]
        out["ring_requests"] = len(requests)
        out["spans"] = summarise(requests)
        out["request_sums"] = request_attrs(requests)
        out["client_join"] = client_join(records, requests, ctx)
    say(window=label, traced=traced, failed=out["failed"], attempted=out["attempted"],
        e2e=out["end_to_end"], gauges=gauges)
    return out, records, trace_path, ctx


# -- the beacon: profile, ring and client on one axis -------------------------


def token_delivery(trace_path, records, steps):
    """From the replica's profile: the clock beacons' disagreement, and the
    token's way from `llm.step`'s end (profile clock, mapped to the host's
    monotonic through the beacons) to this client's own stamp.  A token's step
    is the latest that ended before the client read it.  `steps` are the
    harness's own stamps of the same steps' ends (`BenchIngress`, monotonic
    already): the same reduction on them is the cross-check."""
    from benchmarks.harness import program_trace

    events = program_trace.extract(trace_path)
    beacons = [s for s in program_trace.spans_named(events, "llm.pump.sync") if "mono_ns" in s[4]]
    if not beacons:
        return {"beacons": 0}
    # a beacon's stamps were taken just before its annotation began
    offsets = [float(s[4]["mono_ns"]) - s[1] for s in beacons]
    wall_offsets = [float(s[4]["wall_ns"]) - s[1] for s in beacons]
    offset = sorted(offsets)[len(offsets) // 2]
    ends = sorted((s[1] + s[2] + offset) / 1e9 for s in program_trace.spans_named(events, "llm.step"))
    if not ends:
        return {"beacons": len(beacons), "steps_in_profile": 0}
    lo, hi = ends[0], ends[-1]

    def lag(step_ends):
        import bisect

        out = []
        for r in records:
            for t in r["token_times"]:
                if lo < t <= hi + 0.05:
                    i = bisect.bisect_right(step_ends, t) - 1
                    if i >= 0:
                        out.append(1e3 * (t - step_ends[i]))
        return out

    by_profile = lag(ends)
    by_harness = lag(sorted(s[0] for s in steps if lo - 1.0 <= s[0] <= hi + 1.0))
    streams = program_trace.spans_named(events, "llm.stream")
    firsts = program_trace.spans_named(events, "llm.stream.first_token")
    return {
        "beacons": len(beacons),
        "beacon_mono_disagreement_us": (max(offsets) - min(offsets)) / 1e3,
        "beacon_wall_disagreement_us": (max(wall_offsets) - min(wall_offsets)) / 1e3,
        "steps_in_profile": len(ends), "tokens_joined": len(by_profile),
        "token_delivery_ms": {"p50": percentile(by_profile, 50), "p90": percentile(by_profile, 90),
                              "p99": percentile(by_profile, 99)} if by_profile else None,
        "token_delivery_ms_by_harness_stamps": {
            "p50": percentile(by_harness, 50), "p99": percentile(by_harness, 99)} if by_harness else None,
        "profile_llm_stream": len(streams), "profile_llm_stream_first_token": len(firsts),
        "profile_first_token_ms_p50": percentile([s[2] / 1e6 for s in firsts], 50) if firsts else None,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, required=True, help="the deployment's weights and check")
    ap.add_argument("--plan", default="overhead,profile,sweep")
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--sweep-seconds", type=float, default=40.0)
    ap.add_argument("--levels", default="6,8,10,12,16,24")
    ap.add_argument("--overhead-seeds", type=int, default=3)
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out", "pr36_phases.json"))
    args = ap.parse_args(argv)
    steps = args.plan.split(",")

    import cluster_anywhere_tpu as ca

    closed, steady = manifest.load_cell("chat-closed6"), manifest.load_cell("chat-steady")
    vocab = closed["config_file"]["config"]["vocab_size"]
    result = {"seed": args.seed, "cpu_count": os.cpu_count(), "windows": []}
    delivery_input = None
    try:
        say(resources=cluster.init_cluster(1, closed["traffic_file"].get("cluster_env")))
        port = cluster.free_port()
        handle = serve_driver._deploy(closed, args.seed, port)
        result["check"] = serve_driver._warm_up_and_check(closed, handle, port, args.seed)
        result["setup_s"] = time.monotonic() - T_START
        say(setup_s=result["setup_s"], check_ok=result["check"]["ok"])

        def window(cell, **kw):
            out, records, trace_path, ctx = run_window(cell, port, handle, vocab, **kw)
            result["windows"].append(out)
            with open(args.out, "w") as f:  # what is there so far survives a later fault
                json.dump(result, f)
            return out, records, trace_path

        if "overhead" in steps:
            for cell in (closed, steady):
                for k in range(args.overhead_seeds):
                    order = (False, True) if k % 2 == 0 else (True, False)
                    for traced in order:
                        window(cell, label=f"{cell['name']}.{'on' if traced else 'off'}.{k}",
                               seconds=args.seconds, plan_seed=args.seed + 1 + k, traced=traced)
        if "profile" in steps:
            out, records, trace_path = window(
                closed, label="chat-closed6.profile", seconds=args.seconds,
                plan_seed=args.seed + 11, traced=True, profile=True)
            collected = handle.bench_collect.remote().result(timeout_s=60)
            delivery_input = (trace_path, records, collected["steps"])
        if "sweep" in steps:
            for n in (int(x) for x in args.levels.split(",")):
                cell = copy.deepcopy(closed)
                cell["callers"] = n
                window(cell, label=f"sweep.{n}", seconds=args.sweep_seconds,
                       plan_seed=args.seed + 20 + n, traced=True)
        result["device"] = handle.bench_collect.remote().result(timeout_s=60)["device"]
    except BaseException:
        cluster.save_session_logs("pr36-phases")
        raise
    finally:
        ca.shutdown()
    cluster.require_tpu(result["device"], 1)
    if delivery_input is not None and delivery_input[0]:
        result["token_delivery"] = token_delivery(*delivery_input)
    with open(args.out, "w") as f:
        json.dump(result, f)
    print(json.dumps({
        "cpu_count": result["cpu_count"], "device": result["device"],
        "token_delivery": result.get("token_delivery"),
        "windows": [
            {k: w.get(k) for k in ("label", "traced", "attempted", "failed", "end_to_end", "gauges")}
            for w in result["windows"]],
    }), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
