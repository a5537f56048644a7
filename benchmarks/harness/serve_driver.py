"""Drives a serving cell: the configuration behind the program's normal path
(`serve.run` -> proxy -> router -> a continuous-batching replica that owns the
chip), the traffic mix sent by loadgen.py, and the reduction of what the
client and the replica recorded to the cell's metrics."""

from __future__ import annotations

import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, List, Tuple

import numpy as np

from . import cluster, loadgen, manifest
from .stats import percentile

HOST, ROUTE = "127.0.0.1", "/llm"
TRACE_SLICE_S = 4.0  # a traced run profiles this much of the window's end


def weights_seed(config_file: Dict[str, Any], seed: int) -> int:
    """The seed the replica makes its random weights from: the run's own, unless
    the configuration names one draw for every run (`"weights": {"seed": n}`).
    One does where the work depends on the draw: of a held share of the
    experts, how many of a token's lie on this chip is the router's draw, and
    a step's time with it.  --seed then makes the traffic and the check's
    prompts, and every seed's run serves the same model."""
    return int(config_file.get("weights", {}).get("seed", seed)) % (2 ** 31)


def _deploy(cell: Dict[str, Any], seed: int, port: int):
    import jax.numpy as jnp

    from cluster_anywhere_tpu import serve
    from cluster_anywhere_tpu.llm.processor import ModelSpec, ProcessorConfig

    from .replica import BenchIngress, IdTokenizer

    dep, cfg = cell["traffic_file"]["deployment"], cell["config_file"]
    serve.start(host=HOST, port=port)
    pcfg = ProcessorConfig(
        model=ModelSpec(
            preset="custom", seed=weights_seed(cfg, seed),
            config_overrides=manifest.reference_of(cell).program_config(cfg, param_dtype=jnp.bfloat16),
        ),
        tokenizer=IdTokenizer(cfg["config"]["vocab_size"]),
        max_prompt_len=dep["max_prompt_len"], max_new_tokens=dep["max_new_tokens"],
        prefix_cache_entries=dep["prefix_cache_entries"],
    )
    # named after the cell: a process caches its router by these names
    name = "llm-" + cell["name"]
    app = serve.deployment(
        BenchIngress, name=name, num_replicas=1, num_tpus=1,
        max_ongoing_requests=dep["slots"],
    ).bind(pcfg, dep["slots"])
    serve.run(app, name=name, route_prefix=ROUTE, wait_timeout_s=900)
    return serve.get_deployment_handle(name, name)


def _one(port: int, prompt_ids, n_new: int, timeout_s: float = 600.0) -> List[int]:
    """One request outside the window; raises unless it answers in full."""
    import asyncio

    rec: Dict[str, Any] = {}
    body = loadgen.request_body(prompt_ids, n_new)
    asyncio.run(loadgen.stream_request(HOST, port, ROUTE, body, rec, timeout_s))
    if rec["error"] is not None or rec["status"] != 200:
        raise RuntimeError(f"set-up request failed: {rec['status']} {rec['error']}")
    return rec["tokens"]


def _warm_up_and_check(cell, handle, port: int, seed: int) -> Dict[str, Any]:
    """Every shape the mix will use, once (the traffic file lists the prompt
    lengths that reach each program); then the correctness checks.  The check
    streams (`check.stream_prompt_lens`, `check.stream_new_tokens` tokens each)
    are sent together, so the decode program serves them as one batch, and
    held to the reference in the replica (reference.check_serving says which
    program each part of the check holds), which finds each by the id it was
    sent under.  One prompt sent alone twice has to answer identically."""
    traffic, vocab = cell["traffic_file"], cell["config_file"]["config"]["vocab_size"]
    rng = np.random.default_rng(seed + 1)
    for n in traffic["warmup_prompt_lens"]:
        _one(port, rng.integers(0, vocab, n), traffic["warmup_new_tokens"])
    chk = traffic["check"]
    t_begin = time.monotonic()
    plan = [
        {"id": f"check{i}", "due": 0.0, "prompt_ids": rng.integers(0, vocab, n),
         "max_new_tokens": chk["stream_new_tokens"]}
        for i, n in enumerate(chk["stream_prompt_lens"])
    ]
    together = {"traffic_file": {"kind": "open_poisson", "drain_s": 600.0}}
    recs = loadgen.send(together, HOST, port, ROUTE, plan, 0.0, t_begin)
    bad = [r for r in recs if r["error"] is not None or r["status"] != 200]
    if bad:
        raise RuntimeError(f"check stream failed: {bad[0]['status']} {bad[0]['error']}")
    streams = [{"prompt_ids": [int(t) for t in q["prompt_ids"]], "served": r["tokens"],
                "bench_id": r["bench_id"]} for q, r in zip(plan, recs)]
    report = handle.bench_check.remote(
        streams, t_begin, cell["config_file"]["reference"]).result(timeout_s=600)
    prompt = rng.integers(0, vocab, chk["repeat_prompt_len"])
    first = _one(port, prompt, chk["repeat_new_tokens"])
    report["repeat_identical"] = first == _one(port, prompt, chk["repeat_new_tokens"])
    report["ok"] = bool(report["ok"] and report["repeat_identical"])
    return report


def measure(cell: Dict[str, Any], seed: int, seconds: float, trace: bool,
            t_start: float) -> Dict[str, Any]:
    """Runs the cell on a cluster that is already up.  Returns the context the
    metrics are read from; the caller shuts the cluster down."""
    from cluster_anywhere_tpu import serve

    traffic, vocab = cell["traffic_file"], cell["config_file"]["config"]["vocab_size"]
    kind = traffic["kind"]
    port = cluster.free_port()
    handle = _deploy(cell, seed, port)
    check = _warm_up_and_check(cell, handle, port, seed)
    plan = loadgen.make_plan(cell, seconds, seed, vocab)
    t_open = time.monotonic() + traffic["ramp_s"] + 0.25
    setup_s = t_open - t_start
    def traced_slice() -> Tuple[str, float]:
        """The profile's path and the seconds the stop took.  The waits are
        backstops that end a run that hangs, not budgets: the stop returns when
        the profile is collected and written (25-36 s for 51-68 MB: PERF.md, PR 58), and
        the same waits cover `jax.profiler`'s own export where a JAX takes
        `stop_trace`'s fallback (100-160 s for those profiles: PERF.md, PR 56)."""
        tdir = cluster.trace_dir(cell["name"])
        time.sleep(max(0.0, t_open + seconds - TRACE_SLICE_S - time.monotonic()))
        handle.bench_trace.remote("start", tdir).result(timeout_s=60)
        time.sleep(max(0.0, t_open + seconds - time.monotonic()))
        t_stop = time.monotonic()
        path = handle.bench_trace.remote("stop", tdir).result(timeout_s=300)
        return path, time.monotonic() - t_stop

    with ThreadPoolExecutor(max_workers=1) as pool:
        tracing = pool.submit(traced_slice) if trace else None
        records = loadgen.send(cell, HOST, port, ROUTE, plan, seconds, t_open)
        trace_path, trace_stop_s = tracing.result(timeout=480) if tracing else (None, None)
    replica = handle.bench_collect.remote().result(timeout_s=60)
    serve.shutdown()
    cluster.wait_tpu_workers_gone()
    return {
        "cell": cell, "kind": kind, "seconds": float(seconds), "t_open": t_open,
        "setup_s": setup_s, "records": records, "replica": replica, "check": check,
        "device": replica["device"], "trace_path": trace_path, "trace_stop_s": trace_stop_s, "chips": 1,
    }


# -- reduction ----------------------------------------------------------------


def in_window(ctx: Dict[str, Any], t: float) -> bool:
    return ctx["t_open"] <= t < ctx["t_open"] + ctx["seconds"]


def window_records(ctx) -> List[Dict[str, Any]]:
    """The requests that were due inside the window."""
    return [r for r in ctx["records"] if in_window(ctx, r["due"])]


def ttfts(ctx) -> List[float]:
    """Seconds from the instant a request was due to its first token; a
    request that failed, or never got one, misses at the window's length."""
    out = []
    for r in window_records(ctx):
        ok = r["error"] is None and r["token_times"]
        out.append(r["token_times"][0] - r["due"] if ok else ctx["seconds"])
    return out


def token_gaps(ctx) -> List[float]:
    """Every gap between successive tokens of one stream that ended inside
    the window."""
    out = []
    for r in ctx["records"]:
        ts = r["token_times"]
        out.extend(b - a for a, b in zip(ts, ts[1:]) if in_window(ctx, b))
    return out


def tokens_in_window(ctx) -> int:
    return sum(in_window(ctx, t) for r in ctx["records"] for t in r["token_times"])


def end_to_end(ctx: Dict[str, Any]) -> Dict[str, float]:
    """Every end-to-end number a serving run can give; run.py reports those
    BENCHMARK.json lists for the cell.  `gap_mean_s` is the time between two
    tokens of a stream, stalls included, over every gap that ended inside the
    window; `gap_p50_s` is the same gaps' median, which the stalls do not reach.
    `serve_out_tok_s` is every token that arrived inside the window over its
    length: what a closed loop's callers got out of the system."""
    gaps = token_gaps(ctx)
    out = {"setup_s": ctx["setup_s"], "serve_out_tok_s": tokens_in_window(ctx) / ctx["seconds"]}
    if gaps:
        out.update(gap_p50_s=percentile(gaps, 50), gap_p99_s=percentile(gaps, 99),
                   gap_mean_s=sum(gaps) / len(gaps))
    out["ttft_p90_s"] = percentile(ttfts(ctx), 90)
    return out


def knee_stats(ctx: Dict[str, Any]) -> Dict[str, float]:
    """What the sweep that finds the knee reads: output tokens delivered inside
    the window over those offered inside it, and the median TTFT of the
    window's last quarter over that of its first (a backlog that grows shows as
    a ratio well above 1).  The share has the window's edges in it: tokens of
    requests due in the ramp that fell inside the window (`carry_in_tokens`)
    and tokens of the window's requests that fell after it
    (`carry_out_tokens`); it is 1 when the two are equal and nothing is lost."""
    recs = sorted(window_records(ctx), key=lambda r: r["due"])
    tt = ttfts({**ctx, "records": recs})
    q = max(1, len(recs) // 4)
    first, last = sorted(tt[:q])[q // 2], sorted(tt[-q:])[q // 2]
    offered = sum(r["n_out"] for r in recs)
    t_close = ctx["t_open"] + ctx["seconds"]
    return {
        "requests": len(recs), "offered_tokens": offered,
        "delivered_share": tokens_in_window(ctx) / offered if offered else 0.0,
        "carry_in_tokens": sum(in_window(ctx, t) for r in ctx["records"] if r["due"] < ctx["t_open"]
                               for t in r["token_times"]),
        "carry_out_tokens": sum(t >= t_close for r in recs for t in r["token_times"]),
        "ttft_p50_first_quarter": first, "ttft_p50_last_quarter": last,
        "ttft_p50": percentile(tt, 50) if tt else 0.0,
    }


def outcome(ctx: Dict[str, Any]) -> Dict[str, Any]:
    """attempted / failed over every request sent, ramp and drain included:
    each has to answer 200 with exactly the tokens asked for."""
    failed = [r for r in ctx["records"] if r["error"] is not None or r["status"] != 200]
    return {
        "attempted": len(ctx["records"]), "failed": len(failed),
        "correct": bool(ctx["check"]["ok"] and not failed and window_records(ctx)),
        "first_failure": failed[0]["error"] if failed else None,
    }


def dump(ctx: Dict[str, Any]) -> Dict[str, Any]:
    """Per-request and per-admit detail, times relative to the window's opening."""
    t0 = ctx["t_open"]
    return {
        "requests": [
            {"id": r["id"], "due": r["due"] - t0, "late": r.get("send", r["due"]) - r["due"],
             "n_prompt": r["n_prompt"], "n_out": r["n_out"], "error": r["error"],
             "ttft": r["token_times"][0] - r["due"] if r["token_times"] else None,
             "end": r["token_times"][-1] - t0 if r["token_times"] else None}
            for r in ctx["records"]
        ],
        "admits": [[a[0] - t0, a[1], a[2], a[3], a[4]] for a in ctx["replica"]["admits"]],
        "compiles": [[t - t0, d] for t, d in ctx["replica"]["compiles"]],
        "steps": len(ctx["replica"]["steps"]), "stats": ctx["replica"]["stats"],
        "gaps": token_gaps(ctx), "tokens_in_window": tokens_in_window(ctx),
        "step_ends": [s[0] - t0 for s in ctx["replica"]["steps"]],
    }
