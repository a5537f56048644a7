#!/usr/bin/env python3
"""The builder's reading of set-up's parts (ISSUE 52): one run of one serving
cell through the benchmark's own driver, with what a result line does not
hold in one place: `setup_s` beside the three per-layer parts of it, what is
left of it, and the replica's other set-up counts.

    chiprun --timeout 3000 -- python3 scripts/setup_phases.py \
        --workload chat-closed6 --seed 2520000101 [--trace 1] [--tracing 1] [--root _parent]

It is `benchmarks/run.py`'s `main` to the letter (the cell's files by name, the
cluster through `ca.init()`, `driver.measure`, `result_line`), and keeps the
context: a traced line has the per-layer metrics and no `setup_s`, an untraced
one the reverse, and neither the replica's stamps.  `--tracing 1` calls
`tracing.enable()` in this process before the deploy, as an operator who wants
a deploy's timeline does, and adds the set-up spans the head's ring then holds
(`worker.boot`, `actor.create`, `actor.init`, `serve.replica.start`,
`llm.replica.init` and its children), times from this file's first line.
`--root` runs another checkout's program and benchmark (the parent's, unpacked
into a git-ignored directory) under this file.  The last line of standard
output is one JSON object; `--out` appends it to a file under `chiprun_out/`.
This process never touches JAX's devices.
"""

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

SETUP_SPANS = ("worker.boot", "actor.create", "actor.init", "serve.replica.start")
SETUP_STATS = (
    "replica_init_s", "backend_init_s", "params_init_s", "init_build_s", "program_build_s",
    "program_trace_s", "program_builds", "program_cache_misses", "prefill_traces",
)
PARTS = ("setup_before_replica_s.closed", "replica_init_s.closed", "program_build_s.closed")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--tracing", type=int, choices=(0, 1), default=0)
    ap.add_argument("--root", default=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    ap.add_argument("--out", default=None)
    ap.add_argument("--label", default="")
    args = ap.parse_args(argv)
    # the checkout under test: this process and, through the working directory
    # and PYTHONPATH, every process the cluster starts
    root = os.path.abspath(args.root)
    out_path = os.path.abspath(args.out) if args.out else None
    os.chdir(root)
    sys.path.insert(0, root)
    os.environ["PYTHONPATH"] = os.pathsep.join(filter(None, [root, os.environ.get("PYTHONPATH")]))

    import cluster_anywhere_tpu as ca
    from benchmarks import run
    from benchmarks.harness import cluster, manifest
    from cluster_anywhere_tpu.util import state, tracing

    assert os.path.dirname(os.path.dirname(os.path.abspath(run.__file__))) == root, run.__file__
    cell = manifest.load_cell(args.workload)
    driver = importlib.import_module("benchmarks.harness." + cell["traffic_file"]["driver"])
    spans = []
    try:
        cluster.init_cluster(cell["chips"], cell["traffic_file"].get("cluster_env"))
        if args.tracing:
            tracing.enable()
        ctx = driver.measure(cell, args.seed, args.seconds, bool(args.trace), T_START)
        if args.tracing:
            time.sleep(2.5)  # a worker's events reach the head's ring within its housekeeping's second
            spans = [
                {"name": e["name"], "at_s": e["mono"] - T_START, "s": e["end"] - e["start"],
                 **{k: e[k] for k in ("cls", "pool", "chips", "deployment", "source", "backend_ms",
                                      "build_ms", "param_bytes", "cache_bytes", "buckets") if k in e}}
                for e in state._head("list_task_events", limit=100_000)["events"]
                if e.get("state") == "SPAN" and (e["name"] in SETUP_SPANS or e["name"].startswith("llm.replica."))
            ]
    except BaseException:
        cluster.save_session_logs(cell["name"])
        raise
    finally:
        ca.shutdown()
    line = run.result_line(cell, driver, ctx, bool(args.trace))
    stats = ctx["replica"]["stats"]
    e2e = driver.end_to_end(ctx)
    parts = {m["name"]: manifest.load_reader(m["reader"])(ctx, **m.get("args", {}))
             for m in manifest.layer_metrics_for(cell["name"]) if m["name"] in PARTS}
    out = {
        "label": args.label, "root": os.path.basename(root), "cell": cell["name"], "seed": args.seed,
        "trace": args.trace, "tracing": args.tracing, "correct": line["correct"], "failed": line["failed"],
        "setup_s": e2e["setup_s"], "serve_out_tok_s": e2e["serve_out_tok_s"],
        "gap_p50_s": e2e.get("gap_p50_s"), "gap_mean_s": e2e.get("gap_mean_s"),
        "parts": parts,
        "remainder_s": e2e["setup_s"] - sum(parts.values()) if parts and None not in parts.values() else None,
        "stats": {k: stats.get(k) for k in SETUP_STATS},
        "compiles": len(ctx["replica"]["compiles"]),
        "compiles_in_window": sum(driver.in_window(ctx, t) for t, _ in ctx["replica"]["compiles"]),
        "device": {k: line["device"].get(k) for k in ("kind", "count", "memory_peak_bytes")},
        "cache_dir": ctx["device"].get("cache_dir"),
    }
    if args.trace:
        out["layer"] = {k: v["value"] for k, v in line["metrics"].items()
                        if k in PARTS or k.split(".")[0] in ("pump_between_ms_p50", "step_upload_ms_p50",
                                                             "decode_step_ms_p50", "device_idle")}
    if spans:
        out["spans"] = sorted(spans, key=lambda s: s["at_s"])
    text = json.dumps(out)
    if out_path:
        os.makedirs(os.path.dirname(out_path), exist_ok=True)
        with open(out_path, "a") as f:
            f.write(text + "\n")
    print(text, flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
