#!/usr/bin/env python3
"""The benchmark's command.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Resolves the cell's files by name (harness/manifest.py), brings a cluster up
through `ca.init()`, runs the cell through the program's normal path on the
chip, and prints as the last line of standard output one JSON object:
`correct`, `attempted`, `failed`, `metrics`, `device` (and `breakdown` when
traced).  With `--trace 0` the metrics are the cell's end-to-end metrics, with
`--trace 1` its per-layer metrics.  No TPU, or fewer chips than the cell asks
for, is exit code 1 and no result line.  This process never initialises a JAX
backend; the process that holds the chip reports `device`.
"""

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def say(**fields) -> None:
    print("[bench] " + json.dumps(fields, default=str), file=sys.stderr, flush=True)


def result_line(cell, driver, ctx, trace: bool):
    """The contract's JSON object from what a driver measured."""
    from benchmarks.harness import cluster, manifest, trace_reduce

    manifest_doc = manifest.load_manifest()
    device = cluster.require_tpu(
        {k: ctx["device"][k] for k in ("platform", "kind", "count", "memory_peak_bytes")},
        cell["chips"],
    )
    out = dict(driver.outcome(ctx))
    first_failure = out.pop("first_failure", None)
    if trace:
        # what the profile cost: a cell whose profile grows is seen here before its stop is late
        ctx["trace_bytes"] = os.path.getsize(ctx["trace_path"])
        say(trace_stop_s=ctx["trace_stop_s"], trace_bytes=ctx["trace_bytes"])
        events = trace_reduce.extract(ctx["trace_path"])
        ctx["trace"] = events
        busy = trace_reduce.busy(events)
        if busy is None or not busy["busy_s"] > 0:
            raise RuntimeError("the traced run saw no operation on the device")
        device.update(busy)
        out["metrics"] = manifest.read_layer_metrics(cell["name"], ctx)
        out["breakdown"] = trace_reduce.breakdown(events)
        with open(os.path.join(cluster.out_dir(), f"{cell['name']}.trace_head.json"), "w") as f:
            json.dump(trace_reduce.head(events, 0.5), f)
    else:
        measured = driver.end_to_end(ctx)
        out["metrics"] = {
            m["name"]: {"value": float(measured[m["name"]]), "unit": m["unit"]}
            for m in manifest_doc["end_to_end"]
            if cell["name"] in m.get("workloads", [cell["name"]])
        }
    out["device"] = device
    if hasattr(driver, "knee_stats"):
        out["knee"] = driver.knee_stats(ctx)
    for extra in ("restarts", "stalls"):
        if extra in ctx:
            out[extra] = ctx[extra]
    if first_failure:
        out["first_failure"] = first_failure
    out["check"] = ctx["check"]  # every number compared beside its limit: the line's last key
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rate", type=float, default=None,
                    help="requests/s in place of the cell's own: only for the sweep that finds the knee")
    args = ap.parse_args(argv)

    import cluster_anywhere_tpu as ca
    from benchmarks.harness import cluster, manifest

    cell = manifest.load_cell(args.workload)
    if args.rate is not None:
        cell["rate"] = args.rate
    driver = importlib.import_module(
        "benchmarks.harness." + manifest.check_name(cell["traffic_file"]["driver"])
    )
    try:
        say(resources=cluster.init_cluster(cell["chips"], cell["traffic_file"].get("cluster_env")))
        ctx = driver.measure(cell, args.seed, args.seconds, bool(args.trace), T_START)
    except BaseException:
        cluster.save_session_logs(cell["name"])  # they go with the session
        raise
    finally:
        ca.shutdown()
    line = result_line(cell, driver, ctx, bool(args.trace))
    if hasattr(driver, "dump"):
        # what a builder reads when a number looks wrong; nothing reads it back
        with open(os.path.join(cluster.out_dir(), f"{cell['name']}.last.json"), "w") as f:
            json.dump(driver.dump(ctx), f)
    say(correct=line["correct"], check=line["check"])  # and standard error's last line
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
