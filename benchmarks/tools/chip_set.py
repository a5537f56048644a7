#!/usr/bin/env python3
"""Runs one cell several times, one process a run, and says how the runs
spread.  For the builder of a benchmark PR, on the chip:

    chiprun -- python3 benchmarks/tools/chip_set.py --workload chat-steady \\
        --seeds 11,12,13 --seconds 40 --tag chat-a

Every result line goes to chiprun_out/<tag>.jsonl and the end of every run's
standard error to chiprun_out/<tag>.<i>.err.  Touches no JAX."""

import argparse
import glob
import json
import os
import signal
import statistics
import subprocess
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.harness.stats import quartile_spread  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated, one run each")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--rates", default="", help="comma-separated: one run per rate (a sweep)")
    ap.add_argument("--tag", required=True)
    ap.add_argument("--timeout", type=float, default=1500.0)
    a = ap.parse_args()
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    seeds = [int(s) for s in a.seeds.split(",")]
    rates = [float(r) for r in a.rates.split(",")] if a.rates else [None] * len(seeds)
    if len(rates) != len(seeds):
        seeds = seeds[:1] * len(rates)
    lines = []
    for i, (seed, rate) in enumerate(zip(seeds, rates)):
        cmd = [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"), "--workload", a.workload,
               "--seed", str(seed), "--seconds", str(a.seconds), "--trace", str(a.trace)]
        if rate is not None:
            cmd += ["--rate", str(rate)]
        t0 = time.monotonic()
        # a session of its own, so that a run cut at the limit takes its cluster with it
        proc = subprocess.Popen(cmd, cwd=ROOT, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                                text=True, start_new_session=True)
        try:
            out, err = proc.communicate(timeout=a.timeout)
        except subprocess.TimeoutExpired:
            os.killpg(proc.pid, signal.SIGKILL)
            out, err = proc.communicate()
            err += f"\n[chip_set] cut after {a.timeout} s"
        wall = time.monotonic() - t0
        with open(os.path.join(out_dir, f"{a.tag}.{i}.err"), "w") as f:
            f.write(err[-20000:])
            if proc.returncode != 0:  # the cluster's own logs say why a worker died
                for log in sorted(glob.glob(os.path.join(
                        tempfile.gettempdir(), "ca_tpu", "session_*", "*.log")))[-8:]:
                    with open(log, errors="replace") as g:
                        f.write(f"\n==== {log}\n" + g.read()[-6000:])
        dumped = os.path.join(ROOT, "bench_out", f"{a.workload}.last.json")
        if os.path.exists(dumped):
            os.replace(dumped, os.path.join(out_dir, f"{a.tag}.{i}.records.json"))
        head = os.path.join(ROOT, "bench_out", f"{a.workload}.trace_head.json")
        if os.path.exists(head):
            os.replace(head, os.path.join(out_dir, f"{a.tag}.{i}.trace_head.json"))
        # a worker's stall trail (one a process) and the session's logs of a run that lost one
        for left in glob.glob(os.path.join(ROOT, "bench_out", f"{a.workload}.stalls.*.txt")) + \
                glob.glob(os.path.join(ROOT, "bench_out", f"{a.workload}.session.log")):
            os.replace(left, os.path.join(out_dir, f"{a.tag}.{i}." + os.path.basename(left)))
        last = out.strip().splitlines()[-1] if out.strip() else ""
        try:
            line = json.loads(last)
        except ValueError:
            line = {"error": last[-500:], "stderr": err[-1500:]}
        line.update(seed=seed, rate=rate, rc=proc.returncode, wall_s=wall)
        lines.append(line)
        with open(os.path.join(out_dir, f"{a.tag}.jsonl"), "a") as f:
            f.write(json.dumps(line) + "\n")
        brief = {k: round(v["value"], 4) for k, v in line.get("metrics", {}).items()}
        print(json.dumps({"run": i, "seed": seed, "rate": rate, "rc": proc.returncode,
                          "wall_s": round(wall, 1), "correct": line.get("correct"),
                          "attempted": line.get("attempted"), "failed": line.get("failed"),
                          "metrics": brief, "check": line.get("check"),
                          "device": line.get("device"), "knee": line.get("knee"), "error": line.get("error"),
                          "restarts": line.get("restarts"), "stalls": (line.get("stalls") or {}).get("worst"),
                          "stderr": line.get("stderr")}), flush=True)
    names = sorted({k for ln in lines for k in ln.get("metrics", {})})
    for name in names:
        vals = [ln["metrics"][name]["value"] for ln in lines if name in ln.get("metrics", {})]
        row = {"metric": name, "n": len(vals), "median": statistics.median(vals),
               "min": min(vals), "max": max(vals)}
        if len(vals) >= 3 and statistics.median(vals):
            row["iqr_spread"] = quartile_spread(vals)
        print(json.dumps(row), flush=True)
    return 0 if all(ln["rc"] == 0 for ln in lines) else 1


if __name__ == "__main__":
    raise SystemExit(main())
