"""Cluster bring-up for a benchmark run, copied from chip_smoke.py (later PRs
may change the smoke, not the yardstick).  This process never initialises a
JAX backend: only workers the head spawned touch the chip."""

from __future__ import annotations

import os
import socket
import time
from typing import Any, Dict, Optional


def init_cluster(chips: int, env: Optional[Dict[str, str]] = None) -> Dict[str, float]:
    """`ca.init()` finds the chips itself.  Fewer than the cell asks for is a
    failure, never a CPU run.  The cluster's session files go under this run's
    TMPDIR through the program's own `CA_SESSION_DIR_ROOT` (its default is the
    fixed /tmp/ca_tpu), unless that would make its unix socket paths too long.
    `env` is the mix's `cluster_env`: settings of the program's own tunables
    table (`CA_<NAME>`, core/config.py) that this deployment's operator makes."""
    import tempfile

    import cluster_anywhere_tpu as ca

    # JAX keeps a compiled program only if it took a second to compile.  The
    # serving path compiles its short-prompt prefill in 0.7-0.9 s and asks for
    # it again at every admit, so such a program was compiled anew in every
    # process and every admit until one compilation happened to take longer
    # (PERF.md section 6).  Every program is kept: after a checkout's first
    # run nothing compiles.  Workers inherit the variable; JAX reads it.
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    for key, value in (env or {}).items():
        if not key.startswith("CA_"):
            raise ValueError(f"cluster_env sets only the program's CA_ tunables, not {key!r}")
        os.environ[key] = str(value)
    root = os.path.join(tempfile.gettempdir(), "ca_tpu")
    if len(root) <= 60:  # + /session_<ms>_<pid>/head.sock has to stay under 108
        os.environ.setdefault("CA_SESSION_DIR_ROOT", root)
    res = ca.init()["resources"]
    if res.get("TPU", 0.0) < float(chips):
        raise RuntimeError(f"need {chips} TPU chip(s), ca.init() found {res.get('TPU', 0)}")
    return res


def require_tpu(device: Dict[str, Any], chips: int) -> Dict[str, Any]:
    """The chip-holding process's own view of the device, as the result line
    reports it.  Anything but `chips` TPU devices is a failure."""
    if device["platform"] != "tpu" or device["count"] != chips:
        raise RuntimeError(f"worker ran on {device}, need {chips} tpu device(s)")
    return device


def wait_tpu_workers_gone(timeout_s: float = 60.0) -> None:
    """A chip belongs to one process; the run is over when its holder is gone."""
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


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def transformer_overrides(config_file: Dict[str, Any], **extra) -> Dict[str, Any]:
    """The program's TransformerConfig fields from a configuration file's
    published keys."""
    c = config_file["config"]
    out = dict(
        d_model=c["hidden_size"], n_layers=c["num_hidden_layers"],
        n_heads=c["num_attention_heads"], n_kv_heads=c["num_key_value_heads"],
        d_head=c["head_dim"], d_ff=c["intermediate_size"], rope_theta=c["rope_theta"],
        max_seq_len=c["max_position_embeddings"],
    )
    out.update(extra)
    return out


def out_dir() -> str:
    """Where a run leaves what a builder reads afterwards: inside the
    checkout, git-ignored.  Nothing reads it back."""
    from .manifest import ROOT

    d = os.path.join(ROOT, "bench_out")
    os.makedirs(d, exist_ok=True)
    return d


def trace_dir(cell: str) -> str:
    """Where a traced run's profile goes."""
    d = os.path.join(out_dir(), "trace", cell)
    os.makedirs(d, exist_ok=True)
    return d


def save_session_logs(cell: str, tail_bytes: int = 6000) -> Optional[str]:
    """The ends of the cluster's own logs (the head's events and log, every
    accelerator worker's log), kept in `bench_out/<cell>.session.log` and said
    on standard error.  `ca.shutdown()` removes the session with its logs, so
    this is called before it, when a run failed or lost a worker."""
    import glob
    import sys

    try:
        from cluster_anywhere_tpu.core.worker import global_worker

        session = global_worker().session_dir
        live = global_worker().head_call("list_workers")["workers"]
    except Exception as e:  # the cluster is already gone
        print(f"[bench] no session logs: {e!r}", file=sys.stderr, flush=True)
        return None
    tpu_workers = {w["worker_id"] for w in live if w["pool"] != "cpu"}
    paths = [os.path.join(session, "events.jsonl"), os.path.join(session, "head.log")]
    paths += sorted(
        p for p in glob.glob(os.path.join(session, "*.log"))
        if os.path.basename(p)[: -len(".log")] in tpu_workers
    )
    parts = []
    for p in paths:
        try:
            with open(p, "rb") as f:
                f.seek(0, os.SEEK_END)
                f.seek(max(0, f.tell() - tail_bytes))
                parts.append(f"==== {os.path.basename(p)}\n" + f.read().decode(errors="replace"))
        except OSError:
            continue
    text = "\n".join(parts)
    dest = os.path.join(out_dir(), f"{cell}.session.log")
    with open(dest, "w") as f:
        f.write(text)
    print("[bench] session logs:\n" + text[-12000:], file=sys.stderr, flush=True)
    return dest
