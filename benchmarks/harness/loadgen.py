"""The one traffic generator.  A traffic mix is a file of parameters
(`traffic/<mix>.json`); this module turns it and a seed into requests, and
sends them.

Steadiness: the *sizes* of a run (how many requests, their arrival gaps,
prompt and output lengths) are drawn once from the mix's `shape_seed`, so every
`--seed` carries the same multiset of work: one frozen draw from the mix's
distributions, not a new draw a run.  `--seed` decides the order the sizes come
in and the token ids themselves (and, in the driver, the weights).  So the
spread between runs is the spread of order, not of the draw; PERF.md gives
both.

Kinds:
  open_poisson     independent users: requests are due on a schedule whether
                   or not earlier ones finished (a Poisson process conditioned
                   on its count).  Latency counts from the due time.
"""

from __future__ import annotations

import asyncio
import json
import time
from typing import Any, Dict, List, Optional

import numpy as np

CONNECT_TIMEOUT_S = 30.0


def _lengths(rng, n: int, spec: Dict[str, Any]) -> np.ndarray:
    """n whole lengths from a mix's {"dist", "min", "max", ...}."""
    if spec["dist"] == "lognormal":
        x = rng.lognormal(np.log(spec["median"]), spec["sigma"], n)
        return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)
    if spec["dist"] == "uniform":
        return rng.integers(spec["min"], spec["max"] + 1, n)
    raise ValueError(f"unknown length distribution {spec['dist']!r}")


def prompt_text(ids) -> str:
    """The wire form of a prompt: space-separated decimal token ids, which the
    benchmark's tokenizer (replica.IdTokenizer) parses back exactly."""
    return " ".join(str(int(i)) for i in ids)


def _open_part(shape, rng, n: int, start: float, length: float, traffic, vocab: int):
    """n requests due over [start, start + length): gaps and lengths from the
    mix's own `shape` generator, their order and the token ids from `rng`."""
    arrivals = np.sort(shape.uniform(0.0, length, n))
    gaps = np.diff(arrivals, prepend=0.0)
    p_lens = _lengths(shape, n, traffic["prompt_len"])
    o_lens = _lengths(shape, n, traffic["output_len"])
    due = start + np.cumsum(rng.permutation(gaps))
    pairs = rng.permutation(n)  # a prompt keeps its output length: one request, one size
    return [
        {"due": float(due[i]), "prompt_ids": rng.integers(0, vocab, int(p_lens[j])),
         "max_new_tokens": int(o_lens[j])}
        for i, j in enumerate(pairs)
    ]


def open_poisson_schedule(traffic: Dict[str, Any], rate: float, seconds: float,
                          seed: int, vocab: int) -> List[Dict[str, Any]]:
    """Requests due over [-ramp_s, seconds), times relative to the window's
    opening: a Poisson process conditioned on its count, the ramp and the
    window drawn apart so that every seed puts the same requests inside the
    window.  Each: {"id", "due", "prompt_ids", "max_new_tokens"}."""
    shape = np.random.default_rng(traffic["shape_seed"])
    rng = np.random.default_rng(seed)
    ramp = traffic["ramp_s"]
    parts = _open_part(shape, rng, max(1, int(round(rate * ramp))), -ramp, ramp, traffic, vocab)
    parts += _open_part(shape, rng, max(1, int(round(rate * seconds))), 0.0, seconds, traffic, vocab)
    return [dict(r, id=i) for i, r in enumerate(parts)]


# -- the client: one thread, one event loop ---------------------------------


async def stream_request(host: str, port: int, path: str, body: Dict[str, Any],
                         rec: Dict[str, Any], timeout_s: float) -> Dict[str, Any]:
    """One streamed POST.  Fills rec with "send", "status", "tokens",
    "token_times" (host monotonic clock, one per `data:` event) and "error"."""
    rec.update(tokens=[], token_times=[], status=None, error=None)
    payload = json.dumps(body).encode()
    head = (
        f"POST {path} HTTP/1.1\r\nHost: {host}:{port}\r\n"
        "Content-Type: application/json\r\nAccept: text/event-stream\r\n"
        f"Content-Length: {len(payload)}\r\nConnection: close\r\n\r\n"
    ).encode()
    writer = None

    async def talk():
        nonlocal writer
        rec["send"] = time.monotonic()
        reader, writer = await asyncio.wait_for(
            asyncio.open_connection(host, port, limit=1 << 20), CONNECT_TIMEOUT_S
        )
        writer.write(head + payload)
        await writer.drain()
        status_line = await reader.readline()
        rec["status"] = int(status_line.split()[1])
        while (await reader.readline()) not in (b"\r\n", b"\n", b""):
            pass
        if rec["status"] != 200:
            rec["error"] = (await reader.read()).decode("utf-8", "replace")[:500]
            return
        while True:
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


def request_body(prompt_ids, max_new_tokens: int, bench_id: Optional[str] = None) -> Dict[str, Any]:
    """A request's JSON body.  `bench_id` is a key the program ignores; the
    benchmark's replica stamps the request under it."""
    out = {"prompt": prompt_text(prompt_ids), "max_new_tokens": int(max_new_tokens),
           "temperature": 0.0}
    if bench_id is not None:
        out["bench_id"] = bench_id
    return out


async def run_open(host, port, path, schedule, traffic, t_open: float,
                   seconds: float) -> List[Dict[str, Any]]:
    """Send each request when it is due (t_open + due on the monotonic clock),
    then let those in flight finish, for at most drain_s."""
    recs, tasks = [], []
    deadline = t_open + seconds + traffic["drain_s"]
    for req in schedule:
        delay = t_open + req["due"] - time.monotonic()
        if delay > 0:
            await asyncio.sleep(delay)
        rec = {"id": req["id"], "bench_id": f"r{req['id']}", "due": t_open + req["due"],
               "n_prompt": len(req["prompt_ids"]), "n_out": req["max_new_tokens"]}
        recs.append(rec)
        body = request_body(req["prompt_ids"], req["max_new_tokens"], rec["bench_id"])
        tasks.append(asyncio.create_task(stream_request(
            host, port, path, body, rec, max(1.0, deadline - time.monotonic()))))
    await asyncio.gather(*tasks)
    return recs


def make_plan(kind: str, traffic, rate: Optional[float], seconds: float, seed: int, vocab: int):
    """The mix's requests for one run, from the seed (no clock in here)."""
    if kind == "open_poisson":
        return open_poisson_schedule(traffic, rate, seconds, seed, vocab)
    raise ValueError(f"unknown traffic kind {kind!r}")


def send(kind: str, host, port, path, plan, traffic, seconds: float, t_open: float):
    """Blocking: send the plan's requests around a window that opens at t_open
    (monotonic clock).  Returns the per-request records."""
    if kind != "open_poisson":
        raise ValueError(f"unknown traffic kind {kind!r}")
    return asyncio.run(run_open(host, port, path, plan, traffic, t_open, seconds))
