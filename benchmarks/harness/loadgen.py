"""The one traffic generator.  A traffic mix is a file of parameters
(`traffic/<mix>.json`); this module turns it and a seed into requests, and
sends them.

Steadiness: the *sizes* of a run (how many requests, their arrival gaps,
prompt and output lengths) are drawn once from the mix's `shape_seed`, so every
`--seed` carries the same multiset of work: one frozen draw from the mix's
distributions, not a new draw a run.  `--seed` decides the order the sizes come
in and the token ids themselves (and, in the driver, the weights).  So the
spread between runs is the spread of order, not of the draw; PERF.md gives
both.  (A closed loop's callers each hold more sizes than a window uses, so
there the order also decides which of them the window reaches; a mix whose
requests are few and long, so that this draw shows in its rate, says
`"caller_sizes": "quantiles"` and `"caller_rounds"`: a caller then goes round
one short cycle of the distributions' own quantiles, and any window holds
whole cycles and a remainder of less than one.)

Kinds:
  open_poisson     independent users: requests are due on a schedule whether
                   or not earlier ones finished (a Poisson process conditioned
                   on its count).  Latency counts from the due time.
  closed_loop      `callers` callers that each wait for a reply: a caller
                   sends a request, reads the whole answer, and sends its next
                   at once (think time 0) until the window closes.  A request
                   is due the instant its caller was free to send it: its
                   start, or its previous answer's last token.  A slow system
                   receives less load; what it completes is the measure.
"""

from __future__ import annotations

import asyncio
import json
import statistics
import time
from typing import Any, Dict, List, Optional

import numpy as np

CONNECT_TIMEOUT_S = 30.0


def _quantile_lengths(n: int, spec: Dict[str, Any]) -> np.ndarray:
    """The n lengths that cut a mix's {"dist", "min", "max", ...} into n parts of
    equal weight (its quantiles at (i + 1/2) / n), ascending: no draw."""
    q = (np.arange(n) + 0.5) / n
    if spec["dist"] == "lognormal":
        z = np.array([statistics.NormalDist().inv_cdf(float(v)) for v in q])
        x = spec["median"] * np.exp(spec["sigma"] * z)
        return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)
    if spec["dist"] == "uniform":
        return np.floor(spec["min"] + q * (spec["max"] + 1 - spec["min"])).astype(np.int64)
    raise ValueError(f"unknown length distribution {spec['dist']!r}")


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


def open_poisson_plan(cell: Dict[str, Any], seconds: float, seed: int, vocab: int):
    """An open loop's cell gives its load as `rate` requests/s."""
    return open_poisson_schedule(cell["traffic_file"], cell["rate"], seconds, seed, vocab)


def closed_loop_plan(cell: Dict[str, Any], seconds: float, seed: int,
                     vocab: int) -> List[Dict[str, Any]]:
    """A closed loop's cell gives its load as `callers`.  One entry a caller:
    {"caller", "start", "requests": [{"prompt_ids", "max_new_tokens"}, ...]}.
    `start` is relative to the window's opening: the callers start evenly over
    the ramp, so they are out of step when the window opens.  Each caller's
    `caller_requests` sizes are one frozen draw from `shape_seed`, more than a
    window can use (a caller that does run out stops).  `--seed` permutes each
    caller's sizes and makes the token ids, as in the open mix; which of its
    sizes a caller reaches before the window closes is then the seed's.

    Where a window reaches few requests a caller, that choice is most of the
    spread between seeds.  Such a mix says `"caller_sizes": "quantiles"`: a
    caller's `caller_requests` prompt lengths are the distribution's own
    quantiles and so are its output lengths, paired once from `shape_seed`;
    and `"caller_rounds": r`: a caller goes r times round its sizes in the one
    order the seed gave them, the token ids new each time.  Any
    `caller_requests` requests in a row are then the whole set, whatever the
    seed, and a window holds whole sets and less than one more."""
    traffic, callers = cell["traffic_file"], cell["callers"]
    shape = np.random.default_rng(traffic["shape_seed"])
    rng = np.random.default_rng(seed)
    n, ramp = traffic["caller_requests"], traffic["ramp_s"]
    how, rounds = traffic.get("caller_sizes", "draw"), traffic.get("caller_rounds", 1)
    if how == "draw":
        def lengths(key):
            return _lengths(shape, n, traffic[key])
    elif how == "quantiles":
        def lengths(key):
            return shape.permutation(_quantile_lengths(n, traffic[key]))
    else:
        raise ValueError(f"unknown caller_sizes {how!r}")
    sizes = [np.stack([lengths("prompt_len"), lengths("output_len")], 1) for _ in range(callers)]
    return [
        {"caller": c, "start": -ramp + ramp * c / callers,
         "requests": [{"prompt_ids": rng.integers(0, vocab, int(p)), "max_new_tokens": int(o)}
                      for p, o in np.tile(sizes[c][rng.permutation(n)], (rounds, 1))]}
        for c in range(callers)
    ]


async def run_closed(host, port, path, plan, traffic, t_open: float,
                     seconds: float) -> List[Dict[str, Any]]:
    """Each caller of the plan: from its start, send a request, read the whole
    answer, send the next at once.  A request's `due` is the instant its
    caller was free: its start, the last token of its previous answer, or the
    return of a previous request that failed (whatever tokens it had sent).
    Nothing is sent once the window has closed; requests in flight then
    finish, for at most drain_s."""
    t_close = t_open + seconds
    deadline = t_close + traffic["drain_s"]
    recs: List[Dict[str, Any]] = []

    async def caller(entry):
        due = t_open + entry["start"]
        await asyncio.sleep(max(0.0, due - time.monotonic()))
        for k, req in enumerate(entry["requests"]):
            if due >= t_close:
                return
            bench_id = f"c{entry['caller']}r{k}"
            rec = {"id": bench_id, "bench_id": bench_id, "caller": entry["caller"], "due": due,
                   "n_prompt": len(req["prompt_ids"]), "n_out": req["max_new_tokens"]}
            recs.append(rec)
            body = request_body(req["prompt_ids"], req["max_new_tokens"], bench_id)
            await stream_request(host, port, path, body, rec, max(1.0, deadline - time.monotonic()))
            due = rec["token_times"][-1] if rec["error"] is None else time.monotonic()

    await asyncio.gather(*[asyncio.create_task(caller(e)) for e in plan])
    return recs


# a kind of load: how a loaded cell and a seed make its plan, and how the plan is sent
KINDS = {"open_poisson": (open_poisson_plan, run_open), "closed_loop": (closed_loop_plan, run_closed)}


def _kind(cell: Dict[str, Any]):
    kind = cell["traffic_file"]["kind"]
    if kind not in KINDS:
        raise ValueError(f"unknown traffic kind {kind!r}")
    return KINDS[kind]


def make_plan(cell: Dict[str, Any], seconds: float, seed: int, vocab: int):
    """The requests of a loaded cell's mix for one run, from the seed (no clock
    in here)."""
    return _kind(cell)[0](cell, seconds, seed, vocab)


def send(cell: Dict[str, Any], host, port, path, plan, seconds: float, t_open: float):
    """Blocking: send the plan's requests around a window that opens at t_open
    (monotonic clock).  Returns the per-request records."""
    return asyncio.run(_kind(cell)[1](host, port, path, plan, cell["traffic_file"], t_open, seconds))
