"""The benchmark's arithmetic: percentiles, spreads, and the operations and
bytes a step needs, computed from shapes.  No JAX in here."""

from __future__ import annotations

import json
import math
import os
import statistics
from typing import Any, Dict, List, Sequence

_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def percentile(values: Sequence[float], q: float) -> float:
    """The q-th percentile (0..100), linear between closest ranks (numpy's
    default).  Raises on an empty sample: a metric with nothing to read is
    left out by its caller, never reported as 0."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of an empty sample")
    k = (len(xs) - 1) * q / 100.0
    lo, hi = math.floor(k), math.ceil(k)
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


def quartile_spread(values: Sequence[float]) -> float:
    """Distance between the first and third quartile as a share of the median,
    with the quartiles of `statistics.quantiles(values, n=4)`: the spread the
    bounds in BENCHMARK.json are set from."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def peaks(device_kind: str) -> Dict[str, Any]:
    """The published peaks of one chip, by `device_kind`.  A device that is
    not in the table is an error, not a default."""
    with open(_PEAKS) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device_kind {device_kind!r}: add it to {_PEAKS}")
    return table[device_kind]


# -- shapes: a dense decoder with grouped-query attention and a SwiGLU FFN ----
# `c` is the `config` object of a configuration file (the published keys).


def _dims(c: Dict[str, Any]):
    e, h, kv = c["hidden_size"], c["num_attention_heads"], c["num_key_value_heads"]
    return e, h, kv, c["head_dim"], c["intermediate_size"], c["num_hidden_layers"], c["vocab_size"]


def param_count(c: Dict[str, Any]) -> int:
    e, h, kv, d, f, L, V = _dims(c)
    per_layer = e * h * d + 2 * e * kv * d + h * d * e + 3 * e * f + 2 * e
    return L * per_layer + 2 * V * e + e


def train_flops_per_step(c: Dict[str, Any], batch: int, seq: int) -> float:
    """Operations the forward and backward passes require for `batch`
    sequences of `seq` tokens: 2 per multiply-add, attention counted in full
    (the 4*t*t*d*h square, no causal discount), backward twice the forward,
    recomputation not counted.  Copied from bench.py's
    model_flops_per_step."""
    e, h, kv, d, f, L, V = _dims(c)
    per_tok_layer = 2 * (e * h * d + 2 * e * kv * d + h * d * e + 3 * e * f)
    attn_per_seq_layer = 4 * seq * seq * d * h
    fwd = batch * seq * per_tok_layer * L + batch * attn_per_seq_layer * L + batch * seq * 2 * e * V
    return 3.0 * fwd


def decode_step_bytes(c: Dict[str, Any], slots: int, t_max: int, bytes_per: int = 2) -> int:
    """Bytes one decode step has to read at the least: every weight once (the
    embedding only its `slots` rows) and the whole key/value cache, which the
    program attends over in full whatever the rows' depths."""
    e, h, kv, d, f, L, V = _dims(c)
    weights = param_count(c) - V * e + slots * e
    cache = 2 * L * slots * t_max * kv * d
    return (weights + cache) * bytes_per


def mfu_percent(flops_per_token: float, tokens_per_s: float, chips: int, device_kind: str) -> float:
    return 100.0 * flops_per_token * tokens_per_s / (chips * peaks(device_kind)["bf16_flops"])
