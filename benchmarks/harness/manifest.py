"""Finds a cell's files by the names in BENCHMARK.json.

A cell is `cells/<cell>.json`; it names a configuration (`configs/<config>.json`)
and a traffic mix (`traffic/<mix>.json`).  A per-layer metric is
`layer_metrics/<name>.json`, which names its reader
(`layer_metrics/readers/<reader>.py`) and where it is read: the `cells` it
lists, or the `family` that a cell's file joins through its `families`.  A
configuration names its architecture's file (`references/<reference>.py`: the plain reference, the
mapping to the program's fields, the scope names, the counts, the tolerances).
A later PR adds a cell, a mix, a metric, a configuration or an architecture by
adding such files and entries in BENCHMARK.json: nothing here lists them.
"""

from __future__ import annotations

import functools
import importlib.util
import json
import os
import re
from typing import Any, Callable, Dict, List

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)

# what a file under references/ has to define (references/__init__.py says what each is)
REFERENCE_INTERFACE = (
    "program_config", "forward", "loss", "SCOPES", "KERNELS",
    "param_count", "train_flops_per_step", "decode_step_bytes",
    "LOGIT_TOL", "REGRET_MAX_TOL", "REGRET_MEAN_TOL", "LOSS_TOL",
)
# what it may define besides: the parts of a serving cell's check that depend on
# the architecture (reference.check_serving has a default for each it lacks)
REFERENCE_OPTIONAL = ("chosen_logits", "program_logits", "mechanism_checks")

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def _load(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def check_name(name: str) -> str:
    if not NAME_RE.match(name):
        raise ValueError(f"not a valid name: {name!r}")
    return name


def load_manifest(root: str = ROOT) -> Dict[str, Any]:
    return _load(os.path.join(root, "BENCHMARK.json"))


def load_cell(name: str, bench_dir: str = BENCH_DIR) -> Dict[str, Any]:
    """The cell's file with its configuration and traffic files resolved:
    {"name", "config", "traffic", "chips", "why", ..., "config_file": {...},
    "traffic_file": {...}, "bench_dir"}."""
    cell = _load(os.path.join(bench_dir, "cells", check_name(name) + ".json"))
    cell["name"], cell["bench_dir"] = name, bench_dir
    cell["config_file"] = _load(
        os.path.join(bench_dir, "configs", check_name(cell["config"]) + ".json")
    )
    cell["traffic_file"] = _load(
        os.path.join(bench_dir, "traffic", check_name(cell["traffic"]) + ".json")
    )
    return cell


def layer_metrics_for(cell: str, bench_dir: str = BENCH_DIR) -> List[Dict[str, Any]]:
    """Every per-layer metric file that is the cell's, sorted by name: one that
    lists the cell under `cells`, one whose `family` is among the `families`
    of the cell's own file (so a new cell joins a family by its own file, and
    no file that is there changes), and one with neither key (all cells)."""
    cell_file = _load(os.path.join(bench_dir, "cells", check_name(cell) + ".json"))
    families = {check_name(f) for f in cell_file.get("families", [])}
    out, known = [], set()
    d = os.path.join(bench_dir, "layer_metrics")
    for fn in sorted(os.listdir(d)):
        if not fn.endswith(".json"):
            continue
        m = _load(os.path.join(d, fn))
        m["name"] = fn[: -len(".json")]
        if "cells" in m and "family" in m:
            raise ValueError(f"layer_metrics/{fn} has both `cells` and `family`")
        if "family" in m:
            known.add(check_name(m["family"]))
            mine = m["family"] in families
        else:
            mine = "cells" not in m or cell in m["cells"]
        if mine:
            out.append(m)
    if families - known:
        raise ValueError(f"cells/{cell}.json names families no metric has: {sorted(families - known)}")
    return out


def _module_at(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(reader: str, bench_dir: str = BENCH_DIR) -> Callable:
    """The `read(ctx, **args)` function of layer_metrics/readers/<reader>.py."""
    path = os.path.join(bench_dir, "layer_metrics", "readers", check_name(reader) + ".py")
    return _module_at(path, f"bench_reader_{reader}").read


@functools.lru_cache(maxsize=None)
def _reference_at(path: str):
    mod = _module_at(path, "bench_reference_" + os.path.basename(path)[: -len(".py")])
    missing = [n for n in REFERENCE_INTERFACE if not hasattr(mod, n)]
    if missing:
        raise AttributeError(f"{path} lacks {missing}")
    return mod


def load_reference(reference: str, bench_dir: str = BENCH_DIR):
    """The module references/<reference>.py: one architecture's plain
    reference and what else the harness asks of it (REFERENCE_INTERFACE).  One
    module a file and process, so its jitted functions compile once."""
    return _reference_at(os.path.join(bench_dir, "references", check_name(reference) + ".py"))


def reference_of(cell: Dict[str, Any]):
    """The reference module of a loaded cell's configuration."""
    return load_reference(cell["config_file"]["reference"], cell["bench_dir"])


def read_layer_metrics(cell: str, ctx: Dict[str, Any], bench_dir: str = BENCH_DIR):
    """{name: {"value", "unit"}} for every per-layer metric of the cell whose
    reader found something to read."""
    out = {}
    for m in layer_metrics_for(cell, bench_dir):
        value = load_reader(m["reader"], bench_dir)(ctx, **m.get("args", {}))
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out
