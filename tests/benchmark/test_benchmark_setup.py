"""`setup_s` in three read parts (ISSUE 52): the reader of the seconds before
the replica's constructor, and the three metric files resolved for exactly the
closed serving cells, each from its own file's `families`."""

import json
import os

import pytest

from benchmarks.harness import manifest

with open(os.path.join(manifest.ROOT, "BENCHMARK.json")) as _f:
    DOC = json.load(_f)
CELLS = [w["name"] for w in DOC["workloads"]]
CLOSED = [c for c in CELLS if "closed" in manifest.load_cell(c).get("families", [])]
PARTS = {
    "setup_before_replica_s.closed": ("setup_before_replica", None, "host_clock"),
    "replica_init_s.closed": ("replica_stat", {"stat": "replica_init_s"}, "program_counter"),
    "program_build_s.closed": ("replica_stat", {"stat": "program_build_s"}, "program_counter"),
}
LAYER = "replica set-up (llm/serve_llm.py, llm/continuous.py)"


def _ctx(stats):
    # run.py's first line at 1000.0 on the host's monotonic clock, the window's opening 52.5 s on
    return {"t_open": 1052.5, "setup_s": 52.5, "replica": {"stats": stats}}


def test_the_seconds_before_the_constructor_are_the_stamp_less_the_commands_first_line():
    read = manifest.load_reader("setup_before_replica")
    assert read(_ctx({"replica_init_mono": 1013.25})) == pytest.approx(13.25)
    # a program that keeps no such stamp (the parent of the PR that added it): nothing, and no error
    assert read(_ctx({"prefill_traces": 4})) is None
    assert read({"t_open": 1052.5, "setup_s": 52.5}) is None


@pytest.mark.parametrize("name", list(PARTS))
def test_a_part_of_set_up_is_read_in_the_closed_cells_and_no_other(name):
    reader, args, source = PARTS[name]
    # eight closed cells when the parts came (PR 52); a later closed cell joins from its own file
    assert len(CLOSED) >= 8 and "chat-steady" not in CLOSED and "train-fsdp4" not in CLOSED
    resolved = [c for c in CELLS if name in {m["name"] for m in manifest.layer_metrics_for(c)}]
    assert resolved == CLOSED
    m = next(m for m in manifest.layer_metrics_for(CLOSED[0]) if m["name"] == name)
    assert (m["reader"], m.get("args"), m["family"]) == (reader, args, "closed")
    entry = next(e for e in DOC["per_layer"] if e["name"] == name)
    assert entry == {"name": name, "unit": "s", "better": "lower", "source": source, "layer": LAYER,
                     "moves": "setup_s", "workloads": CLOSED}
    assert {k: m[k] for k in ("unit", "layer", "moves", "source")} == {
        k: entry[k] for k in ("unit", "layer", "moves", "source")}


def test_the_three_parts_read_a_replicas_counts_and_leave_the_harness_its_own():
    """What `bench_collect` hands over, as the replica's constructor and its
    batcher write it: the three parts, each over 0, their sum under `setup_s`;
    what is left is the warm-up's and the check's running and the ramp."""
    ctx = _ctx({"replica_init_mono": 1013.25, "replica_init_s": 14.5, "program_build_s": 5.75,
                "program_trace_s": 4.0, "program_builds": 13, "program_cache_misses": 0})
    got = {name: manifest.load_reader(reader)(ctx, **(args or {})) for name, (reader, args, _) in PARTS.items()}
    assert got == {"setup_before_replica_s.closed": pytest.approx(13.25), "replica_init_s.closed": 14.5,
                   "program_build_s.closed": 5.75}
    assert all(v > 0 for v in got.values()) and sum(got.values()) < ctx["setup_s"]
    # the entries were appended together, in this order, and later PRs' follow them
    listed = [e["name"] for e in DOC["per_layer"]]
    first = listed.index(next(iter(PARTS)))
    assert listed[first:first + 3] == list(PARTS) and len(listed) <= 128
