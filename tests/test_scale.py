"""Scalability-envelope tests: trimmed versions of the reference's
release/benchmarks single-node table (BASELINE.md) — many returns, many
args, many objects, deep task queues, multi-GiB objects.  Bounds are
completion deadlines (generous for shared CI hosts), not perf assertions;
the envelope numbers themselves come from ca microbenchmark."""

import time

import numpy as np
import pytest

import cluster_anywhere_tpu as ca


@pytest.fixture(scope="module", autouse=True)
def cluster():
    if ca.is_initialized():
        ca.shutdown()
    ca.init(num_cpus=4)
    yield
    ca.shutdown()


def test_many_returns_from_one_task():
    """3,000 returns from one task (baseline: 5.81 s)."""
    n = 3000

    @ca.remote
    def burst():
        return tuple(range(n))

    refs = burst.options(num_returns=n).remote()
    assert len(refs) == n
    vals = ca.get(refs, timeout=120)
    assert vals[0] == 0 and vals[-1] == n - 1


def test_many_object_args_to_one_task():
    """2,000 ObjectRef args resolved into a single task invocation
    (baseline row: 10,000 args in 17.3 s on an m4.16xlarge)."""
    n = 2000
    refs = [ca.put(i) for i in range(n)]

    @ca.remote
    def total(*xs):
        return sum(xs)

    assert ca.get(total.remote(*refs), timeout=120) == n * (n - 1) // 2


def test_get_many_objects():
    """ca.get over 5,000 distinct objects (baseline row: 10,000 in 23.9 s)."""
    n = 5000
    refs = [ca.put(i) for i in range(n)]
    vals = ca.get(refs, timeout=120)
    assert vals == list(range(n))


def test_deep_task_queue():
    """20,000 tasks queued at once on 4 CPUs drain to completion (baseline
    row: 1,000,000 queued tasks in 193 s on a 64-core box)."""
    n = 20_000

    @ca.remote
    def one():
        return 1

    t0 = time.monotonic()
    refs = [one.remote() for _ in range(n)]
    out = ca.get(refs, timeout=300)
    assert sum(out) == n
    assert time.monotonic() - t0 < 300


def test_multi_gib_object_roundtrip():
    """A single ~1.5 GiB object puts at arena speed and reads back zero-copy
    (baseline envelope: 100 GiB single object at ~3.5 GB/s on a machine
    with the RAM for it)."""
    size = 3 * 512 * 1024 * 1024 // 4  # 1.5 GiB of float32
    arr = np.ones(size // 4, dtype=np.float32)
    t0 = time.monotonic()
    ref = ca.put(arr)
    put_s = time.monotonic() - t0
    back = ca.get(ref, timeout=120)
    assert back.nbytes == arr.nbytes
    assert back[0] == 1.0 and back[-1] == 1.0
    assert put_s < 60, f"1.5 GiB put took {put_s:.1f}s"
    del back, ref


def test_sixteen_node_scheduling_stress():
    """16 one-CPU virtual nodes + head: a SPREAD flood must fan out across
    most of the cluster and a PG spanning all 16 must place (trimmed
    release/benchmarks many_nodes_tests analogue; honest for one physical
    core — the assertion is placement breadth + completion, not speed)."""
    import os as _os

    from cluster_anywhere_tpu.cluster_utils import Cluster

    ca.shutdown()
    c = Cluster(head_resources={"CPU": 1})
    try:
        for _ in range(16):
            c.add_node(num_cpus=1)
        c.connect()
        c.wait_for_nodes(17)

        @ca.remote
        def where(t):
            time.sleep(t)
            return _os.environ.get("CA_NODE_ID", "n0")

        f = where.options(scheduling_strategy="SPREAD")
        spots = set(ca.get([f.remote(0.5) for _ in range(32)], timeout=180))
        assert len(spots) >= 12, f"SPREAD used only {len(spots)} of 17 nodes: {spots}"
        # a 16-bundle STRICT_SPREAD PG: every bundle on a distinct agent node
        pg = ca.placement_group([{"CPU": 1}] * 16, strategy="STRICT_SPREAD")
        assert pg.wait(60)
        table = {p["pg_id"]: p for p in ca.placement_group_table()}
        nodes = table[pg.id.hex()]["bundle_nodes"]
        assert len(set(nodes)) == 16, nodes
        ca.remove_placement_group(pg)
    finally:
        try:
            ca.shutdown()
        except Exception:
            pass
        c.shutdown()
        ca.init(num_cpus=4)  # restore the module fixture's cluster
