"""Drain-plane tests (graceful node drain & preemption handling).

The drain plane converts an ANNOUNCED node exit — preemption warning,
`ca drain`, autoscaler downscale — into zero-loss evacuation: placement
stops, delegated lease blocks are recalled, actors restart on survivors
without consuming their restart budget, sole-copy primary objects
re-replicate, and running tasks get until the deadline before a kill whose
retries are exempt from the user's max_retries budget.  Mirrors the
reference GCS DrainNode protocol tests (test_draining.py)."""

import os
import signal
import time

import pytest

import cluster_anywhere_tpu as ca
from cluster_anywhere_tpu.cluster_utils import Cluster
from cluster_anywhere_tpu.core.scheduling_strategies import (
    NodeAffinitySchedulingStrategy,
)


def _node_state(cluster, nid):
    rec = next((n for n in cluster.nodes() if n["node_id"] == nid), None)
    return rec["state"] if rec else None


def _wait_state(cluster, nid, states, timeout=20.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        s = _node_state(cluster, nid)
        if s in states:
            return s
        time.sleep(0.05)
    raise TimeoutError(f"node {nid} never reached {states} (last: {s})")


def test_drain_fsm_idle_node():
    """alive -> draining -> drained for an idle node; idempotent re-drain;
    the head node and bad reasons are rejected."""
    c = Cluster(head_resources={"CPU": 1})
    nid = c.add_node(num_cpus=1)
    c.connect()
    try:
        c.wait_for_nodes(2)
        with pytest.raises(Exception):
            ca.drain_node("n0")  # the head cannot drain itself
        with pytest.raises(Exception):
            ca.drain_node(nid, reason="because")  # unknown reason
        r = ca.drain_node(nid, reason="manual", deadline_s=10)
        assert r["state"] == "draining"
        # an idle node quiesces long before the deadline
        assert _wait_state(c, nid, ("drained",), timeout=10) == "drained"
        # idempotent: draining an already-drained node reports its state
        assert ca.drain_node(nid)["state"] == "drained"
        stats = ca.cluster_stats()
        assert stats["nodes_drained"] == 1
        assert stats["drain_nodes_manual"] == 1
        # a drained node contributes no capacity
        assert ca.cluster_resources().get("CPU", 0) == 1.0
    finally:
        c.shutdown()


def test_drain_acceptance_tasks_actor_object(tmp_path):
    """The acceptance scenario: draining a node with in-flight zero-retry
    tasks, a live zero-restart actor, and a sole-copy object yields every
    task result (budget untouched), the actor serving on a survivor before
    the deadline, and the object readable without reconstruction."""
    import numpy as np

    from cluster_anywhere_tpu.core.worker import drain_stats

    c = Cluster(head_resources={"CPU": 0})
    n1 = c.add_node(num_cpus=2)
    n2 = c.add_node(num_cpus=2)
    c.connect()
    try:
        c.wait_for_nodes(3)

        @ca.remote(num_cpus=1, max_restarts=0)
        class Counter:
            def __init__(self):
                self.n = 0

            def incr(self):
                self.n += 1
                return self.n

            def node(self):
                return os.environ.get("CA_NODE_ID")

        @ca.remote
        def slow(t, started):
            open(os.path.join(started, os.urandom(4).hex()), "w").close()
            time.sleep(t)
            return os.environ.get("CA_NODE_ID")

        @ca.remote
        def produce():
            return np.arange(200_000, dtype=np.float64)

        actor = Counter.remote()
        victim = ca.get(actor.node.remote(), timeout=30)
        assert victim in (n1, n2)
        survivor = n2 if victim == n1 else n1
        # sole-copy primary object on the victim
        obj = produce.options(
            scheduling_strategy=NodeAffinitySchedulingStrategy(victim)
        ).remote()
        ca.wait([obj], timeout=30)
        # in-flight tasks with ZERO retry budget, outliving the deadline
        refs = [
            slow.options(max_retries=0).remote(2.5, str(tmp_path)) for _ in range(4)
        ]
        # in flight: one on each of the three CPUs the actor left (a worker
        # may take over a second to start), the fourth behind one of them
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline and len(os.listdir(tmp_path)) < 3:
            time.sleep(0.02)
        assert len(os.listdir(tmp_path)) >= 3
        time.sleep(0.8)
        t0 = time.monotonic()
        r = ca.drain_node(victim, reason="preemption", deadline_s=4.0)
        assert r["state"] == "draining"
        # the actor serves again on a survivor BEFORE the deadline expires
        # (checked first: proactive migration must not wait out the window).
        # `drain_node` returns before the victim's instance is stopped, which
        # may still answer the first calls: the wait is for the survivor's
        assert ca.get(actor.incr.remote(), timeout=30) >= 1
        where = ca.get(actor.node.remote(), timeout=10)
        while where != survivor and time.monotonic() - t0 < 4.0:
            time.sleep(0.05)
            where = ca.get(actor.node.remote(), timeout=10)
        assert time.monotonic() - t0 < 4.0
        assert where == survivor
        # every result arrives even though max_retries=0: deadline kills are
        # system failures, retried without touching the budget
        got = ca.get(refs, timeout=60)
        assert len(got) == 4 and all(g is not None for g in got)
        # the sole-copy object survived the drain (no ObjectLostError, no
        # reconstruction — its creating task never re-ran)
        arr = ca.get(obj, timeout=30)
        assert arr.shape == (200_000,)
        _wait_state(c, victim, ("drained", "dead"), timeout=15)
        stats = ca.cluster_stats()
        assert stats["drain_actors_migrated"] == 1
        assert stats["drain_objects_migrated"] >= 1
        assert stats["drain_nodes_preemption"] == 1
        # restart budget untouched: the migrated actor still has
        # max_restarts=0 headroom (it would be dead otherwise) — and the
        # incarnation bumped so clients re-resolved
        from cluster_anywhere_tpu.util.state import list_actors

        acts = list_actors()
        assert len(acts) == 1 and acts[0]["state"] == "alive"
        assert acts[0]["incarnation"] == 1
        # the driver exempted at least one retry from the budget, unless
        # every in-flight task happened to finish inside the window
        assert (
            drain_stats()["tasks_evacuated_total"] >= 1
            or stats["drain_deadline_kills"] == 0
        )
    finally:
        c.shutdown()


def test_drain_pg_actor_migrates_and_bundle_accounting_holds():
    """A PG-charged actor on a draining node migrates with its re-placed
    bundle, and the bundle's used-accounting stays correct: the drain-time
    reservation wipe plus the migration charge-return must not double-credit
    (a negative `used` would let a second actor oversubscribe the bundle)."""
    c = Cluster(head_resources={"CPU": 0})
    c.add_node(num_cpus=2)
    c.add_node(num_cpus=2)
    c.connect()
    try:
        c.wait_for_nodes(3)
        pg = ca.placement_group([{"CPU": 1}], strategy="PACK")
        ca.get(pg.ready(), timeout=30)

        @ca.remote(num_cpus=1, max_restarts=0)
        class A:
            def node(self):
                return os.environ.get("CA_NODE_ID")

            def ping(self):
                return "ok"

        a = A.options(placement_group=pg).remote()
        anode = ca.get(a.node.remote(), timeout=30)
        ca.drain_node(anode, reason="manual", deadline_s=8.0)
        # the actor comes back inside the re-placed bundle on the survivor
        assert ca.get(a.ping.remote(), timeout=30) == "ok"
        assert ca.get(a.node.remote(), timeout=10) != anode
        # the 1-CPU bundle is FULL with the migrated actor: a second actor
        # must be refused (the double-credit bug made used go negative and
        # this would wrongly schedule)
        with pytest.raises(Exception, match="resources unavailable"):
            b = A.options(placement_group=pg).remote()
            ca.get(b.ping.remote(), timeout=10)
        _wait_state(c, anode, ("drained", "dead"), timeout=15)
        assert ca.cluster_stats()["drain_actors_migrated"] == 1
    finally:
        c.shutdown()


def test_sigterm_self_drains_and_agent_exits():
    """SIGTERM to a node agent (the preemption warning) self-drains through
    the head — alive -> draining -> drained — and the agent process exits on
    the head's node_shutdown, without SIGKILL."""
    c = Cluster(head_resources={"CPU": 1})
    nid = c.add_node(num_cpus=1)
    c.connect()
    try:
        c.wait_for_nodes(2)
        proc = c._agents[nid]
        os.kill(proc.pid, signal.SIGTERM)
        assert _wait_state(c, nid, ("drained", "dead"), timeout=20) == "drained"
        stats = ca.cluster_stats()
        assert stats["drain_nodes_preemption"] == 1
        assert stats["nodes_died"] == 0  # an announced exit, not a death
        proc.wait(timeout=10)
        assert proc.returncode == 0
    finally:
        c.shutdown()


def test_rank_delegation_excludes_draining_nodes():
    """The submitter-side lease directory skips draining nodes: a block on
    announced-leaving capacity would be killed at the deadline."""
    from cluster_anywhere_tpu.core.scheduling import rank_delegation

    entries = [
        {"node_id": "a", "addr": "x", "pools": {"cpu": {"size": 4, "used": 0}}},
        {"node_id": "b", "addr": "y", "pools": {"cpu": {"size": 4, "used": 1}}},
    ]
    assert [e["node_id"] for e in rank_delegation(entries, "cpu")] == ["a", "b"]
    assert [
        e["node_id"] for e in rank_delegation(entries, "cpu", exclude={"a"})
    ] == ["b"]
    assert rank_delegation(entries, "cpu", exclude={"a", "b"}) == []


@pytest.mark.slow
def test_preemption_mid_workload_chaos():
    """PreemptionSimulator fires mid-workload while WorkerKiller churns pool
    workers: the preempted node drains, every surviving task result arrives,
    and the cluster serves new work afterwards."""
    from cluster_anywhere_tpu.util.chaos import PreemptionSimulator, WorkerKiller

    c = Cluster(head_resources={"CPU": 1})
    n1 = c.add_node(num_cpus=2)
    n2 = c.add_node(num_cpus=2)
    c.connect()
    try:
        c.wait_for_nodes(3)

        @ca.remote
        def work(i):
            time.sleep(0.2)
            return i

        killer = WorkerKiller(period_s=0.7, max_kills=3).start()
        refs = [work.options(max_retries=4).remote(i) for i in range(60)]
        time.sleep(0.5)
        sim = PreemptionSimulator(n1, kill_after_s=20.0).start()
        got = ca.get(refs, timeout=120)
        killer.stop()
        assert got == list(range(60))
        # the preempted node drained (announced exit), not died
        _wait_state(c, n1, ("drained", "dead"), timeout=25)
        sim.stop()
        assert not sim.sigkilled, "drain did not finish inside the warning window"
        # cluster still serves new work after the churn
        assert ca.get(work.remote(7), timeout=60) == 7
        stats = ca.cluster_stats()
        assert stats["drain_nodes_preemption"] == 1
    finally:
        c.shutdown()
