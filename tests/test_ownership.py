"""Ownership-based object directory + p2p collective transport (VERDICT r4
missing #2/#3): the head must stop being the data/location hot path.

Reference roles: src/ray/object_manager/ownership_based_object_directory.h:37
(owners answer location queries), gloo_collective_group.py:184 (collective
bytes move directly between workers).  The head's per-method rpc_counts make
the claim falsifiable: these tests assert the hot loops add ~zero head RPCs.
"""

import os
import time

import numpy as np
import pytest

import cluster_anywhere_tpu as ca
from cluster_anywhere_tpu.parallel import collectives as coll


def _head_counts():
    from cluster_anywhere_tpu.core.worker import global_worker

    return global_worker().head_call("stats").get("rpc_counts", {})


@ca.remote
class _Rank(coll.CollectiveActorMixin):
    def allreduce_many(self, x, n_ops, group="default"):
        out = None
        for _ in range(n_ops):
            out = coll.allreduce(np.asarray(x, dtype=np.float64), group_name=group)
        return out

    def allgather_once(self, x, group="default"):
        return coll.allgather(np.asarray(x), group_name=group)

    def sendrecv(self, peer, value, group="default"):
        coll.send(np.asarray([value], dtype=np.float64), peer, group_name=group)
        return coll.recv(peer, group_name=group)


def test_p2p_collectives_add_no_per_op_head_traffic(ca_cluster_module):
    """After the one-time rendezvous, N ranks x K allreduces must add ZERO
    kv_get/kv_put/obj_locate head calls — the bytes ride rank-to-rank
    connections (ring), not the head KV or the object store."""
    world = 4
    ranks = [_Rank.remote() for _ in range(world)]
    coll.create_collective_group(ranks, world, list(range(world)))
    # warmup op: lazy peer-addr resolution does its kv_gets here
    ca.get([r.allreduce_many.remote(i, 1) for i, r in enumerate(ranks)])

    before = _head_counts()
    outs = ca.get(
        [r.allreduce_many.remote(float(i), 10) for i, r in enumerate(ranks)],
        timeout=120,
    )
    after = _head_counts()

    expect = sum(range(world))
    for out in outs:
        np.testing.assert_allclose(out, expect)
    for m in ("kv_get", "kv_put", "kv_keys", "obj_locate"):
        delta = after.get(m, 0) - before.get(m, 0)
        assert delta == 0, f"{m} grew by {delta} during p2p collectives"
    coll.destroy_group_on(ranks)
    for r in ranks:
        ca.kill(r)


def test_p2p_allgather_and_sendrecv(ca_cluster_module):
    world = 2
    ranks = [_Rank.remote() for _ in range(world)]
    coll.create_collective_group(ranks, world, [0, 1], group_name="sr")
    ca.get([r.allreduce_many.remote(0.0, 1, "sr") for r in ranks])  # warmup

    before = _head_counts()
    gathered = ca.get([r.allgather_once.remote(i * 10, "sr") for i, r in enumerate(ranks)])
    swapped = ca.get(
        [ranks[0].sendrecv.remote(1, 5.0, "sr"), ranks[1].sendrecv.remote(0, 7.0, "sr")],
        timeout=60,
    )
    after = _head_counts()

    for lst in gathered:
        assert [int(np.asarray(x)) for x in lst] == [0, 10]
    assert float(swapped[0][0]) == 7.0 and float(swapped[1][0]) == 5.0
    for m in ("kv_get", "kv_put", "kv_keys", "obj_locate"):
        assert after.get(m, 0) - before.get(m, 0) == 0, m
    coll.destroy_group_on(ranks, "sr")
    for r in ranks:
        ca.kill(r)


def test_kv_backend_still_available(ca_cluster_module):
    """backend='kv' keeps the KV-rendezvous transport (remote clients)."""
    g = coll.init_collective_group(1, 0, backend="kv", group_name="kv1")
    out = g.allreduce(np.asarray([1.0, 2.0]))
    np.testing.assert_allclose(out, [1.0, 2.0])
    coll.destroy_collective_group("kv1")


def test_forwarded_ref_resolves_via_owner(ca_cluster_module):
    """A ref forwarded ahead of completion resolves by polling its OWNER
    process (p2p), not the head: the borrower's wait adds at most a couple
    of fallback obj_locate calls instead of one per poll tick."""

    @ca.remote
    def slow_make():
        time.sleep(0.6)
        return np.arange(1000)

    @ca.remote
    def consume(holder):
        return int(ca.get(holder[0]).sum())

    before = _head_counts()
    r = slow_make.remote()
    out = ca.get(consume.remote([r]), timeout=60)
    after = _head_counts()

    assert out == 499500
    # ~30 poll ticks over 0.6s; owner-first polling sends at most every 8th
    # to the head.  Generous bound: the old path would have done ~all of
    # them against the head.
    delta = after.get("obj_locate", 0) - before.get("obj_locate", 0)
    assert delta <= 6, f"borrower leaned on the head: {delta} obj_locate calls"
    # the p2p directory was actually consulted
    assert after.get("client_addr", 0) > before.get("client_addr", 0)


def test_owner_locate_answers_for_driver_objects(ca_cluster_module):
    """The driver serves owner_locate for objects it owns (it runs a p2p
    listener like every worker — core_worker.h role)."""
    from cluster_anywhere_tpu.core.worker import global_worker

    w = global_worker()
    ref = ca.put(np.arange(64, dtype=np.float64))
    loc = w.owner_locate_local(ref.id.binary())
    # small puts may be shm-backed or served inline by value; either way the
    # owner answers authoritatively
    assert loc["found"], loc
    assert loc.get("shm_name") or loc.get("v") is not None, loc
    # and over the wire: a worker can dial the driver's p2p socket
    addr = w._p2p_addr() or w.serve_addr
    assert addr, "driver has no p2p listener"


def test_owner_served_inline_nested_refs_survive_container_release(ca_cluster_module):
    """An inline container of ObjectRefs served by value over the owner path
    must carry transit pins for the nested refs (the task-arg borrowing
    protocol): without them the head can GC the inner object between the
    owner's reply and the borrower registering its handle.  Regression for
    the bare-serialization.pack gap in owner_locate."""

    @ca.remote
    def make_container():
        inner = ca.put(np.arange(256, dtype=np.float64))
        time.sleep(0.4)  # borrower polls while we're still pending
        return [inner]  # small list of refs: stays inline on the owner

    @ca.remote
    def consume(holder):
        # resolve the forwarded container ref (owner-served, inline), then
        # drop every container handle before touching the inner ref
        container = ca.get(holder[0])
        inner = container[0]
        del container, holder
        import gc

        gc.collect()
        time.sleep(0.3)  # any missing pin lets GC reap the inner object now
        return int(ca.get(inner).sum())

    r = make_container.remote()
    out = ca.get(consume.remote([r]), timeout=60)
    assert out == int(np.arange(256).sum())


def test_p2p_and_kv_backend_dtype_parity(ca_cluster_module):
    """The two interchangeable host backends must agree on result dtypes and
    values: bool sums count (not saturate), integer max/min keep their
    dtype, float32 mean stays float32."""
    cases = [
        (np.array([True, False, True]), "sum", np.int64),
        (np.array([3, 9], dtype=np.int32), "max", np.int32),
        (np.array([3, 9], dtype=np.int32), "min", np.int32),
        (np.array([2.0, 4.0], dtype=np.float32), "mean", np.float32),
        (np.array([1, 2], dtype=np.int32), "mean", np.float64),
    ]
    for i, (arr, op, want_dtype) in enumerate(cases):
        gk = coll.init_collective_group(1, 0, backend="kv", group_name=f"dk{i}")
        gp = coll.init_collective_group(1, 0, backend="host", group_name=f"dp{i}")
        try:
            rk, rp = gk.allreduce(arr, op=op), gp.allreduce(arr, op=op)
            assert rk.dtype == rp.dtype == want_dtype, (op, arr.dtype, rk.dtype, rp.dtype)
            np.testing.assert_allclose(rk, rp)
        finally:
            coll.destroy_collective_group(f"dk{i}")
            coll.destroy_collective_group(f"dp{i}")


def test_owner_death_fails_fast_with_object_lost():
    """TRUE owner death (the reference's OwnerDiedError): a ref CREATED BY a
    worker on a doomed node is forwarded to a borrower pinned to the head
    node; killing the owner's node makes the borrower's get raise
    ObjectLostError promptly (head tombstones the departed client; the
    borrower's head-fallback check concludes unrecoverability) instead of
    polling to its timeout."""
    import cluster_anywhere_tpu.cluster_utils as cu
    from cluster_anywhere_tpu.core.scheduling_strategies import (
        NodeAffinitySchedulingStrategy,
    )

    if ca.is_initialized():  # the module fixture's single-node cluster
        ca.shutdown()
    c = cu.Cluster(head_resources={"CPU": 2})
    nid = c.add_node(num_cpus=2)
    c.connect()
    c.wait_for_nodes(2)
    try:

        @ca.remote
        def slow_make():
            time.sleep(3.0)
            return np.arange(500)

        @ca.remote
        def make_on_node():
            # the inner ref's OWNER is this worker process on nid
            return [slow_make.remote()]

        @ca.remote
        def consume(holder):
            t0 = time.monotonic()
            try:
                val = int(ca.get(holder[0], timeout=30).sum())
                return ("ok", val)
            except Exception as e:
                return ("err", type(e).__name__, time.monotonic() - t0)

        holder = ca.get(
            make_on_node.options(
                scheduling_strategy=NodeAffinitySchedulingStrategy(nid, soft=False)
            ).remote(),
            timeout=30,
        )
        # pin the borrower to the head node so the kill below cannot take it
        out_ref = consume.options(
            scheduling_strategy=NodeAffinitySchedulingStrategy("n0", soft=False)
        ).remote(holder)
        time.sleep(1.0)  # borrower is mid-poll against the nid owner
        c.remove_node(nid)  # the OWNER (and producer) dies
        out = ca.get(out_ref, timeout=60)
        assert out[0] == "err" and out[1] == "ObjectLostError", out
        assert out[2] < 15.0, f"owner death took {out[2]:.1f}s to surface"
    finally:
        c.shutdown()


def test_producer_node_death_reconstructs_for_borrower():
    """Contrast case: the ref is DRIVER-owned (normal f.remote return), only
    the producing node dies — the borrower (pinned to the surviving head
    node) resolves via lineage reconstruction."""
    import cluster_anywhere_tpu.cluster_utils as cu
    from cluster_anywhere_tpu.core.scheduling_strategies import (
        NodeAffinitySchedulingStrategy,
    )

    if ca.is_initialized():
        ca.shutdown()
    c = cu.Cluster(head_resources={"CPU": 2})
    nid = c.add_node(num_cpus=2)
    c.connect()
    c.wait_for_nodes(2)
    try:

        @ca.remote
        def slow_make():
            time.sleep(1.2)
            return np.arange(2000)

        @ca.remote
        def consume(holder):
            return int(ca.get(holder[0], timeout=90).sum())

        ref = slow_make.options(
            scheduling_strategy=NodeAffinitySchedulingStrategy(nid, soft=True)
        ).remote()
        out_ref = consume.options(
            scheduling_strategy=NodeAffinitySchedulingStrategy("n0", soft=False)
        ).remote([ref])
        time.sleep(0.4)
        c.remove_node(nid)  # producer dies; the DRIVER owner survives
        assert ca.get(out_ref, timeout=120) == int(np.arange(2000).sum())
    finally:
        c.shutdown()


# ---------------------------------------------------------------------------
# Ownership plane (owner-resident lifetime): the borrower ledger settles
# inc/dec at OWNER processes over direct connections; the head keeps only the
# registry (obj_created/obj_release) and adopts orphaned ledgers on owner
# death from the owner_sync digests.


def _driver_arena_bytes():
    from cluster_anywhere_tpu.core.worker import global_worker

    w = global_worker()
    return sum(
        a.size - sum(sz for _, sz in a.free)
        for a in w.shm_store._arenas.values()
    )


def test_owner_plane_settles_objects_off_head(ca_cluster_module):
    """The acceptance workload (create -> borrow across workers -> release)
    must settle refcounts with ZERO head obj_refs/transit_done messages: the
    borrower registrations, transit acks, value pins, and releases all land
    on the driver's OwnerLedger over direct connections."""
    import gc

    from cluster_anywhere_tpu.core.ownership import OWNER_STATS

    @ca.remote
    def borrow(holder):
        return int(ca.get(holder[0]).sum())

    arr = np.arange(4000)
    ca.get([borrow.remote([ca.put(arr)]) for _ in range(3)], timeout=60)
    time.sleep(1.2)  # let warmup refcounts settle before counting
    before = _head_counts()
    recv0 = OWNER_STATS["refs_recv"]
    refs = [ca.put(arr) for _ in range(8)]
    outs = ca.get([borrow.remote([r]) for r in refs], timeout=120)
    assert outs == [int(arr.sum())] * 8
    del refs, outs
    gc.collect()
    deadline = time.monotonic() + 15
    while time.monotonic() < deadline and _driver_arena_bytes() > 0:
        time.sleep(0.2)
    after = _head_counts()
    for m in ("obj_refs", "transit_done", "obj_pin"):
        delta = after.get(m, 0) - before.get(m, 0)
        assert delta == 0, f"{m} grew by {delta}: settlement leaned on the head"
    # the ledger actually served borrowers (owner_refs/owner_transit_done)
    assert OWNER_STATS["refs_recv"] > recv0
    # ... and the promoted slices were reclaimed owner-side (full settle)
    assert _driver_arena_bytes() == 0


def test_owner_death_failover_adopts_ledger(ca_cluster_module):
    """Owner dies with a live borrower: the head adopts the ledger from the
    last owner_sync digest (the borrower appears as a holder), the
    borrower's release settles through the central path, and the registry
    record plus the dead owner's shm files are reclaimed — no leaked
    segments or spill files."""
    import gc
    import signal as _signal

    from cluster_anywhere_tpu.core.worker import global_worker

    @ca.remote
    class Owner:
        def __init__(self):
            self._keep = None

        def make(self):
            self._keep = ca.put(np.full(50_000, 7.0))  # shm-backed put
            return [self._keep]  # driver borrows via the holder list

        def pid_cid(self):
            from cluster_anywhere_tpu.core.worker import global_worker

            return os.getpid(), global_worker().client_id

    o = Owner.remote()
    holder = ca.get(o.make.remote(), timeout=30)
    inner = holder[0]
    oid_hex = inner.id.hex()
    assert float(ca.get(inner, timeout=30)[0]) == 7.0
    pid, owner_cid = ca.get(o.pid_cid.remote(), timeout=30)
    # one owner_sync period so the borrower-bearing digest reaches the head
    time.sleep(1.8)
    os.kill(pid, _signal.SIGKILL)
    time.sleep(2.5)  # head notices the death and adopts the ledger

    from cluster_anywhere_tpu.util import state

    recs = [x for x in state.list_objects() if x["object_id"] == oid_hex]
    assert recs, "head dropped the record instead of adopting the ledger"
    # now the borrower releases: settlement must drain through the head
    del holder, inner
    gc.collect()
    deadline = time.monotonic() + 20
    while time.monotonic() < deadline:
        if not any(
            x["object_id"] == oid_hex for x in state.list_objects()
        ):
            break
        time.sleep(0.3)
    assert not any(
        x["object_id"] == oid_hex for x in state.list_objects()
    ), "adopted object never settled after the borrower released"
    # the dead owner's arena files were swept (no leaked shm segments)
    w = global_worker()
    sdir = os.path.join("/dev/shm", w.session_name)
    leaked = []
    for root, _dirs, files in os.walk(sdir):
        leaked += [f for f in files if f.startswith(f"arena_{owner_cid}_")]
    assert not leaked, f"dead owner's segments leaked: {leaked}"


def test_early_ref_grace_window_bounds_pending_refs():
    """Regression for the inc-before-obj_created race handling: a holder
    registration that arrives early is adopted if obj_created lands within
    the grace window, and is SWEPT (stats early_refs_expired) — not kept by
    dict-ordering luck — once the window passes."""
    from cluster_anywhere_tpu.core.worker import global_worker

    if ca.is_initialized():
        ca.shutdown()
    ca.init(num_cpus=1, early_ref_grace_s=1.0)
    try:
        w = global_worker()

        def notify(method, **fields):
            w.loop.call_soon_threadsafe(
                lambda: w.head.notify(method, **fields)
            )

        from cluster_anywhere_tpu.util import state

        def registered(oid, bound=10.0):
            """The head's record of `oid`, once obj_created has landed."""
            deadline = time.monotonic() + bound
            rec = None
            while time.monotonic() < deadline and rec is None:
                rec = next(
                    (x for x in state.list_objects()
                     if x["object_id"] == oid.hex()), None,
                )
                time.sleep(0.1)
            return rec

        # within the window: early inc, then obj_created -> holder adopted
        oid1 = os.urandom(20)
        notify("obj_refs", inc=[oid1], as_id="ghost-holder")
        time.sleep(0.3)
        notify("obj_created", oid=oid1, size=1, owner="ghost-owner")
        rec = registered(oid1)
        assert rec is not None and rec["num_holders"] == 1, rec

        # past the window: the early inc is swept before obj_created lands
        oid2 = os.urandom(20)
        notify("obj_refs", inc=[oid2], as_id="ghost-holder")
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            if w.head_call("stats")["stats"].get("early_refs_expired", 0) >= 1:
                break
            time.sleep(0.2)
        assert w.head_call("stats")["stats"].get("early_refs_expired", 0) >= 1
        notify("obj_created", oid=oid2, size=1, owner="ghost-owner")
        rec2 = registered(oid2)
        assert rec2 is not None and rec2["num_holders"] == 0, rec2
    finally:
        ca.shutdown()
