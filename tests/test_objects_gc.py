"""Object lifecycle: shm GC on ref drop, ownership of task returns, lease
failure surfacing (regression tests for review findings)."""

import os
import signal
import time

import numpy as np
import pytest

import cluster_anywhere_tpu as ca


def _session_shm_files(info):
    """All shm file names of the session, across node namespaces."""
    d = os.path.join("/dev/shm", os.path.basename(info["session_dir"]))
    out = []
    for root, _dirs, files in os.walk(d):
        out.extend(files)
    return out


def _driver_arena_allocated() -> int:
    """Bytes currently allocated out of the driver's shm arenas."""
    from cluster_anywhere_tpu.core.worker import global_worker

    total = 0
    for a in global_worker().shm_store._arenas.values():
        total += a.size - sum(sz for _, sz in a.free)
    return total


def test_put_object_gc_after_ref_drop(ca_cluster):
    """Dropping the last ref reclaims the object's arena slice (objects live
    in pre-faulted arena files now, so the file itself persists)."""
    ref = ca.put(np.ones(1_000_000))
    ca.get(ref)
    assert _driver_arena_allocated() >= 8_000_000
    del ref
    deadline = time.time() + 30  # a loaded machine: the bound, not the wait
    while time.time() < deadline and _driver_arena_allocated() > 0:
        time.sleep(0.2)
    assert _driver_arena_allocated() == 0


def test_zero_copy_view_survives_ref_drop(ca_cluster):
    """A numpy view returned by get() must stay intact after the ObjectRef is
    dropped: the value pin keeps the arena slice from being recycled until
    the view itself is garbage-collected (r2 review finding)."""
    import gc

    expect = np.arange(2_000_000, dtype=np.float64)
    ref = ca.put(np.arange(2_000_000, dtype=np.float64))
    view = ca.get(ref)
    del ref
    time.sleep(0.6)  # dec + head GC propagate
    # puts that would land exactly in the freed slice if the pin were absent
    for _ in range(4):
        r2 = ca.put(np.zeros(2_000_000))
        del r2
    time.sleep(0.3)
    np.testing.assert_array_equal(view, expect)
    del view, expect
    gc.collect()
    deadline = time.time() + 8
    while time.time() < deadline and _driver_arena_allocated() > 0:
        time.sleep(0.2)
    assert _driver_arena_allocated() == 0  # pin released -> slice reclaimed


def test_task_return_gc_after_ref_drop(ca_cluster):
    """Task returns are written into the executing worker's arena; the head
    must route the reclaim to that worker (not the submitting owner).  If
    slices leaked, 12 x 64MB returns would overflow a 256MB arena and force
    extra arena files."""
    info = ca_cluster

    @ca.remote
    def big():
        return np.ones(8_000_000)  # 64 MB

    for _ in range(12):
        ref = big.remote()
        assert ca.get(ref).shape == (8_000_000,)
        del ref
    deadline = time.time() + 10

    def arena_files():
        return [f for f in _session_shm_files(info) if f.startswith("arena_")]

    # allow the frees to drain, then check the worker never needed a second
    # arena per process (12 x 64MB through one 256MB arena requires reuse)
    time.sleep(1.0)
    per_owner = {}
    for f in arena_files():
        owner = f[len("arena_"): f.rfind("_")]
        per_owner[owner] = per_owner.get(owner, 0) + 1
    assert per_owner and all(n <= 2 for n in per_owner.values()), per_owner


def test_removed_pg_lease_error_surfaces(ca_cluster):
    pg = ca.placement_group([{"CPU": 1}])
    ca.remove_placement_group(pg)

    @ca.remote
    def f():
        return 1

    ref = f.options(placement_group=pg).remote()
    with pytest.raises(ca.CAError):
        ca.get(ref, timeout=10)


def test_named_actor_reusable_after_init_failure(ca_cluster):
    @ca.remote
    class Bad:
        def __init__(self):
            raise RuntimeError("nope")

    @ca.remote
    class Good:
        def ok(self):
            return 42

    with pytest.raises(ca.CAError):
        Bad.options(name="svc").remote()
    g = Good.options(name="svc").remote()
    assert ca.get(g.ok.remote()) == 42


def test_shm_value_still_readable_while_ref_held(ca_cluster):
    ref = ca.put(np.arange(500_000))
    for _ in range(3):
        out = ca.get(ref)
        assert out[-1] == 499_999


def test_driver_tables_drain_after_refs_die(ca_cluster):
    """Owned in-memory results, owned marks, and lineage specs must all be
    released once their ObjectRefs are garbage collected — a 16k-task run
    used to pin one memstore entry + owned mark + task spec per task,
    degrading every later submission (GC scan + dict weight)."""
    import gc

    from cluster_anywhere_tpu.core.worker import global_worker

    @ca.remote
    def noop():
        return None

    w = global_worker()
    ca.get([noop.remote() for _ in range(50)], timeout=60)  # settle pools
    gc.collect()
    base = (
        len(w.memory_store._entries),
        len(w.reference_counter._owned),
        len(w._lineage),
    )
    refs = [noop.remote() for _ in range(500)]
    assert ca.get(refs, timeout=60) == [None] * 500
    # while refs are alive everything is retained (reconstruction possible)
    assert len(w._lineage) >= 500
    del refs
    gc.collect()
    after = (
        len(w.memory_store._entries),
        len(w.reference_counter._owned),
        len(w._lineage),
    )
    assert all(a <= b for a, b in zip(after, base)), (
        f"driver tables leaked: {base} -> {after}"
    )

    # fire-and-forget: refs dropped BEFORE results arrive must not resurrect
    # unevictable entries when the results land
    for _ in range(200):
        noop.remote()
    time.sleep(2.0)  # let all results arrive
    gc.collect()
    ff = (
        len(w.memory_store._entries),
        len(w.reference_counter._owned),
        len(w._lineage),
    )
    assert all(a <= b for a, b in zip(ff, base)), (
        f"fire-and-forget resurrected entries: {base} -> {ff}"
    )


def test_refcount_debounce_released_once_under_churn(ca_cluster):
    """A flood of handle churn (clone/drop storms, interleaved lifetimes)
    rides the debounced obj_refs coalescer; every object must still be
    released EXACTLY once — the arena drains fully (no leak) and values stay
    readable while any handle is live (no double-free / premature free)."""
    from cluster_anywhere_tpu.core.object_ref import ObjectRef
    from cluster_anywhere_tpu.core.worker import global_worker

    w = global_worker()
    refs = [ca.put(np.full(200_000, float(i))) for i in range(16)]
    # churn: waves of extra handles on every object, dropped immediately —
    # each wave's inc/dec traffic coalesces in the debounce window
    for _ in range(40):
        clones = [ObjectRef(r.id, r.owner, w) for r in refs]
        del clones
    # interleaved drop of half the objects while reading the other half
    for i, r in enumerate(refs[:8]):
        assert ca.get(refs[8 + i])[0] == float(8 + i)  # still readable
        del r
    refs = refs[8:]
    for i, r in enumerate(refs):
        assert ca.get(r)[0] == float(8 + i)  # survived the churn intact
    del refs, r  # the loop variable holds the last object too
    import gc

    gc.collect()
    deadline = time.time() + 10
    while time.time() < deadline and _driver_arena_allocated() > 0:
        time.sleep(0.2)
    assert _driver_arena_allocated() == 0  # every slice reclaimed once


def test_refcount_coalescer_merges_and_cancels(ca_cluster):
    """Unit-level contract of the obj_refs debouncer: updates queued within
    one window merge into one send (suppressed counter), a dec→inc revival
    cancels to a no-op, and an inc→dec pair ships both so the head still
    sees the release.  Verified against the head's holder table."""
    import asyncio

    from cluster_anywhere_tpu.core import protocol
    from cluster_anywhere_tpu.core.worker import global_worker

    w = global_worker()
    ref = ca.put(np.ones(200_000))  # shm-backed: registered at the head
    oid_b = ref.id.binary()
    base_suppressed = protocol.WIRE_STATS["refcount_flushes_suppressed"]

    async def churn():
        # 50 pin/unpin cycles for one synthetic holder, all in one window:
        # first pair ships (inc then dec — the head must see the release),
        # later pairs merge/cancel into it
        for _ in range(50):
            w._queue_refs_on_loop([oid_b], [], "test#pin", False)
            w._queue_refs_on_loop([], [oid_b], "test#pin", False)

    w.run_coro(churn())
    assert (
        protocol.WIRE_STATS["refcount_flushes_suppressed"] - base_suppressed >= 90
    )

    def holders(want):
        # the object's lifetime AUTHORITY: the driver's own ledger, read once
        # the debounce timer has fired and the window is flushed (the driver's
        # own handle registers on the housekeeping tick), however long a
        # loaded machine takes over that
        deadline = time.monotonic() + 10
        n = None
        while time.monotonic() < deadline:
            hs = w.owner_ledger.holders_of(oid_b)
            n = None if hs is None else len(hs)
            if n == want and not w._ref_pending and not w._ref_flush_scheduled:
                break
            time.sleep(0.02)
        return n

    # net effect of the churn is zero: only the driver's own handle remains
    assert holders(1) == 1
    # dec→inc cancellation: a revived pin within one window must leave the
    # holder registered at the head
    async def pin_then_revive():
        w._queue_refs_on_loop([oid_b], [], "test#pin", False)
        w._queue_refs_on_loop([], [oid_b], "test#pin", False)
        w._queue_refs_on_loop([oid_b], [], "test#pin", False)

    w.run_coro(pin_then_revive())
    assert holders(2) == 2  # driver + the revived synthetic pin
    w.run_coro(churn())  # ends on an unpin-balanced window: pin released
    assert holders(1) == 1
    assert ca.get(ref)[0] == 1.0  # object untouched throughout
    del ref


def test_view_survives_producer_sigkill(ca_cluster):
    """Crash-consistency of the arena sweep: a consumer holding a zero-copy
    view of a SIGKILLed producer's object keeps reading valid bytes — the
    unlinked arena file persists while mapped (POSIX), so the head's sweep
    of the dead client's arenas can't corrupt live readers."""
    import numpy as np

    from cluster_anywhere_tpu.core.errors import CAError

    @ca.remote
    class Producer:
        def make(self):
            return ca.put(np.full(300_000, 9.0))

        def pid(self):
            return os.getpid()

    p = Producer.remote()
    ref = ca.get(p.make.remote(), timeout=30)
    arr = ca.get(ref, timeout=30)  # zero-copy view over the producer's arena
    assert arr[0] == 9.0
    pid = ca.get(p.pid.remote(), timeout=30)
    os.kill(pid, signal.SIGKILL)
    # give the head time to notice the death and sweep the dead client's
    # arena files out of /dev/shm
    time.sleep(3.0)
    # the held view stays fully readable after the sweep
    assert float(arr.sum()) == 9.0 * 300_000
    assert arr[-1] == 9.0
