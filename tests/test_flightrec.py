"""Flight recorder: the bounded decision journal (util/flightrec.py), its
metrics-piggyback shipping, cross-plane trace stamping, and the incident
query surface (`flightrec` RPC, `ca events` / `ca incident`,
util.state.flightrec_events/incident).

Fast tier-1 paths: ring bounds + drop-oldest accounting, ship-cursor
drain/restage semantics, the disabled path (REC is None everywhere, zero
allocation), ambient/explicit trace stamping, W3C traceparent round-trip,
error black boxes (typed failures carry `.flight_events`), and netchaos
schedule firings landing in the journal with the seed that replays them.

The full chaos acceptance — seeded blackhole, death verdict, fence, heal,
and an `incident()` timeline that matches the netchaos schedule — is marked
`slow` (seed printed for replay, CA_PARTITION_SEED=<seed>)."""

import os
import time

import pytest

import cluster_anywhere_tpu as ca
from cluster_anywhere_tpu.core import netchaos
from cluster_anywhere_tpu.core.errors import (
    DagTimeoutError,
    FencedError,
)
from cluster_anywhere_tpu.util import flightrec, tracing

SEED = int(os.environ.get("CA_PARTITION_SEED", "1234"))


@pytest.fixture(autouse=True)
def _clean_flightrec():
    """REC and its stats are process-global: never leak armed state (or a
    half-filled ring) into other tests."""
    saved = flightrec.REC
    stats = dict(flightrec.FLIGHTREC_STATS)
    flightrec.REC = None
    yield
    flightrec.REC = saved
    flightrec.FLIGHTREC_STATS.update(stats)
    netchaos.clear()
    netchaos.set_local_node(os.environ.get("CA_NODE_ID", "n0"))


# ------------------------------------------------------------- ring bounds
def test_ring_bounds_and_drop_oldest_accounting():
    rec = flightrec.FlightRecorder(cap=16, node_id="nA", proc="t")
    for i in range(40):
        rec.record("fence", "mint", i=i)
    st = rec.stats()
    assert st["len"] == 16 and st["cap"] == 16
    assert st["seq"] == 40
    assert st["dropped"] == 24
    evs = rec.recent(100)
    # drop-oldest: the survivors are exactly the newest 16, in order
    assert [e["i"] for e in evs] == list(range(24, 40))
    assert all(e["node"] == "nA" and e["proc"] == "t" for e in evs)
    # every event below the floor counts as dropped_unshipped (nothing was
    # ever drained in this process)
    assert st["dropped_unshipped"] == 24


def test_cap_floor():
    # cap is clamped to a sane floor: a misconfigured 0/negative ring would
    # silently drop every event at append time
    assert flightrec.FlightRecorder(cap=0).cap >= 16


def test_ship_cursor_drain_restage_semantics():
    rec = flightrec.FlightRecorder(cap=64)
    for i in range(10):
        rec.record("drain", "fsm", i=i)
    batch = rec.drain()
    assert [e["i"] for e in batch] == list(range(10))
    # the ring is NOT consumed: recent() still sees shipped events (an
    # error raised after the flush still gets its black box)
    assert len(rec.recent(100)) == 10
    # nothing new -> nothing to drain
    assert rec.drain() == []
    # failed send: restage rewinds the cursor, the batch re-drains intact
    rec.restage(batch)
    again = rec.drain()
    assert [e["seq"] for e in again] == [e["seq"] for e in batch]
    # partial drain honors max_n and keeps the remainder staged
    for i in range(10, 16):
        rec.record("drain", "fsm", i=i)
    part = rec.drain(max_n=3)
    assert [e["i"] for e in part] == [10, 11, 12]
    rest = rec.drain()
    assert [e["i"] for e in rest] == [13, 14, 15]


def test_dropped_unshipped_counts_only_unshipped():
    rec = flightrec.FlightRecorder(cap=16)
    for i in range(16):
        rec.record("chaos", "fire", i=i)
    rec.drain()  # everything shipped
    # rotate the whole ring once more WITHOUT draining
    for i in range(16, 32):
        rec.record("chaos", "fire", i=i)
    st = rec.stats()
    assert st["dropped"] == 16
    # the dropped events had been shipped -> no blind spot recorded
    assert st["dropped_unshipped"] == 0
    # now rotate again while the second batch is still unshipped
    for i in range(32, 48):
        rec.record("chaos", "fire", i=i)
    st = rec.stats()
    assert st["dropped"] == 32
    assert st["dropped_unshipped"] == 16


def test_memory_bytes_is_positive_and_bounded():
    rec = flightrec.FlightRecorder(cap=32)
    for i in range(64):
        rec.record("serve", "shed", deployment="d", code=503)
    m = rec.memory_bytes()
    assert 0 < m < 32 * 1024  # 32 small events; sanity bound, not a spec


# ----------------------------------------------------------- disabled path
def test_disabled_path_is_inert():
    """Before init() REC is None: module-level record() is a
    no-op, recent() is [], and error black boxes are empty lists — no
    allocation, no counter bumps."""
    assert flightrec.REC is None
    before = dict(flightrec.FLIGHTREC_STATS)
    flightrec.record("fence", "mint", nid="x")
    assert flightrec.recent() == []
    assert flightrec.FLIGHTREC_STATS == before
    assert FencedError("stale").flight_events == []
    assert DagTimeoutError("n", 1.0).flight_events == []


def test_init_idempotent_updates_origin():
    r1 = flightrec.init(cap=64, node_id=None, proc="early")
    r1.record("node", "boot")
    # late re-init (worker learns its node id after registration) updates
    # origin stamps on the SAME recorder — the ring survives
    r2 = flightrec.init(node_id="n7", proc="worker-1")
    assert r2 is r1 and r2.node_id == "n7"
    r2.record("node", "ready")
    evs = r2.recent()
    assert evs[0]["node"] is None and evs[1]["node"] == "n7"
    flightrec.shutdown()
    assert flightrec.REC is None


# ----------------------------------------------------------- trace stamping
def test_record_stamps_ambient_trace_and_explicit_override():
    rec = flightrec.init(cap=64, node_id="n0", proc="t")
    tr = {"tid": tracing.new_trace_id(), "sid": tracing.new_span_id()}
    tok = tracing.push_execution(tr)
    try:
        rec.record("dag", "tick")
    finally:
        tracing.pop_execution(tok)
    ev = rec.recent()[-1]
    assert ev["trace"]["tid"] == tr["tid"]
    # outside the span: no trace stamp
    rec.record("dag", "tick2")
    assert "trace" not in rec.recent()[-1]
    # explicit trace kwarg (async call sites with no ambient ctx) wins over
    # the ambient stamp — fields update after the ambient trace is written
    explicit = {"tid": "feedbeef" * 4, "sid": "12345678"}
    rec.record("serve", "shed", trace=explicit)
    assert rec.recent()[-1]["trace"] == explicit


def test_traceparent_roundtrip():
    tr = {"tid": tracing.new_trace_id(), "sid": tracing.new_span_id()}
    hdr = tracing.format_traceparent(tr)
    ver, tid32, sid16, flags = hdr.split("-")
    assert ver == "00" and len(tid32) == 32 and len(sid16) == 16
    back = tracing.parse_traceparent(hdr)
    # internally-minted (zero-padded) ids round-trip to their short form
    assert back["tid"] == tr["tid"] and back["sid"] == tr["sid"]
    # externally-minted full-width ids survive verbatim
    ext = "00-" + "ab" * 16 + "-" + "cd" * 8 + "-01"
    got = tracing.parse_traceparent(ext)
    assert got["tid"] == "ab" * 16 and got["sid"] == "cd" * 8
    # malformed headers parse to None, never raise
    for bad in (None, "", "xx", "00-short-1234-01", "zz-" + "a" * 32):
        assert tracing.parse_traceparent(bad) is None


# --------------------------------------------------------- error black box
def test_typed_errors_carry_plane_filtered_slices():
    rec = flightrec.init(cap=64, node_id="n0", proc="t")
    rec.record("fence", "rpc_fenced", nid="n9")
    rec.record("dag", "dag_actor_death", actor="a1")
    rec.record("serve", "serve_shed", code=503)
    fe = FencedError("stale incarnation")
    assert [e["event"] for e in fe.flight_events] == ["rpc_fenced"]
    de = DagTimeoutError("node3", 2.0)
    assert [e["event"] for e in de.flight_events] == ["dag_actor_death"]
    # slices are plain picklable dicts — they cross process boundaries
    import pickle

    fe2 = pickle.loads(pickle.dumps(fe))
    assert fe2.flight_events == fe.flight_events


# ------------------------------------------------- netchaos -> the journal
def test_netchaos_firings_recorded_and_match_schedule():
    """Every seeded schedule transition lands in the journal with the seed
    and spec, so a chaos incident is replayable from the events alone — and
    the journal's transition order matches nc.events exactly."""
    rec = flightrec.init(cap=256, node_id="n0", proc="t")
    spec = f"seed={SEED};n0<>node1:blackhole@1+2;n0>node2:flap=0.5/0.5@0.5"
    nc = netchaos.NetworkChaos(spec, local="n0", now=0.0)
    for t in [i * 0.1 for i in range(45)]:  # scripted clock: deterministic
        nc.link_down("n0", "node1", now=t)
        nc.link_down("n0", "node2", now=t)
    journal = rec.recent(256, plane="chaos")
    assert journal, "schedule firings never reached the journal"
    assert all(e["seed"] == SEED and e["spec"] == spec for e in journal)
    j = [
        ("down" if e["event"] == "link_down" else "up",
         e["src"], e["dst"], e["t_rel"])
        for e in journal
    ]
    assert j == list(nc.events)
    # the blackhole window itself is in there: down@1, up@3 on the bh link
    bh = [x for x in j if x[1] == "n0" and x[2] == "node1"]
    assert ("down", "n0", "node1", 1.0) in bh
    assert ("up", "n0", "node1", 3.0) in bh


# --------------------------------------------- cluster: the incident query
def test_fence_incident_timeline_on_killed_node():
    """Kill a node, fence a zombie re-register, then ask the head for the
    story: the merged journal must contain the death verdict and the fence
    refusal in timestamp order, `incident()` must aggregate them, and the
    trace/plane filters must hold."""
    from cluster_anywhere_tpu.cluster_utils import Cluster
    from cluster_anywhere_tpu.core import protocol as P
    from cluster_anywhere_tpu.core.config import CAConfig
    from cluster_anywhere_tpu.core.worker import global_worker
    from cluster_anywhere_tpu.util import state

    cfg = CAConfig()
    cfg.health_check_period_s = 0.5
    cfg.health_check_failure_threshold = 3
    c = Cluster(head_resources={"CPU": 1}, config=cfg)
    nid = c.add_node(num_cpus=1)
    c.connect()
    try:
        c.wait_for_nodes(2)
        row = next(n for n in ca.nodes() if n["node_id"] == nid)
        inc0 = row["incarnation"]
        c.remove_node(nid)  # SIGKILL: silent death
        deadline = time.time() + 30
        while time.time() < deadline:
            row = next((n for n in ca.nodes() if n["node_id"] == nid), None)
            if row is not None and not row["alive"]:
                break
            time.sleep(0.1)
        assert row is not None and not row["alive"], "death verdict missing"

        bc = P.BlockingClient(c.head_tcp)
        try:
            with pytest.raises(FencedError):
                bc.call(
                    "register", role="agent", client_id=nid,
                    addr="tcp:127.0.0.1:1", resources={"CPU": 1}, ninc=inc0,
                )
        finally:
            bc.close()

        w = global_worker()
        r = w.head_call("flightrec", limit=5000)
        evs = r["events"]
        by_event = {}
        for e in evs:
            by_event.setdefault(e["event"], []).append(e)
        assert "node_died" in by_event, [e["event"] for e in evs]
        assert "agent_register_fenced" in by_event or "rpc_fenced" in by_event
        died_ts = by_event["node_died"][0]["ts"]
        fence_ev = (by_event.get("agent_register_fenced")
                    or by_event["rpc_fenced"])[0]
        # causal order: the verdict precedes the refusal it authorizes
        assert died_ts <= fence_ev["ts"]
        assert fence_ev["plane"] == "fence"
        # the query surface filters server-side
        fenced_only = w.head_call("flightrec", plane="fence")["events"]
        assert fenced_only and all(e["plane"] == "fence" for e in fenced_only)

        # incident() aggregates the same window into planes/nodes/span
        inc = state.incident(window_s=600.0)
        assert inc["events"]
        assert inc["planes"].get("fence", 0) >= 1
        assert inc["span_s"] >= 0

        # driver-side events ship head-ward on the metrics piggyback: this
        # process's journal slice must appear in the head ring (no new RPC)
        assert flightrec.REC is not None  # armed by connect()
        flightrec.REC.record("fence", "test_probe_event", marker="xyzzy")
        deadline = time.time() + 30
        found = False
        while time.time() < deadline and not found:
            evs = w.head_call("flightrec", event="test_probe_event")["events"]
            found = any(e.get("marker") == "xyzzy" for e in evs)
            if not found:
                time.sleep(0.25)
        assert found, "driver journal slice never reached the head ring"
    finally:
        c.shutdown()


# ------------------------------------------------------- the slow acceptance
@pytest.mark.slow
def test_chaos_timeline_acceptance():
    """THE flight-recorder acceptance: a seeded netchaos blackhole severs a
    node mid-workload; after the heal, `incident()` reconstructs the whole
    cross-node story — fence -> cancel -> heal -> rejoin — in timestamp
    order, and the journal's chaos firings carry the seed that replays the
    schedule.  Replay a failure with CA_PARTITION_SEED=<seed>."""
    print(f"\n[flightrec-chaos] seed={SEED} (replay: CA_PARTITION_SEED={SEED})")
    from cluster_anywhere_tpu.cluster_utils import Cluster
    from cluster_anywhere_tpu.core.config import CAConfig
    from cluster_anywhere_tpu.core.worker import global_worker
    from cluster_anywhere_tpu.util import state
    from cluster_anywhere_tpu.util.chaos import NetworkPartition

    cfg = CAConfig()
    cfg.health_check_period_s = 0.5
    cfg.health_check_failure_threshold = 3
    c = Cluster(head_resources={"CPU": 2}, config=cfg)
    nid = c.add_node(num_cpus=2)
    c.connect()
    try:
        c.wait_for_nodes(2)
        w = global_worker()
        row = next(n for n in ca.nodes() if n["node_id"] == nid)
        inc0 = row["incarnation"]

        @ca.remote(max_retries=5)
        def work(i, sleep_s):
            import time as _t

            _t.sleep(sleep_s)
            return i

        refs = [work.remote(i, 2.0) for i in range(6)]
        time.sleep(0.4)
        part = NetworkPartition(nid, "n0", duration_s=8.0, seed=SEED).start()

        deadline = time.time() + 30
        while time.time() < deadline:
            row = next((n for n in ca.nodes() if n["node_id"] == nid), None)
            if row is None or not row["alive"]:
                break
            time.sleep(0.05)
        assert row is None or not row["alive"], f"no death verdict (seed={SEED})"
        assert ca.get(refs, timeout=120) == list(range(6))

        part.wait_heal()
        deadline = time.time() + 40
        row = None
        while time.time() < deadline:
            row = next((n for n in ca.nodes() if n["node_id"] == nid), None)
            if row is not None and row["alive"] and row["incarnation"] > inc0:
                break
            time.sleep(0.1)
        assert row is not None and row["alive"] and row["incarnation"] > inc0

        # give the last journal slices a flush cycle to reach the head
        def phase_ts():
            evs = w.head_call("flightrec", limit=5000)["events"]
            out = {}
            for e in evs:
                out.setdefault(e["event"], []).append(e)
            return evs, out

        deadline = time.time() + 30
        while time.time() < deadline:
            evs, by_event = phase_ts()
            if ("node_died" in by_event
                    and ("rpc_fenced" in by_event
                         or "agent_register_fenced" in by_event)
                    and "node_joined" in by_event):
                break
            time.sleep(0.5)

        assert "node_died" in by_event, f"seed={SEED}: no verdict event"
        fences = (by_event.get("rpc_fenced", [])
                  + by_event.get("agent_register_fenced", []))
        assert fences, f"seed={SEED}: fence never fired in the journal"
        died = min(e["ts"] for e in by_event["node_died"])
        fence = min(e["ts"] for e in fences)
        # rejoin: the node joined again AFTER the verdict, at a bumped
        # incarnation
        rejoins = [
            e for e in by_event.get("node_joined", [])
            if e["ts"] > died and e.get("node_id") == nid
        ]
        assert died <= fence, f"seed={SEED}: fence preceded its verdict"
        assert rejoins, f"seed={SEED}: no rejoin in the journal"
        assert fence <= max(e["ts"] for e in rejoins) + 40

        inc = state.incident(window_s=900.0, limit=5000)
        assert inc["planes"].get("fence", 0) >= 1
        assert inc["planes"].get("node", 0) >= 1
        assert nid in inc["nodes"] or any(
            e.get("node_id") == nid for e in inc["events"]
        )
        # events come back ts-sorted: the timeline is directly renderable
        ts = [e["ts"] for e in inc["events"]]
        assert ts == sorted(ts)
    finally:
        c.shutdown()
