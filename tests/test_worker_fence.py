"""A worker the head takes for dead leaves no process behind, and neither
does a session: the head kills its own silent child when it fences it, a
chip-holding worker is given longer before that, and `ca.shutdown()` sweeps
what a killed head could not."""

import os
import signal
import time

import pytest

import cluster_anywhere_tpu as ca
from cluster_anywhere_tpu.core import api
from cluster_anywhere_tpu.core.config import CAConfig
from cluster_anywhere_tpu.core.worker import global_worker


def _alive(pid: int) -> bool:
    """Runs, and is not a zombie waiting to be reaped."""
    try:
        with open(f"/proc/{pid}/stat") as f:
            return f.read().rsplit(")", 1)[1].split()[0] != "Z"
    except OSError:
        return False


def _wait(cond, timeout_s: float) -> bool:
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(0.05)
    return cond()


def _workers():
    return global_worker().head_call("list_workers")["workers"]


def _fast_config() -> CAConfig:
    cfg = CAConfig()
    cfg.health_check_period_s = 0.5
    # wide enough that a worker starved by the other tests is not silent
    cfg.health_check_failure_threshold = 6  # a cpu worker: silent for 3 s
    cfg.accel_health_check_failure_threshold = 16  # a chip's holder: 8 s
    return cfg


@pytest.fixture
def cluster(monkeypatch):
    for k in ("TPU_VISIBLE_CHIPS", "TPU_CHIPS_PER_PROCESS_BOUNDS", "TPU_PROCESS_BOUNDS"):
        monkeypatch.delenv(k, raising=False)
    if ca.is_initialized():
        ca.shutdown()
    ca.init(num_cpus=2, num_tpus=1, config=_fast_config())
    try:
        yield
    finally:
        if ca.is_initialized():
            ca.shutdown()


def test_silent_worker_is_killed_when_fenced_and_a_chip_holder_gets_longer(cluster):
    @ca.remote
    class Pid:
        def pid(self):
            return os.getpid()

    cpu = Pid.remote()
    tpu = Pid.options(num_tpus=1).remote()
    cpu_pid, tpu_pid = ca.get([cpu.pid.remote(), tpu.pid.remote()], timeout=60)
    pools = {w["pid"]: w["pool"] for w in _workers()}
    assert pools[cpu_pid] == "cpu" and pools[tpu_pid] != "cpu"

    def state_of(pid):
        return next(w["state"] for w in _workers() if w["pid"] == pid)

    # both stand still, as a process does whose native call holds the lock
    t0 = time.monotonic()
    os.kill(cpu_pid, signal.SIGSTOP)
    os.kill(tpu_pid, signal.SIGSTOP)
    try:
        assert _wait(lambda: state_of(cpu_pid) == "dead", 12)
        fenced_after = time.monotonic() - t0
        # the verdict is carried out: a stopped process reads no closed socket
        assert _wait(lambda: not _alive(cpu_pid), 5)
        # the chip's holder outlives the cpu worker's limit...
        assert fenced_after < 6.0
        assert state_of(tpu_pid) != "dead" and _alive(tpu_pid)
        time.sleep(max(0.0, 5.0 - (time.monotonic() - t0)))
        assert state_of(tpu_pid) != "dead"
        # ...and comes back unharmed when its stall ends inside its own
        os.kill(tpu_pid, signal.SIGCONT)
        assert ca.get(tpu.pid.remote(), timeout=30) == tpu_pid
        # a stall past its limit is fenced and killed like any other
        os.kill(tpu_pid, signal.SIGSTOP)
        assert _wait(lambda: state_of(tpu_pid) == "dead", 25)
        assert _wait(lambda: not _alive(tpu_pid), 5)
    finally:
        for pid in (cpu_pid, tpu_pid):
            try:
                os.kill(pid, signal.SIGCONT)
            except ProcessLookupError:
                pass


def test_shutdown_leaves_no_process_even_when_the_head_was_killed(cluster):
    @ca.remote
    def pid():
        return os.getpid()

    worker_pid = ca.get(pid.remote(), timeout=60)
    head_pid = api._head_proc.pid
    pids = {w["pid"] for w in _workers() if w["state"] != "dead"} | {worker_pid}
    assert all(_alive(p) for p in pids)
    # a head that cannot tear down: its workers would wait out their grace
    os.kill(head_pid, signal.SIGKILL)
    ca.shutdown()
    assert _wait(lambda: not any(_alive(p) for p in pids | {head_pid}), 5)


def test_a_head_that_stood_still_itself_fences_nobody(cluster):
    """The whole host stands still for longer than the limit (a TPU backend
    starting up does that to a small one) and the head wakes first: the beats
    it could not hear are not held against the workers."""
    @ca.remote
    def pid():
        return os.getpid()

    ca.get(pid.remote(), timeout=60)
    head_pid = api._head_proc.pid
    before = {w["worker_id"]: w["pid"] for w in _workers() if w["state"] != "dead"}
    assert before
    frozen = [head_pid, *before.values()]
    for p in frozen:
        os.kill(p, signal.SIGSTOP)
    try:
        time.sleep(6.0)  # twice the cpu workers' limit of 3 s
    finally:
        for p in frozen:
            os.kill(p, signal.SIGCONT)
            time.sleep(0.05)
    time.sleep(1.5)  # several monitor ticks and a beat from everyone
    after = {w["worker_id"]: w for w in _workers()}
    with open(os.path.join(global_worker().session_dir, "events.jsonl")) as f:
        events = f.read()
    assert "head_deaf" in events
    assert [wid for wid in before if after[wid]["state"] == "dead"] == [], events
    assert all(_alive(p) for p in before.values())
    assert ca.get(pid.remote(), timeout=30) in before.values()


def test_a_ref_dropped_inside_the_ref_counter_does_not_wait_for_itself():
    """The collector may finalise an ObjectRef while its thread is inside
    `add_local_ref` (at the id's `__hash__`): the finaliser's
    `remove_local_ref` comes back into the counter on the same thread."""
    import threading

    from cluster_anywhere_tpu.core.ids import ObjectID
    from cluster_anywhere_tpu.core.reference_counter import ReferenceCounter

    rc = ReferenceCounter()
    other = ObjectID(b"\x01" * ObjectID.SIZE)
    rc.add_local_ref(other)

    class DropsARefWhenHashed(ObjectID):
        def __hash__(self):
            if rc.local_count(other):
                rc.remove_local_ref(other)  # what ObjectRef.__del__ does
            return super().__hash__()

    t = threading.Thread(
        target=rc.add_local_ref, args=(DropsARefWhenHashed(b"\x02" * ObjectID.SIZE),), daemon=True)
    t.start()
    t.join(timeout=10)
    assert not t.is_alive(), "add_local_ref waits for the lock it holds"
    assert rc.local_count(other) == 0
