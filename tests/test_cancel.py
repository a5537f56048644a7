"""ca.cancel() — ray.cancel semantics (task_manager.h CancelTask +
task_canceller role): queued tasks drop immediately, running tasks get
TaskCancelledError raised in their executing thread, force kills the
worker, cancelled tasks never retry, finished tasks are untouched."""

import os
import time

import pytest

import cluster_anywhere_tpu as ca


@pytest.fixture(scope="module", autouse=True)
def cluster():
    if ca.is_initialized():
        ca.shutdown()
    ca.init(num_cpus=2)
    yield
    ca.shutdown()


def test_cancel_running_task_interrupts(tmp_path):
    """A pure-Python loop hits the async-raised TaskCancelledError at a
    bytecode boundary; get() surfaces it."""
    started = str(tmp_path / "started")

    @ca.remote
    def spin(started):
        open(started, "w").close()
        t0 = time.time()
        while time.time() - t0 < 60:
            sum(range(1000))  # bytecode boundaries for the async exception
        return "finished"

    ref = spin.remote(started)
    deadline = time.time() + 30  # a worker may take seconds to start
    while time.time() < deadline and not os.path.exists(started):
        time.sleep(0.05)
    assert os.path.exists(started)  # it is executing
    ca.cancel(ref)
    t0 = time.time()
    with pytest.raises(ca.exceptions.TaskCancelledError):
        ca.get(ref, timeout=30)
    assert time.time() - t0 < 20  # cancelled, not run to the 60s end


def test_cancel_queued_task_never_runs():
    """With every CPU busy, a queued task cancels without ever executing
    (and the long holders are themselves cancelled for cleanup)."""
    import os

    @ca.remote
    def hold():
        # short sleeps: bytecode boundaries let the cleanup cancel land
        # promptly (one long C-level sleep would defer it to the end)
        for _ in range(300):
            time.sleep(0.1)
        return os.getpid()

    @ca.remote
    def marker(path):
        open(path, "w").write("ran")
        return "ran"

    holders = [hold.remote() for _ in range(2)]  # occupy both CPUs
    time.sleep(0.8)
    import tempfile

    path = tempfile.mktemp()
    queued = marker.remote(path)
    time.sleep(0.3)
    ca.cancel(queued)
    with pytest.raises(ca.exceptions.TaskCancelledError):
        ca.get(queued, timeout=30)
    assert not os.path.exists(path), "cancelled-queued task still executed"
    for h in holders:
        ca.cancel(h)
    for h in holders:
        with pytest.raises(ca.exceptions.TaskCancelledError):
            ca.get(h, timeout=30)


def test_force_cancel_kills_blocked_worker():
    """time.sleep never reaches a bytecode boundary mid-call; force=True
    kills the worker process, the ref resolves to TaskCancelledError (NOT
    WorkerCrashedError, and no retry), and the pool recovers."""

    @ca.remote
    def block():
        time.sleep(120)
        return "finished"

    ref = block.options(max_retries=2).remote()
    time.sleep(1.0)
    ca.cancel(ref, force=True)
    with pytest.raises(ca.exceptions.TaskCancelledError):
        ca.get(ref, timeout=30)
    # the cluster still works afterwards (dead worker replaced)
    @ca.remote
    def ok():
        return 42

    assert ca.get([ok.remote() for _ in range(8)], timeout=60) == [42] * 8


def test_cancel_finished_task_is_noop():
    @ca.remote
    def quick():
        return 7

    ref = quick.remote()
    assert ca.get(ref, timeout=30) == 7
    ca.cancel(ref)
    time.sleep(0.2)
    assert ca.get(ref, timeout=30) == 7  # value untouched


def test_cancel_actor_task_interrupts():
    """Actor-task cancel: the executing method thread gets the exception;
    the actor itself survives and serves later calls."""

    @ca.remote
    class Busy:
        def spin(self):
            t0 = time.time()
            while time.time() - t0 < 60:
                sum(range(1000))
            return "finished"

        def ping(self):
            return "pong"

    a = Busy.remote()
    ref = a.spin.remote()
    time.sleep(1.0)
    ca.cancel(ref)
    with pytest.raises(ca.exceptions.TaskCancelledError):
        ca.get(ref, timeout=30)
    assert ca.get(a.ping.remote(), timeout=30) == "pong"
    ca.kill(a)


def test_cancel_async_actor_method():
    """Coroutine actor methods cancel via asyncio (exact, no async-exc
    race): the awaiting method unwinds at its next await point and the
    actor keeps serving."""
    import asyncio

    @ca.remote
    class AsyncActor:
        async def slow(self):
            await asyncio.sleep(60)
            return "finished"

        async def ping(self):
            return "pong"

    a = AsyncActor.remote()
    assert ca.get(a.ping.remote(), timeout=30) == "pong"
    ref = a.slow.remote()
    time.sleep(0.8)
    ca.cancel(ref)
    t0 = time.time()
    with pytest.raises(ca.exceptions.TaskCancelledError):
        ca.get(ref, timeout=30)
    assert time.time() - t0 < 20
    assert ca.get(a.ping.remote(), timeout=30) == "pong"
    ca.kill(a)


def test_cancel_streaming_task():
    """Generator tasks cancel between yields; the consumer's next() raises
    and the stream ends."""

    @ca.remote(num_returns="streaming")
    def gen():
        for i in range(1000):
            time.sleep(0.05)
            yield i

    it = gen.remote()
    first = ca.get(next(it), timeout=30)
    assert first == 0
    # item refs share the generator's task id, so any of them cancels it
    ref2 = next(it)
    ca.cancel(ref2)
    t0 = time.time()
    consumed = 1
    with pytest.raises(ca.exceptions.TaskCancelledError):
        # a few in-flight items may still deliver; the cancellation then
        # surfaces as the stream's terminal error — quickly, NOT after the
        # generator ran its full 1000 x 50ms course
        for _ in range(1000):
            ca.get(next(it), timeout=30)
            consumed += 1
    assert consumed < 500, f"stream ran to {consumed} items despite cancel"
    assert time.time() - t0 < 20
