"""TPU accelerator manager: topology detection feeding the resource model
(_private/accelerators/tpu.py:70 TPUAcceleratorManager analogue)."""

import pytest

import cluster_anywhere_tpu as ca
from cluster_anywhere_tpu.core import accelerators as acc


@pytest.fixture
def clean_tpu_env(monkeypatch):
    for var in (
        acc.VISIBLE_CHIPS_ENV,
        acc.ACCELERATOR_TYPE_ENV,
        acc.CHIPS_PER_HOST_BOUNDS_ENV,
        acc.WORKER_ID_ENV,
        acc.POD_NAME_ENV,
        acc.NOSET_VISIBLE_CHIPS_ENV,
        "CA_NUM_TPUS",
    ):
        monkeypatch.delenv(var, raising=False)
    # no device files unless a test makes some
    monkeypatch.setattr(acc, "_DEVICE_GLOBS", ())
    return monkeypatch


def test_chip_count_sources(clean_tpu_env):
    m = clean_tpu_env
    m.setenv(acc.CHIPS_PER_HOST_BOUNDS_ENV, "2,2,1")
    assert acc.num_tpu_chips() == 4
    # visible-chips restriction wins over host bounds
    m.setenv(acc.VISIBLE_CHIPS_ENV, "0,1")
    assert acc.num_tpu_chips() == 2
    assert acc.visible_chip_ids() == ["0", "1"]


@pytest.mark.parametrize(
    "files, env, want",
    [
        # the one-chip machine: one VFIO group cut from a 2x2 host whose
        # bounds still describe the whole board — the files win
        (["vfio/3", "vfio/vfio"], {"TPU_CHIPS_PER_HOST_BOUNDS": "2,2,1"}, 1),
        # the four-chip host: one group per chip, the container node is no chip
        (["vfio/0", "vfio/1", "vfio/2", "vfio/3", "vfio/vfio"], {}, 4),
        # older hosts expose /dev/accel<N>
        (["accel0", "accel1", "accel2", "accel3"], {}, 4),
        # no device files (a container that hides them): the TPU_* bounds
        ([], {"TPU_CHIPS_PER_HOST_BOUNDS": "2,2,1"}, 4),
        # a visible-chips restriction wins over both
        (["vfio/0", "vfio/1", "vfio/2", "vfio/3"], {"TPU_VISIBLE_CHIPS": "2"}, 1),
        # nothing: not a TPU host
        (["vfio/vfio"], {}, 0),
    ],
)
def test_chip_detection(clean_tpu_env, tmp_path, files, env, want):
    m = clean_tpu_env
    for f in files:
        (tmp_path / f).parent.mkdir(exist_ok=True)
        (tmp_path / f).touch()
    m.setattr(
        acc, "_DEVICE_GLOBS", (f"{tmp_path}/accel*", f"{tmp_path}/vfio/[0-9]*")
    )
    for k, v in env.items():
        m.setenv(k, v)
    assert acc.num_tpu_chips() == want


def test_accelerator_type_from_env_only(clean_tpu_env):
    assert acc.pod_type() is None and acc.accelerator_type() is None
    clean_tpu_env.setenv(acc.ACCELERATOR_TYPE_ENV, "v5litepod-4")
    assert acc.pod_type() == "v5litepod-4"
    assert acc.accelerator_type() == "TPU-V5LITEPOD"


def test_pod_topology(clean_tpu_env):
    m = clean_tpu_env
    m.setenv(acc.ACCELERATOR_TYPE_ENV, "v5e-16")
    m.setenv(acc.CHIPS_PER_HOST_BOUNDS_ENV, "2,2,1")
    m.setenv(acc.WORKER_ID_ENV, "0")
    m.setenv(acc.POD_NAME_ENV, "mypod")
    assert acc.pod_type() == "v5e-16"
    assert acc.accelerator_type() == "TPU-V5E"
    assert acc.num_workers_in_pod() == 4  # 16 chips / 4 per host
    assert acc.pod_name() == "mypod"
    extra = acc.additional_resources()
    assert extra["TPU-V5E"] == 4.0
    assert extra["TPU-v5e-16-head"] == 1.0
    # workers other than 0 don't carry the pod-head resource
    m.setenv(acc.WORKER_ID_ENV, "2")
    assert "TPU-v5e-16-head" not in acc.additional_resources()


def test_v4_pod_counts_cores(clean_tpu_env):
    m = clean_tpu_env
    m.setenv(acc.ACCELERATOR_TYPE_ENV, "v4-16")  # 16 TensorCores = 8 chips
    m.setenv(acc.CHIPS_PER_HOST_BOUNDS_ENV, "2,2,1")  # 4 chips/host
    assert acc.num_workers_in_pod() == 2


def test_validate_chip_request():
    for ok in (1, 2, 4, 8, 0.5):
        acc.validate_chip_request(ok)
    for bad in (3, 5, 16, 1.5):
        with pytest.raises(ValueError):
            acc.validate_chip_request(bad)
    with pytest.raises(ValueError):
        @ca.remote(num_tpus=3)
        def f():
            pass


@pytest.mark.parametrize(
    "request_tpus, pool, ids, bounds",
    [
        (4, "tpu4", ("0", "1", "2", "3"), "2,2,1"),  # the whole host
        (2, "tpu2", ("0", "1"), "1,2,1"),  # an aligned pair
        (1, "tpu", ("0",), "1,1,1"),
        (0.25, "tpu", ("0",), "1,1,1"),  # a fraction shares one chip
    ],
)
def test_worker_chip_view(clean_tpu_env, request_tpus, pool, ids, bounds):
    """A worker gets as many chips as its request's TPU count, with the ids
    and the full set of bounds libtpu needs for that view."""
    assert acc.worker_pool(request_tpus) == pool
    chips = acc.ChipAllocator(4).acquire(acc.pool_chips(pool))
    assert chips == ids
    assert acc.visible_chips_env_for_worker(chips) == {
        "TPU_VISIBLE_CHIPS": ",".join(ids),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": bounds,
        "TPU_PROCESS_BOUNDS": "1,1,1",
        "TPU_CHIPS_PER_HOST_BOUNDS": bounds,
        "TPU_HOST_BOUNDS": "1,1,1",
    }


def test_pools_and_pinning_switch(clean_tpu_env):
    assert acc.worker_pool(0) == "cpu" and acc.pool_chips("cpu") == 0
    assert acc.visible_chips_env_for_worker(()) == {}
    clean_tpu_env.setenv(acc.NOSET_VISIBLE_CHIPS_ENV, "1")
    assert acc.visible_chips_env_for_worker(("2",)) == {}


def test_init_detects_topology_resources(clean_tpu_env):
    m = clean_tpu_env
    m.setenv(acc.ACCELERATOR_TYPE_ENV, "v5e-8")
    m.setenv(acc.CHIPS_PER_HOST_BOUNDS_ENV, "2,2,1")
    m.setenv(acc.WORKER_ID_ENV, "0")
    if ca.is_initialized():
        ca.shutdown()
    info = ca.init(num_cpus=2)
    try:
        res = info["resources"]
        assert res["TPU"] == 4.0
        assert res["TPU-V5E"] == 4.0
        assert res["TPU-v5e-8-head"] == 1.0
    finally:
        ca.shutdown()


def test_validate_rejects_nonpositive_and_actor_path():
    with pytest.raises(ValueError):
        acc.validate_chip_request(-2)
    with pytest.raises(ValueError):
        acc.validate_chip_request(0)
    with pytest.raises(ValueError):
        @ca.remote(num_tpus=3)
        class A:
            pass
    with pytest.raises(ValueError):
        @ca.remote
        class B:
            pass
        B.options(num_tpus=-1)


def test_chip_allocator(clean_tpu_env):
    alloc = acc.ChipAllocator(2)
    a, b = alloc.acquire(), alloc.acquire()
    assert {a, b} == {("0",), ("1",)}
    # oversubscription (fractional requests) shares the least-loaded chip,
    # never an unrestricted view
    c = alloc.acquire()
    assert c in (("0",), ("1",))
    alloc.release(c)
    alloc.release(a)
    assert alloc.acquire() == a  # freed chip is reused first
    # honors a parent visible-chips restriction
    clean_tpu_env.setenv(acc.VISIBLE_CHIPS_ENV, "4,5")
    alloc2 = acc.ChipAllocator(2)
    assert {alloc2.acquire(), alloc2.acquire()} == {("4",), ("5",)}


def test_chip_allocator_groups(clean_tpu_env):
    alloc = acc.ChipAllocator(4)
    one = alloc.acquire(1)
    # a pair never straddles a group boundary, and avoids the busy chip
    assert alloc.acquire(2) == ("2", "3")
    assert alloc.acquire(2) == ("0", "1")
    alloc.release(one)
    assert alloc.acquire(4) == ("0", "1", "2", "3")
    # more chips than the host has: nothing to pin (the scheduler never
    # places such a request; the TPU resource total is the guard)
    assert acc.ChipAllocator(2).acquire(4) == ()


@pytest.mark.parametrize("ambient", ["/some/dir", None])
def test_compile_cache_dir(monkeypatch, ambient):
    """One place for JAX's persistent cache: the ambient
    JAX_COMPILATION_CACHE_DIR when set, else the fixed in-checkout path —
    never a temp name, pid or timestamp (a directory that moves never hits)."""
    import os

    if ambient is None:
        monkeypatch.delenv(acc.COMPILE_CACHE_ENV, raising=False)
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        want = os.path.join(root, ".jax_cache")
    else:
        monkeypatch.setenv(acc.COMPILE_CACHE_ENV, ambient)
        want = ambient
    assert acc.compile_cache_dir() == want
    assert acc.compile_cache_dir() == want  # stable from call to call


def test_tpu_worker_chip_view_and_lifetime(clean_tpu_env):
    """Through the head: a TPU: 4 actor's process sees four chips and the
    compile cache; a TPU task's pooled worker is retired with its lease (a
    process that has initialised the TPU backend owns its chip until it
    exits), so the next TPU process starts on a free chip."""
    import os
    import time

    from cluster_anywhere_tpu.core.worker import global_worker

    clean_tpu_env.setenv(acc.COMPILE_CACHE_ENV, "/some/dir")
    if ca.is_initialized():
        ca.shutdown()
    ca.init(num_cpus=2, num_tpus=4)
    try:
        keys = ("TPU_VISIBLE_CHIPS", "TPU_CHIPS_PER_PROCESS_BOUNDS", acc.COMPILE_CACHE_ENV)

        @ca.remote(num_tpus=4)
        class Four:
            def env(self):
                return [os.environ.get(k) for k in keys]

        four = Four.remote()
        assert ca.get(four.env.remote(), timeout=60) == ["0,1,2,3", "2,2,1", "/some/dir"]
        ca.kill(four)

        @ca.remote(num_tpus=1)
        def whoami():
            return os.getpid(), os.environ.get("TPU_VISIBLE_CHIPS")

        pid1, chip1 = ca.get(whoami.remote(), timeout=60)
        assert chip1 in ("0", "1", "2", "3")

        def state_of(pid):
            ws = global_worker().head_call("list_workers")["workers"]
            return next(w["state"] for w in ws if w["pid"] == pid)

        deadline = time.monotonic() + 30
        while state_of(pid1) != "dead" and time.monotonic() < deadline:
            time.sleep(0.1)
        assert state_of(pid1) == "dead"  # retired, not pooled
        pid2, _ = ca.get(whoami.remote(), timeout=60)
        assert pid2 != pid1
    finally:
        ca.shutdown()
