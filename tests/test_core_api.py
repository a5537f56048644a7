"""Core API tests: put/get/wait, tasks, errors, nested tasks.

Modeled on the reference's python/ray/tests/test_basic.py coverage.
"""

import time

import numpy as np
import pytest

import cluster_anywhere_tpu as ca


def test_put_get_small(ca_cluster_module):
    ref = ca.put({"a": 1, "b": [1, 2, 3]})
    assert ca.get(ref) == {"a": 1, "b": [1, 2, 3]}


def test_put_get_large_numpy(ca_cluster_module):
    arr = np.arange(1_000_000, dtype=np.float32)
    ref = ca.put(arr)
    out = ca.get(ref)
    np.testing.assert_array_equal(arr, out)


def test_simple_task(ca_cluster_module):
    @ca.remote
    def add(a, b):
        return a + b

    assert ca.get(add.remote(1, 2)) == 3


def test_task_with_kwargs(ca_cluster_module):
    @ca.remote
    def f(a, b=10, c=20):
        return a + b + c

    assert ca.get(f.remote(1, c=2)) == 13


def test_task_with_ref_args(ca_cluster_module):
    @ca.remote
    def double(x):
        return 2 * x

    r1 = double.remote(10)
    r2 = double.remote(r1)
    assert ca.get(r2) == 40


def test_task_large_arg_and_return(ca_cluster_module):
    @ca.remote
    def mean_and_double(arr):
        return arr * 2

    arr = np.ones((512, 512), dtype=np.float64)
    ref = mean_and_double.remote(ca.put(arr))
    out = ca.get(ref)
    assert out.shape == (512, 512)
    assert out[0, 0] == 2.0


def test_many_tasks(ca_cluster_module):
    @ca.remote
    def sq(x):
        return x * x

    refs = [sq.remote(i) for i in range(200)]
    assert ca.get(refs) == [i * i for i in range(200)]


def test_num_returns(ca_cluster_module):
    @ca.remote
    def three():
        return 1, 2, 3

    a, b, c = three.options(num_returns=3).remote()
    assert ca.get([a, b, c]) == [1, 2, 3]


def test_task_error_propagates(ca_cluster_module):
    @ca.remote
    def boom():
        raise ValueError("kapow")

    with pytest.raises(ca.TaskError, match="kapow"):
        ca.get(boom.remote())


def test_error_chains_through_deps(ca_cluster_module):
    @ca.remote
    def boom():
        raise ValueError("root cause")

    @ca.remote
    def passthrough(x):
        return x

    with pytest.raises(ca.CAError):
        ca.get(passthrough.remote(boom.remote()))


def test_wait_semantics(ca_cluster_module):
    @ca.remote
    def sleepy(t):
        time.sleep(t)
        return t

    fast = sleepy.remote(0.0)
    slow = sleepy.remote(2.0)
    ready, not_ready = ca.wait([fast, slow], num_returns=1, timeout=1.5)
    assert ready == [fast]
    assert not_ready == [slow]


def test_wait_timeout_empty(ca_cluster_module):
    @ca.remote
    def sleepy():
        time.sleep(5)

    r = sleepy.remote()
    ready, not_ready = ca.wait([r], num_returns=1, timeout=0.2)
    assert ready == []
    assert not_ready == [r]


def test_get_timeout(ca_cluster_module):
    @ca.remote
    def sleepy():
        time.sleep(5)

    with pytest.raises(ca.GetTimeoutError):
        ca.get(sleepy.remote(), timeout=0.2)


def test_nested_tasks(ca_cluster_module):
    @ca.remote
    def inner(x):
        return x + 1

    @ca.remote
    def outer(x):
        import cluster_anywhere_tpu as ca2

        return ca2.get(inner.remote(x)) + 100

    assert ca.get(outer.remote(1)) == 102


def test_cluster_resources(ca_cluster_module):
    total = ca.cluster_resources()
    assert total["CPU"] == 4.0
    assert len(ca.nodes()) == 1


def test_direct_call_raises(ca_cluster_module):
    @ca.remote
    def f():
        return 1

    with pytest.raises(TypeError):
        f()


def test_a_plane_has_no_off_switch():
    """The host planes run one way: the only on/off settings are whether the
    pool is prestarted and whether this driver prints the cluster's logs, and
    a plane's old switch is refused before anything is started."""
    import dataclasses

    from cluster_anywhere_tpu.core.config import CAConfig

    bools = {f.name for f in dataclasses.fields(CAConfig) if isinstance(f.default, bool)}
    assert bools == {"worker_prestart", "log_to_driver"}
    if ca.is_initialized():  # an unknown key is looked at after this
        ca.shutdown()
    with pytest.raises(ValueError, match="unknown config key 'owner_plane'"):
        ca.init(owner_plane=False)
    assert not ca.is_initialized()
