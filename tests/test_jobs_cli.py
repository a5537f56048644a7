"""Runtime env / job submission / multi-driver / CLI tests (modeled on the
reference's python/ray/tests/test_runtime_env*.py and
dashboard/modules/job/tests, compressed)."""

import os
import subprocess
import sys
import time

import pytest

import cluster_anywhere_tpu as ca

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module", autouse=True)
def cluster():
    if ca.is_initialized():
        ca.shutdown()
    ca.init(num_cpus=4)
    yield
    ca.shutdown()


def test_runtime_env_env_vars_task():
    @ca.remote(runtime_env={"env_vars": {"CA_TEST_VAR": "hello"}})
    def read_env():
        return os.environ.get("CA_TEST_VAR")

    assert ca.get(read_env.remote()) == "hello"

    @ca.remote
    def read_env2():
        return os.environ.get("CA_TEST_VAR")

    # pool worker restored the env afterwards
    assert ca.get(read_env2.remote()) is None


def test_runtime_env_env_vars_actor():
    @ca.remote(runtime_env={"env_vars": {"CA_ACTOR_VAR": "act"}})
    class EnvActor:
        def read(self):
            return os.environ.get("CA_ACTOR_VAR")

    a = EnvActor.remote()
    assert ca.get(a.read.remote()) == "act"
    ca.kill(a)


def test_runtime_env_working_dir(tmp_path):
    d = tmp_path / "wd"
    d.mkdir()
    (d / "data.txt").write_text("payload42")
    (d / "helper.py").write_text("VALUE = 7\n")

    @ca.remote(runtime_env={"working_dir": str(d)})
    def use_wd():
        import helper  # importable from the working dir

        return open("data.txt").read(), helper.VALUE

    text, val = ca.get(use_wd.remote())
    assert text == "payload42" and val == 7


def test_runtime_env_py_modules(tmp_path):
    mod = tmp_path / "mymod"
    mod.mkdir()
    (mod / "__init__.py").write_text("def f():\n    return 'from_mymod'\n")

    @ca.remote(runtime_env={"py_modules": [str(mod)]})
    def use_mod():
        import mymod

        return mymod.f()

    assert ca.get(use_mod.remote()) == "from_mymod"


def test_runtime_env_validation():
    with pytest.raises(Exception):

        @ca.remote(runtime_env={"bogus_key": 1})
        def f():
            pass

        ca.get(f.remote())


def test_job_submission_and_logs():
    from cluster_anywhere_tpu.jobs import JobSubmissionClient

    client = JobSubmissionClient()  # already initialized
    sid = client.submit_job(entrypoint="echo hello_from_job && echo line2")
    status = client.wait_until_finish(sid, timeout_s=30)
    assert status == "SUCCEEDED"
    logs = client.get_job_logs(sid)
    assert "hello_from_job" in logs and "line2" in logs
    infos = client.list_jobs()
    assert any(i.submission_id == sid for i in infos)


def test_job_failure_status():
    from cluster_anywhere_tpu.jobs import JobSubmissionClient

    client = JobSubmissionClient()
    sid = client.submit_job(entrypoint="exit 3")
    assert client.wait_until_finish(sid, timeout_s=30) == "FAILED"
    assert client.get_job_info(sid).return_code == 3


def test_job_stop():
    from cluster_anywhere_tpu.jobs import JobSubmissionClient

    client = JobSubmissionClient()
    sid = client.submit_job(entrypoint="sleep 60")
    time.sleep(0.5)
    assert client.stop_job(sid)
    status = client.wait_until_finish(sid, timeout_s=15)
    assert status == "STOPPED"


def test_job_driver_connects_to_cluster(tmp_path):
    from cluster_anywhere_tpu.jobs import JobSubmissionClient

    script = tmp_path / "driver.py"
    script.write_text(
        "import os, sys\n"
        f"sys.path.insert(0, {REPO!r})\n"
        "import cluster_anywhere_tpu as ca\n"
        "ca.init(address=os.environ['CA_ADDRESS'])\n"
        "@ca.remote\n"
        "def f(x):\n"
        "    return x * 2\n"
        "print('job-result:', ca.get(f.remote(21)))\n"
        "ca.shutdown()\n"
    )
    client = JobSubmissionClient()
    sid = client.submit_job(entrypoint=f"{sys.executable} {script}")
    assert client.wait_until_finish(sid, timeout_s=60) == "SUCCEEDED"
    assert "job-result: 42" in client.get_job_logs(sid)


def test_second_driver_joins():
    from cluster_anywhere_tpu.core.worker import global_worker

    session = global_worker().session_dir
    code = (
        f"import sys; sys.path.insert(0, {REPO!r})\n"
        "import cluster_anywhere_tpu as ca\n"
        f"ca.init(address={session!r})\n"
        "print('joined:', ca.cluster_resources()['CPU'])\n"
        "ca.shutdown()\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, timeout=60
    )
    assert out.returncode == 0, out.stderr
    assert "joined: 4.0" in out.stdout
    # the original driver's cluster must still be alive
    assert ca.cluster_resources()["CPU"] == 4.0


def test_cli_status_and_summary():
    env = dict(os.environ, PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""))
    from cluster_anywhere_tpu.core.worker import global_worker

    session = global_worker().session_dir
    out = subprocess.run(
        [sys.executable, "-m", "cluster_anywhere_tpu.cli", "status", "--address", session],
        capture_output=True,
        text=True,
        timeout=60,
        env=env,
    )
    assert out.returncode == 0, out.stderr
    assert "CPU" in out.stdout

    out = subprocess.run(
        [sys.executable, "-m", "cluster_anywhere_tpu.cli", "list", "nodes", "--address", session],
        capture_output=True,
        text=True,
        timeout=60,
        env=env,
    )
    assert out.returncode == 0, out.stderr
    assert "node_id" in out.stdout


def test_rest_job_submission(ca_cluster):
    """Dashboard REST job API (dashboard/modules/job parity): POST submits,
    GET lists/status, the job joins this cluster, and `ca jobs`/SDK see it."""
    import http.client
    import json as _json

    from cluster_anywhere_tpu.core.worker import global_worker

    sdir = global_worker().session_dir
    deadline = time.time() + 10
    addr_file = os.path.join(sdir, "dashboard.addr")
    while not os.path.exists(addr_file) and time.time() < deadline:
        time.sleep(0.1)
    host, port = open(addr_file).read().strip().replace("http://", "").split(":")

    def req(method, path, body=None):
        conn = http.client.HTTPConnection(host, int(port), timeout=30)
        conn.request(
            method, path,
            body=_json.dumps(body) if body is not None else None,
            headers={"Content-Type": "application/json"},
        )
        r = conn.getresponse()
        out = (r.status, _json.loads(r.read() or b"{}"))
        conn.close()
        return out

    code = (
        "import cluster_anywhere_tpu as ca; ca.init(address='auto');\n"
        "print('rest job ran', ca.get(ca.put(41)) + 1)"
    )
    status, resp = req("POST", "/api/jobs", {"entrypoint": f"python -c \"{code}\""})
    assert status == 200
    sid = resp["submission_id"]

    deadline = time.time() + 60
    info = {}
    while time.time() < deadline:
        status, info = req("GET", f"/api/jobs/{sid}")
        if info.get("status") in ("SUCCEEDED", "FAILED", "STOPPED"):
            break
        time.sleep(0.3)
    assert info.get("status") == "SUCCEEDED", info
    log = open(os.path.join(sdir, f"job-{sid}.log")).read()
    assert "rest job ran 42" in log
    # visible through the job SDK (same KV namespace)
    from cluster_anywhere_tpu.jobs import JobSubmissionClient

    assert any(
        j.submission_id == sid for j in JobSubmissionClient().list_jobs()
    )
    status, jobs = req("GET", "/api/jobs")
    assert any(j["submission_id"] == sid for j in jobs)


def test_ca_up_down(tmp_path, monkeypatch):
    """`ca up <yaml>` boots head + agent nodes from a config; `ca down`
    tears the whole cluster back down (ray up/down role, local provider)."""
    import subprocess
    import sys
    import tempfile

    if ca.is_initialized():
        ca.shutdown()
    # "auto" and `ca down` take the newest session under the root: under the
    # shared one that can be another test's, started a moment later by a
    # parallel worker, and `down` then stops that one (its test hangs, this
    # cluster is left).  A root of its own, short enough for a socket path.
    root = tempfile.mkdtemp(prefix="ca_updown_")
    monkeypatch.setenv("CA_SESSION_DIR_ROOT", root)
    cfg = tmp_path / "cluster.yaml"
    cfg.write_text(
        "head: {num_cpus: 2}\n"
        "nodes:\n"
        "  - {count: 2, num_cpus: 2}\n"
    )
    env = dict(os.environ)
    out = subprocess.run(
        [sys.executable, "-m", "cluster_anywhere_tpu.cli", "up", str(cfg)],
        capture_output=True, text=True, timeout=120, env=env,
    )
    assert out.returncode == 0, out.stdout + out.stderr
    assert "cluster up: 3 nodes" in out.stdout, out.stdout
    try:
        info = ca.init(address="auto")
        alive = [n for n in ca.nodes() if n["alive"]]
        assert len(alive) == 3
        assert ca.cluster_resources().get("CPU") == 6.0

        @ca.remote
        def f(x):
            return x + 1

        assert ca.get([f.remote(i) for i in range(12)], timeout=60) == list(range(1, 13))
        ca.shutdown()
    finally:
        down = subprocess.run(
            [sys.executable, "-m", "cluster_anywhere_tpu.cli", "down"],
            capture_output=True, text=True, timeout=60, env=env,
        )
        import shutil

        shutil.rmtree(root, ignore_errors=True)
    assert down.returncode == 0, down.stdout + down.stderr
    assert "stopping cluster" in down.stdout


def test_cli_debug_attaches_to_breakpoint():
    """`ca debug <idx>` end to end: a task parks on set_trace, the CLI
    subprocess lists the KV-registered breakpoint, attaches over TCP,
    inspects a local, continues, and the task finishes (reference
    `ray debug`)."""
    import time as _t

    if not ca.is_initialized():  # the up/down test above tears down
        ca.init(num_cpus=4)

    @ca.remote
    def buggy(x):
        secret = x * 7
        from cluster_anywhere_tpu.util.rpdb import set_trace

        set_trace(timeout=60)
        return secret

    ref = buggy.remote(6)
    # wait for the breakpoint to register
    from cluster_anywhere_tpu.core.worker import global_worker
    from cluster_anywhere_tpu.util import rpdb

    w = global_worker()
    deadline = _t.monotonic() + 20
    while _t.monotonic() < deadline and not rpdb.list_breakpoints(w):
        _t.sleep(0.2)
    assert rpdb.list_breakpoints(w)

    env = dict(os.environ, PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""))
    session = w.session_dir
    out = subprocess.run(
        [sys.executable, "-m", "cluster_anywhere_tpu.cli", "debug", "0",
         "--address", session],
        input="p secret\nc\n",
        capture_output=True,
        text=True,
        timeout=90,
        env=env,
    )
    assert out.returncode == 0, out.stderr
    assert "42" in out.stdout, out.stdout
    assert ca.get(ref, timeout=30) == 42
