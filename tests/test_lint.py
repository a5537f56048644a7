"""`ca lint` static analyzer: fixture-snippet unit tests for every rule in
every pass (RPC contract, asyncio hazards, CFG resource lifetimes,
unbounded awaits, cancellation hygiene), direct CFG/dataflow solver tests
(try/finally, early return, loop back-edge, `with`), pragma suppression
incl. decorated/nested defs, baseline round-trip + stale detection + growth
warning, `--rules`/`--changed` modes, the tier-1 self-check over the real
repo, contract generation/freshness, the chaos-spec contract validation,
and a regression test for the analyzer-found actors-pub defect (drivers
were never subscribed, so actor address pubs reached nobody).
"""

import ast
import json
import os
import subprocess
import textwrap

import pytest

from cluster_anywhere_tpu.analysis import contract as contract_mod
from cluster_anywhere_tpu.analysis import engine
from cluster_anywhere_tpu.analysis.cfg import build_cfg
from cluster_anywhere_tpu.analysis.dataflow import solve
from cluster_anywhere_tpu.analysis.lint import main as lint_main
from cluster_anywhere_tpu.analysis.resource_rules import _ResourceAnalysis

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# fixture trees write handler files at the real surface paths (the surface
# table in analysis/contract.py is keyed by path)
HEAD = "cluster_anywhere_tpu/core/head.py"
AGENT = "cluster_anywhere_tpu/core/nodeagent.py"
WORKER = "cluster_anywhere_tpu/core/worker.py"


def run_fixture(tmp_path, files, passes=("rpc", "async")):
    for rel, src in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    return engine.run_lint(
        root=str(tmp_path), passes=passes,
        baseline_file=str(tmp_path / "baseline.json"),
    )


def rules_of(report):
    return sorted({f.rule for f in report["findings"]})


# ------------------------------------------------------------- pass 1: RPC


def test_unknown_method_flagged_and_known_clean(tmp_path):
    report = run_fixture(tmp_path, {
        HEAD: """
            class Head:
                async def _h_foo(self, state, msg, reply, reply_err):
                    reply(v=msg["x"])
            """,
        WORKER: """
            async def caller(conn):
                await conn.call("fooo", x=1)   # typo'd
                await conn.call("foo", x=1)    # fine (also keeps foo alive)
            """,
    }, passes=("rpc",))
    unknown = [f for f in report["findings"] if f.rule == "rpc-unknown-method"]
    assert len(unknown) == 1 and "fooo" in unknown[0].message
    assert not any(
        f.rule == "rpc-dead-handler" and "foo" in f.message
        for f in report["findings"]
    )


def test_dead_handler_flagged(tmp_path):
    report = run_fixture(tmp_path, {
        HEAD: """
            class Head:
                async def _h_used(self, state, msg, reply, reply_err):
                    reply()
                async def _h_orphan(self, state, msg, reply, reply_err):
                    reply()
            """,
        WORKER: "async def c(conn):\n    await conn.call('used')\n",
    }, passes=("rpc",))
    dead = [f for f in report["findings"] if f.rule == "rpc-dead-handler"]
    assert [f.detail for f in dead] == ["head:orphan"]


def test_missing_field_only_for_unconditional_reads(tmp_path):
    report = run_fixture(tmp_path, {
        HEAD: """
            class Head:
                async def _h_put(self, state, msg, reply, reply_err):
                    key = msg["key"]            # hard requirement
                    if msg.get("versioned"):
                        old = msg["version"]    # branch-only: NOT required
                    reply(k=key)
            """,
        WORKER: """
            async def c(conn):
                await conn.call("put", versioned=True)  # missing key only
            """,
    }, passes=("rpc",))
    missing = [f for f in report["findings"] if f.rule == "rpc-missing-field"]
    assert [f.detail for f in missing] == ["put.key"]


def test_unread_field_flagged_unless_opaque(tmp_path):
    report = run_fixture(tmp_path, {
        HEAD: """
            class Head:
                async def _h_closed(self, state, msg, reply, reply_err):
                    reply(v=msg["x"])
                async def _h_open(self, state, msg, reply, reply_err):
                    self.queue.append(msg)   # msg escapes: reads unknowable
                    reply()
            """,
        WORKER: """
            async def c(conn):
                await conn.call("closed", x=1, stray=2)
                await conn.call("open", anything=3)
            """,
    }, passes=("rpc",))
    unread = [f for f in report["findings"] if f.rule == "rpc-unread-field"]
    assert [f.detail for f in unread] == ["closed.stray"]


def test_chain_surface_and_negated_dispatch(tmp_path):
    """Agent-style elif chains and the `if m != "pub": return` driver-push
    shape both register handlers; dynamic **fields skip field checks."""
    report = run_fixture(tmp_path, {
        AGENT: """
            class NodeAgent:
                async def _handle(self, state, msg, reply, reply_err):
                    m = msg["m"]
                    if m == "alpha":
                        reply(v=msg["a"])
                    elif m in ("beta", "gamma"):
                        reply(v=msg.get("b"))
                    else:
                        reply_err(ValueError(m))
            """,
        WORKER: """
            class Worker:
                async def _on_push(self, msg):
                    if msg.get("m") != "pub":
                        return
                    ch = msg.get("ch")

            async def c(conn, fields):
                await conn.call("alpha", a=1)
                conn.notify("beta", **fields)   # dynamic: method check only
                conn.notify("gamma", b=2)
                conn.notify("pub", ch="x")
            """,
    }, passes=("rpc",))
    assert report["findings"] == [], [f.render() for f in report["findings"]]


def test_spec_dict_and_wrapper_call_sites(tmp_path):
    """{"m": ...} dict literals and the util/state `_head` wrapper are call
    sites: they keep handlers alive and get field-checked."""
    report = run_fixture(tmp_path, {
        HEAD: """
            class Head:
                async def _h_evt(self, state, msg, reply, reply_err):
                    reply(v=msg["seq"])
                async def _h_listed(self, state, msg, reply, reply_err):
                    reply(n=msg.get("limit"))
            """,
        WORKER: """
            def push(writer, write_frame):
                write_frame(writer, {"m": "evt", "seq": 7})

            def state_api(_head):
                return _head("listed", limit=5)
            """,
    }, passes=("rpc",))
    assert report["findings"] == [], [f.render() for f in report["findings"]]


# ---------------------------------------------------------- pass 2: asyncio


def test_blocking_calls_in_async_def(tmp_path):
    report = run_fixture(tmp_path, {
        "cluster_anywhere_tpu/mod.py": """
            import asyncio, time, subprocess

            async def bad(fut, proc):
                time.sleep(1)
                subprocess.run(["true"])
                fut.result()
                proc.wait()

            async def good(ev):
                await asyncio.sleep(0)
                await ev.wait()          # awaited: the async dual

            def sync_ok():
                time.sleep(0.01)         # not on the loop
            """,
    }, passes=("async",))
    blocked = [f for f in report["findings"] if f.rule == "async-blocking-call"]
    assert len(blocked) == 4
    assert all(f.context == "bad" for f in blocked)


def test_dropped_task_rule(tmp_path):
    report = run_fixture(tmp_path, {
        "cluster_anywhere_tpu/mod.py": """
            import asyncio
            from cluster_anywhere_tpu.util.aio import spawn_logged

            async def bad(coro):
                asyncio.ensure_future(coro)          # dropped

            def also_bad(loop, coro):
                loop.create_task(coro)               # dropped, sync caller

            async def good(coro):
                t = asyncio.ensure_future(coro)      # held
                spawn_logged(coro, "named")          # guarded wrapper
                return t
            """,
    }, passes=("async",))
    dropped = [f for f in report["findings"] if f.rule == "async-dropped-task"]
    assert sorted(f.context for f in dropped) == ["also_bad", "bad"]


def test_await_race_rule(tmp_path):
    report = run_fixture(tmp_path, {
        "cluster_anywhere_tpu/mod.py": """
            class S:
                async def carried(self):
                    n = self.count
                    await self.flush()
                    self.count = n + 1          # stale n

                async def in_statement(self):
                    self.total = self.total + await self.price()

                async def augmented(self):
                    self.total += await self.price()

                async def fine(self):
                    self.addr = await self.dial()   # plain overwrite
                    self.count += 1                 # atomic RMW, no yield
                    n = self.count
                    self.count = n + 1              # no await between
            """,
    }, passes=("async",))
    races = [f for f in report["findings"] if f.rule == "async-await-race"]
    assert sorted(f.context for f in races) == [
        "S.augmented", "S.carried", "S.in_statement"
    ]
    assert all(f.detail in ("self.count", "self.total") for f in races)


# -------------------------------------------------- CFG + dataflow (direct)


def _cfg_of(src: str, name: str):
    tree = ast.parse(textwrap.dedent(src))
    fn = next(
        n for n in ast.walk(tree)
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
        and n.name == name
    )
    return build_cfg(fn)


def _acq_facts(state, var):
    return [f for f in (state or {}).get(var, ()) if f[0] == "acq"]


def test_cfg_try_finally_release_reaches_both_exits():
    """The finally body is duplicated onto the exception path: the release
    must be visible at raise_exit, not just at the normal exit."""
    cfg = _cfg_of("""
        def f(p):
            fd = os.open(p, 0)
            try:
                os.write(fd, b"x")
            finally:
                os.close(fd)
            return 1
        """, "f")
    assert any(b.label == "finally.exc" for b in cfg.blocks)
    states = solve(cfg, _ResourceAnalysis())
    assert not _acq_facts(states.get(cfg.exit.id), "fd")
    assert not _acq_facts(states.get(cfg.raise_exit.id), "fd")


def test_cfg_early_return_path_carries_the_acquire():
    cfg = _cfg_of("""
        def f(p, flag):
            fd = os.open(p, 0)
            if flag:
                return None
            os.close(fd)
            return fd
        """, "f")
    # two returns + falling off the end never happens -> >= 2 exit preds
    assert len(cfg.exit.preds) >= 2
    states = solve(cfg, _ResourceAnalysis())
    assert _acq_facts(states.get(cfg.exit.id), "fd")  # the early return leaks


def test_cfg_loop_back_edge_feeds_the_header():
    cfg = _cfg_of("""
        def f(ps):
            for p in ps:
                fd = os.open(p, 0)
                os.close(fd)
            return 0
        """, "f")
    head = next(b for b in cfg.blocks if b.label == "loop")
    assert any(src.id > head.id for src, _ in head.preds), "no back edge"
    states = solve(cfg, _ResourceAnalysis())
    # close-in-loop: nothing survives to either exit
    assert not _acq_facts(states.get(cfg.exit.id), "fd")
    assert not _acq_facts(states.get(cfg.raise_exit.id), "fd")


def test_cfg_back_edge_preserves_branch_narrowing():
    """`if off is None: continue-ish` — the false arm's narrowed state must
    ride the back edge, or every guarded loop acquire looks leaked."""
    cfg = _cfg_of("""
        def f(arenas, size):
            for a in arenas:
                off = a.alloc(size)
                if off is not None:
                    return a, off
            return None
        """, "f")
    states = solve(cfg, _ResourceAnalysis())
    assert not _acq_facts(states.get(cfg.exit.id), "off")
    assert not _acq_facts(states.get(cfg.raise_exit.id), "off")


def test_cfg_with_statement_suppresses_tracking():
    cfg = _cfg_of("""
        def f(p):
            with open(p) as fh:
                data = fh.read()
            return data
        """, "f")
    assert any(b.label == "with" for b in cfg.blocks)
    states = solve(cfg, _ResourceAnalysis())
    assert not _acq_facts(states.get(cfg.exit.id), "fh")
    assert not _acq_facts(states.get(cfg.raise_exit.id), "fh")


# ------------------------------------------- pass 3: resource lifetimes


def res_fixture(tmp_path, body):
    return run_fixture(
        tmp_path,
        {"cluster_anywhere_tpu/mod.py": "import os\nimport asyncio\n" + textwrap.dedent(body)},
        passes=("res",),
    )


def test_shm_channel_leak_fires_and_released_is_clean(tmp_path):
    """A BufferedShmChannel that can leave the function without release()
    (close() alone doesn't free the segment) is a leak; releasing on every
    path is clean."""
    report = res_fixture(tmp_path, """
        def leaky(spec, flag):
            ch = open_channel(spec, 0)
            if flag:
                return None         # early exit with the segment mapped
            ch.release()
            return True

        def leaky_ctor(n):
            ch = BufferedShmChannel(num_readers=n)
            return None             # dropped without release

        def clean(spec):
            ch = open_channel(spec, 0)
            try:
                return ch.read(1.0)
            finally:
                ch.release()
        """)
    leaks = [f for f in report["findings"] if f.rule.startswith("res-leak")]
    assert sorted({f.context for f in leaks}) == ["leaky", "leaky_ctor"]
    assert not [f for f in report["findings"] if f.context == "clean"]


def test_leak_on_raise_fires_and_finally_is_clean(tmp_path):
    report = res_fixture(tmp_path, """
        def leaky(p):
            fd = os.open(p, 0)
            data = os.read(fd, 1)    # may raise while fd is held
            os.close(fd)
            return data

        def clean(p):
            fd = os.open(p, 0)
            try:
                data = os.read(fd, 1)
            finally:
                os.close(fd)
            return data
        """)
    raised = [f for f in report["findings"] if f.rule == "res-leak-on-raise"]
    assert [f.context for f in raised] == ["leaky"]
    assert not [f for f in report["findings"] if f.context == "clean"]


def test_leak_on_early_return_fires_and_released_return_is_clean(tmp_path):
    report = res_fixture(tmp_path, """
        def leaky(p, flag):
            fd = os.open(p, 0)
            if flag:
                return None          # fd still open
            os.close(fd)
            return fd

        def clean(p, flag):
            fd = os.open(p, 0)
            if flag:
                os.close(fd)
                return None
            os.close(fd)
            return fd
        """)
    ret = [f for f in report["findings"] if f.rule == "res-leak-on-return"]
    assert [f.context for f in ret] == ["leaky"]
    assert not [f for f in report["findings"] if f.context == "clean"]


def test_double_release_fires_and_disjoint_paths_are_clean(tmp_path):
    report = res_fixture(tmp_path, """
        def double(p):
            fd = os.open(p, 0)
            os.close(fd)
            os.close(fd)             # may already be released

        def clean(p, flag):
            fd = os.open(p, 0)
            if flag:
                os.close(fd)
                return
            os.close(fd)
        """)
    dbl = [f for f in report["findings"] if f.rule == "res-double-release"]
    assert [f.context for f in dbl] == ["double"]
    assert not [f for f in report["findings"] if f.context == "clean"]


def test_loop_carried_acquire_fires_and_close_in_loop_is_clean(tmp_path):
    report = res_fixture(tmp_path, """
        def leaky(ps):
            for p in ps:
                fd = os.open(p, 0)
                os.write(fd, b"x")
            os.close(fd)             # only the LAST iteration's fd

        def clean(ps):
            for p in ps:
                fd = os.open(p, 0)
                os.close(fd)
        """)
    leaks = [f for f in report["findings"] if f.rule == "res-leak-on-return"]
    assert [f.context for f in leaks] == ["leaky"]
    assert "rebound" in leaks[0].message
    assert not [f for f in report["findings"] if f.context == "clean"]


def test_with_statement_and_escape_and_guard_are_clean(tmp_path):
    report = res_fixture(tmp_path, """
        def managed(p):
            with open(p) as fh:      # structural release
                return fh.read()

        class C:
            async def kept(self, addr):
                conn = await connect_addr(addr)
                self._conns[addr] = conn   # escapes: not this fn's leak
                return conn

        def guarded(p, flag):
            fd = None
            if flag:
                fd = os.open(p, 0)
            if fd is not None:       # narrowing: the None arm holds nothing
                os.close(fd)
        """)
    assert report["findings"] == [], [f.render() for f in report["findings"]]


def test_lock_and_stream_pairs(tmp_path):
    report = res_fixture(tmp_path, """
        async def lock_leak(lk, q):
            lk.acquire()
            await q.get()            # raise path leaves lk held
            lk.release()

        def lock_clean(lk, work):
            lk.acquire()
            try:
                work()
            finally:
                lk.release()

        async def stream_leak(host):
            r, w = await asyncio.open_connection(host, 1)
            data = await r.readexactly(4)
            w.close()
            return data
        """)
    by_ctx = {}
    for f in report["findings"]:
        by_ctx.setdefault(f.context, []).append(f.rule)
    assert "res-leak-on-raise" in by_ctx.get("lock_leak", [])
    assert "lock_clean" not in by_ctx
    assert "res-leak-on-raise" in by_ctx.get("stream_leak", [])


# ---------------------------------------------- pass 4: unbounded awaits


def test_unbounded_io_fires_and_bounded_variants_are_clean(tmp_path):
    report = run_fixture(tmp_path, {
        "cluster_anywhere_tpu/mod.py": """
            import asyncio
            from cluster_anywhere_tpu.util import aio

            async def bad_dial(host):
                r, w = await asyncio.open_connection(host, 1)

            async def bad_drain(writer):
                await writer.drain()

            async def bad_read(reader):
                return await reader.readline()

            async def wrapped(host):
                r, w = await asyncio.wait_for(
                    asyncio.open_connection(host, 1), 5)

            async def helper(addr):
                return await aio.dial(addr)

            async def kwarg(addr):
                return await aio.dial(addr, timeout=2)

            async def ctx_block(writer):
                async with asyncio.timeout(5):
                    await writer.drain()
            """,
    }, passes=("await",))
    flagged = sorted(
        f.context for f in report["findings"] if f.rule == "async-unbounded-io"
    )
    assert flagged == ["bad_dial", "bad_drain", "bad_read"]


# ------------------------------------------ pass 5: cancellation hygiene


def test_swallowed_cancel_shapes(tmp_path):
    report = run_fixture(tmp_path, {
        "cluster_anywhere_tpu/mod.py": """
            import asyncio

            async def swallow(q):
                try:
                    await q.get()
                except Exception:
                    pass

            async def swallow_bare(q):
                try:
                    await q.get()
                except:
                    pass

            async def swallow_explicit(q):
                try:
                    await q.get()
                except asyncio.CancelledError:
                    pass

            async def safe_first(q):
                try:
                    await q.get()
                except asyncio.CancelledError:
                    raise
                except Exception:
                    pass

            async def safe_reraise(q, log):
                try:
                    await q.get()
                except Exception:
                    log()
                    raise

            async def safe_narrow(q):
                try:
                    await q.get()
                except ConnectionError:
                    pass

            def sync_ok(q):
                try:
                    q.get()
                except Exception:
                    pass
            """,
    }, passes=("cancel",))
    flagged = sorted(
        f.context for f in report["findings"]
        if f.rule == "async-swallowed-cancel"
    )
    assert flagged == ["swallow", "swallow_bare", "swallow_explicit"]


def test_swallowed_cancel_seen_past_reraising_exception_handler(tmp_path):
    """An `except Exception: ...; raise` cannot catch cancellation, so a
    LATER broader handler that swallows it must still be reported."""
    report = run_fixture(tmp_path, {
        "cluster_anywhere_tpu/mod.py": """
            import asyncio

            async def sneaky(q, log, store):
                try:
                    await q.get()
                except Exception:
                    log()
                    raise
                except BaseException as e:
                    store(e)
            """,
    }, passes=("cancel",))
    flagged = [f for f in report["findings"] if f.rule == "async-swallowed-cancel"]
    assert [f.context for f in flagged] == ["sneaky"]


def test_finally_await_fingerprint_survives_unrelated_finally_edits(tmp_path):
    """The fingerprint indexes awaits among AWAITS, so adding a plain
    statement to the finally body must not churn it."""
    src = """
        async def f(q, conn, log):
            try:
                await q.get()
            finally:
                {extra}await conn.close()
        """
    r1 = run_fixture(tmp_path, {
        "cluster_anywhere_tpu/mod.py": src.format(extra=""),
    }, passes=("cancel",))
    (tmp_path / "cluster_anywhere_tpu/mod.py").write_text(
        textwrap.dedent(src.format(extra="log()\n                "))
    )
    r2 = engine.run_lint(
        root=str(tmp_path), passes=("cancel",),
        baseline_file=str(tmp_path / "baseline.json"),
    )
    fp1 = [f.fingerprint for f in r1["findings"] if f.rule == "finally-await"]
    fp2 = [f.fingerprint for f in r2["findings"] if f.rule == "finally-await"]
    assert fp1 and fp1 == fp2


def test_run_lint_rejects_unknown_pass(tmp_path):
    with pytest.raises(ValueError, match="unknown lint pass"):
        engine.run_lint(
            root=str(tmp_path), passes=("resx",),
            baseline_file=str(tmp_path / "b.json"),
        )


def test_finally_await_fires_and_wrapper_is_clean(tmp_path):
    report = run_fixture(tmp_path, {
        "cluster_anywhere_tpu/mod.py": """
            from cluster_anywhere_tpu.util.aio import finally_await

            async def masks(q, conn):
                try:
                    await q.get()
                finally:
                    await conn.close()

            async def safe(q, conn):
                try:
                    await q.get()
                finally:
                    await finally_await(conn.close(), "close")
            """,
    }, passes=("cancel",))
    flagged = [f for f in report["findings"] if f.rule == "finally-await"]
    assert [f.context for f in flagged] == ["masks"]


def test_finally_await_helper_preserves_inflight_exception():
    """util.aio.finally_await: a failing cleanup must not mask the in-flight
    exception (the finally-await rule's fix has to actually work)."""
    import asyncio

    from cluster_anywhere_tpu.util.aio import finally_await

    async def failing_cleanup():
        raise RuntimeError("cleanup blew up")

    async def main():
        try:
            try:
                raise ValueError("the real error")
            finally:
                await finally_await(failing_cleanup(), "t")
        except ValueError:
            return "preserved"
        except RuntimeError:
            return "masked"

    assert asyncio.run(main()) == "preserved"


# ------------------------------------------- pragmas, baseline, engine bits


def test_pragma_suppression(tmp_path):
    files = {
        HEAD: """
            class Head:
                # ca-lint: ignore[rpc-dead-handler]
                async def _h_probe(self, state, msg, reply, reply_err):
                    reply()
                async def _h_dead(self, state, msg, reply, reply_err):  # ca-lint: ignore
                    reply()
                # ca-lint: ignore[rpc-unknown-method]
                async def _h_wrong_rule(self, state, msg, reply, reply_err):
                    reply()
            """,
    }
    report = run_fixture(tmp_path, files, passes=("rpc",))
    assert [f.detail for f in report["findings"]] == ["head:wrong_rule"]
    assert report["suppressed"] == 2


def test_pragma_scopes_to_decorated_def(tmp_path):
    """A pragma above a decorator stack must suppress findings anchored at
    the `def` line below it (ast line numbers point at `def`, not `@`)."""
    report = run_fixture(tmp_path, {
        HEAD: """
            def deco(fn):
                return fn

            class Head:
                # ca-lint: ignore[rpc-dead-handler]
                @deco
                @deco
                async def _h_probe(self, state, msg, reply, reply_err):
                    reply()
                @deco
                async def _h_dead(self, state, msg, reply, reply_err):
                    reply()
            """,
    }, passes=("rpc",))
    assert [f.detail for f in report["findings"]] == ["head:dead"]
    assert report["suppressed"] == 1


def test_pragma_scopes_to_nested_function_site(tmp_path):
    report = run_fixture(tmp_path, {
        "cluster_anywhere_tpu/mod.py": """
            import asyncio

            def outer(coro, coro2):
                def inner():
                    asyncio.ensure_future(coro)  # ca-lint: ignore[async-dropped-task]
                def inner2():
                    asyncio.ensure_future(coro2)
                return inner, inner2
            """,
    }, passes=("async",))
    dropped = [f for f in report["findings"] if f.rule == "async-dropped-task"]
    assert [f.context for f in dropped] == ["outer.inner2"]
    assert report["suppressed"] == 1


def test_update_baseline_growth_warning_and_stale_exit(tmp_path, capsys):
    """The two engine edges the CLI wraps: --update-baseline warns when the
    baseline GROWS, and a stale entry fails the gate (exit 1) until the
    baseline shrinks back."""
    (tmp_path / HEAD).parent.mkdir(parents=True, exist_ok=True)
    (tmp_path / HEAD).write_text(textwrap.dedent("""
        class Head:
            async def _h_orphan(self, state, msg, reply, reply_err):
                reply()
        """))
    baseline = str(tmp_path / "baseline.json")
    common = ["--root", str(tmp_path), "--baseline", baseline]

    assert lint_main(common + ["--update-baseline"]) == 0
    assert "GREW" in capsys.readouterr().out  # 0 -> 1 entries

    # "fix" the finding: the baseline entry is now stale -> gate fails
    (tmp_path / HEAD).write_text("class Head:\n    pass\n")
    assert lint_main(common) == 1
    assert "STALE" in capsys.readouterr().out

    # shrinking is silent
    assert lint_main(common + ["--update-baseline"]) == 0
    assert "GREW" not in capsys.readouterr().out
    assert lint_main(common) == 0


def test_cli_exits_1_on_synthetic_leak_fixture(tmp_path, capsys):
    (tmp_path / "cluster_anywhere_tpu").mkdir(parents=True)
    (tmp_path / "cluster_anywhere_tpu/mod.py").write_text(textwrap.dedent("""
        import os

        def leaky(p):
            fd = os.open(p, 0)
            data = os.read(fd, 10)
            os.close(fd)
            return data
        """))
    rc = lint_main([
        "--root", str(tmp_path), "--baseline", str(tmp_path / "b.json"),
        "--format", "json",
    ])
    out = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert out["counts"] == {"res-leak-on-raise": 1}


def test_cli_rules_lists_every_pass(capsys):
    assert lint_main(["--rules"]) == 0
    out = capsys.readouterr().out
    for rule in (
        "rpc-dead-handler", "async-dropped-task", "res-leak-on-raise",
        "async-unbounded-io", "async-swallowed-cancel", "finally-await",
    ):
        assert rule in out
    for pass_name in engine.ALL_PASSES:
        assert f"pass {pass_name}:" in out


@pytest.mark.skipif(
    subprocess.run(["git", "--version"], capture_output=True).returncode != 0,
    reason="git unavailable",
)
def test_changed_mode_filters_to_diffed_files(tmp_path, capsys):
    """--changed: a pre-existing finding in an untouched file stays out of
    the report; a finding in a file differing from the merge-base fails."""
    def git(*args):
        subprocess.run(
            ("git", "-C", str(tmp_path)) + args, check=True,
            capture_output=True,
            env={**os.environ, "GIT_AUTHOR_NAME": "t", "GIT_AUTHOR_EMAIL": "t@t",
                 "GIT_COMMITTER_NAME": "t", "GIT_COMMITTER_EMAIL": "t@t"},
        )

    (tmp_path / "cluster_anywhere_tpu").mkdir(parents=True)
    old = tmp_path / "cluster_anywhere_tpu/old.py"
    old.write_text(textwrap.dedent("""
        import os

        def old_leak(p):
            fd = os.open(p, 0)
            os.read(fd, 1)
            os.close(fd)
        """))
    git("init", "-q", "-b", "main")
    git("add", "-A")
    git("commit", "-q", "-m", "seed")

    common = ["--root", str(tmp_path), "--baseline", str(tmp_path / "b.json")]
    # only the committed leak exists: --changed reports nothing
    assert lint_main(common + ["--changed", "--format", "json"]) == 0
    assert json.loads(capsys.readouterr().out)["findings"] == []

    # a NEW (untracked) leaky file fails, and only it is reported
    (tmp_path / "cluster_anywhere_tpu/new.py").write_text(textwrap.dedent("""
        import os

        def new_leak(p):
            fd = os.open(p, 0)
            os.read(fd, 1)
            os.close(fd)
        """))
    assert lint_main(common + ["--changed", "--format", "json"]) == 1
    out = json.loads(capsys.readouterr().out)
    assert {f["file"] for f in out["findings"]} == {"cluster_anywhere_tpu/new.py"}


def test_baseline_round_trip_and_stale_detection(tmp_path):
    files = {
        HEAD: """
            class Head:
                async def _h_orphan(self, state, msg, reply, reply_err):
                    reply()
            """,
    }
    baseline = tmp_path / "baseline.json"
    report = run_fixture(tmp_path, files, passes=("rpc",))
    assert not report["ok"] and len(report["new"]) == 1

    engine.save_baseline(str(baseline), report["findings"])
    report = engine.run_lint(
        root=str(tmp_path), passes=("rpc",), baseline_file=str(baseline)
    )
    assert report["ok"] and report["new"] == [] and report["stale"] == []

    # "fix" the dead handler: the baseline entry must now itself fail (the
    # baseline only shrinks)
    (tmp_path / HEAD).write_text(textwrap.dedent("""
        class Head:
            pass
        """))
    report = engine.run_lint(
        root=str(tmp_path), passes=("rpc",), baseline_file=str(baseline)
    )
    assert not report["ok"] and len(report["stale"]) == 1

    engine.save_baseline(str(baseline), report["findings"])
    assert json.loads(baseline.read_text())["findings"] == []


def test_fingerprints_survive_line_drift(tmp_path):
    files = {
        HEAD: """
            class Head:
                async def _h_orphan(self, state, msg, reply, reply_err):
                    reply()
            """,
    }
    r1 = run_fixture(tmp_path, files, passes=("rpc",))
    (tmp_path / HEAD).write_text(
        "# a comment\n# another\n" + textwrap.dedent(files[HEAD])
    )
    r2 = engine.run_lint(
        root=str(tmp_path), passes=("rpc",),
        baseline_file=str(tmp_path / "baseline.json"),
    )
    assert [f.fingerprint for f in r1["findings"]] == \
        [f.fingerprint for f in r2["findings"]]
    assert r1["findings"][0].line != r2["findings"][0].line


def test_parse_error_is_a_finding(tmp_path):
    report = run_fixture(tmp_path, {
        "cluster_anywhere_tpu/broken.py": "def broken(:\n",
    })
    assert [f.rule for f in report["findings"]] == ["parse-error"]


def test_cli_json_format_and_exit_codes(tmp_path, capsys):
    (tmp_path / "cluster_anywhere_tpu").mkdir(parents=True)
    (tmp_path / HEAD).parent.mkdir(parents=True, exist_ok=True)
    (tmp_path / HEAD).write_text(textwrap.dedent("""
        class Head:
            async def _h_orphan(self, state, msg, reply, reply_err):
                reply()
        """))
    baseline = str(tmp_path / "baseline.json")
    rc = lint_main([
        "--root", str(tmp_path), "--baseline", baseline, "--format", "json"
    ])
    out = json.loads(capsys.readouterr().out)
    assert rc == 1 and not out["ok"]
    assert out["counts"] == {"rpc-dead-handler": 1}
    assert out["new"][0]["rule"] == "rpc-dead-handler"

    assert lint_main([
        "--root", str(tmp_path), "--baseline", baseline, "--update-baseline"
    ]) == 0
    capsys.readouterr()
    rc = lint_main(["--root", str(tmp_path), "--baseline", baseline])
    assert rc == 0 and "clean" in capsys.readouterr().out


def test_ca_cli_routes_lint_flags_directly(tmp_path, capsys):
    """`ca lint --format json` must work without a `--` separator (argparse
    REMAINDER rejects leading option tokens; the CLI hands the tail straight
    to the lint parser)."""
    from cluster_anywhere_tpu.cli import main as ca_main

    (tmp_path / HEAD).parent.mkdir(parents=True, exist_ok=True)
    (tmp_path / HEAD).write_text("class Head:\n    pass\n")
    with pytest.raises(SystemExit) as ei:
        ca_main([
            "lint", "--root", str(tmp_path),
            "--baseline", str(tmp_path / "b.json"), "--format", "json",
        ])
    assert ei.value.code == 0
    assert json.loads(capsys.readouterr().out)["ok"] is True


def test_unparsable_top_level_file_is_a_finding_not_a_crash(tmp_path):
    (tmp_path / "scripts").mkdir()
    (tmp_path / "scripts" / "probe.py").write_text("def broken(:\n")
    report = engine.run_lint(
        root=str(tmp_path), baseline_file=str(tmp_path / "b.json")
    )
    assert [f.rule for f in report["findings"]] == ["parse-error"]


# ----------------------------------------------------- the repo self-check


def test_self_check_repo_is_clean():
    """Tier-1 gate: the full analyzer over this checkout must report zero
    non-baselined findings and zero stale baseline entries.  Fix the code,
    pragma the intentional site, or (last resort) --update-baseline."""
    report = engine.run_lint(root=REPO_ROOT)
    new = [f.render() for f in report["new"]]
    stale = [e["fingerprint"] for e in report["stale"]]
    assert report["ok"], (
        f"ca lint: {len(new)} new finding(s) {new[:10]}, "
        f"{len(stale)} stale baseline entrie(s) {stale[:10]}"
    )


def test_contract_covers_every_head_and_worker_handler():
    files = engine.collect_files(REPO_ROOT)
    c = contract_mod.extract_contract(files)
    head_methods = {h.method for h in c.handlers if h.surface == "head"}
    # every `_h_*` def in head.py must appear in the contract
    import re

    src = open(os.path.join(REPO_ROOT, "cluster_anywhere_tpu/core/head.py")).read()
    defs = set(re.findall(r"async def _h_(\w+)\(", src))
    assert head_methods == defs
    assert len(head_methods) >= 55  # ~60 modulo dead-handler burn-down
    worker_methods = {h.method for h in c.handlers if h.surface == "worker"}
    for m in ("push_task", "actor_call", "spawn_actor", "owner_refs",
              "owner_pin", "coll_push", "cancel", "stream_ack"):
        assert m in worker_methods, m
    # agent + driver surfaces came out non-trivially too
    assert len([h for h in c.handlers if h.surface == "agent"]) >= 10
    assert len([h for h in c.handlers if h.surface == "driver_p2p"]) >= 5


def test_committed_contract_is_fresh(tmp_path):
    """docs/PROTOCOL_CONTRACT.json must match regeneration — future PRs that
    touch handlers or call sites run `ca lint --contract`."""
    files = engine.collect_files(REPO_ROOT)
    current = contract_mod.contract_to_json(contract_mod.extract_contract(files))
    with open(os.path.join(REPO_ROOT, "docs", "PROTOCOL_CONTRACT.json")) as f:
        committed = json.load(f)
    assert committed == current, (
        "docs/PROTOCOL_CONTRACT.json is stale: run `ca lint --contract`"
    )


# ------------------------------------------------- chaos-spec validation


def test_chaos_spec_rejects_unknown_method():
    from cluster_anywhere_tpu.core.protocol import RpcChaos

    with pytest.raises(ValueError, match="unknown RPC method.*push_taskk"):
        RpcChaos("push_taskk=1")
    # valid methods (including notify-only and agent-side ones) parse fine
    RpcChaos("push_task=2,lease_grant=1,obj_refs=3")


def test_chaos_spec_skips_validation_without_contract(tmp_path, monkeypatch):
    from cluster_anywhere_tpu.core.protocol import RpcChaos

    monkeypatch.setenv("CA_CONTRACT_PATH", str(tmp_path / "nope.json"))
    RpcChaos("anything_goes=1")  # best-effort: no contract, no check


# ------------------------- analyzer-found defect: actor pubs reached nobody


def test_actor_address_pub_reaches_driver_cache(ca_cluster):
    """`ca lint` found the head's `subscribe` RPC had no caller, so
    `_pub("actors", ...)` fanned out to zero subscribers and the driver's
    _actor_addr_cache only ever filled via get_actor refresh-on-failure.
    Drivers are now subscribed at register: actor creation must push the
    address into the cache with no cache-miss round trip."""
    import time

    import cluster_anywhere_tpu as ca
    from cluster_anywhere_tpu.core.worker import global_worker

    @ca.remote
    class A:
        def f(self):
            return 1

    a = A.remote()
    assert ca.get(a.f.remote()) == 1
    w = global_worker()
    deadline = time.time() + 10
    while time.time() < deadline and not w._actor_addr_cache:
        time.sleep(0.05)
    assert w._actor_addr_cache, (
        "actors pub did not reach the driver's address cache"
    )
