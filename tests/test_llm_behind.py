"""The causal step reads one step behind."""

import numpy as np
import pytest

from _llm_tiny import (  # noqa: F401 (llm_spans is a fixture)
    TRACE,
    _TINY,
    _TINY_HYBRID,
    _TINY_MIXTURE,
    _float32_model,
    _tiny_batcher,
    llm_spans,
)


def _watch_dispatches(monkeypatch):
    """Records what every `_decode_step_rowpos` call was handed: [(ints, a copy
    of it as it was, floats, a copy)]."""
    from cluster_anywhere_tpu.llm import continuous

    handed, real = [], continuous._decode_step_rowpos

    def spy(params, cache, ints, floats, prev, rng, *, cfg):
        handed.append((ints, ints.copy(), floats, floats.copy()))
        return real(params, cache, ints, floats, prev, rng, cfg=cfg)

    monkeypatch.setattr(continuous, "_decode_step_rowpos", spy)
    return handed


@pytest.mark.parametrize("model", [_TINY, _TINY_MIXTURE, _TINY_HYBRID], ids=["dense", "mixture", "hybrid"])
def test_a_batcher_that_reads_one_step_behind_answers_as_generate_does(model, monkeypatch):
    """Requests of unlike lengths through three slots, each step dispatched
    before the one before is read: one ends by eos in mid-stream (the step in
    flight holds its row once more: computed late, dropped), a waiting request
    takes its slot at the very next call, while that late step still runs; one
    is cancelled while a step holds its row, and its slot is taken likewise; one
    fills its cache rows to the last (`bucket + max_new_tokens == t_max`).
    Every greedy token is `generate()`'s, one by one; nothing is handed out past
    an eos, a length or a cancel; `tokens_out` is what was handed out; and no
    step, late ones included, was given a position outside the cache."""
    import jax
    import jax.numpy as jnp

    from cluster_anywhere_tpu.llm import ContinuousBatcher
    from cluster_anywhere_tpu.models.generate import generate

    cfg, params = _float32_model(model)
    t_max = 32
    rng = np.random.default_rng(7)
    answer = lambda prompt, n: np.asarray(generate(
        params, jnp.asarray([prompt], jnp.int32), jax.random.key(0), cfg=cfg, max_new_tokens=n))[0].tolist()
    prompt = lambda n: rng.integers(1, cfg.vocab_size, n).tolist()
    # a prompt whose greedy answer brings a token it has not held before as its 3rd to 6th:
    # that token as the request's eos ends it there, in mid-stream
    for _ in range(20):
        stopped = prompt(4)
        full = answer(stopped, 10)
        at = next((j for j in range(2, 6) if full[j] not in full[:j]), None)
        if at is not None:
            break
    assert at is not None
    handed = _watch_dispatches(monkeypatch)
    cb = ContinuousBatcher(params, cfg, slots=3, t_max=t_max, prefill_buckets=(8,))
    sent = {}  # name -> (request, the tokens it is to be handed)

    def submit(name, ids, n, keep=None, **kw):
        sent[name] = (cb.submit(ids, max_new_tokens=n, **kw), answer(ids, n)[:keep])
        return sent[name][0]

    edge = submit("edge", prompt(5), 24)  # admitted in bucket 8: 8 + 24 is the cache's length
    assert cb._bucket(5, 24) + 24 == t_max
    stops = submit("stops", stopped, 10, keep=at + 1, eos_id=full[at])
    submit("short", prompt(6), 4)
    submit("waits", prompt(3), 7)  # these two take the first two slots that free
    gone = submit("gone", prompt(7), 12, keep=3)
    streams, calls, ahead, took_over = {}, 0, 0, {}
    while cb.has_work:
        landing, slots_before = cb._flight, list(cb._by_slot)
        out = cb.step()
        calls += 1
        ahead += landing is not None and cb._flight is not None
        for rid, toks in out.items():
            streams.setdefault(rid, []).extend(toks)
        for name, late in (("stops", stops), ("gone", gone)):
            # the call after its end: its slot was free as the call began, the step then in
            # flight still held its row, and the call's admit put the next request into the slot
            if (late.done and slots_before[late.slot] is None and landing is not None
                    and any(r is late for _, r in landing.rows)):
                took_over.setdefault(name, cb._by_slot[late.slot])
        if len(gone.out_tokens) == 3 and not gone.done:
            assert any(r is gone for _, r in cb._flight.rows)  # a step holds its row: computed for nothing
            assert cb.cancel(gone.request_id)
            submit("last", prompt(2), 5)
    assert calls < 60 and all(r.done for r, _ in sent.values())
    for name, (req, want) in sent.items():
        assert req.out_tokens == want and streams[req.request_id] == want, name
    assert stops.out_tokens[-1] == full[at] and len(stops.out_tokens) < 10
    assert cb.stats["tokens_out"] == sum(len(t) for t in streams.values())
    # each of the two was in one step more than it was handed tokens of, and its slot was
    # given away while that step ran
    assert cb.stats["late_rows"] == 2 and cb.stats["cancelled"] == 1
    assert took_over["stops"] is not None and took_over["gone"] is sent["last"][0]
    # every call but the first read a step, and every call but the last dispatched one before it read
    assert cb.stats["decode_steps"] == len(handed) == calls - 1 and cb.stats["steps_ahead"] == ahead == calls - 2
    # every position any step was given lies in the cache; the request that fills its rows
    # was last dispatched at the last but one, and its idle row rests on the last
    assert all(0 <= was[1].min() and was[1].max() < t_max for _, was, _, _ in handed)
    assert max(was[1][edge.slot] for _, was, _, _ in handed) == t_max - 2 and cb._pos[edge.slot] == t_max - 1


def test_a_step_is_dispatched_before_the_step_before_it_is_read(llm_spans, monkeypatch):
    """The order is held: in a call that has a step in flight and dispatches
    another (`ahead=1`), `llm.step.dispatch` closes before `llm.step.readback`
    opens; the arrays a step was handed are its own, unchanged when the
    scheduler has written its vectors again; and `steps_ahead`, `late_rows` and
    the two series they are shipped as count what the calls below come to."""
    from cluster_anywhere_tpu.llm import serve_llm
    from cluster_anywhere_tpu.util import metrics, tracing

    handed = _watch_dispatches(monkeypatch)
    cb = _tiny_batcher()
    token = tracing.push_execution(TRACE)
    try:
        a, b = cb.submit([1, 2, 3], max_new_tokens=5), cb.submit([4, 5], max_new_tokens=3)
        outs = [cb.step() for _ in range(3)]
        # call 0 admitted both and dispatched step 0; call 1 dispatched step 1 and read step 0;
        # call 2 dispatched step 2 for `a` alone (`b` reaches its length with step 1) and read step 1
        assert [sorted(map(len, o.values())) for o in outs] == [[1, 1], [1, 1], [1, 1]]
        assert b.done and not a.done and cb._flight.rows == [(a.slot, a)]
        assert cb.cancel(a.request_id)  # while step 2 holds its row
        assert cb.has_work and cb.step() == {} and not cb.has_work  # call 3 read step 2 and dropped the row
        assert cb.step() == {}  # nothing in flight, nothing live: no step
    finally:
        tracing.pop_execution(token)
    assert (len(a.out_tokens), len(b.out_tokens)) == (3, 3)
    counted = dict(decode_steps=3, steps_ahead=2, late_rows=1, tokens_out=6, finished=1, cancelled=1)
    assert {k: cb.stats[k] for k in counted} == counted
    events = llm_spans()
    steps = [e for e in events if e["name"] == "llm.step"]
    assert [(e["live"], e["ahead"]) for e in steps] == [(0, 0), (2, 1), (2, 1), (1, 0), (0, 0)]
    part = lambda step, name: [e for e in events if e["name"] == name and e["trace"].get("psid") == step["trace"]["sid"]]
    for step, dispatched, read in zip(steps, (1, 1, 1, 0, 0), (0, 1, 1, 1, 0)):
        dispatch, readback = part(step, "llm.step.dispatch"), part(step, "llm.step.readback")
        assert (len(dispatch), len(readback)) == (dispatched, read)
        if step["ahead"]:
            closes = dispatch[0]["mono"] + (dispatch[0]["end"] - dispatch[0]["start"])
            assert closes <= readback[0]["mono"]
    # three dispatches, each handed arrays of its own: the scheduler moved its positions on and
    # took the fresh marks back right after each, and what the step was handed still reads as it did
    assert len(handed) == 3
    for ints, was, floats, floats_was in handed:
        assert not np.shares_memory(ints, cb._ints) and not np.shares_memory(floats, cb._floats)
        assert np.array_equal(ints, was) and np.array_equal(floats, floats_was)
    fresh, pos = [h[1][4].tolist() for h in handed], [h[1][1].tolist() for h in handed]
    assert fresh == [[1, 1], [0, 0], [0, 0]] and cb._fresh.tolist() == [0, 0]
    assert pos[1] == [p + 1 for p in pos[0]] and pos[2][a.slot] == pos[0][a.slot] + 2
    # shipped beside the batcher's other counters, as deltas of `cb.stats`
    shipped = []
    monkeypatch.setattr(metrics.Counter, "inc", lambda self, value=1.0, tags=None: shipped.append((self.name, value)))
    server = object.__new__(serve_llm.ContinuousLLMServer)  # the method's own needs, no pump's thread
    server.cb, server._metrics_synced = cb, {}
    server.engine_device = {"count": 1, "platform": "cpu", "device_kind": "cpu"}
    server._sync_engine_metrics()
    shipped = dict(shipped)
    assert shipped["ca_serve_steps_ahead_total"] == 2 and shipped["ca_serve_late_rows_total"] == 1
    assert shipped["ca_serve_decode_steps_total"] == 3
