"""The decode step is read one step behind: a causal token a slot, or a pass of
every slot's block."""

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


def _watch_dispatches(monkeypatch, step="_decode_step_rowpos"):
    """Records what every call of the step's program (`_pass_step_rowpos`: a
    pass of blocks) was handed: [(ints, a copy of it as it was, floats, a copy,
    the device's `prev` it was given, the one it returned)]."""
    from cluster_anywhere_tpu.llm import continuous

    handed, real = [], getattr(continuous, step)

    def spy(params, cache, ints, floats, prev, rng, *, cfg):
        made = real(params, cache, ints, floats, prev, rng, cfg=cfg)
        handed.append((ints, ints.copy(), floats, floats.copy(), prev, made[-4]))
        return made

    monkeypatch.setattr(continuous, step, spy)
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
    assert all(0 <= h[1][1].min() and h[1][1].max() < t_max for h in handed)
    assert max(h[1][1][edge.slot] for h in handed) == t_max - 2 and cb._pos[edge.slot] == t_max - 1


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
    for ints, was, floats, floats_was, _, _ in handed:
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


def _state(cb, slot):
    """The host's mirror of a slot of a block batcher, as the program carries
    it: the block's position, its tokens, its fixed flags, and whether the
    block before is yet to be stored (whose tokens the program alone keeps)."""
    return [int(cb._pos[slot]), *cb._blk_tokens[:, slot].tolist(), *cb._blk_fixed[:, slot].tolist(),
            int(cb._blk_pending[slot])]


@pytest.mark.parametrize("sharpen", [0.0, 40.0], ids=["a-position-a-pass", "sharpened-head"])
def test_a_block_batcher_that_reads_one_pass_behind_serves_the_plain_loop(sharpen, monkeypatch):
    """Requests of unlike lengths through three slots, each pass dispatched
    before the one before is read: one ends by eos inside a block, one fills its
    cache rows to the last, one is cancelled while a pass holds its row, and
    each of them leaves the pass in flight one row computed late and dropped;
    the next call admits a waiting request into the freed slot while that pass
    still runs, and the request starts from its own rows (`fresh`).  Every
    stream, its `fixed_at` and its `block_tail` are the plain loop's over the
    reference, a request at a time; after every read the state the device
    carries for a slot is the host's mirror of it, a storing pass's move and an
    admit's tail among them; and no pass was given a block outside the cache."""
    from test_llm_blocks import MASK, plain_generate, program

    from cluster_anywhere_tpu.llm import ContinuousBatcher

    cfg, params = program(sharpen)
    b, t_max = cfg.block_length, 64
    rng = np.random.default_rng(7)
    prompt = lambda n: rng.integers(0, MASK, n).tolist()
    # a prompt whose answer brings a token it has not held before as its 3rd to 6th: that
    # token as the request's eos ends it there
    for _ in range(20):
        stopped = prompt(6)
        full = plain_generate(params, cfg, stopped, 10)[0]
        at = next((j for j in range(2, 6) if full[j] not in full[:j]), None)
        if at is not None:
            break
    assert at is not None
    handed = _watch_dispatches(monkeypatch, "_pass_step_rowpos")
    cb = ContinuousBatcher(params, cfg, slots=3, t_max=t_max, prefill_buckets=(16, 32), prefix_cache_entries=0)
    sent = {}  # name -> (request, the plain loop's (answer, passes, _, tail))

    def submit(name, ids, n, **kw):
        sent[name] = (cb.submit(ids, max_new_tokens=n, **kw), plain_generate(params, cfg, ids, n, eos=kw.get("eos_id")))
        return sent[name][0]

    edge = submit("edge", prompt(12), 48)  # three blocks in bucket 16, twelve more: its last ends the cache
    assert cb.block_plan(12, 48)[1] + 48 == t_max
    stops = submit("stops", stopped, 10, eos_id=full[at])
    submit("short", prompt(9), 5)
    submit("waits", prompt(3), 7)  # these two take the first two slots that free
    gone = submit("gone", prompt(18), 12)
    streams, calls, ahead, took_over, moves = {}, 0, 0, {}, set()
    while cb.has_work:
        landing, slots_before = cb._flight, list(cb._by_slot)
        late = [r for _, r in landing.rows if r.done] if landing is not None else []
        before = {s: _state(cb, s) for s, _ in landing.rows} if landing is not None else {}
        out = cb.step()
        calls += 1
        ahead += landing is not None and cb._flight is not None
        for rid, toks in out.items():
            streams.setdefault(rid, []).extend(toks)
        if landing is not None:
            # what the pass that was read left on the device for the next is what the host
            # has made of its mirror, for every row whose request the host goes on serving (the
            # mirror of one that ended at this read stays where its answer ended)
            _, ints, _, _, _, carried = handed[-1 - (cb._flight is not None)]
            for s, r in landing.rows:
                if not r.done:
                    left = np.asarray(carried)[:, s].tolist()
                    assert left[:2 + 2 * b] == _state(cb, s)
                    # a block the pass left whole is pending: the slot stands a block on, and what
                    # the next pass stores is the block as the pass that was read left it
                    pending = _state(cb, s)[-1]
                    assert _state(cb, s)[0] == before[s][0] + b * pending
                    if pending:
                        assert left[2 + 2 * b:] == np.asarray(landing.made)[:b, s].tolist()
                        moves.add("stored")
                    else:
                        assert left[2 + 2 * b:] == [0] * b
                    if ints[2][s] and any(before[s][1 + b:1 + 2 * b]):
                        moves.add("fresh-tail")
        for name, ended in (("stops", stops), ("gone", gone)):
            # the call after its end: its slot was free as the call began, the pass then in
            # flight still held its row, and the call's admit put the next request into the slot
            if ended.done and slots_before[ended.slot] is None and any(r is ended for r in late):
                took_over.setdefault(name, cb._by_slot[ended.slot])
        if len(gone.out_tokens) >= 3 and not gone.done:
            assert any(r is gone for _, r in cb._flight.rows)  # a pass holds its row: computed for nothing
            assert cb.cancel(gone.request_id)
            submit("last", prompt(7), 5)
    assert calls < 120 and all(r.done for r, _ in sent.values()) and moves == {"stored", "fresh-tail"}
    tails = []
    for name, (req, (want, passes, _, tail)) in sent.items():
        n = len(req.out_tokens)
        assert (n == len(want)) != (req is gone) and n >= 3, name
        assert req.out_tokens == want[:n] == streams[req.request_id] and req.fixed_at == passes[:n], name
        if req is not gone:  # the batcher keeps the record of those that finished
            assert cb.fixed_at(req.request_id) == passes and cb.block_tail(req.request_id) == tail, name
            tails += tail
    assert stops.out_tokens[-1] == full[at] and len(stops.out_tokens) == at + 1 and tails
    assert cb.stats["tokens_out"] == sum(len(t) for t in streams.values())
    # an answer's end is in what a pass fixes: each request, ended or cancelled, was in one pass
    # more than the host served it in, and the two slots were given away while that pass ran
    assert cb.stats["late_rows"] == len(sent) == 6 and cb.stats["cancelled"] == 1
    assert took_over["stops"] is not None and took_over["gone"] is sent["last"][0]
    # every call but the first read a pass, and every call but the last dispatched one before it read
    assert cb.stats["decode_steps"] == len(handed) == calls - 1 and cb.stats["steps_ahead"] == ahead == calls - 2
    assert cb.stats["block_passes"] == sum(int(h[1][1].sum()) for h in handed)
    # the block every live row of every pass was given lies in the cache, but for the one row
    # computed late for the request that fills its rows: it ran its last passes on the cache's
    # last block, and the pass in flight at its end stood a block past it (and wrote that nowhere)
    given = np.concatenate([np.where((was[2] != 0) | (was[1] == 0), was[3], np.asarray(prev)[0])[was[1] != 0]
                            for _, was, _, _, prev, _ in handed])
    assert given.min() >= 0 and sorted(given)[-2:] == [t_max - b, t_max] and cb._pos[edge.slot] == t_max - b


def test_a_pass_is_dispatched_before_the_pass_before_it_is_read(llm_spans, monkeypatch):
    """The order is held for a pass of blocks as for a causal step: in a call
    that has a pass in flight and dispatches another (`ahead=1`),
    `llm.step.dispatch` closes before `llm.step.readback` opens; `llm.step`
    says of the pass that was READ how many rows it held, a late one among them;
    the arrays a pass was handed are its own; a slot is marked fresh for its
    first pass alone; and `steps_ahead`, `late_rows` count what the calls come to."""
    from test_llm_blocks import program

    from cluster_anywhere_tpu.llm import ContinuousBatcher
    from cluster_anywhere_tpu.util import tracing

    cfg, params = program()
    handed = _watch_dispatches(monkeypatch, "_pass_step_rowpos")
    cb = ContinuousBatcher(params, cfg, slots=2, t_max=32, prefill_buckets=(8, 16), prefix_cache_entries=0)
    token = tracing.push_execution(TRACE)
    try:
        a, b = cb.submit(list(range(1, 7)), max_new_tokens=9), cb.submit(list(range(1, 6)), max_new_tokens=2)
        flights = []  # a call: the rows of the pass it read, whether it dispatched one
        while not b.done:
            landing = cb._flight
            out = cb.step()
            flights.append((len(landing.rows) if landing is not None else 0, cb._flight is not None))
            assert (not out) == (landing is None) or not b.done  # an admit hands out nothing
        # `b` ended at that read: the pass dispatched just before holds its row once more
        assert not a.done and cb._flight.rows == [(a.slot, a), (b.slot, b)]
        assert cb.step().keys() <= {a.request_id}  # read it: `b`'s row dropped; dispatched `a` alone
        assert cb._flight.rows == [(a.slot, a)] and cb.cancel(a.request_id)  # while a pass holds its row
        assert cb.has_work and cb.step() == {} and not cb.has_work  # read that pass and dropped the row
        assert cb.step() == {}  # nothing in flight, nothing live: no pass
    finally:
        tracing.pop_execution(token)
    flights += [(2, True), (1, False), (0, False)]
    assert flights[:2] == [(0, True), (2, True)] and len(b.out_tokens) == 2 and 0 < len(a.out_tokens) < 9
    counted = dict(decode_steps=len(flights) - 2, steps_ahead=len(flights) - 3, late_rows=2, finished=1, cancelled=1,
                   tokens_out=len(a.out_tokens) + 2)
    assert {k: cb.stats[k] for k in counted} == counted
    events = llm_spans()
    steps = [e for e in events if e["name"] == "llm.step"]
    assert [(e["live"], e["ahead"]) for e in steps] == [(live, int(live > 0 and more)) for live, more in flights]
    # a pass that was read says what it held, late rows among them: the device ran them
    assert [e.get("block_rows", 0) for e in steps] == [4 * live for live, _ in flights]
    part = lambda step, name: [e for e in events if e["name"] == name and e["trace"].get("psid") == step["trace"]["sid"]]
    for step, (live, more) in zip(steps, flights):
        dispatch, readback = part(step, "llm.step.dispatch"), part(step, "llm.step.readback")
        assert (len(dispatch), len(readback)) == (int(more), int(live > 0))
        if step["ahead"]:
            closes = dispatch[0]["mono"] + (dispatch[0]["end"] - dispatch[0]["start"])
            assert closes <= readback[0]["mono"]
    # each pass was handed arrays of its own, which read as they did when the scheduler has
    # written its vectors again; both slots were fresh for the first pass and none after it
    assert len(handed) == len(flights) - 2
    for ints, was, floats, floats_was, _, _ in handed:
        assert not np.shares_memory(ints, cb._ints) and not np.shares_memory(floats, cb._floats)
        assert np.array_equal(ints, was) and np.array_equal(floats, floats_was)
    assert [h[1][2].tolist() for h in handed] == [[1, 1]] + [[0, 0]] * (len(handed) - 1) and not cb._fresh.any()
    assert [h[1][1].tolist() for h in handed][-2:] == [[1, 1], [1, 0]]
