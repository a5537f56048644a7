"""What decides `correct` in a serving cell (harness/reference.py
check_serving, replica.BenchIngress.bench_check) and the parts of it that a
configuration's reference may bring: for the three references that bring no
`chosen_logits` the report is the parent's, number for number; a toy whose
generation is not one causal token a step is held to its reference through
the hooks and a record its batcher keeps by request id; an entry of
`mechanism_checks` over its tolerance is not correct."""

import copy
import json
import os
import shutil
import threading
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import cluster_anywhere_tpu as ca
from benchmarks import run as bench_run
from benchmarks.harness import cluster, manifest, replica, serve_driver
from benchmarks.harness.reference import check_serving
from cluster_anywhere_tpu.llm.continuous import ContinuousBatcher
from cluster_anywhere_tpu.models.transformer import TransformerConfig, init_params

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")

# each architecture's published block at a test's widths, through its own cell's files
TINY = {
    "dense_gqa": ("chat-closed6", dict(
        hidden_size=64, num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        intermediate_size=128, vocab_size=512)),
    "olmoe": ("olmoe-closed6", dict(
        hidden_size=64, num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=4, head_dim=16,
        intermediate_size=48, vocab_size=512, num_experts=8, num_experts_per_tok=2)),
    "jamba": ("jamba-closed6", dict(
        hidden_size=64, num_hidden_layers=6, num_attention_heads=4, num_key_value_heads=1, head_dim=16,
        intermediate_size=96, vocab_size=512, attn_layer_period=3, attn_layer_offset=2, mamba_d_state=8,
        mamba_dt_rank=8, layers_block_type=["mamba", "mamba", "attention"] * 2)),
}


def parent_check_serving(cb, streams, ref):
    """`check_serving` as it stood before a reference could say what chose a
    token (commit 20b9388), kept word for word as the yardstick of the default
    path: one causal token a step, the prefill's logits held to the first."""
    from cluster_anywhere_tpu.models.generate import prefill

    fulls = [np.asarray(s["prompt_ids"] + s["served"][:-1], np.int32) for s in streams]
    length = max(len(f) for f in fulls)
    logit_err, regrets, agree = 0.0, [], 0
    for s, full in zip(streams, fulls):
        prompt, served = np.asarray(s["prompt_ids"], np.int32), [int(t) for t in s["served"]]
        n = len(prompt)
        want = np.asarray(ref.forward(cb.params, np.pad(full, (0, length - len(full))), cb.cfg))
        want = want[n - 1: n - 1 + len(served)]  # row i: the logits that choose served[i]
        bucket = cb._bucket(n, len(served))
        padded = np.zeros(bucket, np.int32)
        padded[bucket - n:] = prompt
        logits, _ = prefill(cb.params, jnp.asarray(padded[None]), cb.cfg, cb.t_max,
                            pad=jnp.asarray([bucket - n], np.int32))
        logit_err = max(logit_err, float(np.max(np.abs(np.asarray(logits[0], np.float32) - want[0]))))
        regret = want.max(axis=-1) - want[np.arange(len(served)), served]
        regrets.extend(float(r) for r in regret)
        agree += int(np.sum(regret == 0.0))
    report = {
        "logit_max_abs_err": logit_err, "logit_tolerance": ref.LOGIT_TOL,
        "regret_max": max(regrets), "regret_max_tolerance": ref.REGRET_MAX_TOL,
        "regret_mean": sum(regrets) / len(regrets), "regret_mean_tolerance": ref.REGRET_MEAN_TOL,
        "agree_share": agree / len(regrets), "streams": len(streams), "positions": len(regrets),
    }
    report["ok"] = bool(
        logit_err <= ref.LOGIT_TOL and report["regret_max"] <= ref.REGRET_MAX_TOL
        and report["regret_mean"] <= ref.REGRET_MEAN_TOL
    )
    return report


@pytest.mark.parametrize("arch, own_pass", [(a, False) for a in sorted(TINY)] + [("olmoe", True)],
                         ids=sorted(TINY) + ["olmoe-own-pass"])
def test_the_default_path_gives_the_parents_report_number_for_number(arch, own_pass):
    name, tiny = TINY[arch]
    cell = copy.deepcopy(manifest.load_cell(name))
    cell["config_file"]["config"].update(tiny)
    ref = manifest.reference_of(cell)
    assert ref is manifest.load_reference(arch)
    # OLMoE's file brings the default's pass as its own, to keep what its mechanism reads
    assert hasattr(ref, "chosen_logits") == (arch == "olmoe") and not hasattr(ref, "program_logits")
    cfg = TransformerConfig(**ref.program_config(cell["config_file"], vocab_size=tiny["vocab_size"],
                                                 dtype=jnp.bfloat16, param_dtype=jnp.bfloat16))
    cb = ContinuousBatcher(init_params(jax.random.key(7), cfg), cfg, slots=4, t_max=128,
                           prefill_buckets=(32, 64, 96))
    rng = np.random.default_rng(2)
    reqs = [cb.submit(rng.integers(0, cfg.vocab_size, n), max_new_tokens=10) for n in (9, 33, 70)]
    cb.pump()
    streams = [{"prompt_ids": r.prompt_ids.tolist(), "served": list(r.out_tokens),
                "request_id": r.request_id} for r in reqs]
    want = parent_check_serving(cb, streams, ref)
    got = check_serving(cb, streams, ref if own_pass else without(ref, "chosen_logits"))
    mechanism = got.pop("mechanism", None)
    # bf16 against float32: the numbers are not 0
    assert want["logit_max_abs_err"] > 1e-4 and want["positions"] == 30
    assert list(got) == list(want)
    if own_pass:
        # the same float32 arithmetic at other compiled shapes (each stream padded by itself,
        # the head over the rows that chose a token): the parent's numbers to float32's rounding
        assert got == {k: pytest.approx(v, rel=1e-4, abs=1e-5) for k, v in want.items()}
    else:
        assert got == want  # the parent's to the last digit
    assert (mechanism is not None) == hasattr(ref, "mechanism_checks") == (arch == "olmoe")


# -- a generation that is not one causal token a step ------------------------------


class BlockBatcher:
    """The toy's program (data/toy_blocks.py says what it generates), in
    numpy and written apart from the reference: every live request gets one
    pass over its current block a step.  A pass fixes each masked position
    whose confidence is at least 0.97 of the best one's, a step hands out the
    tokens that are fixed with every token before them, and `fixed_at` keeps,
    by request id, the pass of its block in which each served token was
    fixed.  Has what `BenchIngress` wraps and reads of a batcher."""

    def __init__(self, params, cfg):
        self.params, self.cfg = params, cfg
        self._w = {k: np.asarray(v, np.float32) for k, v in params.items()}
        self.queue, self._by_slot = [], []
        self.stats = {"admitted": 0, "decode_steps": 0, "prefix_tokens_reused": 0}
        self._live, self._fixed_at, self._next_id = [], {}, 100

    def _admit(self, out=None):
        pass

    def submit(self, ids, max_new_tokens):
        req = types.SimpleNamespace(
            request_id=self._next_id, prompt_ids=[int(t) for t in ids], max_new=int(max_new_tokens),
            out_tokens=[], fixed={}, start=len(ids) - len(ids) % self.cfg.block, pass_no=0)
        self._next_id += 7
        self._fixed_at[req.request_id] = {}
        self._live.append(req)
        self.stats["admitted"] += 1
        return req

    def first_pass_logits(self, ids):
        w, b = self._w, self.cfg.block
        x = w["embed"][np.asarray(ids)] + w["pos"][: len(ids)]
        blocks = np.arange(len(ids)) // b
        s = (x @ w["wq"]) @ (x @ w["wk"]).T / np.sqrt(np.float32(x.shape[-1]))
        s = np.where(blocks[None, :] <= blocks[:, None], s, -np.inf)
        p = np.exp(s - s.max(axis=-1, keepdims=True))
        return (x + (p / p.sum(axis=-1, keepdims=True)) @ (x @ w["wv"])) @ w["head"]

    def fixed_at(self, request_id):
        passes = self._fixed_at[request_id]
        return [passes[i] for i in sorted(passes)]

    def step(self):
        out = {}
        for req in list(self._live):
            n, total = len(req.prompt_ids), len(req.prompt_ids) + req.max_new
            end = min(req.start + self.cfg.block, total)
            known = req.prompt_ids + [req.fixed[i] for i in range(n, req.start)]
            state = [known[i] if i < len(known) else req.fixed.get(i, self.cfg.mask_id)
                     for i in range(req.start, end)]
            logits = self.first_pass_logits(known[: req.start] + state)
            masked = [i for i in range(max(req.start, n), end) if i not in req.fixed]
            conf = {}
            for i in masked:
                p = np.exp(logits[i] - logits[i].max())
                conf[i] = float(p.max() / p.sum())
            for i in masked:
                if conf[i] >= 0.97 * max(conf.values()):
                    req.fixed[i] = int(np.argmax(logits[i]))
                    self._fixed_at[req.request_id][i] = req.pass_no
            req.pass_no += 1
            if all(i in req.fixed for i in range(max(req.start, n), end)):
                req.start, req.pass_no = end, 0
            new = []
            while n + len(req.out_tokens) + len(new) in req.fixed:
                new.append(req.fixed[n + len(req.out_tokens) + len(new)])
            if new:
                req.out_tokens.extend(new)
                out[req.request_id] = new
            if len(req.out_tokens) == req.max_new:
                self._live.remove(req)
        self.stats["decode_steps"] += 1
        return out


@pytest.fixture(scope="module")
def toy(tmp_path_factory):
    """The toy's reference, loaded as a later PR's would be: from
    references/<name>.py of a benchmark directory, by name."""
    bench = tmp_path_factory.mktemp("bench")
    os.mkdir(bench / "references")
    shutil.copy(os.path.join(DATA, "toy_blocks.py"), bench / "references" / "toy_blocks.py")
    ref = manifest.load_reference("toy_blocks", str(bench))
    assert all(hasattr(ref, n) for n in manifest.REFERENCE_OPTIONAL[:2]) and not hasattr(ref, "mechanism_checks")
    config_file = {"config": dict(hidden_size=32, vocab_size=97, block_length=4, max_position_embeddings=64)}
    fields = ref.program_config(config_file)
    e, v = fields["d_model"], fields["vocab_size"]
    keys = jax.random.split(jax.random.key(11), 7)
    shapes = dict(embed=(v, e), pos=(fields["max_seq_len"], e), wq=(e, e), wk=(e, e), wv=(e, e), head=(e, v))
    params = {name: jax.random.normal(k, shape) * (0.3 if name == "pos" else shape[0] ** -0.5 * 3.0)
              for k, (name, shape) in zip(keys, shapes.items())}
    cfg = types.SimpleNamespace(block=fields["block"], mask_id=v - 1, vocab_size=v)
    return ref, params, cfg


PROMPT_LENS, NEW_TOKENS = (5, 8, 14), 11  # the answers start inside a block, at a block's edge, and end inside one


def serve_together(cb, submit):
    rng = np.random.default_rng(4)
    reqs = [submit(rng.integers(0, cb.cfg.mask_id, n), NEW_TOKENS) for n in PROMPT_LENS]
    handed = []
    while any(len(r.out_tokens) < NEW_TOKENS for r in reqs):
        handed.append(cb.step())
    return reqs, handed


def without(ref, *names, **more):
    return types.SimpleNamespace(**{k: v for k, v in vars(ref).items()
                                    if not k.startswith("__") and k not in names}, **more)


def test_a_blockwise_generation_is_held_to_its_reference_through_the_hooks(toy):
    ref, params, cfg = toy
    cb = BlockBatcher(params, cfg)
    reqs, handed = serve_together(cb, lambda ids, n: cb.submit(ids, max_new_tokens=n))
    # a step hands a request 0 to 4 tokens, and blocks took one to several passes
    sizes = {len(v) for out in handed for v in out.values()}
    assert sizes >= {1, 2} and max(sizes) <= cfg.block and any(len(out) < len(reqs) for out in handed)
    passes = [cb.fixed_at(r.request_id) for r in reqs]
    assert max(max(p) for p in passes) >= 2 and any(p != sorted(p) for p in passes)
    streams = [{"prompt_ids": r.prompt_ids, "served": r.out_tokens, "request_id": r.request_id} for r in reqs]
    rep = check_serving(cb, streams, ref)
    assert rep["ok"] and rep["positions"] == 3 * NEW_TOKENS and rep["agree_share"] == 1.0, rep
    assert rep["logit_max_abs_err"] < 1e-4 and rep["regret_max"] == 0.0 and "mechanism" not in rep
    # the record of another order (left to right, one position a pass) replays other
    # block states: the same tokens are no longer the reference's choice
    other = copy.copy(cb)
    other.fixed_at = lambda rid: [
        i - max(i - i % cfg.block, len(r.prompt_ids)) for r in reqs if r.request_id == rid
        for i in range(len(r.prompt_ids), len(r.prompt_ids) + NEW_TOKENS)]
    assert [other.fixed_at(r.request_id) for r in reqs] != passes
    bad = check_serving(other, streams, ref)
    assert not bad["ok"] and bad["regret_max"] > ref.REGRET_MAX_TOL and bad["agree_share"] < 1.0, bad
    # a record that is not this stream's is an error, not a low number
    short = copy.copy(cb)
    short.fixed_at = lambda rid: passes[0][:-1]
    with pytest.raises(ValueError, match="passes for"):
        check_serving(short, streams, ref)
    # one causal token a step, the default: row n - 1 + i of a causal pass chose none of these
    causal = check_serving(cb, streams, without(ref, "chosen_logits"))
    assert not causal["ok"] and causal["regret_max"] > 100 * ref.REGRET_MAX_TOL, causal
    assert causal["logit_max_abs_err"] > ref.LOGIT_TOL


def ingress_over(cb):
    """`BenchIngress` around a batcher that is there already: what
    `__init__` does after the program's own set-up."""
    ing = replica.BenchIngress.__new__(replica.BenchIngress)
    ing.cb, ing._lock = cb, threading.Lock()
    ing._wrap_batcher()

    def submit(bench_id):
        def send(ids, n):
            ing._tls.bench_id, ing._tls.t_submit = bench_id, time.monotonic()
            return ing.cb.submit(ids, max_new_tokens=n)
        return send

    return ing, submit


def test_the_replica_finds_a_check_stream_by_its_id_and_counts_the_requests_a_step_served(toy, monkeypatch):
    ref, params, cfg = toy
    monkeypatch.setattr(manifest, "load_reference", lambda name: {"toy_blocks": ref}[name])
    ing, submit = ingress_over(BlockBatcher(params, cfg))
    t_begin = time.monotonic()
    ids = iter(["rcheck0", "rcheck1", "rcheck2"])
    reqs, _ = serve_together(ing.cb, lambda p, n: submit(next(ids))(p, n))
    streams = [{"prompt_ids": r.prompt_ids, "served": r.out_tokens, "bench_id": f"rcheck{i}"}
               for i, r in enumerate(reqs)]
    rep = ing.bench_check(streams, t_begin, "toy_blocks")
    assert rep["ok"] and rep["agree_share"] == 1.0 and rep["positions"] == 3 * NEW_TOKENS, rep
    assert 1.0 < rep["decode_requests_mean"] <= 3.0 and rep["decode_batch_mean"] > rep["decode_requests_mean"]
    # the window's requests leave no id behind, and a second check finds no stream
    submit("c0r0")(reqs[0].prompt_ids, 2)
    assert ing._check_ids is None
    with pytest.raises(RuntimeError, match="never submitted"):
        ing.bench_check(streams, t_begin, "toy_blocks")
    # a stream under an id the replica never saw is an error, not a None
    ing, submit = ingress_over(BlockBatcher(params, cfg))
    req = submit("rcheck0")(reqs[0].prompt_ids, NEW_TOKENS)
    while len(req.out_tokens) < NEW_TOKENS:
        ing.cb.step()
    one = {"prompt_ids": req.prompt_ids, "served": req.out_tokens, "bench_id": "rcheck0"}
    with pytest.raises(RuntimeError, match=r"\['rcheck9'\] were never submitted"):
        ing.bench_check([one, dict(one, bench_id="rcheck9")], t_begin, "toy_blocks")


def test_streams_served_one_after_the_other_did_not_overlap_whatever_a_step_hands_out(toy, monkeypatch):
    ref, params, cfg = toy
    monkeypatch.setattr(manifest, "load_reference", lambda name: ref)
    ing, submit = ingress_over(BlockBatcher(params, cfg))
    t_begin, streams = time.monotonic(), []
    rng = np.random.default_rng(4)
    for i, n in enumerate(PROMPT_LENS):
        req = submit(f"rcheck{i}")(rng.integers(0, cfg.mask_id, n), NEW_TOKENS)
        while len(req.out_tokens) < NEW_TOKENS:
            ing.cb.step()
        streams.append({"prompt_ids": req.prompt_ids, "served": req.out_tokens, "bench_id": f"rcheck{i}"})
    rep = ing.bench_check(streams, t_begin, "toy_blocks")
    # every number passes and a step handed out 1 to 4 tokens, which the tokens a step
    # alone would take for a batch; but no step served two requests
    assert rep["regret_max"] == 0.0 and rep["logit_max_abs_err"] < 1e-4
    assert rep["decode_batch_mean"] > 1.0 and rep["decode_requests_mean"] == 1.0 and not rep["ok"], rep
    assert {s[3] for s in ing._steps if s[3]} >= {1, 2} and {s[6] for s in ing._steps} <= {0, 1}
    # one stream alone is held to no such rule
    ing, submit = ingress_over(BlockBatcher(params, cfg))
    req = submit("rcheck0")(streams[0]["prompt_ids"], NEW_TOKENS)
    while len(req.out_tokens) < NEW_TOKENS:
        ing.cb.step()
    assert ing.bench_check([streams[0]], t_begin, "toy_blocks")["ok"]


@pytest.mark.parametrize("error, passes", [(0.5, True), (1.0, True), (1.5, False), (float("nan"), False)])
def test_a_mechanism_entry_over_its_tolerance_is_not_correct_and_a_references_verdict_is_ignored(
        toy, error, passes):
    ref, params, cfg = toy
    cb = BlockBatcher(params, cfg)
    reqs, _ = serve_together(cb, lambda ids, n: cb.submit(ids, max_new_tokens=n))
    streams = [{"prompt_ids": r.prompt_ids, "served": r.out_tokens, "request_id": r.request_id} for r in reqs]
    seen = []

    def mechanism_checks(batcher, given):
        seen.append((batcher, given))
        return [{"name": "fine", "error": 0.0, "tolerance": 0.0, "why": "exact", "ok": False},
                {"name": "state", "error": error, "tolerance": 1.0, "why": "a test's", "ok": not passes}]

    rep = check_serving(cb, streams, without(ref, mechanism_checks=mechanism_checks))
    assert seen == [(cb, streams)]
    # every other number passes; the verdict is the harness's, from error and tolerance alone
    assert rep["regret_max"] == 0.0 and rep["logit_max_abs_err"] < 1e-4 and rep["ok"] is passes, rep
    assert [set(m) for m in rep["mechanism"]] == [{"name", "error", "tolerance", "why"}] * 2
    assert rep["mechanism"][1]["tolerance"] == 1.0 and rep["mechanism"][1]["name"] == "state"
    # and a passing mechanism does not mend a regret over its bound
    wrong = [dict(s) for s in streams]
    wrong[0]["served"] = [(t + 1) % cfg.mask_id for t in streams[0]["served"]]
    assert not check_serving(cb, wrong, without(ref, mechanism_checks=lambda *a: []))["ok"]


@pytest.mark.parametrize("correct", [True, False])
def test_a_run_ends_both_its_outputs_with_the_numbers_compared(correct, monkeypatch, capsys):
    """`run.py` from its arguments to its two last lines, with the cluster and
    the measurement put aside: standard error ends with `correct` and every
    number of the check beside its limit, standard output with the result's
    line, `check` its last key."""
    check = {"logit_max_abs_err": 0.05, "logit_tolerance": 0.2, "ok": correct,
             "mechanism": [{"name": "state", "error": 0.3 if correct else 3.0, "tolerance": 1.0, "why": "a test's"}]}
    ctx = {"check": check, "device": dict(platform="tpu", kind="TPU v5 lite", count=1, memory_peak_bytes=5 << 30)}
    monkeypatch.setattr(cluster, "init_cluster", lambda chips, env=None: {"TPU": 1.0})
    monkeypatch.setattr(ca, "shutdown", lambda: None)
    monkeypatch.setattr(serve_driver, "measure", lambda cell, seed, seconds, trace, t_start: ctx)
    monkeypatch.setattr(serve_driver, "outcome", lambda c: {"correct": c["check"]["ok"], "attempted": 7, "failed": 0})
    monkeypatch.setattr(serve_driver, "end_to_end", lambda c: {"setup_s": 50.0, "serve_out_tok_s": 300.0})
    monkeypatch.setattr(serve_driver, "knee_stats", lambda c: {"rate": 1.0})
    monkeypatch.setattr(serve_driver, "dump", lambda c: {})
    assert bench_run.main(["--workload", "olmoe-closed6", "--seed", "2147483659", "--seconds", "1"]) == 0
    out, err = (text.strip().splitlines() for text in capsys.readouterr())
    line = json.loads(out[-1])
    assert list(line)[-1] == "check" and line["check"] == check and line["correct"] is correct
    assert set(line["metrics"]) == {"setup_s", "serve_out_tok_s"} and "knee" in line
    assert err[-1].startswith("[bench] ")
    assert json.loads(err[-1][len("[bench] "):]) == {"correct": correct, "check": check}
