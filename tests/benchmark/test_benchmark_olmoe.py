"""OLMoE's plain reference (references/olmoe.py, loaded as the harness loads
it) against the program at a small size on the CPU: the forward and the loss in
float32, prefill and then the batch decode through a tiny batcher held by the
serving check, the counts against hand counts, and `olmoe-closed6` rehearsed at
tiny widths through serve.run, proxy, router and replica."""

import copy
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import cluster_anywhere_tpu as ca
from benchmarks import run as bench_run
from benchmarks.harness import manifest, program_trace, serve_driver
from benchmarks.harness.reference import check_serving
from cluster_anywhere_tpu.llm.continuous import ContinuousBatcher
from cluster_anywhere_tpu.models.transformer import (
    TransformerConfig, cross_entropy_loss, forward, init_params,
)

CELL = "olmoe-closed6"
reference = manifest.load_reference("olmoe")
# 8 experts, 2 a token, q/k-norm on: the published block at a test's widths
TINY = dict(hidden_size=64, num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=4,
            head_dim=16, intermediate_size=48, vocab_size=512, num_experts=8, num_experts_per_tok=2)


def tiny_config(**over):
    cell = copy.deepcopy(manifest.load_cell(CELL))
    cell["config_file"]["config"].update(TINY, **over)
    return cell


def program(dtype, **over):
    cell = tiny_config(**over)
    fields = reference.program_config(cell["config_file"], vocab_size=TINY["vocab_size"],
                                      dtype=dtype, param_dtype=dtype)
    cfg = TransformerConfig(**fields)
    params = init_params(jax.random.key(3), cfg)
    # the norms' weights off 1, so a norm that is left out, or laid over the heads, shows
    blocks = params["blocks"]
    blocks["q_norm"] = blocks["q_norm"] * jnp.linspace(0.5, 1.5, blocks["q_norm"].shape[-1]).astype(dtype)
    blocks["k_norm"] = blocks["k_norm"] * jnp.linspace(1.4, 0.6, blocks["k_norm"].shape[-1]).astype(dtype)
    return cfg, params


def test_the_published_keys_build_the_published_block():
    cell = manifest.load_cell(CELL)
    cfg = TransformerConfig(**reference.program_config(cell["config_file"]))
    assert (cfg.d_model, cfg.n_layers, cfg.n_heads, cfg.n_kv_heads, cfg.d_head) == (2048, 10, 16, 16, 128)
    assert (cfg.n_experts, cfg.n_experts_per_tok, cfg.d_ff) == (64, 8, 1024)
    assert cfg.moe_gated and cfg.qk_norm and not cfg.moe_renormalize and cfg.rope_theta == 10000.0
    blocks = jax.eval_shape(lambda k: init_params(k, cfg), jax.random.key(0))["blocks"]
    assert blocks["w_gate"].shape == blocks["w_up"].shape == (10, 64, 2048, 1024)
    assert blocks["w_down"].shape == (10, 64, 1024, 2048) and blocks["router"].shape == (10, 2048, 64)
    assert blocks["q_norm"].shape == blocks["k_norm"].shape == (10, 2048)
    assert set(reference.SCOPES) == {"moe.router", "moe.dispatch", "moe.experts", "moe.combine"}
    # the four scopes nest under `ffn`, and the innermost names the operation
    op = "jit(_decode_step_rowpos)/while/body/closed_call/ffn/moe.experts/ragged_dot_general:"
    scopes, _ = program_trace.known_names({"cell": cell})
    assert program_trace.scope_of(op, scopes) == "moe.experts" and program_trace.scope_of(op) == "ffn"


@pytest.mark.parametrize("renormalize", [False, True], ids=["as-published", "norm_topk_prob"])
def test_reference_forward_and_loss_match_the_program_in_float32(renormalize):
    cfg, params = program(jnp.float32, norm_topk_prob=renormalize)
    assert cfg.moe_renormalize is renormalize
    ids = np.random.default_rng(0).integers(0, cfg.vocab_size, 41)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(forward(params, jnp.asarray(ids[None, :-1]), cfg)[0])
        want_loss = float(cross_entropy_loss(jnp.asarray(want[None]), jnp.asarray(ids[None, 1:])))
    got = np.asarray(reference.forward(params, ids[:-1], cfg))
    # float32 both sides: what is left is the order of summation
    assert np.max(np.abs(got - want)) < 2e-4
    assert reference.loss(params, ids, cfg) == pytest.approx(want_loss, abs=1e-4)
    # the reference is causal: a later token changes no earlier logit
    ids2 = ids.copy()
    ids2[30] = (ids2[30] + 1) % cfg.vocab_size
    got2 = np.asarray(reference.forward(params, ids2[:-1], cfg))
    assert np.array_equal(got[:30], got2[:30]) and not np.allclose(got[30:], got2[30:])
    # and it is this architecture's: the program without the norm on q and k, or the
    # reference with one expert a token, is another model
    with jax.default_matmul_precision("highest"):
        no_norm = forward(params, jnp.asarray(ids[None, :-1]), dataclasses.replace(cfg, qk_norm=False))[0]
    top1 = reference.forward(params, ids[:-1], dataclasses.replace(cfg, n_experts_per_tok=1))
    assert np.max(np.abs(np.asarray(no_norm) - got)) > 1e-2 and np.max(np.abs(np.asarray(top1) - got)) > 1e-2


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
def test_serving_check_holds_prefill_and_the_batch_decode_to_the_reference(dtype):
    cfg, params = program(dtype)
    cb = ContinuousBatcher(params, cfg, slots=4, t_max=128, prefill_buckets=(32, 64, 96))
    rng = np.random.default_rng(1)
    reqs = [cb.submit(rng.integers(0, cfg.vocab_size, n), max_new_tokens=12) for n in (20, 40, 70)]
    cb.pump()
    assert cb.stats["decode_steps"] == 11  # all three in every step, the fourth slot empty
    assert cb.stats["moe_assignments"] == (20 + 40 + 70) * 2 + 11 * 3 * 2
    streams = [{"prompt_ids": r.prompt_ids.tolist(), "served": list(r.out_tokens)} for r in reqs]
    rep = check_serving(cb, streams, reference)
    assert rep["streams"] == 3 and rep["positions"] == 36 and rep["logit_max_abs_err"] <= reference.LOGIT_TOL, rep
    if dtype == jnp.float32:
        # float32 both sides: every served token is the reference's own best
        assert rep["ok"] and rep["logit_max_abs_err"] < 1e-3 and rep["regret_max"] < 1e-3, rep
        assert rep["agree_share"] > 0.9, rep
    # a token the reference ranks low is caught
    ref = np.asarray(reference.forward(
        params, np.asarray(streams[1]["prompt_ids"] + streams[1]["served"][:5]), cfg))[-1]
    wrong = [dict(s) for s in streams]
    wrong[1]["served"] = streams[1]["served"][:5] + [int(np.argmin(ref))] + streams[1]["served"][6:]
    bad = check_serving(cb, wrong, reference)
    assert not bad["ok"] and bad["regret_max"] > reference.REGRET_MAX_TOL


def _float8_experts(routed_ffn):
    """The program's expert layer with the layer's experts rounded to float8 e4m3
    (3 bits of mantissa), the experts alone."""
    def routed(x, router, experts, layer=0, **kw):
        rounded = {n: w[layer][None].astype(jnp.float8_e4m3fn).astype(w.dtype) for n, w in experts.items()}
        return routed_ffn(x, router, rounded, 0, **kw)
    return routed


def _bf16_softmax(routed_ffn):
    """The program's expert layer with the router's softmax computed in bf16."""
    def routed(*args, **kw):
        with pytest.MonkeyPatch.context() as m:
            softmax = jax.nn.softmax
            m.setattr(jax.nn, "softmax", lambda v, axis=-1: softmax(v.astype(jnp.bfloat16), axis=axis))
            return routed_ffn(*args, **kw)
    return routed


def _served_together(cfg, params, lens=(11, 40, 70), new_tokens=9):
    """A batcher alone and the streams it served together, as `bench_check`
    hands them to `check_serving`."""
    cb = ContinuousBatcher(params, cfg, slots=4, t_max=128, prefill_buckets=(32, 64, 96))
    rng = np.random.default_rng(5)
    reqs = [cb.submit(rng.integers(0, cfg.vocab_size, n), max_new_tokens=new_tokens) for n in lens]
    cb.pump()
    return cb, [{"prompt_ids": r.prompt_ids.tolist(), "served": list(r.out_tokens),
                 "request_id": r.request_id} for r in reqs]


@pytest.mark.parametrize("variant, fails", [
    (None, set()), (_float8_experts, {"moe_experts_rel_err"}), (_bf16_softmax, {"moe_router_other_set"}),
], ids=["program", "float8-experts", "bf16-softmax"])
def test_the_expert_layer_is_held_by_itself(variant, fails, monkeypatch):
    """The serving check at a test's widths with float32 weights: the program
    passes, and each of the two lower precisions that the logits cannot see on
    the chip, planted in `routed_ffn` once the streams are served, comes out as
    not correct by `check_serving`'s own verdict: it fails the mechanism's
    number that is its own while the three numbers on the logits pass."""
    from cluster_anywhere_tpu.parallel import moe

    cfg, params = program(jnp.float32, num_experts=32, num_experts_per_tok=4)
    cb, streams = _served_together(cfg, params)
    if variant is not None:
        monkeypatch.setattr(moe, "routed_ffn", variant(moe.routed_ffn))
    rep = check_serving(cb, streams, reference)
    got = {m["name"]: m for m in rep["mechanism"]}
    assert list(got) == ["moe_router_other_set", "moe_experts_rel_err"]
    assert got["moe_router_other_set"]["tolerance"] == reference.MOE_ROUTER_SET_TOL
    assert got["moe_experts_rel_err"]["tolerance"] == reference.MOE_EXPERTS_ERR_TOL
    # every position of every stream at every layer: (11 + 40 + 70 + 3 * 8) rows x 2 layers
    assert "of 290 in which" in got["moe_router_other_set"]["why"]
    assert {n for n, m in got.items() if not m["error"] <= m["tolerance"]} == fails, got
    # the streams were served by the program as it is, in float32: the logits see nothing
    assert rep["logit_max_abs_err"] < 1e-3 and rep["regret_max"] < 1e-3 and rep["regret_mean"] < 1e-3, rep
    assert rep["ok"] is (not fails), rep
    assert not reference._given  # what `chosen_logits` kept, `mechanism_checks` took
    if variant is None:
        # float32 on both sides: the same sets in every row, the results to rounding
        assert got["moe_router_other_set"]["error"] == 0.0 and got["moe_experts_rel_err"]["error"] < 1e-5
        # asked by itself it makes the pass that `chosen_logits` did not keep for it
        alone = reference.mechanism_checks(cb, streams)
        assert [m["error"] for m in alone] == [m["error"] for m in rep["mechanism"]]
        monkeypatch.setattr(reference, "MOE_EXPERTS_ERR_TOL", rep["mechanism"][1]["error"] / 2)
        assert not check_serving(cb, streams, reference)["ok"]


def test_the_mechanism_enters_the_expert_layer_as_the_served_programs_do(monkeypatch):
    """The compiled prefill and decode programs hand out no layer's result, so
    `mechanism_checks` calls the block's entry to its expert layer,
    `transformer._moe`, itself.  What holds it to the timed path: a batcher
    that serves the check streams traces `_moe` with the shapes, the types and
    the unsliced stack that the check gives it, and no others; each of those
    calls reaches `routed_ffn`."""
    from cluster_anywhere_tpu.models import transformer
    from cluster_anywhere_tpu.parallel import moe

    # a configuration no other test of this process has traced: the calls are seen at trace time
    cfg, params = program(jnp.bfloat16, num_experts=16, num_experts_per_tok=3, intermediate_size=40)
    calls, reached = [], []
    inner_moe, inner_routed = transformer._moe, moe.routed_ffn

    def seen_moe(bp, y, cfg_, live=None, experts=None):
        stack, layer = experts
        calls.append((y.shape, str(y.dtype), live.shape, str(live.dtype), isinstance(layer, jax.core.Tracer),
                      tuple(sorted((n, w.shape) for n, w in stack.items() if "w_in" not in stack))))
        return inner_moe(bp, y, cfg_, live, experts)

    def seen_routed(x, *a, **kw):
        reached.append(x.shape)
        return inner_routed(x, *a, **kw)

    monkeypatch.setattr(transformer, "_moe", seen_moe)
    monkeypatch.setattr(moe, "routed_ffn", seen_routed)
    cb, streams = _served_together(cfg, params)
    served, served_reached = set(calls), set(reached)
    # three prompts alone in their buckets, and every decode step over the four slots
    stack = tuple(sorted((n, params["blocks"][n].shape) for n in moe.EXPERT_MATRICES if n in params["blocks"]))
    assert served == {((1, b, 64), "bfloat16", (1, b), "bool", True, stack) for b in (32, 64, 96)} | {
        ((4, 1, 64), "bfloat16", (4, 1), "bool", True, stack)}
    assert served_reached == {(32, 64), (64, 64), (96, 64), (4, 64)}
    del calls[:], reached[:]
    numbers = reference.mechanism_checks(cb, streams)
    assert all(m["error"] <= m["tolerance"] for m in numbers), numbers
    # the model's experts as the programs hand them over; the probe experts are the check's own
    assert {c for c in calls if c[-1]} == served and {c[:4] for c in calls} == {c[:4] for c in served}
    assert set(reached) == served_reached
    prefills, decode = reference.program_shapes(cb, streams)
    assert [(t, pad) for _, t, pad in prefills] == [(11, 21), (40, 24), (70, 26)]
    # a step holds the three streams' rows in the first slots; the fourth slot is not live
    assert decode.shape == (8, 4) and decode[0].tolist() == [11, 19 + 40, 19 + 48 + 70, 145]
    assert (decode[:, 3] == 145).all() and (np.diff(decode[:, :3], axis=0) == 1).all()


def test_counts_against_hand_counts():
    c = dict(hidden_size=8, num_attention_heads=2, num_key_value_heads=2, head_dim=4, intermediate_size=16,
             num_hidden_layers=3, vocab_size=32, num_experts=4, num_experts_per_tok=2)
    # a layer: wq, wk, wv, wo 8*8 each, two norms of 8 over q and k, the router 8*4,
    # 4 experts of three 8*16 matrices, the block's two norms of 8
    attention, expert = 4 * 64 + 16, 3 * 128
    per_layer = attention + 32 + 4 * expert + 16
    assert reference.param_count(c) == 3 * per_layer + 2 * 32 * 8 + 8
    assert reference.expert_bytes(c) == 2 * expert and reference.expert_bytes(c, 4) == 4 * expert
    # forward, one sequence of 5: 2 flops a weight a token over the projections, the
    # router and 2 experts; attention 4*t*t*d*h; the head
    weights = 4 * 64 + 32 + 2 * expert
    fwd = 5 * 2 * weights * 3 + 4 * 5 * 5 * 4 * 2 * 3 + 5 * 2 * 8 * 32
    assert reference.train_flops_per_step(c, batch=1, seq=5) == 3 * fwd
    assert reference.train_flops_per_step(c, batch=4, seq=5) == 4 * 3 * fwd
    # decode: one row touches its 2 experts, many rows all 4, in between X(1-(1-k/X)^rows)
    assert reference.experts_touched(c, 1) == pytest.approx(2.0)
    assert reference.experts_touched(c, 2) == pytest.approx(4 * (1 - 0.25))
    assert reference.experts_touched(c, 64) == pytest.approx(4.0, abs=1e-6)
    outside = 3 * (attention + 32 + 16) + 32 * 8 + 8 + 2 * 8  # all but the embedding table, its 2 rows
    cache = 2 * 3 * 2 * 10 * 2 * 4
    assert reference.decode_step_bytes(c, slots=2, t_max=10) == int(2 * (outside + 3 * 3.0 * expert + cache))
    # the published model: 6.9 B parameters at 16 layers, 1.3 B of them met by a token
    pub = manifest.load_cell(CELL)["config_file"]["published"]
    pub = dict(pub, head_dim=128)
    assert 6.9e9 < reference.param_count(pub) < 6.95e9
    active = reference.param_count(pub) - 16 * (64 - 8) * reference.expert_params(pub)
    assert 1.25e9 < active < 1.35e9
    assert 34 < reference.experts_touched(pub, 6) < 36 and 63 < reference.experts_touched(pub, 32) < 64


def test_the_experts_reader_knows_the_kernel_by_name_and_counts_the_touched_bytes():
    cell = manifest.load_cell(CELL)
    read = manifest.load_reader("experts_kernel")
    span = lambda start, **args: [1, float(start), 40e6, "llm.step", args]
    # the compiler's grouped-matmul kernel carries no op_name, so no scope; the
    # activation between two of them is a fusion under `moe.experts`
    kernel = lambda start, dur, n: [float(start), float(dur),
                                    f"%ragged-dot-none{n} = bf16[256,1024] custom-call()", ""]
    other = lambda start, dur, scope: [float(start), float(dur), "%fusion.7 = bf16[256,1024] fusion()", scope]
    # two steps that touched 30 and 40 experts a layer: 70 x 10 layers x 12.58 MB = 8.8 GB;
    # 20 ms in the experts at 819 GB/s could have read 16.4 GB
    events = {"spans": [span(0, live=6, moe_rows=6, moe_experts_touched=30.0),
                        span(50e6, live=6, moe_rows=6, moe_experts_touched=40.0)],
              "ops": {"/device:TPU:0": [kernel(1e6, 7e6, ""), other(9e6, 1e6, "moe.experts"),
                                        kernel(51e6, 12e6, ".2"), other(70e6, 5e6, "attn.core"),
                                        other(80e6, 15e6, "")]}}
    ctx = {"cell": cell, "program_trace": events, "device": {"kind": "TPU v5 lite"}}
    want = 100 * 70 * 10 * 3 * 2048 * 1024 * 2 / (20e-3 * 819e9)
    assert read(ctx, share_of="hbm_roofline") == pytest.approx(want) and 50 < want < 60
    assert read(ctx, share_of="busy") == pytest.approx(100 * 20 / 40)
    with pytest.raises(ValueError):
        read(ctx, share_of="flops")
    # the two metrics of the cell go through it, and the manifest's reader finds them
    got = manifest.read_layer_metrics(CELL, dict(ctx, replica={"steps": [], "admits": [], "first": {}},
                                                 records=[], t_open=0.0, seconds=1.0))
    assert got["experts_hbm_share.moe"]["value"] == pytest.approx(want)
    assert got["moe_experts_share.moe"]["value"] == pytest.approx(50.0)
    assert got["experts_touched_mean.moe"]["value"] == pytest.approx(35.0)
    # a program without the count (the parent): no roofline; without the kernel, or a run
    # that was not traced: nothing at all
    older = copy.deepcopy(events)
    older["spans"] = [[1, 0.0, 40e6, "llm.step", {"live": 6}]]
    assert read(dict(ctx, program_trace=older), share_of="hbm_roofline") is None
    dense = copy.deepcopy(events)
    dense["ops"] = {"/device:TPU:0": [other(0, 5e6, "ffn"), other(9e6, 1e6, "attn.core")]}
    assert read(dict(ctx, program_trace=dense), share_of="busy") is None
    assert read(dict(ctx, program_trace=dense), share_of="hbm_roofline") is None
    assert read(dict(ctx, program_trace=None), share_of="busy") is None
    # a dense cell's reference names no such kernel
    assert read(dict(ctx, cell=manifest.load_cell("chat-closed6")), share_of="busy") is None


def test_serve_rehearsal_of_olmoe_closed6():
    """The cell at tiny widths through the program's normal path on the CPU
    backend (a TPU resource that is only a number), as test_serve_rehearsal
    does for the two dense cells."""
    cell = tiny_config()
    cell.update(callers=3)
    cell["traffic_file"].update(
        ramp_s=0.5, drain_s=60.0, warmup_prompt_lens=[20, 70],
        prompt_len=dict(dist="lognormal", median=24, sigma=0.5, min=8, max=80),
        output_len=dict(dist="lognormal", median=6, sigma=0.3, min=4, max=12),
        check=dict(stream_prompt_lens=[12, 30, 70], stream_new_tokens=8, repeat_prompt_len=40,
                   repeat_new_tokens=5),
        deployment=dict(slots=4, max_prompt_len=96, max_new_tokens=16, prefix_cache_entries=0),
    )
    if ca.is_initialized():
        ca.shutdown()
    ca.init(num_cpus=4, num_tpus=1)
    try:
        ctx = serve_driver.measure(cell, seed=3_000_000_019, seconds=3.0, trace=False,
                                   t_start=time.monotonic())
    finally:
        ca.shutdown()
    out = serve_driver.outcome(ctx)
    assert out["failed"] == 0 and out["attempted"] >= 3, out
    check = ctx["check"]
    assert check["streams"] == 3 and check["positions"] == 24 and check["decode_batch_mean"] > 1.0, check
    assert check["ok"] and check["repeat_identical"] and out["correct"], check
    assert check["logit_tolerance"] == reference.LOGIT_TOL and check["decode_requests_mean"] > 1.0
    # the expert layer by itself, over every position of the three streams in both layers
    mechanism = {m["name"]: m for m in check["mechanism"]}
    assert set(mechanism) == {"moe_router_other_set", "moe_experts_rel_err"}
    assert all(m["error"] <= m["tolerance"] for m in mechanism.values()), mechanism
    assert f"of {2 * (12 + 30 + 70 + 3 * 7)} in which" in mechanism["moe_router_other_set"]["why"]
    # the batcher counted the routed assignments: the replica ran the expert path
    assert ctx["replica"]["stats"]["moe_assignments"] > 0
    layer = manifest.read_layer_metrics(CELL, ctx)
    assert layer["decode_batch_mean.closed"]["value"] >= 1.0
    assert {n + ".closed" for n in ("gen_late_p99_ms", "front_overhead_p50_ms", "admit_ms_mean",
                                 "decode_step_ms_p50", "gap_p99_s", "ttft_p50_s")} <= set(layer)
    # no trace: the readers of the trace return nothing
    assert not {"device_idle.closed", "attn_share.closed", "moe_experts_share.moe", "experts_hbm_share.moe",
                "experts_touched_mean.moe", "moe_dispatch_share.moe"} & set(layer)
    with pytest.raises(RuntimeError, match="need 1 tpu"):
        bench_run.result_line(ctx["cell"], serve_driver, ctx, trace=False)
    ctx["device"].update(platform="tpu", kind="TPU v5 lite", count=1)
    line = bench_run.result_line(ctx["cell"], serve_driver, ctx, trace=False)
    assert set(line["metrics"]) == {"setup_s", "serve_out_tok_s"} and line["correct"]
