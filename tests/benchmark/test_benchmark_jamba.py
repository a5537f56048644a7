"""Jamba's plain reference (references/jamba.py, loaded as the harness loads it)
against the program at a small size on the CPU: the published keys build the
published stack, the forward and the loss in float32, prefill and then the
batch decode through a tiny batcher held by the serving check, the counts
against hand counts, the mixers' roofline reader on a made trace, and
`jamba-closed6` rehearsed at tiny widths through serve.run, proxy, router and
replica."""

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
from cluster_anywhere_tpu.models import generate
from cluster_anywhere_tpu.models.transformer import (
    TransformerConfig, cross_entropy_loss, forward, init_params, make_loss_fn,
)

CELL = "jamba-closed6"
reference = manifest.load_reference("jamba")
# six layers with attention at 2 of a period of 3: runs of two state-space layers
# and of one attention layer, twice; one cached head, no rotary, a tied head
TINY = dict(hidden_size=64, num_hidden_layers=6, num_attention_heads=4, num_key_value_heads=1,
            head_dim=16, intermediate_size=96, vocab_size=512, attn_layer_period=3,
            attn_layer_offset=2, mamba_d_state=8, mamba_dt_rank=8,
            layers_block_type=["mamba", "mamba", "attention"] * 2)


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
    # the three inner norms' weights off 1 and the convolution's bias off 0, so that
    # a norm or a bias that is left out, or laid over the wrong part, shows
    ssm = params["ssm_blocks"]
    for name, lo, hi in (("dt_norm", 0.6, 1.4), ("b_norm", 1.5, 0.7), ("c_norm", 0.8, 1.3)):
        ssm[name] = ssm[name] * jnp.linspace(lo, hi, ssm[name].shape[-1]).astype(dtype)
    ssm["conv_b"] = ssm["conv_b"] + jnp.linspace(-0.3, 0.3, ssm["conv_b"].shape[-1]).astype(dtype)
    return cfg, params


def test_the_published_keys_build_the_published_stack():
    cell = manifest.load_cell(CELL)
    doc = cell["config_file"]
    order = ["attention" if i in (7, 21) else "mamba" for i in range(28)]
    assert doc["reduced"] == {} and doc["config"] == dict(doc["published"], head_dim=128, layers_block_type=order)
    assert set(doc["assumed"]) == {"head_dim", "layers_block_type"}
    assert all(doc[k] == v for k, v in doc["published"].items())  # the catalog's keys at the top level
    cfg = TransformerConfig(**reference.program_config(doc, vocab_size=doc["config"]["vocab_size"],
                                                       param_dtype=jnp.bfloat16))
    assert (cfg.d_model, cfg.n_layers, cfg.n_heads, cfg.n_kv_heads, cfg.d_head, cfg.d_ff) == (
        2560, 28, 20, 1, 128, 8192)
    assert (cfg.d_inner, cfg.ssm_d_state, cfg.ssm_d_conv, cfg.ssm_dt_rank) == (5120, 16, 4, 160)
    assert cfg.ssm_conv_bias and cfg.tie_embeddings and not cfg.rotary and not cfg.n_experts
    kinds = cfg.layer_kinds
    assert [i for i, k in enumerate(kinds) if k == "attn"] == [7, 21] and kinds.count("ssm") == 26
    shapes = jax.eval_shape(lambda k: init_params(k, cfg), jax.random.key(0))
    assert sum(x.size for x in jax.tree_util.tree_leaves(shapes)) == 3_029_337_472
    assert "lm_head" not in shapes and shapes["embed"].shape == (65536, 2560)
    assert shapes["blocks"]["wk"].shape == (2, 2560, 128)  # attention's parameters for 2 layers
    assert shapes["ssm_blocks"]["ssm_in"].shape == (26, 2560, 10240)  # the mixer's for 26
    assert shapes["ssm_blocks"]["ssm_x"].shape == (26, 5120, 192)
    assert not {"wq", "wk", "wv", "wo"} & set(shapes["ssm_blocks"])
    # a slot's rows: keys and values for 2 layers, the recurrent state for 26
    cache = jax.eval_shape(lambda: generate.init_cache(cfg, 32, 768))
    assert {k: (v.shape, v.dtype) for k, v in cache.items()} == {
        "k": ((2, 32, 768, 1, 128), jnp.bfloat16), "v": ((2, 32, 768, 1, 128), jnp.bfloat16),
        "conv": ((26, 32, 3, 5120), jnp.bfloat16), "h": ((26, 32, 5120, 16), jnp.float32)}
    assert generate.recurrent_state_bytes(cache) == 32 * reference.slot_state_bytes(doc["config"])
    assert set(reference.SCOPES) == {"ssm.in", "ssm.conv", "ssm.scan", "ssm.state", "ssm.out"}
    # the innermost known scope names the operation: the loop's own `ssm.state`
    # takes only what the body names nothing for
    scopes, _ = program_trace.known_names({"cell": cell})
    inner = "jit(_decode_step_rowpos)/ssm.state/while/body/closed_call/ssm.scan/mul"
    assert program_trace.scope_of(inner, scopes) == "ssm.scan"
    assert program_trace.scope_of("jit(f)/ssm.state/while/body/closed_call/ffn/dot_general", scopes) == "ffn"
    assert program_trace.scope_of("jit(f)/ssm.state/while/body/dynamic_update_slice", scopes) == "ssm.state"
    # another Jamba (experts) is another architecture, and says so
    with pytest.raises(ValueError, match="num_experts"):
        reference.program_config({"config": dict(doc["config"], num_experts=16)})
    with pytest.raises(ValueError, match="layers_block_type"):
        reference.program_config({"config": dict(doc["config"], attn_layer_offset=3)})


@pytest.mark.parametrize("conv_bias", [True, False], ids=["as-published", "no-conv-bias"])
def test_reference_forward_and_loss_match_the_program_in_float32(conv_bias):
    cfg, params = program(jnp.float32)
    if not conv_bias:
        cfg = dataclasses.replace(cfg, ssm_conv_bias=False)
        params = dict(params, ssm_blocks={k: v for k, v in params["ssm_blocks"].items() if k != "conv_b"})
    ids = np.random.default_rng(0).integers(0, cfg.vocab_size, 41)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(forward(params, jnp.asarray(ids[None, :-1]), cfg)[0])
        want_loss = float(cross_entropy_loss(jnp.asarray(want[None]), jnp.asarray(ids[None, 1:])))
        assert float(make_loss_fn(cfg)(params, {"ids": jnp.asarray(ids[None])})) == pytest.approx(want_loss, abs=1e-6)
    got = np.asarray(reference.forward(params, ids[:-1], cfg))
    # float32 both sides: what is left is the order of summation (a chunked scan
    # against one position after the other)
    assert np.max(np.abs(got - want)) < 2e-4
    assert reference.loss(params, ids, cfg) == pytest.approx(want_loss, abs=1e-4)
    # the reference is causal: a later token changes no earlier logit
    ids2 = ids.copy()
    ids2[30] = (ids2[30] + 1) % cfg.vocab_size
    got2 = np.asarray(reference.forward(params, ids2[:-1], cfg))
    assert np.array_equal(got[:30], got2[:30]) and not np.allclose(got[30:], got2[30:])
    # and it is this architecture's: the program with rotary attention, with the three
    # inner norms' weights back at 1, or with another layer order is another model
    with jax.default_matmul_precision("highest"):
        run = lambda p, c: np.asarray(forward(p, jnp.asarray(ids[None, :-1]), c)[0])
        rotary = run(params, dataclasses.replace(cfg, rotary=True))
        ones = jax.tree_util.tree_map(jnp.ones_like, {k: params["ssm_blocks"][k] for k in ("dt_norm", "b_norm", "c_norm")})
        no_norm = run(dict(params, ssm_blocks={**params["ssm_blocks"], **ones}), cfg)
        moved = run(params, dataclasses.replace(cfg, attn_layer_offset=0))  # a s s a s s: the same counts
    for other in (rotary, no_norm, moved):
        assert np.max(np.abs(other - got)) > 1e-2


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
def test_serving_check_holds_prefill_and_the_batch_decode_to_the_reference(dtype):
    cfg, params = program(dtype)
    cb = ContinuousBatcher(params, cfg, slots=4, t_max=128, prefill_buckets=(32, 64, 96))
    rng = np.random.default_rng(1)
    reqs = [cb.submit(rng.integers(0, cfg.vocab_size, n), max_new_tokens=12) for n in (20, 40, 70)]
    cb.pump()
    assert cb.stats["decode_steps"] == 11  # all three in every step, the fourth slot empty
    slot = 4 * (3 * 128 * dtype.dtype.itemsize + 128 * 8 * 4)  # four layers' window and float32 h
    assert cb.stats["ssm_state_bytes"] == 3 * slot + 11 * 2 * 4 * slot
    assert cb.cache["h"].dtype == jnp.float32 and cb.cache["h"].shape == (4, 4, 128, 8)
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


def test_counts_against_hand_counts():
    c = dict(hidden_size=8, num_attention_heads=2, num_key_value_heads=1, head_dim=4, intermediate_size=16,
             num_hidden_layers=6, vocab_size=32, attn_layer_period=3, attn_layer_offset=2,
             mamba_d_state=2, mamba_d_conv=4, mamba_expand=2, mamba_dt_rank=3, mamba_conv_bias=True,
             tie_word_embeddings=True)
    # a mixer, inner width 16: W_in 8*32, the convolution 16*4 and its bias 16, W_x 16*(3+2+2),
    # W_dt 3*16 and its bias 16, A_log 16*2, D 16, the three norms 3+2+2, W_out 16*8
    mixer = 256 + 64 + 16 + 112 + 48 + 16 + 32 + 16 + 7 + 128
    attention = 8 * 8 + 2 * 8 * 4 + 8 * 8  # wq, wk and wv to one head, wo
    mlp = 3 * 8 * 16
    assert reference.mixer_params(c) == mixer
    # four state-space and two attention layers, each with its MLP and two norms; the
    # embedding, which is the head; the final norm
    assert reference.param_count(c) == 4 * mixer + 2 * attention + 6 * (mlp + 16) + 32 * 8 + 8
    assert reference.param_count(dict(c, tie_word_embeddings=False)) == reference.param_count(c) + 32 * 8
    # a slot: h [16, 2] in float32 and the window [3, 16] in bf16, over four layers
    slot = 4 * (16 * 2 * 4 + 3 * 16 * 2)
    assert reference.slot_state_bytes(c) == slot
    assert reference.mixer_step_bytes(c, slots=5) == 4 * mixer * 2 + 2 * 5 * slot
    assert reference.mixer_step_bytes(c, slots=5, bytes_per=4) == 4 * mixer * 4 + 2 * 5 * slot
    # a decode step: every weight once, the state of every slot twice, the two
    # attention layers' keys and values over the whole cache
    cache = 2 * 2 * 5 * 10 * 1 * 4
    weights = reference.param_count(c)
    assert reference.decode_step_bytes(c, slots=5, t_max=10) == 2 * (weights + cache) + 2 * 5 * slot
    # forward, one sequence of 5: 2 flops a weight a token over the matrices, the
    # convolution's 4 taps, 9 a channel and state in the recurrence, attention's square
    mixer_matmul = 256 + 112 + 48 + 128
    per_token = 2 * attention + 4 * (mixer_matmul + 64) + 6 * mlp + 8 * 32
    fwd = 5 * 2 * per_token + 4 * 5 * 5 * 4 * 2 * 2 + 5 * 9 * 16 * 2 * 4
    assert reference.train_flops_per_step(c, batch=1, seq=5) == 3 * fwd
    assert reference.train_flops_per_step(c, batch=4, seq=5) == 4 * 3 * fwd
    # the published model: ISSUE 31's counts
    pub = manifest.load_cell(CELL)["config_file"]["config"]
    assert reference.mixer_params(pub) == 41_241_792 and reference.param_count(pub) == 3_029_337_472
    assert reference.slot_state_bytes(pub) == 26 * 358_400
    assert reference.mixer_step_bytes(pub, 32) == 26 * 41_241_792 * 2 + 2 * 32 * 26 * 358_400
    # the mixers are 35% of the weights a step reads and, with the state, about 41% of its bytes
    step = reference.decode_step_bytes(pub, 32, 768)
    assert 0.40 < reference.mixer_step_bytes(pub, 32) / step < 0.42 and 6.6e9 < step < 6.8e9


def test_the_mixer_reader_counts_the_steps_bytes_over_the_time_under_the_scopes():
    cell = manifest.load_cell(CELL)
    read = manifest.load_reader("ssm_mixer")
    span = lambda start, **args: [1, float(start), 14e6, "llm.step", args]
    op = lambda start, dur, scope: [float(start), float(dur), "%fusion.7 = bf16[32,5120] fusion()", scope]
    # two steps; 8 ms under the five scopes between them, 6 ms elsewhere
    events = {"spans": [span(0, live=6, ssm_state_bytes=596377600), span(15e6, live=6, ssm_state_bytes=596377600)],
              "ops": {"/device:TPU:0": [op(1e6, 2e6, "ssm.in"), op(3e6, 1e6, "ssm.scan"), op(4e6, 1e6, "ssm.state"),
                                        op(5e6, 3e6, "ffn"), op(16e6, 2e6, "ssm.out"), op(18e6, 1e6, "ssm.conv"),
                                        op(19e6, 1e6, "ssm.state"), op(20e6, 3e6, "")]}}
    ctx = {"cell": cell, "program_trace": events, "device": {"kind": "TPU v5 lite"}}
    moved = 2 * (26 * 41_241_792 * 2 + 2 * 32 * 26 * 358_400)
    want = 100 * moved / (8e-3 * 819e9)
    assert read(ctx) == pytest.approx(want) and 80 < want < 90
    got = manifest.read_layer_metrics(CELL, dict(ctx, replica={"steps": [], "admits": [], "first": {}},
                                                 records=[], t_open=0.0, seconds=1.0))
    assert got["ssm_hbm_share.ssm"]["value"] == pytest.approx(want)
    assert got["ssm_state_bytes.ssm"] == {"value": 596377600.0, "unit": "bytes"}
    assert got["ssm_proj_share.ssm"]["value"] == pytest.approx(100 * 4 / 14)
    assert got["ssm_scan_share.ssm"]["value"] == pytest.approx(100 * 4 / 14)
    assert got["ffn_share.ssm"]["value"] == pytest.approx(100 * 3 / 14)
    # a program without the count or the scopes (the parent), a run that was not traced,
    # and a cell of another architecture: nothing
    older = copy.deepcopy(events)
    older["spans"] = [[1, 0.0, 14e6, "llm.step", {"live": 6}]]
    assert read(dict(ctx, program_trace=older)) is None
    unscoped = copy.deepcopy(events)
    unscoped["ops"] = {"/device:TPU:0": [op(0, 5e6, "ffn"), op(9e6, 1e6, "attn.core")]}
    assert read(dict(ctx, program_trace=unscoped)) is None
    assert read(dict(ctx, program_trace=None)) is None
    assert read(dict(ctx, cell=manifest.load_cell("chat-closed6"))) is None
    # the cell reads what chat-closed6 reads through the families `closed` and `causal`, the
    # two of `attn`, and the five that were its own through `ffn_dense` and `ssm`, which the
    # later hybrids join from their own files (PR 54)
    names = {m["name"] for m in manifest.layer_metrics_for(CELL)}
    closed = {m["name"] for m in manifest.layer_metrics_for("chat-closed6") if m["name"].endswith(".closed")}
    assert len(closed) >= 16 and closed <= names
    assert names - closed >= {
        "attn_share.closed", "cache_share.closed", "ffn_share.ssm", "ssm_proj_share.ssm",
        "ssm_scan_share.ssm", "ssm_state_bytes.ssm", "ssm_hbm_share.ssm", "hbm_peak_gb"}
    listed = {m["name"]: m for m in manifest.load_manifest()["per_layer"]}
    assert all(listed[n]["workloads"][0] == CELL for n in names if n.endswith(".ssm"))
    assert listed["ffn_share.ssm"]["workloads"] == [CELL, "phi4flash-reason-closed8"]
    assert listed["ssm_hbm_share.ssm"]["workloads"] == [CELL, "phi4flash-reason-closed8", "nemotron3nano-reason-closed8"]
    assert all(CELL in listed[n]["workloads"] for n in names - {"hbm_peak_gb"})


def test_serve_rehearsal_of_jamba_closed6():
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
    assert check["logit_tolerance"] == reference.LOGIT_TOL
    # the batcher counted the recurrent state it moved: the replica ran the state-space layers
    assert ctx["replica"]["stats"]["ssm_state_bytes"] > 0
    layer = manifest.read_layer_metrics(CELL, ctx)
    assert layer["decode_batch_mean.closed"]["value"] >= 1.0
    assert {n + ".closed" for n in ("gen_late_p99_ms", "front_overhead_p50_ms", "admit_ms_mean",
                                 "decode_step_ms_p50", "gap_p99_s", "ttft_p50_s")} <= set(layer)
    # no trace: the readers of the trace return nothing
    assert not {"device_idle.closed", "ssm_scan_share.ssm", "ssm_hbm_share.ssm", "ssm_state_bytes.ssm",
                "ffn_share.ssm"} & set(layer)
    with pytest.raises(RuntimeError, match="need 1 tpu"):
        bench_run.result_line(ctx["cell"], serve_driver, ctx, trace=False)
    ctx["device"].update(platform="tpu", kind="TPU v5 lite", count=1)
    line = bench_run.result_line(ctx["cell"], serve_driver, ctx, trace=False)
    assert set(line["metrics"]) == {"setup_s", "serve_out_tok_s"} and line["correct"]
