"""`kimi-linear-48b-a3b-ep16-serve1` and its cell `kimilinear-reason-closed8` (ISSUE 60): the configuration's
file against the catalog row, the reference's interface and counts against hand arithmetic, the cell's files
through the manifest, the serving check at a test's widths (the program, and each planted fault by the numbers
that are its own), and the new reader against hand counts."""

import copy
import importlib.util
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import cluster_anywhere_tpu as ca
from benchmarks.harness import manifest, program_trace, serve_driver
from benchmarks.harness.reference import check_serving
from cluster_anywhere_tpu.llm.continuous import ContinuousBatcher
from cluster_anywhere_tpu.models import generate
from cluster_anywhere_tpu.models.transformer import TransformerConfig, init_params

CELL = "kimilinear-reason-closed8"
CONFIG = "kimi-linear-48b-a3b-ep16-serve1"
reference = manifest.load_reference("kimi_linear")
# the catalog row's `config` (model-configs guide, architectures.jsonl, Kimi-Linear-48B-A3B-Instruct)
CATALOG = {
    "first_k_dense_replace": 1, "head_dim": 72, "hidden_act": "silu", "hidden_size": 2304, "intermediate_size": 9216,
    "kv_lora_rank": 512,
    "linear_attn_config": {"full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "head_dim": 128,
                           "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19, 21, 22, 23, 25, 26],
                           "num_heads": 32, "short_conv_kernel_size": 4},
    "mla_use_nope": True, "model_max_length": 1048576, "model_type": "kimi_linear", "moe_intermediate_size": 1024,
    "moe_layer_freq": 1, "moe_renormalize": True, "moe_router_activation_func": "sigmoid", "num_attention_heads": 32,
    "num_expert_group": 1, "num_experts": 256, "num_experts_per_token": 8, "num_hidden_layers": 27,
    "num_key_value_heads": 32, "num_nextn_predict_layers": 0, "num_shared_experts": 1, "q_lora_rank": None,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
    "routed_scaling_factor": 2.446, "tie_word_embeddings": False, "topk_group": 1, "use_grouped_topk": True,
    "v_head_dim": 128, "vocab_size": 163840}
# the faults are the chip's controls' own (scripts/kimi_controls.py, which `--tiny` rehearses), and its widths
_spec = importlib.util.spec_from_file_location(
    "kimi_controls", os.path.join(os.path.dirname(manifest.BENCH_DIR), "scripts", "kimi_controls.py"))
controls = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(controls)
TINY = controls.TINY


def tiny_config(**over):
    cell = copy.deepcopy(manifest.load_cell(CELL))
    cell["config_file"]["config"].update(TINY, **over)
    return cell


def program(dtype, **over):
    cell = tiny_config(**over)
    fields = reference.program_config(cell["config_file"], vocab_size=TINY["vocab_size"], dtype=dtype, param_dtype=dtype)
    cfg = TransformerConfig(**fields)
    params = jax.jit(init_params, static_argnums=1)(jax.random.key(3), cfg)
    # the norms' weights off 1, so one that is left out or misplaced shows
    for stack in ("kda_dense_blocks", "kda_blocks", "blocks"):
        for name in ("ln1", "ln2", "kda_norm", "kv_a_norm"):
            if name in params[stack]:
                w = params[stack][name]
                params[stack][name] = (w * jnp.linspace(0.6, 1.4, w.shape[-1])).astype(dtype)
    return cfg, params


@pytest.fixture(scope="module")
def served():
    """The float32 program at a test's widths and two streams served together through its batcher."""
    cfg, params = program(jnp.float32)
    return _served_together(cfg, params)


def _served_together(cfg, params, lens=(11, 70), new_tokens=9):
    cb = ContinuousBatcher(params, cfg, slots=4, t_max=128, prefill_buckets=(32, 64, 96))
    rng = np.random.default_rng(5)
    reqs = [cb.submit(rng.integers(0, cfg.vocab_size, n), max_new_tokens=new_tokens) for n in lens]
    cb.pump()
    return cb, [{"prompt_ids": r.prompt_ids.tolist(), "served": list(r.out_tokens),
                 "request_id": r.request_id} for r in reqs]


def test_the_published_keys_build_the_published_stack_and_the_bytes_are_the_issues_arithmetic():
    cell = manifest.load_cell(CELL)
    file = cell["config_file"]
    config, published = file["config"], file["published"]
    assert published == CATALOG
    reduced = {"num_experts": 16, "vocab_size": 20480}
    assert all(file[k] == reduced.get(k, v) and config[k] == reduced.get(k, v) for k, v in CATALOG.items())
    assert {k: (v["published"], v["here"]) for k, v in file["reduced"].items()} == {
        "num_experts": (256, 16), "vocab_size": (163840, 20480)}
    assumed = {"num_experts_routed": 256, "experts_held_first": 0, "kda_gate_rank": 128, "kda_a_log_shape": "a head [32]",
               "kda_dt_bias_shape": "a key channel [32 x 128]", "no_gate_bias": True, "no_selection_bias": True,
               "kda_state_dtype": "float32", "kda_chunk": 64, "num_experts_per_tok": 8}
    assert {k: config[k] for k in set(config) - set(CATALOG)} == assumed and set(assumed) == set(file["assumed"])
    assert {"head_dim", "mla_use_nope", "q_lora_rank", "conv_layout", "kr_cache_lanes"} <= set(file["departures"])
    assert file["reference"] == "kimi_linear" and "precision" in file and "one chip of 16" in file["deployment"]
    assert "weights" not in file  # the weights are the run's own draw: `--seed` makes them
    cfg = TransformerConfig(vocab_size=config["vocab_size"], **reference.program_config(file))
    kinds = cfg.layer_kinds
    assert len(kinds) == 27 and kinds[0] == "kda_dense" and kinds.count("kda") == 19 and kinds.count("attn") == 7
    assert [i + 1 for i, k in enumerate(kinds) if k == "attn"] == [4, 8, 12, 16, 20, 24, 27]  # the last period too
    assert (cfg.d_model, cfg.n_heads, cfg.kda_n_heads, cfg.kda_head_dim, cfg.ssm_d_conv) == (2304, 32, 32, 128, 4)
    assert (cfg.kv_lora_rank, cfg.q_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim) == (512, 0, 128, 64, 128)
    assert (cfg.rotary, cfg.rotates("attn"), cfg.attn_scale, cfg.norm_eps, cfg.conv_width) == (False, False, 192 ** -0.5, 1e-5, 12288)
    assert (cfg.n_experts, cfg.n_experts_per_tok, cfg.experts_held, cfg.n_shared_experts, cfg.d_expert, cfg.d_ff,
            cfg.n_dense_layers) == (256, 8, (0, 16), 1, 1024, 9216, 1)
    assert (cfg.moe_gated, cfg.moe_scoring, cfg.moe_renormalize, cfg.moe_routed_scale) == (True, "sigmoid", True, 2.446)
    assert reference.layer_counts(config) == {"kda": 20, "attn": 7, "dense": 1, "moe": 26}
    # the issue's arithmetic: 39.5 M a KDA mixer, 29.1 M a latent layer, 7.08 M an expert, 8.6 GB of weights
    assert reference.kda_params(config) == (3 * 2304 * 4096 + 3 * 4096 * 4 + 2 * (2304 * 128 + 128 * 4096) + 4096 + 32
                                            + 2304 * 32 + 128 + 4096 * 2304 + 2304) == 39_516_576
    assert reference.attention_params(config) == (2304 * 6144 + 2304 * 576 + 512 + 512 * 8192 + 4096 * 2304 + 2304) == 29_117_184
    assert reference.expert_params(config) == 3 * 2304 * 1024 == 7_077_888 and reference.expert_bytes(config) == 14_155_776
    assert reference.mixture_params(config) == 2304 * 256 + 17 * 7_077_888 + 2304 == 120_916_224
    held = reference.param_count(config)
    assert held == (20 * 39_516_576 + 7 * 29_117_184 + 3 * 2304 * 9216 + 2304 + 26 * 120_916_224
                    + 2 * 20480 * 2304 + 2304) == 4_296_051_072
    assert held * 2 / 1e9 == pytest.approx(8.59, abs=0.01)
    # the same count by the shapes the program makes
    shapes = jax.eval_shape(lambda k: init_params(k, cfg), jax.random.key(0))
    assert sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(shapes)) == held
    assert shapes["kda_blocks"]["kda_qkv"].shape == (19, 2304, 12288) and shapes["kda_blocks"]["kda_conv"].shape == (19, 4, 12288)
    assert shapes["kda_blocks"]["w_gate"].shape == (19, 16, 2304, 1024) and shapes["kda_dense_blocks"]["w_gate"].shape == (1, 2304, 9216)
    assert shapes["blocks"]["wq"].shape == (7, 2304, 6144) and shapes["blocks"]["router"].shape == (7, 2304, 256)
    assert shapes["kda_blocks"]["a_log"].shape == (19, 32) and shapes["kda_blocks"]["dt_bias"].shape == (19, 4096)
    # what a slot and a token keep: 42 MB of matrix state, 2.2 MB of convolution tails, 8,064 B of latent rows
    assert reference.kda_state_bytes(config) == 20 * 32 * 128 * 128 * 4 == 41_943_040
    assert reference.slot_state_bytes(config) == 41_943_040 + 20 * 3 * 12288 * 2 == 43_417_600
    assert reference.token_bytes(config) == 7 * 576 * 2 == 8064
    from cluster_anywhere_tpu.models.generate import cache_bytes_per_token, init_cache, recurrent_state_bytes

    cache = jax.eval_shape(lambda: init_cache(cfg, 32, 4096))
    assert {n: c.shape for n, c in cache.items()} == {
        "ckv": (7, 32, 4096, 512), "kr": (7, 32, 4096, 128), "conv": (20, 32, 3, 12288), "h": (20, 32, 32, 128, 128)}
    assert recurrent_state_bytes(cache) == 32 * 43_417_600 and cache["h"].dtype == jnp.float32
    assert cache_bytes_per_token(cache, cfg) == 7 * 640 * 2  # the shared key cached 128 lanes wide


def test_the_counts_of_a_step_and_of_a_prefill_are_hand_arithmetic():
    config = manifest.load_cell(CELL)["config_file"]["config"]
    assert reference.kda_update_bytes(config, 8) == 2 * 8 * 41_943_040 == 671_088_640  # the issue's 0.67 GB a step
    assert reference.kda_step_flops(config, 8) == 8 * 20 * 32 * 7 * 128 * 128
    # a chunk of 64 a head: two Gram halves, the solve, W S, Q S, A U and the state's update
    a_chunk = 2 * (2 * 2080 * 128 + 2016 * 256 + 2 * 64 * 128 * 128 + 2080 * 128 + 64 * 128 * 128)
    assert reference.kda_prefill_flops(config, 64) == a_chunk * 32 * 20 == reference.kda_prefill_flops(config, 1)
    assert reference.kda_prefill_flops(config, 1000) == 16 * a_chunk * 32 * 20
    assert reference.experts_touched(config, 8) == pytest.approx(16 * (1 - (1 - 8 / 256) ** 8))
    outside = (20 * 39_516_576 + 7 * 29_117_184 + 3 * 2304 * 9216 + 2304 + 26 * (2304 * 256 + 7_077_888 + 2304)
               + 20480 * 2304 + 2304 + 8 * 2304)
    step = reference.decode_step_bytes(config, 8, 4096, lengths=[1000] * 8, touched=4.0)
    assert step == 2 * (outside + 26 * 4 * 7_077_888) + 2 * 8 * 43_417_600 + 8000 * 8064
    # the issue's prediction: about 2.5 GB (2.61) outside the experts (1.6 GB of it the KDA mixers'), 1.5 GB of touched experts
    assert 2 * outside / 1e9 == pytest.approx(2.61, abs=0.01) and 20 * 39_516_576 * 2 / 1e9 == pytest.approx(1.58, abs=0.01)
    assert reference.train_flops_per_step(config, 1, 128) > 0


def test_the_reference_has_the_interface_and_a_program_without_the_fields_refuses_by_name(monkeypatch):
    assert all(hasattr(reference, name) for name in manifest.REFERENCE_INTERFACE)
    assert all(hasattr(reference, name) for name in ("chosen_logits", "mechanism_checks"))
    assert {"kda.proj", "kda.conv", "kda.gates", "kda.chunk", "kda.step", "kda.out", "ssm.state"} <= set(reference.SCOPES)
    with pytest.raises(ValueError, match="a direct query projection and no rotation"):
        reference.program_config(tiny_config(q_lora_rank=64)["config_file"])
    with pytest.raises(ValueError, match="kda_gate_rank=8, kda_chunk=64: the program's low-rank projections have the head's width, 16"):
        reference.program_config(tiny_config(kda_gate_rank=8)["config_file"])
    import dataclasses

    from cluster_anywhere_tpu.models import transformer

    older = dataclasses.make_dataclass("TransformerConfig", [
        (f.name, f.type, f) for f in dataclasses.fields(transformer.TransformerConfig) if not f.name.startswith("kda_")])
    monkeypatch.setattr(transformer, "TransformerConfig", older)
    with pytest.raises(NotImplementedError, match=r"has no \['kda_head_dim', 'kda_n_heads'\]"):
        reference.program_config(manifest.load_cell(CELL)["config_file"])


KDA = {"kda_share.kda", "kda_state_hbm_share.kda", "kda_prefill_roofline.kda", "kda_state_bytes.kda", "mla_nope_share.kda",
       "state_vs_cache_bytes.kda"}


def test_the_cells_files_through_the_manifest():
    metrics = {m["name"]: m for m in manifest.layer_metrics_for(CELL)}
    names = set(metrics)
    assert KDA <= names and {metrics[n]["family"] for n in KDA} == {"kda"}
    assert {"attn_share.closed", "ffn_share.mla", "shared_expert_share.mla", "held_assignments_share.mla",
            "cache_bytes_per_token.mla", "experts_touched_mean.moe", "moe_experts_share.moe", "moe_router_share.moe",
            "decode_batch_mean.closed"} <= names
    # not the families whose readers would find nothing: no state-space scope, no window, no admit in most slices
    assert not {n for n in names if n.endswith((".blk", ".swa", ".sambay", ".ssm", ".dsa", ".nemotronh"))}
    assert "held_compact_share.mla" not in names and "mla_share.mla" not in names
    bench = manifest.load_manifest()
    listed = {m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", [CELL])}
    assert listed == names and len(bench["per_layer"]) == 117
    assert [m["name"] for m in bench["per_layer"][-6:]] == [
        "kda_share.kda", "kda_state_hbm_share.kda", "kda_prefill_roofline.kda", "kda_state_bytes.kda",
        "mla_nope_share.kda", "state_vs_cache_bytes.kda"]
    for m in bench["per_layer"][-6:]:
        file = metrics[m["name"]]
        assert m["workloads"] == [CELL] and m["moves"] == "serve_out_tok_s"
        assert {k: m[k] for k in ("unit", "layer", "source")} == {k: file[k] for k in ("unit", "layer", "source")}
    cell = manifest.load_cell(CELL)
    assert bench["workloads"][-1] == {"name": CELL, "config": CONFIG, "traffic": "reason-closed", "chips": 1, "why": cell["why"]}
    assert len(cell["why"]) <= 200 and cell["callers"] == 8 and cell["families"] == [
        "closed", "causal", "attn", "ffn_moe", "shared_expert", "held", "cache_bytes", "experts_touched", "moe_kernel",
        "moe_route", "kda"]
    assert bench["configs"][-1]["name"] == CONFIG and bench["configs"][-1]["reduced"] == ["num_experts", "vocab_size"]
    assert bench["configs"][-1]["source"] == cell["config_file"]["source"] == (
        "https://huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct/blob/main/config.json")
    assert next(m for m in bench["end_to_end"] if m["name"] == "serve_out_tok_s")["workloads"][-1] == CELL
    dep = cell["traffic_file"]["deployment"]
    assert (dep["slots"], dep["max_prompt_len"] + dep["max_new_tokens"], dep["prefix_cache_entries"]) == (32, 4096, 0)


MECHANISMS = ["kda_prefill_state_rel_err", "kda_conv_tail_rel_err", "latent_rows_rel_err", "kda_chunk_state_rel_err",
              "kda_state_rel_err", "kda_out_rel_err", "kda_state_step_err", "mla_absorb_rel_err", "moe_router_other_set",
              "moe_experts_rel_err"]


# -- the faults that each mechanism's number is there to catch ----------------------
# The chip's controls themselves (scripts/kimi_controls.py says what each is), planted in the program's own
# functions once the streams are served.  name: the numbers it moves past their bounds, of the mechanism's ten
ROWS = {"kda_prefill_state_rel_err", "kda_conv_tail_rel_err", "latent_rows_rel_err"}
RULE = {"kda_chunk_state_rel_err", "kda_state_rel_err", "kda_out_rel_err"}
# (the chip's other four: decay-dropped, beta-ignored and conv-skipped move the same six as decay-scalar, padding-kept
# the three of the rows a prefill installs: the script's `--tiny` rehearses them)
FAULTS = {"state-bf16": {"kda_state_step_err", "kda_state_rel_err"}, "decay-scalar": ROWS | RULE,
          "rotary-applied": {"latent_rows_rel_err"}}


@pytest.mark.parametrize("control", [None, *FAULTS], ids=["program", *FAULTS])
def test_each_planted_fault_is_refused_by_the_numbers_that_are_its_own(served, control, monkeypatch):
    """The serving check at a test's widths with float32 weights: the program passes, and each fault, planted once
    the streams are served, fails the mechanism's numbers that are its own.  At float32 the bounds that are set
    from the chip's bf16 readings are loose, so the faults are held to a hundredth of them here."""
    cb, streams = served
    for name in ("KDA_PREFILL_STATE_ERR_TOL", "KDA_CONV_TAIL_ERR_TOL", "LATENT_ROWS_ERR_TOL", "KDA_CHUNK_STATE_ERR_TOL",
                 "KDA_STATE_ERR_TOL", "KDA_OUT_ERR_TOL", "KDA_STATE_STEP_ERR_TOL", "MLA_ABSORB_ERR_TOL", "MOE_EXPERTS_ERR_TOL"):
        monkeypatch.setattr(reference, name, getattr(reference, name) / 100)
    fails = set()
    if control is not None:
        fails = FAULTS[control]
        for module, name, value in controls.CONTROLS[control]():
            monkeypatch.setattr(module, name, value)
    # what was traced without the fault is not what runs under it: the admit's prefill is the one program the check
    # enters that outlives a call (the others are traced anew at every check)
    generate.prefill_counted.clear_cache()
    if control == "state-bf16":  # the batcher's cache is what says how the state is kept between two tokens
        monkeypatch.setitem(cb.cache, "h", cb.cache["h"].astype(jnp.bfloat16))
    try:
        rep = check_serving(cb, streams, reference)
    finally:
        generate.prefill_counted.clear_cache()
    got = {m["name"]: m for m in rep["mechanism"]}
    assert list(got) == MECHANISMS
    assert "over 16 rows" in got["kda_out_rel_err"]["why"]  # every decode row of the two streams
    failed = {n for n, m in got.items() if not m["error"] <= m["tolerance"]}
    # a fault upstream of a layer moves every row that layer installs: those three are held to >=
    assert failed >= fails if control in ("state-bf16", "padding-kept", "rotary-applied") else failed == fails, (failed, got)
    assert rep["ok"] is (not fails), rep
    assert not reference._given  # what `chosen_logits` kept, `mechanism_checks` took
    if control is None:
        assert all(m["error"] < 2e-5 for m in got.values()), got
        assert rep["logit_max_abs_err"] < 1e-3 and rep["regret_max"] < 1e-3 and rep["agree_share"] > 0.9, rep


def test_the_new_reader_against_hand_counts():
    cell = manifest.load_cell(CELL)
    span = lambda name, start, **args: [1, float(start), 8e6, name, args]
    op = lambda start, dur, scope, name="%fusion.7 = f32[32,32,128,128] fusion()": [float(start), float(dur), name, scope]
    events = {"spans": [span("llm.step", 0, live=8), span("llm.step", 20e6, live=6), span("llm.admit", 40e6, prompt_len=300)],
              "ops": {"/device:TPU:0": [op(0, 3e6, "kda.step"), op(3e6, 1e6, "ssm.state"), op(4e6, 2e6, "ffn"),
                                        op(6e6, 5e6, "kda.chunk"), op(11e6, 1e6, "attn.core")]}}
    config = cell["config_file"]["config"]
    ctx = {"cell": cell, "device": {"kind": "TPU v5 lite"}, "program_trace": events,
           "replica": {"stats": {"state_bytes_per_slot": 43_417_600, "cache_bytes_per_token": 8960}},
           "records": [{"error": None, "n_prompt": 200, "n_out": 1000}, {"error": None, "n_prompt": 400, "n_out": 2000},
                       {"error": "cut", "n_prompt": 9, "n_out": 9}]}
    read = manifest.load_reader("kda")
    assert read(ctx, "state_hbm") == pytest.approx(100 * 14 * 2 * 41_943_040 / (4e-3 * 819e9))
    assert read(ctx, "prefill_roofline") == pytest.approx(100 * reference.kda_prefill_flops(config, 300) / (5e-3 * 197e12))
    context = (1000 * 700 + 2000 * 1400) / 3000
    assert read(ctx, "state_vs_cache") == pytest.approx(43_417_600 / (8960 * context))
    assert manifest.load_reader("scope_share")(ctx, scopes=["kda.", "ssm.state"]) == pytest.approx(100 * 9 / 12)
    assert manifest.load_reader("scope_share")(ctx, scopes=["attn."]) == pytest.approx(100 * 1 / 12)
    assert manifest.load_reader("replica_stat")(ctx, stat="state_bytes_per_slot") == 43_417_600
    # a slice with steps and no prefill reads 0, not nothing; one of another program reads nothing and does not raise
    no_admit = dict(ctx, program_trace={"spans": events["spans"][:2], "ops": {"/device:TPU:0": events["ops"]["/device:TPU:0"][:3]}})
    assert read(no_admit, "prefill_roofline") == 0.0 and read(no_admit, "state_hbm") > 0
    older = dict(ctx, program_trace={"spans": events["spans"], "ops": {"/device:TPU:0": [op(0, 3e6, "ffn")]}}, replica={"stats": {}})
    assert read(older, "state_hbm") is None and read(older, "prefill_roofline") is None and read(older, "state_vs_cache") is None
    other = dict(ctx, cell=manifest.load_cell("chat-closed6"))
    assert read(other, "state_hbm") is None
    with pytest.raises(ValueError, match="what is"):
        read(ctx, "state")
    assert program_trace.scope_of("jit(f)/while/body/kda.step/mul", program_trace.SCOPES + reference.SCOPES) == "kda.step"


def test_serve_rehearsal_of_kimilinear_reason_closed8():
    """The cell at tiny widths through the program's normal path on the CPU backend (a TPU resource that is only a
    number)."""
    cell = tiny_config()
    cell.update(callers=3)
    cell["traffic_file"].update(
        ramp_s=0.5, drain_s=60.0, warmup_prompt_lens=[20, 70, 150],
        prompt_len=dict(dist="lognormal", median=40, sigma=0.5, min=8, max=160),
        output_len=dict(dist="lognormal", median=6, sigma=0.3, min=4, max=12),
        check=dict(stream_prompt_lens=[12, 30, 70, 150], stream_new_tokens=8, repeat_prompt_len=40, repeat_new_tokens=5),
        deployment=dict(slots=4, max_prompt_len=160, max_new_tokens=16, prefix_cache_entries=0),
    )
    if ca.is_initialized():
        ca.shutdown()
    ca.init(num_cpus=4, num_tpus=1)  # the replica is a process of its own: it reads the tolerances as the file has them
    try:
        ctx = serve_driver.measure(cell, seed=3_000_000_019, seconds=3.0, trace=False, t_start=time.monotonic())
    finally:
        ca.shutdown()
    out = serve_driver.outcome(ctx)
    assert out["failed"] == 0 and out["attempted"] >= 3, out
    check = ctx["check"]
    assert check["streams"] == 4 and check["positions"] == 32 and check["repeat_identical"], check
    assert check["logit_max_abs_err"] <= reference.LOGIT_TOL and check["regret_max"] <= reference.REGRET_MAX_TOL, check
    mechanism = {m["name"]: m for m in check["mechanism"]}
    assert list(mechanism) == MECHANISMS, mechanism
    assert check["regret_mean"] <= 3 * reference.REGRET_MEAN_TOL, check
    held = {n: m for n, m in mechanism.items() if n != "moe_router_other_set"}
    assert all(m["error"] <= 3 * m["tolerance"] for m in held.values()), mechanism
    stats = ctx["replica"]["stats"]
    assert stats["state_bytes_per_slot"] == 6 * (4 * 16 * 16 * 4 + 3 * 192 * 2)
    assert stats["cache_bytes_per_token"] == 2 * (32 + 128) * 2 and stats["ssm_state_bytes"] > 0 and stats["moe_held_layers"] > 0
    layer = manifest.read_layer_metrics(CELL, ctx)
    assert layer["kda_state_bytes.kda"]["value"] == stats["state_bytes_per_slot"] and layer["state_vs_cache_bytes.kda"]["value"] > 0
    assert not {"kda_share.kda", "kda_state_hbm_share.kda", "kda_prefill_roofline.kda"} & set(layer)  # no trace, no share
    assert json.dumps(layer)
