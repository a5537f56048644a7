"""NVIDIA-Nemotron-3-Nano-30B-A3B's plain reference (references/nemotronh.py,
loaded as the harness loads it) against the program at a small size on the CPU:
the published keys as the program's fields and the issue's arithmetic, the
counts against the program's own shapes, prefill and then the batch decode
through a tiny batcher held by the serving check, each mechanism held by itself
with the fault that is its to catch planted (a state handed on in bf16, another
expert activation, a rotary embedding applied, a router in bf16), the cell's
files through the manifest, the new reader against hand counts, and
`nemotron3nano-reason-closed8` rehearsed at tiny widths through serve.run,
proxy, router and replica."""

import copy
import dataclasses
import importlib.util
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import cluster_anywhere_tpu as ca
from benchmarks import run as bench_run
from benchmarks.harness import manifest, serve_driver
from benchmarks.harness.reference import check_serving
from cluster_anywhere_tpu.llm.continuous import ContinuousBatcher
from cluster_anywhere_tpu.models.transformer import TransformerConfig, init_params

CELL = "nemotron3nano-reason-closed8"
CONFIG = "nemotron-3-nano-30b-a3b-ep8-serve1"
reference = manifest.load_reference("nemotronh")
# the catalog row's `config` (model-configs guide, architectures.jsonl, NVIDIA-Nemotron-3-Nano-30B-A3B-BF16)
CATALOG = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4, "expand": 2, "head_dim": 128, "hidden_size": 2688,
    "hybrid_override_pattern": "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME", "intermediate_size": 1856,
    "layer_norm_epsilon": 1e-05, "mamba_head_dim": 64, "mamba_hidden_act": "silu", "mamba_num_heads": 64,
    "mamba_proj_bias": False, "max_position_embeddings": 262144, "mlp_bias": False, "mlp_hidden_act": "relu2",
    "model_type": "nemotron_h", "moe_intermediate_size": 1856, "moe_shared_expert_intermediate_size": 3712,
    "n_group": 1, "n_groups": 8, "n_routed_experts": 128, "n_shared_experts": 1, "norm_eps": 1e-05,
    "norm_topk_prob": True, "num_attention_heads": 32, "num_experts_per_tok": 6, "num_hidden_layers": 52,
    "num_key_value_heads": 2, "num_logits_to_keep": 1, "partial_rotary_factor": 1, "rescale_prenorm_residual": True,
    "residual_in_fp32": False, "rope_theta": 10000, "routed_scaling_factor": 2.5, "sliding_window": None,
    "ssm_state_size": 128, "tie_word_embeddings": False, "time_step_floor": 0.0001, "time_step_max": 0.1,
    "time_step_min": 0.001, "topk_group": 1, "use_bias": False, "use_conv_bias": True, "use_mamba_kernels": True,
    "vocab_size": 131072}
# the published pattern's start and end at a test's widths (10 layers, two mixers in a row, an FFN between two) and
# the faults are the chip's controls' own (scripts/nemotronh_controls.py, which `--tiny` rehearses)
_spec = importlib.util.spec_from_file_location(
    "nemotronh_controls", os.path.join(os.path.dirname(manifest.BENCH_DIR), "scripts", "nemotronh_controls.py"))
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
    params = init_params(jax.random.key(3), cfg)
    # the norms' weights and the mixer's per-head vectors off 1, so one that is left out or misplaced shows
    for stack, names in (("mamba2_blocks", ("ln1", "ssm_norm", "ssm_d")), ("alone_blocks", ("ln1",)), ("ffn_blocks", ("ln2",))):
        for name in names:
            w = params[stack][name]
            params[stack][name] = (w * jnp.linspace(0.6, 1.4, w.shape[-1])).astype(dtype)
    return cfg, params


def test_the_published_keys_build_the_published_stack_and_the_bytes_are_the_issues_arithmetic():
    cell = manifest.load_cell(CELL)
    file = cell["config_file"]
    config, published = file["config"], file["published"]
    assert published == CATALOG
    reduced = {"n_routed_experts": 16, "vocab_size": 16384}
    assert all(file[k] == reduced.get(k, v) and config[k] == reduced.get(k, v) for k, v in CATALOG.items())
    assert {k: (v["published"], v["here"]) for k, v in file["reduced"].items()} == {
        "n_routed_experts": (128, 16), "vocab_size": (131072, 16384)}
    assert config["num_hidden_layers"] == 52 and config["hybrid_override_pattern"] == CATALOG["hybrid_override_pattern"]
    assumed = {"n_routed_experts_routed": 128, "experts_held_first": 0, "no_rotary_embedding": True,
               "gated_norm_groups": "8 groups of 512; gate, then norm", "no_selection_bias": True,
               "time_step_init_only": True, "ssm_state_dtype": "float32"}
    assert {k: config[k] for k in set(config) - set(CATALOG)} == assumed and set(assumed) == set(file["assumed"])
    assert {"inner_width", "conv_layout"} <= set(file["departures"])
    assert file["reference"] == "nemotronh" and "precision" in file and "one chip of 8" in file["deployment"]
    cfg = TransformerConfig(vocab_size=config["vocab_size"], **reference.program_config(file))
    assert (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_head, cfg.d_inner, cfg.conv_width) == (2688, 32, 2, 128, 4096, 6144)
    assert (cfg.ssm_n_heads, cfg.ssm_head_dim, cfg.ssm_n_groups, cfg.ssm_d_state, cfg.ssm_chunk, cfg.ssm_d_conv) == (64, 64, 8, 128, 128, 4)
    kinds = cfg.layer_kinds
    assert [kinds.count(k) for k in ("mamba2", "ffn", "attn_alone")] == [23, 23, 6] and len(kinds) == 52
    assert reference.layer_counts(config) == {"mamba2": 23, "ffn": 23, "attn_alone": 6} and reference.expert_layers(config) == 23
    assert (cfg.n_experts, cfg.n_experts_per_tok, cfg.experts_held, cfg.n_shared_experts, cfg.d_expert, cfg.d_shared) == (
        128, 6, (0, 16), 1, 1856, 3712)
    assert (cfg.moe_act, cfg.moe_gated, cfg.moe_scoring, cfg.moe_renormalize, cfg.moe_routed_scale) == ("relu2", False, "sigmoid", True, 2.5)
    assert (cfg.rotary, cfg.tie_embeddings, cfg.norm_eps, cfg.flat_heads, cfg.half_layers) == (False, False, 1e-5, 2, True)
    # the issue's arithmetic: 38.75 M a mixer, 23.40 M an attention layer, 9.98 M an expert, 179.95 M a mixture layer
    # at 16 experts, 5,258 M parameters and 10.5 GB in all
    assert reference.mixer_params(config) == 2688 * 10304 + 4096 * 2688 + 6144 * 5 + 3 * 64 + 4096 + 2688 == 38_744_896
    assert reference.attention_params(config) == 2688 * 4096 * 2 + 2 * 2688 * 256 + 2688 == 23_399_040
    assert reference.expert_params(config) == 2 * 2688 * 1856 == 9_977_856 and reference.expert_bytes(config) == 19_955_712
    assert reference.mixture_params(config) == 2688 * 128 + 2 * 2688 * 3712 + 16 * 9_977_856 + 2688 == 179_948_160
    assert reference.mixture_params(config, held=128) / 1e6 == pytest.approx(1297.5, abs=0.1)
    held = reference.param_count(config)
    assert held == 23 * 38_744_896 + 6 * 23_399_040 + 23 * 179_948_160 + 2 * 16384 * 2688 + 2688 == 5_258_417_600
    assert held * 2 / 1e9 == pytest.approx(10.52, abs=0.01)
    # the same count by the shapes the program makes, but for the columns of zeros an expert's first matrix and a
    # mixer's in-projection are stored with (1,856 -> 1,920, 10,304 -> 10,368: parallel/moe.py LANES)
    shapes = jax.eval_shape(lambda k: init_params(k, cfg), jax.random.key(0))
    assert shapes["ffn_blocks"]["w_in"].shape == (23, 16, 2688, 1920) and shapes["ffn_blocks"]["w_out"].shape == (23, 16, 1856, 2688)
    made = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(shapes))
    assert made - held == 23 * 16 * 2688 * 64 + 23 * 2688 * 64
    assert shapes["mamba2_blocks"]["ssm_in"].shape == (23, 2688, 10368) and shapes["mamba2_blocks"]["conv_w"].shape == (23, 4, 6144)
    assert shapes["alone_blocks"]["wk"].shape == (6, 2688, 256) and shapes["ffn_blocks"]["router"].shape == (23, 2688, 128)
    assert shapes["ffn_blocks"]["shared_in"].shape == (23, 2688, 3712) and shapes["lm_head"].shape == (2688, 16384)
    # the cache by the program's own shapes at the cell's deployment: 1.54 GB of state, 27 MB of windows, 0.81 GB of keys and values
    from cluster_anywhere_tpu.models import generate

    dep = cell["traffic_file"]["deployment"]
    t_max = dep["max_prompt_len"] + dep["max_new_tokens"]
    cache = jax.eval_shape(lambda: generate.init_cache(cfg, dep["slots"], t_max))
    assert t_max == 4096 and cache["k"].shape == (6, 32, 4096 * 2, 128) and cache["h"].shape == (23, 32, 64, 64, 128)
    assert cache["conv"].shape == (23, 32, 3, 6144) and cache["h"].dtype == jnp.float32
    assert generate.cache_bytes_per_token(cache, cfg) == 6144 == reference.token_bytes(config)
    state = generate.recurrent_state_bytes(cache)
    assert state == 32 * reference.slot_state_bytes(config) and reference.slot_state_bytes(config) == 23 * (64 * 64 * 128 * 4 + 3 * 6144 * 2)
    assert reference.slot_state_bytes(config) / 1e6 == pytest.approx(49.1, abs=0.1) and state / 1e9 == pytest.approx(1.57, abs=0.01)
    keys = generate.cache_kind_bytes(cache)["full"]
    assert keys == 32 * 4096 * 6144 and keys / 1e9 == pytest.approx(0.81, abs=0.005)
    resident = 2 * made + keys + state
    assert resident / 1e9 == pytest.approx(13.02, abs=0.02) and resident / 16e9 > 0.25
    # a decode step at 8 live rows: the mixers' weights 1.78 GB and every slot's state twice 3.14 GB; 5.1 of 16 held
    # experts a layer, 2.3 GB over 23 layers; about 8.5 GB in all
    assert reference.mixer_step_bytes(config, 32) == 23 * 38_744_896 * 2 + 2 * state
    assert 23 * 38_744_896 * 2 / 1e9 == pytest.approx(1.78, abs=0.01)
    touched = reference.experts_touched(config, 8)
    assert touched == pytest.approx(16 * (1 - (1 - 6 / 128) ** 8)) and touched == pytest.approx(5.1, abs=0.05)
    assert reference.expert_layers(config) * touched * reference.expert_bytes(config) / 1e9 == pytest.approx(2.34, abs=0.02)
    step = reference.decode_step_bytes(config, 32, t_max, lengths=[2000] * 8, touched=touched)
    assert step / 1e9 == pytest.approx(8.6, abs=0.15)
    assert reference.mixer_step_bytes(config, 32) / step == pytest.approx(0.57, abs=0.02)
    assert reference.train_flops_per_step(config, 1, 4096) > 3 * 2 * 4096 * 1.5e9


def test_a_program_without_the_fields_refuses_the_configuration_by_name(monkeypatch):
    """The parent of the PR that brought this file: the cell fails at once, in
    the driver's own process, before anything is deployed."""
    from cluster_anywhere_tpu.models import transformer

    @dataclasses.dataclass(frozen=True)
    class Older:
        d_model: int = 0
        n_layers: int = 0
        layer_mixers: tuple = ()

    monkeypatch.setattr(transformer, "TransformerConfig", Older)
    with pytest.raises(NotImplementedError, match="ssm_n_heads"):
        reference.program_config(manifest.load_cell(CELL)["config_file"])
    with pytest.raises(ValueError, match="relu"):
        reference.program_config(tiny_config(mlp_hidden_act="silu")["config_file"])


# its own family's three, and what it reads through the families it shares with the cells named
OWN = {"moe_experts_share.nemotronh", "experts_hbm_share.nemotronh", "held_compact_share.nemotronh"}
SHARED = {"ssm_hbm_share.ssm": "ssm", "ssm_scan_share.ssm": "ssm", "ssm_proj_share.ssm": "ssm",
          "ssm_state_bytes.ssm": "ssm", "held_assignments_share.mla": "held", "shared_expert_share.mla": "shared_expert",
          "ffn_share.mla": "ffn_moe", "cache_bytes_per_token.mla": "cache_bytes", "experts_touched_mean.moe": "experts_touched"}
NEMOTRONH = OWN | set(SHARED)


def test_the_cells_files_through_the_manifest():
    """The cell joins `closed`, `causal`, `attn`, its own `nemotronh` and, since
    PR 54, the families of the metrics it shares with other cells, from its own
    file, and not `moe` (whose roofline share counts every layer as an expert
    layer); BENCHMARK.json lists it where the manifest resolves it; the mix is
    `reason-closed`, which warms all five buckets since PR 54."""
    metrics = {m["name"]: m for m in manifest.layer_metrics_for(CELL)}
    names = set(metrics)
    assert NEMOTRONH <= names and {"cache_read_share.closed", "attn_share.closed", "decode_batch_mean.closed"} <= names
    assert {n: metrics[n]["family"] for n in SHARED} == SHARED and {metrics[n]["family"] for n in OWN} == {"nemotronh"}
    assert not {n for n in names if n.endswith((".blk", ".swa", ".sambay"))} and "experts_hbm_share.moe" not in names
    bench = manifest.load_manifest()
    listed = {m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", [CELL])}
    assert listed == names and len(bench["per_layer"]) <= 128
    for m in bench["per_layer"]:
        if m["name"] in NEMOTRONH:
            assert (m["workloads"] == [CELL]) == (m["name"] in OWN) and m["moves"] == "serve_out_tok_s"
    cell = manifest.load_cell(CELL)
    assert [w for w in bench["workloads"] if w["name"] == CELL] == [
        {"name": CELL, "config": CONFIG, "traffic": "reason-closed", "chips": 1, "why": cell["why"]}]
    assert len(cell["why"]) <= 200  # found by its name: later cells follow it
    assert cell["callers"] == 8 and cell["families"] == [
        "closed", "causal", "attn", "nemotronh", "ffn_moe", "ssm", "shared_expert", "held", "cache_bytes", "experts_touched"]
    (entry,) = [c for c in bench["configs"] if c["name"] == CONFIG]
    assert entry["reduced"] == ["n_routed_experts", "vocab_size"]
    assert entry["source"] == cell["config_file"]["source"] == (
        "https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16/blob/main/config.json")
    assert CELL in next(m for m in bench["end_to_end"] if m["name"] == "serve_out_tok_s")["workloads"]
    # PR 50's copy of the mix with the fifth warm-up length is the mix itself since PR 54
    assert not os.path.exists(os.path.join(manifest.BENCH_DIR, "traffic", "reason-closed-warm64.json"))
    from cluster_anywhere_tpu.llm.continuous import prefill_buckets_for

    assert tuple(cell["traffic_file"]["warmup_prompt_lens"]) == prefill_buckets_for(1024) == (64, 128, 256, 512, 1024)


def _served_together(cfg, params, lens=(11, 40, 70), new_tokens=9):
    cb = ContinuousBatcher(params, cfg, slots=4, t_max=128, prefill_buckets=(32, 64, 96))
    rng = np.random.default_rng(5)
    reqs = [cb.submit(rng.integers(0, cfg.vocab_size, n), max_new_tokens=new_tokens) for n in lens]
    cb.pump()
    return cb, [{"prompt_ids": r.prompt_ids.tolist(), "served": list(r.out_tokens),
                 "request_id": r.request_id} for r in reqs]


MECHANISMS = ["prefill_rows_rel_err", "ssm_prefill_state_rel_err", "ssm_state_rel_err", "ssm_out_rel_err",
              "ssm_state_step_err", "attn_decode_rel_err", "moe_router_other_set", "moe_experts_rel_err"]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
def test_serving_check_holds_prefill_and_the_batch_decode_to_the_reference(dtype):
    cfg, params = program(dtype)
    cb, streams = _served_together(cfg, params, lens=(20, 40, 70), new_tokens=12)
    assert cb.stats["decode_steps"] == 11
    rep = check_serving(cb, streams, reference)
    assert rep["streams"] == 3 and rep["positions"] == 36, rep
    assert [m["name"] for m in rep["mechanism"]] == MECHANISMS
    if dtype == jnp.float32:
        assert rep["ok"] and rep["logit_max_abs_err"] < 1e-3 and rep["regret_max"] < 1e-3, rep
        assert rep["agree_share"] > 0.9 and all(m["error"] < 1e-4 for m in rep["mechanism"]), rep
    else:
        # a test's 64 channels average a bf16 rounding less than the cell's 2,688 do, and 16 sigmoid scores of a
        # bf16 stream tie more often than 128 of the chip's: the chip's bounds are held at three times their size
        # here, and the router's set is left to the float32 case
        got = {m["name"]: m for m in rep["mechanism"]}
        assert all(m["error"] <= 3 * m["tolerance"] for n, m in got.items() if n != "moe_router_other_set"), rep
    ref = np.asarray(reference.forward(
        params, np.asarray(streams[1]["prompt_ids"] + streams[1]["served"][:5]), cfg))[-1]
    wrong = [dict(s) for s in streams]
    wrong[1]["served"] = streams[1]["served"][:5] + [int(np.argmin(ref))] + streams[1]["served"][6:]
    bad = check_serving(cb, wrong, reference)
    assert not bad["ok"] and bad["regret_max"] > reference.REGRET_MAX_TOL


# -- the faults that each mechanism's number is there to catch ----------------------
# Planted in the program's own functions once the streams are served: the chip's controls themselves
# (scripts/nemotronh_controls.py says what each is).  name: (how it is planted, the numbers it moves past their bounds)
STATE = {"ssm_prefill_state_rel_err", "ssm_state_rel_err", "ssm_state_step_err"}
CONTROLS = {"state-bf16": (controls.state_bf16, STATE | {"ssm_out_rel_err"}),
            "state-kept": (controls.state_kept, {"ssm_state_rel_err", "ssm_state_step_err", "ssm_out_rel_err"}),
            "bf16-softmax": (controls.common.bf16_softmax, {"attn_decode_rel_err"}),
            "silu-experts": (controls.silu_experts, {"moe_experts_rel_err"}),  # the prefill program was traced before the fault
            "rotary-applied": (controls.rotary_applied, {"prefill_rows_rel_err"}),
            "no-gate": (controls.no_gate, {"ssm_out_rel_err"})}


@pytest.mark.parametrize("control", [None, *CONTROLS], ids=["program", *CONTROLS])
def test_each_mechanism_is_held_by_itself(control, monkeypatch):
    """The serving check at a test's widths with float32 weights: the program
    passes, and each fault, planted once the streams are served, fails the
    mechanism's numbers that are its own.  At float32 the bounds that are set
    from the chip's bf16 readings are loose, so the faults are held to a
    hundredth of them here."""
    from cluster_anywhere_tpu.models import generate, transformer
    from cluster_anywhere_tpu.parallel import moe

    cfg, params = program(jnp.float32)
    cb, streams = _served_together(cfg, params)
    for name in ("SSM_PREFILL_STATE_ERR_TOL", "SSM_STATE_ERR_TOL", "SSM_OUT_ERR_TOL", "SSM_STATE_STEP_ERR_TOL",
                 "ATTN_DECODE_ERR_TOL", "MOE_EXPERTS_ERR_TOL", "PREFILL_ROWS_ERR_TOL"):
        monkeypatch.setattr(reference, name, getattr(reference, name) / 100)
    fails = set()
    if control is not None:
        plant, fails = CONTROLS[control]
        for module, name, value in plant():
            monkeypatch.setattr(module, name, value)
    rep = check_serving(cb, streams, reference)
    got = {m["name"]: m for m in rep["mechanism"]}
    assert list(got) == MECHANISMS
    assert "over 24 rows" in got["ssm_out_rel_err"]["why"]  # every decode row of the three streams
    failed = {n for n, m in got.items() if not m["error"] <= m["tolerance"]}
    assert failed >= fails if control == "state-bf16" else failed == fails, (failed, got)
    assert rep["ok"] is (not fails), rep
    assert not reference._given  # what `chosen_logits` kept, `mechanism_checks` took
    if control is None:
        assert all(m["error"] < 1e-5 for m in got.values()), got
        assert rep["logit_max_abs_err"] < 1e-3 and rep["regret_max"] < 1e-3 and rep["regret_mean"] < 1e-3, rep


def test_the_new_reader_against_hand_counts():
    cell = manifest.load_cell(CELL)
    config = cell["config_file"]["config"]
    span = lambda name, start, **args: [1, float(start), 8e6, name, args]
    op = lambda start, dur, scope, name="%fusion.7 = bf16[32,64] fusion()": [float(start), float(dur), name, scope]
    kernel = "%ragged-dot-none.3 = bf16[48,2688] custom-call()"
    step = dict(live=8, ssm_state_bytes=3_141_271_552, moe_rows=8)
    events = {"spans": [span("llm.step", 0, moe_experts_touched=5.0, moe_held_assignments=6.5, **step),
                        span("llm.step", 10e6, moe_experts_touched=6.0, moe_held_assignments=5.5, **step),
                        span("llm.admit", 20e6, moe_held_layers=23, moe_compact_layers=22, ssm_chunks=2)],
              "ops": {"/device:TPU:0": [op(0, 8e6, "", kernel), op(8e6, 1e6, "moe.experts"), op(9e6, 1e6, "moe.shared"),
                                        op(10e6, 1e6, "moe.router"), op(11e6, 8e6, "ssm.scan"), op(19e6, 1e6, "ssm.scan.chunk"),
                                        op(20e6, 1e6, "ssm.norm"), op(21e6, 4e6, "ssm.in"), op(25e6, 2e6, "ssm.out"),
                                        op(27e6, 1e6, "attn.core"), op(28e6, 12e6, "head")]}}
    ctx = {"cell": cell, "program_trace": events, "device": {"kind": "TPU v5 lite"},
           "replica": {"steps": [], "admits": [], "first": {},
                       "stats": {"cache_bytes_per_token": 6144, "moe_held_layers": 11 * 23, "moe_compact_layers": 2 * 23}},
           "records": [], "t_open": 0.0, "seconds": 1.0}
    experts = manifest.load_reader("experts_layers")
    # two steps touched 5 and 6 held experts a layer in the mean, over the 23 layers that hold experts, 19,955,712 B each
    want = 100 * 11 * 23 * 19_955_712 / (9e-3 * 819e9)
    assert experts(ctx) == pytest.approx(want) and 0 < want < 100
    # the accepted reader over all 52 layers would count 52 / 23 times the bytes, and here over 100%
    old = manifest.load_reader("experts_kernel")(ctx, share_of="hbm_roofline")
    assert old == pytest.approx(want * 52 / 23) and old > 100
    got = manifest.read_layer_metrics(CELL, ctx)
    assert got["experts_hbm_share.nemotronh"]["value"] == pytest.approx(want)
    assert got["moe_experts_share.nemotronh"]["value"] == pytest.approx(100 * 9 / 40)
    assert got["experts_touched_mean.moe"]["value"] == pytest.approx(5.5)
    # a slice without a grouped matmul (no prompt of the largest bucket was admitted in it): the loop's products alone
    loop_only = copy.deepcopy(events)
    loop_only["ops"]["/device:TPU:0"][0] = op(0, 8e6, "moe.experts")
    assert experts(dict(ctx, program_trace=loop_only)) == pytest.approx(want)
    assert experts(dict(ctx, program_trace=loop_only), share_of="busy") == pytest.approx(100 * 9 / 40)
    assert manifest.load_reader("experts_kernel")(dict(ctx, program_trace=loop_only), share_of="busy") is None
    assert got["shared_expert_share.mla"]["value"] == pytest.approx(100 * 1 / 40)
    assert got["ffn_share.mla"]["value"] == pytest.approx(100 * 3 / 40)  # the kernel itself carries no scope
    assert got["ssm_scan_share.ssm"]["value"] == pytest.approx(100 * 10 / 40)
    assert got["ssm_proj_share.ssm"]["value"] == pytest.approx(100 * 6 / 40)
    assert got["ssm_hbm_share.ssm"]["value"] == pytest.approx(
        100 * 2 * reference.mixer_step_bytes(config, 32) / (16e-3 * 819e9))
    assert 0 < got["ssm_hbm_share.ssm"]["value"] < 100
    assert got["ssm_state_bytes.ssm"] == {"value": 3_141_271_552.0, "unit": "bytes"}
    assert 2 * 32 * reference.slot_state_bytes(config) == 3_141_271_552
    assert got["held_assignments_share.mla"]["value"] == pytest.approx(100 * 12 / (6 * 16))
    # of the replica's eleven admits two were of the largest bucket: read from its totals, not from the slice's one admit
    assert got["held_compact_share.nemotronh"]["value"] == pytest.approx(100 * 2 / 11)
    assert got["cache_bytes_per_token.mla"] == {"value": 6144.0, "unit": "bytes"}
    assert NEMOTRONH <= set(got)
    # a program without the kernel or the count (the parent, a dense model), a run without a trace, a reference
    # that does not say how many layers hold experts: nothing, and no error
    other = copy.deepcopy(events)
    other["ops"] = {"/device:TPU:0": [op(0, 2e6, "attn.core"), op(2e6, 1e6, "ffn")]}
    assert experts(dict(ctx, program_trace=other)) is None
    quiet = copy.deepcopy(events)
    for s in quiet["spans"]:
        s[4].pop("moe_experts_touched", None)
    assert experts(dict(ctx, program_trace=quiet)) is None
    assert experts(dict(ctx, program_trace=None)) is None
    assert experts(dict(ctx, cell=manifest.load_cell("kexaone-longrag-closed6"))) is None


def test_serve_rehearsal_of_nemotron3nano_reason_closed8():
    """The cell at tiny widths through the program's normal path on the CPU
    backend (a TPU resource that is only a number)."""
    cell = tiny_config()
    cell.update(callers=3)
    # the file's one draw of the weights is the full model's: a tiny one is another model, from this test's seed
    assert cell["config_file"].pop("weights")["seed"] == 2_540_000_402
    cell["traffic_file"].update(
        ramp_s=0.5, drain_s=60.0, warmup_prompt_lens=[20, 70, 150],
        prompt_len=dict(dist="lognormal", median=40, sigma=0.5, min=8, max=160),
        output_len=dict(dist="lognormal", median=6, sigma=0.3, min=4, max=12),
        check=dict(stream_prompt_lens=[12, 30, 70, 150], stream_new_tokens=8, repeat_prompt_len=40,
                   repeat_new_tokens=5),
        deployment=dict(slots=4, max_prompt_len=160, max_new_tokens=16, prefix_cache_entries=0),
    )
    if ca.is_initialized():
        ca.shutdown()
    ca.init(num_cpus=4, num_tpus=1)  # the replica is a process of its own: it reads the tolerances as the file has them
    try:
        ctx = serve_driver.measure(cell, seed=3_000_000_019, seconds=3.0, trace=False,
                                   t_start=time.monotonic())
    finally:
        ca.shutdown()
    out = serve_driver.outcome(ctx)
    assert out["failed"] == 0 and out["attempted"] >= 3, out
    check = ctx["check"]
    assert check["streams"] == 4 and check["positions"] == 32 and check["decode_batch_mean"] > 1.0, check
    assert check["repeat_identical"], check
    assert check["logit_max_abs_err"] <= reference.LOGIT_TOL and check["regret_max"] <= reference.REGRET_MAX_TOL, check
    mechanism = {m["name"]: m for m in check["mechanism"]}
    assert list(mechanism) == MECHANISMS, mechanism
    # the replica is a process of its own and holds the numbers to the chip's bounds; a test's 64 channels average a
    # bf16 rounding less than the cell's 2,688 do and 16 bf16 scores tie more often than the chip's 128: three times
    # the bounds here, and the router's set left to the float32 tests.  The mean regret likewise: one token of these
    # 32 positions that a tie sent to another expert is a 32nd of its regret (0.052 from one of 1.66), where the
    # cell's streams have 256 positions
    assert check["regret_mean"] <= 3 * reference.REGRET_MEAN_TOL, check
    held = {n: m for n, m in mechanism.items() if n != "moe_router_other_set"}
    assert all(m["error"] <= 3 * m["tolerance"] for m in held.values()), mechanism
    stats = ctx["replica"]["stats"]
    assert stats["cache_bytes_per_token"] == 2 * 2 * 2 * 16 * 2 and stats["ssm_state_bytes"] > 0 and stats["moe_assignments"] > 0
    assert stats["moe_held_layers"] == 4 * stats["admitted"] and stats["moe_experts_touched"] > 0
    layer = manifest.read_layer_metrics(CELL, ctx)
    assert layer["decode_batch_mean.closed"]["value"] >= 1.0 and layer["cache_bytes_per_token.mla"]["value"] == 256.0
    assert not {"ssm_hbm_share.ssm", "experts_hbm_share.nemotronh", "ffn_share.mla"} & set(layer)
    ctx["device"].update(platform="tpu", kind="TPU v5 lite", count=1)
    line = bench_run.result_line(ctx["cell"], serve_driver, ctx, trace=False)
    assert set(line["metrics"]) == {"setup_s", "serve_out_tok_s"} and line["correct"] == check["ok"]
