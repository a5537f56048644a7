"""Phi-4-mini-flash-reasoning's plain reference (references/sambay.py, loaded as
the harness loads it) against the program at a small size on the CPU: the
published keys as the program's fields and the issue's arithmetic, the counts
against the program's own shapes, prefill and then the batch decode through a
tiny batcher held by the serving check, each mechanism held by itself with the
fault that is its to catch planted (a subtraction after the two maps' results
were rounded, a state handed on in bf16, a window off by one, a cross layer on
a stack of its own), the cell's files through the manifest, the new reader
against hand counts, and `phi4flash-reason-closed8` rehearsed at tiny widths
through serve.run, proxy, router and replica."""

import copy
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

CELL = "phi4flash-reason-closed8"
CONFIG = "phi-4-mini-flash-reasoning-serve1"
reference = manifest.load_reference("sambay")
# the catalog row's `config` (model-configs guide, architectures.jsonl, Phi-4-mini-flash-reasoning): every
# key of it is the configuration file's own, at the top level
CATALOG = {"embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560, "intermediate_size": 10240,
           "layer_norm_eps": 1e-05, "max_position_embeddings": 262144, "mb_per_layer": 2, "model_type": "phi4flash",
           "num_attention_heads": 40, "num_hidden_layers": 32, "num_key_value_heads": 20, "resid_pdrop": 0,
           "sliding_window": 512, "tie_word_embeddings": True, "mlp_bias": False, "lm_head_bias": False,
           "vocab_size": 200064}
# the published map at a test's widths: 8 layers (0 ssm, 1 window, 2 ssm, 3 window, 4 ssm -> the memory,
# 5 full -> the shared keys and values, 6 gmu, 7 cross), 8 query heads on 4 cached heads of 8, a window of 8
TINY = dict(hidden_size=64, num_attention_heads=8, num_key_value_heads=4, head_dim=8, intermediate_size=160,
            vocab_size=512, num_hidden_layers=8, sliding_window=8, mamba_dt_rank=4, mamba_d_state=4, layer_map=None)


def tiny_config(**over):
    cell = copy.deepcopy(manifest.load_cell(CELL))
    cell["config_file"]["config"].update(TINY, **over)
    return cell


def program(dtype, ring=16, **over):
    cell = tiny_config(**over)
    fields = reference.program_config(cell["config_file"], vocab_size=TINY["vocab_size"], attn_ring=ring,
                                      dtype=dtype, param_dtype=dtype)
    cfg = TransformerConfig(**fields)
    params = init_params(jax.random.key(3), cfg)
    # the norms' weights off 1, so a norm that is left out or misplaced shows
    for stack in ("ssm_blocks", "win_blocks", "blocks", "gmu_blocks", "cross_blocks"):
        b = params[stack]
        for name, (lo, hi) in {"ln1": (0.6, 1.4), "ln2": (1.3, 0.7), "subln": (0.5, 1.5)}.items():
            if name in b:
                b[name] = b[name] * jnp.linspace(lo, hi, b[name].shape[-1]).astype(dtype)
    return cfg, params


def test_the_published_keys_build_the_published_stack_and_the_bytes_are_the_issues_arithmetic():
    cell = manifest.load_cell(CELL)
    file = cell["config_file"]
    config, published = file["config"], file["published"]
    assert published == CATALOG and all(file[k] == v for k, v in CATALOG.items())
    assert file["reduced"] == {} and all(config[k] == v for k, v in CATALOG.items())
    assumed = {"head_dim": 64, "mamba_d_state": 16, "mamba_d_conv": 4, "mamba_expand": 2, "mamba_dt_rank": 160,
               "mamba_conv_bias": True, "mamba_proj_bias": False, "mamba_inner_norms": False,
               "differential_attention": True, "norm": "layer_norm", "attention_bias": True,
               "no_positional_embedding": True, "gmu": True, "layer_map": list(reference.layer_map(CATALOG))}
    # everything the row lacks is in `config` under a key of its own and under `assumed` with where it is from
    assert {k: config[k] for k in set(config) - set(CATALOG)} == assumed and set(assumed) == set(file["assumed"])
    assert {"sampling", "mamba_conv_layout", "qkv_layout", "prefill"} <= set(file["departures"])
    assert file["reference"] == "sambay" and "precision" in file and "deployment" in file
    cfg = TransformerConfig(vocab_size=config["vocab_size"], **reference.program_config(file))
    assert (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_head, cfg.d_ff, cfg.d_inner) == (2560, 40, 20, 64, 10240, 5120)
    kinds = cfg.layer_kinds
    assert kinds == ("ssm", "attn_win") * 8 + ("ssm", "attn") + ("gmu", "attn_cross") * 7
    assert [kinds.count(k) for k in ("ssm", "attn_win", "attn", "gmu", "attn_cross")] == [9, 8, 1, 7, 7]
    assert reference.layer_counts(config) == {"ssm": 9, "attn_win": 8, "attn": 1, "gmu": 7, "attn_cross": 7}
    assert (cfg.attn_window, cfg.rotary, cfg.tie_embeddings, cfg.layer_norm, cfg.norm_eps) == (512, False, True, True, 1e-5)
    assert cfg.diff_attn and cfg.attn_bias and not cfg.ssm_inner_norms and (cfg.ssm_dt_rank, cfg.ssm_d_state) == (160, 16)
    assert (cfg.cached_heads, cfg.cached_width, cfg.flat_heads, cfg.shared_readers) == (10, 128, 10, 8)
    assert cfg.lambda_inits("attn")[0] == pytest.approx(0.8 - 0.6 * np.exp(-0.3 * 17))
    assert cfg.lambda_inits("attn_cross")[-1] == pytest.approx(0.8 - 0.6 * np.exp(-0.3 * 31))
    # the issue's arithmetic: 3,852,562,944 parameters, 7.71 GB in bf16
    assert reference.mixer_params(config) == 41_241_600 and reference.attention_params(config) == 19_668_864
    assert reference.attention_params(config, cross=True) == 13_112_704 and reference.gmu_params(config) == 26_214_400
    held = reference.param_count(config)
    assert held == 3_852_562_944 and held * 2 / 1e9 == pytest.approx(7.71, abs=0.01)
    # the same count by the shapes the program makes
    shapes = jax.eval_shape(lambda k: init_params(k, cfg), jax.random.key(0))
    assert sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(shapes)) == held
    assert shapes["cross_blocks"]["wq"].shape == (7, 2560, 2560) and "wk" not in shapes["cross_blocks"]
    assert shapes["gmu_blocks"]["gmu_in"].shape == (7, 2560, 5120) and shapes["ssm_blocks"]["ssm_x"].shape == (9, 5120, 192)
    assert "lm_head" not in shapes and shapes["embed"].shape == (200064, 2560)
    # the cache by the program's own shapes at the cell's deployment: 0.67 GB + 0.67 GB + 0.10 GB
    from cluster_anywhere_tpu.models import generate

    dep = cell["traffic_file"]["deployment"]
    t_max = dep["max_prompt_len"] + dep["max_new_tokens"]
    cache = jax.eval_shape(lambda: generate.init_cache(cfg, dep["slots"], t_max))
    assert t_max == 4096 and cache["k"].shape == (1, 32, 4096 * 10, 128) and cache["kw"].shape == (8, 32, 512 * 10, 128)
    assert cache["h"].shape == (9, 32, 5120, 16) and cache["conv"].shape == (9, 32, 3, 5120)
    assert generate.cache_context_bytes_per_token(cache, cfg) == 5120 == reference.token_bytes(config)
    assert generate.cache_bytes_per_token(cache, cfg) == 9 * 5120  # the eight rings hold a token too, while it is in the window
    kinds_bytes = generate.cache_kind_bytes(cache)
    assert kinds_bytes == {"full": 32 * 4096 * 5120, "window": 8 * 32 * 512 * 5120}
    assert kinds_bytes["full"] / 1e9 == pytest.approx(0.67, abs=0.005) and kinds_bytes["window"] / 1e9 == pytest.approx(0.67, abs=0.005)
    state = generate.recurrent_state_bytes(cache)
    assert state == 32 * reference.slot_state_bytes(config) == 32 * 9 * 358_400 and state / 1e9 == pytest.approx(0.10, abs=0.005)
    resident = 2 * held + sum(kinds_bytes.values()) + state
    assert resident / 1e9 == pytest.approx(9.15, abs=0.01) and resident / 16e9 == pytest.approx(0.57, abs=0.005)
    # a decode step: 7.7 GB of weights of which the MLPs and the head are 79%; the mixers' weights and state
    mlps_and_head = 32 * 3 * 2560 * 10240 + 200064 * 2560
    assert mlps_and_head / held == pytest.approx(0.79, abs=0.005)
    assert reference.mixer_step_bytes(config, 32) == 9 * 41_241_600 * 2 + 2 * 32 * 9 * 358_400
    lengths = [2000] * 8
    step = reference.decode_step_bytes(config, 32, t_max, lengths=lengths)
    assert step - 2 * held == 8 * 5120 * (8 * 2000 + 8 * 512) + 2 * 32 * 9 * 358_400
    # what the steps fetched of the shared stack, from the batcher's own count: eight readers x the rows' key blocks
    first, last = np.zeros(8, np.int64), np.full(8, 2000)
    rows = generate.key_slots(jax.eval_shape(lambda: generate.init_cache(cfg, 32, t_max)), first, last, 512, cfg)
    assert rows == ((8 * 8 * 2048 + 8 * 8 * 512) // 16, 8 * 8 * 512 // 16, 8 * 8 * 2048 // 16)
    assert reference.shared_cache_step_bytes(config, rows[2]) == 8 * 8 * 2048 * 5120
    assert reference.train_flops_per_step(config, 1, 4096) > 3 * 2 * 4096 * (held - 200064 * 2560)


def test_a_program_without_the_fields_refuses_the_configuration_by_name(monkeypatch):
    """The parent of the PR that brought this file: the cell fails at once, in
    the driver's own process, before anything is deployed."""
    import dataclasses

    from cluster_anywhere_tpu.models import transformer

    @dataclasses.dataclass(frozen=True)
    class Older:
        d_model: int = 0
        n_layers: int = 0
        layer_mixers: tuple = ()

    monkeypatch.setattr(transformer, "TransformerConfig", Older)
    with pytest.raises(NotImplementedError, match="diff_attn"):
        reference.program_config(manifest.load_cell(CELL)["config_file"])
    with pytest.raises(ValueError, match="mb_per_layer"):
        reference.program_config(tiny_config(num_hidden_layers=6)["config_file"])


def test_the_cells_files_through_the_manifest():
    """The cell joins `closed`, `causal`, `attn`, its own `sambay` and, since
    PR 54, the families of what it shares with other cells (`ffn_dense` and `ssm`
    with the Mamba-1 hybrid, `window` with the window-and-full mixture) from its
    own file; BENCHMARK.json lists it where the manifest resolves it; the mix is
    `chat-closed` but for lengths and what follows from them."""
    metrics = {m["name"]: m for m in manifest.layer_metrics_for(CELL)}
    names = set(metrics)
    sambay = {"ssm_share.sambay", "gmu_share.sambay", "full_attn_share.sambay", "cross_attn_share.sambay",
              "shared_cache_hbm_share.sambay", "shared_rows_read_share.sambay", "cache_bytes_per_token.sambay",
              "prefill_tail_share.sambay"}
    shared = {"ffn_share.ssm": "ffn_dense", "ssm_hbm_share.ssm": "ssm", "ssm_state_bytes.ssm": "ssm",
              "ssm_proj_share.ssm": "ssm", "ssm_scan_share.ssm": "ssm", "swa_attn_share.swa": "window",
              "swa_cache_share.swa": "window"}
    assert sambay | set(shared) <= names
    assert {"cache_read_share.closed", "attn_share.closed", "decode_batch_mean.closed"} <= names
    assert {n: metrics[n]["family"] for n in shared} == shared and {metrics[n]["family"] for n in sambay} == {"sambay"}
    assert not {n for n in names if n.endswith((".mla", ".moe", ".blk", ".nemotronh"))}
    assert {n for n in names if n.endswith(".swa")} == {"swa_attn_share.swa", "swa_cache_share.swa"}
    bench = manifest.load_manifest()
    listed = {m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", [CELL])}
    assert listed == names and len(bench["per_layer"]) <= 128
    for m in bench["per_layer"]:
        if m["name"] in sambay | set(shared):
            assert (m["workloads"] == [CELL]) == (m["name"] in sambay) and m["moves"] == "serve_out_tok_s"
    cell = manifest.load_cell(CELL)
    assert [w for w in bench["workloads"] if w["name"] == CELL] == [
        {"name": CELL, "config": CONFIG, "traffic": "reason-closed", "chips": 1, "why": cell["why"]}]
    assert cell["callers"] == 8 and cell["families"] == ["closed", "causal", "attn", "sambay", "ffn_dense", "ssm", "window"]
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == [] and entry["source"] == cell["config_file"]["source"]
    assert entry["source"] == "https://huggingface.co/microsoft/Phi-4-mini-flash-reasoning/blob/main/config.json"
    assert CELL in next(m for m in bench["end_to_end"] if m["name"] == "serve_out_tok_s")["workloads"]
    load = lambda name: json.load(open(os.path.join(manifest.BENCH_DIR, "traffic", name + ".json")))
    mine, chat = load("reason-closed"), load("chat-closed")
    differs = {k for k in chat if mine[k] != chat[k]}
    assert differs == {"what", "shape_seed", "drain_s", "prompt_len", "output_len", "deployment",
                       "warmup_prompt_lens", "check"} and set(mine) == set(chat)
    assert mine["kind"] == "closed_loop" and (mine["caller_requests"], mine["ramp_s"], mine["drain_s"]) == (96, 15.0, 60.0)
    assert mine["prompt_len"] == {"dist": "lognormal", "median": 256, "sigma": 0.8, "min": 64, "max": 1024}
    assert mine["output_len"] == {"dist": "lognormal", "median": 1536, "sigma": 0.5, "min": 512, "max": 3072}
    assert mine["deployment"] == {"slots": 32, "max_prompt_len": 1024, "max_new_tokens": 3072, "prefix_cache_entries": 0}
    # a length a bucket of the deployment's ladder: ISSUE 47's four and, since PR 54, the fifth, 64, which a prompt
    # clipped to exactly 64 tokens (about 4% of the mix) enters (it compiled inside the window where a seed drew it)
    from cluster_anywhere_tpu.llm.continuous import prefill_buckets_for

    assert tuple(mine["warmup_prompt_lens"]) == prefill_buckets_for(1024) == (64, 128, 256, 512, 1024)
    assert mine["warmup_new_tokens"] == 4
    assert mine["check"] == {"stream_prompt_lens": [100, 200, 480, 1000], "stream_new_tokens": 64,
                             "repeat_prompt_len": 200, "repeat_new_tokens": 9}


def _served_together(cfg, params, lens=(11, 40, 70), new_tokens=9):
    cb = ContinuousBatcher(params, cfg, slots=4, t_max=128, prefill_buckets=(32, 64, 96))
    rng = np.random.default_rng(5)
    reqs = [cb.submit(rng.integers(0, cfg.vocab_size, n), max_new_tokens=new_tokens) for n in lens]
    cb.pump()
    return cb, [{"prompt_ids": r.prompt_ids.tolist(), "served": list(r.out_tokens),
                 "request_id": r.request_id} for r in reqs]


MECHANISMS = ["window_decode_rel_err", "full_decode_rel_err", "cross_decode_rel_err", "cross_shared_rows_miss",
              "cross_ring_rows_moved", "prefill_rows_rel_err", "ssm_memory_rel_err", "ssm_state_rel_err",
              "ssm_state_step_err"]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
def test_serving_check_holds_prefill_and_the_batch_decode_to_the_reference(dtype):
    cfg, params = program(dtype)
    cb, streams = _served_together(cfg, params, lens=(20, 40, 70), new_tokens=12)
    assert cb.stats["decode_steps"] == 11 and cb.stats["prefill_tail_positions_total"] == 3
    rep = check_serving(cb, streams, reference)
    assert rep["streams"] == 3 and rep["positions"] == 36, rep
    assert [m["name"] for m in rep["mechanism"]] == MECHANISMS
    if dtype == jnp.float32:
        assert rep["ok"] and rep["logit_max_abs_err"] < 1e-3 and rep["regret_max"] < 1e-3, rep
        assert rep["agree_share"] > 0.9 and all(m["error"] < 1e-4 for m in rep["mechanism"] if "miss" not in m["name"]), rep
    else:
        # a test's 20-80 keys a row average a bf16 probability's rounding less than the cell's 100-4,000 do:
        # the chip's bounds on the cores are held at three times their size here
        loose = {f"{k}_decode_rel_err" for k in reference.DECODE_ERR_TOL} | {"ssm_memory_rel_err", "ssm_state_rel_err"}
        assert all(m["error"] <= m["tolerance"] * (3 if m["name"] in loose else 1) for m in rep["mechanism"]), rep
    ref = np.asarray(reference.forward(
        params, np.asarray(streams[1]["prompt_ids"] + streams[1]["served"][:5]), cfg))[-1]
    wrong = [dict(s) for s in streams]
    wrong[1]["served"] = streams[1]["served"][:5] + [int(np.argmin(ref))] + streams[1]["served"][6:]
    bad = check_serving(cb, wrong, reference)
    assert not bad["ok"] and bad["regret_max"] > reference.REGRET_MAX_TOL


# -- the faults that each mechanism's number is there to catch ----------------------
# Planted in the program's own functions once the streams are served (the chip's
# controls are these, by the same names: PERF.md section 6, PR 47).


def rounded_maps(modules):
    """The two maps' results are rounded to bf16 before they are subtracted."""
    generate = modules["generate"]
    inner = generate._kv_decode_core

    def core(*a, **kw):
        out, cache = inner(*a, **kw)
        return out.astype(jnp.bfloat16).astype(out.dtype), cache

    return {("generate", "_kv_decode_core"): core}


def window_of(width):
    """The window layers' decode step sees `width` positions, not the window's 8."""

    def plant(modules):
        inner = modules["generate"]._kv_decode_core

        def core(cache, layer, pos, pads, cfg, *a, **kw):
            import dataclasses

            return inner(cache, layer, pos, pads, dataclasses.replace(cfg, attn_window=width), *a, **kw)

        return {("generate", "_kv_decode_core"): core}

    return plant


def state_in_bf16(modules):
    """The recurrent state is handed from one token to the next in bf16."""
    transformer = modules["transformer"]
    inner = transformer._ssm_mix

    def mix(bp, xs, state, cfg, keep=None):
        y, (window, h) = inner(bp, xs, state, cfg, keep)
        return y, (window, h.astype(jnp.bfloat16).astype(h.dtype))

    return {("transformer", "_ssm_mix"): mix}


def own_stack(modules):
    """A cross layer attends to a stack that nothing wrote: zeros, not the full layer's."""
    generate = modules["generate"]
    inner = generate._kv_decode_core

    def core(cache, layer, pos, pads, cfg, q, k, v, **kw):
        if k is None:
            cache = dict(cache, k=jnp.zeros_like(cache["k"]), v=jnp.zeros_like(cache["v"]))
        return inner(cache, layer, pos, pads, cfg, q, k, v, **kw)

    return {("generate", "_kv_decode_core"): core}


# name: (how it is planted, the numbers it moves past their bounds)
DECODES = {"window_decode_rel_err", "full_decode_rel_err", "cross_decode_rel_err"}
CONTROLS = {"rounded-maps": (rounded_maps, DECODES),
            "window-7": (window_of(7), {"window_decode_rel_err"}),
            "window-9": (window_of(9), {"window_decode_rel_err"}),
            "state-bf16": (state_in_bf16, {"ssm_memory_rel_err", "ssm_state_rel_err", "ssm_state_step_err"}),
            "own-stack": (own_stack, {"cross_decode_rel_err", "cross_shared_rows_miss"})}


@pytest.mark.parametrize("control", [None, *CONTROLS], ids=["program", *CONTROLS])
def test_each_mechanism_is_held_by_itself(control, monkeypatch):
    """The serving check at a test's widths with float32 weights: the program
    passes, and each fault, planted once the streams are served, fails the
    mechanism's numbers that are its own while the three numbers on the logits
    pass.  At float32 the bounds that are set from the chip's bf16 readings are
    loose, so the faults are held to a hundredth of them here."""
    from cluster_anywhere_tpu.models import generate, transformer

    cfg, params = program(jnp.float32)
    cb, streams = _served_together(cfg, params)
    # bounds of a float32 program: a hundredth of the chip's
    monkeypatch.setattr(reference, "DECODE_ERR_TOL", {k: v / 100 for k, v in reference.DECODE_ERR_TOL.items()})
    monkeypatch.setattr(reference, "SSM_MEMORY_ERR_TOL", reference.SSM_MEMORY_ERR_TOL / 100)
    monkeypatch.setattr(reference, "SSM_STATE_ERR_TOL", reference.SSM_STATE_ERR_TOL / 100)
    fails = set()
    if control is not None:
        plant, fails = CONTROLS[control]
        modules = {"generate": generate, "transformer": transformer}
        for (module, name), fn in plant(modules).items():
            monkeypatch.setattr(modules[module], name, fn)
    rep = check_serving(cb, streams, reference)
    got = {m["name"]: m for m in rep["mechanism"]}
    assert list(got) == MECHANISMS
    assert "over 24 rows" in got["window_decode_rel_err"]["why"]  # every decode row of the three streams
    assert {n for n, m in got.items() if not m["error"] <= m["tolerance"]} == fails, got
    assert rep["logit_max_abs_err"] < 1e-3 and rep["regret_max"] < 1e-3 and rep["regret_mean"] < 1e-3, rep
    assert rep["ok"] is (not fails), rep
    assert not reference._given  # what `chosen_logits` kept, `mechanism_checks` took
    if control is None:
        assert all(got[n]["error"] < 1e-5 for n in DECODES | {"ssm_memory_rel_err", "ssm_state_rel_err"}), got
        assert got["prefill_rows_rel_err"]["error"] < 1e-5 and got["cross_ring_rows_moved"]["error"] == 0.0
        assert got["cross_shared_rows_miss"]["error"] < 0.5


def test_the_mechanism_enters_the_programs_own_functions_at_the_served_shapes(monkeypatch):
    """`mechanism_checks` calls the decode core as the served programs do: a
    batcher that serves the check streams traces `_kv_decode_core` over the
    rings, the full stack and, with no keys of its own, the cross layer, with
    the shapes the check gives it."""
    from cluster_anywhere_tpu.models import generate

    # a configuration no other test of this process has traced: the calls are seen at trace time
    cfg, params = program(jnp.float32, intermediate_size=96)
    seen = set()
    core = generate._kv_decode_core

    def spy(cache, layer, pos, pads, cfg_, q, k, v, **kw):
        name = generate.LAYER_STATE[generate._state_kind(kw["kind"], cfg_)][0]
        seen.add((kw["kind"], q.shape, None if k is None else k.shape, name, cache[name].shape[1:]))
        return core(cache, layer, pos, pads, cfg_, q, k, v, **kw)

    monkeypatch.setattr(generate, "_kv_decode_core", spy)
    cb, streams = _served_together(cfg, params)
    served = set(seen)
    assert served == {("attn_win", (4, 1, 8, 16), (4, 1, 2, 16), "kw", (4, 32, 16)),
                      ("attn", (4, 1, 8, 16), (4, 1, 2, 16), "k", (4, 256, 16)),
                      ("attn_cross", (4, 1, 8, 16), None, "k", (4, 256, 16))}
    seen.clear()
    reference.mechanism_checks(cb, streams)
    assert seen == served


def test_the_new_reader_against_hand_counts():
    cell = manifest.load_cell(CELL)
    config = cell["config_file"]["config"]
    span = lambda name, start, **args: [1, float(start), 8e6, name, args]
    op = lambda start, dur, scope, name="%fusion.7 = bf16[32,64] fusion()": [float(start), float(dur), name, scope]
    kernel = "%decode_attn.3 = f32[32,40,128] custom-call()"
    step = dict(live=8, ssm_state_bytes=206_438_400, cache_rows=90_112)
    events = {"spans": [span("llm.step", 0, cache_rows_read=10_240, window_rows_read=2_048, shared_rows_read=8_192,
                             shared_readers=8, **step),
                        span("llm.step", 10e6, cache_rows_read=6_144, window_rows_read=2_048, shared_rows_read=4_096,
                             shared_readers=8, **step)],
              "ops": {"/device:TPU:0": [op(0, 2e6, "attn.core.full", kernel), op(2e6, 6e6, "attn.core.cross", kernel),
                                        op(8e6, 1e6, "attn.core.window", kernel), op(9e6, 1e6, "attn.diff"),
                                        op(10e6, 4e6, "ssm.scan"), op(14e6, 2e6, "ssm.in"), op(16e6, 4e6, "gmu.in"),
                                        op(20e6, 18e6, "ffn"), op(38e6, 2e6, "")]}}
    ctx = {"cell": cell, "program_trace": events, "device": {"kind": "TPU v5 lite"},
           "replica": {"steps": [], "admits": [], "first": {}, "stats": {
               "cache_window_share": 50.0, "cache_bytes_per_token": 46080, "cache_context_bytes_per_token": 5120,
               "prefill_tail_share": 0.39}},
           "records": [], "t_open": 0.0, "seconds": 1.0}
    shared = manifest.load_reader("shared_cache")
    # 16 layers read a stack; the argument is their mean, so times 16 it is readers x slots, 5,120 B each
    want = 100 * (8_192 + 4_096) * 16 * 5120 / (8e-3 * 819e9)
    assert shared(ctx) == pytest.approx(want) and 0 < want < 100
    got = manifest.read_layer_metrics(CELL, ctx)
    assert got["shared_cache_hbm_share.sambay"]["value"] == pytest.approx(want)
    assert got["full_attn_share.sambay"]["value"] == pytest.approx(100 * 2 / 40)
    assert got["cross_attn_share.sambay"]["value"] == pytest.approx(100 * 6 / 40)
    assert got["swa_attn_share.swa"]["value"] == pytest.approx(100 * 1 / 40)
    assert got["attn_share.closed"]["value"] == 0.0  # every core here runs under a scope of the stack it reads
    assert got["ssm_share.sambay"]["value"] == pytest.approx(100 * 6 / 40)
    assert got["gmu_share.sambay"]["value"] == pytest.approx(100 * 4 / 40)
    assert got["ffn_share.ssm"]["value"] == pytest.approx(100 * 18 / 40)
    assert got["shared_rows_read_share.sambay"]["value"] == pytest.approx(100 * 12_288 / 16_384)
    assert got["cache_read_share.closed"]["value"] == pytest.approx(100 * 16_384 / 180_224)
    assert got["ssm_hbm_share.ssm"]["value"] == pytest.approx(
        100 * 2 * reference.mixer_step_bytes(config, 32) / (6e-3 * 819e9))
    assert got["ssm_proj_share.ssm"]["value"] == pytest.approx(100 * 2 / 40)
    assert got["ssm_scan_share.ssm"]["value"] == pytest.approx(100 * 4 / 40)
    assert got["ssm_state_bytes.ssm"] == {"value": 206_438_400.0, "unit": "bytes"}
    assert got["cache_bytes_per_token.sambay"] == {"value": 5120.0, "unit": "bytes"}
    assert got["swa_cache_share.swa"] == {"value": 50.0, "unit": "%"}
    assert got["prefill_tail_share.sambay"] == {"value": 0.39, "unit": "%"}
    # a slice without an admit leaves every number of the family a number
    assert {m["name"] for m in manifest.layer_metrics_for(CELL) if m.get("family") == "sambay"} <= set(got)
    # a program without the scopes or the count (the parent, another architecture), a run without a trace, a
    # reference that counts no shared stack: nothing, and no error
    other = copy.deepcopy(events)
    other["ops"] = {"/device:TPU:0": [op(0, 2e6, "attn.core"), op(2e6, 1e6, "ffn")]}
    assert shared(dict(ctx, program_trace=other)) is None
    quiet = copy.deepcopy(events)
    for s in quiet["spans"]:
        del s[4]["shared_rows_read"]
    assert shared(dict(ctx, program_trace=quiet)) is None
    assert shared(dict(ctx, program_trace=None)) is None
    assert shared(dict(ctx, cell=manifest.load_cell("kexaone-longrag-closed6"))) is None
    assert "prefill_tail_share.sambay" not in manifest.read_layer_metrics(CELL, dict(ctx, replica=dict(ctx["replica"], stats={})))


def test_serve_rehearsal_of_phi4flash_reason_closed8():
    """The cell at tiny widths through the program's normal path on the CPU
    backend (a TPU resource that is only a number)."""
    cell = tiny_config()
    cell.update(callers=3)
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
    assert check["regret_mean"] <= reference.REGRET_MEAN_TOL, check
    mechanism = {m["name"]: m for m in check["mechanism"]}
    # the replica is a process of its own and holds the cores to the chip's bounds; a test's 12-150 keys a row
    # average a bf16 probability's rounding less than the cell's 100-4,000 do, so here they are held at three times
    loose = {f"{k}_decode_rel_err" for k in reference.DECODE_ERR_TOL} | {"ssm_memory_rel_err", "ssm_state_rel_err"}
    assert list(mechanism) == MECHANISMS, mechanism
    assert all(m["error"] <= m["tolerance"] * (3 if name in loose else 1) for name, m in mechanism.items()), mechanism
    assert out["correct"] == check["ok"] == all(m["error"] <= m["tolerance"] for m in mechanism.values())
    stats = ctx["replica"]["stats"]
    # a window of 8 at 2 cached pairs: the decode kernel's key block, 1,024 slots, is longer than a context of 176
    assert stats["cache_context_bytes_per_token"] == 2 * 4 * 8 * 2 and stats["cache_window_share"] == pytest.approx(100 * 2 / 3)
    assert stats["shared_rows_read"] > 0 and stats["window_rows_read"] > 0 and stats["ssm_state_bytes"] > 0
    assert 0 < stats["prefill_tail_share"] < 5 and stats["prefill_tail_positions_total"] == stats["admitted"]
    layer = manifest.read_layer_metrics(CELL, ctx)
    assert layer["decode_batch_mean.closed"]["value"] >= 1.0 and layer["cache_bytes_per_token.sambay"]["value"] == 128.0
    assert layer["prefill_tail_share.sambay"]["value"] == pytest.approx(stats["prefill_tail_share"])
    assert not {"ssm_share.sambay", "shared_cache_hbm_share.sambay", "attn_share.closed", "ffn_share.ssm"} & set(layer)
    ctx["device"].update(platform="tpu", kind="TPU v5 lite", count=1)
    line = bench_run.result_line(ctx["cell"], serve_driver, ctx, trace=False)
    assert set(line["metrics"]) == {"setup_s", "serve_out_tok_s"} and line["correct"] == check["ok"]
