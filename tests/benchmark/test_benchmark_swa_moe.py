"""K-EXAONE's plain reference (references/swa_moe.py, loaded as the harness loads
it) against the program at a small size on the CPU: the published keys as the
program's fields and the cut's arithmetic, the counts against the program's own
shapes, prefill and then the batch decode through a tiny batcher held by the
serving check, each mechanism held by itself with the fault that is its to
catch planted (a window off by one, a full mask, a ring rounded to float8), the
cell's files through the manifest, the new reader against hand counts, and
`kexaone-longrag-closed6` rehearsed at tiny widths through serve.run, proxy,
router and replica."""

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

CELL = "kexaone-longrag-closed6"
reference = manifest.load_reference("swa_moe")
# the published block at a test's widths: 4 query heads on 2 cached heads of 16, a window of 8 in
# a ring of 16, 'LLLG LLLG' with a dense first layer, 32 routed experts of which 8-11 are held
TINY = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=2, head_dim=16, intermediate_size=160,
            moe_intermediate_size=24, vocab_size=512, num_experts=4, num_experts_routed=32, experts_held_first=8,
            num_experts_per_tok=4, sliding_window=8)


def tiny_config(**over):
    cell = copy.deepcopy(manifest.load_cell(CELL))
    config = cell["config_file"]["config"]
    config.update(TINY, **over)
    config["sliding_windows"] = [8 if w else 0 for w in config["sliding_windows"]]
    return cell


def program(dtype, ring=16, **over):
    cell = tiny_config(**over)
    fields = reference.program_config(cell["config_file"], vocab_size=TINY["vocab_size"], attn_ring=ring,
                                      dtype=dtype, param_dtype=dtype)
    cfg = TransformerConfig(**fields)
    params = init_params(jax.random.key(3), cfg)
    # the norms' weights off 1, so a norm that is left out or misplaced shows
    for stack in ("blocks", "win_blocks", "win_dense_blocks"):
        b = params[stack]
        for name, (lo, hi) in {"ln1": (0.6, 1.4), "ln2": (1.3, 0.7), "q_norm": (0.5, 1.5), "k_norm": (1.5, 0.5)}.items():
            b[name] = b[name] * jnp.linspace(lo, hi, b[name].shape[-1]).astype(dtype)
    return cfg, params


def test_the_published_keys_build_the_published_block_and_the_cut_is_the_issues_arithmetic():
    cell = manifest.load_cell(CELL)
    file = cell["config_file"]
    config, published = file["config"], file["published"]
    cfg = TransformerConfig(vocab_size=config["vocab_size"], **reference.program_config(file))
    assert (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_head, cfg.d_ff, cfg.d_expert) == (6144, 64, 8, 128, 18432, 2048)
    assert cfg.layer_kinds == ("attn_win_dense", "attn_win", "attn_win", "attn", "attn_win", "attn_win", "attn_win", "attn")
    assert (cfg.attn_window, cfg.rope_theta, cfg.rotary_full, cfg.norm_output) == (128, 1e6, False, True)
    assert (cfg.n_experts, cfg.n_experts_per_tok, cfg.experts_held, cfg.n_shared_experts) == (128, 8, (0, 8), 1)
    assert (cfg.moe_scoring, cfg.moe_routed_scale, cfg.moe_renormalize, cfg.moe_gated) == ("sigmoid", 2.5, True, True)
    assert cfg.qk_norm and cfg.qk_norm_per_head and not cfg.latent
    # every number of the published config is in the file under its own key, and what differs is listed
    differs = {k for k, v in published.items() if file[k] != v}
    assert differs == set(file["reduced"]) == {"num_hidden_layers", "num_experts", "vocab_size"}
    assert all(config[k] == file[k] for k in published)
    assert {"qk_norm", "rotary_layers", "norm_position"} <= set(file["assumed"])
    assert {"mtp_layer", "rms_norm_eps", "rope_layout"} == set(file["departures"])
    # the issue's arithmetic, bf16: attention 113.2 M a layer; an expert 37.7 M; 7.73 GB in all
    assert reference.attention_params(config) == 113_246_208 + 256
    assert reference.expert_params(config) == 37_748_736
    assert reference.layer_counts(config) == (1, 7, 6, 2)
    held = reference.param_count(config)
    assert held * 2 / 1e9 == pytest.approx(7.73, abs=0.01)
    # the same count by the shapes the program makes
    shapes = jax.eval_shape(lambda k: init_params(k, cfg), jax.random.key(0))
    assert sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(shapes)) == held
    assert shapes["win_blocks"]["w_gate"].shape == (5, 8, 6144, 2048) and shapes["blocks"]["router"].shape == (2, 6144, 128)
    assert shapes["win_dense_blocks"]["w_gate"].shape == (1, 6144, 18432) and shapes["lm_head"].shape == (6144, 19200)
    # uncut: a whole expert layer is 4.98 B parameters, 9.97 GB
    whole = dict(published, num_experts_routed=128, experts_held_first=0)
    a_layer = reference.param_count(dict(whole, num_hidden_layers=2)) - reference.param_count(dict(whole, num_hidden_layers=1))
    assert a_layer == pytest.approx(4.98e9, rel=2e-3)
    # the cache by the program's own shapes at the cell's deployment: 2.215 GB + 0.201 GB
    from cluster_anywhere_tpu.models import generate

    dep = cell["traffic_file"]["deployment"]
    t_max = dep["max_prompt_len"] + dep["max_new_tokens"]
    cache = jax.eval_shape(lambda: generate.init_cache(cfg, dep["slots"], t_max))
    assert cache["k"].shape == (2, 32, 8448, 8, 128) and cache["kw"].shape == (6, 32, 256, 8, 128)
    by_kind = {"full": 2 * 2 * 32 * 8448 * 1024 * 2, "window": 2 * 6 * 32 * 256 * 1024 * 2}
    assert by_kind["full"] / 1e9 == pytest.approx(2.215, abs=1e-3) and by_kind["window"] / 1e9 == pytest.approx(0.201, abs=1e-3)
    assert 100 * by_kind["window"] / sum(by_kind.values()) == pytest.approx(8.33, abs=0.01)
    # a decode step by the live rows' own lengths: 5.7 rows of about 4,400 read 0.23 GB of keys and values
    lengths = [4400] * 6
    assert reference.cache_step_bytes(config, lengths) == 6 * 4096 * (2 * 4400 + 6 * 128)
    step = reference.decode_step_bytes(config, 32, t_max, touched=reference.experts_touched(config, 6), lengths=lengths)
    assert 4.3e9 < step < 5.3e9
    assert reference.decode_step_bytes(config, 32, t_max) - step > 2.0e9  # every slot's whole cache is not what a step reads
    # the band: a prompt of 4,096 meets 128 keys a query from the 128th on
    assert reference.band_pairs(4096, 128) == 128 * 129 // 2 + (4096 - 128) * 128
    assert reference.band_pairs(50, 128) == 50 * 51 // 2
    assert reference.swa_flash_flops(config, 4096) == 4.0 * 6 * 64 * 128 * reference.band_pairs(4096, 128)
    full_pairs = 4096 * 4097 // 2
    assert reference.train_flops_per_step(config, 1, 4096) > 3 * 4 * 64 * 128 * 2 * full_pairs


def test_a_program_without_the_fields_refuses_the_configuration_by_name(monkeypatch):
    """The parent of the PR that brought this file: the cell fails at once, in
    the driver's own process, before anything is deployed."""
    import dataclasses

    from cluster_anywhere_tpu.models import transformer

    @dataclasses.dataclass(frozen=True)
    class Older:
        d_model: int = 0
        n_layers: int = 0

    monkeypatch.setattr(transformer, "TransformerConfig", Older)
    with pytest.raises(NotImplementedError, match="layer_mixers"):
        reference.program_config(manifest.load_cell(CELL)["config_file"])
    bad = tiny_config()
    bad["config_file"]["config"]["sliding_windows"][1] = 64
    with pytest.raises(ValueError, match="one window"):
        reference.program_config(bad["config_file"])


def test_the_cells_files_through_the_manifest():
    """The cell joins `closed`, `causal`, `attn`, its own `swa` and, since PR 54,
    the families of what it shares with other cells (the held mixtures', the
    routed mixtures', the window layers') from its own file; BENCHMARK.json lists
    it where the manifest resolves it; the mix is `rag-closed` but for lengths
    and what follows from them."""
    metrics = {m["name"]: m for m in manifest.layer_metrics_for(CELL)}
    names = set(metrics)
    shared = {"swa_cache_share.swa": "window", "swa_attn_share.swa": "window", "ffn_share.mla": "ffn_moe",
              "moe_experts_share.moe": "moe_kernel", "shared_expert_share.mla": "shared_expert",
              "held_assignments_share.mla": "held", "held_compact_share.mla": "held_compact",
              "moe_dispatch_share.moe": "moe_route", "moe_router_share.moe": "moe_route"}
    assert {n: metrics[n]["family"] for n in shared} == shared
    assert {n for n, m in metrics.items() if m.get("family") == "swa"} == {"swa_flash_roofline.swa", "window_rows_read_share.swa"}
    assert {"cache_read_share.closed", "attn_share.closed", "decode_batch_mean.closed"} <= names
    assert not {n for n in names if n.endswith((".ssm", ".blk", ".sambay", ".nemotronh"))} and "experts_hbm_share.moe" not in names
    assert manifest.load_cell(CELL)["families"] == [
        "closed", "causal", "attn", "swa", "ffn_moe", "shared_expert", "held", "held_compact", "moe_kernel", "moe_route", "window"]
    bench = manifest.load_manifest()
    listed = {m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", [CELL])}
    assert listed == names
    assert [w for w in bench["workloads"] if w["name"] == CELL] == [
        {"name": CELL, "config": "k-exaone-236b-a23b-ep16-serve1", "traffic": "longrag-closed", "chips": 1,
         "why": manifest.load_cell(CELL)["why"]}]
    entry = next(c for c in bench["configs"] if c["name"] == "k-exaone-236b-a23b-ep16-serve1")
    assert entry["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert CELL in next(m for m in bench["end_to_end"] if m["name"] == "serve_out_tok_s")["workloads"]
    load = lambda name: json.load(open(os.path.join(manifest.BENCH_DIR, "traffic", name + ".json")))
    mine, rag = load("longrag-closed"), load("rag-closed")
    differs = {k for k in rag if mine[k] != rag[k]}
    # since PR 54 a caller goes round 24 quantiles where it drew 96 sizes: a window reaches about 23
    assert differs == {"what", "shape_seed", "caller_requests", "prompt_len", "deployment", "warmup_prompt_lens", "check"}
    assert set(mine) - set(rag) == {"caller_sizes", "caller_rounds", "caller_sizes_why"} and set(rag) <= set(mine)
    assert (mine["caller_requests"], mine["caller_sizes"], mine["caller_rounds"]) == (24, "quantiles", 8)
    assert mine["prompt_len"] == dict(rag["prompt_len"], median=4096, min=1024, max=8192)
    assert mine["deployment"] == dict(rag["deployment"], max_prompt_len=8192)
    assert mine["warmup_prompt_lens"] == [1024, 2048, 4096, 8192]
    assert mine["check"] == dict(rag["check"], stream_prompt_lens=[1100, 2100, 3900, 5900])


def _served_together(cfg, params, lens=(11, 40, 70), new_tokens=9):
    cb = ContinuousBatcher(params, cfg, slots=4, t_max=128, prefill_buckets=(32, 64, 96))
    rng = np.random.default_rng(5)
    reqs = [cb.submit(rng.integers(0, cfg.vocab_size, n), max_new_tokens=new_tokens) for n in lens]
    cb.pump()
    return cb, [{"prompt_ids": r.prompt_ids.tolist(), "served": list(r.out_tokens),
                 "request_id": r.request_id} for r in reqs]


MECHANISMS = ["swa_decode_rel_err", "full_decode_rel_err", "swa_full_mask_miss", "moe_router_other_set",
              "moe_experts_rel_err"]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
def test_serving_check_holds_prefill_and_the_batch_decode_to_the_reference(dtype):
    cfg, params = program(dtype)
    cb, streams = _served_together(cfg, params, lens=(20, 40, 70), new_tokens=12)
    assert cb.stats["decode_steps"] == 11 and cb.stats["moe_assignments"] == (20 + 40 + 70) * 4 + 11 * 3 * 4
    rep = check_serving(cb, streams, reference)
    assert rep["streams"] == 3 and rep["positions"] == 36, rep
    assert [m["name"] for m in rep["mechanism"]] == MECHANISMS
    if dtype == jnp.float32:
        assert rep["ok"] and rep["logit_max_abs_err"] < 1e-3 and rep["regret_max"] < 1e-3, rep
        assert rep["agree_share"] > 0.9 and all(m["error"] < 1e-4 for m in rep["mechanism"] if "miss" not in m["name"]), rep
    else:
        assert all(m["error"] <= m["tolerance"] for m in rep["mechanism"]), rep
    ref = np.asarray(reference.forward(
        params, np.asarray(streams[1]["prompt_ids"] + streams[1]["served"][:5]), cfg))[-1]
    wrong = [dict(s) for s in streams]
    wrong[1]["served"] = streams[1]["served"][:5] + [int(np.argmin(ref))] + streams[1]["served"][6:]
    bad = check_serving(cb, wrong, reference)
    assert not bad["ok"] and bad["regret_max"] > reference.REGRET_MAX_TOL


# -- the faults that each mechanism's number is there to catch ----------------------
# Planted in the program's own functions once the streams are served (the chip's
# controls are these, by the same names: PERF.md section 6, PR 43).


def mantissa_bits(x, bits: int):
    """x rounded to `bits` bits of mantissa by arithmetic on its float32 form
    (float8 e4m3 keeps 3)."""
    u = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    drop = 23 - bits
    u = (u + jnp.uint32(1 << (drop - 1))) & jnp.uint32(~((1 << drop) - 1) & 0xFFFFFFFF)
    return jax.lax.bitcast_convert_type(u, jnp.float32).astype(x.dtype)


def float8_cache(generate):
    """The decode cores read keys and values that were rounded to float8's 3 bits."""
    inner = generate._masked_attention

    def masked_attention(q, k, v, *a, **kw):
        return inner(q, mantissa_bits(k, 3), mantissa_bits(v, 3), *a, **kw)

    return {"_masked_attention": masked_attention}


def window_of(width):
    """The window layers' decode step sees `width` positions, not the window's 8."""

    def plant(generate):
        inner = generate._kv_decode_core

        def core(cache, layer, pos, pads, cfg, *a, **kw):
            import dataclasses

            return inner(cache, layer, pos, pads, dataclasses.replace(cfg, attn_window=width), *a, **kw)

        return {"_kv_decode_core": core}

    return plant


# name: (how it is planted, the numbers it moves past their bounds)
CONTROLS = {"float8-cache": (float8_cache, {"swa_decode_rel_err", "full_decode_rel_err"}),
            "window-7": (window_of(7), {"swa_decode_rel_err"}),
            "window-9": (window_of(9), {"swa_decode_rel_err"}),
            "whole-ring": (window_of(16), {"swa_decode_rel_err"})}


@pytest.mark.parametrize("control", [None, *CONTROLS], ids=["program", *CONTROLS])
def test_each_mechanism_is_held_by_itself(control, monkeypatch):
    """The serving check at a test's widths with float32 weights: the program
    passes, and each fault, planted once the streams are served, fails the
    mechanism's number that is its own while the three numbers on the logits
    pass.  (A ring of 16 holds no more than 16 positions: the widest mask a
    served window layer can have; `swa_full_mask_miss` is held from the other
    side, by the program's own output against the reference's full mask.)"""
    from cluster_anywhere_tpu.models import generate

    cfg, params = program(jnp.float32)
    cb, streams = _served_together(cfg, params)
    fails = set()
    if control is not None:
        plant, fails = CONTROLS[control]
        for name, fn in plant(generate).items():
            monkeypatch.setattr(generate, name, fn)
    rep = check_serving(cb, streams, reference)
    got = {m["name"]: m for m in rep["mechanism"]}
    assert list(got) == MECHANISMS
    assert got["swa_decode_rel_err"]["tolerance"] == reference.SWA_DECODE_ERR_TOL
    assert got["full_decode_rel_err"]["tolerance"] == reference.FULL_DECODE_ERR_TOL
    assert "over 24 rows" in got["swa_decode_rel_err"]["why"]  # every decode row of the three streams
    assert f"of {7 * (11 + 40 + 70 + 3 * 8)} in which" in got["moe_router_other_set"]["why"]  # every expert layer
    assert {n for n, m in got.items() if not m["error"] <= m["tolerance"]} == fails, got
    assert rep["logit_max_abs_err"] < 1e-3 and rep["regret_max"] < 1e-3 and rep["regret_mean"] < 1e-3, rep
    assert rep["ok"] is (not fails), rep
    assert not reference._given  # what `chosen_logits` kept, `mechanism_checks` took
    # the program's window layer misses the full-mask reference by about its whole norm
    assert got["swa_full_mask_miss"]["error"] < 0.5 or control == "whole-ring", got
    if control is None:
        assert got["moe_router_other_set"]["error"] == 0.0
        assert got["moe_experts_rel_err"]["error"] < 1e-5 and got["swa_decode_rel_err"]["error"] < 1e-5
        monkeypatch.setattr(reference, "SWA_DECODE_ERR_TOL", got["swa_decode_rel_err"]["error"] / 2)
        assert not check_serving(cb, streams, reference)["ok"]


def test_the_mechanism_enters_the_programs_own_functions_at_the_served_shapes(monkeypatch):
    """`mechanism_checks` calls the decode core as the served programs do: a
    batcher that serves the check streams traces `_kv_decode_core` over both
    kinds of stack with the shapes the check gives it."""
    from cluster_anywhere_tpu.models import generate

    # a configuration no other test of this process has traced: the calls are seen at trace time
    cfg, params = program(jnp.float32, moe_intermediate_size=40, num_experts_per_tok=3)
    seen = set()
    core = generate._kv_decode_core

    def spy(cache, layer, pos, pads, cfg_, q, k, v, **kw):
        name = generate.LAYER_STATE[generate._state_kind(kw["kind"], cfg_)][0]
        seen.add((q.shape, k.shape, name, cache[name].shape[1:]))
        return core(cache, layer, pos, pads, cfg_, q, k, v, **kw)

    monkeypatch.setattr(generate, "_kv_decode_core", spy)
    cb, streams = _served_together(cfg, params)
    served = set(seen)
    assert served == {((4, 1, 4, 16), (4, 1, 2, 16), "kw", (4, 16, 2, 16)), ((4, 1, 4, 16), (4, 1, 2, 16), "k", (4, 128, 2, 16))}
    seen.clear()
    reference.mechanism_checks(cb, streams)
    assert seen == served


def test_the_new_reader_against_hand_counts():
    cell = manifest.load_cell(CELL)
    config = cell["config_file"]["config"]
    span = lambda name, start, **args: [1, float(start), 8e6, name, args]
    op = lambda start, dur, scope, name="%fusion.7 = bf16[32,64] fusion()": [float(start), float(dur), name, scope]
    banded = "%swa_flash.3 = bf16[64,4096,128] custom-call()"
    events = {"spans": [span("llm.step", 0, live=6, moe_rows=6, moe_held_assignments=3.0, cache_rows_read=4000,
                             cache_rows=8000, window_rows_read=1000),
                        span("llm.step", 10e6, live=5, moe_rows=5, moe_held_assignments=2.0, cache_rows_read=3000,
                             cache_rows=8000, window_rows_read=1000),
                        span("llm.admit", 2e6, prompt_len=4096), span("llm.admit", 12e6, prompt_len=1500)],
              "ops": {"/device:TPU:0": [op(0, 2e6, "attn.core"), op(2e6, 1e6, "attn.core.window"),
                                        op(3e6, 4e6, "attn.core.window", banded), op(7e6, 1e6, "attn.cache"),
                                        op(8e6, 2e6, "moe.shared"), op(10e6, 8e6, "ffn"), op(18e6, 2e6, "")]}}
    ctx = {"cell": cell, "program_trace": events, "device": {"kind": "TPU v5 lite"},
           "replica": {"steps": [], "admits": [], "first": {}, "stats": {"cache_window_share": 8.33}},
           "records": [], "t_open": 0.0, "seconds": 1.0}
    swa = manifest.load_reader("swa")
    pairs = reference.band_pairs(4096, 128) + reference.band_pairs(1500, 128)
    want = 100 * 4.0 * 6 * 64 * 128 * pairs / (4e-3 * 197e12)
    assert swa(ctx, what="flash_roofline") == pytest.approx(want) and 0 < want < 100
    with pytest.raises(ValueError):
        swa(ctx, what="bytes")
    got = manifest.read_layer_metrics(CELL, ctx)
    assert got["swa_flash_roofline.swa"]["value"] == pytest.approx(want)
    assert got["swa_attn_share.swa"]["value"] == pytest.approx(100 * 5 / 20)  # the window layers' core, its kernel in it
    assert got["attn_share.closed"]["value"] == pytest.approx(100 * 2 / 20)  # the full layers' alone
    assert got["swa_cache_share.swa"] == {"value": 8.33, "unit": "%"}
    assert got["window_rows_read_share.swa"]["value"] == pytest.approx(100 * 2000 / 7000)
    assert got["cache_read_share.closed"]["value"] == pytest.approx(100 * 7000 / 16000)
    assert got["shared_expert_share.mla"]["value"] == pytest.approx(10.0)
    assert got["ffn_share.mla"]["value"] == pytest.approx(50.0)
    assert got["held_assignments_share.mla"]["value"] == pytest.approx(100 * 5 / (11 * 8))
    # a program without the kernel (the parent, another architecture), a slice without an admit, a run
    # without a trace, a reference that counts no band: nothing, and no error
    other = copy.deepcopy(events)
    other["ops"] = {"/device:TPU:0": [op(0, 2e6, "attn.core"), op(2e6, 1e6, "ffn")]}
    assert swa(dict(ctx, program_trace=other), what="flash_roofline") is None
    quiet = copy.deepcopy(events)
    quiet["spans"] = [s for s in events["spans"] if s[3] == "llm.step"]
    assert swa(dict(ctx, program_trace=quiet), what="flash_roofline") is None
    assert swa(dict(ctx, program_trace=None), what="flash_roofline") is None
    assert swa(dict(ctx, cell=manifest.load_cell("chat-closed6")), what="flash_roofline") is None
    assert "swa_cache_share.swa" not in manifest.read_layer_metrics(CELL, dict(ctx, replica=dict(ctx["replica"], stats={})))


def test_serve_rehearsal_of_kexaone_longrag_closed6():
    """The cell at tiny widths through the program's normal path on the CPU
    backend (a TPU resource that is only a number)."""
    # a width of 128 and a routed scale of 0.25: at 64 and 2.5 a held expert's part is so large a share
    # of the stream that one bf16 flip of a router near-tie moves a logit past the regrets' bounds
    cell = tiny_config(hidden_size=128, routed_scaling_factor=0.25)
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
    ca.init(num_cpus=4, num_tpus=1)
    try:
        ctx = serve_driver.measure(cell, seed=3_000_000_019, seconds=3.0, trace=False,
                                   t_start=time.monotonic())
    finally:
        ca.shutdown()
    out = serve_driver.outcome(ctx)
    assert out["failed"] == 0 and out["attempted"] >= 3, out
    check = ctx["check"]
    assert check["streams"] == 4 and check["positions"] == 32 and check["decode_batch_mean"] > 1.0, check
    assert check["ok"] and check["repeat_identical"] and out["correct"], check
    mechanism = {m["name"]: m for m in check["mechanism"]}
    assert list(mechanism) == MECHANISMS and all(m["error"] <= m["tolerance"] for m in mechanism.values()), mechanism
    stats = ctx["replica"]["stats"]
    # a window of 8 at 2 cached heads: the decode kernel's key block, 1,024 slots, is longer than a context of 176
    assert stats["moe_assignments"] > 0 and stats["cache_window_share"] == pytest.approx(75.0)
    assert stats["window_rows_read"] > 0 and stats["cache_rows_read"] > stats["window_rows_read"]
    layer = manifest.read_layer_metrics(CELL, ctx)
    assert layer["decode_batch_mean.closed"]["value"] >= 1.0 and layer["swa_cache_share.swa"]["value"] == pytest.approx(75.0)
    assert not {"swa_attn_share.swa", "swa_flash_roofline.swa", "attn_share.closed", "ffn_share.mla"} & set(layer)
    ctx["device"].update(platform="tpu", kind="TPU v5 lite", count=1)
    line = bench_run.result_line(ctx["cell"], serve_driver, ctx, trace=False)
    assert set(line["metrics"]) == {"setup_s", "serve_out_tok_s"} and line["correct"]

