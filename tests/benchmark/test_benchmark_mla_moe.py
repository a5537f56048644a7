"""A.X-K1's plain reference (references/mla_moe.py, loaded as the harness loads
it) against the program at a small size on the CPU: the published keys as the
program's fields and the cut's arithmetic, the forward and the loss in float32,
prefill and then the batch decode through a tiny batcher held by the serving
check, each mechanism held by itself with the lower precision that is its to
catch planted, the counts and the two new readers against hand counts, and
`axk1-rag-closed6` rehearsed at tiny widths through serve.run, proxy, router
and replica."""

import copy
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
from cluster_anywhere_tpu.models.transformer import (
    TransformerConfig, cross_entropy_loss, forward, init_params,
)

CELL = "axk1-rag-closed6"
reference = manifest.load_reference("mla_moe")
# the published block at a test's widths: 4 heads of 16 + 8 over a latent of 32, a
# leading dense layer, 32 routed experts of which experts 8-11 are held, 4 a token
TINY = dict(hidden_size=64, num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=4,
            intermediate_size=160, moe_intermediate_size=24, vocab_size=512, kv_lora_rank=32, q_lora_rank=48,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=12, n_routed_experts=4, n_routed_experts_routed=32,
            experts_held_first=8, num_experts_per_tok=4)


def tiny_config(**over):
    cell = copy.deepcopy(manifest.load_cell(CELL))
    cell["config_file"]["config"].update(TINY, **over)
    cell["config_file"]["config"]["rope_scaling"] = dict(
        cell["config_file"]["config"]["rope_scaling"], original_max_position_embeddings=32)
    return cell


def program(dtype, **over):
    cell = tiny_config(**over)
    fields = reference.program_config(cell["config_file"], vocab_size=TINY["vocab_size"],
                                      dtype=dtype, param_dtype=dtype)
    cfg = TransformerConfig(**fields)
    params = init_params(jax.random.key(3), cfg)
    # the low-rank norms' weights off 1, so a norm that is left out shows
    for stack in ("blocks", "dense_blocks"):
        b = params[stack]
        b["q_a_norm"] = b["q_a_norm"] * jnp.linspace(0.5, 1.5, b["q_a_norm"].shape[-1]).astype(dtype)
        b["kv_a_norm"] = b["kv_a_norm"] * jnp.linspace(1.4, 0.6, b["kv_a_norm"].shape[-1]).astype(dtype)
    return cfg, params


def test_the_published_keys_build_the_published_block_and_the_cut_is_the_issues_arithmetic():
    cell = manifest.load_cell(CELL)
    config, published = cell["config_file"]["config"], cell["config_file"]["published"]
    cfg = TransformerConfig(vocab_size=config["vocab_size"], **reference.program_config(cell["config_file"]))
    assert (cfg.d_model, cfg.n_heads, cfg.kv_lora_rank, cfg.q_lora_rank) == (7168, 64, 512, 1536)
    assert (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim, cfg.d_ff, cfg.d_expert) == (128, 64, 128, 18432, 2048)
    assert cfg.layer_kinds == ("attn_dense",) + ("attn",) * 6 and cfg.latent
    assert (cfg.n_experts, cfg.n_experts_per_tok, cfg.experts_held, cfg.n_shared_experts) == (192, 8, (0, 12), 1)
    assert (cfg.moe_scoring, cfg.moe_routed_scale, cfg.moe_renormalize, cfg.moe_gated) == ("sigmoid", 2.5, True, True)
    assert (cfg.rope_factor, cfg.rope_original_max_len, cfg.rope_theta) == (32.0, 4096, 10000.0)
    assert cfg.attn_scale == pytest.approx(0.130861, rel=1e-5)
    # the issue's arithmetic, bf16: attention 101.1 M a layer; an expert 44.04 M; an expert layer
    # here 675.0 M; the dense layer 497.5 M; embedding and head 293.6 M; 9.68 GB in all
    assert reference.attention_params(config) == pytest.approx(101.1e6, rel=1e-3)
    assert reference.expert_params(config) == pytest.approx(44.04e6, rel=1e-3)
    held = reference.param_count(config)
    assert held * 2 / 1e9 == pytest.approx(9.68, abs=0.01)
    # the same count by the shapes the program makes
    shapes = jax.eval_shape(lambda k: init_params(k, cfg), jax.random.key(0))
    assert sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(shapes)) == held
    assert shapes["blocks"]["w_gate"].shape == (6, 12, 7168, 2048) and shapes["blocks"]["router"].shape == (6, 7168, 192)
    assert shapes["dense_blocks"]["w_gate"].shape == (1, 7168, 18432) and shapes["lm_head"].shape == (7168, 20480)
    # uncut: a whole expert layer is 8.60 B parameters, 17.2 GB: no chip holds one
    whole = dict(published, n_routed_experts_routed=192, experts_held_first=0)
    a_layer = (reference.param_count(dict(whole, num_hidden_layers=2)) - reference.param_count(dict(whole, num_hidden_layers=1)))
    assert a_layer == pytest.approx(8.60e9, rel=2e-3)
    # the cache: 1,152 B a token a layer as the model has it (the rotated key's 64 lie in 128 lanes: 1,280)
    assert (config["kv_lora_rank"] + config["qk_rope_head_dim"]) * 2 == 1152
    assert reference.mla_core_bytes(config, 32, 4352) == 7 * 32 * 4352 * 1152 == pytest.approx(1.123e9, rel=1e-3)
    # a decode step reads about 5.6 GB by the issue's reckoning at 6 live rows
    assert 5.0e9 < reference.decode_step_bytes(config, 32, 4352, touched=reference.experts_touched(config, 6)) < 6.2e9


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
    with pytest.raises(NotImplementedError, match="kv_lora_rank"):
        reference.program_config(manifest.load_cell(CELL)["config_file"])


def test_reference_forward_and_loss_match_the_program_in_float32():
    cfg, params = program(jnp.float32)
    ids = np.random.default_rng(0).integers(0, cfg.vocab_size, 41)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(forward(params, jnp.asarray(ids[None, :-1]), cfg)[0])
        want_loss = float(cross_entropy_loss(jnp.asarray(want[None]), jnp.asarray(ids[None, 1:])))
    got = np.asarray(reference.forward(params, ids[:-1], cfg))
    assert np.max(np.abs(got - want)) < 2e-4
    assert reference.loss(params, ids, cfg) == pytest.approx(want_loss, abs=1e-4)
    ids2 = ids.copy()
    ids2[30] = (ids2[30] + 1) % cfg.vocab_size
    got2 = np.asarray(reference.forward(params, ids2[:-1], cfg))
    assert np.array_equal(got[:30], got2[:30]) and not np.allclose(got[30:], got2[30:])
    # the low-rank norms are in it: without their weights the logits move
    flat = dict(params)
    flat["blocks"] = dict(flat["blocks"], kv_a_norm=jnp.ones_like(flat["blocks"]["kv_a_norm"]))
    assert not np.allclose(np.asarray(reference.forward(flat, ids[:-1], cfg)), got, atol=1e-3)


def _served_together(cfg, params, lens=(11, 40, 70), new_tokens=9):
    cb = ContinuousBatcher(params, cfg, slots=4, t_max=128, prefill_buckets=(32, 64, 96))
    rng = np.random.default_rng(5)
    reqs = [cb.submit(rng.integers(0, cfg.vocab_size, n), max_new_tokens=new_tokens) for n in lens]
    cb.pump()
    return cb, [{"prompt_ids": r.prompt_ids.tolist(), "served": list(r.out_tokens),
                 "request_id": r.request_id} for r in reqs]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
def test_serving_check_holds_prefill_and_the_batch_decode_to_the_reference(dtype):
    cfg, params = program(dtype)
    cb, streams = _served_together(cfg, params, lens=(20, 40, 70), new_tokens=12)
    assert cb.stats["decode_steps"] == 11 and cb.stats["moe_assignments"] == (20 + 40 + 70) * 4 + 11 * 3 * 4
    rep = check_serving(cb, streams, reference)
    assert rep["streams"] == 3 and rep["positions"] == 36, rep
    assert [m["name"] for m in rep["mechanism"]] == ["mla_absorb_rel_err", "moe_router_other_set", "moe_experts_rel_err"]
    if dtype == jnp.float32:
        assert rep["ok"] and rep["logit_max_abs_err"] < 1e-3 and rep["regret_max"] < 1e-3, rep
        assert rep["agree_share"] > 0.9 and all(m["error"] < 1e-4 for m in rep["mechanism"]), rep
    else:
        # at a width of 64 and three layers bf16's rounding is not yet averaged out of the logits (a
        # held expert enters with a weight near 2.5 / 4); each mechanism by itself is inside its bound
        assert rep["logit_max_abs_err"] < 1.0 and all(m["error"] <= m["tolerance"] for m in rep["mechanism"]), rep
    ref = np.asarray(reference.forward(
        params, np.asarray(streams[1]["prompt_ids"] + streams[1]["served"][:5]), cfg))[-1]
    wrong = [dict(s) for s in streams]
    wrong[1]["served"] = streams[1]["served"][:5] + [int(np.argmin(ref))] + streams[1]["served"][6:]
    bad = check_serving(cb, wrong, reference)
    assert not bad["ok"] and bad["regret_max"] > reference.REGRET_MAX_TOL


# -- the lower precisions that each mechanism's number is there to catch ------------
# Planted in the program's own functions once the streams are served (the chip's
# controls are these, by the same names: PERF.md section 6, PR 41).


def mantissa_bits(x, bits: int):
    """x rounded to `bits` bits of mantissa by arithmetic on its float32 form
    (float8 e4m3 keeps 3): the chip's compiler takes a cast to float8 and back
    inside one program for nothing (references/olmoe.py)."""
    u = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    drop = 23 - bits
    u = (u + jnp.uint32(1 << (drop - 1))) & jnp.uint32(~((1 << drop) - 1) & 0xFFFFFFFF)
    return jax.lax.bitcast_convert_type(u, jnp.float32).astype(x.dtype)


def float8_latent_cache(generate):
    """The decode core reads a latent cache that was rounded to float8's 3 bits."""
    inner = generate._latent_attention

    def latent_attention(q_lat, q_rope, ckv, kr, *a, **k):
        return inner(q_lat, q_rope, mantissa_bits(ckv, 3), mantissa_bits(kr, 3), *a, **k)

    return {"_latent_attention": latent_attention}


def bf16_scores(generate):
    """The absorbed scores accumulated in bf16 (the program: float32)."""
    inner = generate._masked_attention

    def masked_attention(*a, **k):
        with pytest.MonkeyPatch.context() as m:
            einsum = jnp.einsum

            def low(spec, x, y, preferred_element_type=None, **kw):
                if spec == "bqgrd,bkgd->bgrqk":
                    return einsum(spec, x.astype(jnp.bfloat16), y.astype(jnp.bfloat16),
                                  preferred_element_type=jnp.bfloat16, **kw).astype(jnp.float32)
                return einsum(spec, x, y, preferred_element_type=preferred_element_type, **kw)

            m.setattr(jnp, "einsum", low)
            return inner(*a, **k)

    return {"_masked_attention": masked_attention}


def bf16_router(moe):
    """The router's sigmoid and its top-k in bf16 (the program: float32)."""
    inner = moe.routed_ffn

    def routed(*a, **k):
        with pytest.MonkeyPatch.context() as m:
            sigmoid = jax.nn.sigmoid
            m.setattr(jax.nn, "sigmoid", lambda v: sigmoid(v.astype(jnp.bfloat16)))
            return inner(*a, **k)

    return {"routed_ffn": routed}


# name: (the module it is planted in, how, the number it moves, whether that number passes its
# tolerance at a test's widths: scores over 40 columns near 1 lose little in bf16, those of the
# cell, over 576 columns, fail the bound on the chip)
CONTROLS = {"float8-latent-cache": ("generate", float8_latent_cache, "mla_absorb_rel_err", True),
            "bf16-scores": ("generate", bf16_scores, "mla_absorb_rel_err", False),
            "bf16-router": ("moe", bf16_router, "moe_router_other_set", True)}


@pytest.mark.parametrize("control", [None, *CONTROLS], ids=["program", *CONTROLS])
def test_each_mechanism_is_held_by_itself(control, monkeypatch):
    """The serving check at a test's widths with float32 weights: the program
    passes, and each lower precision that the logits cannot see on the chip,
    planted once the streams are served, fails the mechanism's number that is
    its own while the three numbers on the logits pass."""
    from cluster_anywhere_tpu.models import generate
    from cluster_anywhere_tpu.parallel import moe

    cfg, params = program(jnp.float32)
    cb, streams = _served_together(cfg, params)
    fails, moved = set(), None
    if control is not None:
        where, plant, moved, over = CONTROLS[control]
        fails = {moved} if over else set()
        module = {"generate": generate, "moe": moe}[where]
        for name, fn in plant(module).items():
            monkeypatch.setattr(module, name, fn)
    rep = check_serving(cb, streams, reference)
    got = {m["name"]: m for m in rep["mechanism"]}
    assert got["mla_absorb_rel_err"]["tolerance"] == reference.MLA_ABSORB_ERR_TOL
    assert got["moe_router_other_set"]["tolerance"] == reference.MOE_ROUTER_SET_TOL
    assert got["moe_experts_rel_err"]["tolerance"] == reference.MOE_EXPERTS_ERR_TOL
    # every decode row at every layer; every position at every expert layer
    assert f"over {3 * 8 * 3} (row, layer) pairs" in got["mla_absorb_rel_err"]["why"]
    assert f"of {2 * (11 + 40 + 70 + 3 * 8)} in which" in got["moe_router_other_set"]["why"]
    assert {n for n, m in got.items() if not m["error"] <= m["tolerance"]} == fails, got
    # float32 weights: the program's own error is rounding's 1e-6, the control's a thousand times that
    assert moved is None or got[moved]["error"] > 1e-3, got
    assert rep["logit_max_abs_err"] < 1e-3 and rep["regret_max"] < 1e-3 and rep["regret_mean"] < 1e-3, rep
    assert rep["ok"] is (not fails), rep
    assert not reference._given  # what `chosen_logits` kept, `mechanism_checks` took
    if control is None:
        assert got["moe_router_other_set"]["error"] == 0.0
        assert got["moe_experts_rel_err"]["error"] < 1e-5 and got["mla_absorb_rel_err"]["error"] < 1e-5
        alone = reference.mechanism_checks(cb, streams)
        assert [m["error"] for m in alone] == [m["error"] for m in rep["mechanism"]]
        monkeypatch.setattr(reference, "MLA_ABSORB_ERR_TOL", rep["mechanism"][0]["error"] / 2)
        assert not check_serving(cb, streams, reference)["ok"]


def test_the_mechanism_enters_the_programs_own_functions_at_the_served_shapes(monkeypatch):
    """`mechanism_checks` calls the decode core and the expert layer as the
    served programs do: a batcher that serves the check streams traces
    `_latent_decode_core` and `_moe` with the shapes the check gives them."""
    from cluster_anywhere_tpu.models import generate, transformer

    # a configuration no other test of this process has traced: the calls are seen at trace time
    cfg, params = program(jnp.float32, moe_intermediate_size=40, num_experts_per_tok=3)
    seen = {"core": set(), "moe": set()}
    core, moe_ = generate._latent_decode_core, transformer._moe

    def spy_core(bp, cache, layer, pos, pads, cfg_, q, k_rope, c_kv):
        seen["core"].add((q.shape, cache["ckv"].shape[1:], cache["kr"].shape[1:]))
        return core(bp, cache, layer, pos, pads, cfg_, q, k_rope, c_kv)

    def spy_moe(bp, y, cfg_, live=None, experts=None):
        if cfg_.experts_held is not None:  # the probe's call goes through the same router without a share
            seen["moe"].add((y.shape, experts[0]["w_gate"].shape[1:]))
        return moe_(bp, y, cfg_, live, experts)

    monkeypatch.setattr(generate, "_latent_decode_core", spy_core)
    monkeypatch.setattr(transformer, "_moe", spy_moe)
    monkeypatch.setattr(generate, "_moe", spy_moe, raising=False)
    cb, streams = _served_together(cfg, params)
    served = {k: set(v) for k, v in seen.items()}
    assert served["core"] == {((4, 1, 4, 24), (4, 128, 32), (4, 128, 128))}
    assert served["moe"] == {((1, 32, 64), (4, 64, 40)), ((1, 64, 64), (4, 64, 40)), ((1, 96, 64), (4, 64, 40)),
                             ((4, 1, 64), (4, 64, 40))}
    for v in seen.values():
        v.clear()
    reference.mechanism_checks(cb, streams)
    assert seen == served


def test_the_new_readers_against_hand_counts():
    cell = manifest.load_cell(CELL)
    config = cell["config_file"]["config"]
    span = lambda start, **args: [1, float(start), 8e6, "llm.step", args]
    op = lambda start, dur, scope, name="%fusion.7 = bf16[32,64] fusion()": [float(start), float(dur), name, scope]
    flash = "%flash_fwd.3 = bf16[64,4096,128] custom-call()"
    events = {"spans": [span(0, live=6, moe_rows=6, moe_held_assignments=3.0),
                        span(10e6, live=5, moe_rows=5, moe_held_assignments=2.0), span(20e6, live=0)],
              "ops": {"/device:TPU:0": [op(0, 2e6, "attn.core"), op(2e6, 1e6, "attn.cache"), op(3e6, 1e6, "attn.mla.absorb"),
                                        op(4e6, 3e6, "attn.mla.expand"), op(7e6, 5e6, "attn.core", flash),
                                        op(12e6, 2e6, "moe.shared"), op(14e6, 4e6, "ffn"), op(18e6, 2e6, "")]}}
    ctx = {"cell": cell, "program_trace": events, "device": {"kind": "TPU v5 lite"},
           "replica": {"steps": [], "admits": [], "first": {}, "stats": {"cache_bytes_per_token": 8960}},
           "records": [], "t_open": 0.0, "seconds": 1.0}
    mla = manifest.load_reader("mla")
    # two steps read live rows: 2 x 1.123 GB at 819 GB/s is 2.742 ms (the operations, 2 x 135.7 G
    # at 197 T, are 1.378 ms: memory bounds it); 3 ms under attn.core and attn.cache, the kernel left out
    by_bytes = 2 * 7 * 32 * 4352 * 1152 / 819e9
    by_flops = 2 * 2.0 * 7 * 32 * 4352 * 64 * (2 * 512 + 64) / 197e12
    assert by_flops < by_bytes and reference.mla_core_flops(config, 32, 4352) == pytest.approx(by_flops / 2 * 197e12)
    assert mla(ctx, what="core_roofline") == pytest.approx(100 * by_bytes / 3e-3)
    assert mla(ctx, what="expand_share") == pytest.approx(100 * 8 / 20)
    with pytest.raises(ValueError):
        mla(ctx, what="flops")
    got = manifest.read_layer_metrics(CELL, ctx)
    assert got["mla_core_hbm_share.mla"]["value"] == pytest.approx(100 * by_bytes / 3e-3)
    assert got["mla_expand_share.mla"]["value"] == pytest.approx(40.0)
    assert got["mla_share.mla"]["value"] == pytest.approx(100 * 12 / 20)  # attn.mla.*, attn.core, attn.cache
    assert got["cache_bytes_per_token.mla"] == {"value": 8960.0, "unit": "bytes"}
    assert got["shared_expert_share.mla"]["value"] == pytest.approx(10.0)
    assert got["ffn_share.mla"]["value"] == pytest.approx(30.0)  # ffn and every moe.* under it
    assert got["held_assignments_share.mla"]["value"] == pytest.approx(100 * 5 / (11 * 8))
    # a program without the scopes (another architecture, an older program): nothing; no step with
    # live rows: no roofline; a program without the count: nothing
    other = copy.deepcopy(events)
    other["ops"] = {"/device:TPU:0": [op(0, 2e6, "attn.core"), op(2e6, 1e6, "ffn")]}
    assert mla(dict(ctx, program_trace=other), what="core_roofline") is None
    assert mla(dict(ctx, program_trace=other), what="expand_share") is None
    idle = copy.deepcopy(events)
    idle["spans"] = [span(0, live=0)]
    assert mla(dict(ctx, program_trace=idle), what="core_roofline") is None
    assert mla(dict(ctx, program_trace=None), what="expand_share") is None
    assert mla(dict(ctx, cell=manifest.load_cell("chat-closed6")), what="expand_share") is None
    stat = manifest.load_reader("replica_stat")
    assert stat({"replica": {"stats": {}}}, stat="cache_bytes_per_token") is None and stat({}, stat="x") is None


def test_serve_rehearsal_of_axk1_rag_closed6():
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
    assert set(mechanism) == {"mla_absorb_rel_err", "moe_router_other_set", "moe_experts_rel_err"}
    assert all(m["error"] <= m["tolerance"] for m in mechanism.values()), mechanism
    stats = ctx["replica"]["stats"]
    assert stats["moe_assignments"] > 0 and stats["cache_bytes_per_token"] == 3 * (32 + 128) * 2
    layer = manifest.read_layer_metrics(CELL, ctx)
    assert layer["decode_batch_mean.closed"]["value"] >= 1.0 and layer["cache_bytes_per_token.mla"]["value"] == 960.0
    assert not {"mla_share.mla", "mla_core_hbm_share.mla", "attn_share.closed", "ffn_share.mla"} & set(layer)
    ctx["device"].update(platform="tpu", kind="TPU v5 lite", count=1)
    line = bench_run.result_line(ctx["cell"], serve_driver, ctx, trace=False)
    assert set(line["metrics"]) == {"setup_s", "serve_out_tok_s"} and line["correct"]
