"""SDAR's plain reference (references/sdar.py, loaded as the harness loads it)
and its three hooks against the program at a small size on the CPU: the
configuration file against the published keys, the serving check through a tiny
batcher (the replay of the passes from the batcher's record, the program's first
pass, the expert layer in the program's own shapes, the order of the reveal),
what the check catches, the counts against hand counts, the two readers the cell
brings, and `sdar-closed6` rehearsed at tiny widths through serve.run, proxy,
router and replica."""

import copy
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import cluster_anywhere_tpu as ca
from benchmarks import run as bench_run
from benchmarks.harness import manifest, program_trace, serve_driver
from benchmarks.harness.reference import check_serving
from cluster_anywhere_tpu.llm import continuous
from cluster_anywhere_tpu.llm.continuous import ContinuousBatcher
from cluster_anywhere_tpu.models.transformer import TransformerConfig, init_params

CELL = "sdar-closed6"
reference = manifest.load_reference("sdar")
# 8 experts, 2 a token, 4 query heads on 2 cached ones: the published block at a test's widths
TINY = dict(hidden_size=64, num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            moe_intermediate_size=48, vocab_size=512, num_experts=8, num_experts_per_tok=2, mask_token_id=511)
# what the catalog's row holds under `config` (model-configs/architectures.jsonl, SDAR-30B-A3B-Chat)
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 6144, "max_position_embeddings": 32768, "max_window_layers": 48, "mlp_only_layers": [],
    "model_type": "sdar_moe", "moe_intermediate_size": 768, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts": 128, "num_experts_per_tok": 8, "num_hidden_layers": 48, "num_key_value_heads": 4,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "use_sliding_window": False, "vocab_size": 151936,
}


def tiny_config(**over):
    cell = copy.deepcopy(manifest.load_cell(CELL))
    cell["config_file"]["config"].update(TINY, **over)
    return cell


def program(dtype, **over):
    cell = tiny_config(**over)
    cfg = TransformerConfig(**reference.program_config(
        cell["config_file"], vocab_size=TINY["vocab_size"], dtype=dtype, param_dtype=dtype))
    params = init_params(jax.random.key(3), cfg)
    blocks = params["blocks"]  # the norms' weights off 1, so a norm left out or laid over the vector shows
    blocks["q_norm"] = blocks["q_norm"] * jnp.linspace(0.5, 1.5, blocks["q_norm"].shape[-1]).astype(dtype)
    blocks["k_norm"] = blocks["k_norm"] * jnp.linspace(1.4, 0.6, blocks["k_norm"].shape[-1]).astype(dtype)
    return cfg, params


def served_together(cfg, params, lens=(13, 40, 70, 23), new_tokens=10):
    """A batcher alone and the streams it served together, as `bench_check`
    hands them to `check_serving`: prompts that leave 1, 0, 2 and 3 tokens in
    the first answer block, answers that end inside a block."""
    cb = ContinuousBatcher(params, cfg, slots=6, t_max=128, prefill_buckets=(32, 64, 96), prefix_cache_entries=0)
    rng = np.random.default_rng(5)
    reqs = [cb.submit(rng.integers(0, 511, n), max_new_tokens=new_tokens) for n in lens]
    cb.pump()
    return cb, [{"prompt_ids": r.prompt_ids.tolist(), "served": list(r.out_tokens),
                 "request_id": r.request_id} for r in reqs]


def test_the_configuration_is_the_catalogs_and_builds_the_published_block():
    cell = manifest.load_cell(CELL)
    doc = cell["config_file"]
    assert doc["published"] == PUBLISHED and doc["source"].endswith("JetLM/SDAR-30B-A3B-Chat/blob/main/config.json")
    # the top level is the catalog's keys as run: depth alone is cut
    assert {k: doc[k] for k in PUBLISHED} == dict(PUBLISHED, num_hidden_layers=7)
    assert list(doc["reduced"]) == ["num_hidden_layers"] and doc["reduced"]["num_hidden_layers"]["here"] == 7
    entry = next(c for c in manifest.load_manifest()["configs"] if c["name"] == cell["config"])
    assert entry["reduced"] == ["num_hidden_layers"] and entry["source"] == doc["source"]
    run = doc["config"]
    assert {k: run[k] for k in PUBLISHED} == dict(PUBLISHED, num_hidden_layers=7)
    assert (run["block_length"], run["denoising_steps"], run["confidence_threshold"], run["mask_token_id"]) == (
        4, 4, 0.9, 151669) and run["remasking_strategy"] == "low_confidence_dynamic"
    assert {"block_length", "denoising_steps", "confidence_threshold", "mask_token_id"} <= set(doc["assumed"])
    cfg = TransformerConfig(**reference.program_config(doc))
    assert (cfg.d_model, cfg.n_layers, cfg.n_heads, cfg.n_kv_heads, cfg.d_head) == (2048, 7, 32, 4, 128)
    assert (cfg.n_experts, cfg.n_experts_per_tok, cfg.d_ff, cfg.rope_theta) == (128, 8, 768, 1e6)
    assert cfg.moe_gated and cfg.moe_renormalize and cfg.qk_norm and cfg.qk_norm_per_head
    assert (cfg.block_length, cfg.mask_token_id, cfg.denoise_steps, cfg.confidence_threshold) == (4, 151669, 4, 0.9)
    shapes = jax.eval_shape(lambda k: init_params(k, TransformerConfig(vocab_size=151936, **reference.program_config(doc))),
                            jax.random.key(0))
    blocks = shapes["blocks"]
    assert blocks["w_gate"].shape == blocks["w_up"].shape == (7, 128, 2048, 768)
    assert blocks["q_norm"].shape == blocks["k_norm"].shape == (7, 128) and blocks["wk"].shape == (7, 2048, 512)
    assert shapes["lm_head"].shape == (2048, 151936)
    assert sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(shapes)) == reference.param_count(run)
    # the traffic is chat-closed's to the letter but for the check's prompts
    mine, theirs = cell["traffic_file"], manifest.load_cell("chat-closed6")["traffic_file"]
    assert {k: v for k, v in mine.items() if k != "check"} == {k: v for k, v in theirs.items() if k != "check"}
    assert [n % 4 for n in mine["check"]["stream_prompt_lens"]] == [1, 2, 3, 0] and mine["check"]["repeat_prompt_len"] == 202
    assert cell["callers"] == manifest.load_cell("chat-closed6")["callers"] == 6
    # `block.choose` is this architecture's scope, beside the expert path's four
    scopes, kernels = program_trace.known_names({"cell": cell})
    op = "jit(_pass_step_rowpos)/block.choose/reduce_max"
    assert program_trace.scope_of(op, scopes) == "block.choose" and "ragged-dot-none" in kernels
    assert all(hasattr(reference, name) for name in manifest.REFERENCE_OPTIONAL)


def test_a_checkout_that_cannot_generate_by_blocks_says_so_before_a_replica_starts(monkeypatch):
    monkeypatch.setattr(reference, "PROGRAM_FIELDS", reference.PROGRAM_FIELDS + ("speculation_depth",))
    with pytest.raises(NotImplementedError, match="speculation_depth.*cannot generate by blocks"):
        reference.program_config(manifest.load_cell(CELL)["config_file"])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
def test_serving_check_replays_the_passes_from_the_batchers_record(dtype):
    cfg, params = program(dtype)
    cb, streams = served_together(cfg, params)
    rep = check_serving(cb, streams, reference)
    assert rep["streams"] == 4 and rep["positions"] == 40, rep
    assert [m["name"] for m in rep["mechanism"]] == [
        "moe_router_other_set", "moe_experts_rel_err", "reveal_regret_max", "choose_regret_max"]
    assert not reference._kept  # what `chosen_logits` kept, `mechanism_checks` took
    if dtype == jnp.bfloat16:
        # at 8 experts of width 48 one near-tie of the router moves a logit by more than the
        # chip's bound: what bf16 shows here is that the replay runs and the expert layer holds
        assert rep["agree_share"] > 0.7 and rep["mechanism"][1]["error"] <= reference.MOE_EXPERTS_ERR_TOL, rep
        return
    # float32 both sides: every served token is the reference's own best, in the reference's own order
    assert rep["ok"] and rep["logit_max_abs_err"] < 1e-3 and rep["regret_max"] == 0.0 and rep["agree_share"] == 1.0, rep
    assert [m["error"] for m in rep["mechanism"]] == pytest.approx([0.0, 0.0, 0.0, 0.0], abs=1e-5)
    # the program's first pass is held to the rows of the tokens it fixed
    logits, rows = reference.program_logits(cb, streams[0])
    assert logits.shape == (len(rows), 512) and rows and all(cb.fixed_at(streams[0]["request_id"])[j] == 0 for j in rows)
    # a token the reference ranks low is caught
    wrong = [dict(s) for s in streams]
    row = reference.chosen_logits(cb, streams[1])[5]
    wrong[1]["served"] = streams[1]["served"][:5] + [int(np.argmin(row))] + streams[1]["served"][6:]
    bad = check_serving(cb, wrong, reference)
    assert not bad["ok"] and bad["regret_max"] > reference.REGRET_MAX_TOL
    # another order's record: the same tokens said to be fixed first to last are another generation
    in_order = types.SimpleNamespace(**{k: getattr(cb, k) for k in ("params", "cfg", "slots", "t_max", "block_plan",
                                                                    "block_tail", "_bucket")})
    first = {s["request_id"]: 4 - len(s["prompt_ids"]) % 4 for s in streams}  # the first block's share of the answer
    in_order.fixed_at = lambda rid: list(range(first[rid])) + [p % 4 for p in range(10 - first[rid])]
    assert cb.fixed_at(streams[1]["request_id"]) != in_order.fixed_at(streams[1]["request_id"])
    other = check_serving(in_order, streams, reference)
    assert not other["ok"] and (other["regret_max"] > reference.REGRET_MAX_TOL
                                or other["mechanism"][2]["error"] > reference.REVEAL_REGRET_TOL), other
    # and under the causal default (one next token a step from the last position's logits:
    # a reference that brings no `chosen_logits`) the same streams are another model's
    causal = types.SimpleNamespace(**{k: getattr(reference, k) for k in manifest.REFERENCE_INTERFACE},
                                   program_logits=reference.program_logits)
    read_causally = check_serving(cb, streams, causal)
    assert not read_causally["ok"] and read_causally["regret_max"] > reference.REGRET_MAX_TOL
    reference._kept.clear()


def _float8_experts(routed_ffn):
    def routed(x, router, experts, layer=0, **kw):
        rounded = {n: w[layer][None].astype(jnp.float8_e4m3fn).astype(w.dtype) for n, w in experts.items()}
        return routed_ffn(x, router, rounded, 0, **kw)
    return routed


def _bf16_softmax(routed_ffn):
    def routed(*args, **kw):
        with pytest.MonkeyPatch.context() as m:
            softmax = jax.nn.softmax
            m.setattr(jax.nn, "softmax", lambda v, axis=-1: softmax(v.astype(jnp.bfloat16), axis=axis))
            return routed_ffn(*args, **kw)
    return routed


@pytest.mark.parametrize("variant, fails", [
    (None, set()), (_float8_experts, {"moe_experts_rel_err"}), (_bf16_softmax, {"moe_router_other_set"}),
], ids=["program", "float8-experts", "bf16-softmax"])
def test_the_expert_layer_is_held_by_itself_in_the_pass_shape(variant, fails, monkeypatch):
    """As tests/benchmark/test_benchmark_olmoe.py for OLMoE's step: each lower
    precision that the logits cannot see, planted in `routed_ffn` once the
    streams are served, fails the mechanism's number that is its own while the
    three numbers on the logits pass."""
    from cluster_anywhere_tpu.parallel import moe

    cfg, params = program(jnp.float32, num_experts=32, num_experts_per_tok=4)
    cb, streams = served_together(cfg, params)
    reference.program_logits(cb, streams[0])  # the check's pass of one block is the program's, traced as it is
    if variant is not None:
        monkeypatch.setattr(moe, "routed_ffn", variant(moe.routed_ffn))
    rep = check_serving(cb, streams, reference)
    got = {m["name"]: m for m in rep["mechanism"]}
    # every position of every stream through its answer's last block, at both layers
    rows = sum(-(-(n + 10) // 4) * 4 for n in (13, 40, 70, 23))
    assert f"of {2 * rows} in which" in got["moe_router_other_set"]["why"]
    assert {n for n, m in got.items() if not m["error"] <= m["tolerance"]} == fails, got
    assert rep["logit_max_abs_err"] < 1e-3 and rep["regret_max"] < 1e-3 and rep["ok"] is (not fails), rep


def test_the_order_of_the_reveal_is_held_where_the_tokens_regrets_see_nothing(monkeypatch):
    """A step that fixes the LEAST confident masked position serves tokens that
    are each the best of the pass that fixed them: no regret, the logits the
    program's own.  `reveal_regret_max` alone says that the rule was another."""
    inner = continuous._choose_block

    def least_confident(logits, fixed, live, temps, rng, cfg):
        # the same tokens; the order turned round by flattening the logits of the confident
        tok, _ = inner(logits, fixed, live, temps, rng, cfg)
        log_conf = jnp.max(jax.nn.log_softmax(logits, axis=-1), axis=-1)
        conf = jnp.where(~fixed & live[:, None], -log_conf, -jnp.inf)
        return tok, (conf == jnp.max(conf, axis=-1, keepdims=True)) & ~fixed & live[:, None]

    monkeypatch.setattr(continuous, "_choose_block", least_confident)
    cfg, params = program(jnp.float32, moe_intermediate_size=40)  # a step no other test has traced
    cb, streams = served_together(cfg, params)
    rep = check_serving(cb, streams, reference)
    got = {m["name"]: m["error"] for m in rep["mechanism"]}
    assert rep["regret_max"] == 0.0 and rep["logit_max_abs_err"] < 1e-3
    assert got["reveal_regret_max"] > reference.REVEAL_REGRET_TOL and not rep["ok"], rep
    # and the rule by itself, given the reference's own logits, is seen to be another
    assert got["choose_regret_max"] > reference.CHOOSE_REGRET_TOL


def test_the_mechanism_enters_the_expert_layer_as_the_served_programs_do(monkeypatch):
    """A batcher that serves the check streams traces `transformer._moe` with
    the shapes, the types and the unsliced stack that `mechanism_checks` gives
    it, and no others: each prompt's whole blocks alone [1, bucket, E], and the
    pass [slots, 8, E] with its live mask [slots, 8]: the block the pass stores
    and the block it works on (`_pass_step_rowpos`, since PR 51)."""
    from cluster_anywhere_tpu.models import transformer
    from cluster_anywhere_tpu.parallel import moe

    cfg, params = program(jnp.bfloat16, num_experts=16, num_experts_per_tok=3, moe_intermediate_size=24)
    calls, inner_moe = [], transformer._moe

    def seen_moe(bp, y, cfg_, live=None, experts=None):
        stack, layer = experts
        if live is None:
            return inner_moe(bp, y, cfg_, live, experts)  # the check's batch of one (`program_logits`): every row live
        calls.append((y.shape, str(y.dtype), live.shape, str(live.dtype), isinstance(layer, jax.core.Tracer),
                      tuple(sorted((n, w.shape) for n, w in stack.items() if "w_in" not in stack))))
        return inner_moe(bp, y, cfg_, live, experts)

    monkeypatch.setattr(transformer, "_moe", seen_moe)
    cb, streams = served_together(cfg, params)
    served = set(calls)
    stack = tuple(sorted((n, params["blocks"][n].shape) for n in moe.EXPERT_MATRICES if n in params["blocks"]))
    assert served == {((1, b, 64), "bfloat16", (1, b), "bool", True, stack) for b in (32, 64, 96)} | {
        ((6, 8, 64), "bfloat16", (6, 8), "bool", True, stack)}
    del calls[:]
    numbers = reference.mechanism_checks(cb, streams)
    assert all(m["error"] <= m["tolerance"] for m in numbers[:2]), numbers  # the expert layer's two
    assert {c for c in calls if c[-1]} == served and {c[:4] for c in calls} == {c[:4] for c in served}
    for s in streams:
        reference._replay(cb, s)
    prefills, passes, read, n = reference.program_shapes(cb, streams)
    reference._kept.clear()
    # 13 + 10 -> 24 rows, 40 + 10 -> 52, 70 + 10 -> 80, 23 + 10 -> 36; whole blocks 12, 40, 68, 20
    assert n == 24 + 52 + 80 + 36 and [(t, pad) for _, t, pad in prefills] == [(12, 20), (40, 24), (68, 28), (20, 12)]
    assert passes.shape == read.shape == (4, 6, 8)
    # a slot's first pass has nothing to store; its second stores the first's block before its own
    assert passes[0, 0].tolist() == [n] * 4 + [12, 13, 14, 15] and passes[0, 1].tolist() == [n] * 4 + [64, 65, 66, 67]
    assert passes[1, 0].tolist() == [12, 13, 14, 15, 16, 17, 18, 19]
    # the fourth stream's answer takes a block more than the others': their slots are not live in that step
    assert (passes[:, 4:] == n).all() and passes[2, 0, 4] == 20 and (passes[3, :3] == n).all()
    assert passes[3, 3].tolist() == list(range(n - 8, n))
    # every row of the answers' blocks is read once: where a pass stores it, the last block in its own pass
    assert not read[passes == n].any() and not read[0, :, :4].any() and read[1:, :, :4][passes[1:, :, :4] < n].all()
    assert sorted(passes[read].tolist()) == [
        *range(12, 24), *range(24 + 40, 24 + 52), *range(76 + 68, 76 + 80), *range(156 + 20, n)]
    assert read[2, :3, 4:].all() and read[3, 3, 4:].all() and read[:, :, 4:].sum() == 4 * 4


def test_counts_against_hand_counts():
    c = dict(hidden_size=8, num_attention_heads=4, num_key_value_heads=2, head_dim=4, moe_intermediate_size=16,
             num_hidden_layers=3, vocab_size=32, num_experts=4, num_experts_per_tok=2, block_length=4)
    # a layer: wq 8*16, wk and wv 8*8, wo 16*8, a norm of 4 over a head of q and of k, the
    # router 8*4, 4 experts of three 8*16 matrices, the block's two norms of 8
    attention, expert = 8 * 16 + 2 * 8 * 8 + 16 * 8 + 8, 3 * 128
    per_layer = attention + 32 + 4 * expert + 16
    assert reference.param_count(c) == 3 * per_layer + 2 * 32 * 8 + 8
    assert reference.expert_bytes(c) == 2 * expert
    weights = (attention - 8) + 32 + 2 * expert
    fwd = 5 * 2 * weights * 3 + 4 * 5 * 5 * 4 * 4 * 3 + 5 * 2 * 8 * 32
    assert reference.train_flops_per_step(c, batch=2, seq=5) == 2 * 3 * fwd
    # one pass at [slots, 4]: every live slot's four positions take their experts
    outside = 3 * (attention + 32 + 16) + 32 * 8 + 8 + 2 * 4 * 8
    cache = 2 * 3 * 2 * 12 * 2 * 4
    touched = lambda rows: 4 * (1 - 0.5 ** rows)
    assert reference.decode_step_bytes(c, slots=2, t_max=12) == int(2 * (outside + 3 * touched(8) * expert + cache))
    assert reference.decode_step_bytes(c, slots=2, t_max=12, touched=1.5) == int(2 * (outside + 3 * 1.5 * expert + cache))
    # the published model: 30.5 B parameters at 48 layers, 3.3 B of them met by a token; the cut 9.97 GB
    doc = manifest.load_cell(CELL)["config_file"]
    pub = dict(doc["config"], num_hidden_layers=48)
    assert reference.expert_params(pub) == 4_718_592 and reference._attention_params(pub) == 18_874_368 + 256
    assert 30.4e9 < reference.param_count(pub) < 30.6e9
    assert 3.2e9 < reference.param_count(pub) - 48 * 120 * reference.expert_params(pub) < 3.4e9
    assert 9.96e9 < 2 * reference.param_count(doc["config"]) < 9.98e9
    assert 99 < reference.experts_touched(pub, 24) < 101 and reference.experts_touched(pub, 128) > 127.9
    with pytest.raises(NotImplementedError, match="no training cell"):
        reference.loss(None, None, None)


def test_the_cells_two_readers():
    cell = manifest.load_cell(CELL)
    step = lambda start, **args: [1, float(start), 20e6, "llm.step", args]
    op = lambda start, dur, scope: [float(start), float(dur), "%fusion.7 = bf16[128,2048] fusion()", scope]
    events = {"spans": [step(0, live=6, block_rows=24, tokens_out=5, tokens_fixed=6, store_rows=1, moe_experts_touched=60.0),
                        step(30e6, live=5, block_rows=20, tokens_out=3, tokens_fixed=4, store_rows=2, moe_experts_touched=50.0),
                        step(60e6, live=0)],
              "ops": {"/device:TPU:0": [op(1e6, 15e6, "ffn"), op(31e6, 14e6, "block.choose"), op(50e6, 1e6, "")]}}
    ctx = {"cell": cell, "program_trace": events, "device": {"kind": "TPU v5 lite"}}
    ratio = manifest.load_reader("span_arg_ratio")
    assert ratio(ctx, span="llm.step", over="live", under="tokens_out") == pytest.approx(11 / 8)
    assert ratio(ctx, span="llm.step", over="store_rows", under="live", scale=100.0) == pytest.approx(100 * 3 / 11)
    assert ratio(ctx, span="llm.step", over="live", under="ssm_state_bytes") is None
    c, dep = cell["config_file"]["config"], cell["traffic_file"]["deployment"]
    want = sum(reference.decode_step_bytes(c, dep["slots"], 768, touched=n) for n in (60.0, 50.0)) / (30e-3 * 819e9)
    assert manifest.load_reader("pass_hbm")(ctx) == pytest.approx(100 * want) and 0.3 < want < 0.5
    got = manifest.read_layer_metrics(CELL, dict(ctx, replica={"steps": [], "admits": [], "first": {}},
                                                 records=[], t_open=0.0, seconds=1.0))
    assert got["passes_per_token.blk"]["value"] == pytest.approx(1.375)
    assert got["store_pass_share.blk"]["value"] == pytest.approx(100 * 3 / 11)
    assert got["step_tokens_out_mean.blk"]["value"] == pytest.approx(4.0)
    assert got["choose_share.blk"]["value"] == pytest.approx(100 * 14 / 30)
    assert got["pass_hbm_share.blk"]["value"] == pytest.approx(100 * want)
    # a program without the attributes (the parent, a causal model): nothing to read, and nothing raised
    older = dict(events, spans=[[1, 0.0, 20e6, "llm.step", {"live": 6}]])
    assert manifest.load_reader("pass_hbm")(dict(ctx, program_trace=older)) is None
    assert ratio(dict(ctx, program_trace=older), span="llm.step", over="live", under="tokens_out") is None
    assert manifest.load_reader("pass_hbm")(dict(ctx, program_trace=None)) is None
    # every metric of the cell in BENCHMARK.json has its file, and the other way round: five of
    # its own, the rest through the families it joins (`causal` is not one: a block admit hands out no token)
    named = {m["name"] for m in manifest.load_manifest()["per_layer"] if m.get("workloads") == [CELL]}
    mine = manifest.layer_metrics_for(CELL)
    assert named == {m["name"] for m in mine if m.get("cells") == [CELL]} and len(named) >= 5
    assert {m["family"] for m in mine if "family" in m} == set(cell["families"]) >= {"closed", "attn", "moe"}
    assert "decode_batch_mean.closed" not in {m["name"] for m in mine}


def test_serve_rehearsal_of_sdar_closed6():
    """The cell at tiny widths through the program's normal path on the CPU
    backend (a TPU resource that is only a number)."""
    cell = tiny_config()
    cell.update(callers=3)
    cell["traffic_file"].update(
        ramp_s=0.5, drain_s=60.0, warmup_prompt_lens=[20, 70],
        prompt_len=dict(dist="lognormal", median=24, sigma=0.5, min=8, max=80),
        output_len=dict(dist="lognormal", median=6, sigma=0.3, min=4, max=12),
        # 40 tokens a check stream: 50 passes each, so that the three overlap on a loaded host too
        check=dict(stream_prompt_lens=[13, 30, 71], stream_new_tokens=40, repeat_prompt_len=42, repeat_new_tokens=5),
        deployment=dict(slots=4, max_prompt_len=96, max_new_tokens=48, prefix_cache_entries=0),
    )
    if ca.is_initialized():
        ca.shutdown()
    ca.init(num_cpus=4, num_tpus=1)
    try:
        ctx = serve_driver.measure(cell, seed=3_000_000_019, seconds=3.0, trace=False, t_start=time.monotonic())
    finally:
        ca.shutdown()
    out = serve_driver.outcome(ctx)
    assert out["failed"] == 0 and out["attempted"] >= 3, out
    check = ctx["check"]
    assert check["streams"] == 3 and check["positions"] == 120 and check["decode_requests_mean"] > 1.0, check
    # bf16 at 8 experts of width 48 is louder than the chip's bounds allow (one near-tie of the
    # router moves a logit by 1): the verdict is the chip's to give, the plumbing is held here
    assert check["repeat_identical"] and out["correct"] == check["ok"], check
    assert check["regret_mean"] < 0.1 and check["agree_share"] > 0.8, check
    mechanism = {m["name"]: m for m in check["mechanism"]}
    assert set(mechanism) == {"moe_router_other_set", "moe_experts_rel_err", "reveal_regret_max", "choose_regret_max"}
    # what is exact in any precision: the rule by itself, and the router on the same rounded rows
    assert mechanism["choose_regret_max"]["error"] == 0.0 and mechanism["moe_router_other_set"]["error"] <= 0.01
    assert mechanism["moe_experts_rel_err"]["error"] <= reference.MOE_EXPERTS_ERR_TOL, mechanism
    stats = ctx["replica"]["stats"]
    # every token came out of a pass; an admit handed out none
    assert stats["block_passes"] > stats["tokens_out"] > 0 and stats["block_tokens_fixed"] >= stats["tokens_out"]
    assert stats["tokens_out"] == sum(len(r["tokens"]) for r in ctx["records"]) + 3 * 40 + 2 * 5 + 2 * 4
    layer = manifest.read_layer_metrics(CELL, ctx)
    assert {n + ".closed" for n in ("gen_late_p99_ms", "front_overhead_p50_ms", "admit_ms_mean",
                                 "decode_step_ms_p50", "gap_p99_s", "ttft_p50_s")} <= set(layer)
    assert not {"device_idle.closed", "moe_experts_share.moe", "passes_per_token.blk", "pass_hbm_share.blk", "choose_share.blk"} & set(layer)
    ctx["device"].update(platform="tpu", kind="TPU v5 lite", count=1)
    line = bench_run.result_line(ctx["cell"], serve_driver, ctx, trace=False)
    assert set(line["metrics"]) == {"setup_s", "serve_out_tok_s"} and line["correct"] == check["ok"]
    assert [m["name"] for m in line["check"]["mechanism"]] == list(mechanism)
