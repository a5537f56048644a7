"""Keye-VL-2.0's plain reference (references/keye_dsa.py, loaded as the harness
loads it) against the program at a small size on the CPU: the published keys as
the program's fields and the cut's arithmetic, the counts against the program's
own shapes, the forward and prefill then decoding through the cache against the
reference's full pass (logits, and the selected sets equal), the serving check
with every control of PERF.md's chip run planted and refused, the cell's files
through the manifest, the new reader against hand counts, and
`keye-longdoc-closed4` rehearsed at tiny widths through serve.run, proxy, router
and replica."""

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
from cluster_anywhere_tpu.models import generate, transformer
from cluster_anywhere_tpu.models.transformer import TransformerConfig, init_params
from cluster_anywhere_tpu.ops import sparse_attention as sparse

CELL = "keye-longdoc-closed4"
CONFIG = "keye-vl-2.0-30b-a3b-ep8-serve1"
reference = manifest.load_reference("keye_dsa")
TOPK = 16
# the published block at a test's widths: 4 query heads on 2 cached heads of 16, an indexer of 2 heads x 8, 16 of a
# context selected, 32 routed experts of which 8-11 are held, 4 layers
TINY = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=2, head_dim=16, intermediate_size=160,
            moe_intermediate_size=24, vocab_size=512, num_experts=4, num_local_experts=4, num_experts_routed=32,
            experts_held_first=8, num_experts_per_tok=4, num_hidden_layers=4,
            sa_config=dict(indexer_head_dim=8, indexer_num_heads=2, indexer_num_kv_heads=1, kv_chunk_size=512,
                           q_chunk_size=512, topk=TOPK))
MECHANISMS = ["dsa_select_other_set", "dsa_core_rel_err", "dsa_index_key_err"]


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    """The reference's blocks of queries at a test's lengths."""
    monkeypatch.setattr(reference, "ATTN_BLOCK", 16)
    monkeypatch.setattr(reference, "MECH_ROWS", 16)
    monkeypatch.setattr(reference, "MECH_STRIDE", 2)


def tiny_config(**over):
    cell = copy.deepcopy(manifest.load_cell(CELL))
    cell["config_file"]["config"].update(TINY, **over)
    return cell


def program(dtype=jnp.float32, **over):
    cell = tiny_config(**over)
    cfg = TransformerConfig(**reference.program_config(cell["config_file"], vocab_size=TINY["vocab_size"], dtype=dtype,
                                                       param_dtype=dtype))
    params = init_params(jax.random.key(3), cfg)
    b = params["blocks"]
    # the norms' weights off 1, so a norm that is left out or misplaced shows
    for name, (lo, hi) in {"ln1": (0.6, 1.4), "ln2": (1.3, 0.7), "q_norm": (0.5, 1.5), "k_norm": (1.5, 0.5),
                           "k_idx_norm": (0.7, 1.3)}.items():
        b[name] = b[name] * jnp.linspace(lo, hi, b[name].shape[-1]).astype(dtype)
    return cfg, params


def test_the_published_keys_build_the_published_block_and_the_cut_is_the_issues_arithmetic():
    cell = manifest.load_cell(CELL)
    file = cell["config_file"]
    config, published = file["config"], file["published"]
    cfg = TransformerConfig(vocab_size=config["vocab_size"], **reference.program_config(file))
    assert (cfg.d_model, cfg.n_layers, cfg.n_heads, cfg.n_kv_heads, cfg.d_head, cfg.d_expert) == (2048, 48, 32, 4, 128, 768)
    assert (cfg.index_topk, cfg.index_n_heads, cfg.index_head_dim) == (2048, 16, 64)
    assert (cfg.n_experts, cfg.n_experts_per_tok, cfg.experts_held, cfg.moe_renormalize, cfg.moe_gated) == (128, 8, (0, 16), True, True)
    assert cfg.qk_norm and cfg.qk_norm_per_head and cfg.rope_theta == 1e7 and cfg.norm_eps == 1e-6
    assert cfg.layer_kinds == ("attn",) * 48
    # every key of the catalog's config is in the file under its own name, and what differs is listed
    rows = [json.loads(line) for line in open("/opt/skills/guides/model-configs/architectures.jsonl")] \
        if os.path.exists("/opt/skills/guides/model-configs/architectures.jsonl") else []
    for row in rows:
        if row["name"] == "Keye-VL-2.0-30B-A3B":
            assert row["config"] == published and row["source_url"] == file["source"]
    differs = {k for k, v in published.items() if file[k] != v}
    assert differs == set(file["reduced"]) == {"num_experts", "num_local_experts", "vocab_size"}
    assert all(config[k] == file[k] for k in published) and config["num_hidden_layers"] == 48
    assert {"qk_norm_per_head", "indexer_query_from", "indexer_key_norm", "indexer_weight_scale", "indexer_rotary",
            "indexer_every_layer", "selection_granularity", "indexer_key_cache_dtype"} <= set(file["assumed"])
    assert {"hadamard_and_float8", "rope_layout", "text_positions_only"} <= set(file["departures"])
    # the issue's arithmetic, bf16
    assert reference._attention_params(config) == 18_874_368 + 256 and reference.indexer_params(config) == 2_261_120
    assert reference.expert_params(config) == 4_718_592
    held = reference.param_count(config)
    assert held == 48 * 96_899_456 + 2 * 18_992 * 2048 + 2048 and held * 2 / 1e9 == pytest.approx(9.46, abs=0.005)
    # the same count by the shapes the program makes at the published widths
    shapes = jax.eval_shape(lambda k: init_params(k, cfg), jax.random.key(0))
    assert sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(shapes)) == held
    assert shapes["blocks"]["w_gate"].shape == (48, 16, 2048, 768) and shapes["blocks"]["router"].shape == (48, 2048, 128)
    assert shapes["blocks"]["wq_idx"].shape == (48, 2048, 1024) and shapes["lm_head"].shape == (2048, 18_992)
    # uncut: 30.6 B, 61 GB
    whole = dict(published, num_experts_routed=128)
    assert reference.param_count(whole) == pytest.approx(30.64e9, rel=1e-3)
    # the cache by the program's own shapes at the cell's deployment: 104,448 B a token, 3.64 GB
    dep = cell["traffic_file"]["deployment"]
    t_max = dep["max_prompt_len"] + dep["max_new_tokens"]
    cache = jax.eval_shape(lambda: generate.init_cache(cfg, dep["slots"], t_max))
    assert cache["kv"].shape == (48, 4, 8704, 8, 128) and cache["ki"].shape == (48, 4, 64, 8704)
    assert generate.cache_bytes_per_token(cache, cfg) == 48 * 2176 == 104_448
    assert 4 * 8704 * 104_448 / 1e9 == pytest.approx(3.64, abs=0.005)
    assert 48 * (reference.selected_row_bytes(config) + reference.index_key_bytes(config)) == 104_448
    # a decode step at 4 live rows of 6,500: the selected rows, not the contexts
    step = reference.decode_step_bytes(config, 4, t_max, contexts=[6500] * 4)
    assert 4.5e9 < step < 5.0e9
    cache_part = 48 * 4 * (2048 * 2048 + 6500 * 128)
    no_cache = reference.decode_step_bytes(config, 4, t_max, contexts=[0] * 4)
    assert step - no_cache == cache_part
    assert reference.decode_step_bytes(config, 4, t_max, contexts=[6500] * 4, selected=4 * 2048) == step
    # an admit of 8,192: every causal pair scored, 2,048 a query attended
    assert reference.causal_pairs(8192) == 8192 * 8193 // 2
    assert reference.selected_pairs(8192, 2048) == 2048 * 2049 // 2 + 6144 * 2048
    assert reference.selected_pairs(100, 2048) == reference.causal_pairs(100)
    assert reference.dsa_prefill_flops(config, 8192) == 48 * (2.0 * 16 * 64 * reference.causal_pairs(8192)
                                                              + 4.0 * 32 * 128 * reference.selected_pairs(8192, 2048))


def test_a_program_without_the_fields_refuses_the_configuration_by_name(monkeypatch):
    """The parent of the PR that brought this file: the cell fails at once, in the driver's own process, before
    anything is deployed."""

    @dataclasses.dataclass(frozen=True)
    class Older:
        d_model: int = 0
        experts_held: tuple = ()

    monkeypatch.setattr(transformer, "TransformerConfig", Older)
    with pytest.raises(NotImplementedError, match="index_topk"):
        reference.program_config(manifest.load_cell(CELL)["config_file"])


def test_the_cells_files_through_the_manifest():
    metrics = {m["name"]: m for m in manifest.layer_metrics_for(CELL)}
    names = set(metrics)
    dsa = {"indexer_share.dsa", "select_share.dsa", "sparse_core_share.dsa", "selected_rows_share.dsa",
           "sparse_core_hbm_share.dsa", "indexer_hbm_share.dsa", "dsa_prefill_roofline.dsa", "step_hbm_share.dsa"}
    assert {n for n, m in metrics.items() if m.get("family") == "dsa"} == dsa
    assert {"cache_read_share.closed", "attn_share.closed", "decode_batch_mean.closed", "ffn_share.mla",
            "held_compact_share.mla", "moe_experts_share.moe", "cache_bytes_per_token.mla"} <= names
    assert not {n for n in names if n.endswith((".ssm", ".blk", ".sambay", ".nemotronh", ".swa"))}
    cell = manifest.load_cell(CELL)
    assert cell["families"] == ["closed", "causal", "attn", "ffn_moe", "held", "held_compact", "moe_kernel", "moe_route",
                                "experts_touched", "cache_bytes", "dsa"]
    bench = manifest.load_manifest()
    assert {m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", [CELL])} == names
    # 111 entries, 11 cells and 10 configurations with this cell; a later one adds to them
    assert len(bench["per_layer"]) >= 111 and len(bench["workloads"]) >= 11 and len(bench["configs"]) >= 10
    assert [w for w in bench["workloads"] if w["name"] == CELL] == [
        {"name": CELL, "config": CONFIG, "traffic": "longdoc-closed", "chips": 1, "why": cell["why"]}]
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == ["num_experts", "num_local_experts", "vocab_size"]
    assert CELL in next(m for m in bench["end_to_end"] if m["name"] == "serve_out_tok_s")["workloads"]
    mix = cell["traffic_file"]
    assert (cell["callers"], mix["kind"], mix["ramp_s"], mix["drain_s"]) == (4, "closed_loop", 15.0, 60.0)
    assert mix["prompt_len"] == dict(dist="lognormal", median=5120, sigma=0.35, min=3072, max=8192)
    assert mix["output_len"] == dict(dist="lognormal", median=192, sigma=0.5, min=48, max=512)
    # since ISSUE 58: a window reaches 8 requests a caller, so a caller goes round 8 quantiles, 24 rounds at most
    # (ISSUE 56's 24 and 8 left the seed to choose which 8 of 24 a window held: PERF.md section 6, PR 58)
    assert (mix["caller_requests"], mix["caller_sizes"], mix["caller_rounds"]) == (8, "quantiles", 24)
    assert mix["deployment"] == dict(slots=4, max_prompt_len=8192, max_new_tokens=512, prefix_cache_entries=0)
    assert mix["warmup_prompt_lens"] == [4096, 8192] and mix["warmup_new_tokens"] == 4
    assert mix["check"] == dict(stream_prompt_lens=[3100, 3900, 5900, 7900], stream_new_tokens=64,
                                repeat_prompt_len=3900, repeat_new_tokens=9)
    # every check stream's selection leaves positions out from its first served token
    assert all(n > cell["config_file"]["config"]["sa_config"]["topk"] for n in mix["check"]["stream_prompt_lens"])
    others = {json.load(open(os.path.join(manifest.BENCH_DIR, "traffic", f)))["shape_seed"]
              for f in os.listdir(os.path.join(manifest.BENCH_DIR, "traffic")) if f != "longdoc-closed.json"
              and "shape_seed" in json.load(open(os.path.join(manifest.BENCH_DIR, "traffic", f)))}
    assert mix["shape_seed"] not in others


@pytest.mark.parametrize("n", [TOPK - 3, 48], ids=["within-topk", "selecting"])
def test_the_forward_is_the_references_full_pass_and_selects_its_sets(n):
    cfg, params = program()
    ids = np.random.default_rng(n).integers(0, cfg.vocab_size, n)
    with jax.default_matmul_precision("highest"):
        got = transformer.forward(params, ids[None], cfg)[0]
    np.testing.assert_allclose(got, reference.forward(params, ids, cfg), atol=3e-5)
    assert reference.loss(params, ids, cfg) == pytest.approx(
        float(transformer.cross_entropy_loss(got[None, :-1], jnp.asarray(ids)[None, 1:])), abs=1e-5)
    sets = reference.selected_sets(params, ids, cfg)
    assert sets.shape == (4, n, n) and np.array_equal(sets.sum(-1)[0], np.minimum(np.arange(1, n + 1), TOPK))
    # the program's first layer selects the reference's sets, query by query
    bp = jax.tree_util.tree_map(lambda w: w[0], params["blocks"])
    y = transformer._norm(params["embed"][jnp.asarray(ids)][None], bp, "ln1", cfg)
    with jax.default_matmul_precision("highest"):
        q, k, v = transformer._project_qkv(bp, y, cfg)
        _, mask = transformer._sparse_attention(q, k, v, transformer._project_index(bp, y, cfg, jnp.arange(n)[None]), cfg,
                                                chosen=True)
    assert (mask is None) == (n <= TOPK)
    if mask is not None:
        assert np.array_equal(np.asarray(mask[0]) != 0, sets[0])


def _served_together(cfg, params, lens=(20, 40, 70), new_tokens=12):
    cb = ContinuousBatcher(params, cfg, slots=4, t_max=128, prefill_buckets=(32, 64, 96))
    rng = np.random.default_rng(5)
    reqs = [cb.submit(rng.integers(0, cfg.vocab_size, n), max_new_tokens=new_tokens) for n in lens]
    cb.pump()
    return cb, [{"prompt_ids": r.prompt_ids.tolist(), "served": list(r.out_tokens), "request_id": r.request_id}
                for r in reqs]


def test_serving_check_holds_prefill_and_the_batch_decode_to_the_reference():
    cfg, params = program()
    cb, streams = _served_together(cfg, params)
    assert cb.stats["decode_steps"] == 11
    assert cb.stats["cache_rows_read"] == 11 * 3 * TOPK and cb.stats["context_rows"] == sum(
        n + j + 1 for n in (20, 40, 70) for j in range(11))
    rep = check_serving(cb, streams, reference)
    assert rep["streams"] == 3 and rep["positions"] == 36 and rep["ok"], rep
    assert rep["logit_max_abs_err"] < 1e-3 and rep["regret_max"] < 1e-3 and rep["agree_share"] > 0.9, rep
    assert [m["name"] for m in rep["mechanism"]] == MECHANISMS and all(m["error"] < 1e-4 for m in rep["mechanism"]), rep
    ref = np.asarray(reference.forward(params, np.asarray(streams[1]["prompt_ids"] + streams[1]["served"][:5]), cfg))[-1]
    wrong = [dict(s) for s in streams]
    wrong[1]["served"] = streams[1]["served"][:5] + [int(np.argmin(ref))] + streams[1]["served"][6:]
    bad = check_serving(cb, wrong, reference)
    assert not bad["ok"] and bad["regret_max"] > reference.REGRET_MAX_TOL


# -- the controls: what the chip run plants (scripts/keye_controls.py, by the same names), each refused here too --


def float8(a):
    return a.astype(jnp.float8_e4m3fn).astype(a.dtype)


def plant(monkeypatch, control):
    """The fault `control` in the program's own functions, for everything traced from here on."""
    if control == "no-relu":
        def scores(qi, ki, w, keys_last=False):
            s = jnp.einsum("bthd,bds->bths" if keys_last else "bthd,bsd->bths", qi, ki,
                           preferred_element_type=jnp.float32, precision=jax.lax.Precision.HIGHEST)
            return jnp.sum(w[..., None] * s, axis=2)
        monkeypatch.setattr(sparse, "index_scores_reference", scores)
    elif control == "index-keys-float8":
        project = transformer._project_index

        def rounded(*a, **kw):
            qi, ki, w = project(*a, **kw)
            return qi, float8(ki), w
        monkeypatch.setattr(transformer, "_project_index", rounded)
    elif control == "core-probs-float8":  # the chip script's own plant: the core's probabilities in float8, nothing else
        spec = importlib.util.spec_from_file_location("keye_controls", os.path.join(manifest.ROOT, "scripts", "keye_controls.py"))
        script = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(script)
        for module, name, fault in script.core_probs_float8():
            monkeypatch.setattr(module, name, fault)
    jax.clear_caches()


@pytest.mark.parametrize("control", ["selection-skipped", "topk-halved", "no-relu", "index-keys-float8", "float8-weights",
                                     "core-probs-float8"])
def test_every_planted_control_is_refused(monkeypatch, control):
    cfg, params = program(num_hidden_layers=2)  # two layers: every control traces its programs anew
    served_cfg, served_params = cfg, params
    if control == "selection-skipped":  # dense attention served: every context within topk
        served_cfg = dataclasses.replace(cfg, index_topk=10 ** 6)
    elif control == "topk-halved":
        served_cfg = dataclasses.replace(cfg, index_topk=TOPK // 2)
    elif control == "float8-weights":
        served_params = jax.tree_util.tree_map(lambda a: float8(a) if a.ndim >= 3 or a.shape[0] > 64 else a, params)
    with monkeypatch.context() as planted:
        plant(planted, control)
        cb, streams = _served_together(served_cfg, served_params)
        cb.cfg, cb.params = cfg, params  # the check reads the configuration and the weights that were asked for
        rep = check_serving(cb, streams, reference)
    jax.clear_caches()
    assert not rep["ok"], rep
    failed = [m["name"] for m in rep["mechanism"] if m["error"] > m["tolerance"]]
    if control == "no-relu":
        assert "dsa_select_other_set" in failed, rep  # the selection is another, and not by a near-tie
    if control == "index-keys-float8":
        # the cached key's own number; the selection's band is a bound, and a float8 key moves few scores past it
        assert "dsa_index_key_err" in failed, rep
    if control == "core-probs-float8":
        assert failed == ["dsa_core_rel_err"], rep  # the core's own number, and no other of the mechanism's
    if control in ("selection-skipped", "topk-halved", "float8-weights"):
        assert rep["logit_max_abs_err"] > reference.LOGIT_TOL or rep["regret_max"] > reference.REGRET_MAX_TOL \
            or rep["regret_mean"] > reference.REGRET_MEAN_TOL, rep


def test_the_new_reader_against_hand_counts():
    cell = manifest.load_cell(CELL)
    config = cell["config_file"]["config"]
    span = lambda name, start, **args: [1, float(start), 8e6, name, args]
    op = lambda start, dur, scope, name="%fusion.7 = bf16[32,64] fusion()": [float(start), float(dur), name, scope]
    kernel = lambda name: f"%{name}.3 = bf16[4,8192,128] custom-call()"
    step_args = dict(live=4, cache_rows_read=4 * 2048, cache_rows=4 * 8704, context_rows=4 * 6000,
                     index_rows_read=4 * 8704, moe_rows=4, moe_experts_touched=3.5)
    events = {
        "spans": [span("llm.step", 0, **step_args), span("llm.step", 30e6, **dict(step_args, live=2, cache_rows_read=2 * 2048,
                                                                             context_rows=2 * 7000)),
                  span("llm.step", 40e6, live=0), span("llm.admit", 8e6, prompt_len=5000)],
        "ops": {"/device:TPU:0": [
            # a decode step: 6 ms in all
            op(0, 1e6, "embed"), op(1e6, 1e6, "attn.indexer"), op(2e6, 0.5e6, "attn.select"), op(2.5e6, 1.5e6, "attn.sparse_core"),
            op(4e6, 2e6, "ffn"),
            # an admit's prefill, its install and its first token: 20 ms
            op(8e6, 1e6, "embed"), op(9e6, 2e6, "attn.indexer", kernel("dsa_index")), op(11e6, 1e6, "attn.indexer"),
            op(12e6, 3e6, "attn.select", kernel("dsa_select")), op(15e6, 8e6, "attn.sparse_core", kernel("dsa_flash")),
            op(23e6, 4e6, "ffn"), op(27e6, 1e6, "attn.cache"),
            # a second step: 4 ms
            op(30e6, 1e6, "embed"), op(31e6, 1e6, "attn.indexer"), op(32e6, 1e6, "attn.sparse_core"), op(33e6, 1e6, "head")]}}
    ctx = {"cell": cell, "program_trace": events, "device": {"kind": "TPU v5 lite"},
           "replica": {"steps": [], "admits": [], "first": {}, "stats": {}}, "records": [], "t_open": 0.0, "seconds": 1.0}
    dsa = manifest.load_reader("dsa")
    bw, peak = 819e9, 197e12
    assert dsa(ctx, what="sparse_core_hbm") == pytest.approx(100 * 48 * 2048 * 6 * 2048 / (2.5e-3 * bw))
    assert dsa(ctx, what="indexer_hbm") == pytest.approx(
        100 * 48 * (128 * (4 * 6000 + 2 * 7000) + 2 * 2 * 2_261_120) / (2.5e-3 * bw))
    want_step = sum(reference.decode_step_bytes(config, 4, 8704, contexts=c, touched=3.5) for c in ([6000] * 4, [7000] * 2))
    assert dsa(ctx, what="step_hbm") == pytest.approx(100 * want_step / (10e-3 * bw))
    assert dsa(ctx, what="prefill_roofline") == pytest.approx(100 * reference.dsa_prefill_flops(config, 5000) / (14e-3 * peak))
    assert all(0 < dsa(ctx, what=w) for w in ("sparse_core_hbm", "indexer_hbm", "step_hbm", "prefill_roofline"))
    with pytest.raises(ValueError):
        dsa(ctx, what="bytes")
    got = manifest.read_layer_metrics(CELL, ctx)
    assert got["selected_rows_share.dsa"]["value"] == pytest.approx(100 * 6 * 2048 / (4 * 6000 + 2 * 7000))
    assert got["indexer_share.dsa"]["value"] == pytest.approx(100 * 5 / 30)
    assert got["select_share.dsa"]["value"] == pytest.approx(100 * 3.5 / 30)
    assert got["sparse_core_share.dsa"]["value"] == pytest.approx(100 * 10.5 / 30)
    assert got["cache_read_share.closed"]["value"] == pytest.approx(100 * 6 * 2048 / (2 * 4 * 8704))
    assert {"sparse_core_hbm_share.dsa", "indexer_hbm_share.dsa", "dsa_prefill_roofline.dsa", "step_hbm_share.dsa"} <= set(got)
    # a program without the scopes (the parent, another architecture), a slice without a step or an admit, a
    # reference that counts none of it: nothing, and no error
    other = copy.deepcopy(events)
    other["ops"] = {"/device:TPU:0": [op(0, 2e6, "attn.core"), op(2e6, 1e6, "ffn")]}
    assert all(dsa(dict(ctx, program_trace=other), what=w) is None
               for w in ("sparse_core_hbm", "indexer_hbm", "step_hbm", "prefill_roofline"))
    quiet = copy.deepcopy(events)
    quiet["spans"] = []
    assert all(dsa(dict(ctx, program_trace=quiet), what=w) is None for w in ("sparse_core_hbm", "prefill_roofline"))
    assert dsa(dict(ctx, program_trace=None), what="step_hbm") is None
    assert dsa(dict(ctx, cell=manifest.load_cell("olmoe-closed6")), what="step_hbm") is None


def test_serve_rehearsal_of_keye_longdoc_closed4():
    """The cell at tiny widths through the program's normal path on the CPU backend (a TPU resource that is only
    a number)."""
    cell = tiny_config(hidden_size=128)
    cell.update(callers=3)
    cell["traffic_file"].update(
        ramp_s=0.5, drain_s=60.0, warmup_prompt_lens=[20, 70, 150],
        prompt_len=dict(dist="lognormal", median=40, sigma=0.5, min=20, max=160),
        output_len=dict(dist="lognormal", median=6, sigma=0.3, min=4, max=12),
        check=dict(stream_prompt_lens=[20, 30, 70, 150], stream_new_tokens=8, repeat_prompt_len=40, repeat_new_tokens=5),
        deployment=dict(slots=4, max_prompt_len=160, max_new_tokens=16, prefix_cache_entries=0),
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
    assert check["streams"] == 4 and check["positions"] == 32 and check["decode_batch_mean"] > 1.0, check
    mechanism = {m["name"]: m for m in check["mechanism"]}
    assert list(mechanism) == MECHANISMS and all(m["error"] <= m["tolerance"] for m in mechanism.values()), mechanism
    assert check["repeat_identical"], check
    stats = ctx["replica"]["stats"]
    assert 0 < stats["cache_rows_read"] < stats["context_rows"] and stats["index_rows_read"] > 0
    assert stats["cache_bytes_per_token"] == 4 * (2 * 2 * 16 + 8) * 2
    layer = manifest.read_layer_metrics(CELL, ctx)
    assert layer["decode_batch_mean.closed"]["value"] >= 1.0 and layer["cache_bytes_per_token.mla"]["value"] == 576
    assert not {"indexer_share.dsa", "step_hbm_share.dsa", "attn_share.closed"} & set(layer)  # no trace, no device time
    ctx["device"].update(platform="tpu", kind="TPU v5 lite", count=1)
    line = bench_run.result_line(ctx["cell"], serve_driver, ctx, trace=False)
    assert set(line["metrics"]) == {"setup_s", "serve_out_tok_s"}
