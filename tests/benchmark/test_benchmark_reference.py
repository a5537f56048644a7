"""The plain reference against the program's model code, at a tiny width on
the CPU: the full forward, the loss, and the serving check (prefill, then
decode steps through the cache rows) that decides `correct` on the chip."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import reference
from cluster_anywhere_tpu.llm.continuous import ContinuousBatcher
from cluster_anywhere_tpu.models.transformer import (
    TransformerConfig, cross_entropy_loss, forward, init_params,
)

TINY = dict(vocab_size=512, d_model=64, n_layers=3, n_heads=4, n_kv_heads=2, d_head=16,
            d_ff=128, rope_theta=1e6)


@pytest.fixture(scope="module")
def f32():
    cfg = TransformerConfig(**TINY, dtype=jnp.float32, param_dtype=jnp.float32)
    return cfg, init_params(jax.random.key(3), cfg)


def test_reference_forward_and_loss_match_the_program_in_float32(f32):
    cfg, params = f32
    ids = np.random.default_rng(0).integers(0, cfg.vocab_size, 41)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(forward(params, jnp.asarray(ids[None, :-1]), cfg)[0])
        want_loss = float(cross_entropy_loss(jnp.asarray(want[None]), jnp.asarray(ids[None, 1:])))
    got = np.asarray(reference.forward(params, ids[:-1], **reference.dims_of(cfg)))
    # float32 both sides: what is left is the order of summation
    assert np.max(np.abs(got - want)) < 2e-4
    assert reference.loss(params, ids, **reference.dims_of(cfg)) == pytest.approx(want_loss, abs=1e-4)
    # and it is the reference that is causal: a later token changes no earlier logit
    ids2 = ids.copy()
    ids2[30] = (ids2[30] + 1) % cfg.vocab_size
    got2 = np.asarray(reference.forward(params, ids2[:-1], **reference.dims_of(cfg)))
    assert np.array_equal(got[:30], got2[:30]) and not np.allclose(got[30:], got2[30:])


def _served(cfg, params, lens, new_tokens):
    """Streams answered by one batcher at once: unequal prompts, so the decode
    program runs them as one batch at unequal row positions and pads."""
    cb = ContinuousBatcher(params, cfg, slots=4, t_max=128, prefill_buckets=(32, 64, 96))
    rng = np.random.default_rng(1)
    reqs = [cb.submit(rng.integers(0, cfg.vocab_size, n), max_new_tokens=new_tokens) for n in lens]
    cb.pump()
    assert cb.stats["decode_steps"] == new_tokens - 1  # all three in every step
    return cb, [{"prompt_ids": r.prompt_ids.tolist(), "served": list(r.out_tokens)} for r in reqs]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_serving_check_holds_prefill_and_the_batch_decode_to_the_reference(dtype, monkeypatch):
    cfg = TransformerConfig(**TINY, dtype=dtype, param_dtype=dtype)
    params = init_params(jax.random.key(5), cfg)
    cb, streams = _served(cfg, params, lens=(20, 40, 70), new_tokens=12)
    rep = reference.check_serving(cb, streams)
    assert rep["streams"] == 3 and rep["positions"] == 36 and rep["logit_max_abs_err"] <= reference.LOGIT_TOL, rep
    if dtype == jnp.float32:
        # float32 both sides: every served token is the reference's own best
        assert rep["ok"] and rep["regret_max"] < 1e-3 and rep["agree_share"] > 0.9, rep
    # one decode token the reference ranks low is caught by the largest regret ...
    ref = np.asarray(reference.forward(
        params, np.asarray(streams[1]["prompt_ids"] + streams[1]["served"][:5]), **reference.dims_of(cfg)))[-1]
    wrong = [dict(s) for s in streams]
    wrong[1]["served"] = streams[1]["served"][:5] + [int(np.argmin(ref))] + streams[1]["served"][6:]
    bad = reference.check_serving(cb, wrong)
    assert not bad["ok"] and bad["regret_max"] > reference.REGRET_MAX_TOL
    # ... and the mean has a bound of its own: a runner-up as one stream's last token
    # (no later position sees it) passes under a wide largest-regret bound only
    if dtype == jnp.float32:
        near = [dict(s) for s in streams]
        near[0]["served"] = streams[0]["served"][:-1] + [int(np.argsort(np.asarray(reference.forward(
            params, np.asarray(streams[0]["prompt_ids"] + streams[0]["served"][:-1]),
            **reference.dims_of(cfg)))[-1])[-2])]
        monkeypatch.setattr(reference, "REGRET_MAX_TOL", 10.0)
        monkeypatch.setattr(reference, "REGRET_MEAN_TOL", 10.0)
        rep = reference.check_serving(cb, near)
        assert rep["ok"] and rep["regret_mean"] == pytest.approx(rep["regret_max"] / 36) and rep["regret_max"] > 0
        monkeypatch.setattr(reference, "REGRET_MEAN_TOL", rep["regret_mean"] / 2)
        assert not reference.check_serving(cb, near)["ok"]
