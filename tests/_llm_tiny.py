"""What the tests of the LLM engine share: three tiny models, a batcher and a
float32 model of each, the decode step's program with its arguments' shapes,
and the reader of the engine's own spans."""

import pytest

TRACE = {"tid": "feedfacefeedface", "sid": "0badf00d"}


@pytest.fixture
def llm_spans(monkeypatch):
    """Reads the `llm.*` SPAN events (or those of another prefix) out of
    tracing's event buffer.  Where this process is a cluster driver, its
    housekeeping ships that buffer to the head every second: it is held back
    while the test reads."""
    from cluster_anywhere_tpu.util import tracing

    drain = tracing.drain_events
    monkeypatch.setattr(tracing, "drain_events", lambda: [])
    drain()  # what earlier tests left
    assert not tracing.is_enabled()
    return lambda prefix="llm.": [
        e for e in drain() if e["state"] == "SPAN" and e["name"].startswith(prefix)
    ]


_TINY = dict(vocab_size=64, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2, d_head=8, d_ff=64)
_TINY_MIXTURE = dict(_TINY, n_experts=4, n_experts_per_tok=2, moe_gated=True)
# a layer pattern: state-space, state-space, attention, twice over; one cached
# head, no rotary, a tied head: runs of length two and one of each kind
_TINY_HYBRID = dict(_TINY, n_layers=6, n_kv_heads=1, attn_layer_period=3, attn_layer_offset=2,
                    ssm_d_state=8, ssm_dt_rank=8, rotary=False, tie_embeddings=True)


def _tiny_batcher(**kw):
    import jax

    from cluster_anywhere_tpu.llm import ContinuousBatcher
    from cluster_anywhere_tpu.models.transformer import TransformerConfig, init_params

    cfg = TransformerConfig(**_TINY)
    return ContinuousBatcher(
        init_params(jax.random.key(0), cfg), cfg, slots=2, t_max=64, prefill_buckets=(8, 32), **kw
    )


def _hybrid(dtype=None, seed=0):
    """(cfg, params) of `_TINY_HYBRID` in float32 with the three inner norms'
    weights moved off 1 and a convolution bias off 0, so that a norm or a bias
    that is left out shows."""
    import jax
    import jax.numpy as jnp

    from cluster_anywhere_tpu.models.transformer import TransformerConfig, init_params

    dtype = dtype or jnp.float32
    cfg = TransformerConfig(**_TINY_HYBRID, dtype=dtype, param_dtype=dtype)
    params = init_params(jax.random.key(seed), cfg)
    ssm = params["ssm_blocks"]
    for name, lo, hi in (("dt_norm", 0.6, 1.4), ("b_norm", 1.5, 0.7), ("c_norm", 0.8, 1.3)):
        ssm[name] = ssm[name] * jnp.linspace(lo, hi, ssm[name].shape[-1]).astype(dtype)
    ssm["conv_b"] = ssm["conv_b"] + jnp.linspace(-0.3, 0.3, ssm["conv_b"].shape[-1]).astype(dtype)
    return cfg, params


def _float32_model(model, seed=0):
    """(cfg, params) in float32: `_TINY_HYBRID` as `_hybrid` makes it."""
    import jax
    import jax.numpy as jnp

    from cluster_anywhere_tpu.models.transformer import TransformerConfig, init_params

    if model is _TINY_HYBRID:
        return _hybrid(seed=seed)
    cfg = TransformerConfig(**model, dtype=jnp.float32, param_dtype=jnp.float32)
    return cfg, init_params(jax.random.key(seed), cfg)


def _decode_step_program(cfg, slots, t_max):
    """`_decode_step_rowpos` unjitted, and the shapes of its arguments."""
    import jax
    import jax.numpy as jnp

    from cluster_anywhere_tpu.llm import continuous
    from cluster_anywhere_tpu.models import generate, transformer

    params = jax.eval_shape(lambda k: transformer.init_params(k, cfg), jax.random.key(0))
    cache = jax.eval_shape(lambda: generate.init_cache(cfg, slots, t_max))
    key = jax.eval_shape(lambda: jax.random.key(0))
    ints = jax.ShapeDtypeStruct((6, slots), jnp.int32)
    floats = jax.ShapeDtypeStruct((2, slots), jnp.float32)
    prev = jax.ShapeDtypeStruct((slots,), jnp.int32)
    # a fresh function each time: jit keeps what it traced for one it has seen
    fn = lambda *a: continuous._decode_step_rowpos.__wrapped__(*a, cfg=cfg)
    return fn, (params, cache, ints, floats, prev, key)
