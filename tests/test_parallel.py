"""Parallelism-strategy tests on a virtual 8-device CPU mesh: ring attention
and Ulysses vs dense reference, pipeline parallel vs sequential, MoE shapes,
mesh/sharding helpers, in-graph collectives."""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import cluster_anywhere_tpu as ca
from jax import shard_map
from jax.sharding import PartitionSpec as P

from cluster_anywhere_tpu.parallel import MeshSpec, auto_spec, make_mesh
from cluster_anywhere_tpu.parallel.moe import init_moe_params, moe_ffn
from cluster_anywhere_tpu.parallel.pipeline import pipeline_sharded
from cluster_anywhere_tpu.parallel.ring_attention import (
    reference_attention,
    ring_attention_sharded,
)
from cluster_anywhere_tpu.parallel.ulysses import ulysses_attention_sharded


def test_mesh_spec():
    spec = auto_spec(8, tp=2, pp=2)
    assert spec.dp == 2 and spec.size == 8
    mesh = make_mesh(spec)
    assert mesh.shape["dp"] == 2 and mesh.shape["tp"] == 2 and mesh.shape["pp"] == 2


@pytest.mark.parametrize("causal", [True, False])
def test_ring_attention_matches_dense(causal):
    mesh = make_mesh(MeshSpec(sp=4, dp=2))
    key = jax.random.PRNGKey(0)
    b, t, h, d = 2, 32, 4, 16
    q, k, v = (
        jax.random.normal(kk, (b, t, h, d), dtype=jnp.float32)
        for kk in jax.random.split(key, 3)
    )
    expect = reference_attention(q, k, v, causal=causal)
    got = ring_attention_sharded(q, k, v, mesh, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expect), rtol=2e-4, atol=2e-4)


def test_ring_attention_grads_match():
    mesh = make_mesh(MeshSpec(sp=4, dp=2))
    key = jax.random.PRNGKey(1)
    b, t, h, d = 1, 16, 2, 8
    q, k, v = (
        jax.random.normal(kk, (b, t, h, d), dtype=jnp.float32)
        for kk in jax.random.split(key, 3)
    )

    def loss_ring(q, k, v):
        return jnp.sum(ring_attention_sharded(q, k, v, mesh, causal=True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(reference_attention(q, k, v, causal=True) ** 2)

    g_ring = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g_ring, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("causal", [True, False])
def test_ring_flash_matches_dense(causal):
    """The flash-kernel ring path (per-block Pallas kernel + lse merge) must
    match dense attention; runs in interpret mode on the CPU mesh."""
    mesh = make_mesh(MeshSpec(sp=4, dp=2))
    key = jax.random.PRNGKey(3)
    b, t, h, d = 1, 128, 2, 16  # 32 per shard; flash blocks = shard size
    q, k, v = (
        jax.random.normal(kk, (b, t, h, d), dtype=jnp.float32)
        for kk in jax.random.split(key, 3)
    )
    expect = reference_attention(q, k, v, causal=causal)
    got = ring_attention_sharded(q, k, v, mesh, causal=causal, use_flash=True, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expect), rtol=2e-4, atol=2e-4)


def test_ring_flash_grads_match():
    mesh = make_mesh(MeshSpec(sp=4, dp=2))
    key = jax.random.PRNGKey(4)
    b, t, h, d = 1, 64, 2, 8
    q, k, v = (
        jax.random.normal(kk, (b, t, h, d), dtype=jnp.float32)
        for kk in jax.random.split(key, 3)
    )

    def loss_ring(q, k, v):
        return jnp.sum(
            ring_attention_sharded(q, k, v, mesh, causal=True, use_flash=True, interpret=True) ** 2
        )

    def loss_ref(q, k, v):
        return jnp.sum(reference_attention(q, k, v, causal=True) ** 2)

    g_ring = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g_ring, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), rtol=1e-3, atol=1e-3)


def test_ulysses_matches_dense():
    mesh = make_mesh(MeshSpec(sp=4, dp=2))
    key = jax.random.PRNGKey(2)
    b, t, h, d = 2, 32, 8, 16  # heads divisible by sp
    q, k, v = (
        jax.random.normal(kk, (b, t, h, d), dtype=jnp.float32)
        for kk in jax.random.split(key, 3)
    )
    expect = reference_attention(q, k, v, causal=True)
    got = ulysses_attention_sharded(q, k, v, mesh, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expect), rtol=2e-4, atol=2e-4)


def test_pipeline_matches_sequential():
    mesh = make_mesh(MeshSpec(pp=4, dp=2))
    key = jax.random.PRNGKey(3)
    n_stages, batch, dim = 4, 16, 32
    ws = jax.random.normal(key, (n_stages, dim, dim)) * 0.1

    def stage_fn(w, x):
        return jnp.tanh(x @ w)

    apply = pipeline_sharded(stage_fn, mesh, num_microbatches=4)
    x = jax.random.normal(jax.random.PRNGKey(4), (batch, dim))
    got = apply(ws, x)
    expect = x
    for i in range(n_stages):
        expect = stage_fn(ws[i], expect)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expect), rtol=1e-5, atol=1e-5)


def test_pipeline_grad_flows():
    mesh = make_mesh(MeshSpec(pp=4, dp=2))
    n_stages, batch, dim = 4, 8, 16
    ws = jax.random.normal(jax.random.PRNGKey(5), (n_stages, dim, dim)) * 0.1
    x = jax.random.normal(jax.random.PRNGKey(6), (batch, dim))

    def stage_fn(w, x):
        return jnp.tanh(x @ w)

    apply = pipeline_sharded(stage_fn, mesh, num_microbatches=2)

    def loss_pp(ws):
        return jnp.mean(apply(ws, x) ** 2)

    def loss_seq(ws):
        y = x
        for i in range(n_stages):
            y = stage_fn(ws[i], y)
        return jnp.mean(y ** 2)

    g_pp = jax.grad(loss_pp)(ws)
    g_seq = jax.grad(loss_seq)(ws)
    np.testing.assert_allclose(np.asarray(g_pp), np.asarray(g_seq), rtol=1e-4, atol=1e-5)


def test_moe_runs_and_balances():
    mesh = make_mesh(MeshSpec(ep=4, dp=2))
    e_model, f_hidden, n_experts = 16, 32, 8
    params = init_moe_params(jax.random.PRNGKey(7), e_model, f_hidden, n_experts)
    n_tokens = 64
    x = jax.random.normal(jax.random.PRNGKey(8), (n_tokens, e_model))

    def inner(x, router, w_in, w_out):
        r = moe_ffn(x, router, w_in, w_out, capacity_factor=2.0)
        return r.out, jax.lax.pmean(r.aux_loss, "dp")

    fn = shard_map(
        inner,
        mesh=mesh,
        in_specs=(P("dp"), P(), P("ep"), P("ep")),
        out_specs=(P("dp"), P()),
        check_vma=False,
    )
    out, aux = fn(x, params["router"], params["w_in"], params["w_out"])
    assert out.shape == (n_tokens, e_model)
    assert np.isfinite(np.asarray(out)).all()
    assert float(aux[()] if hasattr(aux, "shape") else aux) > 0


def test_xla_collectives():
    from cluster_anywhere_tpu.parallel.collectives import xla

    mesh = make_mesh(MeshSpec(dp=8))

    def inner(x):
        total = xla.allreduce(x.sum(), "dp")
        gathered = xla.allgather(x, "dp")
        return total, gathered

    fn = shard_map(inner, mesh=mesh, in_specs=P("dp"), out_specs=(P(), P()), check_vma=False)
    x = jnp.arange(16.0)
    total, gathered = fn(x)
    assert float(total) == float(x.sum())
    assert gathered.shape == (16,)


def test_host_collective_group_across_actors(ca_cluster_module):
    """Host (Gloo-role) collectives between actor ranks: payloads ride the
    object store's data plane, KV carries only refs; allreduce is rooted
    (O(world) tensor movements)."""
    from cluster_anywhere_tpu.parallel.collectives import (
        CollectiveActorMixin,
        create_collective_group,
    )

    @ca.remote
    class Rank(CollectiveActorMixin):
        def do_allreduce(self, n):
            from cluster_anywhere_tpu.parallel import collectives as col

            g = col.get_group()
            return g.allreduce(np.full(n, g.rank + 1.0))

        def do_allgather(self):
            from cluster_anywhere_tpu.parallel import collectives as col

            g = col.get_group()
            return [a.tolist() for a in g.allgather(np.array([g.rank * 10.0]))]

        def do_broadcast(self):
            from cluster_anywhere_tpu.parallel import collectives as col

            g = col.get_group()
            src = np.array([42.0]) if g.rank == 1 else None
            return float(g.broadcast(src, src_rank=1)[0])

        def do_reducescatter(self):
            from cluster_anywhere_tpu.parallel import collectives as col

            g = col.get_group()
            return g.reducescatter(np.arange(6, dtype=np.float64)).tolist()

        def do_p2p(self):
            from cluster_anywhere_tpu.parallel import collectives as col

            g = col.get_group()
            if g.rank == 0:
                g.send(np.array([7.0, 8.0]), dst_rank=1)
                return None
            return g.recv(0).tolist()

    actors = [Rank.remote() for _ in range(3)]
    create_collective_group(actors, world_size=3, ranks=[0, 1, 2])

    # allreduce over a LARGE tensor (4 MB): KV would choke if payloads went
    # through it; the data plane carries them
    n = 1 << 20
    outs = ca.get([a.do_allreduce.remote(n) for a in actors], timeout=120)
    for o in outs:
        assert o.shape == (n,) and o[0] == 6.0  # 1+2+3

    gathers = ca.get([a.do_allgather.remote() for a in actors], timeout=60)
    assert all(g == [[0.0], [10.0], [20.0]] for g in gathers)

    bcasts = ca.get([a.do_broadcast.remote() for a in actors], timeout=60)
    assert bcasts == [42.0, 42.0, 42.0]

    rs = ca.get([a.do_reducescatter.remote() for a in actors], timeout=60)
    assert rs[0] == [0.0, 3.0] and rs[1] == [6.0, 9.0] and rs[2] == [12.0, 15.0]

    p2p = ca.get([actors[0].do_p2p.remote(), actors[1].do_p2p.remote()], timeout=60)
    assert p2p[1] == [7.0, 8.0]
    for a in actors:
        ca.kill(a)


def test_host_collective_cross_node():
    """Host collectives across NODES: ranks on different node agents move
    payloads via the chunked node-to-node object transfer."""
    from cluster_anywhere_tpu.cluster_utils import Cluster
    from cluster_anywhere_tpu.core.scheduling_strategies import (
        NodeAffinitySchedulingStrategy,
    )
    from cluster_anywhere_tpu.parallel.collectives import (
        CollectiveActorMixin,
        create_collective_group,
    )

    if ca.is_initialized():  # a module-scoped cluster may still be attached
        ca.shutdown()
    c = Cluster(head_resources={"CPU": 2})
    nid = c.add_node(num_cpus=2)
    c.connect()
    c.wait_for_nodes(2)
    try:

        @ca.remote
        class Rank(CollectiveActorMixin):
            def reduce_big(self, n):
                from cluster_anywhere_tpu.parallel import collectives as col

                g = col.get_group()
                out = g.allreduce(np.full(n, g.rank + 1.0))
                return float(out[0]), float(out[-1])

        a0 = Rank.remote()
        a1 = Rank.options(
            scheduling_strategy=NodeAffinitySchedulingStrategy(nid, soft=False)
        ).remote()
        create_collective_group([a0, a1], world_size=2, ranks=[0, 1])
        n = 1 << 19  # 4 MB crosses the node boundary via chunked pulls
        outs = ca.get([a.reduce_big.remote(n) for a in (a0, a1)], timeout=120)
        assert outs == [(3.0, 3.0), (3.0, 3.0)]
    finally:
        c.shutdown()


def test_moe_through_pipeline_matches_unpipelined():
    """MoE + pipeline parallelism (pp x ep): the pipelined stack's loss must
    match the unpipelined MoE stack on identical params/batch (CE term is
    exact; the load-balance aux is estimated per microbatch, so compare with
    a tolerance), and gradients must flow into the expert weights."""
    from cluster_anywhere_tpu.models import TransformerConfig, make_train_step
    from cluster_anywhere_tpu.models.transformer import (
        init_params,
        make_loss_fn,
    )

    tiny = dict(
        vocab_size=64, d_model=16, n_layers=4, n_heads=2, n_kv_heads=2,
        d_head=8, d_ff=32, max_seq_len=32, dtype=jnp.float32,
    )
    batch = {
        "ids": jnp.asarray(
            np.random.default_rng(0).integers(0, 64, (8, 17), dtype=np.int32)
        )
    }

    cfg_pp = TransformerConfig(
        **tiny, n_experts=4, ep=2, pp=2, num_microbatches=2, attn_impl="dense"
    )
    mesh_pp = make_mesh(MeshSpec(fsdp=2, pp=2, ep=2))
    params_pp = init_params(jax.random.PRNGKey(0), cfg_pp)
    loss_pp = jax.jit(make_loss_fn(cfg_pp, mesh_pp))(params_pp, batch)

    # same params, unpipelined: un-restack [pp, L/pp, ...] -> [L, ...]
    cfg_flat = TransformerConfig(**tiny, n_experts=4, ep=2, attn_impl="dense")
    mesh_flat = make_mesh(MeshSpec(fsdp=4, ep=2))
    params_flat = dict(params_pp)
    params_flat["blocks"] = jax.tree_util.tree_map(
        lambda x: x.reshape(x.shape[0] * x.shape[1], *x.shape[2:]),
        params_pp["blocks"],
    )
    loss_flat = jax.jit(make_loss_fn(cfg_flat, mesh_flat))(params_flat, batch)

    assert np.isfinite(float(loss_pp)) and np.isfinite(float(loss_flat))
    # CE dominates; aux differs only by the per-microbatch estimate
    np.testing.assert_allclose(
        float(loss_pp), float(loss_flat), rtol=0.02
    ), (float(loss_pp), float(loss_flat))

    # one optimizer step: expert weights move
    step, init_state = make_train_step(cfg_pp, mesh_pp)
    params0, opt0 = init_state(jax.random.PRNGKey(1))
    params1, _, loss = jax.jit(step)(params0, opt0, batch)
    assert np.isfinite(float(loss))
    dw = float(jnp.abs(params1["blocks"]["w_in"] - params0["blocks"]["w_in"]).sum())
    assert dw > 0, "no gradient reached the experts through the pipeline"
