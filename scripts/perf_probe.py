"""TPU perf probe for the flagship model: A/B attention impls, remat, batch.

Run on the real chip (no JAX_PLATFORMS override):
    python scripts/perf_probe.py [variant ...]
Variants: jnp8 flash8 jnp16 flash16 jnp16r jnp32r attnmicro
Default: all step variants.

A hard watchdog (CA_PROBE_TIMEOUT seconds, default 900) SIGKILLs the whole
process group if the accelerator runtime wedges: a hung runtime makes
jax.devices()/compilation block forever in C++ where no Python exception or
signal handler can reach, and the runtime forks helper processes that would
otherwise survive the probe and keep the device wedged for the next run
(BENCH_r05 "probe hung").  killpg is the only reliable way out.
"""

import os
import signal
import sys
import threading
import time


def _arm_watchdog():
    timeout_s = float(os.environ.get("CA_PROBE_TIMEOUT", "900"))
    if timeout_s <= 0:
        return
    # own process group, so the watchdog's killpg takes the accelerator
    # runtime's forked helpers down with us (and nothing else)
    if os.getpid() != os.getpgid(0):
        try:
            os.setpgid(0, 0)
        except OSError:
            pass

    def _fire():
        print(
            f"[perf_probe] watchdog: no completion within {timeout_s:.0f}s — "
            "killing process group (wedged accelerator runtime)",
            file=sys.stderr,
            flush=True,
        )
        try:
            os.killpg(os.getpgid(0), signal.SIGKILL)
        except OSError:
            os.kill(os.getpid(), signal.SIGKILL)

    t = threading.Timer(timeout_s, _fire)
    t.daemon = True
    t.start()
    return t


_WATCHDOG = _arm_watchdog()

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, ".")

from cluster_anywhere_tpu.models import TransformerConfig, make_train_step
from cluster_anywhere_tpu.parallel import MeshSpec, make_mesh


def base_cfg(**kw):
    return TransformerConfig(
        vocab_size=32000,
        d_model=1024,
        n_layers=8,
        n_heads=16,
        n_kv_heads=8,
        d_head=64,
        d_ff=4096,
        max_seq_len=1024,
        dtype=jnp.bfloat16,
        **kw,
    )


def run_step(name, cfg, b, t, n=10):
    mesh = make_mesh(MeshSpec(dp=1))
    step, init_state = make_train_step(cfg, mesh)
    params, opt = init_state(jax.random.PRNGKey(0))
    batch = {"ids": jnp.asarray(np.random.randint(0, 32000, (b, t + 1), dtype=np.int32))}
    jstep = jax.jit(step, donate_argnums=(0, 1))
    t0 = time.time()
    params, opt, loss = jstep(params, opt, batch)
    jax.block_until_ready(loss)
    compile_s = time.time() - t0
    t0 = time.time()
    for _ in range(n):
        params, opt, loss = jstep(params, opt, batch)
    jax.block_until_ready(loss)
    dt = (time.time() - t0) / n
    print(
        f"{name:10s}: {dt*1000:7.1f} ms/step  {b*t/dt:10,.0f} tok/s  "
        f"(compile {compile_s:.0f}s, loss {float(loss):.3f})",
        flush=True,
    )
    return dt


def attn_micro():
    from cluster_anywhere_tpu.ops.attention import flash_attention, reference_attention

    b, t, h, d = 8, 1024, 16, 64
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, t, h, d), jnp.bfloat16)
    k = jax.random.normal(ks[1], (b, t, h, d), jnp.bfloat16)
    v = jax.random.normal(ks[2], (b, t, h, d), jnp.bfloat16)

    def bench(name, fn):
        f = jax.jit(jax.grad(lambda q, k, v: fn(q, k, v).astype(jnp.float32).sum(), argnums=(0, 1, 2)))
        out = f(q, k, v)
        jax.block_until_ready(out)
        t0 = time.time()
        for _ in range(20):
            out = f(q, k, v)
        jax.block_until_ready(out)
        print(f"attn {name:24s}: {(time.time()-t0)/20*1000:7.2f} ms fwd+bwd", flush=True)

    bench("jnp", lambda q, k, v: reference_attention(q, k, v, causal=True))
    for bq, bk in ((128, 128), (256, 256), (512, 512), (256, 512), (512, 1024), (1024, 1024)):
        bench(
            f"flash bq{bq} bk{bk}",
            lambda q, k, v, bq=bq, bk=bk: flash_attention(
                q, k, v, causal=True, block_q=bq, block_k=bk
            ),
        )


def serve_smoke():
    """Serving-plane smoke under the probe's watchdog: start a cluster,
    deploy a tiny ContinuousLLMServer, stream one SSE request through the
    HTTP proxy, tear down.  A wedged accelerator runtime (or a serve
    regression) can't hang the harness — the watchdog killpg's us."""
    import socket

    import cluster_anywhere_tpu as ca
    from cluster_anywhere_tpu import serve
    from cluster_anywhere_tpu.llm.processor import ProcessorConfig
    from cluster_anywhere_tpu.llm.serve_llm import build_continuous_llm_deployment
    from cluster_anywhere_tpu.microbenchmark import _sse_request

    ca.init(num_cpus=4)
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    serve.start(host="127.0.0.1", port=port)
    app = build_continuous_llm_deployment(
        ProcessorConfig(max_prompt_len=64, max_new_tokens=8),
        slots=2, num_replicas=1, sse_ingress=True,
    )
    serve.run(app, name="probesmoke", route_prefix="/probesmoke")
    status, ttft, total, n_events = _sse_request(
        "127.0.0.1", port, "/probesmoke",
        {"prompt": "probe smoke", "max_new_tokens": 8}, timeout=120,
    )
    assert status == 200, f"serve smoke: HTTP {status}"
    assert n_events >= 8, f"serve smoke: {n_events} SSE events (wanted >= 8)"
    print(
        f"serve smoke : {n_events} tokens streamed, TTFT {ttft*1e3:7.1f} ms "
        f"(cold: includes jit compile), total {total*1e3:7.1f} ms",
        flush=True,
    )
    serve.delete("probesmoke")
    serve.shutdown()
    ca.shutdown()


VARIANTS = {
    "jnp8": lambda: run_step("jnp b8", base_cfg(attn_impl="jnp"), 8, 1024),
    "flash8": lambda: run_step("flash b8", base_cfg(attn_impl="flash"), 8, 1024),
    "jnp16": lambda: run_step("jnp b16", base_cfg(attn_impl="jnp"), 16, 1024),
    "flash16": lambda: run_step("flash b16", base_cfg(attn_impl="flash"), 16, 1024),
    "jnp16r": lambda: run_step("jnp b16 rm", base_cfg(attn_impl="jnp", remat=True), 16, 1024),
    "jnp32r": lambda: run_step("jnp b32 rm", base_cfg(attn_impl="jnp", remat=True), 32, 1024),
    "attnmicro": attn_micro,
    "serve": serve_smoke,
}


def main():
    names = [a for a in sys.argv[1:] if a in VARIANTS] or ["jnp8", "flash8", "jnp16", "flash16"]
    print(f"devices: {jax.devices()}", flush=True)
    from cluster_anywhere_tpu.util.logplane import log_stats

    lp0 = log_stats()
    for n in names:
        VARIANTS[n]()
    # trailing JSON record for the BENCH harness: log-plane counter deltas
    # over the probe (zeros unless capture is active in this process — the
    # row exists either way so "plane off" and "never recorded" differ)
    import json as _json

    lp1 = log_stats()
    print(
        _json.dumps(
            {"logplane_deltas": {k: lp1[k] - lp0.get(k, 0) for k in lp1}}
        ),
        flush=True,
    )
    if _WATCHDOG is not None:
        _WATCHDOG.cancel()  # clean exit: don't let the timer outlive main


if __name__ == "__main__":
    main()
