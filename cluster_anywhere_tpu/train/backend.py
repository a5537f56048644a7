"""Framework backends: per-worker process-group setup hooks.

Analogue of the reference's `_TorchBackend` (train/torch/config.py:66-153,
which calls torch.distributed.init_process_group) — except the TPU-native
backend wires up JAX: rank env vars always; `jax.distributed.initialize`
when the config asks for a true multi-host runtime (TPU pod / multi-proc
CPU). Single-host JAX needs no collective bootstrap at all: a Mesh over
locally visible chips is enough, XLA emits the ICI collectives.
"""

from __future__ import annotations

from typing import TYPE_CHECKING

from .config import BackendConfig, JaxConfig

if TYPE_CHECKING:
    from .worker_group import WorkerGroup


class Backend:
    """No-op base backend."""

    def on_start(self, worker_group: "WorkerGroup", backend_config: BackendConfig):
        pass

    def on_training_start(
        self, worker_group: "WorkerGroup", backend_config: BackendConfig
    ):
        pass

    def on_shutdown(self, worker_group: "WorkerGroup", backend_config: BackendConfig):
        pass


def ensure_cpu_collectives():
    """Select Gloo for CPU cross-process collectives.  Must run BEFORE the
    runtime initializes (newer jaxlibs default to "none" and every
    multi-process computation raises).  The knob only affects the CPU
    backend, so it is set unconditionally — probing the platform here would
    initialize backends ahead of distributed.initialize and pin the mesh
    local; TPU/GPU runtimes keep their native ICI/DCN paths regardless."""
    import jax

    try:
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    except Exception:
        pass  # older jax: gloo is the baked-in default


def _init_jax_distributed(coordinator: str, num_processes: int, process_id: int):
    import jax

    from ..util import tracing

    tracing.enable_jax_profiling()  # the worker has jax loaded from here on
    ensure_cpu_collectives()
    jax.distributed.initialize(
        coordinator_address=coordinator,
        num_processes=num_processes,
        process_id=process_id,
    )


class JaxBackend(Backend):
    def on_start(self, worker_group: "WorkerGroup", backend_config: JaxConfig):
        n = worker_group.num_workers
        local_ranks = worker_group.local_ranks()
        node_ranks = worker_group.node_ranks()
        import cluster_anywhere_tpu as ca

        coordinator = None
        if backend_config.init_jax_distributed:
            port = backend_config.coordinator_port or ca.get(
                worker_group.workers[0].free_port.remote()
            )
            host = worker_group.node_infos[0]["hostname"]
            coordinator = f"{host}:{port}"

        refs = []
        for rank, w in enumerate(worker_group.workers):
            env = {
                "CA_WORLD_SIZE": str(n),
                "CA_WORLD_RANK": str(rank),
                "CA_LOCAL_RANK": str(local_ranks[rank]),
                "CA_NODE_RANK": str(node_ranks[rank]),
            }
            if coordinator:
                env["CA_COORDINATOR"] = coordinator
            refs.append(w.set_env.remote(env))
        ca.get(refs)

        if coordinator:
            ca.get(
                [
                    w.execute.remote(_init_jax_distributed, coordinator, n, rank)
                    for rank, w in enumerate(worker_group.workers)
                ]
            )


def _init_torch_pg(master_addr: str, master_port: int, world_size: int, rank: int,
                   backend: str, timeout_s: float):
    import datetime
    import os as _os

    import torch.distributed as dist

    _os.environ["MASTER_ADDR"] = master_addr
    _os.environ["MASTER_PORT"] = str(master_port)
    dist.init_process_group(
        backend=backend,
        world_size=world_size,
        rank=rank,
        timeout=datetime.timedelta(seconds=timeout_s),
    )


def _destroy_torch_pg():
    import torch.distributed as dist

    if dist.is_initialized():
        dist.destroy_process_group()


class TorchBackend(Backend):
    """torch.distributed process group across the worker group (reference
    _TorchBackend, train/torch/config.py:66-153): rank-0's node hosts the
    TCP store; every worker joins with its rank envs, enabling DDP/FSDP
    training loops unchanged (gloo on CPU hosts, nccl where tenable)."""

    def on_start(self, worker_group: "WorkerGroup", backend_config):
        import cluster_anywhere_tpu as ca

        n = worker_group.num_workers
        local_ranks = worker_group.local_ranks()
        node_ranks = worker_group.node_ranks()
        port = backend_config.port or ca.get(
            worker_group.workers[0].free_port.remote()
        )
        host = worker_group.node_infos[0]["hostname"]
        refs = []
        for rank, w in enumerate(worker_group.workers):
            env = {
                "CA_WORLD_SIZE": str(n),
                "CA_WORLD_RANK": str(rank),
                "CA_LOCAL_RANK": str(local_ranks[rank]),
                "CA_NODE_RANK": str(node_ranks[rank]),
                "MASTER_ADDR": host,
                "MASTER_PORT": str(port),
            }
            refs.append(w.set_env.remote(env))
        ca.get(refs)
        ca.get(
            [
                w.execute.remote(
                    _init_torch_pg, host, port, n, rank,
                    backend_config.backend, backend_config.timeout_s,
                )
                for rank, w in enumerate(worker_group.workers)
            ]
        )

    def on_shutdown(self, worker_group: "WorkerGroup", backend_config):
        import cluster_anywhere_tpu as ca

        try:
            ca.get([w.execute.remote(_destroy_torch_pg) for w in worker_group.workers])
        except Exception:
            pass
