"""TPU accelerator manager: chip/topology detection feeding the resource model.

Reference parity: ``python/ray/_private/accelerators/tpu.py:70``
(TPUAcceleratorManager) and ``python/ray/util/accelerators/tpu.py`` (pod
helpers).  Detection is env/device-file driven and never calls a metadata
service (zero-egress environments) — a host exposes its chips as device files
(``/dev/accel*``, or one VFIO group per chip under ``/dev/vfio/`` on v5e and
newer) and a GKE/GCE-style deployment sets the standard ``TPU_*`` variables.

Detected topology surfaces as schedulable resources at ``init``:
  TPU                  chips on this host (the reference's TPU resource)
  TPU-<GEN>            accelerator-type marker, e.g. TPU-V5E (1 per chip)
  TPU-<pod_type>-head  exactly one, on worker 0 of a pod slice — lets a
                       driver pin one task per pod for SPMD launch
"""

from __future__ import annotations

import glob
import os
from typing import Dict, Optional, Tuple

RESOURCE_NAME = "TPU"
VALID_CHIP_REQUESTS = (1, 2, 4, 8)  # whole-host or sub-host chip groups

VISIBLE_CHIPS_ENV = "TPU_VISIBLE_CHIPS"
NOSET_VISIBLE_CHIPS_ENV = "CA_EXPERIMENTAL_NOSET_TPU_VISIBLE_CHIPS"
ACCELERATOR_TYPE_ENV = "TPU_ACCELERATOR_TYPE"  # e.g. "v5e-16" (pod type)
CHIPS_PER_HOST_BOUNDS_ENV = "TPU_CHIPS_PER_HOST_BOUNDS"  # e.g. "2,2,1"
HOST_BOUNDS_ENV = "TPU_HOST_BOUNDS"
WORKER_ID_ENV = "TPU_WORKER_ID"
POD_NAME_ENV = "TPU_NAME"
# the same two bounds under the names newer libtpu reads first
CHIPS_PER_PROCESS_BOUNDS_ENV = "TPU_CHIPS_PER_PROCESS_BOUNDS"
PROCESS_BOUNDS_ENV = "TPU_PROCESS_BOUNDS"

# one entry per chip; /dev/vfio/vfio is the container node, not a chip
_DEVICE_GLOBS = ("/dev/accel*", "/dev/vfio/[0-9]*")


def visible_chip_ids() -> Optional[list]:
    """Chip ids this process may use, or None when unrestricted
    (get_current_process_visible_accelerator_ids analogue)."""
    v = os.environ.get(VISIBLE_CHIPS_ENV)
    if v is None or v == "":
        return None
    return [s for s in v.split(",") if s != ""]


def num_tpu_chips() -> int:
    """TPU chips this process can reach.  Priority: visible-chips
    restriction, device files, host-bounds env.  The files come first because
    the bounds describe the host's board, not what is attached: a one-chip
    machine cut from a 2x2 host still says TPU_CHIPS_PER_HOST_BOUNDS=2,2,1."""
    vis = visible_chip_ids()
    if vis is not None:
        return len(vis)
    for pattern in _DEVICE_GLOBS:
        dev = glob.glob(pattern)
        if dev:
            return len(dev)
    bounds = os.environ.get(CHIPS_PER_HOST_BOUNDS_ENV)
    if bounds:
        try:
            n = 1
            for part in bounds.split(","):
                n *= int(part)
            return n
        except ValueError:
            pass
    return 0


def pod_type() -> Optional[str]:
    """TPU pod/slice type, e.g. "v5e-16" (_get_current_node_tpu_pod_type)."""
    return os.environ.get(ACCELERATOR_TYPE_ENV) or None


def accelerator_type() -> Optional[str]:
    """Marker-resource name, e.g. "TPU-V5E" (get_current_node_accelerator_type)."""
    t = pod_type()
    if not t:
        return None
    return "TPU-" + t.split("-")[0].upper()


def worker_id() -> Optional[int]:
    v = os.environ.get(WORKER_ID_ENV)
    try:
        return int(v) if v is not None else None
    except ValueError:
        return None


def pod_name() -> Optional[str]:
    return os.environ.get(POD_NAME_ENV)


def _cores_per_chip(gen: str) -> int:
    # pod-type suffixes count TensorCores on v2-v4/v5p (2 per chip) but
    # chips on the single-core-per-chip efficiency gens (v5e/v6e)
    return 1 if gen in ("v5e", "v5litepod", "v6e") else 2


def num_workers_in_pod() -> Optional[int]:
    """Hosts in this pod slice = slice cores-or-chips / per-host equivalent
    (get_num_workers_in_current_tpu_pod analogue)."""
    t = pod_type()
    per_host = num_tpu_chips()
    if not t or per_host <= 0:
        return None
    try:
        gen, suffix = t.split("-")[0], int(t.split("-")[1])
    except (IndexError, ValueError):
        return None
    return max(1, suffix // (per_host * _cores_per_chip(gen)))


def validate_chip_request(n: float) -> None:
    """TPU requests must be 1/2/4/8 chips (ICI-connected groups) or a
    positive fraction <1 of one chip (validate_resource_request_quantity)."""
    if n <= 0:
        raise ValueError(f"TPU request must be positive, got {n}")
    if n < 1:
        return
    if n != int(n) or int(n) not in VALID_CHIP_REQUESTS:
        raise ValueError(
            f"TPU request of {n} is invalid: whole-chip requests must be one "
            f"of {VALID_CHIP_REQUESTS} (chips in an ICI-connected group)"
        )


def worker_pool(n_tpus: float) -> str:
    """Worker pool serving a request for n_tpus chips: "cpu", "tpu" (one
    chip, whole or a shared fraction) or "tpu<N>".  A worker's chip view is
    fixed when its process starts, so the pool name carries the chip count
    and a request only ever meets workers that were started with its view."""
    if not n_tpus:
        return "cpu"
    return "tpu" if n_tpus <= 1 else f"tpu{int(n_tpus)}"


def pool_chips(pool: str) -> int:
    """Chips a worker of `pool` is started with (inverse of worker_pool)."""
    if not pool.startswith("tpu"):
        return 0
    return int(pool[3:] or 1)


class ChipAllocator:
    """Per-host chip assignment for spawned TPU workers.

    A worker gets as many chips as its pool's request: an aligned group of
    ids (i*n .. i*n+n-1, neighbours on the host's board) for n whole chips,
    one chip for the one-chip pool.  Least-loaded first: 1:1 pinning while
    groups are free, stable sharing (never an unrestricted view) once
    fractional requests oversubscribe a chip.  Honors a parent process's
    TPU_VISIBLE_CHIPS restriction — ids are drawn from that set, not range(n).
    """

    def __init__(self, n_chips: int):
        vis = visible_chip_ids()
        ids = vis if vis is not None else [str(i) for i in range(max(n_chips, 0))]
        self._load: Dict[str, int] = {cid: 0 for cid in ids}

    def acquire(self, n: int = 1) -> Tuple[str, ...]:
        ids = list(self._load)
        groups = [tuple(ids[i:i + n]) for i in range(0, len(ids) - n + 1, n)]
        if not groups:
            return ()
        best = min(groups, key=lambda g: sum(self._load[c] for c in g))
        for cid in best:
            self._load[cid] += 1
        return best

    def release(self, chips: Tuple[str, ...]) -> None:
        for cid in chips:
            if self._load.get(cid, 0) > 0:
                self._load[cid] -= 1


def additional_resources() -> Dict[str, float]:
    """Topology-derived resources beyond the TPU chip count: the
    accelerator-type marker and, on worker 0 only, the pod-head resource
    (get_current_node_additional_resources analogue)."""
    out: Dict[str, float] = {}
    chips = num_tpu_chips()
    if chips <= 0:
        return out
    at = accelerator_type()
    if at:
        out[at] = float(chips)
    pt = pod_type()
    wid = worker_id()
    if pt and wid == 0:
        out[f"TPU-{pt}-head"] = 1.0
    return out


def node_labels() -> Dict[str, str]:
    """Topology labels this node registers with the head, feeding
    NodeLabelSchedulingStrategy (the reference's ray.io/* node labels +
    the TPU fields its autoscaler puts in node metadata).  Keys:

      ca.io/accelerator-type   "TPU-V5E" marker (generation, upper-case)
      ca.io/tpu-generation     "v5e"
      ca.io/tpu-pod-type       "v5e-16" (slice type)
      ca.io/tpu-topology       TPU_CHIPS_PER_HOST_BOUNDS, e.g. "2,2,1"
      ca.io/tpu-slice-name     TPU_NAME (pod/slice identity for gang placement)
      ca.io/tpu-worker-id      "0".."N-1" within the slice
    """
    out: Dict[str, str] = {}
    if num_tpu_chips() <= 0:
        return out
    at = accelerator_type()
    if at:
        out["ca.io/accelerator-type"] = at
    pt = pod_type()
    if pt:
        out["ca.io/tpu-pod-type"] = pt
        out["ca.io/tpu-generation"] = pt.split("-")[0]
    bounds = os.environ.get(CHIPS_PER_HOST_BOUNDS_ENV)
    if bounds:
        out["ca.io/tpu-topology"] = bounds
    nm = pod_name()
    if nm:
        out["ca.io/tpu-slice-name"] = nm
    wid = worker_id()
    if wid is not None:
        out["ca.io/tpu-worker-id"] = str(wid)
    return out


def detect_node_labels(node_id: Optional[str] = None) -> Dict[str, str]:
    """The one label-derivation used by every node: auto-detected TPU
    topology labels + CA_NODE_LABELS env overrides (+ ca.io/node-id when the
    caller knows it).  Head-embedded node and agents must share this, or
    NodeLabelSchedulingStrategy selectors behave differently per node kind."""
    labels = dict(node_labels())
    labels.update(parse_labels_env(os.environ.get("CA_NODE_LABELS")))
    if node_id is not None:
        labels["ca.io/node-id"] = node_id
    return labels


def parse_labels_env(env_val: Optional[str]) -> Dict[str, str]:
    """Parse a CA_NODE_LABELS-style JSON object into a str->str label map;
    malformed or non-object JSON yields {} (a bad env var must not kill a
    node agent at startup)."""
    if not env_val:
        return {}
    import json

    try:
        obj = json.loads(env_val)
    except ValueError:
        return {}
    if not isinstance(obj, dict):
        return {}
    return {str(k): str(v) for k, v in obj.items()}


COMPILE_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def compile_cache_dir() -> str:
    """Directory of JAX's persistent compilation cache for TPU workers, which
    JAX reads from JAX_COMPILATION_CACHE_DIR at import: the ambient value when
    one is set, else `.jax_cache` at the root of this checkout.  Every TPU
    worker is a new process (a replica start, a trainer restart), so without
    it each one compiles from nothing.  The path is fixed because it is part
    of what a cache entry is found by: a directory that moves never hits."""
    return os.environ.get(COMPILE_CACHE_ENV) or os.path.join(_CHECKOUT, ".jax_cache")


# board shape libtpu is told for a view of n chips of one host
_VIEW_BOUNDS = {1: "1,1,1", 2: "1,2,1", 4: "2,2,1", 8: "2,4,1"}


def visible_chips_env_for_worker(chips: Tuple[str, ...]) -> Dict[str, str]:
    """Env a spawned TPU-pool worker receives to see exactly `chips`
    (set_current_process_visible_accelerator_ids analogue): the ids, and the
    bounds of a one-process slice of that shape — without them libtpu still
    expects the whole host's board behind a restricted id list.  Empty when
    pinning is disabled or no chip was assigned."""
    if not chips or os.environ.get(NOSET_VISIBLE_CHIPS_ENV):
        return {}
    view, one = _VIEW_BOUNDS[len(chips)], "1,1,1"
    return {
        VISIBLE_CHIPS_ENV: ",".join(chips),
        CHIPS_PER_PROCESS_BOUNDS_ENV: view,
        PROCESS_BOUNDS_ENV: one,
        CHIPS_PER_HOST_BOUNDS_ENV: view,
        HOST_BOUNDS_ENV: one,
    }


def worker_env(pool: str, alloc: Optional[ChipAllocator]) -> Tuple[Dict[str, str], Tuple[str, ...]]:
    """What the head or a node agent adds to the environment of a worker it
    spawns for `pool`, and the chips it pinned it to (release them when the
    process is gone).  A CPU worker must not grab the accelerator: jax is
    pinned to the host platform if user code imports it.  A TPU worker gets
    the compilation cache and, on a multi-chip host (`alloc`), exactly its
    pool's chips so that concurrent workers don't fight over a device; a
    single-chip host leaves the chip variables untouched."""
    n_chips = pool_chips(pool)
    if not n_chips:
        return {"JAX_PLATFORMS": "cpu"}, ()
    chips = alloc.acquire(n_chips) if alloc is not None else ()
    env = {COMPILE_CACHE_ENV: compile_cache_dir()}
    env.update(visible_chips_env_for_worker(chips))
    return env, chips
