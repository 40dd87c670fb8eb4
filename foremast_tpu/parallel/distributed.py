"""Multi-host (DCN) scale-out: jax.distributed initialization + global mesh.

The reference's only distributed mechanism is N shared-nothing workers
leasing jobs from Elasticsearch (docs/guides/design.md:37-43); adding a
host adds a poller. Here adding a host extends the SPMD mesh: each process
calls `initialize()` (jax.distributed handshake over DCN), after which
`jax.devices()` spans every host's chips and the SAME fleet-sharded
program (parallel/fleet.py) runs across pods — batch halves per host,
reductions ride ICI within a pod and DCN across pods, and no engine code
changes.

Env contract (standard JAX multi-process variables, all optional on
Cloud TPU where they are auto-detected from the pod metadata):

  COORDINATOR_ADDRESS   host:port of process 0 (e.g. "10.0.0.2:8476")
  NUM_PROCESSES         world size
  PROCESS_ID            this process's rank
  LOCAL_DEVICE_IDS      comma-separated local chip ids (optional)

`HostInfo` + `process_batch_slice` give the host-side scheduler the piece
of a global batch this process should feed its addressable devices —
inputs are created per-host, sharded with `jax.make_array_from_process_local_data`.
"""
from __future__ import annotations

import logging
from dataclasses import dataclass

import jax

from .mesh import fleet_mesh
from ..utils import knobs

log = logging.getLogger("foremast_tpu.parallel")

__all__ = ["initialize", "HostInfo", "host_info", "global_fleet_mesh",
           "process_batch_slice", "replica_identity"]

_initialized = False


def initialize(coordinator: str | None = None, num_processes: int | None = None,
               process_id: int | None = None, env: dict | None = None) -> bool:
    """Join (or skip joining) the multi-host world. Idempotent.

    Returns True if jax.distributed was initialized by this call, False if
    running single-host (no coordinator configured) or already initialized.
    Safe to call unconditionally at runtime startup: single-host deploys
    simply proceed with local devices.
    """
    global _initialized
    if _initialized:
        return False
    # env reads resolve through the knob registry (defaults + tolerant
    # parse live there): a templated NUM_PROCESSES=garbage falls back to
    # 0 with a log line instead of a ValueError at boot
    coordinator = coordinator or knobs.read("COORDINATOR_ADDRESS", env)
    n = num_processes if num_processes is not None \
        else knobs.read("NUM_PROCESSES", env)
    pid = process_id if process_id is not None \
        else knobs.read("PROCESS_ID", env)
    if not coordinator or n <= 1:
        # single-host, or Cloud TPU pod where jax auto-detects: only call
        # into jax.distributed when the pod metadata names MORE THAN ONE
        # worker. A single-host TPU VM sets TPU_WORKER_HOSTNAMES=localhost
        # too, and there the argument-less jax.distributed.initialize()
        # goes to the metadata server for the rest of the cluster spec —
        # on a machine with no metadata server it raises before the first
        # cycle (observed on the v5e check machine), for a world of one
        # that needs no handshake. A partial config (coordinator without
        # world size or vice versa, or a templated NUM_PROCESSES=1) must
        # not kill a runtime that works fine single-host either — warn
        # and proceed local.
        workers = [h for h in
                   knobs.read("TPU_WORKER_HOSTNAMES", env).split(",")
                   if h.strip()]
        if len(workers) > 1:
            jax.distributed.initialize()
            _initialized = True
            return True
        if coordinator or n > 1:
            log.warning(
                "incomplete multi-host config (COORDINATOR_ADDRESS=%r, "
                "NUM_PROCESSES=%s); need both — continuing single-host",
                coordinator, n,
            )
        return False
    kwargs = {"coordinator_address": coordinator, "num_processes": n}
    if pid >= 0:
        kwargs["process_id"] = pid
    local = knobs.read("LOCAL_DEVICE_IDS", env)
    if local:
        kwargs["local_device_ids"] = [int(x) for x in local.split(",")]
    jax.distributed.initialize(**kwargs)
    _initialized = True
    return True


def replica_identity(env: dict | None = None):
    """(replica_id, static_members) for the sharded brain
    (engine/sharding.py): each process of a multi-process world is one
    shard-ring replica, with the membership FIXED by the launcher — no
    archive heartbeats needed, rebalance only on restart with a new world
    size. Post-``initialize()`` the live jax.distributed world is
    authoritative; before it (or single-host) the registered
    NUM_PROCESSES/PROCESS_ID knobs decide. Returns ("", None) for
    single-host deploys — the runtime then falls back to REPLICA_ID /
    hostname-pid identity with archive-heartbeat membership."""
    if _initialized:
        n, pid = jax.process_count(), jax.process_index()
    else:
        n = knobs.read("NUM_PROCESSES", env)
        pid = knobs.read("PROCESS_ID", env)
    if n and n > 1 and pid is not None and pid >= 0:
        return f"proc-{pid}", [f"proc-{i}" for i in range(n)]
    return "", None


@dataclass(frozen=True)
class HostInfo:
    process_id: int
    num_processes: int
    local_devices: int
    global_devices: int


def host_info() -> HostInfo:
    return HostInfo(
        process_id=jax.process_index(),
        num_processes=jax.process_count(),
        local_devices=jax.local_device_count(),
        global_devices=jax.device_count(),
    )


def global_fleet_mesh(model_parallel: int = 1):
    """Fleet mesh over EVERY process's devices (== fleet_mesh single-host)."""
    return fleet_mesh(jax.devices(), model_parallel=model_parallel)


def process_batch_slice(global_batch: int, info: HostInfo | None = None) -> slice:
    """This process's contiguous slice of a fleet-sharded global batch.

    The global batch must divide evenly by process count (pad first with
    parallel.mesh.pad_to_multiple); each host materializes only its slice
    and hands it to jax.make_array_from_process_local_data.
    """
    info = info or host_info()
    if global_batch % info.num_processes != 0:
        raise ValueError(
            f"global batch {global_batch} not divisible by "
            f"{info.num_processes} processes; pad it first"
        )
    per = global_batch // info.num_processes
    return slice(info.process_id * per, (info.process_id + 1) * per)
