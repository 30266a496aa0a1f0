"""Multi-host runtime initialization — the distributed communication
backend's control plane.

The reference's distributed story is Apache Spark: a driver spawns
executors and shuffles move data (SURVEY.md §2 "Parallelism & distributed-
communication components"). The TPU build replaces that with JAX's
multi-controller SPMD runtime: every host runs the SAME program,
`jax.distributed.initialize` wires the hosts into one runtime, and after
that `jax.devices()` spans all hosts — a single `Mesh` laid over it makes
XLA compile collectives that ride ICI within a slice and DCN across slices.
There is no driver/executor split and no shuffle service; the "backend" is
the compiled program itself.

Configuration mirrors the storage locator's env-var style:

    PIO_TPU_COORDINATOR   host:port of process 0 (present => multi-host)
    PIO_TPU_NUM_PROCESSES total process count
    PIO_TPU_PROCESS_ID    this process's index

On Cloud TPU pods these are auto-detected by JAX (initialize() with no
args); the env vars exist for DCN clusters and tests. Single-host runs
skip initialization entirely — every code path in this framework works
unchanged either way, because meshes are built from whatever
`jax.devices()` reports.
"""

from __future__ import annotations

import logging
import os

import jax

log = logging.getLogger("pio_tpu.parallel")

_initialized = False


def distributed_env() -> dict | None:
    """Read PIO_TPU_{COORDINATOR,NUM_PROCESSES,PROCESS_ID}; None when the
    process is not part of a multi-host job."""
    addr = os.environ.get("PIO_TPU_COORDINATOR")
    if not addr:
        return None
    nproc = os.environ.get("PIO_TPU_NUM_PROCESSES")
    pid = os.environ.get("PIO_TPU_PROCESS_ID")
    env = {"coordinator_address": addr}
    # Completeness is validated on the MERGED args+env config inside
    # initialize_distributed — a launcher may legitimately pass
    # num_processes/process_id as arguments with only the coordinator in env.
    if nproc is not None:
        env["num_processes"] = int(nproc)
    if pid is not None:
        env["process_id"] = int(pid)
    return env


def initialize_distributed(
    coordinator_address: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
) -> bool:
    """Join the multi-host runtime; returns True if initialization ran.

    Arguments fall back to the PIO_TPU_* env vars, then to JAX's TPU-pod
    auto-detection. Safe to call more than once and on single-host jobs
    (both are no-ops). Call BEFORE any other jax API touches the backend.
    """
    global _initialized
    if _initialized:
        return False
    if None not in (coordinator_address, num_processes, process_id):
        env = {}  # fully specified explicitly; env vars are irrelevant
    else:
        env = distributed_env() or {}
    kwargs = {
        "coordinator_address": coordinator_address
        or env.get("coordinator_address"),
        "num_processes": num_processes or env.get("num_processes"),
        "process_id": process_id if process_id is not None
        else env.get("process_id"),
    }
    if kwargs["coordinator_address"] is None:
        # not configured: single-host (or TPU-pod auto-detect at first use)
        return False
    if kwargs["num_processes"] is None or kwargs["process_id"] is None:
        # A coordinator with no process count/index means every host would
        # form its own 1-process "cluster" — fail fast on the merged config.
        raise ValueError(
            "a coordinator address is configured but num_processes/"
            "process_id are not (set PIO_TPU_NUM_PROCESSES/"
            "PIO_TPU_PROCESS_ID or pass them as arguments); all three are "
            "required for a multi-host job"
        )
    jax.distributed.initialize(**kwargs)
    _initialized = True
    log.info(
        "joined distributed runtime: process %s/%s via %s "
        "(%d local / %d global devices)",
        kwargs["process_id"], kwargs["num_processes"],
        kwargs["coordinator_address"],
        jax.local_device_count(), jax.device_count(),
    )
    return True


def is_primary() -> bool:
    """True on process 0 — the process that writes checkpoints/metadata
    (single-controller duties in the multi-controller model)."""
    return jax.process_index() == 0


def any_process(flag: bool) -> bool:
    """OR-reduce a per-process boolean across all hosts (identity on a
    single host). Used for the preemption flag: the scheduler may
    SIGTERM only one host's VM, and a host that force-saved while its
    peers kept training would deadlock the save barrier — every host
    must agree to stop before any of them does. Collective: all
    processes must call it at the same point (the trainers do, at span
    boundaries)."""
    if jax.process_count() <= 1:
        return flag
    import numpy as np
    from jax.experimental import multihost_utils

    return bool(
        multihost_utils.process_allgather(np.asarray([flag])).any()
    )


def barrier(name: str) -> None:
    """Block until every process reaches this point (no-op single-host).

    Used at checkpoint-save boundaries: every host contributes its
    addressable shards to an orbax save, and process 0 must not record
    the step as durable (metadata write, COMPLETED transition) until all
    hosts have finished theirs — otherwise a preemption between hosts
    leaves a checkpoint that restores on some meshes and not others.
    """
    if jax.process_count() <= 1:
        return
    from jax.experimental import multihost_utils

    multihost_utils.sync_global_devices(name)


def runtime_info() -> dict:
    """Topology snapshot for `pio status` / logs."""
    return {
        "process_index": jax.process_index(),
        "process_count": jax.process_count(),
        "local_devices": jax.local_device_count(),
        "global_devices": jax.device_count(),
        "platform": jax.devices()[0].platform,
        "distributed": _initialized,
    }
