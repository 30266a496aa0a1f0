"""Device mesh + sharding helpers — the replacement for the reference's
Spark cluster substrate (SURVEY.md section 2 "Parallelism & distributed-
communication components").

The reference scales by partitioning RDDs over Spark executors and shuffling
between stages; here a `jax.sharding.Mesh` over TPU chips plays that role:
 * axis "data"  — batch/entity sharding (Spark's RDD partitioning);
 * axis "model" — factor/feature sharding (MLlib's block matrices);
collectives (psum/all_gather/reduce_scatter over ICI) replace shuffles.

Multi-host: `jax.devices()` already spans hosts under jax.distributed; the
same mesh axes then ride ICI within a slice and DCN across slices.
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DATA_AXIS = "data"
SEQ_AXIS = "seq"
MODEL_AXIS = "model"


@dataclass(frozen=True)
class MeshConfig:
    """Mesh shape: data-parallel x sequence-parallel x model-parallel.
    -1 = use all remaining. The seq axis carries ring/all-to-all sequence
    parallelism (ops/attention.py); it is 1 for the non-sequence templates."""

    data: int = -1
    seq: int = 1
    model: int = 1

    def resolve(self, n_devices: int) -> tuple[int, int, int]:
        model = self.model if self.model > 0 else 1
        seq = self.seq if self.seq > 0 else 1
        data = self.data if self.data > 0 else n_devices // (model * seq)
        if data * seq * model > n_devices:
            raise ValueError(
                f"mesh {data}x{seq}x{model} needs {data * seq * model} "
                f"devices, have {n_devices}"
            )
        return data, seq, model


def create_mesh(
    config: MeshConfig | None = None, devices: list | None = None
) -> Mesh:
    devices = devices if devices is not None else jax.devices()
    config = config or MeshConfig()
    data, seq, model = config.resolve(len(devices))
    dev_array = np.array(devices[: data * seq * model]).reshape(
        data, seq, model
    )
    return Mesh(dev_array, (DATA_AXIS, SEQ_AXIS, MODEL_AXIS))


def describe_devices() -> str:
    """Count, platform, kind and jax version in one line. Every process
    that takes the device logs it, so a run can show which backend it
    ran on."""
    devices = jax.devices()
    return (f"{len(devices)} x {devices[0].platform} "
            f"({devices[0].device_kind}), jax {jax.__version__}")


def describe_device_memory() -> str:
    """Peak and current bytes in use per local device, as the backend
    reports them (the CPU backend reports none)."""
    parts = []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        parts.append(
            f"{d.id}: peak {stats.get('peak_bytes_in_use', 'n/a')} "
            f"in_use {stats.get('bytes_in_use', 'n/a')} "
            f"limit {stats.get('bytes_limit', 'n/a')}")
    return "; ".join(parts)


def data_sharding(mesh: Mesh) -> NamedSharding:
    """Leading axis split across the data axis."""
    return NamedSharding(mesh, P(DATA_AXIS))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def pad_to_multiple(x: np.ndarray, multiple: int, axis: int = 0, fill=0):
    """Pad `axis` of x up to a multiple (XLA wants static, divisible shapes)."""
    n = x.shape[axis]
    target = math.ceil(n / multiple) * multiple if n else multiple
    if target == n:
        return x, n
    pad_width = [(0, 0)] * x.ndim
    pad_width[axis] = (0, target - n)
    return np.pad(x, pad_width, constant_values=fill), n


def shard_batch(x: np.ndarray, mesh: Mesh) -> jax.Array:
    """Host numpy -> device array sharded on the data axis (the analogue of
    parallelize()-ing an RDD). Pads the leading axis to the mesh size."""
    n_data = mesh.shape[DATA_AXIS]
    padded, _ = pad_to_multiple(x, n_data, axis=0)
    return jax.device_put(padded, data_sharding(mesh))


def replicate(x, mesh: Mesh) -> jax.Array:
    return jax.device_put(x, replicated(mesh))
