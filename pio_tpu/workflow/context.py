"""WorkflowContext — what every DASE stage receives.

The reference threads a SparkContext through every stage signature
(core/.../workflow/WorkflowContext.scala:11-28 creates it). The TPU-native
context carries the device mesh (the cluster), the storage facade (the event
store), and a PRNG key — the single-controller runtime state.
"""

from __future__ import annotations

from dataclasses import dataclass, field, replace
from typing import Any

import jax

from pio_tpu.data.eventstore import EventStore
from pio_tpu.data.storage import Storage, get_storage
from pio_tpu.parallel.mesh import MeshConfig, create_mesh


@dataclass
class WorkflowContext:
    storage: Storage
    mesh: Any = None          # jax.sharding.Mesh | None (None = single device)
    seed: int = 0
    batch: str = ""
    params: dict = field(default_factory=dict)  # runtime conf (sparkConf slot)
    # training supervision handle (workflow/lifecycle.py TrainLifecycle):
    # heartbeats, preemption checks, and the per-instance checkpoint dir.
    # Set by run_train; None outside a supervised training run.
    lifecycle: Any = None
    # the job's one CompileMeter (utils/compilecache.py): `Engine.train`
    # labels `train.algorithms` with what it counted over that span. Set
    # by run_train; None outside a job (`pio eval`).
    compile_meter: Any = None

    @property
    def event_store(self) -> EventStore:
        return EventStore(self.storage)

    def rng(self) -> jax.Array:
        return jax.random.PRNGKey(self.seed)

    def with_seed(self, seed: int) -> "WorkflowContext":
        return replace(self, seed=seed)


def create_workflow_context(
    storage: Storage | None = None,
    mesh_config: MeshConfig | None = None,
    use_mesh: bool = True,
    seed: int = 0,
    batch: str = "",
    params: dict | None = None,
) -> WorkflowContext:
    """Reference WorkflowContext.scala: conf -> SparkContext; here conf ->
    Mesh over available devices (all of them by default). When
    PIO_TPU_COORDINATOR is set, the multi-host runtime is joined first so
    the mesh spans every host's devices (parallel/distributed.py)."""
    from pio_tpu.parallel.distributed import initialize_distributed

    initialize_distributed()  # no-op unless configured; must precede mesh
    storage = storage or get_storage()
    mesh = None
    if use_mesh:
        mesh = create_mesh(mesh_config)
    return WorkflowContext(
        storage=storage, mesh=mesh, seed=seed, batch=batch,
        params=dict(params or {}),
    )
