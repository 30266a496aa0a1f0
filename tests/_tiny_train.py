"""A `run_train` small enough for a test, or for a recorded chip trace:
the recommendation engine's ALS over seeded interactions that a
DataSource hands over, against in-memory storage. Shared by
tests/test_train_spans.py and tests/record_train_trace.py."""

import numpy as np

from pio_tpu.controller.base import (
    DataSource, FirstServing, IdentityPreparator,
)
from pio_tpu.controller.engine import Engine, EngineParams
from pio_tpu.data.bimap import EntityIdIndex
from pio_tpu.data.eventstore import Interactions
from pio_tpu.data.storage import Storage
from pio_tpu.models.recommendation import ALSAlgorithm


def memory_storage() -> Storage:
    return Storage({
        "PIO_STORAGE_SOURCES_MEM_TYPE": "memory",
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "MEM",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "MEM",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "MEM",
    })


def tiny_engine(n_users: int = 300, n_items: int = 200, nnz: int = 5000,
                seed: int = 0) -> Engine:
    """Distinct (user, item) pairs, levels 1-5, every job the same."""
    rng = np.random.default_rng(seed)
    pairs = rng.choice(n_users * n_items, nnz, replace=False)
    interactions = Interactions(
        (pairs // n_items).astype(np.int32),
        (pairs % n_items).astype(np.int32),
        rng.integers(1, 6, nnz).astype(np.float32),
        EntityIdIndex([f"u{n}" for n in range(n_users)]),
        EntityIdIndex([f"i{n}" for n in range(n_items)]))

    class SeededSource(DataSource):
        def __init__(self, params=None):
            self.params = params

        def read_training(self, ctx):
            return interactions

    return Engine(SeededSource, IdentityPreparator, {"als": ALSAlgorithm},
                  FirstServing)


def tiny_params(rank: int = 8, sweeps: int = 3, **algorithm) -> EngineParams:
    return EngineParams(
        datasource=("", None),
        algorithms=[("als", dict(
            rank=rank, num_iterations=sweeps, implicit_prefs=True,
            alpha=10.0, lambda_=0.05, **algorithm))])
