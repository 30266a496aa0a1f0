"""The engine the train cells run: the program's recommendation engine,
with a DataSource that hands `run_train` seeded interactions.

`source: "interactions"` in a traffic file selects SeededSource: the
COO arrays and id indexes are made once in set-up, and every job's
`read_training` returns them, as a DataSource over a columnar store
would. `source: "events"` takes the program's own event-reading
DataSource instead (benchmark/drivers/train_child.py).
"""

from __future__ import annotations

from pio_tpu.controller.base import DataSource, FirstServing, IdentityPreparator
from pio_tpu.controller.engine import Engine
from pio_tpu.models.recommendation import ALSAlgorithm


def seeded_engine(interactions) -> Engine:
    class SeededSource(DataSource):
        def __init__(self, params=None):
            self.params = params

        def read_training(self, ctx):
            return interactions

    return Engine(SeededSource, IdentityPreparator, {"als": ALSAlgorithm},
                  FirstServing)
