"""The engine the train_sequence cells run: the program's sequence
engine, with a DataSource that hands `run_train` seeded histories (as
benchmark/engines.py hands the ALS cells their COO arrays).

A history is a user's most recent `history_events` events and the next
one, the target: what `build_sequences` keeps of a longer history, with
no padding. Item ids are Zipf over the items of the vocabulary slice
(id 0 is PAD and is never drawn), a pure function of the seed.
"""

from __future__ import annotations

import numpy as np

from pio_tpu.controller.base import DataSource, FirstServing, IdentityPreparator
from pio_tpu.controller.engine import Engine
from pio_tpu.data.bimap import EntityIdIndex
from pio_tpu.models.sequence import SequenceAlgorithm, SequenceData

# what of a configuration file is not the block specification
_NOT_MODEL = ("name", "source", "source_states", "engine", "deployment",
              "published", "precision", "assumed", "reduced", "check")


def block_spec_of(config: dict) -> dict:
    return {k: v for k, v in config.items() if k not in _NOT_MODEL}


def make_histories(n: int, length: int, n_items: int, exponent: float,
                   seed: int, stream: int = 0) -> np.ndarray:
    """(n, length) int32 item ids in [1, n_items], item r drawn with
    probability proportional to r ** -exponent."""
    p = np.arange(1, n_items + 1, dtype=np.float64) ** -exponent
    rng = np.random.default_rng([seed, 0x5E9, stream])
    return (rng.choice(n_items, size=(n, length), p=p / p.sum())
            + 1).astype(np.int32)


def seeded_engine(seqs: np.ndarray, n_items: int) -> Engine:
    data = SequenceData(
        seqs, EntityIdIndex([f"u{n}" for n in range(len(seqs))]),
        EntityIdIndex([f"i{n}" for n in range(1, n_items + 1)]))

    class SeededHistories(DataSource):
        def __init__(self, params=None):
            self.params = params

        def read_training(self, ctx):
            return data

    return Engine(SeededHistories, IdentityPreparator,
                  {"sasrec": SequenceAlgorithm}, FirstServing)


def algorithm_params(config: dict, traffic: dict, seed: int) -> dict:
    return {"max_len": traffic["history_events"] + 1,
            "batch_size": traffic["batch_histories"],
            "steps": traffic["steps"],
            "learning_rate": traffic["learning_rate"],
            "seed": seed, "block_spec": block_spec_of(config)}
