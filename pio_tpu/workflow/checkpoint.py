"""Model persistence.

The reference Kryo-serializes trained models into the MODELDATA repository
(CoreWorkflow.scala:73-78, storage/Models.scala:30-48), with the PAlgorithm
escape hatch of persisting Unit and retraining at deploy
(PAlgorithm.makePersistentModel, Engine.prepareDeploy:208-230). Here every
model — including device-resident pytrees — serializes for real: jax.Arrays
are pulled to host numpy inside the pytree and pickled; restore optionally
`device_put`s back onto a serving mesh. No retrain-on-deploy.

A job's models go to their store in one pass (`models_on_host` ->
`HostModels`): a store that keeps a model as a file (sqlite at 1 MiB or
more, localfs) has them write themselves there, each array's bytes from
where they lie through the CRC into the file; a store that keeps a row or
sends a message takes `bytes(...)` of the same writer.

Orbax-style sharded step checkpoints for large multi-host models live beside
this (see pio_tpu/workflow/orbax_ckpt.py once models outgrow a blob).
"""

from __future__ import annotations

import io
import pickle
import time
from typing import Any

import jax
import numpy as np

from pio_tpu.data.bimap import EntityIdIndex
from pio_tpu.utils import tracing
from pio_tpu.utils.durable import (
    HEADER_BYTES,
    ModelIntegrityError,
    stream_frame,
    unframe,
)

__all__ = [
    "HostModels", "ModelIntegrityError", "host_copy", "models_from_bytes",
    "models_on_host", "models_to_bytes",
]


def _to_host(x: Any) -> Any:
    if isinstance(x, jax.Array):
        return np.asarray(x)
    return x


def host_copy(model: Any) -> Any:
    """Pytree-map jax.Array leaves to numpy; non-pytree objects untouched."""
    return jax.tree_util.tree_map(_to_host, model)


class HostModels:
    """A job's models on the host, not yet serialized: what a `Model`
    holds (`data/dao.py`) from `persist.d2h` to the store.

    `write_framed(f)` is the one writer: the CRC32C frame of
    utils/durable.py around a protocol-5 pickle, which hands every
    array's memory to the sink as it lies. The checksum rides INSIDE the
    blob, so every backend (file, SQL BLOB, wire) hands
    `models_from_bytes` enough to detect truncation and bit-rot. Into a
    store's file it is the only pass over the model; `bytes(...)` is the
    same writer into memory, for a store that keeps a row or sends a
    message. `min_bytes` is the arrays' own bytes, which no frame of
    these models is shorter than: a store chooses by it before a byte
    is written. `framed_bytes` is the frame's length once written."""

    def __init__(self, on_host: list[Any], min_bytes: int):
        self.on_host = on_host
        self.min_bytes = min_bytes
        self.framed_bytes: int | None = None

    def write_framed(self, f) -> int:
        """The frame into the binary file `f`; -> its length. The spans
        `persist.pickle` (the pickler's own seconds) and `persist.frame`
        (the checksum's) are the sink's sums, laid end to end from where
        the dump began; the seconds `f.write` took stay with the span
        open around this call (`models.file`, where `f` is a file)."""
        ids = sum(len(x) for m in self.on_host
                  for x in getattr(m, "__dict__", {}).values()
                  if isinstance(x, EntityIdIndex))
        t0 = time.monotonic()
        sink = stream_frame(
            f, lambda sink: pickle.dump(self.on_host, sink, protocol=5))
        pickle_s = max(
            time.monotonic() - t0 - sink.crc_s - sink.write_s, 0.0)
        tracing.emit("persist.pickle", pickle_s, t0,
                     bytes=sink.length, ids=ids)
        self.framed_bytes = HEADER_BYTES + sink.length
        tracing.emit("persist.frame", sink.crc_s, t0 + pickle_s,
                     bytes=self.framed_bytes)
        return self.framed_bytes

    def __bytes__(self) -> bytes:
        buf = io.BytesIO()
        self.write_framed(buf)
        return buf.getvalue()


def models_on_host(models: list[Any]) -> HostModels:
    """Device to host, once (`persist.d2h`): every jax.Array leaf as the
    numpy array the pickler will hand on."""
    with tracing.span("persist.d2h") as sp:
        on_host = [host_copy(m) for m in models]
        arrays = {id(x): x.nbytes
                  for x in jax.tree_util.tree_leaves(on_host)
                  if isinstance(x, np.ndarray) and x.dtype != object}
        sp["bytes"] = sum(arrays.values())
    return HostModels(on_host, sp["bytes"])


def models_to_bytes(models: list[Any]) -> bytes:
    """The framed blob in memory: `HostModels`' writer into a buffer."""
    return bytes(models_on_host(models))


def models_from_bytes(data: bytes) -> list[Any]:
    """Verify + unpickle. Raises ModelIntegrityError (NOT a pickle error
    deep in a partial stream) when a framed blob fails its checksum;
    legacy unframed blobs from pre-durability stores unpickle as before."""
    return pickle.loads(unframe(data, source="model blob"))
