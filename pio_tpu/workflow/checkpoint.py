"""Model persistence.

The reference Kryo-serializes trained models into the MODELDATA repository
(CoreWorkflow.scala:73-78, storage/Models.scala:30-48), with the PAlgorithm
escape hatch of persisting Unit and retraining at deploy
(PAlgorithm.makePersistentModel, Engine.prepareDeploy:208-230). Here every
model — including device-resident pytrees — serializes for real: jax.Arrays
are pulled to host numpy inside the pytree and pickled; restore optionally
`device_put`s back onto a serving mesh. No retrain-on-deploy.

Orbax-style sharded step checkpoints for large multi-host models live beside
this (see pio_tpu/workflow/orbax_ckpt.py once models outgrow a blob).
"""

from __future__ import annotations

import io
import pickle
from typing import Any

import jax
import numpy as np

from pio_tpu.data.bimap import EntityIdIndex
from pio_tpu.utils import tracing
from pio_tpu.utils.durable import (
    HEADER_BYTES,
    ModelIntegrityError,
    frame_in_place,
    unframe,
)

__all__ = [
    "ModelIntegrityError", "host_copy", "models_from_bytes",
    "models_to_bytes",
]


def _to_host(x: Any) -> Any:
    if isinstance(x, jax.Array):
        return np.asarray(x)
    return x


def host_copy(model: Any) -> Any:
    """Pytree-map jax.Array leaves to numpy; non-pytree objects untouched."""
    return jax.tree_util.tree_map(_to_host, model)


def models_to_bytes(models: list[Any]) -> bytes:
    """Pickle + CRC32C-frame (utils/durable.py): the checksum rides
    INSIDE the blob, so every backend — file, SQL BLOB, wire — hands
    `models_from_bytes` enough to detect truncation and bit-rot, not
    just the localfs path with its own file-level durability."""
    with tracing.span("persist.d2h") as sp:
        on_host = [host_copy(m) for m in models]
        leaves = jax.tree_util.tree_leaves(on_host)
        sp["bytes"] = sum(
            x.nbytes for x in leaves if isinstance(x, np.ndarray))
    with tracing.span("persist.pickle") as sp:
        buf = io.BytesIO()
        buf.write(bytes(HEADER_BYTES))      # the frame's header goes here
        pickle.dump(on_host, buf, protocol=5)
        sp["bytes"] = buf.tell() - HEADER_BYTES
        sp["ids"] = sum(
            len(x) for m in on_host
            for x in getattr(m, "__dict__", {}).values()
            if isinstance(x, EntityIdIndex))
    with tracing.span("persist.frame") as sp:
        blob = frame_in_place(buf)
        sp["bytes"] = len(blob)
    return blob


def models_from_bytes(data: bytes) -> list[Any]:
    """Verify + unpickle. Raises ModelIntegrityError (NOT a pickle error
    deep in a partial stream) when a framed blob fails its checksum;
    legacy unframed blobs from pre-durability stores unpickle as before."""
    return pickle.loads(unframe(data, source="model blob"))
