"""Crash-safe training lifecycle (workflow/lifecycle.py, utils/durable.py):
durable model persistence, preemption-aware supervision, heartbeats +
zombie sweep, deterministic chaos kills, and exact resume — the training-
path counterpart of tests/test_resilience.py's serving-path guarantees."""

import json
import os
import signal
import subprocess
import sys
import time
from datetime import timedelta

import jax
import numpy as np
import pytest

from pio_tpu.controller.engine import EngineParams
from pio_tpu.data import DataMap, Event
from pio_tpu.data.dao import App, EngineInstance, Model
from pio_tpu.data.storage import Storage
from pio_tpu.models.twotower import (
    TwoTowerDataSourceParams,
    TwoTowerEngine,
    TwoTowerParams,
)
from pio_tpu.resilience import chaos
from pio_tpu.utils.durable import (
    ModelIntegrityError,
    crc32c,
    durable_read,
    durable_write,
    frame,
    unframe,
)
from pio_tpu.utils.time import utcnow
from pio_tpu.workflow.context import create_workflow_context
from pio_tpu.workflow.lifecycle import (
    EXIT_PREEMPTED,
    PreemptionHandler,
    TrainingPreempted,
    TrainLifecycle,
    checkpoint_dir_for,
    find_resumable,
    stale_instances,
    sweep_zombies,
)
from pio_tpu.workflow.train import load_models, run_train


def _mem_storage():
    return Storage(env={
        "PIO_STORAGE_SOURCES_MEM_TYPE": "memory",
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "MEM",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "MEM",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "MEM",
    }, test=True)


def _seed_interactions(storage, app_name="ttapp"):
    apps = storage.get_metadata_apps()
    app_id = apps.insert(App(0, app_name))
    ev = storage.get_events()
    ev.init(app_id)
    rng = np.random.default_rng(7)
    t0 = utcnow()
    for k in range(300):
        ev.insert(
            Event(
                event="view",
                entity_type="user",
                entity_id=f"u{rng.integers(0, 24)}",
                target_entity_type="item",
                target_entity_id=f"i{rng.integers(0, 16)}",
                event_time=t0 + timedelta(seconds=k),
            ),
            app_id,
        )
    return app_id


def _tt_engine(steps=10, checkpoint_every=3):
    engine = TwoTowerEngine.apply()
    ep = EngineParams(
        datasource=("", TwoTowerDataSourceParams(app_name="ttapp")),
        algorithms=[("twotower", TwoTowerParams(
            embed_dim=8, hidden_dim=16, out_dim=8, steps=steps,
            batch_size=16, seed=3, checkpoint_every=checkpoint_every,
        ))],
    )
    return engine, ep


def _tt_run(storage, tmp_path, **kwargs):
    engine, ep = _tt_engine(**{
        k: kwargs.pop(k) for k in ("steps", "checkpoint_every")
        if k in kwargs
    })
    ctx = create_workflow_context(storage, use_mesh=False)
    return run_train(
        engine, ep, storage, engine_id="tt",
        engine_factory="pio_tpu.models.twotower.TwoTowerEngine",
        ctx=ctx, checkpoint_root=str(tmp_path / "ckpt"),
        heartbeat_every_steps=1, **kwargs,
    ), engine, ep, ctx


def _leaves(model):
    return [np.asarray(x) for x in jax.tree_util.tree_leaves(
        {"params": model.params,
         "item_embeddings": model.item_embeddings})]


# ---------------------------------------------------------------------------
# durable persistence primitives
# ---------------------------------------------------------------------------

def test_crc32c_known_vector():
    # the standard CRC32C check value (RFC 3720 appendix / every impl)
    assert crc32c(b"123456789") == 0xE3069283
    assert crc32c(b"") == 0


def test_frame_roundtrip_and_corruption():
    payload = os.urandom(4096)
    blob = frame(payload)
    assert unframe(blob) == payload
    # legacy (unframed) blobs pass through unverified
    assert unframe(b"not-a-frame") == b"not-a-frame"
    # truncation inside the payload
    with pytest.raises(ModelIntegrityError, match="truncated"):
        unframe(blob[:-10])
    # truncation inside the header
    with pytest.raises(ModelIntegrityError, match="truncated"):
        unframe(blob[:8])
    # single flipped payload bit
    bad = bytearray(blob)
    bad[-1] ^= 0x01
    with pytest.raises(ModelIntegrityError, match="crc32c"):
        unframe(bytes(bad))


def test_durable_write_atomic_and_clean(tmp_path):
    path = str(tmp_path / "pio_model_a.bin")
    durable_write(path, b"v1")
    assert durable_read(path) == b"v1"
    durable_write(path, b"v2" * 1000)
    assert durable_read(path) == b"v2" * 1000
    # no tmp litter left behind
    assert os.listdir(tmp_path) == ["pio_model_a.bin"]


def test_localfs_truncated_blob_raises_model_integrity_error(tmp_path):
    """Regression (the reference bug): a crash mid-write used to leave a
    truncated pio_model_*.bin that `get` happily returned and unpickling
    misparsed. Now the frame catches it and load_models raises a CLEAR
    ModelIntegrityError."""
    storage = Storage(env={
        "PIO_STORAGE_SOURCES_MEM_TYPE": "memory",
        "PIO_STORAGE_SOURCES_FS_TYPE": "localfs",
        "PIO_STORAGE_SOURCES_FS_PATH": str(tmp_path / "models"),
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "MEM",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "MEM",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "FS",
    }, test=True)
    _seed_interactions(storage)
    instance_id, engine, ep, ctx = _tt_run(storage, tmp_path, steps=4)
    # intact blob restores fine
    assert load_models(storage, engine, ep, instance_id, ctx=ctx)
    # simulate the torn write: truncate the blob file on disk
    [blob_file] = os.listdir(tmp_path / "models")
    p = tmp_path / "models" / blob_file
    data = p.read_bytes()
    p.write_bytes(data[:len(data) // 2])
    with pytest.raises(ModelIntegrityError, match="truncated"):
        load_models(storage, engine, ep, instance_id, ctx=ctx)


def test_any_backend_detects_bitrot_via_blob_frame():
    """The checksum rides inside the blob (models_to_bytes frame), so
    even backends with their own durability detect corruption."""
    storage = _mem_storage()
    _seed_interactions(storage)
    models = storage.get_model_data_models()
    from pio_tpu.workflow.checkpoint import models_from_bytes, models_to_bytes

    blob = models_to_bytes([{"w": np.ones(3, np.float32)}])
    corrupted = bytearray(blob)
    corrupted[-2] ^= 0xFF
    models.insert(Model("x", bytes(corrupted)))
    with pytest.raises(ModelIntegrityError, match="crc32c"):
        models_from_bytes(models.get("x").models)


# ---------------------------------------------------------------------------
# supervised run_train: checkpoints, heartbeats, terminal statuses
# ---------------------------------------------------------------------------

def test_run_train_wires_checkpoints_and_heartbeats(tmp_path):
    storage = _mem_storage()
    _seed_interactions(storage)
    instance_id, engine, ep, ctx = _tt_run(storage, tmp_path, steps=10)
    inst = storage.get_metadata_engine_instances().get(instance_id)
    assert inst.status == "COMPLETED"
    # the per-instance checkpoint dir exists and holds saved steps
    ckpt_dir = checkpoint_dir_for(instance_id, str(tmp_path / "ckpt"))
    assert inst.progress["checkpoint_dir"] == ckpt_dir
    assert os.path.isdir(ckpt_dir) and os.listdir(ckpt_dir)
    # terminal progress carries the final step + liveness fields
    assert inst.progress["step"] == 9
    assert inst.progress["total_steps"] == 10
    assert inst.progress["pid"] == os.getpid()
    assert "heartbeat" in inst.progress


def test_failed_status_update_does_not_mask_training_error(tmp_path):
    """Satellite regression: the original training exception used to be
    masked when the FAILED status write itself threw (store down) — now
    the training error propagates, chained to the bookkeeping failure."""
    storage = _mem_storage()
    _seed_interactions(storage)

    class _BoomEngine:
        def train(self, ctx, ep, stop_after_read=False,
                  stop_after_prepare=False):
            # the store "goes down" DURING training, so the TRAINING
            # transition succeeded but the FAILED transition cannot
            chaos.install(chaos.ChaosMonkey(
                [chaos.ChaosSpec(target="storage.MEM.update", error=1.0)]))
            raise ValueError("the real training bug")

    ctx = create_workflow_context(storage, use_mesh=False)
    try:
        with pytest.raises(ValueError, match="the real training bug") as ei:
            run_train(_BoomEngine(), EngineParams(), storage,
                      engine_id="boom", ctx=ctx,
                      checkpoint_root=str(tmp_path / "ckpt"))
    finally:
        chaos.uninstall()
    assert isinstance(ei.value.__cause__, chaos.ChaosError)


def test_preempted_trainer_saves_final_checkpoint(tmp_path):
    from pio_tpu.data.bimap import EntityIdIndex
    from pio_tpu.data.eventstore import Interactions
    from pio_tpu.models.twotower import train_two_tower
    from pio_tpu.workflow.orbax_ckpt import (
        StepCheckpointConfig, StepCheckpointer,
    )

    rng = np.random.default_rng(0)
    inter = Interactions(
        user_idx=rng.integers(0, 16, 128).astype(np.int32),
        item_idx=rng.integers(0, 12, 128).astype(np.int32),
        values=np.ones(128, np.float32),
        users=EntityIdIndex(f"u{i}" for i in range(16)),
        items=EntityIdIndex(f"i{i}" for i in range(12)),
    )
    storage = _mem_storage()
    instances = storage.get_metadata_engine_instances()
    iid = instances.insert(EngineInstance(
        id="", status="TRAINING", start_time=utcnow(), end_time=utcnow(),
        engine_id="tt", engine_version="1", engine_variant="default",
        engine_factory=""))
    handler = PreemptionHandler()
    handler.requested.set()  # the SIGTERM already arrived
    lc = TrainLifecycle(instances, instances.get(iid),
                        checkpoint_dir=str(tmp_path / "pc"),
                        preemption=handler)
    p = TwoTowerParams(embed_dim=8, hidden_dim=16, out_dim=8, steps=10,
                       batch_size=16)
    with StepCheckpointer(
            StepCheckpointConfig(str(tmp_path / "pc"), save_every=100)) as ck:
        with pytest.raises(TrainingPreempted):
            train_two_tower(inter, p, checkpoint=ck, lifecycle=lc)
        # honored at the FIRST span boundary, with the step checkpointed
        assert ck.latest_step() is not None
    assert lc.instance.progress["step"] == ck.latest_step()


def test_run_train_marks_preemption_interrupted(tmp_path):
    storage = _mem_storage()

    class _PreemptedEngine:
        def train(self, ctx, ep, stop_after_read=False,
                  stop_after_prepare=False):
            raise TrainingPreempted(7)

    ctx = create_workflow_context(storage, use_mesh=False)
    with pytest.raises(TrainingPreempted):
        run_train(_PreemptedEngine(), EngineParams(), storage,
                  engine_id="tt", ctx=ctx,
                  checkpoint_root=str(tmp_path / "ckpt"))
    [inst] = storage.get_metadata_engine_instances().get_all()
    assert inst.status == "INTERRUPTED"
    assert inst.progress["preempted_at_step"] == 7
    assert inst.progress["resumable"] is True


# ---------------------------------------------------------------------------
# zombie sweep
# ---------------------------------------------------------------------------

def _instance(status, start_time, progress=None, engine_id="tt"):
    return EngineInstance(
        id="", status=status, start_time=start_time, end_time=start_time,
        engine_id=engine_id, engine_version="1", engine_variant="default",
        engine_factory="", progress=progress or {})


def test_zombie_sweep_marks_stale_inflight_failed():
    storage = _mem_storage()
    instances = storage.get_metadata_engine_instances()
    now = utcnow()
    dead = instances.insert(_instance("INIT", now - timedelta(hours=1)))
    live = instances.insert(_instance(
        "TRAINING", now - timedelta(hours=1),
        progress={"heartbeat": now.isoformat(), "step": 40}))
    done = instances.insert(_instance("COMPLETED", now - timedelta(hours=1)))
    # read-only detection first
    assert [i.id for i in stale_instances(storage)] == [dead]
    swept = sweep_zombies(storage)
    assert [i.id for i in swept] == [dead]
    assert instances.get(dead).status == "FAILED"
    assert instances.get(dead).progress["zombie"] is True
    # a live heartbeat and terminal statuses are untouched
    assert instances.get(live).status == "TRAINING"
    assert instances.get(done).status == "COMPLETED"


def test_run_train_startup_sweep(tmp_path):
    storage = _mem_storage()
    _seed_interactions(storage)
    instances = storage.get_metadata_engine_instances()
    zombie = instances.insert(_instance(
        "TRAINING", utcnow() - timedelta(hours=2)))
    _tt_run(storage, tmp_path, steps=4)
    assert instances.get(zombie).status == "FAILED"


def test_doctor_sweeps_zombies(cli, memory_storage):
    instances = memory_storage.get_metadata_engine_instances()
    zombie = instances.insert(_instance("INIT", utcnow() - timedelta(hours=1)))
    # report-only by default (downed surfaces are fine for this check)
    rc, out = cli("doctor", "--timeout", "0.2", "--json")
    report = json.loads(out.out)
    assert [z["id"] for z in report["zombies"]] == [zombie]
    assert report["zombies"][0]["action"] == "stale"
    assert instances.get(zombie).status == "INIT"
    rc, out = cli("doctor", "--timeout", "0.2", "--json", "--sweep-zombies")
    report = json.loads(out.out)
    assert report["zombies"][0]["action"] == "swept"
    assert instances.get(zombie).status == "FAILED"


# ---------------------------------------------------------------------------
# chaos kills + exact resume
# ---------------------------------------------------------------------------

def test_chaos_watches():
    assert not chaos.watches("train.step")
    with chaos.inject("train.step.6", error=1.0):
        assert chaos.watches("train.step")       # spec under the family
        assert chaos.watches("train.step.6")
        assert not chaos.watches("train.persist")
    with chaos.inject("train", error=0.0):
        assert chaos.watches("train.step")       # spec above the family


def test_kill_at_step_then_resume_bit_identical(tmp_path):
    """Satellite: chaos-kill a two-tower run at an arbitrary step, resume
    it, and the final model is BIT-identical to an uninterrupted run —
    the (seed, step)-keyed batch stream promise, now tested."""
    # ground truth: uninterrupted run. The benign train.step spec forces
    # the same per-step span programs the killed/resumed runs compile.
    storage_a = _mem_storage()
    _seed_interactions(storage_a)
    with chaos.inject("train.step", error=0.0):
        gt_id, engine, ep, ctx_a = _tt_run(storage_a, tmp_path / "a",
                                           steps=10)
    [gt_model] = load_models(storage_a, engine, ep, gt_id, ctx=ctx_a)

    # run 2: killed hard at step 6 (checkpoints at 0 and 3)
    storage_b = _mem_storage()
    _seed_interactions(storage_b)
    with pytest.raises(chaos.ChaosError):
        with chaos.inject("train.step.6", error=1.0):
            _tt_run(storage_b, tmp_path / "b", steps=10)
    [inst] = storage_b.get_metadata_engine_instances().get_all()
    assert inst.status == "FAILED"
    assert os.listdir(checkpoint_dir_for(inst.id, str(tmp_path / "b/ckpt")))

    # resume from the last checkpoint and finish
    with chaos.inject("train.step", error=0.0):
        resumed_id, engine_b, ep_b, ctx_b = _tt_run(
            storage_b, tmp_path / "b", steps=10,
            resume_instance_id=inst.id)
    assert resumed_id == inst.id
    final = storage_b.get_metadata_engine_instances().get(inst.id)
    assert final.status == "COMPLETED"
    assert "resumed_at" in final.progress
    [resumed_model] = load_models(storage_b, engine_b, ep_b, resumed_id,
                                  ctx=ctx_b)
    for a, b in zip(_leaves(gt_model), _leaves(resumed_model)):
        np.testing.assert_array_equal(a, b)


def test_auto_resume_picks_latest_resumable(tmp_path):
    storage = _mem_storage()
    _seed_interactions(storage)
    with pytest.raises(chaos.ChaosError):
        with chaos.inject("train.step.4", error=1.0):
            _tt_run(storage, tmp_path, steps=10)
    [failed] = storage.get_metadata_engine_instances().get_all()
    found = find_resumable(
        storage.get_metadata_engine_instances(), "tt", "1", "default",
        str(tmp_path / "ckpt"))
    assert found is not None and found.id == failed.id
    resumed_id, *_ = _tt_run(storage, tmp_path, steps=10, auto_resume=True)
    assert resumed_id == failed.id
    assert storage.get_metadata_engine_instances().get(
        failed.id).status == "COMPLETED"


def test_persist_fault_fails_then_resumes(tmp_path):
    """Storage fault during the FINAL model write: the run lands FAILED
    (never COMPLETED-without-a-blob) and resumes cheaply from its last
    checkpoint."""
    storage = _mem_storage()
    _seed_interactions(storage)
    with pytest.raises(chaos.ChaosError):
        with chaos.inject("train.persist", error=1.0):
            _tt_run(storage, tmp_path, steps=10)
    [inst] = storage.get_metadata_engine_instances().get_all()
    assert inst.status == "FAILED"
    assert storage.get_model_data_models().get(inst.id) is None
    iid, engine, ep, ctx = _tt_run(storage, tmp_path, steps=10,
                                   resume_instance_id=inst.id)
    assert storage.get_metadata_engine_instances().get(iid).status \
        == "COMPLETED"
    assert load_models(storage, engine, ep, iid, ctx=ctx)


def test_checkpoint_write_fault_surfaces(tmp_path):
    storage = _mem_storage()
    _seed_interactions(storage)
    with pytest.raises(chaos.ChaosError):
        with chaos.inject("train.checkpoint", error=1.0):
            _tt_run(storage, tmp_path, steps=10)
    [inst] = storage.get_metadata_engine_instances().get_all()
    assert inst.status == "FAILED"


def test_resume_validates_instance(tmp_path):
    storage = _mem_storage()
    _seed_interactions(storage)
    with pytest.raises(ValueError, match="not found"):
        _tt_run(storage, tmp_path, steps=4, resume_instance_id="ghost")
    done_id, *_ = _tt_run(storage, tmp_path, steps=4)
    with pytest.raises(ValueError, match="COMPLETED"):
        _tt_run(storage, tmp_path, steps=4, resume_instance_id=done_id)
    # resuming another ENGINE's instance would cross-wire model blobs
    other = storage.get_metadata_engine_instances().insert(
        _instance("FAILED", utcnow(), engine_id="other-engine"))
    with pytest.raises(ValueError, match="belongs to engine"):
        _tt_run(storage, tmp_path, steps=4, resume_instance_id=other)


def test_liveness_beat_keeps_heartbeat_fresh_between_spans():
    """Regression: step heartbeats only fire at span boundaries, which on
    big models can be further apart than the zombie-stale threshold —
    the background liveness thread must keep the stamp fresh on its
    own."""
    storage = _mem_storage()
    instances = storage.get_metadata_engine_instances()
    iid = instances.insert(_instance("TRAINING", utcnow()))
    lc = TrainLifecycle(instances, instances.get(iid),
                        liveness_interval_s=0.05)
    lc.start()
    try:
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            if "heartbeat" in (instances.get(iid).progress or {}):
                break
            time.sleep(0.02)
    finally:
        lc.stop()
    assert "heartbeat" in instances.get(iid).progress


def test_resume_uses_recorded_checkpoint_dir(tmp_path):
    """Regression: resume must read the directory the original run
    RECORDED, not recompute it from the current --checkpoint-root — a
    different root would silently restart from step 0."""
    storage = _mem_storage()
    _seed_interactions(storage)
    with pytest.raises(chaos.ChaosError):
        with chaos.inject("train.step.6", error=1.0):
            _tt_run(storage, tmp_path / "a", steps=10)
    [failed] = storage.get_metadata_engine_instances().get_all()
    recorded = checkpoint_dir_for(failed.id, str(tmp_path / "a" / "ckpt"))
    assert failed.progress["checkpoint_dir"] == recorded
    # resume under a DIFFERENT root: the recorded dir must win
    _tt_run(storage, tmp_path / "b", steps=10,
            resume_instance_id=failed.id)
    final = storage.get_metadata_engine_instances().get(failed.id)
    assert final.status == "COMPLETED"
    assert final.progress["checkpoint_dir"] == recorded
    wrong = checkpoint_dir_for(failed.id, str(tmp_path / "b" / "ckpt"))
    assert not os.path.isdir(wrong)


@pytest.mark.parametrize("piece", [1 << 30, 1 << 20, 7])
@pytest.mark.parametrize("payload", [b"", b"x", bytes(range(256)) * 9000])
def test_stream_frame_is_frame(payload, piece):
    """A payload handed over in pieces, the header filled in behind them,
    gives the bytes `frame` gives: whole, in pieces of 1 MiB, and in
    pieces of 7 bytes that no boundary of anything falls on."""
    import io

    from pio_tpu.utils.durable import HEADER_BYTES, stream_frame

    def produce(sink):
        for at in range(0, len(payload), piece):
            sink.write(payload[at:at + piece])

    buf = io.BytesIO(b"before")
    buf.seek(0, io.SEEK_END)
    sink = stream_frame(buf, produce)
    blob = buf.getvalue()[len(b"before"):]
    assert blob == frame(payload) and unframe(blob) == payload
    assert sink.length == len(payload)
    assert buf.tell() == len(b"before") + HEADER_BYTES + len(payload)


def test_durable_write_no_double_frame(tmp_path):
    """An already content-framed payload (models_to_bytes output) is
    written verbatim — no second checksum pass — and round-trips
    byte-for-byte; truncation is still caught."""
    payload = frame(b"pickled-model-bytes")
    path = str(tmp_path / "pio_model_f.bin")
    durable_write(path, payload)
    with open(path, "rb") as f:
        assert f.read() == payload  # written as-is, single frame
    assert durable_read(path) == payload
    with open(path, "wb") as f:  # pio: lint-ok[durable-write] test
        # fixture simulating the torn write itself
        f.write(payload[:-4])
    with pytest.raises(ModelIntegrityError):
        durable_read(path)


def test_heartbeat_not_starved_by_checkpoint_cadence():
    """Regression: throttling by `step % N` starved the store of beats
    whenever the checkpoint cadence was not a multiple of N (trainers
    only call at checkpoint-aligned span boundaries) — a healthy run
    then read as a zombie and got swept mid-flight."""
    storage = _mem_storage()
    instances = storage.get_metadata_engine_instances()
    iid = instances.insert(_instance("TRAINING", utcnow()))
    lc = TrainLifecycle(instances, instances.get(iid),
                        heartbeat_every_steps=10,
                        heartbeat_min_interval_s=0.0)
    assert lc.heartbeat(128, 512)       # 128 % 10 != 0: must still write
    assert not lc.heartbeat(129, 512)   # only 1 step since the last beat
    assert lc.heartbeat(256, 512)
    assert instances.get(iid).progress["step"] == 256


# ---------------------------------------------------------------------------
# serve falls back past a corrupt blob
# ---------------------------------------------------------------------------

def test_serve_falls_back_to_previous_completed_on_corrupt_blob(tmp_path):
    from pio_tpu.workflow.serve import QueryServer, ServingConfig

    storage = _mem_storage()
    _seed_interactions(storage)
    older_id, engine, ep, ctx = _tt_run(storage, tmp_path, steps=4)
    time.sleep(0.01)  # distinct start_time ordering
    newer_id, *_ = _tt_run(storage, tmp_path, steps=4)
    # corrupt the NEWER instance's blob in place
    models = storage.get_model_data_models()
    blob = bytearray(models.get(newer_id).models)
    blob[-3] ^= 0xFF
    models.insert(Model(newer_id, bytes(blob)))
    qs = QueryServer(
        engine, ep, storage,
        ServingConfig(engine_id="tt", engine_version="1",
                      engine_variant="default"),
        ctx=ctx,
    )
    try:
        assert qs.instance.id == older_id  # degraded, not dead
        assert qs.query({"user": "u1", "num": 3}) is not None
    finally:
        qs.close()
    # an EXPLICIT instance id does not fall back
    with pytest.raises(ModelIntegrityError):
        QueryServer(
            engine, ep, storage,
            ServingConfig(engine_id="tt", engine_version="1",
                          engine_variant="default"),
            ctx=ctx, instance_id=newer_id,
        )


# ---------------------------------------------------------------------------
# end-to-end SIGTERM preemption through the real CLI (the CI
# train-preemption job's scenario)
# ---------------------------------------------------------------------------

def _sqlite_env(tmp_path):
    return {
        "PIO_STORAGE_SOURCES_SQL_TYPE": "sqlite",
        "PIO_STORAGE_SOURCES_SQL_PATH": str(tmp_path / "pio.db"),
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "SQL",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "SQL",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "SQL",
    }


@pytest.mark.slow
def test_sigterm_preemption_resume_end_to_end(tmp_path):
    """kill -TERM during step-train -> exit 75, instance INTERRUPTED,
    checkpoint on disk -> `pio train --resume` -> COMPLETED, final model
    bit-identical to an uninterrupted run."""
    engine_dir = tmp_path / "engine"
    engine_dir.mkdir()
    (engine_dir / "engine.json").write_text(json.dumps({
        "id": "ttpreempt",
        "engineFactory": "pio_tpu.models.twotower.TwoTowerEngine",
        "datasource": {"params": {"app_name": "ttapp"}},
        "algorithms": [{"name": "twotower", "params": {
            "embed_dim": 8, "hidden_dim": 16, "out_dim": 8,
            "steps": 200, "batch_size": 16, "seed": 5,
            "checkpoint_every": 10,
        }}],
    }))
    storage = Storage(env=_sqlite_env(tmp_path))
    _seed_interactions(storage)
    storage.close()

    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    base_env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        PIO_TPU_PLATFORM="cpu",
        PIO_TPU_CKPT_ROOT=str(tmp_path / "ckpt"),
        PYTHONPATH=os.pathsep.join(
            p for p in (repo_root, os.environ.get("PYTHONPATH")) if p),
        **_sqlite_env(tmp_path),
    )
    argv = [sys.executable, "-m", "pio_tpu.tools.cli", "train",
            "--engine-dir", str(engine_dir), "--no-mesh"]

    # run 1: ~40ms/step chaos stall paces the run so the SIGTERM lands
    # mid-flight deterministically enough (and forces per-step spans)
    proc = subprocess.Popen(
        argv,
        env=dict(base_env, PIO_TPU_CHAOS="train.step:slow=1,slow_s=0.04"),
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        cwd=str(tmp_path),
    )
    # wait for training to prove progress (heartbeat in the instance row)
    storage = Storage(env=_sqlite_env(tmp_path))
    instances = storage.get_metadata_engine_instances()
    deadline = time.monotonic() + 120
    inst = None
    while time.monotonic() < deadline:
        rows = instances.get_all()
        inst = rows[0] if rows else None
        if inst is not None and (inst.progress or {}).get("step", 0) >= 20:
            break
        if proc.poll() is not None:
            break
        time.sleep(0.2)
    assert proc.poll() is None, (
        f"train exited early: {proc.communicate()[0]}")
    proc.send_signal(signal.SIGTERM)
    out, _ = proc.communicate(timeout=120)
    assert proc.returncode == EXIT_PREEMPTED, out

    inst = instances.get_all()[0]
    assert inst.status == "INTERRUPTED", out
    ckpt_dir = checkpoint_dir_for(inst.id, str(tmp_path / "ckpt"))
    assert os.path.isdir(ckpt_dir) and os.listdir(ckpt_dir)

    # run 2: resume to completion (no stall; per-step spans kept so the
    # compiled programs match the ground truth's)
    r = subprocess.run(
        argv + ["--resume", inst.id],
        env=dict(base_env, PIO_TPU_CHAOS="train.step:slow=0"),
        capture_output=True, text=True, cwd=str(tmp_path), timeout=300,
    )
    assert r.returncode == 0, r.stdout + r.stderr
    inst = instances.get(inst.id)
    assert inst.status == "COMPLETED"

    # ground truth: a fresh uninterrupted run in the same store
    r = subprocess.run(
        argv,
        env=dict(base_env, PIO_TPU_CHAOS="train.step:slow=0"),
        capture_output=True, text=True, cwd=str(tmp_path), timeout=300,
    )
    assert r.returncode == 0, r.stdout + r.stderr
    gt = next(i for i in instances.get_all() if i.id != inst.id
              and i.status == "COMPLETED")
    models = storage.get_model_data_models()
    from pio_tpu.workflow.checkpoint import models_from_bytes

    [resumed] = models_from_bytes(models.get(inst.id).models)
    [fresh] = models_from_bytes(models.get(gt.id).models)
    for a, b in zip(_leaves(resumed), _leaves(fresh)):
        np.testing.assert_array_equal(a, b)
    storage.close()
