"""Train workflow — read -> prepare -> train -> persist -> record.

Mirrors reference CoreWorkflow.runTrain (core/.../workflow/CoreWorkflow.scala:42-98)
and CreateWorkflow's EngineInstance bookkeeping (CreateWorkflow.scala:133-273):
 * EngineInstance inserted with status INIT, updated COMPLETED/FAILED;
 * models serialized into the MODELDATA repository keyed by instance id;
 * deploy later picks getLatestCompleted — never a half-trained run.

Beyond the reference, the run is *supervised* (workflow/lifecycle.py):

 * every run gets a per-instance step-checkpoint directory (keyed by
   EngineInstance.id) that the iterative trainers save into, so a killed
   run loses at most `checkpoint_every` steps;
 * SIGTERM/SIGINT request a final checkpoint at the next step boundary —
   the instance lands INTERRUPTED (resumable), not half-dead INIT;
 * heartbeats keep the instance's `progress` field fresh; stale
   INIT/TRAINING zombies from kill -9'd runs are swept to FAILED at the
   next train startup (and by `pio doctor --sweep-zombies`) so deploy's
   get_latest_completed contract is never starved silently;
 * `resume_instance_id` / `auto_resume` re-enter a resumable instance:
   the (seed, step)-keyed batch streams make the resumed run reproduce
   the uninterrupted one exactly;
 * multi-host: only process 0 writes metadata/models; all hosts barrier
   on checkpoint saves and on the final persist.
"""

from __future__ import annotations

import json
import logging
import os
import time
import traceback
from contextlib import nullcontext
from dataclasses import replace
from typing import Any

from pio_tpu.controller.base import TrainingInterruption
from pio_tpu.controller.engine import Engine, EngineParams
from pio_tpu.data.dao import EngineInstance, Model
from pio_tpu.data.storage import Storage
from pio_tpu.obs import make_recorder
from pio_tpu.resilience import chaos
from pio_tpu.utils.tracing import Tracer
from pio_tpu.utils.time import format_time, utcnow
from pio_tpu.workflow.checkpoint import models_from_bytes, models_on_host
from pio_tpu.workflow.context import WorkflowContext, create_workflow_context
from pio_tpu.workflow.lifecycle import (
    RESUMABLE_STATUSES,
    PreemptionHandler,
    TrainingPreempted,
    TrainLifecycle,
    checkpoint_dir_for,
    find_resumable,
    sweep_zombies,
)

log = logging.getLogger("pio_tpu.workflow")


def _resolve_instance(
    instances,
    primary: bool,
    resume_instance_id: str | None,
    auto_resume: bool,
    engine_id: str,
    engine_version: str,
    engine_variant: str,
    engine_factory: str,
    batch: str,
    engine_params: EngineParams,
    checkpoint_root: str | None,
) -> EngineInstance:
    """Resume an existing resumable instance, or insert a fresh one."""
    now = utcnow()
    if resume_instance_id:
        instance = instances.get(resume_instance_id)
        if instance is None:
            raise ValueError(
                f"cannot resume: engine instance {resume_instance_id} "
                "not found"
            )
        if instance.status not in RESUMABLE_STATUSES:
            raise ValueError(
                f"cannot resume instance {resume_instance_id}: status is "
                f"{instance.status} (resumable: "
                f"{', '.join(RESUMABLE_STATUSES)})"
            )
        got = (instance.engine_id, instance.engine_version,
               instance.engine_variant)
        want = (engine_id, engine_version, engine_variant)
        if got != want:
            # resuming under the wrong engine would persist engine B's
            # model blob against engine A's instance — and deploy's
            # get_latest_completed would then serve it
            raise ValueError(
                f"cannot resume instance {resume_instance_id}: it belongs "
                f"to engine {got}, not {want} (wrong --engine-dir?)"
            )
        return instance
    if auto_resume:
        instance = find_resumable(
            instances, engine_id, engine_version, engine_variant,
            checkpoint_root,
        )
        if instance is not None:
            log.info("auto-resume: picking up instance %s (%s, last step "
                     "%s)", instance.id, instance.status,
                     instance.progress.get("step"))
            return instance
        log.info("auto-resume: no resumable instance with checkpoints "
                 "found; starting fresh")
    # multi-host: every process must agree on the instance id, and only
    # process 0 may insert — an explicit PIO_TPU_RUN_ID provides both
    run_id = os.environ.get("PIO_TPU_RUN_ID", "")
    fresh = EngineInstance(
        id=run_id,
        status="INIT",
        start_time=now,
        end_time=now,
        engine_id=engine_id,
        engine_version=engine_version,
        engine_variant=engine_variant,
        engine_factory=engine_factory,
        batch=batch,
        datasource_params=f"{engine_params.datasource}",
        preparator_params=f"{engine_params.preparator}",
        algorithms_params=f"{engine_params.algorithms}",
        serving_params=f"{engine_params.serving}",
    )
    if not primary:
        if not run_id:
            raise ValueError(
                "multi-host training needs PIO_TPU_RUN_ID set (identically "
                "on every host) so non-primary processes know the "
                "engine-instance id without writing metadata"
            )
        return fresh
    instance_id = instances.insert(fresh)
    return instances.get(instance_id)


def process_age_s() -> float | None:
    """Seconds since the operating system started this process, or None
    where it does not say (`/proc/self/stat` holds the start in clock
    ticks since boot: Linux)."""
    try:
        with open("/proc/self/stat", "rb") as f:
            # the fields after the command, which may itself hold spaces
            fields = f.read().rsplit(b")", 1)[1].split()
        started = int(fields[19]) / os.sysconf("SC_CLK_TCK")
        return time.clock_gettime(time.CLOCK_BOOTTIME) - started
    except (OSError, ValueError, IndexError, AttributeError):
        return None


def _seconds(tracer: Tracer, *spans: str) -> float:
    """What this job's spans of those names took, together (each ran
    once in it: a tracer serves one `run_train`)."""
    return sum(tracer.histogram(name).last for name in spans)


def run_train(
    engine: Engine,
    engine_params: EngineParams,
    storage: Storage,
    engine_id: str,
    engine_version: str = "1",
    engine_variant: str = "default",
    engine_factory: str = "",
    batch: str = "",
    ctx: WorkflowContext | None = None,
    stop_after_read: bool = False,
    stop_after_prepare: bool = False,
    resume_instance_id: str | None = None,
    auto_resume: bool = False,
    checkpoint_root: str | None = None,
    supervise: bool = True,
    heartbeat_every_steps: int = 10,
    sweep_stale_s: float | None = None,
    use_mesh: bool = True,
) -> str:
    """Returns the EngineInstance id (status COMPLETED on success).

    With ``supervise`` (the default) the run gets the full lifecycle:
    per-instance checkpoint dir, SIGTERM/SIGINT preemption handling
    (raises TrainingPreempted; instance INTERRUPTED), heartbeats, and a
    startup zombie sweep. ``resume_instance_id`` re-enters a resumable
    (INTERRUPTED/FAILED) instance; ``auto_resume`` picks the most recent
    one with checkpoints on disk. Without a ``ctx`` the run makes its
    own (over every device, or with ``use_mesh=False`` none) inside the
    `train.devices` span: a fresh process reaches the chip there.

    The run is one `train` trace on a recorder of its own (surface
    `train`; docs/observability.md "Training"): every stage below is a
    span in it, emitted where the work happens, and the finished tree
    is logged as one `train spans:` record.
    """
    from pio_tpu.utils.compilecache import CompileMeter

    tracer = Tracer(recorder=make_recorder("train"), device=True)
    trace_id = None
    try:
        # the job's one set of jax listeners, from the root's start: what
        # it hears inside the trace it opens as `compile.*` spans
        with tracer.trace("train", engine=engine_id) as (trace_id, root), \
                CompileMeter() as compile_meter:
            age = process_age_s()
            if age is not None:
                root["process_age_s"] = round(age, 3)
            return _run_train(
                tracer, root, compile_meter, engine, engine_params,
                storage, engine_id, engine_version, engine_variant,
                engine_factory, batch, ctx, use_mesh, stop_after_read,
                stop_after_prepare, resume_instance_id, auto_resume,
                checkpoint_root, supervise, heartbeat_every_steps,
                sweep_stale_s)
    finally:
        if trace_id is not None:
            log.info("train spans: %s", json.dumps(
                tracer.recorder.span_rows(trace_id),
                separators=(",", ":")))


def _run_train(
    tracer: Tracer, root: dict, compile_meter, engine, engine_params,
    storage, engine_id, engine_version, engine_variant, engine_factory,
    batch, ctx, use_mesh, stop_after_read, stop_after_prepare,
    resume_instance_id, auto_resume, checkpoint_root, supervise,
    heartbeat_every_steps, sweep_stale_s,
) -> str:
    # persistent XLA compile cache BEFORE any engine compile: the second
    # consecutive train of the same engine deserializes its executables
    # instead of re-running XLA (utils/compilecache.py; PIO_TPU_COMPILE_
    # CACHE=off disables)
    from pio_tpu.parallel.mesh import describe_device_memory, describe_devices
    from pio_tpu.utils.compilecache import enable_compile_cache

    with tracer.span("train.setup"):
        enable_compile_cache()
        # the first thing that asks jax for its devices: in a fresh
        # process, the seconds it takes to reach the chip
        with tracer.span("train.devices"):
            ctx = ctx or create_workflow_context(storage, use_mesh=use_mesh)
            log.info("train devices: %s", describe_devices())
        instances = storage.get_metadata_engine_instances()
        from pio_tpu.parallel.distributed import barrier, is_primary

        primary = is_primary()
        if supervise and primary:
            try:
                swept = sweep_zombies(
                    storage,
                    **({"stale_after_s": sweep_stale_s}
                       if sweep_stale_s is not None else {}),
                )
                if swept:
                    log.warning("startup sweep transitioned %d zombie "
                                "instance(s) to FAILED: %s",
                                len(swept), [i.id for i in swept])
            except Exception:  # noqa: BLE001 - the sweep is advisory
                log.warning("startup zombie sweep failed", exc_info=True)

        instance = _resolve_instance(
            instances, primary, resume_instance_id, auto_resume,
            engine_id, engine_version, engine_variant, engine_factory, batch,
            engine_params, checkpoint_root,
        )
        resumed = instance.status in RESUMABLE_STATUSES
        instance_id = instance.id
        root.update(
            instance=instance_id,
            chips=ctx.mesh.devices.size if ctx.mesh is not None else 1)

        # a resumed run MUST read the directory the original run recorded
        # — recomputing from the current --checkpoint-root/env could point
        # at an empty dir and silently restart from step 0 (and
        # --auto-resume's has_checkpoint validation reads the recorded dir)
        ckpt_dir = (
            (instance.progress or {}).get("checkpoint_dir")
            if resumed else None
        ) or checkpoint_dir_for(instance_id, checkpoint_root)
        handler = PreemptionHandler() if supervise else None
        lifecycle = TrainLifecycle(
            instances,
            instance,
            checkpoint_dir=ckpt_dir,
            heartbeat_every_steps=heartbeat_every_steps,
            preemption=handler,
            readonly=not primary,
        )

        def record(status: str, **progress_extra) -> None:
            """Terminal status transition, keeping accumulated progress."""
            # the liveness beat must not race terminal writes
            lifecycle.stop()
            if not primary:
                return
            progress = dict(lifecycle.instance.progress)
            progress.update(progress_extra)
            lifecycle.instance = replace(
                lifecycle.instance, status=status, end_time=utcnow(),
                progress=progress,
            )
            instances.update(lifecycle.instance)

        # mark the run live before training: TRAINING + an initial
        # heartbeat so a kill -9 from now on is detectable as a stale zombie
        progress = dict(instance.progress)
        if resumed:
            progress["resumed_at"] = format_time(utcnow())
        lifecycle.instance = replace(
            instance, status="TRAINING", progress=progress
        )
        if primary:
            instances.update(lifecycle.instance)
        lifecycle.heartbeat(progress.get("step", 0), force=True)
        lifecycle.start()  # wall-clock liveness beat (see TrainLifecycle)

    ctx.lifecycle = lifecycle
    ctx.compile_meter = compile_meter
    try:
        with handler if handler is not None else nullcontext():
            models = engine.train(
                ctx,
                engine_params,
                stop_after_read=stop_after_read,
                stop_after_prepare=stop_after_prepare,
            )
            # chaos point: a `train.persist` spec simulates a storage
            # fault during the final model write — the run must land
            # FAILED (resumable from its last checkpoint), never
            # COMPLETED-without-a-blob. The barrier is reached on BOTH
            # outcomes: a host whose persist epoch failed must not leave
            # its peers blocked in sync_global_devices forever.
            # The models go from their host arrays to the store in one
            # pass: where the store keeps a file they are pickled,
            # checksummed and written a piece at a time under
            # `persist.insert` (`persist.pickle` and `persist.frame` are
            # the pickler's and the checksum's seconds inside it), and no
            # blob is ever held here.
            persist_error: Exception | None = None
            try:
                on_host = models_on_host(models)
                with tracer.span("persist.insert") as sp:
                    chaos.maybe_inject("train.persist")
                    if primary:
                        storage.get_model_data_models().insert(
                            Model(instance_id, on_host)
                        )
                    sp["bytes"] = on_host.framed_bytes or 0
            except Exception as e:  # noqa: BLE001 - re-raised after barrier
                persist_error = e
            # the COMPLETED transition must not outrun any host's part of
            # the persist epoch
            with tracer.span("train.barrier"):
                barrier("train-persist")
            if persist_error is not None:
                raise persist_error
            with tracer.span("train.complete"):
                record("COMPLETED")
            log.info("training %s COMPLETED (%d bytes of models)",
                     instance_id, on_host.framed_bytes or 0)
            # from the spans: engine.train is its three stages, persist
            # everything from the models in hand to the instance COMPLETED
            # (the pickle, the checksum and the file lie inside the insert)
            log.info("train timing: engine.train %.3fs, of which compile "
                     "%s; persist %.3fs",
                     _seconds(tracer, "train.read", "train.prepare",
                              "train.algorithms"),
                     compile_meter,
                     _seconds(tracer, "persist.d2h", "persist.insert",
                              "train.barrier", "train.complete"))
            log.info("train device memory: %s", describe_device_memory())
            # the models in hand and their host arrays go back to the
            # allocator here, not unseen as the frame unwinds
            with tracer.span("train.release", bytes=on_host.min_bytes):
                del on_host, models
            return instance_id
    except TrainingPreempted as preempted:
        try:
            record(
                "INTERRUPTED",
                preempted_at_step=preempted.step,
                resumable=True,
            )
        except Exception:  # noqa: BLE001 - preserve the preemption signal
            log.error("could not mark %s INTERRUPTED (status store down)",
                      instance_id, exc_info=True)
        log.warning(
            "training %s INTERRUPTED by preemption at step %s; resume "
            "with: pio train --resume %s",
            instance_id, preempted.step, instance_id,
        )
        raise
    except TrainingInterruption:
        record("INTERRUPTED")
        raise
    except Exception as train_error:
        log.error("training %s FAILED:\n%s",
                  instance_id, traceback.format_exc())
        try:
            record("FAILED")
        except Exception as update_error:
            # the status write failing (store down) must not MASK why
            # training died: surface the training error, chained to the
            # bookkeeping failure
            raise train_error from update_error
        raise
    finally:
        lifecycle.stop()
        ctx.lifecycle = None
        ctx.compile_meter = None


def load_models(
    storage: Storage,
    engine: Engine,
    engine_params: EngineParams,
    instance_id: str,
    ctx: WorkflowContext | None = None,
) -> list[Any]:
    """Restore an instance's models and run per-algorithm deploy prep
    (reference Engine.prepareDeploy, Engine.scala:196-266 — minus the
    retrain-on-deploy hack: device models restore straight from bytes).

    Raises ModelIntegrityError (utils/durable.py) when the stored blob
    fails its CRC32C frame — a truncated or bit-rotted artifact never
    reaches the unpickler; serve falls back to the previous COMPLETED
    instance on that error."""
    ctx = ctx or create_workflow_context(storage)
    record = storage.get_model_data_models().get(instance_id)
    if record is None:
        raise ValueError(f"no models stored for engine instance {instance_id}")
    models = models_from_bytes(record.models)
    _, _, algos, _ = engine._doers(engine_params)
    if len(models) != len(algos):
        raise ValueError(
            f"instance {instance_id} has {len(models)} models but engine "
            f"params define {len(algos)} algorithms"
        )
    return [
        algo.prepare_model_for_deploy(ctx, m)
        for algo, m in zip(algos, models)
    ]
