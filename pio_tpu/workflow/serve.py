"""Deploy server — REST query serving with models resident in device HBM.

Mirrors reference core/.../workflow/CreateServer.scala:
  GET  /               -> engine status (instance info + latency stats,
                          reference :463-487)
  POST /queries.json   -> supplement -> per-algo predict -> serve
                          (+ optional feedback event, plugins, latency
                          bookkeeping; reference :492-615)
  POST /reload         -> hot-swap to the latest eligible COMPLETED
                          instance (reference MasterActor ReloadServer
                          :334-360; GET kept as a deprecated alias)
  POST /rollout/*      -> guarded canary deploy/promote/rollback
                          (pio_tpu/rollout/, docs/serving.md)
  POST /stop           -> shut down (server-key auth, reference
                          KeyAuthentication + :277-302)
  GET  /plugins.json   -> plugin listing; /plugins/<name>/* -> plugin REST

TPU-native differences: models restore straight from the model store into
HBM (no retrain-on-deploy); predict paths are jit-warmed at startup with a
sample query so first-request latency is compile-free.
"""

from __future__ import annotations

import contextvars
import logging
import queue
from contextlib import nullcontext
import threading
import time
from concurrent.futures import (
    FIRST_COMPLETED,
    Future,
    ThreadPoolExecutor,
    TimeoutError as FuturesTimeoutError,
    wait as futures_wait,
)
from dataclasses import dataclass, field
from typing import Any

from pio_tpu.controller.engine import Engine, EngineParams
from pio_tpu.data.dao import AccessKey
from pio_tpu.data.event import Event
from pio_tpu.data.storage import Storage
from pio_tpu.resilience import CircuitOpenError, Deadline, DeadlineExceeded
from pio_tpu.resilience.health import (
    breaker_checks, install_health_routes, shedder_check,
)
from pio_tpu.rollout import (
    ARM_ACTIVE, ARM_CANDIDATE, install_rollout_routes,
    is_auto_advance_eligible,
)
from pio_tpu.server.http import (
    AsyncHttpServer, HttpApp, HttpServer, Request, json_response,
    server_key_ok,
)
from pio_tpu.server.plugins import PluginContext
from pio_tpu.utils.durable import ModelIntegrityError
from pio_tpu.utils.time import format_time, utcnow
from pio_tpu.utils.tracing import Tracer
from pio_tpu.workflow.context import WorkflowContext, create_workflow_context
from pio_tpu.workflow.train import load_models

log = logging.getLogger("pio_tpu.serve")


@dataclass
class ServingConfig:
    ip: str = "0.0.0.0"
    port: int = 8000
    engine_id: str = ""
    engine_version: str = "1"
    engine_variant: str = "default"
    feedback: bool = False
    feedback_app_name: str = ""   # app receiving pio_pr predict events
    access_key: str = ""          # access key used for feedback inserts
    server_key: str = ""          # guards /stop and /reload (KeyAuthentication)
    warm_query: dict | None = None  # sample query to jit-warm at startup
    certfile: str | None = None   # TLS cert (PEM); with keyfile -> HTTPS
    keyfile: str | None = None
    backend: str = "async"        # HTTP transport: "async" | "threaded"
    # dynamic micro-batching: concurrent /queries.json requests arriving
    # within the window are executed as ONE batch_predict per algorithm.
    # batch_window_ms > 0: fixed collection window; < 0: ADAPTIVE
    # (continuous) batching — no artificial wait, each batch is whatever
    # queued while the previous one executed, so batch size self-tunes to
    # arrival-rate x device-roundtrip (the right mode when dispatch is
    # round-trip-dominated) —
    # the TPU-native answer to CreateServer.scala:516's "TODO: Parallelize"
    # (one big matmul beats many small ones on the MXU). 0 = off.
    batch_window_ms: float = 0.0
    batch_max: int = 64
    # batches concurrently in flight. 0 = AUTO from the measured dispatch
    # RTT: 2 on a local device (double buffering — the collection window
    # overlaps the in-flight batch; depth 1 idles the device through
    # every window and deeper pipelines convoy, the round-2 "357 ms p99"
    # artifact), 4 over a high-RTT link where in-flight batches hide the
    # round trip.
    batch_pipeline: int = 0
    # tail hedging for the predict dispatch: if a device dispatch has not
    # returned after hedge_after x the rolling predict-stage MEDIAN, issue
    # a duplicate dispatch and take whichever finishes first. predict is a
    # pure function of (model, queries), so the duplicate is safe; it only
    # costs device time on the rare stall, which micro-batching would
    # otherwise amplify into a whole-batch p99 convoy. 0 disables.
    # Hedging arms only after
    # 20 recorded predict spans; warm-up calls record no spans at all
    # (record=False skips the histograms), so compiles never skew the
    # median the hedge timeout derives from.
    hedge_after: float = 3.0
    # per-request time budget (seconds) opened around each /queries.json
    # dispatch and propagated (resilience.Deadline contextvar) into the
    # storage DAO calls made on the REQUEST THREAD: retries stop
    # sleeping and I/O stops starting once the budget is spent, and the
    # request answers 503 instead of holding a connection past its
    # usefulness. Work executed on other pools (micro-batched execution,
    # hedged/multi-algo predict dispatch, background feedback) does not
    # inherit the contextvar — the batcher instead enforces the budget
    # at its result wait, and predict stages are bounded by their own
    # hedging. 0 = off.
    request_budget_s: float = 0.0
    # cross-request continuous batching (pio_tpu/serving/batcher.py):
    # > 0 puts a ContinuousBatcher in front of the device program —
    # concurrent /queries.json requests coalesce into ONE batched
    # einsum+top_k whenever a pipeline slot frees OR this window (ms)
    # elapses, whichever comes first (2 ms is the recommended default
    # when enabling; docs/serving.md "Continuous batching"). Unlike
    # batch_window_ms it is Deadline-aware: a query whose budget cannot
    # survive the window dispatches solo or sheds 503 instead of
    # parking. Takes precedence over batch_window_ms. 0 = off.
    coalesce_window_ms: float = 0.0


@dataclass
class _CandidateArm:
    """The second model slot a guarded rollout serves its canary from
    (pio_tpu/rollout/): a fully-restored instance living BEHIND the
    same swap lock as the active one, so promote is one pointer move
    and rollback is one pointer drop — never a reload."""

    instance: Any
    models: list
    algorithms: list
    serving: Any


class QueryServer:
    """Serving runtime: engine + params + restored models (reference
    ServerActor state, CreateServer.scala:407-431)."""

    def __init__(
        self,
        engine: Engine,
        engine_params: EngineParams,
        storage: Storage,
        config: ServingConfig,
        ctx: WorkflowContext | None = None,
        plugin_context: PluginContext | None = None,
        instance_id: str | None = None,
    ):
        self.engine = engine
        self.engine_params = engine_params
        self.storage = storage
        self.config = config
        self.ctx = ctx or create_workflow_context(storage)
        self.plugins = plugin_context or PluginContext()
        self._lock = threading.RLock()
        # per-stage latency histograms (replaces the reference's rolling
        # average, CreateServer.scala:420-422; SURVEY.md §5 real tracing)
        # + distributed span records (pio_tpu/obs/): every span under an
        # active trace context lands in the recorder, and the HTTP edge
        # (dispatch_safe) opens that context per request
        from pio_tpu.obs import make_recorder

        self.recorder = make_recorder("serving")
        self.tracer = Tracer(recorder=self.recorder)
        self.start_time = utcnow()
        self._stop_requested = threading.Event()
        self._predict_pool = ThreadPoolExecutor(
            max_workers=8, thread_name_prefix="predict"
        )
        # separate pool for hedged device dispatches: _hedged may be
        # CALLED from a _predict_pool worker (multi-algo path), so its
        # inner submissions must not compete for the same workers or a
        # full pool deadlocks on its own children
        self._hedge_pool = ThreadPoolExecutor(
            max_workers=8, thread_name_prefix="hedge"
        )
        self.hedged_dispatches = 0
        self.last_reload_error: str | None = None
        # streaming fold-in accounting (foldin_upsert): how many user
        # rows the freshness subsystem has hot-swapped in, and the last
        # batch's measured event-ingest -> servable staleness
        self.foldin_applied_users = 0
        self.foldin_applied_items = 0
        self.foldin_last_time = None
        self.foldin_last_staleness_s: float | None = None
        # guarded rollout (pio_tpu/rollout/): the candidate arm and the
        # controller splitting traffic onto it. Both live behind the
        # existing locks — queries snapshot whichever arm serves them
        # exactly like they snapshot the active model today.
        self.rollout = None                       # RolloutController
        self.candidate: _CandidateArm | None = None
        # fold-in rows that could not land on the candidate arm yet
        # (arm mid-swap, rank mismatch): queued and retried on the next
        # apply so freshness never silently diverges the experiment
        self._candidate_foldin_pending: dict = {}
        self._candidate_item_pending: dict = {}
        # serializes whole reloads (resolve + restore + swap) end to end
        # WITHOUT blocking queries: queries snapshot state under
        # self._lock, which a reload only takes for the final swap.
        # Without this, two concurrent /reloads could resolve different
        # "latest" instances and swap in restore-completion order,
        # leaving the older one serving.
        self._load_lock = threading.Lock()
        from pio_tpu.parallel.mesh import (
            describe_device_memory, describe_devices,
        )
        from pio_tpu.utils.compilecache import (
            BucketRegistry, CompileMeter, enable_compile_cache,
        )

        log.info("serving devices: %s", describe_devices())
        t_load = time.monotonic()
        self._load(instance_id)
        t_warm = time.monotonic()
        # admission stage in front of the device program: the continuous
        # batcher (deadline-aware, slot-OR-window drain) takes precedence
        # over the window-only micro-batcher; both expose the same
        # .query()/.close() so the serving edge and the readiness
        # "buckets" gate treat them interchangeably
        if config.coalesce_window_ms > 0:
            from pio_tpu.serving.batcher import ContinuousBatcher

            self.batcher = ContinuousBatcher(
                self, config.coalesce_window_ms / 1e3, config.batch_max,
                pipeline_depth=config.batch_pipeline
                or _auto_pipeline_depth())
        elif config.batch_window_ms != 0:
            self.batcher = QueryBatcher(
                self, config.batch_window_ms / 1e3, config.batch_max,
                pipeline_depth=config.batch_pipeline
                or _auto_pipeline_depth())
        else:
            self.batcher = None
        # persistent XLA compile cache: a re-deploy deserializes the
        # predict/bucket executables the last deployment compiled instead
        # of re-running XLA (utils/compilecache.py); the bucket registry
        # remembers WHICH buckets that deployment actually served so the
        # warm sweep compiles exactly that set
        cache_dir = enable_compile_cache()
        self.bucket_registry = (
            BucketRegistry(config.engine_id, config.engine_version,
                           config.engine_variant, cache_dir=cache_dir)
            if cache_dir is not None else None
        )
        self._buckets_warmed = False
        self._warm_once = threading.Lock()
        # /readyz gate (resilience/health.py "buckets" check): starts
        # NOT-ready only when a warm sweep is owed at startup (batching on
        # + a warm query to run it with); set once the sweep completes so
        # a load balancer never routes traffic into a bucket-miss compile.
        # Without a warm query the first real request triggers the
        # background sweep — gating then would deadlock readiness on the
        # traffic it gates, so the server reports ready and the gate only
        # drops while that background warm is in flight.
        self._buckets_ready = threading.Event()
        if self.batcher is None or config.warm_query is None:
            self._buckets_ready.set()
        with CompileMeter() as compile_meter:
            self._warm()
        log.info("serving start-up: load %.2fs, warm %.2fs, of which "
                 "compile %s", t_warm - t_load, time.monotonic() - t_warm,
                 compile_meter)
        log.info("serving device memory: %s", describe_device_memory())

    # -- model lifecycle ----------------------------------------------------
    def _load(self, instance_id: str | None = None) -> None:
        """Restore an instance's models and swap them in ATOMICALLY: every
        failable step (metadata lookup, model restore, doer construction)
        runs before the swap, so a failed load leaves the previous
        instance/models/algorithms fully intact — the last-good model
        keeps serving through a broken /reload (reference MasterActor
        keeps its old ServerActor when ReloadServer fails). Whole loads
        (resolve + restore + swap) are serialized by _load_lock so
        concurrent reloads cannot swap in restore-completion order;
        queries are NOT blocked — they contend only on the final swap."""
        with self._load_lock:
            self._load_locked(instance_id)

    def _load_locked(self, instance_id: str | None) -> None:
        c = self.config
        instances = self.storage.get_metadata_engine_instances()
        if instance_id is None:
            candidates = instances.get_completed(
                c.engine_id, c.engine_version, c.engine_variant
            )
            # rollout verdicts gate AUTO-advancement: an instance the
            # guards ROLLED_BACK (or whose canary is still in flight)
            # is skipped, so no reload/restart quietly re-serves a
            # rejected model. Operators can still pin one explicitly.
            candidates = [
                cand for cand in candidates
                if is_auto_advance_eligible(self.storage, cand.id)
            ]
            if not candidates:
                raise ValueError(
                    f"No COMPLETED engine instance eligible for engine "
                    f"{c.engine_id} {c.engine_version} "
                    f"{c.engine_variant} (rolled-back canaries are "
                    "skipped). Run train first."
                )
        else:
            instance = instances.get(instance_id)
            if instance is None:
                raise ValueError(f"Engine instance {instance_id} not found")
            candidates = [instance]
        # restore OUTSIDE the lock: queries keep serving the old model
        # while the new one loads (restore can take seconds on big models).
        # A corrupt blob (CRC32C mismatch — torn write, bit rot) on the
        # latest instance falls back to the previous COMPLETED one:
        # integrity failures are permanent for that blob, and an older
        # good model beats no model. Explicit instance_ids do not fall
        # back — the operator asked for THAT instance.
        models = instance = None
        last_integrity_error: ModelIntegrityError | None = None
        for candidate in candidates:
            try:
                models = load_models(
                    self.storage, self.engine, self.engine_params,
                    candidate.id, ctx=self.ctx,
                )
                instance = candidate
                break
            except ModelIntegrityError as e:
                log.error(
                    "model blob for instance %s is corrupt (%s); trying "
                    "the previous COMPLETED instance", candidate.id, e,
                )
                last_integrity_error = e
        if models is None:
            raise last_integrity_error
        _, _, algorithms, serving = self.engine._doers(self.engine_params)
        with self._lock:
            # hot-swap: retire the outgoing doers' resources (e.g. an
            # external engine's child process) — but on a delay: queries
            # that snapshotted the old algorithms may still be mid-predict,
            # and closing under them would kill their child mid-call
            self._retire_algorithms(getattr(self, "algorithms", []))
            self.instance = instance
            self.models = models
            self.algorithms = algorithms
            self.serving = serving
        log.info("deployed engine instance %s", instance.id)

    def reload(self) -> str:
        """Hot-swap to the latest completed instance; returns its id. On
        failure the exception propagates and the last-good model keeps
        serving (the /reload route maps it to 503 + the serving id)."""
        try:
            self._load(None)
        except Exception as e:
            self.last_reload_error = f"{type(e).__name__}: {e}"
            raise
        self.last_reload_error = None
        return self.instance.id

    # -- guarded rollout arms (pio_tpu/rollout/) -----------------------------
    def rollout_active_instance_id(self) -> str:
        with self._lock:
            return self.instance.id

    def load_candidate(self, instance_id: str) -> None:
        """Restore `instance_id` into the CANDIDATE slot alongside the
        active model. Every failable step runs before the slot is set
        (same atomicity contract as _load); no last-good fallback — a
        canary candidate is THAT instance or nothing."""
        with self._load_lock:
            instance = self.storage.get_metadata_engine_instances().get(
                instance_id)
            if instance is None:
                raise ValueError(f"Engine instance {instance_id} not found")
            if instance.status != "COMPLETED":
                raise ValueError(
                    f"candidate instance {instance_id} is "
                    f"{instance.status}, not COMPLETED")
            models = load_models(
                self.storage, self.engine, self.engine_params,
                instance.id, ctx=self.ctx,
            )
            _, _, algorithms, serving = self.engine._doers(self.engine_params)
            with self._lock:
                self._retire_algorithms(
                    self.candidate.algorithms if self.candidate else [])
                self.candidate = _CandidateArm(
                    instance=instance, models=models,
                    algorithms=algorithms, serving=serving)
                self._candidate_foldin_pending = {}
                self._candidate_item_pending = {}
        log.info("candidate arm loaded: instance %s", instance_id)

    def drop_candidate(self) -> None:
        """Discard the candidate arm (rollback). The active arm is
        untouched — in-flight queries that snapshotted the candidate
        finish on their snapshot; new ones never see it."""
        with self._lock:
            cand, self.candidate = self.candidate, None
            self._candidate_foldin_pending = {}
            self._candidate_item_pending = {}
            if cand is not None:
                self._retire_algorithms(cand.algorithms)

    def promote_candidate(self) -> None:
        """The candidate becomes the active instance (100%): one
        pointer swap under the lock, the exact shape _load uses. The
        outgoing active arm's resources retire on the usual delay.
        Queued candidate fold-ins flush under ``_load_lock`` (an upsert
        landing between an unlocked flush and the swap would be
        silently discarded); anything STILL pending at the swap — rank
        mismatch, or an apply racing the swap itself — is logged, and
        the next fold-in cycle re-solves those users."""
        with self._load_lock:
            self._flush_candidate_foldin()
            with self._lock:
                cand = self.candidate
                if cand is None:
                    raise ValueError("no candidate arm to promote")
                dropped = (len(self._candidate_foldin_pending)
                           + len(self._candidate_item_pending))
                if dropped:
                    log.warning(
                        "%d queued candidate fold-in row(s) could not "
                        "apply at promote and are dropped (next fold-in "
                        "cycle re-solves those users)", dropped)
                self._retire_algorithms(self.algorithms)
                self.instance = cand.instance
                self.models = cand.models
                self.algorithms = cand.algorithms
                self.serving = cand.serving
                self.candidate = None
                self._candidate_foldin_pending = {}
                self._candidate_item_pending = {}
        log.info("candidate promoted: instance %s now active",
                 self.instance.id)

    def _retire_algorithms(self, algorithms) -> None:
        """Close an arm's algorithm resources on a delay (see
        _load_locked: queries that snapshotted them may be mid-predict).
        Callers hold self._lock."""
        retired = [
            close for algo in algorithms
            if callable(close := getattr(algo, "close", None))
        ]
        if retired:
            # pio: lint-ok[context-loss] deliberate detach: the delayed
            # close must outlive the request (and its budget) that
            # triggered the reload
            t = threading.Timer(30.0, lambda: [c() for c in retired])
            t.daemon = True
            t.start()

    def _arm_snapshot(self, arm: str):
        """-> (models, algorithms, serving, instance_id) for the arm a
        query rides. A candidate request that races a just-finished
        rollback falls through to the active arm — a dropped arm is
        never served."""
        with self._lock:
            if arm == ARM_CANDIDATE and self.candidate is not None:
                c = self.candidate
                return c.models, c.algorithms, c.serving, c.instance.id
            return (self.models, self.algorithms, self.serving,
                    self.instance.id)

    def shadow_predict(self, q: dict, arm: str) -> Any:
        """Score `q` on one arm without stats, feedback, or plugins —
        the rollout controller's divergence sampler."""
        models, algorithms, serving, _ = self._arm_snapshot(arm)
        supplemented = serving.supplement(dict(q))
        predictions = [
            a.predict(m, supplemented) for a, m in zip(algorithms, models)
        ]
        return serving.serve(q, predictions)

    def close(self) -> None:
        """Release serving resources (predict pool, batcher thread, and any
        algorithm-held children such as external engine processes). The
        HTTP transport's stop() does not know about them."""
        from pio_tpu.parallel.mesh import describe_device_memory

        log.info("serving device memory at close: %s",
                 describe_device_memory())
        if self.batcher is not None:
            self.batcher.close()
        if self.bucket_registry is not None:
            self.bucket_registry.flush()
        self._predict_pool.shutdown(wait=False)
        self._hedge_pool.shutdown(wait=False)
        if self.rollout is not None:
            self.rollout.close()
        arms = list(getattr(self, "algorithms", []))
        if self.candidate is not None:
            arms += self.candidate.algorithms
        for algo in arms:
            close = getattr(algo, "close", None)
            if callable(close):
                close()

    def _warm_bucket_set(self) -> list[int]:
        """The bucket sizes the warm sweep compiles: exactly what the
        LAST deployment of this engine served (bucket registry) when
        known, else the full power-of-two ladder up to batch_max."""
        recorded = (
            self.bucket_registry.buckets() if self.bucket_registry else []
        )
        recorded = [b for b in recorded if b <= self.config.batch_max]
        if recorded:
            return sorted(set(recorded) | {1})
        out = []
        b = 1
        while b <= self.config.batch_max:
            out.append(b)
            b *= 2
        return out

    def _warm(self) -> None:
        if self.config.warm_query is None:
            return
        try:
            # record=False: warm-up neither counts toward stats nor
            # generates feedback events
            self.query(dict(self.config.warm_query), record=False)
        except Exception:  # noqa: BLE001 - warmup is best-effort
            log.warning("warm query failed", exc_info=True)
        if self.batcher is None:
            return
        try:
            # compile the registry's bucket set (or the power-of-two
            # ladder) up front so the micro-batcher's varying batch sizes
            # never pay jit in traffic; with the persistent compile cache
            # each of these is a deserialize, not an XLA run
            for b in self._warm_bucket_set():
                self.query_batch(
                    [dict(self.config.warm_query)] * b, record=False
                )
            self._buckets_warmed = True
        except Exception:  # noqa: BLE001 - warmup is best-effort
            log.warning("warm batch failed", exc_info=True)
        finally:
            # ready either way: a failed warm means traffic pays the
            # compile, which beats a permanently not-ready instance
            self._buckets_ready.set()

    # -- query path (reference CreateServer.scala:492-615) ------------------
    def _auto_warm_buckets(self, sample: dict) -> None:
        """Compile every micro-batch bucket in the background using a clone
        of the first real query, so bucket-miss jit never lands mid-traffic
        (a fresh bucket costs a full XLA compile — client-timeout
        territory). Explicit
        ServingConfig.warm_query still does this up-front at startup."""
        # atomic test-and-set: concurrent batch executions must not spawn
        # duplicate warm threads (each runs a full compile sweep)
        if self.batcher is None:
            return
        with self._warm_once:
            if self._buckets_warmed:
                return
            self._buckets_warmed = True
        # pio: lint-ok[attr-no-lock] threading.Event is internally locked
        self._buckets_ready.clear()  # /readyz drops while the sweep runs

        def go():
            try:
                for b in self._warm_bucket_set():
                    self.query_batch([dict(sample)] * b, record=False)
            except Exception:  # noqa: BLE001 - warmup is best-effort
                log.warning("background bucket warm failed", exc_info=True)
            finally:
                self._buckets_ready.set()

        # pio: lint-ok[context-loss] deliberate detach: bucket warm-up
        # is best-effort background compile priming, not on the
        # triggering request's clock or trace
        threading.Thread(
            target=go, name="bucket-warm", daemon=True
        ).start()

    def query(self, q: dict, record: bool = True) -> Any:
        t0 = time.monotonic()
        tr = self.tracer
        # guarded rollout: the controller picks the arm (sticky crc32c
        # user split); warm-ups (record=False) always ride active
        rollout = self.rollout if record else None
        arm = rollout.arm_for(q) if rollout is not None else ARM_ACTIVE
        # warm-up calls (record=False) must not enter the stage
        # histograms: their compile-heavy spans would pollute dashboard
        # quantiles AND the hedge-arming median (_hedge_timeout)
        span = tr.span if record else (lambda _n, **_kw: nullcontext())
        models, algorithms, serving, instance_id = self._arm_snapshot(arm)
        try:
            with span("supplement", arm=arm):
                supplemented = serving.supplement(q)
            with span("predict", arm=arm):
                if len(algorithms) > 1:
                    # concurrent per-algo predict (the parallelization
                    # the reference left as TODO, CreateServer.scala:516);
                    # device dispatch releases the GIL so the algos
                    # genuinely overlap. copy_context: predict runs ON
                    # the request path — the Deadline budget and trace
                    # must follow it onto the pool worker
                    futures = [
                        self._predict_pool.submit(
                            contextvars.copy_context().run,
                            a.predict, m, supplemented)
                        for a, m in zip(algorithms, models)
                    ]
                    predictions = [f.result() for f in futures]
                else:
                    predictions = [
                        algorithms[0].predict(models[0], supplemented)]
            with span("serve", arm=arm):
                prediction = serving.serve(q, predictions)
        except Exception:
            if rollout is not None:
                rollout.observe(arm, q, None, time.monotonic() - t0,
                                error=True)
            raise
        if rollout is not None:
            rollout.observe(arm, q, prediction, time.monotonic() - t0)
        if record:
            self._auto_warm_buckets(q)
        return self._postprocess(q, prediction, instance_id, record, t0)

    def _hedge_timeout(self) -> float | None:
        """Seconds after which a predict dispatch gets a duplicate, or
        None when hedging is off / not yet armed (needs 20 recorded spans
        so warm-up compiles never count as stalls)."""
        if self.config.hedge_after <= 0:
            return None
        h = self.tracer.histogram("predict")
        if h.count < 20:
            return None
        p50 = h.quantiles((0.5,))["p50"]
        if p50 <= 0:
            return None
        return max(0.05, self.config.hedge_after * p50)

    def _hedged(self, fn, *args):
        """Run fn on the predict pool; if it outlives the hedge timeout,
        race a duplicate and return whichever finishes first. fn must be
        pure (device predict is), so the loser is discarded harmlessly.

        The hedge clock starts when the task actually STARTS on a pool
        worker, not at submit: under >pool-width concurrent dispatches,
        queue wait would otherwise read as a "stall" and fire spurious
        duplicates into the already-saturated pool (round-3 advisor
        finding). If the task hasn't even started within the timeout,
        the pool is saturated — a duplicate could only queue behind the
        original, so hedging is skipped entirely."""
        timeout = self._hedge_timeout()
        if timeout is None:
            return fn(*args)
        started = threading.Event()
        t_start: list[float] = []

        def wrapped(*a):
            t_start.append(time.monotonic())
            started.set()
            return fn(*a)

        # copy_context on both attempts: the hedged dispatch is the
        # request's own predict — it must see the Deadline budget and
        # parent its spans into the request trace
        futs = [self._hedge_pool.submit(
            contextvars.copy_context().run, wrapped, *args)]
        if not started.wait(timeout):
            # saturated pool: no worker picked the task up within the
            # hedge window — duplicates add load without cutting latency
            return futs[0].result()
        try:
            remaining = t_start[0] + timeout - time.monotonic()
            return futs[0].result(timeout=max(0.0, remaining))
        except FuturesTimeoutError:
            with self._lock:
                self.hedged_dispatches += 1
            futs.append(self._hedge_pool.submit(
                contextvars.copy_context().run, fn, *args))
        # first SUCCESS wins; an attempt's exception propagates only once
        # every attempt has failed (the stalled original may fail while
        # the duplicate is still inbound with the answer)
        pending = set(futs)
        first_exc: BaseException | None = None
        while pending:
            done, pending = futures_wait(
                pending, timeout=60.0, return_when=FIRST_COMPLETED
            )
            for f in done:
                exc = f.exception()
                if exc is None:
                    for loser in pending:
                        loser.cancel()  # free not-yet-started duplicates
                    return f.result()
                first_exc = first_exc or exc
        raise first_exc

    def query_batch(self, queries: list[dict], record: bool = True,
                    observe_batch_errors: bool = True) -> list:
        """Serve several queries as one batch_predict per algorithm (the
        micro-batching execution path; also the bulk path behind
        /batch/queries.json). With a rollout in flight the batch is
        partitioned by arm — each sub-batch executes against its own
        arm's models, results reassemble in request order.

        observe_batch_errors=False is for callers that retry each query
        SOLO after a batch failure (QueryBatcher/ContinuousBatcher): the
        solo retries record per-arm rollout stats themselves, so the
        batch-level error observation here would double-count every
        member and skew the latency-ratio guard. Double-count audit:
        `query(record=False)` takes rollout=None (no stats at all), and
        `_hedged` duplicates run the bare predict fn — neither path ever
        re-records a request's per-arm stats; this flag closes the one
        path that did."""
        t0 = time.monotonic()
        rollout = self.rollout if record else None
        if rollout is not None:
            arms = [rollout.arm_for(q) for q in queries]
            if ARM_CANDIDATE in arms:
                out: list = [None] * len(queries)
                for arm in (ARM_ACTIVE, ARM_CANDIDATE):
                    idx = [i for i, a in enumerate(arms) if a == arm]
                    if not idx:
                        continue
                    sub = self._query_batch_arm(
                        [queries[i] for i in idx], arm, record, t0,
                        rollout, observe_batch_errors)
                    for i, r in zip(idx, sub):
                        out[i] = r
                return out
        return self._query_batch_arm(queries, ARM_ACTIVE, record, t0,
                                     rollout, observe_batch_errors)

    def _query_batch_arm(self, queries: list[dict], arm: str, record: bool,
                         t0: float, rollout,
                         observe_batch_errors: bool = True) -> list:
        tr = self.tracer
        # see query(): warm-up spans stay out of the histograms
        span = tr.span if record else (lambda _n, **_kw: nullcontext())
        # per-ARM clock for the rollout stats (t0 stays the whole-batch
        # clock for _postprocess bookkeeping): the arms execute
        # sequentially, so charging candidate observations from the
        # whole-batch start would bill the active sub-batch's time to
        # the candidate and trip the latency-ratio guard on perfectly
        # healthy canaries
        arm_t0 = time.monotonic()
        models, algorithms, serving, instance_id = self._arm_snapshot(arm)
        try:
            return self._query_batch_body(
                queries, arm, record, t0, arm_t0, rollout, span, models,
                algorithms, serving, instance_id)
        except Exception:
            if rollout is not None and observe_batch_errors:
                # per-QUERY time (sub-batch wall / size): whole-batch
                # time would make each arm's mean scale with its share
                # of the split — at 25% the candidate would look 3x
                # faster (slow canary promoted) and at 80% 4x slower
                # (healthy canary rolled back)
                dt = (time.monotonic() - arm_t0) / max(1, len(queries))
                for q in queries:
                    rollout.observe(arm, q, None, dt, error=True)
            raise

    def _query_batch_body(self, queries, arm, record, t0, arm_t0, rollout,
                          span, models, algorithms, serving, instance_id):
        with span("supplement", arm=arm):
            supplemented = [serving.supplement(q) for q in queries]
        with span("predict", arm=arm):
            if len(algorithms) > 1:
                futures = [
                    self._predict_pool.submit(
                        contextvars.copy_context().run,
                        self._hedged, a.batch_predict, m, supplemented)
                    for a, m in zip(algorithms, models)
                ]
                per_algo = [f.result() for f in futures]
            else:
                per_algo = [
                    self._hedged(
                        algorithms[0].batch_predict, models[0], supplemented)
                ]
        if record and queries:
            # the batched path is the PRIMARY path when the batcher is on
            # (query() is bypassed), so auto-warm must hook here too; the
            # warm calls themselves pass record=False and cannot recurse
            self._auto_warm_buckets(queries[0])
            if self.bucket_registry is not None:
                # remember the pow2 bucket this batch landed in so the
                # NEXT deployment's warm sweep compiles exactly the set
                # this one served
                self.bucket_registry.record(
                    min(1 << (len(queries) - 1).bit_length(),
                        self.config.batch_max))
        with span("serve", arm=arm):
            predictions = [
                serving.serve(q, [algo_out[i] for algo_out in per_algo])
                for i, q in enumerate(queries)
            ]
        if rollout is not None:
            # per-query time, not whole-sub-batch time — see the error
            # path above
            dt = (time.monotonic() - arm_t0) / max(1, len(queries))
            for q, p in zip(queries, predictions):
                rollout.observe(arm, q, p, dt)
        return [
            self._postprocess(q, p, instance_id, record, t0)
            for q, p in zip(queries, predictions)
        ]

    def _postprocess(self, q, prediction, instance_id, record, t0):
        if record and self.config.feedback:
            prediction = self._feedback(q, prediction, instance_id)
        for blocker in self.plugins.output_blockers:
            prediction = blocker.process(
                q, prediction, {"engineInstanceId": instance_id}
            )
        if record:
            self.tracer.record("query", time.monotonic() - t0)
        return prediction

    def _feedback(self, query: dict, prediction: Any, instance_id: str):
        """Record the prediction as a pio_pr 'predict' event
        (reference CreateServer.scala:536-598). In-process insert — there is
        no separate event-server JVM to POST across."""
        import secrets

        pr_id = None
        if isinstance(prediction, dict):
            pr_id = prediction.get("prId") or None
        new_pr_id = pr_id or secrets.token_urlsafe(48)[:64]
        event = Event(
            event="predict",
            entity_type="pio_pr",
            entity_id=new_pr_id,
            properties={
                "engineInstanceId": instance_id,
                "query": query,
                "prediction": prediction,
            },
            pr_id=query.get("prId") if isinstance(query, dict) else None,
        )

        def send():
            try:
                app = self.storage.get_metadata_apps().get_by_name(
                    self.config.feedback_app_name
                )
                if app is None:
                    log.error(
                        "feedback app %r not found",
                        self.config.feedback_app_name,
                    )
                    return
                self.storage.get_events().insert(event, app.id)
            except Exception:  # noqa: BLE001 - feedback must not fail serving
                log.error("feedback event failed", exc_info=True)

        # pio: lint-ok[context-loss] deliberate detach (see
        # Deadline docstring): the feedback insert must not be
        # cancelled by the request's exhausted budget, and it runs
        # after the response is already decided
        threading.Thread(target=send, daemon=True).start()
        if isinstance(prediction, dict) and "prId" in prediction:
            prediction = dict(prediction, prId=new_pr_id)
        return prediction

    # -- streaming fold-in (pio_tpu/freshness/) ------------------------------
    def foldin_upsert(self, rows, staleness_s: float | None = None,
                      items=None) -> dict:
        """Hot-swap refreshed user factor rows into the serving model
        (the freshness subsystem's apply surface): existing users'
        rows are replaced in place, new users are APPENDED — id index
        and factor table extended together, so ``recommend_topk`` and
        the id decode stay aligned. Last-good semantics: the new model
        is built completely OUTSIDE the lock and swapped atomically; a
        failure anywhere leaves the previous model serving untouched.
        ``rows`` maps user id → (k,)-float sequence. With a rollout in
        flight the rows land on BOTH arms (or queue for the candidate),
        so streaming freshness never silently diverges the experiment.

        ``items`` maps item id → (k,)-float sequence and upserts
        EXISTING items' factor rows in the same atomic swap — including
        the two-stage retrieval sidecar (ops/retrieval.py): the cached
        quantized table and cluster assignments are re-encoded for
        exactly the touched rows, so an upserted item is retrievable
        through the candidate tier immediately after this call returns,
        not after a lazy rebuild. Unknown item ids are REJECTED (shard
        parity: appending an item needs a global dense index that only
        a retrain/repartition assigns)."""
        rows = rows or {}
        items = items or {}
        if not rows and not items:
            with self._lock:
                return {"applied": 0, "new": 0,
                        "engineInstanceId": self.instance.id}
        with self._lock:
            models = list(self.models)
            instance_id = self.instance.id
        mi, model, new_model, new_ids = _fold_rows_into(models, rows)
        items_applied, items_rejected = 0, []
        if items:
            new_model, items_applied, items_rejected = \
                _fold_item_rows_into(new_model, items)
        with self._lock:
            # the model may have moved while we built the new one: a
            # /reload (new instance — applying stale rows onto it would
            # mix factor spaces) or a CONCURRENT fold-in apply (swapping
            # over it would silently drop the other batch's rows, which
            # the folder then never refolds — its cursor advanced).
            # Object identity catches both; report instead of guessing
            if (self.instance.id != instance_id
                    or self.models[mi] is not model):
                raise ValueError(
                    f"serving model changed (instance {instance_id} -> "
                    f"{self.instance.id}, or a concurrent fold-in apply) "
                    "during fold-in apply; retry")
            models = list(self.models)
            models[mi] = new_model
            self.models = models
            self.foldin_applied_users += len(rows)
            self.foldin_applied_items += items_applied
            self.foldin_last_time = utcnow()
            if staleness_s is not None:
                self.foldin_last_staleness_s = float(staleness_s)
        out = {"applied": len(rows), "new": len(new_ids),
               "engineInstanceId": instance_id}
        if items:
            out["itemsApplied"] = items_applied
            out["itemsRejected"] = items_rejected
        # second arm: the ACTIVE apply above is the durable one (the
        # folder's cursor advances on it); the candidate apply is
        # best-effort-with-queue — a failure parks the rows in
        # _candidate_foldin_pending and retries on the next apply (and
        # at promote), never blocking freshness on the experiment
        with self._lock:
            has_candidate = self.candidate is not None
        if has_candidate:
            out["candidateQueued"] = self._apply_foldin_to_candidate(
                rows, items)
        return out

    def _apply_foldin_to_candidate(self, rows, items=None) -> int:
        """Apply `rows`/`items` (plus anything previously queued) to the
        candidate arm. Returns the queue depth left behind (0 = fully
        applied). Never raises: the active apply already succeeded and
        the folder must not re-solve the window for a canary hiccup."""
        with self._lock:
            cand = self.candidate
            if cand is None:
                self._candidate_foldin_pending = {}
                self._candidate_item_pending = {}
                return 0
            pending = dict(self._candidate_foldin_pending)
            pending.update(rows)
            pending_items = dict(self._candidate_item_pending)
            pending_items.update(items or {})
            models = list(cand.models)
        try:
            mi, model, new_model, _ = _fold_rows_into(models, pending)
            if pending_items:
                new_model, _, _ = _fold_item_rows_into(
                    new_model, pending_items)
        except ValueError as e:
            with self._lock:
                self._candidate_foldin_pending = pending
                self._candidate_item_pending = pending_items
            log.warning("fold-in rows queued for candidate arm (%d "
                        "users, %d items): %s", len(pending),
                        len(pending_items), e)
            return len(pending) + len(pending_items)
        with self._lock:
            cand = self.candidate
            if cand is None:
                self._candidate_foldin_pending = {}
                self._candidate_item_pending = {}
                return 0
            if cand.models[mi] is not model:
                # the arm moved mid-build (promote/drop/another apply):
                # queue and let the next apply land on the new arm
                self._candidate_foldin_pending = pending
                self._candidate_item_pending = pending_items
                return len(pending) + len(pending_items)
            cand_models = list(cand.models)
            cand_models[mi] = new_model
            self.candidate = _CandidateArm(
                instance=cand.instance, models=cand_models,
                algorithms=cand.algorithms, serving=cand.serving)
            self._candidate_foldin_pending = {}
            self._candidate_item_pending = {}
        return 0

    def _flush_candidate_foldin(self) -> None:
        """Drain queued candidate fold-ins (called before promote so
        the promoted arm is as fresh as the active one was)."""
        with self._lock:
            pending = dict(self._candidate_foldin_pending)
            pending_items = dict(self._candidate_item_pending)
        if pending or pending_items:
            self._apply_foldin_to_candidate(pending, pending_items)

    def foldin_status(self) -> dict:
        """Bounded-staleness accounting for /readyz + /metrics.json."""
        with self._lock:
            return {
                "appliedUsers": self.foldin_applied_users,
                "appliedItems": self.foldin_applied_items,
                "lastAppliedTime": (format_time(self.foldin_last_time)
                                    if self.foldin_last_time else None),
                "stalenessSeconds": self.foldin_last_staleness_s,
                "candidateQueued": (len(self._candidate_foldin_pending)
                                    + len(self._candidate_item_pending)),
            }

    # -- status -------------------------------------------------------------
    @property
    def request_count(self) -> int:
        return self.tracer.histogram("query").count

    @property
    def avg_serving_sec(self) -> float:
        h = self.tracer.histogram("query")
        return h.total / h.count if h.count else 0.0

    @property
    def last_serving_sec(self) -> float:
        return self.tracer.histogram("query").last

    def status(self) -> dict:
        with self._lock:
            return {
                "status": "alive",
                "engineInstance": {
                    "id": self.instance.id,
                    "engineId": self.instance.engine_id,
                    "engineVersion": self.instance.engine_version,
                    "engineVariant": self.instance.engine_variant,
                    "startTime": format_time(self.instance.start_time),
                },
                "startTime": format_time(self.start_time),
                "requestCount": self.request_count,
                "avgServingSec": round(self.avg_serving_sec, 6),
                "lastServingSec": round(self.last_serving_sec, 6),
            }

    def metrics(self) -> dict:
        """Per-stage latency histograms (p50/p90/p95/p99 over the recent
        window, all-time count/avg) — the serving observability surface.
        ``exemplars`` link each span's slowest recent occurrence to a
        trace id fetchable with ``pio trace <id>``."""
        out = {
            "startTime": format_time(self.start_time),
            "spans": self.tracer.snapshot(),
            "hedgedDispatches": self.hedged_dispatches,
            "foldin": self.foldin_status(),
        }
        if self.recorder is not None:
            out["exemplars"] = self.recorder.exemplars()
        return out


def _fold_rows_into(models: list, rows) -> tuple:
    """Build an updated factor-table model with `rows` upserted —
    existing users replaced in place, new users appended with the id
    index extended in lockstep. Pure with respect to serving state (the
    caller swaps under its lock): returns
    ``(model_index, old_model, new_model, new_ids)``. Raises ValueError
    when no deployed model has a factor table or a row's rank
    mismatches."""
    import dataclasses

    import jax.numpy as jnp
    import numpy as np

    for mi, model in enumerate(models):
        factors = getattr(model, "factors", None)
        if (getattr(factors, "user_factors", None) is not None
                and getattr(model, "users", None) is not None):
            break
    else:
        raise ValueError(
            "fold-in needs a factor-table model (factors.user_factors "
            "+ users index); none of the deployed models qualifies")
    uf = model.factors.user_factors
    k = int(uf.shape[1])
    users = model.users
    existing: list[tuple[int, list[float]]] = []
    new_ids: list = []
    new_rows: list = []
    for uid, row in rows.items():
        if len(row) != k:
            raise ValueError(
                f"fold-in row for {uid!r} has {len(row)} dims, model "
                f"rank is {k}")
        if uid in users:
            existing.append((users.index_of(uid), row))
        else:
            new_ids.append(uid)
            new_rows.append(row)
    new_uf = uf
    if existing:
        idx = np.fromiter((i for i, _ in existing), np.int32,
                          count=len(existing))
        vals = np.asarray([r for _, r in existing], np.float32)
        new_uf = new_uf.at[jnp.asarray(idx)].set(jnp.asarray(vals))
    if new_ids:
        new_uf = jnp.concatenate(
            [new_uf, jnp.asarray(np.asarray(new_rows, np.float32))])
    new_model = dataclasses.replace(
        model,
        factors=dataclasses.replace(model.factors, user_factors=new_uf),
        users=users.extended(new_ids) if new_ids else users,
    )
    # a user-only fold-in leaves item_factors the SAME array object, so
    # the retrieval sidecar cache (keyed by item-table identity in
    # models/recommendation.py) stays valid — carry it so a user upsert
    # never forces a k-means rebuild on the next clustered query
    cache = getattr(model, "_retrieval_cache", None)
    if cache is not None:
        new_model._retrieval_cache = cache
    return mi, model, new_model, new_ids


def _fold_item_rows_into(model, items) -> tuple:
    """Upsert EXISTING items' factor rows on `model` — the item-side
    half of streaming fold-in. Returns ``(new_model, applied,
    rejected_ids)``; unknown ids are rejected, not appended (appending
    an item needs the retrieval/partition tier's dense index space to
    grow, which only a retrain assigns — shard.upsert_item_rows makes
    the same call). When the model carries a two-stage retrieval cache
    for its current item table, the quantized rows and cluster
    assignments are re-encoded for the touched positions IN THIS BUILD,
    so the swap that publishes the f32 rows publishes the candidate
    tier's view of them too — never a stale quantized row serving
    beside a fresh f32 one. Raises ValueError on rank mismatch."""
    import dataclasses

    import jax.numpy as jnp
    import numpy as np

    itf = getattr(getattr(model, "factors", None), "item_factors", None)
    if itf is None or getattr(model, "items", None) is None:
        raise ValueError(
            "item fold-in needs a factor-table model (factors."
            "item_factors + items index); the deployed model "
            "does not qualify")
    k = int(itf.shape[1])
    positions: list[int] = []
    vals: list = []
    rejected: list = []
    for iid, row in items.items():
        if len(row) != k:
            raise ValueError(
                f"fold-in row for item {iid!r} has {len(row)} dims, "
                f"model rank is {k}")
        if iid in model.items:
            positions.append(model.items.index_of(iid))
            vals.append(row)
        else:
            rejected.append(iid)
    if not positions:
        return model, 0, rejected
    pos = np.fromiter(positions, np.int32, count=len(positions))
    rows_f32 = np.asarray(vals, np.float32)
    new_itf = itf.at[jnp.asarray(pos)].set(jnp.asarray(rows_f32))
    new_model = dataclasses.replace(
        model,
        factors=dataclasses.replace(model.factors, item_factors=new_itf),
    )
    cache = getattr(model, "_retrieval_cache", None)
    if cache is not None and cache[0] is itf:
        from pio_tpu.ops import retrieval as rt

        idx, _didx = cache[1]
        new_idx = idx.updated(pos, rows_f32)
        new_model._retrieval_cache = (
            new_itf, (new_idx, rt.build_device_index(new_idx)))
    return new_model, len(positions), rejected


def _depth_for_rtt(rtt_s: float) -> int:
    """Dispatch-RTT -> pipeline depth. Devices behind a high-RTT link
    want several batches in flight to hide it; local devices get TWO:
    with one batch in flight any stall serializes the whole queue
    behind it, and 2 is the minimal depth that avoids that while still
    bounding how deep a queue can build behind a stalled batch. Note
    this sizes the pipeline GIVEN that the operator enabled batching;
    whether batching pays at all is a separate call (ROADMAP S5)."""
    return 4 if rtt_s > 0.005 else 2


_auto_depth_cache: int | None = None
_auto_depth_lock = threading.Lock()


def _auto_pipeline_depth() -> int:
    """Resolve ServingConfig.batch_pipeline=0: measure the device dispatch
    round-trip once per process (cached — re-deploys and multi-engine
    processes skip the probe) and map it via _depth_for_rtt. Probe and
    cache write run under a lock: two engines deploying concurrently must
    not both pay the probe (found by `pio lint`, global-no-lock)."""
    global _auto_depth_cache
    with _auto_depth_lock:
        if _auto_depth_cache is not None:
            return _auto_depth_cache
        try:
            import jax
            import jax.numpy as jnp

            one = jnp.ones(())
            add = jax.jit(lambda x: x + 1)
            # pio: lint-ok[blocking-under-lock] one-time boot probe:
            # the lock exists to serialize exactly this measurement
            # (docstring above); steady state returns the cache
            jax.block_until_ready(add(one))  # compile, not measurement
            samples = []
            for _ in range(5):
                t0 = time.monotonic()
                jax.block_until_ready(add(one))
                samples.append(time.monotonic() - t0)
            depth = _depth_for_rtt(sorted(samples)[len(samples) // 2])
        except Exception:  # noqa: BLE001 - sizing must never fail boot
            depth = 2
        _auto_depth_cache = depth
        return depth


class QueryBatcher:
    """Dynamic micro-batching: requests enqueue, a collector thread drains
    up to `max_batch` of them within `window_s`, and each batch executes as
    one `query_batch` ON A POOL — so several batches stay in flight at once.
    One big top-k matmul replaces N small ones (the MXU-friendly shape) and
    the pipelining keeps throughput up even when a device dispatch is
    round-trip-dominated; cost is up to window_s added latency, so it
    is off unless ServingConfig.batch_window_ms is set.

    window_s < 0 selects ADAPTIVE batching: the collector never waits —
    it drains everything already queued and hands it off, so while a
    batch executes the next one accumulates. Batch size then self-tunes
    to arrival_rate x execution_time with ZERO added latency at low
    load; a fixed window can only lose against it when execution is
    RTT-dominated."""

    def __init__(self, server: QueryServer, window_s: float, max_batch: int,
                 pipeline_depth: int):
        self.server = server
        self.window_s = window_s
        self.max_batch = max_batch
        self._q: queue.Queue[tuple[dict, Future]] = queue.Queue()
        self._closed = False
        self._pool = ThreadPoolExecutor(
            max_workers=pipeline_depth, thread_name_prefix="batch-exec"
        )
        # backpressure: ThreadPoolExecutor.submit never blocks, so without
        # this bound the collector shreds the queue into 1-sized batches
        # that pile up in the executor's unbounded queue — no batch ever
        # forms and latency becomes queue wait. Acquired BEFORE draining,
        # so requests accumulate while all pipeline slots are busy and
        # each freed slot takes a real batch (CPU co-located, 16 clients:
        # batched 6.6ms p50 / 1499 qps vs unbatched async 12.6ms / 1242).
        self._slots = threading.BoundedSemaphore(pipeline_depth)
        self._thread = threading.Thread(
            target=self._run, name="query-batcher", daemon=True
        )
        self._thread.start()

    def query(self, q: dict) -> Any:
        fut: Future = Future()
        self._q.put((q, fut))
        # batch execution runs on the batcher pool, which does not
        # inherit the caller's Deadline contextvar — enforce the budget
        # here, at the wait (the batch result lands harmlessly later)
        timeout = Deadline.remaining()
        try:
            return fut.result(timeout=timeout)
        except FuturesTimeoutError:
            raise DeadlineExceeded(
                "request budget exhausted waiting for batch execution"
            ) from None

    def _run(self):
        while not self._closed:
            try:
                first = self._q.get(timeout=0.5)
            except queue.Empty:
                continue
            self._slots.acquire()  # wait for a pipeline slot FIRST
            batch = [first]
            if self.window_s < 0:  # adaptive: take what's there, no wait
                while len(batch) < self.max_batch:
                    try:
                        batch.append(self._q.get_nowait())
                    except queue.Empty:
                        break
            else:
                deadline = time.monotonic() + self.window_s
                while len(batch) < self.max_batch:
                    remaining = deadline - time.monotonic()
                    if remaining <= 0:
                        break
                    try:
                        batch.append(self._q.get(timeout=remaining))
                    except queue.Empty:
                        break
            # hand off and go straight back to collecting the next batch
            try:
                self._pool.submit(self._execute, batch)
            except RuntimeError as e:
                self._slots.release()
                # close() raced the collection: fail the batch's waiters
                # rather than stranding them on never-set futures
                for _, fut in batch:
                    if not fut.done():
                        fut.set_exception(e)
                return

    def _execute(self, batch: list[tuple[dict, Future]]):
        queries = [q for q, _ in batch]
        try:
            self._do_execute(batch, queries)
        finally:
            self._slots.release()

    def _do_execute(self, batch, queries):
        try:
            # observe_batch_errors=False: the per-query retry below
            # records each member's rollout stats exactly once on a
            # batch failure (see query_batch's double-count audit)
            results = self.server.query_batch(
                queries, observe_batch_errors=False)
            for (_, fut), res in zip(batch, results):
                fut.set_result(res)
        except Exception:  # noqa: BLE001 - isolate the bad query
            # one malformed query must not fail its batch-mates: retry
            # each one alone so only the offender sees the error
            for q, fut in batch:
                if fut.done():
                    continue
                try:
                    fut.set_result(self.server.query(q))
                except Exception as e:  # noqa: BLE001
                    fut.set_exception(e)

    def close(self):
        self._closed = True
        self._pool.shutdown(wait=False)


def build_serving_app(server: QueryServer) -> HttpApp:
    app = HttpApp("serving")
    config = server.config

    def check_server_key(req: Request) -> bool:
        return server_key_ok(req, config.server_key)

    @app.route("GET", r"/")
    def root(req: Request):
        return 200, server.status()

    def _budgeted(fn):
        """Run one query dispatch under the per-request Deadline budget
        (ServingConfig.request_budget_s); exhausted budgets and tripped
        storage breakers surface as 503 + Retry-After instead of a 500
        or a connection held past its usefulness."""
        try:
            if config.request_budget_s > 0:
                with Deadline.budget(config.request_budget_s):
                    return 200, fn()
            return 200, fn()
        except KeyError as e:
            return 400, {"message": f"query missing field {e}"}
        except DeadlineExceeded as e:
            return 503, json_response(
                {"message": f"request budget exhausted: {e}"},
                {"Retry-After": "1"},
            )
        except CircuitOpenError as e:
            return 503, json_response(
                {"message": str(e)},
                {"Retry-After": f"{max(1, round(e.retry_after_s))}"},
            )

    @app.route("POST", r"/queries\.json")
    def queries(req: Request):
        try:
            q = req.json()
        except Exception as e:  # noqa: BLE001 - malformed body
            return 400, {"message": f"Invalid query: {e}"}
        if not isinstance(q, dict):
            return 400, {"message": "query must be a JSON object"}
        if server.batcher is not None:
            return _budgeted(lambda: server.batcher.query(q))
        return _budgeted(lambda: server.query(q))

    @app.route("POST", r"/batch/queries\.json")
    def batch_queries(req: Request):
        """Bulk endpoint: a JSON array of queries answered by one
        batch_predict per algorithm (no reference analogue; the event
        server's /batch/events.json shape applied to serving)."""
        try:
            qs = req.json()
        except Exception as e:  # noqa: BLE001 - malformed body
            return 400, {"message": f"Invalid query batch: {e}"}
        if not isinstance(qs, list) or not all(isinstance(q, dict) for q in qs):
            return 400, {"message": "body must be a JSON array of objects"}
        if not qs:
            return 200, []
        return _budgeted(lambda: server.query_batch(qs))

    @app.route("POST", r"/model/upsert_users")
    def upsert_users(req: Request):
        """Streaming fold-in apply surface (pio_tpu/freshness/): body
        ``{"users": {id: [row]}, "items"?: {id: [row]},
        "stalenessSeconds"?: s}``. Item rows upsert existing items AND
        their two-stage retrieval sidecar in the same swap. Guarded
        like /reload — it mutates the serving model."""
        if not check_server_key(req):
            return 401, {"message": "Invalid accessKey."}
        try:
            body = req.json()
        except Exception as e:  # noqa: BLE001 - malformed body
            return 400, {"message": f"Invalid body: {e}"}
        users = body.get("users") if isinstance(body, dict) else None
        items = body.get("items") if isinstance(body, dict) else None
        if not isinstance(users, dict) and not isinstance(items, dict):
            return 400, {"message": "body must be {\"users\": {id: [row]}}"
                                    " and/or {\"items\": {id: [row]}}"}
        try:
            out = server.foldin_upsert(
                users if isinstance(users, dict) else {},
                body.get("stalenessSeconds"),
                items=items if isinstance(items, dict) else {})
        except ValueError as e:
            return 400, {"message": str(e)}
        return 200, out

    @app.route("POST", r"/reload")
    @app.route("GET", r"/reload")  # deprecated alias: reload MUTATES
    # serving state, so POST is the canonical route (docs/serving.md);
    # GET remains for pre-PR-8 clients and scripts
    def reload(req: Request):
        if not check_server_key(req):
            return 401, {"message": "Invalid accessKey."}
        try:
            instance_id = server.reload()
        except Exception as e:  # noqa: BLE001 - degrade, don't die
            # last-good serving: the failed load left the previous
            # instance fully in place (see QueryServer._load), so report
            # the failure AND what is still serving
            with server._lock:
                still = server.instance.id
            return 503, json_response(
                {"message": f"Reload failed ({type(e).__name__}: {e}); "
                            "still serving last-good model",
                 "engineInstanceId": still},
                {"Retry-After": "1"},
            )
        return 200, {"message": "Reloaded", "engineInstanceId": instance_id}

    @app.route("POST", r"/stop")
    def stop(req: Request):
        if not check_server_key(req):
            return 401, {"message": "Invalid accessKey."}
        server._stop_requested.set()
        return 200, {"message": "Shutting down."}

    @app.route("GET", r"/metrics\.json")
    def metrics(req: Request):
        return 200, server.metrics()

    @app.route("GET", r"/metrics")
    def metrics_prometheus(req: Request):
        """Prometheus text exposition of the same data as /metrics.json
        (span latency summaries + counters) for scrape-based stacks —
        through the shared renderer with the uniform `surface` label
        (docs/observability.md)."""
        from pio_tpu.server.http import RawResponse
        from pio_tpu.utils.httpclient import pool_counters
        from pio_tpu.utils.tracing import (
            PROMETHEUS_CONTENT_TYPE, prometheus_text,
        )

        counters = {
            "hedged_dispatches_total": float(server.hedged_dispatches),
            "foldin_applied_users_total":
                float(server.foldin_applied_users),
            "uptime_seconds":
                (utcnow() - server.start_time).total_seconds(),
        }
        # outbound keep-alive pool (docs/performance.md "Internal RPC
        # plane"): the serving process's storage DAO RPCs ride it
        counters.update(pool_counters())
        text = prometheus_text(server.tracer.snapshot(), counters,
                               labels={"surface": "serving"})
        batcher = server.batcher
        if batcher is not None and hasattr(batcher, "occupancy_exposition"):
            # continuous batching: batch-occupancy distribution (fraction
            # of batch_max per coalesced dispatch) as a real histogram
            # family — the occupancy-pinned-at-1.0 saturation signal
            # docs/observability.md documents
            from pio_tpu.utils.tracing import prometheus_histogram

            buckets, counts, total, occ_sum = batcher.occupancy_exposition()
            text += "\n".join(prometheus_histogram(
                "serving_batch_occupancy", buckets, counts, total, occ_sum,
                labels={"surface": "serving"})) + "\n"
        return 200, RawResponse(text, PROMETHEUS_CONTENT_TYPE)

    @app.route("GET", r"/batcher\.json")
    def batcher_status(req: Request):
        """Admission-stage visibility: which batcher fronts the device
        program (continuous / micro / none) and its live counters —
        dispatches, coalesced queries, occupancy, coalesce wait, solo
        bypasses and deadline sheds (docs/serving.md "Continuous
        batching")."""
        batcher = server.batcher
        if batcher is None:
            return 200, {"mode": None, "enabled": False}
        if hasattr(batcher, "stats"):
            return 200, {"enabled": True, **batcher.stats()}
        return 200, {
            "enabled": True, "mode": "micro",
            "windowMs": config.batch_window_ms,
            "maxBatch": config.batch_max,
        }

    @app.route("POST", r"/batcher/window")
    def batcher_window(req: Request):
        """Live coalesce-window retune (server-key guarded, like /reload):
        the occupancy runbook's knob — widen a window whose batches run
        near-empty, narrow one pinned at occupancy 1.0 — without a
        redeploy. Continuous batcher only."""
        if not check_server_key(req):
            return 401, {"message": "Invalid accessKey."}
        batcher = server.batcher
        if batcher is None or not hasattr(batcher, "set_window"):
            return 409, {"message": "continuous batching is not enabled "
                                    "(ServingConfig.coalesce_window_ms)"}
        try:
            body = req.json()
            window_ms = float(body["windowMs"])
        except Exception as e:  # noqa: BLE001 - malformed body
            return 400, {"message": f"body must be {{\"windowMs\": ms}}: "
                                    f"{e}"}
        if not (0 < window_ms <= 1000):
            return 400, {"message": "windowMs must be in (0, 1000]"}
        batcher.set_window(window_ms / 1e3)
        return 200, {"message": "window updated", **batcher.stats()}

    @app.route("POST", r"/profile/start")
    def profile_start(req: Request):
        """Capture a device (XLA/TPU) trace while serving — the TPU
        equivalent of attaching the Spark UI. Guarded like /stop."""
        if not check_server_key(req):
            return 401, {"message": "Invalid accessKey."}
        from pio_tpu.utils.tracing import start_device_profile

        logdir = req.params.get("logdir", "/tmp/pio_tpu_profile")
        if not start_device_profile(logdir):
            return 409, {"message": "profile already running"}
        return 200, {"message": "profiling", "logdir": logdir}

    @app.route("POST", r"/profile/stop")
    def profile_stop(req: Request):
        if not check_server_key(req):
            return 401, {"message": "Invalid accessKey."}
        from pio_tpu.utils.tracing import stop_device_profile

        logdir = stop_device_profile()
        if logdir is None:
            return 409, {"message": "no profile running"}
        return 200, {"message": "profile written", "logdir": logdir}

    def readiness() -> dict:
        """model loaded + storage breakers not open + async-transport
        queue under its shed watermark (resilience/health.py contract)."""
        checks = breaker_checks(server.storage)
        with server._lock:
            inst = getattr(server, "instance", None)
        checks["model"] = {
            "ok": inst is not None,
            "engineInstanceId": inst.id if inst is not None else None,
            "lastReloadError": server.last_reload_error,
        }
        # fold-in visibility, NEVER a readiness gate: a stale/absent
        # folder means batch-stale serving (degraded freshness), and
        # flipping serving readyz for it would turn that degradation
        # into the outage the freshness contract forbids
        checks["freshness"] = {"ok": True, **server.foldin_status()}
        # bucket-warm gate: NOT ready while a micro-batch warm sweep is
        # owed or in flight — a balancer that routes on /readyz never
        # lands traffic in a bucket-miss XLA compile (a cold start's
        # p99 is that compile). Always-true when batching is off
        # or no warm query is configured (the sweep then rides the first
        # real request, which readiness must not deadlock on).
        if server.batcher is not None:
            checks["buckets"] = {
                "ok": server._buckets_ready.is_set(),
                "warmed": server._buckets_warmed,
                "registry": (server.bucket_registry.buckets()
                             if server.bucket_registry else None),
            }
        # rollout visibility, never a readiness gate: a breached canary
        # auto-rolls-back to the active arm — the server stays ready
        # throughout (that atomic revert is the whole point)
        rollout = server.rollout
        if rollout is not None:
            st = rollout.status()
            checks["rollout"] = {
                "ok": True,
                "stagePct": st["stagePct"],
                "verdict": st["verdict"],
                "candidateInstanceId": st["candidateInstanceId"],
            }
        checks.update(shedder_check(getattr(app, "transport", None)))
        return checks

    install_health_routes(app, readiness)
    # distributed tracing (pio_tpu/obs/): /debug/traces.json +
    # /debug/spans.json, and app.recorder switches the dispatch edge
    # into traced mode; app.tracer feeds the per-surface `request`
    # histogram
    from pio_tpu.obs.http import install_trace_routes

    app.tracer = server.tracer
    install_trace_routes(app, server.recorder, check_server_key)
    # guarded rollout verbs (pio_tpu/rollout/): /rollout/deploy,
    # /rollout/promote, /rollout/rollback (server-key guarded) +
    # /rollout/status
    install_rollout_routes(app, server, server.storage, check_server_key)

    @app.route("GET", r"/plugins\.json")
    def plugins_list(req: Request):
        return 200, {
            "plugins": {
                p.plugin_name: {"type": p.plugin_type}
                for p in server.plugins.plugins
            }
        }

    @app.route("GET", r"/plugins/([^/]+)(/.*)?")
    def plugin_rest(req: Request):
        name = req.path_args[0]
        plugin = server.plugins.get(name)
        if plugin is None:
            return 404, {"message": f"plugin {name} not found"}
        return 200, plugin.handle_rest(req.path_args[1] or "/", req.params)

    return app


def create_query_server(
    engine: Engine,
    engine_params: EngineParams,
    storage: Storage,
    config: ServingConfig,
    ctx: WorkflowContext | None = None,
    plugin_context: PluginContext | None = None,
    instance_id: str | None = None,
) -> tuple[HttpServer, QueryServer]:
    qs = QueryServer(
        engine, engine_params, storage, config,
        ctx=ctx, plugin_context=plugin_context, instance_id=instance_id,
    )
    from pio_tpu.server.security import server_ssl_context

    app = build_serving_app(qs)
    ssl_ctx = server_ssl_context(config.certfile, config.keyfile)
    if config.backend == "async":
        kwargs = {}
        if config.coalesce_window_ms > 0:
            # admission sized for coalescing: parked waiters are the
            # mechanism, not the overload — admit what one full batch per
            # pipeline slot (plus one forming) can absorb before the
            # LoadShedder starts answering 503 (SLO shedding rides the
            # same watermark as before, just sized to batch capacity)
            depth = config.batch_pipeline or _auto_pipeline_depth()
            kwargs["shed_watermark"] = max(
                128, config.batch_max * (depth + 1))
        http = AsyncHttpServer(
            app, host=config.ip, port=config.port, ssl_context=ssl_ctx,
            **kwargs)
    else:
        http = HttpServer(
            app, host=config.ip, port=config.port, ssl_context=ssl_ctx)
    return http, qs
