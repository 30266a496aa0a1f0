"""Persistent XLA compilation cache + serving bucket-shape registry.

Every fresh ``pio train`` process pays the full XLA compile of the
training programs before the first useful step, and a fresh ``pio
deploy`` pays one compile per micro-batch bucket. Both are pure
recomputation: the programs are byte-identical across runs. This module
kills that cold start twice over:

 * :func:`enable_compile_cache` turns on jax's persistent compilation
   cache, so the SECOND process deserializes executables instead of
   re-running XLA.  Keyed by HLO + compile options + jax/XLA version, so
   upgrades invalidate naturally — stale entries are never *wrong*, only
   unused; ``clear`` reclaims the space.
 * :class:`BucketRegistry` records which serving batch buckets a
   deployment actually compiled, persisted alongside the cache keyed by
   the engine triple — the next ``pio deploy`` pre-warms exactly that
   bucket set (each warm now a cache hit) instead of guessing a
   power-of-two sweep.

Where the cache lives is decided from OUTSIDE the program: jax reads
``JAX_COMPILATION_CACHE_DIR`` into its config at import, and when it is
set this module uses that directory and sets none of its own. Otherwise
the cache sits at one fixed path inside the checkout (``.jax_cache``
beside the package) — never a temp name: the path is part of the cache
key's lookup, so a directory that moves never hits.

Kill switch: ``PIO_TPU_COMPILE_CACHE=off`` (or ``0``/``false``/``no``).
"""

from __future__ import annotations

import json
import logging
import os
import threading

from pio_tpu.utils.tracing import ambient_tracer

log = logging.getLogger("pio_tpu.compilecache")

_OFF_VALUES = ("off", "0", "false", "no")
_CHECKOUT_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))),
    ".jax_cache")
_lock = threading.Lock()
_enabled = False


def default_cache_dir() -> str:
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or _CHECKOUT_CACHE


def cache_disabled() -> bool:
    return os.environ.get(
        "PIO_TPU_COMPILE_CACHE", "").lower() in _OFF_VALUES


def enable_compile_cache() -> str | None:
    """Turn on jax's persistent compilation cache at
    :func:`default_cache_dir`. Returns the directory, or None when
    disabled. Idempotent and thread-safe; safe to call after backend
    init (the cache config is read per compile). The
    min-compile-time/entry-size floors are dropped so even fast compiles
    persist — a training session compiles dozens of small programs whose
    sum, not max, is the warm-up."""
    global _enabled
    if cache_disabled():
        import jax

        # jax reads JAX_COMPILATION_CACHE_DIR by itself: off is off
        jax.config.update("jax_enable_compilation_cache", False)
        return None
    d = default_cache_dir()
    with _lock:
        if _enabled:
            return d
        import jax

        if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
            try:
                os.makedirs(d, exist_ok=True)
            except OSError as e:  # read-only checkout: run uncached
                log.warning("persistent compile cache unavailable: %s", e)
                return None
            jax.config.update("jax_compilation_cache_dir", d)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        _enabled = True
        log.info("persistent XLA compile cache at %s", d)
        return d


def cache_stats(cache_dir: str | None = None) -> dict:
    """{dir, entries, bytes} for the cache directory (entries = compiled
    executables, not atime sidecars or bucket registries)."""
    d = cache_dir or default_cache_dir()
    entries = 0
    size = 0
    try:
        for name in os.listdir(d):
            p = os.path.join(d, name)
            if not os.path.isfile(p):
                continue
            if name.endswith("-atime") or name.startswith("buckets__"):
                continue
            entries += 1
            try:
                size += os.path.getsize(p)
            except OSError:
                pass
    except OSError:
        pass
    return {"dir": d, "entries": entries, "bytes": size}


def clear_cache(cache_dir: str | None = None) -> int:
    """Delete every cache entry (and bucket registries); returns the
    number of files removed."""
    d = cache_dir or default_cache_dir()
    removed = 0
    try:
        names = os.listdir(d)
    except OSError:
        return 0
    for name in names:
        p = os.path.join(d, name)
        if os.path.isfile(p):
            try:
                os.remove(p)
                removed += 1
            except OSError:
                pass
    return removed


class _Phase:
    """One trace, lowering or backend call jax has begun on a thread."""

    __slots__ = ("event", "span", "labels", "cache")

    def __init__(self, event: str, span, labels):
        self.event = event
        self.span = span          # the entered span, or None
        self.labels = labels      # its label dict
        self.cache: dict = {}     # what the cache said inside a backend call


class _OpenPhases(threading.local):
    """A thread's stack of open phases (phases of one thread nest)."""

    def __init__(self):
        self.phases: list[_Phase] = []


class CompileMeter:
    """What this process spent getting programs ready, from jax's own
    monitoring events, counted once: seconds in trace + lowering + backend
    compile-or-cache-load, programs counted, persistent-cache hits among
    them. Listens from construction until :meth:`close` (or the end of its
    ``with`` block).

    jax calls its listeners at both ends of each phase (the scalar
    listener with the start time as the phase begins, the duration
    listener as it ends, both with ``fun_name``), and fires a trace event
    for EVERY jitted function it traces, the ones traced inside another
    trace too (an inner ``jax.jit``, every ``jnp`` function). So the meter
    keeps a stack of open phases a thread: only a phase begun with none
    open on its thread adds to ``seconds``, and only that one opens a span
    of the ambient tracer (``utils/tracing.py``), closed when jax says the
    phase ended. The span then has the parent that was open where the
    program was first called (``als.dispatch``, ``seq.init``, ...), a
    ``TraceAnnotation`` on a device tracer, and a row in ``train spans:``:

    * ``compile.trace``: Python tracing of the outermost function;
    * ``compile.lower``: jaxpr to MLIR module;
    * ``compile.backend``: ``compile_or_get_cached``, with ``cache`` =
      ``hit`` / ``miss`` / ``off`` (the cache was not asked) and, on a
      hit, ``retrieval_s`` (the read and deserialize) and ``saved_s``
      (what the compile took when the entry was written, less that);

    each with ``program`` = jax's ``fun_name``. A thread with no ambient
    tracer (a partition worker) opens no span; its seconds count.
    """

    _SPAN_OF = {
        "/jax/core/compile/jaxpr_trace_duration": "compile.trace",
        "/jax/core/compile/jaxpr_to_mlir_module_duration": "compile.lower",
        "/jax/core/compile/backend_compile_duration": "compile.backend",
    }
    _CACHE_SECONDS = {
        "/jax/compilation_cache/cache_retrieval_time_sec": "retrieval_s",
        "/jax/compilation_cache/compile_time_saved_sec": "saved_s",
    }

    def __init__(self):
        import jax

        self._monitoring = jax.monitoring
        self._lock = threading.Lock()
        self._open = _OpenPhases()
        self.seconds = 0.0
        self.programs = 0
        self.cache_hits = 0
        jax.monitoring.register_scalar_listener(self._on_begin)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_begin(self, event: str, _start: float, fun_name: str = "",
                  **_kw) -> None:
        name = self._SPAN_OF.get(event)
        if name is None:
            return
        phases = self._open.phases
        span = labels = None
        if not phases:
            tracer = ambient_tracer()
            if tracer is not None:
                span = tracer.span(name, program=fun_name)
                labels = span.__enter__()
        phases.append(_Phase(event, span, labels))

    def _on_duration(self, event: str, duration: float, **_kw) -> None:
        phases = self._open.phases
        if event in self._CACHE_SECONDS:
            if phases:
                phases[-1].cache[self._CACHE_SECONDS[event]] = duration
            return
        if event not in self._SPAN_OF:
            return
        backend = self._SPAN_OF[event] == "compile.backend"
        # a phase begun before the meter listened has no entry: it counts
        phase = (phases.pop() if phases and phases[-1].event == event
                 else None)
        with self._lock:
            if not phases:
                self.seconds += duration
            if backend:
                self.programs += 1
        if phase is None or phase.span is None:
            return
        if backend:
            said = phase.cache
            phase.labels["cache"] = (
                "hit" if "hit" in said else
                "miss" if "asked" in said else "off")
            for key in self._CACHE_SECONDS.values():
                if key in said:
                    phase.labels[key] = round(said[key], 4)
        phase.span.__exit__(None, None, None)

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            with self._lock:
                self.cache_hits += 1
            said = "hit"
        elif event == "/jax/compilation_cache/compile_requests_use_cache":
            said = "asked"
        else:
            return
        phases = self._open.phases
        if phases:
            phases[-1].cache[said] = True

    def totals(self) -> tuple[float, int, int]:
        """(seconds, programs, cache_hits) so far: a stage's share is the
        difference of two readings."""
        with self._lock:
            return self.seconds, self.programs, self.cache_hits

    def close(self) -> None:
        self._monitoring.unregister_scalar_listener(self._on_begin)
        self._monitoring.unregister_event_duration_listener(
            self._on_duration)
        self._monitoring.unregister_event_listener(self._on_event)

    def __enter__(self) -> "CompileMeter":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def __str__(self) -> str:
        return (f"{self.seconds:.2f}s over {self.programs} programs "
                f"({self.cache_hits} persistent-cache hits)")


# ---------------------------------------------------------------------------
# serving bucket-shape registry
# ---------------------------------------------------------------------------

class BucketRegistry:
    """Persisted set of micro-batch bucket sizes one engine's deployment
    actually served.  ``pio deploy`` pre-compiles exactly this set (plus
    bucket 1 for the single-query path) so a restart never pays a
    bucket-miss compile mid-traffic, and never wastes warm time on
    buckets the workload does not reach."""

    def __init__(self, engine_id: str, engine_version: str = "1",
                 engine_variant: str = "default",
                 cache_dir: str | None = None):
        d = cache_dir or default_cache_dir()
        safe = "__".join(
            s.replace("/", "_").replace("\\", "_") or "_"
            for s in (engine_id, engine_version, engine_variant)
        )
        self.path = os.path.join(d, f"buckets__{safe}.json")
        self._lock = threading.Lock()
        self._buckets: set[int] = set()
        self._dirty = False
        self._flush_timer: threading.Timer | None = None
        try:
            with open(self.path, encoding="utf-8") as f:
                data = json.load(f)
            self._buckets = {
                int(b) for b in data.get("buckets", []) if int(b) > 0
            }
        except (OSError, ValueError):
            pass

    def buckets(self) -> list[int]:
        with self._lock:
            return sorted(self._buckets)

    def record(self, bucket: int) -> None:
        """Note a served bucket size. The disk write is DEBOUNCED onto a
        background timer: record() sits on the serving hot path, and a
        synchronous write on first sighting measurably bends request
        p99 on small hosts. Durability is best-effort by design — the
        registry only tunes the NEXT deploy's warm sweep."""
        if bucket <= 0:
            return
        with self._lock:
            if bucket in self._buckets:
                return
            self._buckets.add(bucket)
            self._dirty = True
            if self._flush_timer is None:
                self._flush_timer = threading.Timer(1.0, self._flush_bg)
                self._flush_timer.daemon = True
                self._flush_timer.start()

    def _flush_bg(self) -> None:
        with self._lock:
            self._flush_timer = None
        self.flush()

    def flush(self) -> None:
        with self._lock:
            if not self._dirty:
                return
            payload = {"buckets": sorted(self._buckets)}
            self._dirty = False
        try:
            os.makedirs(os.path.dirname(self.path), exist_ok=True)
            tmp = self.path + ".tmp"
            with open(tmp, "w", encoding="utf-8") as f:
                json.dump(payload, f)
            os.replace(tmp, self.path)
        except OSError as e:
            log.warning("bucket registry write failed: %s", e)
