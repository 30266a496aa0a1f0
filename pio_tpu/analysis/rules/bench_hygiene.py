"""Benchmark-hygiene rules: timing that measures the wrong thing, and
per-event allocation in data-plane hot loops.

  * `bench-clock` — `time.time()` for duration measurement: the wall
    clock is not monotonic (NTP slews it mid-measurement) and has coarse
    resolution on some platforms; use time.perf_counter() (or
    time.monotonic() for deadlines).
  * `bench-no-sync` — a timed region that dispatches jax work but never
    forces completion (`jax.block_until_ready`, a scalar readback via
    `float()` / `.item()`, or `np.asarray`). jax dispatch is async: the
    stopwatch stops when the work is *enqueued*, not when it finishes,
    so the "measurement" is the dispatch overhead, which reads as a
    plausible, much too small time.
  * `hot-loop-alloc` — per-event `json.loads`/`Event(...)`/
    `Event.from_api_dict`/`DataMap.from_json` construction inside a
    `for`/`while` loop in the data plane (`pio_tpu/data/`,
    `pio_tpu/server/`): the row-at-a-time deserialization the columnar
    path (data/columnar.py) exists to eliminate: one Python object and
    one JSON parse an event where a batch decode makes arrays once. Use
    the columnar batch/decode APIs, or justify the row fallback with
    `# pio: lint-ok[hot-loop-alloc] <why>`.

Timed regions are matched structurally: `t = <clock>()` ... any later
statement in the same suite containing `<clock>() - t`. Helper calls are
resolved one level deep through module-local defs, so the repo idiom

    def go(): return float(jnp.sum(model.x))   # forces completion
    t0 = time.monotonic(); go(); dt = time.monotonic() - t0

counts as synced.
"""

from __future__ import annotations

import ast
from typing import Iterator

from pio_tpu.analysis.astutil import local_function_defs
from pio_tpu.analysis.engine import ModuleContext
from pio_tpu.analysis.findings import Finding, Severity

_CLOCKS = frozenset({
    "time.time", "time.monotonic", "time.perf_counter",
    "time.process_time",
})
_SYNC_ATTRS = frozenset({"block_until_ready", "item", "tolist"})
_SYNC_CALLS = frozenset({
    "jax.block_until_ready", "jax.device_get",
    "numpy.asarray", "numpy.array",
})
_SYNC_BUILTINS = frozenset({"float", "int", "bool"})
# jax APIs that are host-synchronous (no async dispatch to wait on):
# timing around these is legitimate — backend init, device enumeration,
# AOT lowering/compilation, and wrapper construction all complete before
# returning
_SYNCHRONOUS_JAX = frozenset({
    "jax.devices", "jax.local_devices", "jax.device_count",
    "jax.local_device_count", "jax.default_backend", "jax.process_index",
    "jax.jit", "jax.pjit", "jax.shard_map", "jax.config.update",
    "jax.ShapeDtypeStruct",
})


class BenchHygieneRule:
    id = "bench"
    ids = ("bench-clock", "bench-no-sync")

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        for node in ast.walk(ctx.tree):
            if (isinstance(node, ast.Call)
                    and ctx.imports.canonical(node.func) == "time.time"):
                yield Finding(
                    "bench-clock", Severity.WARNING, ctx.path,
                    node.lineno, node.col_offset,
                    "time.time() is wall-clock (NTP can slew it "
                    "mid-measurement); use time.perf_counter() for "
                    "timing, time.monotonic() for deadlines")
        if ctx.imports_any("jax"):
            defs = local_function_defs(ctx.tree)
            for fn in ast.walk(ctx.tree):
                if isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    yield from self._check_regions(ctx, fn, defs)

    # -- un-synced timed regions ---------------------------------------------
    def _check_regions(self, ctx: ModuleContext, fn: ast.AST,
                       defs: dict) -> Iterator[Finding]:
        for suite in self._suites(fn):
            starts: dict[str, int] = {}  # clock var -> stmt index
            for i, stmt in enumerate(suite):
                tvar = self._clock_assign(ctx, stmt)
                if tvar:
                    starts[tvar] = i
                    continue
                for tvar2 in self._clock_reads(ctx, stmt, set(starts)):
                    region = suite[starts[tvar2] + 1: i] + [stmt]
                    if (self._has_jax_call(ctx, region, defs, depth=2)
                            and not self._has_sync(ctx, region, defs,
                                                   depth=2)):
                        yield Finding(
                            "bench-no-sync", Severity.WARNING, ctx.path,
                            stmt.lineno, stmt.col_offset,
                            "timed region dispatches jax work but never "
                            "syncs (jax.block_until_ready or a scalar "
                            "readback): async dispatch means this "
                            "measures enqueue time, not execution time")
                    del starts[tvar2]

    @staticmethod
    def _suites(fn: ast.AST):
        """Every statement list in the function (body, loop/with/if
        bodies), so `t0 = clock()` and its read match within one suite."""
        for node in ast.walk(fn):
            for attr in ("body", "orelse", "finalbody"):
                suite = getattr(node, attr, None)
                if isinstance(suite, list) and suite \
                        and isinstance(suite[0], ast.stmt):
                    yield suite

    def _clock_assign(self, ctx: ModuleContext, stmt: ast.stmt) -> str | None:
        if (isinstance(stmt, ast.Assign) and len(stmt.targets) == 1
                and isinstance(stmt.targets[0], ast.Name)
                and isinstance(stmt.value, ast.Call)
                and ctx.imports.canonical(stmt.value.func) in _CLOCKS):
            return stmt.targets[0].id
        return None

    def _clock_reads(self, ctx: ModuleContext, stmt: ast.stmt,
                     tvars: set[str]) -> list[str]:
        """tvars read as `<clock>() - tvar` anywhere inside stmt."""
        out = []
        for node in ast.walk(stmt):
            if (isinstance(node, ast.BinOp) and isinstance(node.op, ast.Sub)
                    and isinstance(node.right, ast.Name)
                    and node.right.id in tvars
                    and isinstance(node.left, ast.Call)
                    and ctx.imports.canonical(node.left.func) in _CLOCKS):
                out.append(node.right.id)
        return out

    def _has_jax_call(self, ctx, region, defs, depth: int) -> bool:
        return self._scan(ctx, region, defs, depth, self._is_jax_call)

    def _has_sync(self, ctx, region, defs, depth: int) -> bool:
        return self._scan(ctx, region, defs, depth, self._is_sync_call)

    def _scan(self, ctx, region, defs, depth, pred) -> bool:
        for stmt in region:
            for node in ast.walk(stmt):
                if not isinstance(node, ast.Call):
                    continue
                if pred(ctx, node):
                    return True
                # one-level helper resolution: go() defined locally
                if depth > 0 and isinstance(node.func, ast.Name):
                    for helper in defs.get(node.func.id, []):
                        if self._scan(ctx, helper.body, defs, depth - 1,
                                      pred):
                            return True
        return False

    @staticmethod
    def _is_jax_call(ctx: ModuleContext, node: ast.Call) -> bool:
        name = ctx.imports.canonical(node.func) or ""
        if name in _SYNCHRONOUS_JAX:
            return False
        return name == "jax" or name.startswith("jax.")

    @staticmethod
    def _is_sync_call(ctx: ModuleContext, node: ast.Call) -> bool:
        name = ctx.imports.canonical(node.func)
        if name in _SYNC_CALLS:
            return True
        if name in _SYNC_BUILTINS:
            return True
        return (isinstance(node.func, ast.Attribute)
                and node.func.attr in _SYNC_ATTRS)


# per-event constructors the data plane must not run row-at-a-time
_HOT_ALLOC_CALLS = frozenset({
    "json.loads",
    "pio_tpu.data.event.Event",
    "pio_tpu.data.event.Event.from_api_dict",
    "pio_tpu.data.event.Event.from_json",
    "pio_tpu.data.datamap.DataMap.from_json",
    "pio_tpu.data.backends.wire.event_from_wire",
})
# data-plane path fragments the rule applies to (normalized separators)
_HOT_PATHS = ("pio_tpu/data/", "pio_tpu/server/")

# ops scope: array materialization inside a PYTHON loop. Every
# iteration of an un-jitted host loop re-traces and re-materializes a
# device buffer (and inside a jitted function an unrolled python loop
# emits one buffer PER ITERATION into the HLO — compile-time and
# live-range bloat the als group chaining is carefully structured to
# avoid); hot-path loops over groups/chunks must hoist the allocation
# or vectorize it. The kernel-adjacent helpers that intentionally
# allocate per group carry `# pio: lint-ok[hot-loop-alloc]`
# justifications.
_TRACE_ALLOC_CALLS = frozenset({
    "jax.numpy.zeros", "jax.numpy.ones", "jax.numpy.full",
    "jax.numpy.eye", "jax.numpy.arange", "jax.numpy.linspace",
    "jax.numpy.concatenate", "jax.numpy.stack", "jax.numpy.asarray",
    "jax.numpy.array",
    "numpy.zeros", "numpy.ones", "numpy.full", "numpy.concatenate",
    "jax.device_put",
})
_OPS_PATHS = ("pio_tpu/ops/",)


class HotLoopAllocRule:
    """`hot-loop-alloc`: flag per-iteration allocation inside explicit
    `for`/`while` loops on hot paths. Two scopes, one id:

      * data plane (`pio_tpu/data/`, `pio_tpu/server/`): per-event
        decode/construction (`json.loads`, `Event(...)`, ...) — the
        row-at-a-time cost the columnar path removes;
      * ops layer (`pio_tpu/ops/`): array materialization
        (`jnp.zeros`, `jnp.concatenate`, `device_put`, ...) — each
        python-loop iteration re-traces an allocation XLA materializes
        per call (kernel group loops must thread aliased buffers, not
        allocate fresh ones).

    Scoped by path so engine templates, tests, and tools keep their
    readable loops; in scope every finding is either fixed or carries a
    `# pio: lint-ok[hot-loop-alloc] <why>` justification."""

    id = "bench"
    ids = ("hot-loop-alloc",)

    def check(self, ctx: ModuleContext):
        path = ctx.path.replace("\\", "/")
        if any(p in path for p in _HOT_PATHS):
            calls, msg = _HOT_ALLOC_CALLS, self._data_msg
        elif any(p in path for p in _OPS_PATHS):
            calls, msg = _TRACE_ALLOC_CALLS, self._ops_msg
        else:
            return
        seen: set[tuple[int, int]] = set()  # nested loops: flag once
        for loop in ast.walk(ctx.tree):
            if not isinstance(loop, (ast.For, ast.AsyncFor, ast.While)):
                continue
            for node in ast.walk(loop):
                if not isinstance(node, ast.Call):
                    continue
                if (node.lineno, node.col_offset) in seen:
                    continue
                name = ctx.imports.canonical(node.func)
                if name not in calls:
                    continue
                seen.add((node.lineno, node.col_offset))
                yield Finding(
                    "hot-loop-alloc", Severity.WARNING, ctx.path,
                    node.lineno, node.col_offset, msg(name))

    @staticmethod
    def _data_msg(name: str) -> str:
        short = name.rsplit(".", 2)[-1] if name != "json.loads" \
            else "json.loads"
        return (
            f"per-event {short}() inside a data-plane loop: "
            "row-at-a-time deserialization is the ingest/training "
            "bottleneck the columnar path removes — use "
            "data/columnar.py (decode_api_batch / find_columnar "
            "/ insert_batch), or justify the row fallback with "
            "# pio: lint-ok[hot-loop-alloc]")

    @staticmethod
    def _ops_msg(name: str) -> str:
        return (
            f"{name.rsplit('.', 1)[-1]}() materializes an array inside "
            "a Python loop in the ops layer: each iteration re-traces "
            "an allocation (unrolled into the HLO under jit) — hoist "
            "it out of the loop, vectorize, or justify with "
            "# pio: lint-ok[hot-loop-alloc]")
