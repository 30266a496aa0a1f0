"""`pio lint` (pio_tpu/analysis/): per-family positive/negative fixtures,
suppression-comment handling, CLI wiring, and a repo-wide smoke test.

Every rule family gets at least one known-bad snippet that must fire and
one known-good snippet that must stay silent — the analyzer's own
contract (ISSUE 1 acceptance criteria).
"""

import textwrap

import pytest

from pio_tpu.analysis import ProjectInfo, Severity, lint_text, run_lint


def lint(src: str, select=None, project=None):
    return lint_text(textwrap.dedent(src), select=select, project=project)


def rules_of(findings):
    return {f.rule for f in findings}


# -- trace purity -----------------------------------------------------------

def test_trace_item_and_print_fire():
    fs = lint("""
        import jax

        @jax.jit
        def step(x):
            print("step", x)
            return x.item()
    """)
    assert "trace-print" in rules_of(fs)
    assert "trace-host-sync" in rules_of(fs)


def test_trace_clock_rng_global_fire():
    fs = lint("""
        import time
        import jax
        import numpy as np

        COUNT = 0

        @jax.jit
        def step(x):
            global COUNT
            COUNT = COUNT + 1
            t = time.time()
            np.random.seed(0)
            return x * t
    """)
    assert {"trace-clock", "trace-rng", "trace-global"} <= rules_of(fs)


def test_trace_partial_jit_and_wrapped_fn_detected():
    fs = lint("""
        from functools import partial
        import jax

        @partial(jax.jit, static_argnames=("n",))
        def decorated(x, n):
            return float(x)

        def wrapped(x):
            return x.item()

        wrapped_jit = jax.jit(wrapped)
    """)
    assert len([f for f in fs if f.rule == "trace-host-sync"]) == 2


def test_trace_shard_map_detected():
    fs = lint("""
        from functools import partial
        import jax

        @partial(jax.shard_map, mesh=None, in_specs=(), out_specs=())
        def run(x):
            return x.item()
    """)
    assert "trace-host-sync" in rules_of(fs)


def test_trace_clean_function_silent():
    fs = lint("""
        import jax
        import jax.numpy as jnp

        @jax.jit
        def step(x):
            y = jnp.sum(x * 2)
            return jnp.sqrt(y)

        def host_side(x):
            # host code may read back freely: not traced
            return float(jnp.sum(x)), x.item()
    """)
    assert fs == []


# -- shard spec -------------------------------------------------------------

def test_shard_axis_typo_fires():
    fs = lint("""
        from jax.sharding import PartitionSpec as P

        spec = P("bath", None)
    """)
    assert rules_of(fs) == {"shard-axis"}
    assert "'bath'" in fs[0].message


def test_shard_known_axes_and_unresolvable_silent():
    fs = lint("""
        from jax.sharding import PartitionSpec as P

        def make(axis_name):
            return P("data", ("seq", "model"), None, axis_name)
    """)
    assert fs == []


def test_collective_axis_fires_and_mesh_constants_pass():
    fs = lint("""
        import jax
        from pio_tpu.parallel.mesh import DATA_AXIS

        def f(x):
            good = jax.lax.psum(x, DATA_AXIS)
            also = jax.lax.all_gather(x, "data", tiled=True)
            bad = jax.lax.psum(x, "dp")
            return good + also + bad
    """)
    assert [f.rule for f in fs] == ["collective-axis"]
    assert "'dp'" in fs[0].message


def test_custom_mesh_vocabulary_respected():
    project = ProjectInfo(mesh_axes=frozenset({"x", "y"}))
    fs = lint("""
        from jax.sharding import PartitionSpec as P

        a = P("x")
        b = P("data")
    """, project=project)
    assert [f.rule for f in fs] == ["shard-axis"]
    assert "'data'" in fs[0].message


def test_donate_hint_info():
    fs = lint("""
        import jax

        @jax.jit
        def update(table, idx, val):
            table = table.at[idx].set(val)
            return table
    """)
    hints = [f for f in fs if f.rule == "donate-hint"]
    assert len(hints) == 1
    assert hints[0].severity == Severity.INFO


def test_donate_hint_silent_when_donated():
    fs = lint("""
        from functools import partial
        import jax

        @partial(jax.jit, donate_argnums=(0,))
        def update(table, idx, val):
            table = table.at[idx].set(val)
            return table
    """)
    assert [f for f in fs if f.rule == "donate-hint"] == []


# -- concurrency ------------------------------------------------------------

def test_unlocked_counter_fires():
    fs = lint("""
        import threading

        class Handler:
            def __init__(self):
                self.count = 0
                self.rows = []

            def handle(self, req):
                self.count += 1
                self.rows.append(req)
    """)
    assert [f.rule for f in fs] == ["attr-no-lock", "attr-no-lock"]


def test_locked_counter_silent():
    fs = lint("""
        import threading

        class Handler:
            def __init__(self):
                self._lock = threading.Lock()
                self.count = 0

            def handle(self, req):
                with self._lock:
                    self.count += 1
    """)
    assert fs == []


def test_init_and_async_and_no_threading_exempt():
    fs = lint("""
        import asyncio

        class Conn:
            def __init__(self):
                self.tasks = set()
                self.n = 0
                self.n += 1          # __init__ is single-threaded

            async def handle(self, task):
                self.tasks.add(task)  # event-loop-confined
    """)
    assert fs == []
    fs2 = lint("""
        class Script:
            def bump(self):
                self.n += 1  # no threading import: not a shared object
    """)
    assert fs2 == []


def test_module_global_write_fires():
    fs = lint("""
        import threading

        _cache = None

        def get():
            global _cache
            _cache = compute()
            return _cache
    """)
    assert [f.rule for f in fs] == ["global-no-lock"]


def test_module_mutable_append_fires_and_locked_silent():
    fs = lint("""
        import threading

        REGISTRY = []
        _lock = threading.Lock()

        def register(x):
            REGISTRY.append(x)

        def register_safe(x):
            with _lock:
                REGISTRY.append(x)
    """)
    assert [f.rule for f in fs] == ["global-no-lock"]
    assert fs[0].line == 8  # the unlocked append, not the locked one


def test_blocking_call_in_async_fires():
    fs = lint("""
        import time
        import urllib.request

        async def handler(req):
            time.sleep(0.1)
            urllib.request.urlopen("http://x")
    """)
    assert [f.rule for f in fs] == ["async-blocking", "async-blocking"]


def test_async_with_executor_silent():
    fs = lint("""
        import asyncio

        async def handler(pool, req):
            return await asyncio.get_running_loop().run_in_executor(
                pool, work, req)
    """)
    assert fs == []


def test_bare_retry_loop_fires():
    fs = lint("""
        import time
        import urllib.request

        def fetch(url):
            for attempt in range(3):
                try:
                    return urllib.request.urlopen(url)
                except OSError:
                    time.sleep(1.0)
    """)
    assert [f.rule for f in fs] == ["bare-retry"]
    assert "RetryPolicy" in fs[0].message


def test_bare_retry_innermost_loop_only():
    fs = lint("""
        import time

        def sweep(urls):
            for url in urls:
                while True:
                    try:
                        return fetch(url)
                    except ConnectionError:
                        time.sleep(0.5)
    """)
    assert [f.rule for f in fs] == ["bare-retry"]
    assert fs[0].line == 6  # the while (retry), not the for (iteration)


def test_bare_retry_not_exempted_by_lookalike_names():
    # exact-identifier exemption: `max_attempts` must NOT read as a
    # RetryPolicy schedule (regression: substring matching exempted it)
    fs = lint("""
        import time
        import urllib.request

        def fetch(url, max_attempts=3):
            for attempt in range(max_attempts):
                try:
                    return urllib.request.urlopen(url)
                except OSError:
                    time.sleep(1.0)
    """, select=["bare-retry"])
    assert [f.rule for f in fs] == ["bare-retry"]


def test_policy_driven_retry_loop_silent():
    fs = lint("""
        import asyncio
        from pio_tpu.resilience import RetryPolicy

        async def bind(make):
            delays = list(RetryPolicy(attempts=3).delays())
            for attempt in range(len(delays) + 1):
                try:
                    return make()
                except OSError:
                    await asyncio.sleep(delays[attempt])
    """, select=["bare-retry"])
    assert fs == []


def test_sleep_without_transport_handler_silent():
    fs = lint("""
        import time

        def poll(q):
            while True:
                item = q.get_nowait()
                if item is None:
                    time.sleep(0.1)
    """, select=["bare-retry"])
    assert fs == []


def test_durable_write_model_artifact_fires():
    fs = lint("""
        def save(root, blob):
            with open(root + "/pio_model_x.bin", "wb") as f:
                f.write(blob)
    """, select=["durable-write"])
    assert [f.rule for f in fs] == ["durable-write"]


def test_durable_write_checkpoint_mode_kw_fires():
    fs = lint("""
        def save(checkpoint_path, blob):
            f = open(checkpoint_path, mode="ab")
            f.write(blob)
    """, select=["durable-write"])
    assert [f.rule for f in fs] == ["durable-write"]


def test_durable_write_non_artifact_and_text_silent():
    fs = lint("""
        def save(path, blob, model):
            with open(path + "/notes.bin", "wb") as f:   # not an artifact
                f.write(blob)
            with open(path + "/model.json", "w") as f:   # text mode
                f.write("{}")
            with open(path + "/model.bin", "rb") as f:   # read
                return f.read()
    """, select=["durable-write"])
    assert fs == []


def test_durable_write_suppressible():
    fs = lint("""
        def save(path, blob):
            # pio: lint-ok[durable-write] scratch checkpoint, torn ok
            with open(path + "/ckpt.tmp", "wb") as f:
                f.write(blob)
    """, select=["durable-write"])
    assert fs == []


def test_foldin_cursor_any_write_in_freshness_fires():
    from pio_tpu.analysis import lint_text
    src = """
        import json
        import pickle

        def save(path, cursor):
            with open(path, "w") as f:        # text write: still flagged
                json.dump(cursor, f)
            open(path + ".bak", mode="wb").write(b"x")
            pickle.dump(cursor, open(path, "r+b"))
    """
    fs = lint_text(textwrap.dedent(src),
                   path="pio_tpu/freshness/cursor.py",
                   select=["foldin-cursor"])
    # open("w"), json.dump, open("wb"), pickle.dump, open("r+b")
    assert [f.rule for f in fs] == ["foldin-cursor"] * 5
    # identical code OUTSIDE the freshness package is out of scope
    assert lint_text(textwrap.dedent(src),
                     path="pio_tpu/workflow/cursor.py",
                     select=["foldin-cursor"]) == []


def test_foldin_cursor_durable_and_reads_silent():
    from pio_tpu.analysis import lint_text
    src = """
        from pio_tpu.utils.durable import durable_read, durable_write

        def save(path, cursor_json):
            durable_write(path, cursor_json.encode("utf-8"))

        def load(path):
            with open(path, "rb") as f:      # plain read: fine
                f.read()
            return durable_read(path)
    """
    assert lint_text(textwrap.dedent(src),
                     path="pio_tpu/freshness/cursor.py",
                     select=["foldin-cursor"]) == []


def test_hint_log_any_write_in_replicated_backend_fires():
    from pio_tpu.analysis import lint_text
    src = """
        import json

        def stash_hint(path, rec):
            with open(path, "ab") as f:       # raw append: flagged
                f.write(rec)
            json.dump(rec, open(path + ".json", "w"))
    """
    fs = lint_text(textwrap.dedent(src),
                   path="pio_tpu/data/backends/replicated.py",
                   select=["hint-log"])
    # open("ab"), open("w"), json.dump
    assert [f.rule for f in fs] == ["hint-log"] * 3
    # identical code in any OTHER backend is out of scope
    assert lint_text(textwrap.dedent(src),
                     path="pio_tpu/data/backends/memory.py",
                     select=["hint-log"]) == []


def test_hint_log_framelog_and_reads_silent():
    from pio_tpu.analysis import lint_text
    src = """
        from pio_tpu.utils.durable import FrameLog, durable_write

        def stash_hint(log: FrameLog, rec: bytes, state_path, state):
            log.append(rec)                   # the sanctioned append
            durable_write(state_path, state)  # the sanctioned blob

        def load(path):
            with open(path, "rb") as f:       # plain read: fine
                return f.read()
    """
    assert lint_text(textwrap.dedent(src),
                     path="pio_tpu/data/backends/replicated.py",
                     select=["hint-log"]) == []


def test_rollout_state_write_outside_transition_fires():
    from pio_tpu.analysis import lint_text
    src = """
        import json

        class Controller:
            def __init__(self):
                self.stage_index = 0          # construction: allowed
                self.verdict = None

            def _transition(self, verdict):
                self.verdict = verdict        # the sanctioned writer

            def hack(self):
                self.verdict = "PROMOTED"     # bypasses lock + persist
                self.stage_index += 1
                self.stage_pct = 100

            def persist(self, path, record):
                with open(path, "w") as f:    # bypasses utils/durable
                    json.dump(record, f)
    """
    fs = lint_text(textwrap.dedent(src),
                   path="pio_tpu/rollout/controller.py",
                   select=["rollout-state"])
    # verdict, stage_index +=, stage_pct, open("w"), json.dump
    assert [f.rule for f in fs] == ["rollout-state"] * 5
    # identical code OUTSIDE the rollout package is out of scope
    assert lint_text(textwrap.dedent(src),
                     path="pio_tpu/workflow/controller.py",
                     select=["rollout-state"]) == []


def test_rollout_state_transition_and_reads_silent():
    from pio_tpu.analysis import lint_text
    src = """
        from pio_tpu.rollout import state as rstate

        class Controller:
            def __init__(self):
                self.stage_index = 0
                self.verdict = None

            def _transition(self, stage_index=None, verdict=None):
                if stage_index is not None:
                    self.stage_index = stage_index
                if verdict is not None:
                    self.verdict = verdict
                rstate.save_record(self.storage, self._record())

            def status(self):
                return {"verdict": self.verdict,
                        "stage": self.stage_index}
    """
    assert lint_text(textwrap.dedent(src),
                     path="pio_tpu/rollout/controller.py",
                     select=["rollout-state"]) == []


# -- obs: outbound HTTP must ride utils/httpclient ---------------------------

def test_raw_http_fires_in_pio_tpu():
    from pio_tpu.analysis import lint_text
    src = """
        import urllib.request
        from http.client import HTTPConnection
        import requests

        def poll(url):
            with urllib.request.urlopen(url, timeout=2):
                pass
            HTTPConnection("host", 80)
            requests.get(url)
    """
    fs = lint_text(textwrap.dedent(src),
                   path="pio_tpu/tools/poller.py", select=["raw-http"])
    assert [f.rule for f in fs] == ["raw-http"] * 3
    # the same code OUTSIDE pio_tpu/ (tests, bench drivers) is exempt:
    # raw clients there measure the servers from outside the topology
    assert lint_text(textwrap.dedent(src),
                     path="tests/test_poller.py",
                     select=["raw-http"]) == []


def test_raw_http_sanctioned_client_and_parse_silent():
    from pio_tpu.analysis import lint_text
    src = """
        import urllib.parse
        from pio_tpu.utils.httpclient import JsonHttpClient

        def call(base, path, params):
            qs = urllib.parse.urlencode(params)   # parsing: not a request
            return JsonHttpClient(base).request("GET", path + "?" + qs)
    """
    assert lint_text(textwrap.dedent(src),
                     path="pio_tpu/tools/caller.py",
                     select=["raw-http"]) == []


# -- bench hygiene ----------------------------------------------------------

def test_time_time_fires():
    fs = lint("""
        import time

        def measure():
            t0 = time.time()
            work()
            return time.time() - t0
    """)
    assert rules_of(fs) == {"bench-clock"}


def test_unsynced_jax_timing_fires():
    fs = lint("""
        import time
        import jax.numpy as jnp

        def measure(x):
            t0 = time.perf_counter()
            y = jnp.dot(x, x)
            return time.perf_counter() - t0
    """)
    assert "bench-no-sync" in rules_of(fs)


def test_synced_jax_timing_silent():
    fs = lint("""
        import time
        import jax
        import jax.numpy as jnp

        def measure(x):
            t0 = time.perf_counter()
            jax.block_until_ready(jnp.dot(x, x))
            return time.perf_counter() - t0

        def measure_via_readback(x):
            t0 = time.perf_counter()
            v = float(jnp.sum(jnp.dot(x, x)))
            return time.perf_counter() - t0
    """)
    assert fs == []


def test_sync_through_local_helper_recognized():
    fs = lint("""
        import time
        import jax.numpy as jnp

        def measure(x):
            def go():
                return float(jnp.sum(jnp.dot(x, x)))

            go()  # compile
            t0 = time.perf_counter()
            go()
            return time.perf_counter() - t0
    """)
    assert fs == []


def test_hot_loop_alloc_fires_in_data_plane():
    src = """
        import json
        from pio_tpu.data.event import Event

        def decode_rows(rows):
            out = []
            for r in rows:
                out.append(Event.from_api_dict(json.loads(r)))
            return out
    """
    fs = lint_text(textwrap.dedent(src), path="pio_tpu/data/backends/x.py")
    assert {f.rule for f in fs} == {"hot-loop-alloc"}
    # json.loads AND the Event decode each flagged, but once per call
    # site (nested loops must not double-report)
    assert len(fs) == 2


def test_hot_loop_alloc_scoped_to_data_plane_paths():
    src = """
        import json

        def parse_all(rows):
            out = []
            for r in rows:
                out.append(json.loads(r))
            return out
    """
    # engine templates / tools / tests keep their row loops
    assert lint_text(textwrap.dedent(src), path="pio_tpu/models/x.py") == []
    assert lint_text(textwrap.dedent(src), path="tests/test_x.py") == []
    assert lint_text(textwrap.dedent(src), path="pio_tpu/server/x.py") != []


def test_hot_loop_alloc_silent_outside_loops_and_suppressible():
    ok = """
        import json
        from pio_tpu.data.event import Event

        def decode_one(raw):
            return Event.from_api_dict(json.loads(raw))
    """
    assert lint_text(textwrap.dedent(ok), path="pio_tpu/data/x.py") == []
    suppressed = """
        import json

        def fallback(rows):
            for r in rows:
                # pio: lint-ok[hot-loop-alloc] documented row fallback
                yield json.loads(r)
    """
    assert lint_text(
        textwrap.dedent(suppressed), path="pio_tpu/data/x.py") == []


def test_hot_loop_alloc_ops_scope_flags_array_materialization():
    src = """
        import jax.numpy as jnp

        def fold_groups(groups, k):
            acc = None
            for g in groups:
                buf = jnp.zeros((g, k, k))
                acc = buf if acc is None else acc + buf
            return acc
    """
    fs = lint_text(textwrap.dedent(src), path="pio_tpu/ops/x.py")
    assert {f.rule for f in fs} == {"hot-loop-alloc"}
    assert "materializes an array" in fs[0].message
    # models/, eval/, tests keep their readable loops
    assert lint_text(textwrap.dedent(src), path="pio_tpu/models/x.py") == []
    # and the data-plane call set does NOT apply in ops (json decode in
    # an ops tool loop is not a columnar-path regression)
    ops_json = """
        import json

        def parse(rows):
            return [json.loads(r) for r in rows]
    """
    assert lint_text(textwrap.dedent(ops_json), path="pio_tpu/ops/x.py") == []


def test_hot_loop_alloc_ops_scope_hoisted_and_suppressed_ok():
    hoisted = """
        import jax.numpy as jnp

        def fold_groups(groups, k):
            acc = jnp.zeros((128, k, k))
            for g in groups:
                acc = acc + g
            return acc
    """
    assert lint_text(textwrap.dedent(hoisted), path="pio_tpu/ops/x.py") == []
    suppressed = """
        import jax.numpy as jnp

        def trails(parts):
            out = []
            for p in parts:
                # pio: lint-ok[hot-loop-alloc] one tiny trail per group
                out.append(jnp.asarray(p))
            return out
    """
    assert lint_text(
        textwrap.dedent(suppressed), path="pio_tpu/ops/x.py") == []


def test_non_jax_timing_silent():
    fs = lint("""
        import time

        def measure():
            t0 = time.perf_counter()
            rows = fetch_http()
            return time.perf_counter() - t0
    """)
    assert fs == []


# -- workflow contracts -----------------------------------------------------

def test_missing_dase_methods_fire():
    fs = lint("""
        from pio_tpu.controller.base import PAlgorithm, Serving

        class MyAlgo(PAlgorithm):
            def train(self, ctx, pd):
                return pd
            # predict missing

        class MyServing(Serving):
            pass
    """)
    assert [f.rule for f in fs] == ["dase-contract", "dase-contract"]
    assert "'predict'" in fs[0].message
    assert "'serve'" in fs[1].message


def test_complete_dase_class_silent():
    fs = lint("""
        from pio_tpu.controller.base import DataSource, LAlgorithm

        class MySource(DataSource):
            def read_training(self, ctx):
                return []

        class MyAlgo(LAlgorithm):
            def train(self, ctx, pd):
                return pd

            def predict(self, model, query):
                return {}
    """)
    assert fs == []


def test_abstract_intermediate_exempt_but_leaf_checked():
    fs = lint("""
        import abc
        from pio_tpu.controller.base import Algorithm

        class SharedBase(Algorithm):
            def train(self, ctx, pd):
                return pd

        class Leaf(SharedBase):
            pass
    """)
    # SharedBase contains "Base" -> exempt; Leaf still owes predict
    assert [f.rule for f in fs] == ["dase-contract"]
    assert fs[0].message.startswith("class 'Leaf'")


# -- wire-codec (DASE-contracts family) -------------------------------------

def test_wire_codec_packing_outside_codec_fires():
    from pio_tpu.analysis import lint_text
    src = """
        import struct
        import numpy as np

        def handle(req):
            head = struct.pack("<I", 7)        # a second codec sprouting
            rows = np.frombuffer(req.body, "<i4", 10, 4)
            return head + rows.tobytes()
    """
    fs = lint_text(textwrap.dedent(src),
                   path="pio_tpu/server/someroute.py",
                   select=["wire-codec"])
    assert [f.rule for f in fs] == ["wire-codec"] * 3
    assert "ONE codec" in fs[0].message
    # the same code outside pio_tpu/ (tests bit-flipping frames, bench
    # drivers) is exempt
    assert lint_text(textwrap.dedent(src),
                     path="tests/test_frames.py",
                     select=["wire-codec"]) == []


def test_wire_codec_owner_modules_and_suppression_silent():
    from pio_tpu.analysis import lint_text
    src = """
        import struct

        HEAD = struct.Struct("<HHIIQQ")

        def pack(n):
            return HEAD.pack(1, 0, n, 0, 0, 0)
    """
    # the codec module itself (and every sanctioned protocol owner) is
    # exactly where this packing belongs — incl. the fleet's binary
    # shard-RPC wire (serving_fleet/rpcwire.py, ISSUE 15)
    for owner in ("pio_tpu/data/columnar.py", "pio_tpu/utils/durable.py",
                  "pio_tpu/data/backends/pgwire.py",
                  "pio_tpu/serving_fleet/rpcwire.py"):
        assert lint_text(textwrap.dedent(src), path=owner,
                         select=["wire-codec"]) == []
    suppressed = """
        import struct

        def read_tomb(blob):
            # pio: lint-ok[wire-codec] reads the record codec's own file
            return struct.unpack_from("<H", blob, 0)
    """
    assert lint_text(textwrap.dedent(suppressed),
                     path="pio_tpu/data/backends/x.py",
                     select=["wire-codec"]) == []


# -- suppressions -----------------------------------------------------------

def test_suppression_same_line_and_block_above():
    fs = lint("""
        import threading

        class H:
            def inc(self):
                self.n += 1  # pio: lint-ok[attr-no-lock] metrics-only

            def dec(self):
                # pio: lint-ok[attr-no-lock] single writer thread,
                # documented in the ops runbook
                self.n -= 1

            def raw(self):
                self.n += 1
    """)
    assert len(fs) == 1
    assert fs[0].line == 14


def test_suppression_wrong_rule_does_not_apply():
    fs = lint("""
        import threading

        class H:
            def inc(self):
                self.n += 1  # pio: lint-ok[bench-clock] wrong id
    """)
    assert [f.rule for f in fs] == ["attr-no-lock"]


def test_star_suppression():
    fs = lint("""
        import time

        def f():
            t = time.time()  # pio: lint-ok[*]
            return t
    """)
    assert fs == []


# -- engine / CLI / repo smoke ---------------------------------------------

def test_select_filters_families():
    src = """
        import time
        import jax

        @jax.jit
        def step(x):
            return x.item()

        def measure():
            t0 = time.time()
            return time.time() - t0
    """
    assert rules_of(lint(src, select={"trace"})) == {"trace-host-sync"}
    assert rules_of(lint(src, select={"bench"})) == {"bench-clock"}


def test_select_and_ignore_by_concrete_finding_id(tmp_path):
    src = (
        "import jax\n"
        "from functools import partial\n\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    print(x)\n"
        "    return x.item()\n\n"
        "@jax.jit\n"
        "def g(table, i, v):\n"
        "    table = table.at[i].set(v)\n"
        "    return table\n"
    )
    (tmp_path / "m.py").write_text(src)
    # selecting a concrete id narrows to exactly that finding
    r = run_lint([str(tmp_path)], select={"trace-host-sync"})
    assert [f.rule for f in r.findings] == ["trace-host-sync"]
    # ignoring one id must not silence its family-mates
    r = run_lint([str(tmp_path)], ignore={"donate-hint"})
    rules = [f.rule for f in r.findings]
    assert "donate-hint" not in rules
    assert "trace-print" in rules and "trace-host-sync" in rules
    # family ignore still drops the whole family
    r = run_lint([str(tmp_path)], ignore={"trace"})
    assert [f.rule for f in r.findings] == ["donate-hint"]


def test_run_lint_on_files(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text(
        "import jax\n\n@jax.jit\ndef f(x):\n    return x.item()\n")
    good = tmp_path / "good.py"
    good.write_text("x = 1\n")
    report = run_lint([str(tmp_path)])
    assert report.n_files == 2
    assert report.exit_code == 1
    assert [f.rule for f in report.findings] == ["trace-host-sync"]


def test_syntax_error_reported_not_raised(tmp_path):
    f = tmp_path / "broken.py"
    f.write_text("def f(:\n")
    report = run_lint([str(tmp_path)])
    assert [x.rule for x in report.findings] == ["parse-error"]
    assert report.exit_code == 1


def test_cli_lint_verb(tmp_path, capsys):
    from pio_tpu.tools.cli import main

    bad = tmp_path / "bad.py"
    bad.write_text("import time\nt0 = time.time()\n")
    assert main(["lint", str(tmp_path)]) == 1
    out = capsys.readouterr().out
    assert "bench-clock" in out
    (tmp_path / "bad.py").write_text("x = 1\n")
    assert main(["lint", str(tmp_path)]) == 0


LINTED_TREES = ("pio_tpu", "tests", "eval", "examples")


@pytest.mark.parametrize("tree", LINTED_TREES)
def test_repo_lints_clean(tree):
    """The analyzer's own acceptance bar: zero unsuppressed findings on
    the tree it ships in (ISSUE 1), one case for each tree `make lint`
    names."""
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    report = run_lint([os.path.join(root, tree)])
    assert report.failing == [], "\n".join(
        f.format() for f in report.failing)


def test_make_lint_names_the_trees_the_test_lints():
    """`make lint` (what CI's lint job runs) and the test above name the
    same trees: a tree added to one and not the other is linted in one
    place only."""
    import os
    import re

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "Makefile")) as f:
        [line] = re.findall(r"^\t.*tools\.cli lint (?!--deep)(.*)$",
                            f.read(), re.M)
    assert tuple(t.rstrip("/") for t in line.split()) == LINTED_TREES
    with open(os.path.join(root, ".github", "workflows", "ci.yml")) as f:
        assert "run: make lint\n" in f.read()


def test_eval_determinism_fires_in_tuning_scope():
    """eval-determinism (ISSUE 13): unseeded RNG, ambient np.random
    draws, wall clock, and set iteration inside pio_tpu/tuning/ are
    findings — each breaks the sweep's bit-reproducible resume
    contract."""
    src = """
        import time
        import numpy as np

        def assign_folds(n, k):
            rng = np.random.default_rng()        # unseeded
            tags = np.random.permutation(n) % k  # ambient state
            salt = time.time()                   # wall clock
            for u in set(str(i) for i in range(n)):  # hash-salted order
                pass
            return tags
    """
    fs = lint_text(textwrap.dedent(src), path="pio_tpu/tuning/splits.py",
                   select={"eval-determinism"})
    assert {f.rule for f in fs} == {"eval-determinism"}
    assert len(fs) == 4


def test_eval_determinism_scoped_and_seeded_ok():
    """Seeded RNG and deterministic iteration pass; the same unseeded
    code OUTSIDE pio_tpu/tuning/ is out of scope (bench/eval scripts
    keep their own rules)."""
    good = """
        import numpy as np

        def assign_folds(n, k, seed):
            rng = np.random.default_rng(seed)
            tags = rng.permutation(n) % k
            for u in sorted(set(range(n))):
                pass
            return tags
    """
    assert lint_text(textwrap.dedent(good),
                     path="pio_tpu/tuning/splits.py",
                     select={"eval-determinism"}) == []
    bad_elsewhere = """
        import numpy as np

        def shuffle(n):
            return np.random.permutation(n)
    """
    assert lint_text(textwrap.dedent(bad_elsewhere),
                     path="pio_tpu/models/x.py",
                     select={"eval-determinism"}) == []
