"""Persistent compile cache + bucket registry (utils/compilecache.py),
and their serving wiring: /readyz bucket gating, registry-driven warm
sweeps, the `pio compilecache` verb, and the bench smoke gate's plumbing.
"""

from __future__ import annotations

import json
import threading

import pytest

from pio_tpu.utils import compilecache as cc


def _reset_jax_cache():
    # jax binds its cache instance to the FIRST directory used in the
    # process; tests that switch directories must reset it (real
    # deployments use one directory per process, so only tests care)
    from jax._src import compilation_cache as jcc

    jcc.reset_cache()


@pytest.fixture()
def cache_dir(tmp_path, monkeypatch):
    """A private cache directory, placed the way a deployment places it:
    JAX_COMPILATION_CACHE_DIR. jax reads that variable into its config
    at import; it is long imported here, so the fixture mirrors the read
    by hand (the program itself must set no directory when it is set)."""
    import jax

    d = str(tmp_path / "cc")
    prev = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", d)
    jax.config.update("jax_compilation_cache_dir", d)
    # reset the module's enable-once state so each test sees a fresh dir
    monkeypatch.setattr(cc, "_enabled", False)
    _reset_jax_cache()
    yield d
    jax.config.update("jax_compilation_cache_dir", prev)
    _reset_jax_cache()


def test_enable_and_stats_and_clear(cache_dir):
    d = cc.enable_compile_cache()
    assert d == cache_dir
    # idempotent
    assert cc.enable_compile_cache() == d
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.tanh(x) * 3)
    float(f(jnp.ones(())))
    stats = cc.cache_stats(d)
    assert stats["entries"] >= 1
    assert stats["bytes"] > 0
    removed = cc.clear_cache(d)
    assert removed >= stats["entries"]
    assert cc.cache_stats(d)["entries"] == 0


def _spy_config_updates(monkeypatch) -> list[str]:
    """-> the (growing) list of jax config options updated from now on."""
    import jax

    names: list[str] = []
    real = jax.config.update

    def update(name, value):
        names.append(name)
        return real(name, value)

    monkeypatch.setattr(jax.config, "update", update)
    return names


def test_env_places_the_cache_and_code_sets_no_directory(
        cache_dir, monkeypatch):
    """JAX_COMPILATION_CACHE_DIR set: jax has read it itself, so the
    program uses that directory — for the stats and the bucket registry
    too — and sets none of its own."""
    updated = _spy_config_updates(monkeypatch)
    assert cc.enable_compile_cache() == cache_dir
    assert "jax_compilation_cache_dir" not in updated
    assert updated         # the persistence floors are still dropped
    assert cc.default_cache_dir() == cache_dir
    import jax
    import jax.numpy as jnp

    float(jax.jit(lambda x: jnp.cos(x) - 7)(jnp.ones(())))
    stats = cc.cache_stats()
    assert stats["dir"] == cache_dir and stats["entries"] >= 1
    reg = cc.BucketRegistry("rec", "1", "default")
    assert reg.path.startswith(cache_dir)
    # registries ride in the directory without counting as executables
    reg.record(4)
    reg.flush()
    assert cc.cache_stats()["entries"] == stats["entries"]


def test_without_env_the_cache_sits_at_one_fixed_path_in_the_checkout(
        monkeypatch):
    import os
    import subprocess
    import sys

    import jax

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    want = os.path.join(repo, ".jax_cache")
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    code = ("from pio_tpu.utils import compilecache as cc; "
            "print(cc.default_cache_dir())")
    seen = {
        subprocess.run([sys.executable, "-c", code], cwd=cwd, env=dict(
            env, PYTHONPATH=repo), capture_output=True, text=True,
            check=True).stdout.strip()
        for cwd in (repo, "/")     # two processes, two working dirs
    }
    assert seen == {want}           # no pid, time or temp name in it

    # in this process: with the variable unset the code points jax there
    prev = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(cc, "_enabled", False)
    updated = _spy_config_updates(monkeypatch)
    try:
        assert cc.enable_compile_cache() == want
        assert "jax_compilation_cache_dir" in updated
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
        _reset_jax_cache()


def test_kill_switch(monkeypatch):
    import jax

    monkeypatch.setenv("PIO_TPU_COMPILE_CACHE", "off")
    assert cc.cache_disabled()
    prev = jax.config.jax_enable_compilation_cache
    try:
        assert cc.enable_compile_cache() is None
        # jax is told too: it finds JAX_COMPILATION_CACHE_DIR by itself
        assert jax.config.jax_enable_compilation_cache is False
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)


def test_bucket_registry_round_trip(cache_dir):
    reg = cc.BucketRegistry("rec", "1", "default")
    assert reg.buckets() == []
    reg.record(4)
    reg.record(16)
    reg.record(4)      # dedup
    reg.record(0)      # ignored
    assert reg.buckets() == [4, 16]
    reg.flush()        # records debounce to a background write; force it
    # a fresh instance (next deploy) reads the persisted set
    reg2 = cc.BucketRegistry("rec", "1", "default")
    assert reg2.buckets() == [4, 16]
    # engine triple keys are isolated
    assert cc.BucketRegistry("other", "1", "default").buckets() == []


def test_bucket_registry_concurrent_records(cache_dir):
    reg = cc.BucketRegistry("conc", "1", "default")
    threads = [
        threading.Thread(target=lambda b=b: reg.record(b))
        for b in [1, 2, 4, 8] * 8
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert reg.buckets() == [1, 2, 4, 8]


# ---------------------------------------------------------------------------
# serving wiring
# ---------------------------------------------------------------------------

def test_serving_records_buckets_and_warms_from_registry(
        cache_dir, memory_storage):
    from pio_tpu.workflow.serve import ServingConfig, create_query_server
    from tests.test_serve import call, seed_and_train

    engine, ep, ctx, _ = seed_and_train(memory_storage, n_iter=2)
    cfg = ServingConfig(
        ip="127.0.0.1", port=0, engine_id="rec", backend="async",
        batch_window_ms=2.0, batch_max=16,
        warm_query={"user": "u0", "num": 3},
    )
    http, qs = create_query_server(engine, ep, memory_storage, cfg, ctx=ctx)
    http.start()
    try:
        # a real batched query records its pow2 bucket
        st, _ = call(http.port, "POST", "/queries.json",
                     {"user": "u1", "num": 3})
        assert st == 200
        deadline = 50
        while not qs.bucket_registry.buckets() and deadline:
            deadline -= 1
            import time

            time.sleep(0.05)
        assert 1 in qs.bucket_registry.buckets()
        # warm sweep completed at startup -> ready
        st, body = call(http.port, "GET", "/readyz")
        assert st == 200
        assert body["checks"]["buckets"]["ok"] is True
    finally:
        http.stop()
        qs.close()

    # second deployment: the warm set comes from the registry
    http, qs = create_query_server(engine, ep, memory_storage, cfg, ctx=ctx)
    try:
        assert qs._warm_bucket_set() == sorted(
            set(qs.bucket_registry.buckets()) | {1})
        assert qs._buckets_ready.is_set()
    finally:
        qs.close()


def test_readyz_gates_on_bucket_warm(cache_dir, memory_storage):
    """A server whose warm sweep has not finished reports NOT ready on
    /readyz — balancers never route into a bucket-miss compile."""
    from pio_tpu.workflow.serve import ServingConfig, create_query_server
    from tests.test_serve import call, seed_and_train

    engine, ep, ctx, _ = seed_and_train(memory_storage, n_iter=2)
    http, qs = create_query_server(
        engine, ep, memory_storage,
        ServingConfig(ip="127.0.0.1", port=0, engine_id="rec",
                      backend="async", batch_window_ms=2.0, batch_max=8,
                      warm_query={"user": "u0", "num": 3}),
        ctx=ctx)
    http.start()
    try:
        qs._buckets_ready.clear()   # simulate an in-flight warm sweep
        st, body = call(http.port, "GET", "/readyz")
        assert st == 503
        assert body["checks"]["buckets"]["ok"] is False
        qs._buckets_ready.set()
        st, body = call(http.port, "GET", "/readyz")
        assert st == 200
    finally:
        http.stop()
        qs.close()


def test_no_batcher_or_no_warm_query_is_ready_immediately(
        cache_dir, memory_storage):
    from pio_tpu.workflow.serve import ServingConfig, QueryServer
    from tests.test_serve import seed_and_train

    engine, ep, ctx, _ = seed_and_train(memory_storage, n_iter=2)
    # batching off -> no bucket gate
    qs = QueryServer(engine, ep, memory_storage,
                     ServingConfig(ip="127.0.0.1", port=0, engine_id="rec"),
                     ctx=ctx)
    assert qs._buckets_ready.is_set()
    qs.close()
    # batching on but no warm query: the sweep rides the first request,
    # so readiness must NOT deadlock waiting for it
    qs = QueryServer(engine, ep, memory_storage,
                     ServingConfig(ip="127.0.0.1", port=0, engine_id="rec",
                                   batch_window_ms=2.0, batch_max=8),
                     ctx=ctx)
    assert qs._buckets_ready.is_set()
    qs.close()


def test_run_train_enables_cache(cache_dir, memory_storage):
    import jax

    from tests.test_serve import seed_and_train

    # drop the in-memory jit cache: earlier tests may have compiled the
    # same training programs, which would satisfy jit without touching
    # the (fresh) persistent cache this test asserts on
    jax.clear_caches()
    seed_and_train(memory_storage, n_iter=2)   # calls run_train
    assert cc.cache_stats(cache_dir)["entries"] > 0


# ---------------------------------------------------------------------------
# CLI verb
# ---------------------------------------------------------------------------

def test_cli_compilecache_info_and_clear(cache_dir, capsys):
    from pio_tpu.tools.cli import main

    cc.enable_compile_cache()
    reg = cc.BucketRegistry("rec", "1", "default")
    reg.record(8)
    reg.flush()
    assert main(["compilecache", "--json"]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["dir"] == cache_dir
    assert "buckets__rec__1__default.json" in out["bucket_registries"]
    assert main(["compilecache"]) == 0
    text = capsys.readouterr().out
    assert "compile cache" in text and "[8]" in text
    assert main(["compilecache", "--clear"]) == 0
    assert cc.cache_stats(cache_dir)["entries"] == 0
