"""Benchmark driver: ALS throughput + MFU + serving latency + ingest rate.

Prints ONE JSON line {"metric", "value", "unit", "vs_baseline", "extra"}.

Primary metric (BASELINE.md): ALS implicit ratings/sec/chip at MovieLens-20M
shape (138,493 users x 26,744 items, 20M ratings, rank 64). vs_baseline =
speedup over the same kernel on one CPU core (stand-in for the reference's
Spark-CPU MLlib baseline, which cannot run in this image; single-core Spark
ALS is, if anything, slower than single-core XLA, so the ratio is
conservative). `extra` carries the rest of BASELINE.md's table: an MFU
estimate (analytic FLOPs / wall-clock vs device peak), p50/p99 /queries.json
latency with the model resident on-device, and event-ingest throughput.

One process per chip: the parent process NEVER imports jax, and every
phase is a fresh subprocess with its own timeout, run strictly one after
another — a chip belongs to one process at a time, and a parent that has
touched jax holds it. The device phases run on whatever backend jax finds
by default and there is no fallback: no accelerator, a failed phase or a
device kind PEAK_TABLE does not know is a non-zero exit. `--smoke` (the CPU
CI gate), the one-core CPU baseline and the host-bound ingest phase are the
only CPU runs, selected in the child via PIO_BENCH_PLATFORM=cpu and
labelled as such.

Usage: python bench.py [--small] [--no-serving] [--no-ingest] [--no-cpu]
       python bench.py --smoke [--update-baseline]      (CPU CI gate)
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

SMALL = "--small" in sys.argv

# MovieLens-20M shape (BASELINE.md) unless --small
N_USERS = 5000 if SMALL else 138_493
N_ITEMS = 1000 if SMALL else 26_744
NNZ = 200_000 if SMALL else 20_000_000
RANK = 16 if SMALL else 64
# 10 sweeps = the recommendation template's engine.json default
# (num_iterations: 10); the one-time on-device layout build + host->HBM
# transfer amortizes over sweeps, so the sweep count materially shapes the
# headline rate (measured: ~1.1s fixed + 0.082s/sweep at this shape)
ITERS = 2 if SMALL else 10
CHUNK = 8192

CPU_NNZ = 100_000 if SMALL else 400_000
CPU_ITERS = 1
# CPU proxy problem: same rank and same ratings-per-user density, scaled
# down uniformly so the per-sweep cost structure matches the TPU run
_CPU_SCALE = max(1, NNZ // CPU_NNZ)
CPU_N_USERS = max(64, N_USERS // _CPU_SCALE)
CPU_N_ITEMS = max(32, N_ITEMS // _CPU_SCALE)

PROBE_TIMEOUT = 300
TRAIN_TIMEOUT = 3000
SERVING_TIMEOUT = 2700
INGEST_TIMEOUT = 600
CPU_TIMEOUT = 1800

# per-chip peaks keyed by substring of device_kind: (bf16 MXU FLOP/s,
# HBM bytes/s) in ONE table so a new device kind cannot land in one
# lookup and silently vanish from the other. The ALS kernel accumulates
# in f32; MFU is reported against the bf16 peak (the conservative
# figure). The sweep is memory-bound (eval/ALS_ROOFLINE.md: ~166
# GB/sweep ≈ 203 ms bound vs 0.47 s measured at the ML-20M shape), so
# fraction-of-HBM-bound is the legible headline efficiency —
# mfu_vs_bf16_peak reads as 0.003 for a kernel already at 40% of its
# true (memory) roofline.
PEAK_TABLE = [
    ("v6", 918e12, 1640e9), ("trillium", 918e12, 1640e9),
    ("v5p", 459e12, 2765e9),
    ("v5 lite", 197e12, 819e9), ("v5e", 197e12, 819e9),
    ("v5litepod", 197e12, 819e9),
    ("v4", 275e12, 1228e9),
    ("v3", 123e12, 900e9),
    ("v2", 45e12, 700e9),
]


def _peaks_for(device_kind: str) -> tuple[float, float]:
    """A device kind the table does not know is an error, not a default:
    the MFU and roofline fields would quietly turn into nulls."""
    dk = (device_kind or "").lower()
    for sub, flops, hbm in PEAK_TABLE:
        if sub in dk:
            return flops, hbm
    raise ValueError(f"device kind {device_kind!r} is not in PEAK_TABLE")


def peak_for(device_kind: str) -> float:
    return _peaks_for(device_kind)[0]


def hbm_peak_for(device_kind: str) -> float:
    return _peaks_for(device_kind)[1]


def als_hbm_bytes_per_sweep(nnz: int, n_users: int, n_items: int,
                            rank: int, cg_iters: int,
                            width: int = 128) -> float:
    """Analytic physical HBM traffic for one full ALS sweep (both
    halves), mirroring eval/ALS_ROOFLINE.md's per-op accounting. All
    minor dims are lane-padded to 128 on TPU — a 2x tax at rank 64 —
    and slot layouts pad each entity's ratings to a multiple of
    `width` (expected padding: width/2 per entity row). Terms:
      - ne factor gather (bf16): written by the emitter, re-read by the
        block build — 2 passes over the slot-padded rows, both halves
      - per-slot (k,k) f32 blocks: written as scan outputs, re-read by
        the scatter — 2 passes
      - A (n,k,k) f32: zero-init + scatter write + one solve read
      - CG: one pass over A per matvec iteration, both halves
    At the ML-20M shape this sums to ~155 GB vs the trace-derived
    ~166 GB (eval/ALS_ROOFLINE.md) — within 7%; the analytic form is
    used so the bound scales with the benched shape."""
    lane = max(128, -(-rank // 128) * 128)
    slot_rows = nnz * 2 + (n_users + n_items) * width // 2
    gather = 2 * slot_rows * lane * 2
    blocks = 2 * (slot_rows // width) * rank * lane * 4
    a_bytes = 3 * (n_users + n_items) * rank * lane * 4
    cg = max(cg_iters, 1) * (n_users + n_items) * rank * lane * 4
    return float(gather + blocks + a_bytes + cg)


def als_flops_per_sweep(nnz: int, n_users: int, n_items: int, rank: int,
                        cg_iters: int) -> float:
    """Analytic FLOPs for one full ALS sweep (both halves) of the slot-layout
    CG kernel in ops/als.py. Dominant terms only:
      - normal-equation build: each rating row contributes a k x k outer
        product (via W-wide matmuls) per half  -> 2 * 2*nnz*k^2
      - rhs build: 2*nnz*k per half
      - Gram YtY/XtX: 2*n*k^2 for the opposing side per half
      - solve: CG = matvec 2*k^2 per entity per iteration;
               direct (cg_iters=0) = k^3/3 Cholesky + 2*k^2 triangular
               solves per entity
    """
    k = rank
    build = 2 * (2 * nnz * k * k + 2 * nnz * k)
    gram = 2 * n_items * k * k + 2 * n_users * k * k
    if cg_iters > 0:
        solve = 2 * (n_users + n_items) * cg_iters * k * k
    else:
        solve = (n_users + n_items) * (k * k * k / 3 + 2 * k * k)
    return float(build + gram + solve)


def synth(nnz: int, n_users: int = None, n_items: int = None, seed=0):
    import numpy as np

    n_users = n_users or N_USERS
    n_items = n_items or N_ITEMS
    rng = np.random.default_rng(seed)
    # zipf-ish popularity for realism in the gather/scatter patterns
    users = (rng.zipf(1.2, nnz) % n_users).astype(np.int64)
    items = (rng.zipf(1.2, nnz) % n_items).astype(np.int64)
    vals = rng.integers(1, 6, nnz).astype(np.float32)
    return users, items, vals


def bench_params(iters: int, rank: int = None, chunk: int = None):
    from pio_tpu.ops.als import ALSParams

    # cg_iters pinned to the full-shape auto choice (16 at rank 64) so
    # the scaled-down CPU proxy runs the SAME solver as the TPU shape
    # (auto would flip the small proxy to exact Cholesky and turn
    # vs_baseline into a cross-algorithm ratio)
    return ALSParams(rank=rank or RANK, iterations=iters, reg=0.05,
                     alpha=10.0, implicit=True, chunk=chunk or CHUNK,
                     cg_iters=ALSParams(rank=rank or RANK)
                     .resolved_cg_iters(N_USERS))


def run_als(users, items, vals, iters: int,
            n_users: int = None, n_items: int = None,
            rank: int = None, chunk: int = None, repeats: int = 3,
            layouts=None) -> float | None:
    """-> best wall seconds for `iters` sweeps over `repeats` runs, compile
    excluded (the pre-timing call runs the exact same program: iterations
    is a static scan length), or None when repeats<=0 (compile-only mode —
    not a measurement; programs are usually pre-compiled shape-abstract
    via als_warm_compile instead). Best-of-N so run-to-run noise does not
    swamp per-sweep deltas.
    With `layouts` (ops/als.py ALSLayouts) the runs measure the RETRAIN
    path: slot layouts resident in HBM, no per-call rebuild."""
    from pio_tpu.ops.als import als_train

    n_users = n_users or N_USERS
    n_items = n_items or N_ITEMS

    import jax.numpy as jnp

    def go():
        p = bench_params(iters, rank, chunk)
        model = als_train(users, items, vals, n_users, n_items, p,
                          layouts=layouts)
        # a scalar readback ends the timed region: the value on the
        # host proves the work happened
        return float(jnp.sum(model.user_factors))

    go()  # compile (identical program: same static iterations)
    if repeats <= 0:      # compile-only mode: not a measurement —
        return None       # never let inf masquerade as a timing
    best = float("inf")
    for _ in range(repeats):
        t0 = time.monotonic()
        go()
        best = min(best, time.monotonic() - t0)
    return best


# ---------------------------------------------------------------------------
# phases (each runs in its own subprocess: `python bench.py --phase NAME`)
# ---------------------------------------------------------------------------

def phase_probe() -> dict:
    from pio_tpu.utils.tpu_health import staged_probe

    return staged_probe(os.environ.get("PIO_PROBE_PROGRESS"))


def phase_train() -> dict:
    from pio_tpu.utils.tpu_health import StageWriter

    # custom stage names (not the probe's): classify_hang reports
    # hang-after-<last> for these, which is the honest label for a
    # train-phase stall
    trail = StageWriter(os.environ.get("PIO_PROBE_PROGRESS"))
    trail.stage("train_start", pid=os.getpid())
    # persistent XLA compile cache (utils/compilecache.py): the SECOND
    # bench/train run deserializes the warm-up programs instead of
    # re-running XLA — the probe records hit/miss so the warmup_compile_
    # sec trajectory is legible (cold ~14.6s on the r05 CPU rig)
    from pio_tpu.utils.compilecache import CacheProbe

    cache_probe = CacheProbe()
    from pio_tpu.ops.als import ALSParams

    trail.stage("imports_done")

    nnz, iters, n_users, n_items = NNZ, ITERS, N_USERS, N_ITEMS
    users, items, vals = synth(nnz, n_users=n_users, n_items=n_items)

    # measure the host->HBM COO transfer once, explicitly, then time the
    # train on device-RESIDENT arrays so layout/sweep numbers do not carry
    # the transfer
    import jax
    import numpy as np

    # wire format: ids need int32, but MovieLens-class ratings fit uint8
    # — ship the value column quantized and upcast on device (als_train
    # casts device inputs to f32 itself), cutting the host->HBM volume
    # 25%
    host = [np.ascontiguousarray(users, np.int32),
            np.ascontiguousarray(items, np.int32),
            np.ascontiguousarray(vals, np.uint8)
            if float(vals.max()) <= 255 and np.all(vals == vals.astype(np.uint8))
            else np.ascontiguousarray(vals, np.float32)]
    import jax.numpy as jnp

    float(jnp.sum(jax.device_put(np.ones(8))))  # backend up
    trail.stage("backend_up")

    from pio_tpu.ops.als import als_build_layouts, als_warm_compile

    # ---- cold-start overlap: warm-up compiles run WHILE the COO columns
    # are in flight, so a cold first train pays max(compile, transfer),
    # not their sum. Warm-up is AOT
    # (als_warm_compile: abstract shapes through .lower().compile()) —
    # rounds 1-5 EXECUTED the programs on zero-filled arrays to reach the
    # same compiles, burning ~the sweep cost in pointless device math;
    # compile-only warm-up also makes warmup_compile_sec the clean number
    # the persistent compile cache shrinks (a warm restart deserializes
    # instead of re-running XLA; see extra.train.compile_cache).
    t_put = time.monotonic()
    dev = [jax.device_put(x) for x in host]          # async
    # pre-warm the fence expression at the real columns' shapes/dtypes so
    # its own compile doesn't pollute the exposed-transfer measurement
    fz = [jnp.zeros(h.shape, h.dtype) for h in host]
    float(jnp.sum(fz[0]) + jnp.sum(fz[1])
          + jnp.sum(fz[2].astype(jnp.float32)))
    als_warm_compile(nnz, n_users, n_items, bench_params(iters),
                     sweep_lengths=(iters, 1))
    warm_s = time.monotonic() - t_put
    del fz
    # fence: scalar readback touching ALL THREE columns — device_put is
    # async and a fence on one array creates no dependency on the others
    float(jnp.sum(dev[0]) + jnp.sum(dev[1])
          + jnp.sum(dev[2].astype(jnp.float32)))
    exposed_transfer_s = max(time.monotonic() - t_put - warm_s, 0.0)
    # raw (un-overlapped) transfer, for cross-round comparability: the
    # same host bytes again, fully fenced, nothing else in flight
    t0 = time.monotonic()
    dev2 = [jax.device_put(x) for x in host]
    float(jnp.sum(dev2[0]) + jnp.sum(dev2[1])
          + jnp.sum(dev2[2].astype(jnp.float32)))
    transfer_s = time.monotonic() - t0
    del dev2
    trail.stage("transfer_done", transfer_sec=round(transfer_s, 2),
                exposed_after_overlap=round(exposed_transfer_s, 2))
    d_users, d_items, d_vals = dev

    # ---- layout build, measured directly (persisted across retrains)
    t0 = time.monotonic()
    lay = als_build_layouts(d_users, d_items, d_vals, n_users, n_items,
                            bench_params(iters))
    float(jnp.sum(lay.by_user[3]) + jnp.sum(lay.by_item[3]))
    layout_s = time.monotonic() - t0
    trail.stage("layout_done", layout_sec=round(layout_s, 2))

    dt = run_als(d_users, d_items, d_vals, iters,
                 n_users=n_users, n_items=n_items, layouts=lay)
    trail.stage("train_done", train_sec=round(dt, 2))
    # end-to-end first train: transfer + layout build + sweeps (compile
    # excluded as before; with the overlap above a cold session hides the
    # transfer under it anyway)
    rate = nnz * iters / (dt + transfer_s + layout_s)
    # the RETRAIN loop (device-resident COO + persisted layouts — the
    # analogue of MLlib iterating on a cached RDD): sweeps only
    retrain_rate = nnz * iters / dt
    dt1 = run_als(d_users, d_items, d_vals, 1,
                  n_users=n_users, n_items=n_items, layouts=lay)
    # None when noise makes the split meaningless (dt <= dt1): garbage
    # rates must not masquerade as measurements
    sweep_s = (dt - dt1) / max(iters - 1, 1) if dt > dt1 else None
    p = ALSParams(rank=RANK)
    # MUST match bench_params' pin, so the reported cg/FLOPs describe the
    # solver that actually ran
    cg = p.resolved_cg_iters(N_USERS)
    # padded nnz is what the kernel actually crunches
    nnz_pad = nnz + (-nnz % CHUNK)
    # the trainer's warm-CG schedule (ops/als.py _cg_schedule) runs the
    # first cg_warm_sweeps sweeps at full CG strength and the rest at
    # cg_warm_iters; the FLOPs accounting must mirror the actual mix or
    # MFU is inflated by phantom matvecs
    from pio_tpu.ops.als import _cg_schedule

    sched_p = ALSParams(rank=RANK, iterations=iters, cg_iters=cg)
    n_full, n_warm, w_cg, _ = _cg_schedule(sched_p, cg, cg)
    fl_full = als_flops_per_sweep(nnz_pad, n_users, n_items, RANK, cg)
    fl_warm = als_flops_per_sweep(nnz_pad, n_users, n_items, RANK, w_cg)
    fl_total = fl_full * n_full + fl_warm * n_warm
    # sweeps 2..iters (what the dt-dt1 split measures): drop one full sweep
    fl_split = fl_full * (n_full - 1) + fl_warm * n_warm
    fl = fl_split / max(iters - 1, 1)        # per STEADY (post-split) sweep
    import jax
    kind = jax.devices()[0].device_kind
    peak = peak_for(kind)
    flops_per_sec = fl_total / dt
    split_ok = sweep_s is not None
    # fraction-of-HBM-roofline for a steady sweep: analytic bound time /
    # measured time, 1.0 = the kernel streams at memory peak. Same
    # full/warm CG mix as the FLOPs split (sweeps 2..iters).
    hbm_bw = hbm_peak_for(kind)
    by_full = als_hbm_bytes_per_sweep(nnz_pad, n_users, n_items, RANK, cg)
    by_warm = als_hbm_bytes_per_sweep(nnz_pad, n_users, n_items, RANK, w_cg)
    by_split = (by_full * (n_full - 1) + by_warm * n_warm) \
        / max(iters - 1, 1)
    hbm_bound_sweep_s = by_split / hbm_bw
    frac_roofline = round(hbm_bound_sweep_s / sweep_s, 4) \
        if split_ok else None
    return {
        "rate": rate,
        "compile_cache": cache_probe.report(),
        "retrain_rate": round(retrain_rate, 1),
        "wall_sec": round(dt + transfer_s + layout_s, 3),
        "nnz": nnz,
        "sweeps": iters,
        "transfer_sec": round(transfer_s, 3),
        "exposed_transfer_after_overlap_sec": round(exposed_transfer_s, 3),
        # COMPILE-only since round 6 (AOT warm-up): rounds 1-5 folded the
        # zero-data warm executions in, so this number dropped ~2x by
        # construction — compare compile_cache.status across runs for the
        # persistent-cache effect
        "warmup_compile_sec": round(warm_s, 3),
        # DIRECTLY measured now (als_build_layouts, persisted across the
        # timed retrain runs) — rounds 1-3 inferred it from the
        # dt(N)-dt(1) split
        "fixed_layout_sec": round(layout_s, 3),
        "retrain_residual_sec": round(max(dt1 - sweep_s, 0.0), 3)
        if split_ok else None,
        "per_sweep_sec": round(sweep_s, 4) if split_ok else None,
        "per_sweep_rate": round(nnz / sweep_s, 1) if split_ok else None,
        "flops_per_sweep": fl,
        "flops_per_sec": flops_per_sec,
        "mfu_vs_bf16_peak": round(flops_per_sec / peak, 4),
        "sweep_mfu_vs_bf16_peak": round(fl / sweep_s / peak, 4)
        if split_ok else None,
        # the legible efficiency metric for this memory-bound kernel:
        # 1.0 = steady sweep streams at HBM peak
        "hbm_bytes_per_sweep": by_split,
        "hbm_bound_sweep_sec": round(hbm_bound_sweep_s, 4),
        "frac_of_hbm_roofline": frac_roofline,
        "device_kind": kind,
        "rank": RANK,
        "cg_iters": cg,
        "cg_warm_iters": w_cg if n_warm else None,
        "cg_full_sweeps": n_full,
        "accum": ALSParams(rank=RANK).resolved_accum(),
    }


def phase_cpu() -> dict:
    users, items, vals = synth(CPU_NNZ, n_users=CPU_N_USERS,
                               n_items=CPU_N_ITEMS)
    dt = run_als(users, items, vals, CPU_ITERS, n_users=CPU_N_USERS,
                 n_items=CPU_N_ITEMS, rank=RANK, chunk=CHUNK)
    return {"rate": CPU_NNZ * CPU_ITERS / dt}


def phase_serving() -> dict:
    """Train a moderate ALS model, deploy the real HTTP query server, and
    measure /queries.json p50/p99 over the wire with the model on-device
    (reference latency bookkeeping: CreateServer.scala:605-612)."""
    import threading
    import urllib.request

    import numpy as np

    from pio_tpu.controller import EngineParams
    from pio_tpu.data import DataMap, Event
    from pio_tpu.data.dao import App
    from pio_tpu.data.storage import Storage
    from pio_tpu.models.recommendation import (
        ALSAlgorithmParams, DataSourceParams, RecommendationEngine,
    )
    from pio_tpu.workflow.context import create_workflow_context
    from pio_tpu.workflow.serve import ServingConfig, create_query_server
    from pio_tpu.workflow.train import run_train

    n_users, n_items, n_events = (200, 60, 2_000) if SMALL \
        else (5_000, 1_500, 100_000)

    storage = Storage(env={
        "PIO_STORAGE_SOURCES_M_TYPE": "memory",
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "M",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "M",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "M",
    })
    app_id = storage.get_metadata_apps().insert(App(0, "benchapp"))
    ev = storage.get_events()
    ev.init(app_id)
    rng = np.random.default_rng(0)
    uu = rng.integers(0, n_users, n_events)
    ii = rng.integers(0, n_items, n_events)
    for m in range(n_events):
        ev.insert(Event(
            event="rate", entity_type="user", entity_id=f"u{uu[m]}",
            target_entity_type="item", target_entity_id=f"i{ii[m]}",
            properties=DataMap({"rating": int(rng.integers(1, 6))})), app_id)

    engine = RecommendationEngine.apply()
    ep = EngineParams(
        datasource=("", DataSourceParams(app_name="benchapp")),
        algorithms=[("als", ALSAlgorithmParams(
            rank=32, num_iterations=5, lambda_=0.05, chunk=8192))],
    )
    ctx = create_workflow_context(storage, use_mesh=False)
    run_train(engine, ep, storage, engine_id="bench", ctx=ctx)

    def pcts(lat_s: list) -> dict:
        lat_ms = sorted(x * 1e3 for x in lat_s)

        def pct(p):
            return lat_ms[min(len(lat_ms) - 1, int(p / 100 * len(lat_ms)))]

        return {"p50_ms": round(pct(50), 3), "p90_ms": round(pct(90), 3),
                "p99_ms": round(pct(99), 3)}

    def measure_sequential(port, n_req, warmup=20):
        lat = []
        for r in range(n_req + warmup):
            q = json.dumps({"user": f"u{r % n_users}", "num": 10}).encode()
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/queries.json", data=q,
                method="POST")
            t0 = time.monotonic()
            with urllib.request.urlopen(req, timeout=30) as resp:
                resp.read()
            if r >= warmup:
                lat.append(time.monotonic() - t0)
        return {**pcts(lat), "qps": round(len(lat) / sum(lat), 1),
                "n_requests": len(lat)}

    def _measure_concurrent_once(port, n_req, workers=16):
        """Keep-alive connection per worker, n_req total requests."""
        import http.client

        lat: list[float] = []
        lock = threading.Lock()
        per_worker = n_req // workers
        t_start = time.monotonic()

        def worker(w):
            conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
            mine = []
            try:
                for r in range(per_worker):
                    q = json.dumps(
                        {"user": f"u{(w * per_worker + r) % n_users}",
                         "num": 10}).encode()
                    t0 = time.monotonic()
                    conn.request("POST", "/queries.json", body=q)
                    conn.getresponse().read()
                    mine.append(time.monotonic() - t0)
            finally:
                conn.close()
            with lock:
                lat.extend(mine)

        threads = [threading.Thread(target=worker, args=(w,))
                   for w in range(workers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.monotonic() - t_start
        return {**pcts(lat), "qps": round(len(lat) / wall, 1),
                "n_requests": len(lat), "client_threads": workers}

    def measure_concurrent(port, n_req, workers=16, reps=5):
        """Median-of-`reps` by p99: the in-process 16-thread client harness
        shares the box's core with the server, so any single run can catch
        a scheduler stall that lands on whichever mode is measuring at
        that moment (10x p99 swings at fixed config were seen); only
        rep-level medians filter that. 5 reps tolerate two polluted
        ones. The per-rep tails are kept in the artifact."""
        runs = [_measure_concurrent_once(port, n_req, workers)
                for _ in range(reps)]
        tails = [r["p99_ms"] for r in runs]   # run order, pre-sort
        runs.sort(key=lambda r: r["p99_ms"])
        med = dict(runs[len(runs) // 2])
        med["reps"] = reps
        med["p99_all"] = tails
        return med

    def deploy(backend, batch_window_ms=0.0):
        # steady-state measurement: warm_query pre-compiles the single path
        # AND every micro-batch bucket before traffic (a bucket-miss compile
        # mid-traffic is client-timeout territory)
        http, qs = create_query_server(
            engine, ep, storage,
            ServingConfig(ip="127.0.0.1", port=0, engine_id="bench",
                          backend=backend, batch_window_ms=batch_window_ms,
                          # 16 clients -> batches <= 16; warming buckets
                          # beyond that only buys compiles
                          batch_max=16,
                          warm_query={"user": "u0", "num": 10}),
            ctx=ctx,
        )
        http.start()
        return http, qs

    import threading

    n_seq = 50 if SMALL else 400
    n_conc = 200 if SMALL else 2000

    out: dict = {}
    # context for the latency rows: a REST predict pays one device dispatch,
    # so p50 is floored by the host<->device round trip
    import jax
    import jax.numpy as jnp

    one = jnp.ones(())
    add = jax.jit(lambda x: x + 1)
    jax.block_until_ready(add(one))  # compile
    rtts = []
    for _ in range(15):
        t0 = time.monotonic()
        jax.block_until_ready(add(one))
        rtts.append(time.monotonic() - t0)
    out["device_roundtrip_ms"] = round(sorted(rtts)[len(rtts) // 2] * 1e3, 3)

    # production path (async transport): sequential latency = the BASELINE.md
    # "p50 /queries.json" row
    http, qs = deploy("async")
    try:
        out.update(measure_sequential(http.port, n_seq))
        out["concurrent"] = {"async": measure_concurrent(http.port, n_conc)}
    finally:
        http.stop()
        qs.close()
    # before/after for the round-1 "serving throughput unproven" finding:
    # threaded thread-per-connection vs async vs async+micro-batching
    http, qs = deploy("threaded")
    try:
        out["concurrent"]["threaded"] = measure_concurrent(http.port, n_conc)
    finally:
        http.stop()
        qs.close()
    http, qs = deploy("async", batch_window_ms=2.0)
    try:
        out["concurrent"]["async_batched"] = measure_concurrent(
            http.port, n_conc)
    finally:
        http.stop()
        qs.close()
    # adaptive (continuous) batching: batch = whatever queued during the
    # previous batch's execution; self-tunes to RTT-dominated dispatch
    http, qs = deploy("async", batch_window_ms=-1.0)
    try:
        out["concurrent"]["async_adaptive"] = measure_concurrent(
            http.port, n_conc)
    finally:
        http.stop()
        qs.close()
    return out


def phase_ingest() -> dict:
    """Event-server ingest throughput over the wire (batch POSTs over
    keep-alive connections); storage-bound, not TPU-bound (BASELINE.md).

    Measured twice: against the native C++ eventlog backend (the fast
    path: parse+validate+append entirely in C, server/eventserver.py
    _native_fast_path) and against the memory backend (the Python
    pipeline), so the native ingest win is visible in the artifact."""
    out = {}
    import shutil
    import tempfile

    eldir = tempfile.mkdtemp(prefix="pio_bench_el_")
    try:
        native = _ingest_once({
            "PIO_STORAGE_SOURCES_EL_TYPE": "eventlog",
            "PIO_STORAGE_SOURCES_EL_PATH": eldir,
            "PIO_STORAGE_SOURCES_M_TYPE": "memory",
            "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "M",
            "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "EL",
            "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "M",
        })
        mem_env = {
            "PIO_STORAGE_SOURCES_M_TYPE": "memory",
            "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "M",
            "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "M",
            "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "M",
        }
        python_path = _ingest_once(mem_env)
        # ROADMAP item 4 / ISSUE 11: the Python pipeline over the binary
        # columnar wire — the JSON decode is gone, so this is the number
        # contracted to beat the native row (>1.0x on the bench rig)
        binary_path = _ingest_once(mem_env, wire="binary")
    finally:
        shutil.rmtree(eldir, ignore_errors=True)
    out = dict(native)
    out["backend"] = "eventlog(native ingest)"
    out["python_pipeline"] = python_path
    out["binary_pipeline"] = binary_path
    out["binary_ingest_x_native"] = round(
        binary_path["events_per_sec"] / native["events_per_sec"], 3)
    return out


def _ingest_once(env: dict, wire: str = "json") -> dict:
    from pio_tpu.data.dao import AccessKey, App
    from pio_tpu.data.storage import Storage
    from pio_tpu.server.eventserver import EventServerConfig, create_event_server

    storage = Storage(env=env)
    app_id = storage.get_metadata_apps().insert(App(0, "ingestapp"))
    storage.get_metadata_access_keys().insert(AccessKey("IK", app_id, ()))
    storage.get_events().init(app_id)

    srv = create_event_server(
        storage, EventServerConfig(ip="127.0.0.1", port=0))
    srv.start()
    try:
        import http.client
        import threading

        port = srv.port
        workers = 2 if SMALL else 8
        total_events = (20 if SMALL else 400) * 50
        # the JSON route carries the reference's 50-event batch
        # contract; the binary columnar route is a BULK wire
        # (MAX_EVENTS_PER_BINARY_BATCH) — each arm drives its own
        # wire the way production clients would, same total events
        per_batch = 500 if wire == "binary" else 50
        n_batches = max(workers, total_events // per_batch)
        batch = [
            {"event": "rate", "entityType": "user", "entityId": f"u{j}",
             "targetEntityType": "item", "targetEntityId": f"i{j}",
             "properties": {"rating": 4}}
            for j in range(per_batch)
        ]
        if wire == "binary":
            # the loadgen encodes the columnar frame natively — the
            # per-batch encode cost is paid once here, OUTSIDE the
            # timed loop, exactly like the JSON dumps below
            from pio_tpu.data.columnar import (
                COLUMNAR_CONTENT_TYPE, encode_api_batch,
            )

            body = encode_api_batch(batch)
            content_type = COLUMNAR_CONTENT_TYPE
        else:
            body = json.dumps(batch).encode()
            content_type = "application/json"

        def sequential(n):
            """One keep-alive connection, n batches; -> (loop seconds,
            events ACCEPTED, events shed, events retried). Only per-event
            201s count — failed ingests must not inflate the rate — and
            response parsing happens OUTSIDE the timed loop: the server
            shares this process (and GIL), so client-side JSON work
            during the measurement would deflate the server's rate.
            Batches with 429-shed slots (spill backpressure) are
            re-queued once and the shed/retried counts reported, so a
            run that hit backpressure is visible in the artifact."""
            conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
            payloads = []
            try:
                t0 = time.monotonic()
                for _ in range(n):
                    conn.request(
                        "POST", "/batch/events.json?accessKey=IK",
                        body=body,
                        headers={"Content-Type": content_type})
                    resp = conn.getresponse()
                    payload = resp.read()
                    if resp.status != 200:
                        raise RuntimeError(
                            f"ingest HTTP {resp.status}: {payload[:200]}")
                    payloads.append(payload)
                elapsed = time.monotonic() - t0
                shed = sum(
                    1 for p in payloads for s in json.loads(p)
                    if s.get("status") == 429
                )
                retried = 0
                if shed:
                    # shed-and-retry accounting: the load generator
                    # replays one batch per shed batch, OUTSIDE the
                    # timed window and EXCLUDED from `accepted` —
                    # retries are overhead to report, never rate (the
                    # binary_ingest_x_native contract gate reads the
                    # rate, so a backpressured run must not inflate it)
                    for p in payloads:
                        if any(s.get("status") == 429
                               for s in json.loads(p)):
                            conn.request(
                                "POST", "/batch/events.json?accessKey=IK",
                                body=body,
                                headers={"Content-Type": content_type})
                            conn.getresponse().read()
                            retried += 1
            finally:
                conn.close()
            # only 201s from the TIMED window count toward the rate
            accepted = sum(
                1 for p in payloads for s in json.loads(p)
                if s.get("status") == 201
            )
            return elapsed, accepted, shed, retried

        seq_dt, seq_accepted, seq_shed, seq_retried = sequential(
            max(1, n_batches // 4))

        # concurrent keep-alive clients = the real server capacity (the
        # round-1 number was sequential urllib without keep-alive, i.e.
        # client-bound, not server-bound)
        per_worker = max(1, n_batches // workers)
        results: list[tuple[float, int, int, int]] = []
        errors: list[Exception] = []

        def worker():
            try:
                results.append(sequential(per_worker))
            except Exception as e:  # noqa: BLE001 - surfaced below
                errors.append(e)

        threads = [threading.Thread(target=worker) for _ in range(workers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if errors:
            raise errors[0]
        conc_dt = max(dt for dt, *_ in results)
        return {
            "events_per_sec": round(
                sum(n for _, n, *_ in results) / conc_dt, 1),
            "events_per_sec_sequential": round(seq_accepted / seq_dt, 1),
            "batches": n_batches,
            "client_threads": workers,
            "wire": wire,
            "shed_events": seq_shed + sum(s for *_, s, _ in results),
            "retried_batches": seq_retried + sum(r for *_, r in results),
        }
    finally:
        srv.stop()


def phase_smoke() -> dict:
    """CPU-stable micro-bench for the CI perf gate (`make bench-smoke`):
    Python-pipeline ingest events/s + serving p50 with a tiny model.
    Deliberately avoids the TPU probe, the native eventlog, and the
    concurrent-tail machinery — only metrics that are stable on a loaded
    CI box, compared against BASELINE.json published.smoke with a
    tolerance band so perf regressions fail PRs instead of surfacing in
    round reviews."""
    import urllib.request

    import numpy as np

    out: dict = {}
    # best-of-3 reps throughout: a scheduler stall or GC pause on a
    # loaded CI box halves a single rep; the best rep is the stable
    # capability number a 2x-class regression gate needs
    ingest_reps = [
        _ingest_once({
            "PIO_STORAGE_SOURCES_M_TYPE": "memory",
            "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "M",
            "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "M",
            "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "M",
        })
        for _ in range(3)
    ]
    out["ingest_events_per_sec"] = max(
        r["events_per_sec"] for r in ingest_reps)
    out["ingest_events_per_sec_sequential"] = max(
        r["events_per_sec_sequential"] for r in ingest_reps)
    out["binary_ingest"] = _smoke_binary_ingest_cell()
    out["binary_ingest_x_native"] = out["binary_ingest"].get("x_native")
    out["replicated_ingest"] = _smoke_replicated_ingest_cell(
        out["binary_ingest"]["binary_events_per_sec"])
    out["replicated_ingest_x_single"] = out["replicated_ingest"].get(
        "x_single")

    from pio_tpu.controller import EngineParams
    from pio_tpu.data import DataMap, Event
    from pio_tpu.data.dao import App
    from pio_tpu.data.storage import Storage
    from pio_tpu.models.recommendation import (
        ALSAlgorithmParams, DataSourceParams, RecommendationEngine,
    )
    from pio_tpu.workflow.context import create_workflow_context
    from pio_tpu.workflow.serve import ServingConfig, create_query_server
    from pio_tpu.workflow.train import run_train

    n_users, n_items, n_events = 200, 60, 2_000
    storage = Storage(env={
        "PIO_STORAGE_SOURCES_M_TYPE": "memory",
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "M",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "M",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "M",
    })
    app_id = storage.get_metadata_apps().insert(App(0, "smokeapp"))
    ev = storage.get_events()
    ev.init(app_id)
    rng = np.random.default_rng(0)
    uu = rng.integers(0, n_users, n_events)
    ii = rng.integers(0, n_items, n_events)
    ev.insert_batch([
        Event(event="rate", entity_type="user", entity_id=f"u{uu[m]}",
              target_entity_type="item", target_entity_id=f"i{ii[m]}",
              properties=DataMap({"rating": int(rng.integers(1, 6))}))
        for m in range(n_events)
    ], app_id)
    engine = RecommendationEngine.apply()
    ep = EngineParams(
        datasource=("", DataSourceParams(app_name="smokeapp")),
        algorithms=[("als", ALSAlgorithmParams(
            rank=16, num_iterations=3, lambda_=0.05, chunk=2048))],
    )
    ctx = create_workflow_context(storage, use_mesh=False)
    smoke_iid = run_train(engine, ep, storage, engine_id="smoke", ctx=ctx)
    http, qs = create_query_server(
        engine, ep, storage,
        ServingConfig(ip="127.0.0.1", port=0, engine_id="smoke",
                      backend="async",
                      warm_query={"user": "u0", "num": 10}),
        ctx=ctx,
    )
    http.start()
    try:
        def one_rep(port: int) -> tuple[float, float]:
            lat = []
            for r in range(120):
                q = json.dumps(
                    {"user": f"u{r % n_users}", "num": 10}).encode()
                req = urllib.request.Request(
                    f"http://127.0.0.1:{port}/queries.json", data=q,
                    method="POST")
                t0 = time.monotonic()
                with urllib.request.urlopen(req, timeout=30) as resp:
                    resp.read()
                if r >= 20:
                    lat.append(time.monotonic() - t0)
            lat.sort()
            return (lat[len(lat) // 2] * 1e3,
                    lat[max(0, int(len(lat) * 0.99) - 1)] * 1e3)

        # best-of-3: a scheduler stall on a loaded CI box can double a
        # single rep's numbers; the BEST rep is the stable capability
        # number a regression gate needs (p99 keyed — the fleet gate
        # below compares tails)
        single = min((one_rep(http.port) for _ in range(3)),
                     key=lambda t: t[1])
        out["serving_p50_ms"] = round(single[0], 3)
        out["serving_p99_ms"] = round(single[1], 3)
        out["freshness"] = _smoke_freshness_cell(
            storage, ev, app_id, qs, http.port, n_users)
        # the parity oracle is the PERSISTED instance's in-process
        # prediction — the live single-host server has already been
        # fold-in-refreshed by the freshness cell above, so its answers
        # legitimately differ from the partitioned instance's
        from pio_tpu.workflow.train import load_models as _load_models

        algo = engine._doers(ep)[2][0]
        full_model = _load_models(storage, engine, ep, smoke_iid,
                                  ctx=ctx)[0]
        out["fleet"] = _smoke_fleet_cell(
            storage, one_rep, single[1],
            lambda q: algo.predict(full_model, q))
        out["tenant"] = _smoke_tenant_cell(
            storage, lambda q: algo.predict(full_model, q))
        out["tracing"] = _smoke_tracing_cell(http, qs)
        out["batching"] = _smoke_batching_cell(qs)
    finally:
        http.stop()
        qs.close()
    out["batched_qps_x_solo"] = out["batching"]["qps_x_solo"]
    out["freshness_new_user_seconds"] = out["freshness"][
        "new_user_seconds"]
    out["fleet_p99_x_single_host"] = out["fleet"]["p99_x_single_host"]
    out["pooled_binary_fleet_p99_x_fresh_json"] = out["fleet"][
        "pooled_binary_p99_x_fresh_json"]
    out["tenant_victim_p99_x_solo"] = out["tenant"]["victim_p99_x_solo"]
    out["tracing_overhead_p50_x"] = out["tracing"]["p50_overhead_x"]
    out["sweep"] = _smoke_sweep_cell()
    out["sweep_8pt_x_2seq"] = out["sweep"]["x_2seq"]
    out["retrieval"] = _smoke_retrieval_cell()
    out["retrieval_p99_x_exact"] = out["retrieval"]["p99_x_exact"]
    return out


def _smoke_sweep_cell() -> dict:
    """Batched-sweep cell (ISSUE 13 / ROADMAP item 5 acceptance): the
    wall-clock of an 8-point BATCHED hyperparameter sweep (read once,
    2 seeded folds, all 8 candidates trained as one stacked vmapped
    program per fold + vectorized scoring, per-fold results persisted)
    vs 2x ONE candidate through the SHIPPED sequential evaluation path
    (MetricEvaluator -> Engine.eval: datasource read, per-fold train,
    batch_predict, QPA metric) on the same data. The BASELINE.json
    `sweep_8pt_x_2seq: 1.0` ceiling is the contract: evaluating 8
    param points must cost less than evaluating 2 sequentially —
    batching must amortize the read/layout/dispatch work at least 4x.
    Both arms best-of-3 on the same box moments apart, measured AFTER a
    warm-up rep so XLA compiles (persistent-cached anyway) drop out."""
    import numpy as np

    from pio_tpu.controller import EngineParams
    from pio_tpu.controller.evaluation import MetricEvaluator
    from pio_tpu.data import DataMap, Event
    from pio_tpu.data.dao import App
    from pio_tpu.data.storage import Storage
    from pio_tpu.models.recommendation import (
        ALSAlgorithmParams, DataSourceParams, RecommendationEngine,
    )
    from pio_tpu.tuning import SweepConfig, parse_metric
    from pio_tpu.tuning.sweep import SweepRunner
    from pio_tpu.workflow.context import create_workflow_context

    n_users, n_items, n_events = 400, 100, 6_000
    storage = Storage(env={
        "PIO_STORAGE_SOURCES_M_TYPE": "memory",
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "M",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "M",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "M",
    })
    app_id = storage.get_metadata_apps().insert(App(0, "sweepapp"))
    ev = storage.get_events()
    ev.init(app_id)
    rng = np.random.default_rng(0)
    ev.insert_batch([
        Event(event="rate", entity_type="user",
              entity_id=f"u{rng.integers(0, n_users)}",
              target_entity_type="item",
              target_entity_id=f"i{rng.integers(0, n_items)}",
              properties=DataMap({"rating": int(rng.integers(1, 6))}))
        for _ in range(n_events)
    ], app_id)
    engine = RecommendationEngine.apply()
    ds = DataSourceParams(app_name="sweepapp", eval_k=2)
    regs = (0.01, 0.02, 0.05, 0.1, 0.2, 0.5, 1.0, 2.0)
    candidates = [
        EngineParams(
            datasource=("", ds),
            algorithms=[("als", ALSAlgorithmParams(
                rank=8, num_iterations=2, lambda_=reg, chunk=2048))],
        )
        for reg in regs
    ]
    ctx = create_workflow_context(storage, use_mesh=False)
    metric = parse_metric("map@10")

    def seq_once():
        # the shipped sequential arm: ONE candidate, full pipeline
        return MetricEvaluator(metric).evaluate_base(
            ctx, engine, [candidates[0]])

    run_counter = [0]

    def sweep_once():
        run_counter[0] += 1
        config = SweepConfig(metric=parse_metric("map@10"),
                             split="kfold", folds=2, seed=42)
        runner = SweepRunner(
            engine, candidates, storage, config,
            eval_id=f"bench-sweep-{run_counter[0]}")
        return runner.run(ctx)

    seq_once()
    sweep_once()   # warm-up: compiles drop out of both arms
    t_seq = []
    for _ in range(3):
        t0 = time.perf_counter()
        seq_once()   # metric .calculate forces every score to host
        t_seq.append(time.perf_counter() - t0)
    t_sweep = []
    for _ in range(3):
        t0 = time.perf_counter()
        sweep_once()
        t_sweep.append(time.perf_counter() - t0)
    best_seq, best_sweep = min(t_seq), min(t_sweep)
    return {
        "n_candidates": len(regs),
        "folds": 2,
        "seq_one_candidate_ms": round(best_seq * 1e3, 1),
        "batched_sweep_ms": round(best_sweep * 1e3, 1),
        "x_2seq": round(best_sweep / (2 * best_seq), 4),
    }


def _smoke_tracing_cell(http, qs) -> dict:
    """Tracing-overhead cell (ISSUE 9): serving p50/p99 with the
    TraceRecorder enabled vs disabled on the SAME warm server — the
    recorder is detached/reattached, so model, compiled executables,
    socket, and box state are identical and the delta is the recorder
    alone (micro-measured at ~10us/span; ~5 spans/query). The two arms
    interleave PER QUERY (on, off, on, off, ...) so scheduler drift on
    a loaded 2-core box hits both arms equally, and the rep-level
    ratio is taken as the MIN over 5 reps: recorder overhead is a
    constant additive cost, so noise can only inflate a rep's ratio —
    the min approaches the true overhead. The gate (BASELINE.json
    `tracing_overhead_p50_x`, absolute, never --update-baseline'd)
    holds it to <= 5% p50, so observability can never silently tax the
    hot path."""
    import urllib.request

    app = http.app
    recorder = getattr(app, "recorder", None)
    tracer_recorder = qs.tracer.recorder

    def set_tracing(on: bool) -> None:
        app.recorder = recorder if on else None
        qs.tracer.recorder = tracer_recorder if on else None

    def p50(lat: list) -> float:
        lat = sorted(lat)
        return lat[len(lat) // 2] * 1e3

    def rep(port: int) -> tuple[float, float, float, float]:
        # same query mix as the serving_p50_ms cell (users vary), so
        # the ratio's denominator IS the gated serving p50, not a
        # warm-cache fast path that would inflate relative overhead
        on, off = [], []
        for r in range(260):
            set_tracing(r % 2 == 0)
            body = json.dumps(
                {"user": f"u{(r // 2) % 200}", "num": 10}).encode()
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/queries.json", data=body,
                method="POST")
            t0 = time.monotonic()
            with urllib.request.urlopen(req, timeout=30) as resp:
                resp.read()
            if r >= 20:
                (on if r % 2 == 0 else off).append(
                    time.monotonic() - t0)
        on.sort()
        off.sort()
        return (p50(on), p50(off),
                on[max(0, int(len(on) * 0.99) - 1)] * 1e3,
                off[max(0, int(len(off) * 0.99) - 1)] * 1e3)

    try:
        reps = [rep(http.port) for _ in range(5)]
    finally:
        set_tracing(True)
    best = min(reps, key=lambda t: (t[0] / t[1]) if t[1] > 0 else 1e9)
    p50_on, p50_off, p99_on, p99_off = best
    return {
        "p50_on_ms": round(p50_on, 3),
        "p50_off_ms": round(p50_off, 3),
        "p99_on_ms": round(p99_on, 3),
        "p99_off_ms": round(p99_off, 3),
        "p50_overhead_x": (round(p50_on / p50_off, 4)
                           if p50_off > 0 else None),
        "rep_overheads_x": [round(t[0] / t[1], 4) for t in reps
                            if t[1] > 0],
        "enabled": recorder is not None,
    }


def _smoke_batching_cell(qs) -> dict:
    """Continuous-batching cell (cross-request coalescing): closed-loop
    qps of 8 concurrent workers through a ContinuousBatcher (2 ms
    window) vs the same workers on the per-request path, on the SAME
    warm QueryServer — model, compiled executables, fold-in state, and
    box identical, so the delta is the admission stage alone. Before
    any timing counts, the coalesced answers are asserted BIT-identical
    to the per-request path for a mixed query set (the parity contract
    — a faster batcher that changes answers is a regression, not a
    win). The BASELINE.json `batched_qps_x_solo: 1.0` gate is an
    ABSOLUTE contract FLOOR, never refreshed by --update-baseline:
    coalescing shares one device program across concurrent queries, so
    it must not LOSE throughput to per-request dispatch. The rep-level
    ratio is the MAX over 3 reps: a scheduler stall can only depress a
    rep's batched arm, so the max approaches the true capability."""
    import threading as _threading

    from pio_tpu.serving.batcher import ContinuousBatcher

    batcher = ContinuousBatcher(qs, window_s=0.002, max_batch=32)
    try:
        # parity FIRST: concurrent queries through the coalescer must
        # answer bit-identically to the sequential per-request path
        parity_queries = [
            {"user": f"u{u}", "num": 10} for u in range(12)
        ] + [{"user": "u1", "num": 5, "blackList": ["i3"]},
             {"user": "nobody", "num": 4}]
        want = [qs.query(dict(q)) for q in parity_queries]
        got = [None] * len(parity_queries)

        def one(i):
            got[i] = batcher.query(dict(parity_queries[i]))

        threads = [_threading.Thread(target=one, args=(i,))
                   for i in range(len(parity_queries))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert got == want, "coalesced answers diverged from solo"

        def workload(call) -> tuple[float, float]:
            n_workers, per = 8, 30
            lat: list[float] = []
            lock = _threading.Lock()

            def worker(w):
                for r in range(per):
                    q = {"user": f"u{(w * per + r) % 200}", "num": 10}
                    t0 = time.monotonic()
                    call(q)
                    dt = time.monotonic() - t0
                    with lock:
                        lat.append(dt)

            t0 = time.monotonic()
            ws = [_threading.Thread(target=worker, args=(w,))
                  for w in range(n_workers)]
            for t in ws:
                t.start()
            for t in ws:
                t.join()
            wall = time.monotonic() - t0
            lat.sort()
            p99 = lat[max(0, int(len(lat) * 0.99) - 1)] * 1e3
            return (n_workers * per) / wall, p99

        reps = []
        for _ in range(3):
            solo_qps, solo_p99 = workload(qs.query)
            bat_qps, bat_p99 = workload(batcher.query)
            reps.append((bat_qps / solo_qps if solo_qps > 0 else None,
                         solo_qps, bat_qps, solo_p99, bat_p99))
        best = max(reps, key=lambda t: t[0] or 0.0)
        st = batcher.stats()
        return {
            "qps_x_solo": round(best[0], 4) if best[0] else None,
            "solo_qps": round(best[1], 1),
            "batched_qps": round(best[2], 1),
            "solo_p99_ms": round(best[3], 3),
            "batched_p99_ms": round(best[4], 3),
            "rep_ratios_x": [round(t[0], 4) for t in reps if t[0]],
            "mean_occupancy": st["meanOccupancy"],
            "dispatches": st["dispatches"],
            "coalesced_queries": st["coalescedQueries"],
        }
    finally:
        batcher.close()


def _smoke_retrieval_cell() -> dict:
    """Two-stage retrieval cell (ISSUE 19 acceptance): p99 of the
    clustered+int8 candidate tier vs the exact-f32 oracle einsum over
    the SAME warm device-resident tables, arms measured moments apart
    in one process (an HTTP hop would add an identical constant to
    both arms and mask the tier under test — the contract here is the
    scan itself). BASELINE.json `retrieval_p99_x_exact: 1.0` is an
    ABSOLUTE ceiling, never refreshed by --update-baseline: a clustered
    scan that loses to brute force has regressed into overhead.

    Catalog: 128k items x rank 64, a 64-center mixture (items cluster —
    the structure real catalogs have and k-means exists to exploit);
    131k is the smallest catalog where the scan's win clears dispatch
    overhead on a CPU CI box (measured: ratio ~0.31 at nprobe=16/512
    clusters, ~0.88 at nprobe=32; below ~64k items brute force wins on
    CPU and the whole tier should stay off — docs/performance.md).
    recall@10 over 128 users is asserted >= 0.95 BEFORE any timing
    counts and reported alongside, so the ratio can never be bought
    with a recall regression."""
    import numpy as np

    from pio_tpu.ops import als
    from pio_tpu.ops import retrieval as rt

    n_items, rank, n_users = 131072, 64, 256
    rng = np.random.default_rng(7)
    centers = rng.normal(size=(64, rank)).astype(np.float32)
    itf = (centers[rng.integers(0, 64, n_items)]
           + 0.25 * rng.normal(size=(n_items, rank))).astype(np.float32)
    uf = (centers[rng.integers(0, 64, n_users)]
          + 0.25 * rng.normal(size=(n_users, rank))).astype(np.float32)
    # nprobe=16 of 512 clusters: the cell pins a scan fraction (1/32)
    # deep enough to show the win; serving defaults (nprobe=32) are
    # tuned for recall on trained factors, not for this cell
    params = rt.RetrievalParams(mode="clustered", dtype="int8",
                                nprobe=16, rerank_k=512)
    idx = rt.build_index(itf, params)
    didx = rt.build_device_index(idx)
    import jax

    itf_dev = jax.device_put(itf)
    model = als.ALSModel(jax.device_put(uf), itf_dev)

    def exact_one(i: int):
        _, ix = als.recommend_topk(model, np.array([i % n_users]), 10)
        return np.asarray(ix)[0]

    def clustered_one(i: int):
        _, ix = rt.candidate_topk(didx, itf_dev, uf[i % n_users], 10)
        return ix[0]

    exact_one(0)
    clustered_one(0)  # warm: both arms' jits compiled before timing
    hits = 0
    for i in range(128):
        want = set(int(x) for x in exact_one(i))
        got = set(int(x) for x in clustered_one(i) if x >= 0)
        hits += len(want & got)
    recall = hits / (128 * 10)
    if recall < 0.95:
        raise AssertionError(
            f"retrieval cell recall@10 {recall:.3f} < 0.95 at "
            f"nprobe={params.nprobe} — no timing is comparable when "
            "the candidate tier drops the answers")

    def p99(f) -> float:
        lat = []
        for i in range(100):
            t0 = time.monotonic()
            f(i)
            lat.append(time.monotonic() - t0)
        lat.sort()
        return lat[max(0, int(len(lat) * 0.99) - 1)] * 1e3

    # exact arm first ("measured moments earlier"), best-of-3 each
    e99 = min(p99(exact_one) for _ in range(3))
    c99 = min(p99(clustered_one) for _ in range(3))
    qbytes = idx.nbytes()
    fbytes = itf.nbytes
    return {
        "exact_p99_ms": round(e99, 3),
        "clustered_p99_ms": round(c99, 3),
        "p99_x_exact": round(c99 / e99, 4) if e99 > 0 else None,
        "recall_at_10": round(recall, 4),
        "n_items": n_items,
        "nprobe": params.nprobe,
        "quantized_bytes": qbytes,
        "f32_bytes": fbytes,
        "hbm_cut_x": round(fbytes / qbytes, 2) if qbytes else None,
    }


def _smoke_fleet_cell(storage, one_rep, single_p99_ms: float,
                      oracle) -> dict:
    """Fleet serving cell (the remaining ROADMAP item 1 measurement +
    the ISSUE 15 internal-RPC-plane contract): the same query stream
    through a 2-shard fleet router, best-of-3 p50/p99, against the
    single-host numbers measured moments earlier on the same box (so
    host noise largely cancels). Two gates ride this cell:

      * BASELINE.json `fleet_p99_x_single_host` bounds the ROUTER TAIL:
        router p99 must stay within 2x the single-host oracle's p99 —
        sharding buys capacity with two RPC hops, and this cell keeps
        those hops honest on every PR;
      * BASELINE.json `pooled_binary_fleet_p99_x_fresh_json` (absolute
        1.0 ceiling, never --update-baseline'd) pits the DEFAULT router
        (keep-alive pooled connections + binary top-k wire) against a
        control router over the SAME warm shards with pooling off and
        the JSON wire pinned — i.e. the pre-ISSUE-15 RPC plane,
        measured moments earlier. The pooled+binary plane must win
        outright, and both arms' answers are asserted BIT-identical to
        the single-host oracle before any timing counts."""
    import urllib.request

    from pio_tpu.serving_fleet.fleet import deploy_fleet
    from pio_tpu.serving_fleet.router import (
        RouterConfig, create_fleet_router,
    )

    handle = deploy_fleet(storage, engine_id="smoke", n_shards=2,
                          n_replicas=1)
    json_http = json_router = None
    try:
        port = handle.router_http.port
        one_rep(port)  # warm: first queries pay jit on each shard
        # the control arm: fresh connection per RPC + JSON wire, over
        # the SAME shard processes (same warm kernels, same box moment)
        json_http, json_router = create_fleet_router(
            storage,
            RouterConfig(engine_id="smoke", rpc_wire="json",
                         http_pooled=False, probe_interval_s=0),
            handle.plan, handle.endpoints)
        json_http.start()
        jport = json_http.port
        one_rep(jport)

        def answer(p: int, user: str) -> dict:
            q = json.dumps({"user": user, "num": 10}).encode()
            req = urllib.request.Request(
                f"http://127.0.0.1:{p}/queries.json", data=q,
                method="POST")
            with urllib.request.urlopen(req, timeout=30) as resp:
                return json.loads(resp.read())

        # bit-parity gate before any timing: both wires must reproduce
        # the single-host oracle exactly
        for u in ("u0", "u7", "u42", "u133"):
            want = oracle({"user": u, "num": 10})
            got_binary = answer(port, u)
            got_json = answer(jport, u)
            if got_binary != want or got_json != want:
                raise AssertionError(
                    f"fleet answer diverged from the single-host oracle "
                    f"for {u}: binary={got_binary!r} json={got_json!r} "
                    f"oracle={want!r}")
        # fresh-connection JSON arm FIRST ("measured moments earlier"),
        # then the pooled+binary default — best-of-3 each
        jp50, jp99 = min((one_rep(jport) for _ in range(3)),
                         key=lambda t: t[1])
        p50, p99 = min((one_rep(port) for _ in range(3)),
                       key=lambda t: t[1])
    finally:
        if json_router is not None:
            json_http.stop()
            json_router.close()
        handle.close()
    return {
        "router_p50_ms": round(p50, 3),
        "router_p99_ms": round(p99, 3),
        "single_p99_ms": round(single_p99_ms, 3),
        "p99_x_single_host": round(p99 / single_p99_ms, 3)
        if single_p99_ms > 0 else None,
        "fresh_json_p50_ms": round(jp50, 3),
        "fresh_json_p99_ms": round(jp99, 3),
        "pooled_binary_p99_x_fresh_json": round(p99 / jp99, 4)
        if jp99 > 0 else None,
    }


def _smoke_tenant_cell(storage, oracle) -> dict:
    """Noisy-neighbor cell (ISSUE 18 acceptance): two tenants on one
    2-shard multi-tenant pool — the VICTIM's p99 while a co-tenant
    floods at >10x its own quota, against the victim's SOLO p99 on the
    same multi-tenant plane measured moments earlier on the same box.
    BASELINE.json `tenant_victim_p99_x_solo` bounds the ratio as an
    ABSOLUTE ceiling, never refreshed by --update-baseline: per-tenant
    token-bucket admission must stop the flooder at its own 429 wall
    before the victim's tail moves. Before any timing counts, the
    victim's answers are asserted BIT-identical to the single-host
    oracle and the victim stream must be zero-5xx AND zero-429 —
    isolation that merely rate-limits everyone would fail here."""
    import threading
    import urllib.error
    import urllib.request

    import numpy as np

    from pio_tpu.controller import EngineParams
    from pio_tpu.data import DataMap, Event
    from pio_tpu.data.dao import App
    from pio_tpu.models.recommendation import (
        ALSAlgorithmParams, DataSourceParams, RecommendationEngine,
    )
    from pio_tpu.serving_fleet.tenancy import (
        TenantSpec, deploy_multi_fleet, join_fleet_plan, tenant_key,
    )
    from pio_tpu.workflow.context import create_workflow_context
    from pio_tpu.workflow.train import run_train

    # a second tiny engine to play the flooder tenant
    app_id = storage.get_metadata_apps().insert(App(0, "smokebapp"))
    ev = storage.get_events()
    ev.init(app_id)
    rng = np.random.default_rng(1)
    uu = rng.integers(0, 40, 400)
    ii = rng.integers(0, 12, 400)
    ev.insert_batch([
        Event(event="rate", entity_type="user", entity_id=f"u{uu[m]}",
              target_entity_type="item", target_entity_id=f"i{ii[m]}",
              properties=DataMap({"rating": int(rng.integers(1, 6))}))
        for m in range(400)
    ], app_id)
    engine_b = RecommendationEngine.apply()
    ep_b = EngineParams(
        datasource=("", DataSourceParams(app_name="smokebapp")),
        algorithms=[("als", ALSAlgorithmParams(
            rank=4, num_iterations=2, lambda_=0.05, chunk=1024))],
    )
    ctx_b = create_workflow_context(storage, use_mesh=False)
    run_train(engine_b, ep_b, storage, engine_id="smokeb", ctx=ctx_b)

    victim, flooder = tenant_key("smoke"), tenant_key("smokeb")
    # the flooder's contract: 20 qps; the flood below attempts far more
    join_fleet_plan(storage, "smokepool", TenantSpec("smoke"),
                    n_shards=2, n_replicas=1)
    join_fleet_plan(storage, "smokepool",
                    TenantSpec("smokeb", quota_qps=20.0,
                               quota_burst=20.0),
                    n_shards=2, n_replicas=1)
    handle = deploy_multi_fleet(storage, "smokepool")
    flood_stats = {"attempts": 0, "shed": 0, "ok": 0, "other": 0}
    stop = threading.Event()
    try:
        port = handle.router_http.port

        def ask(tenant: str, user: str) -> tuple[int, bytes]:
            q = json.dumps({"user": user, "num": 10}).encode()
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/queries.json", data=q,
                method="POST", headers={"X-Pio-Tenant": tenant})
            try:
                with urllib.request.urlopen(req, timeout=30) as resp:
                    return resp.status, resp.read()
            except urllib.error.HTTPError as e:
                return e.code, e.read()

        def victim_rep() -> float:
            lat = []
            for r in range(100):
                t0 = time.monotonic()
                code, _ = ask(victim, f"u{r % 200}")
                if code != 200:
                    raise AssertionError(
                        f"victim tenant got {code} — isolation broken")
                if r >= 20:
                    lat.append(time.monotonic() - t0)
            lat.sort()
            return lat[max(0, int(len(lat) * 0.99) - 1)] * 1e3

        # warm both tenants' shards (first queries pay jit), then the
        # bit-parity gate before any timing
        victim_rep()
        ask(flooder, "u0")
        for u in ("u0", "u7", "u42", "u133"):
            want = oracle({"user": u, "num": 10})
            got = json.loads(ask(victim, u)[1])
            if got != want:
                raise AssertionError(
                    f"multi-tenant victim answer diverged from the "
                    f"single-host oracle for {u}: {got!r} != {want!r}")

        solo_p99 = min(victim_rep() for _ in range(3))

        def flood():
            while not stop.is_set():
                code, _ = ask(flooder, "u1")
                flood_stats["attempts"] += 1
                if code == 429:
                    flood_stats["shed"] += 1
                elif code == 200:
                    flood_stats["ok"] += 1
                else:
                    flood_stats["other"] += 1
                stop.wait(0.002)  # ~500/s/thread: >10x the 20 qps quota

        threads = [threading.Thread(target=flood, daemon=True)
                   for _ in range(2)]
        for t in threads:
            t.start()
        try:
            flood_p99 = min(victim_rep() for _ in range(3))
        finally:
            stop.set()
            for t in threads:
                t.join(timeout=10)
    finally:
        stop.set()
        handle.close()
    if flood_stats["shed"] == 0:
        raise AssertionError(
            f"flooder was never shed — the per-tenant quota did not "
            f"engage ({flood_stats})")
    return {
        "victim_p99_solo_ms": round(solo_p99, 3),
        "victim_p99_flood_ms": round(flood_p99, 3),
        "victim_p99_x_solo": round(flood_p99 / solo_p99, 3)
        if solo_p99 > 0 else None,
        "flood_attempts": flood_stats["attempts"],
        "flood_shed_429": flood_stats["shed"],
        "flood_admitted": flood_stats["ok"],
        "flood_other": flood_stats["other"],
    }


def _smoke_freshness_cell(storage, ev, app_id, qs, port: int,
                          n_users: int) -> dict:
    """Freshness cell for the smoke gate (ISSUE 7 acceptance): under a
    STEADY ingest load, measure event-ingest → servable for a
    brand-new user — insert their first events, then poll the live
    query endpoint until the answer flips from the cold (popularity /
    zero-row) response to the folded personalized one. The fold-in
    worker is warmed first (the load's own fold-ins compile the pow2
    buckets), matching production where the persistent compile cache
    (PR 4) makes even a restarted folder warm; the measured number is
    the steady-state freshness the < 5 s contract bounds."""
    import tempfile
    import threading
    import urllib.request

    import numpy as np

    from pio_tpu.data import DataMap, Event
    from pio_tpu.freshness import (
        FoldInConfig, FoldInWorker, LocalServingApplier,
    )
    from pio_tpu.ops import als
    from pio_tpu.utils.time import utcnow

    def query(user: str) -> bytes:
        q = json.dumps({"user": user, "num": 5}).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/queries.json", data=q, method="POST")
        with urllib.request.urlopen(req, timeout=30) as resp:
            return resp.read()

    rng = np.random.default_rng(1)
    stop = threading.Event()

    def steady_load():
        # ~200 ev/s of fresh interactions for EXISTING users: the
        # folder keeps folding (and stays warm) for the whole cell, so
        # the new user's measurement shares its batch with real work
        while not stop.is_set():
            u, i = rng.integers(0, n_users), rng.integers(0, 60)
            ev.insert(Event(
                event="rate", entity_type="user", entity_id=f"u{u}",
                target_entity_type="item", target_entity_id=f"i{i}",
                properties=DataMap({"rating": int(rng.integers(1, 6))}),
                event_time=utcnow()), app_id)
            stop.wait(0.005)

    with tempfile.TemporaryDirectory() as td:
        worker = FoldInWorker(
            storage,
            FoldInConfig(
                app_name="smokeapp", engine_id="smoke",
                als_params=als.ALSParams(rank=16, reg=0.05),
                state_path=os.path.join(td, "cursor.bin"),
                poll_interval_s=0.05, staleness_budget_s=5.0),
            LocalServingApplier(qs))
        loader = threading.Thread(target=steady_load, daemon=True)
        worker.start()
        loader.start()
        try:
            # warm: wait for the load's first fold-ins to land (compiles
            # the fold kernel + upsert path once, like a warm folder)
            t0 = time.perf_counter()
            while worker.folded_total == 0:
                if time.perf_counter() - t0 > 120:
                    raise AssertionError(
                        "fold-in worker never applied under steady load: "
                        f"{worker.snapshot()}")
                time.sleep(0.02)
            warm_s = time.perf_counter() - t0
            new_user = "fresh-smoke-user"
            cold = query(new_user)   # popularity fallback baseline
            t0 = time.perf_counter()
            for item, rating in (("i1", 5), ("i3", 5), ("i7", 1)):
                ev.insert(Event(
                    event="rate", entity_type="user", entity_id=new_user,
                    target_entity_type="item", target_entity_id=item,
                    properties=DataMap({"rating": rating}),
                    event_time=utcnow()), app_id)
            while query(new_user) == cold:
                if time.perf_counter() - t0 > 60:
                    raise AssertionError(
                        "new user's fold-in never became servable: "
                        f"{worker.snapshot()}")
                time.sleep(0.02)
            fresh_s = time.perf_counter() - t0
        finally:
            stop.set()
            loader.join(timeout=5)
            worker.stop()
        snap = worker.snapshot()
    return {
        # ingest→query for a brand-new user, the < 5 s acceptance bound
        "new_user_seconds": round(fresh_s, 3),
        # cold-folder warmup (first fold compile) — a canary, not gated
        "first_fold_seconds": round(warm_s, 3),
        "folded_total": snap["foldedTotal"],
        "applied_batches": snap["appliedBatches"],
        "queue_depth_at_end": snap["queueDepth"],
    }


def _smoke_binary_ingest_cell() -> dict:
    """Binary-wire ingest vs the native C++ path (ISSUE 11 acceptance):
    the Python pipeline fed by columnar frames must beat the eventlog
    backend's fused C parse+append fed by JSON — the PR 4 contest
    (0.86x with JSON still on the wire), settled past 1.0 by taking the
    JSON decode off the wire entirely. Both arms are best-of-3 on the
    same box moments apart so host noise cancels; the ratio is the
    BASELINE.json `binary_ingest_x_native` absolute contract floor
    (never --update-baseline'd). A rig without a C++ toolchain reports
    x_native None — and fails the gate, because the contract cannot be
    demonstrated there."""
    import shutil
    import tempfile

    mem_env = {
        "PIO_STORAGE_SOURCES_M_TYPE": "memory",
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "M",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "M",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "M",
    }
    binary = max((_ingest_once(mem_env, wire="binary") for _ in range(3)),
                 key=lambda r: r["events_per_sec"])
    out: dict = {
        "binary_events_per_sec": binary["events_per_sec"],
        "shed_events": binary["shed_events"],
        "retried_batches": binary["retried_batches"],
    }
    eldir = tempfile.mkdtemp(prefix="pio_smoke_el_")
    try:
        native = max(
            (_ingest_once({
                "PIO_STORAGE_SOURCES_EL_TYPE": "eventlog",
                "PIO_STORAGE_SOURCES_EL_PATH": eldir,
                "PIO_STORAGE_SOURCES_M_TYPE": "memory",
                "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "M",
                "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "EL",
                "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "M",
            }) for _ in range(3)),
            key=lambda r: r["events_per_sec"])
        out["native_events_per_sec"] = native["events_per_sec"]
        out["x_native"] = round(
            binary["events_per_sec"] / native["events_per_sec"], 3)
    except Exception as e:  # noqa: BLE001 - no C++ toolchain on this rig
        out["native_events_per_sec"] = None
        out["x_native"] = None
        out["native_error"] = str(e)[:300]
    finally:
        shutil.rmtree(eldir, ignore_errors=True)
    return out


def _smoke_replicated_ingest_cell(single_eps: float) -> dict:
    """Replicated-store ingest overhead (ISSUE 12 acceptance): the same
    binary-wire ingest as the single-backend cell, through a
    ReplicatedEventsDAO fanning every batch to R=3 in-process memory
    replicas at W=2. The ratio vs the single-backend number measured
    moments earlier on the same box is the BASELINE.json
    `replicated_ingest_x_single` absolute contract FLOOR (0.7, never
    --update-baseline'd): replication durability may cost at most 30%
    of ingest throughput on this profile."""
    import shutil
    import tempfile

    hint_dir = tempfile.mkdtemp(prefix="pio_smoke_hints_")
    env = {
        "PIO_STORAGE_SOURCES_M_TYPE": "memory",
        "PIO_STORAGE_SOURCES_R_TYPE": "replicated",
        "PIO_STORAGE_SOURCES_R_TYPES": "memory,memory,memory",
        "PIO_STORAGE_SOURCES_R_WRITE_QUORUM": "2",
        "PIO_STORAGE_SOURCES_R_HINT_DIR": hint_dir,
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "M",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "R",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "M",
    }
    try:
        repl = max((_ingest_once(env, wire="binary") for _ in range(3)),
                   key=lambda r: r["events_per_sec"])
    finally:
        shutil.rmtree(hint_dir, ignore_errors=True)
    return {
        "replicated_events_per_sec": repl["events_per_sec"],
        "single_events_per_sec": single_eps,
        "x_single": (round(repl["events_per_sec"] / single_eps, 3)
                     if single_eps else None),
        "replicas": 3,
        "write_quorum": 2,
        "shed_events": repl["shed_events"],
        "retried_batches": repl["retried_batches"],
    }


PHASES = {
    "probe": phase_probe,
    "train": phase_train,
    "cpu": phase_cpu,
    "serving": phase_serving,
    "ingest": phase_ingest,
    "smoke": phase_smoke,
}


# ---------------------------------------------------------------------------
# orchestration (no jax in this process)
# ---------------------------------------------------------------------------

def run_phase(name: str, timeout: float, env_extra: dict | None = None,
              diagnose: bool = False):
    """-> (result_dict | None, error_string | None).

    With diagnose=True the child writes a lifecycle stage trail
    (import -> device claim -> compile -> run) to a temp file; on
    timeout the trail is folded into the error string, so the artifact
    records WHERE the phase died."""
    import tempfile

    env = dict(os.environ)
    env.update(env_extra or {})
    progress = None
    if diagnose:
        fd, progress = tempfile.mkstemp(prefix=f"pio_bench_{name}_",
                                        suffix=".stages")
        os.close(fd)
        env["PIO_PROBE_PROGRESS"] = progress
    argv = [sys.executable, os.path.abspath(__file__), "--phase", name]
    if SMALL:
        argv.append("--small")
    try:
        out = subprocess.run(argv, capture_output=True, text=True,
                             timeout=timeout, env=env,
                             cwd=os.path.dirname(os.path.abspath(__file__)))
    except subprocess.TimeoutExpired:
        diag = ""
        if progress:
            from pio_tpu.utils.tpu_health import classify_hang, read_stages

            stages = read_stages(progress)
            diag = " " + classify_hang(stages)
            if stages:
                diag += " trail=" + ",".join(
                    f"{s.get('stage')}@{s.get('t')}s" for s in stages)
            os.unlink(progress)
        return None, f"{name}: timeout after {timeout}s{diag}"
    finally:
        if progress and os.path.exists(progress):
            os.unlink(progress)
    if out.returncode != 0:
        tail = (out.stderr or out.stdout or "").strip()[-800:]
        return None, f"{name}: rc={out.returncode}: {tail}"
    for line in reversed((out.stdout or "").strip().splitlines()):
        try:
            obj = json.loads(line)
            if isinstance(obj, dict):
                return obj, None
        except json.JSONDecodeError:
            continue
    return None, f"{name}: no JSON in output: {(out.stdout or '')[-300:]}"


CPU_ENV = {"PIO_BENCH_PLATFORM": "cpu"}


def smoke_main() -> int:
    """`python bench.py --smoke` — the CI perf gate. Runs phase_smoke in
    a CPU subprocess and compares the CPU-stable metrics against
    BASELINE.json's published.smoke block with a +-PIO_SMOKE_TOL band
    (default 0.20): ingest must not be > tol slower, serving p50 not
    > tol higher. rc 1 on regression so the gate fails PRs.
    --update-baseline rewrites the block from this run."""
    tol = float(os.environ.get("PIO_SMOKE_TOL", "0.20"))
    baseline_path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "BASELINE.json")
    res, err = run_phase("smoke", 900, CPU_ENV)
    if res is None:
        print(json.dumps({"smoke": "error", "error": err}))
        return 1
    with open(baseline_path) as f:
        baseline_doc = json.load(f)
    if "--update-baseline" in sys.argv:
        # MERGE into the block: extra keys (the committed floors carry a
        # methodology note explaining they are deliberate conservative
        # floors, not point measurements) must survive a refresh
        block = baseline_doc.setdefault("published", {}).setdefault(
            "smoke", {})
        block.update(
            ingest_events_per_sec=res["ingest_events_per_sec"],
            serving_p50_ms=res["serving_p50_ms"],
        )
        with open(baseline_path, "w") as f:
            json.dump(baseline_doc, f, indent=2)
            f.write("\n")
        print(json.dumps({
            "smoke": "baseline-updated", "measured": res,
            "warning": "values are now THIS rig's point measurements — "
                       "see the block's note about conservative floors "
                       "before committing them"}))
        return 0
    base = (baseline_doc.get("published") or {}).get("smoke")
    if not base:
        print(json.dumps({
            "smoke": "no-baseline", "measured": res,
            "hint": "run `python bench.py --smoke --update-baseline`"}))
        return 1
    checks = {
        "ingest_events_per_sec": (
            res["ingest_events_per_sec"],
            base["ingest_events_per_sec"],
            res["ingest_events_per_sec"]
            >= base["ingest_events_per_sec"] * (1 - tol)),
        "serving_p50_ms": (
            res["serving_p50_ms"], base["serving_p50_ms"],
            res["serving_p50_ms"] <= base["serving_p50_ms"] * (1 + tol)),
    }
    if "freshness_new_user_seconds" in base:
        # the freshness bound is a CONTRACT ceiling (ISSUE 7: < 5 s
        # ingest→query for a brand-new user on the 2-core profile), not
        # a rig measurement — compared absolutely, no tolerance band,
        # and --update-baseline never rewrites it
        checks["freshness_new_user_seconds"] = (
            res["freshness_new_user_seconds"],
            base["freshness_new_user_seconds"],
            res["freshness_new_user_seconds"]
            <= base["freshness_new_user_seconds"])
    if "fleet_p99_x_single_host" in base:
        # the fleet tail bound is a CONTRACT ceiling too (ROADMAP item
        # 1: router p99 within 2x the single-host oracle, both measured
        # best-of-3 on the same box moments apart so host noise
        # cancels) — compared absolutely, never refreshed by
        # --update-baseline
        checks["fleet_p99_x_single_host"] = (
            res["fleet_p99_x_single_host"],
            base["fleet_p99_x_single_host"],
            res["fleet_p99_x_single_host"] is not None
            and res["fleet_p99_x_single_host"]
            <= base["fleet_p99_x_single_host"])
    if "pooled_binary_fleet_p99_x_fresh_json" in base:
        # ISSUE 15 contract CEILING, absolute and never refreshed by
        # --update-baseline: the pooled+binary internal RPC plane
        # (keep-alive connection pool + binary top-k wire — the
        # default) must beat the fresh-connection JSON control arm's
        # p99 on the same warm fleet measured moments earlier, with
        # both arms' answers asserted bit-identical to the single-host
        # oracle first. A pooled plane that lost to dial-per-RPC JSON
        # would mean the pool or codec regressed into overhead.
        checks["pooled_binary_fleet_p99_x_fresh_json"] = (
            res["pooled_binary_fleet_p99_x_fresh_json"],
            base["pooled_binary_fleet_p99_x_fresh_json"],
            res["pooled_binary_fleet_p99_x_fresh_json"] is not None
            and res["pooled_binary_fleet_p99_x_fresh_json"]
            <= base["pooled_binary_fleet_p99_x_fresh_json"])
    if "tenant_victim_p99_x_solo" in base:
        # ISSUE 18 contract CEILING, absolute and never refreshed by
        # --update-baseline: a victim tenant's p99 while a co-tenant
        # floods the shared 2-shard pool at >10x its own quota must
        # stay within this multiple of the victim's solo p99 on the
        # SAME multi-tenant plane measured moments earlier (victim
        # answers bit-identical to the single-host oracle, zero 5xx,
        # zero 429, flooder provably shed at its 429 wall first). A
        # shared token bucket or a shed path that queues instead of
        # failing fast would blow this ratio — the noisy-neighbor
        # regression class this gate exists to catch.
        checks["tenant_victim_p99_x_solo"] = (
            res["tenant_victim_p99_x_solo"],
            base["tenant_victim_p99_x_solo"],
            res["tenant_victim_p99_x_solo"] is not None
            and res["tenant_victim_p99_x_solo"]
            <= base["tenant_victim_p99_x_solo"])
    if "batched_qps_x_solo" in base:
        # continuous-batching contract FLOOR, absolute and never
        # refreshed by --update-baseline: closed-loop qps through the
        # coalescing admission stage vs the per-request path on the
        # SAME warm server (answers asserted bit-identical first) must
        # not drop below 1.0x — sharing one device program across
        # concurrent queries may never cost throughput, or the
        # admission stage has regressed into overhead.
        checks["batched_qps_x_solo"] = (
            res["batched_qps_x_solo"],
            base["batched_qps_x_solo"],
            res["batched_qps_x_solo"] is not None
            and res["batched_qps_x_solo"]
            >= base["batched_qps_x_solo"])
    if "binary_ingest_x_native" in base:
        # ISSUE 11 contract FLOOR (ROADMAP item 4), absolute and never
        # refreshed by --update-baseline: Python ingest over the binary
        # columnar wire must beat the native C++ JSON path outright
        # (>1.0x), both arms best-of-3 on the same box moments apart. A
        # None measurement (no C++ toolchain) fails — the contract
        # cannot be demonstrated on that rig.
        checks["binary_ingest_x_native"] = (
            res["binary_ingest_x_native"],
            base["binary_ingest_x_native"],
            res["binary_ingest_x_native"] is not None
            and res["binary_ingest_x_native"]
            >= base["binary_ingest_x_native"])
    if "replicated_ingest_x_single" in base:
        # ISSUE 12 contract FLOOR, absolute and never refreshed by
        # --update-baseline: W=2-of-3 replicated binary-wire ingest must
        # hold >= this fraction of the single-backend binary-wire rate,
        # both arms best-of-3 on the same box moments apart. Quorum
        # durability may tax ingest, but a fan-out that serializes or
        # re-encodes per replica would crater this ratio — that is the
        # regression class the gate exists to catch.
        checks["replicated_ingest_x_single"] = (
            res["replicated_ingest_x_single"],
            base["replicated_ingest_x_single"],
            res["replicated_ingest_x_single"] is not None
            and res["replicated_ingest_x_single"]
            >= base["replicated_ingest_x_single"])
    if "tracing_overhead_p50_x" in base:
        # observability-cost CONTRACT ceiling (ISSUE 9): serving p50
        # with the TraceRecorder on must stay within 5% of recorder-off
        # on the SAME warm server (per-query interleaved arms, min
        # ratio over 5 reps, so box drift cancels) — absolute, never
        # refreshed by --update-baseline. The recorder must never
        # silently tax the hot path.
        checks["tracing_overhead_p50_x"] = (
            res["tracing_overhead_p50_x"],
            base["tracing_overhead_p50_x"],
            res["tracing_overhead_p50_x"] is not None
            and res["tracing_overhead_p50_x"]
            <= base["tracing_overhead_p50_x"])
    if "sweep_8pt_x_2seq" in base:
        # ISSUE 13 / ROADMAP item 5 contract CEILING, absolute and
        # never refreshed by --update-baseline: an 8-point BATCHED
        # sweep (stacked vmapped train+score, read amortized) must
        # complete faster than 2x one candidate through the shipped
        # sequential evaluation path on the same data — i.e. batching
        # must amortize at least 4x, or the batched path has regressed
        # into a loop with extra steps.
        checks["sweep_8pt_x_2seq"] = (
            res["sweep_8pt_x_2seq"],
            base["sweep_8pt_x_2seq"],
            res["sweep_8pt_x_2seq"] is not None
            and res["sweep_8pt_x_2seq"] <= base["sweep_8pt_x_2seq"])
    if "retrieval_p99_x_exact" in base:
        # ISSUE 19 contract CEILING, absolute and never refreshed by
        # --update-baseline: the clustered+int8 candidate tier's p99
        # must beat the exact-f32 oracle einsum outright on the same
        # warm device tables (128k-item mixture catalog, recall@10
        # asserted >= 0.95 before timing so the ratio cannot be bought
        # with dropped answers). A clustered scan slower than brute
        # force is pure overhead — the regression class this gate
        # exists to catch.
        checks["retrieval_p99_x_exact"] = (
            res["retrieval_p99_x_exact"],
            base["retrieval_p99_x_exact"],
            res["retrieval_p99_x_exact"] is not None
            and res["retrieval_p99_x_exact"]
            <= base["retrieval_p99_x_exact"])
    ok = all(passed for _, _, passed in checks.values())
    print(json.dumps({
        "smoke": "pass" if ok else "FAIL",
        "tolerance": tol,
        "checks": {
            k: {"measured": m, "baseline": b, "ok": passed}
            for k, (m, b, passed) in checks.items()
        },
        "extra": res,
    }))
    return 0 if ok else 1


def main() -> int:
    errors: dict[str, str] = {}
    extra: dict = {"errors": errors, "small": SMALL}
    value = None
    vs = None

    probe, err = run_phase("probe", PROBE_TIMEOUT, diagnose=True)
    if err or not probe.get("ok"):
        errors["probe"] = err or f"probe: {probe}"
    elif probe["platform"] == "cpu":
        errors["probe"] = (
            "jax found no accelerator (platform cpu): bench.py measures "
            "the chip and does not fall back; --smoke is the CPU gate")
    else:
        extra["platform"] = probe["platform"]
        extra["device_kind"] = probe["device_kind"]
        extra["n_devices"] = probe["n_devices"]
        extra["backend_init_sec"] = probe.get("init_sec")

        train, err = run_phase("train", TRAIN_TIMEOUT, diagnose=True)
        if train:
            value = round(train["rate"], 1)
            extra["train"] = {
                k: train[k] for k in
                ("retrain_rate", "wall_sec", "nnz", "sweeps",
                 "transfer_sec", "exposed_transfer_after_overlap_sec",
                 "warmup_compile_sec", "compile_cache", "fixed_layout_sec",
                 "retrain_residual_sec",
                 "per_sweep_sec", "per_sweep_rate", "flops_per_sweep",
                 "flops_per_sec", "mfu_vs_bf16_peak",
                 "sweep_mfu_vs_bf16_peak", "hbm_bytes_per_sweep",
                 "hbm_bound_sweep_sec", "frac_of_hbm_roofline",
                 "rank", "cg_iters",
                 "cg_warm_iters", "cg_full_sweeps", "accum")
                if k in train
            }
        else:
            errors["train"] = err

        # vs_baseline is defined as TPU-vs-one-CPU-core (BASELINE.md): the
        # same kernel in a child held to the CPU, labelled as such
        if "--no-cpu" not in sys.argv:
            cpu, err = run_phase("cpu", CPU_TIMEOUT, CPU_ENV)
            if cpu:
                extra["cpu_baseline_rate"] = round(cpu["rate"], 1)
                if value:
                    vs = round(value / cpu["rate"], 2)
            else:
                errors["cpu"] = err

        if "--no-serving" not in sys.argv:
            serving, err = run_phase("serving", SERVING_TIMEOUT)
            if serving:
                extra["serving"] = serving
            else:
                errors["serving"] = err

        if "--no-ingest" not in sys.argv:
            # host-bound (event server + storage): runs on the CPU
            ingest, err = run_phase("ingest", INGEST_TIMEOUT, CPU_ENV)
            if ingest:
                extra["ingest"] = ingest
            else:
                errors["ingest"] = err

    if not errors:
        del extra["errors"]
    print(json.dumps({
        "metric": "ALS implicit ratings/sec/chip (ML-20M shape, rank 64)"
        if not SMALL else "ALS implicit ratings/sec/chip (small)",
        "value": value,
        "unit": "ratings/sec",
        "vs_baseline": vs,
        "extra": extra,
    }))
    return 1 if errors else 0


if __name__ == "__main__":
    if "--phase" in sys.argv:
        if os.environ.get("PIO_BENCH_PLATFORM") == "cpu":
            import jax

            jax.config.update("jax_platforms", "cpu")
            jax.config.update("jax_num_cpu_devices", 1)
        name = sys.argv[sys.argv.index("--phase") + 1]
        print(json.dumps(PHASES[name]()))
        sys.exit(0)
    if "--smoke" in sys.argv:
        sys.exit(smoke_main())
    sys.exit(main())
