"""A/B bench for the ALS normal-equation accumulation strategies.

Times `als_train` under each {accum mode x chunk_slots} cell on the
CURRENT backend, in one process, and prints one JSON line per cell plus
a "best" line. Kept for ROADMAP S1d: it is the one tool that runs
`accum="stream"` (the overlapped segment flush, reached by tests only)
beside `accum="hybrid"` (what `auto` runs on the chip) on the same data.
A winner here is a candidate for a benchmark run, not a result.

Usage:
  chiprun -- python eval/als_accum_bench.py [--out PATH]
  JAX_PLATFORMS=cpu python eval/als_accum_bench.py --small
"""

from __future__ import annotations

import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from pio_tpu.ops.als import ALSParams, als_train  # noqa: E402

SMALL = "--small" in sys.argv

# ML-20M shape unless --small
N_USERS = 5_000 if SMALL else 138_493
N_ITEMS = 1_000 if SMALL else 26_744
NNZ = 200_000 if SMALL else 20_000_000
RANK = 16 if SMALL else 64
SWEEPS = 2 if SMALL else 6

CELLS = [
    {"accum": "carry", "chunk_slots": 8192},
    {"accum": "carry", "chunk_slots": 32768},    # fewer carries
    {"accum": "stacked", "chunk_slots": 8192},
    {"accum": "stacked", "chunk_slots": 32768},
    # XLA batched-MXU blocks + Pallas segment-flush scatter: what
    # `auto` runs on a TPU
    {"accum": "hybrid", "chunk_slots": 32768},
    # the overlapped segment flush against the cell above (ROADMAP S1d)
    {"accum": "stream", "chunk_slots": 32768},
]


def main() -> None:
    rng = np.random.default_rng(0)
    users = (rng.zipf(1.2, NNZ) % N_USERS).astype(np.int32)
    items = (rng.zipf(1.2, NNZ) % N_ITEMS).astype(np.int32)
    vals = rng.integers(1, 6, NNZ).astype(np.float32)
    d_users = jax.device_put(users)
    d_items = jax.device_put(items)
    d_vals = jax.device_put(vals)
    float(jnp.sum(d_vals))  # transfer done

    dev = jax.devices()[0]
    results = []
    cells = [
        c for c in CELLS
        if not (c["accum"] in ("hybrid", "stream")
                and dev.platform == "cpu")
        # pallas on CPU runs in interpret mode — a correctness tool
        # (tests/test_als_pallas.py), meaningless to time
    ]
    for cell in cells:
        # cg_warm_iters=-1: this A/B isolates the ACCUMULATION strategy,
        # so every sweep must run the same full-strength CG or the
        # carry/stacked delta is diluted by the warm-CG schedule
        p = ALSParams(
            rank=RANK, iterations=SWEEPS, reg=0.05, alpha=10.0,
            implicit=True, chunk=8192, cg_warm_iters=-1,
            cg_iters=ALSParams(rank=RANK).resolved_cg_iters(N_USERS),
            **cell,
        )
        p1 = ALSParams(**{**p.__dict__, "iterations": 1})

        def run(params):
            m = als_train(d_users, d_items, d_vals, N_USERS, N_ITEMS, params)
            # a scalar readback ends the timed region
            return float(jnp.sum(m.user_factors))

        try:
            run(p)  # compile
            best = float("inf")
            for _ in range(3):
                t0 = time.monotonic()
                run(p)
                best = min(best, time.monotonic() - t0)
            run(p1)
            t0 = time.monotonic()
            run(p1)
            one = time.monotonic() - t0
            per_sweep = (best - one) / max(SWEEPS - 1, 1)
            row = {
                **cell,
                "wall_sec": round(best, 3),
                "per_sweep_sec": round(per_sweep, 4)
                if best > one else None,
                "per_sweep_rate": round(NNZ / per_sweep, 1)
                if best > one else None,
                "sweeps": SWEEPS,
            }
        except Exception as e:  # noqa: BLE001 - OOM cells must not kill the run
            row = {**cell, "error": repr(e)[:300]}
        results.append(row)
        print(json.dumps(row), flush=True)

    ok = [r for r in results if "error" not in r]
    best = min(ok, key=lambda r: r["wall_sec"]) if ok else None
    summary = {
        "device_kind": dev.device_kind,
        "platform": dev.platform,
        "shape": {"n_users": N_USERS, "n_items": N_ITEMS, "nnz": NNZ,
                  "rank": RANK},
        "cells": results,
        "best": best,
    }
    print(json.dumps({"best": best}))
    out = None
    if "--out" in sys.argv:
        out = sys.argv[sys.argv.index("--out") + 1]
        with open(out, "w") as f:
            json.dump(summary, f, indent=1)


if __name__ == "__main__":
    main()
