"""Does every Pallas kernel compile on this backend and agree with its
XLA oracle?

One cell per kernel in ops/als_pallas.py (each on both halves of a
sweep), ops/attention.py and ops/retrieval.py, each run ONCE, compiled (on a TPU `interpret` resolves
to False; this script refuses to call a result "compiled" anywhere else),
at the MovieLens-20M factor shapes (138,493 x 64 and 26,744 x 64), and
compared with the plain-XLA implementation of the same contract. The ALS
kernels are checked in composition, through `_solve_factors` (the layout
-> normal equations -> solve half-sweep the trainer runs), because that
is where their block shapes and VMEM budgets are decided.

Prints one JSON line per cell — {"cell", "ok", "rel_err" | "error"} —
and a last line {"device", "cells", "failed"}. A cell's exception is
recorded with the compiler's message and does not stop the others. Exit
code 1 if any cell failed. Times are not taken here: which mode wins is
the benchmark's question.

Usage: python eval/kernel_parity.py [--small] [--out PATH]
  --small: tiny shapes (with JAX_PLATFORMS=cpu: the interpreter; a check
  of this script, not of the kernels' compilation)
"""

from __future__ import annotations

import functools
import json
import os
import sys
import traceback

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from pio_tpu.ops import als  # noqa: E402
from pio_tpu.ops import attention  # noqa: E402
from pio_tpu.ops import retrieval  # noqa: E402

SMALL = "--small" in sys.argv
N_USERS = 3_000 if SMALL else 138_493
N_ITEMS = 700 if SMALL else 26_744
NNZ = 40_000 if SMALL else 2_000_000   # depth; the widths above are full
RANK = 64
ALPHA, REG = 10.0, 0.05
TOL = 2e-3        # ALS cells: both sides build blocks at Precision.HIGH
TOL_DEFAULT = 2e-2  # kernels whose dots run at the MXU's default precision


def rel_err(got, want) -> float:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-30))


def main() -> int:
    dev = jax.devices()[0]
    compiled = dev.platform == "tpu"
    rng = np.random.default_rng(0)
    users = np.concatenate([np.arange(N_USERS),
                            rng.zipf(1.2, NNZ - N_USERS) % N_USERS])
    items = np.concatenate([np.arange(N_USERS) % N_ITEMS,
                            rng.zipf(1.2, NNZ - N_USERS) % N_ITEMS])
    vals = rng.integers(1, 6, NNZ).astype(np.float32)
    p = als.ALSParams(rank=RANK, chunk=8192)
    u, i, v = als._prep_coo(users, items, vals, N_USERS, N_ITEMS, p)
    by_user, by_item, cs = jax.jit(
        als._build_layouts, static_argnames=("n_users", "n_items", "params")
    )(u, i, v, n_users=N_USERS, n_items=N_ITEMS, params=p)
    cs = int(cs)    # the chunk size is static in `_solve_factors`
    ku, ki = jax.random.split(jax.random.PRNGKey(0))
    fac_u = als.init_factors(N_USERS, RANK, ku)
    fac_i = als.init_factors(N_ITEMS, RANK, ki)

    def half(layout, other, n_self, x0, **mode):
        """One half-sweep through _solve_factors under `mode`."""
        fn = jax.jit(lambda lay, o, x: als._solve_factors(
            lay, o, n_self, REG, True, ALPHA, cs, x0=x,
            cg_iters=p.resolved_cg_iters(n_self), bf16_gather=True,
            **mode))
        return np.asarray(fn(layout, other, x0))

    results: list[dict] = []

    def cell(name: str, fn, oracle, tol: float = TOL) -> None:
        try:
            got = fn()
            # the oracle at full f32 precision wherever it multiplies at
            # the default one: its own rounding is not the kernel's error
            with jax.default_matmul_precision("highest"):
                want = oracle()
            err = rel_err(got, want)
            row = {"cell": name, "ok": bool(err <= tol), "rel_err": err,
                   "tolerance": tol, "compiled": compiled}
        except Exception as e:  # noqa: BLE001 - one refusal must not hide the rest
            traceback.print_exc()
            row = {"cell": name, "ok": False, "compiled": compiled,
                   "error": f"{type(e).__name__}: {e}"[:1500]}
        results.append(row)
        print(json.dumps(row), flush=True)

    # -- ALS accumulation kernels, in composition ---------------------------
    # (the gather kernels and the fused kernel had their cells here until
    # PR 28: ops/als_pallas.py's header has the verdicts. Since PR 32 a
    # half-sweep at this rank also runs `pack_flush` and `packed_matvec`:
    # a side that CG solves behind a flush kernel holds A lane-packed)
    @functools.cache
    def xla_users():
        return half(by_user, fac_i, N_USERS, fac_u, accum="carry")

    @functools.cache
    def xla_items():
        return half(by_item, fac_u, N_ITEMS, fac_i, accum="carry")

    for side, layout, other, n_self, x0, oracle in (
            ("users", by_user, fac_i, N_USERS, fac_u, xla_users),
            ("items", by_item, fac_u, N_ITEMS, fac_i, xla_items)):
        for name, accum in (("segment flush", "hybrid"),
                            ("overlapped flush", "stream")):
            cell(f"{name} (accum={accum}), {side} half",
                 functools.partial(half, layout, other, n_self, x0,
                                   accum=accum), oracle)

    # -- flash attention (sequence serving on TPU) --------------------------
    b, s, h, d = (1, 256, 2, 64) if SMALL else (2, 2048, 8, 64)
    q, k_, v_ = (jax.random.normal(kk, (b, s, h, d), jnp.float32)
                 for kk in jax.random.split(jax.random.PRNGKey(1), 3))
    for causal in (False, True):
        cell(f"flash_attention causal={causal}",
             lambda causal=causal: attention.flash_attention(
                 q, k_, v_, causal=causal),
             lambda causal=causal: attention.attention_reference(
                 q, k_, v_, causal=causal), tol=TOL_DEFAULT)

    # -- quantized candidate scan --------------------------------------------
    m = 1024 if SMALL else 8192
    u_row = jax.random.normal(ku, (RANK,), jnp.float32)
    scales = jnp.abs(jax.random.normal(ki, (m,), jnp.float32)) + 0.1
    tables = {
        "int8": jnp.asarray(rng.integers(-127, 128, (m, RANK)), jnp.int8),
        "bf16": jax.random.normal(ki, (m, RANK), jnp.bfloat16),
    }
    for dtype, table in tables.items():
        cell(f"quantized_scores_pallas {dtype}",
             lambda table=table: retrieval.quantized_scores_pallas(
                 table, scales, u_row),
             lambda table=table: retrieval.quantized_scores_xla(
                 table, scales, u_row), tol=TOL_DEFAULT)

    failed = [r["cell"] for r in results if not r["ok"]]
    summary = {
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices()), "jax": jax.__version__},
        "shape": {"n_users": N_USERS, "n_items": N_ITEMS, "nnz": NNZ,
                  "rank": RANK},
        "cells": results, "failed": failed,
    }
    if "--out" in sys.argv:
        out = sys.argv[sys.argv.index("--out") + 1]
        os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
        with open(out, "w") as f:
            json.dump(summary, f, indent=1)
    print(json.dumps({k: summary[k] for k in ("device", "failed")}))
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
