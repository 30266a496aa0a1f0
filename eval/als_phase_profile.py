"""Per-phase decomposition of the ALS sweep on the CURRENT backend.

The round-3 hardware A/B (eval/ALS_ACCUM_BENCH.json) killed the round-2
hypothesis: carry vs stacked accumulation differ by <7% on a real v5e
(0.480 vs 0.505 s/sweep), so the accumulator re-stream is NOT where the
~8x gap to the ~62 ms/sweep roofline (eval/ALS_ROOFLINE.md) lives. This
script times the sweep's constituent phases in isolation so the real
wall is identified by measurement, not inference:

  layout     on-device slot-layout build (once per train, not per sweep)
  gather     y = factors[idx] slot gather only (the roofline's
             "fundamental read" — random 128/256-byte rows from HBM)
  blocks     gather + masked MXU outer-product blocks (no scatter, no A)
  ne         full normal equations (gather + blocks + scatter into A)
  cg         16-iteration batched Jacobi-CG solve on prebuilt (A, b)
  chol       exact batched Cholesky solve on the same (A, b)
  sweep      whole train sweep, from the production path (als_train)

Methodology: every phase runs R times chained through a lax.fori_loop
(each iteration's input is perturbed by the previous result * 1e-30, so
XLA cannot hoist the body as loop-invariant), and the reported time is
(t(R) - t(1)) / (R - 1) — dispatch round trip, readback, and compile
cache effects cancel. A scalar readback ends each timed region.

Usage:
  python eval/als_phase_profile.py [--small] [--out PATH]
"""

from __future__ import annotations

import json
import math
import os
import sys
import time

if os.environ.get("PIO_BENCH_PLATFORM") == "cpu":
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", 1)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from functools import partial  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from pio_tpu.ops.als import (  # noqa: E402
    ALSParams,
    _cg_solve,
    _device_slot_layout,
    _normal_equations,
    _slots_for,
    als_train,
)

SMALL = "--small" in sys.argv

N_USERS = 5_000 if SMALL else 138_493
N_ITEMS = 1_000 if SMALL else 26_744
NNZ = 200_000 if SMALL else 20_000_000
RANK = 16 if SMALL else 64
WIDTH = 128
CHUNK_SLOTS = 8192 if SMALL else 32768
REPS = 4 if SMALL else 6
ALPHA = 10.0


def timed(fn, *args, reps=REPS):
    """(t(reps) - t(1)) / (reps - 1) with scalar readback; min of 3."""
    fn_r = partial(fn, reps)
    fn_1 = partial(fn, 1)
    float(fn_r(*args))  # compile
    float(fn_1(*args))
    best_r = best_1 = float("inf")
    for _ in range(3):
        t0 = time.monotonic()
        float(fn_r(*args))
        best_r = min(best_r, time.monotonic() - t0)
        t0 = time.monotonic()
        float(fn_1(*args))
        best_1 = min(best_1, time.monotonic() - t0)
    return max(best_r - best_1, 0.0) / (reps - 1)


def chain(body, init, reps):
    """Run body reps times, feeding a scalar back so XLA cannot hoist it."""
    def step(_, acc):
        return body(acc)

    return jax.lax.fori_loop(0, reps, step, init)


def main() -> None:
    rng = np.random.default_rng(0)
    users = (rng.zipf(1.2, NNZ) % N_USERS).astype(np.int32)
    items = (rng.zipf(1.2, NNZ) % N_ITEMS).astype(np.int32)
    vals = rng.integers(1, 6, NNZ).astype(np.float32)
    d_u = jax.device_put(users)
    d_i = jax.device_put(items)
    d_v = jax.device_put(vals)
    float(jnp.sum(d_v))

    dev = jax.devices()[0]
    from pio_tpu.utils.tpu_health import telemetry

    out: dict = {
        "transport": telemetry(),
        "device_kind": dev.device_kind,
        "platform": dev.platform,
        "shape": {"n_users": N_USERS, "n_items": N_ITEMS, "nnz": NNZ,
                  "rank": RANK, "width": WIDTH, "chunk_slots": CHUNK_SLOTS},
        "reps": REPS,
        "phases": {},
    }
    phases = out["phases"]

    su = _slots_for(NNZ, N_USERS, WIDTH, CHUNK_SLOTS)
    si = _slots_for(NNZ, N_ITEMS, WIDTH, CHUNK_SLOTS)

    # --- layout build (not part of the sweep; fixed cost per train) ---
    @partial(jax.jit, static_argnums=(0,))
    def layout_t(reps, u, i, v):
        def body(acc):
            rows, idx, val, lens = _device_slot_layout(
                u + (acc * 1e-30).astype(jnp.int32), i, v, N_USERS, WIDTH, su
            )
            return jnp.sum(lens).astype(jnp.float32) * 1e-30

        return chain(body, jnp.float32(0), reps)

    phases["layout_users"] = timed(layout_t, d_u, d_i, d_v)
    print(json.dumps({"layout_users_sec": round(phases['layout_users'], 4)}),
          flush=True)

    # materialize both layouts for the phase bodies
    lay_u = jax.jit(_device_slot_layout, static_argnums=(3, 4, 5))(
        d_u, d_i, d_v, N_USERS, WIDTH, su)
    lay_i = jax.jit(_device_slot_layout, static_argnums=(3, 4, 5))(
        d_i, d_u, d_v, N_ITEMS, WIDTH, si)
    lay_u = tuple(jnp.asarray(x) for x in lay_u)
    lay_i = tuple(jnp.asarray(x) for x in lay_i)
    key = jax.random.PRNGKey(0)
    fac_u = jax.random.normal(key, (N_USERS, RANK), jnp.float32)
    fac_i = jax.random.normal(key, (N_ITEMS, RANK), jnp.float32)
    float(jnp.sum(fac_u) + jnp.sum(fac_i))

    def side(name, lay, other, n_self, x0):
        rows, idx, val, lens = lay
        S = idx.shape[0]

        # --- gather only ---
        @partial(jax.jit, static_argnums=(0,))
        def gather_t(reps, idx, other):
            src = other.astype(jnp.bfloat16)
            n_ch = S // CHUNK_SLOTS
            xs = idx.reshape(n_ch, CHUNK_SLOTS, WIDTH)

            def body(acc):
                def ch(c, x_c):
                    y = (src + acc.astype(jnp.bfloat16))[x_c]
                    return c + jnp.sum(y.astype(jnp.float32)), None

                tot, _ = jax.lax.scan(ch, jnp.float32(0), xs)
                return tot * 1e-30

            return chain(body, jnp.float32(0), reps)

        phases[f"gather_{name}"] = timed(gather_t, idx, other)

        # --- gather + MXU blocks, no scatter ---
        @partial(jax.jit, static_argnums=(0,))
        def blocks_t(reps, idx, val, lens, other):
            from pio_tpu.ops.als import _chunk_blocks

            n_ch = S // CHUNK_SLOTS
            xs = (idx.reshape(n_ch, CHUNK_SLOTS, WIDTH),
                  val.reshape(n_ch, CHUNK_SLOTS, WIDTH),
                  lens.reshape(n_ch, CHUNK_SLOTS))

            def body(acc):
                src = (other + acc).astype(jnp.bfloat16)

                def ch(c, x_c):
                    i_c, v_c, l_c = x_c
                    a_blk, b_blk = _chunk_blocks(
                        src, i_c, v_c, l_c, True, ALPHA)
                    return c + jnp.sum(a_blk[:, 0, 0]) + jnp.sum(
                        b_blk[:, 0]), None

                tot, _ = jax.lax.scan(ch, jnp.float32(0), xs)
                return tot * 1e-30

            return chain(body, jnp.float32(0), reps)

        phases[f"blocks_{name}"] = timed(blocks_t, idx, val, lens, other)

        # --- full normal equations (carry + stacked) ---
        for accum in ("carry", "stacked"):
            @partial(jax.jit, static_argnums=(0,))
            def ne_t(reps, rows, idx, val, lens, other, accum=accum):
                def body(acc):
                    A, b = _normal_equations(
                        (rows, idx, val, lens), other + acc, n_self,
                        True, ALPHA, CHUNK_SLOTS, bf16_gather=True,
                        accum=accum)
                    return (jnp.sum(A[:, 0, 0]) + jnp.sum(b[:, 0])) * 1e-30

                return chain(body, jnp.float32(0), reps)

            phases[f"ne_{accum}_{name}"] = timed(
                ne_t, rows, idx, val, lens, other)

        # --- solves on prebuilt (A, b) ---
        A, b = jax.jit(
            _normal_equations, static_argnums=(2, 3, 4, 5, 6, 7, 8)
        )((rows, idx, val, lens), other, n_self, True, ALPHA,
          CHUNK_SLOTS, True, "stacked", 73728)
        A = A + (other.T @ other)[None] + 0.05 * jnp.eye(RANK)[None]
        A, b = jnp.asarray(A), jnp.asarray(b)
        float(jnp.sum(b))

        @partial(jax.jit, static_argnums=(0,))
        def cg_t(reps, A, b, x0):
            def body(x):
                return _cg_solve(A, b, x, 16)

            x = jax.lax.fori_loop(0, reps, lambda _, x: body(x), x0)
            return jnp.sum(x) * 1e-30

        phases[f"cg16_{name}"] = timed(cg_t, A, b, x0)

        @partial(jax.jit, static_argnums=(0,))
        def chol_t(reps, A, b):
            def body(acc):
                chol = jax.scipy.linalg.cho_factor(
                    A + acc * jnp.eye(RANK)[None])
                x = jax.scipy.linalg.cho_solve(chol, b)
                return jnp.sum(x) * 1e-30

            return chain(body, jnp.float32(0), reps)

        phases[f"chol_{name}"] = timed(chol_t, A, b)

        for k in (f"gather_{name}", f"blocks_{name}", f"ne_carry_{name}",
                  f"ne_stacked_{name}", f"cg16_{name}", f"chol_{name}"):
            print(json.dumps({k + "_sec": round(phases[k], 4)}), flush=True)

    side("users", lay_u, fac_i, N_USERS, fac_u)
    side("items", lay_i, fac_u, N_ITEMS, fac_i)

    # --- whole sweep via the production path, both accum modes ---
    for accum in ("carry", "stacked"):
        # cg_warm_iters=-1: the decomposition below compares against pure
        # cg16 phase timings, so the production warm-CG schedule must be
        # disabled or sweep_{accum} blends two different programs
        p = ALSParams(rank=RANK, iterations=REPS, reg=0.05, alpha=ALPHA,
                      implicit=True, chunk=8192, chunk_slots=CHUNK_SLOTS,
                      accum=accum, cg_warm_iters=-1,
                      cg_iters=ALSParams(rank=RANK).resolved_cg_iters(N_USERS))
        p1 = ALSParams(**{**p.__dict__, "iterations": 1})

        def run(params):
            m = als_train(d_u, d_i, d_v, N_USERS, N_ITEMS, params)
            return float(jnp.sum(m.user_factors))

        run(p)
        run(p1)
        best_r = best_1 = float("inf")
        for _ in range(3):
            t0 = time.monotonic()
            run(p)
            best_r = min(best_r, time.monotonic() - t0)
            t0 = time.monotonic()
            run(p1)
            best_1 = min(best_1, time.monotonic() - t0)
        phases[f"sweep_{accum}"] = max(best_r - best_1, 0.0) / (REPS - 1)
        print(json.dumps(
            {f"sweep_{accum}_sec": round(phases[f'sweep_{accum}'], 4)}),
            flush=True)

    # account: how much of the sweep do the parts explain?
    parts = (phases["ne_stacked_users"] + phases["ne_stacked_items"]
             + phases["cg16_users"] + phases["cg16_items"])
    out["accounted_stacked"] = round(parts, 4)
    out["sweep_minus_parts"] = round(phases["sweep_stacked"] - parts, 4)
    print(json.dumps({"accounted_stacked_sec": out["accounted_stacked"],
                      "sweep_minus_parts_sec": out["sweep_minus_parts"]}),
          flush=True)

    out["phases"] = {k: round(v, 4) for k, v in phases.items()}
    if "--out" in sys.argv:
        path = sys.argv[sys.argv.index("--out") + 1]
        with open(path, "w") as f:
            json.dump(out, f, indent=1)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
