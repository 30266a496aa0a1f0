"""Kernel lab: on-TPU A/B of ALS normal-equation + CG matvec variants.

PERF.md section 5 has where a sweep's time goes inside the train program
(`sweep_*_device_s` of a traced benchmark run). This script measures
candidate kernels in isolation at the full ML-20M shape; an isolated XLA
pass times 3-6 x off what it takes inside the program (PERF.md section
6), so a winner here is a candidate for a benchmark run, not a result:

  blocks.high       current: f32 upcast + Precision.HIGH (3-pass bf16)
  blocks.sqrtw      ys = y * sqrt(w) in bf16, A = ys^T ys, 1 MXU pass,
                    f32 accumulation — symmetric PSD by construction
                    (same operand both sides), one extra bf16 rounding
  matvec.high       current: einsum bij,bj->bi Precision.HIGH on f32 A
  matvec.default    same, default precision
  matvec.packed     A stored (n, k*k) f32 (lane-dim packed), reshaped
                    in-kernel — tests the minor-dim=64 half-lane-waste
                    hypothesis
                    (the Pallas packed matvec and streaming gather
                    had arms here until PR 28: Mosaic refused the
                    kernels they fed, ops/als_pallas.py's header)
  cg16 / cg8        full CG solves at both iteration counts

Numerical error for each blocks variant is reported vs a float64 numpy
reference at a small shape (error is shape-independent; the full shape
only times).

Usage: python eval/als_kernel_lab.py [--small] [--out PATH]
"""

from __future__ import annotations

import json
import os
import sys
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from pio_tpu.ops.als import (  # noqa: E402
    _cg_solve,
    _device_slot_layout,
    _normal_equations,
    _slots_for,
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
    fn_r = partial(fn, reps)
    fn_1 = partial(fn, 1)
    float(fn_r(*args))
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
    return jax.lax.fori_loop(0, reps, lambda _, acc: body(acc), init)


def blocks_high(src_bf16, i_c, v_c, l_c):
    """Current production kernel (ops/als._chunk_blocks, implicit mode)."""
    W = i_c.shape[1]
    mask = (jnp.arange(W, dtype=jnp.int32)[None, :] < l_c[:, None]).astype(
        jnp.float32)
    y = src_bf16[i_c].astype(jnp.float32)
    w_outer = ALPHA * v_c * mask
    w_rhs = (1.0 + ALPHA * v_c) * mask
    a_blk = jnp.einsum("bwi,bwj->bij", y * w_outer[:, :, None], y,
                       preferred_element_type=jnp.float32,
                       precision=jax.lax.Precision.HIGH)
    b_blk = jnp.einsum("bwk,bw->bk", y, w_rhs,
                       preferred_element_type=jnp.float32,
                       precision=jax.lax.Precision.HIGH)
    return a_blk, b_blk


def blocks_sqrtw(src_bf16, i_c, v_c, l_c):
    """ys = y*sqrt(w) in bf16; A = ys^T ys 1-pass, f32 accumulation."""
    W = i_c.shape[1]
    mask = (jnp.arange(W, dtype=jnp.int32)[None, :] < l_c[:, None]).astype(
        jnp.float32)
    y = src_bf16[i_c]                                   # (C, W, k) bf16
    sw = jnp.sqrt(ALPHA * v_c * mask).astype(jnp.bfloat16)
    w_rhs = ((1.0 + ALPHA * v_c) * mask).astype(jnp.bfloat16)
    ys = y * sw[:, :, None]                             # one bf16 rounding
    a_blk = jnp.einsum("bwi,bwj->bij", ys, ys,
                       preferred_element_type=jnp.float32)
    b_blk = jnp.einsum("bwk,bw->bk", y, w_rhs,
                       preferred_element_type=jnp.float32)
    return a_blk, b_blk


def main() -> None:
    rng = np.random.default_rng(0)
    users = (rng.zipf(1.2, NNZ) % N_USERS).astype(np.int32)
    items = (rng.zipf(1.2, NNZ) % N_ITEMS).astype(np.int32)
    vals = rng.integers(1, 6, NNZ).astype(np.float32)
    d_u, d_i, d_v = map(jax.device_put, (users, items, vals))
    float(jnp.sum(d_v))

    dev = jax.devices()[0]
    out: dict = {"device_kind": dev.device_kind, "platform": dev.platform,
                 "shape": {"n_users": N_USERS, "n_items": N_ITEMS,
                           "nnz": NNZ, "rank": RANK}, "results": {}}
    res = out["results"]

    su = _slots_for(NNZ, N_USERS, WIDTH, CHUNK_SLOTS)
    lay = jax.jit(_device_slot_layout, static_argnums=(3, 4, 5))(
        d_u, d_i, d_v, N_USERS, WIDTH, su)
    rows, idx, val, lens = (jnp.asarray(x) for x in lay)
    S = idx.shape[0]
    key = jax.random.PRNGKey(0)
    fac_i = jax.random.normal(key, (N_ITEMS, RANK), jnp.float32) * 0.1
    fac_u = jax.random.normal(key, (N_USERS, RANK), jnp.float32) * 0.1
    float(jnp.sum(fac_i))

    # ---- numerical error of the blocks variants vs float64 (small probe) --
    C = 512
    i_p, v_p, l_p = (np.asarray(idx[:C]), np.asarray(val[:C]),
                     np.asarray(lens[:C]))
    src64 = np.asarray(fac_i, np.float64)
    src_bf = jnp.asarray(fac_i).astype(jnp.bfloat16)
    src64 = np.asarray(src_bf.astype(jnp.float32), np.float64)  # post-gather-rounding ref
    mask = (np.arange(WIDTH)[None, :] < l_p[:, None]).astype(np.float64)
    y64 = src64[i_p]
    w64 = ALPHA * v_p.astype(np.float64) * mask
    a_ref = np.einsum("bwi,bwj->bij", y64 * w64[:, :, None], y64)
    scale = np.abs(a_ref).max()
    for name, fn in (("high", blocks_high), ("sqrtw", blocks_sqrtw)):
        a_blk, _ = jax.jit(fn)(src_bf, jnp.asarray(i_p), jnp.asarray(v_p),
                               jnp.asarray(l_p))
        err = np.abs(np.asarray(a_blk, np.float64) - a_ref).max() / scale
        asym = np.abs(np.asarray(a_blk) - np.swapaxes(np.asarray(a_blk), 1, 2)
                      ).max() / scale
        res[f"blocks_{name}_relerr"] = float(err)
        res[f"blocks_{name}_asym"] = float(asym)
        print(json.dumps({f"blocks_{name}": {"relerr": float(err),
                                             "asym": float(asym)}}),
              flush=True)

    # ---- blocks timing at full shape (scan over all chunks, no scatter) --
    n_ch = S // CHUNK_SLOTS
    xs_shape = (idx.reshape(n_ch, CHUNK_SLOTS, WIDTH),
                val.reshape(n_ch, CHUNK_SLOTS, WIDTH),
                lens.reshape(n_ch, CHUNK_SLOTS))

    for name, fn in (("high", blocks_high), ("sqrtw", blocks_sqrtw)):
        @partial(jax.jit, static_argnums=(0,))
        def blocks_t(reps, idx, val, lens, other, fn=fn):
            xs = (idx.reshape(n_ch, CHUNK_SLOTS, WIDTH),
                  val.reshape(n_ch, CHUNK_SLOTS, WIDTH),
                  lens.reshape(n_ch, CHUNK_SLOTS))

            def body(acc):
                src = (other + acc).astype(jnp.bfloat16)

                def ch(c, x_c):
                    a_blk, b_blk = fn(src, *x_c)
                    return c + jnp.sum(a_blk[:, 0, 0]) + jnp.sum(b_blk[:, 0]), None

                tot, _ = jax.lax.scan(ch, jnp.float32(0), xs)
                return tot * 1e-30

            return chain(body, jnp.float32(0), reps)

        res[f"blocks_{name}_sec"] = timed(blocks_t, idx, val, lens, fac_i)
        print(json.dumps({f"blocks_{name}_sec":
                          round(res[f"blocks_{name}_sec"], 4)}), flush=True)

    # ---- CG matvec + solve variants on a prebuilt full-shape (A, b) ------
    A, b = jax.jit(_normal_equations,
                   static_argnums=(2, 3, 4, 5, 6, 7, 8))(
        (rows, idx, val, lens), fac_i, N_USERS, True, ALPHA,
        CHUNK_SLOTS, True, "stacked", 73728)
    A = A + (fac_i.T @ fac_i)[None] + 0.05 * jnp.eye(RANK)[None]
    A, b = jnp.asarray(A), jnp.asarray(b)
    A_packed = A.reshape(N_USERS, RANK * RANK)
    float(jnp.sum(b))

    def mv_high(Ax, x):
        return jnp.einsum("bij,bj->bi", Ax, x,
                          preferred_element_type=jnp.float32,
                          precision=jax.lax.Precision.HIGH)

    def mv_default(Ax, x):
        return jnp.einsum("bij,bj->bi", Ax, x,
                          preferred_element_type=jnp.float32)

    def mv_packed(Ap, x):
        return jnp.einsum("bij,bj->bi", Ap.reshape(-1, RANK, RANK), x,
                          preferred_element_type=jnp.float32,
                          precision=jax.lax.Precision.HIGH)

    x0 = jnp.zeros_like(b)
    matvec_cells = [("high", mv_high, A, b), ("default", mv_default, A, b),
                    ("packed", mv_packed, A_packed, b)]
    for name, mv, Aarg, xarg in matvec_cells:
        @partial(jax.jit, static_argnums=(0,))
        def mv_t(reps, Ax, x, mv=mv):
            def body(x):
                return mv(Ax, x) * 1e-30 + x * (1 - 1e-30)

            return jnp.sum(chain(body, x, reps)) * 1e-30

        res[f"matvec_{name}_sec"] = timed(mv_t, Aarg, xarg)
        print(json.dumps({f"matvec_{name}_sec":
                          round(res[f"matvec_{name}_sec"], 5)}), flush=True)

    for iters in (8, 16):
        @partial(jax.jit, static_argnums=(0,))
        def cg_t(reps, A, b, x0, iters=iters):
            x = jax.lax.fori_loop(
                0, reps, lambda _, x: _cg_solve(A, b, x, iters), x0)
            return jnp.sum(x) * 1e-30

        res[f"cg{iters}_sec"] = timed(cg_t, A, b, x0)
        print(json.dumps({f"cg{iters}_sec": round(res[f"cg{iters}_sec"], 4)}),
              flush=True)

    if "--out" in sys.argv:
        with open(sys.argv[sys.argv.index("--out") + 1], "w") as f:
            json.dump(out, f, indent=1)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
