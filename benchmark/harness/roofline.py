"""Operations and least bytes of the ALS trainer, from shapes alone.

Copied from bench.py (`als_hbm_bytes_per_sweep`, `als_flops_per_sweep`)
and corrected: bench.py counted the bytes the program physically moves
(lane-padded rows, slot padding, blocks written and read again, three
passes over A). A roofline share needs the LEAST the formulation must
move, or a better layout could read over 100%. Here, for one half-sweep
of a side of n rows with nnz ratings at rank k, solved by `cg` iterations
of conjugate gradients on normal equations kept in HBM:

  ratings        nnz x 8           (index int32 + value float32, once)
  gather         nnz x k x 2       (one opposing row per rating, bfloat16)
  A written      n x k x k x 4     (unpadded, once)
  A read         (cg + 1) x n x k x k x 4   (one matvec for the first
                                   residual, one per iteration; an exact
                                   solve, cg = 0, reads it once)
  x, b           3 x n x k x 4     (start, right-hand side, result)

Nothing is counted for padding, for blocks that a fused kernel keeps on
the chip, or for the Gram matrix (k x k).
"""

from __future__ import annotations


def cg_schedule(sweeps: int, full_iters: int, full_sweeps: int,
                warm_iters: int) -> list[int]:
    """Iterations of each sweep: `full_iters` while cold, then
    `warm_iters` (ops/als.py `_cg_schedule`)."""
    n_full = min(sweeps, full_sweeps) if 1 <= warm_iters < full_iters \
        else sweeps
    return [full_iters] * n_full + [warm_iters] * (sweeps - n_full)


def half_sweep_bytes(n_rows: int, nnz: int, rank: int, cg: int) -> float:
    a = n_rows * rank * rank * 4
    return float(nnz * 8 + nnz * rank * 2 + a + (cg + 1) * a
                 + 3 * n_rows * rank * 4)


def half_sweep_flops(n_rows: int, n_other: int, nnz: int, rank: int,
                     cg: int) -> float:
    """Outer products and right-hand sides of the ratings, the Gram
    matrix of the other side, and the solve (per iteration one matvec;
    exact: Cholesky k^3/3 and two triangular solves)."""
    k = rank
    build = 2 * nnz * k * k + 2 * nnz * k + 2 * n_other * k * k
    solve = (2 * n_rows * (cg + 1) * k * k if cg > 0
             else n_rows * (k ** 3 / 3 + 2 * k * k))
    return float(build + solve)


def job_least(n_users: int, n_items: int, nnz: int, rank: int,
              schedule_users: list[int], schedule_items: list[int]) -> dict:
    """Least bytes and operations of every sweep of one train job."""
    b = f = 0.0
    for cg_u, cg_i in zip(schedule_users, schedule_items):
        b += half_sweep_bytes(n_users, nnz, rank, cg_u)
        b += half_sweep_bytes(n_items, nnz, rank, cg_i)
        f += half_sweep_flops(n_users, n_items, nnz, rank, cg_u)
        f += half_sweep_flops(n_items, n_users, nnz, rank, cg_i)
    return {"bytes": b, "flops": f}
