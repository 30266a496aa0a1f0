"""Plain reference: implicit-feedback ALS in NumPy float64.

Hu, Koren, Volinsky, "Collaborative Filtering for Implicit Feedback
Datasets" (ICDM 2008): preference p = 1 for every observed pair,
confidence c = 1 + alpha * r. One half-sweep solves, for every row x of
one side against the whole other side Y,

    (Y^T Y + Y_r^T diag(alpha r) Y_r + lambda I) x = Y_r^T (1 + alpha r)

exactly (Cholesky), where Y_r are the rows of Y this row has ratings
with. A sweep solves the users and then the items. The regularisation is
plain lambda I, as in the program, not MLlib's count-weighted one.

This file imports nothing of the program and takes nothing it has made.
`precision="bfloat16"` is the control of the output check: the same
mathematics with its tables and the operands of its products rounded to
bfloat16 (products and sums in float32, as a matrix unit would), the
nearest precision below the one the configurations state.
"""

from __future__ import annotations

import numpy as np


def bf16(x: np.ndarray) -> np.ndarray:
    """Round float32 to the nearest bfloat16 (ties to even), as float32."""
    b = np.ascontiguousarray(x, dtype=np.float32).view(np.uint32)
    b = (b + 0x7FFF + ((b >> 16) & 1)) & np.uint32(0xFFFF0000)
    return b.view(np.float32)


def rows_of(row_idx, col_idx, values, rows):
    """The ratings of the given rows, grouped: -> (order of `rows`) lists
    of (cols, values). One pass over the ratings and a sort of the few
    that are wanted."""
    wanted = np.zeros(int(row_idx.max()) + 1 if len(row_idx) else 1,
                      dtype=bool)
    rows = np.asarray(rows)
    wanted[rows[rows < len(wanted)]] = True
    sel = np.flatnonzero(wanted[row_idx])
    r = row_idx[sel]
    order = np.argsort(r, kind="stable")
    sel, r = sel[order], r[order]
    lo = np.searchsorted(r, rows, side="left")
    hi = np.searchsorted(r, rows, side="right")
    return [(col_idx[sel[a:b]], values[sel[a:b]]) for a, b in zip(lo, hi)]


def normal_equations(other, cols, vals, alpha, reg, gram, precision):
    """A (k, k) and b (k,) of one row."""
    if precision in ("float64", "gather_bfloat16"):
        # gather_bfloat16: float64 arithmetic on rows that were rounded
        # to bfloat16 when gathered, as the configurations state the
        # trainer does; the Gram matrix is of the unrounded table
        y = other[cols] if precision == "float64" else bf16(other[cols])
        y = y.astype(np.float64)
        w = alpha * vals.astype(np.float64)
        a = gram + (y * w[:, None]).T @ y
        b = y.T @ (1.0 + w)
    else:   # operands in bfloat16, products and sums in float32
        y = bf16(other[cols])
        w = (alpha * vals).astype(np.float32)
        a = gram.astype(np.float32) + bf16(y * w[:, None]).T @ y
        b = y.T @ bf16(1.0 + w)
    a = a + reg * np.eye(other.shape[1], dtype=a.dtype)
    return a, b


def gram_of(other, precision):
    if precision in ("float64", "gather_bfloat16"):
        y = other.astype(np.float64)
        return y.T @ y
    y = bf16(other)
    return y.T @ y


def solve_rows(other, grouped, alpha, reg, precision="float64"):
    """Exact solutions of the given rows against `other` -> (n, k), and
    each row's matrix A (n, k, k) for norms in the problem's own metric."""
    k = other.shape[1]
    gram = gram_of(other, precision)
    xs = np.empty((len(grouped), k), dtype=np.float64)
    mats = np.empty((len(grouped), k, k), dtype=np.float64)
    for n, (cols, vals) in enumerate(grouped):
        a, b = normal_equations(other, cols, vals, alpha, reg, gram,
                                precision)
        x = np.linalg.solve(a, b)
        xs[n] = bf16(x) if precision == "bfloat16" else x
        mats[n] = a
    return xs, mats


def init_factors(n: int, rank: int, rng) -> np.ndarray:
    return np.abs(rng.standard_normal((n, rank))) / np.sqrt(rank)


def als(user_idx, item_idx, values, n_users, n_items, rank, sweeps, alpha,
        reg, seed, precision="float64"):
    """Every sweep, exact solves: the whole reference, for small sizes."""
    rng = np.random.default_rng(seed)
    users = init_factors(n_users, rank, rng)
    items = init_factors(n_items, rank, rng)
    by_user = rows_of(user_idx, item_idx, values, np.arange(n_users))
    by_item = rows_of(item_idx, user_idx, values, np.arange(n_items))
    for _ in range(sweeps):
        users, _ = solve_rows(items, by_user, alpha, reg, precision)
        items, _ = solve_rows(users, by_item, alpha, reg, precision)
    return users, items


def objective(users, items, user_idx, item_idx, values, alpha, reg):
    """The implicit-ALS loss: sum over ALL pairs c (p - x.y)^2 + lambda
    (|X|^2 + |Y|^2), by the Gram trick (no dense score matrix)."""
    x, y = users.astype(np.float64), items.astype(np.float64)
    pred = np.einsum("nk,nk->n", x[user_idx], y[item_idx])
    c = 1.0 + alpha * values.astype(np.float64)
    every = np.sum((x.T @ x) * (y.T @ y))            # sum of all x.y squared
    seen = np.sum(c * (1.0 - pred) ** 2 - pred ** 2)
    return every + seen + reg * (np.sum(x * x) + np.sum(y * y))
