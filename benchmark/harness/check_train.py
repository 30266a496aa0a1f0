"""Is a trained model correct? Decided from the persisted tables and the
seeded ratings alone, after the window, so it does not depend on
--seconds, on timing or on how many jobs the window held.

(a) tables of exactly the catalog's shape, float32, finite, loaded by the
    path `pio deploy` uses (the caller does the loading);
(b) `item_solve_residual`: for a seeded sample of rows of the side
    solved last (items), the float64 normal equations A x = b of the
    regularised implicit problem, built from the persisted users and that
    row's ratings (benchmark/reference/als.py; the gathered user rows
    rounded to bfloat16 first, which is the precision the configurations
    state for the gather), and the persisted row put into them:
    sqrt(sum |A v - b|^2 / sum |b|^2) over the whole sample.
    The program stops its conjugate gradients early, which leaves an error
    along the directions A is weak in and so a small residual; a rounding
    of the tables or of the products errs in every direction alike and
    leaves a large one. That parts the stated precision from the one
    below it (PERF.md has both lists). A wrong solve (PR 21's exact
    Cholesky under shard_map, 13.5 times off) reads of order 1;
(c) `user_fixed_point_gap`: for a sample of user rows, the distance to
    their exact solutions from the persisted items, in each row's own
    metric, sqrt(sum e'Ae / sum x'Ax). The users were solved half a sweep
    before the items, so this reads the movement of one half-sweep: small
    after ten sweeps, large after two, of order 1 when the user side's
    solve is broken;
(d) `seen_minus_unseen`: mean prediction over a sample of rated pairs
    less that over random pairs. Tables that are consistent with each
    other and mean nothing (a run that forgot the ratings) read near 0.
"""

from __future__ import annotations

import numpy as np

from benchmark.reference import als as ref


def gaps(rows, other, grouped, alpha, reg) -> dict:
    """Distances of `rows` from the exact solutions of their normal
    equations against `other`, over the whole sample: "residual"
    sqrt(sum |Av - b|^2 / sum |b|^2), "energy" sqrt(sum e'Ae / sum x'Ax),
    "l2" sqrt(sum |e|^2 / sum |x|^2)."""
    exact, mats = ref.solve_rows(other, grouped, alpha, reg, STATED)
    e = rows.astype(np.float64) - exact
    resid = np.einsum("nij,nj->ni", mats, e)
    rhs = np.einsum("nij,nj->ni", mats, exact)
    e_a = np.einsum("ni,ni->", e, resid)
    x_a = np.einsum("ni,ni->", exact, rhs)
    return {"residual": float(np.sqrt((resid ** 2).sum() / (rhs ** 2).sum())),
            "energy": float(np.sqrt(e_a / x_a)),
            "l2": float(np.sqrt((e * e).sum() / (exact * exact).sum()))}


# the reference follows the precision the configurations state: opposing
# rows are rounded to bfloat16 when gathered, everything else is float64
STATED = "gather_bfloat16"


def sample_of(rng, n: int, size: int) -> np.ndarray:
    return rng.choice(n, min(size, n), replace=False)


def check(users, items, user_idx, item_idx, values, algorithm: dict,
          limits: dict, seed: int, sample_rows: int) -> dict:
    """-> {"correct", "numbers": {name: value}, "compared": [lines]}.
    `users`/`items` are the persisted tables in the data's own row order."""
    alpha, reg = algorithm["alpha"], algorithm["lambda_"]
    rng = np.random.default_rng([seed, 0xC0FFEE])
    n_users, n_items = len(users), len(items)
    numbers: dict[str, float] = {}
    item_sample = sample_of(rng, n_items, sample_rows)
    numbers["item_solve_residual"] = gaps(
        items[item_sample], users,
        ref.rows_of(item_idx, user_idx, values, item_sample), alpha,
        reg)["residual"]
    user_sample = sample_of(rng, n_users, sample_rows)
    numbers["user_fixed_point_gap"] = gaps(
        users[user_sample], items,
        ref.rows_of(user_idx, item_idx, values, user_sample), alpha,
        reg)["energy"]
    pairs = rng.integers(0, len(values), 65536)
    seen = np.einsum("nk,nk->n", users[user_idx[pairs]],
                     items[item_idx[pairs]]).mean()
    unseen = np.einsum("nk,nk->n", users[rng.integers(0, n_users, 65536)],
                       items[rng.integers(0, n_items, 65536)]).mean()
    numbers["seen_minus_unseen"] = float(seen - unseen)
    return judge(numbers, limits)


def judge(numbers: dict, limits: dict) -> dict:
    """Each number beside its limit: {"max": x} or {"min": x}."""
    compared, ok = [], True
    for name, value in numbers.items():
        lim = limits[name]
        if "max" in lim:
            good = bool(np.isfinite(value)) and value <= lim["max"]
            compared.append(f"{name} {value:.6g} <= {lim['max']:g}: "
                            f"{'ok' if good else 'FAILED'}")
        else:
            good = bool(np.isfinite(value)) and value >= lim["min"]
            compared.append(f"{name} {value:.6g} >= {lim['min']:g}: "
                            f"{'ok' if good else 'FAILED'}")
        ok = ok and good
    return {"correct": ok, "numbers": numbers, "compared": compared}
