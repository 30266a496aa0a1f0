"""Seeded data: distinct (user, item) interactions.

Everything here is a pure function of the seed and the configuration's
`data` block. Every seed gives the same sizes: the
multiset of user degrees is fixed by the configuration (quantiles of a
log-normal law scaled to the exact number of ratings), the item
popularity law is fixed, and the seed only decides which user gets which
degree, which item gets which popularity rank, the draws inside them and
the order of the rows. So two seeds do the same amount of work.

No (user, item) pair appears twice, as in both source data sets, and
every user and every item appears at least once, so the factor tables
come out at exactly the catalog's shape.
"""

from __future__ import annotations

import functools

import numpy as np
from scipy.special import ndtri


def user_degrees(n_users: int, n_items: int, nnz: int, min_degree: int,
                 sigma: float) -> np.ndarray:
    """The fixed multiset of ratings per user, ascending, summing to
    exactly `nnz`: min_degree + log-normal quantiles, each at most half
    the catalog."""
    if nnz < n_users * min_degree:
        raise ValueError(f"{nnz} ratings cannot give {n_users} users "
                         f"{min_degree} each")
    w = np.exp(sigma * ndtri((np.arange(n_users) + 0.5) / n_users))
    cap = n_items // 2 - min_degree
    spare = nnz - n_users * min_degree
    lo, hi = 0.0, spare / w.min()
    for _ in range(60):          # scale so the capped sum is `spare`
        mid = 0.5 * (lo + hi)
        if np.minimum(w * mid, cap).sum() < spare:
            lo = mid
        else:
            hi = mid
    extra = np.floor(np.minimum(w * lo, cap)).astype(np.int64)
    short = int(spare - extra.sum())
    if not 0 <= short <= n_users:
        raise ValueError("degree law cannot reach the number of ratings")
    extra[n_users - short:] += 1    # the remainder, one each, from the top
    return extra + min_degree


def popularity_table(n_items: int, exponent: float, offset: float,
                     uniform_share: float, bits: int) -> np.ndarray:
    """Inverse CDF of the item popularity law on a grid of 2**bits cells:
    cell -> popularity rank. The law is a power law in the rank mixed
    with a uniform floor, so that even the last item is drawn often."""
    r = np.arange(n_items, dtype=np.float64)
    p = 1.0 / (r + offset) ** exponent
    p = (1.0 - uniform_share) * p / p.sum() + uniform_share / n_items
    cdf = np.cumsum(p)
    cdf /= cdf[-1]
    grid = (np.arange(1 << bits, dtype=np.float64) + 0.5) / (1 << bits)
    return np.searchsorted(cdf, grid).astype(np.int32)


def make_interactions(data: dict, seed: int):
    """-> (user_idx int32, item_idx int32, values float32), host arrays of
    (nnz,), made on the default jax device in one jitted call.

    Each user's items are drawn without replacement: its d ratings take
    one stratified draw each from the popularity law, which gives ranks
    in ascending order, and the j-th is moved j ranks on, so that they
    ascend strictly and no dedup decides anything."""
    import jax

    n_users, n_items, nnz = data["n_users"], data["n_items"], data["nnz"]
    deg = user_degrees(n_users, n_items, nnz, data["min_degree"],
                       data["degree_sigma"])
    bits = data["popularity_grid_bits"]
    table = popularity_table(n_items, data["popularity_exponent"],
                             data["popularity_offset"],
                             data["popularity_uniform_share"], bits)
    shares = np.asarray(data["value_shares"], dtype=np.float64)
    make = jax.jit(functools.partial(_interactions, n_users=n_users,
                                     n_items=n_items, nnz=nnz, bits=bits))
    users, items, values = jax.device_get(make(
        seed_key(seed), deg.astype(np.int32), table,
        np.asarray(data["value_levels"], dtype=np.float32),
        np.cumsum(shares / shares.sum()).astype(np.float32)))
    users, items = np.array(users), np.array(items)

    # every item at least once: an item nobody drew replaces one rating
    # of the most rated item (whose user cannot have had the missing one)
    counts = np.bincount(items, minlength=n_items)
    missing = np.flatnonzero(counts == 0)
    if len(missing):
        top = int(np.argmax(counts))
        where = np.flatnonzero(items == top)[:len(missing)]
        if counts[top] <= len(missing):
            raise ValueError("too few ratings to cover every item")
        items[where] = missing
    return users, items, np.array(values)


def seed_key(seed: int):
    """A jax PRNG key from any whole number (--seed passes 2**31)."""
    import jax

    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def _interactions(key, deg, table, levels, cum_shares, *, n_users, n_items,
                  nnz, bits):
    import jax
    import jax.numpy as jnp

    k_deg, k_draw, k_item, k_val, k_order = jax.random.split(key, 5)
    deg = deg[jax.random.permutation(k_deg, n_users)]
    starts = jnp.cumsum(deg) - deg
    users = jnp.repeat(jnp.arange(n_users, dtype=jnp.int32), deg,
                       total_repeat_length=nnz)
    pos = jnp.arange(nnz, dtype=jnp.int32) - starts[users]
    d_row = deg[users]
    t = (pos + jax.random.uniform(k_draw, (nnz,))) / d_row
    cell = jnp.minimum((t * (1 << bits)).astype(jnp.int32), (1 << bits) - 1)
    # the draws of one user come in ascending order, so adding each one's
    # position makes them strictly ascending: no pair twice, no dedup
    rank = jnp.minimum(table[cell], n_items - d_row) + pos
    items = jax.random.permutation(k_item, n_items)[rank]
    values = levels[jnp.searchsorted(
        cum_shares, jax.random.uniform(k_val, (nnz,)))
        .clip(0, levels.shape[0] - 1)]
    order = jax.random.permutation(k_order, nnz)
    return users[order], items[order].astype(jnp.int32), values[order]


def entity_ids(prefix: str, n: int) -> list[str]:
    return [f"{prefix}{k}" for k in range(n)]
