"""`ops/als.py _partition_coo`, the sharded trainer's host split, against
the plain per-device boolean-mask partition it replaced: the stacked COO
arrays must come out element for element (the on-device slot layout sorts
stably, so the model's bits follow each block's arrival order), and with
them and the initial factors the trained model bit for bit."""

import dataclasses

import jax
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from pio_tpu.ops import als
from pio_tpu.ops.als import ALSParams, _partition_coo, als_train_sharded
from pio_tpu.parallel.mesh import DATA_AXIS, MeshConfig, create_mesh


def reference_partition(rows, cols, vals, block, n_dev, chunk):
    """One boolean mask a device, in int64: what `als_train_sharded` did."""
    rows = np.asarray(rows, dtype=np.int64)
    cols = np.asarray(cols, dtype=np.int64)
    dev_of = rows // block
    per_dev = [np.flatnonzero(dev_of == dv) for dv in range(n_dev)]
    counts = [len(ix) for ix in per_dev]
    nnz_max = max(counts)
    nnz_max += -nnz_max % max(1, chunk)
    r_st = np.full((n_dev, nnz_max), block, np.int32)
    c_st = np.zeros((n_dev, nnz_max), np.int32)
    v_st = np.zeros((n_dev, nnz_max), np.float32)
    for dv, ix in enumerate(per_dev):
        r_st[dv, :len(ix)] = rows[ix] - dv * block
        c_st[dv, :len(ix)] = cols[ix]
        v_st[dv, :len(ix)] = vals[ix]
    return r_st, c_st, v_st, nnz_max, counts


def ratings(n_rows, n_cols, nnz, seed, empty_block=None, n_dev=1):
    """Random-order COO; `empty_block`: that block of rows gets nothing."""
    rng = np.random.default_rng(seed)
    rows = rng.integers(0, n_rows, nnz)
    if empty_block is not None:
        block = als._block(n_rows, n_dev)
        lo = empty_block * block
        rows = rows[(rows < lo) | (rows >= lo + block)]
    cols = rng.integers(0, n_cols, len(rows))
    vals = rng.integers(1, 21, len(rows)).astype(np.float32)
    return rows, cols, vals


def as_input(a, kind):
    if kind == "int32":
        return a.astype(np.int32)
    if kind == "int64":
        return a.astype(np.int64)
    # every second element of an int32 buffer twice as long
    wide = np.zeros(2 * len(a), np.int32)
    wide[::2] = a
    view = wide[::2]
    assert not view.flags.c_contiguous or len(a) < 2
    return view


@pytest.mark.parametrize("chunk", [1, 65_536])
@pytest.mark.parametrize("kind", ["int32", "int64", "strided"])
@pytest.mark.parametrize("n_dev,n_rows,empty_block", [
    (1, 37, None),
    (2, 101, None),       # blocks of 51: the last one holds 50 rows
    (4, 1003, None),
    (4, 1003, 2),         # a block that receives no rating
    (8, 45, None),        # blocks of 6: the last one holds 3 rows
    (8, 1001, 7),
])
def test_partition_equals_the_mask_reference(n_dev, n_rows, empty_block,
                                             kind, chunk):
    rows, cols, vals = ratings(n_rows, 333, 20_000, seed=n_dev + n_rows,
                               empty_block=empty_block, n_dev=n_dev)
    block = als._block(n_rows, n_dev)
    want = reference_partition(rows, cols, vals, block, n_dev, chunk)
    got = _partition_coo(as_input(rows, kind), as_input(cols, kind), vals,
                         block, n_dev, chunk)
    for name, w, g, dtype in zip(("rows", "cols", "vals"), want, got,
                                 (np.int32, np.int32, np.float32)):
        assert g.dtype == dtype and g.shape == w.shape, name
        np.testing.assert_array_equal(g, w, err_msg=name)
    assert got[3] == want[3] and got[3] % chunk == 0
    assert list(got[4]) == want[4] and sum(got[4]) == len(rows)
    if empty_block is not None:
        assert got[4][empty_block] == 0
    for dv, n in enumerate(got[4]):      # sentinel rows past each count
        assert (got[0][dv, n:] == block).all()
        assert (got[0][dv, :n] < block).all()


def test_partition_key_widens_past_256_devices():
    rows, cols, vals = ratings(3000, 50, 9000, seed=5)
    want = reference_partition(rows, cols, vals, 10, 300, 64)
    got = _partition_coo(rows, cols, vals, 10, 300, 64)
    for w, g in zip(want[:3], got[:3]):
        np.testing.assert_array_equal(g, w)
    assert got[3:] == want[3:]


@pytest.mark.parametrize("bad", [-1, 48])
def test_partition_refuses_a_row_outside_the_blocks(bad):
    rows = np.array([0, 5, bad, 7], np.int32)
    with pytest.raises(ValueError, match="row ids outside"):
        _partition_coo(rows, rows, np.ones(4, np.float32), 6, 8, 1)


def test_sharded_train_is_bit_equal_through_the_reference_partition():
    """The 8-device mesh: `als_train_sharded` against the same compiled
    program fed the reference partition and the initial factors made the
    old way, through NumPy on the host."""
    n_users, n_items, n_dev = 45, 29, 8      # neither divisible by 8
    users, items, vals = ratings(n_users, n_items, 700, seed=11)
    vals = vals / 4
    params = ALSParams(rank=4, iterations=3, reg=0.1, alpha=5.0,
                       implicit=True, chunk=128, seed=7)
    mesh = create_mesh(MeshConfig(data=n_dev, model=1))
    got = als_train_sharded(users, items, vals, n_users, n_items, params,
                            mesh)

    ub, ib = als._block(n_users, n_dev), als._block(n_items, n_dev)
    u_r, u_c, u_v, u_nnz, _ = reference_partition(
        users, items, vals, ub, n_dev, params.chunk)
    i_r, i_c, i_v, i_nnz, _ = reference_partition(
        items, users, vals, ib, n_dev, params.chunk)
    ku, ki = jax.random.split(jax.random.PRNGKey(params.seed))
    user0 = np.zeros((ub * n_dev, params.rank), np.float32)
    item0 = np.zeros((ib * n_dev, params.rank), np.float32)
    user0[:n_users] = np.array(als.init_factors(n_users, params.rank, ku))
    item0[:n_items] = np.array(als.init_factors(n_items, params.rank, ki))
    cs, su, si = als._slot_counts(u_nnz, i_nnz, ub, ib, params)
    run = als._sharded_train_fn(
        mesh, ub, ib, su, si, cs,
        dataclasses.replace(params, seed=0, chunk=0, chunk_slots=cs))
    sharding = NamedSharding(mesh, P(DATA_AXIS))
    want_u, want_i = run(*[jax.device_put(a, sharding) for a in (
        u_r, u_c, u_v, i_r, i_c, i_v,
        user0.reshape(n_dev, ub, params.rank),
        item0.reshape(n_dev, ib, params.rank))])
    np.testing.assert_array_equal(
        np.asarray(got.user_factors),
        np.asarray(want_u).reshape(-1, params.rank)[:n_users])
    np.testing.assert_array_equal(
        np.asarray(got.item_factors),
        np.asarray(want_i).reshape(-1, params.rank)[:n_items])
