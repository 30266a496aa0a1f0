"""The seeded generators: no pair twice, every id present, the same seed
the same data, every seed the same sizes."""

import numpy as np

from benchmark.harness import data

SHAPE = dict(n_users=700, n_items=300, nnz=21000, min_degree=4,
             degree_sigma=1.1, popularity_exponent=0.9,
             popularity_offset=25.0, popularity_uniform_share=0.1,
             popularity_grid_bits=14, value_levels=[1, 2, 3, 4, 5],
             value_shares=[4, 9, 27, 35, 25])
BIG_SEED = 2 ** 31 + 12345      # more than 32 signed bits hold


def test_distinct_pairs_and_every_id():
    for seed in (0, 7, BIG_SEED):
        u, i, v = data.make_interactions(SHAPE, seed)
        assert u.dtype == np.int32 and i.dtype == np.int32
        assert v.dtype == np.float32 and len(u) == SHAPE["nnz"]
        pairs = u.astype(np.int64) * SHAPE["n_items"] + i
        assert len(np.unique(pairs)) == SHAPE["nnz"]
        assert set(np.unique(u)) == set(range(SHAPE["n_users"]))
        assert set(np.unique(i)) == set(range(SHAPE["n_items"]))
        assert set(np.unique(v)) <= {1.0, 2.0, 3.0, 4.0, 5.0}


def test_same_seed_same_data_and_seeds_differ():
    a = data.make_interactions(SHAPE, BIG_SEED)
    b = data.make_interactions(SHAPE, BIG_SEED)
    c = data.make_interactions(SHAPE, BIG_SEED + 1)
    assert all((x == y).all() for x, y in zip(a, b))
    assert not (a[1] == c[1]).all()


def test_every_seed_does_the_same_work():
    """The multiset of user degrees is the configuration's, not the
    seed's: seeds shuffle who gets which."""
    degs = [np.sort(np.bincount(data.make_interactions(SHAPE, s)[0]))
            for s in (1, 2)]
    assert (degs[0] == degs[1]).all()
    assert degs[0].min() >= SHAPE["min_degree"]
    assert degs[0].max() <= SHAPE["n_items"] // 2 + 1


def test_missing_item_is_patched_in():
    shape = dict(SHAPE, popularity_uniform_share=0.0,
                 popularity_exponent=3.0, nnz=4000, min_degree=1)
    u, i, v = data.make_interactions(shape, 3)
    assert len(np.unique(i)) == shape["n_items"]
    assert len(np.unique(u.astype(np.int64) * 300 + i)) == shape["nnz"]
