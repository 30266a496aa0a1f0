"""The plain reference: it minimises the loss it claims to, and the
system trained on the CPU agrees with it end to end at a small size."""

import numpy as np

from benchmark.harness import data
from benchmark.reference import als as ref

SHAPE = dict(n_users=400, n_items=150, nnz=9000, min_degree=4,
             degree_sigma=1.0, popularity_exponent=0.9,
             popularity_offset=25.0, popularity_uniform_share=0.1,
             popularity_grid_bits=14, value_levels=[1, 2, 3, 4, 5],
             value_shares=[4, 9, 27, 35, 25])
ALPHA, REG, RANK = 10.0, 0.05, 8


def test_every_half_sweep_lowers_the_loss():
    u, i, v = data.make_interactions(SHAPE, 5)
    last = np.inf
    for sweeps in (1, 2, 4):
        x, y = ref.als(u, i, v, 400, 150, RANK, sweeps, ALPHA, REG, seed=1)
        loss = ref.objective(x, y, u, i, v, ALPHA, REG)
        assert loss < last
        last = loss


def test_bf16_rounding():
    x = np.array([1.0, 1.00390625, 1.005859375, -3.14159], np.float32)
    assert ref.bf16(x).tolist() == [1.0, 1.0, 1.0078125, -3.140625]


def test_system_agrees_with_reference_end_to_end():
    """The program's trainer (CPU path, exact solves at this size, its
    default bfloat16 gather) from the reference's own initial factors:
    after three sweeps the tables agree to the gather's rounding
    carried through the sweeps (6e-3 seen; 13.5 times off would read 12)."""
    from pio_tpu.ops import als

    u, i, v = data.make_interactions(SHAPE, 6)
    rng = np.random.default_rng(2)
    x0 = ref.init_factors(400, RANK, rng)
    y0 = ref.init_factors(150, RANK, rng)
    by_user = ref.rows_of(u, i, v, np.arange(400))
    by_item = ref.rows_of(i, u, v, np.arange(150))
    x, y = x0, y0
    for _ in range(3):
        x, _ = ref.solve_rows(y, by_user, ALPHA, REG)
        y, _ = ref.solve_rows(x, by_item, ALPHA, REG)
    got = als.als_train(
        u, i, v, 400, 150,
        als.ALSParams(rank=RANK, iterations=3, reg=REG, alpha=ALPHA,
                      implicit=True, chunk=4096),
        init=als.ALSModel(x0.astype(np.float32), y0.astype(np.float32)))
    for mine, theirs in ((x, got.user_factors), (y, got.item_factors)):
        theirs = np.asarray(theirs, dtype=np.float64)
        assert np.linalg.norm(theirs - mine) / np.linalg.norm(mine) < 2e-2
