"""ops/moe.py `held_moe_ffn`: one expert-parallel rank's part of a
dropless top-k layer, against benchmark/reference/sequence_lm.py's plain
layer (which shares no code with it)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import sequence_lm as reference
from pio_tpu.ops.moe import (
    HeldExperts,
    dispatch_plan,
    grouped_matmul,
    held_moe_ffn,
    route_top_k,
)

T, D, F, E, K = 50, 32, 16, 8, 3


def _layer(seed=1, scale=0.2):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    full = {"router": jax.random.normal(ks[0], (D, E)) * 0.5,
            "w_gate": jax.random.normal(ks[1], (E, D, F)) * scale,
            "w_up": jax.random.normal(ks[2], (E, D, F)) * scale,
            "w_down": jax.random.normal(ks[3], (E, F, D)) * scale}
    return (full, jax.random.normal(ks[4], (T, D)),
            jax.random.normal(ks[5], (T, D)))


def _part(full, x, lo, hi, norm=True, tile=8):
    params = {"router": full["router"],
              **{n: full[n][lo:hi] for n in ("w_gate", "w_up", "w_down")}}
    return held_moe_ffn(params, x, HeldExperts(E, K, (lo, hi), norm, tile),
                        jnp.float32)


def _whole(full, x, norm=True, held=(0, E)):
    lo, hi = held
    return reference.moe_layer(
        x, full["router"], full["w_gate"][lo:hi], full["w_up"][lo:hi],
        full["w_down"][lo:hi], K, norm, held)


@pytest.mark.parametrize("ranks", [1, 2, 4, 8])
def test_the_ranks_parts_add_up_to_the_uncut_layer(ranks):
    """The share test: every rank routes over all the experts and gives
    its own experts' part; the parts sum to the whole layer's output."""
    full, x, _ = _layer()
    each = E // ranks
    parts = [_part(full, x, lo, lo + each) for lo in range(0, E, each)]
    total = sum(y for y, _ in parts)
    np.testing.assert_allclose(total, _whole(full, x), atol=2e-5, rtol=2e-5)
    counts = np.concatenate([np.asarray(a["counts"]) for _, a in parts])
    assert counts.sum() == T * K          # every choice computed once
    assert all(int(a["dropped"]) == 0 for _, a in parts)


@pytest.mark.parametrize("held", [(0, 2), (2, 6), (5, 8)])
@pytest.mark.parametrize("norm", [True, False])
def test_one_rank_equals_the_reference_given_the_same_share(held, norm):
    full, x, _ = _layer(seed=2)
    y, aux = _part(full, x, *held, norm=norm)
    np.testing.assert_allclose(y, _whole(full, x, norm, held),
                               atol=2e-5, rtol=2e-5)
    ids, _ = route_top_k(x @ full["router"], K, norm)
    want = [(np.asarray(ids) == e).sum() for e in range(*held)]
    assert np.asarray(aux["counts"]).tolist() == want


@pytest.mark.parametrize("tile", [8, 16, 64])
def test_gradients_equal_the_references(tile):
    full, x, w = _layer(seed=3)
    got = jax.grad(lambda f, x: jnp.sum(sum(
        _part(f, x, lo, lo + 4, tile=tile)[0] for lo in (0, 4)) * w),
        (0, 1))(full, x)
    want = jax.grad(lambda f, x: jnp.sum(_whole(f, x) * w), (0, 1))(full, x)
    for name in full:
        np.testing.assert_allclose(got[0][name], want[0][name],
                                   atol=5e-5, rtol=5e-5, err_msg=name)
    np.testing.assert_allclose(got[1], want[1], atol=5e-5, rtol=5e-5)


def test_every_token_sent_to_the_same_experts_drops_none():
    """A router that prefers experts 1, 2 and 5 for every token: the held
    pair (1, 2) gets every token twice over, far over any capacity a
    Switch layer would give, and computes them all."""
    full, x, _ = _layer(seed=4)
    bias = np.zeros((D, E), np.float32)
    x = x.at[:, 0].set(10.0)
    bias[0, [1, 2, 5]] = [3.0, 2.0, 1.0]
    full["router"] = full["router"] * 0.01 + bias
    y, aux = _part(full, x, 1, 3)
    assert np.asarray(aux["counts"]).tolist() == [T, T]
    assert int(aux["dropped"]) == 0
    np.testing.assert_allclose(y, _whole(full, x, True, (1, 3)),
                               atol=2e-5, rtol=2e-5)
    # and a rank none of whose experts is chosen gives exactly nothing
    y, aux = _part(full, x, 6, 8)
    assert np.asarray(aux["counts"]).tolist() == [0, 0]
    assert float(jnp.max(jnp.abs(y))) == 0.0


def test_route_top_k_weights():
    logits = jnp.asarray([[0.0, 2.0, 1.0, -1.0], [1.0, 1.0, 1.0, 1.0]])
    ids, w = route_top_k(logits, 2, True)
    assert ids.tolist() == [[1, 2], [0, 1]]      # ties to the lower id
    np.testing.assert_allclose(w.sum(-1), 1.0, rtol=1e-6)
    _, raw = route_top_k(logits, 2, False)
    p = jax.nn.softmax(logits, -1)
    np.testing.assert_allclose(raw[0], [p[0, 1], p[0, 2]], rtol=1e-6)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_dispatch_plan_places_every_held_choice_once(seed):
    cfg = HeldExperts(E, K, (2, 5), True, 8)
    ids = jnp.asarray(np.random.default_rng(seed).integers(0, E, (T, K)),
                      jnp.int32)
    plan = jax.tree_util.tree_map(np.asarray, dispatch_plan(ids, cfg))
    cap = cfg.row_capacity(T)
    assert plan["row_choice"].shape == (cap,) and cap % 8 == 0
    flat = np.asarray(ids).reshape(-1)
    rows = plan["choice_row"].reshape(-1)
    held = (flat >= 2) & (flat < 5)
    assert (rows[~held] == cap).all() and (rows[held] < cap).all()
    assert len(set(rows[held].tolist())) == held.sum()
    assert (plan["row_choice"][rows[held]] == np.nonzero(held)[0]).all()
    # a tile's rows are one expert's, and every expert owns a tile
    tile_of = plan["tile_expert"][rows[held] // 8]
    assert (tile_of == flat[held] - 2).all()
    assert set(plan["tile_expert"].tolist()) == {0, 1, 2}
    assert (np.diff(plan["tile_expert"]) >= 0).all()
    assert int(plan["dropped"]) == 0


@pytest.mark.parametrize("sizes", [[8, 8, 8], [3, 0, 13], [0, 0, 24],
                                   [1, 1, 1]])
def test_grouped_matmul_ragged_groups(sizes):
    """Rows of group g times matrix g, groups of any size (an empty one
    still owns a tile of zeros), forward and both gradients. Two tiles
    follow the last group: the kernels pass over them, whatever they
    hold, and their rows of the results are undefined."""
    tm, k, n = 8, 12, 10
    rng = np.random.default_rng(sum(sizes))
    padded = [max(tm, -(-s // tm) * tm) for s in sizes]
    tiles = sum(padded) // tm + 2              # two tiles past the last row
    x = np.zeros((tiles * tm, k), np.float32)
    expert = []
    at = 0
    for g, (s, p) in enumerate(zip(sizes, padded)):
        x[at:at + s] = rng.normal(size=(s, k))
        expert += [g] * (p // tm)
        at += p
    n_active = len(expert)
    expert += [expert[-1]] * 2
    w = jnp.asarray(rng.normal(size=(len(sizes), k, n)), jnp.float32)
    x[n_active * tm:] = np.nan                 # never read
    x = jnp.asarray(x)
    te = jnp.asarray(expert, jnp.int32)
    used = jnp.asarray([n_active], jnp.int32)
    live = slice(0, n_active * tm)

    def plain(x, w):
        return jnp.einsum("mk,mkn->mn", x[live],
                          w[np.repeat(expert[:n_active], tm)])

    got = grouped_matmul(x, w, te, used, tm)
    np.testing.assert_allclose(got[live], plain(x, w), atol=1e-5, rtol=1e-5)
    cot = rng.normal(size=got.shape)
    cot[n_active * tm:] = np.nan               # never read either
    cot = jnp.asarray(cot, jnp.float32)
    gx, gw = jax.vjp(lambda x, w: grouped_matmul(x, w, te, used, tm),
                     x, w)[1](cot)
    rx, rw = jax.grad(lambda x, w: jnp.sum(plain(x, w) * cot[live]),
                      (0, 1))(x, w)
    np.testing.assert_allclose(gx[live], rx[live], atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(gw, rw, atol=1e-5, rtol=1e-5)
