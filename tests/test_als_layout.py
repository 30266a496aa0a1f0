"""The on-device slot layout (`ops/als.py _device_slot_layout`): element
for element what the scatter formulation it replaced built (kept here as
the oracle) and what a plain loop over the stably sorted ratings builds;
and the mechanism's guard: no combining scatter over the ratings."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pio_tpu.ops import als
from pio_tpu.ops.als import _device_slot_layout, _slots_for

CHUNK_SLOTS = 64


def scatter_layout(u, o, v, n_self: int, width: int, slots_max: int):
    """The layout as it was built up to PR 28: `rows` and `lens` by a
    combining scatter of every sorted rating (`.min`, `.add`)."""
    nnz = u.shape[0]
    u_s, o_s, v_s = jax.lax.sort((u, o, v), num_keys=1, is_stable=True)
    t = jnp.arange(nnz, dtype=jnp.int32)
    newrow = jnp.concatenate([jnp.ones((1,), bool), u_s[1:] != u_s[:-1]])
    row_start = jax.lax.cummax(jnp.where(newrow, t, 0))
    pos = t - row_start
    newslot = newrow | (pos % width == 0)
    slot_id = jnp.cumsum(newslot.astype(jnp.int32)) - 1
    col = pos % width
    slot_id = jnp.where(u_s < n_self, slot_id, slots_max)
    rows = (jnp.full((slots_max,), n_self, jnp.int32)
            .at[slot_id].min(u_s, mode="drop"))
    lens = jnp.zeros((slots_max,), jnp.int32).at[slot_id].add(1, mode="drop")
    idx = (jnp.zeros((slots_max, width), jnp.int32)
           .at[slot_id, col].set(o_s, mode="drop"))
    val = (jnp.zeros((slots_max, width), jnp.float32)
           .at[slot_id, col].set(v_s, mode="drop"))
    return rows, idx, val, lens


def loop_layout(u, o, v, n_self: int, width: int, slots_max: int):
    """One rating at a time, in stable order of the row id."""
    rows = np.full(slots_max, n_self, np.int32)
    lens = np.zeros(slots_max, np.int32)
    idx = np.zeros((slots_max, width), np.int32)
    val = np.zeros((slots_max, width), np.float32)
    slot, row = -1, None
    for t in np.argsort(u, kind="stable"):
        if u[t] >= n_self:
            break                      # padding sorts last
        if u[t] != row or lens[slot] == width:
            slot, row = slot + 1, u[t]
            rows[slot] = row
        idx[slot, lens[slot]] = o[t]
        val[slot, lens[slot]] = v[t]
        lens[slot] += 1
    return rows, idx, val, lens


def _ratings(degrees, n_self: int, pad: int = 0, shuffle: bool = True,
             sentinel: int | None = None, seed: int = 0):
    """COO arrays with `degrees[r]` ratings in row r (rows past the list
    are empty), `pad` sentinel entries at the tail. The opposing ids
    repeat inside a row, so only a stable sort keeps `idx`'s order."""
    rng = np.random.default_rng(seed)
    u = np.repeat(np.arange(len(degrees)), degrees).astype(np.int32)
    if shuffle:
        rng.shuffle(u)
    o = rng.integers(0, 7, len(u)).astype(np.int32)
    v = rng.uniform(0.5, 5.0, len(u)).astype(np.float32)
    sentinel = n_self if sentinel is None else sentinel
    return (np.concatenate([u, np.full(pad, sentinel, np.int32)]),
            np.concatenate([o, np.full(pad, n_self + 3, np.int32)]),
            np.concatenate([v, np.zeros(pad, np.float32)]))


def _cases(width: int) -> dict:
    w = width
    rng = np.random.default_rng(width)
    mixed = rng.integers(0, 3 * w, 40)
    return {
        # name: (degrees by row, n_self, kwargs of _ratings)
        "empty-rows-at-the-head": ([0, 0, 0, 5, 2, w + 3], 6, {}),
        "empty-rows-in-the-middle": ([4, 0, 0, 0, w, 0, 9], 7, {}),
        "empty-rows-at-the-tail": ([3, 2 * w, 1], 9, {}),
        "a-row-of-exactly-width": ([2, w, 3], 3, {}),
        "a-row-of-width-plus-one": ([2, w + 1, 3], 3, {}),
        "a-row-of-three-widths-plus-two": ([1, 3 * w + 2, 0, 4], 4, {}),
        "one-row-holds-everything": ([0, 0, 5 * w + 7, 0], 4, {}),
        "fewer-ratings-than-width": ([1, 0, 2], 3, {}),
        "all-sentinel": ([], 5, {"pad": 96}),
        "long-sentinel-tail": (mixed, 40, {"pad": 53_753}),
        "more-rows-than-ratings": ([1, 0, 0, 2] + [0] * 600 + [1], 700,
                                   {"pad": 11}),
        "sentinels-above-n_self": (mixed, 40,
                                   {"pad": 50, "sentinel": 2**30}),
        "unsorted-equal-keys": ([w + 5] * 6, 6, {"seed": 7}),
        "already-sorted": (mixed, 45, {"shuffle": False, "pad": 3}),
        "every-row-one-rating": ([1] * 300, 300, {}),
    }


CASES = [(name, w) for w in (8, 128) for name in _cases(w)]


@pytest.mark.parametrize("name,width", CASES,
                         ids=[f"{n}-w{w}" for n, w in CASES])
def test_layout_is_the_scatter_layout_and_the_loop(name, width):
    degrees, n_self, kwargs = _cases(width)[name]
    u, o, v = _ratings(degrees, n_self, **kwargs)
    slots_max = _slots_for(len(u), n_self, width, CHUNK_SLOTS)
    args = (jnp.asarray(u), jnp.asarray(o), jnp.asarray(v))
    static = dict(static_argnums=(3, 4, 5))
    got = jax.jit(_device_slot_layout, **static)(
        *args, n_self, width, slots_max)
    oracle = jax.jit(scatter_layout, **static)(
        *args, n_self, width, slots_max)
    loop = loop_layout(u, o, v, n_self, width, slots_max)
    for what, g, a, b in zip(("rows", "idx", "val", "lens"), got, oracle,
                             loop):
        g = np.asarray(g)
        assert g.dtype == b.dtype and g.shape == b.shape, what
        assert np.array_equal(g, np.asarray(a)), f"{what}: the oracle"
        assert np.array_equal(g, b), f"{what}: the loop"
    rows, _, _, lens = (np.asarray(x) for x in got)
    # what `_normal_equations` leans on: slot -> row never descends, and
    # an unused slot is (n_self, 0)
    assert (np.diff(rows) >= 0).all()
    assert ((rows == n_self) == (lens == 0)).all()
    assert int(lens.sum()) == int((u < n_self).sum())


def test_slots_beyond_slots_max_are_dropped():
    """`slots_max` below what the ratings need: the slots that fit are
    the first ones, whole; the rest are dropped as the scatters did."""
    u, o, v = _ratings([20, 0, 9, 30], 4, pad=5)
    args = (jnp.asarray(u), jnp.asarray(o), jnp.asarray(v))
    got = _device_slot_layout(*args, 4, 8, 5)
    oracle = scatter_layout(*args, 4, 8, 5)
    for g, a in zip(got, oracle):
        assert np.array_equal(np.asarray(g), np.asarray(a))


def _primitives(jaxpr) -> set[str]:
    found = set()
    for eqn in jaxpr.eqns:
        found.add(eqn.primitive.name)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found |= _primitives(sub)
    return found


@pytest.mark.parametrize("nnz,n_self,width,chunk_slots", [
    (64, 5, 8, 8),
    (4096, 300, 8, 128),
    (1_048_576, 138_493, 128, 8192),      # the events cell, a side
    (20_054_016, 138_493, 128, 8192),     # ML-20M, users
    (20_054_016, 26_744, 128, 8192),      # ML-20M, items
])
def test_no_combining_scatter_over_the_ratings(nnz, n_self, width,
                                               chunk_slots):
    """The mechanism's guard: whatever the size, the function traces to
    no scatter that combines (`.add`, `.min`, `.max`, `.mul`)."""
    slots_max = _slots_for(nnz, n_self, width, chunk_slots)
    i32 = jax.ShapeDtypeStruct((nnz,), jnp.int32)
    jaxpr = jax.make_jaxpr(
        lambda u, o, v: _device_slot_layout(u, o, v, n_self, width,
                                            slots_max))(
        i32, i32, jax.ShapeDtypeStruct((nnz,), jnp.float32))
    found = _primitives(jaxpr.jaxpr)
    assert "sort" in found
    assert not {p for p in found if p.startswith("scatter") and p != "scatter"}
    # the oracle above is what the guard is for
    old = _primitives(jax.make_jaxpr(
        lambda u, o, v: scatter_layout(u, o, v, n_self, width, slots_max))(
        i32, i32, jax.ShapeDtypeStruct((nnz,), jnp.float32)).jaxpr)
    assert {"scatter-add", "scatter-min"} <= old


def test_train_program_has_no_combining_scatter_in_the_layout():
    """The same guard on the lowered train program: no `als.layout` op
    is a scatter with a combiner."""
    params = als.ALSParams(rank=8, iterations=1, implicit=True,
                           accum="stacked", chunk=256, chunk_slots=128)
    i32 = jax.ShapeDtypeStruct((768,), jnp.int32)
    jaxpr = jax.make_jaxpr(
        lambda u, i, v: als._build_layouts(u, i, v, 64, 48, params)[:2])(
        i32, i32, jax.ShapeDtypeStruct((768,), jnp.float32))
    assert not {p for p in _primitives(jaxpr.jaxpr)
                if p.startswith("scatter") and p != "scatter"}
