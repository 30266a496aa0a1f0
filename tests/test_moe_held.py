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
    grouped_swiglu,
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
    """The plan's contract: every held choice is one row of a tile of
    its expert, a tile's rows that hold one are its first, `tiles_used`
    counts the tiles that hold a group, and `choice_row` says the same
    from the choices' side."""
    tm = 8
    cfg = HeldExperts(E, K, (2, 5), True, tm)
    rng = np.random.default_rng(seed)
    ids = np.argsort(rng.random((T, E)), axis=1)[:, :K]  # distinct a token
    plan = jax.tree_util.tree_map(
        np.asarray, dispatch_plan(jnp.asarray(ids, jnp.int32), cfg))
    n_tiles = cfg.row_capacity(T) // tm
    assert plan["order"].shape == (T * K + tm,)
    for name in ("tile_expert", "tile_start", "tile_valid"):
        assert plan[name].shape == (n_tiles,), name
    flat = ids.reshape(-1)
    counts = [(flat == e).sum() for e in range(2, 5)]
    assert plan["counts"].tolist() == counts
    used = int(plan["tiles_used"][0])
    assert used == sum(max(-(-c // tm), 1) for c in counts) < n_tiles
    cap = cfg.row_capacity(T)
    choice_row = plan["choice_row"].reshape(-1)
    placed, tiles_of = [], {0: [], 1: [], 2: []}
    for i in range(used):
        valid = int(plan["tile_valid"][i])
        assert 0 <= valid <= tm
        rows = plan["order"][plan["tile_start"][i]:][:valid]
        assert (flat[rows] == plan["tile_expert"][i] + 2).all()
        assert (np.diff(rows // K) > 0).all()   # distinct tokens, ascending
        assert (choice_row[rows] == i * tm + np.arange(valid)).all()
        placed += rows.tolist()
        tiles_of[int(plan["tile_expert"][i])].append(valid)
    held = (flat >= 2) & (flat < 5)
    assert sorted(placed) == np.nonzero(held)[0].tolist()
    assert (choice_row[~held] == cap).all() and (choice_row[held] < cap).all()
    for e, valids in tiles_of.items():          # full tiles, then the rest
        assert valids and all(v == tm for v in valids[:-1])
        assert sum(valids) == counts[e]
    assert (np.diff(plan["tile_expert"]) >= 0).all()
    assert (plan["tile_expert"][used:] == 2).all()
    assert int(plan["dropped"]) == 0


T_R = 200                    # tokens of the routings below


def _routed(routing: str, tm: int, seed=5):
    """A layer whose router sends every token where `routing` says:
    feature c of a token of class c is 1, and the router's row c holds
    that class's logits (3, 2, 1 for its three choices in order), far
    over what the other features add."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    classes = {"one held expert gets none": [(T_R, (2, 3, 6))],
               "every token to one expert": [(T_R, (3, 0, 1))],
               "every choice held": [(T_R, (2, 3, 4))],
               "groups of tile - 1, tile, tile + 1": [
                   (tm - 1, (2, 0, 1)), (tm, (3, 0, 1)), (tm + 1, (4, 0, 1)),
                   (T_R - 3 * tm, (0, 1, 5))]}[routing]
    router = np.array(jax.random.normal(ks[0], (D, E))) * 0.01
    x = np.array(jax.random.normal(ks[4], (T_R, D)))
    x[:, :len(classes)] = 0.0
    at = 0
    for c, (n, chosen) in enumerate(classes):
        x[at:at + n, c] = 1.0
        router[c] = 0.0
        router[c, list(chosen)] = [3.0, 2.0, 1.0]
        at += n
    x = x[np.random.default_rng(seed).permutation(T_R)]
    full = {"router": jnp.asarray(router),
            "w_gate": jax.random.normal(ks[1], (E, D, F)) * 0.2,
            "w_up": jax.random.normal(ks[2], (E, D, F)) * 0.2,
            "w_down": jax.random.normal(ks[3], (E, F, D)) * 0.2}
    want = {e: sum(n for n, chosen in classes if e in chosen)
            for e in range(2, 5)}
    return (full, jnp.asarray(x), jax.random.normal(ks[5], (T_R, D)),
            [want[e] for e in range(2, 5)])


def _equals_the_reference(routing, tile):
    full, x, w, counts = _routed(routing, tile)

    def part(f, x):
        return _part(f, x, 2, 5, tile=tile)

    y, aux = part(full, x)
    assert np.asarray(aux["counts"]).tolist() == counts
    assert int(aux["dropped"]) == 0
    np.testing.assert_allclose(y, _whole(full, x, True, (2, 5)),
                               atol=2e-5, rtol=2e-5)
    got = jax.grad(lambda f, x: jnp.sum(part(f, x)[0] * w), (0, 1))(full, x)
    want = jax.grad(lambda f, x: jnp.sum(_whole(f, x, True, (2, 5)) * w),
                    (0, 1))(full, x)
    for name in ("w_gate", "w_up", "w_down"):
        np.testing.assert_allclose(got[0][name][2:5], want[0][name][2:5],
                                   atol=5e-5, rtol=5e-5, err_msg=name)
    np.testing.assert_allclose(got[0]["router"], want[0]["router"],
                               atol=5e-5, rtol=5e-5)
    np.testing.assert_allclose(got[1], want[1], atol=5e-5, rtol=5e-5)


@pytest.mark.parametrize("tile", [8, 16, 64])
@pytest.mark.parametrize("routing", [
    "one held expert gets none", "every token to one expert",
    "every choice held", "groups of tile - 1, tile, tile + 1"])
def test_output_and_gradients_at_the_routings_edges(routing, tile):
    """Empty groups, one group with everything, the buffer full, groups
    that straddle a tile's edge: the layer and every gradient equal the
    float32 reference's."""
    _equals_the_reference(routing, tile)


@pytest.mark.parametrize("routing", [
    "groups of tile - 1, tile, tile + 1",     # 4 tiles: inside the front
    "every token to one expert",              # 27 tiles: past its end
])
def test_the_return_reads_the_front_or_the_whole_buffer(routing, monkeypatch):
    """The return gathers from the buffer's front while the groups end
    inside `FRONT_BYTES`: here the front is 4 tiles of the 78."""
    from pio_tpu.ops import moe

    monkeypatch.setattr(moe, "FRONT_BYTES", 4 * 8 * D * 4)
    _equals_the_reference(routing, 8)


def _held(eqn):
    """The jaxprs an equation holds (a call's, a loop's, a kernel's)."""
    subs = [getattr(sub, "jaxpr", sub) for val in eqn.params.values()
            for sub in (val if isinstance(val, (list, tuple)) else [val])]
    return [sub for sub in subs if hasattr(sub, "eqns")]


def _equations(jaxpr, inside_loop=False):
    """(equation, inside a loop) of a jaxpr and of every jaxpr its
    equations hold, but a Pallas kernel's body."""
    for eqn in jaxpr.eqns:
        yield eqn, inside_loop
        if eqn.primitive.name != "pallas_call":
            for sub in _held(eqn):
                yield from _equations(
                    sub, inside_loop or eqn.primitive.name == "while")


def _moves(jaxpr):
    """(primitive, entries moved, their width, inside a loop) of every
    gather and scatter."""
    found = []
    for eqn, inside_loop in _equations(jaxpr):
        name = eqn.primitive.name
        if name == "gather" or name.startswith("scatter"):
            indices = eqn.invars[1].aval.shape
            moved = eqn.outvars[0] if name == "gather" else eqn.invars[2]
            entries = int(np.prod(indices[:-1]))
            found.append((name, entries, moved.aval.size // entries,
                          inside_loop))
    return found


@pytest.mark.parametrize("score", ["softmax", "sigmoid"])
def test_nothing_is_scattered_and_no_scalar_gathered_a_row_or_choice(score):
    """The regression PR 34 removed, forward and backward, with and
    without a router bias: scatters (0.3 ms to begin and 45-120 ns an
    update on the chip), and gathers of one scalar a row of the
    worst-case buffer or a (token, choice). What is left that long: the
    return's k rows a token, forward and in the backward pass of the
    move into the buffer. The tile loops move a tile at a time."""
    full, x, w = _layer(seed=6)
    cfg = HeldExperts(E, K, (2, 5), True, 8, score)
    params = {"router": full["router"],
              **{n: full[n][2:5] for n in ("w_gate", "w_up", "w_down")}}
    if score == "sigmoid":
        params["router_bias"] = jnp.zeros(E)

    def loss(p, x):
        return jnp.sum(held_moe_ffn(p, x, cfg, jnp.float32)[0] * w)

    moves = _moves(jax.make_jaxpr(jax.value_and_grad(loss, (0, 1)))(
        params, x).jaxpr)
    assert not [m for m in moves if m[0] != "gather"], moves
    in_loops = [m for m in moves if m[3]]
    assert len(in_loops) >= 3                   # rows in, rows and weights back
    assert all(m[1] == cfg.tile_rows for m in in_loops), in_loops
    rows = min(cfg.row_capacity(T), T * K)
    long = [m for m in moves if not m[3] and m[1] >= rows // 2]
    # each return once from the buffer's front and once from all of it
    assert long == [("gather", T * K, D, False)] * 4, long


def test_nothing_outside_the_kernels_passes_over_the_buffer_or_the_weights():
    """What PR 43 took into the kernels, at the timed precision
    (bfloat16 products, float32 masters), forward and backward: outside
    the Pallas calls no equation reads or writes an (M, f) or (M, 2f)
    array (SwiGLU, its derivative, a slice or a pad of gate | up), and
    none converts, concatenates or transposes a (G, d, f)-shaped weight
    or weight gradient: the kernels take the weights as they are stored
    and their gradients leave `moe_tgmm` in float32."""
    full, x, w = _layer(seed=6)
    cfg = HeldExperts(E, K, (2, 5), True, 8)
    m, g, f = cfg.row_capacity(T), cfg.n_held, 24   # f, 2 f: not d
    params = {"router": full["router"], "w_gate": jnp.ones((g, D, f)),
              "w_up": jnp.ones((g, D, f)), "w_down": jnp.ones((g, f, D))}

    def loss(p, x):
        return jnp.sum(held_moe_ffn(p, x, cfg, jnp.bfloat16)[0] * w)

    jaxpr = jax.make_jaxpr(jax.grad(loss, (0, 1)))(params, x).jaxpr
    hidden = {(m, f), (m, 2 * f)}
    weights = {(g, D, f), (g, f, D), (g, D, 2 * f), (g, 2 * f, D)}
    kernels, over_buffer, over_weights = 0, [], []
    for eqn, _ in _equations(jaxpr):
        name = eqn.primitive.name
        kernels += name == "pallas_call"
        shapes = {v.aval.shape for v in (*eqn.invars, *eqn.outvars)
                  if hasattr(v.aval, "shape")}
        if not _held(eqn) and shapes & hidden:     # a call passes it on
            over_buffer.append(name)
        if (name in ("convert_element_type", "concatenate", "transpose")
                and shapes & weights):
            over_weights.append(name)
    assert kernels == 7         # gate | up and down; d hidden, dx, 3 dw
    assert not over_buffer, over_buffer
    assert not over_weights, over_weights
    grads = jax.eval_shape(jax.grad(loss), params, x)
    assert all(grads[n].dtype == jnp.float32
               for n in ("w_gate", "w_up", "w_down"))


def test_rows_behind_tiles_used_are_never_read(monkeypatch):
    """The buffers start as NaN instead of zeros, and every array a
    grouped kernel writes (hidden, gate, up and the rows of the down
    product; d gate, d up and dx backward) is NaN behind the last
    group: whatever the tiles there hold reaches neither output nor
    gradients."""
    from pio_tpu.ops import moe

    full, x, w = _layer(seed=7)

    def run():
        return jax.value_and_grad(
            lambda f, x: jnp.sum(_part(f, x, 2, 5)[0] * w), (0, 1))(full, x)

    want = run()
    made, written = [], []

    def nans(shape, dtype):
        made.append(shape)
        return jnp.full(shape, jnp.nan, dtype)

    over_tiles = moe._over_tiles

    def nans_behind(name, rows, weights, into, tile_expert, tiles_used, tm,
                    **how):
        outs = over_tiles(name, rows, weights, into, tile_expert, tiles_used,
                          tm, **how)
        written.extend((name, o.shape) for o in outs)
        behind = jnp.arange(outs[0].shape[0])[:, None] >= tiles_used[0] * tm
        return [jnp.where(behind, jnp.nan, o) for o in outs]

    monkeypatch.setattr(moe, "_unwritten", nans)
    monkeypatch.setattr(moe, "_over_tiles", nans_behind)
    got = run()
    cfg = HeldExperts(E, K, (2, 5), True, 8)
    m = cfg.row_capacity(T)
    assert made and all(s[0] == m for s in made)
    assert sorted(written) == sorted(
        [("moe_gmm_swiglu", (m, F))] * 3 + [("moe_gmm", (m, D))] * 2
        + [("moe_gmm_dswiglu", (m, F))] * 2)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(a, b)


def _ragged(sizes, tm, k, dtype=jnp.float32):
    """Rows of `sizes` groups padded to tiles of `tm`, two tiles behind
    the last group that hold NaN -> (x (M, k), tile_expert, tiles_used,
    the live rows, their experts)."""
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
    x[n_active * tm:] = np.nan                 # never read
    return (jnp.asarray(x, dtype), jnp.asarray(expert, jnp.int32),
            jnp.asarray([n_active], jnp.int32), slice(0, n_active * tm),
            np.repeat(expert[:n_active], tm))


RAGGED = [[8, 8, 8], [3, 0, 13], [0, 0, 24], [1, 1, 1]]


@pytest.mark.parametrize("sizes", RAGGED)
def test_grouped_matmul_ragged_groups(sizes):
    """Rows of group g times matrix g, groups of any size (an empty one
    still owns a tile of zeros), forward and both gradients. Two tiles
    follow the last group: the kernels pass over them, whatever they
    hold, and their rows of the results are undefined."""
    tm, k, n = 8, 12, 10
    x, te, used, live, of_row = _ragged(sizes, tm, k)
    rng = np.random.default_rng(sum(sizes) + 1)
    w = jnp.asarray(rng.normal(size=(len(sizes), k, n)), jnp.float32)

    def plain(x, w):
        return jnp.einsum("mk,mkn->mn", x[live], w[of_row])

    got = grouped_matmul(x, w, te, used, tm)
    np.testing.assert_allclose(got[live], plain(x, w), atol=1e-5, rtol=1e-5)
    cot = rng.normal(size=got.shape)
    cot[live.stop:] = np.nan                   # never read either
    cot = jnp.asarray(cot, jnp.float32)
    gx, gw = jax.vjp(lambda x, w: grouped_matmul(x, w, te, used, tm),
                     x, w)[1](cot)
    rx, rw = jax.grad(lambda x, w: jnp.sum(plain(x, w) * cot[live]),
                      (0, 1))(x, w)
    np.testing.assert_allclose(gx[live], rx[live], atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(gw, rw, atol=1e-5, rtol=1e-5)


def _plain_swiglu(x, w_gate, w_up, w_down, of_row):
    """The fused op's mathematics a row at a time, in the operands'
    own precision."""
    gate = jnp.einsum("mk,mkn->mn", x, w_gate[of_row])
    up = jnp.einsum("mk,mkn->mn", x, w_up[of_row])
    return jnp.einsum("mk,mkn->mn", jax.nn.silu(gate) * up, w_down[of_row])


@pytest.mark.parametrize("tm", [8, 16])
@pytest.mark.parametrize("sizes", RAGGED)
def test_grouped_swiglu_ragged_groups(sizes, tm):
    """The fused op against the float32 mathematics: the output and all
    four gradients, groups of any size, NaN in the rows and in the
    cotangent behind the last group."""
    d, f = 12, 10
    x, te, used, live, of_row = _ragged(sizes, tm, d)
    rng = np.random.default_rng(sum(sizes) + 2)
    ws = [jnp.asarray(rng.normal(size=shape) * 0.3, jnp.float32)
          for shape in ((len(sizes), d, f), (len(sizes), d, f),
                        (len(sizes), f, d))]
    got, back = jax.vjp(
        lambda x, *ws: grouped_swiglu(x, *ws, te, used, tm), x, *ws)
    np.testing.assert_allclose(
        got[live], _plain_swiglu(x[live], *ws, of_row), atol=1e-5, rtol=1e-5)
    cot = rng.normal(size=got.shape)
    cot[live.stop:] = np.nan
    cot = jnp.asarray(cot, jnp.float32)
    want = jax.grad(lambda x, *ws: jnp.sum(
        _plain_swiglu(x[live], *ws, of_row) * cot[live]), (0, 1, 2, 3))(
            x, *ws)
    for name, a, b in zip(("x", "w_gate", "w_up", "w_down"), back(cot),
                          want):
        if name == "x":
            a, b = a[live], b[live]
        np.testing.assert_allclose(a, b, atol=2e-5, rtol=2e-5, err_msg=name)


def test_grouped_swiglu_rounds_the_stored_weights_in_the_kernel():
    """bfloat16 rows and float32 masters, the timed precision: the
    kernels round a weight block after its DMA, so the results are
    those of weights rounded beforehand (products of bfloat16 operands,
    float32 sums, hidden rounded once), and the weights' gradients come
    back in float32 as `moe_tgmm` summed them, never rounded to
    bfloat16 on the way."""
    tm, d, f = 16, 12, 10
    x, te, used, live, of_row = _ragged([20, 5, 0], tm, d, jnp.bfloat16)
    rng = np.random.default_rng(3)
    ws = [jnp.asarray(rng.normal(size=shape) * 0.3, jnp.float32)
          for shape in ((3, d, f), (3, d, f), (3, f, d))]
    rounded = [w.astype(jnp.bfloat16) for w in ws]
    got, back = jax.vjp(
        lambda x, *ws: grouped_swiglu(x, *ws, te, used, tm), x, *ws)
    want, back_rounded = jax.vjp(
        lambda x, *ws: grouped_swiglu(x, *ws, te, used, tm), x, *rounded)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_array_equal(got[live], want[live])
    np.testing.assert_allclose(
        got[live].astype(jnp.float32),
        _plain_swiglu(x[live].astype(jnp.float32),
                      *(w.astype(jnp.float32) for w in rounded), of_row),
        atol=0.03, rtol=0.03)
    cot = jnp.asarray(rng.normal(size=got.shape), jnp.bfloat16)
    grads, grads_rounded = back(cot), back_rounded(cot)
    np.testing.assert_array_equal(grads[0][live], grads_rounded[0][live])
    for g, r in zip(grads[1:], grads_rounded[1:]):
        assert g.dtype == jnp.float32 and r.dtype == jnp.bfloat16
        np.testing.assert_array_equal(g.astype(jnp.bfloat16), r)
        assert float(jnp.max(jnp.abs(g - r.astype(jnp.float32)))) > 0.0


# -- experts of two matrices with relu^2 between them -------------------------

def _relu2_layer(seed=2, e=16, scale=0.2):
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    full = {"router": jax.random.normal(ks[0], (D, e)) * 0.5,
            "router_bias": jax.random.normal(ks[1], (e,)) * 0.05,
            "w_up": jax.random.normal(ks[2], (e, D, F)) * scale,
            "w_down": jax.random.normal(ks[3], (e, F, D)) * scale,
            "shared_up": jax.random.normal(ks[4], (D, 2 * F)) * scale,
            "shared_down": jax.random.normal(ks[5], (2 * F, D)) * scale}
    return (full, jax.random.normal(ks[6], (T, D)),
            jax.random.normal(ks[7], (T, D)))


def _relu2_part(full, x, lo, hi, tile=8, e=16):
    params = {"router": full["router"], "router_bias": full["router_bias"],
              "w_up": full["w_up"][lo:hi], "w_down": full["w_down"][lo:hi]}
    cfg = HeldExperts(e, K, (lo, hi), True, tile, "sigmoid", 2.5, "relu2")
    return held_moe_ffn(params, x, cfg, jnp.float32)


def _relu2_dense(full, x, lo, hi, e=16):
    """A loop over the held experts, every token through each, times the
    routing weight (0 where the token did not choose it)."""
    ids, w = route_top_k(x @ full["router"], K, True, "sigmoid",
                         full["router_bias"], 2.5)
    out = jnp.zeros_like(x)
    for n in range(lo, hi):
        weight = jnp.sum(jnp.where(ids == n, w, 0.0), axis=1)
        hidden = jnp.square(jax.nn.relu(x @ full["w_up"][n]))
        out = out + weight[:, None] * (hidden @ full["w_down"][n])
    return out


@pytest.mark.parametrize("tile", [8, 16, 64])
@pytest.mark.parametrize("held", [(0, 4), (4, 12), (0, 16)])
def test_relu2_held_experts_equal_a_dense_loop(held, tile):
    full, x, ct = _relu2_layer()
    y, aux = _relu2_part(full, x, *held, tile=tile)
    np.testing.assert_allclose(y, _relu2_dense(full, x, *held),
                               atol=2e-5, rtol=2e-5)
    assert int(aux["dropped"]) == 0 and aux["counts_all"].sum() == T * K

    def mine(full, x):
        return jnp.sum(_relu2_part(full, x, *held, tile=tile)[0] * ct)

    def theirs(full, x):
        return jnp.sum(_relu2_dense(full, x, *held) * ct)

    got, want = (jax.grad(f, argnums=(0, 1))(full, x) for f in (mine, theirs))
    for name in ("router", "w_up", "w_down"):
        np.testing.assert_allclose(got[0][name], want[0][name], atol=3e-5,
                                   rtol=3e-4, err_msg=name)
    np.testing.assert_allclose(got[1], want[1], atol=3e-5, rtol=3e-4)
    assert not np.any(got[0]["router_bias"])     # the bias takes no gradient


def test_the_shares_of_an_expert_block_add_up_to_the_uncut_references():
    """The share test of the state-space configuration (guide section 4):
    4 shares of 4 of 16 experts. Every share routes over all 16, selects
    on score + bias, and gives its own experts' part; the parts added up,
    with the shared expert (which every chip computes alike) counted
    once, are the uncut reference's block."""
    from benchmark.reference import ssm_moe_lm

    full, x, _ = _relu2_layer(seed=3)
    cfg = {"num_experts_per_tok": K, "norm_topk_prob": True,
           "routed_scaling_factor": 2.5, "n_routed_experts": 16}
    flags = ssm_moe_lm.with_faults(cfg)
    whole = ssm_moe_lm.expert_mixer(full, x, cfg, flags, share=(0, 16))
    shared = ssm_moe_lm.relu2_ffn(x, full["shared_up"], full["shared_down"],
                                  flags)
    parts = [_relu2_part(full, x, lo, lo + 4) for lo in range(0, 16, 4)]
    np.testing.assert_allclose(sum(y for y, _ in parts) + shared, whole,
                               atol=3e-5, rtol=3e-5)
    counts = np.concatenate([np.asarray(a["counts"]) for _, a in parts])
    assert counts.sum() == T * K              # every choice computed once
    for (y, _), lo in zip(parts, range(0, 16, 4)):
        lp = dict(full, w_up=full["w_up"][lo:lo + 4],
                  w_down=full["w_down"][lo:lo + 4])
        np.testing.assert_allclose(
            y, ssm_moe_lm.expert_mixer(lp, x, cfg, flags,
                                       share=(lo, lo + 4), shared=False),
            atol=3e-5, rtol=3e-5)


def test_grouped_relu2_leaves_no_pass_over_the_buffer_outside_its_kernels():
    """What PR 43 took out for SwiGLU stays out for relu^2: between the
    rows and the result, forward and backward, the program is the
    kernels' calls alone (no square, maximum, multiply or convert of an
    (M, f) or (G, d, f) array)."""
    from pio_tpu.ops.moe import grouped_relu2

    m, g = 64, 4
    rows = jnp.ones((m, D), jnp.bfloat16)
    w_up, w_down = jnp.ones((g, D, F)), jnp.ones((g, F, D))
    te = jnp.repeat(jnp.arange(g, dtype=jnp.int32), m // 8 // g)

    def both(rows, w_up, w_down):
        out, back = jax.vjp(
            lambda *a: grouped_relu2(*a, te, jnp.array([m // 8]), 8),
            rows, w_up, w_down)
        return out, back(out)

    jaxpr = jax.make_jaxpr(both)(rows, w_up, w_down)
    names = [e.params["name"] for e in jaxpr.jaxpr.eqns
             if e.primitive.name == "pallas_call"]
    assert sorted(names) == ["moe_gmm", "moe_gmm", "moe_gmm_drelu2",
                             "moe_gmm_relu2", "moe_tgmm", "moe_tgmm"]
    # what else there is reads the one number of `tiles_used`
    others = [e for e in jaxpr.jaxpr.eqns if e.primitive.name != "pallas_call"]
    assert all(v.aval.size <= 1 for e in others for v in e.outvars), others
