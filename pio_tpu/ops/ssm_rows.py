"""The element-wise middle of a Mamba-2 block as row-tiled passes over
`in_proj`'s output where it lies: two ops, each a `jax.custom_vjp` of two
Pallas kernels (interpret mode on the CPU).

`u` below is the block's `in_proj` result (B, S, W) float32, the columns
[z | x B C | dt]. Neither op takes a slice of it: a kernel's block
specification addresses a column block inside `u` by its index, so no
contiguous copy of z or of x B C is ever made.

`conv_silu(u, w, bias, start, widths, dtype)`: the causal depthwise
convolution over positions of the columns [start, start + sum(widths)) of
u, then SiLU, float32 inside, written in `dtype` as one array a width (the
scan's x, B and C). out[t] = silu(sum_k w[k] u[t - (taps - 1) + k] + bias).
Forward (`ssm_conv_silu_fwd`) a grid step takes a tile of rows and a
column block; the taps - 1 earlier rows come from a second block of the
same operand, the eight rows before the tile (zeros before the first).
Backward (`ssm_conv_silu_bwd`) walks the row tiles in reverse: the
pre-activation is made again in the tile, d pre = dy silu'(pre), dx[t] =
sum_k w[k] d pre[t + (taps - 1) - k] with the next tile's first rows of
d pre carried in VMEM, dw and d bias summed over the row tiles in a
resident block.

`gated_norm(y, u, gain, groups, eps, dtype)`: RMSNorm_groups(y silu(z))
gain with z the columns [0, y's width) of u, each group's mean square a
sum over its own lanes (no (S, groups, width) view), written in `dtype`.
Backward (`ssm_gated_norm_bwd`) makes the group's statistics again in the
tile, reads y, z and d out once and writes dy and dz once; d gain is
summed over the row tiles in a resident block.

Inside a grid step the tile is walked `_SUB` rows at a time, so that a
chain from load to store stays in registers. `takes` says which shapes
the kernels take (whole lane tiles); `models/seq_blocks.py` keeps the
plain float32 functions they are tested against for the rest.
"""

from __future__ import annotations

from functools import partial
from math import gcd

import jax
import jax.numpy as jnp

F32 = jnp.float32
# a tile's rows and a column block's lanes at most, and the rows a chain
# of element-wise work takes at a time
_ROWS, _LANES, _SUB = 256, 512, 32
# rows of the block that holds a tile's earlier rows: a sublane tile
_HALO = 8
# lanes of one chain of element-wise work
_CHAIN = 128


def _interpret() -> bool:
    return jax.devices()[0].platform == "cpu"


def takes(positions: int, taps: int, *widths: int) -> bool:
    """Whether the kernels take these shapes: whole (16, 128) tiles, so
    that one block shape serves float32 and bfloat16, and the earlier
    rows of a tile within one sublane tile."""
    return (positions % 16 == 0 and 2 <= taps <= _HALO + 1
            and gcd(*widths) % 128 == 0)


def _row_tile(s: int, rows: int | None) -> int:
    """The most rows a tile may take (`rows`, or `_ROWS`) that divide s
    in whole steps of 16."""
    most = min(rows or _ROWS, s)
    return max(t for t in range(16, most + 1, 16) if s % t == 0)


def _sub_tile(t: int) -> int:
    """The rows a chain takes at a time in a tile of t: `_SUB`, or the
    most that divide t."""
    return max(m for m in range(16, min(_SUB, t) + 1, 16) if t % m == 0)


def _col_block(*widths: int) -> int:
    """The widest column block of whole lane tiles, `_LANES` at most,
    that divides every width."""
    g = gcd(*widths)
    return max(c for c in range(128, min(g, _LANES) + 1, 128) if g % c == 0)


def _sigmoid(x):
    return 1.0 / (1.0 + jnp.exp(-x))


def _fold(v):
    """(rows, C) -> (8, C): the sublane tiles added, no sum across
    sublanes."""
    return sum(v[a:a + _HALO] for a in range(0, v.shape[0], _HALO))


def _over_rows(t: int, body, carry, reverse: bool = False):
    """`body(r, sub, first, carry)` over a tile's t rows, `sub` at a time,
    r the sub-tile's first row; the tile's first sub-tile, whose earlier
    rows lie in another block, is traced apart (`first`)."""
    from jax.experimental import pallas as pl

    sub = _sub_tile(t)
    n = t // sub

    def step(k, carry):
        k = n - 1 - k if reverse else k + 1
        return body(pl.multiple_of(k * sub, sub), sub, False, carry)

    if n == 1:
        return body(0, sub, True, carry)
    if reverse:
        return body(0, sub, True, jax.lax.fori_loop(0, n - 1, step, carry))
    return jax.lax.fori_loop(0, n - 1, step, body(0, sub, True, carry))


# ---------------------------------------------------------------------------
# the convolution and its SiLU
# ---------------------------------------------------------------------------

def _lane_tiles(width: int):
    return [slice(a, a + _CHAIN) for a in range(0, width, _CHAIN)]


def _shifted(x_ref, head, r, sub: int, first: bool, lanes, taps: int):
    """x[t - k] for the sub-tile's rows t and k = 0 .. taps - 1, (sub,
    lanes) each: the sub-tile with the eight rows before it, rolled down
    the sublanes."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if first:
        xe = jnp.concatenate([head[:, lanes], x_ref[0, 0:sub, lanes]],
                             axis=0)
    else:
        xe = x_ref[0, pl.ds(r - _HALO, sub + _HALO), lanes]
    return [xe[_HALO:]] + [pltpu.roll(xe, k, 0)[_HALO:]
                           for k in range(1, taps)]


def _tap(w_ref, k: int, lanes):
    """The weight on x[t - k], a row."""
    taps = w_ref.shape[0]
    return w_ref[taps - 1 - k:taps - k, lanes]


def _pre(shifted, w_ref, b_ref, lanes):
    return sum(v * _tap(w_ref, k, lanes) for k, v in enumerate(shifted)
               ) + b_ref[:, lanes]


def _head(prev_ref, tile):
    """The eight rows before the tile; zeros before a history's first."""
    return jnp.where(tile == 0, 0.0, prev_ref[0])


def _conv_fwd_kernel(x_ref, prev_ref, w_ref, b_ref, *refs, bounds):
    from jax.experimental import pallas as pl

    *out_refs, y_ref = refs
    head = _head(prev_ref, pl.program_id(1))

    def rows(r, sub, first, carry):
        for lanes in _lane_tiles(x_ref.shape[2]):
            pre = _pre(_shifted(x_ref, head, r, sub, first, lanes,
                                w_ref.shape[0]), w_ref, b_ref, lanes)
            y_ref[pl.ds(r, sub), lanes] = (pre * _sigmoid(pre)).astype(
                y_ref.dtype)
        return carry

    _over_rows(x_ref.shape[1], rows, 0)
    j = pl.program_id(2)
    for out_ref, (lo, hi) in zip(out_refs, bounds):
        @pl.when((j >= lo) & (j < hi))
        def _(out_ref=out_ref):
            out_ref[0] = y_ref[...]


def _conv_bwd_kernel(x_ref, prev_ref, w_ref, b_ref, *refs, bounds, tiles):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n = len(bounds)
    dy_refs = refs[:n]
    dx_ref, dw_ref, db_ref, dy_ref, after_ref, sums_ref = refs[n:]
    i, j = pl.program_id(2), pl.program_id(1)
    for ref, (lo, hi) in zip(dy_refs, bounds):
        @pl.when((j >= lo) & (j < hi))
        def _(ref=ref):
            dy_ref[...] = ref[0].astype(F32)

    @pl.when(i == 0)
    def _start():
        dw_ref[...] = jnp.zeros_like(dw_ref)
        db_ref[...] = jnp.zeros_like(db_ref)
        # no position after a history's last
        after_ref[...] = jnp.zeros_like(after_ref)

    taps = w_ref.shape[0]
    head = _head(prev_ref, tiles - 1 - i)
    # the tile's sums for dw (a tap a sublane tile) and d bias (the last)
    sums_ref[...] = jnp.zeros_like(sums_ref)

    def rows(r, sub, first, carry):
        for lanes in _lane_tiles(x_ref.shape[2]):
            shifted = _shifted(x_ref, head, r, sub, first, lanes, taps)
            pre = _pre(shifted, w_ref, b_ref, lanes)
            sig = _sigmoid(pre)
            d_pre = dy_ref[pl.ds(r, sub), lanes] * (
                sig * (1.0 + pre * (1.0 - sig)))
            # d pre[t + k]: the sub-tile with the eight rows after it,
            # rolled up the sublanes (a kernel's body: vregs side by side)
            de = jnp.concatenate(  # pio: lint-ok[hot-loop-alloc]
                [d_pre, after_ref[:, lanes]], axis=0)
            after_ref[:, lanes] = d_pre[:_HALO]
            dx = d_pre * _tap(w_ref, 0, lanes)
            for k in range(1, taps):
                dx = dx + (pltpu.roll(de, sub + _HALO - k, 0)[:sub]
                           * _tap(w_ref, k, lanes))
            dx_ref[0, pl.ds(r, sub), lanes] = dx
            for k, v in enumerate(shifted + [1.0]):
                sums_ref[k * _HALO:(k + 1) * _HALO, lanes] += _fold(d_pre * v)
        return carry

    _over_rows(x_ref.shape[1], rows, 0, reverse=True)

    def total(k):
        return jnp.sum(sums_ref[k * _HALO:(k + 1) * _HALO, :], axis=0,
                       keepdims=True)

    for k in range(taps):
        dw_ref[0, taps - 1 - k:taps - k, :] += total(k)
    db_ref[0] += total(taps)


def _parts(start: int, widths, c: int):
    """The column blocks [lo, hi) of each output among the blocks of the
    convolved columns, and the index of the first in u."""
    edges = [0]
    for width in widths:
        edges.append(edges[-1] + width // c)
    return list(zip(edges[:-1], edges[1:])), start // c


@partial(jax.jit, static_argnums=(3, 4, 5, 6, 7), inline=True)
def _conv_fwd(u, w, bias, start, widths, dtype, rows, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bsz, s, _ = u.shape
    t, c = _row_tile(s, rows), _col_block(start, *widths)
    bounds, first = _parts(start, widths, c)

    def part(lo, hi):
        # a block stays where its last write left it while j is outside
        # [lo, hi): written back once, when the tile's row changes
        return pl.BlockSpec((1, t, c), lambda b, i, j: (
            b, i, jnp.clip(j - lo, 0, hi - lo - 1)))

    return pl.pallas_call(
        partial(_conv_fwd_kernel, bounds=bounds), name="ssm_conv_silu_fwd",
        grid=(bsz, s // t, bounds[-1][1]),
        in_specs=[
            pl.BlockSpec((1, t, c), lambda b, i, j: (b, i, first + j)),
            pl.BlockSpec((1, _HALO, c), lambda b, i, j: (
                b, jnp.maximum(i * (t // _HALO) - 1, 0), first + j)),
            pl.BlockSpec((w.shape[0], c), lambda b, i, j: (0, j)),
            pl.BlockSpec((1, c), lambda b, i, j: (0, j))],
        out_specs=[part(lo, hi) for lo, hi in bounds],
        out_shape=[jax.ShapeDtypeStruct((bsz, s, width), dtype)
                   for width in widths],
        scratch_shapes=[pltpu.VMEM((t, c), dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(u, u, w.astype(F32), bias.astype(F32).reshape(1, -1))


@partial(jax.jit, static_argnums=(4, 5, 6, 7), inline=True)
def _conv_bwd(u, w, bias, dys, start, widths, rows, interpret):
    """-> d of the convolved columns (B, S, sum(widths)) float32, dw
    (taps, C), d bias (C,)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bsz, s, _ = u.shape
    taps, total = w.shape[0], sum(widths)
    t, c = _row_tile(s, rows), _col_block(start, *widths)
    bounds, first = _parts(start, widths, c)
    tiles = s // t

    def at(i):
        return tiles - 1 - i

    def part(lo, hi):
        # outside [lo, hi) the block stays at the one the range starts
        # or ended with: fetched once
        return pl.BlockSpec((1, t, c), lambda b, j, i: (
            b, jnp.where(j < lo, at(0), jnp.where(j < hi, at(i), 0)),
            jnp.clip(j - lo, 0, hi - lo - 1)))

    def resident(rows):
        return pl.BlockSpec((1, rows, c), lambda b, j, i: (b, 0, j))

    dx, dw, db = pl.pallas_call(
        partial(_conv_bwd_kernel, bounds=bounds, tiles=tiles),
        name="ssm_conv_silu_bwd", grid=(bsz, bounds[-1][1], tiles),
        in_specs=[
            pl.BlockSpec((1, t, c), lambda b, j, i: (b, at(i), first + j)),
            pl.BlockSpec((1, _HALO, c), lambda b, j, i: (
                b, jnp.maximum(at(i) * (t // _HALO) - 1, 0), first + j)),
            pl.BlockSpec((taps, c), lambda b, j, i: (0, j)),
            pl.BlockSpec((1, c), lambda b, j, i: (0, j)),
        ] + [part(lo, hi) for lo, hi in bounds],
        out_specs=[
            pl.BlockSpec((1, t, c), lambda b, j, i: (b, at(i), j)),
            resident(taps), resident(1)],
        out_shape=[jax.ShapeDtypeStruct((bsz, s, total), F32),
                   jax.ShapeDtypeStruct((bsz, taps, total), F32),
                   jax.ShapeDtypeStruct((bsz, 1, total), F32)],
        scratch_shapes=[pltpu.VMEM((t, c), F32), pltpu.VMEM((_HALO, c), F32),
                        pltpu.VMEM(((taps + 1) * _HALO, c), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(u, u, w.astype(F32), bias.astype(F32).reshape(1, -1), *dys)
    return dx, jnp.sum(dw, axis=0), jnp.sum(db, axis=(0, 1))


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def conv_silu(u, w, bias, start: int, widths: tuple, dtype, rows=None):
    """u (B, S, W) float32; w (taps, C), the last tap on the current
    position, and bias (C,) over the C = sum(widths) columns from
    `start`. -> a (B, S, width) array in `dtype` a width. `rows`: a
    tile's rows at most (the tests' way to a tile's edge)."""
    return _conv_silu_fwd(u, w, bias, start, widths, dtype, rows)[0]


def _conv_silu_fwd(u, w, bias, start, widths, dtype, rows):
    outs = _conv_fwd(u, w, bias, start, tuple(widths), jnp.dtype(dtype),
                     rows, _interpret())
    return tuple(outs), (u, w, bias)


def _conv_silu_bwd(start, widths, dtype, rows, res, dys):
    u, w, bias = res
    dx, dw, db = _conv_bwd(u, w, bias, tuple(dys), start, tuple(widths),
                           rows, _interpret())
    du = jnp.pad(dx, ((0, 0), (0, 0),
                      (start, u.shape[2] - start - dx.shape[2])))
    return du.astype(u.dtype), dw.astype(w.dtype), db.astype(bias.dtype)


conv_silu.defvjp(_conv_silu_fwd, _conv_silu_bwd)


# ---------------------------------------------------------------------------
# the gated group norm
# ---------------------------------------------------------------------------

def _gated(y_ref, z_ref, r, sub, lanes, eps):
    """Of the sub-tile's rows and one group's lanes: y, z, sigmoid(z),
    g = y silu(z) and rsqrt(mean(g g) + eps), a number a row."""
    from jax.experimental import pallas as pl

    y, z = (ref[0, pl.ds(r, sub), lanes] for ref in (y_ref, z_ref))
    sig = _sigmoid(z)
    g = y * (z * sig)
    mean = jnp.sum(g * g, axis=1, keepdims=True) * (
        1.0 / (lanes.stop - lanes.start))
    return y, z, sig, g, jax.lax.rsqrt(mean + eps)


def _groups(width: int, group: int):
    return [slice(a, a + group) for a in range(0, width, group)]


def _norm_fwd_kernel(y_ref, z_ref, gain_ref, out_ref, *, group, eps):
    def rows(r, sub, first, carry):
        from jax.experimental import pallas as pl

        for lanes in _groups(y_ref.shape[2], group):
            _, _, _, g, scale = _gated(y_ref, z_ref, r, sub, lanes, eps)
            out_ref[0, pl.ds(r, sub), lanes] = (
                g * scale * gain_ref[:, lanes]).astype(out_ref.dtype)
        return carry

    _over_rows(y_ref.shape[1], rows, 0)


def _norm_bwd_kernel(y_ref, z_ref, gain_ref, do_ref, dy_ref, dz_ref,
                     dgain_ref, *, group, eps):
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(2) == 0)
    def _start():
        dgain_ref[...] = jnp.zeros_like(dgain_ref)

    groups = _groups(y_ref.shape[2], group)

    def rows(r, sub, first, sums):
        out = []
        for lanes, acc in zip(groups, sums):
            y, z, sig, g, scale = _gated(y_ref, z_ref, r, sub, lanes, eps)
            d_out = do_ref[0, pl.ds(r, sub), lanes].astype(F32)
            out.append(acc + _fold(d_out * g * scale))
            d_normed = d_out * gain_ref[:, lanes]
            # out = g scale(g): the scale's own part takes the row's
            # mean of d normed . g
            dg = d_normed * scale - g * (
                jnp.sum(d_normed * g, axis=1, keepdims=True)
                * (scale * scale * scale * (1.0 / group)))
            dy_ref[0, pl.ds(r, sub), lanes] = dg * (z * sig)
            dz_ref[0, pl.ds(r, sub), lanes] = dg * y * (
                sig * (1.0 + z * (1.0 - sig)))
        return tuple(out)

    sums = _over_rows(
        y_ref.shape[1], rows,
        (jnp.zeros((_HALO, group), F32),) * len(groups))
    for lanes, acc in zip(groups, sums):
        dgain_ref[0, :, lanes] += jnp.sum(acc, axis=0, keepdims=True)


def _norm_block(width: int, groups: int) -> int:
    """A column block of whole groups, `_LANES` wide at most unless one
    group is wider."""
    group = width // groups
    most = max(m for m in range(1, groups + 1)
               if groups % m == 0 and m * group <= max(_LANES, group))
    return most * group


@partial(jax.jit, static_argnums=(3, 4, 5, 6, 7), inline=True)
def _norm_fwd(y, u, gain, groups, eps, dtype, rows, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bsz, s, width = y.shape
    t, c = _row_tile(s, rows), _norm_block(width, groups)
    tile = pl.BlockSpec((1, t, c), lambda b, i, j: (b, i, j))
    return pl.pallas_call(
        partial(_norm_fwd_kernel, group=width // groups, eps=eps),
        name="ssm_gated_norm_fwd", grid=(bsz, s // t, width // c),
        in_specs=[tile, tile, pl.BlockSpec((1, c), lambda b, i, j: (0, j))],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct(y.shape, dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        interpret=interpret,
    )(y.astype(F32), u, gain.astype(F32).reshape(1, -1))


@partial(jax.jit, static_argnums=(4, 5, 6, 7), inline=True)
def _norm_bwd(y, u, gain, d_out, groups, eps, rows, interpret):
    """-> dy, dz (B, S, width) float32 and d gain (width,)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bsz, s, width = y.shape
    t, c = _row_tile(s, rows), _norm_block(width, groups)
    tile = pl.BlockSpec((1, t, c), lambda b, j, i: (b, i, j))
    wide = jax.ShapeDtypeStruct(y.shape, F32)
    dy, dz, dgain = pl.pallas_call(
        partial(_norm_bwd_kernel, group=width // groups, eps=eps),
        name="ssm_gated_norm_bwd", grid=(bsz, width // c, s // t),
        in_specs=[tile, tile, pl.BlockSpec((1, c), lambda b, j, i: (0, j)),
                  tile],
        out_specs=[tile, tile,
                   pl.BlockSpec((1, 1, c), lambda b, j, i: (b, 0, j))],
        out_shape=[wide, wide, jax.ShapeDtypeStruct((bsz, 1, width), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(y.astype(F32), u, gain.astype(F32).reshape(1, -1), d_out)
    return dy, dz, jnp.sum(dgain, axis=(0, 1))


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def gated_norm(y, u, gain, groups: int, eps: float, dtype, rows=None):
    """y (B, S, C) float32, the scan's result; u (B, S, W) float32 whose
    first C columns are the gate z; gain (C,). -> (B, S, C) in `dtype`."""
    return _gated_norm_fwd(y, u, gain, groups, eps, dtype, rows)[0]


def _gated_norm_fwd(y, u, gain, groups, eps, dtype, rows):
    out = _norm_fwd(y, u, gain, groups, float(eps), jnp.dtype(dtype), rows,
                    _interpret())
    return out, (y, u, gain)


def _gated_norm_bwd(groups, eps, dtype, rows, res, d_out):
    y, u, gain = res
    dy, dz, dgain = _norm_bwd(y, u, gain, d_out, groups, float(eps), rows,
                              _interpret())
    du = jnp.pad(dz, ((0, 0), (0, 0), (0, u.shape[2] - dz.shape[2])))
    return dy.astype(y.dtype), du.astype(u.dtype), dgain.astype(gain.dtype)


gated_norm.defvjp(_gated_norm_fwd, _gated_norm_bwd)
