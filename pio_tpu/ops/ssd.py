"""The state-space scan of a Mamba-2 layer, computed in chunks (the
"state-space duality" form), as one op with its own backward pass.

For every head h (of H, each P wide) with a state of P x N, its group's
B_t and C_t (G groups of N; H / G heads share a group's), a step dt_t > 0
(after softplus) and a decay rate A_h < 0:

    h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T          (P x N, h_0 = 0)
    y_t = h_t C_t + D x_t

In chunks of Q positions, with a_t = dt_t A, cum_t the running sum of a
inside the chunk (inclusive), X~ = dt x:

    Y       = ((C B^T) o L) X~ + exp(cum) o (C h_in) + D x
    L_ts    = exp(cum_t - cum_s) for s <= t, else 0
    S       = sum_s exp(cum_end - cum_s) X~_s B_s^T     (the chunk's own)
    h_in'   = exp(cum_end) h_in + S                     (into the next)

The running sums, L, the carried states h_in (B, S / Q, H, P, N) and the
carry's recurrence are float32; the products take operands in x's dtype
(bfloat16 in the block stack) and accumulate in float32. The result does
not depend on Q beyond rounding.

`ssd_scan` is a `jax.custom_vjp`. Forward it keeps h_in, under the name
`KEPT_STATES` (a `jax.checkpoint` policy of the caller keeps it by that
name, as ops/attention.py KEPT_RESIDUALS are kept), and backward it makes
the chunk-local quantities again from the inputs and h_in: one reverse
pass of the carry gives every chunk the gradient of the state it hands
on, and the rest is local to a chunk. Gradients for x, dt, A, B, C, D.

The chunk-local work and the carry are two Pallas kernels
(`ssd_chunk_fwd`, `ssd_chunk_bwd`: a program a (history, group of heads),
the chunks in order, the carried state in VMEM; interpret mode on the
CPU). A grid step takes its group's heads together with the chunk's
positions on the lanes (the comment over the kernels has the layout);
what is a number a (position, head) goes in and comes out in the one
layout `rows` (B, G, R, S). The kernels of PR 45 took a head at a time,
positions down the sublanes: 3.99 ms a backward call and 1.19 a forward
one in the state-space cell at (1, 8192, 64, 64) / (8, 128), made of lane
broadcasts, lane sums and masked column stores, against 1.105 and 0.64
(PERF.md section 6, PR 47, has both schedules and the readings). XLA's lowering of
the same chunked algebra was written beside those and timed on the v5e:
9.1 ms forward and 25.9 forward + backward at two histories against their
5.1 and 15.8, a step of the cell 0.652 s against 0.632; it went (PERF.md
section 6, PR 45).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

# `checkpoint_name` of the states entering each chunk, the one residual
# the forward pass keeps beside its inputs
KEPT_STATES = "ssd_chunk_states"
F32 = jnp.float32


def _interpret() -> bool:
    return jax.devices()[0].platform == "cpu"


def ssd_reference(x, dt, a, b_mat, c_mat, d):
    """The recurrence itself, a position at a time, in float32: the
    oracle of the op's tests. Shapes as `ssd_scan`."""
    bsz, s, h, p = x.shape
    g, n = b_mat.shape[2:]
    rep = h // g
    x, dt, b_mat, c_mat = (v.astype(F32) for v in (x, dt, b_mat, c_mat))

    def step(state, inp):
        x_t, dt_t, b_t, c_t = inp            # (B,H,P) (B,H) (B,G,N) (B,G,N)
        b_h = jnp.repeat(b_t, rep, axis=1)
        c_h = jnp.repeat(c_t, rep, axis=1)
        state = (jnp.exp(dt_t * a)[..., None, None] * state
                 + (dt_t[..., None] * x_t)[..., None] * b_h[:, :, None, :])
        y = jnp.sum(state * c_h[:, :, None, :], axis=-1) + d[:, None] * x_t
        return state, y

    _, ys = jax.lax.scan(
        step, jnp.zeros((bsz, h, p, n), F32),
        tuple(jnp.moveaxis(v, 1, 0) for v in (x, dt, b_mat, c_mat)))
    return jnp.moveaxis(ys, 0, 1)


# ---------------------------------------------------------------------------
# the kernels
# ---------------------------------------------------------------------------
#
# A program a (history, group of R heads); its grid's last axis walks the
# chunks in order (backward: in reverse) and the carried state of the
# group, (R P, N) float32, stays in VMEM from one chunk to the next, so the
# recurrence over chunks costs no pass of its own and no (Q, Q) array
# leaves the chip.
#
# A grid step takes the group's R heads together with the chunk's Q
# positions on the lanes. A number a (position, head) (dt, the running
# sums, their exponentials, and the results d dt, d cum, d D) is a row of
# an (R, Q) tile: XLA hands them over and takes them back in the one
# layout `rows` (B, G, R, S), and nothing narrower than the lanes goes to
# HBM. The block of x (Q, R P) is turned once a step to (R P, Q), a head's
# tile is its P rows of that, (P, Q) lane-dense: scaling by a position's
# number is a row broadcast down the sublanes, and a sum over P lands as a
# row. What all heads share is made once a step: the mask, C B^T, and one
# product each over the group's state for C h^T, B g^T, dC, dB and the two
# state updates, with a head's decay on the operand or the result. A head's
# own products are the three with its (Q, Q) decays on one side; the one
# column the decays need (cum_t down the sublanes) comes from turning the
# (R, Q) sums once a step.
#
# The gradient of the running sums needs no sum over a (Q, Q) tile: cum_t
# enters what position t reads with a plus (y_t less the skip) and what it
# hands on with a minus (x~_t, to later positions and to the end state), so
# d cum_t = dy_t . (y_t - D x_t) - x~_t . dx~_t (+ at the chunk's end what
# the end state's decay takes), the operands as the products saw them.

_MASKED = -1e30     # exp of it is 0


def _small(dt, a, chunk: int, g: int):
    """-> dt and the running sums of dt A by chunk, `rows` (B, G, R, S)
    float32 each: a (position, head) number along the positions."""
    bsz, s, h = dt.shape
    r = h // g
    dtc = dt.astype(F32).reshape(bsz, s // chunk, chunk, g, r)
    cum = jnp.cumsum(dtc * a.astype(F32).reshape(g, r), axis=2)

    def rows(v):
        return jnp.transpose(v.reshape(bsz, s, g, r), (0, 2, 3, 1))

    return rows(dtc), rows(cum)


def _dot(a, b, dims):
    """Operands in x's dtype, the sum float32. Float32 operands give
    float32 products whatever precision the caller's context asks for:
    d cum's two halves cancel only if the element-wise side sees the
    operands the products saw."""
    return jax.lax.dot_general(
        a, b, (dims, ((), ())), preferred_element_type=F32,
        precision=jax.lax.Precision.HIGHEST if a.dtype == F32 else None)


_NN, _NT, _TN = ((1,), (0,)), ((1,), (1,)), ((0,), (0,))


def _group_parts(x_ref, dt_ref, cum_ref):
    """What both kernels make of the group first: x turned, (R P, Q)
    float32; dt, cum, exp(cum) and exp(cum_end - cum) as rows (R, Q);
    cum_end (R, 1); and a function of r, head r's decays L (Q, Q), t
    down the sublanes and s along the lanes."""
    q = x_ref.shape[1]
    dt, cum = dt_ref[0, 0], cum_ref[0, 0]
    end = cum[:, q - 1:q]
    cum_col = cum.T                                       # (Q, R)
    keep = (jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
            >= jax.lax.broadcasted_iota(jnp.int32, (q, q), 1))

    def decays(r):
        return jnp.exp(jnp.where(
            keep, cum_col[:, r:r + 1] - _row(cum, r), _MASKED))

    return (x_ref[0].T.astype(F32), dt, jnp.exp(cum), jnp.exp(end - cum),
            end, decays)


def _row(v, r):
    return v[r:r + 1, :]


def _decay(end, r, n):
    """exp(cum_end) of head r along a state's row, (1, N): Mosaic
    broadcasts along one axis at a time."""
    return jnp.exp(jnp.broadcast_to(end[r:r + 1], (1, n)))


def _fwd_kernel(x_ref, b_ref, c_ref, d_ref, dt_ref, cum_ref,
                y_ref, hin_ref, h_ref, *, heads: int, p: int):
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(2) == 0)
    def _start():
        h_ref[...] = jnp.zeros_like(h_ref)

    dtype = x_ref.dtype
    n = b_ref.shape[2]
    bm, cm = b_ref[0], c_ref[0]                           # (Q, N)
    xt, dt, e_cum, to_end, end, decays = _group_parts(x_ref, dt_ref, cum_ref)
    h = h_ref[...]                                        # (R P, N)
    hin_ref[0, 0] = h.reshape(heads, p, n)
    cb = _dot(cm, bm, _NT)                                # (Q, Q): t, s
    from_h = _dot(h.astype(dtype), cm, _NT)               # (R P, Q)
    ys, xws = [], []
    for r in range(heads):
        rows = slice(r * p, (r + 1) * p)
        x = xt[rows]                                      # (P, Q)
        xdt = x * _row(dt, r)
        y = _dot(xdt.astype(dtype), (cb * decays(r)).astype(dtype), _NT)
        ys.append(y + from_h[rows] * _row(e_cum, r)
                  + x * _row(d_ref[0], r))
        xws.append((xdt * _row(to_end, r)).astype(dtype))
    y_ref[0] = jnp.concatenate(ys, axis=0).T
    update = _dot(jnp.concatenate(xws, axis=0), bm, _NN)  # (R P, N)
    for r in range(heads):
        rows = slice(r * p, (r + 1) * p)
        h_ref[rows, :] = _decay(end, r, n) * h[rows] + update[rows]


def _bwd_kernel(x_ref, b_ref, c_ref, d_ref, dt_ref, cum_ref, hin_ref,
                dy_ref, dx_ref, db_ref, dc_ref, ddt_ref, dcum_ref, dd_ref,
                g_ref, *, heads: int, p: int):
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(2) == 0)
    def _start():
        g_ref[...] = jnp.zeros_like(g_ref)

    dtype = x_ref.dtype
    q, n = b_ref.shape[1:]
    bm, cm = b_ref[0], c_ref[0]
    xt, dt, e_cum, to_end, end, decays = _group_parts(x_ref, dt_ref, cum_ref)
    dyt = dy_ref[0].T                                     # (R P, Q) f32
    h = hin_ref[0, 0].reshape(heads * p, n)
    g_next = g_ref[...]
    gb = g_next.astype(dtype)
    cb = _dot(cm, bm, _NT)
    from_h = _dot(h.astype(dtype), cm, _NT)               # (R P, Q)
    from_g = _dot(gb, bm, _NT)                            # (R P, Q)
    last = jax.lax.broadcasted_iota(jnp.int32, (1, q), 1) == q - 1
    dcb = jnp.zeros((q, q), F32)
    dxs, dyws, xws = [], [], []
    for r in range(heads):
        rows = slice(r * p, (r + 1) * p)
        x, dy = xt[rows], dyt[rows]                       # (P, Q)
        dyb = dy.astype(dtype)
        xdt_exact = x * _row(dt, r)
        xdtb = xdt_exact.astype(dtype)
        # x~ as the products see it: d cum's two halves are made of the
        # same rounded terms, and cancel in a chunk's total as they should
        xdt = xdtb.astype(F32)
        decays_r = decays(r)
        scores = (cb * decays_r).astype(dtype)
        # what the positions read, but for the skip
        y = (_dot(xdtb, scores, _NT) + from_h[rows] * _row(e_cum, r))
        # the chunk's own end state, whose gradient is g_next
        state_side = from_g[rows] * _row(to_end, r)
        dxdt = state_side + _dot(dyb, scores, _NN)
        dcb = dcb + _dot(dyb, xdtb, _TN) * decays_r
        dw = jnp.sum(state_side * xdt, axis=0, keepdims=True)     # (1, Q)
        d_end = (jnp.sum(dw, axis=1, keepdims=True)
                 + _decay(end, r, 1) * jnp.sum(g_next[rows] * h[rows]))
        # cum_t is in what t reads with a plus and in what t hands on
        # with a minus
        dcum_ref[0, 0, r:r + 1, :] = (
            jnp.sum(dyb.astype(F32) * y - xdt * dxdt, axis=0, keepdims=True)
            + jnp.where(last, d_end, 0.0))
        ddt_ref[0, 0, r:r + 1, :] = jnp.sum(dxdt * x, axis=0, keepdims=True)
        dd_ref[0, 0, r:r + 1, :] = jnp.sum(dy * x, axis=0, keepdims=True)
        dxs.append(dxdt * _row(dt, r) + dy * _row(d_ref[0], r))
        dyws.append((dy * _row(e_cum, r)).astype(dtype))
        xws.append((xdt_exact * _row(to_end, r)).astype(dtype))
    dx_ref[0] = jnp.concatenate(dxs, axis=0).T.astype(dx_ref.dtype)
    dyw, xw = jnp.concatenate(dyws, axis=0), jnp.concatenate(xws, axis=0)
    dcbb = dcb.astype(dtype)
    db_ref[0] = (_dot(xw, gb, _TN) + _dot(dcbb, cm, _TN)).astype(db_ref.dtype)
    dc_ref[0] = (_dot(dyw, h.astype(dtype), _TN)
                 + _dot(dcbb, bm, _NN)).astype(dc_ref.dtype)
    update = _dot(dyw, cm, _NN)                           # (R P, N)
    for r in range(heads):
        rows = slice(r * p, (r + 1) * p)
        g_ref[rows, :] = _decay(end, r, n) * g_next[rows] + update[rows]


def _kernel_specs(x, b_mat, chunk: int, reverse: bool):
    """The block specifications both kernels share, by the layout of the
    array: wide (B, S, H P), group (B, S, G N), rows (B, G, R, S), skip
    (G, R, Q), state (B, C, H, P, N)."""
    from jax.experimental import pallas as pl

    bsz, s, h, p = x.shape
    g, n = b_mat.shape[2:]
    r, nc = h // g, s // chunk

    def at(c):
        return nc - 1 - c if reverse else c

    return {
        "wide": pl.BlockSpec((1, chunk, r * p),
                             lambda i, j, c: (i, at(c), j)),
        "group": pl.BlockSpec((1, chunk, n), lambda i, j, c: (i, at(c), j)),
        "skip": pl.BlockSpec((1, r, chunk), lambda i, j, c: (j, 0, 0)),
        "rows": pl.BlockSpec((1, 1, r, chunk),
                             lambda i, j, c: (i, j, 0, at(c))),
        "state": pl.BlockSpec((1, 1, r, p, n),
                              lambda i, j, c: (i, at(c), j, 0, 0)),
    }, (bsz, g, nc)


def _kernel_call(kernel, name, x, b_mat, chunk, reverse, interpret, ins,
                 outs):
    """`ins` / `outs`: (layout, array) / (layout, shape and dtype)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    specs, grid = _kernel_specs(x, b_mat, chunk, reverse)
    bsz, s, h, p = x.shape
    g, n = b_mat.shape[2:]
    return pl.pallas_call(
        partial(kernel, heads=h // g, p=p), name=name, grid=grid,
        in_specs=[specs[layout] for layout, _ in ins],
        out_specs=[specs[layout] for layout, _ in outs],
        out_shape=[shape for _, shape in outs],
        scratch_shapes=[pltpu.VMEM((h // g * p, n), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*(array for _, array in ins))


def _kernel_inputs(x, dt, a, b_mat, c_mat, d, chunk):
    bsz, s, h, p = x.shape
    g, n = b_mat.shape[2:]
    dt_r, cum_r = _small(dt, a, chunk, g)
    skip = jnp.broadcast_to(d.astype(F32).reshape(g, h // g, 1),
                            (g, h // g, chunk))
    return [("wide", x.reshape(bsz, s, h * p)),
            ("group", b_mat.reshape(bsz, s, g * n)),
            ("group", c_mat.reshape(bsz, s, g * n)), ("skip", skip),
            ("rows", dt_r), ("rows", cum_r)], dt_r


@partial(jax.jit, static_argnums=(6, 7), inline=True)
def _scan(x, dt, a, b_mat, c_mat, d, chunk: int, interpret: bool):
    """`ssd_chunk_fwd`: y and the states entering each chunk. Jitted and
    inlined into its caller, as `_grads` is: a step's blocks of one shape,
    and a block's recomputation, then share one traced kernel and one
    lowering of it (a body of R unrolled heads)."""
    bsz, s, h, p = x.shape
    n = b_mat.shape[3]
    y, h_in = _kernel_call(
        _fwd_kernel, "ssd_chunk_fwd", x, b_mat, chunk, False, interpret,
        _kernel_inputs(x, dt, a, b_mat, c_mat, d, chunk)[0],
        [("wide", jax.ShapeDtypeStruct((bsz, s, h * p), F32)),
         ("state", jax.ShapeDtypeStruct((bsz, s // chunk, h, p, n), F32))])
    return y.reshape(x.shape), h_in


@partial(jax.jit, static_argnums=(8, 9), inline=True)
def _grads(x, dt, a, b_mat, c_mat, d, h_in, dy, chunk: int, interpret: bool):
    bsz, s, h, p = x.shape
    g, n = b_mat.shape[2:]
    r = h // g
    rows = jax.ShapeDtypeStruct((bsz, g, r, s), F32)
    ins, dt_r = _kernel_inputs(x, dt, a, b_mat, c_mat, d, chunk)
    dx, db, dc, ddt_x, dcum, dd = _kernel_call(
        _bwd_kernel, "ssd_chunk_bwd", x, b_mat, chunk, True, interpret,
        ins + [("state", h_in.reshape(bsz, s // chunk, h, p, n)),
               ("wide", dy.reshape(bsz, s, h * p))],
        [("wide", jax.ShapeDtypeStruct((bsz, s, h * p), x.dtype)),
         ("group", jax.ShapeDtypeStruct((bsz, s, g * n), b_mat.dtype)),
         ("group", jax.ShapeDtypeStruct((bsz, s, g * n), b_mat.dtype)),
         ("rows", rows), ("rows", rows), ("rows", rows)])

    def by_chunk(v):          # (B, G, R, S) -> (B, G, R, C, Q)
        return v.reshape(bsz, g, r, s // chunk, chunk)

    da = jnp.flip(jnp.cumsum(jnp.flip(by_chunk(dcum), axis=4), axis=4),
                  axis=4)
    ddt = (da * a.astype(F32).reshape(g, r, 1, 1)).reshape(rows.shape) + ddt_x
    return (dx.reshape(x.shape),
            jnp.transpose(ddt, (0, 3, 1, 2)).reshape(dt.shape).astype(
                dt.dtype),
            jnp.sum(da * by_chunk(dt_r), axis=(0, 3, 4)).reshape(-1).astype(
                a.dtype),
            db.reshape(b_mat.shape), dc.reshape(c_mat.shape),
            jnp.sum(dd, axis=(0, 3)).reshape(-1).astype(d.dtype))


# ---------------------------------------------------------------------------
# the op
# ---------------------------------------------------------------------------

@partial(jax.custom_vjp, nondiff_argnums=(6,))
def ssd_scan(x, dt, a, b_mat, c_mat, d, chunk: int = 128):
    """x (B, S, H, P); dt (B, S, H) float32, positive; a (H,) float32,
    negative; b_mat, c_mat (B, S, G, N), G dividing H; d (H,). -> y
    (B, S, H, P) float32. `chunk` divides S."""
    return _ssd_fwd(x, dt, a, b_mat, c_mat, d, chunk)[0]


def _ssd_fwd(x, dt, a, b_mat, c_mat, d, chunk):
    s, h, g = x.shape[1], x.shape[2], b_mat.shape[2]
    if s % chunk or h % g:
        raise ValueError(
            f"a scan over {s} positions in chunks of {chunk}, {h} heads in "
            f"{g} groups: the chunk must divide the positions and the "
            "groups the heads")
    y, h_in = _scan(x, dt, a, b_mat, c_mat, d, chunk, _interpret())
    h_in = checkpoint_name(h_in, KEPT_STATES)
    return y, (x, dt, a, b_mat, c_mat, d, h_in)


def _ssd_bwd(chunk, res, dy):
    return _grads(*res, dy, chunk, _interpret())


ssd_scan.defvjp(_ssd_fwd, _ssd_bwd)
