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
CPU). XLA's lowering of the same chunked algebra was written beside them
and timed on the v5e at (2, 8192, 64, 64) / (8, 128): 9.1 ms forward and
25.9 forward + backward against the kernels' 5.1 and 15.8 (3.6 / 13.4
against 2.7 / 11.9 a history), and a step of the state-space cell 0.652 s
against 0.632; it went (PERF.md section 6, PR 45, has the readings).
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
# chunks in order (backward: in reverse) and the carried state of the R
# heads, (R, P, N) float32, stays in VMEM from one chunk to the next, so
# the recurrence over chunks costs no pass of its own and no (Q, Q) array
# leaves the chip. What is a number a (position, head) is made in XLA and
# handed over in both layouts a kernel reads it in: `cols` (B, G, S, R)
# (a head's values down the positions, to scale rows) and `rows` (B, G, R,
# S) (along them, for the (Q, Q) decays).

_MASKED = -1e30     # exp of it is 0


def _small(dt, a, chunk: int, g: int):
    """-> dt and the running sums of dt A by chunk, (B, G, S, R) float32
    each, and the sums again as (B, G, R, S)."""
    bsz, s, h = dt.shape
    r = h // g
    dtc = dt.astype(F32).reshape(bsz, s // chunk, chunk, g, r)
    cum = jnp.cumsum(dtc * a.astype(F32).reshape(g, r), axis=2)

    def cols(v):
        return jnp.moveaxis(v.reshape(bsz, s, g, r), 2, 1)

    return cols(dtc), cols(cum), jnp.moveaxis(cols(cum), 2, 3)


def _head_parts(r, p, n, x_ref, dt_ref, cumc_ref, cumr_ref):
    """What both kernels make of head r of the block first: x, X~ = dt x,
    X~ exp(cum_end - cum) (float32, (Q, P)), the columns exp(cum) and
    exp(cum_end - cum) (Q, 1), exp(cum_end) along a state's row (1, N:
    Mosaic broadcasts along one axis at a time), and L (Q, Q)."""
    q = x_ref.shape[1]
    x = x_ref[0, :, r * p:(r + 1) * p].astype(F32)
    cum = cumc_ref[0, 0, :, r:r + 1]                      # (Q, 1)
    end = cumc_ref[0, 0, q - 1:q, r:r + 1]                # (1, 1)
    xdt = x * dt_ref[0, 0, :, r:r + 1]
    to_end = jnp.exp(end - cum)
    seg = cum - cumr_ref[0, 0, r:r + 1, :]                # (Q, Q)
    keep = (jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
            >= jax.lax.broadcasted_iota(jnp.int32, (q, q), 1))
    decays = jnp.exp(jnp.where(keep, seg, _MASKED))
    decay = jnp.exp(jnp.broadcast_to(end, (1, n)))
    return x, xdt, xdt * to_end, jnp.exp(cum), to_end, decay, decays


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, (dims, ((), ())),
                               preferred_element_type=F32)


_NN, _NT, _TN = ((1,), (0,)), ((1,), (1,)), ((0,), (0,))


def _fwd_kernel(x_ref, b_ref, c_ref, d_ref, dt_ref, cumc_ref, cumr_ref,
                y_ref, hin_ref, h_ref, *, heads: int, p: int):
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(2) == 0)
    def _start():
        h_ref[...] = jnp.zeros_like(h_ref)

    dtype = x_ref.dtype
    bm, cm = b_ref[0], c_ref[0]                           # (Q, N)
    cb = _dot(cm, bm, _NT)                                # (Q, Q)
    for r in range(heads):
        x, xdt, xw, e_cum, _, decay, decays = _head_parts(
            r, p, bm.shape[1], x_ref, dt_ref, cumc_ref, cumr_ref)
        h = h_ref[r]                                      # (P, N)
        hin_ref[0, 0, r] = h
        y = _dot((cb * decays).astype(dtype), xdt.astype(dtype), _NN)
        y = y + _dot(cm, h.astype(dtype), _NT) * e_cum
        y_ref[0, :, r * p:(r + 1) * p] = (
            y + d_ref[0, :, r * p:(r + 1) * p] * x)
        h_ref[r] = decay * h + _dot(xw.astype(dtype), bm, _TN)


def _bwd_kernel(x_ref, b_ref, c_ref, d_ref, dt_ref, cumc_ref, cumr_ref,
                hin_ref, dy_ref, dx_ref, db_ref, dc_ref, ddt_ref, dcumc_ref,
                dcumr_ref, dd_ref, g_ref, *, heads: int, p: int):
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(2) == 0)
    def _start():
        g_ref[...] = jnp.zeros_like(g_ref)

    dtype = x_ref.dtype
    q = x_ref.shape[1]
    bm, cm = b_ref[0], c_ref[0]
    cb = _dot(cm, bm, _NT)
    last = jax.lax.broadcasted_iota(jnp.int32, (q, 1), 0) == q - 1
    db = jnp.zeros(bm.shape, F32)
    dc = jnp.zeros(cm.shape, F32)
    dcb = jnp.zeros((q, q), F32)
    for r in range(heads):
        x, xdt, xw, e_cum, to_end, decay, decays = _head_parts(
            r, p, bm.shape[1], x_ref, dt_ref, cumc_ref, cumr_ref)
        dy = dy_ref[0, :, r * p:(r + 1) * p].astype(F32)
        dyb, xdtb = dy.astype(dtype), xdt.astype(dtype)
        h, g_next = hin_ref[0, 0, r], g_ref[r]            # (P, N)
        hb, gb = h.astype(dtype), g_next.astype(dtype)
        # the read-out of the entering state
        dyw = (dy * e_cum).astype(dtype)
        dc = dc + _dot(dyw, hb, _NN)
        dcum = jnp.sum(dy * _dot(cm, hb, _NT), axis=1, keepdims=True) * e_cum
        # the chunk's own end state, whose gradient is g_next
        from_state = _dot(bm, gb, _NT)                    # (Q, P)
        dxdt = to_end * from_state
        db = db + _dot(xw.astype(dtype), gb, _NN)
        dw = jnp.sum(from_state * xdtb.astype(F32), axis=1,
                     keepdims=True) * to_end
        d_end = jnp.sum(dw) + decay[:, :1] * jnp.sum(g_next * h)
        dcum = dcum - dw + jnp.where(last, d_end, 0.0)
        # inside the chunk
        scores = (cb * decays).astype(dtype)
        dxdt = dxdt + _dot(scores, dyb, _TN)
        dml = _dot(dyb, xdtb, _NT) * decays
        dcb = dcb + dml
        dll = dml * cb
        dcumc_ref[0, 0, :, r:r + 1] = dcum + jnp.sum(dll, axis=1,
                                                      keepdims=True)
        dcumr_ref[0, 0, r:r + 1, :] = -jnp.sum(dll, axis=0, keepdims=True)
        ddt_ref[0, 0, :, r:r + 1] = jnp.sum(dxdt * x, axis=1, keepdims=True)
        dd_ref[0, 0, :, r:r + 1] = jnp.sum(dy * x, axis=1, keepdims=True)
        dx_ref[0, :, r * p:(r + 1) * p] = (
            dxdt * dt_ref[0, 0, :, r:r + 1]
            + d_ref[0, :, r * p:(r + 1) * p] * dy).astype(dx_ref.dtype)
        g_ref[r] = _dot(dyw, cm, _TN) + decay * g_next
    dcbb = dcb.astype(dtype)
    db_ref[0] = (db + _dot(dcbb, cm, _TN)).astype(db_ref.dtype)
    dc_ref[0] = (dc + _dot(dcbb, bm, _NN)).astype(dc_ref.dtype)


def _kernel_specs(x, b_mat, chunk: int, reverse: bool):
    """The block specifications both kernels share, by the layout of the
    array: wide (B, S, H P), group (B, S, G N), skip (G, 1, R P), cols
    (B, G, S, R), rows (B, G, R, S), state (B, C, H, P, N)."""
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
        "skip": pl.BlockSpec((1, 1, r * p), lambda i, j, c: (j, 0, 0)),
        "cols": pl.BlockSpec((1, 1, chunk, r),
                             lambda i, j, c: (i, j, at(c), 0)),
        "rows": pl.BlockSpec((1, 1, r, chunk),
                             lambda i, j, c: (i, j, 0, at(c))),
        "state": pl.BlockSpec((1, 1, r, p, n),
                              lambda i, j, c: (i, at(c), j, 0, 0)),
    }, (bsz, g, nc)


def _kernel_call(kernel, name, x, b_mat, chunk, reverse, ins, outs):
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
        scratch_shapes=[pltpu.VMEM((h // g, p, n), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_interpret(),
    )(*(array for _, array in ins))


def _kernel_inputs(x, dt, a, b_mat, c_mat, d, chunk):
    bsz, s, h, p = x.shape
    g, n = b_mat.shape[2:]
    dt_c, cum_c, cum_r = _small(dt, a, chunk, g)
    skip = jnp.repeat(d.astype(F32), p).reshape(g, 1, -1)
    return [("wide", x.reshape(bsz, s, h * p)),
            ("group", b_mat.reshape(bsz, s, g * n)),
            ("group", c_mat.reshape(bsz, s, g * n)), ("skip", skip),
            ("cols", dt_c), ("cols", cum_c), ("rows", cum_r)], dt_c


def _scan(x, dt, a, b_mat, c_mat, d, chunk: int):
    bsz, s, h, p = x.shape
    n = b_mat.shape[3]
    y, h_in = _kernel_call(
        _fwd_kernel, "ssd_chunk_fwd", x, b_mat, chunk, False,
        _kernel_inputs(x, dt, a, b_mat, c_mat, d, chunk)[0],
        [("wide", jax.ShapeDtypeStruct((bsz, s, h * p), F32)),
         ("state", jax.ShapeDtypeStruct((bsz, s // chunk, h, p, n), F32))])
    return y.reshape(x.shape), h_in


def _grads(x, dt, a, b_mat, c_mat, d, h_in, dy, chunk: int):
    bsz, s, h, p = x.shape
    g, n = b_mat.shape[2:]
    r = h // g
    cols = jax.ShapeDtypeStruct((bsz, g, s, r), F32)
    ins, dt_c = _kernel_inputs(x, dt, a, b_mat, c_mat, d, chunk)
    dx, db, dc, ddt_x, dcum_c, dcum_r, dd = _kernel_call(
        _bwd_kernel, "ssd_chunk_bwd", x, b_mat, chunk, True,
        ins + [("state", h_in.reshape(bsz, s // chunk, h, p, n)),
               ("wide", dy.reshape(bsz, s, h * p))],
        [("wide", jax.ShapeDtypeStruct((bsz, s, h * p), x.dtype)),
         ("group", jax.ShapeDtypeStruct((bsz, s, g * n), b_mat.dtype)),
         ("group", jax.ShapeDtypeStruct((bsz, s, g * n), b_mat.dtype)),
         ("cols", cols), ("cols", cols),
         ("rows", jax.ShapeDtypeStruct((bsz, g, r, s), F32)),
         ("cols", cols)])

    def by_chunk(v):          # (B, G, S, R) -> (B, C, Q, G, R)
        return jnp.moveaxis(v, 1, 2).reshape(bsz, s // chunk, chunk, g, r)

    dcum = by_chunk(dcum_c + jnp.moveaxis(dcum_r, 2, 3))
    da = jnp.flip(jnp.cumsum(jnp.flip(dcum, axis=2), axis=2), axis=2)
    ddt = da * a.astype(F32).reshape(g, r) + by_chunk(ddt_x)
    return (dx.reshape(x.shape), ddt.reshape(dt.shape).astype(dt.dtype),
            jnp.sum(da * by_chunk(dt_c), axis=(0, 1, 2)).reshape(-1).astype(
                a.dtype),
            db.reshape(b_mat.shape), dc.reshape(c_mat.shape),
            jnp.sum(dd, axis=(0, 2)).reshape(-1).astype(d.dtype))


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
    y, h_in = _scan(x, dt, a, b_mat, c_mat, d, chunk)
    h_in = checkpoint_name(h_in, KEPT_STATES)
    return y, (x, dt, a, b_mat, c_mat, d, h_in)


def _ssd_bwd(chunk, res, dy):
    return _grads(*res, dy, chunk)


ssd_scan.defvjp(_ssd_fwd, _ssd_bwd)
