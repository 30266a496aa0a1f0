"""ALS (alternating least squares) matrix factorization as a TPU kernel.

Replaces MLlib's `ALS.train` / `ALS.trainImplicit` (invoked by the reference
recommendation templates, e.g. examples/scala-parallel-recommendation/
custom-query/src/main/scala/ALSAlgorithm.scala:56-67). MLlib block-partitions
the factor matrices and shuffles ratings between executors each sweep; the
TPU formulation is built around three hardware facts measured on v5e:

 * per-rating outer-product scatters are HBM-bound (O(nnz*k^2) traffic), so
   the per-row normal equations  (Y^T C Y + lambda I) x = Y^T C p  are
   accumulated as *batched matmuls* over fixed-width rating slots — MXU
   work with O(nnz*k) traffic; how the slots' blocks are summed into
   rows is `ALSParams.accum` (one place decides: `resolved_accum`);
 * the solve is short warm-started Jacobi-CG by default: XLA's batched
   Cholesky does not use the MXU, while CG is pure batched matvecs, on a
   lane-packed A behind the flush kernel (`_a_pack`; the CG phase is
   0.44 s of a 4.55 s job at the ML-20M shape: PERF.md section 5).
   Quality at the auto cap max(16, rank//4) is at parity or better
   (eval/RMSE_PARITY.md: the inexact inner solve early-stops the per-row
   overfit that exact ALS commits to). cg_iters=0 selects the exact
   Cholesky when bit-exactness matters;
 * the host is slow relative to the chip (single-core sort of 20M ratings
   costs more than the whole train), so the slot layout itself is built
   ON DEVICE from the raw COO arrays: one stable `lax.sort` by row; the
   slots are then runs of the sorted order, found from the rows'
   boundaries and gathered into place a row of lanes at a time. Nothing
   scatters the ratings: the chip takes a scattered or gathered scalar
   one at a time, ~8 ns each (PERF.md, PR 29). Only the three contiguous
   COO arrays ever cross the host->HBM link.

The multi-chip path (`als_train_sharded`) partitions users/items into
per-device blocks with `shard_map`; each half-sweep all_gathers the
opposing factor block over ICI — the analogue of MLlib's shuffle, but a
single fused collective. Its host splits the ratings by block once a
side (`_partition_coo`); the initial factors never leave the devices.

Ratings slots are (width,)-wide segments of one row's ratings; rows with
more ratings than `width` naturally occupy several slots, and their partial
normal-equation blocks scatter-add into the same row system.
"""

from __future__ import annotations

import contextvars
import dataclasses
import functools
import logging
import math
import os
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from pio_tpu.ops import als_pallas
from pio_tpu.ops.bucketing import pow2_bucket
from pio_tpu.parallel.mesh import DATA_AXIS
from pio_tpu.utils import tracing

log = logging.getLogger("pio_tpu.ops")


@dataclass(frozen=True)
class ALSParams:
    rank: int = 16
    iterations: int = 10
    reg: float = 0.1          # lambda (MLlib default 0.01; templates use 0.01)
    alpha: float = 1.0        # implicit confidence scale
    implicit: bool = False
    seed: int = 3
    chunk: int = 65536        # the ratings are padded to a whole number of
                              # these, with sentinel ids on both sides
    width: int = 128          # ratings per slot (= MXU contraction width)
    chunk_slots: int = 8192   # slots per accumulation step (bounds gather temp)
    # gather the opposing factors in bf16 when building the normal
    # equations: halves that gather's HBM traffic (the gathers are the
    # largest phase of a job on the chip, PERF.md section 5); heldout-RMSE
    # delta vs f32 is 1.7e-4 relative on 2M ratings (bf16+CG 1.33714 vs
    # f32+Cholesky 1.33691), so it defaults on. Set False for
    # bit-conservative factor builds.
    bf16_gather: bool = True
    cg_iters: int = -1        # -1: auto (per-side: exact Cholesky for
                              # small row batches, short warm-started CG
                              # for large); 0: exact batched Cholesky;
                              # >0: explicit CG iteration count
    # auto mode switches a side to CG above this many rows: below it the
    # batched Cholesky's cost (linear in the batch) is small and
    # exactness is free
    auto_cg_rows: int = 8192
    # warm-sweep CG schedule: after `cg_warm_sweeps` full-strength sweeps,
    # drop to `cg_warm_iters` CG iterations (-1 keeps the full count).
    # ALS warm-starts each solve from the previous sweep's factors; once
    # the outer iteration is near its fixed point the inner Krylov
    # correction is small and fewer iterations hold the heldout RMSE.
    # Default 6 (vs the cold cap of 16): per the committed grid artifact
    # (eval/CG_WARM_QUALITY.json) explicit heldout RMSE is
    # flat-to-better at 8 and 6 (0.44459 / 0.44435 vs 0.44494 full) and
    # the implicit objective is BETTER than full-strength CG at both
    # (-2.5% at 8, -3.3% at 6 — the inexact inner solve mildly
    # regularizes), while 4 flips to +2.4% WORSE; 6 is the default, -1
    # disables the schedule.
    cg_warm_iters: int = 6
    cg_warm_sweeps: int = 2
    # how the per-slot blocks are summed into the rows' normal equations
    # (`_normal_equations` has the loops, `resolved_accum` the choice):
    #   "carry":   scatter-add each chunk's blocks into the (n,k,k)
    #              accumulator, a scan carry: what `auto` runs off the
    #              chip, what fold-in pins, the tests' reference;
    #   "stacked": chunks emit their blocks as scan OUTPUTS, one sorted
    #              scatter-add per slot group folds them into A: pure XLA
    #              on the chip (the stacked sweep; ranks the kernel cannot
    #              hold);
    #   "hybrid":  the same groups of blocks, summed by the Pallas
    #              segment-flush kernel (ops/als_pallas.py) in place of
    #              the scatter: what `auto` runs on a TPU;
    #   "stream":  hybrid with the overlapped flush kernel: a candidate,
    #              reached by tests only until ROADMAP S1d's paired runs;
    #   "auto":    per backend and rank (see resolved_accum)
    accum: str = "auto"

    _ACCUM_MODES = ("auto", "carry", "stacked", "hybrid", "stream")

    def __post_init__(self):
        # validate here, not in the kernel: a typo ("strem") would
        # otherwise surface as an error inside the jit trace
        if self.accum not in self._ACCUM_MODES:
            raise ValueError(
                f"ALSParams.accum={self.accum!r}; "
                f"expected one of {self._ACCUM_MODES}")

    def resolved_cg_iters(self, n_self: int | None = None) -> int:
        """-1 (default) = auto, decided per factor side by its row count:

        * n_self <= auto_cg_rows: exact batched Cholesky (0) — at small
          batch the solve is not the bottleneck, and on noiseless/tiny
          data the exact solve measurably generalizes better;
        * large sides: short warm-started Jacobi-CG capped at
          max(16, rank//4): XLA's batched Cholesky does not use the MXU,
          CG is batched matvecs on it. Quality at the cap is at parity
          or better at realistic scale (eval/RMSE_PARITY.md). CG
          convergence is governed by conditioning, not the Krylov
          dimension, so the cap grows only mildly with rank; the warm
          start carries convergence across sweeps.

        With n_self=None (size unknown) auto returns the CG cap."""
        if self.cg_iters >= 0:
            return self.cg_iters
        if n_self is not None and n_self <= self.auto_cg_rows:
            return 0
        return max(16, self.rank // 4)

    def resolved_accum(self) -> str:
        """The accumulation that runs: the one place that turns `auto`,
        the backend and the rank into a mode (`_normal_equations` takes
        the result and nothing else). `auto` is `hybrid` on a TPU — the
        path of every benchmark cell — and `carry` elsewhere, where the
        kernel exists in interpret mode only. Above rank
        `als_pallas.MAX_RANK` the kernel's blocks do not fit VMEM, so
        `hybrid` and `stream` become `stacked`."""
        mode = self.accum
        if mode == "auto":
            mode = "hybrid" if _accelerator_backend() else "carry"
        if mode in ("hybrid", "stream") and self.rank > als_pallas.MAX_RANK:
            mode = "stacked"
        return mode


@dataclass(frozen=True)
class ALSValidation:
    """Per-sweep heldout trajectory from `als_train_validated`.

    The reference's eval workflow picks the best PARAMS
    (MetricEvaluator.scala:138-161) but always keeps the LAST sweep's
    model; measured on ML-20M the heldout RMSE curve bottoms at sweep
    2-3 and then climbs (eval/RMSE_PARITY.json: 0.568 at sweep 2 ->
    0.594 at 10), so "final" silently commits the worst point on its
    own curve. The TPU-idiomatic fix is best-sweep SELECTION inside the
    compiled scan — data-dependent early exit is not expressible under
    jit's static control flow, but tracking argmin factors as a scan
    carry costs one factor copy (~42 MB at the ML-20M shape) and two
    jnp.where selects per sweep, so the full schedule runs at
    unchanged throughput and the returned model is the curve's
    minimum, not its tail."""

    curve: tuple          # heldout RMSE after each sweep, in order
    best_sweep: int       # 1-based sweep index of the minimum
    best_rmse: float
    final_rmse: float     # last sweep's RMSE (what "no selection" returns)


@jax.tree_util.register_pytree_node_class
@dataclass
class ALSModel:
    """Factor matrices. user_factors: (n_users, k); item_factors: (n_items, k)."""

    user_factors: jax.Array
    item_factors: jax.Array

    def tree_flatten(self):
        return (self.user_factors, self.item_factors), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


def _accelerator_backend() -> bool:
    """True when the default backend is a TPU. A backend that fails to
    initialize raises here: answering "not an accelerator" would turn
    `auto` into the CPU path without a word."""
    return jax.devices()[0].platform == "tpu"


# stacked / hybrid / stream: slots whose (k,k) f32 blocks are materialized
# at once, in whole chunks (at k=64: 1.2 GB)
GROUP_SLOTS = 73728


def blocks_group_budget_slots(k: int) -> int:
    """GROUP_SLOTS is sized for k=64; the blocks grow with k^2, so a
    group is capped by BYTES too, or rank 128 runs out of HBM at the
    ML-20M shape."""
    return max(1, (1_200 * 2**20) // (k * k * 4))


def _slots_for(nnz: int, n_self: int, width: int, chunk_slots: int) -> int:
    """Static upper bound on slot count, padded to a chunk multiple.

    At most min(n_self, nnz) rows are non-empty (each adds one boundary
    slot) plus nnz//width width-overflow splits — so the layout stays
    O(nnz) even when the id space is much larger than the data.
    """
    s = nnz // width + 1 + min(n_self, nnz)
    return math.ceil(s / chunk_slots) * chunk_slots


_LANES = 128   # entries of one gathered row: the chip's vector width


def _count_below(a, q, inclusive: bool):
    """Per query of `q`, how many entries of the sorted int32 vector `a`
    lie below it (`<`; `<=` when `inclusive`): `searchsorted`'s left /
    right, for queries under the int32 maximum.

    `a` is cut into rows of 128 entries and those into blocks of 128
    rows. A compare against every block's first entry finds the block,
    one gathered row of the rows' first entries the row, the gathered
    row itself the place: two row gathers a query. A binary search
    gathers one scalar a step, 25 steps at 20 M entries, and the chip
    takes ~8 ns for each (PERF.md, PR 29)."""
    n = a.shape[0]
    n_blocks = max(1, -(-n // (_LANES * _LANES)))
    rows = jnp.concatenate([a, jnp.full(
        (n_blocks * _LANES * _LANES - n,), jnp.iinfo(jnp.int32).max,
        jnp.int32)]).reshape(n_blocks * _LANES, _LANES)
    row_firsts = rows[:, 0].reshape(n_blocks, _LANES)

    def count(keys):
        below = keys <= q[:, None] if inclusive else keys < q[:, None]
        return jnp.sum(below, axis=1, dtype=jnp.int32)

    # everything before the last block (row) that starts below the query
    # is below it, everything after the next one's start is not
    block = jnp.maximum(count(row_firsts[None, :, 0]) - 1, 0)
    row = block * _LANES + jnp.maximum(count(row_firsts[block]) - 1, 0)
    return row * _LANES + count(rows[row])


def _runs_to_slots(x_s, start, lens, width: int):
    """(S, width): slot s holds x_s[start[s] : start[s] + lens[s]], then
    zeros. `x_s` is read as rows of `width`; a run lies in two of them,
    which one row gather each brings, and a shift by the run's offset in
    its row, one bit of the offset at a time, lines it up. (A gather of
    width-long windows at unaligned starts runs as a loop of S steps on
    the chip, and one of single entries takes ~15 ns an entry.)"""
    nnz = x_s.shape[0]
    n_rows = -(-nnz // width) + 2           # start <= nnz: row + 1 exists
    x_rows = jnp.concatenate([x_s, jnp.zeros(
        (n_rows * width - nnz,), x_s.dtype)]).reshape(n_rows, width)
    row, shift = start // width, start % width
    both = jnp.concatenate([x_rows[row], x_rows[row + 1]], axis=1)
    for bit in range((width - 1).bit_length()):
        both = jnp.where(((shift >> bit) & 1 == 1)[:, None],
                         jnp.roll(both, -(1 << bit), axis=1), both)
    held = jnp.arange(width, dtype=jnp.int32)[None, :] < lens[:, None]
    return jnp.where(held, both[:, :width], 0)


@jax.named_scope("als.layout")
def _device_slot_layout(u, o, v, n_self: int, width: int, slots_max: int):
    """Build the slot layout on device from (possibly sentinel-padded) COO.

    u: (nnz,) int32 row ids; entries with u >= n_self are padding and are
    dropped. o: opposing-side ids; v: values. Returns
    (rows (S,), idx (S,width), val (S,width), lens (S,)).

    After the stable sort by row a slot is a run of the sorted ratings:
    a row's ratings cut every `width`. Where the runs start, how long
    they are and whose they are follows from the rows' boundaries in the
    sorted order, work in the rows and slots (10^5), and the runs are
    then gathered into place. Nothing scatters the ratings: a scatter of
    20 M updates takes the chip 0.10 s, a combining one 0.175 s, and the
    layout had four and four of them a job (PERF.md, PR 29).
    """
    nnz = u.shape[0]
    u_s, o_s, v_s = jax.lax.sort((u, o, v), num_keys=1, is_stable=True)
    # ratings before each row: padding sorts last and falls outside
    row_start = _count_below(
        u_s, jnp.arange(n_self + 1, dtype=jnp.int32), inclusive=False)
    degree = jnp.diff(row_start)
    n_slots = (degree + (width - 1)) // width   # heavy rows split
    slot_end = jnp.cumsum(n_slots)              # slots up to and with a row
    s = jnp.arange(slots_max, dtype=jnp.int32)
    # the row whose slots reach past s; empty rows own none
    row = jnp.minimum(_count_below(slot_end, s, inclusive=True), n_self - 1)
    used = s < slot_end[-1]
    k = s - (slot_end - n_slots)[row]           # the slot's place in its row
    # unused slots carry the sentinel row id n_self: the accumulation
    # scatter drops them (mode="drop"), and the slot->row index stays
    # globally NON-DECREASING (real slots ascend, sentinel tail is the
    # max) so scatters can declare indices_are_sorted
    rows = jnp.where(used, row, n_self)
    lens = jnp.where(used, jnp.minimum(degree[row] - k * width, width), 0)
    start = jnp.where(used, row_start[row] + k * width, nnz)
    idx = _runs_to_slots(o_s, start, lens, width)
    val = _runs_to_slots(v_s, start, lens, width)
    return rows, idx, val, lens


def _chunk_blocks(src, i_c, v_c, l_c, implicit: bool, alpha: float):
    """One slot chunk -> per-slot normal-equation blocks
    a_blk (C,k,k), b_blk (C,k) via batched MXU matmuls."""
    W = i_c.shape[1]
    with jax.named_scope("als.blocks"):
        mask = (
            jnp.arange(W, dtype=jnp.int32)[None, :] < l_c[:, None]
        ).astype(jnp.float32)
    with jax.named_scope("als.gather"):
        y = src[i_c].astype(jnp.float32)  # (C, W, k)
    with jax.named_scope("als.blocks"):
        if implicit:
            # c = 1 + alpha*v; A += (c-1) y y^T ; b += c * y   (p == 1)
            w_outer = alpha * v_c * mask
            w_rhs = (1.0 + alpha * v_c) * mask
        else:
            w_outer = mask
            w_rhs = v_c * mask
        # Precision.HIGH (3-pass bf16): the MXU's default 1-pass
        # contraction loses ~3e-3 relative on A, which the CG solve then
        # cannot recover; HIGH restores ~1e-5 at ~3x the matmul passes
        a_blk = jnp.einsum(
            "bwi,bwj->bij", y * w_outer[:, :, None], y,
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGH,
        )
        b_blk = jnp.einsum(
            "bwk,bw->bk", y, w_rhs, preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGH,
        )
    return a_blk, b_blk


def _normal_equations(layout, other_factors, n_self, implicit: bool,
                      alpha: float, chunk_slots: int,
                      bf16_gather: bool = False, accum: str = "carry",
                      group_slots: int = GROUP_SLOTS, wide: bool = False):
    """Accumulate per-row normal equations A (n_self,k,k), b (n_self,k).

    Slots sharing a row (rows wider than `width`) sum into the same row
    system; the slot->row index is non-decreasing with a sentinel tail
    (see _device_slot_layout), so every scatter declares
    indices_are_sorted=True and drops the sentinel.

    `accum` is a RESOLVED mode (`ALSParams.resolved_accum`): "carry"
    keeps A as a lax.scan carry and scatters each chunk into it, O(1)
    temp. The other three build the blocks of a GROUP of chunks as scan
    outputs (`group_slots`, capped by bytes: a bounded (group,k,k) temp)
    and differ in what folds a group into A: "stacked" one sorted
    scatter-add; "hybrid" / "stream" the Pallas segment-flush kernel
    (ops/als_pallas.py), which writes each row of A once; under those
    two `wide` hands A over as the kernel wrote it, (n_self + 1, k, lane),
    for `als_pallas.pack_flush`."""
    rows, idx, val, lens = layout
    k = other_factors.shape[1]
    S, W = idx.shape
    # bf16 source halves the gather's HBM traffic; the f32 upcast happens
    # in-register before the (still f32-accumulated) matmuls
    with jax.named_scope("als.gather"):
        src = (
            other_factors.astype(jnp.bfloat16) if bf16_gather
            else other_factors
        )
    # every caller pads S to a chunk_slots multiple via _slots_for
    assert S % chunk_slots == 0, (S, chunk_slots)

    if accum == "carry":
        n_ch = S // chunk_slots

        def body(carry, xs):
            A, b = carry
            r_c, i_c, v_c, l_c = xs
            a_blk, b_blk = _chunk_blocks(src, i_c, v_c, l_c, implicit, alpha)
            with jax.named_scope("als.blocks"):
                A = A.at[r_c].add(
                    a_blk, mode="drop", indices_are_sorted=True
                )
                b = b.at[r_c].add(
                    b_blk, mode="drop", indices_are_sorted=True
                )
            return (A, b), None

        xs = (
            rows.reshape(n_ch, chunk_slots),
            idx.reshape(n_ch, chunk_slots, W),
            val.reshape(n_ch, chunk_slots, W),
            lens.reshape(n_ch, chunk_slots),
        )
        with jax.named_scope("als.blocks"):
            A0 = jnp.zeros((n_self, k, k), dtype=jnp.float32)
            b0 = jnp.zeros((n_self, k), dtype=jnp.float32)
        (A, b), _ = jax.lax.scan(body, (A0, b0), xs)
        return A, b

    if accum not in ("stacked", "hybrid", "stream"):
        raise ValueError(f"accum must be a resolved mode, got {accum!r}")
    # group = as many whole chunks as fit the temp budget
    g_slots = chunk_slots * max(
        1, min(group_slots, blocks_group_budget_slots(k)) // chunk_slots)

    def group_blocks():
        """-> (lo, hi, a_blks (hi-lo,k,k), b_blks (hi-lo,k)) a group,
        built when asked for: one group's blocks are live at a time."""
        for lo in range(0, S, g_slots):
            hi = min(S, lo + g_slots)
            n_ch = (hi - lo) // chunk_slots
            xs = (
                idx[lo:hi].reshape(n_ch, chunk_slots, W),
                val[lo:hi].reshape(n_ch, chunk_slots, W),
                lens[lo:hi].reshape(n_ch, chunk_slots),
            )

            def body(_, xs_c):
                i_c, v_c, l_c = xs_c
                return None, _chunk_blocks(
                    src, i_c, v_c, l_c, implicit, alpha)

            _, (a_blks, b_blks) = jax.lax.scan(body, None, xs)
            yield lo, hi, a_blks, b_blks

    if accum != "stacked":
        return als_pallas.segment_flush(
            rows, n_self, k, chunk_slots, group_blocks(),
            overlap=(accum == "stream"), wide=wide)
    with jax.named_scope("als.blocks"):
        A = jnp.zeros((n_self, k, k), dtype=jnp.float32)
        b = jnp.zeros((n_self, k), dtype=jnp.float32)
    for lo, hi, a_blks, b_blks in group_blocks():
        with jax.named_scope("als.blocks"):
            r_g = rows[lo:hi]
            A = A.at[r_g].add(
                a_blks.reshape(hi - lo, k, k), mode="drop",
                indices_are_sorted=True,
            )
            b = b.at[r_g].add(
                b_blks.reshape(hi - lo, k), mode="drop",
                indices_are_sorted=True,
            )
    return A, b


def _cg_solve(A, b, x0, n_iter: int, diag=None):
    """Batched Jacobi-preconditioned conjugate gradient for SPD systems.

    ALS is block coordinate descent, so the inexact inner solve (relative
    residual ~1e-4 at 24 iters on k=64) does not change the fixed point it
    converges to; warm-starting from the previous sweep's factors keeps
    later sweeps cheap.

    A is (n, k, k); or, with `diag` given, the lane-packed form and the
    diagonal that `als_pallas.pack_flush` wrote, which the product
    kernel reads as it lies: the same sums over 1/pack of the bytes.
    """
    if diag is not None:
        def mv(x):
            return als_pallas.packed_matvec(A, x)
    else:
        diag = jnp.diagonal(A, axis1=1, axis2=2)

        def mv(x):
            return jnp.einsum(
                "bij,bj->bi", A, x, preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.HIGH,
            )

    dinv = 1.0 / diag
    x = x0
    r = b - mv(x)
    z = r * dinv
    p = z
    rz = jnp.sum(r * z, -1)

    def body(_, st):
        x, r, p, rz = st
        ap = mv(p)
        alpha = rz / jnp.maximum(jnp.sum(p * ap, -1), 1e-30)
        x = x + alpha[:, None] * p
        r = r - alpha[:, None] * ap
        z = r * dinv
        rz_new = jnp.sum(r * z, -1)
        beta = rz_new / jnp.maximum(rz, 1e-30)
        p = z + beta[:, None] * p
        return (x, r, p, rz_new)

    x, *_ = jax.lax.fori_loop(0, n_iter, body, (x, r, p, rz))
    return x


def _shared_yty(other_factors, yty):
    """Shared Y^T Y term (confidence-1 part handled in accumulation).
    The sharded trainer passes a psum-reduced `yty` built from the
    LOCAL opposing block: recomputing it from the gathered matrix
    would be O(n_dev) redundant FLOPs on every device (measured as
    the dominant super-linear term in eval/WEAK_SCALING.json)."""
    if yty is not None:
        return yty
    return jnp.matmul(
        other_factors.T, other_factors,
        precision=jax.lax.Precision.HIGH,
    )


@jax.named_scope("als.chol")
def _chol_solve(A, b):
    chol = jax.scipy.linalg.cho_factor(A)
    return jax.scipy.linalg.cho_solve(chol, b)


def _a_pack(k: int, accum: str, cg_iters: int) -> int:
    """Matrix rows a lane row of A as the solve holds it: a side that CG
    solves behind the flush kernel keeps A lane-packed where the rank
    leaves lanes empty; 1 (not packed) on every other path."""
    if cg_iters > 0 and accum in ("hybrid", "stream"):
        return als_pallas.pack_factor(k)
    return 1


def _solve_factors(layout, other_factors, n_self, reg, implicit, alpha,
                   chunk_slots, x0=None, cg_iters: int = 0,
                   bf16_gather: bool = False, accum: str = "carry",
                   yty=None):
    k = other_factors.shape[1]
    pack = _a_pack(k, accum, cg_iters)
    A, b = _normal_equations(
        layout, other_factors, n_self, implicit, alpha, chunk_slots,
        bf16_gather=bf16_gather, accum=accum, wide=pack > 1,
    )
    diag = None
    with jax.named_scope("als.gram"):
        eye = jnp.eye(k, dtype=jnp.float32)
        if pack > 1:
            # the pass that adds the Gram term writes the packed form
            # and the diagonal; the flush's wide buffer dies here
            gram = reg * eye
            if implicit:
                gram = _shared_yty(other_factors, yty) + gram
            A, diag = als_pallas.pack_flush(A, gram, n_self, pack)
        else:
            if implicit:
                A = A + _shared_yty(other_factors, yty)[None, :, :]
            A = A + reg * eye[None, :, :]
    if cg_iters > 0:
        with jax.named_scope("als.cg"):
            if x0 is None:
                x0 = jnp.zeros_like(b)
            return _cg_solve(A, b, x0, cg_iters, diag)
    return _chol_solve(A, b)


def init_factors(n: int, rank: int, key) -> jax.Array:
    # MLlib-style init: abs normal scaled by 1/sqrt(rank) keeps initial
    # predictions O(1)
    return jnp.abs(jax.random.normal(key, (n, rank), dtype=jnp.float32)) / math.sqrt(rank)


# ---------------------------------------------------------------------------
# single-device (one chip) path — layout build + train in one jitted program
# ---------------------------------------------------------------------------

def _cg_schedule(params: ALSParams, cg_u: int, cg_i: int):
    """-> (n_full, n_warm, w_u, w_i): how many sweeps run at full CG
    strength vs at the warm count, and the per-side warm iteration
    counts (a side on the exact-Cholesky path, cg=0, stays exact).
    Shared by the single-device and sharded trainers so both execute
    the identical schedule."""
    n_full = params.iterations
    n_warm = 0
    # >= 1: cg_iters=0 is the exact-Cholesky sentinel in _solve_factors,
    # so a 0 here would make the "cheap" warm phase the expensive exact
    # solve; 0 and negative both mean "schedule off"
    if 1 <= params.cg_warm_iters < max(cg_u, cg_i):
        n_full = min(params.iterations, max(0, params.cg_warm_sweeps))
        n_warm = params.iterations - n_full
    w_u = params.cg_warm_iters if cg_u > 0 else cg_u
    w_i = params.cg_warm_iters if cg_i > 0 else cg_i
    return n_full, n_warm, w_u, w_i


def _slot_counts(nnz_u: int, nnz_i: int, n_users: int, n_items: int,
                 params: ALSParams) -> tuple[int, int, int]:
    """-> (cs, su, si): the chunk size actually used and each side's
    static slot count, from the (padded) rating counts a side holds."""
    cs = min(params.chunk_slots,
             _slots_for(max(nnz_u, nnz_i), 0, params.width, 1))
    return (cs, _slots_for(nnz_u, n_users, params.width, cs),
            _slots_for(nnz_i, n_items, params.width, cs))


def _cg_matvecs(params: ALSParams, cg_u: int, cg_i: int) -> int:
    """Batched A@x products one job schedules: each CG solve takes its
    iteration count plus one for the first residual; an exact side none."""
    n_full, n_warm, w_u, w_i = _cg_schedule(params, cg_u, cg_i)
    return sum(n * (it + 1) for n, its in ((n_full, (cg_u, cg_i)),
                                          (n_warm, (w_u, w_i)))
               for it in its if it > 0)


def _dispatch_labels(sp: dict, nnz_u: int, nnz_i: int, slots, params,
                     cg_u: int, cg_i: int, n_u: int, n_i: int) -> None:
    """The counts at the `als.dispatch` boundary: what the program about
    to run was sized for (`slots` from `_slot_counts`), how full the
    ratings of one side (of its fullest block, when sharded) leave it,
    and how the solve holds A: `a_pack` matrix rows a lane row (1 = not
    packed) and the bytes of A a side of `n_u` / `n_i` rows (a block's,
    when sharded), as tiled on the chip."""
    cs, su, si = slots
    k, accum = params.rank, params.resolved_accum()
    packs = [_a_pack(k, accum, cg) for cg in (cg_u, cg_i)]
    lane = als_pallas._lane_for(k)
    a_bytes = [n * k * lane * 4 // pack
               for n, pack in zip((n_u, n_i), packs)]
    sp.update(cs=cs, su=su, si=si,
              fill_u=round(nnz_u / (su * params.width), 4),
              fill_i=round(nnz_i / (si * params.width), 4),
              cg_matvecs=_cg_matvecs(params, cg_u, cg_i),
              a_pack=max(packs), a_bytes_u=a_bytes[0], a_bytes_i=a_bytes[1])


def _build_layouts(u, i, v, n_users: int, n_items: int, params: ALSParams):
    """Slot layouts for both halves + the chunk size actually used."""
    nnz = u.shape[0]
    cs, su, si = _slot_counts(nnz, nnz, n_users, n_items, params)
    with jax.named_scope("als.user"):
        by_user = _device_slot_layout(u, i, v, n_users, params.width, su)
    with jax.named_scope("als.item"):
        by_item = _device_slot_layout(i, u, v, n_items, params.width, si)
    return by_user, by_item, cs


def _sweep_factory(by_user, by_item, n_users: int, n_items: int, cs: int,
                   params: ALSParams, reg=None, alpha=None):
    """-> sweep_with(cg_u_n, cg_i_n): the scan body shared by the plain,
    validated and stacked trainers.

    ``reg``/``alpha`` override the params' values and may be TRACED
    scalars — the stacked sweep vmaps candidates over them (they only
    feed arithmetic: `alpha * v` in the accumulation weights and
    `A + reg*I` in the solve), while everything shape- or
    branch-determining in ALSParams stays static."""
    if reg is None:
        reg = params.reg
    if alpha is None:
        alpha = params.alpha
    accum = params.resolved_accum()

    def sweep_with(cg_u_n: int, cg_i_n: int):
        def sweep(carry, _):
            users, items = carry
            with jax.named_scope("als.user"):
                users = _solve_factors(
                    by_user, items, n_users,
                    reg, params.implicit, alpha, cs,
                    x0=users, cg_iters=cg_u_n,
                    bf16_gather=params.bf16_gather, accum=accum,
                )
            with jax.named_scope("als.item"):
                items = _solve_factors(
                    by_item, users, n_items,
                    reg, params.implicit, alpha, cs,
                    x0=items, cg_iters=cg_i_n,
                    bf16_gather=params.bf16_gather, accum=accum,
                )
            return (users, items), None
        return sweep
    return sweep_with


def _run_schedule(sweep_with, params: ALSParams, cg_u: int, cg_i: int,
                  carry):
    """Run the two-phase warm-CG schedule: full-strength CG while cold,
    cg_warm_iters once the warm start carries most of the solution (see
    cg_warm_iters). Shared by every trainer variant."""
    n_full, n_warm, w_u, w_i = _cg_schedule(params, cg_u, cg_i)
    if n_full:
        carry, _ = jax.lax.scan(
            sweep_with(cg_u, cg_i), carry, None, length=n_full
        )
    if n_warm:
        carry, _ = jax.lax.scan(
            sweep_with(w_u, w_i), carry, None, length=n_warm
        )
    return carry


@partial(jax.jit, static_argnames=("n_users", "n_items", "params"))
def _train_jit(u, i, v, n_users: int, n_items: int, params: ALSParams,
               user0, item0):
    by_user, by_item, cs = _build_layouts(u, i, v, n_users, n_items, params)
    cg_u = params.resolved_cg_iters(n_users)
    cg_i = params.resolved_cg_iters(n_items)
    sweep_with = _sweep_factory(by_user, by_item, n_users, n_items, cs,
                                params)
    return _run_schedule(sweep_with, params, cg_u, cg_i, (user0, item0))


@partial(jax.jit, static_argnames=("n_users", "n_items", "params"))
def _train_val_jit(u, i, v, vu, vi, vv, n_users: int, n_items: int,
                   params: ALSParams, user0, item0):
    """Training scan with per-sweep heldout RMSE + best-sweep tracking.

    The heldout slice rides the scan as three fixed-shape device arrays;
    after each sweep the carry keeps the argmin factors via two scalar-
    predicate selects (see ALSValidation). Returns
    (best_users, best_items, curve) with curve (iterations,) f32."""
    by_user, by_item, cs = _build_layouts(u, i, v, n_users, n_items, params)
    cg_u = params.resolved_cg_iters(n_users)
    cg_i = params.resolved_cg_iters(n_items)
    sweep_with = _sweep_factory(by_user, by_item, n_users, n_items, cs,
                                params)

    def val_sweep_with(cg_u_n: int, cg_i_n: int):
        inner = sweep_with(cg_u_n, cg_i_n)

        def sweep(carry, _):
            (users, items), (bu, bi, br) = carry
            (users, items), _ = inner((users, items), None)
            pred = jnp.einsum(
                "nk,nk->n", users[vu], items[vi],
                preferred_element_type=jnp.float32,
            )
            r = jnp.sqrt(jnp.mean((pred - vv) ** 2))
            better = r < br
            bu = jnp.where(better, users, bu)
            bi = jnp.where(better, items, bi)
            br = jnp.where(better, r, br)
            return ((users, items), (bu, bi, br)), r
        return sweep

    n_full, n_warm, w_u, w_i = _cg_schedule(params, cg_u, cg_i)
    carry = ((user0, item0),
             (user0, item0, jnp.array(jnp.inf, jnp.float32)))
    curves = []
    if n_full:
        carry, c = jax.lax.scan(
            val_sweep_with(cg_u, cg_i), carry, None, length=n_full
        )
        curves.append(c)
    if n_warm:
        carry, c = jax.lax.scan(
            val_sweep_with(w_u, w_i), carry, None, length=n_warm
        )
        curves.append(c)
    (_, _), (bu, bi, _) = carry
    return bu, bi, jnp.concatenate(curves)


def als_train(
    user_idx: np.ndarray,
    item_idx: np.ndarray,
    values: np.ndarray,
    n_users: int,
    n_items: int,
    params: ALSParams,
    init: ALSModel | None = None,
) -> ALSModel:
    """Train on one device (or one logical device under jit).

    `init` warm-starts from an existing model (e.g. to continue sweeps or to
    record a per-sweep metric trajectory by calling with iterations=1 in a
    loop — the compiled program is reused across such calls).

    Inputs may be host numpy OR device-resident jax arrays: device inputs
    skip the host conversion/padding copies entirely (pad concatenation
    happens on device), so retrain loops that keep the COO arrays in HBM
    pay the host->device transfer once, not per call."""
    with tracing.span("als.init"):
        user0, item0 = _init_or(init, n_users, n_items, params)
    with tracing.span("als.prep") as sp:
        u, i, v = _prep_coo(
            user_idx, item_idx, values, n_users, n_items, params)
        sp["padded"] = u.shape[0] - len(values)
    with tracing.span("als.transfer") as sp:
        # host arrays would cross inside the jitted call; putting them
        # first (and waiting) keeps the crossing apart from the dispatch
        sp["bytes"] = sum(
            a.nbytes for a in (u, i, v) if not isinstance(a, jax.Array))
        u, i, v = jax.block_until_ready(jax.device_put((u, i, v)))
    with tracing.span("als.dispatch") as sp:
        _dispatch_labels(
            sp, len(values), len(values),
            _slot_counts(u.shape[0], u.shape[0], n_users, n_items, params),
            params, params.resolved_cg_iters(n_users),
            params.resolved_cg_iters(n_items), n_users, n_items)
        users, items = _train_jit(
            u, i, v, n_users, n_items, params, user0, item0
        )
    return ALSModel(users, items)


def _prep_coo(user_idx, item_idx, values, n_users, n_items,
              params: ALSParams):
    """Dtype-normalize + sentinel-pad the COO arrays (host numpy or
    device jax arrays alike — device inputs never round-trip to host)."""
    on_device = isinstance(user_idx, jax.Array)
    if on_device:
        u = user_idx.astype(jnp.int32)
        i = item_idx.astype(jnp.int32)
        v = values.astype(jnp.float32)
    else:
        u = np.ascontiguousarray(user_idx, dtype=np.int32)
        i = np.ascontiguousarray(item_idx, dtype=np.int32)
        v = np.ascontiguousarray(values, dtype=np.float32)
    # pad to a whole number of params.chunk; padding entries carry the
    # sentinel id on BOTH sides (u = n_users, i = n_items) so whichever
    # side keys the layout drops them via its valid mask
    pad = -u.shape[0] % max(1, params.chunk)
    if pad:
        xp = jnp if on_device else np
        u = xp.concatenate([u, xp.full(pad, n_users, xp.int32)])
        i = xp.concatenate([i, xp.full(pad, n_items, xp.int32)])
        v = xp.concatenate([v, xp.zeros(pad, xp.float32)])
    return u, i, v


def _init_or(init: ALSModel | None, n_users: int, n_items: int,
             params: ALSParams):
    if init is not None:
        return init.user_factors, init.item_factors
    key = jax.random.PRNGKey(params.seed)
    ku, ki = jax.random.split(key)
    return (init_factors(n_users, params.rank, ku),
            init_factors(n_items, params.rank, ki))


def als_train_validated(
    user_idx, item_idx, values,
    n_users: int, n_items: int, params: ALSParams,
    val_user_idx, val_item_idx, val_values,
    init: ALSModel | None = None,
) -> tuple[ALSModel, ALSValidation]:
    """Train with a heldout slice scored after every sweep; return the
    BEST-sweep model plus the full trajectory (see ALSValidation — the
    TPU-shaped replacement for early stopping). The heldout slice must
    be disjoint from the training triples; for implicit models the
    curve is RMSE of raw scores against the heldout values — a proxy
    (ranking metrics are the real objective there), but a monotone
    regression on it still flags overfit sweeps."""
    u, i, v = _prep_coo(user_idx, item_idx, values, n_users, n_items, params)
    vu = jnp.asarray(np.asarray(val_user_idx), jnp.int32)
    vi = jnp.asarray(np.asarray(val_item_idx), jnp.int32)
    vv = jnp.asarray(np.asarray(val_values), jnp.float32)
    user0, item0 = _init_or(init, n_users, n_items, params)
    bu, bi, curve = _train_val_jit(
        u, i, v, vu, vi, vv, n_users, n_items, params, user0, item0
    )
    raw = np.asarray(curve)
    # argmin on the UNROUNDED curve: the scan's strict `r < br` keeps the
    # truly-lowest sweep, and ties after rounding must not relabel it
    best_sweep = int(np.argmin(raw)) + 1
    curve_h = tuple(round(float(x), 6) for x in raw)
    return ALSModel(bu, bi), ALSValidation(
        curve=curve_h,
        best_sweep=best_sweep,
        best_rmse=curve_h[best_sweep - 1],
        final_rmse=curve_h[-1],
    )


# ---------------------------------------------------------------------------
# stacked multi-candidate path — the hyperparameter sweep's batched train:
# one layout build + one compiled program trains EVERY candidate that
# shares the static shape config (rank, iterations, implicit, CG
# schedule), vmapped over the continuous hyperparams (reg, alpha)
# ---------------------------------------------------------------------------

def sweep_safe_params(params: ALSParams) -> ALSParams:
    """The static config the stacked trainer actually runs: the pure-XLA
    accumulation paths (carry on CPU, stacked on accelerators). The
    Pallas kernels (hybrid/stream) are written for a single candidate's
    block shapes and do not vmap; the stacked program trades them for
    candidate-level batching — which is the bigger lever for a sweep
    (Chiu et al. 1612.01437: batch the work, amortize the data
    movement)."""
    accum = "stacked" if _accelerator_backend() else "carry"
    return dataclasses.replace(params, accum=accum)


@jax.tree_util.register_pytree_node_class
@dataclass
class StackedALSModel:
    """C candidates' factors as one stacked pytree:
    user_factors (C, n_users, k), item_factors (C, n_items, k)."""

    user_factors: jax.Array
    item_factors: jax.Array

    def __len__(self) -> int:
        return int(self.user_factors.shape[0])

    def candidate(self, c: int) -> ALSModel:
        return ALSModel(self.user_factors[c], self.item_factors[c])

    def tree_flatten(self):
        return (self.user_factors, self.item_factors), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


@partial(jax.jit, static_argnames=("n_users", "n_items", "params"))
def _train_stacked_jit(u, i, v, regs, alphas, n_users: int, n_items: int,
                       params: ALSParams, user0, item0):
    by_user, by_item, cs = _build_layouts(u, i, v, n_users, n_items, params)
    cg_u = params.resolved_cg_iters(n_users)
    cg_i = params.resolved_cg_iters(n_items)

    def train_one(reg, alpha):
        sweep_with = _sweep_factory(
            by_user, by_item, n_users, n_items, cs, params,
            reg=reg, alpha=alpha,
        )
        return _run_schedule(sweep_with, params, cg_u, cg_i, (user0, item0))

    # vmap over the candidate axis: the slot layouts and the init
    # factors broadcast (closure), only (reg, alpha) and the factor
    # carries batch — so the gather/einsum work is shared-shape and XLA
    # fuses the C candidates into batched MXU ops instead of C dispatches
    return jax.vmap(train_one)(regs, alphas)


def als_train_stacked(
    user_idx, item_idx, values,
    n_users: int, n_items: int,
    params: ALSParams,
    regs, alphas,
    mesh: Mesh | None = None,
) -> StackedALSModel:
    """Train C candidates sharing ``params``' static config as ONE
    batched program, differing per candidate only in (reg, alpha).

    The candidate count is pow2-bucketed (padding repeats the last
    candidate) so sweeps of 5, 7 or 8 points hit the same compiled
    program in the persistent compile cache; the pad is trimmed before
    returning. All candidates start from the identical seeded init, so
    candidate c's result matches a sequential ``als_train`` with the
    same (reg, alpha) up to batched-op reassociation (the parity suite
    in tests/test_tuning.py pins the tolerance).

    With a multi-device ``mesh`` whose data axis divides the bucketed
    candidate count, the candidate axis is sharded across devices (the
    SNIPPETS.md [1] pjit pattern: annotate the inputs, let GSPMD
    partition the embarrassingly-parallel candidate dimension)."""
    params = sweep_safe_params(params)
    # reg/alpha are fully overridden by the traced vectors below, but
    # ALSParams is a STATIC jit arg — normalize them so two sweeps whose
    # grids merely start at different values hash to the same compiled
    # program (the pow2-bucketing would otherwise be defeated by the
    # first candidate's values leaking into the cache key)
    params = dataclasses.replace(params, reg=0.0, alpha=1.0)
    regs = np.ascontiguousarray(regs, dtype=np.float32)
    alphas = np.ascontiguousarray(alphas, dtype=np.float32)
    if regs.shape != alphas.shape or regs.ndim != 1 or not len(regs):
        raise ValueError(
            f"regs/alphas must be equal-length 1-d vectors, got "
            f"{regs.shape} / {alphas.shape}")
    n_cand = len(regs)
    bucket = pow2_bucket(n_cand)
    if bucket != n_cand:
        regs = np.concatenate(
            [regs, np.full(bucket - n_cand, regs[-1], np.float32)])
        alphas = np.concatenate(
            [alphas, np.full(bucket - n_cand, alphas[-1], np.float32)])
    u, i, v = _prep_coo(user_idx, item_idx, values, n_users, n_items, params)
    user0, item0 = _init_or(None, n_users, n_items, params)
    regs_d, alphas_d = jnp.asarray(regs), jnp.asarray(alphas)
    if mesh is not None and mesh.devices.size > 1:
        n_dev = mesh.devices.size
        if bucket % n_dev == 0:
            cand_sharding = NamedSharding(mesh, P(DATA_AXIS))
            regs_d = jax.device_put(regs_d, cand_sharding)
            alphas_d = jax.device_put(alphas_d, cand_sharding)
    users, items = _train_stacked_jit(
        u, i, v, regs_d, alphas_d, n_users, n_items, params, user0, item0)
    return StackedALSModel(users[:n_cand], items[:n_cand])


# ---------------------------------------------------------------------------
# sharded multi-chip path — users/items blocked per device, all_gather per
# half-sweep (the MLlib-shuffle replacement)
# ---------------------------------------------------------------------------

def _block(n: int, n_dev: int) -> int:
    return math.ceil(n / n_dev)


def _sharded_cg_iters(params: ALSParams, n_block: int) -> int:
    """`resolved_cg_iters` for one device's block inside `shard_map`.

    On TPUs the exact batched Cholesky is wrong there: on 4 x v5e (jax
    0.9.0 / libtpu 0.0.34, PR 21) a 6,686-row item block solved exactly
    came out 13x off in relative RMS — with the XLA `carry` and the
    Pallas `hybrid` accumulation alike, their normal equations
    bit-equal — while the same solve is right to 3e-7 on one chip and
    the CG solve is right under `shard_map`. So `auto` keeps every
    block on CG there, and asking for the exact solve raises instead of
    returning garbage."""
    cg = params.resolved_cg_iters(n_block)
    if cg > 0 or not _accelerator_backend():
        return cg
    if params.cg_iters == 0:
        raise NotImplementedError(
            "cg_iters=0 (exact Cholesky) inside the sharded trainer "
            "returns wrong factors on TPU (jax 0.9.0 / libtpu 0.0.34); "
            "use a CG iteration count or train on one device")
    return params.resolved_cg_iters(None)


@functools.lru_cache(maxsize=64)
def _sharded_train_fn(mesh: Mesh, ub: int, ib: int, su: int, si: int,
                      cs: int, params: ALSParams):
    """Compiled sharded-train program, cached on its static config.

    Building the shard_map closure inside als_train_sharded made every
    retrain call re-trace the whole program (~13 s of fixed cost per
    call on an 8-virtual-device CPU mesh — measured while building
    eval/weak_scaling.py); Mesh and the frozen ALSParams are hashable,
    so the program is constructed once per (mesh, shapes, params) and
    jit keeps the executable across calls."""
    dev_spec = P(DATA_AXIS)  # leading axis = device blocks
    # each device solves its LOCAL block of rows, so the auto exact-vs-CG
    # decision keys on the per-device batch size
    cg_u = _sharded_cg_iters(params, ub)
    cg_i = _sharded_cg_iters(params, ib)
    accum = params.resolved_accum()

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(dev_spec,) * 8,
        out_specs=dev_spec,
        check_vma=False,
    )
    def run(u_r, u_c, u_v, i_r, i_c, i_v, u0, i0):
        with jax.named_scope("als.user"):
            by_user = _device_slot_layout(
                u_r[0], u_c[0], u_v[0], ub, params.width, su
            )
        with jax.named_scope("als.item"):
            by_item = _device_slot_layout(
                i_r[0], i_c[0], i_v[0], ib, params.width, si
            )

        @jax.named_scope("als.gram")
        def gram_psum(block):
            """Y^T Y of the full factor matrix from the LOCAL block:
            per-device (b,k)x(k,b) matmul + one (k,k) psum over ICI —
            O(1) per device instead of the O(n_dev) every device would
            pay recomputing it from the gathered matrix."""
            g = jnp.matmul(block.T, block,
                           precision=jax.lax.Precision.HIGH)
            return jax.lax.psum(g, DATA_AXIS)

        def sweep_with(cg_u_n: int, cg_i_n: int):
            def sweep(carry, _):
                users, items = carry  # local blocks (ub, k) / (ib, k)
                with jax.named_scope("als.user"):
                    yty_i = gram_psum(items) if params.implicit else None
                    with jax.named_scope("als.all_gather"):
                        all_items = jax.lax.all_gather(
                            items, DATA_AXIS, tiled=True
                        )  # (ib*n_dev, k)
                    users = _solve_factors(
                        by_user, all_items, ub,
                        params.reg, params.implicit, params.alpha, cs,
                        x0=users, cg_iters=cg_u_n,
                        bf16_gather=params.bf16_gather, accum=accum,
                        yty=yty_i,
                    )
                with jax.named_scope("als.item"):
                    yty_u = gram_psum(users) if params.implicit else None
                    with jax.named_scope("als.all_gather"):
                        all_users = jax.lax.all_gather(
                            users, DATA_AXIS, tiled=True
                        )
                    items = _solve_factors(
                        by_item, all_users, ib,
                        params.reg, params.implicit, params.alpha, cs,
                        x0=items, cg_iters=cg_i_n,
                        bf16_gather=params.bf16_gather, accum=accum,
                        yty=yty_u,
                    )
                return (users, items), None
            return sweep

        # same two-phase warm-CG schedule as _train_jit so the sharded
        # path is numerically aligned with the single-device one
        n_full, n_warm, w_u, w_i = _cg_schedule(params, cg_u, cg_i)
        carry = (u0[0], i0[0])
        if n_full:
            carry, _ = jax.lax.scan(
                sweep_with(cg_u, cg_i), carry, None, length=n_full
            )
        if n_warm:
            carry, _ = jax.lax.scan(
                sweep_with(w_u, w_i), carry, None, length=n_warm
            )
        users, items = carry
        return users[None], items[None]

    return jax.jit(run)


def _partition_coo(rows, cols, vals, block: int, n_dev: int, chunk: int):
    """Split COO ratings into `n_dev` contiguous blocks of `block` rows.

    -> (r_st, c_st, v_st, nnz_max, counts): (n_dev, nnz_max) stacks,
    int32 / int32 / float32, with LOCAL row ids, each block's ratings in
    their arrival order (the on-device slot layout sorts stably, so the
    summation order and the model's bits follow it); padding entries
    carry row id = block (the sentinel >= any local id), column 0 and
    value 0; and each device's rating count.

    One narrow block code a rating; then a thread a device lists its
    block's ratings and gathers them straight into the stacks. What
    costs on the host is first-touch page faults of fresh arrays more
    than arithmetic (PERF.md, PR 27), so nothing wider than the inputs
    is allocated but the index lists, and the devices' shares run side
    by side (NumPy releases the GIL in every call here).
    """
    rows = np.asarray(rows)
    if rows.min(initial=0) < 0 or rows.max(initial=0) >= block * n_dev:
        raise ValueError(
            f"row ids outside [0, {block * n_dev}): "
            f"{rows.min()} .. {rows.max()}")
    # int32 from every DataSource: no copy; a wider dtype narrows here
    rows = rows.astype(np.int32, copy=False)
    cols = np.asarray(cols).astype(np.int32, copy=False)
    vals = np.asarray(vals, dtype=np.float32)
    code = np.empty(len(rows), np.uint8 if n_dev <= 256 else np.uint16)
    np.floor_divide(rows, block, out=code, casting="unsafe")
    # no more threads than cores: each holds a mask as long as the ratings
    with ThreadPoolExecutor(min(n_dev, os.cpu_count() or 1)) as pool:
        picks = list(pool.map(lambda dv: np.flatnonzero(code == dv),
                              range(n_dev)))
        counts = [len(ix) for ix in picks]
        # bucket to a chunk multiple for compile reuse across retrains
        nnz_max = max(counts)
        nnz_max += -nnz_max % max(1, chunk)
        r_st = np.empty((n_dev, nnz_max), np.int32)
        c_st = np.zeros((n_dev, nnz_max), np.int32)
        v_st = np.zeros((n_dev, nnz_max), np.float32)

        def fill(dv):
            ix, n = picks[dv], counts[dv]
            # mode="clip": the indexes are in range, and the default
            # mode gathers into a buffer first and copies
            np.take(rows, ix, out=r_st[dv, :n], mode="clip")
            r_st[dv, :n] -= dv * block
            r_st[dv, n:] = block
            np.take(cols, ix, out=c_st[dv, :n], mode="clip")
            np.take(vals, ix, out=v_st[dv, :n], mode="clip")

        list(pool.map(fill, range(n_dev)))
    return r_st, c_st, v_st, nnz_max, counts


def als_train_sharded(
    user_idx: np.ndarray,
    item_idx: np.ndarray,
    values: np.ndarray,
    n_users: int,
    n_items: int,
    params: ALSParams,
    mesh: Mesh,
) -> ALSModel:
    """Multi-device ALS over the mesh's data axis.

    Host-side work is only a per-device split of the COO arrays (users and
    their ratings partitioned into contiguous blocks, one per device;
    likewise items: `_partition_coo`, the two sides side by side),
    sentinel-padded so every device carries the same shapes; only those
    six stacks cross from the host. The initial factors are drawn, padded
    and split into the devices' blocks on the devices. Each device builds
    its slot layouts locally; each half-sweep every device solves its
    block's normal equations against the full opposing factor matrix,
    obtained by `all_gather` over ICI (factors are small: n x k; the
    ratings never move).
    """
    n_dev = mesh.shape[DATA_AXIS]
    ub, ib = _block(n_users, n_dev), _block(n_items, n_dev)

    with tracing.span("als.partition") as sp:
        vals = np.asarray(values, dtype=np.float32)
        with ThreadPoolExecutor(2) as sides:
            by_user = sides.submit(
                contextvars.copy_context().run, _partition_coo,
                user_idx, item_idx, vals, ub, n_dev, params.chunk)
            by_item = sides.submit(
                contextvars.copy_context().run, _partition_coo,
                item_idx, user_idx, vals, ib, n_dev, params.chunk)
            u_r, u_c, u_v, u_nnz, u_counts = by_user.result()
            i_r, i_c, i_v, i_nnz, i_counts = by_item.result()
        nnz = len(vals)
        sp.update(
            rows_u=ub, rows_i=ib,
            nnz_max_u=max(u_counts), nnz_min_u=min(u_counts),
            nnz_max_i=max(i_counts), nnz_min_i=min(i_counts),
            padded_u=round(1 - nnz / (n_dev * u_nnz), 4),
            padded_i=round(1 - nnz / (n_dev * i_nnz), 4))

    sharding = NamedSharding(mesh, P(DATA_AXIS))
    with tracing.span("als.init"):
        key = jax.random.PRNGKey(params.seed)
        ku, ki = jax.random.split(key)

        def init_blocks(n, block, k):
            # draw the init at the UNPADDED shape — the exact same draw
            # als_train makes — then zero-pad the phantom rows: a non-zero
            # init there would contaminate the shared Y^T Y term of the
            # implicit-ALS first sweep. Drawn, padded and split into the
            # devices' blocks without leaving the devices.
            table = jnp.pad(init_factors(n, params.rank, k),
                            ((0, block * n_dev - n), (0, 0)))
            return jax.device_put(
                table.reshape(n_dev, block, params.rank), sharding)

        # waited for, so that the draw is charged to this span
        user0, item0 = jax.block_until_ready(
            [init_blocks(n_users, ub, ku), init_blocks(n_items, ib, ki)])

    cs, su, si = _slot_counts(u_nnz, i_nnz, ub, ib, params)

    # cache key: only program-relevant fields — seed and chunk are
    # host-side (init RNG / padding quantum) and chunk_slots is already
    # folded into cs, so varying them must not re-trace
    key_params = dataclasses.replace(params, seed=0, chunk=0,
                                     chunk_slots=cs)
    run = _sharded_train_fn(mesh, ub, ib, su, si, cs, key_params)
    with tracing.span("als.transfer") as sp:
        host = (u_r, u_c, u_v, i_r, i_c, i_v)
        sp["bytes"] = sum(a.nbytes for a in host)
        # waited for, so that the crossing is not charged to the dispatch
        placed = jax.block_until_ready(
            [jax.device_put(a, sharding) for a in host])
        placed += [user0, item0]
    with tracing.span("als.dispatch") as sp:
        _dispatch_labels(sp, max(u_counts), max(i_counts), (cs, su, si),
                         params, _sharded_cg_iters(params, ub),
                         _sharded_cg_iters(params, ib), ub, ib)
        users, items = run(*placed)
        blocks_on = sorted(s.device.id for s in users.addressable_shards)
        users = users.reshape(-1, params.rank)[:n_users]
        items = items.reshape(-1, params.rank)[:n_items]
    log.info(
        "sharded ALS over %d devices: rating blocks on devices %s, "
        "factor blocks on devices %s", n_dev,
        sorted(s.device.id for s in placed[0].addressable_shards),
        blocks_on)
    return ALSModel(users, items)


# ---------------------------------------------------------------------------
# streaming fold-in: refresh user rows against FIXED item factors
# ---------------------------------------------------------------------------

def _solve_rows_invariant(A, b):
    """Exact per-row solve whose bits do NOT depend on the batch size:
    `lax.map` compiles ONE unbatched (k,k) Cholesky program and runs it
    per row, so row u's solution is identical whether u is solved alone
    or among any batch mates — unlike the BATCHED cho_factor/cho_solve,
    whose CPU lowering drifts by an ULP with batch size (measured; this
    is what the fold-in oracle parity test would catch). Fold-in
    batches are small (≤ a few thousand rows), so per-row is cheap."""
    def solve_one(ab):
        a_row, b_row = ab
        return jax.scipy.linalg.cho_solve(
            jax.scipy.linalg.cho_factor(a_row), b_row)

    return jax.lax.map(solve_one, (A, b))


@partial(jax.jit, static_argnames=("n_users", "params"))
def _fold_in_jit(u, i, v, item_factors, n_users: int, params: ALSParams):
    nnz = u.shape[0]
    cs = min(params.chunk_slots, _slots_for(nnz, 0, params.width, 1))
    su = _slots_for(nnz, n_users, params.width, cs)
    by_user = _device_slot_layout(u, i, v, n_users, params.width, su)
    A, b = _normal_equations(
        by_user, item_factors, n_users, params.implicit, params.alpha, cs,
        bf16_gather=params.bf16_gather, accum=params.resolved_accum(),
    )
    k = item_factors.shape[1]
    if params.implicit:
        A = A + _shared_yty(item_factors, None)[None, :, :]
    A = A + params.reg * jnp.eye(k, dtype=jnp.float32)[None, :, :]
    return _solve_rows_invariant(A, b)


def fold_in_params(params: ALSParams) -> ALSParams:
    """The bit-conservative variant of `params` a fold-in solve runs
    under: f32 gather and the plain XLA `carry` accumulation, so a
    refreshed row is a pure function of (events, item factors) — the
    same answer on every backend, every batch composition, and every
    restart. Iteration-schedule fields are irrelevant (fold-in is one
    half-sweep); they are zeroed so they cannot fragment the jit cache."""
    return dataclasses.replace(
        params, bf16_gather=False, accum="carry", iterations=1,
        cg_warm_iters=-1, seed=0, chunk=0,
    )


def als_fold_in(
    item_factors,
    user_idx: np.ndarray,
    item_idx: np.ndarray,
    values: np.ndarray,
    n_users: int,
    params: ALSParams,
) -> jax.Array:
    """Solve one ridge system per user against FIXED item factors — the
    online half of ALS (the MLlib lineage's fold-in): exactly ONE
    user half-sweep of `_normal_equations` + the exact solve, nothing
    else. Returns (n_users, k) f32 rows.

    `user_idx` holds LOCAL dense ids in [0, n_users); `item_idx` indexes
    `item_factors` rows. Users in [0, n_users) with no events get the
    zero row (b = 0 under the exact solve), which callers treat as
    "don't apply".

    Batch-composition invariance (the freshness subsystem's oracle
    contract, tests/test_freshness.py): with `fold_in_params`, user u's
    row is BIT-identical whether u is folded alone or inside any batch —
    per-slot normal-equation blocks are row-independent batched matmuls,
    the sorted scatter sums u's slots in the same order regardless of
    batch mates, and `_solve_rows_invariant` runs one UNBATCHED Cholesky
    per row. Both the dense-id space (`n_users`) and the event count are
    padded to powers of two here, so a steady fold-in stream compiles
    O(log²) programs and then runs entirely out of the persistent
    compile cache (PR 4)."""
    nnz = len(values)
    if nnz == 0 or n_users <= 0:
        k = item_factors.shape[1]
        return jnp.zeros((max(n_users, 0), k), jnp.float32)
    u = np.ascontiguousarray(user_idx, dtype=np.int32)
    i = np.ascontiguousarray(item_idx, dtype=np.int32)
    v = np.ascontiguousarray(values, dtype=np.float32)
    n_bucket = pow2_bucket(n_users)
    pad = pow2_bucket(nnz) - nnz
    if pad:
        # padding rides the user-side sentinel (u = n_bucket): the slot
        # layout drops those entries entirely, so item id 0 / value 0
        # never reach a real row's system
        u = np.concatenate([u, np.full(pad, n_bucket, np.int32)])
        i = np.concatenate([i, np.zeros(pad, np.int32)])
        v = np.concatenate([v, np.zeros(pad, np.float32)])
    rows = _fold_in_jit(u, i, v, jnp.asarray(item_factors), n_bucket,
                        fold_in_params(params))
    return rows[:n_users]


# ---------------------------------------------------------------------------
# prediction / scoring
# ---------------------------------------------------------------------------

@jax.jit
def predict_pairs(model: ALSModel, user_idx, item_idx) -> jax.Array:
    return jnp.einsum(
        "nk,nk->n",
        model.user_factors[user_idx],
        model.item_factors[item_idx],
    )


@partial(jax.jit, static_argnames=("k",))
def _topk_jit(model: ALSModel, user_idx, k: int):
    scores = model.user_factors[user_idx] @ model.item_factors.T  # (B, I)
    return jax.lax.top_k(scores, k)


def recommend_topk(model: ALSModel, user_idx, k: int):
    """Top-k items for a batch of users: one (B,k)x(k,I) matmul + lax.top_k
    (the MXU path serving /queries.json).

    Both k AND the batch dim are bucketed to the next power of two before
    jit, so per-query k values (e.g. num + len(blackList)) and the varying
    batch sizes the serving micro-batcher produces compile O(log) XLA
    programs instead of one per size; the exact trim happens on host."""
    n_items = model.item_factors.shape[0]
    k = max(1, min(int(k), n_items))
    k_bucket = pow2_bucket(k, cap=n_items)
    user_idx = np.asarray(user_idx)
    b = len(user_idx)
    b_bucket = pow2_bucket(b)
    if b_bucket != b:
        user_idx = np.concatenate(
            [user_idx, np.zeros(b_bucket - b, user_idx.dtype)]
        )
    scores, idx = _topk_jit(model, user_idx, k_bucket)
    return scores[:b, :k], idx[:b, :k]


def rmse(model: ALSModel, user_idx, item_idx, values) -> float:
    pred = predict_pairs(
        model, jnp.asarray(user_idx), jnp.asarray(item_idx)
    )
    return float(jnp.sqrt(jnp.mean((pred - jnp.asarray(values)) ** 2)))
