"""Pallas TPU kernel for the ALS normal-equation accumulation: the
segment flush.

XLA builds the per-slot blocks (a_blk (k,k), b_blk (k,)) with batched MXU
matmuls (`ops/als.py _chunk_blocks`); what is left is summing the blocks of
the slots that share a row into A (n,k,k) and b (n,k). `segment_flush`
does that without a scatter: slots are row-sorted (`_device_slot_layout`)
and a TPU Pallas grid runs sequentially on a core, so a (k,lane) VMEM
scratch accumulates the open row's blocks across grid steps and each
segment that ENDS inside a group is written to A in HBM by one DMA: every
row of A is written once. Slots come in groups (the caller bounds the
blocks held at once); a row may span groups, so each group also emits its
last open segment as a "trail", and all trails fold in afterwards with one
n_groups-row scatter-add. Flush and trail-adds sum to the row exactly: the
flush is the only writer of its row. A and b start as zeros and are
aliased in and out of every group's call, so empty rows read as zeros
with no further pass.

Two kernels, one switch (`overlap`): `_segment_kernel` waits for each row's
DMA where it starts it (`accum="hybrid"`, what `auto` runs on a TPU);
`_segment_kernel_stream` starts it from one of two staging slots and waits
at the next flush that wants the slot (`accum="stream"`), the same adds in
the same order. Which is faster on the chip is ROADMAP S1d's to measure.

The record (TPU v5e, jax 0.9.0 / libtpu 0.0.34, PR 21,
`eval/kernel_parity.py` at the ML-20M factor shapes): both kernels, and a
fused variant that made the blocks in the kernel, compiled through Mosaic
and matched the XLA `carry` path through a half-sweep to 1-2e-4 relative.
`hybrid` is the path of every benchmark cell (PERF.md). The rules they are
built to: accumulators and outputs are LANE-wide (a (K,K) row slice of a
lane-padded HBM memref is refused); row ids arrive as (1,1,chunk) SMEM
blocks (1-d s32 operands tile T(1024), Mosaic wants T(128)); second-minor
block dims divide 8; the stack of double-buffered blocks and scratch stays
under the 16 MB of scoped VMEM.

Refused by Mosaic in that run and removed in PR 28 (the fused variant went
with them: no product path could select it); the git history has the
code. Whoever takes one up again should start from the compiler's words,
not from the same design:

 * lane-packed A, the (K,LANE)->(1,K*K) pack in the flush: "infer-vector-
   layout: unsupported shape cast vector<64x64xf32> -> vector<1x4096xf32>"
   (the packed batched matvec alone compiled and matched to 2e-7);
 * a gather from a VMEM-resident table: dynamic single-row loads, "cannot
   statically prove that index in dimension 0 is a multiple of 8" (bf16
   table); `jnp.take` in the kernel, "Can only load scalars from SMEM";
 * a double-buffered gather of single rows by DMA from HBM: "Slice shape
   along dimension 0 must be aligned to tiling (8), but is 1".
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _pad_lanes(x, lane: int):
    """Zero-pad the last dim to LANE (see _segment_kernel docstring)."""
    k = x.shape[-1]
    if lane == k:
        return x
    return jnp.concatenate(
        [x, jnp.zeros((*x.shape[:-1], lane - k), x.dtype)], axis=-1)


def _slot_blocks(ablk_ref, bblk_ref, i, lane: int):
    """Slot i's contribution, lane-padded: (blk (K,LANE), b_row (LANE,))."""
    return _pad_lanes(ablk_ref[0, i], lane), _pad_lanes(bblk_ref[0, i], lane)


def _segment_kernel(*refs, chunk: int):
    """The segment-flush kernel body. refs =
    (rows_ref (1,1,chunk) SMEM, ablk_ref (1,chunk,K,K),
     bblk_ref (1,chunk,K), a_init, b_init,                    <- inputs
     a_out (n_pad,K,LANE) HBM, b_out (n_pad,LANE) HBM,        <- aliased
     trail_a (K,LANE), trail_b (1,LANE), trail_row (1,1) SMEM,
     acc_a, acc_b, cur_row, dma_sem)                          <- scratch

    One grid step = `chunk` consecutive slots; the sequential TPU grid +
    persistent scratch carry the open row segment across steps. Segments
    that END inside the group DMA to A/b (each A row written exactly
    once); the group's last open segment goes to the trail outputs,
    folded across groups by the caller.

    Accumulators/outputs are LANE(=128-multiple)-wide with columns [K:]
    zero: Mosaic requires HBM memref slices to be lane-tile aligned (a
    (K,K) row slice of a lane-padded (n,K,K) buffer is rejected with
    "Slice shape along dimension 2 must be aligned to tiling (128)"),
    and the physical HBM bytes equal XLA's padded layout anyway."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    (rows_ref, ablk_ref, bblk_ref, _a_init, _b_init, a_out, b_out,
     trail_a, trail_b, trail_row, acc_a, acc_b, cur_row, dma_sem) = refs
    step = pl.program_id(0)
    n_steps = pl.num_programs(0)
    LANE = acc_a.shape[1]

    @pl.when(step == 0)
    def _init():
        cur_row[0] = rows_ref[0, 0, 0]
        acc_a[...] = jnp.zeros_like(acc_a)
        acc_b[...] = jnp.zeros_like(acc_b)

    def flush(row):
        a_copy = pltpu.make_async_copy(acc_a, a_out.at[row], dma_sem)
        a_copy.start()
        a_copy.wait()
        b_copy = pltpu.make_async_copy(
            acc_b, b_out.at[pl.ds(row, 1)], dma_sem)
        b_copy.start()
        b_copy.wait()

    def slot_body(i, _):
        row = rows_ref[0, 0, i]

        @pl.when(row != cur_row[0])
        def _new_segment():
            flush(cur_row[0])
            acc_a[...] = jnp.zeros_like(acc_a)
            acc_b[...] = jnp.zeros_like(acc_b)
            cur_row[0] = row

        blk, b_row = _slot_blocks(ablk_ref, bblk_ref, i, LANE)
        acc_a[...] += blk
        acc_b[...] += b_row[None, :]
        return ()

    jax.lax.fori_loop(0, chunk, slot_body, (), unroll=False)

    @pl.when(step == n_steps - 1)
    def _emit_trail():  # the group's last open segment is NEVER flushed
        trail_a[...] = acc_a[...]
        trail_b[...] = acc_b[...]
        trail_row[0, 0] = cur_row[0]


def _segment_kernel_stream(*refs, chunk: int):
    """Overlapped-flush variant of _segment_kernel (accum="stream").

    Same segment algebra — sequential grid, persistent scratch carrying
    the open row, trail emitted for the group's last open segment — but
    the flush does not wait behind its own DMA: each segment end copies
    the accumulator into one of TWO staging slots, STARTS the HBM row
    writes, and returns to the adds; the wait happens at the NEXT flush
    that wants the same slot (or at the trail emit), so a row's write
    has at least one following segment to land in.

    refs = (rows_ref, ablk_ref, bblk_ref, a_init, b_init,  <- inputs
            a_out, b_out, trail_a, trail_b, trail_row,     <- outputs
            acc_a, acc_b, stage_a, stage_b, cur_row, st,
            sem_a0, sem_a1, sem_b0, sem_b1)                <- scratch

    st (3,) SMEM: [next staging slot, pending row of slot 0, pending
    row of slot 1] (-1 = no DMA in flight). Staging slots are indexed
    with PYTHON ints via parity branches so every ref slice except the
    destination row is static; the destination a_out.at[row] with a
    traced row is the pattern the plain kernel runs on the chip. Waits
    reconstruct the same copy descriptor they started — descriptor
    equality is what pairs a wait with its start."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    (rows_ref, ablk_ref, bblk_ref, _a_init, _b_init, a_out, b_out,
     trail_a, trail_b, trail_row,
     acc_a, acc_b, stage_a, stage_b, cur_row, st,
     sem_a0, sem_a1, sem_b0, sem_b1) = refs
    step = pl.program_id(0)
    n_steps = pl.num_programs(0)
    K = acc_a.shape[0]
    LANE = acc_a.shape[1]
    sems = ((sem_a0, sem_b0), (sem_a1, sem_b1))

    @pl.when(step == 0)
    def _init():
        cur_row[0] = rows_ref[0, 0, 0]
        st[0] = 0
        st[1] = -1
        st[2] = -1
        acc_a[...] = jnp.zeros_like(acc_a)
        acc_b[...] = jnp.zeros_like(acc_b)

    def dmas(slot: int, row):
        sem_a, sem_b = sems[slot]
        return (
            pltpu.make_async_copy(
                stage_a.at[pl.ds(slot * K, K)], a_out.at[row], sem_a),
            pltpu.make_async_copy(
                stage_b.at[pl.ds(slot, 1)], b_out.at[pl.ds(row, 1)],
                sem_b),
        )

    def drain(slot: int):
        """Wait out the slot's in-flight row write, if any."""
        @pl.when(st[1 + slot] >= 0)
        def _():
            a_copy, b_copy = dmas(slot, st[1 + slot])
            a_copy.wait()
            b_copy.wait()

    def flush_into(slot: int, row):
        drain(slot)  # the slot's previous DMA must land before reuse
        stage_a[pl.ds(slot * K, K), :] = acc_a[...]
        stage_b[pl.ds(slot, 1), :] = acc_b[...]
        a_copy, b_copy = dmas(slot, row)
        a_copy.start()
        b_copy.start()
        st[1 + slot] = row

    def flush(row):
        @pl.when(st[0] == 0)
        def _slot0():
            flush_into(0, row)

        @pl.when(st[0] != 0)
        def _slot1():
            flush_into(1, row)

        st[0] = 1 - st[0]

    def slot_body(i, _):
        row = rows_ref[0, 0, i]

        @pl.when(row != cur_row[0])
        def _new_segment():
            flush(cur_row[0])
            acc_a[...] = jnp.zeros_like(acc_a)
            acc_b[...] = jnp.zeros_like(acc_b)
            cur_row[0] = row

        blk, b_row = _slot_blocks(ablk_ref, bblk_ref, i, LANE)
        acc_a[...] += blk
        acc_b[...] += b_row[None, :]
        return ()

    jax.lax.fori_loop(0, chunk, slot_body, (), unroll=False)

    @pl.when(step == n_steps - 1)
    def _emit_trail():
        drain(0)  # every in-flight row write lands before the kernel ends
        drain(1)
        trail_a[...] = acc_a[...]
        trail_b[...] = acc_b[...]
        trail_row[0, 0] = cur_row[0]


@jax.named_scope("als.flush")
def _run_segment_group(rows_g, a_blks, b_blks, a_buf, b_buf, *,
                       chunk: int, k: int, lane: int, interpret: bool,
                       overlap: bool):
    """One pallas_call over a group: rows (G,) and the group's blocks
    (G/chunk, chunk, k, k) / (G/chunk, chunk, k) in, aliased A/b buffers
    accumulated in place, trail emitted."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n_steps = rows_g.shape[0] // chunk
    smem = pltpu.MemorySpace.SMEM
    hbm = pltpu.MemorySpace.HBM
    if overlap:
        kernel = functools.partial(_segment_kernel_stream, chunk=chunk)
        scratch = [
            pltpu.VMEM((k, lane), jnp.float32),          # acc_a
            pltpu.VMEM((1, lane), jnp.float32),          # acc_b
            pltpu.VMEM((2 * k, lane), jnp.float32),      # stage_a (2 slots)
            pltpu.VMEM((2, lane), jnp.float32),          # stage_b
            pltpu.SMEM((1,), jnp.int32),                 # cur_row
            pltpu.SMEM((3,), jnp.int32),                 # slot + pendings
            pltpu.SemaphoreType.DMA,                     # sem_a0
            pltpu.SemaphoreType.DMA,                     # sem_a1
            pltpu.SemaphoreType.DMA,                     # sem_b0
            pltpu.SemaphoreType.DMA,                     # sem_b1
        ]
    else:
        kernel = functools.partial(_segment_kernel, chunk=chunk)
        scratch = [
            pltpu.VMEM((k, lane), jnp.float32),
            pltpu.VMEM((1, lane), jnp.float32),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.SemaphoreType.DMA,
        ]
    return pl.pallas_call(
        kernel,
        grid=(n_steps,),
        in_specs=[
            # (1, 1, chunk) SMEM block: 1-d s32 operands tile T(1024)
            # on the XLA side vs Mosaic's T(128) and fail layout checks,
            # and a (1, chunk) block trips the "second-minor divisible by
            # 8" rule — a middle singleton dim satisfies both
            pl.BlockSpec((1, 1, chunk), lambda i: (i, 0, 0),
                         memory_space=smem),
            pl.BlockSpec((1, chunk, k, k), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((1, chunk, k), lambda i: (i, 0, 0)),
            pl.BlockSpec(memory_space=hbm),         # a_init (aliased)
            pl.BlockSpec(memory_space=hbm),         # b_init (aliased)
        ],
        out_specs=[
            pl.BlockSpec(memory_space=hbm),         # a_out
            pl.BlockSpec(memory_space=hbm),         # b_out
            # trail blocks revisit the same VMEM tile every step: Mosaic
            # writes them back once at grid end
            pl.BlockSpec((k, lane), lambda i: (0, 0)),
            pl.BlockSpec((1, lane), lambda i: (0, 0)),
            pl.BlockSpec(memory_space=smem),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(a_buf.shape, jnp.float32),
            jax.ShapeDtypeStruct(b_buf.shape, jnp.float32),
            jax.ShapeDtypeStruct((k, lane), jnp.float32),
            jax.ShapeDtypeStruct((1, lane), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.int32),
        ],
        scratch_shapes=scratch,
        # A/b accumulate in place across groups (indices count ALL inputs)
        input_output_aliases={3: 0, 4: 1},
        interpret=interpret,
    )(rows_g.reshape(n_steps, 1, chunk), a_blks, b_blks, a_buf, b_buf)


def _lane_for(k: int) -> int:
    return max(128, -(-k // 128) * 128)  # round UP to a lane multiple


# above this rank the kernel's blocks do not fit: >= 8 slots x k^2 x 4 B,
# double-buffered, against 16 MB of scoped VMEM whatever the chunk
MAX_RANK = 256


def _kernel_chunk(k: int, chunk_slots: int) -> int:
    """Slots a grid step: the blocks block is chunk*k*k*4 bytes
    DOUBLE-buffered by the pallas pipeline, and the whole stack must fit
    the 16 MB scoped limit (chunk=128 at k=128 overflows it by 130 KB);
    4 MB a buffer keeps headroom for b/trail/acc up to MAX_RANK. Rounded
    DOWN to a power of two that divides chunk_slots, so that a group of
    whole XLA chunks is a whole number of grid steps."""
    cap = min(128, max(8, (4 * 2**20) // (k * k * 4)))
    chunk = 1 << (cap.bit_length() - 1)
    while chunk_slots % chunk:
        chunk //= 2
    return chunk


def segment_flush(rows, n_self: int, k: int, chunk_slots: int, groups,
                  overlap: bool = False, interpret: bool | None = None):
    """Sum per-slot blocks into A (n_self,k,k), b (n_self,k) by the
    segment-flush kernel. `rows` (S,) is the layout's non-decreasing
    slot->row index with its sentinel tail (row id n_self: one padding row
    absorbs that segment). `groups` yields (lo, hi, a_blks (hi-lo,k,k),
    b_blks (hi-lo,k)) in slot order, each a whole number of `chunk_slots`;
    it is consumed one group at a time, so a generator that builds a
    group's blocks when asked holds one group of them at once.
    `overlap` picks the kernel (module docstring); it changes no sum."""
    if k > MAX_RANK:
        raise ValueError(f"rank {k} > {MAX_RANK}: the kernel's blocks do "
                         "not fit scoped VMEM")
    if interpret is None:
        interpret = jax.devices()[0].platform == "cpu"
    chunk = _kernel_chunk(k, chunk_slots)
    lane = _lane_for(k)
    n_pad = n_self + 1
    with jax.named_scope("als.flush"):
        a_buf = jnp.zeros((n_pad, k, lane), jnp.float32)
        b_buf = jnp.zeros((n_pad, lane), jnp.float32)
    t_rows, t_as, t_bs = [], [], []
    for lo, hi, a_blks, b_blks in groups:
        n_steps = (hi - lo) // chunk
        assert n_steps * chunk == hi - lo, (lo, hi, chunk)
        blocks = (a_blks.reshape(n_steps, chunk, k, k),
                  b_blks.reshape(n_steps, chunk, k))
        a_buf, b_buf, tr_a, tr_b, tr_row = _run_segment_group(
            rows[lo:hi], *blocks, a_buf, b_buf, chunk=chunk, k=k,
            lane=lane, interpret=interpret, overlap=overlap)
        with jax.named_scope("als.flush"):
            t_rows.append(tr_row.reshape(1))
        t_as.append(tr_a)
        t_bs.append(tr_b)
    # the in-kernel flush is the ONLY writer of a row (its segment ends
    # in exactly one group), so flush + trail adds reconstruct rows
    # spanning group boundaries exactly
    with jax.named_scope("als.flush"):
        t_a = jnp.stack(t_as)                       # (n_groups, k, lane)
        A = a_buf.at[jnp.concatenate(t_rows)].add(t_a, mode="drop")
        b = b_buf.at[jnp.concatenate(t_rows)].add(
            jnp.concatenate(t_bs), mode="drop")
        return A[:n_self, :, :k], b[:n_self, :k]
