"""Pallas TPU kernels for the ALS normal equations: the segment flush
that sums them, and the lane-packed form the CG solve holds them in.

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

Lane-packed A (PR 32). A row of A at rank 64 is 64 x 128 floats as the
flush writes it and as XLA tiles an f32[n,64,64]: half of every lane row
is padding, and a CG product that runs at the memory's rate reads it all.
Where `pack_factor(k)` > 1 a side that CG solves holds A as
(n, k/pack, lane) instead: lane row r holds matrix rows r, r + k/pack, ...
side by side (`pack_rows`), A's k*k floats and nothing else. `pack_flush`
is the pass that adds the Gram term: it reads the flush's wide buffer once
and writes the packed rows and their diagonal; `packed_matvec` is the CG
product on that form, float32 multiplies and adds (1.4e-7 of the float64
product where the einsum at Precision.HIGH it replaces has 1.4e-5).

The record (TPU v5e, jax 0.9.0 / libtpu 0.0.34, PR 21,
`eval/kernel_parity.py` at the ML-20M factor shapes): both flush kernels,
and a fused variant that made the blocks in the kernel, compiled through
Mosaic and matched the XLA `carry` path through a half-sweep to 1-2e-4
relative. `hybrid` is the path of every benchmark cell (PERF.md). The
rules they are built to: accumulators and outputs are LANE-wide (a (K,K)
row slice of a lane-padded HBM memref is refused); row ids arrive as
(1,1,chunk) SMEM blocks (1-d s32 operands tile T(1024), Mosaic wants
T(128)); second-minor block dims divide 8; the stack of double-buffered
blocks and scratch stays under the 16 MB of scoped VMEM.

The record of PR 32 (the same chip and versions; 138,493 rows at rank 64,
2.27 GB packed; ms a call alone, 20 calls; PERF.md section 6 has the job):

 * `eval/kernel_parity.py`: a half-sweep through flush, pack and product,
   compiled, against the XLA `carry` path: 2.5e-4 relative on the user
   side and 4.8e-5 on the item side, `hybrid` and `stream` equal;

 * the product, every form compiled through Mosaic. Masked whole-lane sums
   (`_matvec_kernel`): **3.35 ms** alone, 3.21 ms in the train program
   (the einsum on the padded A it replaces: 5.4 ms there, 17.4 alone).
   Sums over lane slices [:k], [k:]: 3.64. The same in a loop over 8 rows
   at a time: 7.68. Lane sums by a 0/1 selection matrix on the MXU: 5.04
   at Precision.HIGHEST, 3.57 with the product split into three bfloat16
   parts by hand. The symmetric form (column sums, x transposed): 13.2,
   its 128 rows unrolled because a dynamic lane index is refused: "cannot
   statically prove that index in dimension 1 is a multiple of 128".
   Plain XLA on the packed form: 6.82;
 * blocks: 128 rows (2 MB of A). 512 rows: "Scoped allocation with size
   17.00M and limit 16.00M exceeded scoped vmem limit by 1.00M"; with
   `vmem_limit_bytes` raised, 128 to 1,024 rows all read 3.55-3.58 ms;
 * the pack. XLA makes four passes of it however it is written (the sum
   materialised, two slices, a concatenate: 5 formulations, compiled for
   the chip), so it is a kernel: 10.2 ms for 4.54 GB read and 2.27 GB
   written, against 13.9 ms for the Gram add it replaces in the program;
 * the flush accumulating and writing packed rows (a slot's block laid
   side by side in the kernel, no pack pass, the Gram term beside the
   product): compiled, bit-equal to the flush packed afterwards, and
   slower: `als.flush` 0.646 -> 0.766 s a job on the user side and 0.147
   -> 0.253 on the item side at ML-20M, the sweep +0.16 s. Left out.

Refused by Mosaic in PR 21's run and removed in PR 28 (the fused variant
went with them: no product path could select it); the git history has the
code. Whoever takes one up again should start from the compiler's words,
not from the same design:

 * a (K,LANE)->(1,K*K) pack inside the flush: "infer-vector-layout:
   unsupported shape cast vector<64x64xf32> -> vector<1x4096xf32>". What
   PR 32 packs is whole sublane tiles side by side, which needs no cast;
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


def _interpret() -> bool:
    """The kernels run compiled on a TPU and interpreted on a CPU."""
    return jax.devices()[0].platform == "cpu"


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
                  overlap: bool = False, interpret: bool | None = None,
                  wide: bool = False):
    """Sum per-slot blocks into A (n_self,k,k), b (n_self,k) by the
    segment-flush kernel. `rows` (S,) is the layout's non-decreasing
    slot->row index with its sentinel tail (row id n_self: one padding row
    absorbs that segment). `groups` yields (lo, hi, a_blks (hi-lo,k,k),
    b_blks (hi-lo,k)) in slot order, each a whole number of `chunk_slots`;
    it is consumed one group at a time, so a generator that builds a
    group's blocks when asked holds one group of them at once.
    `overlap` picks the kernel (module docstring); it changes no sum.
    `wide` returns A as the kernel wrote it, (n_self + 1, k, lane) with
    the padding row and the zero lanes [k:], for `pack_flush` to read."""
    if k > MAX_RANK:
        raise ValueError(f"rank {k} > {MAX_RANK}: the kernel's blocks do "
                         "not fit scoped VMEM")
    if interpret is None:
        interpret = _interpret()
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
        return (A if wide else A[:n_self, :, :k]), b[:n_self, :k]


# ---------------------------------------------------------------------------
# lane-packed A: the form the CG solve holds the normal equations in
# ---------------------------------------------------------------------------

def pack_factor(k: int) -> int:
    """Matrix rows a lane row of the packed A holds: `lane // k` where the
    rank leaves lanes empty and a packed row is whole (8, lane) tiles
    (2 at rank 64, 4 at rank 32), else 1: not packed."""
    lane = _lane_for(k)
    pack = lane // k
    return pack if pack > 1 and lane % k == 0 and k % (8 * pack) == 0 else 1


def pack_rows(a, pack: int):
    """(n, k, k) -> (n, k/pack, pack*k): lane row r holds matrix rows
    r, r + k/pack, r + 2k/pack, ... side by side, so a packed row is A's
    k*k floats and nothing else: slices of whole sublane tiles and a lane
    concatenate, in `pack_flush`'s kernel and, for the tests, in XLA."""
    h = a.shape[1] // pack
    return jnp.concatenate(
        [a[:, q * h:(q + 1) * h, :] for q in range(pack)], axis=-1)


def unpack_rows(a_p, pack: int):
    """The inverse of `pack_rows`."""
    k = a_p.shape[2] // pack
    return jnp.concatenate(
        [a_p[:, :, q * k:(q + 1) * k] for q in range(pack)], axis=1)


def _pack_kernel(a_ref, g_ref, o_ref, d_ref, *, pack: int):
    """A block of the flush's rows (B, k, lane), lanes [k:] zero, plus
    the Gram term g (k, lane) -> the packed rows (B, k/pack, lane) and
    their diagonal (B, k). Slices of whole sublane tiles laid side by
    side along the lanes: nothing is reshaped."""
    k = a_ref.shape[1]
    a = a_ref[...] + g_ref[...][None]
    o_ref[...] = pack_rows(a[:, :, :k], pack)
    eye = (jax.lax.broadcasted_iota(jnp.int32, a.shape[1:], 0)
           == jax.lax.broadcasted_iota(jnp.int32, a.shape[1:], 1))
    d_ref[...] = jnp.sum(jnp.where(eye[None], a, 0.0), axis=-1)


# bytes of A a grid step of the pack and of the product reads: 2 MB is
# 128 rows of packed A at rank 64 (64 of the flush's wide rows);
# double-buffered, with the product beside it, under the 16 MB of scoped
# VMEM (256 rows overrun it; the module docstring has the runs)
_BLOCK_BYTES = 2 * 2**20


def _block_rows(row_bytes: int, n: int) -> int:
    return min(max(8, _BLOCK_BYTES // row_bytes), -(-n // 8) * 8)


@jax.named_scope("als.gram")
def pack_flush(a_wide, gram, n_self: int, pack: int,
               interpret: bool | None = None):
    """The pass that adds the Gram term, writing the form the solve
    holds: a_wide (n_self + 1, k, lane) from `segment_flush(wide=True)`,
    gram (k, k) -> (A packed (n_self, k/pack, lane), diag(A) (n_self, k)).
    One read of the flush's buffer, one write of 1/pack of its bytes."""
    from jax.experimental import pallas as pl

    if interpret is None:
        interpret = _interpret()
    _, k, lane = a_wide.shape
    h = k // pack
    block = _block_rows(k * lane * 4, n_self)
    return pl.pallas_call(
        functools.partial(_pack_kernel, pack=pack),
        grid=(pl.cdiv(n_self, block),),
        in_specs=[pl.BlockSpec((block, k, lane), lambda i: (i, 0, 0)),
                  pl.BlockSpec((k, lane), lambda i: (0, 0))],
        out_specs=[pl.BlockSpec((block, h, lane), lambda i: (i, 0, 0)),
                   pl.BlockSpec((block, k), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((n_self, h, lane), jnp.float32),
                   jax.ShapeDtypeStruct((n_self, k), jnp.float32)],
        interpret=interpret, name="als.gram.pack",
    )(a_wide, _pad_lanes(gram, lane))


def _matvec_kernel(a_ref, x_ref, o_ref, *, pack: int):
    """o[b] = A_b @ x[b] on a block of packed rows: x is laid `pack`
    times along the lanes, multiplied into the block, and each run of k
    lanes is summed: float32 multiplies and adds only."""
    x = x_ref[...]                                       # (B, k)
    k = x.shape[1]
    p = a_ref[...] * jnp.concatenate([x] * pack, axis=-1)[:, None, :]
    run = jax.lax.broadcasted_iota(jnp.int32, p.shape, 2) // k
    o_ref[...] = jnp.concatenate(
        [jnp.sum(jnp.where(run == q, p, 0.0), axis=-1)
         for q in range(pack)], axis=-1)


@jax.named_scope("als.cg")
def packed_matvec(a_p, x, interpret: bool | None = None):
    """Batched A_b @ x[b] on lane-packed A: a_p (n, k/pack, lane) from
    `pack_rows`, x (n, k) -> (n, k), float32. The last block may hang
    over n: rows are independent and what is written past n is dropped."""
    from jax.experimental import pallas as pl

    if interpret is None:
        interpret = _interpret()
    n, h, lane = a_p.shape
    k = x.shape[1]
    pack = lane // k
    assert h * pack == k and x.shape[0] == n, (a_p.shape, x.shape)
    block = _block_rows(h * lane * 4, n)
    return pl.pallas_call(
        functools.partial(_matvec_kernel, pack=pack),
        grid=(pl.cdiv(n, block),),
        in_specs=[pl.BlockSpec((block, h, lane), lambda i: (i, 0, 0)),
                  pl.BlockSpec((block, k), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block, k), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, k), jnp.float32),
        interpret=interpret, name="als.cg.matvec",
    )(a_p, x)
