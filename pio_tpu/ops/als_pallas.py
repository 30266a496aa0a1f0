"""Pallas TPU kernel for the ALS normal-equation accumulation.

The third accumulation strategy (ops/als.py accum="pallas"), designed for
the case where neither XLA path reaches the memory bound
(eval/ALS_ROOFLINE.md):

 * "carry":   scatter into a (n,k,k) lax.scan carry — re-streams the
              accumulator once per chunk if the backend materializes it;
 * "stacked": per-slot blocks as scan outputs + grouped sorted
              scatter-add — bounded temp, but still materializes S·k²
              floats and trusts XLA's scatter lowering;
 * "pallas":  THIS kernel. Slots are processed in GROUPS (bounding the
              XLA factor-gather temp at group_slots·W·k bytes); within a
              group the kernel fuses the per-slot (k,W)x(W,k) MXU
              products with a SEGMENT FLUSH: slots are row-sorted
              (_device_slot_layout) and TPU Pallas grids execute
              sequentially on a core, so a (k,k) VMEM scratch
              accumulates the open row's partial blocks (scratch
              persists across grid steps) and DMAs each segment that
              ENDS inside the group to A in HBM. The group's final open
              segment is emitted as a "trail" output — a row may span
              groups, and each group contributes at most one trail — and
              every trail folds in afterwards with ONE tiny
              n_groups-row scatter-add (rows are sorted, flush is the
              only writer of its row, so flush + trail-adds sum exactly;
              no cross-group seeding or host synchronization needed).
              A/b zero-initialize via input/output aliasing, so empty
              rows read as zeros with no extra pass over A.

Per-sweep traffic: the factor gather (written once by XLA per group,
re-read once by the kernel), the zero-fill + one write of A, and row ids
streamed through SMEM one (1,1,chunk)-block per grid step. No scatter
over k² blocks, no (n,k,k) carry, no unbounded temp.

Status on the chip (TPU v5e, jax 0.9.0 / libtpu 0.0.34, PR 21,
eval/kernel_parity.py at the ML-20M factor shapes): the segment-flush
kernel (accum="hybrid", auto's TPU pick), the fused kernel
(accum="pallas") and the overlapped flush (accum="stream") compile
through Mosaic and match the XLA carry path through a half-sweep to
1-2e-4 relative. The portability rules they were built to: LANE-wide
accumulators/outputs (per-row (K,K) DMA slices of a lane-padded HBM
memref are rejected); (1,1,chunk) SMEM row blocks (1-d s32 operands
tile T(1024) vs Mosaic's T(128)); second-minor block dims must divide
8. Round 3 measured the fused kernel slower than XLA's batched einsum
(0.249 s vs 0.199-0.211 s users-half), so auto never selects it.

The rest of the STREAMING accumulation path (eval/ALS_ROOFLINE.md
round-6 plan) is interpret-validated (tests/test_als_pallas.py) but
Mosaic REFUSES three of its kernels today — selecting them on a TPU
raises the compiler's error, nothing gives way to XLA:

 * packed_a (the (K,LANE)->(1,K*K) pack in the flush): "infer-vector-
   layout: unsupported shape cast vector<64x64xf32> -> vector<1x4096xf32>"
   (packed_block_matvec alone compiles and matches to 2e-7);
 * gather_rows_pallas: copy — "cannot statically prove that index in
   dimension 0 is a multiple of 8" (dynamic single-row load from a bf16
   VMEM table); take — "Can only load scalars from SMEM";
 * gather_rows_stream: "Slice shape along dimension 0 must be aligned
   to tiling (8), but is 1" (single-row DMA out of a tiled HBM table).

 * gather_rows_stream — double-buffered HBM->VMEM streaming gather
   (any table size; mini-group g+1's per-row copies in flight while g
   stores), the custom gather the roofline note calls for;
 * _segment_kernel_stream (accum="stream") — overlapped segment flush:
   each A-row DMA starts at its flush point and is awaited at the NEXT
   flush that reuses the staging slot, hiding the 65 ms/sweep of
   exposed flush latency;
 * lane-packed A: the streaming flush can write A rows (n, k²) —
   k² is a 128-multiple, so no lane padding (a 2x byte cut at k=64) —
   and packed_block_matvec consumes the packed rows natively in CG, so
   the packed form survives end-to-end with no XLA relayout.
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


def _segment_kernel(*refs, chunk: int, slot_fn):
    """Shared segment-flush kernel body. refs =
    (rows_ref (1,1,chunk) SMEM, *data_refs, a_init, b_init,   <- inputs
     a_out (n_pad,K,LANE) HBM, b_out (n_pad,LANE) HBM,        <- aliased
     trail_a (K,LANE), trail_b (1,LANE), trail_row (1,1) SMEM,
     acc_a, acc_b, cur_row, dma_sem)                          <- scratch

    One grid step = `chunk` consecutive slots; the sequential TPU grid +
    persistent scratch carry the open row segment across steps. Segments
    that END inside the group DMA to A/b (each A row written exactly
    once); the group's last open segment goes to the trail outputs,
    folded across groups by the caller. `slot_fn(data_refs, i, K, LANE)`
    -> (blk (K,LANE), b_row (LANE,)) produces slot i's contribution —
    the only difference between the fused-ne and scatter-only variants.

    Accumulators/outputs are LANE(=128-multiple)-wide with columns [K:]
    zero: Mosaic requires HBM memref slices to be lane-tile aligned (a
    (K,K) row slice of a lane-padded (n,K,K) buffer is rejected with
    "Slice shape along dimension 2 must be aligned to tiling (128)"),
    and the physical HBM bytes equal XLA's padded layout anyway."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    (rows_ref, *data_refs, _a_init, _b_init, a_out, b_out,
     trail_a, trail_b, trail_row, acc_a, acc_b, cur_row, dma_sem) = refs
    step = pl.program_id(0)
    n_steps = pl.num_programs(0)
    K = acc_a.shape[0]
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

        blk, b_row = slot_fn(data_refs, i, K, LANE)
        acc_a[...] += blk
        acc_b[...] += b_row[None, :]
        return ()

    jax.lax.fori_loop(0, chunk, slot_body, (), unroll=False)

    @pl.when(step == n_steps - 1)
    def _emit_trail():  # the group's last open segment is NEVER flushed
        trail_a[...] = acc_a[...]
        trail_b[...] = acc_b[...]
        trail_row[0, 0] = cur_row[0]


def _ne_slot_fn(data_refs, i, K, LANE):
    """Fused variant: per-slot (K,W)x(W,LANE) MXU product from gathered
    factors + weights. HIGHEST precision: the default 1-pass bf16 MXU
    contraction loses ~3e-3 relative on A, which the CG solve cannot
    recover (same rationale as _chunk_blocks' Precision.HIGH; Mosaic
    supports only DEFAULT|HIGHEST for dot_general, so XLA's 3-pass HIGH
    middle ground is unavailable in-kernel)."""
    y_ref, wo_ref, wr_ref = data_refs
    y = y_ref[0, i].astype(jnp.float32)          # (W, K)
    wo = wo_ref[0, i].astype(jnp.float32)        # (W,)
    wr = wr_ref[0, i].astype(jnp.float32)
    yw = _pad_lanes(y * wo[:, None], LANE)       # dot fills the lanes
    blk = jax.lax.dot_general(
        y, yw, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )
    b_row = _pad_lanes(jnp.sum(y * wr[:, None], axis=0), LANE)
    return blk, b_row


def _flush_slot_fn(data_refs, i, K, LANE):
    """Scatter-only variant (accum="hybrid"): blocks precomputed by
    XLA's batched MXU einsum; the kernel only streams and flushes."""
    ablk_ref, bblk_ref = data_refs
    return (_pad_lanes(ablk_ref[0, i], LANE),
            _pad_lanes(bblk_ref[0, i], LANE))


def _segment_kernel_stream(*refs, chunk: int, slot_fn, packed: bool):
    """Overlapped-flush variant of _segment_kernel (accum="stream").

    Same segment algebra — sequential grid, persistent scratch carrying
    the open row, trail emitted for the group's last open segment — but
    the flush no longer serializes behind its own DMA: each segment end
    copies the accumulator into one of TWO staging slots, STARTS the
    HBM row writes, and returns to the MXU dots immediately; the wait
    happens at the NEXT flush that wants the same slot (or at the trail
    emit). In the round-5 profile the in-kernel start+wait flushes were
    65 ms/sweep of exposed DMA latency — two staged slots hide a flush
    behind at least one full following segment of compute.

    With packed=True the flush additionally writes A rows LANE-PACKED:
    a_out is (n_pad, k²) — k² is a 128-multiple for every supported k,
    so the physical HBM row carries no lane padding (at k=64 that
    halves A's streamed bytes: the 2x tax eval/ALS_ROOFLINE.md charges
    every k=64 buffer) and the packed batched matvec
    (packed_block_matvec) consumes it natively — no XLA relayout at
    the scatter/solve boundary. The pack itself is a per-FLUSH (per
    A-row, not per-slot) (K,LANE)->(1,K*K) VMEM reshape.

    refs = (rows_ref, *data_refs, a_init, b_init,   <- inputs
            a_out, b_out, trail_a, trail_b, trail_row,  <- outputs
            acc_a, acc_b, stage_a, stage_b, cur_row, st,
            sem_a0, sem_a1, sem_b0, sem_b1)         <- scratch

    st (3,) SMEM: [next staging slot, pending row of slot 0, pending
    row of slot 1] (-1 = no DMA in flight). Staging slots are indexed
    with PYTHON ints via parity branches so every ref slice except the
    destination row is static (the round-3 Mosaic portability rules);
    the destination a_out.at[row] with a traced row is the pattern the
    plain kernel hardware-validated. Waits reconstruct the same copy
    descriptor they started — descriptor equality is what pairs a wait
    with its start."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    (rows_ref, *data_refs, _a_init, _b_init, a_out, b_out,
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
        if packed:
            a_src = stage_a.at[pl.ds(slot, 1)]          # (1, K*K)
            a_dst = a_out.at[pl.ds(row, 1)]
        else:
            a_src = stage_a.at[pl.ds(slot * K, K)]      # (K, LANE)
            a_dst = a_out.at[row]
        return (
            pltpu.make_async_copy(a_src, a_dst, sem_a),
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
        if packed:
            stage_a[pl.ds(slot, 1), :] = (
                acc_a[...][:, :K].reshape(1, K * K))
        else:
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

        blk, b_row = slot_fn(data_refs, i, K, LANE)
        acc_a[...] += blk
        acc_b[...] += b_row[None, :]
        return ()

    jax.lax.fori_loop(0, chunk, slot_body, (), unroll=False)

    @pl.when(step == n_steps - 1)
    def _emit_trail():
        drain(0)  # every in-flight row write lands before the kernel ends
        drain(1)
        trail_a[...] = acc_a[...]   # trail stays UNPACKED; the caller's
        trail_b[...] = acc_b[...]   # fold packs it (n_groups tiny rows)
        trail_row[0, 0] = cur_row[0]


@jax.named_scope("als.flush")
def _run_segment_group(rows_g, data, data_specs, a_buf, b_buf, *,
                       chunk: int, k: int, lane: int, slot_fn,
                       interpret: bool, overlap: bool = False,
                       packed: bool = False):
    """One pallas_call over a group: rows + variant-specific data blocks
    in, aliased A/b buffers accumulated in place, trail emitted.
    overlap/packed select the streaming-flush kernel variant
    (_segment_kernel_stream); packed implies the streaming kernel — the
    plain kernel's acc-shaped DMA cannot write (1, k²) rows."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n_steps = rows_g.shape[0] // chunk
    smem = pltpu.MemorySpace.SMEM
    hbm = pltpu.MemorySpace.HBM
    n_in = 1 + len(data) + 2
    if overlap or packed:
        kernel = functools.partial(
            _segment_kernel_stream, chunk=chunk, slot_fn=slot_fn,
            packed=packed)
        scratch = [
            pltpu.VMEM((k, lane), jnp.float32),          # acc_a
            pltpu.VMEM((1, lane), jnp.float32),          # acc_b
            pltpu.VMEM((2, k * k) if packed else (2 * k, lane),
                       jnp.float32),                     # stage_a (2 slots)
            pltpu.VMEM((2, lane), jnp.float32),          # stage_b
            pltpu.SMEM((1,), jnp.int32),                 # cur_row
            pltpu.SMEM((3,), jnp.int32),                 # slot + pendings
            pltpu.SemaphoreType.DMA,                     # sem_a0
            pltpu.SemaphoreType.DMA,                     # sem_a1
            pltpu.SemaphoreType.DMA,                     # sem_b0
            pltpu.SemaphoreType.DMA,                     # sem_b1
        ]
    else:
        kernel = functools.partial(
            _segment_kernel, chunk=chunk, slot_fn=slot_fn)
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
            *data_specs,
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
        input_output_aliases={n_in - 2: 0, n_in - 1: 1},
        interpret=interpret,
    )(rows_g.reshape(n_steps, 1, chunk), *data, a_buf, b_buf)


@jax.named_scope("als.layout")
def _pad_slots(layout, pad: int, n_self: int):
    """Append `pad` sentinel slots (row id n_self — keeps the sorted-rows
    invariant; zero lens/idx/val contribute nothing) to a slot layout."""
    rows, idx, val, lens = layout
    if not pad:
        return layout
    W = idx.shape[1]
    return (
        jnp.concatenate([rows, jnp.full((pad,), n_self, rows.dtype)]),
        jnp.concatenate([idx, jnp.zeros((pad, W), idx.dtype)]),
        jnp.concatenate([val, jnp.zeros((pad, W), val.dtype)]),
        jnp.concatenate([lens, jnp.zeros((pad,), lens.dtype)]),
    )


def _lane_for(k: int) -> int:
    return max(128, -(-k // 128) * 128)  # round UP to a lane multiple


def _chain_groups(n_self: int, k: int, groups, packed: bool = False):
    """Run group thunks in sequence over aliased A/b buffers and fold
    each group's trailing open segment: the in-kernel flush is the ONLY
    writer of a row (its segment ends in exactly one group), so flush +
    trail adds reconstruct rows spanning group boundaries exactly.
    `groups` yields thunks (a_buf, b_buf) -> 5-tuple from
    _run_segment_group. One padding row absorbs the sentinel segment.

    packed=True allocates A lane-packed (n_pad, k²) — the streaming
    flush kernel writes packed rows — and packs the (few, one per
    group) UNPACKED trails on the XLA side before the fold; the packed
    zero-init also streams k²/  (k·LANE) of the padded bytes (half, at
    k=64)."""
    lane = _lane_for(k)
    n_pad = n_self + 1
    with jax.named_scope("als.flush"):
        if packed:
            a_buf = jnp.zeros((n_pad, k * k), jnp.float32)
        else:
            a_buf = jnp.zeros((n_pad, k, lane), jnp.float32)
        b_buf = jnp.zeros((n_pad, lane), jnp.float32)
    t_rows, t_as, t_bs = [], [], []
    for run in groups:
        a_buf, b_buf, tr_a, tr_b, tr_row = run(a_buf, b_buf, lane)
        with jax.named_scope("als.flush"):
            t_rows.append(tr_row.reshape(1))
        t_as.append(tr_a)
        t_bs.append(tr_b)
    with jax.named_scope("als.flush"):
        t_a = jnp.stack(t_as)                       # (n_groups, k, lane)
        if packed:
            t_a = t_a[:, :, :k].reshape(len(t_as), k * k)
        A = a_buf.at[jnp.concatenate(t_rows)].add(t_a, mode="drop")
        b = b_buf.at[jnp.concatenate(t_rows)].add(
            jnp.concatenate(t_bs), mode="drop")
        if packed:
            return A[:n_self], b[:n_self, :k]
        return A[:n_self, :, :k], b[:n_self, :k]


def normal_equations_pallas(layout, other_factors, n_self: int,
                            implicit: bool, alpha: float,
                            chunk_slots: int = 128,
                            group_slots: int = 65536,
                            bf16_gather: bool = True,
                            interpret: bool | None = None):
    """Fused Pallas segment-flush accumulation: -> A (n_self,k,k),
    b (n_self,k). Same contract as ops/als._normal_equations minus the
    shared YtY / reg terms (added by the caller for implicit mode).

    chunk_slots sizes the VMEM working set (y block = chunk·W·k·2 bytes,
    128·128·64·2 = 2 MB double-buffered); group_slots bounds the XLA
    factor-gather temp (group·W·k·2 = 1.07 GB at the defaults). Fully
    traceable — no host synchronization — so it jits inside the training
    scan like the XLA paths."""
    from jax.experimental import pallas as pl

    if interpret is None:
        interpret = jax.devices()[0].platform == "cpu"
    rows, idx, val, lens = layout
    k = other_factors.shape[1]
    S, W = idx.shape
    chunk = min(chunk_slots, S)
    # pad the slot axis to a whole number of kernel chunks
    pad = -S % chunk
    rows, idx, val, lens = _pad_slots((rows, idx, val, lens), pad, n_self)
    S += pad

    src = (
        other_factors.astype(jnp.bfloat16) if bf16_gather else other_factors
    )
    mask = (
        jnp.arange(W, dtype=jnp.int32)[None, :] < lens[:, None]
    ).astype(jnp.float32)
    vf = val.astype(jnp.float32)
    if implicit:
        w_outer = alpha * vf * mask
        w_rhs = (1.0 + alpha * vf) * mask
    else:
        w_outer = mask
        w_rhs = vf * mask

    g_slots = max(chunk, (group_slots // chunk) * chunk)

    def group_thunk(lo, hi):
        def run(a_buf, b_buf, lane):
            y_g = src[idx[lo:hi]]               # bounded gather temp
            n_steps = (hi - lo) // chunk
            data = (y_g.reshape(n_steps, chunk, W, k),
                    w_outer[lo:hi].reshape(n_steps, chunk, W),
                    w_rhs[lo:hi].reshape(n_steps, chunk, W))
            specs = (
                pl.BlockSpec((1, chunk, W, k), lambda i: (i, 0, 0, 0)),
                pl.BlockSpec((1, chunk, W), lambda i: (i, 0, 0)),
                pl.BlockSpec((1, chunk, W), lambda i: (i, 0, 0)),
            )
            return _run_segment_group(
                rows[lo:hi], data, specs, a_buf, b_buf, chunk=chunk,
                k=k, lane=lane, slot_fn=_ne_slot_fn, interpret=interpret,
            )
        return run

    groups = [group_thunk(lo, min(S, lo + g_slots))
              for lo in range(0, S, g_slots)]
    return _chain_groups(n_self, k, groups)


# ---------------------------------------------------------------------------
# accum="hybrid": XLA batched-MXU blocks + the shared segment-flush kernel
# with the scatter-only slot_fn — no in-kernel dots, pure streaming adds
# ---------------------------------------------------------------------------

def normal_equations_hybrid(layout, other_factors, n_self: int,
                            implicit: bool, alpha: float,
                            chunk_slots: int = 32768,
                            kernel_chunk: int = 128,
                            group_slots: int = 65536,
                            bf16_gather: bool = True,
                            interpret: bool | None = None,
                            gather: str = "xla",
                            overlap: bool = False,
                            packed: bool = False):
    """accum="hybrid": XLA builds the per-slot blocks (batched MXU
    einsum, _chunk_blocks — the hardware A/B showed it beats in-kernel
    serial dots), the shared segment-flush kernel replaces only the
    scatter-add into A (the ~13%-of-peak emitter, 118 ms/sweep in the
    round-3 profile) so each A row is written exactly once. Same
    contract/trail algebra and group chaining as
    normal_equations_pallas.

    overlap=True (accum="stream") swaps in the overlapped-flush kernel
    (_segment_kernel_stream): segment flushes start their HBM DMA and
    wait at the NEXT flush point instead of in-kernel, hiding the
    65 ms/sweep of exposed flush latency the round-5 profile charged
    the hybrid kernel. packed=True additionally stores A lane-packed
    (n_self, k²) — returned 2-d; consumers feed it to
    packed_block_matvec / unpack once for the exact solve."""
    import math as _math

    from jax.experimental import pallas as pl

    from pio_tpu.ops.als import _chunk_blocks  # lazy: als imports us lazily

    if interpret is None:
        interpret = jax.devices()[0].platform == "cpu"
    rows, idx, val, lens = layout
    k = other_factors.shape[1]
    S, W = idx.shape
    # VMEM-budget the kernel chunk: the blocks block is chunk*k*k*4 bytes
    # DOUBLE-buffered by the pallas pipeline, and the whole stack must fit
    # the 16 MB scoped limit (measured: chunk=128 at k=128 overflows by
    # 130 KB); 4 MB per buffer keeps headroom for b/trail/acc up to the
    # k=256 cap (ops/als.py falls back to stacked above it). The chunk is
    # then rounded DOWN to a power of two that divides chunk_slots: a
    # non-divisor chunk makes quantum = lcm(chunk, chunk_slots) explode
    # (k=96 -> chunk 113, lcm(113, 8192) = 925k slots of blocks temp).
    vmem_chunk = max(8, (4 * 2**20) // (k * k * 4))
    cap = max(1, min(kernel_chunk, vmem_chunk, S))
    chunk = 1 << (cap.bit_length() - 1)
    while chunk > 1 and chunk_slots % chunk:
        chunk //= 2
    # every group must hold WHOLE XLA-scan chunks (chunk_slots) and WHOLE
    # kernel chunks, or the scan collapses to one giant chunk and the
    # gather temp that chunk_slots exists to bound becomes unbounded —
    # pad S to the combined quantum so even the last group divides
    quantum = chunk * chunk_slots // _math.gcd(chunk, chunk_slots)
    pad = -S % quantum
    rows, idx, val, lens = _pad_slots((rows, idx, val, lens), pad, n_self)
    S += pad
    with jax.named_scope("als.gather"):
        src = (
            other_factors.astype(jnp.bfloat16) if bf16_gather
            else other_factors
        )
    from pio_tpu.ops.als import blocks_group_budget_slots

    g_eff = min(group_slots, blocks_group_budget_slots(k))
    g_slots = max(quantum, (g_eff // quantum) * quantum)

    def group_thunk(lo, hi):
        def run(a_buf, b_buf, lane):
            # blocks via the XLA scan exactly as accum="stacked"
            # builds them; quantum padding guarantees divisibility
            c_sz = chunk_slots
            n_ch = (hi - lo) // c_sz
            xs = (idx[lo:hi].reshape(n_ch, c_sz, W),
                  val[lo:hi].reshape(n_ch, c_sz, W),
                  lens[lo:hi].reshape(n_ch, c_sz))

            def body(_, xs_c):
                i_c, v_c, l_c = xs_c
                return None, _chunk_blocks(src, i_c, v_c, l_c,
                                           implicit, alpha, gather=gather)

            _, (a_blks, b_blks) = jax.lax.scan(body, None, xs)
            n_steps = (hi - lo) // chunk
            data = (a_blks.reshape(n_steps, chunk, k, k),
                    b_blks.reshape(n_steps, chunk, k))
            specs = (
                pl.BlockSpec((1, chunk, k, k), lambda i: (i, 0, 0, 0)),
                pl.BlockSpec((1, chunk, k), lambda i: (i, 0, 0)),
            )
            return _run_segment_group(
                rows[lo:hi], data, specs, a_buf, b_buf, chunk=chunk,
                k=k, lane=lane, slot_fn=_flush_slot_fn,
                interpret=interpret, overlap=overlap, packed=packed,
            )
        return run

    groups = [group_thunk(lo, min(S, lo + g_slots))
              for lo in range(0, S, g_slots)]
    return _chain_groups(n_self, k, groups, packed=packed)


# ---------------------------------------------------------------------------
# VMEM-resident factor gather (the round-4 lever on the slot-gather wall)
# ---------------------------------------------------------------------------

# table-size budget for keeping the whole factor matrix VMEM-resident:
# 16 MB scoped VMEM minus the output block's double buffer and headroom
GATHER_VMEM_TABLE_BUDGET = 10 * 2**20


def gather_table_bytes(n_rows: int, k: int, bf16: bool) -> int:
    """Physical VMEM bytes for an (n_rows, k) factor table at TPU lane
    padding (minor dim padded UP to a multiple of 128, matching the
    padding gather_rows_pallas applies — max(128, k) would under-count
    e.g. k=192, which physically pads to 256)."""
    lane = _lane_for(k)
    return n_rows * lane * (2 if bf16 else 4)


def _gather_kernel_copy(idx_ref, table_ref, out_ref, *, rows_per_step,
                        group):
    """Row-copy variant: `group` dynamic (1,k) loads stacked into one
    tile-aligned store. The table ref is VMEM-resident (constant index
    map), so every load is a VMEM dynamic slice — no HBM traffic beyond
    the one-time table load and the output writes."""
    from jax.experimental import pallas as pl

    def body(g, _):
        base = g * group
        rows = [
            table_ref[pl.ds(idx_ref[0, 0, base + u], 1), :]
            for u in range(group)
        ]
        out_ref[pl.ds(base, group), :] = jnp.concatenate(rows, axis=0)
        return 0

    jax.lax.fori_loop(0, rows_per_step // group, body, 0)


def _gather_kernel_take(idx_ref, table_ref, out_ref, *, rows_per_step,
                        group):
    """jnp.take variant: materialize the VMEM table once per step and
    let Mosaic lower the vector gather (tpu dynamic-gather path where
    supported). Interpret-mode-validated; the on-hardware A/B against
    the copy variant is staged in eval/als_accum_bench.py (gather
    cells) and had not landed as of round 4 — keep in sync with
    ALSParams.gather's "auto" resolution in ops/als.py."""
    del group
    tbl = table_ref[:, :]
    rows = idx_ref[0, 0, :rows_per_step]
    out_ref[:, :] = jnp.take(tbl, rows, axis=0)


_GATHER_KERNELS = {"copy": _gather_kernel_copy, "take": _gather_kernel_take}


@functools.partial(
    jax.jit, static_argnames=("rows_per_step", "variant", "group",
                              "interpret"))
def gather_rows_pallas(table, idx, rows_per_step: int = 1024,
                       variant: str = "copy", group: int = 8,
                       interpret: bool | None = None):
    """Gather rows of a SMALL factor table with the table pinned in VMEM.

    table (N, k) f32/bf16, idx (M,) int32 -> (M, k) table[idx].

    Why this exists: XLA's gather emitter runs ~10x off HBM peak when
    the table is small enough to fit VMEM (eval/ALS_ROOFLINE.md /
    als_kernel_lab.py: a 20x cliff keyed on the 16 MB boundary, decided
    at codegen and unreachable from JAX — every padding trick fused
    away). At the ML-20M shape the users-half gathers the ITEM factor
    table (26,744 x 64 bf16 = 6.8 MB padded), squarely in the slow
    regime; this kernel makes the VMEM residency explicit instead of
    hoping for the emitter's fast path. Tables over
    GATHER_VMEM_TABLE_BUDGET stay on the XLA path (they already take
    the fast emitter).

    M must divide by rows_per_step (callers pad; slot layouts already
    quantize), and the idx values must be in-range (the ALS layouts
    guarantee < n plus a zero-filled sentinel row).
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    import math

    if interpret is None:
        interpret = jax.devices()[0].platform == "cpu"
    n, k = table.shape
    (m,) = idx.shape
    assert m % rows_per_step == 0, (m, rows_per_step)
    # the copy variant loops rows_per_step//group times — group must
    # divide rows_per_step or trailing rows are silently dropped (and a
    # group larger than the step would write nothing at all)
    group = math.gcd(group, rows_per_step)
    lane = _lane_for(k)   # 128 < k < 256 must pad to 256, not k itself
    tbl = _pad_lanes(table, lane)
    steps = m // rows_per_step
    out = pl.pallas_call(
        functools.partial(
            _GATHER_KERNELS[variant], rows_per_step=rows_per_step,
            group=group),
        grid=(steps,),
        in_specs=(
            # (1,1,R) SMEM: 1-d s32 operands tile T(1024) vs Mosaic's
            # T(128) (round-3 portability rule)
            pl.BlockSpec((1, 1, rows_per_step), lambda i: (i, 0, 0),
                         memory_space=pltpu.MemorySpace.SMEM),
            # whole table, constant index map -> fetched once, resident
            pl.BlockSpec((n, lane), lambda i: (0, 0)),
        ),
        out_specs=pl.BlockSpec((rows_per_step, lane), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, lane), table.dtype),
        interpret=interpret,
    )(idx.reshape(steps, 1, rows_per_step), tbl)
    return out[:, :k]


# ---------------------------------------------------------------------------
# round-6 streaming gather: double-buffered HBM->VMEM row DMA, any table size
# ---------------------------------------------------------------------------

def _gather_kernel_stream(idx_ref, table_ref, out_ref, buf, sem0, sem1,
                          *, rows_per_step, group):
    """Double-buffered streaming gather: the table stays in HBM (no
    VMEM-residency precondition — this is the variant that covers the
    ML-20M USERS table the pallas-copy/take kernels cannot) and rows
    are fetched with per-row async copies into a 2-slot VMEM staging
    buffer: while mini-group g's rows land in slot g%2 and store to the
    output block, mini-group g+1's copies are ALREADY in flight into
    the other slot — the prefetch the XLA gather emitter never issues
    (the ~10x-off-peak wall in eval/ALS_ROOFLINE.md). The output block
    is written sequentially, so the pipeline's write-back streams at
    peak, and the caller reshapes it straight into the (C, W, k) layout
    the blocks einsum consumes — no intermediate XLA copy (the 38 ms
    y-copy in the round-5 profile).

    Staging slots are selected by PARITY branches so every buffer/
    semaphore index except the table row is static (round-3 Mosaic
    rules); waits reconstruct their start's descriptor. All copies on
    one slot share one DMA semaphore — same-size (1, lane) rows, so
    sequential waits pair with completions regardless of order."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n_groups = rows_per_step // group
    sems = (sem0, sem1)

    def row_dma(slot: int, base, u):
        r = idx_ref[0, 0, base + u]
        return pltpu.make_async_copy(
            table_ref.at[pl.ds(r, 1), :],
            buf.at[pl.ds(slot * group + u, 1), :],
            sems[slot],
        )

    def start(slot: int, g):
        def body(u, _):
            row_dma(slot, g * group, u).start()
            return 0

        jax.lax.fori_loop(0, group, body, 0, unroll=False)

    def finish(slot: int, g):
        def body(u, _):
            row_dma(slot, g * group, u).wait()
            return 0

        jax.lax.fori_loop(0, group, body, 0, unroll=False)
        out_ref[pl.ds(g * group, group), :] = (
            buf[slot * group:(slot + 1) * group, :])

    def by_parity(g, fn):
        @pl.when(g % 2 == 0)
        def _even():
            fn(0, g)

        @pl.when(g % 2 != 0)
        def _odd():
            fn(1, g)

    start(0, 0)

    def body(g, _):
        @pl.when(g + 1 < n_groups)
        def _prefetch():
            by_parity(g + 1, start)

        by_parity(g, finish)
        return 0

    jax.lax.fori_loop(0, n_groups, body, 0, unroll=False)


@functools.partial(
    jax.jit, static_argnames=("rows_per_step", "group", "interpret"))
def gather_rows_stream(table, idx, rows_per_step: int = 512,
                       group: int = 32, interpret: bool | None = None):
    """Streaming gather of table rows with HBM->VMEM double buffering.

    table (N, k) f32/bf16 — ANY size, stays in HBM; idx (M,) int32 ->
    (M, k) table[idx]. M is padded internally to a rows_per_step
    multiple (sentinel index 0), so any M works; `group` (clamped to a
    divisor of rows_per_step) sets the prefetch depth — the copies of
    mini-group g+1 are in flight while g's rows store.

    This is ALSParams.gather="stream": unlike the VMEM-resident
    pallas-copy/take variants it has no table-size precondition, so it
    is the candidate for BOTH halves of the sweep (the users-half table
    is 4x over GATHER_VMEM_TABLE_BUDGET at the ML-20M shape). The
    on-hardware A/B lives in eval/als_accum_bench.py (stream cells);
    auto keeps the XLA gather until that A/B lands a win."""
    import math

    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if interpret is None:
        interpret = jax.devices()[0].platform == "cpu"
    n, k = table.shape
    (m,) = idx.shape
    # output blocks are (rows_per_step, lane): second-minor must stay a
    # multiple of 8 (round-3 Mosaic rule)
    rows_per_step = max(8, rows_per_step - rows_per_step % 8)
    group = math.gcd(group, rows_per_step)
    lane = _lane_for(k)
    tbl = _pad_lanes(table, lane)
    pad = -m % rows_per_step
    idx_p = (
        jnp.concatenate([idx, jnp.zeros((pad,), idx.dtype)]) if pad else idx
    )
    steps = (m + pad) // rows_per_step
    out = pl.pallas_call(
        functools.partial(_gather_kernel_stream,
                          rows_per_step=rows_per_step, group=group),
        grid=(steps,),
        in_specs=(
            pl.BlockSpec((1, 1, rows_per_step), lambda i: (i, 0, 0),
                         memory_space=pltpu.MemorySpace.SMEM),
            # the whole table as an HBM memref: rows are DMA'd on demand
            pl.BlockSpec(memory_space=pltpu.MemorySpace.HBM),
        ),
        out_specs=pl.BlockSpec((rows_per_step, lane), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m + pad, lane), table.dtype),
        scratch_shapes=[
            pltpu.VMEM((2 * group, lane), table.dtype),
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA,
        ],
        interpret=interpret,
    )(idx_p.reshape(steps, 1, rows_per_step), tbl)
    return out[:m, :k]


# ---------------------------------------------------------------------------
# round-6 lane-packed batched matvec: the CG half of the packed-A path
# ---------------------------------------------------------------------------

def _matvec_block_rows(k: int, cap: int = 256) -> int:
    """VMEM-budgeted row block for packed_block_matvec: the (B, k²) f32
    A block is double-buffered by the pallas pipeline, and the (k², k)
    reduction operand (resident, constant index map) costs k³·4 bytes
    (1 MB at k=64, 8 MB at k=128) of the 16 MB scoped budget — 2 MB per
    A buffer keeps the stack under it through k=128. Power of two, >= 8
    (second-minor rule)."""
    b = max(8, (2 * 2**20) // (k * k * 4))
    b = 1 << (b.bit_length() - 1)
    return min(cap, b)


def _packed_matvec_kernel(a_ref, x_ref, r_ref, o_ref, *, k):
    """o[b, i] = sum_j a[b, i*k+j] * x[b, j], no unpack to (B, k, k):
    x is lane-TILED k times (xt[b, i*k+j] = x[b, j] — a static lane
    concat, no relayout), multiplied elementwise against the packed
    rows, and the contiguous k-lane groups are summed with one MXU dot
    against a constant 0/1 selection matrix R (r_ref, R[m, i] =
    [m//k == i]). The selection dot spends k× the matvec's FLOPs, but
    the op is HBM-bound by A's packed bytes, which is the term the
    packing halves at k=64 — the on-chip A/B against the XLA reshape
    matvec is the als_kernel_lab.py packed cells."""
    x = x_ref[...]
    xt = jnp.concatenate([x] * k, axis=1)          # (B, k²)
    p = a_ref[...] * xt
    o_ref[...] = jax.lax.dot_general(
        p, r_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )


@functools.partial(
    jax.jit, static_argnames=("block_rows", "interpret"))
def packed_block_matvec(a_packed, x, block_rows: int = 256,
                        interpret: bool | None = None):
    """Batched block-diagonal matvec on LANE-PACKED A.

    a_packed (n, k²) f32 — row b is A_b flattened row-major; x (n, k)
    f32 -> (n, k) with out[b] = A_b @ x[b]. n must divide by block_rows
    (callers pad once OUTSIDE their CG loop — _solve_packed in
    ops/als.py — so no per-iteration pad traffic).

    Why this exists: the packed batched matvec is 6.1x faster than the
    lane-padded einsum in isolation (eval/als_kernel_lab.py), but
    composed through XLA the (n,k²)->(n,k,k) reshape before the dot is
    a real relayout paid per solve (eval/ALS_ROOFLINE.md). This kernel
    consumes the packed rows natively, so the packed form survives from
    the flush kernel through every CG iteration with no relayout —
    tests/test_als_pallas.py pins that property on the optimized HLO."""
    from jax.experimental import pallas as pl

    if interpret is None:
        interpret = jax.devices()[0].platform == "cpu"
    n, k2 = a_packed.shape
    k = x.shape[1]
    assert k * k == k2, (k, k2)
    block = min(block_rows, _matvec_block_rows(k))
    assert n % block == 0, (n, block)
    m_i = jnp.arange(k2, dtype=jnp.int32) // k
    r = (m_i[:, None] == jnp.arange(k, dtype=jnp.int32)[None, :]).astype(
        jnp.float32)
    return pl.pallas_call(
        functools.partial(_packed_matvec_kernel, k=k),
        grid=(n // block,),
        in_specs=(
            pl.BlockSpec((block, k2), lambda i: (i, 0)),
            pl.BlockSpec((block, k), lambda i: (i, 0)),
            # constant index map -> fetched once, resident across steps
            pl.BlockSpec((k2, k), lambda i: (0, 0)),
        ),
        out_specs=pl.BlockSpec((block, k), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, k), jnp.float32),
        interpret=interpret,
    )(a_packed, x, r)
