"""Attention kernels: Pallas flash attention + sequence-parallel variants.

The reference has no sequence models at all (SURVEY.md section 5
"Long-context / sequence parallelism: absent"), so this module is the
framework's net-new long-context capability, built TPU-first:

 * `flash_attention` — blockwise attention with online softmax as a Pallas
   TPU kernel: q blocks stream through VMEM, k/v live in VMEM per
   (batch, head) program, the (block_q, block_k) score tile hits the MXU,
   and softmax renormalization state (m, l) stays in registers/VMEM so the
   (S, S) score matrix is never materialized in HBM.
 * `ring_attention` — sequence parallelism over a mesh axis: each device
   holds a contiguous sequence shard of q/k/v; k/v shards rotate around the
   ring via `jax.lax.ppermute` (ICI neighbor exchange) while each device
   accumulates its q-shard's online softmax. Compute for step i overlaps
   the DMA of step i+1's shard (XLA pipelines the ppermute); memory per
   device is O(S/n), enabling sequences n x longer than one chip's HBM.
 * `ulysses_attention` — the all-to-all alternative: resharding
   (B, S/n, H, D) -> (B, S, H/n, D) with `lax.all_to_all`, full attention
   per head group, then the inverse all-to-all. Two collectives total;
   preferable when H >= n_seq and the mesh axis rides fast ICI.

All three compute the same math as `attention_reference` (tested against
it); masks are additive-big-negative with explicit zeroing so fully-masked
rows (causal prefixes) produce zeros, not NaNs.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl

NEG_INF = -1e30


def attention_reference(q, k, v, causal: bool = False, scale: float | None = None):
    """Plain softmax attention; the correctness oracle for the kernels.

    q: (B, Sq, H, D); k/v: (B, Sk, H, D) -> (B, Sq, H, D).
    """
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        sq, sk = q.shape[1], k.shape[1]
        mask = jnp.arange(sq)[:, None] >= jnp.arange(sk)[None, :]
        s = jnp.where(mask[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


# ---------------------------------------------------------------------------
# Pallas flash attention (single device)
# ---------------------------------------------------------------------------

def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
                  *, block_k: int, causal: bool, scale: float,
                  q_block_offset: bool, kv_len: int | None):
    """One (batch*head, q-block, kv-segment) program: k/v stream through
    VMEM one SEGMENT at a time (grid dim 2, innermost/sequential), and
    the online-softmax state (o, m, l) carries across segments in VMEM
    scratch — so total K/V length is HBM-bound, not VMEM-bound (the
    previous whole-K/V-resident design hit the 16 MB scoped limit at
    seq 32768). Within a segment, k blocks stream in `block_k` slices.
    kv_len masks right-padded key positions (None = no key padding)."""
    seg = pl.program_id(2)
    n_seg = pl.num_programs(2)
    q = q_ref[0].astype(jnp.float32) * scale          # (bq, d)
    bq, d = q.shape
    seg_len = k_ref.shape[1]
    nk = seg_len // block_k
    seg_off = seg * seg_len

    @pl.when(seg == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_pos = jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)
    if q_block_offset:
        q_pos = q_pos + pl.program_id(1) * bq

    def body(j, carry):
        o, m, l = carry
        k_blk = k_ref[0, pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        v_blk = v_ref[0, pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        s = q @ k_blk.T                                # (bq, bk) on the MXU
        keep = None
        k_pos = (
            jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
            + seg_off + j * block_k
        )
        if causal:
            keep = q_pos >= k_pos                      # (bq, bk)
        if kv_len is not None:
            pad_keep = k_pos < kv_len
            keep = pad_keep if keep is None else keep & pad_keep
        if keep is not None:
            s = jnp.where(keep, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        if keep is not None:
            p = jnp.where(keep, p, 0.0)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        o_new = o * alpha + p @ v_blk
        return o_new, m_new, l_new

    if causal and q_block_offset:
        # skip k blocks entirely above the diagonal: this q block's highest
        # position is (pid+1)*bq - 1; blocks of THIS SEGMENT starting past
        # it are fully masked (a segment wholly above gets hi <= 0 and the
        # loop body never runs)
        q_hi = (pl.program_id(1) + 1) * bq
        hi = jnp.clip((q_hi - seg_off + block_k - 1) // block_k, 0, nk)
    else:
        hi = nk
    o, m, l = jax.lax.fori_loop(
        0, hi, body, (acc_ref[...], m_ref[...], l_ref[...]))
    acc_ref[...] = o
    m_ref[...] = m
    l_ref[...] = l

    @pl.when(seg == n_seg - 1)
    def _emit():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                    ).astype(o_ref.dtype)


def _pad_len(n: int, block: int) -> int:
    return (block - n % block) % block


def flash_attention(
    q, k, v,
    causal: bool = False,
    scale: float | None = None,
    block_q: int = 256,
    block_k: int = 512,
    max_seg_bytes: int = 2 * 2**20,
    interpret: bool | None = None,
):
    """Blockwise (flash) attention as a Pallas TPU kernel.

    q: (B, Sq, H, D); k/v: (B, Sk, H, D) -> (B, Sq, H, D). Sequences are
    padded to the block size internally; padded key positions are excluded
    in-kernel via a key-length mask (applied only when padding exists, for
    both the causal and non-causal paths). Causal programs skip k blocks
    entirely above the diagonal. `interpret=True` runs the kernel in
    interpreter mode (used on CPU in tests; auto-detected when None).

    Default blocks 256x512, tuned on v5e at seq 8192 (b4 h8 d64, causal,
    bf16): 128x128 ran at 0.0262 s — 2.2x SLOWER than XLA's naive
    attention — while 256x512 runs 0.0057 s, 2.1x faster than naive;
    512x1024 ties it and 1024x1024 fails to compile. The inner k-loop's
    per-iteration overhead dominates at small blocks (a round-3
    reading under an older JAX; no cell runs this kernel: ROADMAP D17).
    """
    from jax.experimental import pallas as pl

    b, sq, h, d = q.shape
    sk = k.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    if interpret is None:
        interpret = jax.devices()[0].platform == "cpu"

    block_q = min(block_q, max(sq, 8))
    block_k = min(block_k, max(sk, 8))
    pad_q, pad_k = _pad_len(sq, block_q), _pad_len(sk, block_k)
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    sqp, skp = sq + pad_q, sk + pad_k

    # (B, S, H, D) -> (B*H, S, D): one program per (batch, head, q block)
    def bhsd(x):
        return x.transpose(0, 2, 1, 3).reshape(-1, x.shape[1], d)

    qt, kt, vt = bhsd(q), bhsd(k), bhsd(v)
    nq = sqp // block_q
    kv_len_arg = sk if pad_k else None

    # VMEM-budget the k/v residency: one SEGMENT (2 arrays, double-
    # buffered by the pipeline) stays under ~4 MB; the online-softmax
    # scratch carries across segments, so sequence length is unbounded
    # by VMEM (32k+ works single-chip; the previous whole-K/V design
    # overflowed the 16 MB scoped limit there)
    # max_seg_bytes is a knob mostly for tests (forcing n_seg > 1 at
    # small shapes); the default keeps one double-buffered k/v segment
    # pair under ~8 MB of the 16 MB scoped VMEM
    max_seg = max(block_k, max_seg_bytes // (2 * d * kt.dtype.itemsize))
    seg_len = min(skp, max_seg - max_seg % block_k)
    pad_seg = _pad_len(skp, seg_len)
    if pad_seg:
        # pad to a whole number of segments; in-kernel kv_len masking
        # already drops the padded keys
        kt = jnp.pad(kt, ((0, 0), (0, pad_seg), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, pad_seg), (0, 0)))
        if kv_len_arg is None:
            kv_len_arg = sk
    n_seg = (skp + pad_seg) // seg_len

    kernel = partial(
        _flash_kernel, block_k=block_k, causal=causal, scale=scale,
        q_block_offset=True, kv_len=kv_len_arg,
    )
    from jax.experimental.pallas import tpu as pltpu

    out = pl.pallas_call(
        kernel,
        grid=(qt.shape[0], nq, n_seg),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, i, sg: (bh, i, 0)),
            pl.BlockSpec((1, seg_len, d), lambda bh, i, sg: (bh, sg, 0)),
            pl.BlockSpec((1, seg_len, d), lambda bh, i, sg: (bh, sg, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda bh, i, sg: (bh, i, 0)),
        out_shape=jax.ShapeDtypeStruct(qt.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        interpret=interpret,
    )(qt, kt, vt)
    out = out.reshape(b, h, sqp, d).transpose(0, 2, 1, 3)
    return out[:, :sq]


# ---------------------------------------------------------------------------
# Chunked (memory-efficient) attention — the DIFFERENTIABLE long-context
# path for single-device training
# ---------------------------------------------------------------------------

def chunked_attention(
    q, k, v,
    causal: bool = False,
    scale: float | None = None,
    chunk: int = 1024,
):
    """Online-softmax attention as a lax.scan over key/value chunks —
    pure XLA, so it is reverse-differentiable (the Pallas flash kernel
    has no backward and stays the serving/forward-only fast path). Peak
    logits memory is O(B*H*Sq*chunk) instead of O(B*H*Sq*Sk), and
    jax.checkpoint on the per-chunk stats recomputes them in the
    backward pass instead of storing one residual per chunk — the same
    memory shape that lets ring_attention train across devices, applied
    within one device. q: (B, Sq, H, D); k/v: (B, Sk, H, D)."""
    b, sq, h, d = q.shape
    sk = k.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    chunk = min(chunk, sk)
    pad = _pad_len(sk, chunk)
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    n_ch = (sk + pad) // chunk
    ks = k.reshape(b, n_ch, chunk, h, d).transpose(1, 0, 2, 3, 4)
    vs = v.reshape(b, n_ch, chunk, h, d).transpose(1, 0, 2, 3, 4)
    offs = jnp.arange(n_ch) * chunk

    @jax.checkpoint
    def stats(k_c, v_c, off):
        s_ = jnp.einsum("bqhd,bkhd->bhqk", q, k_c) * scale
        q_pos = jnp.arange(sq)
        k_pos = off + jnp.arange(chunk)
        keep = k_pos[None, :] < sk                  # padded keys drop
        if causal:
            keep = keep & (q_pos[:, None] >= k_pos[None, :])
        s_ = jnp.where(keep[None, None], s_, NEG_INF)
        m_ = jnp.max(s_, axis=-1, keepdims=True)
        p_ = jnp.where(keep[None, None], jnp.exp(s_ - m_), 0.0)
        l_ = jnp.sum(p_, axis=-1, keepdims=True)
        o_ = jnp.einsum("bhqk,bkhd->bqhd", p_, v_c)
        return o_, m_, l_

    def step(carry, xs):
        o, m, l = carry
        k_c, v_c, off = xs
        o_i, m_i, l_i = stats(k_c, v_c, off)
        m_new = jnp.maximum(m, m_i)
        a_prev = jnp.exp(m - m_new)
        a_i = jnp.exp(m_i - m_new)
        l_new = l * a_prev + l_i * a_i
        o_new = (o * a_prev.transpose(0, 2, 1, 3)
                 + o_i * a_i.transpose(0, 2, 1, 3))
        return (o_new, m_new, l_new), None

    o0 = jnp.zeros((b, sq, h, d), jnp.float32)
    m0 = jnp.full((b, h, sq, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, sq, 1), jnp.float32)
    (o, m, l), _ = jax.lax.scan(step, (o0, m0, l0), (ks, vs, offs))
    o = o / jnp.maximum(l.transpose(0, 2, 1, 3), 1e-30)
    return o.astype(q.dtype)


# ---------------------------------------------------------------------------
# Trainable flash attention: Pallas forward + chunked-XLA backward
# ---------------------------------------------------------------------------

@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_attention_trainable(q, k, v, causal: bool = False,
                              scale: float | None = None,
                              chunk: int = 1024):
    """flash_attention with gradients: the forward pass runs the Pallas
    kernel (1.7-1.9x the naive attention at 2k/8k on v5e, length
    HBM-bound), and the backward differentiates chunked_attention at the
    same primal point — mathematically the same function, so the
    cotangents are exact up to the forward kernels' mutual rounding
    (pinned by tests). This sidesteps hand-writing a flash backward
    kernel while keeping training forward passes on the fast path;
    memory stays O(S*chunk) in both directions."""
    return flash_attention(q, k, v, causal=causal, scale=scale)


def _fat_fwd(q, k, v, causal, scale, chunk):
    return flash_attention(q, k, v, causal=causal, scale=scale), (q, k, v)


def _fat_bwd(causal, scale, chunk, res, g):
    q, k, v = res
    _, vjp = jax.vjp(
        lambda q_, k_, v_: chunked_attention(
            q_, k_, v_, causal=causal, scale=scale, chunk=chunk
        ),
        q, k, v,
    )
    return vjp(g)


flash_attention_trainable.defvjp(_fat_fwd, _fat_bwd)


# ---------------------------------------------------------------------------
# Ring attention (sequence parallelism over a mesh axis)
# ---------------------------------------------------------------------------

def _block_attn_stats(q, k, v, scale, q_offset, k_offset, causal):
    """Un-normalized blockwise attention + softmax stats for one k/v shard.

    q: (B, Sq, H, D) local queries at global offset q_offset;
    k/v: (B, Sk, H, D) the currently-held shard at global offset k_offset.
    Returns (o, m, l): o = sum_j exp(s_j - m) v_j  (B, Sq, H, D),
    m = rowmax (B, H, Sq, 1), l = sum exp (B, H, Sq, 1).
    """
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        q_pos = q_offset + jnp.arange(q.shape[1])
        k_pos = k_offset + jnp.arange(k.shape[1])
        keep = q_pos[:, None] >= k_pos[None, :]
        s = jnp.where(keep[None, None], s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)                  # (B,H,Sq,1)
    m_safe = jnp.maximum(m, NEG_INF)  # rows fully masked stay at NEG_INF
    p = jnp.exp(s - m_safe)
    if causal:
        p = jnp.where(keep[None, None], p, 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v)
    return o, m_safe, l


def ring_attention(
    q, k, v,
    axis_name: str,
    causal: bool = False,
    scale: float | None = None,
):
    """Sequence-parallel attention; call INSIDE shard_map/pjit with q/k/v
    sharded on their sequence axis over `axis_name`.

    Each device starts with its own k/v shard and rotates the shards one
    neighbor per step with `lax.ppermute` (n-1 ICI hops total), folding each
    visiting shard into its q-shard's online softmax (same m/l accumulation
    as the flash kernel, across devices instead of VMEM blocks). The k/v
    rotation for step i+1 overlaps step i's matmuls — XLA schedules the
    ppermute DMA concurrently with compute on TPU.
    """
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    # axis size is static mesh structure — safe to use for Python loops
    n = jax.lax.axis_size(axis_name)
    my = jax.lax.axis_index(axis_name)
    s_local = q.shape[1]
    perm = [(i, (i + 1) % n) for i in range(n)]

    q_offset = my * s_local
    # the shard held at step i originated at device (my - i) % n
    k_offsets = jnp.mod(my - jnp.arange(n), n) * s_local

    def step(carry, k_offset):
        o, m, l, k_cur, v_cur = carry
        o_i, m_i, l_i = _block_attn_stats(
            q, k_cur, v_cur, scale, q_offset, k_offset, causal
        )
        m_new = jnp.maximum(m, m_i)
        a_prev = jnp.exp(m - m_new)
        a_i = jnp.exp(m_i - m_new)
        l_new = l * a_prev + l_i * a_i
        o_new = (
            o * a_prev.transpose(0, 2, 1, 3)
            + o_i * a_i.transpose(0, 2, 1, 3)
        )
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        return (o_new, m_new, l_new, k_nxt, v_nxt), None

    b, sq, h, d = q.shape
    o0 = jnp.zeros((b, sq, h, d), jnp.float32)
    m0 = jnp.full((b, h, sq, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, sq, 1), jnp.float32)
    # scan (not fori_loop) so the whole ring is reverse-differentiable —
    # the sequence model trains through this
    (o, m, l, _, _), _ = jax.lax.scan(step, (o0, m0, l0, k, v), k_offsets)
    o = o / jnp.maximum(l.transpose(0, 2, 1, 3), 1e-30)
    return o.astype(q.dtype)


def ring_attention_sharded(q, k, v, mesh, axis_name: str,
                           causal: bool = False):
    """Host-facing wrapper: shard (B, S, H, D) on the sequence axis over
    `axis_name` and run ring_attention under shard_map. Batch stays
    replicated across the seq axis here; compose with a data axis via the
    caller's outer shard_map/pjit (see models/sequence.py)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    spec = P(None, axis_name, None, None)

    @partial(
        jax.shard_map, mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    def run(ql, kl, vl):
        return ring_attention(ql, kl, vl, axis_name, causal=causal)

    sharding = NamedSharding(mesh, spec)
    return run(
        jax.device_put(q, sharding),
        jax.device_put(k, sharding),
        jax.device_put(v, sharding),
    )


# ---------------------------------------------------------------------------
# Ulysses-style all-to-all sequence parallelism
# ---------------------------------------------------------------------------

def ulysses_attention(
    q, k, v,
    axis_name: str,
    causal: bool = False,
    scale: float | None = None,
):
    """All-to-all sequence parallelism; call INSIDE shard_map with q/k/v
    sequence-sharded over `axis_name` and H divisible by the axis size.

    all_to_all flips the sharded dim from sequence to heads (each device
    gets the FULL sequence for H/n heads), full attention runs locally,
    and a second all_to_all flips back. Two collectives per layer vs the
    ring's n-1 hops — the better trade when heads are plentiful.
    """
    n = jax.lax.axis_size(axis_name)  # noqa: F841 — documents the contract
    # (B, S/n, H, D) -> (B, S, H/n, D)
    qh = jax.lax.all_to_all(q, axis_name, split_axis=2, concat_axis=1,
                            tiled=True)
    kh = jax.lax.all_to_all(k, axis_name, split_axis=2, concat_axis=1,
                            tiled=True)
    vh = jax.lax.all_to_all(v, axis_name, split_axis=2, concat_axis=1,
                            tiled=True)
    o = attention_reference(qh, kh, vh, causal=causal, scale=scale)
    # (B, S, H/n, D) -> (B, S/n, H, D)
    return jax.lax.all_to_all(o, axis_name, split_axis=1, concat_axis=2,
                              tiled=True).astype(q.dtype)


# ---------------------------------------------------------------------------
# Banded flash attention: grouped-query heads, causal window, one Pallas
# kernel forward and one backward (the language-model path of
# models/seq_blocks.py)
# ---------------------------------------------------------------------------
#
# Layout (B, H, S, D). Query head h reads key-value head h // (Hq // Hkv)
# through the block index map: no repeated copy of k / v exists. The key
# blocks a query block needs (`j <= i`, and `i - j < window` on a window
# layer) are listed on the host, once per shape, as a table of (query
# block, key block) pairs; the grid walks the table, so a block outside
# the band is never fetched. The forward kernel walks the table by query
# block (a block's softmax statistics and output stay in VMEM while its
# key blocks go by); the backward kernel walks it by key block, dk and dv
# of the block in VMEM and dq of the whole head beside them, so a block
# pair's scores and probabilities are made once for all three.
#
# Both kernels make a pair's scores transposed, (keys, queries) = k.q^T:
# a query's statistics are then (1, bq) rows, lane-dense, and the max and
# the sum over keys go down the sublanes. The forward kernel (PR 46) folds
# a pair in tiles of 128 keys, each an online-softmax step, and asks for
# every tile's scores before it folds the first: the compiler then runs a
# tile's softmax beside its neighbours' products, where one (bk, bq) tile
# ran product, softmax, product one after another. The mask is a path of
# its own: scores that pass through a `cond` are copied through VMEM (a
# third of the old kernel's instructions; the backward kernel still pays
# it). The accumulator is o^T, (D, bq) += v^T.prob, scaled by the alpha
# row and transposed once a query block; as (bq, D) += prob^T.v with alpha
# turned to a column it read 14 % / 15 % / 5 % longer a call at (2, 32 / 4,
# 8192, 128) full / window 1,024 / (2, 20 / 20, 8192, 256): 19.57 / 8.19 /
# 15.76 ms against 17.21 / 7.11 / 14.97 (one whole tile, 512 x 512 blocks,
# a v5e, `eval/attention_fwd_bench.py`), so it left the tree. lse leaves
# the kernel as a row, (B, Hq, 1, S). Blocks, ms a call at those three
# shapes (the tree before: 19.52 / 8.28 / 17.93): 512 x 512 10.51 / 4.50 /
# 10.48, 1,024 x 512 9.87 / 4.94 / 10.35, 512 x 1,024 9.39 / 5.04 / 10.06,
# 1,024 x 1,024 9.17 / - / 9.91 (a window layer 4 % under its 512 x 512
# in an earlier form of the kernel), 2,048 x 1,024 9.45 / 5.76 / 10.57,
# 2,048 x 2,048 refused (VMEM): a full layer's forward call takes 1,024
# square at both widths, a window layer's the caller's (`forward_blocks`).

_FIRST, _LAST, _EDGE = 1, 2, 4
_KEY_TILE = 128         # keys of a block pair the forward kernel folds at once
FWD_FULL_BLOCK = 1024   # the forward kernel's block in a full layer
# `checkpoint_name`s of the forward's two residuals that only the kernel
# can make: o (B, Hq, S, D) and the rows' log-sum-exp (B, Hq, S) float32
KEPT_RESIDUALS = ("banded_attention_o", "banded_attention_lse")


def band_pairs(seq_len: int, block_q: int, block_k: int,
               window: int | None, by_key: bool = False):
    """The (query block, key block) pairs of the causal band, as int32
    arrays (qi, kj, flags): sorted by query block (by key block with
    `by_key`), a run of one query (key) block marked _FIRST / _LAST, and
    _EDGE where the block holds a masked score."""
    import numpy as np

    n_q, n_k = -(-seq_len // block_q), -(-seq_len // block_k)
    pairs = []
    for i in range(n_q):
        r_lo, r_hi = i * block_q, (i + 1) * block_q - 1
        k_lo = 0 if window is None else max(0, r_lo - (window - 1))
        for j in range(k_lo // block_k, min(r_hi // block_k, n_k - 1) + 1):
            c_lo, c_hi = j * block_k, (j + 1) * block_k - 1
            whole = c_hi <= r_lo and (window is None
                                      or c_lo >= r_hi - (window - 1))
            pairs.append((i, j, 0 if whole else _EDGE))
    major = 1 if by_key else 0
    pairs.sort(key=lambda p: (p[major], p[1 - major]))
    qi = np.array([p[0] for p in pairs], np.int32)
    kj = np.array([p[1] for p in pairs], np.int32)
    fl = np.array([p[2] for p in pairs], np.int32)
    run = kj if by_key else qi
    fl[np.r_[True, run[1:] != run[:-1]]] |= _FIRST
    fl[np.r_[run[1:] != run[:-1], True]] |= _LAST
    return qi, kj, fl


def band_blocks(seq_len: int, block_q: int, block_k: int,
                window: int | None) -> int:
    """Key blocks in the causal band, counted without the table: what the
    kernels' visited-block counter is held against."""
    total = 0
    for i in range(-(-seq_len // block_q)):
        hi = min(((i + 1) * block_q - 1) // block_k,
                 -(-seq_len // block_k) - 1)
        lo = 0 if window is None else max(
            0, i * block_q - (window - 1)) // block_k
        total += hi - lo + 1
    return total


def _band_scores(q, k, qi, kj, edge, *, scale, window, by_key=False):
    """(bq, bk) float32 scores of one block pair, -inf where masked;
    with `by_key` their transpose, (bk, bq), as the product k.q^T. `edge`
    says whether the pair holds a masked score: a traced flag (the mask
    under a `cond` the scores pass through) or a Python bool."""
    lhs, rhs = (k, q) if by_key else (q, k)
    s = jax.lax.dot_general(lhs, rhs, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    q_axis = int(by_key)

    def masked(s):
        rows = qi * q.shape[0] + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, q_axis)
        cols = kj * k.shape[0] + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1 - q_axis)
        keep = cols <= rows
        if window is not None:
            keep = keep & (rows - cols < window)
        return jnp.where(keep, s, -jnp.inf)

    if isinstance(edge, bool):      # a path of its own either way
        return masked(s) if edge else s
    return jax.lax.cond(edge, masked, lambda s: s, s)


def _band_fwd_kernel(qi_ref, kj_ref, fl_ref, q_ref, k_ref, v_ref,
                     o_ref, lse_ref, m_scr, l_scr, acc_scr,
                     *, scale, window):
    """One block pair of one (batch, query head): the scores transposed,
    (keys, queries), in tiles of `_KEY_TILE` keys; a query's running max,
    sum and log-sum-exp are (1, bq) rows, and the accumulator is o^T, (D,
    bq), transposed once a query block. Every tile's scores are asked
    for before the first is folded in, so a tile's softmax runs beside
    the products of its neighbours; the masked and the whole pair are two
    paths, so no tile crosses a `cond`."""
    p = pl.program_id(2)
    fl = fl_ref[p]
    block_k = k_ref.shape[2]
    tile = _KEY_TILE if block_k % _KEY_TILE == 0 else block_k
    n_tiles = block_k // tile

    @pl.when((fl & _FIRST) != 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def pair(masked):
        q = q_ref[0, 0]
        v_t = v_ref[0, 0].T                          # (D, bk)
        scores = [
            _band_scores(q, k_ref[0, 0, pl.ds(j * tile, tile), :],
                         qi_ref[p], kj_ref[p] * n_tiles + j, masked,
                         scale=scale, window=window, by_key=True)
            for j in range(n_tiles)]
        for j, s in enumerate(scores):
            m_prev = m_scr[...]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
            prob = jnp.exp(s - m_new)                # masked: exp(-inf) = 0
            alpha = jnp.exp(m_prev - m_new)
            l_scr[...] = alpha * l_scr[...] + jnp.sum(prob, axis=0,
                                                      keepdims=True)
            m_scr[...] = m_new
            acc_scr[...] = alpha * acc_scr[...] + jnp.dot(
                v_t[:, j * tile:(j + 1) * tile], prob.astype(v_t.dtype),
                preferred_element_type=jnp.float32)

    jax.lax.cond((fl & _EDGE) != 0, partial(pair, True),
                 partial(pair, False))

    @pl.when((fl & _LAST) != 0)
    def _emit():
        l = l_scr[...]
        o_ref[0, 0] = (acc_scr[...] / l).T.astype(o_ref.dtype)
        lse_ref[0, 0] = m_scr[...] + jnp.log(l)


def _band_bwd_kernel(qi_ref, kj_ref, fl_ref, q_ref, k_ref, v_ref, do_ref,
                     row_ref, dq_ref, dk_ref, dv_ref, dq_scr, dk_scr, dv_scr,
                     *, scale, window):
    """The whole backward pass of one (batch, query head): the band by
    key block, a block pair's scores, probabilities and score gradient
    made once, and made transposed, (bk, bq): dv += P^T.dO and dk +=
    dS^T.Q then take them as they are, into the key block's scratches,
    and dq's product alone transposes an operand. The head's whole dq
    stays in `dq_scr`, each pair adding dS.K to its query block's rows:
    the table axis is sequential, and a query block's pairs come in
    ascending key order."""
    p = pl.program_id(2)
    fl = fl_ref[p]
    qi = qi_ref[p]

    @pl.when(p == 0)
    def _init_head():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    @pl.when((fl & _FIRST) != 0)
    def _init_key_block():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    q, k, do = q_ref[0, 0], k_ref[0, 0], do_ref[0, 0]
    s = _band_scores(q, k, qi, kj_ref[p], (fl & _EDGE) != 0,
                     scale=scale, window=window, by_key=True)
    row = row_ref[0, 0]            # (2, bq): the rows' log-sum-exp, delta
    prob = jnp.exp(s - row[0:1])
    dprob = jax.lax.dot_general(v_ref[0, 0], do, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
    ds = (prob * (dprob - row[1:2]) * scale).astype(q.dtype)
    dv_scr[...] += jnp.dot(prob.astype(do.dtype), do,
                           preferred_element_type=jnp.float32)
    dk_scr[...] += jnp.dot(ds, q, preferred_element_type=jnp.float32)
    block_q = q.shape[0]
    rows = pl.ds(pl.multiple_of(qi * block_q, block_q), block_q)
    dq_scr[rows, :] += jax.lax.dot_general(
        ds, k, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when((fl & _LAST) != 0)
    def _emit_key_block():
        dk_ref[0, 0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[...].astype(dv_ref.dtype)

    @pl.when(p == pl.num_programs(2) - 1)
    def _emit_head():
        dq_ref[0, 0] = dq_scr[...].astype(dq_ref.dtype)


# what the backward kernel may ask of the chip's 128 MiB of VMEM
BWD_VMEM_BUDGET = 96 << 20


def bwd_vmem_bytes(seq: int, block_q: int, block_k: int, head_dim: int,
                   itemsize: int) -> int:
    """VMEM `flash_attention_bwd` asks for (`vmem_limit_bytes`): its
    blocks twice (the pipeline's two buffers), dq of the whole head among
    them and dk / dv as float32; the three accumulators; room for a block
    pair's scores, probabilities, their gradients and the rounded copies
    of two; 2 MiB for the compiler's own."""
    blocks = ((2 * block_q + 2 * block_k + seq) * head_dim * itemsize
              + 8 * block_q * 4 + 2 * block_k * head_dim * 4)
    scratch = (seq + 2 * block_k) * head_dim * 4
    return 2 * blocks + scratch + 6 * block_q * block_k * 4 + (2 << 20)


def _band_call(kernel, name, table, args, kinds, outs, scratch, *,
               batch, heads, group, block_q, block_k, head_dim, interpret,
               vmem_limit_bytes=None):
    """One pallas_call over (batch, query head, table entry). `kinds`
    says, per tensor argument, which block it is: "q" (query head, block
    qi[p]), "k" (key-value head h // group, block kj[p]), "lse" and "row"
    (one and two row statistics of the query block, (1 | 2, block_q) of
    (B, Hq, 1 | 2, S)); `outs` likewise, with the out dtype: "kq" (a key block a
    QUERY head) and "head" (the query head's whole sequence, written back
    when the head is done)."""
    from jax.experimental.pallas import tpu as pltpu

    seq = args[0].shape[2]
    rows = {"lse": 1, "row": 2}

    def spec(kind):
        if kind == "k":
            return pl.BlockSpec(
                (1, 1, block_k, head_dim),
                lambda b, h, p, qi, kj, fl: (b, h // group, kj[p], 0))
        if kind == "kq":
            return pl.BlockSpec(
                (1, 1, block_k, head_dim),
                lambda b, h, p, qi, kj, fl: (b, h, kj[p], 0))
        if kind == "head":
            return pl.BlockSpec((1, 1, seq, head_dim),
                                lambda b, h, p, qi, kj, fl: (b, h, 0, 0))
        if kind in rows:
            return pl.BlockSpec((1, 1, rows[kind], block_q),
                                lambda b, h, p, qi, kj, fl: (b, h, 0, qi[p]))
        return pl.BlockSpec((1, 1, block_q, head_dim),
                            lambda b, h, p, qi, kj, fl: (b, h, qi[p], 0))

    def shape(kind, dtype):
        if kind in rows:
            return jax.ShapeDtypeStruct((batch, heads, rows[kind], seq),
                                        dtype)
        return jax.ShapeDtypeStruct((batch, heads, seq, head_dim), dtype)

    return pl.pallas_call(
        kernel, name=name,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(batch, heads, len(table[0])),
            in_specs=[spec(k) for k in kinds],
            out_specs=[spec(k) for k, _ in outs],
            scratch_shapes=scratch),
        out_shape=[shape(k, dt) for k, dt in outs],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=vmem_limit_bytes),
        interpret=interpret,
    )(*(jnp.asarray(t) for t in table), *args)


def _band_setup(q_shape, hkv, seq_len, block_q, block_k, interpret,
                itemsize):
    """The static arguments of `_band_call`, the padding the sequence
    needs and the VMEM the backward kernel will ask for, for (B, Hq, .,
    D) queries of `seq_len` real positions, `itemsize` bytes a number."""
    b, hq, _, d = q_shape
    if hq % hkv:
        raise ValueError(f"{hq} query heads do not group over {hkv} "
                         "key-value heads")
    if interpret is None:
        interpret = jax.devices()[0].platform == "cpu"
    block = max(block_q, block_k)
    if block % min(block_q, block_k):
        raise ValueError("block_q and block_k must divide one another")
    pad = _pad_len(seq_len, block)
    vmem = bwd_vmem_bytes(seq_len + pad, block_q, block_k, d, itemsize)
    if vmem > BWD_VMEM_BUDGET:
        raise ValueError(
            f"the backward kernel keeps a head's dq in VMEM: {vmem} bytes "
            f"for {seq_len + pad} positions {d} wide, over the budget of "
            f"{BWD_VMEM_BUDGET}")
    return dict(batch=b, heads=hq, group=hq // hkv, block_q=block_q,
                block_k=block_k, head_dim=d, interpret=interpret), pad, vmem


def _pad_seq(x, pad):
    return jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0))) if pad else x


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def banded_flash_attention(q, k, v, window: int | None = None,
                           scale: float | None = None,
                           block_q: int = 512, block_k: int = 512,
                           interpret: bool | None = None):
    """Causal attention over grouped-query heads, optionally within a
    window (`i - j < window`): one Pallas kernel forward
    (`flash_attention_fwd`) and one backward (`flash_attention_bwd`: dq,
    dk and dv from one walk of the band, five products a block pair; a
    head's dq stays in VMEM, so a sequence whose dq does not fit
    `BWD_VMEM_BUDGET` is refused).

    q: (B, Hq, S, D); k, v: (B, Hkv, S, D), Hq a multiple of Hkv; ->
    (B, Hq, S, D). The sequence is padded to the block internally (a
    padded key lies after every real query, so causality masks it)."""
    return _banded_fwd(q, k, v, window, scale, block_q, block_k,
                       interpret)[0]


def forward_blocks(padded: int, block_q: int, block_k: int,
                   window: int | None):
    """The forward kernel's blocks. A full layer's are `FWD_FULL_BLOCK`
    square where the padded sequence is a multiple of it and the
    caller's are no larger (a step of the grid costs the kernel ~0.4 us
    beside its pair: four times fewer of them read 20 % shorter at 128
    wide and 14 % at 256, PERF.md PR 46); a window layer's, and the
    backward kernel's always, are the caller's."""
    if (window is None and padded % FWD_FULL_BLOCK == 0
            and max(block_q, block_k) <= FWD_FULL_BLOCK):
        return FWD_FULL_BLOCK, FWD_FULL_BLOCK
    return block_q, block_k


@partial(jax.jit, static_argnums=(3, 4, 5, 6, 7), inline=True)
def _forward_call(qp, kp, vp, window, scale, block_q, block_k, interpret):
    """`flash_attention_fwd` over padded operands: o, and lse as (B, Hq,
    1, S). Jitted and inlined into its caller: a step's layers of one
    shape then share one traced kernel and one lowering of it (its body
    is 2 x block_k / 128 tile bodies long: a looped stack would trace and
    lower it sixteen times, seconds of every process's set-up)."""
    from jax.experimental.pallas import tpu as pltpu

    b, hq, sp, d = qp.shape
    return _band_call(
        partial(_band_fwd_kernel, scale=scale, window=window),
        "flash_attention_fwd", band_pairs(sp, block_q, block_k, window),
        (qp, kp, vp), ("q", "k", "k"),
        (("q", qp.dtype), ("lse", jnp.float32)),
        [pltpu.VMEM((1, block_q), jnp.float32),
         pltpu.VMEM((1, block_q), jnp.float32),
         pltpu.VMEM((d, block_q), jnp.float32)],
        batch=b, heads=hq, group=hq // kp.shape[1], block_q=block_q,
        block_k=block_k, head_dim=d, interpret=interpret)


def _banded_fwd(q, k, v, window, scale, block_q, block_k, interpret):
    s, d = q.shape[2], q.shape[3]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    block_q, block_k = min(block_q, s), min(block_k, s)
    dims, pad, _ = _band_setup(q.shape, k.shape[1], s, block_q, block_k,
                               interpret, q.dtype.itemsize)
    qp, kp, vp = _pad_seq(q, pad), _pad_seq(k, pad), _pad_seq(v, pad)
    o, lse = _forward_call(
        qp, kp, vp, window, scale,
        *forward_blocks(s + pad, block_q, block_k, window),
        dims["interpret"])
    # what only the kernel can make of a row, under the names a
    # `jax.checkpoint` policy keeps them by: o as the kernel wrote it, and
    # the rows' log-sum-exp, (B, Hq, S) float32 as the kernel's one row
    lse = lse[:, :, 0]
    o = checkpoint_name(o, KEPT_RESIDUALS[0])
    lse = checkpoint_name(lse, KEPT_RESIDUALS[1])
    return o[:, :, :s], (qp, kp, vp, o, lse)


def _banded_bwd(window, scale, block_q, block_k, interpret, res, g):
    from jax.experimental.pallas import tpu as pltpu

    qp, kp, vp, o, lse = res
    sp, d = qp.shape[2], qp.shape[3]
    s = g.shape[2]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    block_q, block_k = min(block_q, s), min(block_k, s)
    dims, pad, vmem = _band_setup(qp.shape, kp.shape[1], s, block_q,
                                  block_k, interpret, qp.dtype.itemsize)
    do = _pad_seq(g.astype(qp.dtype), pad)
    # a row's two statistics, made once a row: (B, Hq, 2, S) float32
    delta = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32), axis=-1)
    group = dims["group"]
    # a key-value head's gradient is the sum over the query heads it
    # serves: float32 out of the kernel where there is a sum to make
    kv_dtype = kp.dtype if group == 1 else jnp.float32
    dq, dk, dv = _band_call(
        partial(_band_bwd_kernel, scale=scale, window=window),
        "flash_attention_bwd",
        band_pairs(sp, block_q, block_k, window, by_key=True),
        (qp, kp, vp, do, jnp.stack([lse, delta], axis=2)),
        ("q", "k", "k", "q", "row"),
        (("head", qp.dtype), ("kq", kv_dtype), ("kq", kv_dtype)),
        [pltpu.VMEM((sp, d), jnp.float32),
         pltpu.VMEM((block_k, d), jnp.float32),
         pltpu.VMEM((block_k, d), jnp.float32)],
        vmem_limit_bytes=vmem, **dims)
    b, hkv = kp.shape[0], kp.shape[1]

    def grouped(x):
        if group > 1:
            x = x.reshape(b, hkv, group, sp, d).sum(2)
        return x[:, :, :s].astype(kp.dtype)

    return dq[:, :, :s], grouped(dk), grouped(dv)


banded_flash_attention.defvjp(_banded_fwd, _banded_bwd)


def banded_attention_reference(q, k, v, window: int | None = None,
                               scale: float | None = None):
    """The masked softmax the banded kernels compute, with the key-value
    heads repeated; (B, H, S, D) layout. The oracle of their tests."""
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    pos = jnp.arange(q.shape[2])
    keep = pos[None, :] <= pos[:, None]
    if window is not None:
        keep = keep & (pos[:, None] - pos[None, :] < window)
    s = jnp.where(keep, s, -jnp.inf)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v)
