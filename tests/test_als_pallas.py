"""The Pallas segment-flush accumulation (accum="hybrid", what a TPU
runs, and accum="stream", its overlapped variant) pinned against the XLA
accumulation paths (interpret mode on CPU). Covers multi-slot rows, empty
rows (zeros contract), sentinel padding slots, chunk and group boundaries
splitting a row's slot run, and both implicit/explicit weightings."""

import ast
import pathlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from pio_tpu.ops import als, als_pallas
from pio_tpu.ops.als import (
    ALSParams,
    _device_slot_layout,
    _normal_equations,
    _slots_for,
)

FLUSH_MODES = ["hybrid", "stream"]


def _layout_and_factors(n_self=37, n_other=23, nnz=600, width=8,
                        chunk_slots=16, k=8, seed=0, heavy_rows=True):
    rng = np.random.default_rng(seed)
    if heavy_rows:
        # skewed rows: several rows own many slots; rows 5,6 own none
        probs = rng.dirichlet(np.full(n_self, 0.3))
        probs[5] = probs[6] = 0.0
        probs /= probs.sum()
        u = rng.choice(n_self, size=nnz, p=probs).astype(np.int32)
    else:
        u = rng.integers(0, n_self, nnz).astype(np.int32)
    o = rng.integers(0, n_other, nnz).astype(np.int32)
    v = rng.random(nnz).astype(np.float32) * 4 + 1
    su = _slots_for(nnz, n_self, width, chunk_slots)
    layout = _device_slot_layout(
        jnp.asarray(u), jnp.asarray(o), jnp.asarray(v), n_self, width, su
    )
    factors = jnp.asarray(
        rng.normal(size=(n_other, k)).astype(np.float32))
    return layout, factors, u


@pytest.mark.parametrize("accum", FLUSH_MODES)
@pytest.mark.parametrize("implicit", [False, True])
def test_pallas_matches_xla_accumulation(implicit, accum):
    n_self = 37
    cs = 16
    layout, factors, u = _layout_and_factors(n_self=n_self, chunk_slots=cs)
    A_ref, b_ref = _normal_equations(
        layout, factors, n_self, implicit, 2.5, cs, accum="carry",
        bf16_gather=False,
    )
    A_p, b_p = _normal_equations(
        layout, factors, n_self, implicit, 2.5, cs, accum=accum,
        bf16_gather=False,
    )
    np.testing.assert_allclose(
        np.asarray(A_p), np.asarray(A_ref), atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(
        np.asarray(b_p), np.asarray(b_ref), atol=1e-4, rtol=1e-4)
    # empty rows honored the zeros contract
    for empty in (5, 6):
        assert empty not in set(u.tolist())
        assert np.all(np.asarray(A_p)[empty] == 0)
        assert np.all(np.asarray(b_p)[empty] == 0)


def _one_heavy_row(n_heavy: int, seed: int, ones: bool):
    """Three rows, the middle one with `n_heavy` ratings at width 4:
    its slot run crosses kernel grid steps (8 slots each)."""
    width, cs, k, n_self, n_other = 4, 8, 8, 3, 11
    u = np.array([0] * 3 + [1] * n_heavy + [2] * 5, np.int32)
    rng = np.random.default_rng(seed)
    o = rng.integers(0, n_other, len(u)).astype(np.int32)
    v = (np.ones(len(u), np.float32) if ones
         else (rng.random(len(u)) * 2 + 0.5).astype(np.float32))
    su = _slots_for(len(u), n_self, width, cs)
    layout = _device_slot_layout(
        jnp.asarray(u), jnp.asarray(o), jnp.asarray(v), n_self, width, su
    )
    factors = jnp.asarray(rng.normal(size=(n_other, k)).astype(np.float32))
    return layout, factors, n_self, cs


@pytest.mark.parametrize("accum", FLUSH_MODES)
def test_pallas_row_spanning_chunk_boundary(accum):
    """A single row whose slot run crosses a grid-step boundary must
    accumulate across steps (the persistent-scratch carry)."""
    # row 1 owns 60 ratings -> 15 slots, spanning several 8-slot chunks
    layout, factors, n_self, cs = _one_heavy_row(60, seed=1, ones=True)
    A_ref, b_ref = _normal_equations(
        layout, factors, n_self, True, 1.5, cs, accum="stacked",
        bf16_gather=False,
    )
    A_p, b_p = _normal_equations(
        layout, factors, n_self, True, 1.5, cs, accum=accum,
        bf16_gather=False,
    )
    np.testing.assert_allclose(
        np.asarray(A_p), np.asarray(A_ref), atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(
        np.asarray(b_p), np.asarray(b_ref), atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("accum", FLUSH_MODES)
def test_pallas_end_to_end_train_matches_carry(accum):
    """als_train through the flush kernel (interpret on CPU, under the
    training jit/scan) reaches the same solution quality as the carry
    path. chunk_slots=192 is a multiple of 64 and not of the kernel's
    128-slot cap, so the kernel chunk is rounded down to divide it."""
    from pio_tpu.ops.als import als_train, rmse

    assert als_pallas._kernel_chunk(8, 192) == 64
    rng = np.random.default_rng(3)
    nu, ni, nnz = 50, 30, 700
    u = rng.integers(0, nu, nnz).astype(np.int64)
    i = rng.integers(0, ni, nnz).astype(np.int64)
    v = (rng.random(nnz) * 4 + 1).astype(np.float32)
    kw = dict(rank=8, iterations=6, reg=0.1, chunk=256, width=8,
              chunk_slots=192)
    m_p = als_train(u, i, v, nu, ni, ALSParams(**kw, accum=accum))
    m_c = als_train(u, i, v, nu, ni, ALSParams(**kw, accum="carry"))
    e_p = rmse(m_p, u, i, v)
    e_c = rmse(m_c, u, i, v)
    assert abs(e_p - e_c) < 5e-3, (e_p, e_c)


@pytest.mark.parametrize("accum", FLUSH_MODES)
def test_pallas_row_spanning_group_boundary(accum):
    """A row whose slots span multiple GROUPS: every group emits a trail,
    only the group where the segment ends flushes, and the final trail
    fold reconstructs the row exactly."""
    # row 1: 120 ratings -> 30 slots
    layout, factors, n_self, cs = _one_heavy_row(120, seed=2, ones=False)
    A_ref, b_ref = _normal_equations(
        layout, factors, n_self, False, 1.0, cs, accum="carry",
        bf16_gather=False,
    )
    # group_slots=16 -> row 1's 30 slots span 2+ groups
    A_p, b_p = _normal_equations(
        layout, factors, n_self, False, 1.0, cs, accum=accum,
        group_slots=16, bf16_gather=False,
    )
    np.testing.assert_allclose(
        np.asarray(A_p), np.asarray(A_ref), atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(
        np.asarray(b_p), np.asarray(b_ref), atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("accum", FLUSH_MODES)
def test_pallas_composes_with_shard_map(accum):
    """The flush kernels inside als_train_sharded's shard_map (8 virtual
    devices): hybrid is auto's TPU pick, so its shard_map composition is
    the production multi-chip configuration."""
    from pio_tpu.ops.als import als_train, als_train_sharded, rmse
    from pio_tpu.parallel.mesh import MeshConfig, create_mesh

    rng = np.random.default_rng(0)
    nu, ni, nnz = 60, 40, 900
    u = rng.integers(0, nu, nnz)
    i = rng.integers(0, ni, nnz)
    v = (rng.random(nnz) * 4 + 1).astype(np.float32)
    mesh = create_mesh(MeshConfig(data=8))
    kw = dict(rank=8, iterations=5, reg=0.1, chunk=256, width=8,
              chunk_slots=64)
    m = als_train_sharded(
        u, i, v, nu, ni, ALSParams(**kw, accum=accum), mesh)
    m1 = als_train(u, i, v, nu, ni, ALSParams(**kw, accum="carry"))
    assert abs(rmse(m, u, i, v) - rmse(m1, u, i, v)) < 5e-3


@pytest.mark.parametrize("accum", FLUSH_MODES)
def test_pallas_bf16_gather_close_to_f32(accum):
    n_self, cs = 21, 16
    layout, factors, _ = _layout_and_factors(
        n_self=n_self, chunk_slots=cs, heavy_rows=False, nnz=300)
    A32, b32 = _normal_equations(
        layout, factors, n_self, False, 1.0, cs, accum=accum,
        bf16_gather=False,
    )
    A16, b16 = _normal_equations(
        layout, factors, n_self, False, 1.0, cs, accum=accum,
        bf16_gather=True,
    )
    np.testing.assert_allclose(
        np.asarray(A16), np.asarray(A32), atol=5e-2, rtol=5e-2)
    np.testing.assert_allclose(
        np.asarray(b16), np.asarray(b32), atol=5e-2, rtol=5e-2)


def test_hybrid_matches_stacked():
    """accum="hybrid" (XLA blocks + Pallas segment-flush scatter) must
    reproduce the stacked path at the A/b level and end-to-end,
    including rows spanning kernel-chunk AND group boundaries."""
    from pio_tpu.ops.als import als_train

    rng = np.random.default_rng(5)
    NU, NI, NNZ, K, W, CS = 700, 90, 30_000, 16, 128, 256
    u = (rng.zipf(1.2, NNZ) % NU).astype(np.int32)
    i = (rng.zipf(1.2, NNZ) % NI).astype(np.int32)
    v = rng.integers(1, 6, NNZ).astype(np.float32)
    su = _slots_for(NNZ, NU, W, CS)
    lay = jax.jit(_device_slot_layout, static_argnums=(3, 4, 5))(
        u, i, v, NU, W, su)
    lay = tuple(jnp.asarray(x) for x in lay)
    fac = jax.random.normal(jax.random.PRNGKey(0), (NI, K), jnp.float32) * 0.1
    ne = jax.jit(_normal_equations, static_argnums=(2, 3, 4, 5, 6, 7, 8))
    # group_slots=256 -> 4 groups over the 1024 padded slots, so zipf-
    # heavy rows' slot runs cross group boundaries and the cross-group
    # trail-fold is genuinely exercised (group_slots=1024 was one group)
    A_h, b_h = ne(lay, fac, NU, True, 10.0, CS, True, "hybrid", 256)
    A_s, b_s = ne(lay, fac, NU, True, 10.0, CS, True, "stacked", 256)
    np.testing.assert_allclose(np.asarray(A_h), np.asarray(A_s),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(b_h), np.asarray(b_s),
                               rtol=2e-4, atol=2e-4)

    p_h = ALSParams(rank=K, iterations=3, reg=0.05, alpha=10.0,
                    implicit=True, chunk=1024, chunk_slots=CS,
                    accum="hybrid", cg_iters=12)
    p_s = ALSParams(**{**p_h.__dict__, "accum": "stacked"})
    m_h = als_train(u, i, v, NU, NI, p_h)
    m_s = als_train(u, i, v, NU, NI, p_s)
    # raw factor entries drift by up to ~0.1 between ANY two accumulation
    # orders on this tiny ill-conditioned zipf problem (the f32
    # reassociation amplifies through the CG solves — the carry-vs-
    # stacked control shows the same band), so the end-to-end contract
    # is asserted where it is well-conditioned: the models must predict
    # the SAME ratings
    from pio_tpu.ops.als import rmse

    pred_gap = abs(rmse(m_h, u, i, v) - rmse(m_s, u, i, v))
    assert pred_gap < 1e-3, pred_gap
    mean_drift = float(np.mean(np.abs(
        np.asarray(m_h.user_factors) - np.asarray(m_s.user_factors))))
    assert mean_drift < 0.01, mean_drift


def _relerr(got, ref):
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    scale = np.abs(ref).max()
    return float(np.abs(got - ref).max() / (scale if scale else 1.0))


@pytest.mark.parametrize("implicit", [False, True])
def test_accum_stream_matches_hybrid_exactly_and_oracle(implicit):
    """accum="stream" (overlapped flush) must be BIT-EXACT vs the plain
    hybrid kernel the chip runs — identical adds in an identical order,
    only the DMA schedule moves — and within 1e-6 relerr of the XLA carry
    oracle, including rows whose slot runs cross kernel-chunk AND group
    boundaries (cross-group trails)."""
    rng = np.random.default_rng(7)
    NU, NI, NNZ, K, W, CS = 70, 30, 4000, 16, 8, 64
    u = (rng.zipf(1.2, NNZ) % NU).astype(np.int32)
    i = (rng.zipf(1.2, NNZ) % NI).astype(np.int32)
    v = rng.integers(1, 6, NNZ).astype(np.float32)
    su = _slots_for(NNZ, NU, W, CS)
    lay = _device_slot_layout(
        jnp.asarray(u), jnp.asarray(i), jnp.asarray(v), NU, W, su)
    fac = jnp.asarray(rng.normal(size=(NI, K)).astype(np.float32)) * 0.3
    # group_slots=128 -> several groups; zipf-heavy rows span them
    kw = dict(group_slots=128, bf16_gather=False)
    A_h, b_h = _normal_equations(
        lay, fac, NU, implicit, 5.0, CS, accum="hybrid", **kw)
    A_s, b_s = _normal_equations(
        lay, fac, NU, implicit, 5.0, CS, accum="stream", **kw)
    np.testing.assert_array_equal(np.asarray(A_s), np.asarray(A_h))
    np.testing.assert_array_equal(np.asarray(b_s), np.asarray(b_h))
    A_ref, b_ref = _normal_equations(
        lay, fac, NU, implicit, 5.0, CS, accum="carry", bf16_gather=False)
    assert _relerr(A_s, A_ref) < 1e-6
    assert _relerr(b_s, b_ref) < 1e-6


@pytest.mark.parametrize("accum", FLUSH_MODES)
@pytest.mark.parametrize("k", [8, 64, 128])
def test_accum_stream_odd_last_chunk_and_k_lane_regimes(k, accum):
    """k=8 and 64 (lane-padded acc) and k=128 (lane-exact) through both
    flush kernels, with chunk_slots=24: the kernel chunk is rounded down
    to 8 to divide it, and the last group is shorter than the others
    (the 'odd last chunk')."""
    assert als_pallas._kernel_chunk(k, 24) == 8
    rng = np.random.default_rng(11)
    NU, NI, NNZ, W, CS = 9, 12, 300, 4, 24
    u = rng.integers(0, NU, NNZ).astype(np.int32)
    i = rng.integers(0, NI, NNZ).astype(np.int32)
    v = (rng.random(NNZ) * 2 + 0.5).astype(np.float32)
    su = _slots_for(NNZ, NU, W, CS)   # multiple of 24, not of 16 or 72
    assert su % 72 and su > 72, su
    lay = _device_slot_layout(
        jnp.asarray(u), jnp.asarray(i), jnp.asarray(v), NU, W, su)
    fac = jnp.asarray(rng.normal(size=(NI, k)).astype(np.float32)) * 0.2
    A_ref, b_ref = _normal_equations(
        lay, fac, NU, False, 1.0, CS, accum="carry", bf16_gather=False)
    A_s, b_s = _normal_equations(
        lay, fac, NU, False, 1.0, CS, accum=accum, group_slots=72,
        bf16_gather=False)
    assert _relerr(A_s, A_ref) < 1e-6
    assert _relerr(b_s, b_ref) < 1e-6


@pytest.mark.parametrize("accum", FLUSH_MODES)
def test_rank_over_kernel_limit_resolves_to_stacked(accum):
    """Above als_pallas.MAX_RANK the kernel's blocks do not fit VMEM:
    `resolved_accum` (the one place that decides) turns hybrid and stream
    into stacked, the trained factors are stacked's bit for bit, and the
    kernel itself refuses the rank."""
    from pio_tpu.ops.als import als_train

    k = 264
    assert k > als_pallas.MAX_RANK
    kw = dict(rank=k, iterations=1, reg=0.1, chunk=256, width=8,
              chunk_slots=16, cg_iters=2)
    assert ALSParams(**kw, accum=accum).resolved_accum() == "stacked"
    assert ALSParams(rank=als_pallas.MAX_RANK,
                     accum=accum).resolved_accum() == accum
    rng = np.random.default_rng(5)
    nu, ni, nnz = 12, 9, 80
    u = rng.integers(0, nu, nnz).astype(np.int64)
    i = rng.integers(0, ni, nnz).astype(np.int64)
    v = (rng.random(nnz) * 4 + 1).astype(np.float32)
    got = als_train(u, i, v, nu, ni, ALSParams(**kw, accum=accum))
    ref = als_train(u, i, v, nu, ni, ALSParams(**kw, accum="stacked"))
    np.testing.assert_array_equal(
        np.asarray(got.user_factors), np.asarray(ref.user_factors))
    np.testing.assert_array_equal(
        np.asarray(got.item_factors), np.asarray(ref.item_factors))
    with pytest.raises(ValueError, match="rank 264"):
        als_pallas.segment_flush(
            jnp.zeros((16,), jnp.int32), nu, k, 16, iter(()))


# ---------------------------------------------------------------------------
# lane-packed A: what the CG solve holds behind the flush kernel (PR 32)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("k,n", [(32, 701), (64, 301), (128, 75)])
def test_packed_matvec_matches_einsum(k, n):
    """The product kernel on packed rows against float64, at a row count
    that is no multiple of its block (512 / 128 / 32 rows) nor of 8."""
    pack = als_pallas.pack_factor(k)
    assert pack == {32: 4, 64: 2, 128: 1}[k]
    rng = np.random.default_rng(2)
    A = rng.normal(size=(n, k, k)).astype(np.float32)
    A = A + np.swapaxes(A, 1, 2)      # symmetric, like a normal equation
    x = rng.normal(size=(n, k)).astype(np.float32)
    a_p = als_pallas.pack_rows(jnp.asarray(A), pack)
    assert a_p.shape == (n, k // pack, max(k, 128))
    got = als_pallas.packed_matvec(a_p, jnp.asarray(x))
    ref = np.einsum("bij,bj->bi", A.astype(np.float64), x)
    assert _relerr(got, ref) < 1e-6


@pytest.mark.parametrize("k", [32, 64])
def test_pack_unpack_identity_and_pack_flush(k):
    """pack -> unpack is the identity, and the pass that adds the Gram
    term writes exactly `pack_rows(A + G)` and its diagonal from the
    flush's wide buffer (padding row and zero lanes included)."""
    pack = als_pallas.pack_factor(k)
    n = 37
    rng = np.random.default_rng(4)
    A = rng.normal(size=(n + 1, k, k)).astype(np.float32)
    G = rng.normal(size=(k, k)).astype(np.float32)
    a = jnp.asarray(A[:n])
    np.testing.assert_array_equal(
        np.asarray(als_pallas.unpack_rows(
            als_pallas.pack_rows(a, pack), pack)), A[:n])
    wide = jnp.pad(jnp.asarray(A), ((0, 0), (0, 0), (0, 128 - k)))
    a_p, diag = als_pallas.pack_flush(wide, jnp.asarray(G), n, pack)
    want = A[:n] + G[None]
    np.testing.assert_array_equal(
        np.asarray(a_p), np.asarray(als_pallas.pack_rows(
            jnp.asarray(want), pack)))
    np.testing.assert_array_equal(
        np.asarray(diag), np.diagonal(want, axis1=1, axis2=2))


def _half_sweep(k, accum, cg_iters, n_self=41, n_other=29, nnz=900,
                implicit=True):
    rng = np.random.default_rng(9)
    u = rng.integers(0, n_self, nnz).astype(np.int32)
    o = rng.integers(0, n_other, nnz).astype(np.int32)
    v = (rng.random(nnz) * 4 + 1).astype(np.float32)
    cs, width = 32, 8
    layout = _device_slot_layout(
        jnp.asarray(u), jnp.asarray(o), jnp.asarray(v), n_self, width,
        _slots_for(nnz, n_self, width, cs))
    other = jnp.asarray(rng.normal(size=(n_other, k)).astype(np.float32)) * .3
    x0 = jnp.asarray(rng.normal(size=(n_self, k)).astype(np.float32)) * .1
    solved = als._solve_factors(
        layout, other, n_self, 0.05, implicit, 4.0, cs, x0=x0,
        cg_iters=cg_iters, bf16_gather=False, accum=accum)
    return solved, (layout, other, n_self, x0, cs)


def test_solve_factors_packed_matches_carry():
    """One half-sweep at rank 64: the flush kernel, the pack pass and the
    product kernel against the XLA carry path and its einsum product."""
    assert als._a_pack(64, "hybrid", 8) == 2
    got, _ = _half_sweep(64, "hybrid", 8)
    ref, _ = _half_sweep(64, "carry", 8)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref), atol=1e-4, rtol=1e-4)
    same, _ = _half_sweep(64, "stream", 8)
    np.testing.assert_array_equal(np.asarray(same), np.asarray(got))


def test_rank_128_solve_is_the_unpacked_path_bit_for_bit():
    """Where the rank fills the lanes nothing is packed: `_solve_factors`
    under hybrid is the flush's A plus the two Gram adds and the einsum
    product, bit for bit what it was before the packed path existed."""
    k = 128
    assert als._a_pack(k, "hybrid", 8) == 1
    got, (layout, other, n_self, x0, cs) = _half_sweep(k, "hybrid", 8)
    A, b = _normal_equations(layout, other, n_self, True, 4.0, cs,
                             bf16_gather=False, accum="hybrid")
    A = A + als._shared_yty(other, None)[None]
    A = A + 0.05 * jnp.eye(k, dtype=jnp.float32)[None]
    want = als._cg_solve(A, b, x0, 8)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_exact_side_still_reaches_cholesky_unpacked(monkeypatch):
    """A side under `auto_cg_rows` (cg_iters resolves to 0) is never
    packed: `_chol_solve` gets (n, k, k)."""
    seen = []
    real = als._chol_solve
    monkeypatch.setattr(
        als, "_chol_solve", lambda A, b: seen.append(A.shape) or real(A, b))
    p = ALSParams(rank=64, accum="hybrid")
    assert p.resolved_cg_iters(41) == 0 and als._a_pack(64, "hybrid", 0) == 1
    _half_sweep(64, "hybrid", 0)
    assert seen == [(41, 64, 64)]


def test_sharded_hybrid_rank64_equals_one_device():
    """The packed path inside `als_train_sharded`'s shard_map (virtual
    CPU mesh) against the one-device trainer, both through the kernels."""
    from pio_tpu.ops.als import als_train, als_train_sharded, rmse
    from pio_tpu.parallel.mesh import MeshConfig, create_mesh

    rng = np.random.default_rng(0)
    nu, ni, nnz = 60, 40, 900
    u = rng.integers(0, nu, nnz)
    i = rng.integers(0, ni, nnz)
    v = (rng.random(nnz) * 4 + 1).astype(np.float32)
    mesh = create_mesh(MeshConfig(data=8))
    kw = dict(rank=64, iterations=2, reg=0.1, chunk=256, width=8,
              chunk_slots=64, cg_iters=6, accum="hybrid")
    m = als_train_sharded(u, i, v, nu, ni, ALSParams(**kw), mesh)
    m1 = als_train(u, i, v, nu, ni, ALSParams(**kw))
    assert abs(rmse(m, u, i, v) - rmse(m1, u, i, v)) < 5e-3
    np.testing.assert_allclose(
        np.asarray(m.user_factors), np.asarray(m1.user_factors),
        atol=2e-3, rtol=2e-3)


@pytest.mark.parametrize("rank,a_pack", [(64, 2), (32, 4), (128, 1)])
def test_dispatch_labels_report_the_packing(rank, a_pack):
    p = ALSParams(rank=rank, accum="hybrid")
    n_u, n_i = 138_493, 26_744
    sp: dict = {}
    als._dispatch_labels(
        sp, 1000, 1000, (8, 16, 16), p, p.resolved_cg_iters(n_u),
        p.resolved_cg_iters(n_i), n_u, n_i)
    assert sp["a_pack"] == a_pack
    lane = max(rank, 128)
    assert sp["a_bytes_u"] == n_u * rank * lane * 4 // a_pack
    assert sp["a_bytes_i"] == n_i * rank * lane * 4 // a_pack
    if rank == 64:      # ISSUE 32: 2.27 GB / 0.44 GB at ML-20M
        assert (sp["a_bytes_u"], sp["a_bytes_i"]) == (2_269_069_312,
                                                      438_173_696)
    # the XLA paths never pack, whatever the rank
    sp2: dict = {}
    als._dispatch_labels(
        sp2, 1000, 1000, (8, 16, 16), ALSParams(rank=rank, accum="carry"),
        16, 16, n_u, n_i)
    assert sp2["a_pack"] == 1


def test_als_pallas_imports_nothing_from_als():
    """The arrows point one way: ops/als.py -> ops/als_pallas.py."""
    tree = ast.parse(pathlib.Path(als_pallas.__file__).read_text())
    imported = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported |= {a.name for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            imported |= {f"{node.module}.{a.name}" for a in node.names}
            imported.add(node.module)
    assert not {m for m in imported
                if m == "pio_tpu.ops.als"
                or m.startswith("pio_tpu.ops.als.")}, imported


# every ALSParams field is set by something that ships, or by a test with
# a stated need: a field no caller can reach is a mode nobody runs
_TEMPLATE_FILES = (
    "pio_tpu/models/recommendation.py", "pio_tpu/models/similarproduct.py",
    "pio_tpu/models/ecommerce.py", "pio_tpu/tuning/sweep.py",
    "pio_tpu/tools/cli.py",
)
_SET_IN_OPS_ALS = {
    "accum": "sweep_safe_params and fold_in_params pin it",
    "bf16_gather": "fold_in_params turns it off",
}
_TESTS_NEED = {
    "width": "slot layouts with rows wider than a slot at tiny sizes",
    "chunk_slots": "several chunks and groups at tiny sizes",
    "auto_cg_rows": "tests/test_als.py forces CG on a small side",
}


def _alsparams_keywords(path: pathlib.Path) -> set[str]:
    found = set()
    for node in ast.walk(ast.parse(path.read_text())):
        if (isinstance(node, ast.Call)
                and getattr(node.func, "attr", getattr(node.func, "id", ""))
                == "ALSParams"):
            found |= {kw.arg for kw in node.keywords if kw.arg}
    return found


def test_every_alsparams_field_has_a_caller():
    import dataclasses

    root = pathlib.Path(als.__file__).parents[2]
    passed = set()
    for rel in _TEMPLATE_FILES:
        passed |= _alsparams_keywords(root / rel)
    fields = {f.name for f in dataclasses.fields(ALSParams)}
    assert passed <= fields, passed - fields
    for name, setter in _SET_IN_OPS_ALS.items():
        assert name in fields and name not in passed, (name, setter)
    unreached = fields - passed - set(_SET_IN_OPS_ALS)
    assert unreached == set(_TESTS_NEED), unreached
    assert len(fields) == 15
