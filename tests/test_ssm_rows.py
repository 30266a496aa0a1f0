"""ops/ssm_rows.py: the Mamba-2 block's convolution with its SiLU and its
gated group norm as row-tiled passes over `in_proj`'s output where it
lies, against the plain float32 functions of models/seq_blocks.py
(`causal_conv`, `gated_group_norm`) on slices: forward and every
gradient, over row tiles that do and do not hold the whole history (the
earlier rows across a tile's edge, zeros before the first), 2 and 4 taps,
with and without a bias, 1 / 2 / 8 groups, float32 and bfloat16 results;
the column blocks addressed inside an array whose z | xBC | dt widths
stand as the state-space cell's do; `_mamba_block` through the passes
against the same block through the plain functions; and what the step
program holds of them (the Pallas kernels in interpret mode)."""

import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pio_tpu.models import seq_blocks
from pio_tpu.ops import ssm_rows
from tests.test_ssm_lm import CFG, POSITIONS, SPEC

S = 64
# the cell's widths an eighth each: z 512 | x 512, B 128, C 128 | dt 8
DI, GN, HEADS = 512, 128, 8
WIDE = 2 * DI + 2 * GN + HEADS
ROWS = {"one tile": None, "two tiles": 32, "four tiles": 16}
DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}
# one bfloat16 step is 2^-8 of the value
RTOL = {"float32": 1e-5, "bfloat16": 2.0 ** -7}
CONV = list(itertools.product(ROWS, (2, 4), ("bias", "no bias"), DTYPES))
NORM = list(itertools.product(ROWS, (1, 2, 8), DTYPES))


def _close(got, want, rtol, name=""):
    got, want = (np.asarray(v, np.float32) for v in (got, want))
    np.testing.assert_allclose(got, want, rtol=rtol,
                               atol=rtol * np.abs(want).max(), err_msg=name)


def _conv_sides(taps, bias, dtype, rows, batch=1, s=S):
    ks = jax.random.split(jax.random.PRNGKey(taps), 6)
    widths = (DI, GN, GN)
    u = jax.random.normal(ks[0], (batch, s, WIDE))
    w = jax.random.uniform(ks[1], (taps, sum(widths)), minval=-.5, maxval=.5)
    b = jax.random.uniform(ks[2], (sum(widths),), minval=-.5, maxval=.5)
    cots = tuple(jax.random.normal(k, (batch, s, width)).astype(dtype)
                 for k, width in zip(ks[3:], widths))

    def passes(u, w, b):
        return ssm_rows.conv_silu(
            u, w, b if bias == "bias" else jnp.zeros_like(b), DI, widths,
            dtype, rows)

    def plain(u, w, b):
        out = jax.nn.silu(seq_blocks.causal_conv(
            u[..., DI:-HEADS], w, b if bias == "bias" else None))
        return tuple(v.astype(dtype)
                     for v in jnp.split(out, (DI, DI + GN), axis=-1))

    return passes, plain, (u, w, b), cots


@pytest.mark.parametrize("rows,taps,bias,dtype", CONV)
def test_the_convolution_and_its_silu_equal_the_plain_ones(rows, taps, bias,
                                                           dtype):
    passes, plain, args, _ = _conv_sides(taps, bias, DTYPES[dtype],
                                         ROWS[rows])
    got, want = passes(*args), plain(*args)
    assert [v.dtype for v in got] == [DTYPES[dtype]] * 3
    for name, a, b in zip("xBC", got, want):
        assert a.shape == b.shape
        _close(a, b, RTOL[dtype], name)


@pytest.mark.parametrize("rows,taps,bias,dtype", CONV)
def test_the_convolutions_three_gradients_equal_the_plain_ones(
        rows, taps, bias, dtype):
    passes, plain, args, cots = _conv_sides(taps, bias, DTYPES[dtype],
                                            ROWS[rows])
    got, want = (jax.vjp(fn, *args)[1](cots) for fn in (passes, plain))
    for name, a, b in zip(("u", "w", "bias"), got, want):
        if name == "bias" and bias == "no bias":
            assert not np.any(b)       # the plain side never read it
            continue
        _close(a, b, 1e-5, name)


def test_two_histories_of_several_tiles_each_start_from_zeros():
    passes, plain, args, cots = _conv_sides(4, "bias", jnp.float32, 16,
                                            batch=2, s=32)
    for a, b in zip(passes(*args), plain(*args)):
        _close(a, b, 1e-5)
    got, want = (jax.vjp(fn, *args)[1](cots) for fn in (passes, plain))
    for name, a, b in zip(("u", "w", "bias"), got, want):
        _close(a, b, 1e-5, name)


def _norm_sides(groups, dtype, rows, batch=1):
    width = 128 * groups
    ks = jax.random.split(jax.random.PRNGKey(groups), 4)
    y = jax.random.normal(ks[0], (batch, S, width))
    u = jax.random.normal(ks[1], (batch, S, 2 * width + 8))
    gain = 1.0 + 0.5 * jax.random.normal(ks[2], (width,))
    cot = jax.random.normal(ks[3], (batch, S, width)).astype(dtype)

    def passes(y, u, gain):
        return ssm_rows.gated_norm(y, u, gain, groups, 1e-5, dtype, rows)

    def plain(y, u, gain):
        return seq_blocks.gated_group_norm(
            y, u[..., :width], gain, groups, 1e-5).astype(dtype)

    return passes, plain, (y, u, gain), cot


@pytest.mark.parametrize("rows,groups,dtype", NORM)
def test_the_gated_norm_equals_the_plain_one(rows, groups, dtype):
    passes, plain, args, _ = _norm_sides(groups, DTYPES[dtype], ROWS[rows])
    got, want = passes(*args), plain(*args)
    assert got.dtype == DTYPES[dtype] and got.shape == want.shape
    _close(got, want, RTOL[dtype])


@pytest.mark.parametrize("rows,groups,dtype", NORM)
def test_the_gated_norms_three_gradients_equal_the_plain_ones(rows, groups,
                                                              dtype):
    passes, plain, args, cot = _norm_sides(groups, DTYPES[dtype], ROWS[rows])
    got, want = (jax.vjp(fn, *args)[1](cot) for fn in (passes, plain))
    for name, a, b in zip(("y", "u", "gain"), got, want):
        _close(a, b, 2e-5, name)


def test_a_pass_reads_its_own_columns_of_the_wide_array_and_no_other():
    """z | xBC | dt as the cell has them: what the passes return depends
    on their own columns alone, and u's gradient is zero outside them."""
    passes, plain, (u, w, b), cots = _conv_sides(4, "bias", jnp.float32, 16)
    other = u.at[..., :DI].add(7.0).at[..., -HEADS:].add(-3.0)
    for a, c in zip(passes(u, w, b), passes(other, w, b)):
        np.testing.assert_array_equal(a, c)
    du = jax.vjp(passes, u, w, b)[1](cots)[0]
    assert not np.any(du[..., :DI]) and not np.any(du[..., -HEADS:])
    assert np.all(np.abs(du[..., DI:-HEADS]).max(axis=1) > 0)
    y = jnp.ones((1, S, DI))
    other = u.at[..., DI:].add(5.0)

    def norm(u):
        return ssm_rows.gated_norm(y, u, jnp.ones(DI), 4, 1e-5, jnp.float32,
                                   16)

    np.testing.assert_array_equal(norm(u), norm(other))
    du = jax.grad(lambda u: jnp.sum(norm(u) ** 2))(u)
    assert not np.any(du[..., DI:])
    assert np.all(np.abs(du[..., :DI]).max(axis=1) > 0)


def test_which_shapes_the_passes_take():
    # the state-space cell's: 8,192 positions, x 4,096, B and C 1,024,
    # groups of 512
    assert ssm_rows.takes(8192, 4, 4096, 1024, 1024, 512)
    assert ssm_rows._row_tile(8192, None) == ssm_rows._ROWS
    assert ssm_rows._col_block(4096, 4096, 1024, 1024) == min(
        ssm_rows._LANES, 1024)
    assert ssm_rows._norm_block(4096, 8) == 512
    # a group wider than a block is a block; narrow groups share one
    assert ssm_rows._norm_block(4096, 2) == 2048
    assert ssm_rows._norm_block(1024, 8) == 512
    # the tiny stacks of the tests: 64 channels, groups of 32
    assert not ssm_rows.takes(POSITIONS, 4, 64, 32, 32, 32)
    assert not ssm_rows.takes(8200, 4, 4096, 1024, 1024, 512)
    assert not ssm_rows.takes(8192, 12, 4096, 1024, 1024, 512)


# -- the block ---------------------------------------------------------------

# SPEC's stack with a mixer of whole lane tiles: x 256 wide, B and C 128,
# two groups of 128
ALIGNED = seq_blocks.BlockSpec.parse({
    **CFG, "mamba_num_heads": 4, "mamba_head_dim": 64, "ssm_state_size": 64,
    "chunk_size": 16})


def _block_sides(monkeypatch, spec, compute):
    monkeypatch.setattr(seq_blocks, "COMPUTE", compute)
    lp = seq_blocks.init_params(spec, 3)["layers"][0]
    x = jax.random.normal(jax.random.PRNGKey(5),
                          (2, POSITIONS, spec.hidden_size))
    cot = jax.random.normal(jax.random.PRNGKey(6), x.shape)

    def run():
        out, back = jax.vjp(
            lambda lp, x: seq_blocks._mamba_block(lp, x, spec=spec), lp, x)
        return out, back(cot)

    through = run()
    monkeypatch.setattr(ssm_rows, "takes", lambda *a: False)
    return through, run()


@pytest.mark.parametrize("compute", sorted(DTYPES))
def test_a_block_through_the_passes_equals_the_block_through_the_plain(
        monkeypatch, compute):
    (out, (dlp, dx)), (want, (dlp_want, dx_want)) = _block_sides(
        monkeypatch, ALIGNED, DTYPES[compute])
    # bfloat16: the two sides round the same float32 values at the same
    # two places, and a value at a step's edge may fall either way
    rtol = {"float32": 2e-5, "bfloat16": 2e-2}[compute]
    _close(out, want, rtol)
    _close(dx, dx_want, rtol, "x")
    for name in dlp_want:
        _close(dlp[name], dlp_want[name], rtol, name)


def test_the_tiny_stacks_block_is_the_plain_one_as_it_was(monkeypatch):
    """SPEC's mixer (64 channels, groups of 32) is sent to the plain
    functions by its shapes: with or without the passes' say, the same
    numbers."""
    (out, (dlp, dx)), (want, (dlp_want, dx_want)) = _block_sides(
        monkeypatch, SPEC, jnp.float32)
    np.testing.assert_array_equal(out, want)
    np.testing.assert_array_equal(dx, dx_want)
    for name in dlp_want:
        np.testing.assert_array_equal(dlp[name], dlp_want[name])


def test_the_step_of_a_lane_aligned_stack_holds_the_passes():
    found = seq_blocks.step_attention_counters.__wrapped__(
        ALIGNED, 0.0213, (2, POSITIONS + 1))
    blocks = ALIGNED.block_kinds.count("M")
    # forward, forward again in the block's recomputation, backward; a
    # block's loop over the histories holds its body once
    assert found["ssm_conv_kernels"] == 3 * blocks
    assert found["ssm_norm_kernels"] == 3 * blocks
    assert found["ssm_fwd_kernels"] == 2 * blocks
    assert found["ssm_bwd_kernels"] == blocks


def test_the_step_of_the_tiny_stack_says_that_it_holds_none():
    found = seq_blocks.step_attention_counters.__wrapped__(
        SPEC, 0.0213, (2, POSITIONS + 1))
    assert found["ssm_conv_kernels"] == found["ssm_norm_kernels"] == 0
    assert found["ssm_fwd_kernels"] == 8


def test_a_stack_without_mamba_blocks_carries_neither_label():
    cfg = {**CFG, "hybrid_override_pattern": "E*E", "num_hidden_layers": 3}
    found = seq_blocks.step_attention_counters.__wrapped__(
        seq_blocks.BlockSpec.parse(cfg), 0.0213, (2, POSITIONS + 1))
    assert not [k for k in found if k.startswith("ssm_")]
