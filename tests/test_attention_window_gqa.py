"""ops/attention.py `banded_flash_attention`: grouped-query heads, a
causal window, Pallas forward and backward (interpret mode on the CPU),
against the masked softmax with the key-value heads repeated."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pio_tpu.ops.attention import (
    band_blocks,
    band_pairs,
    banded_attention_reference,
    banded_flash_attention,
)

# (sequence, window, block_q, block_k, query heads, key-value heads)
SHAPES = [
    (64, None, 32, 32, 4, 2),      # full causal, whole blocks
    (200, None, 64, 64, 4, 1),     # a length that is no multiple of the block
    (200, 70, 64, 64, 4, 2),       # window, ragged length
    (96, 128, 32, 32, 2, 2),       # window >= length: full causal again
    (96, 96, 32, 32, 2, 1),        # window == length
    (256, 64, 64, 32, 8, 2),       # block_q > block_k
    (256, 100, 32, 64, 4, 4),      # block_q < block_k, window off the grid
    (130, 1, 32, 32, 2, 1),        # every query sees itself alone
    (48, 33, 64, 64, 4, 2),        # blocks larger than the sequence
]


def _inputs(s, hq, hkv, d=16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(ks[0], (2, hq, s, d)),
            jax.random.normal(ks[1], (2, hkv, s, d)),
            jax.random.normal(ks[2], (2, hkv, s, d)),
            jax.random.normal(ks[3], (2, hq, s, d)))


@pytest.mark.parametrize("s,window,bq,bk,hq,hkv", SHAPES)
def test_forward_equals_masked_reference(s, window, bq, bk, hq, hkv):
    q, k, v, _ = _inputs(s, hq, hkv)
    got = banded_flash_attention(q, k, v, window, None, bq, bk)
    want = banded_attention_reference(q, k, v, window)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("s,window,bq,bk,hq,hkv", SHAPES)
def test_backward_equals_masked_reference(s, window, bq, bk, hq, hkv):
    q, k, v, w = _inputs(s, hq, hkv, seed=1)
    got = jax.grad(lambda q, k, v: jnp.sum(
        banded_flash_attention(q, k, v, window, None, bq, bk) * w),
        (0, 1, 2))(q, k, v)
    want = jax.grad(lambda q, k, v: jnp.sum(
        banded_attention_reference(q, k, v, window) * w), (0, 1, 2))(q, k, v)
    for g, r in zip(got, want):
        np.testing.assert_allclose(g, r, atol=5e-5, rtol=5e-5)


def test_grouped_heads_equal_repeated_key_value_heads():
    """8 query heads on 2 key-value heads == the same kernel given the
    key-value heads repeated 4 times, forward and backward."""
    q, k, v, w = _inputs(96, 8, 2, seed=2)
    rep = lambda x: jnp.repeat(x, 4, axis=1)          # noqa: E731

    def grouped(q, k, v):
        return jnp.sum(banded_flash_attention(q, k, v, 40, None, 32, 32) * w)

    def repeated(q, k, v):
        return jnp.sum(banded_flash_attention(
            q, rep(k), rep(v), 40, None, 32, 32) * w)

    np.testing.assert_allclose(grouped(q, k, v), repeated(q, k, v),
                               rtol=1e-5)
    for g, r in zip(jax.grad(grouped, (0, 1, 2))(q, k, v),
                    jax.grad(repeated, (0, 1, 2))(q, k, v)):
        np.testing.assert_allclose(g, r, atol=5e-5, rtol=5e-5)


def test_bfloat16_operands_stay_close():
    q, k, v, _ = _inputs(128, 4, 2, seed=3)
    got = banded_flash_attention(*(x.astype(jnp.bfloat16) for x in (q, k, v)),
                                 48, None, 32, 32)
    assert got.dtype == jnp.bfloat16
    want = banded_attention_reference(q, k, v, 48)
    assert float(jnp.max(jnp.abs(got.astype(jnp.float32) - want))) < 0.06


def test_head_counts_that_do_not_group_are_refused():
    q, k, v, _ = _inputs(32, 3, 2)
    with pytest.raises(ValueError, match="do not group"):
        banded_flash_attention(q, k, v)


@pytest.mark.parametrize("s,bq,bk,window", [
    (8192, 512, 512, 1024), (8192, 512, 512, None), (8192, 256, 512, 1024),
    (1024, 128, 256, 100), (512, 512, 512, 2048), (640, 128, 128, 1)])
def test_table_visits_exactly_the_band(s, bq, bk, window):
    for by_key in (False, True):
        qi, kj, fl = band_pairs(s, bq, bk, window, by_key=by_key)
        assert len(qi) == band_blocks(s, bq, bk, window)
        pairs = set(zip(qi.tolist(), kj.tolist()))
        assert len(pairs) == len(qi)
        run = kj if by_key else qi
        assert int(np.sum(fl & 1 != 0)) == len(set(run.tolist()))
        assert int(np.sum(fl & 2 != 0)) == len(set(run.tolist()))
    # every kept (query, key) position lies in a listed block, and every
    # listed block holds one
    rows = np.arange(s)[:, None]
    cols = np.arange(s)[None, :]
    keep = cols <= rows
    if window is not None:
        keep &= rows - cols < window
    if s <= 1024:
        listed = {(int(r) // bq, int(c) // bk)
                  for r, c in zip(*np.nonzero(keep))}
        assert listed == pairs
    if s == 8192 and bq == bk == 512:
        assert len(qi) == (16 * 17 // 2 if window is None else 1 + 2 + 14 * 3)
