"""ops/attention.py `banded_flash_attention`: grouped-query heads, a
causal window, one Pallas kernel forward and one backward (interpret
mode on the CPU),
against the masked softmax with the key-value heads repeated."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from test_sequence_lm import _small as small_blocks

from pio_tpu.models import seq_blocks
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


def _inputs(s, hq, hkv, d=16, seed=0, b=2):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(ks[0], (b, hq, s, d)),
            jax.random.normal(ks[1], (b, hkv, s, d)),
            jax.random.normal(ks[2], (b, hkv, s, d)),
            jax.random.normal(ks[3], (b, hq, s, d)))


@pytest.mark.parametrize("s,window,bq,bk,hq,hkv", SHAPES)
def test_forward_equals_masked_reference(s, window, bq, bk, hq, hkv):
    q, k, v, _ = _inputs(s, hq, hkv)
    got = banded_flash_attention(q, k, v, window, None, bq, bk)
    want = banded_attention_reference(q, k, v, window)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


# the forward kernel's two outputs, o and the rows' log-sum-exp as the
# kernel leaves it, a row: (sequence, window, block, query heads, key-value
# heads, head width, operands' dtype)
ROW_LSE = {
    "128_wide_full_grouped": (96, None, 32, 4, 2, 128, "float32"),
    "128_wide_window_ungrouped": (96, 40, 32, 2, 2, 128, "float32"),
    "128_wide_window_padded_grouped": (150, 70, 64, 4, 1, 128, "float32"),
    "128_wide_full_bfloat16": (128, None, 32, 4, 2, 128, "bfloat16"),
    "256_wide_full_ungrouped": (96, None, 32, 2, 2, 256, "float32"),
    "256_wide_full_padded": (100, None, 32, 2, 2, 256, "float32"),
    "256_wide_window_grouped": (96, 33, 32, 4, 2, 256, "float32"),
    "256_wide_window_padded_bfloat16": (150, 70, 64, 2, 2, 256, "bfloat16"),
    "one_pair_a_query_block": (130, 1, 32, 2, 1, 16, "float32"),
    "blocks_larger_than_the_sequence": (48, 33, 64, 4, 2, 16, "float32"),
    # blocks over one key tile (128) that are no multiple of it
    "a_block_of_200_keys": (200, None, 512, 2, 1, 16, "float32"),
    "window_blocks_of_192_keys": (384, 100, 192, 2, 1, 16, "float32"),
}


def lse_reference(q, k, window):
    """log-sum-exp of a row's kept scores, (B, Hq, S)."""
    k = jnp.repeat(k, q.shape[1] // k.shape[1], axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(q.shape[-1])
    pos = jnp.arange(q.shape[2])
    keep = pos[None, :] <= pos[:, None]
    if window is not None:
        keep = keep & (pos[:, None] - pos[None, :] < window)
    return jax.nn.logsumexp(jnp.where(keep, s, -jnp.inf), axis=-1)


@pytest.mark.parametrize("case", sorted(ROW_LSE))
def test_forward_o_and_row_lse_equal_masked_reference(case):
    from pio_tpu.ops.attention import _banded_fwd

    s, window, block, hq, hkv, d, dtype = ROW_LSE[case]
    q, k, v, _ = (x.astype(dtype) for x in _inputs(s, hq, hkv, d, seed=8))
    o, res = _banded_fwd(q, k, v, window, None, block, block, None)
    lse = res[-1]
    padded = -(-s // min(block, s)) * min(block, s)
    assert o.shape == q.shape and o.dtype == q.dtype
    assert lse.shape == (2, hq, padded) and lse.dtype == jnp.float32
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    limit = 2e-5 if dtype == "float32" else 0.06
    want = banded_attention_reference(q, k, v, window)
    assert float(jnp.max(jnp.abs(o.astype(jnp.float32) - want))) < limit
    # the statistics are float32 whatever the operands: lse is held to the
    # float32 limit on rounded operands too
    np.testing.assert_allclose(lse[:, :, :s], lse_reference(q, k, window),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("window", [None, 40])
def test_lse_leaves_the_forward_kernel_as_a_row(window):
    """The kernel's second output is (B, Hq, 1, S) float32, a block a
    query block: nothing 128 lanes wide a row is written (the heads are
    64 wide here, so no output has a minor dimension of 128), and what
    the backward pass is handed is B x Hq x S x 4 bytes."""
    from pio_tpu.ops.attention import _banded_fwd

    q, k, v, _ = _inputs(96, 4, 2, 64)
    jaxpr = jax.make_jaxpr(lambda q, k, v: _banded_fwd(
        q, k, v, window, None, 32, 32, None))(q, k, v)
    calls = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "pallas_call"]
    assert len(calls) == 1
    outs = [(var.aval.shape, var.aval.dtype) for var in calls[0].outvars]
    assert outs == [((2, 4, 96, 64), jnp.float32),
                    ((2, 4, 1, 96), jnp.float32)]
    assert not [e for e in jaxpr.jaxpr.eqns for var in e.outvars
                if var.aval.shape[-1:] == (128,)]
    kept = jaxpr.out_avals[-1]
    assert kept.shape == (2, 4, 96) and kept.dtype == jnp.float32


def test_layers_of_one_shape_share_one_traced_forward_kernel():
    """Two layers of one shape and window in one program: their
    `flash_attention_fwd` calls hold the same kernel jaxpr (traced once,
    lowered once); another window is another kernel."""
    from pio_tpu.ops.attention import _banded_fwd

    q, k, v, _ = _inputs(96, 4, 2, 64)

    def three(q, k, v):
        o = _banded_fwd(q, k, v, None, None, 32, 32, None)[0]
        o = _banded_fwd(o, k, v, None, None, 32, 32, None)[0]
        return _banded_fwd(o, k, v, 40, None, 32, 32, None)[0]

    kernels = [e.params["jaxpr"] for e in jax.make_jaxpr(three)(q, k, v).eqns
               if e.primitive.name == "pallas_call"]
    assert len(kernels) == 3
    assert kernels[0] is kernels[1] and kernels[2] is not kernels[0]


@pytest.mark.parametrize("padded,bq,bk,window,want", [
    (8192, 512, 512, None, (1024, 1024)),     # the cells' full layers
    (8192, 512, 512, 1024, (512, 512)),       # a window layer: the caller's
    (8704, 512, 512, None, (512, 512)),       # no multiple of 1,024
    (2048, 2048, 1024, None, (2048, 1024)),   # a caller's larger block
    (48, 16, 16, None, (16, 16)),
])
def test_the_forward_kernel_chooses_its_blocks_by_what_it_sees(
        padded, bq, bk, window, want):
    from pio_tpu.ops.attention import forward_blocks

    assert forward_blocks(padded, bq, bk, window) == want


def test_a_full_layers_forward_blocks_of_1024_equal_the_reference():
    """2,048 positions, the caller's blocks 512: the forward kernel walks
    three pairs of 1,024 x 1,024 (eight key tiles each), the backward
    kernel ten of 512 x 512 on the forward's residuals."""
    q, k, v, w = _inputs(2048, 2, 1, 16, seed=9, b=1)

    def loss(q, k, v):
        return jnp.sum(banded_flash_attention(q, k, v, None, None, 512, 512)
                       * w)

    jaxpr = jax.make_jaxpr(jax.grad(loss, (0, 1, 2)))(q, k, v)
    grids = {e.params["name"]: e.params["grid_mapping"].grid
             for e in jaxpr.jaxpr.eqns if e.primitive.name == "pallas_call"}
    assert grids == {"flash_attention_fwd": (1, 2, 3),
                     "flash_attention_bwd": (1, 2, 10)}
    np.testing.assert_allclose(
        banded_flash_attention(q, k, v, None, None, 512, 512),
        banded_attention_reference(q, k, v), atol=2e-5, rtol=2e-5)
    want = jax.grad(lambda q, k, v: jnp.sum(
        banded_attention_reference(q, k, v) * w), (0, 1, 2))(q, k, v)
    for g, r in zip(jax.grad(loss, (0, 1, 2))(q, k, v), want):
        np.testing.assert_allclose(g, r, atol=1e-4, rtol=1e-4)


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


# what the one backward kernel makes new: a head's whole dq stays in the
# kernel while the band goes by key block, so a query block's rows are
# found by offset. (batch, sequence, window, block_q, block_k, query
# heads, key-value heads, head width)
RESIDENT_DQ = {
    "block_q_over_block_k": (1, 192, None, 64, 16, 2, 1, 16),
    "block_k_over_block_q": (1, 192, None, 16, 64, 2, 1, 16),
    "block_k_over_block_q_window": (2, 160, 40, 16, 32, 2, 2, 16),
    "window_narrower_than_a_block": (1, 128, 5, 32, 32, 2, 1, 16),
    "padded_last_block": (1, 150, None, 32, 32, 2, 2, 16),
    "padded_last_block_window": (1, 70, 33, 32, 16, 2, 1, 16),
    "group_8": (1, 96, 40, 32, 32, 8, 1, 16),
    "head_256_wide": (1, 64, None, 32, 32, 2, 1, 256),
    # programs run one after another over one accumulator: every head of
    # every history must find it zeroed
    "programs_in_a_row": (3, 64, None, 16, 16, 4, 4, 8),
}


@pytest.mark.parametrize("case", sorted(RESIDENT_DQ))
def test_resident_dq_equals_masked_reference(case):
    b, s, window, bq, bk, hq, hkv, d = RESIDENT_DQ[case]
    q, k, v, w = _inputs(s, hq, hkv, d, seed=7, b=b)
    got = jax.grad(lambda q, k, v: jnp.sum(
        banded_flash_attention(q, k, v, window, None, bq, bk) * w),
        (0, 1, 2))(q, k, v)
    want = jax.grad(lambda q, k, v: jnp.sum(
        banded_attention_reference(q, k, v, window) * w), (0, 1, 2))(q, k, v)
    for g, r in zip(got, want):
        np.testing.assert_allclose(g, r, atol=1e-4, rtol=1e-4)
    if case == "programs_in_a_row":
        # a head's dq is its own: the heads of one history in another
        # order give the same rows
        flip = jax.grad(lambda q: jnp.sum(banded_flash_attention(
            q, k[:, ::-1], v[:, ::-1], window, None, bq, bk)
            * w[:, ::-1]))(q[:, ::-1])
        np.testing.assert_array_equal(flip[:, ::-1], got[0])


def test_the_gradient_is_one_backward_kernel_a_call():
    """`jax.grad` of two calls: two `flash_attention_bwd`, and neither of
    the kernels dq and dk / dv had to themselves."""
    q, k, v, w = _inputs(64, 4, 2, seed=4)

    def two_layers(q, k, v):
        o = banded_flash_attention(q, k, v, 24, None, 32, 32)
        return jnp.sum(banded_flash_attention(o, k, v, None, None, 32, 32)
                       * w)

    text = str(jax.make_jaxpr(jax.grad(two_layers, (0, 1, 2)))(q, k, v))
    assert text.count("name=flash_attention_bwd") == 2
    assert text.count("name=flash_attention_fwd") == 2
    assert text.count("name=flash_attention_") == 4
    assert "flash_attention_dq" not in text
    assert "flash_attention_dkv" not in text


@pytest.mark.parametrize("s,d,fits", [
    (8192, 256, True), (65536, 128, True), (32768, 256, True),
    (65536, 256, False), (131072, 128, False)])
def test_a_head_whose_dq_overflows_the_vmem_budget_is_refused(s, d, fits):
    """The kernel asks for its VMEM by the shape (`bwd_vmem_bytes`); a
    sequence whose dq does not fit the budget is refused before any
    kernel is built, with both numbers in the message."""
    from pio_tpu.ops import attention

    need = attention.bwd_vmem_bytes(s, 512, 512, d, 2)
    assert (need <= attention.BWD_VMEM_BUDGET) == fits
    # dq in float32, and twice in the operands' type for the pipeline
    assert need > s * d * (4 + 2 * 2)
    if fits:
        return
    shapes = [jax.ShapeDtypeStruct((1, 1, s, d), jnp.bfloat16)] * 3
    with pytest.raises(ValueError, match=(
            rf"{need} bytes for {s} positions {d} wide, over the budget "
            rf"of {attention.BWD_VMEM_BUDGET}")):
        jax.eval_shape(banded_flash_attention, *shapes)


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


# -- the forward's residuals, bare and across the block stack's checkpoint ---
# (models/seq_blocks.py `_layer`; tests/test_latent_attention.py runs the
# same cases on a latent specification with a prediction module)

GQA_CFG = {
    "hidden_size": 32, "num_hidden_layers": 2,
    "layer_types": ["sliding_attention", "full_attention"],
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 8,
    "sliding_window": 12,
    "rope_parameters": {
        "full_attention": {"rope_type": "default", "rope_theta": 10000},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000}},
    "rms_norm_eps": 1e-6, "moe_intermediate_size": 16, "num_experts": 4,
    "num_experts_routed": 8, "experts_held": [2, 6],
    "num_experts_per_tok": 3, "norm_topk_prob": True, "vocab_size": 50,
    "initializer_range": 0.3,
}


def stack_case(cfg, seed=3):
    """(spec, params, tokens, the gradient function of the stack's loss)
    for 40 trained positions of two histories."""
    spec = seq_blocks.BlockSpec.parse(cfg)
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        1, 50, (2, seq_blocks.history_ids(spec, 40))), jnp.int32)
    grad = jax.grad(
        lambda p: seq_blocks.loss_and_counters(p, tokens, spec)[0])
    return spec, seq_blocks.init_params(spec, seed), tokens, grad


def holds_one_forward_kernel_a_layer(cfg, layers):
    """The gradient's jaxpr calls `flash_attention_fwd` once an attention
    layer (twice before the residuals crossed the checkpoint), and keeps
    o and a (B, Hq, S) lse of each, 48 padded positions of 4 heads."""
    spec, params, _, grad = stack_case(cfg)
    jaxpr = jax.make_jaxpr(grad)(params)
    assert str(jaxpr).count("name=flash_attention_fwd") == layers
    assert str(jaxpr).count("name=flash_attention_bwd") == layers
    assert seq_blocks.attention_counters(jaxpr.jaxpr) == {
        "attn_fwd_kernels": layers, "attn_bwd_kernels": layers,
        "layer_applications": layers,
        "attn_residual_bytes": layers * 2 * 4 * 48 * (
            spec.head_dim * 4 + 4)}


# jax.checkpoint as the block stack's halves could have called it, and
# how far the gradients may then lie (norm of the difference over the norm)
REAL_CHECKPOINT = jax.checkpoint
OTHER_CHECKPOINTS = {
    # everything but the half's input recomputed, the forward kernel too:
    # the same operations on the same operands, so the same bits
    "default_policy": (lambda f=None, **kw: REAL_CHECKPOINT(f), 0.0),
    # no checkpoint at all around the attention half: float32 rounding,
    # since XLA compiles (and fuses) a checkpointed half as one program
    # and a bare one runs an operation at a time
    "attention_half_bare": (lambda f=None, **kw: (
        f if "policy" in kw else REAL_CHECKPOINT(f)), 1e-5),
}


def gradients_equal(mp, cfg, other):
    _, params, _, grad = stack_case(cfg)
    got = grad(params)
    checkpoint, limit = OTHER_CHECKPOINTS[other]
    mp.setattr(jax, "checkpoint", checkpoint)
    want = grad(params)
    flat = jax.tree_util.tree_leaves_with_path(want)
    assert len(flat) > 20
    for (path, w), g in zip(flat, jax.tree_util.tree_leaves(got)):
        w, g = np.asarray(w, np.float64), np.asarray(g, np.float64)
        if limit == 0.0:
            assert np.array_equal(g, w), path
        elif w.any():                  # a router bias takes no gradient
            err = np.linalg.norm(g - w) / np.linalg.norm(w)
            assert err < limit, (path, err)


def half_keeps_x_o_and_a_compact_lse(cfg, kind="full_attention"):
    """What one layer keeps for its backward pass: of the attention half
    its input x, o and lse (B, Hq, S) beside the parameters; no second
    array of o's shape (q), none of a key-value head's, nothing 128 wide."""
    from jax._src.ad_checkpoint import saved_residuals

    spec, params, _, _ = stack_case(cfg)
    lp = params["mtp"]["layer"] if spec.mtp_layers else params["layers"][-1]
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 40, spec.hidden_size))
    table = seq_blocks.rope_tables(spec, 40)[kind]
    kept = saved_residuals(
        lambda lp, x: seq_blocks._layer(
            lp, x, table, spec=spec, kind=kind, dense=False)[0], lp, x)
    half = [(a.shape, said) for a, said in kept
            if "_attention_half" in said or said == "from the argument x"]
    hq, dh = spec.num_attention_heads, spec.head_dim
    assert sorted(shape for shape, _ in half) == sorted(
        [(2, 40, spec.hidden_size),             # x
         (2, hq, 48, dh),                       # o, as the kernel wrote it
         (2, hq, 48),                           # lse, no lane padding
         (2, 40, spec.hidden_size)])            # x + out, the next half's
    assert any("banded_attention_lse" in said for _, said in half)
    assert not [a for a, _ in kept if a.shape[-1:] == (128,)]


def test_the_gradient_holds_one_forward_kernel_a_layer(monkeypatch):
    small_blocks(monkeypatch)   # float32 operands, blocks of 16
    holds_one_forward_kernel_a_layer(GQA_CFG, 2)


def test_the_small_stacks_kept_residuals_are_the_parents_bytes(monkeypatch):
    """27,648 bytes before the forward kernel wrote lse as a row (PR 45's
    tree: two layers, o (2, 4, 48, 8) and lse (2, 4, 48) float32 each),
    and after."""
    small_blocks(monkeypatch)
    _, params, _, grad = stack_case(GQA_CFG)
    counted = seq_blocks.attention_counters(
        jax.make_jaxpr(grad)(params).jaxpr)
    assert counted["attn_residual_bytes"] == 27_648
    assert counted["attn_fwd_kernels"] == counted["layer_applications"] == 2


@pytest.mark.parametrize("other", sorted(OTHER_CHECKPOINTS))
def test_kept_residuals_change_no_gradient(monkeypatch, other):
    small_blocks(monkeypatch)
    gradients_equal(monkeypatch, GQA_CFG, other)


@pytest.mark.parametrize("kind", ["sliding_attention", "full_attention"])
def test_the_attention_half_keeps_x_o_and_a_compact_lse(monkeypatch, kind):
    small_blocks(monkeypatch)
    half_keeps_x_o_and_a_compact_lse(GQA_CFG, kind)


@pytest.mark.parametrize("s,window,bq,bk,hq,hkv", SHAPES[1:4])
def test_bare_gradient_under_jit_reads_the_compact_residual(
        s, window, bq, bk, hq, hkv):
    """As the benchmark's window probe calls it: jitted `jax.vjp` of the
    kernel alone. The residual is (B, Hq, padded S), and the backward
    kernel, which reads it as rows beside delta, gives the reference's
    gradients."""
    from pio_tpu.ops.attention import _banded_fwd

    q, k, v, ct = _inputs(s, hq, hkv, seed=6)
    lse = _banded_fwd(q, k, v, window, None, bq, bk, None)[1][-1]
    assert lse.shape == (2, hq, -(-s // max(bq, bk)) * max(bq, bk))
    assert lse.dtype == jnp.float32
    got = jax.jit(lambda q, k, v, ct: jax.vjp(
        lambda q, k, v: banded_flash_attention(q, k, v, window, None, bq, bk),
        q, k, v)[1](ct))(q, k, v, ct)
    want = jax.vjp(lambda q, k, v: banded_attention_reference(
        q, k, v, window), q, k, v)[1](ct)
    for g, r in zip(got, want):
        np.testing.assert_allclose(g, r, atol=5e-5, rtol=5e-5)
