"""The packed-A kernels of the ALS solve, the banded attention's two
kernels, the held experts' grouped kernels, the state-space scan's two
and the four row-tiled passes of a Mamba-2 block, compiled by the TPU's
compiler for a described v5e at the benchmark's widths: what Mosaic
refuses (a slice off the tiling, a stack over the scoped VMEM) shows here,
on a CPU, at no chip time. Nothing runs: these are compiles, not
measurements. The topology is described inside a fixture, in this one
file, so that only the worker that is given the file loads the TPU's
library (guides: on-chip-measurement, section 2)."""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from pio_tpu.ops import als_pallas, attention, moe, ssd, ssm_rows

ML20M_USERS, ML20M_ITEMS, MSD_ITEM_BLOCK = 138_493, 26_744, 96_137


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """The described chip, with the persistent compile cache off: what is
    compiled for a chip that is not attached cannot be read back."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _shape(shape, one_chip):
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)


@pytest.mark.parametrize("n,k", [(ML20M_USERS, 64), (ML20M_ITEMS, 64),
                                 (MSD_ITEM_BLOCK, 64), (ML20M_USERS, 32)])
def test_packed_matvec_compiles_for_v5e(one_chip, n, k):
    pack = als_pallas.pack_factor(k)
    a_p = _shape((n, k // pack, 128), one_chip)
    x = _shape((n, k), one_chip)
    text = jax.jit(lambda a, x: als_pallas.packed_matvec(
        a, x, interpret=False)).lower(a_p, x).compile().as_text()
    assert "tpu_custom_call" in text and "als.cg.matvec" in text


@pytest.mark.parametrize("n,k", [(ML20M_USERS, 64), (MSD_ITEM_BLOCK, 64),
                                 (ML20M_USERS, 32)])
def test_pack_flush_compiles_for_v5e(one_chip, n, k):
    pack = als_pallas.pack_factor(k)
    wide = _shape((n + 1, k, 128), one_chip)
    gram = _shape((k, k), one_chip)
    compiled = jax.jit(lambda w, g: als_pallas.pack_flush(
        w, g, n, pack, interpret=False)).lower(wide, gram).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "als.gram.pack" in text
    # the pass writes 1/pack of what it reads and holds nothing else
    assert compiled.memory_analysis().temp_size_in_bytes < 2**20


# (batch, query heads, key-value heads, positions, head width, window):
# the sequence cells' attention layers, and the longest head the backward
# kernel's VMEM budget admits at 128 wide
ATTENTION = {
    "glm_latent": (2, 20, 20, 8192, 256, None),
    "mellum2_full": (2, 32, 4, 8192, 128, None),
    "mellum2_window": (2, 32, 4, 8192, 128, 1024),
    "ouro": (2, 16, 16, 8192, 128, None),
    "nemotron": (2, 32, 2, 8192, 128, None),
    # groups of 6 and of 8 query heads, a window of one block (PR 49)
    "laguna_full": (2, 48, 8, 8192, 128, None),
    "laguna_window": (2, 64, 8, 8192, 128, 512),
    "64k_positions": (1, 2, 1, 65536, 128, 1024),
}


@pytest.mark.parametrize("case", sorted(ATTENTION))
def test_banded_attention_gradient_compiles_for_v5e(one_chip, case):
    """One forward and one backward kernel; the backward kernel's dq of
    a whole head, its accumulate at a dynamic row offset and the VMEM it
    asks for (`bwd_vmem_bytes`, over the 16 MB a kernel gets unasked)
    pass Mosaic."""
    b, hq, hkv, s, d, window = ATTENTION[case]

    def shape(h):
        return jax.ShapeDtypeStruct((b, h, s, d), jnp.bfloat16,
                                    sharding=one_chip)

    def gradient(q, k, v, ct):
        return jax.vjp(lambda q, k, v: attention.banded_flash_attention(
            q, k, v, window, None, 512, 512, False), q, k, v)[1](ct)

    assert attention.bwd_vmem_bytes(s, 512, 512, d, 2) > 16 << 20
    text = jax.jit(gradient).lower(
        shape(hq), shape(hkv), shape(hkv), shape(hq)).compile().as_text()
    assert text.count("tpu_custom_call") >= 2
    assert "flash_attention_fwd" in text and "flash_attention_bwd" in text
    assert "flash_attention_dq" not in text


# (rows of the sorted buffer a history, hidden, expert width, held
# experts): the expert layers of the sequence cells that route by SwiGLU
EXPERTS = {"mellum2": (73_728, 2304, 896, 16), "glm": (36_864, 2048, 1536, 8),
           "laguna": (73_728, 2048, 512, 16)}


@pytest.mark.parametrize("case", sorted(EXPERTS))
def test_grouped_swiglu_gradient_compiles_for_v5e(one_chip, case, monkeypatch):
    """The fused expert op's seven kernels at the cells' widths, rows in
    bfloat16 and the weights the float32 masters: blocks that span the
    depth, their rounded copies and the accumulators pass Mosaic inside
    the VMEM the kernels ask for, the products that contract over the
    weights' last axis among them, and no copy of a weight is made
    outside the kernels."""
    m, d, f, g = EXPERTS[case]
    monkeypatch.setattr(moe, "_interpret", lambda: False)

    def shape(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def gradient(rows, w_gate, w_up, w_down, te, used, ct):
        out, back = jax.vjp(
            lambda *a: moe.grouped_swiglu(*a, te, used, 512),
            rows, w_gate, w_up, w_down)
        return out, back(ct)

    rows = shape((m, d), jnp.bfloat16)
    text = jax.jit(gradient).lower(
        rows, shape((g, d, f), jnp.float32), shape((g, d, f), jnp.float32),
        shape((g, f, d), jnp.float32), shape((m // 512,), jnp.int32),
        shape((1,), jnp.int32), rows).compile().as_text()
    assert text.count("tpu_custom_call") == 7
    for name in ("moe_gmm_swiglu", "moe_gmm_dswiglu", "moe_gmm", "moe_tgmm"):
        assert name in text
    for copied in (f"bf16[{g},{d},{f}]", f"bf16[{g},{f},{d}]"):
        assert copied not in text


def test_grouped_relu2_gradient_compiles_for_v5e(one_chip, monkeypatch):
    """The gateless expert op's six kernels at the state-space cell's
    widths (8 held experts of 2,688 x 1,856, a history's buffer of
    8,192 x 6 rows + a tile an expert), relu^2 and its derivative in
    their epilogues."""
    m, d, f, g = 49_152 + 8 * 512, 2688, 1856, 8
    monkeypatch.setattr(moe, "_interpret", lambda: False)

    def shape(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def gradient(rows, w_up, w_down, te, used, ct):
        out, back = jax.vjp(
            lambda *a: moe.grouped_relu2(*a, te, used, 512),
            rows, w_up, w_down)
        return out, back(ct)

    rows = shape((m, d), jnp.bfloat16)
    text = jax.jit(gradient).lower(
        rows, shape((g, d, f), jnp.float32), shape((g, f, d), jnp.float32),
        shape((m // 512,), jnp.int32), shape((1,), jnp.int32),
        rows).compile().as_text()
    assert text.count("tpu_custom_call") == 6
    for name in ("moe_gmm_relu2", "moe_gmm_drelu2", "moe_gmm", "moe_tgmm"):
        assert name in text
    for copied in (f"bf16[{g},{d},{f}]", f"bf16[{g},{f},{d}]"):
        assert copied not in text


def test_ssd_scan_gradient_compiles_for_v5e(one_chip, monkeypatch):
    """The scan op forward and backward at the cell's shapes, one
    history (the block stack maps a Mamba-2 block over histories): 64
    heads of 64, 8 groups of 128, 64 chunks of 128: two kernels (lane
    slices of 64, a state of (8, 64, 128) float32 carried in VMEM)."""
    monkeypatch.setattr(ssd, "_interpret", lambda: False)
    s, h, p, g, n = 8192, 64, 64, 8, 128

    def shape(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def gradient(*args):
        y, back = jax.vjp(lambda *a: ssd.ssd_scan(*a, 128), *args)
        return back(y)

    text = jax.jit(gradient).lower(
        shape((1, s, h, p), jnp.bfloat16), shape((1, s, h), jnp.float32),
        shape((h,), jnp.float32), shape((1, s, g, n), jnp.bfloat16),
        shape((1, s, g, n), jnp.bfloat16), shape((h,), jnp.float32),
    ).compile().as_text()
    assert text.count("tpu_custom_call") == 2
    assert "ssd_chunk_fwd" in text and "ssd_chunk_bwd" in text


# in_proj's output in the state-space cell: z 4,096 | x 4,096, B 1,024,
# C 1,024 | dt 64
ROWS_S, ROWS_DI, ROWS_GN, ROWS_H = 8192, 4096, 1024, 64


def _rows_shape(one_chip, *shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct((1, ROWS_S) + shape, dtype, sharding=one_chip)


def test_conv_silu_gradient_compiles_for_v5e(one_chip, monkeypatch):
    """The convolution's pass over the columns [4096, 10240) of (1, 8192,
    10304) float32, forward and backward: x, B and C as three bfloat16
    arrays from one call, their cotangents into one."""
    monkeypatch.setattr(ssm_rows, "_interpret", lambda: False)
    widths = (ROWS_DI, ROWS_GN, ROWS_GN)

    def gradient(u, w, bias, *cots):
        out, back = jax.vjp(lambda *a: ssm_rows.conv_silu(
            *a, ROWS_DI, widths, jnp.bfloat16), u, w, bias)
        return out, back(cots)

    text = jax.jit(gradient).lower(
        _rows_shape(one_chip, 2 * ROWS_DI + 2 * ROWS_GN + ROWS_H),
        jax.ShapeDtypeStruct((4, sum(widths)), jnp.float32,
                             sharding=one_chip),
        jax.ShapeDtypeStruct((sum(widths),), jnp.float32, sharding=one_chip),
        *(_rows_shape(one_chip, w, dtype=jnp.bfloat16) for w in widths),
    ).compile().as_text()
    assert text.count("tpu_custom_call") == 2
    assert "ssm_conv_silu_fwd" in text and "ssm_conv_silu_bwd" in text


def test_gated_norm_gradient_compiles_for_v5e(one_chip, monkeypatch):
    """The gated group norm's pass, 8 groups of 512 lanes, z read in the
    wide array: no (8192, 8, 512) view of anything."""
    monkeypatch.setattr(ssm_rows, "_interpret", lambda: False)

    def gradient(y, u, gain, cot):
        out, back = jax.vjp(lambda *a: ssm_rows.gated_norm(
            *a, 8, 1e-5, jnp.bfloat16), y, u, gain)
        return out, back(cot)

    text = jax.jit(gradient).lower(
        _rows_shape(one_chip, ROWS_DI),
        _rows_shape(one_chip, 2 * ROWS_DI + 2 * ROWS_GN + ROWS_H),
        jax.ShapeDtypeStruct((ROWS_DI,), jnp.float32, sharding=one_chip),
        _rows_shape(one_chip, ROWS_DI, dtype=jnp.bfloat16),
    ).compile().as_text()
    assert text.count("tpu_custom_call") == 2
    assert "ssm_gated_norm_fwd" in text and "ssm_gated_norm_bwd" in text
    assert "8,512]" not in text
