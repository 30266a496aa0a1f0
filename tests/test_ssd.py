"""ops/ssd.py `ssd_scan`: the chunked state-space scan against the
recurrence itself, a position at a time (`ssd_reference`, which shares
no algebra with it): forward and all six gradients, at two chunk sizes
of a small shape and at the state-space cell's group shape (eight heads
of 64 a group, state 128, chunks of 128), with A and dt at both ends of
the ranges a Mamba-2 mixer draws them in (the Pallas kernels in interpret
mode)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pio_tpu.ops import ssd

B, S, H, P, G, N = 2, 32, 8, 8, 2, 16
# the state-space cell's group: eight heads of 64 share a group's B and C
# of 128, chunks of 128; two groups, one history of 512 positions and two
# of 256
CELL = {"cell, one history": (1, 512, 16, 64, 2, 128),
        "cell, two histories": (2, 256, 16, 64, 2, 128)}
NAMES = ("x", "dt", "A", "B", "C", "D")
# (A, dt): uniform in [1, 16] and log-uniform in [0.001, 0.1] as drawn,
# and the four corners: the slowest head remembers every position of a
# history (exp(-0.001) a step), the fastest forgets in one (exp(-1.6))
RANGES = {"drawn": None, "slow": (1.0, 0.001), "fast": (16.0, 0.1),
          "long steps": (1.0, 0.1), "short steps": (16.0, 0.001)}
CASES = [("drawn", 8, None), ("drawn", 16, None), ("slow", 8, None),
         ("fast", 16, None), ("long steps", 8, None),
         ("short steps", 16, None)] + [("drawn", 128, at) for at in CELL] + [
             ("slow", 128, "cell, one history"),
             ("fast", 128, "cell, two histories")]


def inputs(seed: int, ends=None, dtype=jnp.float32, at=None):
    b, s, h, p, g, n = CELL[at] if at else (B, S, H, P, G, N)
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    if ends is None:
        a = -jax.random.uniform(ks[2], (h,), minval=1.0, maxval=16.0)
        dt = jnp.exp(jax.random.uniform(
            ks[1], (b, s, h), minval=np.log(0.001), maxval=np.log(0.1)))
    else:
        a = jnp.full((h,), -ends[0])
        dt = jnp.full((b, s, h), ends[1])
    args = (jax.random.normal(ks[0], (b, s, h, p)).astype(dtype), dt, a,
            jax.random.normal(ks[3], (b, s, g, n)).astype(dtype),
            jax.random.normal(ks[4], (b, s, g, n)).astype(dtype),
            jax.random.normal(ks[5], (h,)))
    return args, jax.random.normal(ks[6], (b, s, h, p))


def gradients(fn, args, cot):
    return jax.grad(lambda *a: jnp.sum(fn(*a) * cot),
                    argnums=range(6))(*args)


@pytest.mark.parametrize("ends,chunk,at", CASES)
def test_forward_equals_the_recurrence(chunk, ends, at):
    args, _ = inputs(1, RANGES[ends], at=at)
    np.testing.assert_allclose(
        ssd.ssd_scan(*args, chunk), ssd.ssd_reference(*args),
        rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("ends,chunk,at", CASES)
def test_all_six_gradients_equal_the_recurrences(chunk, ends, at):
    args, cot = inputs(2, RANGES[ends], at=at)
    mine = gradients(lambda *a: ssd.ssd_scan(*a, chunk), args, cot)
    theirs = gradients(ssd.ssd_reference, args, cot)
    for name, a, b in zip(NAMES, mine, theirs):
        scale = float(jnp.abs(b).max())
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=2e-5 * scale,
                                   err_msg=name)


def test_the_result_does_not_depend_on_the_chunk():
    args, cot = inputs(3)
    ys = [ssd.ssd_scan(*args, chunk) for chunk in (4, 8, 32)]
    for y in ys[1:]:
        np.testing.assert_allclose(y, ys[0], rtol=2e-5, atol=2e-5)


def test_a_later_position_changes_no_earlier_output():
    """Causality across a chunk boundary: position 19 (chunk 2 of 4)
    moves outputs 19.. and nothing before."""
    args, _ = inputs(4)
    x, dt, a, b_mat, c_mat, d = args
    moved = (x.at[:, 19].add(1.0), dt.at[:, 19].mul(2.0), a,
             b_mat.at[:, 19].add(1.0), c_mat.at[:, 19].add(1.0), d)
    before, after = ssd.ssd_scan(*args, 8), ssd.ssd_scan(*moved, 8)
    np.testing.assert_array_equal(before[:, :19], after[:, :19])
    assert float(jnp.abs(before[:, 19:] - after[:, 19:]).min(axis=(0, 2, 3)
                                                             ).min()) > 0


def test_the_state_is_carried_over_every_chunk():
    """A slow head's output at the last position still holds the first
    position's input: what a dropped chunk carry would lose."""
    args, _ = inputs(5, RANGES["slow"])
    moved = (args[0].at[:, 0].add(1.0),) + args[1:]
    delta = ssd.ssd_scan(*moved, 8)[:, -1] - ssd.ssd_scan(*args, 8)[:, -1]
    assert float(jnp.abs(delta).max()) > 1e-4


@pytest.mark.parametrize("batch,chunk,at",
                         [(1, 8, None), (3, 8, None)]
                         + [(1, 128, "cell, one history"),
                            (2, 128, "cell, two histories")])
def test_on_bfloat16_operands_it_stays_near_the_recurrence(batch, chunk, at):
    """As the block stack calls the op: x, B and C in bfloat16, the
    products on bfloat16 operands, everything else float32. What the
    operands' rounding moves is a few parts in a thousand."""
    args, cot = inputs(6, dtype=jnp.bfloat16, at=at)
    args = tuple(v[:batch] if v.ndim > 1 else v for v in args)
    cot = cot[:batch]
    mine = (ssd.ssd_scan(*args, chunk),) + gradients(
        lambda *a: ssd.ssd_scan(*a, chunk), args, cot)
    theirs = (ssd.ssd_reference(*args),) + gradients(
        ssd.ssd_reference, args, cot)
    for name, a, b in zip(("y",) + NAMES, mine, theirs):
        a, b = np.float32(a), np.float32(b)
        assert np.linalg.norm(a - b) <= 0.01 * np.linalg.norm(b), name


def pallas_calls(jaxpr):
    """Every `pallas_call` equation of a jaxpr and of the jaxprs its
    equations hold."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from pallas_calls(sub)


def test_no_kernel_operand_is_narrower_than_the_lanes():
    """At the published widths (64 heads of 64 in 8 groups of 128, chunks
    of 128) every operand and result of the two kernels has a minor
    dimension of 128 or more: a (position, head) number goes in and comes
    out along the positions, so no array 8 wide is padded sixteenfold to
    the 128 lanes in HBM."""
    b, s, h, p, g, n = 2, 256, 64, 64, 8, 128
    shapes = [jax.ShapeDtypeStruct(shape, dtype) for shape, dtype in (
        ((b, s, h, p), jnp.bfloat16), ((b, s, h), jnp.float32),
        ((h,), jnp.float32), ((b, s, g, n), jnp.bfloat16),
        ((b, s, g, n), jnp.bfloat16), ((h,), jnp.float32))]
    jaxpr = jax.make_jaxpr(jax.value_and_grad(
        lambda *a: jnp.sum(ssd.ssd_scan(*a, 128)), argnums=range(6)))(*shapes)
    calls = list(pallas_calls(jaxpr.jaxpr))
    assert sorted(c.params["name"] for c in calls) == [
        "ssd_chunk_bwd", "ssd_chunk_fwd"]
    for call in calls:
        for v in list(call.invars) + list(call.outvars):
            assert v.aval.shape[-1] >= 128, (call.params["name"], v.aval)


def test_the_chunk_states_are_kept_by_name():
    """The forward pass names the states entering each chunk: a
    checkpoint that keeps that name hands them to the backward pass and
    the carry is not run again."""
    args, cot = inputs(7)
    policy = jax.checkpoint_policies.save_only_these_names(ssd.KEPT_STATES)

    def loss(*a):
        return jnp.sum(jax.checkpoint(
            lambda *b: ssd.ssd_scan(*b, 8), policy=policy)(*a) * cot)

    text = str(jax.make_jaxpr(jax.grad(loss, argnums=range(6)))(*args))
    assert ssd.KEPT_STATES in text
    plain = gradients(lambda *a: ssd.ssd_scan(*a, 8), args, cot)
    kept = jax.grad(loss, argnums=range(6))(*args)
    for a, b in zip(kept, plain):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("what", ["chunk", "groups"])
def test_sizes_that_do_not_divide_are_refused(what):
    args, _ = inputs(8)
    if what == "groups":
        args = args[:3] + (args[3][:, :, :1].repeat(3, 2),
                           args[4][:, :, :1].repeat(3, 2)) + args[5:]
    with pytest.raises(ValueError, match="must divide"):
        ssd.ssd_scan(*args, 5 if what == "chunk" else 8)
