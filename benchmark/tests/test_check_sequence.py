"""benchmark/harness/check_sequence.py at a tiny size on the CPU: the
sound program passes, and every faulty reference the limits are set
against fails at least one of them."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import engines_sequence as es
from benchmark.harness import cells, check_sequence
from pio_tpu.models import seq_blocks
from pio_tpu.ops.attention import banded_flash_attention

OVERLAY = cells.load_json(__file__.replace(
    "test_check_sequence.py", "rehearse/sequence-tiny.json"))
CONFIG = cells.merge(cells.load_json(
    cells.ROOT + "/benchmark/configs/mellum2-12b-ep4.json"),
    OVERLAY["config"])
CFG = es.block_spec_of(CONFIG)
# float32 operands on the program's side: the limits below are then those
# of the mathematics, and a fault of one part in a hundred shows
LIMITS = {"loss_logged_rel": {"max": 1e-6}, "loss_step0_rel": {"max": 1e-5},
          "grad_router_rel": {"max": 1e-3}, "grad_expert_rel": {"max": 1e-3},
          "grad_dense_rel": {"max": 1e-3},
          "band_edge_rel": {"max": 1e-3}, "held_loss_rel": {"max": 1e-5},
          "held_below_step0": {"min": 0.05}}
# at this size a window of 1025 is the whole history: one more key is 13
FAULTS = dict(check_sequence.FAULTS, **{"window of 1025": {"window": 13},
                                        "top-7 for top-8": {"top_k": 2}})


@pytest.fixture(scope="module")
def sides():
    spec = seq_blocks.BlockSpec.parse(CFG)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(seq_blocks, "COMPUTE", jnp.float32)
        mp.setattr(seq_blocks, "ATTN_BLOCK", 16)
        mp.setattr(seq_blocks, "MOE_TILE", 8)
        seqs = es.make_histories(8, 41, CFG["vocab_size"] - 1, 1.1, 7)
        tokens0 = jnp.asarray(seqs[:2])
        params0 = seq_blocks.init_params(spec, 7)
        (loss0, _), grads = jax.value_and_grad(
            seq_blocks.loss_and_counters, has_aux=True)(
                params0, tokens0, spec)
        optimizer, step = seq_blocks.make_train_step.__wrapped__(spec, 0.02)
        params, state = params0, optimizer.init(params0)
        params = jax.tree_util.tree_map(jnp.copy, params)
        for batch in jnp.asarray(seqs.reshape(4, 2, 41)):
            params, state, _, _ = step(params, state, batch)
        held = jnp.asarray(es.make_histories(
            2 * check_sequence.HELD_BATCHES, 41, CFG["vocab_size"] - 1, 1.1,
            7, stream=1).reshape(-1, 2, 41))
        probe = check_sequence.band_edge_probe(CFG, 40, 16, 7)
        _, dk, dv = jax.vjp(
            lambda q, k, v: banded_flash_attention(
                q, k, v, probe["window"], None, 16, 16),
            *(jnp.asarray(probe[n]) for n in "qkv"))[1](
                jnp.asarray(probe["ct"]))
        program = {
            "loss0": float(loss0), "loss_logged": float(loss0),
            "slices": check_sequence.gradient_slices(CFG, grads, EXPERT),
            "shape_faults": check_sequence.shape_faults(
                CFG, jax.device_get(params)),
            "held_losses": [float(seq_blocks.loss_and_counters(
                params, batch, spec)[0]) for batch in held],
            "band_edge": check_sequence.band_edge_slice(dk, dv, probe)}
    return program, (params0, tokens0, params, held, EXPERT, probe)


EXPERT = 0        # the tiny router keeps every held expert busy


def test_the_sound_program_passes(sides):
    program, inputs = sides
    verdict = check_sequence.check(
        CFG, LIMITS, program, check_sequence.reference_numbers(CFG, *inputs))
    assert verdict["correct"], verdict["compared"]
    assert len(verdict["numbers"]["grad_rel_by_slice"]) == 12
    assert check_sequence.busiest_expert(
        {"layers": [{}, {"w_down": np.eye(4).reshape(4, 2, 2) * [[[1]], [[3]], [[2]], [[0]]]}]}) == 1


@pytest.mark.parametrize("name", sorted(FAULTS))
def test_a_faulty_reference_fails_a_limit(sides, name):
    program, inputs = sides
    verdict = check_sequence.check(
        CFG, LIMITS, program,
        check_sequence.reference_numbers(CFG, *inputs, FAULTS[name]))
    assert not verdict["correct"]
    assert any("FAILED" in line for line in verdict["compared"])


def test_a_model_of_the_wrong_shape_or_not_finite_fails(sides):
    program, inputs = sides
    params = jax.device_get(inputs[2])
    assert check_sequence.shape_faults(CFG, params) == []
    bad = jax.tree_util.tree_map(np.array, params)
    bad["layers"][1]["wq"][0, 0] = np.nan
    bad["head"] = bad["head"][:-1]
    faults = check_sequence.shape_faults(CFG, bad)
    assert len(faults) == 2 and "not finite" in " ".join(faults)
    verdict = check_sequence.check(
        CFG, LIMITS, dict(program, shape_faults=faults),
        check_sequence.reference_numbers(CFG, *inputs))
    assert not verdict["correct"]


def test_a_job_that_learned_nothing_fails(sides):
    program, inputs = sides
    params0, tokens0, _, held, _, probe = inputs
    stuck = dict(program, held_losses=[float(seq_blocks.loss_and_counters(
        params0, batch, seq_blocks.BlockSpec.parse(CFG))[0])
        for batch in held])
    verdict = check_sequence.check(
        CFG, dict(LIMITS, held_loss_rel={"max": 1.0}), stuck,
        check_sequence.reference_numbers(CFG, params0, tokens0, params0,
                                         held, EXPERT, probe))
    assert not verdict["correct"]


def test_the_band_edge_sees_one_key_of_the_window(sides):
    """A window one key too wide moves the model's gradients by one key
    in a window; the band-edge comparison loses half its numbers."""
    program, inputs = sides
    sound = check_sequence.check(
        CFG, LIMITS, program,
        check_sequence.reference_numbers(CFG, *inputs))["numbers"]
    wide = check_sequence.check(
        CFG, LIMITS, program, check_sequence.reference_numbers(
            CFG, *inputs, FAULTS["window of 1025"]))["numbers"]
    assert sound["band_edge_rel"] < 1e-4 < 0.3 < wide["band_edge_rel"]


@pytest.mark.parametrize("seq_len,window,block", [
    (8192, 1024, 512), (40, 12, 16), (100, 12, 16), (640, 64, 128)])
def test_edge_rows_are_more_than_a_window_apart(seq_len, window, block):
    rows = check_sequence.edge_rows(seq_len, window, block)
    assert rows[-1] == seq_len - 1 and rows[0] >= window
    assert all(b - a > window for a, b in zip(rows, rows[1:]))
    if len(rows) > 2:      # some last keys inside the band open a block
        assert any((r - window + 1) % block == 0 for r in rows)
