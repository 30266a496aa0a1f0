"""benchmark/harness/check_ssm.py at a tiny size on the CPU: the sound
program passes, and every faulty reference the limits are set against
fails at least one of them."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import engines_sequence as es
from benchmark.harness import cells, check_ssm
from benchmark.reference import ssm_moe_lm
from pio_tpu.models import seq_blocks
from pio_tpu.ops.moe import route_top_k
from pio_tpu.ops.ssd import ssd_scan

OVERLAY = cells.load_json(__file__.replace(
    "test_check_ssm.py", "rehearse/ssm-tiny.json"))
CONFIG = cells.merge(cells.load_json(
    cells.ROOT + "/benchmark/configs/nemotron-3-nano-ep16.json"),
    OVERLAY["config"])
CFG = es.block_spec_of(CONFIG)
# float32 operands on the program's side: the limits below are then those
# of the mathematics, and a fault of one part in a hundred shows
LIMITS = {"loss_logged_rel": {"max": 1e-6}, "loss_rel": {"max": 1e-5},
          **{f"grad_{what}_rel": {"max": 1e-3}
             for what in check_ssm.FAMILIES},
          "scan_probe_rel": {"max": 1e-5}, "router_probe_rel": {"max": 1e-5},
          "router_counts_rel": {"max": 0.01},
          "router_bias_abs": {"max": 1e-6}, "held_loss_rel": {"max": 1e-5},
          "held_below_step0": {"min": 0.2}}
STEPS, POSITIONS = 4, 40
EXPERT = 0        # the tiny router keeps every held expert busy


@pytest.fixture(scope="module")
def sides():
    spec = seq_blocks.BlockSpec.parse(CFG)
    length = POSITIONS + 1
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(seq_blocks, "COMPUTE", jnp.float32)
        mp.setattr(seq_blocks, "ATTN_BLOCK", 8)
        mp.setattr(seq_blocks, "MOE_TILE", 8)
        seqs = es.make_histories(2 * STEPS, length, CFG["vocab_size"] - 1,
                                 1.1, 7)
        tokens0 = jnp.asarray(seqs[:2])
        params0 = seq_blocks.init_params(spec, 7)
        loss_of = jax.jit(lambda p, t: seq_blocks.loss_and_counters(
            p, t, spec)[0])
        loss0, grads = jax.jit(jax.value_and_grad(loss_of))(params0, tokens0)
        optimizer, step = seq_blocks.make_train_step.__wrapped__(spec, 0.02)
        params = jax.tree_util.tree_map(jnp.copy, params0)
        state = optimizer.init(params)
        for n, batch in enumerate(jnp.asarray(seqs.reshape(STEPS, 2, length))):
            params, state, _, aux = step(params, state, batch)
            if n == 0:
                counts0 = np.asarray(aux["counts_all"])
                bias1 = check_ssm.router_biases(CFG, params)
        held = jnp.asarray(es.make_histories(
            2 * check_ssm.HELD_BATCHES, length, CFG["vocab_size"] - 1,
            1.1, 7, stream=1).reshape(-1, 2, length))
        host = jax.device_get(params)
        probes = {"router": check_ssm.router_probe(CFG, 7, host, tokens=256),
                  "scan": check_ssm.scan_probe(CFG, 7, host, POSITIONS)}
        experts, routed = spec.experts, []
        for bias in probes["router"]["bias"]:
            ids, w = route_top_k(
                jnp.asarray(probes["router"]["logits"]), experts.top_k,
                experts.norm_topk, experts.score, bias, experts.scale)
            routed.append(np.asarray(jnp.zeros((256, 16)).at[
                jnp.arange(256)[:, None], ids].set(w)))
        pr = probes["scan"]
        program = {
            "loss0": float(loss0), "logged_loss": float(loss0),
            "slices": check_ssm.gradient_slices(CFG, grads, EXPERT),
            "shape_faults": check_ssm.shape_faults(CFG, host),
            "counts0": counts0, "bias1": bias1, "steps": STEPS,
            "bias_model": check_ssm.router_biases(CFG, host),
            "router_probe": np.stack(routed),
            "scan_probe": np.asarray(ssd_scan(
                pr["x"][None], pr["dt"][None], pr["a"], pr["b"][None],
                pr["c"][None], pr["d"], spec.chunk_size)[0]),
            "held_losses": [float(loss_of(params, batch))
                            for batch in held]}
    reference = check_ssm.Reference(CFG)
    inputs = (lambda: params0, tokens0, lambda: params, held, EXPERT, probes)
    return program, reference, inputs, reference.numbers(*inputs)


def test_the_sound_program_passes(sides):
    program, _, _, sound = sides
    verdict = check_ssm.check(CFG, LIMITS, program, sound)
    assert verdict["correct"], verdict["compared"]
    by_slice = verdict["numbers"]["grad_rel_by_slice"]
    # two M blocks' seven, two routers (six blocks at this size), an
    # expert's and the shared expert's two each, the attention block's
    # four, head and embedding
    assert len(by_slice) == 2 * 7 + 2 + 4 + 4 + 2
    assert sorted({check_ssm.family(n) for n in by_slice}) == sorted(
        check_ssm.FAMILIES)
    assert verdict["numbers"]["router_bias_abs"] < 1e-7
    assert verdict["numbers"]["router_counts_rel"] == 0.0
    assert verdict["numbers"]["scan_probe_rel"] < 1e-6
    assert program["counts0"].shape == (2, 2, 16)
    assert np.abs(program["bias1"]).max() == pytest.approx(0.001)
    assert np.abs(program["bias_model"]).max() > 0.0019
    assert set(LIMITS) == set(CONFIG["check"]["limits"])


@pytest.mark.parametrize("name", sorted(check_ssm.FAULTS))
def test_a_faulty_reference_fails_a_limit(sides, name):
    """One compiled reference serves every fault: a fault is a number it
    takes as an argument."""
    program, reference, inputs, _ = sides
    verdict = check_ssm.check(
        CFG, LIMITS, program,
        reference.numbers(*inputs, check_ssm.FAULTS[name]))
    assert not verdict["correct"]
    assert any("FAILED" in line for line in verdict["compared"])


@pytest.mark.parametrize("name", ["carry in bfloat16",
                                  "running sums of dt A in bfloat16"])
def test_a_scan_kept_in_bfloat16_shows_in_the_scans_probe(sides, name):
    """In the step every product rounds its operands to bfloat16, which
    moves a gradient by more than a carry or a running sum kept in
    bfloat16 does: the probe, which hands the op float32 operands, reads
    the op's own arithmetic."""
    program, reference, inputs, sound = sides
    verdict = check_ssm.check(
        CFG, dict(LIMITS, **{f"grad_{what}_rel": {"max": 0.02}
                             for what in check_ssm.FAMILIES},
                  loss_rel={"max": 2e-4}), program,
        reference.numbers(*inputs, check_ssm.FAULTS[name]))
    failed = [line for line in verdict["compared"] if "FAILED" in line]
    assert [line[:11] for line in failed] == ["the scan op"]
    assert verdict["numbers"]["scan_probe_rel"] > 100 * check_ssm.check(
        CFG, LIMITS, program, sound)["numbers"]["scan_probe_rel"]


def test_a_selection_on_the_unbiased_score_shows_in_the_routers_probe(sides):
    """At step 0 every bias is zero: the gradients and the step-0 loss
    cannot see the fault. The probe routes seeded logits under the
    persisted biases."""
    program, reference, inputs, _ = sides
    verdict = check_ssm.check(
        CFG, LIMITS, program, reference.numbers(
            *inputs, check_ssm.FAULTS["top-6 on the unbiased score"]))
    failed = [line for line in verdict["compared"] if "FAILED" in line]
    assert any(line.startswith("routing weights") for line in failed)
    assert not any("step-0" in line for line in failed)


def test_a_model_of_the_wrong_shape_or_not_finite_fails(sides):
    program, _, inputs, sound = sides
    params = jax.device_get(inputs[2]())
    assert check_ssm.shape_faults(CFG, params) == []
    bad = jax.tree_util.tree_map(np.array, params)
    bad["layers"][0]["A_log"][0] = np.nan
    bad["layers"][1]["w_up"] = bad["layers"][1]["w_up"][:-1]
    faults = check_ssm.shape_faults(CFG, bad)
    assert len(faults) == 2 and "not finite" in " ".join(faults)
    del bad["layers"][5]["wq"]
    assert "tree differs" in check_ssm.shape_faults(CFG, bad)[0]
    assert not check_ssm.check(
        CFG, LIMITS, dict(program, shape_faults=faults), sound)["correct"]


@pytest.mark.parametrize("name", sorted(check_ssm.PROGRAM_FAULTS))
def test_wrong_counts_or_a_bias_that_is_not_the_rules_fail(sides, name):
    program, _, _, sound = sides
    wrong = check_ssm.PROGRAM_FAULTS[name](program, CFG)
    verdict = check_ssm.check(CFG, LIMITS, wrong, sound)
    failed = [line for line in verdict["compared"] if "FAILED" in line]
    assert not verdict["correct"]
    assert all(line.startswith(("step-0 token counts", "the bias"))
               for line in failed)


def test_a_job_that_learned_nothing_fails(sides):
    """The initial weights persisted: the held batches' loss is the
    step-0 loss's size, by the program and by the reference alike."""
    program, reference, inputs, _ = sides
    params0, tokens0, _, held, _, probes = inputs
    untrained = reference.numbers(params0, tokens0, params0, held, EXPERT,
                                  probes)
    verdict = check_ssm.check(
        CFG, LIMITS, dict(program, held_losses=untrained["held_losses"]),
        untrained)
    failed = [line for line in verdict["compared"] if "FAILED" in line]
    assert [line[:15] for line in failed] == ["held-batch loss"]


def test_a_reading_that_is_not_a_number_fails(sides):
    program, _, _, sound = sides
    nan = dict(sound, loss0=float("nan"))
    assert not check_ssm.check(CFG, LIMITS, program, nan)["correct"]


def test_the_reference_is_plain():
    """float32 at the highest matmul precision, no import of the program
    or of the benchmark, no kernel, no chunked algebra: the scan is a
    `lax.scan` over positions, and the chunk size is read by the faults
    alone."""
    import ast
    import inspect

    source = inspect.getsource(ssm_moe_lm)
    tree = ast.parse(source)
    names = [a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
             for a in n.names] + [n.module for n in ast.walk(tree)
                                  if isinstance(n, ast.ImportFrom)]
    assert not [n for n in names if n.startswith(("pio_tpu", "benchmark"))]
    assert 'precision="highest"' in inspect.getsource(ssm_moe_lm._dot)
    assert "pallas" not in source and "cumsum" not in source
    scan = inspect.getsource(ssm_moe_lm.scan)
    assert "jax.lax.scan(step" in scan and "einsum" not in scan
    # every use of the chunk in the recurrence is a fault's
    flags = ssm_moe_lm.with_faults(CFG)
    args = [np.float32(np.random.default_rng(0).standard_normal(s))
            for s in ((24, 4, 2), (24, 4), (4,), (24, 2, 3), (24, 2, 3), (4,))]
    args[1], args[2] = np.abs(args[1]) * 0.1, -np.abs(args[2])
    np.testing.assert_array_equal(ssm_moe_lm.scan(*args, flags, 8),
                                  ssm_moe_lm.scan(*args, flags, 3))
    with pytest.raises(ValueError, match="no such fault"):
        ssm_moe_lm.with_faults(CFG, {"chunk": 1})
