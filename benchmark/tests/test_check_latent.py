"""benchmark/harness/check_latent.py at a tiny size on the CPU: the sound
program passes, and every faulty reference the limits are set against
fails at least one of them."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import engines_sequence as es
from benchmark.harness import cells, check_latent
from benchmark.reference import latent_moe_lm
from pio_tpu.models import seq_blocks
from pio_tpu.ops.moe import route_top_k

OVERLAY = cells.load_json(__file__.replace(
    "test_check_latent.py", "rehearse/latent-tiny.json"))
CONFIG = cells.merge(cells.load_json(
    cells.ROOT + "/benchmark/configs/glm-4.7-flash-ep8.json"),
    OVERLAY["config"])
CFG = es.block_spec_of(CONFIG)
# float32 operands on the program's side: the limits below are then those
# of the mathematics, and a fault of one part in a hundred shows
LIMITS = {"loss_logged_rel": {"max": 1e-6}, "loss_main_rel": {"max": 1e-5},
          "loss_mtp_rel": {"max": 1e-5}, "grad_router_rel": {"max": 1e-3},
          "grad_expert_rel": {"max": 1e-3}, "grad_latent_rel": {"max": 1e-3},
          "grad_dense_rel": {"max": 1e-3}, "held_loss_rel": {"max": 1e-5},
          "held_below_step0": {"min": 0.05},
          "router_probe_rel": {"max": 1e-5},
          "router_counts_rel": {"max": 0.01},
          "router_bias_abs": {"max": 1e-6}}
# at this size a head is 12 + 4 wide and three experts are chosen
FAULTS = dict(check_latent.FAULTS, **{
    "scale 1/sqrt(192)": {"scale_dim": 12}, "top-3 for top-4": {"top_k": 2}})
STEPS, LENGTH = 4, 42


@pytest.fixture(scope="module")
def sides():
    spec = seq_blocks.BlockSpec.parse(CFG)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(seq_blocks, "COMPUTE", jnp.float32)
        mp.setattr(seq_blocks, "ATTN_BLOCK", 16)
        mp.setattr(seq_blocks, "MOE_TILE", 8)
        seqs = es.make_histories(2 * STEPS, LENGTH, CFG["vocab_size"] - 1,
                                 1.1, 7)
        tokens0 = jnp.asarray(seqs[:2])
        params0 = seq_blocks.init_params(spec, 7)
        (_, aux), grads = jax.value_and_grad(
            seq_blocks.loss_and_counters, has_aux=True)(
                params0, tokens0, spec)
        main0, mtp0 = (float(x) for x in aux["losses"])
        optimizer, step = seq_blocks.make_train_step.__wrapped__(spec, 0.02)
        params = jax.tree_util.tree_map(jnp.copy, params0)
        state = optimizer.init(params)
        for n, batch in enumerate(jnp.asarray(seqs.reshape(STEPS, 2, LENGTH))):
            params, state, _, aux = step(params, state, batch)
            if n == 0:
                counts0 = np.asarray(aux["counts_all"])
                bias1 = check_latent.router_biases(CFG, params)
        held = jnp.asarray(es.make_histories(
            2 * check_latent.HELD_BATCHES, LENGTH, CFG["vocab_size"] - 1,
            1.1, 7, stream=1).reshape(-1, 2, LENGTH))
        host = jax.device_get(params)
        probe = check_latent.router_probe(CFG, 7, host, tokens=256)
        experts = spec.experts
        routed = []
        for bias in probe["bias"]:
            ids, w = route_top_k(jnp.asarray(probe["logits"]), experts.top_k,
                                 experts.norm_topk, experts.score, bias,
                                 experts.scale)
            routed.append(np.asarray(jnp.zeros((256, 8)).at[
                jnp.arange(256)[:, None], ids].set(w)))
        program = {
            "loss_main0": main0, "loss_mtp0": mtp0, "logged_main": main0,
            "logged_mtp": mtp0,
            "slices": check_latent.gradient_slices(CFG, grads, EXPERT),
            "shape_faults": check_latent.shape_faults(CFG, host),
            "counts0": counts0, "bias1": bias1, "steps": STEPS,
            "bias_model": check_latent.router_biases(CFG, host),
            "router_probe": np.stack(routed),
            "held_losses": [float(seq_blocks.loss_and_counters(
                params, batch, spec)[0]) for batch in held]}
    return program, (lambda: params0, tokens0, lambda: params, held, EXPERT,
                     probe)


EXPERT = 0        # the tiny router keeps every held expert busy


def test_the_sound_program_passes(sides):
    program, inputs = sides
    verdict = check_latent.check(
        CFG, LIMITS, program, check_latent.reference_numbers(CFG, *inputs))
    assert verdict["correct"], verdict["compared"]
    by_slice = verdict["numbers"]["grad_rel_by_slice"]
    assert len(by_slice) == 3 + 6 + 8 + 2    # routers, experts, latent, dense
    assert sorted({check_latent.family(n) for n in by_slice}) == [
        "dense", "expert", "latent", "router"]
    assert verdict["numbers"]["router_bias_abs"] < 1e-7
    assert verdict["numbers"]["router_counts_rel"] == 0.0
    assert program["counts0"].shape == (3, 2, 8)
    assert np.abs(program["bias1"]).max() == pytest.approx(0.001)
    assert np.abs(program["bias_model"]).max() > 0.0019


@pytest.mark.parametrize("name", sorted(FAULTS))
def test_a_faulty_reference_fails_a_limit(sides, name):
    program, inputs = sides
    verdict = check_latent.check(
        CFG, LIMITS, program,
        check_latent.reference_numbers(CFG, *inputs, FAULTS[name]))
    assert not verdict["correct"]
    assert any("FAILED" in line for line in verdict["compared"])


def test_weights_from_the_biased_score_show_in_the_routers_probe(sides):
    """At step 0 every bias is zero: the gradients and the step-0 losses
    cannot see the fault. The probe, which routes seeded logits under the
    persisted biases, sees the weights themselves."""
    program, inputs = sides
    verdict = check_latent.check(
        CFG, LIMITS, program, check_latent.reference_numbers(
            CFG, *inputs, FAULTS["weights from score + bias"]))
    failed = [line for line in verdict["compared"] if "FAILED" in line]
    assert any(line.startswith("routing weights") for line in failed)
    assert not any("step-0" in line for line in failed)
    sound = check_latent.check(
        CFG, LIMITS, program,
        check_latent.reference_numbers(CFG, *inputs))["numbers"]
    assert sound["router_probe_rel"] < 1e-6
    assert verdict["numbers"]["router_probe_rel"] > 1e-4


def test_a_model_of_the_wrong_shape_or_not_finite_fails(sides):
    program, inputs = sides
    params = jax.device_get(inputs[2]())
    assert check_latent.shape_faults(CFG, params) == []
    bad = jax.tree_util.tree_map(np.array, params)
    bad["layers"][1]["wq_a"][0, 0] = np.nan
    bad["mtp"]["eh_proj"] = bad["mtp"]["eh_proj"][:-1]
    faults = check_latent.shape_faults(CFG, bad)
    assert len(faults) == 2 and "not finite" in " ".join(faults)
    del bad["mtp"]
    assert "tree differs" in check_latent.shape_faults(CFG, bad)[0]
    verdict = check_latent.check(
        CFG, LIMITS, dict(program, shape_faults=faults),
        check_latent.reference_numbers(CFG, *inputs))
    assert not verdict["correct"]


@pytest.mark.parametrize("name", sorted(check_latent.PROGRAM_FAULTS))
def test_wrong_counts_or_a_bias_that_is_not_the_rules_fail(sides, name):
    """The program's counts are held to the reference's routing and its
    bias to the reference's rule: nothing the program says of itself."""
    program, inputs = sides
    wrong = check_latent.PROGRAM_FAULTS[name](program, CFG)
    verdict = check_latent.check(
        CFG, LIMITS, wrong, check_latent.reference_numbers(CFG, *inputs))
    failed = [line for line in verdict["compared"] if "FAILED" in line]
    assert not verdict["correct"]
    assert failed[0].startswith(
        "step-0 token counts" if name.startswith("counts") else "the bias")
    assert all(line.startswith(("step-0 token counts", "the bias"))
               for line in failed)


def test_the_distances_of_a_bias_from_the_rule():
    counts = np.array([[3, 1, 2, 2], [0, 4, 4, 0]])
    moved = 0.001 * np.array([[-1.0, 1, 0, 0], [1, -1, -1, 1]])
    assert check_latent.bias_fault(CFG, counts, moved, 5 * moved, 5) < 1e-12
    assert check_latent.bias_fault(       # a move the wrong way
        CFG, counts, -moved, 5 * moved, 5) == pytest.approx(0.002)
    assert check_latent.bias_fault(       # a sixth move in five steps
        CFG, counts, moved, 6 * moved, 5) == pytest.approx(0.001)
    assert check_latent.bias_fault(       # off the whole moves
        CFG, counts, moved, 5 * moved + 3e-4, 5) == pytest.approx(3e-4)
    assert check_latent.bias_fault(CFG, counts, moved[:1], moved, 5) == float(
        "inf")


def test_a_job_that_learned_nothing_fails(sides):
    program, inputs = sides
    params0, tokens0, _, held, _, probe = inputs
    stuck = dict(program, held_losses=[float(seq_blocks.loss_and_counters(
        params0(), batch, seq_blocks.BlockSpec.parse(CFG))[0])
        for batch in held])
    verdict = check_latent.check(
        CFG, dict(LIMITS, held_loss_rel={"max": 1.0}), stuck,
        check_latent.reference_numbers(CFG, params0, tokens0, params0, held,
                                       EXPERT, probe))
    assert not verdict["correct"]


def test_the_reference_imports_nothing_of_the_program():
    import ast
    import inspect

    tree = ast.parse(inspect.getsource(latent_moe_lm))
    names = [a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
             for a in n.names] + [n.module for n in ast.walk(tree)
                                  if isinstance(n, ast.ImportFrom)]
    assert not [n for n in names if n.startswith(("pio_tpu", "benchmark"))]
    assert 'precision="highest"' in inspect.getsource(latent_moe_lm._dot)
