"""benchmark/harness/check_loop.py at a tiny size on the CPU: the sound
program passes, and every faulty reference the limits are set against
fails at least one of them."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import engines_sequence as es
from benchmark.harness import cells, check_loop
from benchmark.reference import looped_lm
from pio_tpu.models import seq_blocks

OVERLAY = cells.load_json(__file__.replace(
    "test_check_loop.py", "rehearse/loop-tiny.json"))
CONFIG = cells.merge(cells.load_json(
    cells.ROOT + "/benchmark/configs/ouro-2.6b-l4.json"), OVERLAY["config"])
CFG = es.block_spec_of(CONFIG)
# float32 operands on the program's side: the limits below are then those
# of the mathematics, and a fault of one part in a hundred shows
LIMITS = {"loss_logged_rel": {"max": 1e-6}, "loss_step0_rel": {"max": 1e-5},
          "exit_loss_rel": {"max": 1e-5}, "exit_mass_abs": {"max": 1e-5},
          "grad_layer_rel": {"max": 1e-3}, "grad_dense_rel": {"max": 1e-3},
          "grad_gate_rel": {"max": 1e-3}, "held_loss_rel": {"max": 1e-5},
          "held_below_step0": {"min": 0.05},
          "exit_probe_abs": {"max": 1e-5},
          "held_stated_rel": {"max": 1e-5}}
# at this size the loop has three passes: one fewer is two
FAULTS = dict(check_loop.FAULTS,
              **{"three passes for four": {"loop_steps": 2}})
STEPS, LENGTH, T, L = 4, 97, 3, 2


@pytest.fixture(autouse=True)
def float32_is_the_stated_precision(monkeypatch):
    """The program below runs float32 operands: so does the reference
    `stated_numbers` makes."""
    monkeypatch.setattr(check_loop, "STATED", {})


def _check(program, inputs, faults=None, limits=LIMITS):
    return check_loop.check(
        CFG, limits, program,
        check_loop.reference_numbers(CFG, *inputs, faults),
        check_loop.stated_numbers(CFG, *inputs[2:4], faults))


@pytest.fixture(scope="module")
def sides():
    spec = seq_blocks.BlockSpec.parse(CFG)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(seq_blocks, "COMPUTE", jnp.float32)
        mp.setattr(seq_blocks, "ATTN_BLOCK", 32)
        seqs = es.make_histories(2 * STEPS, LENGTH, CFG["vocab_size"] - 1,
                                 1.1, 7)
        tokens0 = jnp.asarray(seqs[:2])
        params0 = seq_blocks.init_params(spec, 7)
        (loss0, aux), grads = jax.value_and_grad(
            seq_blocks.loss_and_counters, has_aux=True)(
                params0, tokens0, spec)
        optimizer, step = seq_blocks.make_train_step.__wrapped__(spec, 0.02)
        params = jax.tree_util.tree_map(jnp.copy, params0)
        state = optimizer.init(params)
        for batch in jnp.asarray(seqs.reshape(STEPS, 2, LENGTH)):
            params, state, _, _ = step(params, state, batch)
        held = jnp.asarray(es.make_histories(
            2 * check_loop.HELD_BATCHES, LENGTH, CFG["vocab_size"] - 1,
            1.1, 7, stream=1).reshape(-1, 2, LENGTH))
        exits = [float(x) for x in aux["exit_losses"]]
        probe = check_loop.exit_probe(CFG, 7, tokens=256)
        program = {
            "loss0": float(loss0), "loss_logged": float(loss0),
            "exit_losses0": exits, "exit_losses_logged": exits,
            "exit_mass0": [float(x) for x in aux["exit_mass"]],
            "slices": check_loop.gradient_slices(CFG, grads),
            "shape_faults": check_loop.shape_faults(
                CFG, jax.device_get(params)),
            "job_faults": [],
            "exit_probe": np.asarray(seq_blocks.exit_probabilities(
                seq_blocks.exit_gate_logits(params, probe))),
            "held_losses": [], "held_exit_losses": []}
        for batch in held:
            loss, aux = seq_blocks.loss_and_counters(params, batch, spec)
            program["held_losses"].append(float(loss))
            program["held_exit_losses"].append(
                [float(x) for x in aux["exit_losses"]])
    return program, (lambda: params0, tokens0, lambda: params, held, probe)


def test_the_sound_program_passes(sides):
    program, inputs = sides
    verdict = _check(program, inputs)
    assert verdict["correct"], verdict["compared"]
    by_slice = verdict["numbers"]["grad_rel_by_slice"]
    assert len(by_slice) == 6 + 2 + 2       # two layers' three, dense, gate
    assert sorted({check_loop.family(n) for n in by_slice}) == [
        "dense", "gate", "layer"]
    assert len(verdict["compared"]) == 13
    assert verdict["numbers"]["exit_probe_abs"] < 1e-6
    assert sum(program["exit_mass0"]) == pytest.approx(1.0, abs=1e-6)


def test_the_gates_gradient_error_is_over_the_size_of_its_terms(sides):
    program, inputs = sides
    reference = check_loop.reference_numbers(CFG, *inputs)
    terms = reference["gate_terms"]
    assert sorted(terms) == ["exit_bias", "exit_gate"]
    # a seed whose terms all but cancel in the bias's sum: the same error
    # is then a hundred times the sum, and what the limit holds has not
    # moved
    want = reference["slices"]["exit_bias"]
    shift = 0.99 * want
    near = dict(reference, slices=dict(reference["slices"],
                                       exit_bias=want - shift))
    moved = dict(program, slices=dict(
        program["slices"], exit_bias=program["slices"]["exit_bias"] - shift))
    stated = check_loop.stated_numbers(CFG, *inputs[2:4])
    a, b = (check_loop.check(CFG, LIMITS, p, r, stated)["numbers"]
            for p, r in ((program, reference), (moved, near)))
    assert b["gate_rel_to_sum"]["exit_bias"] == pytest.approx(
        100 * a["gate_rel_to_sum"]["exit_bias"], rel=1e-3)
    assert b["grad_rel_by_slice"]["exit_bias"] == pytest.approx(
        a["grad_rel_by_slice"]["exit_bias"], rel=1e-3)
    assert a["grad_rel_by_slice"]["exit_bias"] == pytest.approx(
        abs(float(program["slices"]["exit_bias"][0] - want[0]))
        / terms["exit_bias"], rel=1e-6)
    # the other families stay relative to the reference's own norm
    assert a["grad_rel_by_slice"]["layer0.wq"] == pytest.approx(
        check_loop.relative_error(program["slices"]["layer0.wq"],
                                  reference["slices"]["layer0.wq"]))


@pytest.mark.parametrize("name", sorted(FAULTS))
def test_a_faulty_reference_fails_a_limit(sides, name):
    program, inputs = sides
    verdict = _check(program, inputs, FAULTS[name])
    assert not verdict["correct"]
    assert any("FAILED" in line for line in verdict["compared"])


@pytest.mark.parametrize("name,line", [
    ("the last exit gated", "step-0 exit masses"),
    ("the layers' gradient from the last pass", "step-0 gradients of 6 layer"),
    ("three passes for four", "step-0 exit losses"),
    ("bfloat16 accumulation", "exit masses of 256 seeded states"),
    ("bfloat16 accumulation", "persisted model's loss and exit losses"),
])
def test_a_fault_fails_the_limit_that_answers_for_it(sides, name, line):
    program, inputs = sides
    verdict = _check(program, inputs, FAULTS[name])
    failed = [l for l in verdict["compared"] if "FAILED" in l]
    assert any(l.startswith(line) for l in failed), failed
    if name.startswith("the layers"):
        # the loss and the exits are the sound ones: the gradients alone
        assert all(l.startswith("step-0 gradients") for l in failed)


def test_a_model_of_the_wrong_shape_or_not_finite_fails(sides):
    program, inputs = sides
    params = jax.device_get(inputs[2]())
    assert check_loop.shape_faults(CFG, params) == []
    bad = jax.tree_util.tree_map(np.array, params)
    bad["layers"][1]["wq"][0, 0] = np.nan
    bad["exit_gate"] = bad["exit_gate"][:-1]
    faults = check_loop.shape_faults(CFG, bad)
    assert len(faults) == 2 and "not finite" in " ".join(faults)
    del bad["layers"][0]["norm1_post"]
    assert "tree differs" in check_loop.shape_faults(CFG, bad)[0]
    assert not _check(dict(program, shape_faults=faults), inputs)["correct"]


def test_a_jobs_record_is_held_to_the_loop():
    good = {"layer_applications": str(T * L), "attn_fwd_kernels": str(T * L),
            "exit_mass_last": "[0.5, 0.25, 0.25]"}
    assert check_loop.job_faults(CFG, [good, good]) == []
    twice = dict(good, attn_fwd_kernels=str(2 * T * L))
    short = dict(good, exit_mass_last="[0.5, 0.25]")
    leaky = dict(good, exit_mass_last="[0.5, 0.25, 0.125]")
    faults = check_loop.job_faults(CFG, [good, twice, short, leaky, {}])
    assert [f.split(":")[0] for f in faults] == [
        "job 1", "job 2", "job 3", "job 4", "job 4"]


def test_a_job_that_learned_nothing_fails(sides):
    program, inputs = sides
    params0, tokens0, _, held, probe = inputs
    stuck = dict(program, held_losses=[float(seq_blocks.loss_and_counters(
        params0(), batch, seq_blocks.BlockSpec.parse(CFG))[0])
        for batch in held])
    verdict = _check(stuck, (params0, tokens0, params0, held, probe),
                     limits=dict(LIMITS, held_loss_rel={"max": 1.0}))
    assert not verdict["correct"]
    assert [l for l in verdict["compared"] if "FAILED" in l][-1].startswith(
        "held-batch loss")


def test_the_reference_imports_nothing_of_the_program():
    import ast
    import inspect

    tree = ast.parse(inspect.getsource(looped_lm))
    names = [a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
             for a in n.names] + [n.module for n in ast.walk(tree)
                                  if isinstance(n, ast.ImportFrom)]
    assert not [n for n in names if n.startswith(("pio_tpu", "benchmark"))]
    assert 'precision="highest"' in inspect.getsource(looped_lm._dot)
    assert "scan" not in inspect.getsource(looped_lm.passes)
