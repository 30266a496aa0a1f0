"""benchmark/harness/check_gated.py at a tiny size on the CPU: the sound
program passes, and every faulty reference the limits are set against
fails at least one of them."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import engines_sequence as es
from benchmark.harness import cells, check_gated
from benchmark.reference import gated_gqa_moe_lm
from pio_tpu.models import seq_blocks
from pio_tpu.ops.attention import banded_flash_attention
from pio_tpu.ops.moe import route_top_k

OVERLAY = cells.load_json(__file__.replace(
    "test_check_gated.py", "rehearse/gated-tiny.json"))
CONFIG = cells.merge(cells.load_json(
    cells.ROOT + "/benchmark/configs/laguna-xs2-ep16.json"),
    OVERLAY["config"])
CFG = es.block_spec_of(CONFIG)
# float32 operands on the program's side: the limits below are then those
# of the mathematics, and a fault of one part in a hundred shows. The
# stated precision's side is then the float32 one's too, but for what
# rounding the reference's own operands does (a few parts in 100,000)
LIMITS = {"loss_logged_rel": {"max": 1e-6}, "loss_rel": {"max": 1e-5},
          **{f"grad_{what}_rel": {"max": 1e-3}
             for what in check_gated.FAMILIES},
          "band_edge_rel": {"max": 1e-5}, "router_probe_rel": {"max": 1e-5},
          "router_counts_rel": {"max": 0.01},
          "router_bias_abs": {"max": 1e-6}, "held_loss_rel": {"max": 1e-5},
          "held_stated_rel": {"max": 3e-4},
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
                bias1 = check_gated.router_biases(CFG, params)
        held = jnp.asarray(es.make_histories(
            2 * check_gated.HELD_BATCHES, length, CFG["vocab_size"] - 1,
            1.1, 7, stream=1).reshape(-1, 2, length))
        host = jax.device_get(params)
        probes = {"router": check_gated.router_probe(CFG, 7, host,
                                                     tokens=256),
                  "edge": check_gated.edge_probe(CFG, POSITIONS, 8, 7)}
        experts, routed = spec.experts, []
        for bias in probes["router"]["bias"]:
            ids, w = route_top_k(
                jnp.asarray(probes["router"]["logits"]), experts.top_k,
                experts.norm_topk, experts.score, bias, experts.scale)
            routed.append(np.asarray(jnp.zeros((256, 16)).at[
                jnp.arange(256)[:, None], ids].set(w)))
        pr = probes["edge"]
        _, dk, dv = jax.vjp(
            lambda q, k, v: banded_flash_attention(
                q, k, v, pr["window"], None, 8, 8),
            *(jnp.asarray(pr[n]) for n in ("q", "k", "v")))[1](
                jnp.asarray(pr["ct"]))
        program = {
            "loss0": float(loss0), "logged_loss": float(loss0),
            "slices": check_gated.gradient_slices(CFG, grads, EXPERT),
            "shape_faults": check_gated.shape_faults(CFG, host),
            "counts0": counts0, "bias1": bias1, "steps": STEPS,
            "bias_model": check_gated.router_biases(CFG, host),
            "router_probe": np.stack(routed),
            "band_edge": check_gated.band_edge_slice(dk, dv, pr),
            "held_losses": [float(loss_of(params, batch))
                            for batch in held]}
    reference = check_gated.Reference(CFG)
    inputs = (lambda: params0, tokens0, lambda: params, held, EXPERT, probes)
    return program, reference, inputs, reference.numbers(*inputs)


def test_the_sound_program_passes(sides):
    program, _, _, sound = sides
    verdict = check_gated.check(CFG, LIMITS, program, sound)
    assert verdict["correct"], verdict["compared"]
    by_slice = verdict["numbers"]["grad_rel_by_slice"]
    # three probed layers' three attention and three gate slices, four
    # routers, an expert's and the shared expert's three each, the dense
    # layer's down projection, head and embedding
    assert len(by_slice) == 3 * 6 + 4 + 6 + 3
    assert sorted({check_gated.family(n) for n in by_slice}) == sorted(
        check_gated.FAMILIES)
    assert check_gated.probed_layers(CFG) == [0, 1, 4]
    assert verdict["numbers"]["router_bias_abs"] < 1e-7
    assert verdict["numbers"]["router_counts_rel"] == 0.0
    assert verdict["numbers"]["band_edge_rel"] < 1e-6
    assert program["counts0"].shape == (4, 2, 16)
    assert np.abs(program["bias1"]).max() == pytest.approx(0.001)
    assert np.abs(program["bias_model"]).max() > 0.0019
    assert set(LIMITS) == set(CONFIG["check"]["limits"])
    assert set(LIMITS) == set(cells.load_json(
        cells.ROOT + "/benchmark/configs/laguna-xs2-ep16.json")[
            "check"]["limits"])


def test_the_faults_are_the_issues():
    """ISSUE 49, Tentpole 5: sixteen faults, one of them the control."""
    assert len(check_gated.FAULTS) == 16
    assert check_gated.FAULTS["bfloat16 accumulation"] == {
        "accumulate_bf16": 1.0}
    used = {k for fault in check_gated.FAULTS.values() for k in fault}
    assert used | set(check_gated.STATED) == set(gated_gqa_moe_lm.SOUND)
    assert set(check_gated.HELD_FAULTS) <= set(check_gated.FAULTS)


@pytest.mark.parametrize("name", sorted(check_gated.FAULTS))
def test_a_faulty_reference_fails_a_limit(sides, name):
    """One compiled reference serves every fault: a fault is a number it
    takes as an argument."""
    program, reference, inputs, _ = sides
    verdict = check_gated.check(
        CFG, LIMITS, program,
        reference.numbers(*inputs, check_gated.FAULTS[name]))
    assert not verdict["correct"]
    assert any("FAILED" in line for line in verdict["compared"])


def test_a_window_one_key_too_wide_shows_at_the_bands_edge(sides):
    program, reference, inputs, _ = sides
    verdict = check_gated.check(
        CFG, dict(LIMITS, **{f"grad_{what}_rel": {"max": 0.5}
                             for what in check_gated.FAMILIES},
                  loss_rel={"max": 0.01}, held_loss_rel={"max": 0.01},
                  held_stated_rel={"max": 0.01},
                  router_counts_rel={"max": 0.1}), program,
        reference.numbers(*inputs, {"window": CFG["sliding_window"] + 1}))
    failed = [line for line in verdict["compared"] if "FAILED" in line]
    assert [line[:13] for line in failed] == ["window kernel"]
    assert verdict["numbers"]["band_edge_rel"] > 0.3


def test_the_control_shows_against_the_stated_precision(sides):
    """The reference with bfloat16 results too: its held losses at the
    stated precision move away from the program's, which the float32
    side's hardly do."""
    program, reference, inputs, sound = sides
    control = reference.numbers(
        *inputs, check_gated.FAULTS["bfloat16 accumulation"])
    assert control["held_stated"] != sound["held_stated"]
    assert control["held_stated"] == control["held_losses"]   # one mode
    assert sound["held_stated"] != sound["held_losses"]


def test_a_model_of_the_wrong_shape_or_not_finite_fails(sides):
    program, _, inputs, sound = sides
    params = jax.device_get(inputs[2]())
    assert check_gated.shape_faults(CFG, params) == []
    bad = jax.tree_util.tree_map(np.array, params)
    bad["layers"][0]["q_head_norm"][0] = np.nan
    bad["layers"][1]["w_gate_heads"] = bad["layers"][1]["w_gate_heads"][:, :-1]
    faults = check_gated.shape_faults(CFG, bad)
    assert len(faults) == 2 and "not finite" in " ".join(faults)
    del bad["layers"][4]["wq"]
    assert "tree differs" in check_gated.shape_faults(CFG, bad)[0]
    assert not check_gated.check(
        CFG, LIMITS, dict(program, shape_faults=faults), sound)["correct"]


@pytest.mark.parametrize("name", sorted(check_gated.PROGRAM_FAULTS))
def test_wrong_counts_or_a_bias_that_is_not_the_rules_fail(sides, name):
    program, _, _, sound = sides
    wrong = check_gated.PROGRAM_FAULTS[name](program, CFG)
    verdict = check_gated.check(CFG, LIMITS, wrong, sound)
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
    verdict = check_gated.check(
        CFG, dict(LIMITS, held_stated_rel={"max": 1.0}),
        dict(program, held_losses=untrained["held_losses"]), untrained)
    failed = [line for line in verdict["compared"] if "FAILED" in line]
    assert [line[:15] for line in failed] == ["held-batch loss"]


def test_a_reading_that_is_not_a_number_fails(sides):
    program, _, _, sound = sides
    nan = dict(sound, loss0=float("nan"))
    assert not check_gated.check(CFG, LIMITS, program, nan)["correct"]


def test_the_reference_is_plain():
    """float32 at the highest matmul precision, one product a call
    whatever the precision, no import of the program or of the
    benchmark, no kernel."""
    import ast
    import inspect

    source = inspect.getsource(gated_gqa_moe_lm)
    tree = ast.parse(source)
    names = [a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
             for a in n.names] + [n.module for n in ast.walk(tree)
                                  if isinstance(n, ast.ImportFrom)]
    assert not [n for n in names if n.startswith(("pio_tpu", "benchmark"))]
    dot = inspect.getsource(gated_gqa_moe_lm._dot)
    assert 'precision="highest"' in dot and dot.count("dot_general(") == 1
    assert "pallas" not in source and "checkpoint_name" not in source
    assert "reduce_precision" in inspect.getsource(gated_gqa_moe_lm._round)
    with pytest.raises(ValueError, match="no such fault"):
        gated_gqa_moe_lm.with_faults(CFG, {"chunk": 1})
    flags = gated_gqa_moe_lm.with_faults(CFG)
    assert flags["kv_group_full"] == 3.0 and flags["rotary_full"] == 0.5
    assert flags["window"] == 12.0 and flags["top_k"] == 8.0
    assert flags["routed_scaling"] == 2.5
