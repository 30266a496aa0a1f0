"""Latent attention, the dense layer and the prediction module of
models/seq_blocks.py against the plain reference
(benchmark/reference/latent_moe_lm.py) on seeded weights at a small size:
both losses, both heads' logits, every parameter's gradient at a
tolerance the bfloat16-result control fails; what the module's targets
are; the attention kernel at `head_dim` 256 with one key-value head a
query head; what `BlockSpec.parse` refuses, by name."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import test_attention_window_gqa as attention_cases

from benchmark.reference import latent_moe_lm as reference
from pio_tpu.models import seq_blocks
from pio_tpu.ops.attention import (
    banded_attention_reference,
    banded_flash_attention,
)

CFG = {
    "hidden_size": 32, "num_hidden_layers": 3, "num_attention_heads": 4,
    "num_key_value_heads": 4, "q_lora_rank": 12, "kv_lora_rank": 10,
    "qk_nope_head_dim": 12, "qk_rope_head_dim": 4, "v_head_dim": 16,
    "rope_theta": 10000, "rope_scaling": None, "partial_rotary_factor": 1,
    "rms_norm_eps": 1e-5, "first_k_dense_replace": 1,
    "intermediate_size": 48, "moe_intermediate_size": 16,
    "n_routed_experts": 4, "num_experts_routed": 8, "experts_held": [2, 6],
    "n_shared_experts": 1, "num_experts_per_tok": 3, "norm_topk_prob": True,
    "routed_scaling_factor": 1.8, "topk_method": "noaux_tc", "n_group": 1,
    "topk_group": 1, "num_nextn_predict_layers": 1, "vocab_size": 50,
    "hidden_act": "silu", "attention_bias": False,
    "tie_word_embeddings": False, "initializer_range": 0.3,
    "max_position_embeddings": 4096, "model_type": "glm4_moe_lite",
}
SPEC = seq_blocks.BlockSpec.parse(CFG)


def _small(mp):
    """Float32 operands, so the comparison is of the mathematics, and
    blocks small enough that a 40-token history spans several."""
    mp.setattr(seq_blocks, "COMPUTE", jnp.float32)
    mp.setattr(seq_blocks, "ATTN_BLOCK", 16)
    mp.setattr(seq_blocks, "MOE_TILE", 8)
    mp.setattr(seq_blocks, "LOSS_CHUNK", 32)


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    _small(monkeypatch)


def _biased(params, seed=1, size=0.05):
    """Router biases as a job leaves them: not zero."""
    rng = np.random.default_rng(seed)
    out = jax.tree_util.tree_map(lambda x: x, params)
    for lp in seq_blocks.expert_layers(out, SPEC):
        lp["router_bias"] = jnp.asarray(
            rng.uniform(-size, size, lp["router_bias"].shape), jnp.float32)
    return out


@pytest.fixture(scope="module")
def case():
    params = _biased(seq_blocks.init_params(SPEC, 3))
    tokens = jnp.asarray(np.random.default_rng(0).integers(1, 50, (2, 42)),
                         jnp.int32)
    with pytest.MonkeyPatch.context() as mp:
        _small(mp)
        (loss, counters), grads = jax.value_and_grad(
            seq_blocks.loss_and_counters, has_aux=True)(params, tokens, SPEC)
    (ref_loss, ref_parts), ref_grads = jax.value_and_grad(
        reference.loss, has_aux=True)(params, tokens, CFG)
    control = jax.value_and_grad(reference.loss, has_aux=True)(
        params, tokens, CFG, {"accumulate": "bfloat16"})
    return {"params": params, "tokens": tokens, "loss": loss,
            "counters": counters, "grads": grads, "ref_loss": ref_loss,
            "ref_parts": ref_parts, "ref_grads": ref_grads,
            "control": control}


def _by_name(tree):
    return {jax.tree_util.keystr(p): g for p, g in
            jax.tree_util.tree_leaves_with_path(tree)}


def _rel(got, want):
    return float(jnp.linalg.norm(got - want)
                 / jnp.maximum(jnp.linalg.norm(want), 1e-30))


def test_both_losses_equal_the_references(case):
    main, module = (float(x) for x in case["counters"]["losses"])
    assert abs(main - float(case["ref_parts"][0])) < 2e-5
    assert abs(module - float(case["ref_parts"][1])) < 2e-5
    assert float(case["loss"]) == pytest.approx(main + 0.3 * module, abs=1e-6)
    assert abs(float(case["loss"]) - float(case["ref_loss"])) < 2e-5
    # the control (every product's result rounded to bfloat16) is further
    assert abs(float(case["control"][0][0]) - float(case["ref_loss"])) > 1e-3


def test_counters_cover_the_stacks_routers_and_the_modules(case):
    c = case["counters"]
    assert c["counts"].shape == (3, 2, 4)       # 2 stack + 1 module, B, held
    assert c["counts_all"].shape == (3, 2, 8)   # over every routed expert
    assert int(np.asarray(c["dropped"]).sum()) == 0
    all_, held = np.asarray(c["counts_all"]), np.asarray(c["counts"])
    assert (all_[..., 2:6] == held).all()
    assert (all_.sum(-1) == 40 * 3).all()        # positions x top-k
    want = reference.routed_counts(case["params"], case["tokens"], CFG)
    assert (all_.sum(1) == np.asarray(want)).all()


def _leaf_names():
    shapes = seq_blocks.param_shapes(SPEC)
    paths = jax.tree_util.tree_leaves_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    return [jax.tree_util.keystr(p) for p, _ in paths]


@pytest.mark.parametrize("leaf", _leaf_names())
def test_every_parameters_gradient_equals_the_references(case, leaf):
    got, want = _by_name(case["grads"])[leaf], _by_name(
        case["ref_grads"])[leaf]
    if leaf.endswith("['router_bias']"):
        assert not np.asarray(got).any() and not np.asarray(want).any()
        return
    assert _rel(got, want) < 5e-5, leaf


def test_the_tolerance_refuses_the_bfloat16_control(case):
    """At 5e-5 the control's gradients fail leaf after leaf."""
    control = _by_name(case["control"][1])
    want = _by_name(case["ref_grads"])
    failed = [n for n in want if not n.endswith("['router_bias']")
              and _rel(control[n], want[n]) >= 5e-5]
    assert len(failed) > len(want) // 2


def test_logits_of_both_heads_equal_the_references(case):
    params, tokens = case["params"], case["tokens"]
    main, module = reference.logits(params, tokens[:, :-1], CFG)
    got = seq_blocks.last_logits(params, tokens[:, :-2], SPEC)
    np.testing.assert_allclose(got, main[:, -1], atol=5e-5, rtol=5e-5)
    x, _ = seq_blocks.hidden_states(params, tokens[:, :-2], SPEC)
    x2, _ = seq_blocks.mtp_hidden_states(params, x, tokens[:, 1:-1], SPEC)
    got2 = seq_blocks.rms_norm(
        x2, params["mtp"]["final_norm"], SPEC.rms_norm_eps) @ params["head"].T
    np.testing.assert_allclose(got2, module, atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("fault", [
    {"score": "softmax"}, {"routed_scaling": 1.0}, {"top_k": 2},
    {"weights_biased": True}, {"kv_norm": False}, {"rope_nope": True},
    {"k_pe": "per_head"}, {"scale_dim": 12}, {"shared": False},
    {"mtp_target": 1}, {"mtp_weight": 0.1}])
def test_a_faulty_reference_differs(case, fault):
    """The reference's faults are faults: each moves the loss or one of
    its two parts (which can cancel in the sum)."""
    total, parts = reference.loss(case["params"], case["tokens"], CFG, fault)
    moved = [abs(float(total) - float(case["ref_loss"]))] + [
        abs(float(a) - float(b)) for a, b in zip(parts, case["ref_parts"])]
    assert max(moved) > 1e-3


def test_the_modules_targets_are_two_ids_on(case):
    """Only the last id changes: the main loss stays, the module's moves
    (its last position predicts that id). Only the id before it changes:
    both move."""
    params, tokens = case["params"], case["tokens"]

    def parts(t):
        return np.asarray(seq_blocks.loss_and_counters(
            params, t, SPEC)[1]["losses"])

    base = parts(tokens)
    last = parts(tokens.at[:, -1].set(tokens[:, -1] % 49 + 1))
    assert last[0] == base[0] and abs(last[1] - base[1]) > 1e-4
    before = parts(tokens.at[:, -2].set(tokens[:, -2] % 49 + 1))
    assert abs(before[0] - base[0]) > 1e-4
    assert abs(before[1] - base[1]) > 1e-4


def test_a_history_of_a_module_has_one_more_id():
    assert seq_blocks.history_ids(SPEC, 40) == 42
    plain = seq_blocks.BlockSpec.parse(
        {**CFG, "num_nextn_predict_layers": 0})
    assert seq_blocks.history_ids(plain, 40) == 41
    assert "mtp" not in seq_blocks.param_shapes(plain)


def test_parameter_counts_at_the_published_widths():
    """ISSUE 33's table: one rank of eight of GLM-4.7-Flash."""
    import json
    import os

    from benchmark import engines_sequence as es

    path = os.path.join(os.path.dirname(__file__), "..", "benchmark",
                        "configs", "glm-4.7-flash-ep8.json")
    with open(path) as f:
        spec = seq_blocks.BlockSpec.parse(es.block_spec_of(json.load(f)))
    shapes = seq_blocks.param_shapes(spec)

    def count(tree):
        return sum(int(np.prod(s)) for s in jax.tree_util.tree_leaves(
            tree, is_leaf=lambda x: isinstance(x, tuple)))

    bias = spec.num_experts_routed
    assert count(shapes["layers"][0]) == 84_677_888
    assert count(shapes["layers"][1]) == 106_829_056 + bias
    assert count(shapes["mtp"]) == 115_223_808 + bias
    assert count(shapes) == 706_518_528 + 5 * bias
    assert spec.head_dim == 256 and spec.rope_dim == 64
    assert spec.scoring == "sigmoid" and spec.router_bias


# -- the attention kernel at the latent heads' width -------------------------

@pytest.fixture(scope="module")
def wide():
    rng = np.random.default_rng(4)
    q, k, v, ct = (jnp.asarray(rng.standard_normal((1, 3, 96, 256)),
                               jnp.float32) for _ in range(4))
    kernel = jax.vjp(lambda q, k, v: banded_flash_attention(
        q, k, v, None, None, 32, 32), q, k, v)
    oracle = jax.vjp(lambda q, k, v: banded_attention_reference(
        q, k, v, None), q, k, v)
    return kernel[0], kernel[1](ct), oracle[0], oracle[1](ct)


@pytest.mark.parametrize("which", ["out", "dq", "dk", "dv"])
def test_the_kernel_at_head_dim_256_group_1(wide, which):
    """20 x 256 is the widest any cell runs it; q.k and p.v are one width
    (192 + 64 = 256), so no kernel changes: the parse holds a
    specification to that."""
    out, grads, want_out, want_grads = wide
    n = ["out", "dq", "dk", "dv"].index(which)
    got, want = (out, want_out) if n == 0 else (grads[n - 1],
                                                want_grads[n - 1])
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-4)


@pytest.mark.parametrize("change,match", [
    ({"index_topk": 2048}, "index_topk"),
    ({"layer_types": ["mamba", "attention", "mamba"]}, "layer_types"),
    ({"layers_block_type": ["mamba"] * 3}, "layers_block_type"),
    ({"n_group": 8, "topk_group": 4}, "n_group"),
    ({"tie_word_embeddings": True}, "tie_word_embeddings"),
    ({"v_head_dim": 32}, "v_head_dim"),
    ({"q_lora_rank": None}, "q_lora_rank"),
    ({"num_key_value_heads": 2}, "num_key_value_heads"),
    ({"rope_scaling": {"type": "yarn", "factor": 4}}, "rope_scaling"),
    ({"partial_rotary_factor": 0.5}, "partial_rotary_factor"),
    ({"topk_method": "group_limited_greedy"}, "topk_method"),
    ({"scoring_func": "softmax"}, "scoring_func"),    # noaux_tc: sigmoid
    ({"router_bias_update_rate": 0.01}, "router_bias_update_rate"),
    ({"mtp_loss_weight": 0.1}, "mtp_loss_weight"),
    ({"num_nextn_predict_layers": 2}, "num_nextn_predict_layers"),
    ({"first_k_dense_replace": 4}, "first_k_dense_replace"),
    ({"layer_types": ["sliding_attention"] * 3, "sliding_window": 8},
     "layer_types"),
])
def test_what_the_stack_does_not_compute_is_refused_by_name(change, match):
    with pytest.raises(ValueError, match=match):
        seq_blocks.BlockSpec.parse({**CFG, **change})


# -- the forward's residuals across the halves' checkpoint -------------------
# (the cases of tests/test_attention_window_gqa.py, on latent attention with
# a dense layer and a prediction module: three attention layers)

MODULE_CFG = {**CFG, "num_hidden_layers": 2}


def test_the_gradient_holds_one_forward_kernel_a_layer():
    attention_cases.holds_one_forward_kernel_a_layer(MODULE_CFG, 3)


@pytest.mark.parametrize("other", sorted(attention_cases.OTHER_CHECKPOINTS))
def test_kept_residuals_change_no_gradient(monkeypatch, other):
    attention_cases.gradients_equal(monkeypatch, MODULE_CFG, other)


def test_the_modules_attention_half_keeps_x_o_and_a_compact_lse():
    attention_cases.half_keeps_x_o_and_a_compact_lse(MODULE_CFG)
