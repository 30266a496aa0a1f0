"""The looped stack of models/seq_blocks.py (`total_ut_steps`: the layers
T times over one set of weights, four norms a layer, an exit gate and a
loss over the T exits) against the plain reference
(benchmark/reference/looped_lm.py) on seeded weights at a tiny size:
the loss, the T exit losses and masses, every parameter's gradient, the
last exit's logits; every fault of the reference moves a compared number
beyond its limit; what the step program holds of the loop; a stack
without expert keys is the older dense layer, program text for program
text; the engine's round trip; what `BlockSpec.parse` refuses, by name."""

import contextlib
import inspect
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import engines_sequence as es
from benchmark.harness import profile
from benchmark.reference import looped_lm as reference
from pio_tpu.controller.engine import EngineParams
from pio_tpu.models import seq_blocks
from pio_tpu.models.sequence import SequenceParams
from pio_tpu.workflow.context import create_workflow_context
from pio_tpu.workflow.train import load_models, run_train
from tests._tiny_train import memory_storage

CFG = {
    "model_type": "ouro", "hidden_size": 64, "num_hidden_layers": 2,
    "layer_types": ["full_attention"] * 2, "num_attention_heads": 4,
    "num_key_value_heads": 4, "head_dim": 16, "intermediate_size": 96,
    "hidden_act": "silu", "rms_norm_eps": 1e-6, "rope_theta": 1000000,
    "rope_scaling": None, "sliding_window": None, "use_sliding_window": False,
    "tie_word_embeddings": False, "total_ut_steps": 3,
    "early_exit_threshold": 1, "vocab_size": 257, "initializer_range": 0.02,
    "embedding_initializer_range": 1.0,
}
SPEC = seq_blocks.BlockSpec.parse(CFG)
POSITIONS, T, L = 96, 3, 2
# float32 operands on the program's side: the limits are the mathematics'
LOSS_ABS, GRAD_REL = 2e-5, 5e-5
# at this size the loop has three passes: one fewer is two
FAULTS = {
    "bfloat16 accumulation": {"accumulate": "bfloat16"},
    "a pass fewer": {"loop_steps": T - 1},
    "the final norm outside the loop": {"final_norm": "outside"},
    "no post-norms": {"post_norms": False},
    "the last exit gated": {"last_exit": "gated"},
    "the entropy term's sign": {"entropy_sign": -1},
    "the layers' gradient from the last pass": {"layer_grads": "last pass"},
}


def _small(mp):
    mp.setattr(seq_blocks, "COMPUTE", jnp.float32)
    mp.setattr(seq_blocks, "ATTN_BLOCK", 32)
    mp.setattr(seq_blocks, "LOSS_CHUNK", 64)


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    _small(monkeypatch)


def _trained_gate(params, seed=1):
    """A gate as a job leaves it: a bias and weights that spread the
    mass unevenly over the exits."""
    rng = np.random.default_rng(seed)
    return {**params, "exit_bias": jnp.asarray([-0.4], jnp.float32),
            "exit_gate": jnp.asarray(
                rng.normal(0, 0.08, params["exit_gate"].shape), jnp.float32)}


@pytest.fixture(scope="module")
def case():
    params = _trained_gate(seq_blocks.init_params(SPEC, 3))
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        1, 257, (2, POSITIONS + 1)), jnp.int32)
    with pytest.MonkeyPatch.context() as mp:
        _small(mp)
        (loss, counters), grads = jax.value_and_grad(
            seq_blocks.loss_and_counters, has_aux=True)(params, tokens, SPEC)
    (ref_loss, ref_aux), ref_grads = jax.value_and_grad(
        reference.loss, has_aux=True)(params, tokens, CFG)
    return {"params": params, "tokens": tokens, "loss": float(loss),
            "counters": counters, "grads": grads,
            "ref_loss": float(ref_loss), "ref_aux": ref_aux,
            "ref_grads": ref_grads}


def _by_name(tree):
    return {jax.tree_util.keystr(p): g for p, g in
            jax.tree_util.tree_leaves_with_path(tree)}


def _rel(got, want):
    return float(jnp.linalg.norm(got - want)
                 / jnp.maximum(jnp.linalg.norm(want), 1e-30))


def _compared(case, ref_loss, ref_aux, ref_grads) -> list[bool]:
    """What the program and a reference are compared on, each against
    its limit: the loss, the T exit losses, the T exit masses, every
    gradient leaf."""
    c = case["counters"]
    if len(ref_aux[0]) != T:
        return [False]
    got, want = _by_name(case["grads"]), _by_name(ref_grads)
    return ([abs(case["loss"] - float(ref_loss)) < LOSS_ABS]
            + [abs(float(a) - float(b)) < LOSS_ABS
               for a, b in zip(c["exit_losses"], ref_aux[0])]
            + [abs(float(a) - float(b)) < LOSS_ABS
               for a, b in zip(c["exit_mass"], ref_aux[1])]
            + [_rel(got[n], want[n]) < GRAD_REL for n in want])


def test_the_loss_the_exit_losses_and_the_masses_equal_the_references(case):
    c, (ref_ce, ref_mass, terms) = case["counters"], case["ref_aux"]
    assert abs(case["loss"] - case["ref_loss"]) < LOSS_ABS
    assert c["exit_losses"].shape == c["exit_mass"].shape == (T,)
    np.testing.assert_allclose(c["exit_losses"], ref_ce, atol=LOSS_ABS)
    np.testing.assert_allclose(c["exit_mass"], ref_mass, atol=LOSS_ABS)
    assert float(jnp.sum(c["exit_mass"])) == pytest.approx(1.0, abs=1e-6)
    # the size the gate's gradient sums would have if their terms were
    # unrelated: the bias's terms are the weight's less a state of norm
    # sqrt(d) each
    assert float(terms["exit_gate"]) == pytest.approx(
        float(terms["exit_bias"]) * CFG["hidden_size"] ** 0.5, rel=1e-4)
    # the gate is off balance, so the exits are told apart
    assert float(jnp.ptp(c["exit_mass"])) > 0.05
    # loss = sum_t p_t CE_t - beta H(p), token by token: not the means'
    assert case["loss"] != pytest.approx(
        float(jnp.sum(c["exit_mass"] * c["exit_losses"])
              - seq_blocks.EXIT_ENTROPY_WEIGHT * c["exit_entropy"]),
        abs=1e-7)


def _leaf_names():
    paths = jax.tree_util.tree_leaves_with_path(
        seq_blocks.param_shapes(SPEC), is_leaf=lambda x: isinstance(x, tuple))
    return [jax.tree_util.keystr(p) for p, _ in paths]


@pytest.mark.parametrize("leaf", _leaf_names())
def test_every_parameters_gradient_equals_the_references(case, leaf):
    got, want = _by_name(case["grads"])[leaf], _by_name(
        case["ref_grads"])[leaf]
    assert float(jnp.linalg.norm(want)) > 0, leaf
    assert _rel(got, want) < GRAD_REL, leaf


def test_the_sound_reference_passes_every_comparison(case):
    held = _compared(case, case["ref_loss"], case["ref_aux"],
                     case["ref_grads"])
    assert len(held) == 1 + 2 * T + len(_leaf_names()) and all(held)


@pytest.mark.parametrize("name", sorted(FAULTS))
def test_a_faulty_reference_fails_a_comparison(case, name):
    (loss, aux), grads = jax.value_and_grad(reference.loss, has_aux=True)(
        case["params"], case["tokens"], CFG, FAULTS[name])
    assert not all(_compared(case, loss, aux, grads)), name


def test_the_last_pass_alone_shows_in_the_layers_gradients_only(case):
    """The fault a loop over one set of weights could hide: the loss, the exits and the gate's
    gradient are the sound ones, a layer's matrices are not."""
    (loss, aux), grads = jax.value_and_grad(reference.loss, has_aux=True)(
        case["params"], case["tokens"], CFG, {"layer_grads": "last pass"})
    assert float(loss) == case["ref_loss"]
    got, want = _by_name(grads), _by_name(case["ref_grads"])
    assert _rel(got["['exit_gate']"], want["['exit_gate']"]) < 1e-6
    assert _rel(got["['layers'][0]['wq']"], want["['layers'][0]['wq']"]) > 0.1


def test_the_last_exits_logits_equal_the_references(case):
    ids = case["tokens"][:, :-1]
    got = seq_blocks.last_logits(case["params"], ids, SPEC)
    want = reference.logits(case["params"], ids, CFG)[:, -1]
    np.testing.assert_allclose(got, want, atol=5e-5, rtol=5e-5)
    fewer = reference.logits(case["params"], ids, CFG,
                             {"loop_steps": T - 1})[:, -1]
    assert float(jnp.abs(got - fewer).max()) > 1e-3


def test_the_exit_distribution_by_hand():
    lam = jnp.asarray([0.25, 0.5, 0.9])
    z = jnp.log(lam / (1 - lam))[:, None]
    p = seq_blocks.exit_probabilities(z)[:, 0]
    np.testing.assert_allclose(p, [0.25, 0.75 * 0.5, 0.75 * 0.5], rtol=1e-6)
    np.testing.assert_allclose(
        reference.exit_probabilities(lam[:, None])[:, 0], p, rtol=1e-6)
    # a gate that is sure of itself: the masses still sum to 1, and the
    # loss's entropy and its gradient hold no nan
    sure = jnp.asarray([[40.0, -40.0], [-40.0, 40.0], [0.0, 0.0]])
    np.testing.assert_allclose(
        seq_blocks.exit_probabilities(sure).sum(axis=0), 1.0, atol=1e-6)

    def entropy(z):
        p = seq_blocks.exit_probabilities(z)
        return -jnp.sum(p * jnp.log(jnp.maximum(p, 1e-30)))

    value, grad = jax.value_and_grad(entropy)(sure)
    assert np.isfinite(float(value)) and np.isfinite(np.asarray(grad)).all()


# -- what the step program holds ---------------------------------------------

def _traced(cfg, batch=(2, POSITIONS + 1)):
    spec = seq_blocks.BlockSpec.parse(cfg)
    optimizer, step = seq_blocks.make_train_step(spec, 0.0173)
    shapes = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s, jnp.float32),
        seq_blocks.param_shapes(spec), is_leaf=lambda x: isinstance(x, tuple))
    return step.trace(shapes, jax.eval_shape(optimizer.init, shapes),
                      jax.ShapeDtypeStruct(batch, jnp.int32))


def _lowered(cfg):
    return _traced(cfg).lower()


PLAIN = {k: v for k, v in CFG.items()
         if k not in ("total_ut_steps", "early_exit_threshold")}
# the older way to a dense layer: expert keys, and every layer leading
LEADING = {**PLAIN, "first_k_dense_replace": 2, "num_experts": 4,
           "num_experts_per_tok": 2, "moe_intermediate_size": 16,
           "norm_topk_prob": True}


def test_no_expert_keys_is_the_older_dense_layer_bit_for_bit(case):
    """One pass, no gate, no post-norms: a specification without expert
    keys and one whose every layer is a leading dense layer lower to one
    program text, and give one loss and one gradient."""
    plain, leading = (seq_blocks.BlockSpec.parse(c) for c in (PLAIN, LEADING))
    assert plain.loop_steps == 0          # not a looped stack
    assert plain.dense_layers == leading.dense_layers == 2
    assert seq_blocks.param_shapes(plain) == seq_blocks.param_shapes(leading)
    assert "norm1_post" not in seq_blocks.param_shapes(plain)["layers"][0]
    assert _lowered(PLAIN).as_text() == _lowered(LEADING).as_text()
    params = seq_blocks.init_params(plain, 3)
    one, two = (jax.value_and_grad(seq_blocks.loss_and_counters, has_aux=True)(
        params, case["tokens"], s) for s in (plain, leading))
    assert one[0][1] == two[0][1] == {}
    assert float(one[0][0]) == float(two[0][0])
    for a, b in zip(*(jax.tree_util.tree_leaves(g) for g in (one[1], two[1]))):
        assert (np.asarray(a) == np.asarray(b)).all()
    # and the loop of one pass is not that program: its state is normed
    # inside the loop and its loss is the exits'
    assert "exit_gate" not in params


def test_the_step_holds_the_stack_a_pass_and_counts_its_layers():
    """The passes are a Python loop (the faster on the chip): the
    program's text holds T x L forward kernels, one a layer and pass."""
    found = seq_blocks.attention_counters(_traced(CFG).jaxpr.jaxpr)
    assert found["layer_applications"] == T * L
    assert found["attn_fwd_kernels"] == T * L
    # o (float32 at this file's widths) and a (B, Hq, S) lse of each
    assert found["attn_residual_bytes"] == T * L * 2 * 4 * POSITIONS * (
        16 * 4 + 4)
    text = str(_traced(CFG).jaxpr)
    assert text.count("name=flash_attention_fwd") == T * L
    assert "scan" not in inspect.getsource(seq_blocks.looped_states).split(
        '"""')[2]
    one_pass = seq_blocks.attention_counters(
        _traced({**CFG, "total_ut_steps": 1}).jaxpr.jaxpr)
    assert one_pass["layer_applications"] == L


@pytest.mark.parametrize("op_name,scope", [
    ("jit(step)/jvp(seq.loop)/seq.attn.full/pallas_call",
     "seq.loop/seq.attn.full"),
    ("jit(step)/transpose(jvp(seq.loop))/checkpoint/"
     "seq.attn.proj/dot_general", "seq.loop/seq.attn.proj"),
    ("jit(step)/transpose(jvp(seq.loop))/while/body/checkpoint/"
     "seq.mlp.dense/mul", "seq.loop/seq.mlp.dense"),
    ("jit(step)/jvp(seq.loop)/seq.head_loss/while/body/"
     "checkpoint/dot_general", "seq.loop/seq.head_loss"),
    ("jit(step)/jvp(seq.loop)/seq.exit/reduce_sum",
     "seq.loop/seq.exit"),
    ("jit(step)/transpose(jvp(seq.loop))/seq.exit/mul", "seq.loop/seq.exit"),
    ("jit(step)/jvp(seq.embed)/gather", "seq.embed"),
])
def test_profile_joins_the_loops_scopes(op_name, scope):
    assert profile.scope_of_op_name(op_name) == scope


def test_the_compiled_step_carries_the_loops_scopes():
    names = set(profile._OP_NAME.findall(_lowered(CFG).compile().as_text()))
    scopes = {profile.scope_of_op_name(n) for n in names}
    assert {"seq.loop/seq.attn.full", "seq.loop/seq.attn.proj",
            "seq.loop/seq.mlp.dense", "seq.loop/seq.head_loss",
            "seq.loop/seq.exit", "seq.embed", "seq.optimizer"} <= scopes
    # a reducer's own computation may carry a scope without the loop:
    # the metrics' files list both paths
    assert {s for s in scopes if s and "/" not in s} <= {
        "seq.loop", "seq.embed", "seq.optimizer", "seq.attn.full",
        "seq.attn.proj", "seq.mlp.dense", "seq.head_loss", "seq.exit"}


# -- the engine ---------------------------------------------------------------

def test_run_train_persists_loads_and_predicts_the_looped_stack(monkeypatch):
    spans = {}

    @contextlib.contextmanager
    def span(name, **labels):
        spans[name] = dict(labels)
        yield spans[name]

    monkeypatch.setattr(seq_blocks.tracing, "span", span)
    seqs = es.make_histories(8, POSITIONS + 1, 256, 1.1, 5)
    engine = es.seeded_engine(seqs, 256)
    storage = memory_storage()
    ctx = create_workflow_context(storage, use_mesh=False)
    ep = EngineParams(datasource=("", None), algorithms=[("sasrec", dict(
        max_len=POSITIONS + 1, batch_size=2, steps=4, learning_rate=0.01,
        seed=11, block_spec=CFG))])
    instance = run_train(engine, ep, storage, engine_id="loop", ctx=ctx)
    [model] = load_models(storage, engine, ep, instance, ctx)
    assert jax.tree_util.tree_map(
        lambda x: x.shape, model.params) == seq_blocks.param_shapes(SPEC)
    algo = engine.algorithm_classes["sasrec"](SequenceParams(
        **ep.algorithms[0][1]))
    out = algo.batch_predict(model, [{"user": "u1", "num": 5},
                                     {"user": "nobody"}])
    assert len(out[0]["itemScores"]) == 5 and out[1]["itemScores"] == []
    # the scores are the last exit's, after every pass
    top = out[0]["itemScores"][0]
    logits = np.asarray(reference.logits(
        jax.tree_util.tree_map(jnp.asarray, model.params),
        jnp.asarray(seqs[1:2, 1:]), CFG)[0, -1])
    seen = set(seqs[1].tolist())
    unseen = [i for i in range(1, 257) if i not in seen]
    assert int(top["item"][1:]) == max(unseen, key=lambda i: logits[i])
    assert top["score"] == pytest.approx(float(logits[unseen].max()),
                                         abs=1e-3)
    # the job's record: what every stack has, and the loop's own
    labels = spans["seq.wait"]
    assert labels["loop_steps"] == T
    assert labels["layer_applications"] == labels["attn_fwd_kernels"] == T * L
    assert labels["tokens_per_step"] == 2 * POSITIONS
    assert not [k for k in labels if k.startswith("expert_")
                or k == "dropped_tokens"]
    mass = json.loads(labels["exit_mass_last"])
    assert len(mass) == T and sum(mass) == pytest.approx(1.0, abs=1e-6)
    for key in ("loss_exit_first", "loss_exit_last"):
        assert len(json.loads(labels[key])) == T
    assert 1.0 < float(labels["exit_expected_steps"]) < T
    assert 0.0 < float(labels["exit_entropy_last"]) <= np.log(T) + 1e-6
    # the seeded start is the reference's step-0 loss, and training moved
    tokens = jnp.asarray(seqs[seq_blocks.epoch_order(8, 4, 2, 11)[0]])
    first = float(reference.loss(
        seq_blocks.init_params(SPEC, 11), tokens, CFG)[0])
    assert float(labels["loss_first"]) == pytest.approx(first, abs=1e-4)
    after = float(reference.loss(jax.tree_util.tree_map(
        jnp.asarray, model.params), tokens, CFG)[0])
    assert after < first - 0.05


def test_parameter_counts_at_the_published_widths():
    """ISSUE 41: 201.33 M in the embedding and the head, 51.38 M a layer
    and its four norms, the final norm and the gate: 406.9 M, 6.51 GB at
    16 bytes a parameter."""
    path = os.path.join(os.path.dirname(__file__), "..", "benchmark",
                        "configs", "ouro-2.6b-l4.json")
    with open(path) as f:
        spec = seq_blocks.BlockSpec.parse(es.block_spec_of(json.load(f)))
    shapes = seq_blocks.param_shapes(spec)

    def count(tree):
        return sum(int(np.prod(s)) for s in jax.tree_util.tree_leaves(
            tree, is_leaf=lambda x: isinstance(x, tuple)))

    assert count(shapes["layers"][0]) == 16_777_216 + 34_603_008 + 4 * 2048
    assert count(shapes) == 2 * 100_663_296 + 4 * 51_388_416 + 2048 + 2049
    assert count(shapes) * 16 / 1e9 == pytest.approx(6.51, abs=0.005)
    assert spec.loop_steps == 4
    assert spec.dense_layers == spec.num_hidden_layers == 4
    assert spec.num_attention_heads == spec.num_key_value_heads == 16


@pytest.mark.parametrize("change,match", [
    ({"early_exit_threshold": 0.5}, "early_exit_threshold"),
    ({"total_ut_steps": 0}, "total_ut_steps"),
    ({"exit_entropy_weight": 0.05}, "exit_entropy_weight"),
    ({"num_experts": 4, "num_experts_per_tok": 2, "moe_intermediate_size": 16,
      "norm_topk_prob": True}, "num_experts"),
    ({"n_routed_experts": 4, "num_experts_per_tok": 2,
      "moe_intermediate_size": 16, "norm_topk_prob": True},
     "n_routed_experts"),
    ({"num_nextn_predict_layers": 1}, "num_nextn_predict_layers"),
    ({"intermediate_size": 0}, "intermediate_size"),
    ({"kv_lora_rank": 8, "q_lora_rank": 8, "qk_nope_head_dim": 12,
      "qk_rope_head_dim": 4, "v_head_dim": 16}, "kv_lora_rank"),
    ({"hidden_act": "xielu"}, "hidden_act"),
    ({"tie_word_embeddings": True}, "tie_word_embeddings"),
    ({"layers_block_type": ["mamba"] * 2}, "layers_block_type"),
])
def test_what_the_stack_does_not_compute_is_refused_by_name(change, match):
    with pytest.raises(ValueError, match=match):
        seq_blocks.BlockSpec.parse({**CFG, **change})


def test_a_prediction_module_without_expert_keys_is_refused():
    with pytest.raises(ValueError, match="num_nextn_predict_layers"):
        seq_blocks.BlockSpec.parse({**PLAIN, "num_nextn_predict_layers": 1})
