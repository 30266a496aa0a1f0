"""The stack of single-mixer blocks of models/seq_blocks.py
(`hybrid_override_pattern`: Mamba-2 mixers with the scan op of
ops/ssd.py, relu^2 experts beside a shared one, attention without rotary
positions) against the plain reference (benchmark/reference/
ssm_moe_lm.py, whose scan is the recurrence a position at a time) on
seeded weights at a tiny size: the loss and every parameter's gradient
by block kind; causality through convolution and scan; what the step
program keeps of the scans; the engine's round trip with the job's
`seq.wait` record; the draw of a mixer's own parameters; what
`BlockSpec.parse` refuses, by name; and the three accepted
specifications, which parse to the `BlockSpec` they gave."""

import contextlib
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import engines_sequence as es
from benchmark.harness import check_ssm
from benchmark.reference import ssm_moe_lm as reference
from pio_tpu.controller.engine import EngineParams
from pio_tpu.models import seq_blocks
from pio_tpu.models.sequence import SequenceParams
from pio_tpu.workflow.context import create_workflow_context
from pio_tpu.workflow.train import load_models, run_train
from tests._tiny_train import memory_storage

HERE = os.path.dirname(__file__)
CFG = {
    "model_type": "nemotron_h", "hidden_size": 32, "num_hidden_layers": 9,
    "hybrid_override_pattern": "MEMEM*EMEMEM*", "attention_rope": False,
    "mamba_num_heads": 8, "mamba_head_dim": 8, "ssm_state_size": 16,
    "n_groups": 2, "conv_kernel": 4, "chunk_size": 8, "use_conv_bias": True,
    "mamba_proj_bias": False, "mamba_hidden_act": "silu",
    "mlp_hidden_act": "relu2", "moe_intermediate_size": 24,
    "moe_shared_expert_intermediate_size": 48, "n_routed_experts": 4,
    "num_experts_routed": 16, "experts_held": [0, 4],
    "num_experts_per_tok": 3, "norm_topk_prob": True,
    "routed_scaling_factor": 2.5, "n_shared_experts": 1, "n_group": 1,
    "topk_group": 1, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 8, "attention_bias": False, "mlp_bias": False,
    "use_bias": False, "layer_norm_epsilon": 1e-5, "rope_theta": 10000,
    "tie_word_embeddings": False, "vocab_size": 64,
    "initializer_range": 0.2, "embedding_initializer_range": 1.0,
}
SPEC = seq_blocks.BlockSpec.parse(CFG)
POSITIONS = 32
# float32 operands on the program's side: the limits are the mathematics'
LOSS_ABS, GRAD_REL = 2e-5, 1e-4


def _small(mp):
    mp.setattr(seq_blocks, "COMPUTE", jnp.float32)
    mp.setattr(seq_blocks, "ATTN_BLOCK", 16)
    mp.setattr(seq_blocks, "LOSS_CHUNK", 32)
    mp.setattr(seq_blocks, "MOE_TILE", 8)


def _tokens(seed=1, batch=2):
    return jax.random.randint(jax.random.PRNGKey(seed),
                              (batch, POSITIONS + 1), 1, CFG["vocab_size"])


@pytest.fixture(scope="module")
def both_sides():
    with pytest.MonkeyPatch.context() as mp:
        _small(mp)
        params = seq_blocks.init_params(SPEC, 3)
        # a router's bias away from zero, as a trained model has it
        layers = [dict(lp, router_bias=0.05 * jax.random.normal(
            jax.random.PRNGKey(n), lp["router_bias"].shape))
                  if "router_bias" in lp else lp
                  for n, lp in enumerate(params["layers"])]
        params = {**params, "layers": layers}
        tokens = _tokens()
        (loss, aux), grads = jax.value_and_grad(
            seq_blocks.loss_and_counters, has_aux=True)(params, tokens, SPEC)
        want, want_grads = jax.value_and_grad(reference.loss)(
            params, tokens, CFG)
        counts = reference.routed_counts(params, tokens, CFG)
        return (float(loss), jax.device_get(aux), jax.device_get(grads),
                float(want), jax.device_get(want_grads),
                np.asarray(counts))


def test_the_pattern_gives_the_blocks_their_kinds():
    assert SPEC.block_kinds == tuple("MEMEM*EME")
    assert SPEC.router_blocks == (1, 3, 6, 8)
    assert SPEC.layer_types == () and SPEC.rope == ()
    assert SPEC.scoring == "sigmoid" and SPEC.expert_act == "relu2"
    shapes = seq_blocks.param_shapes(SPEC)["layers"]
    assert sorted(shapes[0]) == sorted(
        ["norm", "in_proj", "conv_w", "conv_b", "dt_bias", "A_log", "D",
         "ssm_norm", "out_proj"])
    assert shapes[0]["in_proj"] == (32, 64 + (64 + 2 * 2 * 16) + 8)
    assert sorted(shapes[1]) == sorted(
        ["norm", "router", "router_bias", "w_up", "w_down", "shared_up",
         "shared_down"])                     # two matrices: no gate
    assert shapes[1]["shared_up"] == (32, 48)
    assert sorted(shapes[5]) == ["norm", "wk", "wo", "wq", "wv"]


def test_the_loss_and_the_routing_equal_the_references(both_sides):
    loss, aux, _, want, _, counts = both_sides
    assert loss == pytest.approx(want, abs=LOSS_ABS)
    np.testing.assert_array_equal(aux["counts_all"].sum(axis=1), counts)
    assert int(aux["dropped"].sum()) == 0


LEAVES = [jax.tree_util.keystr(path) for path, _ in
          jax.tree_util.tree_leaves_with_path(
              seq_blocks.param_shapes(SPEC),
              is_leaf=lambda x: isinstance(x, tuple))]


@pytest.mark.parametrize("leaf", LEAVES)
def test_every_parameters_gradient_equals_the_references(both_sides, leaf):
    _, _, grads, _, want, _ = both_sides
    mine = {jax.tree_util.keystr(p): g for p, g in
            jax.tree_util.tree_leaves_with_path(grads)}[leaf]
    theirs = {jax.tree_util.keystr(p): g for p, g in
              jax.tree_util.tree_leaves_with_path(want)}[leaf]
    if leaf.endswith("['router_bias']"):
        assert not np.any(mine) and not np.any(theirs)   # no gradient
        return
    assert np.linalg.norm(mine - theirs) <= GRAD_REL * np.linalg.norm(
        theirs), leaf


def test_in_bfloat16_the_loss_stays_near_the_references(monkeypatch):
    """The stack as it trains (bfloat16 operands, the kernels in
    interpret mode) against the float32 reference."""
    monkeypatch.setattr(seq_blocks, "ATTN_BLOCK", 16)
    monkeypatch.setattr(seq_blocks, "MOE_TILE", 8)
    params = seq_blocks.init_params(SPEC, 4)
    tokens = _tokens(2)
    loss, _ = seq_blocks.loss_and_counters(params, tokens, SPEC)
    assert float(loss) == pytest.approx(
        float(reference.loss(params, tokens, CFG)), rel=0.01)


@pytest.mark.parametrize("at", [9, 20])
def test_a_later_id_changes_no_earlier_output(monkeypatch, at):
    """Causality through the convolution (4 taps), the scan (across a
    chunk boundary: chunks of 8), attention and the routed experts."""
    _small(monkeypatch)
    params = seq_blocks.init_params(SPEC, 5)
    ids = _tokens(3)[:, :-1]
    moved = ids.at[:, at].set(ids[:, at] % 63 + 1)
    before, _ = seq_blocks.hidden_states(params, ids, SPEC)
    after, _ = seq_blocks.hidden_states(params, moved, SPEC)
    np.testing.assert_array_equal(before[:, :at], after[:, :at])
    assert np.abs(before[:, at:] - after[:, at:]).max(axis=-1).min() > 0


def test_the_convolution_and_the_gated_norm_by_hand():
    x = jnp.arange(12.0).reshape(1, 6, 2)
    w = jnp.array([[1.0, 0.0], [0.0, 10.0], [100.0, 0.0], [0.0, 1000.0]])
    out = seq_blocks.causal_conv(x, w, jnp.array([0.5, -0.5]))
    # channel 0 reads positions t-3 and t-1, channel 1 t-2 and t
    assert out[0, 4].tolist() == [x[0, 1, 0] + 100 * x[0, 3, 0] + 0.5,
                                  10 * x[0, 2, 1] + 1000 * x[0, 4, 1] - 0.5]
    assert out[0, 0].tolist() == [0.5, 1000 * x[0, 0, 1] - 0.5]
    y = jnp.array([[3.0, 4.0, 6.0, 8.0]])
    z = jnp.zeros((1, 4))                      # silu(0) = 0: all gated off
    assert not np.any(seq_blocks.gated_group_norm(y, z, jnp.ones(4), 2, 1e-6))
    z = jnp.full((1, 4), 50.0)                 # silu(50) = 50
    np.testing.assert_allclose(
        seq_blocks.gated_group_norm(y, z, jnp.ones(4), 2, 0.0),
        [[3 / np.sqrt(12.5), 4 / np.sqrt(12.5), 6 / np.sqrt(50),
          8 / np.sqrt(50)]], rtol=1e-6)


def test_a_mixers_own_parameters_are_drawn_as_its_family_draws_them():
    lp = seq_blocks.init_params(SPEC, 6)["layers"][0]
    a, dt = np.exp(lp["A_log"]), np.logaddexp(lp["dt_bias"], 0.0)
    assert a.min() >= 1.0 and a.max() <= 16.0 and a.std() > 1.0
    assert dt.min() >= 0.001 * 0.999 and dt.max() <= 0.1 * 1.001
    assert np.all(lp["D"] == 1.0) and np.all(lp["ssm_norm"] == 1.0)
    for name in ("conv_w", "conv_b"):
        assert np.abs(lp[name]).max() <= 0.5 and lp[name].std() > 0.2
    assert np.asarray(lp["in_proj"]).std() == pytest.approx(0.2, rel=0.1)


def test_the_step_keeps_the_chunk_states_and_counts_its_scans(monkeypatch):
    _small(monkeypatch)
    found = seq_blocks.step_attention_counters.__wrapped__(
        SPEC, 0.0123, (2, POSITIONS + 1))
    # 4 M blocks x 2 histories x 4 chunks x 8 heads x (8 x 16) float32
    assert found["ssm_state_bytes"] == 4 * 2 * 4 * 8 * 8 * 16 * 4
    assert found["attn_fwd_kernels"] == found["layer_applications"] == 1
    # forward once and again in the block's recomputation, backward once;
    # a block's loop over the histories holds its body once
    assert found["ssm_bwd_kernels"] == 4
    assert found["ssm_fwd_kernels"] == 8


def test_parameter_counts_at_the_published_widths():
    """ISSUE 45: 38,744,896 an M block, 100,125,312 an E block (+ the 128
    router biases, which take no gradient), 23,399,040 the attention
    block, 88,083,072 in embedding, head and final norm: 666,962,944 +
    512, 10.67 GB at 16 bytes a parameter."""
    path = os.path.join(HERE, "..", "benchmark", "configs",
                        "nemotron-3-nano-ep16.json")
    with open(path) as f:
        config = json.load(f)
    spec = seq_blocks.BlockSpec.parse(es.block_spec_of(config))
    shapes = seq_blocks.param_shapes(spec)

    def count(tree):
        return sum(int(np.prod(s)) for s in jax.tree_util.tree_leaves(
            tree, is_leaf=lambda x: isinstance(x, tuple)))

    assert spec.block_kinds == tuple("MEMEM*EME")
    assert count(shapes["layers"][0]) == 38_744_896
    assert count(shapes["layers"][1]) == 100_125_312 + 128
    assert count(shapes["layers"][5]) == 23_399_040
    assert count(shapes) == 666_962_944 + 4 * 128
    assert count(shapes) * 16 / 1e9 == pytest.approx(10.67, abs=0.005)
    assert check_ssm.expected_shapes(es.block_spec_of(config)) == shapes
    # every number of the catalog's config is in the file under its key
    published = config["published"]
    assert {k: config[k] for k in published} == {
        "num_hidden_layers": 9, "n_routed_experts": 8, "vocab_size": 16384}
    assert sorted(published) == sorted(config["reduced"])


# -- the engine ---------------------------------------------------------------

def test_run_train_persists_loads_and_predicts_the_stack(monkeypatch):
    spans = {}

    @contextlib.contextmanager
    def span(name, **labels):
        spans[name] = dict(labels)
        yield spans[name]

    monkeypatch.setattr(seq_blocks.tracing, "span", span)
    # float32 operands: in bfloat16 a top-6 choice that flips moves a
    # token's logits by more than any rounding
    _small(monkeypatch)
    seqs = es.make_histories(8, POSITIONS + 1, 63, 1.1, 5)
    engine = es.seeded_engine(seqs, 63)
    storage = memory_storage()
    ctx = create_workflow_context(storage, use_mesh=False)
    ep = EngineParams(datasource=("", None), algorithms=[("sasrec", dict(
        max_len=POSITIONS + 1, batch_size=2, steps=4, learning_rate=0.01,
        seed=11, block_spec=CFG))])
    instance = run_train(engine, ep, storage, engine_id="ssm", ctx=ctx)
    [model] = load_models(storage, engine, ep, instance, ctx)
    assert jax.tree_util.tree_map(
        lambda x: x.shape, model.params) == seq_blocks.param_shapes(SPEC)
    algo = engine.algorithm_classes["sasrec"](SequenceParams(
        **ep.algorithms[0][1]))
    out = algo.batch_predict(model, [{"user": "u1", "num": 5},
                                     {"user": "nobody"}])
    assert len(out[0]["itemScores"]) == 5 and out[1]["itemScores"] == []
    # a whole forward pass a query: the scores are the reference's logits
    top = out[0]["itemScores"][0]
    logits = np.asarray(reference.logits(
        jax.tree_util.tree_map(jnp.asarray, model.params),
        jnp.asarray(seqs[1:2, 1:]), CFG)[0, -1])
    seen = set(seqs[1].tolist())
    unseen = [i for i in range(1, 64) if i not in seen]
    assert int(top["item"][1:]) == max(unseen, key=lambda i: logits[i])
    assert top["score"] == pytest.approx(float(logits[unseen].max()),
                                         abs=1e-3)
    # the job's record: what every stack has, the experts', the scans'
    labels = spans["seq.wait"]
    assert labels["ssm_blocks"] == 4 and labels["ssm_chunks"] == 4
    assert labels["block_kinds"] == "M4 E4 *1"
    assert labels["ssm_state_bytes"] == 4 * 2 * 4 * 8 * 8 * 16 * 4
    assert labels["ssm_fwd_kernels"] == 8 and labels["ssm_bwd_kernels"] == 4
    # 64 channels in groups of 32: the plain convolution and norm
    assert labels["ssm_conv_kernels"] == labels["ssm_norm_kernels"] == 0
    assert labels["tokens_per_step"] == 2 * POSITIONS
    assert labels["dropped_tokens"] == 0 and labels["loop_steps"] == 1
    assert 0.0 < float(labels["router_bias_abs_max"]) <= 4 * 0.001 + 1e-9
    assert "window_blocks_visited" not in labels
    tokens = jnp.asarray(seqs[seq_blocks.epoch_order(8, 4, 2, 11)[0]])
    first = float(reference.loss(
        seq_blocks.init_params(SPEC, 11), tokens, CFG))
    assert float(labels["loss_first"]) == pytest.approx(first, abs=1e-4)
    assert float(labels["loss_last"]) < first


def test_a_history_the_chunk_does_not_divide_is_refused():
    seqs = es.make_histories(4, 31, 63, 1.1, 5)       # 30 positions
    params = SequenceParams(max_len=31, batch_size=2, steps=1,
                            learning_rate=0.01, seed=1, block_spec=CFG)
    with pytest.raises(ValueError, match="chunk_size 8 does not divide"):
        seq_blocks.train_lm(seqs, params)


def test_a_stack_without_scans_has_none_of_their_labels():
    """`attention_counters` of a program without a scan: the four keys
    it had (tests/test_attention_window_gqa.py holds their values)."""
    cfg = {**CFG, "hybrid_override_pattern": "E*E", "num_hidden_layers": 3}
    found = seq_blocks.step_attention_counters.__wrapped__(
        seq_blocks.BlockSpec.parse(cfg), 0.0321, (2, POSITIONS + 1))
    assert sorted(found) == ["attn_bwd_kernels", "attn_fwd_kernels",
                             "attn_residual_bytes", "layer_applications"]


# -- what parse refuses, and what it still gives ------------------------------

@pytest.mark.parametrize("change,match", [
    ({"hybrid_override_pattern": "ME-EM*EME"}, "hybrid_override_pattern"),
    ({"hybrid_override_pattern": "MEME"}, "hybrid_override_pattern"),
    ({"mamba_proj_bias": True}, "mamba_proj_bias"),
    ({"use_bias": True}, "use_bias"),
    ({"mlp_bias": True}, "mlp_bias"),
    ({"attention_bias": True}, "attention_bias"),
    ({"n_group": 2}, "n_group"),
    ({"topk_group": 2}, "n_group"),
    ({"n_groups": 3}, "n_groups"),
    ({"total_ut_steps": 2}, "total_ut_steps"),
    ({"kv_lora_rank": 8, "q_lora_rank": 8, "qk_nope_head_dim": 12,
      "qk_rope_head_dim": 4, "v_head_dim": 16}, "kv_lora_rank"),
    ({"num_nextn_predict_layers": 1}, "num_nextn_predict_layers"),
    ({"attention_rope": True}, "attention_rope"),
    ({"layer_types": ["full_attention"] * 9}, "layer_types"),
    ({"first_k_dense_replace": 1}, "first_k_dense_replace"),
    ({"mlp_hidden_act": "gelu"}, "mlp_hidden_act"),
    ({"hidden_act": "silu"}, "hidden_act"),      # states another than relu2
    ({"mamba_hidden_act": "gelu"}, "mamba_hidden_act"),
    ({"tie_word_embeddings": True}, "tie_word_embeddings"),
    ({"mamba_n_heads": 8}, "mamba_n_heads"),
    ({"mamba_d_state": 16}, "mamba_d_state"),
    ({"mamba_expand": 2}, "mamba_expand"),
    ({"layers_block_type": ["mamba"] * 9}, "layers_block_type"),
])
def test_what_the_stack_does_not_compute_is_refused_by_name(change, match):
    with pytest.raises(ValueError, match=match) as refused:
        seq_blocks.BlockSpec.parse({**CFG, **change})
    # and the message says which family's state-space keys are computed
    assert "hybrid_override_pattern" in str(refused.value)


def test_a_pattern_stack_must_state_that_its_attention_has_no_rope():
    cfg = {k: v for k, v in CFG.items() if k != "attention_rope"}
    with pytest.raises(ValueError, match="attention_rope"):
        seq_blocks.BlockSpec.parse(cfg)


@pytest.mark.parametrize("key", ["hidden_act", "mlp_hidden_act"])
def test_an_activation_the_stack_lacks_is_refused_under_either_key(key):
    """A stack with dense SwiGLU layers computes silu alone; relu2 is the
    experts' and the shared expert's."""
    path = os.path.join(HERE, "..", "benchmark", "configs",
                        "glm-4.7-flash-ep8.json")
    with open(path) as f:
        cfg = es.block_spec_of(json.load(f))
    cfg.pop("hidden_act")
    seq_blocks.BlockSpec.parse(cfg)              # unstated: silu
    for act in ("relu2", "gelu"):
        with pytest.raises(ValueError, match=key):
            seq_blocks.BlockSpec.parse({**cfg, key: act})
    assert seq_blocks.BlockSpec.parse({**cfg, key: "silu"}).expert_act == \
        "swiglu"


with open(os.path.join(HERE, "data", "block_specs_pr44.json")) as _f:
    ACCEPTED = json.load(_f)


@pytest.mark.parametrize("name", sorted(ACCEPTED))
def test_an_accepted_specification_parses_to_the_blockspec_it_gave(name):
    """tests/data/block_specs_pr44.json: every field `BlockSpec.parse`
    gave at PR 44 for the three accepted configurations; the fields that
    came since say there is no pattern."""
    with open(os.path.join(HERE, "..", "benchmark", "configs",
                           name + ".json")) as f:
        spec = seq_blocks.BlockSpec.parse(es.block_spec_of(json.load(f)))
    now = json.loads(json.dumps(dataclasses.asdict(spec)))
    assert {k: now[k] for k in ACCEPTED[name]} == ACCEPTED[name]
    assert spec.block_kinds == () and spec.mamba_num_heads == 0
    assert spec.expert_act == "swiglu"
    assert spec.shared_intermediate_size == (
        spec.n_shared_experts * spec.moe_intermediate_size)


# the step and the initialiser of the accepted cells' stacks, at their
# rehearsals' tiny sizes: sha256 of the lowered text, first sixteen digits.
# The initialisers' are PR 44's (the parent of the PR that brought the
# pattern's blocks); the steps' were until PR 46 changed the attention's
# forward kernel on purpose (transposed scores in key tiles, row
# statistics, a row lse), and are PR 46's now
LOWERED = {
    "mellum2-12b-ep4.train-8k": ("sequence-tiny.json", 41,
                                 "e64b2590afad2a4d", "963ceb728c145d65"),
    "glm-4.7-flash-ep8.train-8k-mtp": ("latent-tiny.json", 42,
                                       "d2d7900ef37de5c2", "9b74a423c99bb2bd"),
    "ouro-2.6b-l4.train-8k-loop": ("loop-tiny.json", 97,
                                   "a932ac73c797ec7b", "7105dac2051c3849"),
}


@pytest.mark.parametrize("cell", sorted(LOWERED))
def test_an_accepted_stack_initialises_and_lowers_as_it_did(cell):
    import hashlib

    from benchmark.harness import cells

    overlay, ids, step_sha, init_sha = LOWERED[cell]
    config = cells.load_cell(cell, os.path.join(
        cells.BENCH_DIR, "tests", "rehearse", overlay)).config
    spec = seq_blocks.BlockSpec.parse(es.block_spec_of(config))
    optimizer, step = seq_blocks.make_train_step(spec, 0.01)
    shapes = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s, jnp.float32),
        seq_blocks.param_shapes(spec), is_leaf=lambda x: isinstance(x, tuple))
    text = step.lower(shapes, jax.eval_shape(optimizer.init, shapes),
                      jax.ShapeDtypeStruct((2, ids), jnp.int32)).as_text()
    init = seq_blocks._init_program(spec).lower(
        jax.random.PRNGKey(0)).as_text()
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == step_sha
    assert hashlib.sha256(init.encode()).hexdigest()[:16] == init_sha
