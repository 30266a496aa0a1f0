"""The stack of gated grouped-query layers of models/seq_blocks.py
(`model_type` `laguna`: query heads and a rotating width by layer kind,
an RMSNorm on every query and key head, a gate a head on attention's
output, a leading dense layer told by `mlp_layer_types`, sigmoid-routed
experts beside a shared one) against the plain reference
(benchmark/reference/gated_gqa_moe_lm.py) on seeded weights at a tiny
size: the loss, the routing and every parameter's gradient; causality;
the partial rotation, the gate and the head norms by hand; the shares of
an expert layer, which add up to the uncut reference's; the parameter
counts at the published widths; the engine's round trip with the job's
`seq.wait` record; and what `BlockSpec.parse` refuses, by name."""

import contextlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import engines_sequence as es
from benchmark.harness import check_gated
from benchmark.reference import gated_gqa_moe_lm as reference
from pio_tpu.controller.engine import EngineParams
from pio_tpu.models import seq_blocks
from pio_tpu.models.sequence import SequenceParams
from pio_tpu.ops.moe import HeldExperts
from pio_tpu.workflow.context import create_workflow_context
from pio_tpu.workflow.train import load_models, run_train
from tests._tiny_train import memory_storage

HERE = os.path.dirname(__file__)
FULL, SLIDING = "full_attention", "sliding_attention"
CFG = {
    "model_type": "laguna", "hidden_size": 32, "num_hidden_layers": 5,
    "layer_types": [FULL, SLIDING, SLIDING, SLIDING] * 2,
    "num_attention_heads": 6,
    "num_attention_heads_per_layer": [6, 4, 4, 4] * 2,
    "num_key_value_heads": 2, "head_dim": 8, "sliding_window": 12,
    "rope_parameters": {
        FULL: {"rope_type": "yarn", "rope_theta": 500000, "factor": 4,
               "original_max_position_embeddings": 16, "beta_fast": 8,
               "beta_slow": 1, "attention_factor": 1.1386,
               "partial_rotary_factor": 0.5},
        SLIDING: {"rope_type": "default", "rope_theta": 10000,
                  "partial_rotary_factor": 1},
        "original_max_position_embeddings": 16},
    "partial_rotary_factor": 0.5, "gating": True, "use_qk_norm": True,
    "rms_norm_eps": 1e-6, "attention_bias": False,
    "mlp_layer_types": ["dense"] + ["sparse"] * 7, "intermediate_size": 48,
    "moe_intermediate_size": 16, "shared_expert_intermediate_size": 16,
    "num_experts": 2, "num_experts_routed": 8, "experts_held": [2, 4],
    "num_experts_per_tok": 3, "norm_topk_prob": True,
    "topk_method": "noaux_tc", "moe_routed_scaling_factor": 2.5,
    "moe_apply_router_weight_on_input": False,
    "tie_word_embeddings": False, "vocab_size": 64,
    "initializer_range": 0.2, "embedding_initializer_range": 1.0,
}
SPEC = seq_blocks.BlockSpec.parse(CFG)
POSITIONS = 40
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


def _biased(params):
    """A router's bias away from zero, as a trained model has it."""
    layers = [dict(lp, router_bias=0.05 * jax.random.normal(
        jax.random.PRNGKey(n), lp["router_bias"].shape))
              if "router_bias" in lp else lp
              for n, lp in enumerate(params["layers"])]
    return {**params, "layers": layers}


@pytest.fixture(scope="module")
def both_sides():
    with pytest.MonkeyPatch.context() as mp:
        _small(mp)
        params = _biased(seq_blocks.init_params(SPEC, 3))
        tokens = _tokens()
        (loss, aux), grads = jax.value_and_grad(
            seq_blocks.loss_and_counters, has_aux=True)(params, tokens, SPEC)
        want, want_grads = jax.value_and_grad(reference.loss)(
            params, tokens, CFG)
        counts = reference.routed_counts(params, tokens, CFG)
        return (float(loss), jax.device_get(aux), jax.device_get(grads),
                float(want), jax.device_get(want_grads),
                np.asarray(counts))


def test_the_specification_gives_a_kind_its_heads_and_its_rotation():
    assert SPEC.layer_types == (FULL, SLIDING, SLIDING, SLIDING, FULL)
    assert dict(SPEC.heads_by_kind) == {FULL: 6, SLIDING: 4}
    assert dict(SPEC.rotary_by_kind) == {FULL: 4, SLIDING: 8}
    assert SPEC.q_heads(FULL) == 6 and SPEC.q_heads(SLIDING) == 4
    assert SPEC.attn_gate and SPEC.head_norms
    assert SPEC.dense_layers == 1 and SPEC.router_blocks == (1, 2, 3, 4)
    assert SPEC.scoring == "sigmoid" and SPEC.routed_scaling_factor == 2.5
    assert SPEC.n_shared_experts == 1 and SPEC.shared_intermediate_size == 16
    assert SPEC.experts == HeldExperts(8, 3, (2, 4), True, 512, "sigmoid",
                                       2.5)
    shapes = seq_blocks.param_shapes(SPEC)["layers"]
    assert shapes[0]["wq"] == (32, 48) and shapes[0]["wo"] == (48, 32)
    assert shapes[1]["wq"] == (32, 32) and shapes[1]["wo"] == (32, 32)
    assert shapes[0]["w_gate_heads"] == (32, 6)
    assert shapes[1]["w_gate_heads"] == (32, 4)
    assert shapes[0]["q_head_norm"] == shapes[0]["k_head_norm"] == (8,)
    assert all(lp["wk"] == lp["wv"] == (32, 16) for lp in shapes)
    assert "mlp_gate" in shapes[0] and "router" not in shapes[0]
    assert shapes[1]["shared_up"] == (32, 16) and "mlp_up" not in shapes[1]
    assert shapes[1]["w_gate"] == (2, 32, 16)     # the experts' own gate
    tables = seq_blocks.rope_tables(SPEC, POSITIONS)
    assert tables[FULL][0].shape == (POSITIONS, 2)
    assert tables[SLIDING][0].shape == (POSITIONS, 4)
    assert check_gated.expected_shapes(CFG) == seq_blocks.param_shapes(SPEC)


def test_the_loss_and_the_routing_equal_the_references(both_sides):
    loss, aux, _, want, _, counts = both_sides
    assert loss == pytest.approx(want, abs=LOSS_ABS)
    assert aux["counts_all"].shape == (4, 2, 8)   # routers, histories, routed
    np.testing.assert_array_equal(aux["counts_all"].sum(axis=1), counts)
    np.testing.assert_array_equal(aux["counts"], aux["counts_all"][..., 2:4])
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
    assert np.linalg.norm(theirs) > 0
    assert np.linalg.norm(mine - theirs) <= GRAD_REL * np.linalg.norm(
        theirs), leaf


def test_in_bfloat16_the_loss_stays_near_the_references(monkeypatch):
    """The stack as it trains (bfloat16 operands, the kernels in
    interpret mode) against the float32 reference, and nearer still to
    the reference at the precision the configuration states."""
    monkeypatch.setattr(seq_blocks, "ATTN_BLOCK", 16)
    monkeypatch.setattr(seq_blocks, "MOE_TILE", 8)
    params = seq_blocks.init_params(SPEC, 4)
    tokens = _tokens(2)
    loss, _ = seq_blocks.loss_and_counters(params, tokens, SPEC)
    assert float(loss) == pytest.approx(
        float(reference.loss(params, tokens, CFG)), rel=0.01)
    assert float(loss) == pytest.approx(float(reference.loss(
        params, tokens, CFG, {"operands_bf16": 1})), rel=0.002)


@pytest.mark.parametrize("at", [9, 26])
def test_a_later_id_changes_no_earlier_output(monkeypatch, at):
    """Causality through both kinds of attention (the window shorter than
    the history), the gate and the routed experts."""
    _small(monkeypatch)
    params = seq_blocks.init_params(SPEC, 5)
    ids = _tokens(3)[:, :-1]
    moved = ids.at[:, at].set(ids[:, at] % 63 + 1)
    before, _ = seq_blocks.hidden_states(params, ids, SPEC)
    after, _ = seq_blocks.hidden_states(params, moved, SPEC)
    np.testing.assert_array_equal(before[:, :at], after[:, :at])
    assert np.abs(before[:, at:] - after[:, at:]).max(axis=-1).min() > 0


# -- the new arithmetic, by hand ----------------------------------------------

def test_the_partial_rotation_by_hand():
    """Four of a head's eight dimensions rotate, as the pairs (0, 2) and
    (1, 3); the other four pass."""
    x = jnp.arange(1.0, 17.0).reshape(1, 1, 2, 8)
    angle = jnp.array([[0.0, 0.0], [jnp.pi / 2, jnp.pi]])   # (S, 2)
    out = np.asarray(seq_blocks.apply_rope(x, jnp.cos(angle),
                                           jnp.sin(angle)))
    np.testing.assert_allclose(out[0, 0, 0], x[0, 0, 0])    # position 0
    # position 1: pair (0, 2) a quarter turn, pair (1, 3) a half turn
    np.testing.assert_allclose(
        out[0, 0, 1], [-11.0, -10.0, 9.0, -12.0, 13.0, 14.0, 15.0, 16.0],
        atol=1e-5)
    # the whole head: the pairs are (i, i + 4), as before
    whole = np.asarray(seq_blocks.apply_rope(
        x, jnp.cos(jnp.zeros((2, 4)) + jnp.pi / 2),
        jnp.sin(jnp.zeros((2, 4)) + jnp.pi / 2)))
    np.testing.assert_allclose(
        whole[0, 0, 0], [-5.0, -6.0, -7.0, -8.0, 1.0, 2.0, 3.0, 4.0],
        atol=1e-5)


def test_the_frequencies_are_over_the_dimensions_that_rotate():
    """A full layer's YaRN over its 4 rotating dimensions, cos and sin
    times the attention factor; a sliding layer's default RoPE over 8:
    the program's tables are the reference's."""
    tables = seq_blocks.rope_tables(SPEC, POSITIONS)
    for kind, dims in ((FULL, 4), (SLIDING, 8)):
        with_, plus, partner = reference.rotation(
            CFG["rope_parameters"][kind], 8, dims, POSITIONS)
        cos, sin = tables[kind]
        np.testing.assert_allclose(cos, with_[:, :dims // 2], rtol=1e-6)
        np.testing.assert_allclose(sin, plus[:, dims // 2:dims], rtol=1e-6,
                                   atol=1e-7)
        assert partner.tolist()[:dims] == list(range(dims // 2, dims)) + \
            list(range(dims // 2))
        assert partner.tolist()[dims:] == list(range(dims, 8))
    assert tables[FULL][0][0, 0] == pytest.approx(1.1386)   # cos(0) * factor
    inv, scale = reference.inv_frequencies(
        CFG["rope_parameters"][SLIDING], 8)
    np.testing.assert_allclose(inv, 10000.0 ** -(np.arange(4) / 4))
    assert scale == 1.0


def _addend(spec, lp, x, kind):
    tables = seq_blocks.rope_tables(spec, x.shape[1])
    return np.asarray(seq_blocks._attention_half(
        lp, x, *tables[kind], spec=spec, kind=kind) - x)


@pytest.mark.parametrize("n,kind", [(0, FULL), (1, SLIDING)])
def test_the_gate_by_hand(monkeypatch, n, kind):
    """W_g = 0: every head's gate is softplus(0) = ln 2, and the layer
    adds ln 2 times what it adds without `gating`. One head's column
    pushed far below zero: that head's rows of W_o stop mattering."""
    _small(monkeypatch)
    lp = seq_blocks.init_params(SPEC, 8)["layers"][n]
    x = jax.random.normal(jax.random.PRNGKey(2), (2, POSITIONS, 32))
    plain = seq_blocks.BlockSpec.parse(
        {k: v for k, v in CFG.items() if k != "gating"})
    ungated = _addend(plain, {k: v for k, v in lp.items()
                              if k != "w_gate_heads"}, x, kind)
    zero = dict(lp, w_gate_heads=jnp.zeros_like(lp["w_gate_heads"]))
    np.testing.assert_allclose(_addend(SPEC, zero, x, kind),
                               np.log(2.0) * ungated, rtol=2e-5, atol=2e-6)
    # the normed input's dimensions sum to something; a column of -1e4
    # times it is far from zero either way, so gate on |.|: all -1e4
    # against a y whose sum is positive at every position
    y = seq_blocks.rms_norm(x, lp["norm1"], 1e-6)
    sign = jnp.sign(jnp.sum(y, axis=-1))
    assert bool(jnp.all(sign != 0))
    x_pos = x * sign[..., None]                  # now sum(y) > 0 everywhere
    shut = zero["w_gate_heads"].at[:, 0].set(-1e4)
    a = _addend(SPEC, dict(lp, w_gate_heads=shut), x_pos, kind)
    wo = lp["wo"].at[:8].set(0.0)                # head 0's rows of W_o
    b = _addend(SPEC, dict(lp, w_gate_heads=shut, wo=wo), x_pos, kind)
    np.testing.assert_allclose(a, b, atol=1e-6)
    assert np.abs(a).max() > 1e-3


@pytest.mark.parametrize("n,kind", [(0, FULL), (1, SLIDING)])
def test_the_head_norms_by_hand(monkeypatch, n, kind):
    """A head is normed over its own dimensions before it rotates: W_q
    seven times as large, or W_k a third, changes nothing but through
    eps; a gain doubled on q and halved on k leaves every score where it
    was; a gain doubled on q alone does not."""
    _small(monkeypatch)
    lp = seq_blocks.init_params(SPEC, 9)["layers"][n]
    x = jax.random.normal(jax.random.PRNGKey(4), (2, POSITIONS, 32))
    base = _addend(SPEC, lp, x, kind)
    np.testing.assert_allclose(
        _addend(SPEC, dict(lp, wq=7.0 * lp["wq"], wk=0.3 * lp["wk"]), x,
                kind), base, rtol=1e-3, atol=1e-4)
    gained = _addend(SPEC, dict(lp, q_head_norm=2.0 * lp["q_head_norm"]), x,
                     kind)
    assert np.abs(gained - base).max() > 1e-3
    np.testing.assert_allclose(
        _addend(SPEC, dict(lp, q_head_norm=lp["q_head_norm"] * 0.5,
                           k_head_norm=lp["k_head_norm"] * 2.0), x, kind),
        base, rtol=1e-4, atol=1e-5)
    # RMSNorm over a head's 8: the numbers
    t = jnp.array([[3.0, 4.0, 0.0, 0.0, 0.0, 0.0, 0.0, 0.0]])
    np.testing.assert_allclose(
        seq_blocks.rms_norm(t, jnp.ones(8), 0.0)[0, :2],
        [3 / np.sqrt(25 / 8), 4 / np.sqrt(25 / 8)], rtol=1e-6)


def test_tile_fill_and_the_head_counters_by_hand():
    """`expert_tile_fill`: tiles of 8 rows; groups of 3, 9 and 0 rows
    visit 1 + 2 + 1 tiles of which 12 rows are real."""
    held = HeldExperts(8, 3, (0, 3), True, 8)
    counts = np.array([[[[3, 9, 0]]]])
    assert seq_blocks.tile_fill(counts, held) == 12 / 32
    assert seq_blocks.tile_fill(np.array([[[[8, 16, 8]]]]), held) == 1.0
    assert seq_blocks.head_counters(SPEC) == {
        "attn_q_heads_full": 6, "attn_rotary_dims_full": 4,
        "attn_q_heads_window": 4, "attn_rotary_dims_window": 8}


def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_references(
        monkeypatch):
    """The share test of this configuration (guide section 4): 4 shares
    of 2 of 8 experts. Every share routes over all 8, selects on score +
    bias, and gives its own experts' part; the parts added up, with the
    shared expert (which every chip computes alike) counted once, are
    the uncut reference's layer."""
    _small(monkeypatch)
    whole_cfg = {**CFG, "num_experts": 8, "experts_held": [0, 8]}
    lp = _biased(seq_blocks.init_params(
        seq_blocks.BlockSpec.parse(whole_cfg), 6))["layers"][2]
    h = jax.random.normal(jax.random.PRNGKey(5), (POSITIONS, 32))
    flags = reference.with_faults(whole_cfg)
    whole, counts = reference.experts_half(lp, h, whole_cfg, flags,
                                           share=(0, 8))
    shared = reference.swiglu(
        reference.rms_norm(h, lp["norm2"], 1e-6), lp["shared_gate"],
        lp["shared_up"], lp["shared_down"], flags)
    parts, seen = [], 0
    for lo in range(0, 8, 2):
        spec = seq_blocks.BlockSpec.parse(
            {**CFG, "experts_held": [lo, lo + 2]})
        mine = {k: (v[lo:lo + 2] if k in ("w_gate", "w_up", "w_down") else v)
                for k, v in lp.items()}
        out, aux = seq_blocks._experts_half(mine, h, spec=spec)
        parts.append(np.asarray(out - h - shared))   # this share's experts
        seen += int(aux["counts"].sum())
        np.testing.assert_array_equal(aux["counts"], counts[lo:lo + 2])
        theirs, _ = reference.experts_half(mine, h, CFG, flags,
                                           share=(lo, lo + 2), shared=False)
        np.testing.assert_allclose(parts[-1], theirs, atol=3e-5, rtol=3e-5)
    np.testing.assert_allclose(sum(parts) + shared, whole, atol=3e-5,
                               rtol=3e-5)
    assert seen == POSITIONS * 3                 # every choice computed once


def test_parameter_counts_at_the_published_widths():
    """ISSUE 49: 29.46 M a full layer's attention (gate and head norms
    with it), 37.88 M a sliding layer's; this rank 490.3 M parameters,
    7.84 GB at 16 bytes; the whole model 33.44 B."""
    path = os.path.join(HERE, "..", "benchmark", "configs",
                        "laguna-xs2-ep16.json")
    with open(path) as f:
        config = json.load(f)
    cfg = es.block_spec_of(config)
    spec = seq_blocks.BlockSpec.parse(cfg)
    shapes = seq_blocks.param_shapes(spec)

    def count(tree):
        return sum(int(np.prod(s)) for s in jax.tree_util.tree_leaves(
            tree, is_leaf=lambda x: isinstance(x, tuple)))

    attention = ("wq", "wk", "wv", "wo", "w_gate_heads", "q_head_norm",
                 "k_head_norm")
    assert spec.layer_types == (FULL, SLIDING, SLIDING, SLIDING, FULL)
    assert count({k: shapes["layers"][0][k] for k in attention}) == \
        2 * 2048 * 6144 + 2 * 2048 * 1024 + 2048 * 48 + 256 == 29_458_688
    assert count({k: shapes["layers"][1][k] for k in attention}) == \
        37_880_064
    assert count(shapes["layers"][0]) == 79_794_432
    assert count(shapes["layers"][1]) == 91_886_080
    assert count(shapes["layers"][4]) == 83_464_704
    assert count(shapes) == 490_299_648
    assert count(shapes) * 16 / 1e9 == pytest.approx(7.84, abs=0.005)
    assert spec.experts == HeldExperts(256, 8, (0, 16), True, 512,
                                       "sigmoid", 2.5)
    assert check_gated.expected_shapes(cfg) == shapes
    published = config["published"]
    assert {k: config[k] for k in published} == {
        "num_hidden_layers": 5, "num_experts": 16, "vocab_size": 12544}
    assert sorted(published) == sorted(config["reduced"])
    whole = dict(cfg, **published)
    whole.pop("experts_held")
    assert count(seq_blocks.param_shapes(
        seq_blocks.BlockSpec.parse(whole))) == 33_442_617_088


# -- the engine ---------------------------------------------------------------

def test_run_train_persists_loads_and_predicts_the_stack(monkeypatch):
    spans = {}

    @contextlib.contextmanager
    def span(name, **labels):
        spans[name] = dict(labels)
        yield spans[name]

    monkeypatch.setattr(seq_blocks.tracing, "span", span)
    # float32 operands: in bfloat16 a top-3 choice that flips moves a
    # token's logits by more than any rounding
    _small(monkeypatch)
    seqs = es.make_histories(8, POSITIONS + 1, 63, 1.1, 5)
    engine = es.seeded_engine(seqs, 63)
    storage = memory_storage()
    ctx = create_workflow_context(storage, use_mesh=False)
    ep = EngineParams(datasource=("", None), algorithms=[("sasrec", dict(
        max_len=POSITIONS + 1, batch_size=2, steps=4, learning_rate=0.01,
        seed=11, block_spec=CFG))])
    instance = run_train(engine, ep, storage, engine_id="gated", ctx=ctx)
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
    # the job's record: what every stack has, the heads by kind, the
    # experts' with the tiles' fill, the band's
    labels = spans["seq.wait"]
    assert labels["attn_q_heads_full"] == 6
    assert labels["attn_q_heads_window"] == 4
    assert labels["attn_rotary_dims_full"] == 4
    assert labels["attn_rotary_dims_window"] == 8
    # ~15 rows a history and held expert, in tiles of 8
    assert 0.5 < float(labels["expert_tile_fill"]) <= 1.0
    assert labels["attn_fwd_kernels"] == labels["layer_applications"] == 5
    assert labels["tokens_per_step"] == 2 * POSITIONS
    assert labels["dropped_tokens"] == 0 and labels["loop_steps"] == 1
    assert labels["window_blocks_visited"] <= labels["window_blocks_in_band"]
    assert 0.0 < float(labels["router_bias_abs_max"]) <= 4 * 0.001 + 1e-9
    tokens = jnp.asarray(seqs[seq_blocks.epoch_order(8, 4, 2, 11)[0]])
    first = float(reference.loss(
        seq_blocks.init_params(SPEC, 11), tokens, CFG))
    assert float(labels["loss_first"]) == pytest.approx(first, abs=1e-4)
    assert float(labels["loss_last"]) < first


def test_an_accepted_stack_has_its_kinds_head_counters_and_no_more():
    """A stack whose kinds share one head count writes it under both
    labels; a pattern stack's attention has no rotation."""
    with open(os.path.join(HERE, "..", "benchmark", "configs",
                           "mellum2-12b-ep4.json")) as f:
        spec = seq_blocks.BlockSpec.parse(es.block_spec_of(json.load(f)))
    assert seq_blocks.head_counters(spec) == {
        "attn_q_heads_full": 32, "attn_rotary_dims_full": 128,
        "attn_q_heads_window": 32, "attn_rotary_dims_window": 128}
    with open(os.path.join(HERE, "..", "benchmark", "configs",
                           "nemotron-3-nano-ep16.json")) as f:
        spec = seq_blocks.BlockSpec.parse(es.block_spec_of(json.load(f)))
    assert seq_blocks.head_counters(spec) == {
        "attn_q_heads_full": 32, "attn_rotary_dims_full": 0}


# -- what parse refuses, and what it still gives ------------------------------

@pytest.mark.parametrize("change,match", [
    # one kind, two counts
    ({"num_attention_heads_per_layer": [6, 4, 4, 6, 6]},
     "num_attention_heads_per_layer"),
    # fewer counts than layers
    ({"num_attention_heads_per_layer": [6, 4, 4]},
     "num_attention_heads_per_layer"),
    # not whole groups of key-value heads
    ({"num_attention_heads_per_layer": [6, 3, 3, 3, 6]},
     "num_attention_heads_per_layer"),
    ({"gating": "per-channel"}, "gating"),
    ({"gating": "elementwise"}, "gating"),
    ({"gating_types": ["per_head", "per_channel"]}, "gating_types"),
    ({"mlp_layer_types": ["dense", "sparse", "dense", "sparse", "sparse"]},
     "mlp_layer_types"),
    ({"mlp_layer_types": ["dense", "moe", "sparse", "sparse", "sparse"]},
     "mlp_layer_types"),
    ({"mlp_layer_types": ["dense"] * 5}, "mlp_layer_types"),
    ({"first_k_dense_replace": 2}, "mlp_layer_types"),
    ({"moe_apply_router_weight_on_input": True},
     "moe_apply_router_weight_on_input"),
    ({"moe_router_logit_softcapping": 30.0}, "moe_router_logit_softcapping"),
    ({"routed_scaling_factor": 1.0}, "moe_routed_scaling_factor"),
    # the top-level factor is no kind's
    ({"partial_rotary_factor": 0.25}, "partial_rotary_factor"),
    # an odd number of rotating dimensions
    ({"rope_parameters": {**CFG["rope_parameters"], FULL: {
        **CFG["rope_parameters"][FULL], "partial_rotary_factor": 0.375}},
      "partial_rotary_factor": 0.375}, "rope_parameters"),
    ({"total_ut_steps": 2}, "num_attention_heads_per_layer"),
    ({"kv_lora_rank": 8, "q_lora_rank": 8, "qk_nope_head_dim": 12,
      "qk_rope_head_dim": 4, "v_head_dim": 16}, "gating"),
    ({"tie_word_embeddings": True}, "tie_word_embeddings"),
    ({"attention_bias": True}, "attention_bias"),
])
def test_what_the_stack_does_not_compute_is_refused_by_name(change, match):
    with pytest.raises(ValueError, match=match):
        seq_blocks.BlockSpec.parse({**CFG, **change})


def test_a_top_level_rotary_factor_needs_every_kinds_own():
    """`partial_rotary_factor` 0.5 at the top level alone is refused, as
    it was: a kind's width comes from `rope_parameters[kind]`."""
    ropes = {kind: {k: v for k, v in CFG["rope_parameters"][kind].items()
                    if k != "partial_rotary_factor"}
             for kind in (FULL, SLIDING)}
    with pytest.raises(ValueError, match="partial_rotary_factor"):
        seq_blocks.BlockSpec.parse({**CFG, "rope_parameters": ropes})
    one = {**ropes, FULL: CFG["rope_parameters"][FULL]}
    with pytest.raises(ValueError, match="partial_rotary_factor"):
        seq_blocks.BlockSpec.parse({**CFG, "rope_parameters": one})
    whole = seq_blocks.BlockSpec.parse(
        {**CFG, "rope_parameters": ropes, "partial_rotary_factor": 1})
    assert whole.rotary_by_kind == () and whole.rotary_dims(FULL) == 8


def test_what_a_specification_may_spell_otherwise():
    assert seq_blocks.BlockSpec.parse(
        {**CFG, "gating": "per-head",
         "gating_types": ["per_head"] * 5}).attn_gate
    plain = seq_blocks.BlockSpec.parse(
        {k: v for k, v in CFG.items() if k not in ("gating", "use_qk_norm")})
    assert not plain.attn_gate and not plain.head_norms
    assert "w_gate_heads" not in seq_blocks.param_shapes(plain)["layers"][0]
    assert "q_head_norm" not in seq_blocks.param_shapes(plain)["layers"][0]
    assert seq_blocks.BlockSpec.parse(
        {**CFG, "moe_router_logit_softcapping": 0}) == SPEC
