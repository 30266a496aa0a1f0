"""models/seq_blocks.py, the sequence engine's block stack, against the
plain reference (benchmark/reference/sequence_lm.py) on seeded weights at
a small size: logits, loss, every parameter's gradient; the rotary
frequencies; and the engine's own path (SequenceAlgorithm.train ->
train_sequence_model -> persist -> batch_predict)."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import sequence_lm as reference
from pio_tpu.data.bimap import EntityIdIndex
from pio_tpu.models import seq_blocks
from pio_tpu.models.sequence import (
    SequenceAlgorithm,
    SequenceData,
    SequenceParams,
)
from pio_tpu.obs import profile

CFG = {
    "hidden_size": 32, "num_hidden_layers": 4,
    "layer_types": ["sliding_attention"] * 3 + ["full_attention"] + ["x"] * 4,
    "mlp_layer_types": ["sparse"] * 8,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 8,
    "sliding_window": 12,
    "rope_parameters": {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 10000, "factor": 4,
            "original_max_position_embeddings": 16, "beta_fast": 8,
            "beta_slow": 1, "attention_factor": 1.1386},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000}},
    "rms_norm_eps": 1e-6, "moe_intermediate_size": 16, "num_experts": 4,
    "num_experts_routed": 8, "experts_held": [2, 6],
    "num_experts_per_tok": 3, "norm_topk_prob": True, "vocab_size": 50,
    "hidden_act": "silu", "attention_bias": False,
    "tie_word_embeddings": False, "initializer_range": 0.3,
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


@pytest.fixture(scope="module")
def case():
    params = seq_blocks.init_params(SPEC, 3)
    tokens = jnp.asarray(np.random.default_rng(0).integers(1, 50, (2, 41)),
                         jnp.int32)
    with pytest.MonkeyPatch.context() as mp:
        _small(mp)
        (loss, counters), grads = jax.value_and_grad(
            seq_blocks.loss_and_counters, has_aux=True)(params, tokens, SPEC)
    ref_loss, ref_grads = jax.value_and_grad(reference.loss)(
        params, tokens, CFG)
    return {"params": params, "tokens": tokens, "loss": loss,
            "counters": counters, "grads": grads, "ref_loss": ref_loss,
            "ref_grads": ref_grads}


def test_loss_equals_the_references(case):
    assert abs(float(case["loss"]) - float(case["ref_loss"])) < 2e-5
    counts = np.asarray(case["counters"]["counts"])
    assert counts.shape == (4, 2, 4)           # layers, histories, held
    assert int(np.asarray(case["counters"]["dropped"]).sum()) == 0


def _leaf_names():
    shapes = seq_blocks.param_shapes(SPEC)
    paths = jax.tree_util.tree_leaves_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    return [jax.tree_util.keystr(p) for p, _ in paths]


@pytest.mark.parametrize("leaf", _leaf_names())
def test_every_parameters_gradient_equals_the_references(case, leaf):
    got = dict((jax.tree_util.keystr(p), g) for p, g in
               jax.tree_util.tree_leaves_with_path(case["grads"]))[leaf]
    want = dict((jax.tree_util.keystr(p), g) for p, g in
                jax.tree_util.tree_leaves_with_path(case["ref_grads"]))[leaf]
    err = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
    assert err < 5e-5, (leaf, err)


def test_last_position_logits_equal_the_references(case):
    ids = case["tokens"][:, :-1]
    want = reference.logits(case["params"], ids, CFG)[:, -1]
    got = seq_blocks.last_logits(case["params"], ids, SPEC)
    np.testing.assert_allclose(got, want, atol=5e-5, rtol=5e-5)


@pytest.mark.parametrize("fault", [
    {"top_k": 2}, {"norm_topk": False}, {"window": 13},
    {"kv_head": "i//4"}, {"rope_full": "default"}])
def test_a_faulty_reference_differs(case, fault):
    """The reference's faults are faults: each moves the loss."""
    faulty = float(reference.loss(case["params"], case["tokens"], CFG, fault))
    assert abs(faulty - float(case["ref_loss"])) > 1e-3


def _yarn_numpy(rope, dim):
    """transformers' _compute_yarn_parameters, transcribed."""
    base, factor = rope["rope_theta"], rope["factor"]
    orig = rope["original_max_position_embeddings"]
    pos_freqs = base ** (np.arange(0, dim, 2).astype(np.float64) / dim)
    extrapolation, interpolation = 1.0 / pos_freqs, 1.0 / (factor * pos_freqs)

    def correction_dim(num_rotations):
        return (dim * math.log(orig / (num_rotations * 2 * math.pi))
                ) / (2 * math.log(base))

    low = max(math.floor(correction_dim(rope["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rope["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0, 1)
    extrapolation_factor = 1 - ramp
    return (interpolation * (1 - extrapolation_factor)
            + extrapolation * extrapolation_factor)


@pytest.mark.parametrize("rope,dim", [
    ({"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
      "original_max_position_embeddings": 8192, "beta_fast": 32,
      "beta_slow": 1, "attention_factor": 1.2772588722239782}, 128),
    (CFG["rope_parameters"]["full_attention"], 8),
    ({"rope_type": "yarn", "rope_theta": 10000, "factor": 2,
      "original_max_position_embeddings": 64, "beta_fast": 4,
      "beta_slow": 4}, 16),
])
def test_yarn_frequencies_against_a_numpy_transcription(rope, dim):
    inv, scale = seq_blocks.rope_inv_freq(rope, dim)
    np.testing.assert_allclose(inv, _yarn_numpy(rope, dim), rtol=1e-12)
    want = rope.get("attention_factor", 0.1 * math.log(rope["factor"]) + 1)
    assert scale == pytest.approx(want)
    cos, sin = reference.rope_tables(rope, dim, 9)
    angle = np.arange(9)[:, None] * inv[None]
    np.testing.assert_allclose(cos, np.cos(angle) * scale, atol=1e-6)
    np.testing.assert_allclose(sin, np.sin(angle) * scale, atol=1e-6)
    # high-frequency pairs keep their frequency, low ones are interpolated
    plain, _ = seq_blocks.rope_inv_freq(
        {"rope_type": "default", "rope_theta": rope["rope_theta"]}, dim)
    assert inv[0] == pytest.approx(plain[0])
    assert inv[-1] == pytest.approx(plain[-1] / rope["factor"])


@pytest.mark.parametrize("change,match", [
    ({"hidden_act": "gelu"}, "hidden_act"),
    ({"tie_word_embeddings": True}, "tie_word_embeddings"),
    ({"attention_bias": True}, "attention_bias"),
    ({"mlp_layer_types": ["dense"] * 8}, "mlp_layer_types"),
    ({"layer_types": ["linear_attention"] * 8}, "layer_types"),
    ({"experts_held": [0, 3]}, "experts_held"),
])
def test_what_the_stack_does_not_compute_is_refused(change, match):
    with pytest.raises(ValueError, match=match):
        seq_blocks.BlockSpec.parse({**CFG, **change})


def test_epoch_order_takes_every_history_once():
    order = seq_blocks.epoch_order(12, 6, 2, seed=7)
    assert sorted(order.reshape(-1).tolist()) == list(range(12))
    assert (order == seq_blocks.epoch_order(12, 6, 2, seed=7)).all()
    again = seq_blocks.epoch_order(12, 9, 2, seed=7)   # begins again
    assert (again[6:].reshape(-1) == order.reshape(-1)[:6]).all()


@pytest.mark.parametrize("embedding", [None, 1.0])
def test_the_embeddings_rows_take_their_own_range(embedding):
    """`embedding_initializer_range` scales the embedding's rows alone,
    and without the key they take `initializer_range` like the rest."""
    wide = dict(CFG, hidden_size=64, vocab_size=512)
    if embedding is not None:
        wide["embedding_initializer_range"] = embedding
    params = seq_blocks.init_params(seq_blocks.BlockSpec.parse(wide), 5)
    want = embedding or CFG["initializer_range"]
    assert abs(float(jnp.std(params["embed"])) / want - 1) < 0.05
    for name in ("head", "layers"):
        leaf = params[name] if name == "head" else params[name][0]["wq"]
        assert abs(float(jnp.std(leaf)) / CFG["initializer_range"] - 1) < 0.05


def test_the_step_program_does_not_hold_the_step_count():
    """Jobs of 2 and of 5 steps run one compiled step: the step takes a
    batch, not the job's histories."""
    _, step = seq_blocks.make_train_step(SPEC, 0.02)
    for steps in (2, 5):
        p = SequenceParams(max_len=41, batch_size=2, steps=steps,
                           learning_rate=0.02, seed=3, block_spec=CFG)
        seq_blocks.train_lm(_data().seqs, p)
    assert step._cache_size() == 1


def test_params_with_a_specification_stay_hashable():
    p = SequenceParams(block_spec=CFG)
    assert isinstance(p.block_spec, str) and hash(p) == hash(
        SequenceParams(block_spec=dict(CFG)))
    assert seq_blocks.BlockSpec.parse(p.block_spec) == SPEC
    assert SequenceParams().block_spec is None       # the toy preset


def _data(n=8, length=41, seed=5):
    seqs = np.random.default_rng(seed).integers(1, 50, (n, length)).astype(
        np.int32)
    return SequenceData(seqs, EntityIdIndex([f"u{i}" for i in range(n)]),
                        EntityIdIndex([f"i{i}" for i in range(1, 50)]))


def test_the_engine_trains_persists_and_serves_the_stack():
    from pio_tpu.workflow.checkpoint import models_from_bytes, models_to_bytes

    p = SequenceParams(max_len=41, batch_size=2, steps=6, learning_rate=0.02,
                       seed=11, block_spec=CFG)
    algo = SequenceAlgorithm(p)
    model = algo.train(None, _data())
    want = jax.tree_util.tree_map(
        lambda s: s, seq_blocks.param_shapes(SPEC),
        is_leaf=lambda x: isinstance(x, tuple))
    got = jax.tree_util.tree_map(lambda x: x.shape, model.params)
    assert got == want
    loaded = models_from_bytes(models_to_bytes([model]))[0]
    out = algo.batch_predict(loaded, [{"user": "u1", "num": 5},
                                      {"user": "nobody"}])
    assert len(out[0]["itemScores"]) == 5 and out[1]["itemScores"] == []
    # the seeded start is the reference's step-0 loss, and training moved
    tokens = jnp.asarray(_data().seqs[seq_blocks.epoch_order(8, 6, 2, 11)[0]])
    start = seq_blocks.init_params(SPEC, 11)
    first = float(reference.loss(start, tokens, CFG))
    after = float(reference.loss(
        jax.tree_util.tree_map(jnp.asarray, model.params), tokens, CFG))
    assert after < first - 0.05


def test_tiles_used_share_of_a_hand_counted_routing():
    """`expert_tiles_used_share` on `seq.wait`: tiles of 8 rows, three
    held experts, histories of 40 positions whose every choice could be
    held: (40 x 3 + 3 x 8) / 8 = 18 tiles. By hand: a history that sent
    0, 8 and 9 tokens fills 1 + 1 + 2 tiles (an empty group owns one),
    one that sent 1, 16 and 17 fills 1 + 2 + 3, one that sent 40 to each
    fills 15: the mean of 4, 6 and 15 over 18."""
    held = seq_blocks.HeldExperts(8, 3, (2, 5), True, 8)
    assert held.row_capacity(40) == 18 * 8
    counts = np.array([[[[0, 8, 9], [1, 16, 17]]], [[[40, 40, 40]] * 2]])
    assert counts.shape == (2, 1, 2, 3)         # steps, routers, histories
    assert seq_blocks.tiles_used_share(counts[:1], held, 40) == \
        pytest.approx((4 + 6) / 2 / 18)
    assert seq_blocks.tiles_used_share(counts, held, 40) == \
        pytest.approx((4 + 6 + 15 + 15) / 4 / 18)


def test_a_history_with_padding_is_refused():
    data = _data()
    data.seqs[0, 0] = 0
    p = SequenceParams(max_len=41, batch_size=2, steps=1, block_spec=CFG)
    with pytest.raises(ValueError, match="PAD"):
        SequenceAlgorithm(p).train(None, data)


@pytest.mark.parametrize("op_name,scope", [
    ("jit(step)/jvp(seq.attn.proj)/dot_general", "seq.attn.proj"),
    ("jit(step)/transpose(jvp(seq.head_loss))/while/body/dot_general",
     "seq.head_loss"),
    ("jit(step)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/seq.moe.route/jit(searchsorted)/jit(step)/"
     "transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/seq.moe.route/sort", "seq.moe.route"),
    ("jit(step)/seq.optimizer/mul", "seq.optimizer"),
    ("jit(_train_jit)/while/body/als.user/als.gather/dot_general",
     "als.user/als.gather"),
    ("jit(f)/while/body/dot_general", None),
    ("jit(f)/my.seq.thing/add", None),
    # the prediction module's scopes nest under its own
    ("jit(step)/jvp(seq.mtp)/seq.attn.latent/dot_general",
     "seq.mtp/seq.attn.latent"),
    ("jit(step)/transpose(jvp(seq.mtp))/seq.head_loss/while/body/"
     "dot_general", "seq.mtp/seq.head_loss"),
    ("jit(step)/transpose(jvp(seq.mtp))/while/body/closed_call/checkpoint/"
     "rematted_computation/seq.mtp/seq.moe.route/jit(searchsorted)/"
     "jit(step)/transpose(jvp(seq.mtp))/while/body/closed_call/checkpoint/"
     "rematted_computation/seq.mtp/seq.moe.route/sort",
     "seq.mtp/seq.moe.route"),
    ("jit(step)/jvp(seq.mtp)/seq.embed/gather", "seq.mtp/seq.embed"),
    ("jit(step)/jvp(seq.mtp)/concatenate", "seq.mtp"),
    ("jit(step)/seq.optimizer/sign", "seq.optimizer"),
])
def test_profile_joins_the_stacks_scopes(op_name, scope):
    assert profile.scope_of_op_name(op_name) == scope


# -- what PR 33 added to the stack leaves the older configuration alone ------
# (the two hashes below were commit 2ef95e1's until PR 34 changed the expert
# layer's moves on purpose, PR 34's until PR 40 let the attention
# forward's o and lse cross the checkpoint, PR 40's until PR 42 made the
# attention's backward pass one kernel, PR 42's until PR 43 made the
# held experts' FFN one fused grouped op, and PR 43's until PR 46 gave the
# attention's forward kernel transposed scores in key tiles, row statistics
# and a row lse; they pin PR 46's text the same way)

def _step_lowered(cfg, learning_rate, batch):
    spec = seq_blocks.BlockSpec.parse(cfg)
    optimizer, step = seq_blocks.make_train_step(spec, learning_rate)
    shapes = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s, jnp.float32),
        seq_blocks.param_shapes(spec), is_leaf=lambda x: isinstance(x, tuple))
    return step.lower(shapes, jax.eval_shape(optimizer.init, shapes),
                      jax.ShapeDtypeStruct(batch, jnp.int32))


def _step_text(cfg, learning_rate, batch):
    return _step_lowered(cfg, learning_rate, batch).as_text()


def _sha(text):
    import hashlib

    return hashlib.sha256(text.encode()).hexdigest()


def test_the_window_and_full_stacks_step_program_is_pr_46s():
    """Latent attention, the dense layer, the shared expert, the sigmoid
    router and the prediction module are chosen by the specification: a
    specification without them lowers to one program text whatever a
    later specification's keys add (the hash is of PR 46's text, at this
    file's small blocks)."""
    assert _sha(_step_text(CFG, 0.0625, (2, 41))) == (
        "be061931219deae003fc92ba552a225cbf0cd70db88ae19bb90dd39bdc0491a9")


def test_mellum2_12b_ep4s_step_program_is_pr_46s(monkeypatch):
    """The benchmark's configuration at its timed shapes and the
    program's own blocks: PR 46's text, by hash."""
    import json
    import os

    from benchmark import engines_sequence as es

    monkeypatch.undo()                   # the published widths' blocks
    path = os.path.join(os.path.dirname(__file__), "..", "benchmark",
                        "configs", "mellum2-12b-ep4.json")
    with open(path) as f:
        cfg = es.block_spec_of(json.load(f))
    assert _sha(_step_text(cfg, 1e-4, (2, 8193))) == (
        "9cc31a47c7bf8340dd2bf5956dbd8ff9f1046a6bc9e351fda4b4440d3d8378a0")


def test_the_repeated_scope_rule_moves_no_path_of_the_older_stack():
    """`scope_of_op_name` now takes a path back to a scope that comes
    again (the prediction module's nested scopes need it). The window
    and full stack's scopes are flat: every operation of its compiled
    step keeps the scope the older rule gave it, so no metric of the
    cell `mellum2-12b-ep4.train-8k` reads other operations than before
    (at the published widths, compiled for a described v5e: PERF.md)."""
    from test_als_scopes import consecutive_rule

    names = set(profile._OP_NAME.findall(
        _step_lowered(CFG, 0.0625, (2, 41)).compile().as_text()))
    scopes = {profile.scope_of_op_name(n) for n in names}
    assert {"seq.attn.window", "seq.attn.full", "seq.moe.route",
            "seq.moe.gmm", "seq.head_loss", "seq.optimizer"} <= scopes
    assert len(names) > 300
    assert not {n for n in names
                if profile.scope_of_op_name(n) != consecutive_rule(n)}


LATENT_CFG = {
    "hidden_size": 32, "num_hidden_layers": 3, "num_attention_heads": 4,
    "num_key_value_heads": 4, "q_lora_rank": 12, "kv_lora_rank": 10,
    "qk_nope_head_dim": 12, "qk_rope_head_dim": 4, "v_head_dim": 16,
    "rope_theta": 10000, "rms_norm_eps": 1e-5, "first_k_dense_replace": 1,
    "intermediate_size": 48, "moe_intermediate_size": 16,
    "n_routed_experts": 4, "num_experts_routed": 8, "experts_held": [0, 4],
    "n_shared_experts": 1, "num_experts_per_tok": 3, "norm_topk_prob": True,
    "routed_scaling_factor": 1.8, "topk_method": "noaux_tc",
    "num_nextn_predict_layers": 1, "vocab_size": 50,
    "tie_word_embeddings": False, "initializer_range": 0.3,
}


def test_the_latent_stacks_step_program_does_not_hold_the_step_count():
    spec = seq_blocks.BlockSpec.parse(LATENT_CFG)
    _, step = seq_blocks.make_train_step(spec, 0.02)
    for steps in (2, 4):
        p = SequenceParams(max_len=41, batch_size=2, steps=steps,
                           learning_rate=0.02, seed=3, block_spec=LATENT_CFG)
        seq_blocks.train_lm(_data(length=42).seqs, p)
    assert step._cache_size() == 1


@pytest.mark.parametrize("which,head_dim", [("window_full", 8),
                                            ("latent_module", 16)])
def test_a_jobs_record_says_what_the_step_program_holds(
        monkeypatch, which, head_dim):
    """`attn_fwd_kernels` and `attn_residual_bytes` on `seq.wait`, read
    off the traced step: one forward kernel an attention layer (4 stack
    layers; 3 and the module's), o (float32 at this file's widths) and a
    (B, Hq, S) lse of each kept, 48 padded positions of 4 heads. Reading
    them traces nothing: two jobs, one trace of the loss."""
    import contextlib

    cfg = {"window_full": CFG, "latent_module": LATENT_CFG}[which]
    waits, traced = [], []

    @contextlib.contextmanager
    def span(name, **labels):
        labels = dict(labels)
        if name == "seq.wait":
            waits.append(labels)
        yield labels

    real = seq_blocks.loss_and_counters
    monkeypatch.setattr(seq_blocks.tracing, "span", span)
    monkeypatch.setattr(seq_blocks, "loss_and_counters",
                        lambda *a: traced.append(1) or real(*a))
    spec = seq_blocks.BlockSpec.parse(cfg)
    p = SequenceParams(max_len=41, batch_size=2, steps=2,
                       learning_rate=0.0271, seed=3, block_spec=cfg)
    for _ in range(2):
        seq_blocks.train_lm(
            _data(length=seq_blocks.history_ids(spec, 40)).seqs, p)
    assert len(traced) == 1
    for labels in waits:
        assert labels["attn_fwd_kernels"] == 4
        assert labels["attn_residual_bytes"] == 4 * 2 * 4 * 48 * (
            head_dim * 4 + 4)


def test_the_engine_trains_persists_and_serves_the_latent_stack(monkeypatch):
    """`SequenceAlgorithm.train` keeps max_len + 1 ids of a history where
    the specification has a prediction module, the job's counters carry
    both losses and the bias rule's sums, the persisted model holds the
    biases, and the scores are the main head's."""
    from benchmark.reference import latent_moe_lm
    import contextlib

    from pio_tpu.workflow.checkpoint import models_from_bytes, models_to_bytes

    spans = {}

    @contextlib.contextmanager
    def span(name, **labels):
        spans[name] = dict(labels)
        yield spans[name]

    monkeypatch.setattr(seq_blocks.tracing, "span", span)
    p = SequenceParams(max_len=41, batch_size=2, steps=4, learning_rate=0.02,
                       seed=11, block_spec=LATENT_CFG)
    algo = SequenceAlgorithm(p)
    model = algo.train(None, _data(length=44))     # 2 ids too many
    assert model.seqs.shape == (8, 42)
    spec = seq_blocks.BlockSpec.parse(LATENT_CFG)
    want = jax.tree_util.tree_map(
        lambda s: s, seq_blocks.param_shapes(spec),
        is_leaf=lambda x: isinstance(x, tuple))
    assert jax.tree_util.tree_map(lambda x: x.shape, model.params) == want
    labels = spans["seq.wait"]
    for key in ("loss_main_first", "loss_mtp_first", "loss_main_last",
                "loss_mtp_last", "router_bias_abs_max",
                "expert_tokens_held_share", "expert_tiles_used_share"):
        assert key in labels, key
    assert "window_blocks_visited" not in labels      # no window layer
    # in the order they open; the copy to the host is a span of its own
    assert list(spans) == ["seq.batch", "seq.init", "seq.dispatch",
                           "seq.wait", "seq.d2h"]
    assert spans["seq.init"] == {} and spans["seq.d2h"] == {
        "bytes": sum(leaf.nbytes for leaf in
                     jax.tree_util.tree_leaves(model.params))}
    assert float(labels["loss_first"]) == pytest.approx(
        float(labels["loss_main_first"])
        + 0.3 * float(labels["loss_mtp_first"]), rel=1e-6)
    got = np.stack([lp["router_bias"]
                    for lp in seq_blocks.expert_layers(model.params, spec)])
    assert got.shape == (3, 8)                  # 2 stack routers, 1 module
    moves = got / seq_blocks.ROUTER_BIAS_RATE   # whole, at most one a step
    np.testing.assert_allclose(moves, np.round(moves), atol=1e-4)
    assert 1 <= np.abs(moves).max() <= 4
    assert float(labels["router_bias_abs_max"]) == pytest.approx(
        np.abs(got).max())
    loaded = models_from_bytes(models_to_bytes([model]))[0]
    out = algo.batch_predict(loaded, [{"user": "u1", "num": 5},
                                      {"user": "nobody"}])
    assert len(out[0]["itemScores"]) == 5 and out[1]["itemScores"] == []
    # the scores are the reference's main head at the last position
    # (the reference's main logits read every id but the last it is given)
    rows = model.seqs[[1]]
    want_scores = latent_moe_lm.logits(
        jax.tree_util.tree_map(jnp.asarray, model.params),
        jnp.asarray(np.pad(rows[:, -40:], ((0, 0), (0, 1)),
                           constant_values=1)), LATENT_CFG)[0][:, -1]
    got_scores = algo._score_last_batch(loaded, rows[:, -41:])
    np.testing.assert_allclose(got_scores, want_scores, atol=2e-4, rtol=2e-4)
