"""The sigmoid router with its bias, the shared expert and the bias rule
(ops/moe.py route_top_k / held_moe_ffn, models/seq_blocks.py) against
the plain reference (benchmark/reference/latent_moe_lm.py) and NumPy
loops: selection follows score + bias while weights follow the score;
the counts cover every routed expert; the shares of 1, 2, 4 and 8 ranks
add up to the uncut layer; the rule over three steps."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import latent_moe_lm as reference
from pio_tpu.models import seq_blocks
from pio_tpu.ops.moe import HeldExperts, held_moe_ffn, route_top_k

E, K, D, F, T = 8, 3, 32, 16, 48
CFG = {
    "hidden_size": D, "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 4, "q_lora_rank": 12, "kv_lora_rank": 10,
    "qk_nope_head_dim": 12, "qk_rope_head_dim": 4, "v_head_dim": 16,
    "rope_theta": 10000, "rms_norm_eps": 1e-5, "first_k_dense_replace": 1,
    "intermediate_size": 48, "moe_intermediate_size": F,
    "n_routed_experts": E, "num_experts_routed": E, "experts_held": [0, E],
    "n_shared_experts": 1, "num_experts_per_tok": K, "norm_topk_prob": True,
    "routed_scaling_factor": 1.8, "topk_method": "noaux_tc",
    "num_nextn_predict_layers": 1, "vocab_size": 50,
    "tie_word_embeddings": False, "initializer_range": 0.3,
}


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    monkeypatch.setattr(seq_blocks, "COMPUTE", jnp.float32)
    monkeypatch.setattr(seq_blocks, "ATTN_BLOCK", 16)
    monkeypatch.setattr(seq_blocks, "MOE_TILE", 8)
    monkeypatch.setattr(seq_blocks, "LOSS_CHUNK", 32)


def test_selection_follows_the_bias_and_weights_the_score():
    """Expert 2 scores lowest; a bias lifts it into the top two. Its
    weight is still its own sigmoid over the chosen pair's sum."""
    logits = jnp.asarray([[2.0, 1.0, -1.0, -3.0]])
    s = np.asarray(jax.nn.sigmoid(logits))[0]
    ids, w = route_top_k(logits, 2, True, "sigmoid")
    assert ids.tolist() == [[0, 1]]
    np.testing.assert_allclose(w[0], s[[0, 1]] / s[[0, 1]].sum(), rtol=1e-6)
    bias = jnp.asarray([0.0, 0.0, 0.6, 0.0])
    ids, w = route_top_k(logits, 2, True, "sigmoid", bias, 1.8)
    assert ids.tolist() == [[0, 2]]            # 0.27 + 0.6 beats 0.73
    np.testing.assert_allclose(w[0], 1.8 * s[[0, 2]] / s[[0, 2]].sum(),
                               rtol=1e-6)
    _, raw = route_top_k(logits, 2, False, "sigmoid", bias)
    np.testing.assert_allclose(raw[0], s[[0, 2]], rtol=1e-6)


def test_a_zero_bias_changes_no_choice_and_no_weight():
    logits = jnp.asarray(np.random.default_rng(0).standard_normal((T, E)),
                         jnp.float32)
    plain = route_top_k(logits, K, True, "sigmoid", None, 1.8)
    zero = route_top_k(logits, K, True, "sigmoid", jnp.zeros(E), 1.8)
    assert (plain[0] == zero[0]).all()
    np.testing.assert_allclose(plain[1], zero[1], rtol=1e-6)


def test_the_softmax_router_is_what_it_was():
    logits = jnp.asarray(np.random.default_rng(1).standard_normal((T, E)),
                         jnp.float32)
    ids, w = route_top_k(logits, K, True)
    probs = np.asarray(jax.nn.softmax(logits, axis=-1))
    want = np.argsort(-probs, axis=1, kind="stable")[:, :K]
    assert (np.asarray(ids) == want).all()
    picked = np.take_along_axis(probs, want, 1)
    np.testing.assert_allclose(w, picked / picked.sum(1, keepdims=True),
                               rtol=1e-6)
    with pytest.raises(ValueError, match="tanh"):
        route_top_k(logits, K, True, "tanh")


def test_the_bias_takes_no_gradient():
    logits = jnp.asarray(np.random.default_rng(2).standard_normal((T, E)),
                         jnp.float32)
    g = jax.grad(lambda b: route_top_k(
        logits, K, True, "sigmoid", b, 1.8)[1].sum())(jnp.full(E, 0.01))
    assert not np.asarray(g).any()


def _layer_params(seed=0):
    rng = np.random.default_rng(seed)

    def draw(*shape):
        return jnp.asarray(0.3 * rng.standard_normal(shape), jnp.float32)

    return {"router": draw(D, E),
            "router_bias": jnp.asarray(rng.uniform(-0.1, 0.1, E), jnp.float32),
            "w_gate": draw(E, D, F), "w_up": draw(E, D, F),
            "w_down": draw(E, F, D), "shared_gate": draw(D, F),
            "shared_up": draw(D, F), "shared_down": draw(F, D)}


def _held_part(lp, z, lo, hi):
    cfg = HeldExperts(E, K, (lo, hi), True, 8, "sigmoid", 1.8)
    part = {k: lp[k][lo:hi] for k in ("w_gate", "w_up", "w_down")}
    return held_moe_ffn(
        dict(part, router=lp["router"], router_bias=lp["router_bias"]),
        z, cfg, jnp.float32)


@pytest.mark.parametrize("ranks", [1, 2, 4, 8])
def test_the_shares_add_up_to_the_uncut_layer(ranks):
    """The share test: every rank's held part, and the shared expert
    counted once, against the reference's whole layer."""
    lp = _layer_params()
    z = jnp.asarray(np.random.default_rng(3).standard_normal((T, D)),
                    jnp.float32)
    whole = reference.moe_layer(lp, z, CFG, (0, E))
    per = E // ranks
    total = seq_blocks._swiglu(z, lp["shared_gate"], lp["shared_up"],
                               lp["shared_down"])
    counts = np.zeros(E, np.int64)
    for r in range(ranks):
        out, aux = _held_part(lp, z, r * per, (r + 1) * per)
        total = total + out
        counts[r * per:(r + 1) * per] = aux["counts"]
        assert int(aux["dropped"]) == 0
        # every rank counts every routed expert alike
        assert aux["counts_all"].shape == (E,)
        assert int(aux["counts_all"].sum()) == T * K
        assert (np.asarray(aux["counts_all"])[r * per:(r + 1) * per]
                == np.asarray(aux["counts"])).all()
    np.testing.assert_allclose(total, whole, atol=2e-5, rtol=2e-5)
    assert counts.sum() == T * K
    # and a rank's part alone is the reference's share for that rank
    out, _ = _held_part(lp, z, 0, per)
    part = {k: lp[k][:per] for k in ("w_gate", "w_up", "w_down")}
    want = reference.moe_layer({**lp, **part}, z, CFG, (0, per), shared=False)
    np.testing.assert_allclose(out, want, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("ranks", [1, 2, 4, 8])
def test_a_whole_layers_shares_count_attention_and_shared_once(ranks):
    """The stack's layer for each rank is h + held_r + shared with h = x +
    attention: summed over the ranks less (ranks - 1) x (h + shared), it
    is the reference's uncut layer."""
    spec = seq_blocks.BlockSpec.parse(CFG)
    lp = seq_blocks.init_params(spec, 5)["layers"][1]
    lp = {**lp, "router_bias": _layer_params()["router_bias"]}
    x = jnp.asarray(np.random.default_rng(6).standard_normal((1, 24, D)),
                    jnp.float32)
    whole = reference._layer(lp, x, *reference._tables(CFG, 24), cfg=CFG,
                             dense=False, faults={})
    table = seq_blocks.rope_tables(spec, 24)["full_attention"]
    h = seq_blocks._attention_half(lp, x, *table, spec=spec,
                                   kind="full_attention")
    z = seq_blocks.rms_norm(h, lp["norm2"], spec.rms_norm_eps)[0]
    once = h[0] + seq_blocks._swiglu(z, lp["shared_gate"], lp["shared_up"],
                                     lp["shared_down"])
    per = E // ranks
    total = -(ranks - 1) * once
    for r in range(ranks):
        held = (r * per, (r + 1) * per)
        rank_spec = seq_blocks.BlockSpec.parse(
            {**CFG, "n_routed_experts": per, "experts_held": held})
        rank_lp = {**lp, **{k: lp[k][held[0]:held[1]]
                            for k in ("w_gate", "w_up", "w_down")}}
        y, _ = seq_blocks._layer(rank_lp, x, table, spec=rank_spec,
                                 kind="full_attention", dense=False)
        total = total + y[0]
    np.testing.assert_allclose(total, whole[0], atol=5e-5, rtol=5e-5)


def test_the_bias_rule_over_three_steps_against_a_numpy_loop():
    spec = seq_blocks.BlockSpec.parse({**CFG, "experts_held": [2, 6],
                                       "n_routed_experts": 4})
    optimizer, step = seq_blocks.make_train_step(spec, 0.01)
    params = seq_blocks.init_params(spec, 7)
    opt_state = optimizer.init(params)
    rng = np.random.default_rng(8)
    want = np.zeros((2, E))             # the stack's router, the module's
    for n in range(3):
        batch = jnp.asarray(rng.integers(1, 50, (2, 26)), jnp.int32)
        params, opt_state, _, aux = step(params, opt_state, batch)
        counts = np.asarray(aux["counts_all"]).sum(axis=1)    # (2, E)
        for r in range(2):
            before = want[r].copy()
            mean = counts[r].sum() / E
            for e in range(E):
                if counts[r, e] < mean:
                    want[r, e] += 0.001
                elif counts[r, e] > mean:
                    want[r, e] -= 0.001
            np.testing.assert_allclose(
                reference.bias_after(before, counts[r]), want[r], atol=1e-12)
        got = np.stack([np.asarray(lp["router_bias"])
                        for lp in seq_blocks.expert_layers(params, spec)])
        np.testing.assert_allclose(got, want, atol=1e-7)
        assert np.abs(got).max() == pytest.approx(0.001 * (n + 1), abs=1e-7)
    # Adam never saw a gradient for it
    mu = opt_state[0].mu
    assert not np.asarray(mu["layers"][1]["router_bias"]).any()
    assert not np.asarray(mu["mtp"]["layer"]["router_bias"]).any()


def test_bias_step_is_the_sign_of_mean_less_count():
    counts = jnp.asarray([[4, 0, 2, 2], [1, 1, 1, 1]])
    np.testing.assert_allclose(
        seq_blocks.bias_step(counts, 0.5),
        [[-0.5, 0.5, 0.0, 0.0], [0.0, 0.0, 0.0, 0.0]])
