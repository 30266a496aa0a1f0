"""benchmark/harness/roofline_latent.py against shapes worked by hand,
and its reader on made-up evidence."""

import pytest

from benchmark.harness import cells, roofline_latent
from benchmark.readers import seq_roofline_latent

TINY = {"hidden_size": 8, "num_attention_heads": 2, "q_lora_rank": 3,
        "kv_lora_rank": 4, "qk_nope_head_dim": 4, "qk_rope_head_dim": 2,
        "v_head_dim": 6, "intermediate_size": 10, "moe_intermediate_size": 5,
        "num_hidden_layers": 3, "first_k_dense_replace": 1,
        "n_routed_experts": 2, "num_experts_routed": 16,
        "n_shared_experts": 1, "num_nextn_predict_layers": 1,
        "vocab_size": 20}
GLM = cells.load_json(cells.ROOT + "/benchmark/configs/glm-4.7-flash-ep8.json")


def test_layer_counts_by_hand():
    assert roofline_latent.layer_counts(TINY) == {
        "attention": 4, "dense": 1, "expert": 3, "heads": 2, "mtp": 1}
    assert roofline_latent.layer_counts(GLM) == {
        "attention": 6, "dense": 1, "expert": 5, "heads": 2, "mtp": 1}


def test_attention_by_hand():
    # 1 history of 4 positions: 10 kept pairs; 2 heads; 6 products of
    # 2 x 6 operations a pair; 4 layers
    work = roofline_latent.latent_attention_least(TINY, 1, 4)
    assert work["flops"] == 10 * 2 * 6 * 2 * 6 * 4
    # q, k, v, o of 2 x 4 x 6 bfloat16: 4 moved forward, 8 backward
    assert work["bytes"] == 12 * (2 * 4 * 6 * 2) * 4


def test_the_issues_attention_count():
    """ISSUE 33: "least 24.7 TFLOP a step"."""
    flops = roofline_latent.latent_attention_least(GLM, 2, 8192)["flops"]
    assert flops == 6 * 3072 * (8192 * 8193 // 2) * 2 * 20
    assert flops / 1e12 == pytest.approx(24.74, abs=0.01)


def test_widths_that_part_are_refused():
    with pytest.raises(ValueError, match="differ"):
        roofline_latent.latent_attention_least(dict(TINY, v_head_dim=8), 1, 4)


def test_weights_by_hand():
    got = roofline_latent.weight_flops_a_token(TINY)
    # latent: 8x3 + 3x2x6 + 8x6 + 4x2x10 + 2x6x8 = 284 a layer, 4 layers
    assert got["latent"] == 2 * 284 * 4
    assert got["dense"] == 2 * 3 * 8 * 10
    assert got["shared"] == 2 * 3 * 8 * 5 * 3
    assert got["router"] == 2 * 8 * 16 * 3
    assert got["join"] == 2 * 2 * 8 * 8
    assert got["head"] == 2 * 8 * 20 * 2


def test_grouped_and_step_by_hand():
    # 7 rows a layer, 3 expert layers: 9 products of 2 x 8 x 5 a row
    assert roofline_latent.latent_grouped_least(TINY, 7)["flops"] == (
        9 * 2 * 8 * 5 * 7 * 3)
    step = roofline_latent.step_least(TINY, 1, 4, 7)
    per_token = sum(roofline_latent.weight_flops_a_token(TINY).values())
    assert step["flops"] == (3 * per_token * 4 + 9 * 2 * 8 * 5 * 7 * 3
                             + 10 * 2 * 6 * 2 * 6 * 4)
    assert sum(step["by_part"].values()) == step["flops"]


def test_the_step_at_the_published_widths():
    """Balanced routing sends this rank 8 x 1,024 rows a layer. A token
    passes 329 M weights (latent 130.5 M in 6 layers, dense 62.9 M, shared
    47.2 M, head 79.3 M, join 8.4 M, routers 0.7 M): x 6 x 16,384 tokens =
    32.3 TFLOP; the held experts 2.3; attention 24.7: 59.4 TFLOP a step,
    two fifths of it attention."""
    step = roofline_latent.step_least(GLM, 2, 8192, 8 * 1024.0)
    assert step["flops"] / 1e12 == pytest.approx(59.4, abs=0.1)
    assert step["by_part"]["attention"] / step["flops"] == pytest.approx(
        0.4165, abs=0.001)


def _evidence(**over):
    base = {"trace": {"busy_s": 64.0, "window_s": 70.0, "scope_s": {
                "seq.attn.full": 20.0, "seq.mtp/seq.attn.full": 4.0,
                "seq.moe.gmm": 4.0, "seq.mtp/seq.moe.gmm": 1.0}},
            "steps_in_window": 64, "counters": [
                {"expert_tokens_mean": "1024.0"}],
            "config": GLM, "traffic": {"batch_histories": 2,
                                       "history_events": 8192},
            "device_kind": "TPU v5 lite", "rehearse": False}
    return {**base, **over}


@pytest.mark.parametrize("name,want", [
    # 24.74 TFLOP / 197 TFLOP/s = 0.1256 s of 0.375 s a step
    ("seq_attn_kernel_roofline.train-sequence-mtp", 33.5),
    # 9 x 2 x 2048 x 1536 x 8192 rows x 5 layers = 2.32 TFLOP: 0.0118 s of
    # 0.078 s a step
    ("seq_moe_gmm_roofline.train-sequence-mtp", 15.07),
    # 59.4 TFLOP over 197 TFLOP/s x 1 s
    ("seq_step_mfu.train-sequence-mtp", 30.15),
])
def test_the_reader_on_made_up_evidence(name, want):
    spec = cells.layer_metric_spec(name)
    assert seq_roofline_latent.read(spec, _evidence()) == pytest.approx(
        want, rel=0.01)
    # nothing to read: a rehearsal, a trace without scopes (the parent's),
    # a window without steps
    assert seq_roofline_latent.read(spec, _evidence(rehearse=True)) is None
    assert seq_roofline_latent.read(
        spec, _evidence(trace={"busy_s": 1.0, "window_s": 2.0})) is None
    assert seq_roofline_latent.read(
        spec, _evidence(steps_in_window=0)) is None
    if "attn" not in name:
        assert seq_roofline_latent.read(
            spec, _evidence(counters=[{}])) is None
