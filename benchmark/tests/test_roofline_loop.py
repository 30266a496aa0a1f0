"""benchmark/harness/roofline_loop.py against shapes worked by hand, and
its reader on made-up evidence."""

import pytest

from benchmark.harness import cells, roofline_loop
from benchmark.readers import seq_roofline_loop

TINY = {"hidden_size": 8, "num_attention_heads": 2, "num_key_value_heads": 2,
        "head_dim": 6, "intermediate_size": 10, "num_hidden_layers": 2,
        "total_ut_steps": 3, "vocab_size": 20}
OURO = cells.load_json(cells.ROOT + "/benchmark/configs/ouro-2.6b-l4.json")


def test_attention_by_hand():
    # 1 history of 4 positions: 10 kept pairs; 2 heads; 6 products of
    # 2 x 6 operations a pair; 3 passes over 2 layers
    work = roofline_loop.loop_attention_least(TINY, 1, 4)
    assert work["flops"] == 10 * 2 * 6 * 2 * 6 * 6
    # q, k, v, o of 2 x 4 x 6 bfloat16: 4 moved forward, 8 backward
    assert work["bytes"] == 12 * (2 * 4 * 6 * 2) * 6


def test_weights_and_step_by_hand():
    got = roofline_loop.weight_flops_a_token(TINY)
    assert got["projections"] == 2 * (4 * 8 * 12) * 6
    assert got["dense"] == 2 * 3 * 8 * 10 * 6
    assert got["head"] == 2 * 8 * 20 * 3
    step = roofline_loop.step_least(TINY, 1, 4)
    assert step["flops"] == (3 * sum(got.values()) * 4
                             + 10 * 2 * 6 * 2 * 6 * 6)
    assert sum(step["by_part"].values()) == step["flops"]


def test_the_issues_counts_at_the_published_widths():
    """ISSUE 41: 16 layer applications of 51.38 M weights and four head
    products of 100.66 M a token: 6 x 16,384 x 1,224.7 M = 120.4 TFLOP;
    attention 26.4; 146.8 TFLOP a step."""
    assert roofline_loop.layer_applications(OURO) == 16
    per_token = roofline_loop.weight_flops_a_token(OURO)
    assert sum(per_token.values()) == 2 * (
        16 * 51_380_224 + 4 * 100_663_296)
    attention = roofline_loop.loop_attention_least(OURO, 2, 8192)["flops"]
    assert attention == 16 * 1536 * (8192 * 8193 // 2) * 2 * 16
    step = roofline_loop.step_least(OURO, 2, 8192)
    assert attention / 1e12 == pytest.approx(26.39, abs=0.01)
    assert step["flops"] / 1e12 == pytest.approx(146.8, abs=0.1)


def _evidence(**over):
    base = {"trace": {"busy_s": 48.0, "window_s": 52.0, "scope_s": {
                "seq.loop/seq.attn.full": 12.0, "seq.attn.full": 0.8}},
            "steps_in_window": 32, "counters": [{}], "config": OURO,
            "traffic": {"batch_histories": 2, "history_events": 8192},
            "device_kind": "TPU v5 lite", "rehearse": False}
    return {**base, **over}


@pytest.mark.parametrize("name,want", [
    # 26.39 TFLOP / 197 TFLOP/s = 0.1340 s of 0.4 s a step
    ("seq_attn_kernel_roofline.train-sequence-loop", 33.49),
    # 146.8 TFLOP over 197 TFLOP/s x 1.5 s
    ("seq_step_mfu.train-sequence-loop", 49.68),
])
def test_the_reader_on_made_up_evidence(name, want):
    spec = cells.layer_metric_spec(name)
    assert seq_roofline_loop.read(spec, _evidence()) == pytest.approx(
        want, rel=0.005)
    # nothing to read: a rehearsal, a trace without scopes or with other
    # scopes than the loop's (the parent's), a window without steps
    assert seq_roofline_loop.read(spec, _evidence(rehearse=True)) is None
    assert seq_roofline_loop.read(
        spec, _evidence(trace={"busy_s": 1.0, "window_s": 2.0})) is None
    assert seq_roofline_loop.read(spec, _evidence(trace={
        "busy_s": 1.0, "window_s": 2.0,
        "scope_s": {"seq.mtp/seq.attn.full": 1.0}})) is None
    assert seq_roofline_loop.read(
        spec, _evidence(steps_in_window=0)) is None
