"""benchmark/harness/roofline_gated.py: the counts by hand at the cell's
sizes, a layer kind at a time, and the reader `seq-roofline-gated` on
made-up evidence."""

import pytest

from benchmark.harness import cells, roofline_gated
from benchmark.readers import seq_roofline_gated

CFG = cells.load_json(
    cells.ROOT + "/benchmark/configs/laguna-xs2-ep16.json")
TRAFFIC = cells.load_json(
    cells.ROOT + "/benchmark/traffic/train-sequence-gated.json")
B, S = TRAFFIC["batch_histories"], TRAFFIC["history_events"]
TOKENS = B * S
FULL, SLIDING = roofline_gated.FULL, roofline_gated.SLIDING


def test_the_layers_by_kind():
    assert roofline_gated.layer_counts(CFG) == {
        FULL: 2, SLIDING: 3, "dense": 1, "expert": 4}
    assert roofline_gated.q_heads(CFG, FULL) == 48
    assert roofline_gated.q_heads(CFG, SLIDING) == 64
    assert roofline_gated.balanced_rows(CFG, B, S) == \
        16384 * 8 * 16 / 256 == 8192      # 256 a held expert and history


def test_attention_a_layer_kind_at_a_time_by_hand():
    """A full layer keeps the causal half at 48 query heads, a sliding
    layer a band of 512 keys at 64; six products of 2 x 128 a pair."""
    by_kind = roofline_gated.attention_by_kind(CFG, B, S)
    causal = 8192 * 8193 // 2
    band = 512 * 513 // 2 + (8192 - 512) * 512
    assert by_kind[FULL]["flops"] == 2 * 6 * 2 * 128 * causal * B * 48
    assert by_kind[SLIDING]["flops"] == 3 * 6 * 2 * 128 * band * B * 64
    q = {FULL: B * 48 * S * 128 * 2, SLIDING: B * 64 * S * 128 * 2}
    kv = B * 8 * S * 128 * 2
    assert by_kind[FULL]["bytes"] == 2 * (6 * q[FULL] + 6 * kv)
    assert by_kind[SLIDING]["bytes"] == 3 * (6 * q[SLIDING] + 6 * kv)
    both = roofline_gated.gated_attention_least(CFG, B, S)
    assert both["flops"] == by_kind[FULL]["flops"] + by_kind[SLIDING]["flops"]
    assert both["flops"] == pytest.approx(12.29e12, rel=1e-3)   # ISSUE 49
    peaks = cells.peaks_for("TPU v5 lite")
    # both kinds are bound by operations, not by bytes
    assert by_kind[FULL]["flops"] / peaks["flops_per_s_bf16"] > \
        by_kind[FULL]["bytes"] / peaks["hbm_bytes_per_s"]
    assert by_kind[SLIDING]["flops"] / peaks["flops_per_s_bf16"] > \
        by_kind[SLIDING]["bytes"] / peaks["hbm_bytes_per_s"]


def test_the_grouped_products_by_hand():
    rows = 8192.0
    work = roofline_gated.gated_grouped_least(CFG, rows)
    # three products an expert, each forward and two backward, four layers
    assert work["flops"] == 3 * 3 * 2 * 2048 * 512 * rows * 4
    weights = 16 * 3 * 2048 * 512
    assert work["bytes"] == 4 * (2 * weights * 2 + weights * 4
                                 + 2 * (2 * 2048 + 3 * 512) * 2 * rows)


def test_the_steps_least_operations_by_hand():
    per = roofline_gated.weight_flops_a_token(CFG)
    full = 2 * 2048 * 48 * 128 + 2 * 2048 * 1024
    sliding = 2 * 2048 * 64 * 128 + 2 * 2048 * 1024
    assert per["projections"] == 2 * (2 * full + 3 * sliding)
    assert per["gate"] == 2 * 2048 * (2 * 48 + 3 * 64)
    assert per["dense"] == 2 * 3 * 2048 * 8192
    assert per["shared"] == 2 * 3 * 2048 * 512 * 4
    assert per["router"] == 2 * 2048 * 256 * 4
    assert per["head"] == 2 * 2048 * 12544
    step = roofline_gated.step_least(CFG, B, S)
    assert step["by_part"]["projections"] == 3 * per["projections"] * TOKENS
    assert step["by_part"]["experts"] == roofline_gated.gated_grouped_least(
        CFG, 8192.0)["flops"]
    assert step["flops"] == sum(step["by_part"].values())
    assert step["flops"] == pytest.approx(38.79e12, rel=1e-3)   # ISSUE 49
    # ISSUE 49: 269.6 M weights a token uses at balance (263.3 M outside
    # the held experts, 8 x 16 / 256 of an expert's 3.146 M a layer), and
    # attention's kernels and projections ~75 % of the step
    held = 4 * 8 * 16 / 256 * 3 * 2048 * 512
    assert sum(per.values()) / 2 + held == pytest.approx(269.6e6, rel=2e-3)
    parts = step["by_part"]
    assert (parts["attention"] + parts["projections"] + parts["gate"]
            ) / step["flops"] == pytest.approx(0.75, abs=0.01)
    more = roofline_gated.step_least(CFG, B, S, rows_a_layer=16384.0)
    assert more["flops"] - step["flops"] == step["by_part"]["experts"]


def evidence(**over):
    base = {"trace": {"scope_s": {"seq.attn.window": 64 * 0.05,
                                  "seq.attn.full": 64 * 0.1,
                                  "seq.moe.gmm": 64 * 0.02},
                      "busy_s": 64 * 0.6},
            "steps_in_window": 64, "config": CFG, "traffic": TRAFFIC,
            "device_kind": "TPU v5 lite",
            "counters": [{"expert_tokens_mean": "512.0"}]}
    return {**base, **over}


def spec(kernel, scopes):
    return {"kernel": kernel, "scopes": scopes}


ATTENTION = ["seq.attn.window", "seq.attn.full"]


def test_the_reader_divides_the_least_time_by_the_scopes_seconds():
    peaks = cells.peaks_for("TPU v5 lite")
    work = roofline_gated.gated_attention_least(CFG, B, S)
    least = max(work["flops"] / peaks["flops_per_s_bf16"],
                work["bytes"] / peaks["hbm_bytes_per_s"])
    assert seq_roofline_gated.read(
        spec("attention", ATTENTION), evidence()) == pytest.approx(
            100 * least / 0.15)
    mfu = seq_roofline_gated.read(spec("step", ATTENTION), evidence())
    assert mfu == pytest.approx(
        100 * roofline_gated.step_least(CFG, B, S)["flops"] / 197e12 / 0.6)
    assert 30 < mfu < 35
    grouped = seq_roofline_gated.read(
        spec("grouped", ["seq.moe.gmm"]), evidence())
    # 16 held experts x 512 tokens a step: the balanced 8,192 rows a layer
    rows = roofline_gated.gated_grouped_least(CFG, 16 * 512.0)
    assert grouped == pytest.approx(100 * max(
        rows["flops"] / peaks["flops_per_s_bf16"],
        rows["bytes"] / peaks["hbm_bytes_per_s"]) / 0.02)
    assert 0 < grouped < 100


@pytest.mark.parametrize("kernel,scopes", [
    ("grouped", ["seq.moe.gmm"]), ("attention", ATTENTION),
    ("step", ATTENTION)])
def test_nothing_to_read_without_the_scopes_or_in_a_rehearsal(kernel, scopes):
    """A parent whose program has no such layers, a CPU rehearsal, a
    trace without a profile view."""
    s = spec(kernel, scopes)
    assert seq_roofline_gated.read(s, evidence(rehearse=True)) is None
    assert seq_roofline_gated.read(s, evidence(trace=None)) is None
    assert seq_roofline_gated.read(s, evidence(trace={"scope_s": None})) is None
    assert seq_roofline_gated.read(s, evidence(
        trace={"scope_s": {"seq.ssm.scan": 1.0}, "busy_s": 1.0})) is None
    if kernel == "grouped":
        assert seq_roofline_gated.read(s, evidence(counters=[])) is None
    if kernel == "step":
        assert seq_roofline_gated.read(s, evidence(
            trace={"scope_s": {"seq.attn.full": 1.0}})) is None
