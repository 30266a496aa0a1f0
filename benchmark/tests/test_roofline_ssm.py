"""benchmark/harness/roofline_ssm.py: the counts by hand at the cell's
sizes, and the reader `seq-roofline-ssm` on made-up evidence."""

import pytest

from benchmark.harness import cells, roofline_ssm
from benchmark.readers import seq_roofline_ssm

CFG = cells.load_json(
    cells.ROOT + "/benchmark/configs/nemotron-3-nano-ep16.json")
TRAFFIC = cells.load_json(
    cells.ROOT + "/benchmark/traffic/train-sequence-ssm.json")
B, S = TRAFFIC["batch_histories"], TRAFFIC["history_events"]
TOKENS = B * S


def test_the_blocks_by_kind():
    assert roofline_ssm.block_counts(CFG) == {"M": 4, "E": 4, "*": 1}
    assert roofline_ssm.balanced_rows(CFG, B, S) == 16384 * 6 * 8 / 128 == 6144


def test_the_scans_least_work_by_hand():
    """A token and head: 4 N P operations forward and 8 backward. A
    token: x and y (2 bytes x 4,096 each), B and C (2 x 1,024 each) and
    dt (4 x 64) forward; x, dy and dx, B, C, dB, dC, dt and its gradient
    backward."""
    work = roofline_ssm.scan_least(CFG, B, S)
    assert work["flops"] == 12 * 128 * 64 * 64 * TOKENS * 4 == 412_316_860_416
    forward = 2 * 8192 + 2 * 2048 + 256
    backward = 3 * 8192 + 4 * 2048 + 2 * 256
    assert work["bytes"] == (forward + backward) * TOKENS * 4
    peaks = cells.peaks_for("TPU v5 lite")
    by_flops = work["flops"] / peaks["flops_per_s_bf16"]
    by_bytes = work["bytes"] / peaks["hbm_bytes_per_s"]
    assert by_bytes > by_flops          # the bytes bound it
    assert by_bytes == pytest.approx(4.3e-3, rel=0.02)


def test_the_grouped_products_by_hand():
    rows = 6144.0
    work = roofline_ssm.relu2_grouped_least(CFG, rows)
    # two products an expert, each forward and two backward, four blocks
    assert work["flops"] == 2 * 3 * 2 * 2688 * 1856 * rows * 4
    weights = 8 * 2 * 2688 * 1856
    assert work["bytes"] == 4 * (2 * weights * 2 + weights * 4
                                 + 2 * (2 * 2688 + 2 * 1856) * 2 * rows)


def test_the_steps_least_operations_by_hand():
    per = roofline_ssm.weight_flops_a_token(CFG)
    assert per["ssm"] == 2 * 4 * (2688 * 10304 + 4096 * 2688 + 4 * 6144)
    assert per["shared"] == 2 * 4 * 2 * 2688 * 3712
    assert per["router"] == 2 * 4 * 2688 * 128
    assert per["projections"] == 2 * (2 * 2688 * 4096 + 2 * 2688 * 256)
    assert per["head"] == 2 * 2688 * 16384
    step = roofline_ssm.step_least(CFG, B, S)
    assert step["by_part"]["ssm"] == 3 * per["ssm"] * TOKENS
    assert step["by_part"]["attention"] == 6 * 2 * 128 * (
        8192 * 8193 // 2) * 2 * 32
    assert step["flops"] == sum(step["by_part"].values())
    assert step["flops"] == pytest.approx(35.02e12, rel=1e-3)
    # ISSUE 45: the Mamba-2 blocks are ~49 % of the weights a token
    # takes on this chip (155 M of 318 M; of its six experts a block,
    # 6 x 8 / 128 are held here at balance)
    experts = 2 * 4 * 6 * 8 / 128 * 2 * 2688 * 1856
    assert per["ssm"] / (sum(per.values()) + experts) == pytest.approx(
        0.49, abs=0.01)


def evidence(**over):
    base = {"trace": {"scope_s": {"seq.ssm.scan": 0.96 * 2, "seq.moe.gmm": 0.5,
                                  "seq.attn.full": 2.4},
                      "busy_s": 48 * 0.5},
            "steps_in_window": 48, "config": CFG, "traffic": TRAFFIC,
            "device_kind": "TPU v5 lite",
            "counters": [{"expert_tokens_mean": "768.0"}]}
    return {**base, **over}


def spec(kernel, scopes):
    return {"kernel": kernel, "scopes": scopes}


def test_the_reader_divides_the_least_time_by_the_scopes_seconds():
    peaks = cells.peaks_for("TPU v5 lite")
    work = roofline_ssm.scan_least(CFG, B, S)
    least = work["bytes"] / peaks["hbm_bytes_per_s"]
    assert seq_roofline_ssm.read(
        spec("scan", ["seq.ssm.scan"]), evidence()) == pytest.approx(
            100 * least / (0.96 * 2 / 48))
    mfu = seq_roofline_ssm.read(spec("step", ["seq.ssm.scan"]), evidence())
    assert mfu == pytest.approx(
        100 * roofline_ssm.step_least(CFG, B, S)["flops"] / 197e12 / 0.5)
    assert 0 < seq_roofline_ssm.read(
        spec("grouped", ["seq.moe.gmm"]), evidence()) < 100
    assert 0 < seq_roofline_ssm.read(
        spec("attention", ["seq.attn.full"]), evidence()) < 100


@pytest.mark.parametrize("kernel,scopes", [
    ("scan", ["seq.ssm.scan"]), ("grouped", ["seq.moe.gmm"]),
    ("attention", ["seq.attn.full"]), ("step", ["seq.ssm.scan"])])
def test_nothing_to_read_without_the_scopes_or_in_a_rehearsal(kernel, scopes):
    """A parent whose program has no such blocks, a CPU rehearsal, a
    trace without a profile view."""
    s = spec(kernel, scopes)
    assert seq_roofline_ssm.read(s, evidence(rehearse=True)) is None
    assert seq_roofline_ssm.read(s, evidence(trace=None)) is None
    assert seq_roofline_ssm.read(s, evidence(trace={"scope_s": None})) is None
    assert seq_roofline_ssm.read(s, evidence(
        trace={"scope_s": {"seq.attn.window": 1.0}, "busy_s": 1.0})) is None
    if kernel == "grouped":
        assert seq_roofline_ssm.read(s, evidence(counters=[])) is None
