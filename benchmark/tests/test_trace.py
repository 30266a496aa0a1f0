"""The reduction from a trace to numbers: on the small trace recorded on
a TPU v5e (record_trace.py), and on made-up planes for what that trace
does not hold (several devices, collectives)."""

import os

import pytest

from benchmark.harness import trace

RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "tiny_tpu.xplane.pb")


def test_recorded_tpu_trace():
    planes = trace.read_planes(RECORDED)
    assert list(planes["devices"]) == ["/device:TPU:0"]
    out = trace.reduce(planes)
    # three calls, 50 ms of sleep after each, inside the annotation
    assert 0.15 < out["window_s"] < 0.5
    assert 0 < out["busy_s"] < out["window_s"] - 0.14
    gaps = out["longest_gaps_s"]
    assert gaps == sorted(gaps, reverse=True) and gaps[2] > 0.045
    # own times count nested operations once: they add up to the busy time
    assert sum(out["op_seconds"].values()) == pytest.approx(
        out["busy_s"], rel=0.02)
    assert any(name.startswith("while") for name in out["op_seconds"])
    assert out["collective_exposed_s"] is None      # one device


def test_nested_operations_count_once():
    ops = [("while.1", 0, 100), ("fusion.1", 0, 30), ("fusion.2", 40, 30),
           ("fusion.3", 200, 50)]
    own = trace.own_times(ops)
    assert own["while.1"] == pytest.approx(40e-9)
    assert own["fusion.1"] == pytest.approx(30e-9)
    assert trace.union([(0, 100), (0, 30), (200, 250)]) == [(0, 100),
                                                            (200, 250)]


def test_window_devices_and_collectives():
    planes = {
        "host": [(trace.WINDOW, 0, 1000)],
        "devices": {
            "/device:TPU:0": [("fusion.1 f32[8]", 100, 300),
                              ("all-gather.2 f32[8]", 400, 100),
                              ("fusion.9 f32[8]", 900, 400)],   # runs over
            "/device:TPU:1": [("fusion.1 f32[8]", 0, 500),
                              ("all-reduce.3 f32[8]", 500, 300)],
        }}
    out = trace.reduce(planes)
    assert out["window_s"] == pytest.approx(1000e-9)
    assert out["busy_by_device"]["/device:TPU:0"] == pytest.approx(500e-9)
    assert out["busy_s"] == pytest.approx(650e-9)
    assert out["collective_exposed_s"] == pytest.approx(200e-9)
    # every device's gaps, the longest first: 500-900 and 0-100 on the
    # first, 800-1000 on the second (program.name_gaps names a gap by the
    # span that holds most of it: test_program_view.py)
    assert out["longest_gaps_s"] == pytest.approx(
        [400e-9, 200e-9, 100e-9])


def test_short_names():
    line = ("%fusion.548 = bf16[1048576,64]{1,0:T(8,128)(2,1)} "
            "fusion(bf16[138493,64]{1,0} %g), kind=kCustom")
    assert trace.short_name(line) == "fusion.548 bf16[1048576,64]"
    assert trace.short_name("dot_general.1") == "dot_general.1"


def test_a_trace_without_device_operations_is_refused():
    with pytest.raises(ValueError):
        trace.reduce({"host": [], "devices": {}})
