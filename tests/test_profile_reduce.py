"""pio_tpu/obs/profile.py: from a device profile of `pio train` to device
seconds by scope and idle seconds by span. Two small traces recorded on
the chip (tests/record_train_trace.py: a v5e, one chip and four), and
synthetic ones for what a recording cannot pin down."""

import os
import re

import pytest

from pio_tpu.obs import profile

DATA = os.path.join(os.path.dirname(__file__), "data")
TRACES = {"one-chip": ("tiny_one_chip.xplane.pb.gz", 1),
          "sharded": ("tiny_sharded.xplane.pb.gz", 4)}
PHASES = {"als.layout", "als.gather", "als.blocks", "als.flush", "als.gram",
          "als.cg"}


@pytest.fixture(scope="module", params=TRACES)
def recorded(request):
    name, chips = TRACES[request.param]
    read = profile.read_profile(os.path.join(DATA, name))
    return request.param, chips, read, profile.reduce(read)


def test_a_recorded_trace_holds_spans_devices_and_compiled_text(recorded):
    which, chips, read, _ = recorded
    assert len(read["devices"]) == chips
    assert all(lines["ops"] and lines["modules"]
               for lines in read["devices"].values())
    names = {name for name, _, _ in read["spans"]}
    assert {"train", "train.setup", "train.algorithms", "als.dispatch",
            "als.wait", "persist.d2h", "persist.insert"} <= names
    assert ("als.partition" in names) == (which == "sharded")
    # jax's own events on that line are not spans
    assert not {n for n in names if "(" in n or n == "shard_args"}
    program = "jit_run(" if which == "sharded" else "jit__train_jit("
    [text] = [t for name, t in read["hlo"].items()
              if name.startswith(program)]
    assert re.search(r'op_name="[^"]*als\.user/[^"]*als\.gather/', text)


def test_scopes_add_up_to_busy_and_spans_to_idle(recorded):
    which, chips, _, result = recorded
    assert len(result["jobs"]) == 2
    for job in result["jobs"]:
        assert len(job["devices"]) == chips
        for dev in job["devices"].values():
            assert dev["busy_s"] > 0 and dev["idle_s"] > 0
            assert sum(dev["scopes"].values()) == pytest.approx(
                dev["busy_s"], rel=1e-6)
            assert sum(dev["by_rule"].values()) == pytest.approx(
                dev["busy_s"], rel=1e-6)
            assert sum(dev["idle_by_span"].values()) == pytest.approx(
                dev["idle_s"], rel=1e-6)
            assert dev["busy_s"] + dev["idle_s"] == pytest.approx(
                job["wall_s"], rel=1e-6)
    per = result["per_job"]
    walls = sum(j["wall_s"] for j in result["jobs"])
    assert (per["busy_s"] + per["idle_s"]) * 2 + result["between_jobs_s"] \
        == pytest.approx(result["window_s"], rel=1e-6)
    assert walls + result["between_jobs_s"] == pytest.approx(
        result["window_s"], rel=1e-6)


def test_almost_all_device_time_has_a_scope_and_idle_time_a_span(recorded):
    which, _, _, result = recorded
    per = result["per_job"]
    assert per["scopes"].get(profile.UNSCOPED, 0.0) < 0.05 * per["busy_s"]
    leaves = {scope.rsplit("/", 1)[-1] for scope in per["scopes"]}
    want = PHASES | ({"als.all_gather"} if which == "sharded" else set())
    assert want <= leaves
    assert {s.split("/")[0] for s in per["scopes"]} >= {"als.user",
                                                        "als.item"}
    # most of it by the instruction's own metadata
    assert per["by_rule"]["own"] > 0.75 * per["busy_s"]
    idle = per["idle_by_span"]
    assert idle.get(profile.OUTSIDE, 0.0) < 0.05 * per["idle_s"]
    assert "train.algorithms" in idle and "persist.pickle" in idle
    assert profile.render(result).count("\n") > 20


# -- synthetic traces --------------------------------------------------------

S = 1e9      # the trace's clock is in ns


def _job(t0: float, partition: bool = True) -> list:
    """One job's spans: 10 s, the device busy from 3 s to 6 s."""
    spans = [("train", t0, 10 * S), ("train.setup", t0, 0.5 * S),
             ("train.algorithms", t0 + 0.5 * S, 5.5 * S),
             ("als.dispatch", t0 + 2.9 * S, 0.1 * S),
             ("als.wait", t0 + 3 * S, 3 * S),
             ("persist.pickle", t0 + 6 * S, 1 * S),
             ("persist.insert", t0 + 7 * S, 3 * S)]
    if partition:
        spans.append(("als.partition", t0 + 0.5 * S, 2.4 * S))
    return spans


HLO = """HloModule jit_run

%fused_computation (p: f32[8]) -> f32[8] {
  %p = f32[8] parameter(0)
  ROOT %mul.1 = f32[8] multiply(%p, %p), metadata={op_name="jit(run)/als.user/als.blocks/mul"}
}

%body (arg: (s32[], f32[8])) -> (s32[], f32[8]) {
  %arg = (s32[], f32[8]) parameter(0)
  %x = f32[8] get-tuple-element(%arg), index=1
  %gather.7 = f32[8] gather(%x), metadata={op_name="jit(run)/while/body/als.user/als.gather/gather"}
  %fusion.3 = f32[8] fusion(%gather.7), kind=kLoop, calls=%fused_computation
  %copy.9 = f32[8] copy(%fusion.3)
  %custom-call.2 = f32[8] custom-call(%copy.9), custom_call_target="tpu_custom_call", metadata={op_name="jit(run)/while/body/als.user/als.flush/pallas_call"}
  %i = s32[] get-tuple-element(%arg), index=0
  ROOT %t = (s32[], f32[8]) tuple(%i, %custom-call.2)
}

ENTRY %main (a: f32[8]) -> f32[8] {
  %a = f32[8] parameter(0)
  %sort.1 = f32[8] sort(%a), dimensions={0}
  %cumsum.4 = f32[8] reduce-window(%sort.1), metadata={op_name="reduce_window_sum"}
  %scatter.2 = f32[8] scatter(%cumsum.4), metadata={op_name="jit(run)/als.user/als.layout/scatter"}
  %while.5 = (s32[], f32[8]) while(%scatter.2), condition=%cond, body=%body
  ROOT %out = f32[8] get-tuple-element(%while.5), index=1
}
"""


def _two_job_profile() -> dict:
    def op(name, start, dur):
        return (f"%{name} = f32[8] op(...)", start * S, dur * S)

    ops = []
    for t0 in (0.0, 10.0):
        ops += [op("scatter.2", t0 + 3.0, 0.5), op("sort.1", t0 + 3.5, 0.25),
                op("while.5", t0 + 3.75, 2.25),       # holds the next four
                op("gather.7", t0 + 3.75, 1.0), op("fusion.3", t0 + 4.75, 0.5),
                op("copy.9", t0 + 5.25, 0.25), op("custom-call.2", t0 + 5.5, 0.5),
                op("mystery.8", t0 + 3.0 + 2.999, 0.001)]
    return {"devices": {"/device:TPU:0": {
                "ops": ops,
                "modules": [("jit_run(1)", 3.0 * S, 3 * S),
                            ("jit_run(1)", 13.0 * S, 3 * S)]}},
            "spans": _job(0.0) + _job(10 * S),
            "hlo": {"jit_run(1)": HLO}}


def test_scope_by_own_metadata_by_callee_and_by_neighbour():
    scopes = profile.module_scopes(HLO)
    assert scopes["gather.7"] == ("als.user/als.gather", "own")
    assert scopes["fusion.3"] == ("als.user/als.blocks", "called")
    # the compiler's copy takes the scope of the kernel it feeds; the
    # sort it split, and the cumsum whose lowering lost the path, the
    # layout's, through each other
    assert scopes["copy.9"] == ("als.user/als.flush", "neighbour")
    assert scopes["cumsum.4"] == ("als.user/als.layout", "neighbour")
    assert scopes["sort.1"] == ("als.user/als.layout", "neighbour")
    assert scopes["while.5"][1] == "called"
    assert profile.scope_of_op_name("jit(f)/while/body/dot_general") is None


def test_nested_operations_count_once_and_unknown_ones_are_unscoped():
    result = profile.reduce(_two_job_profile())
    per = result["per_job"]
    assert per["busy_s"] == pytest.approx(3.0)
    assert per["scopes"] == pytest.approx({
        "als.user/als.layout": 0.75, "als.user/als.gather": 1.0,
        "als.user/als.blocks": 0.5,
        "als.user/als.flush": 0.75 - 0.001,        # copy.9 + the kernel
        profile.UNSCOPED: 0.001})
    assert per["by_rule"]["neighbour"] == pytest.approx(0.5)


def test_the_operations_behind_a_scopes_seconds():
    """`--ops`: what `reduce` sums under one scope, an instruction a
    row, with how it came by the scope; a scope is matched as a whole
    element of the path."""
    rows = profile.operations(_two_job_profile(), "als.flush")
    assert [(r["op"].split(" = ")[0], r["rule"], r["calls"]) for r in rows] \
        == [("%custom-call.2", "own", 2), ("%copy.9", "neighbour", 2)]
    assert [r["s"] for r in rows] == pytest.approx([2 * 0.499, 2 * 0.25])
    assert sum(r["s"] for r in profile.operations(
        _two_job_profile(), "als.user")) == pytest.approx(2 * 2.999)
    assert profile.operations(_two_job_profile(), "als.flu") == []


def test_a_gap_is_split_between_persist_and_the_next_jobs_partition():
    """The gap from job one's last operation (6 s) to job two's first
    (13 s) holds persist AND the next job's setup and partition. Its
    middle (9.5 s) lies in persist.insert: the midpoint rule
    (benchmark/harness/trace.py) names all 7 s after it."""
    result = profile.reduce(_two_job_profile())
    gap = result["longest_gaps"][0]
    assert gap["s"] == pytest.approx(7.0)
    assert gap["parts"] == pytest.approx({
        "persist.pickle": 1.0, "persist.insert": 3.0, "train.setup": 0.5,
        "als.partition": 2.4, "als.dispatch": 0.1})
    first, second = (job["devices"]["/device:TPU:0"]
                     for job in result["jobs"])
    assert first["idle_by_span"]["persist.insert"] == pytest.approx(3.0)
    assert "als.partition" in first["idle_by_span"]     # its own, 0-3 s
    assert second["idle_by_span"]["als.partition"] == pytest.approx(2.4)
    assert second["idle_s"] == pytest.approx(7.0)


def test_time_under_no_span_is_outside_and_between_jobs_apart():
    read = _two_job_profile()
    # job two starts a second late and has no partition span
    read["spans"] = _job(0.0) + [
        (n, s + 1 * S, d) for n, s, d in _job(10 * S, partition=False)]
    result = profile.reduce(read)
    assert result["between_jobs_s"] == pytest.approx(1.0)
    second = result["jobs"][1]["devices"]["/device:TPU:0"]
    # under train.algorithms alone from 11.5 s to 12 s (shifted: 12.5-13)
    assert second["idle_by_span"]["train.algorithms"] > 0
    assert profile.OUTSIDE not in second["idle_by_span"]
    read["spans"] = [s for s in read["spans"] if s[0] != "train.setup"]
    with_hole = profile.reduce(read)["jobs"][0]["devices"]["/device:TPU:0"]
    # a span that is missing shows as the root's self time, not as a lie
    assert with_hole["idle_by_span"]["train"] == pytest.approx(0.5)


def test_no_root_span_or_no_device_is_an_error_not_a_guess():
    read = _two_job_profile()
    with pytest.raises(ValueError, match="no 'train' span"):
        profile.reduce({**read, "spans": []})
    with pytest.raises(ValueError, match="no /device:TPU plane"):
        profile.reduce({**read, "devices": {}})
    with pytest.raises(FileNotFoundError):
        profile.find_xplane(os.path.dirname(__file__) + "/no-such-dir")
