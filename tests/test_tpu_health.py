"""Device acquisition diagnostics (pio_tpu/utils/tpu_health.py): stage
trails that survive SIGKILL, and hang classification keyed on the
deepest stage reached.
"""

import os
import signal
import subprocess
import sys

import pytest

from pio_tpu.utils.tpu_health import (
    StageWriter,
    classify_hang,
    read_stages,
)


def test_stage_writer_roundtrip(tmp_path):
    p = tmp_path / "trail.jsonl"
    w = StageWriter(str(p))
    w.stage("start", pid=123)
    w.stage("jax_imported", t_import=0.5)
    stages = read_stages(str(p))
    assert [s["stage"] for s in stages] == ["start", "jax_imported"]
    assert stages[0]["pid"] == 123
    assert all("t" in s and "ts" in s for s in stages)


def test_stage_writer_none_path_is_noop():
    w = StageWriter(None)
    w.stage("start")  # must not raise


def test_read_stages_missing_and_garbage(tmp_path):
    assert read_stages(str(tmp_path / "nope")) == []
    p = tmp_path / "bad.jsonl"
    p.write_text('{"stage": "start", "t": 0}\nnot json\n')
    assert [s["stage"] for s in read_stages(str(p))] == ["start"]


@pytest.mark.parametrize("trail,expect", [
    ([], "no-progress-recorded"),
    ([{"stage": "start"}], "hang-at-jax-import"),
    ([{"stage": "start"}, {"stage": "jax_imported"}],
     "hang-at-device-claim"),
    ([{"stage": "start"}, {"stage": "jax_imported"},
      {"stage": "devices_ok"}], "hang-at-first-compile"),
    ([{"stage": "start"}, {"stage": "jax_imported"},
      {"stage": "devices_ok"}, {"stage": "compiled"}], "hang-at-first-run"),
])
def test_classify_hang_probe_stages(trail, expect):
    assert classify_hang(trail) == expect


def test_classify_hang_completed_and_custom_stages():
    done = [{"stage": "start"}, {"stage": "jax_imported"},
            {"stage": "devices_ok"}, {"stage": "compiled"},
            {"stage": "ran"}]
    assert classify_hang(done) == "completed"
    # non-probe trail (train phase): report the last stage reached
    custom = [{"stage": "train_start"}, {"stage": "transfer_done"}]
    assert classify_hang(custom) == "hang-after-transfer_done"


def test_trail_survives_sigkill(tmp_path):
    """The parent reads the trail after killing a hung child — the
    writes must be durable at the moment of SIGKILL (flush+fsync)."""
    p = tmp_path / "trail.jsonl"
    code = (
        "import sys, time\n"
        "sys.path.insert(0, %r)\n"
        "from pio_tpu.utils.tpu_health import StageWriter\n"
        "w = StageWriter(%r)\n"
        "w.stage('start')\n"
        "w.stage('jax_imported')\n"
        "print('staged', flush=True)\n"
        "time.sleep(60)\n"
    ) % (os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
         str(p))
    proc = subprocess.Popen([sys.executable, "-c", code],
                            stdout=subprocess.PIPE, text=True)
    try:
        assert proc.stdout.readline().strip() == "staged"
        proc.send_signal(signal.SIGKILL)
        proc.wait(timeout=10)
    finally:
        if proc.poll() is None:
            proc.kill()
    assert [s["stage"] for s in read_stages(str(p))] == [
        "start", "jax_imported"]
