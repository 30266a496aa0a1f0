"""What the chip bring-up (ISSUE 21) fixed in place: nothing on the main
path may hide the device.

 * `_accelerator_backend` lets a backend-init failure out and answers
   True for "tpu" only;
 * the quantized candidate scan resolves `interpret` from the backend;
 * `chip_smoke.py` refuses a CPU backend without the dry-run flag, keeps
   jax out of its own process, and fails on a child's failure or a
   swallowed warm-up error.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import types

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _fake_devices(platform: str):
    return lambda *a, **k: [types.SimpleNamespace(
        platform=platform, device_kind=f"fake {platform}", id=0)]


# ---------------------------------------------------------------------------
# ops: no silent CPU path, no interpreter on the chip
# ---------------------------------------------------------------------------

def test_accelerator_backend_propagates_backend_error(monkeypatch):
    import jax

    from pio_tpu.ops import als

    def boom(*a, **k):
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.setattr(jax, "devices", boom)
    with pytest.raises(RuntimeError, match="Unable to initialize"):
        als._accelerator_backend()
    # ... and so does the resolution built on it: `auto` must not turn
    # into the CPU `carry` path because the chip failed to come up
    with pytest.raises(RuntimeError, match="Unable to initialize"):
        als.ALSParams().resolved_accum()


@pytest.mark.parametrize("platform,accel", [
    ("tpu", True), ("cpu", False), ("gpu", False), ("mystery", False)])
def test_accelerator_backend_means_tpu(monkeypatch, platform, accel):
    import jax

    from pio_tpu.ops import als

    monkeypatch.setattr(jax, "devices", _fake_devices(platform))
    assert als._accelerator_backend() is accel
    assert als.ALSParams().resolved_accum() == (
        "hybrid" if accel else "carry")


def test_sharded_trainer_keeps_to_cg_on_tpu(monkeypatch):
    """Found by chip_smoke.py on 4 x v5e: the exact batched Cholesky
    inside shard_map returns garbage there. auto must not pick it for a
    small per-device block, and asking for it must raise."""
    import jax

    from pio_tpu.ops import als

    small = 100                      # <= auto_cg_rows: auto says "exact"
    assert als.ALSParams(rank=64).resolved_cg_iters(small) == 0
    monkeypatch.setattr(jax, "devices", _fake_devices("cpu"))
    assert als._sharded_cg_iters(als.ALSParams(rank=64), small) == 0
    monkeypatch.setattr(jax, "devices", _fake_devices("tpu"))
    assert als._sharded_cg_iters(als.ALSParams(rank=64), small) == 16
    assert als._sharded_cg_iters(als.ALSParams(rank=64), 10**6) == 16
    assert als._sharded_cg_iters(
        als.ALSParams(rank=64, cg_iters=5), small) == 5
    with pytest.raises(NotImplementedError, match="exact Cholesky"):
        als._sharded_cg_iters(als.ALSParams(rank=64, cg_iters=0), small)


@pytest.mark.parametrize("platform,interpret", [("tpu", False),
                                                ("cpu", True)])
def test_quantized_scan_interpret_follows_backend(monkeypatch, platform,
                                                  interpret):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    from pio_tpu.ops import retrieval

    seen = {}

    class Captured(Exception):
        pass

    def fake_pallas_call(kernel, **kw):
        seen.update(kw)
        raise Captured

    monkeypatch.setattr(jax, "devices", _fake_devices(platform))
    monkeypatch.setattr(pl, "pallas_call", fake_pallas_call)
    with pytest.raises(Captured):
        retrieval.quantized_scores_pallas(
            jnp.zeros((32, 64), jnp.int8), jnp.ones((32,)),
            jnp.ones((64,)))
    assert seen["interpret"] is interpret


# ---------------------------------------------------------------------------
# chip_smoke.py
# ---------------------------------------------------------------------------

@pytest.fixture()
def smoke_mod(monkeypatch):
    monkeypatch.syspath_prepend(REPO)
    import chip_smoke

    return chip_smoke


def _run_smoke(*flags, cwd=REPO, script=None, timeout=300):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, script or os.path.join(REPO, "chip_smoke.py"),
         *flags], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=timeout)


def test_chip_smoke_refuses_cpu_and_keeps_jax_out_of_the_parent(tmp_path):
    """Without the dry-run flag a CPU backend is a non-zero exit and no
    result line; and the parent process — which must leave the chip to
    its children — has not imported jax by the time it gives up."""
    code = (
        "import sys\n"
        f"sys.path.insert(0, {REPO!r})\n"
        "sys.argv = ['chip_smoke.py']\n"
        "import chip_smoke\n"
        "rc = chip_smoke.main()\n"
        "assert 'jax' not in sys.modules, 'the parent imported jax'\n"
        "sys.exit(rc)\n")
    out = subprocess.run(
        [sys.executable, "-c", code], cwd=str(tmp_path),
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert out.returncode == 1, out.stderr
    assert "not 'tpu'" in out.stderr
    assert '"ok"' not in out.stdout and out.stdout.strip() == ""


def test_chip_smoke_alone_in_a_directory_fails(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    out = _run_smoke(cwd=str(tmp_path),
                     script=str(tmp_path / "chip_smoke.py"))
    assert out.returncode != 0
    assert out.stdout.strip() == ""


def test_chip_smoke_cpu_dry_run_passes_and_says_so():
    """The whole script at a tiny size on the CPU: every phase and check
    runs, and the output cannot be mistaken for a chip result."""
    out = _run_smoke("--cpu-dry-run")
    assert out.returncode == 0, out.stderr[-3000:]
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last["dry_run"] is True and "ok" not in last
    assert last["device"]["platform"] == "cpu"
    assert "NOT a chip result" in out.stdout


def test_chip_smoke_fails_on_swallowed_warmup_error(smoke_mod):
    smoke_mod.check_deploy_log("INFO serving devices: 1 x tpu\n")
    for line in ("WARNING pio_tpu.serve: warm query failed",
                 "WARNING pio_tpu.serve: warm batch failed"):
        with pytest.raises(smoke_mod.SmokeFailure, match="deploy log"):
            smoke_mod.check_deploy_log(
                f"INFO serving devices: 1 x tpu\n{line}\nTraceback ...\n")


def test_chip_smoke_fails_when_a_child_fails(smoke_mod, tmp_path):
    args = argparse.Namespace(time_limit=120.0, cpu_dry_run=True)
    run = smoke_mod.Run(args, str(tmp_path), str(tmp_path))
    try:
        with pytest.raises(smoke_mod.SmokeFailure, match="exit code"):
            run.pio("bad_verb", "no-such-verb")
    finally:
        smoke_mod._kill_children()


def test_chip_smoke_refuses_wrong_platform(smoke_mod):
    dev = smoke_mod.parse_devices(
        "devices: 1 x cpu (cpu), jax 0.9.0\n", "pio status")
    assert dev == {"platform": "cpu", "kind": "cpu", "count": 1,
                   "jax": "0.9.0"}
    with pytest.raises(smoke_mod.SmokeFailure, match="not 'tpu'"):
        smoke_mod.require_tpu(dev, "pio status", dry_run=False)
    tpu = smoke_mod.parse_devices(
        "x INFO train devices: 4 x tpu (TPU v5 lite), jax 0.9.0\n", "t")
    assert (tpu["count"], tpu["kind"]) == (4, "TPU v5 lite")
    smoke_mod.require_tpu(tpu, "pio train", dry_run=False)
