"""Children of a run: one process at a time holds the chip.

The parent (benchmark/run.py) never imports jax. A child that needs the
chip gets the environment as it is; every other child is held to
JAX_PLATFORMS=cpu. Each child is in a process group of its own, is
stopped before the run ends, and is waited for.
"""

from __future__ import annotations

import json
import os
import shutil
import signal
import subprocess
import sys
import tempfile

from benchmark.harness.cells import BENCH_DIR, ROOT, BenchFailure


def child_env(work: str, on_chip: bool, rehearse: bool,
              virtual_devices: int = 0) -> dict:
    """Storage in `work` (under TMPDIR), the compile cache at a fixed
    path in the checkout unless JAX_COMPILATION_CACHE_DIR names one."""
    env = dict(os.environ)
    env["PYTHONPATH"] = ROOT + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    env.update({
        "PIO_STORAGE_SOURCES_EL_TYPE": "eventlog",
        "PIO_STORAGE_SOURCES_EL_PATH": os.path.join(work, "eventlog"),
        "PIO_STORAGE_SOURCES_DB_TYPE": "sqlite",
        "PIO_STORAGE_SOURCES_DB_PATH": os.path.join(work, "pio.db"),
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "DB",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "EL",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "DB",
        "PIO_TPU_HOME": os.path.join(work, "home"),
    })
    env.setdefault("JAX_COMPILATION_CACHE_DIR",
                   os.path.join(BENCH_DIR, ".cache", "jax"))
    env.pop("PIO_TPU_PLATFORM", None)
    if rehearse or not on_chip:
        env["JAX_PLATFORMS"] = "cpu"
    if rehearse and virtual_devices > 1:
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " --xla_force_host_"
                            f"platform_device_count={virtual_devices}")
    return env


class Children:
    """The children of one run and the directory they work in."""

    def __init__(self):
        self.work = tempfile.mkdtemp(prefix="pio_bench_")
        self._procs: list[tuple[str, subprocess.Popen]] = []

    def log_path(self, name: str) -> str:
        # BENCH_LOG_DIR keeps the children's logs and a traced run's
        # trace/ (rehearsals on the chip machine point it into
        # chiprun_out/; `python -m pio_tpu.obs.profile <it>/trace` then
        # prints the seconds by scope and span)
        keep = os.environ.get("BENCH_LOG_DIR")
        if keep:
            os.makedirs(keep, exist_ok=True)
        return os.path.join(keep or self.work, name + ".log")

    def tail(self, name: str, n: int = 40) -> str:
        try:
            with open(self.log_path(name), errors="replace") as f:
                return "".join(f.readlines()[-n:])
        except OSError:
            return ""

    def spawn(self, name: str, argv: list[str],
              env: dict) -> subprocess.Popen:
        with open(self.log_path(name), "w") as logf:
            p = subprocess.Popen(
                argv, env=env, cwd=self.work, stdout=logf,
                stderr=subprocess.STDOUT, start_new_session=True)
        self._procs.append((name, p))
        return p

    def to_end(self, name: str, argv: list[str], env: dict,
               timeout: float) -> None:
        p = self.spawn(name, argv, env)
        try:
            rc = p.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            raise BenchFailure(f"{name}: no end after {timeout:.0f}s\n"
                               + self.tail(name)) from None
        if rc != 0:
            raise BenchFailure(f"{name}: exit code {rc}\n" + self.tail(name))

    def python(self, name: str, module: str, spec: dict, env: dict,
               timeout: float) -> dict:
        """Run `python -m <module> <spec file>` to its end; -> what it
        wrote to the spec's `out` file."""
        spec = dict(spec, out=os.path.join(self.work, name + ".out.json"))
        spec_path = os.path.join(self.work, name + ".spec.json")
        with open(spec_path, "w") as f:
            json.dump(spec, f)
        self.to_end(name, [sys.executable, "-m", module, spec_path], env,
                    timeout)
        with open(spec["out"]) as f:
            return json.load(f)

    def close(self) -> None:
        """Stop what still runs, wait for it, remove the directory."""
        for _name, p in self._procs:
            if p.poll() is None:
                try:
                    os.killpg(p.pid, signal.SIGKILL)
                except ProcessLookupError:
                    pass
            p.wait()
        keep = os.environ.get("BENCH_LOG_DIR")
        traced = os.path.join(self.work, "trace")
        if keep and os.path.isdir(traced):
            shutil.rmtree(os.path.join(keep, "trace"), ignore_errors=True)
            shutil.move(traced, os.path.join(keep, "trace"))
        shutil.rmtree(self.work, ignore_errors=True)


def require_devices(device: dict, chips: int, rehearse: bool) -> None:
    """No accelerator, or fewer chips than the cell asks for: no result."""
    want = "cpu" if rehearse else "tpu"
    if device["platform"] != want:
        raise BenchFailure(
            f"the child ran on platform {device['platform']!r}, not "
            f"{want!r}: no accelerator, no result")
    if device["count"] < chips:
        raise BenchFailure(
            f"the cell asks for {chips} chips, jax found {device['count']}")
