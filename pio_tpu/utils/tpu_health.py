"""Device acquisition diagnostics: staged probes and artifact telemetry.

A probe that dies with nothing but "timeout after Ns" teaches nothing
about WHY (device claim hung? first compile stalled?). This module makes
every acquisition attempt leave a trail:

 * `StageWriter`/`read_stages` — a probe subprocess appends one JSON
   line per lifecycle stage (import → device claim → compile → run) to
   a progress file; when the parent kills the child on timeout it reads
   the file and learns exactly which stage hung.
 * `classify_hang()` — folds the stage trail into one diagnosis string
   for the artifact.
 * `telemetry()` — for eval scripts with a live backend: device kind,
   platform, backend init seconds, and a median dispatch round-trip, so
   every artifact records the conditions it was measured under.
"""

from __future__ import annotations

import json
import math
import os
import time


class StageWriter:
    """Append-only JSON-lines progress trail for a probe subprocess.

    Every stage() call is flushed + fsync'd so the trail survives the
    parent's SIGKILL on timeout.
    """

    def __init__(self, path: str | None):
        self._f = open(path, "a", buffering=1) if path else None
        self._t0 = time.monotonic()

    def stage(self, name: str, **extra) -> None:
        if self._f is None:
            return
        rec = {"stage": name, "t": round(time.monotonic() - self._t0, 2),
               "ts": time.strftime("%H:%M:%S"), **extra}
        self._f.write(json.dumps(rec) + "\n")
        self._f.flush()
        os.fsync(self._f.fileno())


def read_stages(path: str) -> list[dict]:
    try:
        with open(path) as f:
            out = []
            for line in f:
                line = line.strip()
                if line:
                    try:
                        out.append(json.loads(line))
                    except json.JSONDecodeError:
                        pass
            return out
    except OSError:
        return []


# probe lifecycle stage names (ordered); classify_hang keys on these
STAGES = ("start", "jax_imported", "devices_ok", "compiled", "ran")


def classify_hang(stages: list[dict]) -> str:
    """One diagnosis string from a (possibly truncated) stage trail."""
    reached = {s.get("stage") for s in stages}
    if not stages:
        return "no-progress-recorded"
    if "ran" in reached:
        return "completed"
    if "compiled" in reached:
        return "hang-at-first-run"
    if "devices_ok" in reached:
        return "hang-at-first-compile"
    if "jax_imported" in reached:
        # jax.devices() = PJRT client init + device claim
        return "hang-at-device-claim"
    if reached == {"start"}:
        return "hang-at-jax-import"
    # non-probe trail (e.g. a train phase's custom stages): report the
    # last stage reached rather than guessing
    return f"hang-after-{stages[-1].get('stage')}"


def staged_probe(progress_path: str | None = None,
                 matmul_dim: int = 256) -> dict:
    """The full probe body: import jax, claim devices, compile + run one
    tiny matmul, writing a stage trail as it goes. Returns the probe
    result dict (raises nothing — errors land in the trail + result)."""
    w = StageWriter(progress_path)
    w.stage("start", pid=os.getpid())
    t_imp = time.monotonic()
    import jax  # noqa: PLC0415 - the import IS a probe stage

    w.stage("jax_imported", t_import=round(time.monotonic() - t_imp, 2))
    # init_sec clock starts AFTER the jax import; the import's own cost
    # is in the trail
    t0 = time.monotonic()
    dev = jax.devices()[0]
    w.stage("devices_ok", t_claim=round(time.monotonic() - t0, 2),
            platform=dev.platform, device_kind=dev.device_kind,
            n_devices=jax.device_count())
    import jax.numpy as jnp

    t2 = time.monotonic()
    f = jax.jit(lambda x: (x @ x).sum())
    d = matmul_dim
    lowered = f.lower(jax.ShapeDtypeStruct((d, d), jnp.bfloat16))
    compiled = lowered.compile()
    w.stage("compiled", t_compile=round(time.monotonic() - t2, 2))
    t3 = time.monotonic()
    v = float(compiled(jnp.ones((d, d), jnp.bfloat16)))
    w.stage("ran", t_run=round(time.monotonic() - t3, 2))
    return {
        "ok": v == float(d) ** 3,
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "n_devices": jax.device_count(),
        "init_sec": round(time.monotonic() - t0, 1),
    }


def telemetry(samples: int = 7) -> dict:
    """Device conditions for an eval artifact: requires a live backend
    (imports jax).

    Returns device kind/platform, backend init seconds (0 if already
    initialized by the caller), and the median + p90 round-trip of a
    tiny jitted dispatch — the floor under every latency number in the
    same artifact."""
    t0 = time.monotonic()
    import jax
    import jax.numpy as jnp

    dev = jax.devices()[0]
    init_sec = round(time.monotonic() - t0, 2)
    one = jnp.ones(())
    add = jax.jit(lambda x: x + 1)
    jax.block_until_ready(add(one))  # compile outside the timing loop
    rtts = []
    for _ in range(max(3, samples)):
        t1 = time.monotonic()
        jax.block_until_ready(add(one))
        rtts.append((time.monotonic() - t1) * 1e3)
    rtts.sort()
    return {
        "ts": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "n_devices": jax.device_count(),
        "backend_init_sec": init_sec,
        "dispatch_rtt_ms_p50": round(rtts[len(rtts) // 2], 3),
        # nearest-rank p90: ceil(0.9n)-1 (int(0.9n)-1 lands on ~p79 at
        # n=7 and the MEDIAN at n=3)
        "dispatch_rtt_ms_p90": round(
            rtts[max(0, math.ceil(len(rtts) * 0.9) - 1)], 3),
    }
