"""From a profiler trace (.xplane.pb) to numbers.

A device is a plane named /device:TPU:<n>; its operations are the events
of its "XLA Ops" line. On the CPU backend (rehearsals only) there is no
device plane: the operations run on the host plane's PjRt client
threads, and those lines stand in, as one device.

Busy time is the union of the intervals in which an operation ran on a
device, inside the traced window; the window is the span of the
benchmark's own `bench:window` annotation on the same clock. An
operation's own time is its duration less what its nested operations
cover (a `while` does not count its body twice).
"""

from __future__ import annotations

import glob
import os
from collections import defaultdict

WINDOW = "bench:window"
_CPU_OP_LINES = ("tf_XLAPjRtCpuClient", "tf_XLAEigen")


def find_xplane(logdir: str) -> str:
    found = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return found[-1]


def read_planes(path: str) -> dict:
    """-> {"devices": {name: [(op, start_ns, dur_ns)]},
           "host": [(name, start_ns, dur_ns)]} with jax's own reader."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: dict[str, list] = {}
    host: list = []
    cpu_ops: list = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    devices[plane.name] = [
                        (short_name(e.name), e.start_ns, e.duration_ns)
                        for e in line.events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                ops = line.name.startswith(_CPU_OP_LINES)
                for e in line.events:
                    if e.duration_ns <= 0:
                        continue
                    (cpu_ops if ops else host).append(
                        (e.name, e.start_ns, e.duration_ns))
    if not devices and cpu_ops:
        devices["/host:CPU (no device plane: CPU backend)"] = cpu_ops
    return {"devices": devices, "host": host}


def short_name(op: str) -> str:
    """The TPU's trace names an operation by its whole HLO line; keep the
    name and the shape it writes."""
    if " = " not in op:
        return op[:96]
    name, rest = op.split(" = ", 1)
    return (name.lstrip("%") + " " + rest.split(" ", 1)[0].split("{")[0])[:96]


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return [(a, b) for a, b in out]


def clip(intervals, lo: float, hi: float):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def own_times(ops: list[tuple[str, float, float]]) -> dict[str, float]:
    """Seconds per operation name, nested operations counted once."""
    total: dict[str, float] = defaultdict(float)
    stack: list[list] = []                 # [name, end, own_ns]
    for name, start, dur in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and start >= stack[-1][1]:
            done = stack.pop()
            total[done[0]] += done[2]
        if stack:
            stack[-1][2] -= min(dur, stack[-1][1] - start)
        stack.append([name, start + dur, dur])
    for done in stack:
        total[done[0]] += done[2]
    return {k: v / 1e9 for k, v in total.items()}


_COLLECTIVE = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")


def exposed_collective_s(own: dict[str, float]) -> float:
    """Seconds a device spent in collectives alone. The operations of a
    device's line run one at a time, so a collective's own time (less any
    operation nested in it) is time in which nothing else ran there; what
    overlaps compute is asynchronous and leaves only its short start and
    done operations on the line."""
    return sum(sec for name, sec in own.items()
               if name.startswith(_COLLECTIVE))


def reduce(planes: dict) -> dict:
    """-> window_s, busy_s (mean over devices), per-device busy, the ten
    operations with most own time, the seconds of the ten longest idle
    gaps (benchmark/harness/program.py names them by the program's
    spans). Without a `bench:window` annotation the window is the span
    of the device operations."""
    spans = [(s, s + d) for n, s, d in planes["host"] if n == WINDOW]
    every = [o for ops in planes["devices"].values() for o in ops]
    if not every:
        raise ValueError("the trace holds no device operation")
    if spans:
        lo, hi = min(s for s, _ in spans), max(e for _, e in spans)
    else:
        lo = min(s for _, s, _ in every)
        hi = max(s + d for _, s, d in every)
    busy, op_s, exposed = {}, defaultdict(float), []
    gaps: list[float] = []
    for dev, ops in planes["devices"].items():
        inside = [(n, s, d) for n, s, d in ops if s + d > lo and s < hi]
        covered = clip(union([(s, s + d) for _, s, d in inside]), lo, hi)
        busy[dev] = sum(b - a for a, b in covered) / 1e9
        own = own_times(inside)
        exposed.append(exposed_collective_s(own))
        for name, sec in own.items():
            op_s[name] += sec / len(planes["devices"])
        edges = [lo] + [x for ab in covered for x in ab] + [hi]
        gaps += [(edges[k + 1] - edges[k]) / 1e9
                 for k in range(0, len(edges), 2) if edges[k + 1] > edges[k]]
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy.values()) / len(busy),
        "busy_by_device": busy,
        "device_ops": sorted(([n, s] for n, s in op_s.items()),
                             key=lambda x: -x[1])[:10],
        "op_seconds": dict(op_s),
        "collective_exposed_s": (sum(exposed) / len(exposed)
                                 if len(exposed) > 1 else None),
        "longest_gaps_s": sorted(gaps, reverse=True)[:10],
    }
