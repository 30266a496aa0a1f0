#!/usr/bin/env python3
"""Run a cell as the driver's check does and read the spreads: sets of
runs of the exact command, the same seeds in every set, one process a
run, then for each metric the spread of each set (distance between the
first and third quartile over the median, `statistics.quantiles(n=4)`).

    python3 benchmark/sets.py --workload <name> --seeds 1,2,3,4,5,6 \
        --sets 2 --seconds 30 [--trace-seeds 7,8] --out chiprun_out/sets.json
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))


def one_run(workload: str, seed: int, seconds: float, trace: int) -> dict:
    done = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--workload", workload,
         "--seed", str(seed), "--seconds", str(seconds), "--trace",
         str(trace)], capture_output=True, text=True)
    if done.returncode != 0:
        return {"seed": seed, "failed": done.stderr[-2000:]}
    lines = done.stdout.strip().splitlines()
    return dict(json.loads(lines[-1]), seed=seed, compared=lines[:-1])


def spread(values: list[float]) -> float:
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace-seeds", default="")
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    seeds = [int(s) for s in args.seeds.split(",")]
    out: dict = {"workload": args.workload, "sets": [], "traced": []}

    def save() -> None:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)

    for n in range(args.sets):
        runs = []
        out["sets"].append(runs)
        for seed in seeds:
            runs.append(one_run(args.workload, seed, args.seconds, 0))
            print(json.dumps({k: v for k, v in runs[-1].items()
                              if k != "compared"}), flush=True)
            save()
    for seed in (int(s) for s in args.trace_seeds.split(",") if s):
        out["traced"].append(one_run(args.workload, seed, args.seconds, 1))
        print(json.dumps(out["traced"][-1]), flush=True)
        save()
    for n, runs in enumerate(out["sets"]):
        good = [r for r in runs if "metrics" in r]
        for name in (good[0]["metrics"] if good else ()):
            values = [r["metrics"][name]["value"] for r in good]
            if len(values) >= 2:
                print(f"set {n} {name}: median "
                      f"{statistics.median(values):.6g} spread "
                      f"{spread(values):.4%} first {values[0]:.6g}")
    bad = [r for runs in out["sets"] + [out["traced"]] for r in runs
           if not r.get("correct")]
    print(f"{len(bad)} runs without correct=true")
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
