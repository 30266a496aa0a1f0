#!/usr/bin/env python3
"""Rehearse a cell before it is proven: for each of a few fresh seeds,
one run of the cell as the driver would run it, with the output check's
numbers for the program and, from the same tables in the same child, for
the lower-precision control (benchmark/reference/als.py).

    python3 benchmark/rehearse.py --workload <name> --seeds 11,12,13 \
        --seconds 8 [--trace 1] [--out chiprun_out/rehearse.json]

Prints one JSON line per seed; limits are set from these lists, never
the other way round (PERF.md, "How correct is decided").
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.harness import cells  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", metavar="FILE")
    ap.add_argument("--out")
    args = ap.parse_args()
    cell = cells.load_cell(args.workload, args.rehearse)
    driver = cells.module_for("drivers", cell.traffic["kind"])
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.monotonic()
        out = driver.run(cell, seed=seed, seconds=args.seconds,
                         trace=bool(args.trace), t0=t0,
                         rehearse=bool(args.rehearse), explore=True)
        row = {"workload": args.workload, "seed": seed,
               "correct": out["correct"], "compared": out["compared"],
               "end_to_end": out["end_to_end"], "device": out["device"],
               "raw": out["raw"]}
        rows.append(row)
        print(json.dumps({k: row[k] for k in
                          ("seed", "correct", "compared", "end_to_end")}))
        print(json.dumps(out["raw"].get("numbers")))
        if args.out:
            os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
            with open(args.out, "w") as f:
                json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
