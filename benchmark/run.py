#!/usr/bin/env python3
"""One run of one cell of the benchmark:

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is the result, one JSON object. Without
a TPU, with fewer chips than the cell asks for, or outside a checkout of
the program, there is no result line and the exit code is not 0.

This process never imports jax: the chip belongs to one child at a time
(benchmark/drivers/). `--rehearse <file>` lays tiny sizes over the cell
and lets the children run on the CPU; such a line says so and is not a
measurement.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

T0 = time.monotonic()
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.harness import cells  # noqa: E402
from benchmark.harness.cells import BenchFailure  # noqa: E402


def run(args) -> dict:
    if not os.path.isdir(os.path.join(cells.ROOT, "pio_tpu")):
        raise BenchFailure("the pio_tpu package is not beside benchmark/: "
                           "nothing to measure")
    cell = cells.load_cell(args.workload, args.rehearse)
    driver = cells.module_for("drivers", cell.traffic["kind"])
    out = driver.run(cell, seed=args.seed, seconds=args.seconds,
                     trace=bool(args.trace), t0=T0,
                     rehearse=bool(args.rehearse))
    for line in out["compared"]:
        print("compared: " + line)
    if args.trace:
        evidence = out["evidence"]
        metrics = {}
        for m in cell.per_layer:
            spec = cells.layer_metric_spec(m["name"])
            value = cells.module_for("readers", spec["reader"]).read(
                spec, evidence)
            if value is not None:     # nothing to read: left out of the line
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        metrics = {m["name"]: {"value": out["end_to_end"][m["name"]],
                               "unit": m["unit"]}
                   for m in cell.end_to_end}
    line = {"correct": out["correct"], "attempted": out["attempted"],
            "failed": out["failed"], "metrics": metrics,
            "device": out["device"]}
    if args.trace and out.get("breakdown"):
        line["breakdown"] = out["breakdown"]
    if args.rehearse:
        line["rehearsal"] = "tiny sizes, CPU allowed: NOT a measurement"
    return line


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", metavar="FILE",
                    help="JSON of tiny sizes laid over the cell; the "
                         "children may then run on the CPU")
    args = ap.parse_args()
    try:
        line = run(args)
    except BenchFailure as e:
        print(f"benchmark/run.py: NO RESULT: {e}", file=sys.stderr)
        return 1
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
