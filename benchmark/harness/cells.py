"""BENCHMARK.json, and the data files its names point at.

A cell names a configuration and a traffic mix; a per-layer metric names
itself. Each is one file found by that name, so a later PR adds a cell,
a mix, a metric or a reader as new files and new entries:

    benchmark/configs/<configuration>.json
    benchmark/traffic/<mix>.json            "kind" -> benchmark/drivers/<kind>.py
    benchmark/layer_metrics/<metric>.json   "reader" -> benchmark/readers/<reader>.py
"""

from __future__ import annotations

import importlib
import json
import os
from dataclasses import dataclass

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH_DIR)


class BenchFailure(Exception):
    """The run has no result: no chip, a child that failed, a broken file."""


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def merge(base: dict, over: dict) -> dict:
    """`over` laid on `base`, group by group (the rehearsal's tiny sizes)."""
    out = dict(base)
    for key, val in over.items():
        if isinstance(val, dict) and isinstance(out.get(key), dict):
            out[key] = merge(out[key], val)
        else:
            out[key] = val
    return out


def module_for(package: str, name: str):
    return importlib.import_module(
        f"benchmark.{package}.{name.replace('-', '_')}")


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list[dict]     # the metrics this cell reports with --trace 0
    per_layer: list[dict]      # and with --trace 1


def _applies(metric: dict, workload: str, reported: set[str] | None) -> bool:
    if "workloads" in metric:
        return workload in metric["workloads"]
    return reported is None or metric["moves"] in reported


def load_cell(workload: str, rehearse: str | None = None) -> Cell:
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    entry = next((w for w in bench["workloads"] if w["name"] == workload),
                 None)
    if entry is None:
        known = ", ".join(w["name"] for w in bench["workloads"])
        raise BenchFailure(f"no workload {workload!r}; there are: {known}")
    cfg_entry = next(c for c in bench["configs"]
                     if c["name"] == entry["config"])
    config = load_json(os.path.join(ROOT, cfg_entry["file"]))
    traffic = load_json(os.path.join(
        BENCH_DIR, "traffic", entry["traffic"] + ".json"))
    if rehearse:
        over = load_json(rehearse)
        config = merge(config, over.get("config", {}))
        traffic = merge(traffic, over.get("traffic", {}))
    end_to_end = [m for m in bench["end_to_end"]
                  if _applies(m, workload, None)]
    reported = {m["name"] for m in end_to_end}
    per_layer = [m for m in bench["per_layer"]
                 if _applies(m, workload, reported)]
    return Cell(workload, entry["chips"], config, traffic, end_to_end,
                per_layer)


def layer_metric_spec(name: str) -> dict:
    return load_json(os.path.join(BENCH_DIR, "layer_metrics", name + ".json"))


def peaks_for(device_kind: str) -> dict:
    """The one table of peaks, keyed by `device_kind`. A device that is
    not in it is an error, never a default."""
    table = load_json(os.path.join(HERE, "peaks.json"))
    if device_kind not in table:
        raise BenchFailure(
            f"device kind {device_kind!r} is not in benchmark/harness/"
            f"peaks.json ({', '.join(k for k in table if k != 'source')})")
    return table[device_kind]
