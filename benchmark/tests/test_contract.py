"""BENCHMARK.json against the contract it was written to, and the files
its names point at."""

import json
import os
import re

import pytest

from benchmark.harness import cells

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    return cells.load_json(os.path.join(cells.ROOT, "BENCHMARK.json"))


def test_top_level(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["benchmark"]
    assert 1 <= bench["run_seconds"] <= 51
    # 2 + 14 runs a cell, run_seconds + 60 each, 180 s a cell to compile,
    # 1200 s spare: must fit 43200 s with the full 24 cells
    assert (2 + 14 * 24) * (bench["run_seconds"] + 60) + 24 * 180 + 1200 \
        <= 43200
    assert len(json.dumps(bench)) < 64 * 1024


def test_names_units_and_lengths(bench):
    seen = set()
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in bench[group]:
            assert NAME.match(entry["name"]), entry["name"]
            assert (group, entry["name"]) not in seen
            seen.add((group, entry["name"]))
            for key in ("why", "layer", "source"):
                if key in entry and group in ("configs", "workloads",
                                              "per_layer"):
                    assert 1 <= len(entry[key]) <= 200
                    assert "\n" not in entry[key] and "\t" not in entry[key]
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"


def test_cells_and_their_files(bench):
    configs = {c["name"]: c for c in bench["configs"]}
    used = set()
    four = 0
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4)
        four += w["chips"] == 4
        used.add(w["config"])
        cell = cells.load_cell(w["name"])
        assert cells.module_for("drivers", cell.traffic["kind"]).run
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer
        for m in cell.per_layer:
            assert m["moves"] in names
            spec = cells.layer_metric_spec(m["name"])
            assert cells.module_for("readers", spec["reader"]).read
            assert spec["layer"] == m["layer"] and spec["moves"] == m["moves"]
    assert used == set(configs)
    assert four <= max(1, len(bench["workloads"]) // 4)
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        held = cells.load_json(os.path.join(cells.ROOT, c["file"]))
        assert held["reduced"] == c["reduced"] and held["source"] == c["source"]
        assert not any(k.endswith(("_dim", "_rank")) or k == "rank"
                       for k in c["reduced"])


def test_every_layer_metric_file_is_listed(bench):
    listed = {m["name"] for m in bench["per_layer"]}
    on_disk = {f[:-5] for f in os.listdir(
        os.path.join(cells.BENCH_DIR, "layer_metrics"))}
    assert listed == on_disk


def test_unknown_device_is_an_error():
    assert cells.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(cells.BenchFailure):
        cells.peaks_for("TPU v9")
