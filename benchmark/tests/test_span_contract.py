"""The names the benchmark reads are names the program writes: every
span a `span-self` or `idle-span` metric lists is in the `train spans:`
record of a tiny job of a cell the metric lists (ALS from COO on one
chip and on four, ALS from events, both block stacks), and every phase a
`scope-job` metric lists is a `jax.named_scope` of the ALS program's
lowered text. A span or scope renamed in the program fails here, not a
metric silently on the chip."""

import os
import re
import time

import pytest

from benchmark.harness import cells, profile
from benchmark.tests.test_rehearsal import TESTS

BENCH = cells.load_json(os.path.join(cells.ROOT, "BENCHMARK.json"))
OVERLAYS = {"train": "als-tiny.json", "train_sequence": "sequence-tiny.json",
            "train_sequence_mtp": "latent-tiny.json"}
# the sqlite store opens `models.file` for a blob of 1 MiB or more, which
# no tiny model is: pio_tpu/data/backends/sqlite.py holds the name
AT_SIZE_ONLY = {"models.file"}


def specs(*readers: str) -> list[tuple[dict, dict]]:
    out = []
    for m in BENCH["per_layer"]:
        spec = cells.layer_metric_spec(m["name"])
        if spec["reader"] in readers:
            out.append((m, spec))
    return out


@pytest.fixture(scope="module")
def records():
    """{cell: the span names of its warm job and its window's jobs}, one
    untraced tiny run a cell."""
    found: dict[str, set[str]] = {}

    def of(name: str) -> set[str]:
        if name not in found:
            kind = cells.load_cell(name).traffic["kind"]
            cell = cells.load_cell(
                name, os.path.join(TESTS, "rehearse", OVERLAYS[kind]))
            out = cells.module_for("drivers", kind).run(
                cell, seed=2 ** 31 + 23, seconds=1, trace=False,
                t0=time.monotonic(), rehearse=True)
            assert out["correct"] is True
            jobs = [out["evidence"]["warm_job"], *out["evidence"]["jobs"]]
            assert all(j["spans"] for j in jobs)
            found[name] = {r["name"] for j in jobs for r in j["spans"]}
        return found[name]

    return of


@pytest.mark.parametrize("metric,spec", [
    pytest.param(m, s, id=m["name"])
    for m, s in specs("span-self", "idle-span") if s["spans"] != "rest"])
def test_a_listed_span_is_one_the_program_opens(metric, spec, records):
    opened = set().union(*(records(w) for w in metric["workloads"]))
    prefix = spec["reader"] == "idle-span"
    for name in spec["spans"]:
        if name in AT_SIZE_ONLY:
            continue
        assert any(span.startswith(name) if prefix else span == name
                   for span in opened), (name, sorted(opened))


def test_the_rest_names_its_siblings():
    [(metric, spec)] = [(m, s) for m, s in specs("idle-span")
                        if s["spans"] == "rest"]
    siblings = {m["name"]: m for m, s in specs("idle-span")
                if s["spans"] != "rest"}
    assert set(spec["besides"]) == set(siblings)
    for m in siblings.values():
        assert set(m["workloads"]) <= set(metric["workloads"])


def test_a_listed_phase_is_a_scope_of_the_als_program():
    import jax
    import jax.numpy as jnp

    from pio_tpu.ops import als

    def lowered(cg_iters: int) -> str:
        # the accumulation the chip runs, solved by CG or exactly
        params = als.ALSParams(
            rank=8, iterations=2, reg=0.05, alpha=10.0, implicit=True,
            accum="hybrid", cg_iters=cg_iters, cg_warm_iters=1,
            cg_warm_sweeps=1, chunk=256, chunk_slots=128)
        i32 = jax.ShapeDtypeStruct((768,), jnp.int32)
        return als._train_jit.lower(
            i32, i32, jax.ShapeDtypeStruct((768,), jnp.float32),
            n_users=64, n_items=48, params=params,
            user0=jax.ShapeDtypeStruct((64, 8), jnp.float32),
            item0=jax.ShapeDtypeStruct((48, 8), jnp.float32)
        ).as_text(debug_info=True)

    scopes = {part for text in (lowered(3), lowered(0))
              for path in re.findall(r"[\w.()/\-]*als\.(?!py\b)[\w.()/\-]*",
                                     text)
              for part in (profile.scope_of_op_name(path) or "").split("/")}
    listed = specs("scope-job")
    assert len(listed) == 6
    for _, spec in listed:
        names = (spec["sides"] if spec["phases"] == "unphased"
                 else spec["phases"])
        assert set(names) <= scopes, (names, sorted(scopes))
