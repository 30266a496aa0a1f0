"""The output check has to fail what it is there to catch.

The limits are the shipped configuration's own (set from chip runs,
PERF.md). Sound tables come from the program's trainer on the CPU at a
tiny size; each fault is put in underneath and `correct` has to come out
false: the lower-precision control, a solve that is 13.5 times off (PR
21's four-chip fault), a user side that was never solved, tables that
forgot the ratings, and, through the child's whole run, a train step
that returns its state unchanged.
"""

import json
import os

import numpy as np
import pytest

from benchmark.harness import cells, check_train, data
from benchmark.reference import als as ref

TINY = os.path.join(os.path.dirname(__file__), "rehearse", "als-tiny.json")


@pytest.fixture(scope="module")
def cell():
    return cells.load_cell("ml20m-r64.train-coo", TINY)


@pytest.fixture(scope="module")
def trained(cell):
    from pio_tpu.ops import als

    shape, alg = cell.config["data"], cell.config["algorithm"]
    u, i, v = data.make_interactions(shape, 11)
    model = als.als_train(
        u, i, v, shape["n_users"], shape["n_items"],
        als.ALSParams(rank=alg["rank"], iterations=alg["num_iterations"],
                      reg=alg["lambda_"], alpha=alg["alpha"], implicit=True,
                      chunk=alg["chunk"]))
    return (np.asarray(model.user_factors), np.asarray(model.item_factors),
            u, i, v)


def verdict(cell, users, items, u, i, v):
    return check_train.check(users, items, u, i, v, cell.config["algorithm"],
                             cell.config["check"]["limits"], seed=3,
                             sample_rows=64)


def test_sound_tables_pass(cell, trained):
    out = verdict(cell, *trained)
    assert out["correct"], out["compared"]
    assert len(out["compared"]) == len(out["numbers"]) == 3


def test_lower_precision_control_fails(cell, trained):
    """The reference in the program's place for the last half-sweep, its
    tables and products in bfloat16: the residual gives it away."""
    users, items, u, i, v = trained
    alg = cell.config["algorithm"]
    grouped = ref.rows_of(i, u, v, np.arange(len(items)))
    control, _ = ref.solve_rows(ref.bf16(users), grouped, alg["alpha"],
                                alg["lambda_"], precision="bfloat16")
    out = verdict(cell, ref.bf16(users), control.astype(np.float32), u, i, v)
    assert not out["correct"]
    limit = cell.config["check"]["limits"]["item_solve_residual"]["max"]
    assert out["numbers"]["item_solve_residual"] > 2 * limit


@pytest.mark.parametrize("fault", ["items_13x_off", "users_never_solved",
                                   "ratings_forgotten", "not_finite"])
def test_injected_faults_fail(cell, trained, fault):
    users, items, u, i, v = trained
    rng = np.random.default_rng(0)
    if fault == "items_13x_off":
        items = items * 13.5
    elif fault == "users_never_solved":
        users = np.abs(rng.standard_normal(users.shape)).astype(
            np.float32) / np.sqrt(users.shape[1])
        # the items are then solved exactly against those users: (b)
        # alone would pass
        grouped = ref.rows_of(i, u, v, np.arange(len(items)))
        alg = cell.config["algorithm"]
        items = ref.solve_rows(users, grouped, alg["alpha"],
                               alg["lambda_"])[0].astype(np.float32)
    elif fault == "ratings_forgotten":
        u = rng.permutation(u)
    else:
        items = items.copy()
        items[5, 2] = np.nan
    assert not verdict(cell, users, items, u, i, v)["correct"]


def run_child(cell, tmp_path, monkeypatch, seconds=0.5):
    """The rest of a run without the look for a chip: the child's main,
    in this process, on the CPU."""
    from benchmark.drivers import train_child
    from benchmark.harness.children import child_env

    tmp_path.mkdir()
    env = child_env(str(tmp_path), on_chip=True, rehearse=True)
    for key in list(env):
        if key.startswith("PIO_") or key == "JAX_COMPILATION_CACHE_DIR":
            monkeypatch.setenv(key, env[key])
    spec = {"config": cell.config, "traffic": cell.traffic, "chips": 1,
            "seed": 2 ** 31 + 9, "seconds": seconds, "trace": False,
            "rehearse": True, "out": str(tmp_path / "out.json")}
    (tmp_path / "spec.json").write_text(json.dumps(spec))
    import pio_tpu.data.storage as storage_mod

    monkeypatch.setattr(storage_mod, "_storage", None, raising=False)
    assert train_child.main(str(tmp_path / "spec.json")) == 0
    return json.loads((tmp_path / "out.json").read_text())


def test_whole_run_sound_then_broken(cell, tmp_path, monkeypatch):
    sound = run_child(cell, tmp_path / "a", monkeypatch)
    assert sound["correct"], sound["compared"]

    from pio_tpu.ops import als

    def unchanged(user_idx, item_idx, values, n_users, n_items, params,
                  **_kw):
        # a train step that returns its state unchanged
        return als.ALSModel(*als._init_or(None, n_users, n_items, params))

    monkeypatch.setattr(als, "als_train", unchanged)
    broken = run_child(cell, tmp_path / "b", monkeypatch)
    assert not broken["correct"]
    assert any("FAILED" in line for line in broken["compared"])
