"""Traffic kind `train`: `run_train` jobs back to back in one child that
holds the chip(s) (benchmark/drivers/train_child.py does the work).

`train_ratings_per_s` = jobs finished x ratings x `num_iterations`, over
the sum of the jobs' walls. A job is the whole `run_train`: from
`DataSource.read_training` to the model persisted and the instance
COMPLETED, which is when `pio deploy` can serve it.
"""

from __future__ import annotations

from benchmark.harness import program
from benchmark.harness.children import Children, child_env, require_devices


def run(cell, seed: int, seconds: float, trace: bool, t0: float,
        rehearse: bool, explore: bool = False) -> dict:
    kids = Children()
    try:
        out = kids.python(
            "train", "benchmark.drivers.train_child",
            {"config": cell.config, "traffic": cell.traffic,
             "chips": cell.chips, "seed": seed, "seconds": seconds,
             "trace": trace, "rehearse": rehearse, "explore": explore},
            child_env(kids.work, on_chip=True, rehearse=rehearse,
                      virtual_devices=cell.chips),
            timeout=1500)
    finally:
        kids.close()
    require_devices(out["device"], cell.chips, rehearse)
    jobs = out["jobs"]
    sweeps = cell.config["algorithm"]["num_iterations"]
    walls = sum(j["wall_s"] for j in jobs)
    result = {
        "correct": out["correct"],
        "attempted": len(jobs),
        "failed": 0,          # a job that fails ends the child: no result
        "compared": out["compared"],
        "device": out["device"],
        "end_to_end": {
            "setup_s": out["window"]["open"] - t0,
            "train_ratings_per_s": len(jobs) * out["nnz"] * sweeps / walls,
        },
        "evidence": program.evidence(out, cell, rehearse),
        "raw": out,
    }
    if trace:
        tr = out["trace"]
        result["device"].update(busy_s=tr["busy_s"], window_s=tr["window_s"])
        result["breakdown"] = program.breakdown(tr)
        if tr["profile"]:
            result["breakdown"]["scope_job_s"] = program.by_seconds(
                tr["profile"]["scopes"])
    return result
