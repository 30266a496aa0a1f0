"""Traffic kind `train_sequence_gated`: `run_train` jobs of the sequence
engine back to back in one child that holds the chip, for a block
specification of gated grouped-query layers (query heads and a rotating
width by layer kind, head norms, a gate a head on attention's output, a
leading dense layer, sigmoid-routed experts beside a shared one): a
history is history_events + 1 ids, and the check is benchmark/harness/
check_gated.py (benchmark/drivers/train_sequence_gated_child.py does the
work).

`train_ratings_per_s` = jobs finished x trained target events a job
(batch_histories x history_events x steps) over the sum of the jobs'
walls. A job is the whole `run_train`, from `DataSource.read_training`
to the model persisted and the instance COMPLETED.
"""

from __future__ import annotations

from benchmark.harness import program
from benchmark.harness.children import Children, child_env, require_devices


def run(cell, seed: int, seconds: float, trace: bool, t0: float,
        rehearse: bool, explore=False) -> dict:
    kids = Children()
    try:
        out = kids.python(
            "train_sequence_gated",
            "benchmark.drivers.train_sequence_gated_child",
            {"config": cell.config, "traffic": cell.traffic,
             "chips": cell.chips, "seed": seed, "seconds": seconds,
             "trace": trace, "rehearse": rehearse, "explore": explore},
            child_env(kids.work, on_chip=True, rehearse=rehearse),
            timeout=3400 if explore else 1500)
    finally:
        kids.close()
    require_devices(out["device"], cell.chips, rehearse)
    jobs, traffic = out["jobs"], cell.traffic
    events = (traffic["batch_histories"] * traffic["history_events"]
              * traffic["steps"])
    walls = sum(j["wall_s"] for j in jobs)
    result = {
        "correct": out["correct"],
        "attempted": len(jobs),
        "failed": 0,          # a job that fails ends the child: no result
        "compared": out["compared"],
        "device": out["device"],
        "end_to_end": {
            "setup_s": out["window"]["open"] - t0,
            "train_ratings_per_s": len(jobs) * events / walls,
        },
        "evidence": dict(program.evidence(out, cell, rehearse),
                         counters=[j["counters"] for j in jobs],
                         steps_in_window=len(jobs) * traffic["steps"],
                         traffic=traffic),
        "raw": out,
    }
    if trace:
        tr = out["trace"]
        result["device"].update(busy_s=tr["busy_s"], window_s=tr["window_s"])
        result["breakdown"] = program.breakdown(tr)
        if tr.get("scope_s"):
            result["breakdown"]["scope_step_s"] = program.by_seconds(
                tr["scope_s"], per=len(jobs) * traffic["steps"])
    return result
