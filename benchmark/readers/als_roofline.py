"""Reader `als-roofline`: the least time the chip could take for one
job's sweeps (the larger of least bytes over peak bytes/s and operations
over peak FLOP/s, benchmark/harness/roofline.py) over the device's busy
seconds per job, in %. On several chips the work and the busy time are
each chip's share."""

from benchmark.harness import cells, roofline


def read(spec: dict, evidence: dict):
    tr = evidence.get("trace")
    if not tr or evidence.get("rehearse"):
        return None       # a CPU rehearsal has no roofline
    peaks = cells.peaks_for(evidence["device_kind"])
    data, alg = evidence["config"]["data"], evidence["config"]["algorithm"]
    chips = evidence["chips"]

    def schedule(n_rows: int) -> list[int]:
        exact = chips == 1 and n_rows <= spec["exact_solve_up_to_rows"]
        if exact:
            return [0] * alg["num_iterations"]
        return roofline.cg_schedule(
            alg["num_iterations"], spec["cg_full_iters"],
            spec["cg_full_sweeps"], spec["cg_warm_iters"])

    least = roofline.job_least(
        data["n_users"], data["n_items"], data["nnz"], alg["rank"],
        schedule(-(-data["n_users"] // chips)),
        schedule(-(-data["n_items"] // chips)))
    least_s = max(least["bytes"] / peaks["hbm_bytes_per_s"],
                  least["flops"] / peaks["flops_per_s_bf16"]) / chips
    return 100.0 * least_s / (tr["busy_s"] / len(evidence["jobs"]))
