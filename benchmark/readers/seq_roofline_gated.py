"""Reader `seq-roofline-gated`: a share of the chip's peak for the stack
of gated grouped-query layers (benchmark/harness/roofline_gated.py has
the counts), in %, from the traced window:

  `"kernel": "attention"`: the least time of a step's attention kernels,
    a layer kind at a time (a kind's own query heads and band), over the
    device seconds a step of the metric's scopes;
  `"kernel": "grouped"`: the same for the held experts' grouped products
    at the window's real group sizes (the jobs' counter of tokens per
    held expert);
  `"kernel": "step"`: the whole step's least operations at balance over
    the peak FLOP/s times the device's busy seconds a step.

Nothing to read where the trace has none of the metric's scopes (a
program without the layers), where the jobs logged no counters, or in a
CPU rehearsal."""

from statistics import mean

from benchmark.harness import cells, roofline_gated
from benchmark.harness.roofline_sequence import least_seconds


def read(spec: dict, evidence: dict):
    tr = evidence.get("trace") or {}
    steps = evidence.get("steps_in_window")
    by_scope = tr.get("scope_s")
    if not steps or not by_scope or evidence.get("rehearse"):
        return None        # a CPU rehearsal has no roofline
    in_scopes = sum(by_scope.get(s, 0.0) for s in spec["scopes"]) / steps
    if in_scopes <= 0:
        return None
    cfg, traffic = evidence["config"], evidence["traffic"]
    batch, seq_len = traffic["batch_histories"], traffic["history_events"]
    peaks = cells.peaks_for(evidence["device_kind"])
    kernel = spec["kernel"]
    if kernel == "step":
        taken = tr.get("busy_s", 0.0) / steps
        if taken <= 0:
            return None
        work = roofline_gated.step_least(cfg, batch, seq_len)
        return 100.0 * work["flops"] / peaks["flops_per_s_bf16"] / taken
    if kernel == "attention":
        work = roofline_gated.gated_attention_least(cfg, batch, seq_len)
    else:
        counters = [c for c in evidence.get("counters", ())
                    if "expert_tokens_mean" in c]
        if not counters:
            return None
        work = roofline_gated.gated_grouped_least(
            cfg, cfg["num_experts"] * mean(
                float(c["expert_tokens_mean"]) for c in counters))
    return 100.0 * least_seconds(work, peaks) / in_scopes
