"""Reader `seq-roofline-latent`: a share of the chip's peak for the
latent-attention stack (benchmark/harness/roofline_latent.py has the
counts), in %, from the traced window:

  `"kernel": "attention"`: the least time of a step's attention (every
    layer full and causal, the prediction module's with them) over the
    device seconds a step of the metric's scopes;
  `"kernel": "grouped"`: the same for the held experts' grouped products
    at the window's real group sizes (the jobs' counter of tokens per
    held expert);
  `"kernel": "step"`: the whole step's least operations over the peak
    FLOP/s times the device's busy seconds a step.

Nothing to read where the trace has no `seq.*` scopes, where the jobs
logged no counters, or in a CPU rehearsal."""

from statistics import mean

from benchmark.harness import cells, roofline_latent
from benchmark.harness.roofline_sequence import least_seconds


def read(spec: dict, evidence: dict):
    tr = evidence.get("trace") or {}
    steps = evidence.get("steps_in_window")
    by_scope = tr.get("scope_s")
    counters = [c for c in evidence.get("counters", ())
                if "expert_tokens_mean" in c]
    if not steps or not by_scope or evidence.get("rehearse"):
        return None        # a CPU rehearsal has no roofline
    cfg, traffic = evidence["config"], evidence["traffic"]
    batch, seq_len = traffic["batch_histories"], traffic["history_events"]
    if spec["kernel"] == "step":
        taken = tr.get("busy_s", 0.0) / steps
    else:
        taken = sum(by_scope.get(s, 0.0) for s in spec["scopes"]) / steps
    if taken <= 0 or (spec["kernel"] != "attention" and not counters):
        return None
    peaks = cells.peaks_for(evidence["device_kind"])
    if spec["kernel"] == "attention":
        work = roofline_latent.latent_attention_least(cfg, batch, seq_len)
        return 100.0 * least_seconds(work, peaks) / taken
    rows = cfg["n_routed_experts"] * mean(
        float(c["expert_tokens_mean"]) for c in counters)
    if spec["kernel"] == "grouped":
        work = roofline_latent.latent_grouped_least(cfg, rows)
        return 100.0 * least_seconds(work, peaks) / taken
    work = roofline_latent.step_least(cfg, batch, seq_len, rows)
    return 100.0 * work["flops"] / peaks["flops_per_s_bf16"] / taken
