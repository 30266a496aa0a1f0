"""Reader `seq-roofline-loop`: a share of the chip's peak for the looped
stack (benchmark/harness/roofline_loop.py has the counts), in %, from
the traced window:

  `"kernel": "attention"`: the least time of a step's attention (T x L
    full causal layer applications) over the device seconds a step of
    the metric's scopes;
  `"kernel": "step"`: the whole step's least operations over the peak
    FLOP/s times the device's busy seconds a step.

Nothing to read where the trace has none of the metric's scopes (a
program without the loop), or in a CPU rehearsal."""

from benchmark.harness import cells, roofline_loop
from benchmark.harness.roofline_sequence import least_seconds


def read(spec: dict, evidence: dict):
    tr = evidence.get("trace") or {}
    steps = evidence.get("steps_in_window")
    by_scope = tr.get("scope_s")
    if not steps or not by_scope or evidence.get("rehearse"):
        return None        # a CPU rehearsal has no roofline
    # the loop's own scopes: the parent's program has none of them
    in_loop = sum(by_scope.get(s, 0.0) for s in spec["scopes"]) / steps
    if in_loop <= 0:
        return None
    cfg, traffic = evidence["config"], evidence["traffic"]
    batch, seq_len = traffic["batch_histories"], traffic["history_events"]
    peaks = cells.peaks_for(evidence["device_kind"])
    if spec["kernel"] == "attention":
        work = roofline_loop.loop_attention_least(cfg, batch, seq_len)
        return 100.0 * least_seconds(work, peaks) / in_loop
    taken = tr.get("busy_s", 0.0) / steps
    if taken <= 0:
        return None
    work = roofline_loop.step_least(cfg, batch, seq_len)
    return 100.0 * work["flops"] / peaks["flops_per_s_bf16"] / taken
