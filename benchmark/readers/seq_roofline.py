"""Reader `seq-roofline`: the least time the chip could take for one
step's work of a kernel family (benchmark/harness/roofline_sequence.py:
the larger of operations over peak FLOP/s and bytes over peak bytes/s)
over the device seconds a step its scopes took in the traced window, in
%. `"kernel": "attention"` counts the causal half for a full layer and
the band for a window layer; `"kernel": "grouped"` counts the grouped
products at the window's real group sizes (the job's counter of tokens
per held expert)."""

from statistics import mean

from benchmark.harness import cells, roofline_sequence as roofline


def read(spec: dict, evidence: dict):
    tr = evidence.get("trace") or {}
    steps = evidence.get("steps_in_window")
    by_scope = tr.get("scope_s")
    if not steps or not by_scope or evidence.get("rehearse"):
        return None        # a CPU rehearsal has no roofline
    taken = sum(by_scope.get(s, 0.0) for s in spec["scopes"]) / steps
    if taken <= 0:
        return None
    peaks = cells.peaks_for(evidence["device_kind"])
    cfg, traffic = evidence["config"], evidence["traffic"]
    kinds = cfg["layer_types"][:cfg["num_hidden_layers"]]
    if spec["kernel"] == "attention":
        work = roofline.attention_least(
            traffic["batch_histories"], traffic["history_events"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"],
            [cfg["sliding_window"] if k == "sliding_attention" else None
             for k in kinds])
    else:
        n_held = cfg["num_experts"]
        rows = n_held * mean(float(c["expert_tokens_mean"])
                             for c in evidence["counters"])
        work = roofline.grouped_least(
            rows, n_held, cfg["hidden_size"], cfg["moe_intermediate_size"],
            len(kinds))
    return 100.0 * roofline.least_seconds(work, peaks) / taken
