"""Reader `seq-scope`: device seconds of one training step, from the
traced window: of the scopes listed in the metric's file (`jax.named_scope`
names of pio_tpu/models/seq_blocks.py, joined to the trace's operations by
pio_tpu/obs/profile.py), or with `"scopes": "all"` the device's busy
seconds. Nothing to read where the program has no such scopes."""


def read(spec: dict, evidence: dict):
    tr = evidence.get("trace") or {}
    steps = evidence.get("steps_in_window")
    if not steps:
        return None
    if spec["scopes"] == "all":
        return tr["busy_s"] / steps if "busy_s" in tr else None
    by_scope = tr.get("scope_s")
    if not by_scope:
        return None
    return sum(by_scope.get(s, 0.0) for s in spec["scopes"]) / steps
