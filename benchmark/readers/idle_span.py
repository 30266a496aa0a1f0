"""Reader `idle-span`: device idle seconds a job and chip, from the
traced window's profile view (benchmark/harness/program.py: every gap
between a chip's operations, split by overlap with the innermost span
open on the main thread), under the spans whose names start with one of
the metric's `spans`. `"spans": "rest"` is what the metrics named in
`besides` do not take, `outside` (no span open) with it, so a cell's
`besides` and its rest add up to the view's idle seconds a job. Nothing
to read without a view or where no span matches."""

from benchmark.harness import cells


def read(spec: dict, evidence: dict):
    view = evidence.get("profile")
    if not view:
        return None
    if spec["spans"] == "rest":
        taken = tuple(prefix for name in spec["besides"]
                      for prefix in cells.layer_metric_spec(name)["spans"])
        found = [sec for span, sec in view["idle_by_span"].items()
                 if not span.startswith(taken)]
    else:
        found = [sec for span, sec in view["idle_by_span"].items()
                 if span.startswith(tuple(spec["spans"]))]
    return sum(found) if found else None
