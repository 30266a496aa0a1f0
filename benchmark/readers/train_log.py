"""Reader `train-log`: the median over the window's jobs of one field of
the records `run_train` logs ("train stages", "train timing")."""

from statistics import median


def read(spec: dict, evidence: dict):
    values = [j[spec["field"]] for j in evidence.get("jobs", ())
              if spec["field"] in j]
    return median(values) if values else None
