"""Reader `seq-counter`: the median over the window's jobs of one counter
the sequence trainer writes on its `seq.wait` span (the `train spans:`
record). Nothing to read where the program writes no such span."""

from statistics import median


def read(spec: dict, evidence: dict):
    values = [float(c[spec["counter"]]) for c in evidence.get("counters", ())
              if spec["counter"] in c]
    return median(values) if values else None
