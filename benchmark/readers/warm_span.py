"""Reader `warm-span`: the set-up's one `run_train` by its own span
record (`evidence["warm_job"]["spans"]`, the rows of `train spans:`):
the sum of the whole durations of the rows named `span`; with `where`
(`where_not`) only the rows whose labels hold (do not hold) every given
value; with `"as": "count"` how many such rows there are; or, with
`root_label`, one label of the root as a number. Nothing to read where
the record is empty (`PIO_TPU_TRACE=off`), holds no row of that name, or
the root has no such label (a program from before the meter opened
spans); and nothing in a rehearsal, as for a roofline: its warm job gets
tiny programs ready by the CPU's compiler, which says nothing of the
chip's (tests/test_benchmark_span_contract.py holds the names on the
rehearsals' records instead)."""


def read(spec: dict, evidence: dict):
    if evidence.get("rehearse"):
        return None
    rows = (evidence.get("warm_job") or {}).get("spans") or []
    if "root_label" in spec:
        labels = next((r.get("labels") or {} for r in rows
                       if r["parent"] is None), {})
        value = labels.get(spec["root_label"])
        return None if value is None else float(value)
    mine = [r for r in rows if r["name"] == spec["span"]]
    if not mine:
        return None
    where, where_not = spec.get("where", {}), spec.get("where_not", {})
    mine = [r for r in mine
            if all(r["labels"].get(k) == v for k, v in where.items())
            and not any(r["labels"].get(k) == v
                        for k, v in where_not.items())]
    if spec.get("as") == "count":
        return len(mine)
    return sum(r["duration_s"] for r in mine)
