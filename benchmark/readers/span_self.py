"""Reader `span-self`: host seconds a job under the spans the metric's
file lists, from the job's `train spans:` record: the sum of their self
times (a row's `duration_s` less its direct children's, children found
by `parent`; rows of one name add), or with `"as": "total"` of their
whole durations; median over the window's jobs. Nothing to read where no
job's record holds one of the spans (an engine that does not open them,
`PIO_TPU_TRACE=off`)."""

from statistics import median


def job_seconds(rows: list[dict], names: list[str], total: bool):
    mine = [r for r in rows if r["name"] in names]
    if not mine:
        return None
    seconds = sum(r["duration_s"] for r in mine)
    if not total:
        seconds -= sum(r["duration_s"] for r in rows
                       if r["parent"] in names)
    return seconds


def read(spec: dict, evidence: dict):
    total = spec.get("as") == "total"
    values = [job_seconds(j["spans"], spec["spans"], total)
              for j in evidence.get("jobs", ()) if j.get("spans")]
    values = [v for v in values if v is not None]
    return median(values) if values else None
