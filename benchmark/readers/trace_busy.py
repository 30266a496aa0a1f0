"""Reader `trace-busy`: from the reduced device trace, either the busy
seconds of the devices (mean over them) per job of the window
(`"as": "busy_per_job_s"`), or the share of the window in which no
operation ran on a device (`"as": "idle_pct"`)."""


def read(spec: dict, evidence: dict):
    tr = evidence.get("trace")
    if not tr:
        return None
    if spec["as"] == "idle_pct":
        return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
    return tr["busy_s"] / len(evidence["jobs"])
