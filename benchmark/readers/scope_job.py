"""Reader `scope-job`: device seconds a job and chip, from the traced
window's profile view (benchmark/harness/program.py), of every scope
path that has one of the metric's `phases` as an element:
`als.user/als.gather` and `als.item/als.gather` under `als.gather`,
`als.user/als.gram/als.gram.pack` under `als.gram`. `"phases":
"unphased"` is what a side runs under no phase: the paths that end at
one of the metric's `sides`, and `unscoped`. Nothing to read without a
view (a CPU rehearsal) or where no path matches."""

UNSCOPED = "unscoped"


def read(spec: dict, evidence: dict):
    view = evidence.get("profile")
    if not view:
        return None
    if spec["phases"] == "unphased":
        found = [sec for path, sec in view["scopes"].items()
                 if path == UNSCOPED or path.split("/")[-1] in spec["sides"]]
    else:
        found = [sec for path, sec in view["scopes"].items()
                 if set(path.split("/")) & set(spec["phases"])]
    return sum(found) if found else None
