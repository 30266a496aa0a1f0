"""Reader `warm-job`: one field of the set-up's one `run_train` as the
child returns it: `wall_s` by the child's clock, or a field of the two
records the job logged (`compile_s`, `programs`, `cache_hits` of "train
timing"). Nothing to read where the child returns no such field."""


def read(spec: dict, evidence: dict):
    return (evidence.get("warm_job") or {}).get(spec["field"])
