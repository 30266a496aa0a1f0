"""What the program says of itself, for every train child: the span tree
`run_train` logs a job (`train spans:`), and a traced window's profile
reduced by the program's own names (benchmark/harness/profile.py: device
seconds by `jax.named_scope`, device idle seconds by the innermost span
open). The children put both into what they return; the drivers hand
them to the readers as `evidence["jobs"][n]["spans"]`,
`evidence["warm_job"]` and `evidence["profile"]`.
"""

from __future__ import annotations

import json
import logging
import time

_SPANS = "train spans: "
NO_VIEW = "no profile view"


class SpanLog(logging.Handler):
    """The `train spans:` record of a job, whole: `rows` of `name`,
    `parent`, `start_s`, `duration_s`, `labels`. Empty where the program
    logs none (`PIO_TPU_TRACE=off`)."""

    def __init__(self):
        super().__init__(logging.INFO)
        self.rows: list[dict] = []

    def emit(self, record: logging.LogRecord) -> None:
        text = record.getMessage()
        if text.startswith(_SPANS):
            self.rows = json.loads(text[len(_SPANS):])

    def labels(self, name: str) -> dict:
        """The labels of the last span of that name (`seq.wait` carries
        the sequence trainer's counters)."""
        found: dict = {}
        for row in self.rows:
            if row["name"] == name:
                found = dict(row.get("labels") or {})
        return found


def profile_view(trace_dir: str, log: logging.Logger) -> dict | None:
    """A job's seconds by the program's names, mean over jobs and chips:
    `scopes`, `by_rule`, `idle_by_span`, `busy_s`, `idle_s`; beside them
    `between_jobs_s` and the ten `longest_gaps` with their parts by span.
    None where the profile has no `/device:TPU` plane (a CPU rehearsal)
    or no `train` root (a tracer without `device=True`)."""
    from benchmark.harness import profile

    try:
        result = profile.reduce(profile.read_profile(trace_dir))
    except ValueError as e:
        log.info("no profile view: %s", e)
        return None
    return dict(result["per_job"],
                between_jobs_s=result["between_jobs_s"],
                longest_gaps=result["longest_gaps"])


def name_gaps(longest_gaps: list[dict]) -> list[list]:
    """[[span, seconds]] of the profile's longest gaps, each under the
    span that holds the largest part of it (`outside`: no span open)."""
    return [[max(gap["parts"].items(), key=lambda kv: kv[1])[0], gap["s"]]
            for gap in longest_gaps]


def reduce_trace(trace_dir: str, n_jobs: int, log: logging.Logger) -> dict:
    """A child's `trace`: busy and window seconds, operations and
    collectives (benchmark/harness/trace.py), `profile` (the view above,
    or None), `idle_gaps` named from it, `scope_s` (the window's device
    seconds by scope, which the `seq-scope` readers sum: the view's
    seconds a job times the window's `n_jobs`), and the seconds each
    reduction took, after the window and the check, outside every timed
    interval."""
    from benchmark.harness import trace

    t_a = time.monotonic()
    out = trace.reduce(trace.read_planes(trace.find_xplane(trace_dir)))
    out.pop("op_seconds")
    gaps = out.pop("longest_gaps_s")
    t_b = time.monotonic()
    view = profile_view(trace_dir, log)
    out["profile"] = view
    out["scope_s"] = view and {scope: sec * n_jobs
                               for scope, sec in view["scopes"].items()}
    out["idle_gaps"] = (name_gaps(view["longest_gaps"]) if view
                        else [[NO_VIEW, s] for s in gaps])
    out["reduce_seconds"] = {"trace": t_b - t_a,
                             "profile_view": time.monotonic() - t_b}
    log.info("trace reduced in %.1fs, profile view in %.1fs",
             *out["reduce_seconds"].values())
    return out


def evidence(out: dict, cell, rehearse: bool) -> dict:
    """What every train driver hands the readers of a child's output."""
    return {"jobs": out["jobs"], "warm_job": out["warm_job"],
            "trace": out.get("trace"),
            "profile": (out.get("trace") or {}).get("profile"),
            "config": cell.config, "chips": cell.chips,
            "device_kind": out["device"]["kind"], "rehearse": rehearse}


def by_seconds(seconds: dict, per: float = 1.0) -> dict:
    return {k: v / per for k, v in sorted(seconds.items(),
                                          key=lambda kv: -kv[1])}


def breakdown(tr: dict) -> dict:
    """A traced line's `breakdown`: the ten operations and gaps the
    driver copies into the ledger, and idle seconds a job by span."""
    out = {"device_ops": tr["device_ops"], "idle_gaps": tr["idle_gaps"]}
    if tr.get("profile"):
        out["idle_by_span_s"] = by_seconds(tr["profile"]["idle_by_span"])
    return out
